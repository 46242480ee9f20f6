"""Device time per step of the attention layer, forward and backward
(milliseconds): the ops in the program's ``attention`` scope (pre-norm,
projections, attention core, post-norm, residual add), averaged over the
devices."""

from benchmark import scopes


def read(ctx):
    return scopes.device_ms(ctx, ("attention",))
