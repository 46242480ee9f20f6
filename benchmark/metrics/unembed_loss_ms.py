"""Device time per step of the unembedding and loss (milliseconds): the
ops in the program's ``unembed_loss`` scope (final norm, unembedding, softcap,
log-softmax, cross-entropy), forward and backward, averaged over the
devices."""

from benchmark import scopes


def read(ctx):
    return scopes.device_ms(ctx, ("unembed_loss",))
