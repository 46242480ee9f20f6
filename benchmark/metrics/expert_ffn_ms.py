"""Device time per step of the expert FFN (milliseconds): the ops in the
program's ``experts`` scope (token gathers, grouped GEMMs, SwiGLU), forward
and backward, averaged over the devices.  Holds the grouped GEMMs that
``gmm_ms`` reads, so it is never below ``gmm_ms``."""

from benchmark import scopes


def read(ctx):
    return scopes.device_ms(ctx, ("experts",))
