"""Device time per step of routing (milliseconds): the ops in the
program's ``router`` scope (pre-norm, gate logits, softmax, top-k, auxiliary
losses) and ``dispatch`` scope (the index structures' build), forward and
backward, averaged over the devices."""

from benchmark import scopes


def read(ctx):
    return scopes.device_ms(ctx, ("router", "dispatch"))
