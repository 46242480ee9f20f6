"""Device time per step of the combine (milliseconds): the ops in the
program's ``combine`` scope (gate scatter, the gather-sum of each token's k
partials, and their backward), averaged over the devices."""

from benchmark import scopes


def read(ctx):
    return scopes.device_ms(ctx, ("combine",))
