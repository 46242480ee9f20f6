"""Device time per step of the optimizer (milliseconds): the ops in the
program's ``optimizer`` scope (global-norm clip, schedule, AdamW), averaged
over the devices."""

from benchmark import scopes


def read(ctx):
    return scopes.device_ms(ctx, ("optimizer",))
