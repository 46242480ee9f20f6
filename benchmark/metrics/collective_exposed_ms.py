"""Device time per step of collective operations (all-to-all, all-reduce,
all-gather, reduce-scatter, collective-permute) during which no other
operation runs on that device (milliseconds), averaged over the devices."""

from benchmark import trace


def read(ctx):
    hits = trace.matching(ctx.tr, trace.COLLECTIVE)
    if not any(hits.values()):
        return None
    exp = trace.exposed(ctx.tr)
    return sum(exp.values()) / len(exp) / ctx.steps / 1e6
