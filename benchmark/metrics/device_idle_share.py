"""Share of the traced window in which no operation runs on the device,
averaged over the devices (percent)."""

from benchmark import trace


def read(ctx):
    lo, hi = trace.window(ctx.tr)
    busy = trace.busy(ctx.tr)
    if not busy:
        return None
    shares = [1.0 - trace.length(iv) / (hi - lo) for iv in busy.values()]
    return 100.0 * sum(shares) / len(shares)
