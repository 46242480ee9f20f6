"""The grouped GEMMs' share of their roofline (percent): the least time the
chip needs for the step's grouped-GEMM work, the larger of its FLOPs over
the bf16 peak and its bytes over HBM bandwidth (``flops.gmm_work``, split
evenly over the chips), over the measured ``gmm_ms``."""

from benchmark import flops
from benchmark.metrics import gmm_ms


def read(ctx):
    t = gmm_ms.read(ctx)
    if t is None:
        return None
    f, b = flops.gmm_work(ctx.model, ctx.tokens_per_step)
    least = max(f / ctx.peaks["bf16_flops"], b / ctx.peaks["hbm_bw"])
    return 100.0 * least / ctx.chips / (t / 1e3)
