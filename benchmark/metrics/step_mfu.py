"""The whole step's share of the chips' bf16 peak over the traced window
(percent): model FLOPs of the traced steps (``flops.train_flops_per_token``)
over the window's length times the chips' peak."""

from benchmark import flops, trace


def read(ctx):
    lo, hi = trace.window(ctx.tr)
    work = (flops.train_flops_per_token(ctx.model, ctx.seq_len)
            * ctx.tokens_per_step * ctx.steps)
    return 100.0 * work / ((hi - lo) / 1e9 * ctx.chips
                           * ctx.peaks["bf16_flops"])
