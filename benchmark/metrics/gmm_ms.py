"""Device time per step of the grouped matrix multiplications, forward and
backward (milliseconds), averaged over the devices.

An op counts where its own name is one of XLA's ragged-dot ops (the
``ragged`` backend; on a TPU v5e conf6 step: 11 ``ragged-dot-none.N``
custom calls and 3 small ``ragged-dot-metadata.N``) or one of the program's
Pallas grouped-GEMM kernels."""

from benchmark import trace

PATTERN = r"^(ragged-dot|gather_gmm|gmm_dw|fused_moe)"


def read(ctx):
    hits = trace.matching(ctx.tr, PATTERN)
    total = [trace.length(iv) for iv in hits.values()]
    if not total or max(total) == 0:
        return None
    return sum(total) / len(total) / ctx.steps / 1e6
