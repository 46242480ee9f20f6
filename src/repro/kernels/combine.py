"""Gather-of-partials combine kernel (paper §3.1 output aggregation).

Each token gathers its ``k`` partial expert outputs through
``token_index_map`` and contracts them with its gate weights — the
deterministic, gather-based TPU rendering of the paper's on-the-fly reduction
(no scatter, no materialized (L·k, d) buffer; see DESIGN.md §2).

This standalone kernel serves the *unfused* composition
(``kernels.ops.moe_ffn_blaze_pallas``).  The fused path
(``gather_gmm.fused_moe_fwd``) folds the same combine into the grouped-GEMM
grid pass as its epilogue — there the (S, d) partials input never exists.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.kernels.common import (compiler_params, dma_rows, row_words,
                                  rows_from_words)


def _combine_kernel(tim_ref, p_ref, g_ref, y_ref, buf_ref, sem, *, bl: int,
                    k: int, dtype):
    t = pl.program_id(0)
    # buf row i*bl + r <- the partial of token t*bl + r's i-th slot.
    for i in range(k):                           # k is small and static
        dma_rows(p_ref, buf_ref, sem, 0, bl,
                 lambda r, i=i: tim_ref[(t * bl + r) * k + i],
                 lambda r, i=i: i * bl + r)
    acc = jnp.zeros(y_ref.shape, jnp.float32)
    for i in range(k):
        part = rows_from_words(buf_ref[pl.ds(i * bl, bl)], dtype)
        acc = acc + g_ref[:, i:i + 1].astype(jnp.float32) * \
            part.astype(jnp.float32)
    y_ref[...] = acc.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bl",))
def combine(p_out: jax.Array, token_index_map: jax.Array, gates: jax.Array,
            *, bl: int = 128):
    """(S, d) partials + (L, k) map + (L, k) gates -> (L, d) output.

    Each grid step gathers its ``bl`` tokens' ``k`` partial rows by row DMA
    (``p_out`` stays in HBM), so VMEM is bounded by ``k * bl`` rows."""
    S, d = p_out.shape
    L, k = token_index_map.shape
    bl = min(bl, L)
    L_pad = ((L + bl - 1) // bl) * bl
    tim = jnp.pad(token_index_map.astype(jnp.int32),
                  ((0, L_pad - L), (0, 0))).reshape(-1)
    g = jnp.pad(gates, ((0, L_pad - L), (0, 0)))
    pw = row_words(p_out)
    it = p_out.dtype.itemsize
    y = pl.pallas_call(
        functools.partial(_combine_kernel, bl=bl, k=k, dtype=p_out.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(L_pad // bl,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((bl, k), lambda t, tim_r: (t, 0)),
            ],
            out_specs=pl.BlockSpec((bl, d), lambda t, tim_r: (t, 0)),
            scratch_shapes=[pltpu.VMEM((k * bl, 1, pw.shape[-1]), pw.dtype),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((L_pad, d), p_out.dtype),
        compiler_params=compiler_params(
            "combine", k * bl * d * it + 2 * bl * d * it + 2 * bl * d * 4
            + 2 * bl * 128 * 4),
        interpret=kernels.interpret_mode(),
    )(tim, pw, g)
    return y[:L]
