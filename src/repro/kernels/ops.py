"""Public jit'd entry points for the Pallas kernels.

Whether a kernel is compiled or interpreted is decided from the platform, in
one place (:func:`repro.kernels.interpret_mode`): compiled by Mosaic on a TPU,
interpreted on the CPU, where the tests check each kernel against ``ref.py``.

``moe_ffn_blaze_pallas`` composes the kernels into the full MoEBlaze expert
layer — dispatch build, gather-GMM with fused SwiGLU epilogue, second grouped
GEMM, gather-of-partials combine — with a custom VJP that mirrors
Algorithm 1 (SiLU recomputed; routed buffers never materialized).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.gmm_backend import ResolvedBackend, gmm, gmm_dw, resolve
from repro.core.routing import Dispatch
from repro.kernels.combine import combine
from repro.kernels.dispatch import build_dispatch_pallas
from repro.kernels.fused_swiglu import (fused_swiglu_bwd_w, fused_swiglu_bwd_x,
                                        fused_swiglu_fwd)
from repro.kernels.gather_gmm import (fused_moe_bwd, fused_moe_fwd,
                                      gather_gmm, gather_rows_pallas)

__all__ = [
    "fused_swiglu_fwd", "fused_swiglu_bwd_x", "fused_swiglu_bwd_w",
    "gather_gmm", "combine", "build_dispatch_pallas", "swiglu",
    "moe_ffn_blaze_pallas", "moe_ffn_blaze_fused", "gather_rows",
]


# ---------------------------------------------------------------------------
# Dense fused SwiGLU with the paper's checkpoint policy, as a differentiable
# op (used by the dense-arch FFNs when kernels are enabled).
# ---------------------------------------------------------------------------


@jax.custom_vjp
def swiglu(x, w1, w2):
    y, _, _ = fused_swiglu_fwd(x, w1, w2)
    return y


def _swiglu_fwd(x, w1, w2):
    y, a, b = fused_swiglu_fwd(x, w1, w2)
    return y, (x, w1, w2, a, b)           # checkpoint: only the GEMM outputs


def _swiglu_bwd(res, dy):
    x, w1, w2, a, b = res
    dx = fused_swiglu_bwd_x(dy, a, b, w1, w2)
    dw1, dw2 = fused_swiglu_bwd_w(x, dy, a, b)
    return dx, dw1, dw2


swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


# ---------------------------------------------------------------------------
# Full MoEBlaze expert layer out of Pallas kernels.
# ---------------------------------------------------------------------------


def _silu(a):
    return a * jax.nn.sigmoid(a)


def _dsilu(a):
    s = jax.nn.sigmoid(a)
    return s * (1.0 + a * (1.0 - s))


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _moe_pallas(backend, x, w1, w2, w3, gates, eti, off, tim, lens):
    y, _ = _moe_pallas_fwd(backend, x, w1, w2, w3, gates, eti, off, tim,
                           lens)
    return y


def _moe_pallas_fwd(backend, x, w1, w2, w3, gates, eti, off, tim, lens):
    # Fused gather + dual GEMM + SwiGLU epilogue (paper §5.2 kernel).
    y_swi, a, b = gather_gmm(x, eti, off, w1, w2, save_ab=True)
    # Second grouped GEMM (identity gather: rows already in expert order).
    p_out = gather_gmm(y_swi, None, off, w3, epilogue=False)
    y = combine(p_out, tim, gates)
    return y, (x, w1, w2, w3, gates, eti, off, tim, lens, a, b, y_swi)


def _moe_pallas_bwd(backend, res, dy):
    (x, w1, w2, w3, gates, eti, off, tim, lens, a, b, y_swi) = res
    L, k = tim.shape
    S = eti.shape[0]
    g_slot = jnp.zeros((S,), gates.dtype).at[tim.reshape(-1)].set(
        gates.reshape(-1))
    # Expand output grads to slots (gather through the index metadata).
    dyg = jnp.take(dy, eti, axis=0)
    # dW3 / dY_swi via grouped GEMMs (gather_gmm with identity index).
    dw3 = gmm_dw(y_swi * g_slot[:, None].astype(y_swi.dtype), dyg, lens,
                 backend=backend)
    dyu = gather_gmm(dyg, None, off, jnp.swapaxes(w3, 1, 2), epilogue=False)
    dgates = jnp.take(jnp.sum(y_swi * dyu, -1),
                      tim.reshape(-1)).reshape(gates.shape).astype(gates.dtype)
    dy_swi = dyu * g_slot[:, None].astype(dyu.dtype)
    # Fused SwiGLU backward (SiLU recomputed inside the kernels).
    da = dy_swi * b * _dsilu(a)
    db = dy_swi * _silu(a)
    xg = jnp.take(x, eti, axis=0)
    dw1 = gmm_dw(xg, da, lens, backend=backend)
    dw2 = gmm_dw(xg, db, lens, backend=backend)
    dxg = gmm(da, jnp.swapaxes(w1, 1, 2), lens, backend=backend) + \
        gmm(db, jnp.swapaxes(w2, 1, 2), lens, backend=backend)
    dx = jnp.zeros_like(x).at[eti].add(dxg.astype(x.dtype))
    return dx, dw1, dw2, dw3, dgates, None, None, None, None


_moe_pallas.defvjp(_moe_pallas_fwd, _moe_pallas_bwd)


def moe_ffn_blaze_pallas(x: jax.Array, gates: jax.Array, dispatch: Dispatch,
                         w1: jax.Array, w3: jax.Array, w2: jax.Array,
                         *, backend: str | ResolvedBackend | None = None
                         ) -> jax.Array:
    """Kernel-composed MoEBlaze SwiGLU expert layer (single device).

    ``backend`` selects the grouped-GEMM backend for the *backward* GEMMs
    (the forward runs the fused Pallas kernels by construction); resolved
    here — through the full precedence chain, at trace time — so the
    custom-VJP static arg is stable.
    """
    d = dispatch
    return _moe_pallas(resolve(backend).name, x, w1, w2, w3,
                       gates.astype(x.dtype),
                       d.expert_token_indices, d.expert_token_offsets,
                       d.token_index_map, d.expert_lengths)


# ---------------------------------------------------------------------------
# Fully fused dispatch→GEMM→combine MoE layer (the ``pallas_fused`` backend).
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _moe_fused(bl, bh, x, w1, w2, w3, gates, eti, off, tim):
    y, _ = _moe_fused_fwd(bl, bh, x, w1, w2, w3, gates, eti, off, tim)
    return y


def _moe_fused_fwd(bl, bh, x, w1, w2, w3, gates, eti, off, tim):
    S = eti.shape[0]
    g_slot = jnp.zeros((S,), jnp.float32).at[tim.reshape(-1)].set(
        gates.reshape(-1).astype(jnp.float32))
    y = fused_moe_fwd(x, g_slot, eti, off, w1, w2, w3, bl=bl, bh=bh)
    # Residuals: inputs + the (S,) slot-gate *vector* only — no (L·k, h) /
    # (L·k, d) buffer survives the forward (strictly below even the "x"
    # residual mode of the unfused layer; the backward kernel replays the
    # gather and recomputes A/B/SiLU per h-block in VMEM).
    return y.astype(x.dtype), (x, w1, w2, w3, gates, eti, off, tim, g_slot)


def _moe_fused_bwd(bl, bh, res, dy):
    x, w1, w2, w3, gates, eti, off, tim, g_slot = res
    dx, dgs, dw1, dw2, dw3 = fused_moe_bwd(
        x, dy.astype(x.dtype), g_slot, eti, off, w1, w2, w3, bl=bl, bh=bh)
    dgates = jnp.take(dgs, tim.reshape(-1)).reshape(gates.shape)
    return (dx.astype(x.dtype), dw1.astype(w1.dtype), dw2.astype(w2.dtype),
            dw3.astype(w3.dtype), dgates.astype(gates.dtype),
            None, None, None)


_moe_fused.defvjp(_moe_fused_fwd, _moe_fused_bwd)


def moe_ffn_blaze_fused(x: jax.Array, gates: jax.Array, dispatch: Dispatch,
                        w1: jax.Array, w3: jax.Array, w2: jax.Array,
                        *, bl: int | None = None, bh: int | None = None
                        ) -> jax.Array:
    """MoEBlaze SwiGLU expert layer as ONE fused kernel pair.

    Forward: :func:`repro.kernels.gather_gmm.fused_moe_fwd` — gather, both
    first-layer GEMMs, SiLU·gate, the second grouped GEMM, and the gated
    scatter-combine in a single grid pass.  Backward:
    :func:`~repro.kernels.gather_gmm.fused_moe_bwd` replays the gather
    in-kernel.  Neither direction materializes a ``(L·k, h)`` or
    ``(L·k, d)`` buffer in HBM.

    ``bl``/``bh`` default to :func:`repro.roofline.select_moe_tiles` — the
    arithmetic-intensity model picks the tile pair at trace time from the
    static shapes (the kernels still clamp to divisors/extents).
    """
    d = dispatch
    if bl is None or bh is None:
        from repro.roofline import select_moe_tiles
        abl, abh = select_moe_tiles(
            d.expert_token_indices.shape[0], x.shape[1], w1.shape[2],
            dtype_bytes=x.dtype.itemsize, num_experts=w1.shape[0])
        bl = abl if bl is None else bl
        bh = abh if bh is None else bh
    return _moe_fused(bl, bh, x, w1, w2, w3, gates,
                      d.expert_token_indices, d.expert_token_offsets,
                      d.token_index_map)


# ---------------------------------------------------------------------------
# Differentiable row gather (the ep_a2a send-buffer builder).
# ---------------------------------------------------------------------------


@jax.custom_vjp
def gather_rows(src, row_ids):
    """``out[i] = src[row_ids[i]]`` with ``row_ids[i] < 0`` → a zero row,
    as a Pallas kernel: builds an a2a send buffer straight from dispatch
    metadata without materializing an intermediate gathered copy.  The VJP
    scatter-adds valid rows back (dropped rows contribute nothing)."""
    return gather_rows_pallas(src, row_ids)


def _gather_rows_fwd(src, row_ids):
    return gather_rows_pallas(src, row_ids), (src, row_ids)


def _gather_rows_bwd(res, dout):
    src, row_ids = res
    valid = row_ids >= 0
    contrib = jnp.where(valid[:, None], dout, 0).astype(src.dtype)
    dsrc = jnp.zeros_like(src).at[jnp.maximum(row_ids, 0)].add(contrib)
    return dsrc, None


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)
