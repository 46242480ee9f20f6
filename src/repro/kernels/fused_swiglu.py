"""Fused dual-GEMM + SwiGLU epilogue Pallas kernels (paper §5.2).

The paper fuses the two first-layer projections ``a = xW1``, ``b = xW2`` with
the SwiGLU epilogue ``silu(a)·b`` so that the input is loaded **once**, both
GEMMs stream through the MXU, the epilogue runs out of VMEM, and only the
final product (plus the checkpointed ``a``, ``b``) is written to HBM —
eliminating the global-memory round trips for ``σ(a)``, ``silu(a)`` and the
product.

TPU mapping (DESIGN.md §2): grid ``(L/bl, h/bh, d/bk)`` with the contraction
dimension innermost (TPU grids execute sequentially per core, so two f32 VMEM
scratch accumulators carry the partial products across ``d``-tiles); the
epilogue fires on the last contraction step.  Block shapes default to
128×128-aligned tiles to match the MXU systolic array.

Backward kernels implement Algorithm 1's ``FusedBwdX`` / ``FusedBwdW``:
``silu(a)`` is *recomputed* from the checkpointed ``a`` (never stored), the
two branches' elementwise derivatives are formed in VMEM, and the shared-input
gradients are accumulated in-place — no temporary global buffers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels


def _silu(a):
    return a * jax.nn.sigmoid(a)


def _dsilu(a):
    s = jax.nn.sigmoid(a)
    return s * (1.0 + a * (1.0 - s))


# ---------------------------------------------------------------------------
# Forward: (x, w1, w2) -> (y_swi, a, b)
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, w1_ref, w2_ref, y_ref, a_ref, b_ref,
                acc_a, acc_b, *, nk: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_a[...] = jnp.zeros_like(acc_a)
        acc_b[...] = jnp.zeros_like(acc_b)

    x = x_ref[...]
    acc_a[...] += jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
    acc_b[...] += jnp.dot(x, w2_ref[...], preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _epilogue():
        a = acc_a[...]
        b = acc_b[...]
        a_ref[...] = a.astype(a_ref.dtype)
        b_ref[...] = b.astype(b_ref.dtype)
        y_ref[...] = (_silu(a) * b).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bl", "bh", "bk"))
def fused_swiglu_fwd(x: jax.Array, w1: jax.Array, w2: jax.Array,
                     *, bl: int = 128, bh: int = 128, bk: int = 128):
    """Returns ``(y_swi, a, b)`` with a single pass over ``x``."""
    L, d = x.shape
    _, h = w1.shape
    bl, bh, bk = min(bl, L), min(bh, h), min(bk, d)
    assert L % bl == 0 and h % bh == 0 and d % bk == 0, (L, h, d, bl, bh, bk)
    nl, nh, nk = L // bl, h // bh, d // bk
    out_shapes = [jax.ShapeDtypeStruct((L, h), x.dtype)] * 3
    y, a, b = pl.pallas_call(
        functools.partial(_fwd_kernel, nk=nk),
        grid=(nl, nh, nk),
        in_specs=[
            pl.BlockSpec((bl, bk), lambda l, hh, kk: (l, kk)),
            pl.BlockSpec((bk, bh), lambda l, hh, kk: (kk, hh)),
            pl.BlockSpec((bk, bh), lambda l, hh, kk: (kk, hh)),
        ],
        out_specs=[pl.BlockSpec((bl, bh), lambda l, hh, kk: (l, hh))] * 3,
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((bl, bh), jnp.float32)] * 2,
        interpret=kernels.interpret_mode(),
    )(x, w1, w2)
    return y, a, b


# ---------------------------------------------------------------------------
# Backward dX: (dy, a, b, w1, w2) -> dx = da @ w1^T + db @ w2^T   (FusedBwdX)
# ---------------------------------------------------------------------------


def _bwd_x_kernel(dy_ref, a_ref, b_ref, w1_ref, w2_ref, dx_ref,
                  acc, *, nk: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    dy = dy_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    da = dy * b * _dsilu(a)          # silu'(a) recomputed in VMEM
    db = dy * _silu(a)               # silu(a)  recomputed in VMEM
    acc[...] += jnp.dot(da.astype(dy_ref.dtype), w1_ref[...].T,
                        preferred_element_type=jnp.float32)
    acc[...] += jnp.dot(db.astype(dy_ref.dtype), w2_ref[...].T,
                        preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _store():
        dx_ref[...] = acc[...].astype(dx_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bl", "bd", "bk"))
def fused_swiglu_bwd_x(dy: jax.Array, a: jax.Array, b: jax.Array,
                       w1: jax.Array, w2: jax.Array,
                       *, bl: int = 128, bd: int = 128, bk: int = 128) -> jax.Array:
    L, h = dy.shape
    d = w1.shape[0]
    bl, bd, bk = min(bl, L), min(bd, d), min(bk, h)
    assert L % bl == 0 and d % bd == 0 and h % bk == 0
    nl, nd, nk = L // bl, d // bd, h // bk
    return pl.pallas_call(
        functools.partial(_bwd_x_kernel, nk=nk),
        grid=(nl, nd, nk),
        in_specs=[
            pl.BlockSpec((bl, bk), lambda l, dd, kk: (l, kk)),   # dy
            pl.BlockSpec((bl, bk), lambda l, dd, kk: (l, kk)),   # a
            pl.BlockSpec((bl, bk), lambda l, dd, kk: (l, kk)),   # b
            pl.BlockSpec((bd, bk), lambda l, dd, kk: (dd, kk)),  # w1
            pl.BlockSpec((bd, bk), lambda l, dd, kk: (dd, kk)),  # w2
        ],
        out_specs=pl.BlockSpec((bl, bd), lambda l, dd, kk: (l, dd)),
        out_shape=jax.ShapeDtypeStruct((L, d), dy.dtype),
        scratch_shapes=[pltpu.VMEM((bl, bd), jnp.float32)],
        interpret=kernels.interpret_mode(),
    )(dy, a, b, w1, w2)


# ---------------------------------------------------------------------------
# Backward dW: (x, dy, a, b) -> (dw1, dw2) sharing one read of x  (FusedBwdW)
# ---------------------------------------------------------------------------


def _bwd_w_kernel(x_ref, dy_ref, a_ref, b_ref, dw1_ref, dw2_ref,
                  acc1, acc2, *, nk: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc1[...] = jnp.zeros_like(acc1)
        acc2[...] = jnp.zeros_like(acc2)

    x = x_ref[...]
    dy = dy_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    da = (dy * b * _dsilu(a)).astype(x.dtype)
    db = (dy * _silu(a)).astype(x.dtype)
    acc1[...] += jnp.dot(x.T, da, preferred_element_type=jnp.float32)
    acc2[...] += jnp.dot(x.T, db, preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _store():
        dw1_ref[...] = acc1[...].astype(dw1_ref.dtype)
        dw2_ref[...] = acc2[...].astype(dw2_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bd", "bh", "bk"))
def fused_swiglu_bwd_w(x: jax.Array, dy: jax.Array, a: jax.Array,
                       b: jax.Array,
                       *, bd: int = 128, bh: int = 128, bk: int = 128):
    L, d = x.shape
    h = dy.shape[1]
    bd, bh, bk = min(bd, d), min(bh, h), min(bk, L)
    assert d % bd == 0 and h % bh == 0 and L % bk == 0
    nd, nh, nk = d // bd, h // bh, L // bk
    return pl.pallas_call(
        functools.partial(_bwd_w_kernel, nk=nk),
        grid=(nd, nh, nk),
        in_specs=[
            pl.BlockSpec((bk, bd), lambda dd, hh, kk: (kk, dd)),  # x
            pl.BlockSpec((bk, bh), lambda dd, hh, kk: (kk, hh)),  # dy
            pl.BlockSpec((bk, bh), lambda dd, hh, kk: (kk, hh)),  # a
            pl.BlockSpec((bk, bh), lambda dd, hh, kk: (kk, hh)),  # b
        ],
        out_specs=[pl.BlockSpec((bd, bh), lambda dd, hh, kk: (dd, hh))] * 2,
        out_shape=[jax.ShapeDtypeStruct((d, h), x.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((bd, bh), jnp.float32)] * 2,
        interpret=kernels.interpret_mode(),
    )(x, dy, a, b)
