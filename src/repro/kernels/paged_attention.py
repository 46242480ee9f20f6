"""Pallas TPU paged-attention (decode) kernel.

One-token attention against the block-paged KV pool
(``serve/paged_cache``), walking the page table *inside* the kernel: the
grid is ``(batch, pages_per_seq)`` with the page dimension innermost, the
page table and per-request positions ride in as scalar-prefetch operands
(``pltpu.PrefetchScalarGridSpec``), and each KV block's index map resolves
``page_table[b, p]`` — so the kernel DMAs exactly one physical page per
step instead of materializing the dense ``(B, pages_per_seq * page_size,
Hkv, Dh)`` gather the jnp reference builds per token.  Page steps past a
request's current position are redirected to the trash page (a single
constant page — reads do not scale with the reservation) and their scores
are masked by absolute position, exactly like the reference.

Online softmax runs across page steps in VMEM scratch (f32 running max /
denominator / accumulator — TPU grids are sequential per core, the flash
kernel's idiom); causal masking is by ``t <= pos_b`` with optional sliding
window and logit softcap.  int8 pools keep the scale-on-scores contract:
the kernel loads the int8 page plus its f16 per-vector scales, multiplies
scores by ``k_scale`` rows and probabilities by ``v_scale`` rows, and never
dequantizes storage.

Checked against ``paged_cache.paged_gather_attention`` in interpret mode on
the CPU across {f32, bf16, int8} x {window, softcap}; compiled for a TPU the
block shapes must meet the (8, 128) f32 / (32, 128) int8 tile floors
(serving configs use Dh >= 64 and page_size >= 16).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG_INF = -1e30
TRASH_PAGE = 0


def _kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, *refs,
            n_pages: int, ps: int, Hkv: int, G: int, window: int,
            cap: float, scale: float, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_s, l_s, acc_s = refs
    else:
        (o_ref, m_s, l_s, acc_s), ks_ref, vs_ref = refs, None, None
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    pos = pos_ref[b]
    q = (q_ref[0].astype(jnp.float32) * scale).reshape(Hkv, G, -1)
    k = jnp.transpose(k_ref[0], (1, 0, 2)).astype(jnp.float32)  # (Hkv,ps,Dh)
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)  # (Hkv,G,ps)
    if quantized:
        ksc = jnp.transpose(ks_ref[0][..., 0], (1, 0))           # (Hkv, ps)
        s = s * ksc.astype(jnp.float32)[:, None, :]
    if cap:
        s = cap * jnp.tanh(s / cap)
    t_abs = p * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    valid = t_abs <= pos
    if window:
        valid &= t_abs > pos - window
    s = jnp.where(valid[None, :, :], s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    pr = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * corr + pr.sum(axis=-1, keepdims=True)
    if quantized:
        vsc = jnp.transpose(vs_ref[0][..., 0], (1, 0))           # (Hkv, ps)
        pr = pr * vsc.astype(jnp.float32)[:, None, :]
    v = jnp.transpose(v_ref[0], (1, 0, 2)).astype(jnp.float32)   # (Hkv,ps,Dh)
    acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
        pr, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(p == n_pages - 1)
    def _store():
        out = acc_s[...] / jnp.maximum(l_s[...], 1e-30)
        o_ref[0] = out.reshape(Hkv * G, -1).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "cap"))
def paged_attention_pallas(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, k_scale: jax.Array | None,
                           v_scale: jax.Array | None, page_table: jax.Array,
                           positions: jax.Array, *, window: int = 0,
                           cap: float = 0.0) -> jax.Array:
    """q: (B, 1, Hq, Dh); pools: (P, page_size, Hkv, Dh) (+ f16 scales
    ``(P, page_size, Hkv, 1)`` when int8); page_table: (B, pages_per_seq);
    positions: (B,) current written position per request.
    Returns (B, 1, Hq, Dh) — bit-compatible with the dense reference's
    contraction, f32 accumulated."""
    B, _, Hq, Dh = q.shape
    _, ps, Hkv, _ = k_pages.shape
    G = Hq // Hkv
    pps = page_table.shape[1]
    quantized = k_scale is not None

    def page_idx(b, p, pt, pos):
        # Walk the page table: the block for step p is request b's p-th
        # physical page — unless the page starts past the request's
        # position, in which case the (constant) trash page is read and the
        # whole block masks out.
        return (jnp.where(p * ps <= pos[b], pt[b, p], TRASH_PAGE), 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, Hq, Dh), lambda b, p, pt, pos: (b, 0, 0)),
        pl.BlockSpec((1, ps, Hkv, Dh), page_idx),
        pl.BlockSpec((1, ps, Hkv, Dh), page_idx),
    ]
    inputs = [q.reshape(B, Hq, Dh), k_pages, v_pages]
    if quantized:
        in_specs += [pl.BlockSpec((1, ps, Hkv, 1), page_idx)] * 2
        inputs += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, pps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hq, Dh), lambda b, p, pt, pos: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, n_pages=pps, ps=ps, Hkv=Hkv, G=G,
                          window=window, cap=cap, scale=Dh ** -0.5,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Dh), q.dtype),
        interpret=kernels.interpret_mode(),
    )(page_table.astype(jnp.int32), positions.astype(jnp.int32), *inputs)
    return out.reshape(B, 1, Hq, Dh)
