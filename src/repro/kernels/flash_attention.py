"""Pallas TPU flash-attention (forward) kernel.

Online-softmax attention with explicit VMEM tiling: grid
``(batch*heads, q_blocks, kv_blocks)`` with the KV dimension innermost — TPU
grids run sequentially per core, so the running max / denominator / output
accumulator live in VMEM scratch across KV steps and the output tile is
written once on the last step.  Supports causal masking, sliding windows and
logit softcap (gemma2).  Backward uses XLA autodiff over the pure-jnp
reference (attention backward is not a paper contribution; the fwd kernel is
the serving/prefill hot spot).

Checked in interpret mode on the CPU against ``models.attention``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
            nk: int, bq: int, bk: int, causal: bool, window: int,
            cap: float, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0].astype(jnp.float32) * scale           # (bq, dh)
    k = k_ref[0].astype(jnp.float32)                   # (bk, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if cap:
        s = cap * jnp.tanh(s / cap)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * corr + p.sum(axis=1, keepdims=True)
    acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(ki == nk - 1)
    def _store():
        o_ref[0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "cap",
                                             "bq", "bk"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           cap: float = 0.0, bq: int = 128, bk: int = 128) -> jax.Array:
    """q: (B, S, H, Dh); k, v: (B, S, Hkv, Dh) with H % Hkv == 0.
    Returns (B, S, H, Dh)."""
    B, S, H, Dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    bq, bk = min(bq, S), min(bk, S)
    assert S % bq == 0 and S % bk == 0
    nq, nk = S // bq, S // bk
    # fold batch and heads; repeat kv heads across their query group
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, S, Dh)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, S, Dh)
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, bq=bq, bk=bk, causal=causal,
                          window=window, cap=cap, scale=Dh ** -0.5),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, Dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, Dh), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, Dh), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dh), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dh), jnp.float32),
        ],
        interpret=kernels.interpret_mode(),
    )(qf, kf, vf)
    return out.reshape(B, H, S, Dh).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_fused(q, k, v, causal: bool = True, window: int = 0,
                          cap: float = 0.0):
    """Differentiable wrapper: Pallas kernel forward, XLA-autodiff of the
    chunked reference for backward (flash-bwd is not a paper hot spot;
    residuals are just q/k/v — O(S·d), no score matrix saved)."""
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  cap=cap)


def _fa_fwd(q, k, v, causal, window, cap):
    return flash_attention_fused(q, k, v, causal, window, cap), (q, k, v)


def _fa_bwd(causal, window, cap, res, do):
    from repro.models.attention import flash_attention
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        window=window, cap=cap,
                                        chunk=min(512, q.shape[1]),
                                        block_skip=False), q, k, v)
    return vjp(do)


flash_attention_fused.defvjp(_fa_fwd, _fa_bwd)
