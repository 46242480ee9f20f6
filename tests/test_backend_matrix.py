"""Cross-backend parity test matrix (the proof behind context-scoped backend
resolution): every backend reported by ``backend_names()`` must agree —
forward AND gradients — with the portable ``segment`` oracle through every
MoE entry point ({moe_layer, baseline, moe_block}) in both f32 and bf16.

Shape variety (ragged group boundaries, empty experts, k=1 vs k=2) comes
from hypothesis-drawn examples — ``tests/hypothesis_fallback.py`` keeps those
deterministic when hypothesis is not installed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # hypothesis is an optional test extra; fall back to fixed examples
    from hypothesis import given, settings, strategies as st
except ImportError:
    from hypothesis_fallback import given, settings, st

from repro.configs.base import ModelConfig
from repro.core import gmm_backend as GB
from repro.core.baseline import moe_ffn_megablocks
from repro.core.moe_layer import moe_ffn_blaze
from repro.core.routing import build_dispatch, top_k_gating
from repro.models.moe_block import init_moe_params, moe_sublayer

ALL_BACKENDS = GB.backend_names()

LAYERS = ("moe_layer", "baseline", "moe_block")
DTYPES = ("float32", "bfloat16")

# bf16 outputs are rounded to 8 mantissa bits at every gmm boundary, and the
# backends may order their fp32 reductions differently before that rounding.
_TOL = {"float32": dict(rtol=1e-4, atol=1e-5),
        "bfloat16": dict(rtol=5e-2, atol=5e-2)}


def _moe_cfg(dtype="float32", E=4, k=2) -> ModelConfig:
    return ModelConfig(
        name="matrix_moe", arch_type="moe", num_layers=2, d_model=16,
        num_heads=2, num_kv_heads=2, head_dim=8, vocab_size=64,
        num_experts=E, top_k=k, moe_d_ff=32, dtype=dtype,
        param_dtype=dtype, aux_loss_weight=0.01, z_loss_weight=1e-3)


def _inputs(seed, L, d, h, E, k, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt = jnp.dtype(dtype)
    x = jax.random.normal(ks[0], (L, d), jnp.float32).astype(dt)
    wg = jax.random.normal(ks[1], (d, E), jnp.float32) * 0.1
    w1 = (jax.random.normal(ks[2], (E, d, h)) * 0.1).astype(dt)
    w2 = (jax.random.normal(ks[3], (E, d, h)) * 0.1).astype(dt)
    w3 = (jax.random.normal(ks[4], (E, h, d)) * 0.1).astype(dt)
    g = top_k_gating(x.astype(jnp.float32), wg, k)
    disp = build_dispatch(g.topk_experts, E)
    gates = g.topk_weights.astype(dt)
    return x, w1, w2, w3, gates, disp


def _layer_loss(layer, dtype, seed=11, L=40, E=4, k=2):
    """(loss_fn(backend), args) for one matrix cell.  The loss closes over
    the layer entry point; args are the differentiable leaves."""
    d, h = 16, 32
    if layer == "moe_block":
        cfg = _moe_cfg(dtype, E, k)
        params = init_moe_params(jax.random.PRNGKey(seed), cfg, cfg.d_model)
        x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (1, L, cfg.d_model),
                              jnp.float32).astype(jnp.dtype(dtype))

        def loss_fn(backend):
            def f(x, params):
                y, aux = moe_sublayer(
                    x, params, cfg.replace(gmm_backend=backend))
                return (y.astype(jnp.float32) ** 2).sum() + aux
            return f

        return loss_fn, (x, params)

    x, w1, w2, w3, gates, disp = _inputs(seed, L, d, h, E, k, dtype)
    entry = moe_ffn_blaze if layer == "moe_layer" else moe_ffn_megablocks

    def loss_fn(backend):
        def f(x, w1, w2, w3, gates):
            y = entry(x, gates, disp, w1, w3, w2, backend=backend)
            return (y.astype(jnp.float32) ** 2).sum()
        return f

    return loss_fn, (x, w1, w2, w3, gates)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_forward_and_grad_parity(backend, layer, dtype):
    """The matrix cell: value and every input/parameter gradient of ``layer``
    under ``backend`` match the ``segment`` oracle at ``dtype`` tolerance."""
    loss_fn, args = _layer_loss(layer, dtype)
    tol = dict(_TOL[dtype])
    if backend == "pallas_fused" and dtype == "bfloat16":
        # The fused kernels keep the SiLU/gating chains in f32 where the
        # bf16 oracle rounds every elementwise op, so the fused grads land
        # *closer* to the f32 truth than the oracle itself does (measured
        # per-leaf max abs error on this cell: 0.10-0.16 fused vs
        # 0.08-0.29 segment, grads O(25)).  The fused-vs-oracle gap is
        # therefore bounded by the oracle's own bf16 noise, up to ~2x.
        tol["atol"] = 3e-1

    v = loss_fn(backend)(*args)
    vr = loss_fn("segment")(*args)
    np.testing.assert_allclose(float(v), float(vr), rtol=tol["rtol"],
                               err_msg=f"fwd {layer}/{backend}/{dtype}")

    argnums = tuple(range(len(args)))
    g = jax.grad(loss_fn(backend), argnums=argnums)(*args)
    gr = jax.grad(loss_fn("segment"), argnums=argnums)(*args)
    for i, (a, b) in enumerate(zip(jax.tree.leaves(g), jax.tree.leaves(gr))):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), **tol,
            err_msg=f"grad leaf {i} ({layer}/{backend}/{dtype})")


@settings(max_examples=5, deadline=None)
@given(st.integers(17, 49), st.sampled_from([2, 4, 8]), st.integers(1, 2))
def test_forward_parity_drawn_shapes(L, E, k):
    """Forward parity across every available backend and every layer entry
    point at hypothesis-drawn (L, E, k) — odd lengths, ragged group
    boundaries, k=1 routing.  Gradients are covered by the fixed-shape
    matrix above; keeping the drawn sweep forward-only keeps the
    interpret-mode pallas cells fast."""
    for layer in LAYERS:
        loss_fn, args = _layer_loss(layer, "float32", seed=100 + L,
                                    L=L, E=E, k=k)
        ref = float(loss_fn("segment")(*args))
        for backend in ALL_BACKENDS:
            got = float(loss_fn(backend)(*args))
            np.testing.assert_allclose(
                got, ref, rtol=1e-4,
                err_msg=f"{layer}/{backend} at L={L} E={E} k={k}")


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_gmm_primitive_parity_bf16(backend):
    """The raw gmm/gmm_dw primitives at bf16 with a ragged (empty-group)
    split: fp32 accumulation means every backend lands within bf16 rounding
    of the f32 segment oracle."""
    S, d, h, E = 64, 16, 24, 5
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    lhs = jax.random.normal(ks[0], (S, d)).astype(jnp.bfloat16)
    rhs = (jax.random.normal(ks[1], (E, d, h)) * 0.1).astype(jnp.bfloat16)
    dout = jax.random.normal(ks[2], (S, h)).astype(jnp.bfloat16)
    gs = jnp.asarray([20, 0, 24, 0, 20], jnp.int32)

    seg = GB.get_backend("segment")
    ref_y = np.asarray(seg.gmm(lhs.astype(jnp.float32),
                               rhs.astype(jnp.float32), gs))
    ref_dw = np.asarray(seg.gmm_dw(lhs.astype(jnp.float32),
                                   dout.astype(jnp.float32), gs))
    y = np.asarray(GB.gmm(lhs, rhs, gs, backend=backend), np.float32)
    dw = np.asarray(GB.gmm_dw(lhs, dout, gs, backend=backend), np.float32)
    np.testing.assert_allclose(y, ref_y, rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(dw, ref_dw, rtol=5e-2, atol=5e-2)
