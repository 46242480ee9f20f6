"""Per-kernel allclose sweeps against the ref.py pure-jnp oracles
(interpret mode on the CPU), over shapes and dtypes, plus hypothesis
property tests for the Pallas dispatch builder."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # hypothesis is an optional test extra; fall back to fixed examples
    from hypothesis import given, settings, strategies as st
except ImportError:
    from hypothesis_fallback import given, settings, st

from repro.core.routing import build_dispatch
from repro.kernels import ref
from repro.kernels.combine import combine
from repro.kernels.dispatch import build_dispatch_pallas
from repro.kernels.fused_swiglu import (fused_swiglu_bwd_w, fused_swiglu_bwd_x,
                                        fused_swiglu_fwd)
from repro.kernels.gather_gmm import gather_gmm


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("L,d,h", [(128, 128, 128), (256, 64, 384),
                                   (384, 256, 128)])
def test_fused_swiglu_fwd_sweep(L, d, h, dtype):
    ks = jax.random.split(jax.random.PRNGKey(L + d + h), 3)
    x = jax.random.normal(ks[0], (L, d), dtype)
    w1 = (jax.random.normal(ks[1], (d, h)) * 0.05).astype(dtype)
    w2 = (jax.random.normal(ks[2], (d, h)) * 0.05).astype(dtype)
    y, a, b = fused_swiglu_fwd(x, w1, w2, bl=128, bh=128, bk=64)
    yr, ar, br = ref.fused_swiglu_fwd_ref(x, w1, w2)
    for u, v in ((y, yr), (a, ar), (b, br)):
        np.testing.assert_allclose(np.asarray(u, np.float32),
                                   np.asarray(v, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_swiglu_bwd_sweep(dtype):
    L, d, h = 256, 128, 256
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (L, d), dtype)
    w1 = (jax.random.normal(ks[1], (d, h)) * 0.05).astype(dtype)
    w2 = (jax.random.normal(ks[2], (d, h)) * 0.05).astype(dtype)
    _, a, b = fused_swiglu_fwd(x, w1, w2)
    dy = jax.random.normal(ks[3], (L, h), dtype)
    dx = fused_swiglu_bwd_x(dy, a, b, w1, w2)
    np.testing.assert_allclose(
        np.asarray(dx, np.float32),
        np.asarray(ref.fused_swiglu_bwd_x_ref(dy, a, b, w1, w2), np.float32),
        **_tol(dtype))
    dw1, dw2 = fused_swiglu_bwd_w(x, dy, a, b)
    dw1r, dw2r = ref.fused_swiglu_bwd_w_ref(x, dy, a, b)
    np.testing.assert_allclose(np.asarray(dw1, np.float32),
                               np.asarray(dw1r, np.float32),
                               atol=0.3 if dtype == jnp.bfloat16 else 1e-3,
                               rtol=5e-2)
    np.testing.assert_allclose(np.asarray(dw2, np.float32),
                               np.asarray(dw2r, np.float32),
                               atol=0.3 if dtype == jnp.bfloat16 else 1e-3,
                               rtol=5e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("L,d,h,E,k,bl", [
    (200, 64, 128, 8, 2, 64), (128, 128, 128, 4, 1, 128),
    (97, 64, 128, 16, 4, 32),
])
def test_gather_gmm_sweep(L, d, h, E, k, bl, dtype):
    ks = jax.random.split(jax.random.PRNGKey(L + E), 4)
    x = jax.random.normal(ks[0], (L, d), dtype)
    w1 = (jax.random.normal(ks[1], (E, d, h)) * 0.05).astype(dtype)
    w2 = (jax.random.normal(ks[2], (E, d, h)) * 0.05).astype(dtype)
    scores = jax.random.normal(ks[3], (L, E))
    _, topk = jax.lax.top_k(scores, k)
    disp = build_dispatch(topk.astype(jnp.int32), E)
    y, a, b = gather_gmm(x, disp.expert_token_indices,
                         disp.expert_token_offsets, w1, w2,
                         save_ab=True, bl=bl)
    yr, ar, br = ref.gather_gmm_ref(x, disp.expert_token_indices,
                                    disp.expert_token_offsets, w1, w2)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(ar, np.float32), **_tol(dtype))
    # single-GEMM (no epilogue) mode
    y1 = gather_gmm(x, disp.expert_token_indices, disp.expert_token_offsets,
                    w1, epilogue=False, bl=bl)
    np.testing.assert_allclose(
        np.asarray(y1, np.float32),
        np.asarray(ref.gather_gmm_ref(x, disp.expert_token_indices,
                                      disp.expert_token_offsets, w1),
                   np.float32), **_tol(dtype))


@pytest.mark.parametrize("L,k,d,bl", [(100, 2, 64, 64), (256, 4, 128, 128),
                                      (64, 1, 32, 32)])
def test_combine_sweep(L, k, d, bl):
    E = 8
    ks = jax.random.split(jax.random.PRNGKey(L * k), 3)
    scores = jax.random.normal(ks[0], (L, E))
    _, topk = jax.lax.top_k(scores, k)
    disp = build_dispatch(topk.astype(jnp.int32), E)
    p = jax.random.normal(ks[1], (L * k, d))
    gates = jax.random.uniform(ks[2], (L, k))
    y = combine(p, disp.token_index_map, gates, bl=bl)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref.combine_ref(p, disp.token_index_map,
                                                  gates)), atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 100), st.integers(2, 16), st.integers(1, 4),
       st.integers(0, 2**31 - 1), st.sampled_from([32, 64, 256]))
def test_dispatch_pallas_property(L, E, k, seed, bl):
    """Pallas builder == XLA sort-free builder for arbitrary shapes."""
    k = min(k, E)
    scores = jax.random.normal(jax.random.PRNGKey(seed), (L, E))
    _, topk = jax.lax.top_k(scores, k)
    topk = topk.astype(jnp.int32)
    a = build_dispatch_pallas(topk, E, bl=bl)
    b = build_dispatch(topk, E)
    for name, (u, v) in zip(a._fields, zip(a, b)):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v),
                                      err_msg=name)


def test_full_pallas_moe_layer_grads():
    from repro.core.moe_layer import moe_ffn_blaze
    from repro.kernels.ops import moe_ffn_blaze_pallas
    L, d, h, E, k = 128, 64, 128, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (L, d))
    w1 = jax.random.normal(ks[1], (E, d, h)) * 0.05
    w2 = jax.random.normal(ks[2], (E, d, h)) * 0.05
    w3 = jax.random.normal(ks[3], (E, h, d)) * 0.05
    scores = jax.random.normal(ks[4], (L, E))
    _, topk = jax.lax.top_k(scores, k)
    disp = build_dispatch(topk.astype(jnp.int32), E)
    gates = jax.nn.softmax(scores, -1)
    gates = jnp.take_along_axis(gates, topk, 1)
    gates = gates / gates.sum(-1, keepdims=True)

    def f_pal(*a):
        return moe_ffn_blaze_pallas(a[0], gates, disp, a[1], a[3], a[2]).sum()

    def f_ref(*a):
        return moe_ffn_blaze(a[0], gates, disp, a[1], a[3], a[2]).sum()

    gp = jax.grad(f_pal, argnums=(0, 1, 2, 3))(x, w1, w2, w3)
    gr = jax.grad(f_ref, argnums=(0, 1, 2, 3))(x, w1, w2, w3)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
