"""Fused dispatch→GEMM→combine path (the ``pallas_fused`` backend):
fwd+grad parity matrix against the unfused layer across backends × dtypes ×
residual modes, the hardened work-item contracts (non-divisible ``bh``,
empty experts, ``n_valid == 0``), the no-materialized-buffer residual
accounting, and the roofline tile selector."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gmm_backend as GB
from repro.core.checkpoint import saved_residuals
from repro.core.moe_layer import RESIDUAL_MODES, moe_ffn_blaze
from repro.core.routing import build_dispatch, top_k_gating
from repro.kernels.common import lane_tile, largest_divisor_tile
from repro.kernels.gather_gmm import (fused_moe_fwd, gather_gmm,
                                      gather_rows_pallas, gmm_dw_pallas,
                                      make_work_items)

UNFUSED = [b for b in GB.backend_names() if b != "pallas_fused"]


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=5e-4, rtol=5e-4)


def _setup(seed, L, d, h, E, k, dtype=jnp.float32, biased=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (L, d), dtype)
    w1 = (jax.random.normal(ks[2], (E, d, h)) * 0.1).astype(dtype)
    w2 = (jax.random.normal(ks[3], (E, d, h)) * 0.1).astype(dtype)
    w3 = (jax.random.normal(ks[4], (E, h, d)) * 0.1).astype(dtype)
    if biased:
        # Every token picks experts {1, 2} -> all other groups are empty.
        topk = jnp.tile(jnp.array([[1, 2]], jnp.int32), (L, 1))[:, :k]
        gates = jax.nn.softmax(jax.random.normal(ks[1], (L, k)), -1)
    else:
        wg = jax.random.normal(ks[1], (d, E)).astype(jnp.float32) * 0.1
        g = top_k_gating(x.astype(jnp.float32), wg, k)
        topk, gates = g.topk_experts, g.topk_weights
    disp = build_dispatch(topk.astype(jnp.int32), E)
    return x, w1, w2, w3, gates.astype(dtype), disp


# ---------------------------------------------------------------------------
# Parity matrix: fused vs every unfused backend × dtype × residual mode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("residuals", sorted(RESIDUAL_MODES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("backend", UNFUSED)
def test_fused_vs_unfused_parity(backend, dtype, residuals):
    """The fused kernel pair must be value- and gradient-exact (to dtype
    tolerance) against the unfused layer in *every* residual mode — the
    fused backward recomputes everything in-kernel, so each mode's saved
    set is satisfied a fortiori."""
    L, d, h, E, k = 64, 16, 32, 4, 2
    x, w1, w2, w3, gates, disp = _setup(3, L, d, h, E, k, dtype=dtype)

    def loss(be, res_mode):
        def f(x, w1, w2, w3, gates):
            y = moe_ffn_blaze(x, gates, disp, w1, w3, w2,
                              residuals=res_mode, backend=be)
            return (y.astype(jnp.float32) ** 2).sum()
        return f

    args = (x, w1, w2, w3, gates)
    v_f = loss("pallas_fused", residuals)(*args)
    v_u = loss(backend, residuals)(*args)
    np.testing.assert_allclose(float(v_f), float(v_u), rtol=1e-2
                               if dtype == jnp.bfloat16 else 1e-4)
    g_f = jax.grad(loss("pallas_fused", residuals),
                   argnums=(0, 1, 2, 3, 4))(*args)
    g_u = jax.grad(loss(backend, residuals), argnums=(0, 1, 2, 3, 4))(*args)
    for i, (a, b) in enumerate(zip(g_f, g_u)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **_tol(dtype),
                                   err_msg=f"grad argnum {i} vs {backend}")


def test_fused_parity_empty_experts_and_nondivisible_h():
    """The hardened contracts, through the full layer: skewed routing
    (empty experts) on an FFN width that is NOT a multiple of the 128 tile
    request (bh clamps to a divisor)."""
    L, d, h, E, k = 48, 16, 192, 8, 2
    x, w1, w2, w3, gates, disp = _setup(4, L, d, h, E, k, biased=True)
    assert (np.asarray(disp.expert_lengths) == 0).sum() >= E - 2

    def loss(be):
        def f(x, w1, w2, w3, gates):
            y = moe_ffn_blaze(x, gates, disp, w1, w3, w2, backend=be)
            return (y.astype(jnp.float32) ** 2).sum()
        return f

    args = (x, w1, w2, w3, gates)
    g_f = jax.grad(loss("pallas_fused"), argnums=(0, 1, 2, 3, 4))(*args)
    g_u = jax.grad(loss("segment"), argnums=(0, 1, 2, 3, 4))(*args)
    lens = np.asarray(disp.expert_lengths)
    for i, (a, b) in enumerate(zip(g_f, g_u)):
        assert np.isfinite(np.asarray(a, np.float32)).all(), f"argnum {i}"
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"grad argnum {i}")
    for dw in g_f[1:3]:          # dw1/dw2 of empty experts: exact zeros
        np.testing.assert_array_equal(np.asarray(dw)[lens == 0], 0.0)


# ---------------------------------------------------------------------------
# Residual accounting: no (L·k, h) / (L·k, d) buffer survives the forward
# ---------------------------------------------------------------------------


def test_fused_saves_no_slot_buffers():
    """The fused path's saved residuals must contain NO ``(L·k, h)`` or
    ``(L·k, d)`` activation — the tentpole's whole point.  The unfused
    pallas path saves several (a, b, y_swi, and the combine input)."""
    L, d, h, E, k = 64, 16, 32, 4, 2
    x, w1, w2, w3, gates, disp = _setup(5, L, d, h, E, k)
    S = L * k

    def count_slot_avals(be):
        def f(x, w1, w2, w3, gates):
            return moe_ffn_blaze(x, gates, disp, w1, w3, w2, backend=be)
        n = 0
        for aval, src in saved_residuals(f, x, w1, w2, w3, gates):
            if "from the argument" in str(src):
                continue
            if getattr(aval, "shape", None) in ((S, h), (S, d)):
                n += 1
        return n

    assert count_slot_avals("pallas_fused") == 0
    assert count_slot_avals("segment") > 0     # the unfused layer does save


# ---------------------------------------------------------------------------
# Work-item contract regressions (the satellites), on the raw kernels
# ---------------------------------------------------------------------------


def test_largest_divisor_tile():
    assert largest_divisor_tile(192, 128) == 96
    assert largest_divisor_tile(128, 128) == 128
    assert largest_divisor_tile(7, 128) == 7
    assert largest_divisor_tile(100, 64) == 50
    assert largest_divisor_tile(13, 8) == 1    # prime: degenerate but valid


def test_gather_gmm_non_divisible_h():
    """Regression: ``assert h % bh == 0`` used to crash any FFN width that
    wasn't a multiple of the 128 tile request.  Such a width is one
    full-width block (``lane_tile``); 128-multiples tile by divisors."""
    assert lane_tile(192, 128) == 192
    assert lane_tile(4096, 512) == 512
    assert lane_tile(384, 256) == 128
    L, d, h, E, k = 40, 16, 192, 4, 2
    x, w1, w2, w3, gates, disp = _setup(6, L, d, h, E, k)
    y = gather_gmm(x, disp.expert_token_indices, disp.expert_token_offsets,
                   w1, w2, bh=128)
    assert y.shape == (L * k, h)
    assert np.isfinite(np.asarray(y)).all()
    ref = gather_gmm(x, disp.expert_token_indices, disp.expert_token_offsets,
                     w1, w2, bh=h)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_gmm_dw_pallas_zeros_empty_experts_in_kernel():
    """Regression: blocks of empty experts used to be left uninitialized
    (NaN) by the raw kernel, with only caller-side masking as a workaround.
    The efirst filler items now zero them in-kernel."""
    S, d, h = 64, 16, 24
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    lhs = jax.random.normal(ks[0], (S, d))
    dout = jax.random.normal(ks[1], (S, h))
    off = jnp.asarray([0, 30, 30, 64, 64], jnp.int32)   # experts 1, 3 empty
    dw = np.asarray(gmm_dw_pallas(lhs, dout, off))
    assert np.isfinite(dw).all()
    np.testing.assert_array_equal(dw[1], 0.0)
    np.testing.assert_array_equal(dw[3], 0.0)
    ref = np.asarray(lhs)[:30].T @ np.asarray(dout)[:30]
    np.testing.assert_allclose(dw[0], ref, atol=1e-5, rtol=1e-5)


def test_make_work_items_all_empty():
    """Regression: ``n_valid == 0`` (an ``ep_a2a`` shard whose tokens were
    all dropped) used to produce self-referential filler metadata and leave
    every output block uninitialized.  Now: every tile and every expert
    opens a run (first / efirst), all ranges empty."""
    n_tiles, E, bl = 3, 4, 32
    off = jnp.zeros((E + 1,), jnp.int32)
    tile, expert, lo, hi, first, efirst = make_work_items(off, n_tiles, bl, E)
    tile, expert, lo, hi, first, efirst = (
        np.asarray(a) for a in (tile, expert, lo, hi, first, efirst))
    assert tile.shape == (n_tiles + E,)
    np.testing.assert_array_equal(lo, 0)
    np.testing.assert_array_equal(hi, 0)
    # every tile's output block gets exactly one zero-init item ...
    assert sorted(tile[first == 1]) == list(range(n_tiles))
    # ... and every expert's dw block too
    assert sorted(expert[efirst == 1]) == list(range(E))
    # metadata stays in range (no self-referential garbage)
    assert ((tile >= 0) & (tile < n_tiles)).all()
    assert ((expert >= 0) & (expert < E)).all()


@pytest.mark.parametrize("sizes,n_tiles,bl", [
    ([30, 0, 34, 0], 2, 32),            # boundaries mid-tile, empty experts
    ([0, 0, 64, 0, 5], 3, 32),          # leading empties, dead tail tiles
    ([32, 32, 0, 32], 3, 32),           # boundaries on tile edges
    ([7, 0, 0, 100, 1, 0, 19], 5, 32),
])
def test_make_work_items_visit_each_block_in_one_run(sizes, n_tiles, bl):
    """A compiled TPU grid writes an output block back when its index
    changes and never reads it back: every tile and every expert must be
    visited in ONE run of consecutive items, opened by its first/efirst
    flag, and the items' row ranges must cover each expert's rows once."""
    E = len(sizes)
    off = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int32)
    tile, expert, lo, hi, first, efirst = (np.asarray(a) for a in
                                           make_work_items(jnp.asarray(off),
                                                           n_tiles, bl, E))
    assert tile.shape == (n_tiles + E,)
    assert (np.diff(tile) >= 0).all() and (np.diff(expert) >= 0).all()
    assert sorted(tile[first == 1]) == list(range(n_tiles))
    assert sorted(expert[efirst == 1]) == list(range(E))
    np.testing.assert_array_equal(first[1:], np.diff(tile) != 0)
    np.testing.assert_array_equal(efirst[1:], np.diff(expert) != 0)
    covered = np.zeros(n_tiles * bl, np.int32)
    for t, e, a, b in zip(tile, expert, lo, hi):
        rows = t * bl + np.arange(a, b)
        assert ((rows >= off[e]) & (rows < off[e + 1])).all()
        covered[rows] += 1
    np.testing.assert_array_equal(covered[:off[-1]], 1)
    np.testing.assert_array_equal(covered[off[-1]:], 0)


def test_kernels_all_empty_dispatch_produce_zeros():
    """The raw kernels on an all-empty dispatch: finite, exact zeros."""
    L, d, h, E = 32, 16, 24, 4
    S = L * 2
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(ks[0], (L, d))
    w1 = jax.random.normal(ks[1], (E, d, h)) * 0.1
    w2 = jax.random.normal(ks[2], (E, d, h)) * 0.1
    w3 = jax.random.normal(ks[3], (E, h, d)) * 0.1
    idx0 = jnp.zeros((S,), jnp.int32)
    off0 = jnp.zeros((E + 1,), jnp.int32)
    y = np.asarray(gather_gmm(x, idx0, off0, w1, w2))
    np.testing.assert_array_equal(y, 0.0)
    dw = np.asarray(gmm_dw_pallas(jnp.zeros((S, d)), jnp.zeros((S, h)), off0))
    np.testing.assert_array_equal(dw, 0.0)
    yf = np.asarray(fused_moe_fwd(x, jnp.zeros((S,)), idx0, off0, w1, w2, w3))
    np.testing.assert_array_equal(yf, 0.0)


# ---------------------------------------------------------------------------
# gather_rows (the a2a send-buffer kernel)
# ---------------------------------------------------------------------------


def test_gather_rows_pallas_and_vjp():
    from repro.kernels.ops import gather_rows
    L, d = 50, 16
    src = jax.random.normal(jax.random.PRNGKey(2), (L, d))
    ids = jnp.asarray([0, 7, -1, 49, 7, -1], jnp.int32)
    out = np.asarray(gather_rows_pallas(src, ids))
    srcn = np.asarray(src)
    np.testing.assert_allclose(out[0], srcn[0])
    np.testing.assert_allclose(out[1], srcn[7])
    np.testing.assert_array_equal(out[2], 0.0)
    np.testing.assert_array_equal(out[5], 0.0)
    # VJP: scatter-add of valid rows (row 7 appears twice -> grad doubles)
    dsrc = jax.grad(lambda s: gather_rows(s, ids).sum())(src)
    expect = np.zeros((L, d))
    for i in np.asarray(ids):
        if i >= 0:
            expect[i] += 1.0
    np.testing.assert_allclose(np.asarray(dsrc), expect)


# ---------------------------------------------------------------------------
# Roofline tile selection
# ---------------------------------------------------------------------------


def test_select_moe_tiles_properties():
    from repro.roofline import select_moe_tiles
    for n_rows, d, h, dbytes in [(256, 64, 128, 4), (8192, 2048, 5632, 2),
                                 (8192, 1024, 4096, 4), (64, 8, 16, 4)]:
        bl, bh = select_moe_tiles(n_rows, d, h, dtype_bytes=dbytes)
        assert bl % 8 == 0 and bh % 8 == 0          # TPU-tileable requests
        assert 128 <= bl <= 512 and 8 <= bh <= 512
        vmem = ((bl * d + 3 * d * bh) * dbytes + bl * d * 4
                + 3 * bl * bh * 4)
        assert vmem <= 8 * 1024 * 1024
    # bigger weights (larger d) should not select *smaller-AI* tiles than
    # the minimum request
    bl_small, bh_small = select_moe_tiles(4096, 128, 512, dtype_bytes=2)
    assert (bl_small, bh_small) >= (128, 128)
    # with num_experts on the CPU backend, bl shrinks for expert-boundary
    # fragmentation (one full tile per boundary item) but stays TPU-tileable
    bl_f, bh_f = select_moe_tiles(256, 64, 128, dtype_bytes=4, num_experts=8)
    assert bl_f % 8 == 0 and 8 <= bl_f <= 512
    assert bl_f * 8 < 2 * 256 or bl_f == 32   # waste bounded or at the floor
    # plenty of rows per expert -> no shrink below the AI-driven request
    bl_big, _ = select_moe_tiles(8192, 64, 128, dtype_bytes=4, num_experts=8)
    assert bl_big >= 128


def test_fused_never_auto_selected():
    name = GB.resolve_backend_name(None)
    assert name not in ("pallas", "pallas_fused")
