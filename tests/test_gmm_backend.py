"""Backend-parity suite for the pluggable grouped-GEMM registry
(repro.core.gmm_backend): forward + VJP agreement between ``segment``,
``ragged``, ``pallas`` and ``pallas_fused``,
across activations and empty-expert group shapes; plus selection semantics.
(The fused layer path gets its dedicated matrix in test_fused_path.py.)"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gmm_backend as GB
from repro.core.moe_layer import moe_ffn_blaze
from repro.core.routing import build_dispatch, top_k_gating

ALL_BACKENDS = GB.backend_names()


def _grouped(seed, S, d, h, E, sizes=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    lhs = jax.random.normal(ks[0], (S, d), jnp.float32)
    rhs = jax.random.normal(ks[1], (E, d, h), jnp.float32) * 0.1
    dout = jax.random.normal(ks[2], (S, h), jnp.float32)
    if sizes is None:
        base = S // E
        sizes = [base] * E
        sizes[0] += S - base * E
    gs = jnp.asarray(sizes, jnp.int32)
    assert int(gs.sum()) == S
    return lhs, rhs, dout, gs


def _dense_gmm(lhs, rhs, gs):
    """O(E·S) numpy oracle."""
    off = np.concatenate([[0], np.cumsum(np.asarray(gs))])
    out = np.zeros((lhs.shape[0], rhs.shape[-1]), np.float32)
    dw = np.zeros(rhs.shape, np.float32)
    return off, out, dw


@pytest.mark.parametrize("backend", ALL_BACKENDS)
@pytest.mark.parametrize("sizes", [None, (0, 20, 0, 12, 5), (37, 0, 0, 0, 0)],
                         ids=["balanced", "empty-mid", "one-expert"])
def test_gmm_forward_parity(backend, sizes):
    S, d, h, E = 37, 16, 24, 5
    lhs, rhs, dout, gs = _grouped(0, S, d, h, E, sizes)
    off, ref, refdw = _dense_gmm(lhs, rhs, gs)
    ln, rn, dn = (np.asarray(t) for t in (lhs, rhs, dout))
    for e in range(E):
        seg = slice(off[e], off[e + 1])
        ref[seg] = ln[seg] @ rn[e]
        refdw[e] = ln[seg].T @ dn[seg]
    y = GB.gmm(lhs, rhs, gs, backend=backend)
    dw = GB.gmm_dw(lhs, dout, gs, backend=backend)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dw), refdw, rtol=1e-4, atol=1e-5)


def _moe_setup(seed, L, d, h, E, k, biased=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (L, d), jnp.float32)
    wg = (jax.random.normal(ks[1], (d, E)) * 0.1)
    w1 = jax.random.normal(ks[2], (E, d, h)) * 0.1
    w2 = jax.random.normal(ks[3], (E, d, h)) * 0.1
    w3 = jax.random.normal(ks[4], (E, h, d)) * 0.1
    if biased:
        # Every token picks experts {1, 2} -> all other groups are empty.
        topk = jnp.tile(jnp.array([[1, 2]], jnp.int32), (L, 1))[:, :k]
        disp = build_dispatch(topk, E)
        gates = jax.nn.softmax(jax.random.normal(ks[1], (L, k)), -1)
        return x, w1, w2, w3, gates, disp
    g = top_k_gating(x, wg, k)
    disp = build_dispatch(g.topk_experts, E)
    gates = g.topk_weights.astype(x.dtype)
    return x, w1, w2, w3, gates, disp


@pytest.mark.parametrize("act", ["swiglu", "silu", "relu", "gelu"])
@pytest.mark.parametrize("backend", [b for b in ALL_BACKENDS
                                      if b != "segment"])
def test_moe_vjp_parity(backend, act):
    """Forward + full VJP (dx, dw1/dw2/dw3, dgates) of moe_ffn_blaze agree
    between every backend and the portable ``segment`` reference."""
    L, d, h, E, k = 64, 16, 32, 4, 2
    x, w1, w2, w3, gates, disp = _moe_setup(3, L, d, h, E, k)
    w2_ = w2 if act == "swiglu" else None

    def loss(be):
        def f(x, w1, w2, w3, gates):
            w2a = w2 if act == "swiglu" else None
            y = moe_ffn_blaze(x, gates, disp, w1, w3, w2a, activation=act,
                              backend=be)
            return (y.astype(jnp.float32) ** 2).sum()
        return f

    args = (x, w1, w2_ if w2_ is not None else w2, w3, gates)
    v = loss(backend)(*args)
    vr = loss("segment")(*args)
    np.testing.assert_allclose(float(v), float(vr), rtol=1e-4)
    g = jax.grad(loss(backend), argnums=(0, 1, 2, 3, 4))(*args)
    gr = jax.grad(loss("segment"), argnums=(0, 1, 2, 3, 4))(*args)
    for i, (a, b) in enumerate(zip(g, gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5,
                                   err_msg=f"grad argnum {i} ({backend})")


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_moe_vjp_empty_experts(backend):
    """Extreme imbalance: most experts receive zero tokens; every backend
    must produce zero weight-grads for the empty experts and agree with the
    segment reference elsewhere."""
    L, d, h, E, k = 48, 16, 24, 8, 2
    x, w1, w2, w3, gates, disp = _moe_setup(4, L, d, h, E, k, biased=True)

    def f(be):
        def loss(x, w1, w2, w3, gates):
            y = moe_ffn_blaze(x, gates, disp, w1, w3, w2, backend=be)
            return (y.astype(jnp.float32) ** 2).sum()
        return loss

    g = jax.grad(f(backend), argnums=(1, 2, 3))(x, w1, w2, w3, gates)
    gr = jax.grad(f("segment"), argnums=(1, 2, 3))(x, w1, w2, w3, gates)
    lens = np.asarray(disp.expert_lengths)
    assert (lens == 0).sum() >= E - 2          # the routing really is skewed
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    for dw in g[:2]:                           # dw1/dw2 of empty experts == 0
        np.testing.assert_array_equal(
            np.asarray(dw)[lens == 0], 0.0)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_gmm_dw_bf16_fp32_accumulation(backend):
    """The contract requires fp32 accumulation: a bf16 dw over an expert
    spanning many row tiles must match the fp32 reference to bf16 rounding.
    Regression: the pallas dw kernel once accumulated cross-tile partials
    in bf16 (max rel err ~9.7 on this input)."""
    S, d, h, E = 512, 64, 64, 1
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    lhs = jax.random.normal(ks[0], (S, d)).astype(jnp.bfloat16)
    dout = jax.random.normal(ks[1], (S, h)).astype(jnp.bfloat16)
    gs = jnp.array([S], jnp.int32)
    ref = np.asarray(lhs, np.float32).T @ np.asarray(dout, np.float32)
    dw = np.asarray(GB.gmm_dw(lhs, dout, gs, backend=backend), np.float32)
    rel = np.abs(dw[0] - ref).max() / np.abs(ref).max()
    assert rel < 1e-2, rel


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_plain_autodiff_through_megablocks(backend):
    """Every backend must be differentiable by *plain* autodiff (not only
    inside the MoE layer's hand-written VJP): the MegaBlocks-style baseline
    relies on it, as does ``saved_residuals`` in the paper-table benches.
    Regression: the raw pallas_call has no JVP rule and needs its custom-VJP
    wrapper in the registry."""
    from repro.core.baseline import moe_ffn_megablocks
    L, d, h, E, k = 48, 16, 24, 4, 2
    x, w1, w2, w3, gates, disp = _moe_setup(7, L, d, h, E, k)

    def loss(be):
        def f(x, w1, w2, w3):
            y = moe_ffn_megablocks(x, gates, disp, w1, w3, w2, backend=be)
            return (y.astype(jnp.float32) ** 2).sum()
        return f

    g = jax.grad(loss(backend), argnums=(0, 1, 2, 3))(x, w1, w2, w3)
    gr = jax.grad(loss("segment"), argnums=(0, 1, 2, 3))(x, w1, w2, w3)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_segment_matches_moe_dense_oracle():
    """segment-backed blaze layer vs the GShard dense-dispatch oracle —
    ties the backend registry back to the seed suite's ground truth."""
    from repro.core.baseline import moe_ffn_dense
    L, d, h, E, k = 96, 16, 24, 8, 2
    x, w1, w2, w3, gates, disp = _moe_setup(5, L, d, h, E, k)
    y = moe_ffn_blaze(x, gates, disp, w1, w3, w2, backend="segment")
    # rebuild the dense-oracle routing from the same seed / gate weights
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    wg = jax.random.normal(ks[1], (d, E)) * 0.1
    gref = top_k_gating(x, wg, k)
    yd = moe_ffn_dense(x, gref.router_probs, gref.topk_experts, gates,
                       w1, w3, w2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yd),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ALL_BACKENDS)
@pytest.mark.parametrize("S,sizes", [
    (32, (5, 0, 7)),          # dead rows inside the first output tile
    (300, (10, 0, 0)),        # dead rows spanning whole unvisited 128-tiles
    (300, (0, 0, 0)),         # every group empty: all rows dead
], ids=["in-tile", "whole-tiles", "all-empty"])
def test_gmm_trailing_rows_are_exact_zeros(backend, S, sizes):
    """Backend contract regression: rows past the group-size total belong to
    no group and must be *exact zeros* — ``slice_dispatch``'s dead zone (the
    expert-parallel path) combines through them.  The pallas kernel used to
    leave output tiles no work item visits uninitialized (NaN), poisoning
    the EP psum whenever a dead zone spanned a full row tile."""
    d, h = 8, 16
    lhs, rhs, _, gs = _grouped(5, S, d, h, len(sizes), sizes=None)
    gs = jnp.asarray(sizes, jnp.int32)
    total = int(gs.sum())
    y = np.asarray(GB.gmm(lhs, rhs, gs, backend=backend))
    assert np.isfinite(y).all()
    np.testing.assert_array_equal(y[total:], np.zeros((S - total, h)))


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_gmm_non_divisible_h_parity(backend):
    """Regression: ``gather_gmm`` used to crash at trace time on FFN widths
    that weren't multiples of the 128 tile request (``assert h % bh == 0``);
    a width that is not a multiple of 128 now runs as one full-width
    block."""
    S, d, h, E = 48, 16, 192, 4
    lhs, rhs, dout, gs = _grouped(9, S, d, h, E)
    y = GB.gmm(lhs, rhs, gs, backend=backend)
    yr = GB.gmm(lhs, rhs, gs, backend="segment")
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-4, atol=1e-5)
    dw = GB.gmm_dw(lhs, dout, gs, backend=backend)
    dwr = GB.gmm_dw(lhs, dout, gs, backend="segment")
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dwr),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_gmm_dw_empty_experts_cross_backend(backend):
    """Empty-expert contract regression: every backend must return *exact
    zeros* (not NaN, not masked-by-the-caller garbage) for the dw blocks of
    experts with no rows.  The pallas kernel used to leave those blocks
    uninitialized and rely on caller-side masking."""
    S, d, h = 64, 16, 24
    lhs, _, dout, _ = _grouped(11, S, d, h, 4)
    gs = jnp.asarray([30, 0, 34, 0], jnp.int32)
    dw = np.asarray(GB.gmm_dw(lhs, dout, gs, backend=backend))
    assert np.isfinite(dw).all()
    np.testing.assert_array_equal(dw[1], 0.0)
    np.testing.assert_array_equal(dw[3], 0.0)
    ref = np.asarray(lhs)[:30].T @ np.asarray(dout)[:30]
    np.testing.assert_allclose(dw[0], ref, rtol=1e-4, atol=1e-5)


# Selection semantics
# ---------------------------------------------------------------------------


def test_auto_default_resolves_to_available():
    name = GB.resolve_backend_name(None)
    assert name in ALL_BACKENDS
    # the Pallas kernels are never auto-selected
    assert name not in ("pallas", "pallas_fused")


def test_env_var_selection(monkeypatch):
    monkeypatch.setenv(GB.ENV_VAR, "segment")
    assert GB.resolve_backend_name(None) == "segment"
    assert GB.get_backend().name == "segment"
    # explicit argument beats the env var
    monkeypatch.setenv(GB.ENV_VAR, "pallas")
    assert GB.resolve_backend_name("segment") == "segment"


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown gmm backend"):
        GB.resolve_backend_name("cuda")


def test_env_var_reaches_moe_layer(monkeypatch):
    """moe_ffn_blaze picks up REPRO_GMM_BACKEND at trace time."""
    monkeypatch.setenv(GB.ENV_VAR, "segment")
    L, d, h, E, k = 32, 8, 16, 4, 2
    x, w1, w2, w3, gates, disp = _moe_setup(6, L, d, h, E, k)
    y_env = moe_ffn_blaze(x, gates, disp, w1, w3, w2)
    monkeypatch.delenv(GB.ENV_VAR)
    y_exp = moe_ffn_blaze(x, gates, disp, w1, w3, w2, backend="segment")
    np.testing.assert_array_equal(np.asarray(y_env), np.asarray(y_exp))


# ---------------------------------------------------------------------------
# bfloat16 operands where the product is one bfloat16 pass (``ragged``)
# ---------------------------------------------------------------------------


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the one function that looks at the platform: the ``ragged``
    backend then rounds as it does compiled for a TPU, here on the CPU."""
    monkeypatch.setattr(GB, "_by_platform",
                        lambda plain, rounded, *args: rounded(*args))


def _rounded(*ts):
    return tuple(t.astype(jnp.bfloat16).astype(jnp.float32) for t in ts)


def _gemm_operand_dtypes(fn, *args):
    """The operand dtypes of every grouped GEMM ``fn`` traces to."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name.startswith("ragged_dot"):
                found.append(tuple(str(v.aval.dtype) for v in eqn.invars[:2]))
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                    if hasattr(sub, "eqns"):
                        walk(sub)
                    elif hasattr(sub, "jaxpr"):
                        walk(sub.jaxpr)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    assert found
    return found


@pytest.mark.parametrize("sizes", [None, (0, 20, 0, 12, 5)],
                         ids=["balanced", "empty-mid"])
def test_ragged_rounds_f32_operands_on_tpu(on_tpu, sizes):
    """On a TPU at the default precision, ``ragged`` equals the exact oracle
    run on the bfloat16-rounded operands, and still returns float32."""
    lhs, rhs, dout, gs = _grouped(0, 37, 16, 24, 5, sizes)
    y = GB.gmm(lhs, rhs, gs, backend="ragged")
    dw = GB.gmm_dw(lhs, dout, gs, backend="ragged")
    assert y.dtype == dw.dtype == jnp.float32
    lr, rr, dr = _rounded(lhs, rhs, dout)
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        GB.gmm(lr, rr, gs, backend="segment")), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(
        GB.gmm_dw(lr, dr, gs, backend="segment")), rtol=1e-6, atol=1e-6)
    # the rounding is real: the unrounded oracle differs at bfloat16 scale
    assert not np.allclose(np.asarray(y), np.asarray(
        GB.gmm(lhs, rhs, gs, backend="segment")), rtol=1e-5, atol=1e-5)
    assert _gemm_operand_dtypes(
        lambda a, b: GB.gmm(a, b, gs, backend="ragged"),
        lhs, rhs) == [("bfloat16", "bfloat16")]


@pytest.mark.parametrize("precision", ["highest", "float32"])
def test_ragged_keeps_f32_under_higher_precision(on_tpu, monkeypatch,
                                                 precision):
    """A higher ``jax.default_matmul_precision`` in force at trace time
    turns the rounding off: the result is bit-identical to the CPU path."""
    lhs, rhs, dout, gs = _grouped(1, 37, 16, 24, 5)
    with jax.default_matmul_precision(precision):
        y = GB.gmm(lhs, rhs, gs, backend="ragged")
        dw = GB.gmm_dw(lhs, dout, gs, backend="ragged")
        assert _gemm_operand_dtypes(
            lambda a, b: GB.gmm(a, b, gs, backend="ragged"),
            lhs, rhs) == [("float32", "float32")]
    monkeypatch.undo()                                  # the CPU path
    np.testing.assert_array_equal(np.asarray(y), np.asarray(
        GB.gmm(lhs, rhs, gs, backend="ragged")))
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(
        GB.gmm_dw(lhs, dout, gs, backend="ragged")))


def test_ragged_passes_bf16_operands_through(on_tpu):
    """Operands already narrower than float32 are not touched."""
    lhs, rhs, dout, gs = (t.astype(jnp.bfloat16) if t.dtype == jnp.float32
                          else t for t in _grouped(2, 37, 16, 24, 5))
    assert _gemm_operand_dtypes(
        lambda a, b: GB.gmm(a, b, gs, backend="ragged"),
        lhs, rhs) == [("bfloat16", "bfloat16")]
    y = GB.gmm(lhs, rhs, gs, backend="ragged")
    dw = GB.gmm_dw(lhs, dout, gs, backend="ragged")
    assert y.dtype == dw.dtype == jnp.bfloat16
    ref = GB.gmm(lhs.astype(jnp.float32), rhs.astype(jnp.float32), gs,
                 backend="segment")
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_ragged_rounding_keeps_f32_cotangents(on_tpu):
    """Plain autodiff through the rounded GEMM: the gradients are float32,
    each the grouped product of rounded operands (not a rounded result)."""
    lhs, rhs, dout, gs = _grouped(3, 37, 16, 24, 5)

    def f(l, r):
        return (GB.gmm(l, r, gs, backend="ragged") * dout).sum()

    dl, dr = jax.grad(f, argnums=(0, 1))(lhs, rhs)
    assert dl.dtype == dr.dtype == jnp.float32
    lr, rr, dor = _rounded(lhs, rhs, dout)
    np.testing.assert_allclose(np.asarray(dl), np.asarray(GB.gmm(
        dor, jnp.swapaxes(rr, 1, 2), gs, backend="segment")),
        rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dr), np.asarray(GB.gmm_dw(
        lr, dor, gs, backend="segment")), rtol=1e-6, atol=1e-6)


def test_moe_vjp_with_rounded_operands(on_tpu):
    """The MoE layer's staged backward on rounded operands gives every
    gradient, within bfloat16 rounding of the float32 oracle."""
    L, d, h, E, k = 64, 16, 32, 4, 2
    x, w1, w2, w3, gates, disp = _moe_setup(3, L, d, h, E, k)

    def loss(be):
        def f(x, w1, w2, w3, gates):
            y = moe_ffn_blaze(x, gates, disp, w1, w3, w2, backend=be)
            return (y ** 2).sum()
        return f

    args = (x, w1, w2, w3, gates)
    jaxpr = str(jax.make_jaxpr(jax.grad(loss("ragged"), argnums=(0, 1, 2, 3,
                                                                  4)))(*args))
    assert jaxpr.count("optimization_barrier") == 2      # the staged backward
    g = jax.grad(loss("ragged"), argnums=(0, 1, 2, 3, 4))(*args)
    gr = jax.grad(loss("segment"), argnums=(0, 1, 2, 3, 4))(*args)
    for i, (a, b) in enumerate(zip(g, gr)):
        assert a.dtype == jnp.float32
        scale = np.abs(np.asarray(b)).max()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-2 * scale, err_msg=f"argnum {i}")


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_ragged_rounds_where_compiled_for_tpu(platform):
    """The rounding follows the platform the GEMM is lowered for, not the
    process's default backend: bfloat16 copies of both operands for a TPU,
    none for the CPU."""
    lhs, rhs, _, gs = _grouped(4, 37, 16, 24, 5)
    text = jax.jit(lambda a, b: GB.gmm(a, b, gs, backend="ragged")).trace(
        lhs, rhs).lower(lowering_platforms=(platform,)).as_text()
    rounded = re.findall(r"stablehlo.convert %arg\d : \(tensor<[\dx]+xf32>\)"
                         r" -> tensor<[\dx]+xbf16>", text)
    assert len(rounded) == (2 if platform == "tpu" else 0), text
