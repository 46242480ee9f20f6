"""Distribution tests on an 8-host-device mesh (set in conftest; CI pins the
same count via XLA_FLAGS): sharded train steps match single-device numerics,
specs respect divisibility, and the MoE distribution modes ({ep, ep_a2a, tp}
x grouped-GEMM backends x dtypes) match the unsharded oracle forward and
backward through the one Dispatch-driven path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import sharding as shd
from repro.configs import get_config
from repro.configs.base import InputShape, TrainConfig
from repro.core import gmm_backend as GB
from repro.launch import specs as S
from repro.launch.mesh import make_debug_mesh, make_node_mesh
from repro.models import transformer as T
from repro.models.moe_block import (init_moe_params, moe_sublayer,
                                    resolve_moe_parallel)
from repro.train.loop import make_train_step, train
from repro.train.optimizer import init_adamw

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 host devices")

MOE_CFG = get_config("mixtral_8x7b").reduced().replace(
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    num_experts=4, top_k=2, moe_d_ff=64, vocab_size=128, sliding_window=16,
    attn_chunk=16)


def test_param_specs_divisibility():
    mesh = make_debug_mesh(2, 4)
    cfg = get_config("hymba_1_5b")          # 25 heads, awkward dims
    pspecs = shd.param_specs(S.params_shapes(cfg), mesh)
    pshapes = S.params_shapes(cfg)
    for spec, shape in zip(jax.tree.leaves(
            pspecs, is_leaf=lambda x: isinstance(x, P)),
            jax.tree.leaves(pshapes)):
        for dim, ax in zip(shape.shape, spec):
            if ax is None:
                continue
            n = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                n *= mesh.shape[a]
            assert dim % n == 0, (shape.shape, spec)


def test_moe_shard_map_matches_single_device():
    mesh = make_debug_mesh(2, 4)
    cfg = MOE_CFG
    key = jax.random.PRNGKey(0)
    from repro.models.moe_block import init_moe_params
    p = init_moe_params(key, cfg, cfg.d_model)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
    y_ref, aux_ref = moe_sublayer(x, p, cfg, mesh=None)
    with mesh:
        y_sh, aux_sh = jax.jit(
            lambda x, p: moe_sublayer(x, p, cfg, mesh=mesh,
                                      dp_axes=("data",)))(x, p)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_sh),
                               atol=2e-5)
    # the load-balance aux is computed per data shard and averaged — a local
    # estimator (standard practice), not bit-equal to the global statistic
    np.testing.assert_allclose(float(aux_ref), float(aux_sh), rtol=0.05)


# -- the {mode x backend x dtype} parity matrix ------------------------------

# bf16 rounds to 8 mantissa bits at every gmm boundary and the modes order
# their fp32 reductions differently (psum of per-device partials).
_TOL = {"float32": dict(rtol=1e-4, atol=1e-5),
        "bfloat16": dict(rtol=5e-2, atol=5e-2)}


def _matrix_case(dtype, backend, mode):
    cfg = MOE_CFG.replace(dtype=dtype, param_dtype=dtype,
                          gmm_backend=backend, moe_parallel=mode,
                          moe_a2a_capacity=8.0)  # capacity >= worst case
    p = init_moe_params(jax.random.PRNGKey(3), cfg, cfg.d_model)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 16, cfg.d_model),
                          jnp.float32).astype(jnp.dtype(dtype))
    return cfg, p, x


def _y_loss(cfg, mesh):
    # Grads flow through y only: the load-balance aux under a data-sharded
    # mesh is a per-shard estimator (see the aux comments below), which
    # would drown the per-mode comparison in estimator noise.
    def f(x, p):
        y, _ = moe_sublayer(x, p, cfg, mesh=mesh, dp_axes=("data",))
        return (y.astype(jnp.float32) ** 2).mean()
    return f


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("backend", GB.backend_names())
@pytest.mark.parametrize("mode", ["ep", "ep_a2a", "tp"])
def test_moe_parallel_parity_matrix(mode, backend, dtype):
    """Every distribution mode, under every registered grouped-GEMM backend,
    at f32 and bf16, matches the unsharded oracle — forward AND gradients —
    through the one Dispatch-driven path."""
    mesh = make_debug_mesh(2, 4)
    cfg, p, x = _matrix_case(dtype, backend, mode)
    tol = _TOL[dtype]

    y_ref, _ = moe_sublayer(x, p, cfg.replace(moe_parallel="auto"), mesh=None)
    with mesh:
        y, _ = jax.jit(lambda x, p: moe_sublayer(
            x, p, cfg, mesh=mesh, dp_axes=("data",)))(x, p)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **tol,
                               err_msg=f"fwd {mode}/{backend}/{dtype}")

    g_ref = jax.grad(_y_loss(cfg.replace(moe_parallel="auto"), None),
                     argnums=(0, 1))(x, p)
    with mesh:
        g = jax.jit(jax.grad(_y_loss(cfg, mesh), argnums=(0, 1)))(x, p)
    for i, (a, b) in enumerate(zip(jax.tree.leaves(g), jax.tree.leaves(g_ref))):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), **tol,
            err_msg=f"grad leaf {i} ({mode}/{backend}/{dtype})")


def test_ep_a2a_overflow_accounted():
    """Tight ep_a2a capacity drops slots and *reports* it: the overflow stat
    is positive, while ample capacity reports exactly 0."""
    mesh = make_debug_mesh(2, 4)
    cfg, p, x = _matrix_case("float32", "segment", "ep_a2a")
    with mesh:
        _, _, ample = jax.jit(lambda x, p: moe_sublayer(
            x, p, cfg, mesh=mesh, dp_axes=("data",), with_stats=True))(x, p)
        tight_cfg = cfg.replace(moe_a2a_capacity=0.25)
        _, _, tight = jax.jit(lambda x, p: moe_sublayer(
            x, p, tight_cfg, mesh=mesh, dp_axes=("data",),
            with_stats=True))(x, p)
    assert float(ample["a2a_overflow"]) == 0.0
    assert float(tight["a2a_overflow"]) > 0.0


def test_forced_ep_invalid_expert_count_raises():
    """Forced expert parallelism with E % n_model != 0 must raise (the old
    path computed E_loc = E // n_model and silently dropped experts)."""
    mesh = make_debug_mesh(2, 4)
    bad = MOE_CFG.replace(num_experts=6, moe_parallel="ep")
    with pytest.raises(ValueError, match="divisible"):
        resolve_moe_parallel(bad, mesh)
    with pytest.raises(ValueError, match="divisible"):
        make_train_step(bad, TrainConfig(), mesh=mesh)
    from repro.serve.engine import ServeEngine
    with pytest.raises(ValueError, match="divisible"):
        ServeEngine(bad.replace(moe_parallel="ep_a2a"), params={}, mesh=mesh)
    # auto never raises: it falls back to TP for awkward expert counts
    assert resolve_moe_parallel(bad.replace(moe_parallel="auto"),
                                mesh) == "tp"


def test_serve_engine_degrades_ep_a2a_to_ep():
    """Valid ep_a2a configs serve as plain EP: single-token decode slabs
    rarely divide the model axis, and EP is the same math on the same
    expert-sharded weight layout — the fallback must happen at construction,
    never as a mid-generate trace error."""
    from repro.serve.engine import ServeEngine
    mesh = make_debug_mesh(2, 4)
    eng = ServeEngine(MOE_CFG.replace(moe_parallel="ep_a2a"), params={},
                      mesh=mesh)
    assert eng.cfg.moe_parallel == "ep"


def test_ep_a2a_indivisible_tokens_raises():
    mesh = make_debug_mesh(2, 4)
    cfg, p, _ = _matrix_case("float32", "segment", "ep_a2a")
    x = jnp.zeros((4, 15, cfg.d_model))       # 2*15 tokens/device % 4 != 0
    with pytest.raises(ValueError, match="tokens/device"):
        moe_sublayer(x, p, cfg, mesh=mesh, dp_axes=("data",))


# -- context-scoped backend resolution reaches the distributed path ----------


def test_ep_path_honors_context_scoped_backend(monkeypatch):
    """Regression: the old dense EP body bypassed the gmm_backend resolver —
    ``use_backend`` had no effect under a mesh.  A recording backend pinned
    via the context scope must now carry every grouped GEMM of the EP body."""
    calls = []

    class Spy(GB.SegmentBackend):
        name = "spy"

        @staticmethod
        def gmm(lhs, rhs, group_sizes):
            calls.append("gmm")
            return GB.SegmentBackend.gmm(lhs, rhs, group_sizes)

        @staticmethod
        def gmm_dw(lhs, dout, group_sizes):
            calls.append("gmm_dw")
            return GB.SegmentBackend.gmm_dw(lhs, dout, group_sizes)

    monkeypatch.setitem(GB._REGISTRY, "spy", Spy)
    mesh = make_debug_mesh(2, 4)
    for mode in ("ep", "ep_a2a"):
        cfg, p, x = _matrix_case("float32", "auto", mode)
        calls.clear()
        with mesh, GB.use_backend("spy"):
            y, _ = jax.jit(lambda x, p: moe_sublayer(
                x, p, cfg, mesh=mesh, dp_axes=("data",)))(x, p)
        assert calls, f"{mode} body bypassed the context-scoped backend"
        y_ref, _ = moe_sublayer(x, p, cfg, mesh=None)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=2e-5, err_msg=mode)


def test_step_hook_reports_resolved_backend_under_mesh():
    """``step_hook`` metrics carry the resolved grouped-GEMM backend when the
    step runs expert-parallel under an 8-virtual-device mesh, and a context
    scope retargets it — TrainConfig/use_backend now reach the EP path."""
    mesh = make_debug_mesh(2, 4)
    cfg = MOE_CFG.replace(moe_parallel="ep")
    tcfg = TrainConfig(total_steps=2, batch_size=4, seq_len=16,
                       learning_rate=1e-3, log_every=1)
    seen = []

    def hook(step, metrics):
        seen.append(metrics["gmm_backend"])
        assert "moe_overflow" in metrics

    with mesh, GB.use_backend("segment"):
        train(cfg, tcfg, mesh=mesh, log=lambda *_: None, step_hook=hook)
    assert seen == ["segment", "segment"]


def test_sharded_train_step_matches_single_device():
    mesh = make_debug_mesh(2, 4)
    cfg = MOE_CFG
    tcfg = TrainConfig(num_microbatches=2, learning_rate=1e-3)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    opt = init_adamw(params)
    toks = jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}

    p1, _, m1 = jax.jit(make_train_step(cfg, tcfg, mesh=None))(
        params, opt, batch)

    pspecs = shd.param_specs(params, mesh)
    shardings = shd.to_shardings(
        mesh, (pspecs, shd.opt_specs(pspecs),
               shd.batch_specs(cfg, batch, mesh)))
    with mesh:
        p2, _, m2 = jax.jit(make_train_step(cfg, tcfg, mesh=mesh),
                            in_shardings=shardings)(params, opt, batch)
    # The train loss folds in the load-balance aux, which is computed as a
    # per-data-shard estimator under the mesh (see the shard_map test above)
    # — so the sharded loss is not bit-equal, only close.
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=5e-4)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-4)


def test_decode_cache_specs_long_context():
    """long_500k-style cache: batch=1 unshardable -> sequence axis sharded."""
    mesh = make_debug_mesh(2, 4)
    cfg = MOE_CFG.replace(sliding_window=0)
    cache_shapes = jax.eval_shape(lambda: T.init_cache(cfg, 1, 1024))
    cspecs = shd.cache_specs(cfg, cache_shapes, mesh)
    kv_spec = jax.tree.leaves(
        cspecs, is_leaf=lambda x: isinstance(x, P))[0]
    flat = [ax for ax in kv_spec if ax]
    assert flat, "expected some sharded axis on the KV cache"


# -- hierarchical two-hop exchange on node meshes ----------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("backend", GB.backend_names())
def test_moe_hier_parity_matrix(backend, dtype):
    """The two-hop ep_a2a_hier path on a ('data','node','model') mesh matches
    the unsharded oracle forward AND backward, under every registered
    grouped-GEMM backend at f32 and bf16."""
    mesh = make_node_mesh(2, 2, 2)
    cfg, p, x = _matrix_case(dtype, backend, "ep_a2a_hier")
    tol = _TOL[dtype]

    y_ref, _ = moe_sublayer(x, p, cfg.replace(moe_parallel="auto"), mesh=None)
    with mesh:
        y, _ = jax.jit(lambda x, p: moe_sublayer(
            x, p, cfg, mesh=mesh, dp_axes=("data",)))(x, p)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **tol,
                               err_msg=f"fwd hier/{backend}/{dtype}")

    g_ref = jax.grad(_y_loss(cfg.replace(moe_parallel="auto"), None),
                     argnums=(0, 1))(x, p)
    with mesh:
        g = jax.jit(jax.grad(_y_loss(cfg, mesh), argnums=(0, 1)))(x, p)
    for i, (a, b) in enumerate(zip(jax.tree.leaves(g), jax.tree.leaves(g_ref))):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), **tol,
            err_msg=f"grad leaf {i} (hier/{backend}/{dtype})")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("backend", GB.backend_names())
def test_moe_chunked_a2a_parity(backend, dtype):
    """Double-buffered chunked ep_a2a (moe_a2a_chunks=2, chunk i's exchange
    overlapping chunk i-1's grouped GEMM) is numerically the same layer."""
    mesh = make_debug_mesh(2, 4)
    cfg, p, x = _matrix_case(dtype, backend, "ep_a2a")
    cfg = cfg.replace(moe_a2a_chunks=2)
    tol = _TOL[dtype]

    y_ref, _ = moe_sublayer(x, p, cfg.replace(moe_parallel="auto"), mesh=None)
    with mesh:
        y, _ = jax.jit(lambda x, p: moe_sublayer(
            x, p, cfg, mesh=mesh, dp_axes=("data",)))(x, p)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **tol,
                               err_msg=f"fwd chunked/{backend}/{dtype}")

    g_ref = jax.grad(_y_loss(cfg.replace(moe_parallel="auto"), None),
                     argnums=(0, 1))(x, p)
    with mesh:
        g = jax.jit(jax.grad(_y_loss(cfg, mesh), argnums=(0, 1)))(x, p)
    for i, (a, b) in enumerate(zip(jax.tree.leaves(g), jax.tree.leaves(g_ref))):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), **tol,
            err_msg=f"grad leaf {i} (chunked/{backend}/{dtype})")


def test_hier_overflow_accounted():
    """Two-hop capacity drops (either hop) surface in the a2a_overflow stat;
    ample capacity reports exactly 0."""
    mesh = make_node_mesh(2, 2, 2)
    cfg, p, x = _matrix_case("float32", "segment", "ep_a2a_hier")
    with mesh:
        _, _, ample = jax.jit(lambda x, p: moe_sublayer(
            x, p, cfg, mesh=mesh, dp_axes=("data",), with_stats=True))(x, p)
        tight_cfg = cfg.replace(moe_a2a_capacity=0.25)
        _, _, tight = jax.jit(lambda x, p: moe_sublayer(
            x, p, tight_cfg, mesh=mesh, dp_axes=("data",),
            with_stats=True))(x, p)
    assert float(ample["a2a_overflow"]) == 0.0
    assert float(tight["a2a_overflow"]) > 0.0


def test_hier_indivisible_tokens_raises():
    mesh = make_node_mesh(2, 2, 2)
    cfg, p, _ = _matrix_case("float32", "segment", "ep_a2a_hier")
    x = jnp.zeros((4, 15, cfg.d_model))      # 30 tokens/device % 4 != 0
    with pytest.raises(ValueError, match="tokens/device"):
        moe_sublayer(x, p, cfg, mesh=mesh, dp_axes=("data",))


def test_node_mesh_mode_validation_raises_at_resolve():
    """Bad mode x mesh factorizations fail at resolve_moe_parallel, never
    mid-trace: flat ep_a2a on a node mesh, hier on a flat mesh, expert count
    not divisible by the combined (node x model) axes."""
    node = make_node_mesh(2, 2, 2)
    flat = make_debug_mesh(2, 4)
    with pytest.raises(ValueError, match="'node' axis"):
        resolve_moe_parallel(MOE_CFG.replace(moe_parallel="ep_a2a"), node)
    with pytest.raises(ValueError, match="'node' axis"):
        resolve_moe_parallel(
            MOE_CFG.replace(moe_parallel="ep_a2a_hier"), flat)
    with pytest.raises(ValueError, match="divisible"):
        resolve_moe_parallel(
            MOE_CFG.replace(num_experts=6, moe_parallel="ep_a2a_hier"), node)


def test_param_specs_node_axis_expert_dim():
    """A mesh with a 'node' tier factors the expert-bank dim over
    ('node', 'model') — matching the gdev = node_i * n_model + lane_i
    flattening in the hier body."""
    mesh = make_node_mesh(2, 2, 2)
    pspecs = shd.param_specs(S.params_shapes(MOE_CFG), mesh)
    flat, _ = jax.tree_util.tree_flatten_with_path(
        pspecs, is_leaf=lambda s: isinstance(s, P))
    moe_specs = [s for path, s in flat
                 if any(str(getattr(k, "key", "")) in ("w1", "w2", "w3")
                        for k in path)]
    assert moe_specs, "no MoE expert leaves found in param specs"
    for s in moe_specs:
        assert ("node", "model") in tuple(s), s


def test_auto_resolution_follows_cost_model():
    """`auto` is an optimizer, not an alias: on the same 8-device mesh it
    picks ep_a2a where the collective cost model predicts the exchange wins
    (h ~ 3d, tight capacity) and ep where it predicts it loses (h ~ d,
    capacity 2 doubles the wire bytes)."""
    mesh = make_debug_mesh(2, 4)
    wins = MOE_CFG.replace(num_experts=8, moe_d_ff=198,
                           moe_a2a_capacity=1.0)
    assert resolve_moe_parallel(wins, mesh, 1024) == "ep_a2a"
    loses = MOE_CFG.replace(num_experts=8, moe_d_ff=66,
                            moe_a2a_capacity=2.0)
    assert resolve_moe_parallel(loses, mesh, 1024) == "ep"
    # provenance mirrors ResolvedBackend: auto decisions carry the table
    from repro.models.moe_block import resolve_moe_parallel_ex
    dec = resolve_moe_parallel_ex(wins, mesh, 1024)
    assert dec.source == "auto" and len(dec.table) >= 3


def test_auto_resolves_hier_on_node_mesh():
    """On a node mesh where tp is infeasible (odd h) and the model predicts
    the two-hop exchange beats replicated EP, auto lands on ep_a2a_hier —
    and the resulting layer runs."""
    mesh = make_node_mesh(2, 2, 2)
    cfg = MOE_CFG.replace(num_experts=8, moe_d_ff=389, moe_a2a_capacity=1.0)
    n_tok = 4 * 16 // 2
    assert resolve_moe_parallel(cfg, mesh, n_tok * 2) == "ep_a2a_hier"
    p = init_moe_params(jax.random.PRNGKey(3), cfg, cfg.d_model)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 16, cfg.d_model))
    y_ref, _ = moe_sublayer(x, p, cfg.replace(moe_a2a_capacity=8.0),
                            mesh=None)
    with mesh:
        y, _ = jax.jit(lambda x, p: moe_sublayer(
            x, p, cfg.replace(moe_a2a_capacity=8.0), mesh=mesh,
            dp_axes=("data",)))(x, p)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)


def test_dryrun_small_mesh_end_to_end():
    """The dryrun builder lowers + compiles on a small mesh (fast proxy for
    the 512-device run)."""
    from repro.launch.dryrun import build_lowerable
    mesh = make_debug_mesh(2, 4)
    shape = InputShape("tiny_train", 64, 8, "train")
    built, skip, cfg = build_lowerable(
        "mixtral_8x7b", "tiny_train", mesh,
        dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
             head_dim=16, num_experts=4, top_k=2, moe_d_ff=64,
             vocab_size=128, sliding_window=16, attn_chunk=16),
        shape=shape, microbatches=2)
    assert skip is None
    fn, args, shardings = built
    with mesh:
        compiled = jax.jit(fn, in_shardings=shardings).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
