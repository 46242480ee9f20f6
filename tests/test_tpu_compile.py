"""Compile-only checks against a described TPU v5e, no chip attached.

The TPU compiler is installed with JAX and compiles for a chip that is
described and not attached, so these tests catch what interpret mode cannot:
Mosaic refusing a kernel (misaligned slices, more VMEM than the core has) and
a train step that does not fit the chip's HBM.  Shapes are paper conf6 at its
Table-1 width, batch and sequence (16 x 1024 tokens, top-4 of 16 experts:
65,536 routed slots; d = 1024, h = 4096).  Nothing runs, so nothing here says
anything about results or time.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import hardware, kernels

L, D, H, E, K = 16 * 1024, 1024, 4096, 16, 4
S = L * K
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Lower the kernels for the chip, not through the interpreter: the
    process's default backend is the CPU, which is what the kernels ask.
    Traces made either way are dropped before and after."""
    jax.clear_caches()
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    yield
    jax.clear_caches()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _routing(one_chip):
    return (_sds((S,), jnp.int32, one_chip),
            _sds((E + 1,), jnp.int32, one_chip))


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_gather_gmm_compiles_at_conf6(dtype, one_chip, compiled_kernels):
    from repro.kernels.gather_gmm import gather_gmm
    idx, off = _routing(one_chip)
    x = _sds((L, D), dtype, one_chip)
    w = _sds((E, D, H), dtype, one_chip)
    _compile(lambda x, i, o, w1, w2: gather_gmm(x, i, o, w1, w2,
                                                save_ab=True),
             x, idx, off, w, w)
    # The grouped-GEMM backend's form: rows already in expert order.
    xs = _sds((S, H), dtype, one_chip)
    w3 = _sds((E, H, D), dtype, one_chip)
    _compile(lambda x, o, w: gather_gmm(x, None, o, w, epilogue=False),
             xs, off, w3)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_gmm_dw_compiles_at_conf6(dtype, one_chip, compiled_kernels):
    from repro.kernels.gather_gmm import gmm_dw_pallas
    _, off = _routing(one_chip)
    _compile(gmm_dw_pallas, _sds((S, D), dtype, one_chip),
             _sds((S, H), dtype, one_chip), off)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_fused_moe_compiles_at_conf6(dtype, one_chip, compiled_kernels):
    from repro.kernels.gather_gmm import fused_moe_bwd, fused_moe_fwd
    from repro.roofline import select_moe_tiles
    bl, bh = select_moe_tiles(S, D, H, dtype_bytes=jnp.dtype(dtype).itemsize,
                              num_experts=E)
    idx, off = _routing(one_chip)
    x = _sds((L, D), dtype, one_chip)
    g = _sds((S,), jnp.float32, one_chip)
    w12 = _sds((E, D, H), dtype, one_chip)
    w3 = _sds((E, H, D), dtype, one_chip)
    _compile(lambda *a: fused_moe_fwd(*a, bl=bl, bh=bh),
             x, g, idx, off, w12, w12, w3)
    _compile(lambda *a: fused_moe_bwd(*a, bl=bl, bh=bh),
             x, x, g, idx, off, w12, w12, w3)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_dispatch_combine_gather_rows_compile(dtype, one_chip,
                                              compiled_kernels):
    from repro.kernels.combine import combine
    from repro.kernels.dispatch import build_dispatch_pallas
    from repro.kernels.gather_gmm import gather_rows_pallas
    topk = _sds((L, K), jnp.int32, one_chip)
    _compile(lambda t: build_dispatch_pallas(t, E), topk)
    _compile(combine, _sds((S, D), dtype, one_chip), topk,
             _sds((L, K), dtype, one_chip))
    _compile(gather_rows_pallas, _sds((L, D), dtype, one_chip),
             _sds((S,), jnp.int32, one_chip))


def test_kernel_over_vmem_raises_before_lowering(compiled_kernels):
    """Tiles whose working set exceeds the chip's VMEM raise while the
    kernel is traced, naming the need and the limit — no lowering, no
    interpreter fallback.  Shapes only: nothing is described or compiled."""
    from repro.kernels.gather_gmm import gmm_dw_pallas
    args = (jax.ShapeDtypeStruct((S, D), jnp.float32),
            jax.ShapeDtypeStruct((S, H), jnp.float32),
            jax.ShapeDtypeStruct((E + 1,), jnp.int32))
    with pytest.raises(ValueError,
                       match=r"gmm_dw_pallas needs .* MiB of VMEM .* 128 MiB"):
        jax.eval_shape(lambda *a: gmm_dw_pallas(*a, bl=4096, bd=D, bh=H),
                       *args)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_attention_kernels_compile(dtype, one_chip, compiled_kernels):
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.paged_attention import paged_attention_pallas
    q = _sds((16, 1024, 8, 128), dtype, one_chip)
    _compile(flash_attention_pallas, q, q, q)
    B, pages, ps, pps = 8, 257, 16, 32
    kv = _sds((pages, ps, 8, 128), dtype, one_chip)
    _compile(lambda q, k, v, pt, pos: paged_attention_pallas(
        q, k, v, None, None, pt, pos),
        _sds((B, 1, 8, 128), dtype, one_chip), kv, kv,
        _sds((B, pps), jnp.int32, one_chip), _sds((B,), jnp.int32, one_chip))


def _conf6_train_step(one_chip):
    """One whole AdamW train step of paper conf6 (float32, auto backend) at
    16 x 1024 tokens, compiled for one chip; with its footprint, argument +
    output + temp - alias bytes (the benchmark's ``step_hbm_gib``)."""
    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.models import transformer as T
    from repro.train.loop import make_train_step
    from repro.train.optimizer import init_adamw

    cfg = get_config("paper_conf6")
    tcfg = TrainConfig(batch_size=16, seq_len=1024)
    step = make_train_step(cfg, tcfg)
    assert step.resolved_backend.name == "ragged"

    def on_chip(tree):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(lambda k: T.init_params(k, cfg),
                                    jax.random.PRNGKey(0)))
    opt = on_chip(jax.eval_shape(init_adamw, params))
    tok = _sds((16, 1024), jnp.int32, one_chip)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt, {"tokens": tok, "labels": tok}).compile()
    mem = compiled.memory_analysis()
    return compiled, (mem.argument_size_in_bytes + mem.output_size_in_bytes
                      + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


#: The parent program's compiled conf6 step, float32 GEMM operands: the most
#: the step may take (``step_hbm_gib`` 9.871 GiB).
CONF6_STEP_BYTES = 10_598_891_008
#: The parent program's compiled MoE layer (GELU, gradient of every input) at
#: conf6 width, float32 GEMM operands: the most that layer may take.
MLP_LAYER_BYTES = 4_969_625_600


@pytest.fixture(scope="module")
def conf6_step(one_chip):
    """The conf6 train step compiled for one chip, and its footprint."""
    jax.clear_caches()
    yield _conf6_train_step(one_chip)
    jax.clear_caches()


def test_conf6_train_step_fits_one_chip(conf6_step):
    """The program the chip smoke run executes: its compiled footprint must
    fit the chip's HBM."""
    peak = conf6_step[1]
    assert peak <= hardware.peaks().hbm_bytes, peak


def _ragged_dot_operands(hlo: str) -> list[list[str]]:
    """The operand types of each of XLA's TPU ragged-dot kernel calls."""
    calls = []
    for line in hlo.splitlines():      # the GEMMs, not their s32 metadata
        if re.match(r"\s*%ragged-dot-\S* = f32\[", line) and \
                'custom_call_target="tpu_custom_call"' in line:
            ops = re.search(
                r"operand_layout_constraints=\{(.*?)\}, frontend_attributes",
                line)
            calls.append([o.split("[")[0] for o in ops.group(1).split(", ")])
    return calls


def test_conf6_train_step_feeds_gmm_bf16(conf6_step):
    """Compiled for the chip, the ``ragged`` backend hands every grouped GEMM
    of the conf6 step bfloat16 operands (the rounding the kernel did on
    float32 tiles anyway), and the step takes no more HBM than it did with
    float32 operands."""
    compiled, peak = conf6_step
    calls = _ragged_dot_operands(compiled.as_text())
    assert len(calls) == 11, calls        # 9 GEMMs a step + 2 recomputed
    for ops in calls:
        assert ops[-2:] == ["bf16", "bf16"], ops
    assert peak <= CONF6_STEP_BYTES, peak


def test_mlp_moe_layer_feeds_gmm_bf16(one_chip):
    """The MoE layer with a one-matrix activation (GELU) at conf6 width:
    its six grouped GEMMs take bfloat16 operands, and its gradient takes no
    more HBM than it did with float32 operands."""
    from repro.core.moe_layer import moe_ffn_blaze
    from repro.core.routing import build_dispatch

    def loss(x, w1, w3, gates, topk):
        y = moe_ffn_blaze(x, gates, build_dispatch(topk, E), w1, w3,
                          activation="gelu", backend="ragged")
        return (y ** 2).sum()

    f32 = jnp.float32
    jax.clear_caches()
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        _sds((L, D), f32, one_chip), _sds((E, D, H), f32, one_chip),
        _sds((E, H, D), f32, one_chip), _sds((L, K), f32, one_chip),
        _sds((L, K), jnp.int32, one_chip)).compile()
    jax.clear_caches()
    calls = _ragged_dot_operands(compiled.as_text())
    assert len(calls) == 6, calls
    for ops in calls:
        assert ops[-2:] == ["bf16", "bf16"], ops
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert peak <= MLP_LAYER_BYTES, peak
