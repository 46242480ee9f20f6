"""Paper-table benchmarks (Figures 3–6 analogues): activation memory and
training-step time, MoEBlaze vs the MegaBlocks-style materialized baseline,
for conf1..conf7 x {SiLU, SwiGLU}.

Activation memory is measured two ways, both at the paper's FULL tensor
sizes (no execution needed):
  * saved-residual bytes via ``checkpoint.saved_residuals`` (the JAX analogue of
    the paper's PyTorch saved-tensor hooks), parameters excluded;
  * XLA ``temp_size_in_bytes`` of the compiled fwd+bwd step (corroboration).

Step time is wall-clock on this CPU container at a reduced sequence length
(full conf sizes are TFLOP-scale — infeasible on 1 CPU core); it is a
*directional* proxy, the TPU performance story lives in §Roofline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.bench.timing import median_time_us
from repro.core.checkpoint import saved_residual_nbytes
from repro.configs.paper_tables import PAPER_TABLE1
from repro.core.baseline import moe_ffn_megablocks
from repro.core.moe_layer import moe_ffn_blaze
from repro.core.routing import build_dispatch, build_dispatch_sort, top_k_gating

IMPLS = ("blaze", "blaze_min", "blaze_x", "megablocks")

#: custom-VJP residual mode per blaze impl (see core/moe_layer.py):
#: paper-faithful, recompute-Y_swi, and the deepest recompute-A/B point a
#: ``moe:recompute=ffn_a,ffn_b`` checkpoint plan selects.
_RESIDUALS = {"blaze": "ab_yswi", "blaze_min": "ab", "blaze_x": "x"}


def _layer_fn(impl: str, act: str, E: int, k: int):
    def f(x, w1, w2, w3, wg):
        g = top_k_gating(x, wg, k)
        disp = build_dispatch(g.topk_experts, E)
        gates = g.topk_weights.astype(x.dtype)
        w2_ = w2 if act == "swiglu" else None
        if impl == "megablocks":
            y = moe_ffn_megablocks(x, gates, disp, w1, w3, w2_,
                                   activation=act)
        else:
            y = moe_ffn_blaze(x, gates, disp, w1, w3, w2_, activation=act,
                              residuals=_RESIDUALS[impl])
        return (y.astype(jnp.float32) ** 2).sum()
    return f


def _args(conf, *, seq_scale: float = 1.0, dtype=jnp.float32,
          abstract: bool = True):
    d, E, k, B, S = conf
    h = 4 * d
    L = max(int(B * S * seq_scale), 64)
    sds = jax.ShapeDtypeStruct
    shapes = [sds((L, d), dtype), sds((E, d, h), dtype),
              sds((E, d, h), dtype), sds((E, h, d), dtype),
              sds((d, E), dtype)]
    if abstract:
        return shapes
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, len(shapes))
    return [jax.random.normal(kk, s.shape, s.dtype) * 0.05
            for kk, s in zip(ks, shapes)]


def residual_bytes(conf, impl: str, act: str) -> int:
    """Activation bytes saved for backward (params excluded), full size."""
    d, E, k, B, S = conf
    f = _layer_fn(impl, act, E, k)
    return saved_residual_nbytes(f, *_args(conf))


def temp_bytes(conf, impl: str, act: str) -> int:
    """XLA temp buffer bytes for the compiled fwd+bwd at full size."""
    d, E, k, B, S = conf
    f = _layer_fn(impl, act, E, k)
    grad_f = jax.grad(f, argnums=(0, 1, 2, 3, 4))
    compiled = jax.jit(grad_f).lower(*_args(conf)).compile()
    return compiled.memory_analysis().temp_size_in_bytes


def step_time_us(conf, impl: str, act: str, *, seq_scale: float,
                 iters: int = 3) -> float:
    d, E, k, B, S = conf
    f = _layer_fn(impl, act, E, k)
    grad_f = jax.jit(jax.grad(f, argnums=(0, 1, 2, 3, 4)))
    args = _args(conf, seq_scale=seq_scale, abstract=False)
    return median_time_us(grad_f, *args, warmup=1, iters=iters)


def dispatch_build_us(conf, method: str, iters: int = 10) -> float:
    """Dispatch-structure construction time at FULL L·k (paper §6.4 factor 2:
    the dispatch pipeline cost)."""
    d, E, k, B, S = conf
    L = B * S
    key = jax.random.PRNGKey(0)
    scores = jax.random.normal(key, (L, E))
    _, topk = jax.lax.top_k(scores, k)
    topk = topk.astype(jnp.int32)
    builders = {"sortfree": build_dispatch, "sort": build_dispatch_sort}
    if method == "pallas":
        from repro.kernels.dispatch import build_dispatch_pallas
        fn = jax.jit(lambda t: build_dispatch_pallas(t, E), static_argnums=())
    else:
        fn = jax.jit(lambda t: builders[method](t, E))
    return median_time_us(fn, topk, warmup=1, iters=iters)


def run(print_fn=print, *, quick: bool = False):
    """Emit CSV rows: name,us_per_call,derived."""
    rows = []
    confs = list(PAPER_TABLE1.items())
    if quick:
        confs = confs[:2]
    for name, conf in confs:
        for act in ("silu", "swiglu"):
            mems = {}
            for impl in IMPLS:
                mems[impl] = residual_bytes(conf, impl, act)
                rows.append((f"mem_{name}_{act}_{impl}", 0.0,
                             f"residual_MB={mems[impl]/1e6:.1f}"))
            ratio = mems["megablocks"] / max(mems["blaze"], 1)
            ratio_min = mems["megablocks"] / max(mems["blaze_min"], 1)
            rows.append((f"memratio_{name}_{act}", 0.0,
                         f"megablocks/blaze={ratio:.2f}x "
                         f"megablocks/blaze_min={ratio_min:.2f}x"))
            print_fn(f"{name} {act}: blaze={mems['blaze']/1e6:.0f}MB "
                     f"megablocks={mems['megablocks']/1e6:.0f}MB "
                     f"ratio={ratio:.2f}x (min-variant {ratio_min:.2f}x)")
        # step time at reduced scale: fixed 128-row slabs — the CPU backend
        # decomposes ragged_dot dense-per-group, so full-L steps are
        # TFLOP-scale on one core; this axis is directional only (see
        # EXPERIMENTS.md §Paper-validation).
        scale = 128 / (conf[3] * conf[4])
        for act in ("silu", "swiglu"):
            ts = {impl: step_time_us(conf, impl, act, seq_scale=scale,
                                     iters=1)
                  for impl in ("blaze", "megablocks")}
            sp = ts["megablocks"] / ts["blaze"]
            rows.append((f"steptime_{name}_{act}_blaze", ts["blaze"],
                         f"speedup_vs_megablocks={sp:.2f}x@scale={scale:.4f}"))
            print_fn(f"{name} {act}: step blaze={ts['blaze']:.0f}us "
                     f"mega={ts['megablocks']:.0f}us speedup={sp:.2f}x")
        # dispatch build at full L·k
        for method in ("sortfree", "sort"):
            us = dispatch_build_us(conf, method, iters=3 if not quick else 2)
            rows.append((f"dispatch_{name}_{method}", us, f"L={conf[3]*conf[4]}"))
            print_fn(f"{name}: dispatch[{method}] {us:.0f}us")
    return rows
