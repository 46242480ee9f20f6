"""Chip smoke run: the MoEBlaze main path, once, on a TPU.

    python chip_smoke.py             # one chip: phases a-e
    python chip_smoke.py --chips 4   # four chips: the expert-parallel phase

The model is paper conf6 at its Table-1 width, batch and sequence (d=1024,
16 experts, top-4, h=4096, 16 x 1024 tokens; float32, AdamW), with random
weights and data made from ``--seed``.

One chip:
  a  device report (platform, kind, count, JAX version);
  b  five train steps on the auto grouped-GEMM backend: compile time, steady
     step time, tokens/s, peak HBM; every loss finite;
  c  the same first step, from the same params and batch, on the
     ``pallas_fused`` and ``pallas`` backends: the Pallas kernels are
     compiled into the step, and loss and grad norm agree with (b);
  d  grouped-GEMM kernel parity, ``pallas`` against ``ragged``, on a routing
     with empty experts and a group total below the row count;
  e  greedy serving through ``ServeEngine`` with the dense and the Pallas
     paged-attention kernel: identical tokens.

Four chips (``--chips 4``): conf6 train steps on a ('data', 'model') =
(1, 4) mesh with ``moe_parallel`` 'ep' and 'ep_a2a', against the unsharded
step on the first chip; 'ep_a2a' must drop no routed slot.

Each phase prints what it measured.  The last line of standard output is one
JSON object, ``{"ok": true, "device": {...}}``.  Any failed check raises and
the exit code is non-zero; so is it when JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.cache import use_compile_cache  # noqa: E402

#: float32 matmuls on a TPU run at JAX's DEFAULT precision, which rounds
#: operands to bfloat16 (8 significant bits, unit roundoff 2**-8).  Values
#: reached through different kernels are held to a few such units.
F32_TPU_RTOL = 4 * 2.0 ** -8
STEPS = 5


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def device_report() -> dict:
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found "
                         f"{d0.platform!r} ({d0.device_kind})")
    rep = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(devices)}
    log(f"[a] platform={rep['platform']} kind={rep['kind']} "
        f"count={rep['count']} jax={jax.__version__}")
    return rep


def conf6(seed: int):
    """paper conf6 and its train config, plus ``STEPS`` host batches."""
    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.configs.paper_tables import PAPER_TABLE1
    from repro.data.pipeline import make_batch_iterator
    cfg = get_config("paper_conf6")
    *_, batch, seq = PAPER_TABLE1["paper_conf6"]
    tcfg = TrainConfig(batch_size=batch, seq_len=seq, total_steps=STEPS,
                       seed=seed)
    it = make_batch_iterator(cfg.vocab_size, seq, batch, seed)
    return cfg, tcfg, [next(it) for _ in range(STEPS)]


def fresh_state(cfg, tcfg):
    """Params and AdamW state made from the seed (same values every call)."""
    import jax
    from repro.models import transformer as T
    from repro.train.optimizer import init_adamw
    params = T.init_params(jax.random.PRNGKey(tcfg.seed), cfg)
    return params, init_adamw(params)


def compile_step(cfg, tcfg, params, opt, batch, *, backend=None, mesh=None):
    """``make_train_step`` jitted and compiled ahead of the first call;
    returns (executable, compile seconds, step fn)."""
    import jax
    from repro.train.loop import make_train_step
    step = make_train_step(cfg, tcfg, backend=backend, mesh=mesh)
    t0 = time.perf_counter()
    exe = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt, batch).compile()
    return exe, time.perf_counter() - t0, step


def pallas_kernels_in(exe) -> int:
    """Pallas kernels compiled into an executable (``ragged_dot`` is a TPU
    custom call of XLA's own, so the Pallas ones are told apart by name)."""
    return sum(1 for line in exe.as_text().splitlines()
               if "tpu_custom_call" in line and "pallas_call" in line)


def phase_train(cfg, tcfg, batches):
    """(b): five steps on the auto backend.  Returns step 0's metrics."""
    import jax
    import jax.numpy as jnp
    params, opt = fresh_state(cfg, tcfg)
    dev = [{k: jnp.asarray(v) for k, v in b.items()} for b in batches]
    exe, compile_s, step = compile_step(cfg, tcfg, params, opt, dev[0])
    times, losses, first = [], [], None
    for b in dev:
        t0 = time.perf_counter()
        params, opt, m = exe(params, opt, b)
        jax.block_until_ready((params, opt, m))
        times.append(time.perf_counter() - t0)
        losses.append(float(m["loss"]))
        if first is None:
            first = {"loss": losses[0], "grad_norm": float(m["grad_norm"])}
    steady = statistics.median(times[1:])
    tokens = tcfg.batch_size * tcfg.seq_len
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    mem = exe.memory_analysis()
    log(f"[b] conf6 train backend={step.resolved_backend.name} "
        f"tokens/step={tokens} compile_s={compile_s:.3f} "
        f"compiled_argument_bytes={mem.argument_size_in_bytes} "
        f"compiled_temp_bytes={mem.temp_size_in_bytes}")
    log(f"[b] step_s={[round(t, 6) for t in times]} steady_step_s={steady:.6f}"
        f" tokens_per_s={tokens / steady:.1f}")
    log(f"[b] losses={losses} peak_bytes_in_use={peak} "
        f"({peak / 2**30:.3f} GiB)")
    check(all(math.isfinite(v) for v in losses), "finite losses")
    check(step.resolved_backend.name == "ragged", "auto resolves to ragged")
    del params, opt
    return first


def phase_pallas_steps(cfg, tcfg, batches, ref):
    """(c): the first step of (b) on each Pallas backend."""
    import jax
    import jax.numpy as jnp
    batch = {k: jnp.asarray(v) for k, v in batches[0].items()}
    for backend in ("pallas_fused", "pallas"):
        params, opt = fresh_state(cfg, tcfg)
        exe, compile_s, _ = compile_step(cfg, tcfg, params, opt, batch,
                                         backend=backend)
        n_kernels = pallas_kernels_in(exe)
        t0 = time.perf_counter()
        out = jax.block_until_ready(exe(params, opt, batch))
        step_s = time.perf_counter() - t0
        m = out[2]
        del params, opt, out, exe
        loss, gnorm = float(m["loss"]), float(m["grad_norm"])
        dl, dg = rel(loss, ref["loss"]), rel(gnorm, ref["grad_norm"])
        log(f"[c] backend={backend} pallas_tpu_custom_calls={n_kernels} "
            f"compile_s={compile_s:.3f} step_s={step_s:.6f} loss={loss} "
            f"grad_norm={gnorm} rel_dloss={dl:.3e} rel_dgnorm={dg:.3e} "
            f"rtol={F32_TPU_RTOL:.3e}")
        check(n_kernels != 0, f"{backend}: Pallas kernels in the step")
        check(dl <= F32_TPU_RTOL and dg <= F32_TPU_RTOL,
              f"{backend}: loss and grad norm agree with ragged")


def phase_kernel_parity(cfg, tcfg):
    """(d): gmm / gmm_dw, pallas against ragged, at the model's widths on
    the routed slots of eight sequences, 7/8 of them in groups; then the
    whole SwiGLU expert layer, forward and gradients, on the fused kernel
    pair against ragged, with two experts no token picks."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import gmm_backend as GB
    S, d, h, E = (8 * tcfg.seq_len, cfg.d_model, cfg.moe_d_ff,
                  cfg.num_experts)
    rng = np.random.default_rng(tcfg.seed)
    total = S - S // 8
    cuts = np.sort(rng.choice(np.arange(1, total), E - 1, replace=False))
    sizes = np.diff(np.concatenate([[0], cuts, [total]]))
    empty = [E // 4, 3 * E // 4]             # two empty experts
    sizes[[e + 1 for e in empty]] += sizes[empty]
    sizes[empty] = 0
    gs = jnp.asarray(sizes, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(tcfg.seed), 3)
    lhs = jax.random.normal(ks[0], (S, d), jnp.float32)
    rhs = jax.random.normal(ks[1], (E, d, h), jnp.float32) * d ** -0.5
    dout = jax.random.normal(ks[2], (S, h), jnp.float32)
    out = {}
    for be in ("pallas", "ragged"):
        out[be] = (np.asarray(GB.gmm(lhs, rhs, gs, backend=be)),
                   np.asarray(GB.gmm_dw(lhs, dout, gs, backend=be)))

    def nerr(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    e_gmm = nerr(out["pallas"][0][:total], out["ragged"][0][:total])
    e_dw = nerr(out["pallas"][1], out["ragged"][1])
    dead_rows = max(float(np.abs(out[be][0][total:]).max()) for be in out)
    empty_dw = float(np.abs(out["pallas"][1][sizes == 0]).max())
    log(f"[d] gmm/gmm_dw pallas vs ragged S={S} d={d} h={h} E={E} "
        f"group_total={total} empty_experts={int((sizes == 0).sum())} "
        f"rel_err_gmm={e_gmm:.3e} rel_err_gmm_dw={e_dw:.3e} "
        f"max_dead_row={dead_rows} max_empty_expert_dw={empty_dw}")
    check(e_gmm <= F32_TPU_RTOL and e_dw <= F32_TPU_RTOL,
          "pallas grouped GEMMs agree with ragged")
    check(dead_rows == 0.0 and empty_dw == 0.0,
          "rows past the group total (both backends) and empty experts' "
          "pallas dw are exact zeros")

    from repro.core.moe_layer import moe_ffn_blaze
    from repro.core.routing import build_dispatch
    L, k = tcfg.seq_len * 2, cfg.top_k
    picked = np.array([e for e in range(E) if e not in empty])
    topk = np.stack([rng.choice(picked, k, replace=False) for _ in range(L)])
    disp = build_dispatch(jnp.asarray(topk, jnp.int32), E)
    ks = jax.random.split(jax.random.PRNGKey(tcfg.seed + 1), 5)
    args = (jax.random.normal(ks[0], (L, d), jnp.float32),
            jax.random.normal(ks[1], (E, d, h), jnp.float32) * d ** -0.5,
            jax.random.normal(ks[2], (E, d, h), jnp.float32) * d ** -0.5,
            jax.random.normal(ks[3], (E, h, d), jnp.float32) * h ** -0.5,
            jax.nn.softmax(jax.random.normal(ks[4], (L, k)), axis=-1))

    def layer(backend):
        def f(x, w1, w2, w3, gates):
            y = moe_ffn_blaze(x, gates, disp, w1, w3, w2, backend=backend)
            return (y ** 2).sum(), y
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4),
                                          has_aux=True))

    (_, y_f), g_f = layer("pallas_fused")(*args)
    (_, y_r), g_r = layer("ragged")(*args)
    errs = [nerr(np.asarray(a), np.asarray(b))
            for a, b in zip((y_f,) + g_f, (y_r,) + g_r)]
    empty_grads = max(float(np.abs(np.asarray(g)[empty]).max())
                      for g in g_f[1:4])
    log(f"[d] fused SwiGLU layer pallas_fused vs ragged L={L} k={k} "
        f"rel_err y,dx,dw1,dw2,dw3,dgates={[f'{e:.3e}' for e in errs]} "
        f"max_empty_expert_dw={empty_grads}")
    check(max(errs) <= F32_TPU_RTOL, "fused layer agrees with ragged")
    check(empty_grads == 0.0, "empty experts' fused dw are exact zeros")


def phase_serve(cfg, seed: int):
    """(e): greedy requests through the paged engine, dense vs pallas.

    Both runs trace under float32 matmul precision, so the comparison is
    about the kernel, not about where the two paths round to bfloat16."""
    import jax
    import numpy as np
    from repro.models import transformer as T
    from repro.serve.engine import Request, ServeEngine
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(3, cfg.vocab_size, size=n).astype(np.int32)
               for n in (7, 19, 40, 64)]
    tokens = {}
    with jax.default_matmul_precision("float32"):
        for kernel in ("dense", "pallas"):
            eng = ServeEngine(cfg, params, batch_slots=4, capacity=256,
                              page_size=16, paged_kernel=kernel)
            reqs = [Request(prompt=p, max_new_tokens=16) for p in prompts]
            t0 = time.perf_counter()
            eng.generate(reqs)
            wall = time.perf_counter() - t0
            tokens[kernel] = [list(r.out_tokens) for r in reqs]
            log(f"[e] serve paged_kernel={eng.paged_attn.name} "
                f"requests={len(reqs)} generated={eng.stats['generated_tokens']}"
                f" wall_s_incl_compile={wall:.3f} tokens={tokens[kernel]}")
    check(tokens["dense"] == tokens["pallas"],
          "dense and pallas paged attention give identical tokens")


def phase_expert_parallel(cfg, tcfg, batches):
    """Four chips: ep and ep_a2a train steps against the unsharded step."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import sharding as shd
    from repro.launch.mesh import make_debug_mesh
    from repro.train.optimizer import init_adamw
    devices = jax.devices()
    check(len(devices) >= 4, "four chips")
    n_steps = 3
    host = [{k: jnp.asarray(v) for k, v in b.items()} for b in batches]

    params, opt = fresh_state(cfg, tcfg)
    exe, compile_s, _ = compile_step(cfg, tcfg, params, opt, host[0])
    m = exe(params, opt, host[0])[2]
    ref = {"loss": float(m["loss"]), "grad_norm": float(m["grad_norm"])}
    log(f"[ep] unsharded step on {devices[0]}: compile_s={compile_s:.3f} "
        f"loss={ref['loss']} grad_norm={ref['grad_norm']}")
    del params, opt, exe

    mesh = make_debug_mesh(1, 4)
    for mode in ("ep", "ep_a2a"):
        mcfg = cfg.replace(moe_parallel=mode)
        params, _ = fresh_state(mcfg, tcfg)
        pspecs = shd.param_specs(params, mesh, moe_parallel=mode)
        p_sh, o_sh = shd.to_shardings(mesh, (pspecs, shd.opt_specs(pspecs)))
        params = jax.device_put(params, p_sh)
        opt = jax.device_put(init_adamw(params), o_sh)
        rep = NamedSharding(mesh, P())
        dev = [jax.device_put(b, rep) for b in host[:n_steps]]
        exe, compile_s, step = compile_step(mcfg, tcfg, params, opt, dev[0],
                                            mesh=mesh)
        times, first, overflow = [], None, []
        for b in dev:
            t0 = time.perf_counter()
            params, opt, m = exe(params, opt, b)
            jax.block_until_ready((params, opt, m))
            times.append(time.perf_counter() - t0)
            overflow.append(float(m["moe_overflow"]))
            if first is None:
                first = {"loss": float(m["loss"]),
                         "grad_norm": float(m["grad_norm"])}
        dl = rel(first["loss"], ref["loss"])
        dg = rel(first["grad_norm"], ref["grad_norm"])
        log(f"[ep] moe_parallel={mode} mesh=(data=1, model=4) "
            f"compile_s={compile_s:.3f} step_s={[round(t, 6) for t in times]}"
            f" loss={first['loss']} grad_norm={first['grad_norm']} "
            f"rel_dloss={dl:.3e} rel_dgnorm={dg:.3e} moe_overflow={overflow}")
        check(all(o == 0.0 for o in overflow), f"{mode}: no dropped slots")
        check(dl <= F32_TPU_RTOL and dg <= F32_TPU_RTOL,
              f"{mode}: loss and grad norm agree with the unsharded step")
        del params, opt, exe


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()
    device = device_report()
    cfg, tcfg, batches = conf6(args.seed)
    if args.chips == 4:
        phase_expert_parallel(cfg, tcfg, batches)
    else:
        ref = phase_train(cfg, tcfg, batches)
        phase_pallas_steps(cfg, tcfg, batches, ref)
        phase_kernel_parity(cfg, tcfg)
        phase_serve(cfg, args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
