"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes and report memory/cost/roofline terms.

The XLA host-device override MUST precede any jax import (jax locks the
device count on first init) — hence the first two lines.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k --multi-pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --out EXPERIMENTS/dryrun.jsonl

``--gmm-backend`` pins the grouped-GEMM backend (repro.core.gmm_backend) for
every MoE lowering in the run — e.g. ``--gmm-backend segment`` probes the
portable path, ``ragged`` the XLA fast path on newer JAX.  ``--moe-parallel``
pins the MoE distribution mode (auto | ep | ep_a2a | tp) for every lowering —
both the weight PartitionSpecs and the shard_map execution path follow it.

``--remat-policy`` pins the activation-checkpoint plan (a registry name or a
``repro.core.checkpoint`` spec like ``"save=ffn_a,ffn_b,qkv"``);
``--hbm-budget BYTES`` (suffixes ``KiB/MiB/GiB`` accepted; *per device*)
engages ``CheckpointPlan.fit`` instead — the cheapest-recompute plan whose
*simulated per-device train-step peak* (params + grads + optimizer state +
the ``repro.core.memsim`` phase timeline: transient recompute spikes, a2a
capacity buffers, optimizer update) fits the budget is selected per
(arch x shape), with an explicit ``--remat-policy`` as the preferred
candidate.  Every record stamps the resolved plan
(``remat_plan``/``remat_plan_source``), the ``remat_fit`` decision table
(one ``source=explicit|config|default`` row when no budget engages the
fit), and the simulated phase timeline
(``peak_sim_bytes``/``peak_sim_phase``/``sim_phases``).
"""

import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import sharding as shd                      # noqa: E402
from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config  # noqa: E402
from repro.configs.base import TrainConfig             # noqa: E402
from repro.launch import specs as S                    # noqa: E402
from repro.launch.mesh import make_production_mesh     # noqa: E402
from repro.models import transformer as T              # noqa: E402
from repro.roofline import analyze_compiled            # noqa: E402
from repro.train.loop import make_train_step           # noqa: E402
from repro.train.optimizer import init_adamw           # noqa: E402


def _num_microbatches(shape, mesh, cfg=None) -> int:
    """Gradient accumulation count: smallest power-of-two M (up to one
    sequence per device) that keeps the layer-scan residual carries — the
    dominant train-memory term under full per-layer remat — under ~3.5 GiB
    per device."""
    n_dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n_dp *= mesh.shape[a]
    m_cap = max(shape.global_batch // n_dp, 1)
    if cfg is None:
        return min(8, m_cap)
    budget = 3.5 * 2 ** 30
    M = 1
    while M < m_cap:
        tokens_per_dev = shape.global_batch * shape.seq_len / (n_dp * M)
        carry = cfg.num_layers * tokens_per_dev * cfg.d_model * 2
        if carry <= budget and M >= min(8, m_cap):
            break
        M *= 2
    return min(M, m_cap)


def _prefill_chunks(cfg, shape, mesh) -> int:
    """Chunked prefill (vLLM-style) for MoE archs: bound the dense-dispatch
    buffers while keeping each chunk's batch shardable over the data axes."""
    if not cfg.is_moe:
        return 1
    n_dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n_dp *= mesh.shape[a]
    return max(1, shape.global_batch // n_dp)


def build_lowerable(arch: str, shape_name: str, mesh, cfg_overrides=None,
                    shape=None, microbatches=None):
    """Returns (fn, example_args, in_shardings) for jit.

    MoE archs lower the GShard dense-dispatch formulation by default: XLA's
    *CPU* decomposition of ragged_dot is dense-per-group (E x temps/FLOPs),
    which is an artifact of this container, not of the TPU target — the
    dense-dispatch graph has the same collectives and fits.  The TPU gmm
    cost is modelled by the 'proxy_gmm' probes (see run_one).
    """
    cfg = get_config(arch)
    if cfg.is_moe and not (cfg_overrides and "moe_impl" in cfg_overrides):
        cfg = cfg.replace(moe_impl="dense")
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    shape = shape or INPUT_SHAPES[shape_name]
    skip = S.applicable(cfg, shape)
    if skip:
        return None, skip, cfg
    pshapes = S.params_shapes(cfg)
    fsdp = not (shape.kind == "decode" and cfg.serve_replicate_weights)
    pspecs = shd.param_specs(pshapes, mesh, fsdp=fsdp,
                             moe_parallel=cfg.moe_parallel)

    if shape.kind == "train":
        M = microbatches if microbatches is not None \
            else _num_microbatches(shape, mesh, cfg)
        tcfg = TrainConfig(num_microbatches=M)
        oshapes = jax.eval_shape(init_adamw, pshapes)
        ospecs = shd.opt_specs(pspecs)
        bshapes = S.batch_shapes(cfg, shape)
        bspecs = shd.batch_specs(cfg, bshapes, mesh)
        fn = make_train_step(cfg, tcfg, mesh=mesh)
        args = (pshapes, oshapes, bshapes)
        in_specs = (pspecs, ospecs, bspecs)
    elif shape.kind == "prefill":
        bshapes = S.batch_shapes(cfg, shape)
        bspecs = shd.batch_specs(cfg, bshapes, mesh)
        Mp = _prefill_chunks(cfg, shape, mesh) if microbatches is None \
            else microbatches

        def fn(params, batch):
            # Prefill emits only the last-position logits (the first sampled
            # token) — materializing (B, S, vocab) would be absurd at 32k.
            # MoE archs chunk the request batch (vLLM-style chunked prefill)
            # to bound the dense-dispatch buffers.
            if Mp > 1:
                mb = jax.tree.map(
                    lambda x: x.reshape(Mp, x.shape[0] // Mp, *x.shape[1:]),
                    batch)

                def body(_, one):
                    lg, aux = T.forward(params, one, cfg, mesh=mesh,
                                        last_only=True)
                    return None, (lg[:, -1, :], aux)

                _, (lg, aux) = jax.lax.scan(body, None, mb)
                return lg.reshape(shape.global_batch, -1), aux.mean()
            logits, aux = T.forward(params, batch, cfg, mesh=mesh,
                                    last_only=True)
            return logits[:, -1, :], aux

        args = (pshapes, bshapes)
        in_specs = (pspecs, bspecs)
    else:  # decode
        # Serving uses bf16 weights (production standard; f32 masters are a
        # training concern) — re-derive param shapes in the serving dtype.
        cfg = cfg.replace(param_dtype="bfloat16")
        pshapes = S.params_shapes(cfg)
        pspecs = shd.param_specs(pshapes, mesh, fsdp=fsdp,
                                 moe_parallel=cfg.moe_parallel)
        ds = S.decode_shapes(cfg, shape)
        cspecs = shd.cache_specs(cfg, ds["cache"], mesh)
        tok_spec = shd.batch_specs(cfg, {"tokens": ds["tokens"]}, mesh)

        def fn(params, cache, tokens, pos):
            return T.decode_step(params, cache, {"tokens": tokens}, pos,
                                 cfg, mesh=mesh)

        args = (pshapes, ds["cache"], ds["tokens"], ds["pos"])
        in_specs = (pspecs, cspecs, tok_spec["tokens"], jax.sharding.PartitionSpec())

    shardings = shd.to_shardings(mesh, in_specs)
    return (fn, args, shardings), None, cfg


def _compile_once(arch, shape_name, mesh, cfg_overrides, shape=None,
                  microbatches=None):
    built, skip, cfg = build_lowerable(arch, shape_name, mesh, cfg_overrides,
                                       shape=shape, microbatches=microbatches)
    if skip:
        return None, skip, cfg
    fn, args, shardings = built
    # Serving always donates the cache (in-place update); without donation
    # XLA double-buffers the multi-GiB cache as a temp.
    donate = (1,) if (shape or INPUT_SHAPES[shape_name]).kind == "decode" \
        else ()
    t0 = time.perf_counter()
    with mesh:
        lowered = jax.jit(fn, in_shardings=shardings,
                          donate_argnums=donate).lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower
    return (compiled, t_lower, t_compile), None, cfg


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            cfg_overrides=None, verbose: bool = True,
            cost_probe: bool = True, microbatches: int | None = None,
            remat_policy: str | None = None,
            hbm_budget: int | None = None) -> dict:
    """Dry-run one (arch x shape x mesh).

    The full scanned model is lowered+compiled (memory analysis, proof of
    lowering).  Because ``cost_analysis`` counts a ``while`` (layer-scan) body
    only once, FLOPs/bytes/collectives are measured from two *unrolled*
    probes (1 and 2 pattern-groups) and extrapolated linearly:
    ``full = B + (G-1)·(C-B)`` — exact for homogeneous layer stacks.
    """
    import dataclasses

    from repro.core import checkpoint as CK
    from repro.core.gmm_backend import resolve
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    # Resolve the checkpoint plan up front (on the overridden config) so
    # every lowering below — the main compile and the cost probes — runs the
    # same baked plan spec.  The budget is *per device*: the fit estimates
    # the residual set live on one device (global batch / data-parallel
    # shards / gradient-accumulation microbatches).
    cfg_overrides = dict(cfg_overrides or {})
    cfg0 = get_config(arch).replace(**cfg_overrides)
    prefer = CK.get_plan(remat_policy) if remat_policy else None
    ishape = INPUT_SHAPES[shape_name]
    n_dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n_dp *= mesh.shape[a]
    b_dev = max(ishape.global_batch // max(n_dp, 1), 1)
    if ishape.kind == "train":
        M = microbatches if microbatches is not None \
            else _num_microbatches(ishape, mesh, cfg0)
        b_dev = max(b_dev // M, 1)
    n_model = max(mesh.shape.get("model", 1), 1)
    n_node = max(mesh.shape.get("node", 1), 1)
    moe_mode = None
    if cfg0.is_moe:
        from repro.models.moe_block import resolve_moe_parallel_ex
        decision = resolve_moe_parallel_ex(cfg0, mesh,
                                           b_dev * ishape.seq_len)
        moe_mode = decision.mode
        # The full predicted-cost decision table (mirrors remat_fit): one
        # row per distribution mode with roofline time terms, bytes on the
        # wire, live bytes and the chosen flag — the auto optimizer's
        # provenance, stamped even when the mode was forced.
        rec["moe_parallel"] = decision.mode
        rec["moe_parallel_source"] = decision.source
        rec["moe_parallel_tokens"] = decision.n_tokens
        rec["moe_parallel_decision"] = decision.table_rows()
    if hbm_budget is not None:
        fit = CK.CheckpointPlan.fit(
            cfg0, b_dev * ishape.seq_len, hbm_budget, batch=b_dev,
            prefer=prefer, mode=moe_mode, n_model=n_model, n_node=n_node)
        plan_r = fit.resolved
        rec["remat_fit"] = [dict(dataclasses.asdict(r), source="fit")
                            for r in fit.table]
        rec["hbm_budget"] = fit.budget_bytes
        timeline = fit.timeline
    else:
        from repro.core import memsim
        plan_r = CK.resolve_plan(remat_policy, config=cfg0.remat_policy)
        timeline = memsim.simulate(
            cfg0, b_dev * ishape.seq_len, batch=b_dev, plan=plan_r.plan,
            mode=moe_mode, n_model=n_model, n_node=n_node, base="train")
        # No budget: stamp the decision table anyway (one source=explicit /
        # source=config / source=default row for the resolved plan) so CI
        # assertions over remat_fit never vacuously pass on a missing key.
        src = "explicit" if plan_r.source == "arg" else plan_r.source
        rec["remat_fit"] = [dict(
            spec=plan_r.spec, est_saved_bytes=plan_r.plan.estimate_saved_bytes(
                cfg0, b_dev * ishape.seq_len, batch=b_dev),
            fits=None, chosen=True, sim_peak_bytes=timeline.peak_bytes,
            peak_phase=timeline.peak_phase, source=src)]
    cfg_overrides["remat_policy"] = plan_r.spec
    rec["remat_plan"] = plan_r.spec
    rec["remat_plan_source"] = plan_r.source
    # The simulated per-device phase timeline of the chosen plan: the peak,
    # the phase responsible, and the highest-live phases (memsim table).
    rec["peak_sim_bytes"] = timeline.peak_bytes
    rec["peak_sim_phase"] = timeline.peak_phase
    rec["sim_phases"] = [
        {"phase": p.name, "held_bytes": p.held_bytes,
         "transient_bytes": p.transient_bytes,
         "collective_bytes": p.collective_bytes, "live_bytes": p.live_bytes}
        for p in sorted(timeline.phases, key=lambda p: -p.live_bytes)[:4]]
    out, skip, cfg = _compile_once(arch, shape_name, mesh, cfg_overrides,
                                   microbatches=microbatches)
    # Stamp the backend the lowering actually resolved (cfg at the config
    # slot, use_backend scope above it) — not a re-read of the env var.
    rec["gmm_backend"] = resolve(None, config=cfg.gmm_backend).name
    if skip:
        rec["status"] = f"SKIP({skip})"
        return rec
    compiled, t_lower, t_compile = out
    full = analyze_compiled(compiled, cfg, INPUT_SHAPES[shape_name],
                            n_chips=mesh.devices.size)
    rec.update(status="OK", lower_s=round(t_lower, 1),
               compile_s=round(t_compile, 1), **full)

    if cost_probe and cfg.num_groups > 1:
        period = cfg.pattern_period
        shape = INPUT_SHAPES[shape_name]
        # The probes must not hide cost inside a second (microbatch) scan:
        # train probes lower ONE microbatch and scale the result by M.
        M = 1
        if shape.kind == "train":
            M = microbatches if microbatches is not None \
                else _num_microbatches(shape, mesh, cfg)
        elif shape.kind == "prefill":
            M = microbatches if microbatches is not None \
                else _prefill_chunks(cfg, shape, mesh)
        pshape = shape
        if M > 1:
            import dataclasses
            pshape = dataclasses.replace(
                shape, global_batch=shape.global_batch // M)
        probes = []
        for g in (1, 2):
            ov = dict(cfg_overrides or {})
            ov.update(num_layers=g * period, scan_layers=False)
            if cfg.is_moe:
                # TPU-gmm cost model (see build_lowerable docstring).
                ov.setdefault("moe_impl", "proxy_gmm")
            pout, pskip, pcfg = _compile_once(
                arch, shape_name, mesh, ov, shape=pshape, microbatches=1)
            assert pskip is None
            probes.append(analyze_compiled(
                pout[0], pcfg, INPUT_SHAPES[shape_name],
                n_chips=mesh.devices.size))
        b, c = probes
        G = cfg.num_groups

        def extrap(key):
            # clamp: XLA occasionally optimizes the 2-group probe harder than
            # the 1-group one, which would extrapolate below zero
            return max(0.0, M * (b[key] + (G - 1) * (c[key] - b[key])))

        from repro.roofline import HBM_BW, ICI_BW_PER_LINK, PEAK_FLOPS_BF16
        rec["flops_per_dev"] = extrap("flops_per_dev")
        rec["hlo_bytes_per_dev"] = extrap("hlo_bytes_per_dev")
        rec["collective_bytes"] = extrap("collective_bytes")
        rec["collective_counts"] = {
            k: max(0, b["collective_counts"][k] +
                   (G - 1) * (c["collective_counts"][k]
                              - b["collective_counts"][k]))
            for k in b["collective_counts"]}
        rec["t_compute_s"] = rec["flops_per_dev"] / PEAK_FLOPS_BF16
        rec["t_memory_s"] = rec["hlo_bytes_per_dev"] / HBM_BW
        rec["t_collective_s"] = rec["collective_bytes"] / ICI_BW_PER_LINK
        rec["dominant"] = max(
            (("compute", rec["t_compute_s"]), ("memory", rec["t_memory_s"]),
             ("collective", rec["t_collective_s"])), key=lambda kv: kv[1])[0]
        rec["useful_flops_ratio"] = rec["model_flops_global"] / max(
            rec["flops_per_dev"] * mesh.devices.size, 1.0)
        rec["cost_probe"] = "extrapolated(1,2 groups unrolled)"

    if verbose and rec.get("moe_parallel_decision"):
        # Predicted-vs-measured: the cost model's per-mode ranking next to
        # what the compiled HLO actually put on the wire.
        print(f"  moe_parallel={rec['moe_parallel']} "
              f"(source={rec['moe_parallel_source']}, "
              f"ranked at {rec['moe_parallel_tokens']} tokens/dev):")
        for r in rec["moe_parallel_decision"]:
            mark = "*" if r["chosen"] else " "
            why = "" if r["feasible"] else f"  [{r['why']}]"
            print(f"  {mark} {r['mode']:<12}"
                  f" t={r['t_total_s'] * 1e6:9.1f}us"
                  f" (comp {r['t_compute_s'] * 1e6:.1f}"
                  f" mem {r['t_memory_s'] * 1e6:.1f}"
                  f" coll {r['t_collective_s'] * 1e6:.1f})"
                  f" live={r['live_bytes'] / 2**20:8.1f}MiB"
                  f" a2a={r['a2a_bytes'] / 2**20:.2f}MiB"
                  f" psum={r['psum_bytes'] / 2**20:.2f}MiB{why}")
        by_kind = rec.get("collective_bytes_by_kind")
        if by_kind:
            kinds = " ".join(f"{k}={v / 2**20:.1f}MiB"
                             for k, v in sorted(by_kind.items()))
            print(f"    measured (compiled HLO, whole step): {kinds}")
    if verbose:
        print(f"[{arch} x {shape_name} x {rec['mesh']}] "
              f"plan={rec['remat_plan']} "
              f"args={rec['arg_bytes']/2**30:.2f}GiB "
              f"temp={rec['temp_bytes']/2**30:.2f}GiB "
              f"peak={rec['peak_bytes']/2**30:.2f}GiB/dev "
              f"fits={rec['fits_hbm']} | flops/dev={rec['flops_per_dev']:.3e} "
              f"coll={rec['collective_bytes']/2**20:.1f}MiB "
              f"dominant={rec['dominant']}")
        print("  memory_analysis:", compiled.memory_analysis())
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--override", default=None,
                    help="JSON dict of ModelConfig overrides (hillclimbing)")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--no-probe", action="store_true",
                    help="skip the cost-extrapolation probes (multi-pod pass "
                         "only needs the lowering/memory proof)")
    ap.add_argument("--tag", default=None,
                    help="label recorded with each JSONL row (perf log)")
    ap.add_argument("--gmm-backend", default=None,
                    help="grouped-GEMM backend for MoE lowerings "
                         "(ragged | segment | pallas; default auto)")
    ap.add_argument("--moe-parallel", default=None,
                    choices=["auto", "ep", "ep_a2a", "ep_a2a_hier", "tp"],
                    help="MoE distribution mode override (config field "
                         "moe_parallel; see README 'Distribution modes')")
    ap.add_argument("--remat-policy", default=None,
                    help="activation-checkpoint plan: registry name or spec "
                         "('save=ffn_a,ffn_b,qkv;moe:recompute=ffn_yswi'); "
                         "see README 'Activation checkpoint plans'")
    ap.add_argument("--hbm-budget", default=None,
                    help="per-device train-step peak budget (bytes; "
                         "KiB/MiB/GiB suffixes ok) — budget-fit the "
                         "checkpoint plan per (arch x shape) via "
                         "CheckpointPlan.fit over the simulated per-device "
                         "peak (core.memsim phase timeline); an explicit "
                         "--remat-policy becomes the preferred candidate")
    args = ap.parse_args(argv)
    from repro.core.checkpoint import get_plan, parse_size
    if args.remat_policy:
        get_plan(args.remat_policy)      # validate before any compile work
    hbm_budget = parse_size(args.hbm_budget) if args.hbm_budget else None
    overrides = json.loads(args.override) if args.override else None
    if args.moe_parallel:
        overrides = dict(overrides or {}, moe_parallel=args.moe_parallel)
    # --gmm-backend pins via a use_backend scope around the whole run — a
    # process-local, exception-safe pin (the old os.environ mutation leaked
    # into anything else alive in the process).
    import contextlib

    from repro.core.gmm_backend import use_backend
    backend_scope = (use_backend(args.gmm_backend) if args.gmm_backend
                     else contextlib.nullcontext())

    pairs = []
    if args.all:
        for a in ARCH_IDS:
            for s in INPUT_SHAPES:
                pairs.append((a, s))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        pairs.append((args.arch, args.shape))

    ok = True
    with backend_scope:
        for arch, shape in pairs:
            try:
                rec = run_one(arch, shape, multi_pod=args.multi_pod,
                              cfg_overrides=overrides,
                              microbatches=args.microbatches,
                              cost_probe=not args.no_probe,
                              remat_policy=args.remat_policy,
                              hbm_budget=hbm_budget)
                if args.tag:
                    rec["tag"] = args.tag
            except Exception as e:  # noqa: BLE001 — report and continue
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if args.multi_pod else "16x16",
                       "status": f"FAIL({type(e).__name__}: {e})"}
                ok = False
                print(f"[{arch} x {shape}] FAILED: {e}", file=sys.stderr)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
            else:
                print(json.dumps(rec))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
