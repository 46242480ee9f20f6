"""Activation-memory accounting — the harness behind the paper's >50%
activation-saving claim (§5.2), made regression-testable.

Three independent accountants per (model config x checkpoint policy x
grouped-GEMM backend), all on abstract shapes (no arrays allocated):

  * **measured** — ``jax.jit(grad(loss)).lower(...).compile()
    .memory_analysis()``: XLA's temp/argument/output buffer sizes for the
    compiled fwd+bwd;
  * **autodiff residuals** — ``saved_residuals`` (the JAX analogue of the
    paper's PyTorch saved-tensor hooks), parameters excluded — what autodiff
    *saves* under the policy;
  * **static estimate** — ``CheckpointPlan.estimate_saved_bytes``, computed
    from the plan's scoped tag decisions and the config's shapes alone.
    Exact for the tag-based plans and completely version-independent, so it
    is the tightest regression gate.

Every entry stamps the resolved plan's canonical spec in its meta
(``remat_plan``) — BENCH records are self-describing about which checkpoint
plan produced each number.

``memory_suite`` flattens the reports into ``repro.bench.record`` entries and
couples in the roofline model (``roofline.analyze_compiled`` on the same
compiled step), so the tracked ``BENCH_memory.json`` is the single report
both measured and modeled numbers live in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.bench.record import entry
from repro.core.checkpoint import saved_residual_nbytes
from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core import checkpoint as CK
from repro.core import gmm_backend as GB
from repro.core import memsim
from repro.models import transformer as T

#: relative tolerance of the simulated-vs-measured peak parity gate (and of
#: the ``peak_sim/*`` entries' own baseline drift) — the deterministic-entry
#: tolerance the acceptance bar names.
SIM_PARITY_TOLERANCE_PCT = 20.0

#: policy order used by suites and by the ordering assertions in tests —
#: derived from the CheckpointPlan registry (tag plans by ascending save
#: set, then the specials), never hand-maintained in parallel again.
POLICY_ORDER = CK.plan_order()


def bench_config():
    """The small MoE config every tracked bench number is measured on (CPU
    container scale; the same harness takes any ``ModelConfig``)."""
    return get_config("qwen3_moe_30b_a3b").reduced().replace(
        name="tiny_moe", num_layers=2, d_model=64, num_heads=2,
        num_kv_heads=2, head_dim=32, num_experts=4, top_k=2, moe_d_ff=128,
        vocab_size=128, dtype="float32", scan_layers=True)


def bench_dense_config():
    """Dense SwiGLU companion config: its FFN carries the full A/B/Y_swi tag
    set, so it is where the strict ``none < paper_min < paper < full``
    residual ordering is measurable (the MoE expert FFN manages its own
    residuals inside the custom VJP)."""
    return get_config("yi_6b").reduced().replace(
        name="tiny_dense", num_layers=2, d_model=64, num_heads=2,
        num_kv_heads=2, head_dim=32, d_ff=128, vocab_size=128,
        dtype="float32", scan_layers=True)


def bench_ep_config():
    """MoE config for the expert-parallel residual entries.  ``E_loc = E /
    n_model`` must exceed ``top_k`` for the comparison to be meaningful: the
    dense-EP formulation materializes (L, E_loc, h) intermediates while the
    dispatch path's scale with L·k rows."""
    return bench_config().replace(
        name="tiny_moe_ep", num_experts=8, top_k=2, moe_d_ff=128,
        gmm_backend="segment")


def _dense_ep_sublayer(x, p, cfg, mesh):
    """The pre-refactor dense-EP shard_map body — (L, E_loc, h) einsums
    against a dense (L, E) combine-weight matrix.  Deleted from
    ``models/moe_block.py`` (the Dispatch-driven path replaced it); kept
    HERE, next to the other measured baselines, so the dispatch-EP residual
    numbers are gated against the formulation they displaced."""
    from jax.sharding import PartitionSpec as P

    from repro.core import routing
    from repro.core.moe_layer import _silu
    B, S, d = x.shape
    E = cfg.num_experts
    E_loc = E // mesh.shape["model"]
    p_specs = {"wg": P(None, None), "w1": P("model", None, None),
               "w2": P("model", None, None), "w3": P("model", None, None)}
    p_specs = {k: v for k, v in p_specs.items() if k in p}

    def body(xl, pl):
        xf = xl.reshape(B * S, d)
        g = routing.top_k_gating(xf, pl["wg"].astype(xf.dtype), cfg.top_k)
        idx = jax.lax.axis_index("model")
        L = xf.shape[0]
        cw = jnp.zeros((L, E), g.topk_weights.dtype)
        cw = cw.at[jnp.arange(L)[:, None], g.topk_experts].set(g.topk_weights)
        cw_loc = jax.lax.dynamic_slice_in_dim(cw, idx * E_loc, E_loc, axis=1)
        a = jnp.einsum("ld,edh->leh", xf, pl["w1"].astype(xf.dtype))
        y_act = _silu(a) * jnp.einsum("ld,edh->leh", xf,
                                      pl["w2"].astype(xf.dtype))
        p_out = jnp.einsum("leh,ehd->led", y_act, pl["w3"].astype(xf.dtype))
        y = jnp.einsum("le,led->ld", cw_loc.astype(p_out.dtype), p_out)
        return jax.lax.psum(y, "model").reshape(B, S, d)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(None, None, None), p_specs),
                         out_specs=P(None, None, None), check_vma=False)(x, p)


def ep_saved_residual_entries(*, small: bool = False) -> list:
    """Dense-EP vs dispatch-EP activation residuals under an expert-sharded
    mesh, measured in the same run: the refactor's memory claim as tracked
    numbers.  The dispatch entry is the regression gate; the dense entry
    documents the baseline it must stay strictly below."""
    from repro.launch.mesh import make_debug_mesh
    from repro.models.moe_block import init_moe_params, moe_sublayer
    if len(jax.devices()) < 2:
        # Degrade loudly, not fatally: the rest of the memory suite is
        # device-count independent and must keep running.  A --check against
        # the committed baseline will then report the EP pair as missing —
        # an explicit gate signal, not a crash.
        import sys
        print("# skipping EP residual entries: need >= 2 host devices "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
              "before jax initializes; `python -m repro.bench` does this "
              "automatically)", file=sys.stderr)
        return []
    cfg = bench_ep_config()
    mesh = make_debug_mesh(1, 2)
    batch, seq = (2, 32) if small else (4, 64)
    params = jax.eval_shape(
        lambda k: init_moe_params(k, cfg, cfg.d_model), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), jnp.float32)

    # Both functions return y only, so the router-aux branch is dead code in
    # both traces and the residual accounting compares like with like.
    def dispatch_fn(x, p):
        return moe_sublayer(x, p, cfg.replace(moe_parallel="ep"),
                            mesh=mesh)[0]

    def dense_fn(x, p):
        return _dense_ep_sublayer(x, p, cfg, mesh)

    dense_b = saved_residual_nbytes(dense_fn, x, params)
    disp_b = saved_residual_nbytes(dispatch_fn, x, params)
    meta = {"batch": batch, "seq": seq, "mesh": "1x2",
            "num_experts": cfg.num_experts, "top_k": cfg.top_k}
    prefix = f"memory/{cfg.name}"
    return [
        entry(f"{prefix}/ep_dense/residual_bytes", dense_b,
              kind="residual_bytes", unit="bytes", tolerance_pct=20.0, **meta),
        entry(f"{prefix}/ep_dispatch/residual_bytes", disp_b,
              kind="residual_bytes", unit="bytes", tolerance_pct=20.0, **meta),
    ]


def _loss_fn(cfg):
    def loss(params, tokens):
        batch = {"tokens": tokens, "labels": tokens}
        return T.train_loss(params, batch, cfg)[0]
    return loss


def _abstract_args(cfg, batch: int, seq: int):
    params = jax.eval_shape(
        lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    return params, tokens


def residual_bytes(cfg, policy, *, batch: int = 2, seq: int = 32) -> int:
    """Activation bytes autodiff saves for backward under ``policy`` (a plan
    name, spec, or object; arguments/parameters excluded)."""
    cfg = cfg.replace(remat_policy=CK.resolve_plan(policy).spec)
    return saved_residual_nbytes(_loss_fn(cfg), *_abstract_args(cfg, batch, seq))


def activation_memory_report(cfg, policy, *, backend: str | None = None,
                             batch: int = 2, seq: int = 32,
                             with_roofline: bool = False,
                             with_residuals: bool = True) -> dict:
    """Compile fwd+bwd of the train loss under (plan, backend) and account
    its memory three ways.  ``policy`` is a plan name, spec, or
    ``CheckpointPlan``; the resolved canonical spec is stamped into the
    report (``remat_plan``/``plan_source``).  Returns a flat dict of numbers
    (plus the roofline analysis dict when requested).
    ``with_residuals=False`` skips the saved-residuals trace and the static
    estimate (they are backend-independent — callers sweeping the backend
    axis need them only once)."""
    rb = GB.resolve(backend, config=cfg.gmm_backend)
    plan_r = CK.resolve_plan(policy)
    cfg = cfg.replace(remat_policy=plan_r.spec, gmm_backend=rb.name)
    args = _abstract_args(cfg, batch, seq)
    grad = jax.grad(_loss_fn(cfg))
    with GB.use_backend(rb.name):   # pin the trace to the stamped backend
        compiled = jax.jit(grad).lower(*args).compile()
    mem = compiled.memory_analysis()
    arg_b = getattr(mem, "argument_size_in_bytes", 0)
    out_b = getattr(mem, "output_size_in_bytes", 0)
    tmp_b = getattr(mem, "temp_size_in_bytes", 0)
    alias_b = getattr(mem, "alias_size_in_bytes", 0)
    report = {
        "config": cfg.name, "policy": str(policy), "backend": rb.name,
        "backend_source": rb.source,
        "remat_plan": plan_r.spec, "plan_source": plan_r.source,
        "batch": batch, "seq": seq,
        "arg_bytes": arg_b, "out_bytes": out_b, "temp_bytes": tmp_b,
        "peak_bytes": arg_b + out_b + tmp_b - alias_b,
        "residual_bytes": (residual_bytes(cfg, plan_r, batch=batch, seq=seq)
                           if with_residuals else None),
        "est_saved_bytes": (plan_r.plan.estimate_saved_bytes(
            cfg, batch * seq, batch=batch) if with_residuals else None),
    }
    if with_roofline:
        from repro.roofline import analyze_compiled
        shape = InputShape("bench", seq, batch, "train")
        report["roofline"] = analyze_compiled(compiled, cfg, shape, n_chips=1)
    return report


def train_step_memory_entries(cfg, *, batch: int = 2, seq: int = 32) -> list:
    """Whole-train-step (loss + grads + AdamW) memory via the train loop's
    ``compiled_step_memory`` hook."""
    from repro.configs.base import TrainConfig
    from repro.train.loop import compiled_step_memory
    tcfg = TrainConfig(batch_size=batch, seq_len=seq)
    mem = compiled_step_memory(cfg, tcfg)
    prefix = f"memory/{cfg.name}/train_step"
    # The step's resolved backend and checkpoint plan ride in the meta —
    # stamped from the resolutions the compiled step actually used, not
    # re-read from the env/config.
    meta = {"batch": batch, "seq": seq, "gmm_backend": mem["gmm_backend"],
            "remat_plan": mem["remat_plan"]}
    return [
        entry(f"{prefix}/temp_bytes", mem["temp_bytes"],
              kind="temp_bytes", unit="bytes", tolerance_pct=100.0, **meta),
        entry(f"{prefix}/arg_bytes", mem["arg_bytes"],
              kind="arg_bytes", unit="bytes", tolerance_pct=20.0, **meta),
    ]


def memory_suite(*, small: bool = False) -> list:
    """All memory-axis entries: (config x policy x backend) reports, the
    roofline coupling, and the train-step axis.  The MoE config sweeps the
    grouped-GEMM backend axis; the dense config carries the full FFN tag set
    (and therefore the strict policy ordering)."""
    auto = GB.resolve(None).name
    # Entry names embed the backend, so the committed baseline must only
    # contain names every CI leg reproduces: the portable `segment` is always
    # swept (and is the dense config's only axis — it has no grouped GEMM);
    # the auto-resolved backend adds entries on JAX versions that have it,
    # which enter the gate once committed from such a version.
    plan = [(bench_config(), list(dict.fromkeys(["segment", auto]))),
            (bench_dense_config(), ["segment"])]
    batch, seq = (2, 32) if small else (4, 64)
    out = []
    for cfg, backends in plan:
        for policy in POLICY_ORDER:
            for i, backend in enumerate(backends):
                with_roofline = policy == "paper" and i == 0
                r = activation_memory_report(cfg, policy, backend=backend,
                                             batch=batch, seq=seq,
                                             with_roofline=with_roofline,
                                             with_residuals=(i == 0))
                prefix = f"memory/{cfg.name}/{policy}/{backend}"
                meta = {"batch": batch, "seq": seq,
                        "remat_plan": r["remat_plan"]}
                out.append(entry(f"{prefix}/temp_bytes", r["temp_bytes"],
                                 kind="temp_bytes", unit="bytes",
                                 tolerance_pct=100.0, **meta))
                out.append(entry(f"{prefix}/peak_bytes", r["peak_bytes"],
                                 kind="peak_bytes", unit="bytes",
                                 tolerance_pct=100.0, **meta))
                if i == 0:  # backend-independent accountants: record once
                    sim = memsim.simulate_peak(cfg, batch * seq, batch=batch,
                                               plan=policy, mode="single",
                                               base="grad")
                    out.append(entry(
                        f"peak_sim/{cfg.name}/{policy}/single", sim,
                        kind="peak_sim_bytes", unit="bytes",
                        tolerance_pct=SIM_PARITY_TOLERANCE_PCT, **meta))
                    out.append(entry(
                        f"memory/{cfg.name}/{policy}/residual_bytes",
                        r["residual_bytes"], kind="residual_bytes",
                        unit="bytes", tolerance_pct=20.0, **meta))
                    if r["est_saved_bytes"] is not None:
                        out.append(entry(
                            f"memory/{cfg.name}/{policy}/est_saved_bytes",
                            r["est_saved_bytes"], kind="est_saved_bytes",
                            unit="bytes", tolerance_pct=20.0, **meta))
                if with_roofline:
                    from repro.roofline import bench_entries
                    out += bench_entries(r["roofline"],
                                         f"memory/{cfg.name}/roofline")
    out += train_step_memory_entries(bench_config(), batch=batch, seq=seq)
    out += ep_saved_residual_entries(small=small)
    out += ep_peak_entries(small=small)
    return out


def ep_peak_entries(*, small: bool = False) -> list:
    """Measured XLA peaks AND simulated peaks of fwd+bwd under the
    expert-sharded modes (``ep`` and ``ep_a2a`` on a 1x2 debug mesh), one
    pair per registry plan — the distributed half of the simulator-parity
    matrix (the single-device half lives in :func:`memory_suite`'s
    ``peak_sim/*/single`` entries).  Pairs are emitted atomically so
    :func:`sim_parity_failures` never sees an unmatched sim entry."""
    from repro.launch.mesh import make_debug_mesh
    if len(jax.devices()) < 2:
        import sys
        print("# skipping EP peak entries: need >= 2 host devices "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
              "before jax initializes; `python -m repro.bench` does this "
              "automatically)", file=sys.stderr)
        return []
    mesh = make_debug_mesh(1, 2)
    n_model = mesh.shape["model"]
    batch, seq = (2, 32) if small else (4, 64)
    out = []
    for mode in ("ep", "ep_a2a"):
        cfg = bench_config().replace(moe_parallel=mode,
                                     gmm_backend="segment")
        for policy in POLICY_ORDER:
            c = cfg.replace(remat_policy=CK.resolve_plan(policy).spec)

            def loss(params, tokens):
                b = {"tokens": tokens, "labels": tokens}
                return T.train_loss(params, b, c, mesh=mesh)[0]

            args = _abstract_args(c, batch, seq)
            with mesh:
                compiled = jax.jit(jax.grad(loss)).lower(*args).compile()
            mem = compiled.memory_analysis()
            peak = (getattr(mem, "argument_size_in_bytes", 0)
                    + getattr(mem, "output_size_in_bytes", 0)
                    + getattr(mem, "temp_size_in_bytes", 0)
                    - getattr(mem, "alias_size_in_bytes", 0))
            sim = memsim.simulate_peak(c, batch * seq, batch=batch,
                                       plan=policy, mode=mode,
                                       n_model=n_model, base="grad")
            meta = {"batch": batch, "seq": seq, "mesh": "1x2",
                    "remat_plan": CK.resolve_plan(policy).spec}
            out.append(entry(f"memory/{cfg.name}/{policy}/{mode}/peak_bytes",
                             peak, kind="peak_bytes", unit="bytes",
                             tolerance_pct=100.0, **meta))
            out.append(entry(f"peak_sim/{cfg.name}/{policy}/{mode}", sim,
                             kind="peak_sim_bytes", unit="bytes",
                             tolerance_pct=SIM_PARITY_TOLERANCE_PCT, **meta))
    return out


def sim_parity_failures(entries: list) -> list:
    """The simulated-vs-measured peak gate: every ``peak_sim/<cfg>/<plan>/
    <mode>`` entry must agree with its measured counterpart — the
    ``memory/<cfg>/<plan>/segment/peak_bytes`` entry for ``single`` (the
    simulator models the portable segment backend's buffers; other backends'
    peaks are tracked but not parity-gated) or ``memory/<cfg>/<plan>/<mode>/
    peak_bytes`` for the sharded modes — within the sim entry's tolerance.
    Returns human-readable failure lines (empty == parity holds)."""
    by_name = {e["name"]: e for e in entries}
    fails = []
    for e in entries:
        if not e["name"].startswith("peak_sim/"):
            continue
        _, cfg_name, plan, sim_mode = e["name"].split("/")
        backend = "segment" if sim_mode == "single" else sim_mode
        want = f"memory/{cfg_name}/{plan}/{backend}/peak_bytes"
        measured = by_name.get(want)
        if measured is None:
            fails.append(f"PARITY {e['name']}: measured counterpart "
                         f"{want} missing from this run")
            continue
        tol = e["tolerance_pct"] or SIM_PARITY_TOLERANCE_PCT
        err = (e["value"] - measured["value"]) / max(measured["value"], 1.0)
        if abs(err) * 100.0 > tol:
            fails.append(
                f"PARITY {e['name']}: sim {int(e['value']):,} vs measured "
                f"{int(measured['value']):,} ({err * 100.0:+.1f}% "
                f"> +/-{tol:.0f}%)")
    return fails
