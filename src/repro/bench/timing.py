"""Kernel/backend timing axis of the bench harness (paper §5.2 analogues).

Refactored out of the old ``benchmarks/kernel_bench.py`` script into an
importable suite: fused vs unfused SwiGLU HLO traffic, Pallas interpret-mode
kernel wall time, the grouped-GEMM backend comparison, and one train-step
timing probe through ``train.loop``'s ``step_hook``.

Timing protocol: ``median_time_us`` — compile + ``warmup`` untimed calls,
then the median of ``iters`` individually ``jax.block_until_ready``-fenced
calls.  Medians, not means: a single GC pause or CI-runner hiccup must not
move the recorded number.  Wall-clock entries are informational
(``tolerance_pct=None``) — this container/CI measures CPU interpret paths —
while HLO flops/bytes are deterministic and gated.

Exception to "wall time is informational": the ``kernels/fused_path/*``
entries are pair-gated against each other in the SAME run by
:func:`fused_gate_failures` (wired into ``repro.bench --check``) — relative
ordering on one machine is meaningful even when absolute numbers are not.
"""

from __future__ import annotations

import statistics
import time

import jax
import jax.numpy as jnp

from repro.bench.record import entry


def median_time_us(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall time of ``fn(*args)`` in microseconds, each call fenced
    with ``block_until_ready`` so async dispatch cannot hide work."""
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(samples)


def hlo_cost(fn, *args) -> tuple[float, float]:
    """(flops, bytes accessed) from XLA cost analysis of the jitted ``fn``."""
    c = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(c, list):
        c = c[0]
    return float(c.get("flops", 0)), float(c.get("bytes accessed", 0))


def swiglu_traffic_entries(L=4096, d=1024, h=4096, dtype=jnp.bfloat16) -> list:
    """HLO traffic of fwd+bwd SwiGLU: naive autodiff (saves every elementwise
    intermediate) vs the paper checkpoint policy (save A/B, recompute SiLU)."""
    sds = jax.ShapeDtypeStruct
    x, w1, w2 = sds((L, d), dtype), sds((d, h), dtype), sds((d, h), dtype)

    def naive(x, w1, w2):
        return (jax.nn.silu(x @ w1) * (x @ w2)).astype(jnp.float32).sum()

    from repro.core.checkpoint import FFN_A, FFN_B, POLICIES, tag

    def paper_ckpt(x, w1, w2):
        def inner(x):
            a = tag(x @ w1, FFN_A)
            b = tag(x @ w2, FFN_B)
            return jax.nn.silu(a) * b
        y = jax.checkpoint(inner, policy=POLICIES["paper_min"])(x)
        return y.astype(jnp.float32).sum()

    meta = {"L": L, "d": d, "h": h}
    out = []
    for name, f in (("naive", naive), ("paper_ckpt", paper_ckpt)):
        fl, by = hlo_cost(jax.grad(f, argnums=(0, 1, 2)), x, w1, w2)
        out.append(entry(f"kernels/swiglu_traffic/{name}/flops", fl,
                         kind="flops", unit="flop", tolerance_pct=20.0, **meta))
        out.append(entry(f"kernels/swiglu_traffic/{name}/bytes", by,
                         kind="bytes_accessed", unit="bytes",
                         tolerance_pct=100.0, **meta))
    return out


def pallas_kernel_entries(L=1024, d=256, h=512, iters=5) -> list:
    """Wall time of the Pallas fused-SwiGLU kernel in interpret mode
    (correctness-path cost only — not representative of TPU speed)."""
    from repro.kernels.fused_swiglu import fused_swiglu_fwd
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (L, d), jnp.float32)
    w1 = jax.random.normal(key, (d, h), jnp.float32) * 0.05
    w2 = jax.random.normal(key, (d, h), jnp.float32) * 0.05
    us = median_time_us(fused_swiglu_fwd, x, w1, w2, warmup=1, iters=iters)
    return [entry("kernels/pallas_fused_swiglu_interpret/time", us,
                  kind="time_us", unit="us", L=L, d=d, h=h)]


def gmm_backend_entries(S=2048, d=256, h=512, E=8, iters=5, *,
                        include_pallas=False) -> list:
    """Every available grouped-GEMM backend on one routed workload: median
    wall time of fwd + dw plus the jitted forward's HLO flops/bytes.

    ``pallas`` runs in interpret mode on CPU — wall time there measures the
    interpreter, not the kernel, so it is opt-in."""
    from repro.core import gmm_backend as GB
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    lhs = jax.random.normal(ks[0], (S, d), jnp.float32)
    rhs = jax.random.normal(ks[1], (E, d, h), jnp.float32) * 0.05
    dout = jax.random.normal(ks[2], (S, h), jnp.float32)
    base = S // E
    gs = jnp.asarray([base] * (E - 1) + [S - base * (E - 1)], jnp.int32)

    out = []
    meta = {"S": S, "d": d, "h": h, "E": E}
    for name in GB.backend_names():
        if name == "pallas" and not include_pallas:
            continue

        def fwd(lhs, rhs, gs, _name=name):
            return GB.gmm(lhs, rhs, gs, backend=_name)

        def dw(lhs, dout, gs, _name=name):
            return GB.gmm_dw(lhs, dout, gs, backend=_name)

        fl, by = hlo_cost(fwd, lhs, rhs, gs)
        jf, jd = jax.jit(fwd), jax.jit(dw)
        us = median_time_us(lambda: (jf(lhs, rhs, gs), jd(lhs, dout, gs)),
                            warmup=1, iters=iters)
        out.append(entry(f"kernels/gmm_backend/{name}/time", us,
                         kind="time_us", unit="us", **meta))
        out.append(entry(f"kernels/gmm_backend/{name}/flops", fl,
                         kind="flops", unit="flop", tolerance_pct=20.0, **meta))
        out.append(entry(f"kernels/gmm_backend/{name}/bytes", by,
                         kind="bytes_accessed", unit="bytes",
                         tolerance_pct=100.0, **meta))
    return out


def fused_path_entries(L=128, d=64, h=128, E=8, k=2, iters=3) -> list:
    """The fused dispatch→GEMM→combine layer vs the unfused Pallas kernel
    composition it replaces, on one routed MoE shape (interpret mode):
    median fwd+grad wall time plus the saved-residual accounting — how many
    ``(L·k, h)`` / ``(L·k, d)`` slot buffers autodiff saves, and their bytes.

    The time entries are informational against the *baseline* (CI wall time
    drifts) but load-bearing against *each other*:
    :func:`fused_gate_failures` pairs them in the same run — same machine,
    same interpreter — exactly like the memory suite's sim-parity gate."""
    from repro.core.checkpoint import saved_residuals
    from repro.core.moe_layer import moe_ffn_blaze
    from repro.core.routing import build_dispatch, top_k_gating
    from repro.kernels.ops import moe_ffn_blaze_pallas

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (L, d), jnp.float32)
    wg = jax.random.normal(ks[1], (d, E), jnp.float32) * 0.1
    w1 = jax.random.normal(ks[2], (E, d, h), jnp.float32) * 0.05
    w2 = jax.random.normal(ks[3], (E, d, h), jnp.float32) * 0.05
    w3 = jax.random.normal(ks[4], (E, h, d), jnp.float32) * 0.05
    g = top_k_gating(x, wg, k)
    disp = build_dispatch(g.topk_experts, E)
    gates = g.topk_weights
    S = L * k

    def layer(label):
        if label == "fused":
            def f(x, w1, w2, w3, gates):
                return moe_ffn_blaze(x, gates, disp, w1, w3, w2,
                                     backend="pallas_fused")
        else:
            def f(x, w1, w2, w3, gates):
                return moe_ffn_blaze_pallas(x, gates, disp, w1, w3, w2,
                                            backend="pallas")
        return f

    def slot_buffers(label):
        n, nbytes = 0, 0
        for aval, src in saved_residuals(
                layer(label), x, w1, w2, w3, gates):
            if "from the argument" in str(src):
                continue
            if getattr(aval, "shape", None) in ((S, h), (S, d)):
                n += 1
                nbytes += aval.size * aval.dtype.itemsize
        return n, nbytes

    def grad_fn(label):
        f = layer(label)

        def loss(x, w1, w2, w3, gates):
            return (f(x, w1, w2, w3, gates).astype(jnp.float32) ** 2).sum()

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))

    meta = {"L": L, "d": d, "h": h, "E": E, "k": k}
    out = []
    for label in ("fused", "unfused_pallas"):
        us = median_time_us(grad_fn(label), x, w1, w2, w3, gates,
                            warmup=1, iters=iters)
        n, nbytes = slot_buffers(label)
        gated = 0.0 if label == "fused" else None   # fused counts must be 0
        out.append(entry(f"kernels/fused_path/{label}/time", us,
                         kind="time_us", unit="us", **meta))
        out.append(entry(f"kernels/fused_path/{label}/slot_buffers", n,
                         kind="count", unit="buffers", tolerance_pct=gated,
                         **meta))
        out.append(entry(f"kernels/fused_path/{label}/slot_residual_bytes",
                         nbytes, kind="bytes", unit="bytes",
                         tolerance_pct=gated, **meta))
    return out


def fused_gate_failures(entries: list) -> list:
    """Same-run pairing gates for the fused MoE path (the analogue of the
    memory suite's ``sim_parity_failures``): (1) the fused layer's autodiff
    must save ZERO ``(L·k, ·)`` slot buffers — the whole point of the
    fusion — and (2) its fwd+grad wall time must not exceed the unfused
    Pallas composition measured in the *same* run.  Returns human-readable
    failure lines (empty == both gates hold)."""
    by_name = {e["name"]: e for e in entries}
    pre = "kernels/fused_path"
    fused_n = by_name.get(f"{pre}/fused/slot_buffers")
    fused_t = by_name.get(f"{pre}/fused/time")
    ref_t = by_name.get(f"{pre}/unfused_pallas/time")
    if fused_n is None and fused_t is None and ref_t is None:
        # No fused_path family at all (synthetic/legacy record): nothing to
        # pair.  Fresh runs always emit the family via ``kernels_suite``,
        # and the CI workflow asserts its presence independently.
        return []
    if fused_n is None or fused_t is None or ref_t is None:
        return [f"FUSED {pre}/* family incomplete in this run "
                "(regenerate the record with the current suite)"]
    fails = []
    if fused_n["value"] != 0:
        fails.append(f"FUSED {pre}/fused/slot_buffers: "
                     f"{int(fused_n['value'])} (L*k, .) buffer(s) in the "
                     "saved-residual set; the fused path must save none")
    if fused_t["value"] > ref_t["value"]:
        fails.append(f"FUSED {pre}/fused/time: {fused_t['value']:.0f}us vs "
                     f"unfused pallas {ref_t['value']:.0f}us in the same "
                     "run; the fused kernels must not be slower")
    return fails


def parallel_bench_config():
    """The MoE shape the ``parallel/*`` family benches: h ≈ 3d with a tight
    exchange capacity — the region where the roofline cost model predicts
    the token exchange beats replicated EP outright (and where the measured
    CPU ranking agrees, with a wide margin on both sides).  h % 4 != 0
    keeps tp out of the ranking on the 4-way model axis, mirroring the
    awkward-ff paper configs."""
    from repro.configs import get_config
    return get_config("mixtral_8x7b").reduced().replace(
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        vocab_size=128, sliding_window=16, attn_chunk=16,
        num_experts=8, top_k=2, d_model=64, moe_d_ff=198,
        moe_a2a_capacity=1.0)


def parallel_entries(L: int = 2048, iters: int = 5) -> list:
    """MoE distribution modes timed on the 8-virtual-device (2 data x 4
    model) debug mesh, next to the roofline cost model's predictions for
    the SAME config x mesh x slab — the measurement half of the ``auto``
    optimizer's validation loop.

    Per mode: median fwd+grad wall time of one jitted ``moe_sublayer`` call
    (informational vs the baseline — CI wall time drifts) plus the
    predicted ``t_total`` entry.  :func:`parallel_gate_failures` pairs them
    in the same run: the predicted ep vs ep_a2a ranking must agree with the
    measured one, and the chunked-overlap path must not be slower than the
    unchunked exchange."""
    if len(jax.devices()) < 8:
        import sys
        print("# skipping parallel entries: need >= 8 host devices "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
              "before jax initializes; `python -m repro.bench` does this "
              "automatically)", file=sys.stderr)
        return []
    from repro import roofline
    from repro.launch.mesh import make_debug_mesh
    from repro.models.moe_block import init_moe_params, moe_sublayer

    cfg = parallel_bench_config()
    mesh = make_debug_mesh(2, 4)
    decision = roofline.select_moe_parallel(cfg, mesh, L)
    pred = {c.mode: c for c in decision.table}
    p = init_moe_params(jax.random.PRNGKey(0), cfg, cfg.d_model)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, L, cfg.d_model),
                          jnp.float32)
    meta = {"L": L, "d": cfg.d_model, "h": cfg.moe_d_ff,
            "E": cfg.num_experts, "k": cfg.top_k,
            "capacity": cfg.moe_a2a_capacity, "mesh": "2x4"}

    def timed(mode, chunks):
        c = cfg.replace(moe_parallel=mode, moe_a2a_chunks=chunks)

        def loss(x, p):
            y, _ = moe_sublayer(x, p, c, mesh=mesh, dp_axes=("data",))
            return (y.astype(jnp.float32) ** 2).mean()

        f = jax.jit(jax.value_and_grad(loss))
        with mesh:
            # warmup=2: the first post-compile call still carries allocator
            # warmup on the 8-virtual-device host mesh, and the chunked gate
            # pairs wall times at a few-percent resolution.
            return median_time_us(f, x, p, warmup=2, iters=iters)

    out = [entry("kernels/parallel/auto_mode",
                 float(decision.mode == "ep_a2a"), kind="count", unit="bool",
                 tolerance_pct=0.0, resolved=decision.mode,
                 source=decision.source, **meta)]
    for label, mode, chunks in (("ep", "ep", 1), ("ep_a2a", "ep_a2a", 1),
                                ("ep_a2a_chunked", "ep_a2a", 2)):
        us = timed(mode, chunks)
        out.append(entry(f"kernels/parallel/{label}/time", us,
                         kind="time_us", unit="us", chunks=chunks, **meta))
        pc = pred[mode]
        out.append(entry(f"kernels/parallel/{label}/predicted",
                         pc.t_total_s * 1e6 if chunks == 1 else
                         _chunked_predicted_us(cfg, mesh, L, chunks),
                         kind="time_us", unit="us", chunks=chunks,
                         feasible=pc.feasible, **meta))
    return out


def _chunked_predicted_us(cfg, mesh, L, chunks) -> float:
    """Predicted t_total of the chunked-overlap exchange (the cost model
    reads ``cfg.moe_a2a_chunks``)."""
    from repro import roofline
    d = roofline.select_moe_parallel(
        cfg.replace(moe_a2a_chunks=chunks), mesh, L)
    return next(c.t_total_s for c in d.table if c.mode == "ep_a2a") * 1e6


#: measured chunked/unchunked slack: XLA's async-collective overlap does not
#: exist on the CPU host backend, so the chunked path only has to hold
#: parity there, not win — and host-mesh wall clocks pair at ~±10% noise
#: (repeated solo runs of the same binary span 0.95-1.13x), so the gate
#: only catches gross regressions such as a serialized per-chunk sync.
PARALLEL_CHUNK_TOL = 1.25


def parallel_gate_failures(entries: list) -> list:
    """Same-run pairing gates for the ``parallel/*`` family: (1) the cost
    model's predicted ep vs ep_a2a ranking must agree with the measured
    ranking of the SAME run, (2) the chunked-overlap exchange must not be
    slower than the unchunked one (within :data:`PARALLEL_CHUNK_TOL` — CPU
    runners have no async-collective overlap to win with), and (3) ``auto``
    must have resolved to the predicted winner.  Returns human-readable
    failure lines (empty == all gates hold)."""
    by_name = {e["name"]: e for e in entries}
    pre = "kernels/parallel"
    names = (f"{pre}/ep/time", f"{pre}/ep_a2a/time",
             f"{pre}/ep/predicted", f"{pre}/ep_a2a/predicted",
             f"{pre}/ep_a2a_chunked/time", f"{pre}/auto_mode")
    got = [by_name.get(n) for n in names]
    if all(g is None for g in got):
        # No parallel family at all (device-starved/legacy record): nothing
        # to pair.  The CI workflow asserts the family's presence
        # independently on the 8-device legs.
        return []
    if any(g is None for g in got):
        return [f"PARALLEL {pre}/* family incomplete in this run "
                "(regenerate the record with the current suite)"]
    ep_t, a2a_t, ep_p, a2a_p, ch_t, auto = (g["value"] for g in got)
    fails = []
    if (ep_p < a2a_p) != (ep_t < a2a_t):
        fails.append(
            f"PARALLEL predicted ranking disagrees with measured: "
            f"predicted ep={ep_p:.0f}us vs ep_a2a={a2a_p:.0f}us, measured "
            f"ep={ep_t:.0f}us vs ep_a2a={a2a_t:.0f}us in the same run")
    if ch_t > a2a_t * PARALLEL_CHUNK_TOL:
        fails.append(
            f"PARALLEL {pre}/ep_a2a_chunked/time: {ch_t:.0f}us vs unchunked "
            f"{a2a_t:.0f}us in the same run; the chunked-overlap path must "
            f"not be slower (tol {PARALLEL_CHUNK_TOL:.2f}x)")
    want = "ep" if ep_p < a2a_p else "ep_a2a"
    resolved = by_name[f"{pre}/auto_mode"]["meta"].get("resolved")
    if resolved != want:
        fails.append(
            f"PARALLEL auto resolved to {resolved!r} but the cost model's "
            f"predicted winner in the same run is {want!r}")
    return fails


def train_step_entries(steps: int = 3) -> list:
    """Per-step wall time of the tiny-config train loop, collected through
    ``train.loop``'s ``step_hook`` (the hook the harness regresses against)."""
    from repro.bench.memory import bench_config
    from repro.configs.base import TrainConfig
    from repro.train.loop import train

    cfg = bench_config()
    tcfg = TrainConfig(total_steps=steps + 1, batch_size=2, seq_len=32,
                       log_every=10_000)
    times, backends = [], []

    def hook(step, m):
        times.append(m["step_s"])
        backends.append(m["gmm_backend"])   # resolved name, not the env var

    train(cfg, tcfg, log=lambda *_: None, step_hook=hook)
    # First step includes compile; report the median of the rest.
    us = statistics.median(times[1:]) * 1e6
    return [entry(f"kernels/train_step/{cfg.name}/time", us,
                  kind="time_us", unit="us", steps=steps,
                  compile_s=times[0], gmm_backend=backends[-1])]


def kernels_suite(*, small: bool = False) -> list:
    """All timing-axis entries.  ``small`` is the CI/test sweep."""
    out = []
    out += swiglu_traffic_entries(L=1024 if small else 4096)
    out += pallas_kernel_entries(L=256 if small else 1024,
                                 iters=3 if small else 5)
    out += gmm_backend_entries(S=512 if small else 2048,
                               iters=3 if small else 5,
                               include_pallas=small)
    out += fused_path_entries(L=64 if small else 128,
                              iters=3 if small else 5)
    # The parallel family keeps L=2048 even in the small sweep: at L=1024
    # the chunked exchange's per-hop fixed overhead (no async overlap on the
    # host backend) dominates the halved chunk and the parity gate turns
    # into a coin flip; at 2048 chunked holds parity or wins on CPU.
    out += parallel_entries(L=2048, iters=3 if small else 5)
    out += train_step_entries()
    return out


def legacy_rows(entries: list) -> list:
    """Project record entries onto the old ``(name, us, derived)`` CSV rows
    still emitted by ``benchmarks/run.py``."""
    rows = []
    for e in entries:
        us = e["value"] if e["kind"] == "time_us" else 0.0
        derived = ";".join(f"{k}={v}" for k, v in e["meta"].items())
        if e["kind"] != "time_us":
            derived = f"{e['kind']}={e['value']:.4g};{derived}"
        rows.append((e["name"].replace("/", "_"), us, derived.rstrip(";")))
    return rows
