"""One run of one benchmark cell: set-up, measured window, check of outputs.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file, ``traffic/<traffic>.json``, the limits of its check
of outputs in ``limits/<cell>.json``, the reference module the configuration
names under ``reference/`` and, in a traced run, one reader per per-layer
metric under ``metrics/``.

The window drives the program's own training loop,
``repro.train.loop.train``, through its ``batch_iterator=`` and
``step_hook=`` arguments.  One call of it runs the whole run: its first three
steps are the ones the check of outputs follows, the next two warm up, then
the window opens and the hook ends the call by raising once the window has
lasted ``seconds`` (or, traced, ``TRACE_STEPS`` steps).  A step's time is the
interval between consecutive hook calls, so fetching and moving the batch,
dispatch, the device's work and the per-step sync are all in it.

The hook reads the optimizer state after step 1 and the parameters after
step 3 from the loop's frame (``params``, ``opt_state``), and the jitted
step it ran (``step_fn``), since the loop hands neither to a hook.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
N_CHECK = 3          # steps the reference follows
N_WARM = 2           # steady steps between the checked ones and the window
TRACE_STEPS = 8      # steps in a traced window


class _WindowClosed(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def seed32(seed: int) -> np.uint32:
    """A 32-bit key seed that differs for every seed (PRNGKey keeps 32
    bits of a Python int).  Jitted code takes it as an argument, so that
    one compiled program serves every seed."""
    return np.random.SeedSequence(seed).generate_state(1)[0]


# ---------------------------------------------------------------------------
# What a cell is made of
# ---------------------------------------------------------------------------


def load_cell(name: str, root: Path = ROOT) -> dict:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "benchmark" / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads(
        (root / "benchmark" / "limits" / f"{name}.json").read_text())

    def listed(m):
        return name in m.get("workloads", [name])

    return {"cell": cell, "config": config, "traffic": traffic,
            "limits": limits,
            "end_to_end": [m for m in spec["end_to_end"] if listed(m)],
            "per_layer": [m for m in spec["per_layer"] if listed(m)]}


def require_devices(chips: int):
    """The devices the cell runs on; exits without a result where JAX finds
    no TPU or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"benchmark needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips]


def use_cache(root: Path) -> None:
    """JAX's persistent compilation cache at one fixed path in the checkout."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def program(config: dict, devices):
    """(ModelConfig, TrainConfig, mesh) of the program for a configuration."""
    from repro.configs.base import ModelConfig, TrainConfig
    m = dict(config["model"])
    m["block_pattern"] = tuple(m["block_pattern"])
    cfg = ModelConfig(**m)
    t = config["train"]
    tcfg = TrainConfig(**t, log_every=1 << 30, checkpoint_every=0)
    mesh = None
    if config.get("mesh"):
        from jax.sharding import Mesh
        axes = tuple(config["mesh"])
        shape = tuple(config["mesh"][a] for a in axes)
        mesh = Mesh(np.array(devices).reshape(shape), axes)
    return cfg, tcfg, mesh


def make_params(cfg, mesh, seed: int, dtype):
    """The program's parameter tree, made on the device(s) in one jitted
    call from the seed, sharded by the program's own rules under a mesh."""
    import jax
    from repro.models import transformer as T
    from benchmark import weights
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    out = None
    if mesh is not None:
        from repro import sharding as shd
        out = shd.to_shardings(mesh, shd.param_specs(shapes, mesh))
    fn = jax.jit(lambda s: weights.init_tree(s, shapes, dtype),
                 out_shardings=out)
    return fn(seed32(seed)), shapes


# ---------------------------------------------------------------------------
# The training loop's hook
# ---------------------------------------------------------------------------


class Window:
    """Feeds the pool to ``train`` and ends the call when the window closes."""

    def __init__(self, pool, seconds: float, *, traced: bool, seed: int,
                 b1: float, param_dtype, compiles: dict, log=None):
        import jax
        from benchmark import weights
        self.pool, self.seconds, self.traced = pool, seconds, traced
        self.b1, self.seed = b1, seed32(seed)
        self.losses, self.hook_t = [], []
        self.grad, self.update = None, None
        self.open_step = N_CHECK + N_WARM - 1
        self.t_open = self.t_close = None
        self.step_fn = self.shapes = None
        self.window_steps = 0
        self.overflow = 0.0
        self.trace_dir = None
        self._ann = None
        self.compiles = compiles
        self.compiles_open = self.compiles_close = None
        self.log = log or (lambda _: None)

        def norms(tree):
            flat = jax.tree_util.tree_flatten_with_path(tree)[0]
            return {weights.path_str(p): jax.numpy.linalg.norm(
                x.astype(jax.numpy.float32).reshape(-1)) for p, x in flat}

        def update_norms(params, s):
            p0 = weights.init_tree(s, params, param_dtype)
            return norms(jax.tree.map(
                lambda a, b: a.astype(jax.numpy.float32)
                - b.astype(jax.numpy.float32), params, p0))

        self._norms = jax.jit(norms)
        self._update_norms = jax.jit(update_norms)

    def batches(self):
        import jax
        i = 0
        while True:
            with jax.profiler.TraceAnnotation("bench.next_batch"):
                b = self.pool[i % len(self.pool)]
            i += 1
            yield b

    def hook(self, step, metrics):
        import jax
        frame = sys._getframe(1)
        if frame.f_code.co_name != "train" or "params" not in frame.f_locals:
            raise RuntimeError("step_hook is no longer called from "
                               "repro.train.loop.train with params in scope")
        with jax.profiler.TraceAnnotation("bench.hook"):
            now = time.perf_counter()
            self.hook_t.append(now)
            if step <= self.open_step:
                self.log(f"step {step} done")
            if step < N_CHECK:
                self.losses.append(float(metrics["loss"]))
            if step == 0:
                mu = frame.f_locals["opt_state"].mu
                self.grad = {k: float(v) / (1.0 - self.b1)
                             for k, v in self._norms(mu).items()}
            if step == N_CHECK - 1:
                self.update = {k: float(v) for k, v in
                               self._update_norms(frame.f_locals["params"],
                                                  self.seed).items()}
            if step == self.open_step:
                self._open(frame)
                self.hook_t[-1] = self.t_open    # the window's first step
            elif self.t_open is not None:
                self.window_steps += 1
                self.overflow = max(self.overflow,
                                    float(metrics["moe_overflow"]))
                if (self.window_steps >= TRACE_STEPS if self.traced
                        else now - self.t_open >= self.seconds):
                    self._close()
                    raise _WindowClosed

    def _open(self, frame):
        import jax
        f = frame.f_locals
        self.step_fn = f["step_fn"]
        sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                             sharding=x.sharding)
        self.shapes = (jax.tree.map(sds, f["params"]),
                       jax.tree.map(sds, f["opt_state"]),
                       jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                           x.shape, x.dtype), f["batch"]))
        if self.traced:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.trace_dir)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
        self.compiles_open = self.compiles["n"]
        self.t_open = time.perf_counter()

    def _close(self):
        import jax
        self.t_close = time.perf_counter()
        self.compiles_close = self.compiles["n"]
        if self.traced:
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()

    def step_times(self) -> list:
        i = self.open_step
        t = self.hook_t
        return [t[j + 1] - t[j] for j in range(i, len(t) - 1)]


# ---------------------------------------------------------------------------
# Reading the results
# ---------------------------------------------------------------------------


@dataclass
class TraceContext:
    """What a per-layer metric reader sees."""
    tr: dict
    model: dict
    seq_len: int
    tokens_per_step: int
    steps: int
    chips: int
    peaks: dict


def compiled_bytes(step_fn, shapes) -> tuple[int, dict]:
    """Bytes the step's executable needs on one device: arguments + outputs
    - aliased (donated) + temporaries, by ``memory_analysis``."""
    ma = step_fn.lower(*shapes).compile().memory_analysis()
    parts = {"argument": ma.argument_size_in_bytes,
             "output": ma.output_size_in_bytes,
             "alias": ma.alias_size_in_bytes,
             "temp": ma.temp_size_in_bytes}
    return (parts["argument"] + parts["output"] - parts["alias"]
            + parts["temp"]), parts


def reference_run(config: dict, pool, seed: int, mesh, cd="float32",
                  md=None) -> dict:
    """The reference over the first ``N_CHECK`` batches: step losses, leaf
    norms of the first clipped gradient and of the change after the
    steps.  Runs at the ``highest`` matmul precision; ``cd``/``md`` give it
    a lower precision for the control."""
    import jax
    import jax.numpy as jnp
    from benchmark import weights
    ref = importlib.import_module(f"benchmark.reference.{config['reference']}")
    m, t = config["model"], config["train"]
    table = ref.param_table(m)
    shapes = {k: jax.ShapeDtypeStruct(v, jnp.float32) for k, v in table.items()}
    shard = None
    if mesh is not None:
        shard = {k: _ref_sharding(mesh, k, v) for k, v in table.items()}
    pd = jnp.dtype(m.get("param_dtype", "float32"))
    init = jax.jit(lambda s: weights.init_tree(s, shapes, pd),
                   out_shardings=shard)
    zeros = jax.jit(lambda p: {k: jnp.zeros(v.shape, jnp.float32)
                               for k, v in p.items()}, out_shardings=shard)

    def step(p, mu, nu, i, tokens, labels):
        with jax.default_matmul_precision("highest"):
            return ref.train_step(p, mu, nu, i, tokens, labels, m, t,
                                  jnp.dtype(cd), md and jnp.dtype(md))

    step = jax.jit(step, donate_argnums=(0, 1, 2),
                   out_shardings=(shard, shard, shard, None, shard, None))
    norms = jax.jit(lambda tree: {k: jnp.linalg.norm(v.reshape(-1))
                                  for k, v in tree.items()})

    @jax.jit
    def delta(p, s):
        p0 = weights.init_tree(s, shapes, pd)
        return {k: jnp.linalg.norm((p[k].astype(jnp.float32)
                                    - p0[k].astype(jnp.float32)).reshape(-1))
                for k in p}

    p = init(seed32(seed))
    mu, nu = zeros(p), zeros(p)
    losses, grad, load = [], None, None
    for i in range(N_CHECK):
        b = pool[i]
        p, mu, nu, loss, g, counts = step(p, mu, nu, jnp.int32(i),
                                          b["tokens"], b["labels"])
        losses.append(float(loss))
        if i == 0:
            grad = {k: float(v) for k, v in norms(g).items()}
            c = np.asarray(counts)
            load = float(c.max() / c.mean())
        del g
    update = {k: float(v) for k, v in delta(p, seed32(seed)).items()}
    return {"loss": losses, "grad": grad, "update": update,
            "expert_load": load}


def _ref_sharding(mesh, path: str, shape):
    """Reference placement under a mesh, tensor-parallel over 'model': the
    experts' hidden axis, the attention heads and the vocabulary are split,
    the rest replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.shape.get("model", 1)
    name = path.split("/")[-1]
    axis = {"w1": -1, "w2": -1, "w3": -2, "wq": -1, "wk": -1, "wv": -1,
            "wo": -2, "embed": 0, "unembed": -1}.get(name)
    spec = [None] * len(shape)
    if axis is not None and len(shape) > 1 and shape[axis] % n == 0:
        spec[axis] = "model"
    return NamedSharding(mesh, P(*spec))


def device_report(devices) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def quantile90(xs) -> float:
    return statistics.quantiles(xs, n=10)[-1] if len(xs) > 1 else xs[0]


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def run(workload: str, seed: int, seconds: float, traced: bool, t_start: float,
        *, root: Path = ROOT, devices=None) -> dict:
    """Set-up, window and check of one cell; returns the result line.
    ``devices`` skips the look for a chip (tests)."""
    import jax
    from benchmark import check, flops, peaks as peaks_mod, traffic, trace
    from repro.train import loop

    spec = load_cell(workload, root)
    cell, config = spec["cell"], spec["config"]
    chips = cell["chips"]
    if devices is None:
        devices = require_devices(chips)
    kind = devices[0].device_kind
    pk = peaks_mod.peaks(kind) if devices[0].platform == "tpu" else None
    use_cache(root)
    compiles = {"n": 0, "hits": 0, "misses": 0}

    def on_duration(event, *_a, **_k):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1

    def on_event(event, **_k):
        if event == "/jax/compilation_cache/cache_hits":
            compiles["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            compiles["misses"] += 1

    def phase(name):
        log(f"[setup] {name} at {time.perf_counter() - t_start:.3f} s")

    phase("devices found")
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        cfg, tcfg, mesh = program(config, devices)
        t = config["train"]
        B, S = tcfg.batch_size, tcfg.seq_len
        pool = traffic.batches(spec["traffic"], vocab=cfg.vocab_size, batch=B,
                               seq_len=S, seed_seq=np.random.SeedSequence(seed))
        phase("traffic made")
        pd = jax.numpy.dtype(cfg.param_dtype)
        params, _ = make_params(cfg, mesh, seed, pd)
        jax.block_until_ready(params)
        phase("weights made")
        win = Window(pool, seconds, traced=traced, seed=seed, b1=t["b1"],
                     param_dtype=pd, compiles=compiles, log=phase)
        log(f"[setup] workload={workload} seed={seed} device={kind} "
            f"chips={chips} tokens/step={B * S} "
            f"setup_before_train_s={time.perf_counter() - t_start:.3f}")
        with mesh if mesh is not None else contextlib.nullcontext():
            try:
                loop.train(cfg, tcfg, mesh=mesh, params=params,
                           batch_iterator=win.batches(), step_hook=win.hook,
                           log=lambda s: None)
            except _WindowClosed:
                pass
            else:
                raise RuntimeError("train() ended before the window closed")
        del params
        setup_s = win.t_open - t_start
        times = win.step_times()
        window_s = win.t_close - win.t_open
        med = statistics.median(times)
        slow = sorted(((t_, i) for i, t_ in enumerate(times) if t_ > 1.5 * med),
                      reverse=True)
        log(f"[window] steps={win.window_steps} window_s={window_s:.6f} "
            f"setup_s={setup_s:.6f} step_ms_median={1e3 * med:.3f} "
            f"step_ms_max={1e3 * max(times):.3f} slow_steps(ms, index)="
            f"{[(round(1e3 * t_, 1), i) for t_, i in slow[:8]]} "
            f"n_slow={len(slow)} first_steps_s="
            f"{[round(b - a, 6) for a, b in zip(win.hook_t, win.hook_t[1:N_CHECK + N_WARM])]}")
        dev = device_report(devices)
        step_bytes, parts = compiled_bytes(win.step_fn, win.shapes)
        win.step_fn = win.shapes = None
        resolved = loop.make_train_step(cfg, tcfg, mesh=mesh)
        mode = "single"
        if mesh is not None:
            from repro.models.moe_block import resolve_moe_parallel
            mode = resolve_moe_parallel(cfg, mesh, B * S)
        log(f"[program] gmm_backend={resolved.resolved_backend.name} "
            f"moe_parallel={mode} remat_plan={resolved.resolved_plan.spec} "
            f"peak_sim_bytes={resolved.peak_sim_bytes} "
            f"max_moe_overflow={win.overflow}")
        log(f"[memory] peak_bytes_in_use={dev['memory_peak_bytes']} "
            f"compiled_step_bytes={step_bytes} {parts}")
        log(f"[compiles] programs_before_window={win.compiles_open} "
            f"in_window={win.compiles_close - win.compiles_open} "
            f"cache_hits={compiles['hits']} cache_misses={compiles['misses']}")
        tr = None
        if traced:
            tr = trace.load(win.trace_dir)
            shutil.rmtree(win.trace_dir, ignore_errors=True)
            lo, hi = trace.window(tr)
            busy = trace.busy(tr)
            dev["busy_s"] = (sum(trace.length(v) for v in busy.values())
                             / max(len(busy), 1) / 1e9)
            dev["window_s"] = (hi - lo) / 1e9
        gc.collect()

        # Check of outputs, once the program's state is gone.
        t_ref = time.perf_counter()
        ref = reference_run(config, pool, seed, mesh)
        log(f"[reference] seconds={time.perf_counter() - t_ref:.3f} "
            f"losses={ref['loss']} program_losses={win.losses} "
            f"expert_load_max_over_mean={ref['expert_load']:.4f}")
        values = check.compare(
            {"loss": win.losses, "grad": win.grad, "update": win.update}, ref)
        log(f"[check] {values}")
        correct, checks = check.verdict(values, spec["limits"])

        tokens = B * S
        metrics = {}
        if not traced:
            tps = win.window_steps * tokens / window_s
            fpt = flops.train_flops_per_token(config["model"], S)
            e2e = {"tokens_per_s": (tps, "tokens/s"),
                   "mfu": (100.0 * tps * fpt / (chips * pk["bf16_flops"])
                           if pk else float("nan"), "%"),
                   "step_hbm_gib": (step_bytes / 2**30, "GiB"),
                   "step_ms_p90": (1e3 * quantile90(times), "ms"),
                   "setup_s": (setup_s, "s")}
            for m in spec["end_to_end"]:
                v, unit = e2e[m["name"]]
                metrics[m["name"]] = {"value": v, "unit": unit}
        else:
            tc = TraceContext(tr=tr, model=config["model"], seq_len=S,
                              tokens_per_step=tokens,
                              steps=win.window_steps, chips=chips,
                              peaks=pk or {})
            for m in spec["per_layer"]:
                reader = importlib.import_module(f"benchmark.metrics.{m['name']}")
                v = reader.read(tc)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result = {"correct": correct, "attempted": win.window_steps,
                  "failed": 0, "metrics": metrics, "device": dev}
        if traced:
            result["breakdown"] = {"device_ops": trace.top_ops(tr),
                                   "idle_gaps": trace.idle_gaps(tr)}
        result["checks"] = checks
        return result
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def emit(result: dict) -> None:
    """The compared numbers as the last lines of standard error, and the
    result as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None, t_start: float | None = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    result = run(a.workload, a.seed, a.seconds, bool(a.trace), t_start)
    emit(result)
    return 0
