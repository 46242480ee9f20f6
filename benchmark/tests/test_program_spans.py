"""The join of the program's span record to a trace, and the two host
metrics, on a hand trace and record whose idle splits are known; and the
join on a real trace of a small CPU run."""

import numpy as np
import pytest

from benchmark import program_spans, trace
from benchmark.harness import TraceContext
from benchmark.metrics import input_wait_ms, loop_wait_ms

US = 1_000.0                 # ns
C = 7.5e12                   # the record's clock runs this far ahead

# One step from its start (trace clock), in microseconds; the sync takes
# what is left of the step.
PARTS = [("train.next_batch", 0, 50), ("train.to_device", 50, 100),
         ("train.resolve", 100, 120), ("train.dispatch", 120, 200)]
# Steps -2 to 2, of uneven length; the window holds steps 0 to 2 and the
# record ends with the window's last step.
STARTS = [-2300 * US, -1000 * US, 0.0, 1200 * US, 2100 * US]
END = 3400 * US


def record(first=0):
    out = []
    for t0, t1 in list(zip(STARTS, STARTS[1:] + [END]))[first:]:
        out += [(n, t0 + a * US + C, t0 + b * US + C) for n, a, b in PARTS]
        out += [("train.sync", t0 + 200 * US + C, t1 - 100 * US + C),
                ("train.hook", t1 - 100 * US + C, t1 + C),
                ("train", t0 + C, t1 + C)]
    return out


def hand_trace(stick_out=-10 * US):
    """Device 0 is busy from 150 us into each step to 120 us before its
    end: idle 100 us under input, 50 under the loop's own work, 20 under
    the sync, 100 under the hook.  Device 1 is busy throughout.  Each
    window step's ``bench.hook`` starts 10 us after its ``train.hook`` and
    ends 10 us before it, or, the last, ``stick_out`` ns after it."""
    bounds = list(zip(STARTS, STARTS[1:] + [END]))
    ops0 = [(t0 + 150 * US, t1 - 120 * US, f"fusion.{i}", "fusion")
            for i, (t0, t1) in enumerate(bounds)]
    hooks = [(t1 - 90 * US, t1 - 10 * US, "bench.hook")
             for t0, t1 in bounds[2:]]
    s, _, n = hooks[-1]
    hooks[-1] = (s, END + stick_out, n)
    return {"ops": {0: ops0, 1: [(STARTS[0], END, "fusion.9", "fusion")]},
            "host": sorted(hooks + [(0.0, END, "bench.window")])}


def ctx(tr):
    return TraceContext(tr=tr, model={}, seq_len=4, tokens_per_step=4,
                        steps=3, chips=2, peaks={})


def test_join_places_record_on_trace_clock():
    spans, residual = program_spans.join(hand_trace(), record(), 3)
    assert residual == 0.0
    assert sorted(spans) == sorted((s - C, e - C, n) for n, s, e in record())


# The last bench.hook ending X after its train.hook leaves the offsets
# [X, 10 us] (less the record's lead): at their middle it sticks out
# (X - 10 us) / 2.
@pytest.mark.parametrize("stick_out,residual", [
    (5 * US, 0.0), (60 * US, 25 * US), (109 * US, 49.5 * US),
    (111 * US, None), (200 * US, None)])
def test_join_refuses_beyond_50_us(stick_out, residual):
    got = program_spans.join(hand_trace(stick_out), record(), 3)
    if residual is None:
        assert got is None
    else:
        assert got[1] == pytest.approx(residual)


def test_join_without_the_last_hook():
    """The ``bench.hook`` open as the trace stops may be missing."""
    tr = hand_trace()
    tr["host"] = [h for h in tr["host"] if h[0] < 3200 * US]
    spans, residual = program_spans.join(tr, record(), 3)
    assert residual == 0.0
    assert sorted(spans) == sorted((s - C, e - C, n) for n, s, e in record())


def test_join_needs_the_window_steps():
    tr = hand_trace()
    assert program_spans.join(tr, [], 3) is None
    assert program_spans.join(tr, record(first=3), 3) is None  # too few
    assert program_spans.join(tr, record(), 2) is None         # too many hooks
    # a record that does not end with the window pairs the wrong steps
    assert program_spans.join(tr, record()[:-3], 3) is None


# three window steps; device 1 never idles
@pytest.mark.parametrize("reader,want", [(input_wait_ms, 3 * 100 / 2),
                                         (loop_wait_ms, 3 * 50 / 2)])
def test_host_metrics(monkeypatch, reader, want):
    monkeypatch.setattr(program_spans, "record", record)
    assert reader.read(ctx(hand_trace())) == pytest.approx(
        want * US / 3 / 1e6)


@pytest.mark.parametrize("reader", [input_wait_ms, loop_wait_ms])
def test_host_metrics_refused_or_absent(monkeypatch, reader):
    monkeypatch.setattr(program_spans, "record", record)
    assert reader.read(ctx(hand_trace(200 * US))) is None
    monkeypatch.setattr(program_spans, "record", lambda: None)
    assert reader.read(ctx(hand_trace())) is None


def test_join_on_a_real_cpu_trace(tmp_path):
    """The program's record and the profiler's host events, from one small
    ``train`` under ``jax.profiler``, join within the tolerance."""
    import jax
    from repro import obs
    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.train.loop import train
    cfg = get_config("mixtral_8x7b").reduced().replace(
        num_layers=1, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
        vocab_size=128, num_experts=4, top_k=2, moe_d_ff=64,
        sliding_window=0)
    tcfg = TrainConfig(batch_size=2, seq_len=32, total_steps=6,
                       log_every=1 << 30, checkpoint_every=0)
    rng = np.random.default_rng(0)

    def batches():
        while True:
            t = rng.integers(0, cfg.vocab_size, (2, 32)).astype(np.int32)
            yield {"tokens": t, "labels": t}

    state = {}

    def hook(step, metrics):
        with jax.profiler.TraceAnnotation("bench.hook"):
            if step == 1:
                jax.profiler.start_trace(str(tmp_path))
                state["ann"] = jax.profiler.TraceAnnotation("bench.window")
                state["ann"].__enter__()
            elif step == 5:
                state["ann"].__exit__(None, None, None)
                jax.profiler.stop_trace()

    train(cfg, tcfg, batch_iterator=batches(), step_hook=hook,
          log=lambda s: None)
    tr = trace.load(str(tmp_path))
    assert len([1 for *_, n in tr["host"] if n == "bench.hook"]) >= 3
    joined = program_spans.join(tr, obs.spans(), 4)   # steps 2 to 5
    assert joined is not None
    assert 0.0 <= joined[1] <= program_spans.TOLERANCE_NS
