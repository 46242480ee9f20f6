"""The scope table of a compiled train step, on a hand-written HLO text and
on a small step compiled on the CPU, and the six per-scope device metrics
on a hand trace."""

import re

import numpy as np
import pytest

from benchmark import scopes
from benchmark.harness import TraceContext
from benchmark.metrics import (attention_ms, combine_ms, expert_ffn_ms,
                               optimizer_ms, router_dispatch_ms,
                               unembed_loss_ms)

HLO = """HloModule jit_step_fn, is_scheduled=true

FileNames
1 "/src/model.py"

StackFrames
1 {file_location_id=1 parent_frame_id=1}

%fused_computation.3 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %exp.1 = f32[8]{0} exponential(%param_0.1), metadata={op_name="jit(step_fn)/jvp()/while/body/closed_call/checkpoint/rematted_computation/router/exp" stack_frame_id=1}
  ROOT %mul.4 = f32[8]{0} multiply(%exp.1, %exp.1), metadata={op_name="jit(step_fn)/jvp()/while/body/closed_call/router/mul"}
}

ENTRY %main.9 (p.1: f32[8], p.2: s32[]) -> (f32[8], s32[]) {
  %p.1 = f32[8]{0} parameter(0), metadata={op_name="params[\\'embed\\']"}
  %p.2 = s32[] parameter(1)
  %gather.2 = f32[8]{0} gather(%p.1, %p.2), metadata={op_name="jit(step_fn)/jvp(embed)/jit(_take)/gather"}
  %fusion.3 = f32[8]{0} fusion(%gather.2), kind=kLoop, calls=%fused_computation.3
  %ragged-dot-none.4 = f32[8]{0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %ragged-dot-none.5 = f32[8]{0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/experts/ragged_dot_general"}
  %gather.5 = f32[8]{0} gather(%ragged-dot-none.4, %p.2), metadata={op_name="jit(step_fn)/transpose(jvp(experts))/combine/gather"}
  %copy-start.6 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%gather.5)
  %while.7 = f32[8]{0} while(%gather.5), condition=%cond, body=%body, metadata={op_name="jit(step_fn)/jvp(attention)/while"}
  %add.8 = f32[8]{0} add(%while.7, %while.7), metadata={op_name="jit(step_fn)/optimizer/add"}
  ROOT %tuple.9 = (f32[8]{0}, s32[]) tuple(%add.8, %p.2)
}
"""


def test_scope_of():
    assert scopes.scope_of("jit(step_fn)/transpose(jvp(attention))/dot") \
        == "attention"
    # innermost wins
    assert scopes.scope_of("jit(f)/jvp(experts)/inner/combine/mul") \
        == "combine"
    assert scopes.scope_of(
        "jit(f)/transpose(jvp())/while/body/checkpoint/"
        "rematted_computation/dispatch/jit(take_along_axis)/lt") == "dispatch"
    assert scopes.scope_of("jit(f)/jvp()/while") == ""
    assert scopes.scope_of("reduce_sum") == ""


def test_table_of_hand_hlo():
    tab = scopes.table(HLO)
    assert tab == {
        "param_0.1": "", "exp.1": "router", "mul.4": "router",
        "p.1": "", "p.2": "", "gather.2": "embed",
        "fusion.3": "router",          # no op_name: its computation's scope
        # the TPU's ragged-dot rewrite names the custom call after itself
        "ragged-dot-none.4": "experts", "ragged-dot-none.5": "experts",
        "gather.5": "combine",
        "copy-start.6": "", "while.7": "attention", "add.8": "optimizer",
        "tuple.9": ""}


def _ctx(tr, steps=1):
    return TraceContext(tr=tr, model={}, seq_len=4, tokens_per_step=4,
                        steps=steps, chips=1, peaks={})


# Window [0, 200] ns on two devices; the ops carry the hand HLO's names.
TRACE = {
    "ops": {0: [(0.0, 20.0, "gather.2", "gather"),
                (20.0, 50.0, "fusion.3", "fusion"),
                (50.0, 90.0, "ragged-dot-none.4", "custom-call"),
                (80.0, 100.0, "gather.5", "gather"),      # overlaps 10
                (100.0, 160.0, "while.7", "while"),
                (110.0, 120.0, "gather.5", "gather"),     # inside the while
                (160.0, 170.0, "copy-start.6", "copy-start"),
                (170.0, 190.0, "add.8", "add")],
            1: [(0.0, 40.0, "ragged-dot-none.4", "custom-call"),
                (40.0, 60.0, "add.8", "add"),
                (60.0, 70.0, "unknown.1", "fusion")]},
    "host": [(0.0, 200.0, "bench.window")],
}


@pytest.mark.parametrize("reader,want", [
    (attention_ms, (60 + 0) / 2),
    (router_dispatch_ms, (30 + 0) / 2),
    (expert_ffn_ms, (40 + 40) / 2),
    (combine_ms, (20 + 10 + 0) / 2),
    (optimizer_ms, (20 + 20) / 2),
    (unembed_loss_ms, None),
])
def test_device_metrics_on_hand_trace(monkeypatch, reader, want):
    monkeypatch.setattr(scopes, "last_step_table",
                        lambda: scopes.table(HLO))
    got = reader.read(_ctx(TRACE, steps=2))
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want / 2 / 1e6)


def test_no_program_step_reads_nothing(monkeypatch):
    monkeypatch.setattr(scopes, "last_step_table", lambda: None)
    assert attention_ms.read(_ctx(TRACE)) is None


def test_table_of_small_cpu_step():
    """The step ``train`` last ran, lowered again from the program's own
    record: every scope is named, the table keys are the compiled module's
    instruction names and the grouped GEMMs fall in ``experts``."""
    from repro import obs
    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.train.loop import train
    cfg = get_config("mixtral_8x7b").reduced().replace(
        num_layers=1, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
        vocab_size=128, num_experts=4, top_k=2, moe_d_ff=64,
        sliding_window=0)
    tcfg = TrainConfig(batch_size=2, seq_len=32, total_steps=1,
                       log_every=1 << 30, checkpoint_every=0)
    rng = np.random.default_rng(0)
    t = rng.integers(0, cfg.vocab_size, (2, 32)).astype(np.int32)
    train(cfg, tcfg, batch_iterator=iter([{"tokens": t, "labels": t}]),
          log=lambda s: None)
    tab = scopes.last_step_table()
    assert set(tab.values()) == set(scopes.SCOPES) | {""}
    assert scopes.last_step_table() is tab          # compiled once
    fn, args = obs.last_step()
    text = fn.lower(*args).compile().as_text()
    assert set(tab) >= {ln.split(" = ")[0].split()[-1].lstrip("%")
                        for ln in text.splitlines()
                        if " = " in ln and ln.startswith("  ")}
    # the three grouped GEMMs of the forward and six of the backward (on
    # the CPU, XLA's ragged dot becomes dots over the groups)
    dots = [ln.split(" = ")[0].split()[-1].lstrip("%")
            for ln in text.splitlines() if re.search(r"\]\S* dot\(", ln)]
    assert sum(tab[n] == "experts" for n in dots) >= 9
