"""The trace reduction on a hand-made trace whose answers are known, and on
a small trace recorded on the chip."""

import json
from pathlib import Path

import pytest

from benchmark import trace
from benchmark.harness import TraceContext
from benchmark.metrics import (collective_exposed_ms, device_idle_share,
                               gmm_ms, gmm_roofline)

# Two devices, window [0, 100] ns.  Device 0: a matmul 10-30, a ragged dot
# 25-40 (overlapping it), an all-reduce 50-70 of which 60-65 overlaps a
# fusion.  Device 1: a ragged dot 0-50, an all-to-all 80-90 alone.
HAND = {
    "ops": {0: [(10.0, 30.0, "dot.1", "dot"),
                (25.0, 40.0, "ragged-dot-none.3", "custom-call"),
                (50.0, 70.0, "all-reduce-start.7", "all-reduce-start"),
                (60.0, 65.0, "fusion.2", "fusion")],
            1: [(0.0, 50.0, "ragged-dot-none.1", "custom-call"),
                (80.0, 90.0, "all-to-all.2", "all-to-all")]},
    "host": [(0.0, 100.0, "bench.window"),
             (40.0, 50.0, "bench.hook"),
             (70.0, 80.0, "bench.next_batch")],
}


def ctx(tr, steps=1):
    m = {"d_model": 8, "num_experts": 2, "top_k": 1, "moe_d_ff": 8,
         "num_layers": 1, "dtype": "float32", "ffn_act": "swiglu"}
    return TraceContext(tr=tr, model=m, seq_len=4, tokens_per_step=4,
                        steps=steps, chips=2,
                        peaks={"bf16_flops": 1e12, "hbm_bw": 1e12})


def test_busy_union_and_idle():
    busy = trace.busy(HAND)
    assert busy[0] == [(10.0, 40.0), (50.0, 70.0)]
    assert busy[1] == [(0.0, 50.0), (80.0, 90.0)]
    # idle: device 0 50 of 100 ns, device 1 40 of 100 ns
    assert device_idle_share.read(ctx(HAND)) == pytest.approx(45.0)


def test_idle_gaps_tagged_by_host_span():
    gaps = dict(trace.idle_gaps(HAND))
    # device 0 gaps: 0-10 (loop), 40-50 (hook), 70-100 (loop, mid 85);
    # device 1 gaps: 50-80 (mid 65: loop), 90-100 (loop)
    assert gaps["bench.hook"] == pytest.approx(10 / 2 / 1e9)
    assert gaps["train.loop"] == pytest.approx((10 + 30 + 30 + 10) / 2 / 1e9)


def test_gmm_ms_and_roofline():
    # ragged dots: 15 ns on device 0, 50 ns on device 1, over 2 steps
    c = ctx(HAND, steps=2)
    assert gmm_ms.read(c) == pytest.approx((15 + 50) / 2 / 2 / 1e6)
    # 9 GEMMs x 2 x 4 rows x 8 x 8 = 4608 FLOP; bytes 9 x (4 x 16 + 128) x 4
    least = max(4608 / 1e12, 9 * (64 + 128) * 4 / 1e12)
    assert gmm_roofline.read(c) == pytest.approx(
        100 * least / 2 / (gmm_ms.read(c) / 1e3))


def test_collective_exposed():
    # device 0: all-reduce 20 ns, 5 hidden under the fusion; device 1: 10
    assert collective_exposed_ms.read(ctx(HAND)) == pytest.approx(
        (15 + 10) / 2 / 1e6)


def test_parse_op():
    text = ("%ragged-dot-none.4 = f32[65536,1024]{1,0:T(8,128)} custom-call("
            "s32[1]{0:T(128)} %get-tuple-element.1143), custom_call_target="
            "\"tpu_custom_call\"")
    assert trace.parse_op(text) == ("ragged-dot-none.4", "custom-call")
    text = ("%copy-start.8 = (f32[16,8]{1,0}, f32[16,8]{1,0}, u32[]{:S(2)}) "
            "copy-start(f32[16,8]{1,0} %fusion.3)")
    assert trace.parse_op(text) == ("copy-start.8", "copy-start")
    # an op is not matched by an operand's name
    text = "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %ragged-dot-none.4)"
    assert trace.parse_op(text) == ("fusion.9", "fusion")


def test_no_matching_ops_reads_nothing():
    tr = {"ops": {0: [(0.0, 10.0, "fusion.1", "fusion")]},
          "host": [(0.0, 20.0, "bench.window")]}
    assert gmm_ms.read(ctx(tr)) is None
    assert gmm_roofline.read(ctx(tr)) is None
    assert collective_exposed_ms.read(ctx(tr)) is None


RECORDED = Path(__file__).parent / "data"


@pytest.mark.parametrize("path", sorted(RECORDED.glob("*.json")),
                         ids=lambda p: p.stem)
def test_recorded_trace(path):
    rec = json.loads(path.read_text())
    tr = {"ops": {int(k): [tuple(e) for e in v]
                  for k, v in rec["trace"]["ops"].items()},
          "host": [tuple(e) for e in rec["trace"]["host"]]}
    lo, hi = trace.window(tr)
    for d, iv in trace.busy(tr).items():
        assert 0 < trace.length(iv) <= hi - lo
        hits = trace.matching(tr, gmm_ms.PATTERN)[d]
        assert trace.length(hits) <= trace.length(iv)
    for name, want in rec["expect"].items():
        got = {"gmm_ms": gmm_ms, "device_idle_share": device_idle_share,
               "collective_exposed_ms": collective_exposed_ms}[name].read(
            ctx(tr, steps=rec["steps"]))
        assert got == pytest.approx(want, rel=rec["tolerance"][name]), name
