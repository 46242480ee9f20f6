"""Model FLOPs and grouped-GEMM work of paper conf6, against a hand count."""

import json

from benchmark import flops
from benchmark.tests.small import ROOT

CONF6 = json.loads((ROOT / "benchmark/configs/paper_conf6.json").read_text())


def test_conf6_forward_flops_by_part():
    # d=1024, 8 heads of 128, 16 experts, top-4, h=4096, V=32000, S=1024.
    parts = flops.forward_flops_per_token(CONF6["model"], 1024)
    assert parts == {
        "router": 2 * 1024 * 16,                                # 32,768
        "attn_proj": 2 * 1024 * 3 * 1024 + 2 * 1024 * 1024,     # 8,388,608
        "attn_core": 4 * 1024 * 512.5,     # QK and PV over (S+1)/2 keys
        "experts": 4 * 3 * 2 * 1024 * 4096,                     # 100,663,296
        "unembed": 2 * 1024 * 32000,                            # 65,536,000
    }
    assert flops.train_flops_per_token(CONF6["model"], 1024) == 3 * 176_719_872


def test_conf6_gmm_work():
    f, b = flops.gmm_work(CONF6["model"], 16 * 1024)
    rows = 16 * 1024 * 4
    assert f == 9 * 2 * rows * 1024 * 4096                      # 4.95 TFLOP
    assert b == 9 * (rows * (1024 + 4096) + 16 * 1024 * 4096) * 4


def test_window_caps_keys():
    assert flops.avg_keys(8, 0) == 4.5
    assert flops.avg_keys(8, 2) == (1 + 2 * 7) / 8
