"""A checkout-shaped directory whose configurations are the benchmark's own
at small widths, for runs on the CPU."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SMALL_MODEL = {"d_model": 64, "num_heads": 4, "num_kv_heads": 2,
               "head_dim": 16, "vocab_size": 256, "num_experts": 4,
               "moe_d_ff": 128, "sliding_window": 48, "attn_chunk": 32}


#: a test-only cell on a (data, model) = (1, 4) mesh of host devices, so
#: the harness's sharded path (weights made under the program's sharding
#: rules, the expert exchange, the reference split over the mesh) runs in the
#: tests while no benchmark cell asks for four chips: Mixtral-shaped (bf16
#: compute, windowed attention, top-2) at small widths.
MESH_CELL = {"name": "small.ep4", "config": "small_ep4", "traffic": "zipf_docs",
             "chips": 4, "why": "test only"}
MESH_CONFIG = {
    "reference": "moe_transformer",
    "model": {"name": "small-ep4", "arch_type": "moe", "num_layers": 1,
              "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
              "head_dim": 16, "d_ff": 0, "vocab_size": 256,
              "ffn_act": "swiglu", "num_experts": 4, "top_k": 2,
              "moe_d_ff": 128, "block_pattern": ["attn_local_moe"],
              "sliding_window": 48, "rope_theta": 1000000.0,
              "aux_loss_weight": 0.02, "z_loss_weight": 0.0,
              "dtype": "bfloat16", "param_dtype": "float32",
              "attn_chunk": 32},
    "train": {"batch_size": 4, "seq_len": 64, "learning_rate": 0.0003,
              "warmup_steps": 100, "total_steps": 100000,
              "weight_decay": 0.1, "b1": 0.9, "b2": 0.95, "eps": 1e-08,
              "grad_clip": 1.0},
    "precision": {"matmul_operands": "bfloat16"},
    "mesh": {"data": 1, "model": 4},
}
#: set from CPU readings of seeds 5-7: sound runs 0.0026-0.0084
#: (grad_norm) and 0.0010-0.0033 (update_norm); the float8 control
#: 0.027-0.055 and 0.0078-0.015; half batch and no exchange 0.079 and above
#: on grad_norm, 0.030 and above on update_norm.
MESH_LIMITS = {"grad_norm": 0.02, "update_norm": 0.02}


def make_root(tmp: Path) -> Path:
    """BENCHMARK.json as committed, each configuration cut to small widths
    (batch 4 x 64 tokens, two experts a token) with its limits kept, plus
    the test-only mesh cell."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "benchmark" / "configs").mkdir(parents=True)
    for d in ("traffic", "limits"):
        shutil.copytree(ROOT / "benchmark" / d, tmp / "benchmark" / d)
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        m = cfg["model"]
        m.update({k: v for k, v in SMALL_MODEL.items()
                  if k in m or k == "attn_chunk"})
        m["top_k"] = 2
        if not m.get("sliding_window"):
            m["sliding_window"] = 0
        cfg["train"].update(batch_size=4, seq_len=64)
        (tmp / c["file"]).write_text(json.dumps(cfg))
    mesh_file = "benchmark/configs/small_ep4.json"
    (tmp / mesh_file).write_text(json.dumps(MESH_CONFIG))
    (tmp / "benchmark" / "limits" / f"{MESH_CELL['name']}.json").write_text(
        json.dumps(MESH_LIMITS))
    spec["configs"].append({"name": "small_ep4", "file": mesh_file})
    spec["workloads"].append(MESH_CELL)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def cells(chips: int | None = None) -> list[str]:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [w["name"] for w in spec["workloads"] + [MESH_CELL]
            if chips is None or w["chips"] == chips]


def run(root: Path, cell: str, seed: int = 5) -> dict:
    import time
    import jax
    from benchmark import harness
    chips = [w for w in json.loads((root / "BENCHMARK.json").read_text())
             ["workloads"] if w["name"] == cell][0]["chips"]
    return harness.run(cell, seed, 0.5, False, time.perf_counter(),
                       root=root, devices=jax.devices()[:chips])
