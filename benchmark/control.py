"""Readings that set the limits of the check of outputs, on the chip.

    python3 benchmark/control.py --workload conf6.zipf --seeds 11,12,13 \
        --variant program

Runs the cell once per seed in one process, each with a short window, and
prints one JSON line per run with the numbers compared.  ``--variant``:

* ``program``: the program as the configuration states it (lower readings);
* ``lower_precision``: the control: the reference put in the program's
  place with its matmul operands one precision below the configuration's
  (``precision.matmul_operands``): float8 (e4m3, per-tensor scales) for
  ``bfloat16``, which the program has no path for;
* a fault of ``faults.PLANTS`` planted under the timed path.

The benchmark's own runs never run this.
"""

import contextlib
import json
import os
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

#: matmul operand dtype of the control, one precision below the one the
#: configuration states
LOWER = {"bfloat16": "float8_e4m3fn"}


def reference_in_place(workload: str, seed: int, *, root=None,
                       devices=None) -> dict:
    """The check's numbers with the reference, in lower precision, as the
    program: no window, the same three steps.  Activations stay in the
    configuration's dtype; matmul operands go one precision lower."""
    import numpy as np
    from benchmark import check, harness, traffic
    root = root or harness.ROOT
    spec = harness.load_cell(workload, root)
    config = spec["config"]
    if devices is None:
        devices = harness.require_devices(spec["cell"]["chips"])
    harness.use_cache(root)
    _, tcfg, mesh = harness.program(config, devices)
    pool = traffic.batches(spec["traffic"], vocab=config["model"]["vocab_size"],
                           batch=tcfg.batch_size, seq_len=tcfg.seq_len,
                           seed_seq=np.random.SeedSequence(seed))
    low = harness.reference_run(
        config, pool, seed, mesh, cd=config["model"]["dtype"],
        md=LOWER[config["precision"]["matmul_operands"]])
    ref = harness.reference_run(config, pool, seed, mesh)
    values = check.compare(low, ref)
    correct, checks = check.verdict(values, spec["limits"])
    return {"correct": correct, "checks": checks, "values": values,
            "metrics": {}}


def main(argv=None) -> int:
    import argparse
    from benchmark import faults, harness
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variant", default="program")
    ap.add_argument("--seconds", type=float, default=2.0)
    a = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    plant = contextlib.nullcontext
    if a.variant not in ("program", "lower_precision"):
        plant = faults.PLANTS[a.variant]
    for seed in (int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        if a.variant == "lower_precision":
            r = reference_in_place(a.workload, seed)
        else:
            with plant():
                r = harness.run(a.workload, seed, a.seconds, False, t0)
        print(json.dumps({"control": a.variant, "workload": a.workload,
                          "seed": seed, "correct": r["correct"],
                          "checks": r["checks"], "values": r.get("values"),
                          "seconds": time.perf_counter() - t0,
                          "tokens_per_s": r["metrics"].get(
                              "tokens_per_s", {}).get("value")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
