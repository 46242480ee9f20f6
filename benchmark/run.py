"""Run one cell of the benchmark on the chip(s) of this machine.

    python3 benchmark/run.py --workload conf6.zipf --seed 7 --seconds 25 --trace 0

Prints the cell's end-to-end metrics (``--trace 0``) or its per-layer
metrics from a profiler trace of a few steps (``--trace 1``) as the last
line of standard output, one JSON object; the numbers the check of outputs
compared, each beside its limit, are the last lines of standard error.
Exits non-zero, with no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from benchmark.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
