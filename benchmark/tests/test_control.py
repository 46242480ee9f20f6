"""The control, the reference in the program's place with float8 matmul
operands (one precision below the bfloat16 operands every configuration
states), at small widths on the CPU: it reads wider gaps than the program on
the same seed, and comes out not correct."""

import json

import jax
import pytest

from benchmark import control
from benchmark.tests import small


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small.make_root(tmp_path_factory.mktemp("checkout"))


def run_control(root, cell):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    w = [w for w in spec["workloads"] if w["name"] == cell][0]
    return control.reference_in_place(cell, 5, root=root,
                                      devices=jax.devices()[:w["chips"]])


@pytest.mark.parametrize("cell", small.cells())
def test_lower_precision_reads_wider_gaps(root, cell):
    program = small.run(root, cell)["checks"]
    low = run_control(root, cell)["checks"]
    for name, c in low.items():
        assert c["value"] > program[name]["value"], (name, low, program)


@pytest.mark.parametrize("cell", small.cells())
def test_float8_control_is_caught(root, cell):
    assert not run_control(root, cell)["correct"]
