"""A run with the timed path broken underneath comes out not correct; a
sound run of the same size comes out correct."""

import pytest

from benchmark import faults
from benchmark.tests import small


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("cell", small.cells())
def test_sound_run_is_correct(root, cell):
    r = small.run(root, cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", ["frozen", "half_batch"])
@pytest.mark.parametrize("cell", small.cells())
def test_fault_is_caught(root, cell, fault):
    with faults.PLANTS[fault]():
        r = small.run(root, cell)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", small.cells(chips=4))
def test_missing_exchange_is_caught(root, cell):
    with faults.no_exchange():
        r = small.run(root, cell)
    assert not r["correct"], r["checks"]
