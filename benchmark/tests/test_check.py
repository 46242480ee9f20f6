"""The comparison that decides ``correct``, on hand-made leaf norms."""

import math

from benchmark import check

# Four leaves; the median reference gradient norm is 1.5, so "tiny" (under
# a thousandth of it) is left out of the update numbers.
REF = {"loss": [10.0, 10.0, 10.0],
       "grad": {"a": 1.0, "b": 2.0, "c": 3.0, "tiny": 1e-4},
       "update": {"a": 1.0, "b": 1.0, "c": 1.0, "tiny": 1.0}}
PROG = {"loss": [10.0, 10.1, 10.0],
        "grad": {"a": 1.0, "b": 2.0, "c": 3.3, "tiny": 1e-4},
        "update": {"a": 1.1, "b": 1.0, "c": 1.0, "tiny": 5.0}}


def test_worst_leaf_and_the_rest():
    v = check.compare(PROG, REF)
    assert math.isclose(v["loss"], 0.01)
    # c: |3.3 - 3| / max(3, 1.5) = 0.1; the rest 0.
    assert math.isclose(v["grad_norm"], 0.1)
    assert v["grad_norm_rest"] == 0.0
    # "tiny" is still under Adam's round-off and left out; a: 0.1 / 1.
    assert math.isclose(v["update_norm"], 0.1)
    assert v["update_norm_rest"] == 0.0
    v = check.compare(dict(PROG, grad=dict(PROG["grad"], a=1.15)), REF)
    assert math.isclose(v["grad_norm_rest"], 0.1 / 3)


def test_verdict_compares_only_limited_numbers():
    v = check.compare(PROG, REF)
    ok, checks = check.verdict(v, {"grad_norm": 0.11})
    assert ok and list(checks) == ["grad_norm"]
    ok, _ = check.verdict(v, {"grad_norm": 0.09})
    assert not ok
    ok, _ = check.verdict(dict(v, grad_norm=math.nan), {"grad_norm": 1.0})
    assert not ok
