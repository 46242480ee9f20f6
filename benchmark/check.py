"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against the plain reference run over the same seeded
weights and the same first three batches:

* ``loss``: the largest relative gap of the step loss over steps 1-3;
* ``grad_norm``: by the worst parameter leaf, the gap between the program's
  and the reference's norm of the first gradient as the optimizer gets it
  (after clipping; the program's is read back from AdamW's first moment
  after one step), over the larger of the reference's norm of that leaf and
  of the median leaf;
* ``update_norm``: the same for the change of each leaf over the three
  steps.  Leaves whose reference gradient is below a thousandth of the
  median leaf's move under Adam by round-off alone and are left out;
* ``grad_norm_rest``, ``update_norm_rest``: the mean of the same gaps over
  the leaves other than the worst, which the round-off of one leaf (the
  router's, where a token's expert choice flips) does not set.
"""

from __future__ import annotations

import statistics

NUMBERS = ("loss", "grad_norm", "update_norm", "grad_norm_rest",
           "update_norm_rest")
#: a leaf whose first reference gradient is below this share of the median
#: leaf's is left out of ``update_norm``.
STILL_LEAF = 1e-3


def _leaf_gaps(prog: dict, ref: dict, leaves) -> list:
    floor = statistics.median(ref[n] for n in leaves)
    return [abs(prog[n] - ref[n]) / max(ref[n], floor, 1e-30)
            for n in leaves]


def compare(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref``: ``{"loss": [3 floats], "grad": {leaf: norm},
    "update": {leaf: norm}}``.  Returns ``{number: value}``."""
    if set(prog["grad"]) != set(ref["grad"]):
        raise ValueError("program and reference disagree on the leaves: "
                         f"{sorted(set(prog['grad']) ^ set(ref['grad']))}")
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"]))
    leaves = sorted(ref["grad"])
    g_med = statistics.median(ref["grad"].values())
    moving = [n for n in leaves if ref["grad"][n] >= STILL_LEAF * g_med]
    grad = _leaf_gaps(prog["grad"], ref["grad"], leaves)
    update = _leaf_gaps(prog["update"], ref["update"], moving)
    return {"loss": loss, "grad_norm": max(grad), "update_norm": max(update),
            "grad_norm_rest": statistics.mean(sorted(grad)[:-1]),
            "update_norm_rest": statistics.mean(sorted(update)[:-1])}


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {number: {"value", "limit"}}) over the numbers the cell
    gives a limit; a number with no limit (no fault or control
    separates it from sound runs) is not compared.  A number that is not
    finite fails."""
    out = {n: {"value": values[n], "limit": limits[n]} for n in NUMBERS
           if n in limits}
    ok = all(v["value"] == v["value"] and v["value"] <= v["limit"]
             for v in out.values())
    return ok, out
