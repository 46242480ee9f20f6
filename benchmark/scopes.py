"""The layer of each device op of the train step, from the program's named
scopes.

The program names the layers of its train step with ``jax.named_scope``
(``SCOPES``).  A scope reaches the compiled HLO as part of each
instruction's ``op_name`` metadata, wrapped by the transformations that
made the instruction (``jvp(attention)/...``,
``transpose(jvp())/.../checkpoint/rematted_computation/router/top_k``).
``table`` maps every instruction name, over every computation, to the
innermost of the scopes in its ``op_name``, or ``""`` where it has none; a
device op of the trace (``trace.load``) carries its instruction's name.
The TPU compiler rewrites XLA's ragged dot into custom calls whose
``op_name`` is their own name (``ragged-dot-none``): an instruction that
names no scope and is one of ``gmm_ms``'s grouped GEMMs is the experts'.

``last_step_table`` lowers and compiles the step the program's ``train``
last ran (``repro.obs.last_step()``) once per process, and returns ``None``
where the program keeps no such step.  The compilation is the one the
harness made for ``memory_analysis``, so the persistent cache serves it.
"""

from __future__ import annotations

import re

from benchmark import trace
from benchmark.metrics import gmm_ms

SCOPES = ("embed", "attention", "router", "dispatch", "experts", "combine",
          "unembed_loss", "optimizer")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_WRAPPER = re.compile(r"[\w.\-]*\(|\)")
_GEMM = re.compile(gmm_ms.PATTERN)

_cached = None


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` in an ``op_name``, or ``""``."""
    found = ""
    for part in _WRAPPER.sub("/", op_name).split("/"):
        if part in SCOPES:
            found = part
    return found


def table(hlo_text: str) -> dict:
    """``{instruction name: scope}`` over every computation of an HLO
    module's text.  An instruction with no ``op_name`` that calls a
    computation (a fusion) takes the scope most of that computation's
    instructions carry; a grouped GEMM that names none is ``experts``."""
    out, members, calls = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        out[name] = scope_of(op.group(1)) if op else ""
        if not out[name] and _GEMM.search(name):
            out[name] = "experts"
        members.setdefault(comp, []).append(name)
        called = _CALLS.search(rest)
        if op is None and called is not None:
            calls[name] = called.group(1)
    for name, comp in calls.items():
        found = [out[n] for n in members.get(comp, ()) if out[n]]
        if found:
            out[name] = max(SCOPES, key=found.count)
    return out


def last_step_table():
    global _cached
    try:
        from repro import obs
    except ImportError:
        return None
    last = obs.last_step()
    if last is None:
        return None
    fn, args = last
    if _cached is None or _cached[0] is not fn:
        _cached = (fn, table(fn.lower(*args).compile().as_text()))
    return _cached[1]


def device_ms(ctx, names) -> float | None:
    """Device time per step of the ops in any of the scopes ``names``
    (milliseconds; the union of their intervals in the traced window),
    averaged over the devices; ``None`` where the program names no such
    scope or no such op ran."""
    tab = last_step_table()
    if tab is None:
        return None
    want = set(names)
    lo, hi = trace.window(ctx.tr)
    total = [trace.length(trace.clip(trace.union(
        ev for ev in evs if tab.get(ev[2]) in want), lo, hi))
        for evs in ctx.tr["ops"].values()]
    if not total or max(total) == 0:
        return None
    return sum(total) / len(total) / ctx.steps / 1e6
