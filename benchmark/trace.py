"""Reduction of a profiler trace to device intervals and host spans.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events; everything after works on those events, so the tests can feed a
small recorded trace.  Times are nanoseconds on the trace's one clock.

* ``ops``: ``{device: [(start, end, name, kind)]}`` from each device
  plane's ``XLA Ops`` line, where an event's name is the op's HLO text
  (``%ragged-dot-none.4 = f32[..] custom-call(...), ...``): ``name`` is the
  instruction's own name (``ragged-dot-none.4``) and ``kind`` its opcode
  (``custom-call``), so that an op is never matched by its operands.
* ``host``: ``[(start, end, name)]`` for every host event whose name starts
  with ``bench.`` (the harness's own annotations).
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute)")


def parse_op(text: str) -> tuple[str, str]:
    """(name, opcode) of an HLO instruction's text."""
    name, _, rest = text.partition(" = ")
    name = name.strip().lstrip("%")
    rest = rest.strip()
    if rest.startswith("("):                  # tuple-shaped result
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:].strip()
    else:
        rest = rest.partition(" ")[2]
    return name, rest.partition("(")[0].strip()


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    pd = ProfileData.from_file(files[0])
    ops, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    evs.append((float(e.start_ns), float(e.end_ns),
                                *parse_op(e.name)))
            ops[int(m.group(1))] = sorted(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((float(e.start_ns), float(e.end_ns),
                                     e.name))
    return {"ops": ops, "host": sorted(host)}


def union(intervals) -> list:
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window(tr: dict) -> tuple[float, float]:
    """The traced window: the harness's ``bench.window`` host span."""
    spans = [(s, e) for s, e, n in tr["host"] if n == "bench.window"]
    if len(spans) != 1:
        raise RuntimeError(f"expected one bench.window span, got {spans}")
    return spans[0]


def matching(tr: dict, pattern) -> dict:
    """{device: merged intervals of the ops whose name or opcode matches}."""
    rx = re.compile(pattern) if isinstance(pattern, str) else pattern
    lo, hi = window(tr)
    return {d: clip(union(ev for ev in evs
                          if rx.search(ev[2]) or rx.search(ev[3])), lo, hi)
            for d, evs in tr["ops"].items()}


def busy(tr: dict) -> dict:
    lo, hi = window(tr)
    return {d: clip(union(evs), lo, hi) for d, evs in tr["ops"].items()}


def exposed(tr: dict, pattern=COLLECTIVE) -> dict:
    """{device: time of matching ops during which no other op runs}."""
    lo, hi = window(tr)
    rx = re.compile(pattern) if isinstance(pattern, str) else pattern
    out = {}
    for d, evs in tr["ops"].items():
        hit = [ev for ev in evs if rx.search(ev[2]) or rx.search(ev[3])]
        rest = [ev for ev in evs if not (rx.search(ev[2]) or rx.search(ev[3]))]
        out[d] = length(subtract(clip(union(hit), lo, hi),
                                 clip(union(rest), lo, hi)))
    return out


def top_ops(tr: dict, n: int = 10) -> list:
    """[[op name, seconds]] of the ops with the most device time, summed
    over the window and averaged over devices."""
    lo, hi = window(tr)
    tot = {}
    for evs in tr["ops"].values():
        for s, e, name, _ in evs:
            t = min(e, hi) - max(s, lo)
            if t > 0:
                tot[name] = tot.get(name, 0.0) + t
    nd = max(len(tr["ops"]), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / nd / 1e9] for k, v in best]


def idle_gaps(tr: dict, n: int = 10) -> list:
    """[[host span, seconds]]: the device's idle time in the window, summed
    by the harness annotation that was open on the host at the middle of
    each gap (``train.loop`` where none was), averaged over devices."""
    lo, hi = window(tr)
    spans = [(s, e, name) for s, e, name in tr["host"]
             if name != "bench.window"]
    tot = {}
    for iv in busy(tr).values():
        for s, e in subtract([(lo, hi)], iv):
            mid = 0.5 * (s + e)
            tag = "train.loop"
            for hs, he, name in spans:
                if hs <= mid < he:
                    tag = name
            tot[tag] = tot.get(tag, 0.0) + (e - s)
    nd = max(len(tr["ops"]), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / nd / 1e9] for k, v in best]
