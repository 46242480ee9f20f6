"""The program's own host spans on a trace's clock.

The program records ``(name, start_ns, end_ns)`` for each host span of its
training loop (``repro.obs.spans()``) on its own clock.  ``join`` moves
them onto the trace's clock by the offset that puts each window step's
``bench.hook`` (the harness's annotation, inside the step hook) inside the
program's ``train.hook`` span that encloses it: the middle of the offsets
that do so for every step.  It refuses (``None``) where, at the best
offset, a ``bench.hook`` sticks out of its ``train.hook`` by more than
``TOLERANCE_NS``.  (An offset that put each ``bench.hook`` start at its
``train.hook`` start would carry the hook's entry delay, 14-42 us on a
v5e host, into the residual.)

``idle_ms`` reads the device's idle time while the host was in given
spans.
"""

from __future__ import annotations

from benchmark import trace

TOLERANCE_NS = 50_000.0


def record():
    """The program's span record, or ``None`` where it keeps none."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.spans() or None


def join(tr: dict, spans, steps: int) -> tuple[list, float] | None:
    """``([(start, end, name)] on the trace's clock, largest residual in
    ns)``, or ``None`` where the spans cannot be placed within
    ``TOLERANCE_NS``.

    The record ends with the window's last step, since the harness's hook
    ends ``train`` as the window closes, so the window's ``steps`` steps are
    the record's last.  The trace holds the ``bench.hook`` of each window
    step in order from the first (the hook open as the trace starts is not
    in it; the one open as it stops may not be).  The residual is how far a
    ``bench.hook`` sticks out of its ``train.hook`` once moved (0 where all
    lie inside)."""
    lo, hi = trace.window(tr)
    hooks = sorted((s, e) for s, e, n in tr["host"]
                   if n == "bench.hook" and lo <= s <= hi)
    mine = sorted((s, e) for n, s, e in spans if n == "train.hook")
    if not hooks or len(hooks) > steps or len(mine) < steps:
        return None
    pairs = list(zip(hooks, mine[len(mine) - steps:]))
    # Each pair bounds the offset: the bench.hook lies inside its
    # train.hook for any offset in [end - end, start - start].
    first = max(b[1] - m[1] for b, m in pairs)
    last = min(b[0] - m[0] for b, m in pairs)
    off = 0.5 * (first + last)
    out = max(0.0, 0.5 * (first - last))
    if out > TOLERANCE_NS:
        return None
    return sorted((s + off, e + off, n) for n, s, e in spans), out


def idle_ms(ctx, names) -> float | None:
    """Device idle time per step while the host was in any of the program
    spans ``names`` (milliseconds), averaged over the devices; ``None``
    where the spans are absent or cannot be joined to the trace."""
    spans = record()
    joined = spans and join(ctx.tr, spans, ctx.steps)
    if not joined:
        return None
    lo, hi = trace.window(ctx.tr)
    host = trace.clip(trace.union(
        (s, e) for s, e, n in joined[0] if n in names), lo, hi)
    if not host:
        return None
    total = []
    for busy in trace.busy(ctx.tr).values():
        idle = trace.subtract([(lo, hi)], busy)
        total.append(trace.length(idle)
                     - trace.length(trace.subtract(idle, host)))
    if not total:
        return None
    return sum(total) / len(total) / ctx.steps / 1e6
