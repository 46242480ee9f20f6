"""Tracing inside the program: host spans and the step ``train`` last ran.

Always on; there is no flag.

* ``span(name)`` marks a stretch of host work twice: as a
  ``jax.profiler.TraceAnnotation`` (a ``StepTraceAnnotation`` when given
  ``step_num``), so any xprof or Perfetto trace shows it on the trace's clock,
  and as ``(name, start_ns, end_ns)`` in a bounded in-memory record on
  ``time.perf_counter_ns``'s clock (the last ``RECORD_LEN`` spans, appended
  as each span ends, so an inner span precedes the span that holds it).
  With the profiler off a span costs a few microseconds.
* ``note_step(fn, args)`` keeps the jitted step and the
  ``ShapeDtypeStruct``s (with sharding) of its arguments, never an array, so
  that the step can be lowered again after ``train`` returns while nothing
  holds device memory.  ``last_step()`` returns that pair.

The device side: the layers of the train step are ``jax.named_scope``s
(``SCOPES``; ``scoped`` puts a whole function in one), which reach the
compiled HLO as each instruction's ``op_name`` and change nothing else in
it.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import time

import jax

#: the named scopes that split the train step into disjoint layers
#: (innermost wins where they nest).
SCOPES = ("embed", "attention", "router", "dispatch", "experts", "combine",
          "unembed_loss", "optimizer")

RECORD_LEN = 4096
_record: collections.deque = collections.deque(maxlen=RECORD_LEN)
_step = None


def scoped(name: str):
    """Decorator: trace the function inside ``jax.named_scope(name)`` (a
    fresh scope per call, so calls may nest and threads may trace at once)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


@contextlib.contextmanager
def span(name: str, step_num: int | None = None):
    ann = (jax.profiler.TraceAnnotation(name) if step_num is None
           else jax.profiler.StepTraceAnnotation(name, step_num=step_num))
    start = time.perf_counter_ns()
    try:
        with ann:
            yield
    finally:
        _record.append((name, start, time.perf_counter_ns()))


def spans() -> list:
    """A copy of the record: ``[(name, start_ns, end_ns)]`` in end order."""
    return list(_record)


def note_step(fn, args) -> None:
    global _step
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                         sharding=x.sharding)
    _step = (fn, jax.tree.map(sds, tuple(args)))


def last_step():
    """``(jitted step, argument shapes)`` of the step ``train`` last ran, or
    ``None`` before any."""
    return _step
