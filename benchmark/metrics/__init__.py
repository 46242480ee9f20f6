"""Per-layer metric readers: ``<name>.py`` defines ``read(ctx)``, which
returns the metric's value, or ``None`` where the trace holds nothing for
it.  ``ctx`` is a ``benchmark.harness.TraceContext``."""
