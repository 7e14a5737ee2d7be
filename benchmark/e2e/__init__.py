"""End-to-end metrics, one module each, found by the metric's name with '.'
and '-' read as '_'. ``read(window, ctx)`` returns the value; all are taken
by the benchmark's own host clock around calls that return only when the
device has finished (``engine.step()`` fetches its tokens)."""
