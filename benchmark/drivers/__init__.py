"""Loop kinds. A traffic mix names one in its ``driver`` key; the module of
that name gives ``prepare`` (all set-up; returns a state with ``counters``),
``window``, ``notes``, ``trace_started``, ``traced_part``, ``release`` and
``compared``."""
