"""A host phase of the program, from the spans its own tracer kept in
memory (``senweaver_ide_tpu.obs.get_tracer().spans()``): ``args.stat``
(``median_ms`` | ``p75_ms``) of the durations of the spans named
``args.span``, each less its direct children named in
``args.minus_children`` (all of a span's children: its self time).

Only the spans that ended in the traced part of the window count: from
the start of the first step after the profiler session began to the
window's end, on ``time.perf_counter``, the clock of the spans'
``end_ns``. The tracer is the process's own and outlives the engine the
driver frees, so it may also hold what an earlier session or
``obs.enable()`` in this process recorded. A run with no trace, or a
program that records no such span (a commit from before its spans),
gives None."""

import statistics

import numpy as np

STATS = {"median_ms": statistics.median,
         "p75_ms": lambda v: float(np.percentile(v, 75))}


def traced_ns(r):
    """The traced part of the window as ``perf_counter_ns`` readings, or
    None where the run has none."""
    if r.trace is None or not r.traced_steps:
        return None
    first = r.window.steps[len(r.window.steps) - len(r.traced_steps)]
    return int(first[0] * 1e9), int(r.window.t1 * 1e9)


def recorded(r) -> list:
    """The spans the program's tracer holds that ended in the traced part
    of ``r``'s window, oldest first."""
    part = traced_ns(r)
    if part is None:
        return []
    from senweaver_ide_tpu.obs import get_tracer
    return [s for s in get_tracer().spans()
            if part[0] <= getattr(s, "end_ns", 0) <= part[1]]


def durations_ms(spans: list, name: str, minus_children=()) -> list:
    less = {}
    for c in spans:
        if c.name in minus_children:
            less[c.parent_id] = less.get(c.parent_id, 0.0) + c.duration_ms
    return [s.duration_ms - less.get(s.span_id, 0.0)
            for s in spans if s.name == name]


def read(r, args):
    v = durations_ms(recorded(r), args["span"],
                     tuple(args.get("minus_children", ())))
    return STATS[args["stat"]](v) if v else None
