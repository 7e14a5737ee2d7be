"""Device time of one compiled program (``args.module``, a part of its name
in the trace's ``XLA Modules`` line): the median duration of its runs, ms."""

import statistics


def runs(r, args):
    if r.trace is None:
        return []
    return [d for name, evs in r.trace.modules.items()
            if args["module"] in name for _s, d in evs]


def read(r, args):
    d = runs(r, args)
    return statistics.median(d) / 1e6 if d else None
