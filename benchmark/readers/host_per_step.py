"""Host time of one engine step: the benchmark's own annotation
(``args.annotation``) around ``engine.step()``, minus the device time of the
program (``args.module``) that ran inside it; median over steps, ms."""

import bisect
import statistics


def read(r, args):
    if r.trace is None:
        return None
    steps = r.trace.host.get(args["annotation"], [])
    runs = sorted((s, d) for name, evs in r.trace.modules.items()
                  if args["module"] in name for s, d in evs)
    if not steps or not runs:
        return None
    starts = [s for s, _ in runs]
    out = []
    for s, d in steps:
        i, inside = bisect.bisect_left(starts, s), 0.0
        while i < len(runs) and runs[i][0] < s + d:
            inside += runs[i][1]
            i += 1
        if inside:
            out.append((d - inside) / 1e6)
    return statistics.median(out) if out else None
