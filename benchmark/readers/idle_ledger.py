"""Whose time is the device's idle time between two fused steps? One part
of it a step, ms (``args.part``: ``gap`` | ``emit`` | ``caller`` | ``plan``
| ``copies`` | ``dispatch`` | ``runtime``): the mean over the kept steps, so
the six parts add up to ``gap``. With ``part`` ``unqueued_share``, %: the
program's counter of time with no fused step in flight (``args.attr`` of the
``args.spans.step`` spans, the first traced step's left out) over the traced
steps' span, which needs the spans alone.

A trace has the device's runs on the device's clock (``record.trace.modules``,
the ``XLA Modules`` line) and the program's spans (``program_span.recorded``)
on ``time.perf_counter_ns()``. The two clocks are offset by a few tenths of a
millisecond, a fifth of the gap to be explained, so nothing here compares a
reading of one with a reading of the other. The join is by STEP: the k-th
``step`` span that launched (it has a ``launch`` and a ``fetch`` child) is the
k-th run of ``args.module``. The span names are ``args.spans``'s: the
yardstick holds them, not the program. Between run *k-1* and run *k*

- the **gap** is two readings of the device's clock, less the device time of
  any other program that ran in between (a copy-on-write's
  ``jit_copy_blocks``, a state-row copy);
- the **host chain** from ``fetch``'s return in step *k-1* to the end of
  ``dispatch`` in step *k* is readings of ``perf_counter_ns`` alone, and the
  spans cut it into ``emit`` (fetch end to step end), ``caller`` (to the next
  step's start: whoever drives the engine), ``plan`` (to launch start, less
  copies), ``copies`` (the spans named in ``copies`` below it; a program
  without one of them counts that time under ``plan``) and ``dispatch``
  (launch start to the end of its ``dispatch`` child; what the wrapper does
  after that overlaps the device and owns no idle time);
- gap minus chain is **runtime**: what no span of the program covers, the
  latency from the program's end to the host's wake-up plus from the enqueue
  to the program's start. Their SUM needs no clock alignment. How it splits
  between the two depends on the offset, and causality bounds that: a run
  starts after its launch began and ends before its fetch returned, so over
  all pairs the offset (``perf_counter_ns`` minus the device's clock) lies in
  ``[max_k(launch.start - run.start), min_k(fetch.end - run.end)]``.
  :func:`attribute` returns that window and the split at both of its ends:
  two bracketing pairs of numbers, not one guess.

The pairing is anchored at the last complete step and checked without any
clock (every pair's launch-to-fetch time holds its run's duration; the causal
window is not empty). A pairing that fails is not repaired: :func:`attribute`
returns ``None`` and the reason. The one difference in the counts it takes is
a first step whose run the traced window cut off (the window begins at a host
event, the run is on the device's clock): that step is left out and the
ledger says so. The first and the last pair are dropped from the per-step
rows: starting a profiler stalls the loop, and the last step has no gap after
it.

The ledger's summary (steps, floor count, the clocks' causal window, the two
bracketing splits of ``runtime``) or the reason it gave nothing goes to the
log once a run. None where the run has no trace, the trace no device run of
the module, the join fails, or the program records no such span or attr (a
commit from before them). :func:`attribute` and :func:`unqueued_share` are
pure Python and import nothing of the program."""

import bisect
import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .program_span import recorded

PARTS = ("emit", "caller", "plan", "copies", "dispatch", "runtime")

Run = Tuple[float, float]                # (start_ns, duration_ns)


@dataclasses.dataclass
class IdleLedger:
    """``steps``: one row a kept gap, keys ``gap``, :data:`PARTS` and
    ``other`` (ms; the six parts sum to ``gap`` except where ``runtime``
    was floored at 0, ``floored`` rows; ``other`` is the device time of
    other programs that was taken out of ``gap``). ``pairs``: (step,
    run) pairs joined, kept or not; ``unpaired_first``: 1 where the
    first step's run lay outside the traced window and the step was
    left out, else 0. ``gaps_all_ms``: the summed gap
    between every two successive runs, the dropped first and last
    included (with the idle time inside programs it is the device's idle
    time in the traced part, a second way to it). ``offset_window_ms``:
    the causal window of ``perf_counter_ns`` minus the device's clock.
    ``split_at_lower`` / ``split_at_upper``: the mean (start latency,
    return latency) of
    ``runtime`` with the offset at that end of the window — start is
    enqueue-to-start (``.dispatch`` end to the run's start; negative
    where the program started before the call returned), return is
    end-to-wake-up (the run's end to ``engine.fetch``'s end). The two
    sum to the mean ``runtime`` plus the mean ``other``: the latencies
    are run to run, whatever else the device did in between."""
    steps: List[Dict[str, float]]
    pairs: int
    unpaired_first: int
    floored: int
    gaps_all_ms: float
    offset_window_ms: Tuple[float, float]
    split_at_lower: Tuple[float, float]
    split_at_upper: Tuple[float, float]

    def mean(self, part: str) -> Optional[float]:
        if not self.steps:
            return None
        return sum(s[part] for s in self.steps) / len(self.steps)

    def summary(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"pairs": self.pairs,
                               "unpaired_first": self.unpaired_first,
                               "steps": len(self.steps),
                               "floored": self.floored}
        for part in ("gap",) + PARTS + ("other",):
            out[part + "_ms"] = self.mean(part)
        out["gaps_all_ms"] = self.gaps_all_ms
        out["offset_window_ms"] = list(self.offset_window_ms)
        out["runtime_start_return_ms_at_lower"] = list(self.split_at_lower)
        out["runtime_start_return_ms_at_upper"] = list(self.split_at_upper)
        return out


@dataclasses.dataclass
class _Step:
    step: Any
    launch: Any
    fetch: Any
    dispatch_end_ns: int
    copies_ns: int = 0


def _launched_steps(spans: Sequence[Any], names: Dict[str, Any]
                    ) -> List[_Step]:
    """The ``names["step"]`` spans with a ``launch`` and a ``fetch``
    child, oldest first, each with the time of the ``copies`` spans below
    it."""
    by_id = {s.span_id: s for s in spans}
    kids: Dict[str, Dict[str, Any]] = {}
    for s in spans:
        if s.name in (names["launch"], names["fetch"]):
            kids.setdefault(s.parent_id, {})[s.name] = s
    dispatch_end = {s.parent_id: s.end_ns for s in spans
                    if s.name == names["dispatch"]}
    out: Dict[str, _Step] = {}
    for s in spans:
        if s.name != names["step"]:
            continue
        k = kids.get(s.span_id, {})
        if names["launch"] in k and names["fetch"] in k:
            launch = k[names["launch"]]
            out[s.span_id] = _Step(
                s, launch, k[names["fetch"]],
                dispatch_end.get(launch.span_id, launch.end_ns))
    for s in spans:
        if s.name not in names["copies"]:
            continue
        up = by_id.get(s.parent_id)
        while up is not None and up.name != names["step"]:
            up = by_id.get(up.parent_id)
        if up is not None and up.span_id in out:
            out[up.span_id].copies_ns += s.end_ns - s.start_ns
    return sorted(out.values(), key=lambda p: p.step.start_ns)


def attribute(fused_runs: Sequence[Run], other_runs: Sequence[Run],
              spans: Sequence[Any], names: Dict[str, Any]
              ) -> Tuple[Optional[IdleLedger], str]:
    """The idle ledger of one traced part: ``fused_runs`` are the fused
    step's device runs and ``other_runs`` every other program's, both
    ``(start_ns, duration_ns)`` on the device's clock; ``spans`` the
    tracer's records of the same part; ``names`` the span names to read
    (``step``, ``launch``, ``fetch``, ``dispatch``, and the list
    ``copies``). Returns ``(ledger, "")`` or ``(None, why)``."""
    steps = _launched_steps(spans, names)
    runs = sorted(fused_runs)
    # Anchored at the LAST complete step. A traced window begins at a
    # host event, on another clock than the runs': the first step's run
    # can fall just outside it. One such step is left unpaired (and
    # said so); any other difference in the counts is no edge effect,
    # and a run missing elsewhere shifts every earlier pair by a whole
    # step, which the causal window below refuses.
    unpaired = len(steps) - len(runs)
    if unpaired not in (0, 1):
        return None, (f"{len(steps)} {names['step']} spans with a launch and "
                      f"a fetch, {len(runs)} runs of the fused step: the "
                      f"k-th cannot be paired with the k-th")
    steps = steps[unpaired:]
    if len(steps) < 4:
        return None, (f"{len(steps)} paired steps: none is left between "
                      f"the first and the last")
    lower, upper = float("-inf"), float("inf")
    for i, (p, (start, dur)) in enumerate(zip(steps, runs)):
        if p.fetch.end_ns - p.launch.start_ns < dur:
            return None, (f"pair {i} of {len(steps)}: the step's launch-to-"
                          f"fetch time "
                          f"{(p.fetch.end_ns - p.launch.start_ns) / 1e6:.3f}"
                          f" ms is under its run's {dur / 1e6:.3f} ms: a "
                          f"wrong join")
        lower = max(lower, p.launch.start_ns - start)
        upper = min(upper, p.fetch.end_ns - (start + dur))
    if lower > upper:
        return None, (f"no offset between the clocks lets every run start "
                      f"after its launch and end before its fetch (window "
                      f"{(upper - lower) / 1e6:.3f} ms wide): a wrong join")
    others = sorted(other_runs)
    other_starts = [s for s, _ in others]

    def idle_between(p_end, c_start):
        """(idle, other programs' device time) from one run's end to the
        next one's start, ns."""
        busy = 0.0
        i = bisect.bisect_left(other_starts, p_end)
        while i < len(others) and others[i][0] < c_start:
            busy += min(others[i][1], c_start - others[i][0])
            i += 1
        return c_start - p_end - busy, busy

    # between[k]: from run k's end to run k + 1's start
    between = [idle_between(a + d, b)
               for (a, d), (b, _) in zip(runs, runs[1:])]
    rows, floored = [], 0
    start_lat = return_lat = 0.0         # sums at offset 0, ns
    for k in range(2, len(steps) - 1):   # first and last pair dropped
        prev, cur = steps[k - 1], steps[k]
        p_end, c_start = sum(runs[k - 1]), runs[k][0]
        gap, busy = between[k - 1]
        row = {
            "gap": gap,
            "emit": prev.step.end_ns - prev.fetch.end_ns,
            "caller": cur.step.start_ns - prev.step.end_ns,
            "plan": (cur.launch.start_ns - cur.step.start_ns
                     - cur.copies_ns),
            "copies": cur.copies_ns,
            "dispatch": cur.dispatch_end_ns - cur.launch.start_ns,
        }
        rest = row["gap"] - sum(row[k] for k in PARTS[:-1])
        if rest < 0:
            floored += 1
        row["runtime"] = max(rest, 0.0)
        row["other"] = busy
        rows.append({k: v / 1e6 for k, v in row.items()})
        return_lat += prev.fetch.end_ns - p_end
        start_lat += c_start - cur.dispatch_end_ns
    n = len(rows)

    def split(offset):
        return ((start_lat / n + offset) / 1e6,
                (return_lat / n - offset) / 1e6)

    return IdleLedger(rows, len(steps), unpaired, floored,
                      sum(idle for idle, _ in between) / 1e6,
                      (lower / 1e6, upper / 1e6),
                      split(lower), split(upper)), ""


def unqueued_share(spans: Sequence[Any], step: str, attr: str
                   ) -> Optional[float]:
    """100 x the summed ``attr`` (ms) of the spans named ``step`` after
    the first over the time from the first one's end to the last one's
    end: the share of that time in which the host had no fused step in
    flight (``RuntimeProfiler.end_step``'s counter, read in a window). A
    step's reading covers the time BEFORE its launch, so the first step's
    is left out: it holds whatever came before the window (a profiler's
    start stalls the loop ~45 ms). None where no later step carries the
    attr."""
    steps = sorted((s for s in spans if s.name == step),
                   key=lambda s: s.start_ns)
    ms = [s.attrs[attr] for s in steps[1:] if attr in s.attrs]
    if not ms:
        return None
    span_ns = steps[-1].end_ns - steps[0].end_ns
    return 100.0 * sum(ms) * 1e6 / span_ns if span_ns > 0 else None


def ledger(r, args) -> Optional[IdleLedger]:
    """The run's idle ledger or None, built once and kept on ``r``."""
    if "idle_ledger" in vars(r):
        return r.idle_ledger
    r.idle_ledger = None
    if r.trace is None:
        return None
    fused, others = [], []
    for name, runs in r.trace.modules.items():
        (fused if args["module"] in name else others).extend(runs)
    if not fused:
        return None
    r.idle_ledger, why = attribute(fused, others, recorded(r),
                                   args["spans"])
    print("idle_ledger: " + (why or json.dumps(r.idle_ledger.summary())),
          flush=True)
    return r.idle_ledger


def read(r, args):
    if args["part"] == "unqueued_share":
        return unqueued_share(recorded(r), args["spans"]["step"],
                              args["attr"])
    led = ledger(r, args)
    return led.mean(args["part"]) if led is not None else None
