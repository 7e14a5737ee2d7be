"""From a profiler trace (``*.xplane.pb``) to the numbers the readers use.

Read with ``jax.profiler.ProfileData`` alone. On a TPU each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Modules`` holds one event for each run of
a compiled program and whose line ``XLA Ops`` one for each operation; host
threads are lines of the plane ``/host:CPU``, and the benchmark's own
``TraceAnnotation``s (``bench.*``) are events there, on the same clock.

    busy      union of the device-op intervals, clipped to the traced window
    window    first to last ``bench.*`` annotation on the host
    modules   durations of each program, by name
    ops       summed self time of each operation, by name (with its shape where
              the trace carries one)
    gaps      idle intervals of the device, each put to the innermost host
              event that covers its middle
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)
MIN_GAP_NS = 20_000
MAX_NAMED_GAPS = 300


@dataclasses.dataclass
class Reduced:
    window_ns: Tuple[float, float]
    devices: int
    busy_s: float                         # mean over devices
    modules: Dict[str, List[Tuple[float, float]]]   # name -> [(start, dur)]
    ops: Dict[str, float]                 # name -> seconds, device 0
    gaps: Dict[str, float]                # host event -> idle seconds
    host: Dict[str, List[Tuple[float, float]]]      # bench.* annotations

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _stat(event, *names):
    try:
        for k, v in event.stats:
            if k in names:
                return v
    except Exception:
        pass
    return None


def _op_name(event) -> str:
    """The operation's name as the trace prints it, with the shape of its
    result where the trace carries one: ``fusion.257_bf16_8192_16_2_128_``."""
    name = event.name.lstrip("%").split(" ")[0]
    shape = _stat(event, "shape_with_layout", "shape", "tensor_shapes")
    if shape is None:
        m = re.search(r"=\s*\(?([a-z]+\d*\[[^\]]*\])", event.name)
        shape = m.group(1) if m else None
    if shape:
        head = re.match(r"\(?([a-z]+\d*\[[0-9,]*\])", str(shape))
        if head:
            name += "_" + re.sub(r"[^A-Za-z0-9]+", "_", head.group(1))
    return name


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    return found[-1]


def reduce_file(path: str, annotation_prefix: str = "bench.") -> Reduced:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes,
                         annotation_prefix)


def reduce_planes(planes, annotation_prefix: str = "bench.",
                  min_gap_ns: float = MIN_GAP_NS) -> Reduced:
    host_lines: List[List[Tuple[float, float, str]]] = []
    ann: Dict[str, List[Tuple[float, float]]] = {}
    dev = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            mods, ops = [], []
            for line in plane.lines:
                if line.name in MODULE_LINES:
                    mods = [(e.start_ns, e.duration_ns, e.name)
                            for e in line.events]
                elif line.name in OP_LINES:
                    ops = [(e.start_ns, e.duration_ns, _op_name(e))
                           for e in line.events]
            dev.append((plane.name, mods, ops))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = [(e.start_ns, e.duration_ns, e.name)
                       for e in line.events if e.duration_ns > 0]
                if evs:
                    host_lines.append(evs)
                for s, d, n in evs:
                    if n.startswith(annotation_prefix):
                        ann.setdefault(n, []).append((s, d))
    dev.sort(key=lambda t: int(t[0].rsplit(":", 1)[1]))
    if not ann:
        raise ValueError("trace holds no bench.* annotation: no window")
    lo = min(s for v in ann.values() for s, _ in v)
    hi = max(s + d for v in ann.values() for s, d in v)
    busy, modules, ops, gaps = [], {}, {}, {}
    for i, (_name, mods, dops) in enumerate(dev):
        iv = _clip(_union([(s, s + d) for s, d, _ in dops]), lo, hi)
        busy.append(sum(b - a for a, b in iv) / 1e9)
        if i:
            continue
        for s, d, n in mods:
            if lo <= s and s + d <= hi:
                key = re.sub(r"\(.*$", "", n)
                modules.setdefault(key, []).append((s, d))
        for s, d, n in _self_times(dops):
            if lo <= s and s + d <= hi:
                ops[n] = ops.get(n, 0.0) + d / 1e9
        edges = [(lo, lo)] + iv + [(hi, hi)]
        idle = sorted(((b - a, a, b) for (_, a), (b, _)
                       in zip(edges, edges[1:]) if b - a >= min_gap_ns),
                      reverse=True)
        # the longest gaps are named; the many short ones are one entry
        for n, (d, a, b) in enumerate(idle):
            who = (_innermost(host_lines, (a + b) / 2) if n < MAX_NAMED_GAPS
                   else "gaps_not_named")
            gaps[who] = gaps.get(who, 0.0) + d / 1e9
    return Reduced((lo, hi), len(dev),
                   sum(busy) / len(busy) if busy else 0.0,
                   modules, ops, gaps, ann)


def _self_times(events):
    """(start, self duration, name) of each op: a ``while`` or a call holds
    the ops of its body as later, nested events of the same line, and its
    own time is what they leave uncovered, so that the table adds up to the
    busy time and a loop does not hide what runs inside it."""
    out, stack = [], []          # stack: [start, end, name, covered]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            s, e, n, cov = stack.pop()
            out.append((s, max(e - s - cov, 0.0), n))
            if stack:
                stack[-1][3] += e - s

    for s, d, n in sorted(events, key=lambda t: (t[0], -t[1])):
        close(s)
        stack.append([s, s + d, n, 0.0])
    close(float("inf"))
    return out


def _innermost(host_lines, t: float) -> str:
    best, best_d = "no_host_event", float("inf")
    for evs in host_lines:
        for s, d, n in evs:
            if s <= t <= s + d and d < best_d:
                best, best_d = n, d
    return re.sub(r"[^A-Za-z0-9_.:()-]+", "_", best)[:80]


def top(table: Dict[str, float], n: int = 10) -> list:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]
