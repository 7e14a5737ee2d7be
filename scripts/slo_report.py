#!/usr/bin/env python3
"""SLO report from an exemplar-timeline JSONL
(``SLOTracker.export_jsonl``): per class, the derived latencies,
violations and milestones it contains, as one JSON document.

Usage::

    python scripts/slo_report.py exemplars.jsonl [--out FILE]

The accounting itself (one timeline a request under lost-response
chaos, replayed RPCs never double-executed, stitched traces) is held
by ``tests/test_distributed_tracing.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List

# Allow running from a source checkout without installation.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SLO_KEYS = ("ttft_s", "tpot_s", "queue_wait_s", "e2e_s")


def summarize_jsonl(path: str) -> Dict[str, Any]:
    """Aggregate an exemplar-timeline JSONL (one timeline per line)."""
    timelines: List[Dict[str, Any]] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue            # torn tail line from a crash
            if isinstance(rec, dict):
                timelines.append(rec)
    by_class: Dict[str, Dict[str, Any]] = {}
    for tl in timelines:
        cls = by_class.setdefault(str(tl.get("priority")), {
            "timelines": 0, "violating": 0, "violations": {},
            "derived": {k: [] for k in SLO_KEYS}})
        cls["timelines"] += 1
        if tl.get("violations"):
            cls["violating"] += 1
            for v in tl["violations"]:
                cls["violations"][v] = cls["violations"].get(v, 0) + 1
        for k in SLO_KEYS:
            v = (tl.get("derived") or {}).get(k)
            if v is not None:
                cls["derived"][k].append(float(v))
    for cls in by_class.values():
        cls["derived"] = {
            k: {"count": len(vs), "max_s": round(max(vs), 6),
                "mean_s": round(sum(vs) / len(vs), 6)}
            for k, vs in cls["derived"].items() if vs}
    return {"mode": "jsonl", "path": path,
            "timelines": len(timelines), "per_class": by_class}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="SLO report (JSON) from an exemplar JSONL: "
                    "latencies, violations, milestones.")
    parser.add_argument("path",
                        help="exemplar JSONL from "
                             "SLOTracker.export_jsonl()")
    parser.add_argument("--out", help="also write the report JSON here")
    args = parser.parse_args(argv)

    if not os.path.exists(args.path):
        print(f"slo_report: no such file: {args.path}", file=sys.stderr)
        return 2
    text = json.dumps(summarize_jsonl(args.path), indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
