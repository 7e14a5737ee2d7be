#!/usr/bin/env python3
"""GRPO training-health report from a per-round health ring JSONL
(``TrainingHealthMonitor.export_jsonl``): the signal ranges, trigger
counts and worst rounds it contains, as one JSON document.

Usage::

    python scripts/training_health_report.py health.jsonl [--out FILE]

The detectors, the monitor's surfaces and the mitigations are held by
``tests/test_training_health.py``.
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


def summarize_ring(path: str) -> Dict[str, Any]:
    """Summarize an exported health ring JSONL: per-signal min/max/last,
    trigger counts, and the worst rounds by trigger count."""
    rounds: List[Dict[str, Any]] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                rounds.append(json.loads(line))
    signals: Dict[str, Dict[str, float]] = {}
    trigger_counts: Dict[str, int] = {}
    for rec in rounds:
        for key, value in (rec.get("health") or {}).items():
            s = signals.setdefault(key, {"min": value, "max": value})
            s["min"] = min(s["min"], value)
            s["max"] = max(s["max"], value)
            s["last"] = value
        for t in rec.get("triggers", ()):
            trigger_counts[t] = trigger_counts.get(t, 0) + 1
    worst = sorted(rounds, key=lambda r: len(r.get("triggers", ())),
                   reverse=True)[:5]
    return {"mode": "jsonl", "rounds": len(rounds), "signals": signals,
            "trigger_counts": trigger_counts, "worst_rounds": worst}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="GRPO training-health report (JSON) from a health "
                    "ring JSONL.")
    parser.add_argument("path",
                        help="health ring JSONL from "
                             "TrainingHealthMonitor.export_jsonl()")
    parser.add_argument("--out", default=None,
                        help="also write the JSON report here")
    args = parser.parse_args(argv)

    if not os.path.exists(args.path):
        print(f"training_health_report: no such file: {args.path}",
              file=sys.stderr)
        return 2
    report = summarize_ring(args.path)
    body = json.dumps(report, indent=2, sort_keys=True, default=str)
    print(body)
    if args.out:
        with open(args.out, "w") as f:
            f.write(body + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
