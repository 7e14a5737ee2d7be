#!/usr/bin/env python3
"""Disaggregated-learner health summary from a metrics JSONL.

Usage::

    python scripts/learner_report.py metrics.jsonl

Companion to ``scripts/remote_fleet_report.py`` (the wire) — this one
answers "what did the LEARNER do?": fenced publishes, stale-writer
rejections, lease epochs and autoscaler actions. Reads the "Serving
Snapshot" events a ``ServingFleet(metrics_service=...)`` captures and
emits a JSON summary of the learner/publication fields (cumulative
counters — the last snapshot is the total). The kill / restart / fence
chaos is held by ``tests/test_learner.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict

# Allow running from a source checkout without installation.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SNAPSHOT_EVENT = "Serving Snapshot"

LEARNER_FIELDS = ("weight_version", "publish_epoch", "stale_publishes",
                  "autoscale_actions", "learner_publishes")


def summarize_jsonl(path: str) -> Dict[str, Any]:
    from senweaver_ide_tpu.services.metrics import load_jsonl_metrics

    last: Dict[str, Any] = {}
    snapshots = 0
    for e in load_jsonl_metrics(path):
        if e.get("event") != SNAPSHOT_EVENT:
            continue
        snapshots += 1
        p = e.get("properties", e)
        for f in LEARNER_FIELDS:
            if f in p:
                last[f] = p[f]
    return {"mode": "jsonl", "path": path, "snapshots": snapshots,
            **{f: last.get(f, 0) for f in LEARNER_FIELDS}}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Disaggregated-learner health summary (JSON).")
    parser.add_argument("path",
                        help="metrics JSONL from "
                             "MetricsService(jsonl_path=...)")
    args = parser.parse_args(argv)

    if not os.path.exists(args.path):
        print(f"learner_report: no such file: {args.path}",
              file=sys.stderr)
        return 2
    print(json.dumps(summarize_jsonl(args.path), indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
