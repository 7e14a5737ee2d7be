#!/usr/bin/env python3
"""Fleet observability report: summarize an incident JSONL
(``IncidentCorrelator.export_jsonl``) — alerts, top causes, peers.

Usage::

    python scripts/fleet_obs_report.py incidents.jsonl

The plane itself (metrics federation, alerting, incident correlation
under partition / KV-squat / eager-publish chaos) is held by
``tests/test_fleet_obs.py``.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
from typing import Any, Dict

# Allow running from a source checkout without installation.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def summarize_jsonl(path: str) -> Dict[str, Any]:
    alerts = collections.Counter()
    causes = collections.Counter()
    peers = collections.Counter()
    n = 0
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            n += 1
            alerts[rec.get("alert", "?")] += 1
            cands = rec.get("candidates") or []
            if cands:
                causes[cands[0].get("cause", "?")] += 1
            if rec.get("worst_peer"):
                peers[rec["worst_peer"]] += 1
    return {"mode": "jsonl", "path": path, "incidents": n,
            "alerts": dict(alerts), "top_causes": dict(causes),
            "worst_peers": dict(peers)}


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("path", help="incident JSONL to scan")
    args = parser.parse_args()
    print(json.dumps(summarize_jsonl(args.path), indent=2))


if __name__ == "__main__":
    main()
