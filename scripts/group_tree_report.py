#!/usr/bin/env python3
"""Group-shared rollout / tree-branching report: summarize a metrics
JSONL.

Usage::

    python scripts/group_tree_report.py metrics.jsonl

Companion to ``scripts/kv_pressure_report.py`` (memory plane) — this
one answers "what did GROUP SHARING do?": prefills paid vs avoided,
forks and COW splits, branch events, and degrade counts, as the last
values the engine's group/fork counter fields held. The invariants
(one prefill a group, leaf exactness, honest degrades) are held by
``tests/test_group_tree.py``.
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

GROUP_FIELDS = ("engine_group_prefills", "engine_group_forks",
                "engine_branch_forks",
                "engine_group_prefill_tokens_avoided",
                "engine_group_degrades", "engine_kv_cow_copies",
                "engine_prefills")


def summarize_jsonl(path: str) -> Dict[str, Any]:
    from senweaver_ide_tpu.services.metrics import load_jsonl_metrics

    last: Dict[str, Any] = {}
    events = 0
    for e in load_jsonl_metrics(path):
        p = e.get("properties", e)
        hit = False
        for f in GROUP_FIELDS:
            if f in p:
                last[f] = p[f]
                hit = True
        events += hit
    return {"mode": "jsonl", "path": path, "events_with_group": events,
            **{f: last.get(f) for f in GROUP_FIELDS}}


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("path", help="metrics JSONL to scan")
    args = parser.parse_args()
    print(json.dumps(summarize_jsonl(args.path), indent=2))


if __name__ == "__main__":
    main()
