#!/usr/bin/env python3
"""Fleet speculative-decoding report: summarize a metrics JSONL.

Usage::

    python scripts/spec_report.py metrics.jsonl

Companion to ``scripts/serve_report.py`` (serving plane) — this one
answers "what did SPECULATION do?": depth the controller chose,
acceptance, wasted draft tokens, draft staleness and republishes, as
the last values the spec-prefixed snapshot fields (``spec_depth``,
``spec_acceptance`` …) held. The two control loops are held by
``tests/test_spec_controller.py`` and ``tests/test_spec_engine.py``.
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

SPEC_FIELDS = ("spec_depth", "spec_acceptance", "spec_wasted_draft_tokens",
               "spec_draft_staleness", "spec_draft_version",
               "draft_publishes")


def summarize_jsonl(path: str) -> Dict[str, Any]:
    from senweaver_ide_tpu.services.metrics import load_jsonl_metrics

    last: Dict[str, Any] = {}
    events = 0
    for e in load_jsonl_metrics(path):
        p = e.get("properties", e)
        hit = False
        for f in SPEC_FIELDS:
            if f in p:
                last[f] = p[f]
                hit = True
        events += hit
    return {"mode": "jsonl", "path": path, "events_with_spec": events,
            **{f: last.get(f) for f in SPEC_FIELDS}}


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("path", help="metrics JSONL to scan")
    args = parser.parse_args()
    print(json.dumps(summarize_jsonl(args.path), indent=2))


if __name__ == "__main__":
    main()
