#!/usr/bin/env python3
"""Quantized-KV-ladder report: summarize a metrics JSONL.

Usage::

    python scripts/kv_quant_report.py metrics.jsonl

Companion to ``scripts/kv_pressure_report.py`` (what did pressure do?)
— this one answers "what did PRECISION buy?": the pool's rung, bytes
per block, device and host bytes, evictions and preemptions, as the
last values the KV byte-ledger fields held. The ladder itself is held
by ``tests/test_kv_quant.py``.
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

KV_FIELDS = ("kv_dtype", "kv_bytes_per_block", "kv_bytes_device",
             "kv_bytes_host", "prefix_evictions", "kv_preemptions")



def summarize_jsonl(path: str) -> Dict[str, Any]:
    from senweaver_ide_tpu.services.metrics import load_jsonl_metrics

    last: Dict[str, Any] = {}
    events = 0
    for e in load_jsonl_metrics(path):
        p = e.get("properties", e)
        hit = False
        for f in KV_FIELDS:
            if f in p:
                last[f] = p[f]
                hit = True
        events += hit
    return {"mode": "jsonl", "path": path, "events_with_kv": events,
            **{f: last.get(f) for f in KV_FIELDS}}


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("path", help="metrics JSONL to scan")
    args = parser.parse_args()
    print(json.dumps(summarize_jsonl(args.path), indent=2))


if __name__ == "__main__":
    main()
