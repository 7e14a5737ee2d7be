"""BENCHMARK.json and the data files it names.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric sits in a file of its own, found by its name:

    configs/<config>.json        widths as published, ``reduced``, ``assumed``
    archs/<model_type>.py        those keys -> the program's ``ModelConfig``
    traffic/<mix>.json           ``driver`` and its parameters
    cells/<workload>.json        limits of the output check, with readings
    layer_metrics/<metric>.json  ``reader`` (a module under ``readers/``)

so a later PR adds a cell by adding files and entries, never by an edit.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


REHEARSAL = os.path.join(HERE, "rehearsal")


class Manifest:
    """``BENCHMARK.json`` and one of its cells. With ``rehearse`` the cell
    keeps its name, driver and metrics and takes its sizes from
    ``rehearsal/``: the tiny-test configuration, ``traffic/<mix>.json`` and
    one ``limits.json`` there."""

    def __init__(self, workload: str, rehearse: bool = False):
        path = os.path.join(ROOT, "BENCHMARK.json")
        self.doc = load_json(path)
        cells = {w["name"]: w for w in self.doc["workloads"]}
        if workload not in cells:
            raise SystemExit(f"benchmark: no workload {workload!r} in "
                             f"{path}; there: {sorted(cells)}")
        self.cell = cells[workload]
        configs = {c["name"]: c for c in self.doc["configs"]}
        self.config_entry = configs[self.cell["config"]]
        mix = self.cell["traffic"] + ".json"
        if rehearse:
            self.config = load_json(REHEARSAL, "tiny-test.json")
            self.traffic = load_json(REHEARSAL, "traffic", mix)
            self.limits = load_json(REHEARSAL, "limits.json")
        else:
            self.config = load_json(ROOT, self.config_entry["file"])
            self.traffic = load_json(HERE, "traffic", mix)
            self.limits = load_json(HERE, "cells", workload + ".json")

    @property
    def name(self) -> str:
        return self.cell["name"]

    @property
    def chips(self) -> int:
        return int(self.cell["chips"])

    def _reported(self, section: str) -> list:
        """Entries of ``end_to_end`` / ``per_layer`` this cell reports: one
        without ``workloads`` is due in every cell that reports the metric it
        moves (every cell, for an end-to-end metric)."""
        out = []
        e2e = {m["name"] for m in self.doc["end_to_end"]
               if self.name in m.get("workloads", [self.name])}
        for m in self.doc[section]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif section == "end_to_end" or m["moves"] in e2e:
                out.append(m)
        return out

    def end_to_end(self) -> list:
        return self._reported("end_to_end")

    def per_layer(self) -> list:
        return self._reported("per_layer")


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file, by the
    ``archs/<model_type>.py`` its ``model_type`` names."""
    import importlib
    return importlib.import_module(
        f"benchmark.archs.{cfg['model_type']}").model_config(cfg)
