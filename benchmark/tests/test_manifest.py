"""BENCHMARK.json against the contract's limits on names, units and keys,
and against the files it names."""

import json
import os
import re

import pytest

from benchmark.manifest import (HERE, ROOT, Manifest, load_json,
                                model_config)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
DOC = load_json(ROOT, "BENCHMARK.json")


def test_top_level_keys_and_sizes():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert isinstance(DOC["run_seconds"], int) and 1 <= DOC["run_seconds"] <= 51
    assert DOC["paths"] == ["benchmark"]
    four = sum(w["chips"] == 4 for w in DOC["workloads"])
    assert four <= max(1, len(DOC["workloads"]) // 4)


def test_names_units_and_entry_keys():
    for c in DOC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        assert c["file"].startswith("benchmark/")
    for w in DOC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in DOC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in DOC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    names = [m["name"] for m in DOC["end_to_end"] + DOC["per_layer"]]
    assert len(names) == len(set(names))
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert "setup_s" in names


@pytest.mark.parametrize("cell", [w["name"] for w in DOC["workloads"]])
def test_every_cell_has_its_files_and_reports_enough(cell):
    man = Manifest(cell)
    assert man.traffic["driver"] and man.limits["served_tokens_min"] >= 100
    assert 0 < man.limits["served_logp_gap_mean"] < man.limits[
        "served_logp_gap_max"]
    # the rehearsal runs the same cell, driver and metrics at its own sizes
    tiny = Manifest(cell, rehearse=True)
    assert tiny.traffic["driver"] == man.traffic["driver"]
    assert tiny.config["name"] == "tiny-test"
    assert tiny.per_layer() == man.per_layer()
    model_config(tiny.config)
    assert os.path.exists(os.path.join(HERE, "drivers",
                                       man.traffic["driver"] + ".py"))
    e2e = [m["name"] for m in man.end_to_end()]
    assert "setup_s" in e2e and len(e2e) >= 2
    for name in e2e:
        if name != "setup_s":
            assert os.path.exists(os.path.join(HERE, "e2e", name + ".py"))
    layer = man.per_layer()
    assert layer
    for m in layer:
        assert m["moves"] in e2e
        spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
        assert spec["layer"] == m["layer"]
        assert os.path.exists(os.path.join(HERE, "readers",
                                           spec["reader"] + ".py"))
    # the file holds the configuration as it is run, with its own reduced
    assert man.config["reduced"] == man.config_entry["reduced"]
    assert man.config["source"] == man.config_entry["source"]


def test_every_per_layer_metric_moves_an_end_to_end_metric():
    e2e = {m["name"] for m in DOC["end_to_end"]}
    for m in DOC["per_layer"]:
        assert m["moves"] in e2e
    json.dumps(DOC)
