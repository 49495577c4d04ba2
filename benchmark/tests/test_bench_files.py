"""Every file a cell or a metric is made of loads and names what exists."""
import glob
import json
import os
import re

import pytest

from benchmark import harness

ROOT = harness.ROOT
BENCH = harness.load_json("BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = {w["name"]: w for w in BENCH["workloads"]}
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
WORKLOAD_FILES = sorted(glob.glob(
    os.path.join(ROOT, "benchmark", "workloads", "*.json")))
METRIC_FILES = sorted(glob.glob(
    os.path.join(ROOT, "benchmark", "metrics", "*.py")))


def test_names_and_units_keep_to_the_allowed_characters():
    for entry in (BENCH["configs"] + BENCH["workloads"]
                  + BENCH["end_to_end"] + BENCH["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert w["name"] == w["config"] + "." + w["traffic"]
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert "setup_s" in E2E


@pytest.mark.parametrize("path", WORKLOAD_FILES, ids=os.path.basename)
def test_workload_file(path):
    w = json.load(open(path))
    assert os.path.basename(path) == w["name"] + ".json"
    assert w["config"] in CONFIGS
    assert os.path.exists(os.path.join(ROOT, "benchmark", "kinds",
                                       w["kind"] + ".py"))
    assert w["chips"] in (1, 4)
    assert w["limits"] and all(v >= 0 for v in w["limits"].values())
    if w["name"] in CELLS:      # a cell kept for later has a file, no entry
        cell = CELLS[w["name"]]
        assert (cell["config"], cell["chips"], cell["why"]) == (
            w["config"], w["chips"], w["why"])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_has_its_files_and_metrics(cell):
    c = CELLS[cell]
    assert os.path.exists(os.path.join(ROOT, "benchmark", "workloads",
                                       cell + ".json"))
    cfg = json.load(open(os.path.join(ROOT, CONFIGS[c["config"]]["file"])))
    assert cfg["source"] == CONFIGS[c["config"]]["source"]
    assert cfg["reduced"] == CONFIGS[c["config"]]["reduced"]
    mine = [m for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [cell])]
    assert {"setup_s"} < {m["name"] for m in mine}
    assert any(cell in m["workloads"] for m in BENCH["per_layer"])


@pytest.mark.parametrize("path", METRIC_FILES, ids=os.path.basename)
def test_metric_file(path):
    name = os.path.basename(path)[:-3]
    mod = harness.load_module("metrics", name)
    assert callable(mod.compute)
    entries = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert len(entries) == 1, f"{name} has no entry in BENCHMARK.json"
    entry = entries[0]
    assert entry["layer"] == mod.LAYER and entry["source"] == mod.SOURCE
    assert entry["moves"] in E2E
    for cell in entry["workloads"]:
        assert cell in CELLS
        assert cell in E2E[entry["moves"]].get("workloads", [cell])


def test_every_per_layer_entry_has_a_reader():
    have = {os.path.basename(p)[:-3] for p in METRIC_FILES}
    assert {m["name"] for m in BENCH["per_layer"]} <= have
