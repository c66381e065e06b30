"""Every data file the benchmark finds by name loads, and BENCHMARK.json keeps
to the parts of its contract that can be checked here."""

import glob
import json
import os
import re

import pytest

import harness

BENCH = harness.HERE
MANIFEST = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_every_cell_loads_with_all_its_files():
    for w in MANIFEST["workloads"]:
        cell = harness.Cell(w["name"])
        assert cell.traffic["kind"] == "train" and cell.chips in (1, 4)
        numbers = {n + m for n in ("grad1", "change", "momentum") for m in ("", "_median")}
        assert set(cell.limits()) <= numbers | {f"loss{i}" for i in range(1, 10)}
        assert hasattr(cell.method, "SLOT") and callable(cell.method.make)
        cell.reference().make_loss_and_grad
        names = cell.config_mod.names(cell.config)
        assert len({n for n, _ in names}) == len(names)
        for m in cell.per_layer():
            assert hasattr(harness.load_module(
                cell.path("metrics", m["name"] + ".py")), "read")


@pytest.mark.parametrize("sub", ["configs", "traffic", "limits"])
def test_every_json_file_parses(sub):
    files = glob.glob(os.path.join(BENCH, sub, "*.json"))
    assert files
    for f in files:
        assert isinstance(json.load(open(f)), dict)


def test_names_units_and_keys():
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = [m["name"] for m in metrics] + [w["name"] for w in MANIFEST["workloads"]] \
        + [c["name"] for c in MANIFEST["configs"]]
    assert len(set(names)) == len(names)
    for n in names + [w["traffic"] for w in MANIFEST["workloads"]]:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.1
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert "setup_s" in [m["name"] for m in MANIFEST["end_to_end"]]


def test_each_per_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    cells = [w["name"] for w in MANIFEST["workloads"]]
    for m in MANIFEST["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells and cell in moved.get("workloads", cells)
    for cfg in MANIFEST["configs"]:
        assert cfg["file"].startswith("benchmarks/") and os.path.exists(
            os.path.join(harness.ROOT, cfg["file"]))
        assert any(w["config"] == cfg["name"] for w in MANIFEST["workloads"])
        assert sorted(json.load(open(os.path.join(harness.ROOT, cfg["file"])))["reduced"]) \
            == sorted(cfg["reduced"])


def test_files_under_paths_are_named_from_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for root, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            assert ok.match(os.path.relpath(os.path.join(root, f), harness.ROOT)), f
