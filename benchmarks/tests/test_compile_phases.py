"""The four readers of set-up's compile phases (`compile_phases.py`): None on
a registry with no `compile/*` entry (the parent's program records none), the
registry's totals on a planted one, the by-program table on standard error
once, and their entries in BENCHMARK.json."""

import json
import os

import pytest

import compile_phases
import harness
from bigdl_tpu.obs import registry as program_registry

READERS = {"compile_trace_s.train": 1.5 + 0.25, "compile_lower_s.train": 2.0,
           "compile_backend_s.train": 0.0, "compile_cache_misses.train": 3}


def _readers():
    return {name: harness.load_module(os.path.join(harness.HERE, "metrics", name + ".py"))
            for name in READERS}


@pytest.fixture
def fresh(monkeypatch):
    reg = program_registry.MetricRegistry()
    monkeypatch.setattr(program_registry, "registry", reg)
    monkeypatch.setattr(compile_phases, "_PRINTED", [])
    return reg


def test_nothing_to_read_on_a_program_without_the_recorder(fresh, capsys):
    fresh.histogram("train/step_wall").observe(0.2)
    fresh.counter("feed/h2d_bytes").inc(64)
    for name, reader in _readers().items():
        assert reader.read(None) is None, name
    assert "compile_phases" not in capsys.readouterr().err


def test_the_planted_totals(fresh, capsys):
    fresh.histogram("compile/trace").observe(1.5)
    fresh.histogram("compile/trace").observe(0.25)
    fresh.histogram("compile/lower").observe(2.0)
    fresh.counter("compile/cache_misses").inc(3)
    fresh.counter("compile/cache_hits").inc(5)
    got = {name: reader.read(None) for name, reader in _readers().items()}
    assert got == READERS
    err = capsys.readouterr().err
    assert err.count("compile_phases: ") == 1
    table = json.loads(err.split("compile_phases: ", 1)[1].splitlines()[0])
    assert table["trace_s"] == 1.75 and table["cache_misses"] == 3


def test_a_warm_run_misses_nothing(fresh):
    fresh.histogram("compile/backend").observe(0.5)
    fresh.counter("compile/cache_hits").inc(4)
    assert _readers()["compile_cache_misses.train"].read(None) == 0


def test_their_entries_report_in_every_cell():
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in READERS:
        entry = by_name[name]
        assert "workloads" not in entry and entry["moves"] == "setup_s"
        assert (entry["layer"], entry["source"]) == ("compile", "program_counter")
    for w in manifest["workloads"]:
        assert set(READERS) <= {m["name"] for m in harness.Cell(w["name"]).per_layer()}
