"""Tests of the benchmark's own files, on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

They are not part of the repo's tier-1 run (`tests/`). A tiny copy of the
benchmark (`tiny_bench`) stands in for the chip's cells: the same harness,
runner, references and readers over configurations cut to what a CPU holds,
which also shows that a cell is added with files alone.
"""

import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# no persistent compile cache here, as in the repo's own tests: XLA:CPU
# complains about its machine's features on every hit
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

# `tiny/<config>.json`: a configuration's cut to what a CPU holds in a test
# (`config`, laid over its file), its traffic mixes' cuts (`traffic`, by mix)
# and the limits read at that size on the CPU (`limits`). A configuration
# brings its own as a new file; the cells of one that brings none are not in
# the tiny copy, and a test that names one skips (`test_run.py`, `_run`).
TINY_DIR = os.path.join(HERE, "tiny")


@pytest.fixture(scope="session")
def tiny_bench(tmp_path_factory):
    """A copy of the benchmark with tiny configurations and traffic, and one
    throw-away cell more, added by files alone."""
    root = tmp_path_factory.mktemp("tiny")
    bench = root / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "limits"))
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    found = {c["name"]: os.path.join(TINY_DIR, c["name"] + ".json")
             for c in manifest["configs"]}
    cuts = {name: json.load(open(path)) for name, path in found.items()
            if os.path.exists(path)}
    manifest["configs"] = [c for c in manifest["configs"] if c["name"] in cuts]
    manifest["workloads"] = [w for w in manifest["workloads"] if w["config"] in cuts]
    for name, cut in cuts.items():
        path = bench / "configs" / f"{name}.json"
        path.write_text(json.dumps({**json.loads(path.read_text()), **cut["config"]}))
        for mix, less in cut["traffic"].items():
            path = bench / "traffic" / f"{mix}.json"
            path.write_text(json.dumps({**json.loads(path.read_text()), **less}))
    # the throw-away cell: a new traffic file, a new entry, no edit elsewhere
    extra = json.loads((bench / "traffic" / "train-t1024.json").read_text())
    extra.update(batch=2, fuse_steps=2)
    (bench / "traffic" / "train-throwaway.json").write_text(json.dumps(extra))
    manifest["workloads"].append(
        {"name": "gpt2-medium.train-throwaway", "config": "gpt2-medium",
         "traffic": "train-throwaway", "chips": 1, "why": "a test's cell"})
    (bench / "limits").mkdir()
    for w in manifest["workloads"]:
        (bench / "limits" / f"{w['name']}.json").write_text(
            json.dumps({"limits": cuts[w["config"]]["limits"]}))
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(bench)
