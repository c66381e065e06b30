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

TINY = {
    "gpt2-medium": {"n_embd": 64, "n_head": 4, "n_layer": 2, "vocab_size": 512,
                    "n_positions": 32, "n_ctx": 32},
    "resnet50": {"image_size": 32, "class_num": 10},
}
# ResNet-50 on 8 images of 32x32 is chaotic at the cell's learning rate (the
# loss rises from 2.7 to 10 in two steps); a tenth of it keeps nine steps close
TINY_TRAFFIC = {
    "train-t1024": {"batch": 4, "seq_len": 32},
    "train-stream": {"batch": 8, "n_batches": 16, "label_classes": 4,
                     "optim_method": {"class": "SGD", "args": {
                         "learningrate": 0.002, "momentum": 0.9, "dampening": 0.0}}},
}
# limits at these sizes, from readings on the CPU (the cells' own are read on
# the chip): GPT-2 reads grad1 0.0034-0.0045 and change 0.001-0.007 over 4
# seeds, its float8 control 0.031 and 0.030 on 2; ResNet reads change_median
# 0.01-0.05 and momentum_median 0.02-0.06 over 2 seeds, a fault 0.8 and more
TINY_LIMITS = {
    "gpt2-medium": {"grad1": 0.012, "change": 0.015},
    "resnet50": {"change_median": 0.3, "momentum_median": 0.3},
}


@pytest.fixture(scope="session")
def tiny_bench(tmp_path_factory):
    """A copy of the benchmark with tiny configurations and traffic, and one
    throw-away cell more, added by files alone."""
    root = tmp_path_factory.mktemp("tiny")
    bench = root / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "limits"))
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for name, cut in TINY.items():
        path = bench / "configs" / f"{name}.json"
        path.write_text(json.dumps({**json.loads(path.read_text()), **cut}))
    for name, cut in TINY_TRAFFIC.items():
        path = bench / "traffic" / f"{name}.json"
        path.write_text(json.dumps({**json.loads(path.read_text()), **cut}))
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
            json.dumps({"limits": TINY_LIMITS[w["config"]]}))
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(bench)
