"""The package's boxes and the arrows between them, and the documents against
the tree: what may import what, with every upward import written down as a
debt beside the files that make it; and every name a document gives a reader
(a ``BIGDL_*`` switch, a path, a ``bigdl-tpu`` sub-command) exists.

Read with ``ast`` and ``re``: nothing is imported but ``bigdl_tpu.cli`` and
``bigdl_tpu.obs.mfu``, so a case costs milliseconds."""

import ast
import glob
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "bigdl_tpu")

# ------------------------------------------------------------ import graph
# Lowest first. A box may import boxes on lines above its own; ``dataset`` and
# ``transform`` share a line, and so do the roots nothing imports.
LAYERS = [
    ("native",),
    ("utils",),
    ("obs",),
    ("kernels",),
    ("nn",),
    ("dataset", "transform"),
    ("parallel",),
    ("optim",),
    ("visualization",),
    ("models",),
    ("serving",),
    ("dlframes", "top"),        # top: __init__, cli, convergence, dryrun
    ("examples",),
]
RANK = {box: i for i, line in enumerate(LAYERS) for box in line}

# box -> the lower boxes it may import. An edge that is not here and not a
# debt fails the box's case: add a downward one here, and no upward one.
MAY = {
    "native": set(),
    "utils": set(),
    "obs": {"utils"},
    "kernels": {"utils"},
    "nn": {"utils", "obs", "kernels"},
    "dataset": {"native", "utils", "obs"},
    "transform": {"utils"},
    "parallel": {"utils", "obs", "kernels", "nn"},
    "optim": {"utils", "obs", "kernels", "nn", "dataset", "parallel"},
    "visualization": set(),
    "models": {"utils", "obs", "kernels", "nn", "dataset", "transform",
               "parallel", "optim", "visualization"},
    "serving": {"utils", "obs", "nn", "optim", "models"},
    "dlframes": {"utils", "nn", "dataset", "optim"},
    "top": {"utils", "obs", "nn", "dataset", "parallel", "optim", "models"},
    "examples": {"utils", "nn", "dataset", "transform", "optim", "models",
                 "dlframes"},
}

# The debts (ROADMAP D12): imports that point upward or sideways, each with
# the files that make it. A new file on a debt fails; a debt that is paid
# fails too, until it is taken out of this table.
DEBTS = {
    "utils": {
        "obs": {"utils/robustness.py"},
        "nn": {"utils/caffe/loader.py", "utils/caffe/ops.py",
               "utils/caffe/saver.py", "utils/serializer.py",
               "utils/tf/loader.py", "utils/tf/ops.py", "utils/tf/saver.py",
               "utils/torchfile.py"},
        "parallel": {"utils/elastic_ckpt.py"},
        "optim": {"utils/serializer.py"},
    },
    "obs": {"dataset": {"obs/access_log.py"}},
    "kernels": {"nn": {"kernels/conv_bn.py"},
                "optim": {"kernels/fused_update.py"}},
    "nn": {
        "dataset": {"nn/keras/topology.py"},
        "parallel": {"nn/attention.py"},
        "optim": {"nn/abstractnn.py", "nn/keras/topology.py"},
        "models": {"nn/incremental.py"},
    },
    "dataset": {"transform": {"dataset/image.py", "dataset/image_folder.py",
                              "dataset/recordio.py",
                              "dataset/sample_cache.py"}},
    "transform": {"dataset": {"transform/vision/image.py"}},
    "parallel": {"optim": {"parallel/embedding.py"}},
}


def _box(first: str) -> str:
    """The box of ``bigdl_tpu.<first>``: a sub-package by its name, the
    module ``dlframes`` by its own, every other top-level name ``top``."""
    if os.path.isdir(os.path.join(PKG, first)):
        return first
    return "dlframes" if first == "dlframes" else "top"


def _imports(path: str, package: list):
    """Dotted names of what the file at ``path`` imports, relative imports
    resolved against ``package``; ``from a import b`` gives ``a.b``."""
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            yield from (f"{mod}.{a.name}" for a in node.names)


def _package_files():
    for dirpath, dirnames, files in os.walk(PKG):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(dirpath, f)
                yield path, os.path.relpath(path, PKG)


def _graph():
    """box -> box it imports -> the files (relative to the package) that do,
    with ``benchmarks`` and ``tests`` as boxes of their own."""
    edges = {}
    for path, rel in _package_files():
        parts = rel.split(os.sep)
        src = _box(parts[0][:-3] if len(parts) == 1 else parts[0])
        for name in _imports(path, ["bigdl_tpu"] + parts[:-1]):
            p = name.split(".")
            if p[0] in ("benchmarks", "tests"):
                dst = p[0]
            elif p[0] == "bigdl_tpu" and len(p) > 1:
                dst = _box(p[1])
            else:
                continue
            if dst != src:
                edges.setdefault(src, {}).setdefault(dst, set()).add(
                    rel.replace(os.sep, "/"))
    return edges


@pytest.fixture(scope="module")
def graph():
    return _graph()


def test_the_table_names_every_box():
    boxes = {_box(n[:-3] if n.endswith(".py") else n) for n in os.listdir(PKG)
             if n != "__pycache__" and (n.endswith(".py") or os.path.isdir(os.path.join(PKG, n)))}
    assert boxes == set(MAY) == set(RANK)


@pytest.mark.parametrize("box", sorted(MAY))
def test_a_box_imports_what_the_table_allows(graph, box):
    assert all(RANK[dst] < RANK[box] for dst in MAY[box]), \
        f"{box}: MAY holds an edge that does not point downward"
    assert all(RANK[dst] >= RANK[box] for dst in DEBTS.get(box, {})), \
        f"{box}: a debt that points downward is no debt: move it to MAY"
    found = graph.get(box, {})
    debts = DEBTS.get(box, {})
    new = {dst: sorted(files) for dst, files in found.items()
           if dst not in MAY[box] and dst not in debts}
    assert not new, f"{box} has imports the table does not allow: {new}"
    for dst, files in debts.items():
        assert found.get(dst, set()) == files, (
            f"{box} -> {dst} is a debt of {sorted(files)}; the tree has "
            f"{sorted(found.get(dst, set()))}: bring DEBTS (and ROADMAP D12) "
            f"up to date")


def test_the_package_imports_neither_the_benchmark_nor_the_tests(graph):
    outward = {src: {dst: sorted(files) for dst, files in dsts.items()
                     if dst in ("benchmarks", "tests")}
               for src, dsts in graph.items()}
    assert not any(outward.values()), outward


# ------------------------------------------------------------------ peaks
def test_the_programs_peaks_are_the_benchmarks(monkeypatch):
    """``obs/mfu.py`` holds the live gauge's peak FLOP/s and
    ``benchmarks/peaks.json`` the benchmark's: for every device kind the
    benchmark names, the two say the same."""
    from bigdl_tpu.obs import mfu
    monkeypatch.delenv("BIGDL_PEAK_FLOPS", raising=False)
    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as f:
        devices = json.load(f)["devices"]
    assert devices
    for kind, peaks in devices.items():
        assert mfu.peak_flops_for(kind) == peaks["flops_per_s"], kind


# -------------------------------------------------------------- documents
DOCUMENTS = (["README.md", "Makefile", ".claude/skills/verify/SKILL.md"]
             + sorted(os.path.relpath(p, ROOT)
                      for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))))

SWITCH = re.compile(r"BIGDL_[A-Z0-9_]*[A-Z0-9]")
# a path as a document writes it: under one of the tree's directories, or a
# root script; up to the first character a path cannot hold
PATH = re.compile(r"(?<![\w./-])((?:bigdl_tpu|benchmarks|tests|scripts|docs)/"
                  r"[\w./*-]*[\w*]|\w+\.py)(?![\w/])")


@pytest.fixture(scope="module")
def switches_read():
    read = set()
    for path, _ in _package_files():
        with open(path) as f:
            read |= set(SWITCH.findall(f.read()))
    return read


@pytest.fixture(scope="module")
def sub_commands():
    import contextlib
    import io

    from bigdl_tpu import cli
    out = io.StringIO()
    with contextlib.redirect_stdout(out), pytest.raises(SystemExit):
        cli.main(["--help"])
    return set(re.search(r"\{([\w,-]+)\}", out.getvalue()).group(1).split(","))


def _spans(document: str, text: str):
    """What a document sets apart as code: every line of the Makefile; of a
    Markdown file the lines of its fenced blocks and its backticked spans."""
    if document == "Makefile":
        return text.splitlines()
    fenced = re.findall(r"^```[^\n]*\n(.*?)^```", text, re.M | re.S)
    inline = re.findall(r"`([^`\n]+)`", re.sub(r"^```.*?^```", "", text, flags=re.M | re.S))
    return [line for block in fenced for line in block.splitlines()] + inline


def test_the_documents_are_all_here():
    assert len(DOCUMENTS) == 10 and all(
        os.path.isfile(os.path.join(ROOT, d)) for d in DOCUMENTS), DOCUMENTS


@pytest.mark.parametrize("document", DOCUMENTS)
def test_a_document_names_what_exists(document, switches_read, sub_commands):
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    here = os.path.dirname(os.path.join(ROOT, document))
    # a root script, or a path from the root; docs/ also name their neighbours bare
    missing = {path for span in _spans(document, text) for path in PATH.findall(span)
               if not (glob.glob(os.path.join(ROOT, path))
                       or ("/" not in path and glob.glob(os.path.join(here, path))))}
    wrong = {
        "switches nothing under bigdl_tpu/ reads":
            set(SWITCH.findall(text)) - switches_read,
        "paths that are not in the tree": missing,
        "sub-commands cli.py does not parse":
            # ("bigdl-tpu run report" is the heading the run report prints)
            set(re.findall(r"bigdl-tpu (?!run report)([a-z][\w-]*)", text)) - sub_commands,
    }
    assert not any(wrong.values()), (
        f"{document} names " + "; ".join(f"{what}: {sorted(names)}"
                                         for what, names in wrong.items() if names))
