"""What every cell of the benchmark shares: finding a cell's files by the
names in `BENCHMARK.json`, the chip and its peaks, seeds, the mapping between
the benchmark's named weights and a program's parameter tree, and the one
result line. Nothing here knows a model or a kind of traffic: those are the
files under `configs/`, `traffic/`, `work/`, `reference/`, `metrics/`,
`limits/` and `runners/`.
"""

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchError(Exception):
    """The benchmark cannot run as asked: a missing file, an unknown name, no
    chip. `run.py` prints it and exits with a code other than 0."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """A Python file of the benchmark found by name (names may hold `-` and
    `.`, so plain `import` does not reach them)."""
    if not os.path.exists(path):
        raise BenchError(f"no such file: {os.path.relpath(path, ROOT)}")
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in
                              os.path.relpath(path, HERE))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of `workloads` with everything its names point to."""

    def __init__(self, name, bench_dir=HERE):
        self.bench_dir = bench_dir
        self.manifest = load_json(
            os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise BenchError(f"unknown workload {name!r}; BENCHMARK.json has "
                             f"{sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = self.entry["chips"]
        cfg_entry = {c["name"]: c for c in self.manifest["configs"]}[
            self.entry["config"]]
        root = os.path.dirname(bench_dir)
        self.config = load_json(os.path.join(root, cfg_entry["file"]))
        self.traffic = load_json(self.path("traffic", self.entry["traffic"] + ".json"))
        self.config_mod = load_module(self.path("configs", self.entry["config"] + ".py"))
        self.work = load_module(self.path("work", self.entry["config"] + ".py"))
        method = self.traffic.get("optim_method")
        if method is not None:      # the check's side of the optimizer method
            self.method = load_module(self.path("methods", method["class"] + ".py"))
        kind = self.traffic["kind"]
        runner = self.path("runners", kind + ".py")
        if not os.path.exists(runner):
            raise BenchError(f"traffic {self.entry['traffic']!r} is of kind "
                             f"{kind!r}, and there is no runners/{kind}.py")
        self.runner = load_module(runner)

    def path(self, *parts):
        return os.path.join(self.bench_dir, *parts)

    def reference(self):
        return load_module(self.path("reference", self.entry["config"] + ".py"))

    def limits(self):
        path = self.path("limits", self.name + ".json")
        if not os.path.exists(path):
            raise BenchError(f"no limits/{self.name}.json: a cell's outputs "
                             f"are judged against limits read on the chip")
        return load_json(path)["limits"]

    def _metrics(self, group):
        return [m for m in self.manifest[group]
                if self.name in m.get("workloads", [self.name])]

    def end_to_end(self):
        return self._metrics("end_to_end")

    def per_layer(self):
        return self._metrics("per_layer")


def attach(chips):
    """The devices of this machine and the peaks of their kind. Anything but
    `chips` or more TPUs of a kind in `peaks.json` is an error."""
    import jax
    devices = jax.devices()
    peaks = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    kinds = sorted({d.device_kind for d in devices})
    if any(d.platform != "tpu" for d in devices):
        raise BenchError(f"no accelerator: JAX's devices are "
                         f"{sorted({d.platform for d in devices})}; the "
                         f"benchmark measures on the chip only")
    if len(kinds) != 1 or kinds[0] not in peaks:
        raise BenchError(f"device kind {kinds} is not in peaks.json")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices, peaks[kinds[0]]


def seed_key(seed):
    """A PRNG key from any whole number (seeds run past 32 signed bits)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _natural(path):
    out = []
    for k in path:
        k = getattr(k, "key", getattr(k, "idx", k))
        if isinstance(k, str) and k.isdigit():
            k = int(k)
        out.append((0, k, "") if isinstance(k, int) else (1, 0, str(k)))
    return out


def natural_order(tree):
    """Indices of `tree`'s leaves in the order of their paths with numbers
    read as numbers: the order in which the model was put together."""
    import jax
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    return sorted(range(len(paths)), key=lambda i: _natural(paths[i]))


def tree_from_names(template, names, weights):
    """`weights` (name -> array) laid out as `template`, a program's parameter
    tree whose leaves, in natural order, are `names` [(name, shape)]."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(template)
    order = natural_order(template)
    if len(order) != len(names):
        raise BenchError(f"the program's tree has {len(order)} leaves, the "
                         f"configuration names {len(names)}")
    out = list(leaves)
    for i, (name, shape) in zip(order, names):
        if tuple(leaves[i].shape) != tuple(shape):
            raise BenchError(f"{name}: the program holds {leaves[i].shape}, "
                             f"the configuration says {shape}")
        out[i] = weights[name]
    return jax.tree_util.tree_unflatten(treedef, out)


def names_from_tree(tree, names):
    """The inverse: a program's tree as name -> leaf."""
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    order = natural_order(tree)
    if len(order) != len(names):
        raise BenchError(f"tree of {len(order)} leaves for {len(names)} names")
    return {name: leaves[i] for i, (name, _) in zip(order, names)}


def device_report(devices, chips):
    """The device as JAX reports it, with the memory peak of the fullest chip.
    On a TPU the peak of the bytes in use holds the arrays (weights, optimizer
    state, batches) and the peak of the bytes reserved a running program's
    temporaries; the two need not fall together, so their sum is only an upper
    bound. The larger of them is what the chip held at one moment for certain,
    and that is reported; both are given beside it."""
    peak = {}
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        got = {k: int(stats.get(k, 0)) for k in ("peak_bytes_in_use", "peak_bytes_reserved")}
        if max(got.values()) >= max(peak.values(), default=0):
            peak = got
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peak.values()), **peak}


def print_result(result, compared):
    """The comparisons as the last lines of standard error, and the one
    result line, the comparisons last in it, as the last line of standard
    output."""
    for name, (value, limit) in compared.items():
        print(f"compared {name}: {value:.6g} (limit {limit:.6g})", file=sys.stderr)
    sys.stderr.flush()
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    print(json.dumps(result), flush=True)


class CompileCounter:
    """Counts the programs JAX asks its compiler for while it is entered. A
    request that the persistent cache answers counts too: the event wraps the
    look into the cache and the compilation alike, and the window should make
    neither."""

    _EVENT = "/jax/core/compile/backend_compile_duration"
    _active = []
    _registered = False

    def __init__(self):
        self.count = 0

    @classmethod
    def _listen(cls, event, duration, **kw):
        if event == cls._EVENT:
            for counter in cls._active:
                counter.count += 1

    def __enter__(self):
        import jax
        if not CompileCounter._registered:
            jax.monitoring.register_event_duration_secs_listener(CompileCounter._listen)
            CompileCounter._registered = True
        CompileCounter._active.append(self)
        # whatever does compile in here is named on standard error
        self._logged = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        return self

    def __exit__(self, *exc):
        import jax
        jax.config.update("jax_log_compiles", self._logged)
        CompileCounter._active.remove(self)
        return False
