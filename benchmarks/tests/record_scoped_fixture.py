"""Records the fixture of `test_scoped_trace.py` on the chip, and reads what a
test cannot: the scopes inside a scanned window, and the skew between the
program's clock and the profile's.

    chiprun -- python3 benchmarks/tests/record_scoped_fixture.py

Writes `chiprun_out/scoped_fixture/tiny_lm_scoped.xplane.pb` (the tiny LM cell
of `conftest.TINY`: twelve steps, three epochs of four batches, the device's
record alone) and `tiny_lm_scoped.spans.json` (the program's spans over the
same extent) to be copied to `tests/data/`, and prints one JSON line: the
bills of the per-step program and of a fused window of two steps
(`fuse_steps` 2: the scopes have to survive `lax.scan`), and for five
blocking dispatches of a small program how long before the device's
execution the span began and how long after it the span ended. The execution lies inside the span when the clocks agree; the
smaller of the two margins bounds the skew.
"""

import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

# the sizes of conftest.TINY (importing conftest would pin JAX to the CPU)
TINY = {"n_embd": 64, "n_head": 4, "n_layer": 2, "vocab_size": 512,
        "n_positions": 32, "n_ctx": 32}
TRAFFIC = {"batch": 4, "seq_len": 32}
STEPS = 12
SEED = 2_600_000_011


def tiny_copy():
    """A copy of the benchmark with the tiny LM and two cells of it: one step
    a dispatch, and two steps fused."""
    root = tempfile.mkdtemp(prefix="scoped-fixture-")
    bench = os.path.join(root, "benchmarks")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "limits"))
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))

    def patch(path, cut):
        json.dump({**json.load(open(path)), **cut}, open(path, "w"))

    patch(os.path.join(bench, "configs", "gpt2-medium.json"), TINY)
    traffic = os.path.join(bench, "traffic", "train-t1024.json")
    patch(traffic, TRAFFIC)
    fused = os.path.join(bench, "traffic", "train-fused.json")
    shutil.copy(traffic, fused)
    patch(fused, {"fuse_steps": 2})
    manifest["workloads"].append(
        {"name": "gpt2-medium.train-fused", "config": "gpt2-medium",
         "traffic": "train-fused", "chips": 1, "why": "the recorder's cell"})
    json.dump(manifest, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return bench


def start_device_trace(trace_dir):
    """The profiler with the device's record alone, as the runner takes it."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def traced_steps(bench, name, devices):
    """`STEPS` steps of a cell under the profiler, the runner's set-up and
    warm-up before them: the profile's path and its `Scoped`."""
    import jax

    import harness
    import scoped_trace
    import trace_reduce
    from bigdl_tpu.obs import trace as program
    from bigdl_tpu.optim import Trigger

    cell = harness.Cell(name, bench)
    st = cell.runner.setup(cell, SEED, devices)
    opt = st["opt"]
    trace_dir = tempfile.mkdtemp(prefix="fixture-trace-")
    program.configure(enabled=True, trace_dir=trace_dir)
    start_device_trace(trace_dir)
    opt.set_end_when(Trigger.max_iteration(opt.state["neval"] - 1 + STEPS))
    opt.optimize()
    jax.profiler.stop_trace()
    cell.runner.release(st)
    path = trace_reduce.find_xplane(trace_dir)
    return path, scoped_trace.Scoped(path, program.spans_between)


def inside_the_scan(scoped):
    """Bills of the operations that ran inside the window's `while`."""
    import scoped_trace
    import trace_reduce
    holder = trace_reduce.CONTAINER.match
    holders = [op for op in scoped.ops[0] if holder(op.name)]
    held = [op for op in scoped.ops[0] if not holder(op.name)
            and any(h.start <= op.start and op.end <= h.end for h in holders)]
    bills = {}
    for op in held:
        b = scoped_trace.bill(op.tf_op)
        bills[b] = bills.get(b, 0) + 1
    sample = next((op.tf_op for op in held if "bigdl_update" in op.tf_op), None)
    return {"holders": len(holders), "operations": len(held), "bills": bills,
            "a_tf_op": sample}


def clock_skew():
    """Five blocking dispatches of one small program under a span each, the
    profiler on: (span start to execution start, execution end to span end),
    microseconds."""
    import jax
    import jax.numpy as jnp

    import scoped_trace
    import trace_reduce
    from bigdl_tpu.obs import trace as program

    def skew_probe(x):
        return (x @ x).sum()

    run = jax.jit(skew_probe)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    run(x).block_until_ready()
    trace_dir = tempfile.mkdtemp(prefix="skew-trace-")
    start_device_trace(trace_dir)
    for _ in range(5):
        with program.span("skew/blocking_dispatch"):
            run(x).block_until_ready()
    jax.profiler.stop_trace()
    scoped = scoped_trace.Scoped(trace_reduce.find_xplane(trace_dir),
                                 program.spans_between)
    runs = [(s, e) for s, e, name in scoped.modules[0] if "skew_probe" in name]
    spans = [s for s in scoped.spans if s.name == "skew/blocking_dispatch"]
    shutil.rmtree(trace_dir, ignore_errors=True)
    return [{"before_us": (r[0] - s.start) / 1e6, "after_us": (s.end - r[1]) / 1e6,
             "execution_us": (r[1] - r[0]) / 1e6}
            for s, r in zip(spans, runs)]


def main():
    import jax
    import bigdl_tpu  # noqa: F401  places the compile cache
    import harness
    import scoped_trace

    devices, _ = harness.attach(1)
    out_dir = os.path.join(ROOT, "chiprun_out", "scoped_fixture")
    os.makedirs(out_dir, exist_ok=True)
    bench = tiny_copy()
    path, scoped = traced_steps(bench, "gpt2-medium.train-t1024", devices)
    shutil.copy(path, os.path.join(out_dir, "tiny_lm_scoped.xplane.pb"))
    spans = [{"name": s.name, "tid": s.tid, "start_ps": s.start, "end_ps": s.end,
              "args": s.args} for s in scoped.spans]
    json.dump(spans, open(os.path.join(out_dir, "tiny_lm_scoped.spans.json"), "w"))
    result = {"fixture_bytes": os.path.getsize(path), "spans": len(spans),
              "step": {"by_scope": scoped.by_scope(), "busy_s": scoped.busy_s,
                       "held_elsewhere": scoped.held_elsewhere(),
                       "idle_split": scoped.idle_split(),
                       "longest_gaps": scoped.longest_gaps(5)}}
    _, fused = traced_steps(bench, "gpt2-medium.train-fused", devices)
    result["window"] = {"by_scope": fused.by_scope(), "busy_s": fused.busy_s,
                        "inside_the_scan": inside_the_scan(fused),
                        "idle_split": fused.idle_split(),
                        "longest_gaps": fused.longest_gaps(5)}
    jax.clear_caches()
    result["clock_skew"] = clock_skew()
    print(json.dumps(result))
    json.dump(result, open(os.path.join(out_dir, "result.json"), "w"), indent=1)


if __name__ == "__main__":
    main()
