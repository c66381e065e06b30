"""Readings for a cell's limits, taken on the chip at the cell's own size. No
benchmark run calls this; a `benchmark` PR does, when it sets or checks
`limits/<cell>.json`:

    python3 benchmarks/calibrate.py --workload <cell> --seeds 1,2,3 [--control 3]

For each seed: the program's first steps against the reference (the lower
readings). For the first `--control` seeds also, each in the program's place:
the reference in float8 (the control), the reference on half of every batch
with the mean over that half, and a step that leaves its state unchanged (the
faults). Each is judged by the cell's own limits, as a run is: `verdict` says
which came out correct and which numbers failed the others. One JSON line a
seed on standard output. Without `limits/<cell>.json` (a new cell) the
readings come without a verdict.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [p for p in (HERE, os.path.dirname(HERE)) if p not in sys.path]

import check  # noqa: E402
import harness  # noqa: E402


def main(argv=None, bench_dir=harness.HERE, require_chip=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control", type=int, default=0,
                    help="read the control and the faults on the first N seeds")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, bench_dir)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    import bigdl_tpu  # noqa: F401
    devices = harness.attach(cell.chips)[0] if require_chip else jax.devices()
    runner = cell.runner
    try:
        limits = cell.limits()
    except harness.BenchError:
        limits = None
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        st = runner.setup(cell, seed, devices, warm_up=False)
        observed = st["observed"]
        runner.release(st, programs=False)
        want = runner.verify(cell, seed, st, observed)
        worst = {}
        readings = {"program": check.compare(observed, want, worst)}
        if n < args.control:
            half = slice(0, cell.traffic["batch"] // 2)
            for name, fault in (("control_fp8", {"q": check.fp8}),
                                ("fault_half_batch", {"rows": half}),
                                ("fault_state_unchanged", {"frozen": True})):
                readings[name] = check.compare(
                    runner.verify(cell, seed, st, mask=want["mask"], **fault), want)
        line = {"cell": cell.name, "seed": seed, **readings, "worst_leaf": worst,
                "losses": {"program": observed["losses"], "reference": want["losses"]}}
        if limits is not None:
            line["verdict"] = {}
            for name, numbers in readings.items():
                ok, compared = check.judge(numbers, limits)
                line["verdict"][name] = {"correct": ok,
                                         "failed_by": check.failed_by(compared)}
        line["seconds"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
