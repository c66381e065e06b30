"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: attach to the chip (anything else than TPUs of a known kind is
an error), set up, measure one window, check the outputs against the plain
reference, print one JSON line. See benchmarks/README.md.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [p for p in (HERE, os.path.dirname(HERE)) if p not in sys.path]

import harness  # noqa: E402


def main(argv=None, bench_dir=harness.HERE, require_chip=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return _run(args, bench_dir, require_chip)
    except harness.BenchError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 3


def _run(args, bench_dir, require_chip):
    fixed = sorted(k for k in os.environ if k.startswith("BIGDL_"))
    if fixed:
        raise harness.BenchError(
            f"{fixed} set in the environment: the benchmark runs the program "
            f"as its API configures it, and no BIGDL_* variable may steer it")
    cell = harness.Cell(args.workload, bench_dir)
    import jax
    # every program goes to the persistent cache, however fast it compiled,
    # so that a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    import bigdl_tpu  # noqa: F401  places the cache: $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    if require_chip:
        devices, peak = harness.attach(cell.chips)
    else:   # the tests' way in: any device, the first row of the peaks table
        devices = jax.devices()
        peak = next(iter(harness.load_json(
            os.path.join(harness.HERE, "peaks.json"))["devices"].values()))
    print(f"platform: {devices[0].platform}  device_kind: {devices[0].device_kind}  "
          f"count: {len(devices)}  cache: {jax.config.jax_compilation_cache_dir}",
          file=sys.stderr)
    out = cell.runner.run(cell, args.seed, args.seconds, bool(args.trace),
                          T_START, devices, peak)
    print(f"window: {out['extra']}", file=sys.stderr)
    device = out["device"]
    if args.trace:
        specs, values = cell.per_layer(), out["per_layer"]["values"]
        device.update(busy_s=out["per_layer"]["busy_s"],
                      window_s=out["per_layer"]["window_s"])
    else:
        specs, values = cell.end_to_end(), out["values"]
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"],
              "metrics": {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                          for m in specs if m["name"] in values},
              "device": device}
    if args.trace:
        result["breakdown"] = out["per_layer"]["breakdown"]
    result.update(out["extra"])
    harness.print_result(result, out["compared"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
