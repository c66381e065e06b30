"""Set-up's compile phases as the program records them (`bigdl_tpu/obs/
compile.py`): registry totals of jax's outermost trace, lowering and backend
compile events, and the compile requests its persistent cache did not
answer. Read with the per-layer metrics, after the window and before the
reference; the window compiles nothing (`compiles_in_window` 0), so the
totals are set-up's and warm-up's. The first read leaves the programs that
took longest on standard error, one `compile_phases: {...}` line."""

import json
import sys

_PRINTED = []


def totals():
    """{"trace", "lower", "backend": seconds, "cache_misses": count}, or None
    where the program records no `compile/*` entry (a program from before
    its recorder: the metric is then left out of the line)."""
    from bigdl_tpu.obs.registry import registry
    snap = registry.snapshot()
    hists, counters = snap["histograms"], snap["counters"]
    if not any(k.startswith("compile/") for k in (*hists, *counters)):
        return None
    if not _PRINTED:
        from bigdl_tpu.obs import compile as phases
        print("compile_phases: " + json.dumps(phases.summary(snap, n=12)), file=sys.stderr)
        _PRINTED.append(True)
    out = {p: hists.get("compile/" + p, {}).get("total", 0.0)
           for p in ("trace", "lower", "backend")}
    out["cache_misses"] = counters.get("compile/cache_misses", 0)
    return out
