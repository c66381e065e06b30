"""The unified end-of-run report — one merged view of a training run.

:func:`build_report` reduces a registry snapshot delta (step-time
percentiles, feed-stage attribution, feed stalls, phase means, robustness
counters) plus the tracer's span totals and the process's compile phases
(``obs/compile.py``: seconds by phase, the persistent cache's answers, the
programs that took longest) into one plain-data dict; the
Optimizer stores it in ``state["run_report"]``, logs :func:`format_report`,
and appends it to the JSONL event log, where ``bigdl-tpu diag <jsonl>``
re-renders the IDENTICAL text — the on-call engineer reads the same report
whether the process is still alive or all that's left is the log.
"""

from __future__ import annotations

from typing import Optional

from bigdl_tpu.obs import compile as obs_compile


def _hist_delta(snap0: dict, snap1: dict, name: str) -> Optional[dict]:
    """Per-run (count, mean) delta for one histogram; window percentiles come
    from the newer snapshot (recent observations ≈ this run)."""
    h1 = snap1.get("histograms", {}).get(name)
    if h1 is None:
        return None
    h0 = snap0.get("histograms", {}).get(name, {})
    dc = h1["count"] - h0.get("count", 0)
    dt = h1["total"] - h0.get("total", 0.0)
    if dc <= 0:
        return None
    return {"count": dc, "mean": dt / dc,
            "p50": h1["p50"], "p95": h1["p95"], "p99": h1["p99"]}


def _counter_deltas(snap0: dict, snap1: dict, prefix: str) -> dict:
    out = {}
    c0 = snap0.get("counters", {})
    for name, n in snap1.get("counters", {}).items():
        if not name.startswith(prefix):
            continue
        d = n - c0.get(name, 0)
        if d > 0:
            out[name[len(prefix):]] = d
    return out


def build_report(snap0: dict, snap1: dict,
                 span_totals: Optional[dict] = None,
                 robustness: Optional[dict] = None,
                 watchdog_dumps: int = 0) -> dict:
    """Merge a run's registry delta + span totals into the report dict, with
    the process's compile phases as of ``snap1``
    (:func:`~bigdl_tpu.obs.compile.summary`). Everything is
    JSON-plain (ints/floats/strings) so the dict survives the JSONL round
    trip bit-for-bit and ``diag`` re-renders identical text."""
    rep: dict = {}
    step = _hist_delta(snap0, snap1, "train/step_wall")
    if step is not None:
        rep["steps"] = {
            "count": step["count"],
            "mean_ms": round(step["mean"] * 1e3, 3),
            "p50_ms": round(step["p50"] * 1e3, 3),
            "p95_ms": round(step["p95"] * 1e3, 3),
            "p99_ms": round(step["p99"] * 1e3, 3),
        }
    thr = snap1.get("gauges", {}).get("train/throughput")
    if thr is not None:
        rep["throughput_records_per_sec"] = round(thr, 1)
    stages = {}
    for name in snap1.get("histograms", {}):
        if name.startswith("feed/"):
            stage = name[len("feed/"):]
        elif name == "phase/put_batch":
            stage = "h2d"
        else:
            continue
        d = _hist_delta(snap0, snap1, name)
        if d is not None:
            stages[stage] = {"mean_ms": round(d["mean"] * 1e3, 3),
                             "count": d["count"]}
    if stages:
        rep["feed_stages"] = stages
    stalls = _counter_deltas(snap0, snap1, "train/").get("feed_stall", 0)
    rep["feed_stalls"] = stalls
    phases = {}
    for name in snap1.get("histograms", {}):
        if not name.startswith("phase/"):
            continue
        d = _hist_delta(snap0, snap1, name)
        if d is not None:
            phases[name[len("phase/"):]] = round(d["mean"] * 1e3, 3)
    if phases:
        rep["phases_mean_ms"] = phases
    rob = robustness if robustness is not None \
        else _counter_deltas(snap0, snap1, "robustness/")
    if rob:
        rep["robustness"] = dict(rob)
    if span_totals:
        top = sorted(span_totals.items(),
                     key=lambda kv: kv[1]["total_ms"], reverse=True)[:12]
        rep["spans"] = {name: dict(v) for name, v in top}
    if watchdog_dumps:
        rep["watchdog_dumps"] = int(watchdog_dumps)
    compiles = obs_compile.summary(snap1)
    if compiles:
        rep["compile"] = compiles
    return rep


def format_report(rep: dict) -> str:
    """Deterministic text rendering — the trainer's end-of-run log and the
    ``diag`` subcommand produce byte-identical output from the same dict."""
    lines = ["=== bigdl-tpu run report ==="]
    steps = rep.get("steps")
    if steps:
        lines.append(
            f"steps: {steps['count']}  "
            f"mean {steps['mean_ms']:.3f} ms  "
            f"p50 {steps['p50_ms']:.3f}  p95 {steps['p95_ms']:.3f}  "
            f"p99 {steps['p99_ms']:.3f}")
    thr = rep.get("throughput_records_per_sec")
    if thr is not None:
        lines.append(f"throughput: {thr:.1f} records/s")
    stages = rep.get("feed_stages")
    if stages:
        parts = ", ".join(
            f"{s} {d['mean_ms']:.3f} (x{d['count']})"
            for s, d in sorted(stages.items()))
        lines.append(f"feed stages (mean ms): {parts}")
    lines.append(f"feed stalls: {rep.get('feed_stalls', 0)}")
    phases = rep.get("phases_mean_ms")
    if phases:
        parts = ", ".join(f"{k} {v:.3f}" for k, v in sorted(phases.items()))
        lines.append(f"phases (mean ms): {parts}")
    rob = rep.get("robustness")
    if rob:
        parts = "; ".join(f"{k}={v}" for k, v in sorted(rob.items()))
        lines.append(f"robustness: {parts}")
    else:
        lines.append("robustness: no events")
    spans = rep.get("spans")
    if spans:
        parts = ", ".join(
            f"{name} {d['total_ms']:.1f}ms (x{d['count']})"
            for name, d in spans.items())
        lines.append(f"span totals: {parts}")
    if rep.get("watchdog_dumps"):
        lines.append(f"watchdog dumps: {rep['watchdog_dumps']}")
    comp = rep.get("compile")
    if comp:
        parts = ", ".join(f"{p} {comp[p + '_s']:.3f} s (x{comp[p + '_count']})"
                          for p in ("trace", "lower", "backend") if p + "_s" in comp)
        lines.append(f"compile since process start: {parts}; persistent cache "
                     f"hits {comp.get('cache_hits', 0)}, misses "
                     f"{comp.get('cache_misses', 0)}")
        progs = ", ".join(f"{p} {f} {s:.3f} s (x{c})"
                          for p, f, s, c in comp.get("programs", []))
        if progs:
            lines.append(f"largest compiles: {progs}")
    return "\n".join(lines)
