"""Compile phases, from jax's own monitoring events — where set-up goes.

jax reports every compile in three phases. Each is a start
(``record_scalar`` with the Unix time), then a duration and a Unix time span,
all carrying the ``fun_name`` of what is compiled:

- ``/jax/core/compile/jaxpr_trace_duration`` — tracing Python to a jaxpr;
- ``/jax/core/compile/jaxpr_to_mlir_module_duration`` — lowering the jaxpr to
  MLIR (where Pallas kernels lower to Mosaic);
- ``/jax/core/compile/backend_compile_duration`` — the backend compile
  request, whether XLA compiles or the persistent cache answers.

and its persistent cache counts ``compile_requests_use_cache``, ``cache_hits``
and ``cache_misses`` (a miss is counted as its entry is written), with the
seconds each hit took to read back.

:func:`install` (called once from ``import bigdl_tpu``, before any program
of the package compiles) listens to all of them and records, always:

- histograms ``compile/trace``, ``compile/lower``, ``compile/backend``
  (seconds) and ``compile/cache_retrieval`` (seconds a hit took);
- counters ``compile/cache_requests``, ``compile/cache_hits``,
  ``compile/cache_misses``;
- a table by program: seconds and count by (phase, ``fun_name``)
  (:func:`largest`, :func:`summary`: the run report and ``/statusz``).

jax's trace events nest: tracing ``jit(step)`` fires one for every inner
function and eager op it traces, then one for ``step``. A depth per thread
and phase, opened by the start and closed by the span, keeps only the
outermost event, so a phase's total is wall seconds. With tracing on
(:mod:`bigdl_tpu.obs.trace`) each outermost event is also a span of the
phase's name with ``args={"fun": fun_name}`` at the Unix times jax gives;
with tracing off nothing is added to the span ring.

No event fires on the dispatch of a compiled program: the cost is a few
callbacks a compile, at set-up, and none a step.
"""

from __future__ import annotations

import threading
from typing import Optional

from bigdl_tpu.obs import trace
from bigdl_tpu.obs.registry import registry

#: jax's phase events -> the registry's histogram and span name
PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
}
#: jax's persistent-cache events -> the registry's counter
CACHE_COUNTERS = {
    "/jax/compilation_cache/compile_requests_use_cache": "compile/cache_requests",
    "/jax/compilation_cache/cache_hits": "compile/cache_hits",
    "/jax/compilation_cache/cache_misses": "compile/cache_misses",
}
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

_lock = threading.Lock()
_depth: dict = {}          # (thread id, phase) -> events open on that thread
_programs: dict = {}       # (phase, fun_name) -> [count, seconds], outermost only
_installed = False


def _on_start(event: str, value, **_) -> None:
    phase = PHASES.get(event)
    if phase is not None:
        key = (threading.get_ident(), phase)
        _depth[key] = _depth.get(key, 0) + 1


def _on_span(event: str, start: float, end: float, fun_name: str = "?",
             **_) -> None:
    phase = PHASES.get(event)
    if phase is None:
        return
    key = (threading.get_ident(), phase)
    depth = _depth.pop(key, 0) - 1
    if depth > 0:               # an inner event: its time is the outer one's
        _depth[key] = depth
        return
    seconds = end - start
    registry.histogram(phase).observe(seconds)
    with _lock:
        slot = _programs.get((phase, fun_name))
        if slot is None:
            _programs[(phase, fun_name)] = [1, seconds]
        else:
            slot[0] += 1
            slot[1] += seconds
    if trace.enabled():
        t0 = int(start * 1e9)
        trace.record_span(phase, t0, int(end * 1e9) - t0, {"fun": fun_name})


def _on_event(event: str, **_) -> None:
    counter = CACHE_COUNTERS.get(event)
    if counter is not None:
        registry.counter(counter).inc()


def _on_duration(event: str, duration: float, **_) -> None:
    if event == CACHE_RETRIEVAL:
        registry.histogram("compile/cache_retrieval").observe(duration)


def install() -> None:
    """Register the listeners with ``jax.monitoring``, once a process."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    from jax import monitoring
    monitoring.register_scalar_listener(_on_start)
    monitoring.register_event_time_span_listener(_on_span)
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


def largest(n: int = 8) -> list:
    """The ``n`` (phase, program) pairs that took the most seconds since the
    process started: ``[phase, fun_name, seconds, count]``, largest first;
    the phase without its ``compile/``."""
    with _lock:
        rows = [[phase.split("/", 1)[1], fun, seconds, count]
                for (phase, fun), (count, seconds) in _programs.items()]
    rows.sort(key=lambda r: -r[2])
    return [[p, f, round(s, 3), c] for p, f, s, c in rows[:n]]


def summary(snapshot: Optional[dict] = None, n: int = 8) -> dict:
    """Totals since the process started, from a registry snapshot (a fresh
    one without it), and the ``n`` largest programs: the run report's and
    ``/statusz``'s block. Empty where nothing was compiled."""
    snap = registry.snapshot() if snapshot is None else snapshot
    hists, counters = snap.get("histograms", {}), snap.get("counters", {})
    out = {}
    for phase in PHASES.values():
        h = hists.get(phase)
        if h:
            short = phase.split("/", 1)[1]
            out[short + "_s"] = round(h["total"], 3)
            out[short + "_count"] = h["count"]
    if not out:
        return {}
    for counter in CACHE_COUNTERS.values():
        out[counter.split("/", 1)[1]] = counters.get(counter, 0)
    out["programs"] = largest(n)
    return out
