"""Unified observability subsystem — one rail for traces, metrics, hangs.

The framework grew four disjoint observability rails (``optim/metrics.py``
phase timings, ``dataset/profiling.py`` feed-stage stats,
``utils/robustness.py`` recovery events, and the ``TrainSummary`` curves),
each with its own accumulator and consumer glue. This package unifies them:

- :mod:`bigdl_tpu.obs.trace` — thread-aware span tracer with Chrome-trace /
  Perfetto JSON export and a structured JSONL event log, gated by
  ``BIGDL_TRACE`` with a near-zero-cost disabled path;
- :mod:`bigdl_tpu.obs.registry` — process-wide metric registry (counters /
  gauges / histograms with p50/p95/p99) that the legacy rails publish
  through, so every consumer reads ONE source;
- :mod:`bigdl_tpu.obs.watchdog` — hang watchdog: a step/window exceeding
  N× the rolling median (or a hard ``BIGDL_WATCHDOG_S`` timeout) dumps all
  Python thread stacks plus the open-span tree to stderr and the JSONL log;
- :mod:`bigdl_tpu.obs.report` — the end-of-run report (step-time
  percentiles, feed-stage attribution, robustness counters, span totals),
  rendered identically by the trainer and ``bigdl-tpu diag``;
- :mod:`bigdl_tpu.obs.exporter` — live ``/metrics`` (Prometheus text) +
  ``/healthz`` + ``/statusz`` endpoint on ``BIGDL_METRICS_PORT`` (stdlib
  http.server; zero-alloc no-op when the port is unset);
- :mod:`bigdl_tpu.obs.mfu` — always-on MFU accounting: per-compiled-program
  XLA cost-analysis FLOPs feeding live ``train/mfu`` and
  ``serve/model_flops_per_sec`` gauges against a peak-FLOPs table;
- :mod:`bigdl_tpu.obs.slo` — SLO monitor over windowed registry percentiles
  (p99 TTFT, feed-stall rate, throughput floor) whose breach events flip
  serving health to ``degraded``;
- :mod:`bigdl_tpu.obs.device` — device-memory accounting: HBM gauges from
  ``memory_stats()``, live-buffer census, per-program ``memory_analysis()``
  attribution, ``hbm_pressure`` events (``BIGDL_HBM_PRESSURE_PCT``);
- :mod:`bigdl_tpu.obs.cluster` — multi-host aggregation: per-process
  snapshot spools (``BIGDL_OBS_SPOOL_DIR``) merged into one ``/metrics``
  scrape with ``{host=}`` labels;
- :mod:`bigdl_tpu.obs.access_log` — opt-in structured request log
  (``BIGDL_ACCESS_LOG``) with the ``to_bdlrec`` flywheel converter;
- :mod:`bigdl_tpu.obs.compile` — jax's trace, lowering and backend-compile
  phases and the persistent cache's answers as always-on registry totals, a
  table by program, and spans when tracing is on (installed by
  ``import bigdl_tpu``).

Dependency-free by design: nothing here imports ``optim``/``dataset``/
``nn``, so every layer of the framework may publish into it (``mfu``
imports jax lazily; ``slo`` reaches the robustness event rail lazily).
"""

from __future__ import annotations

import os

from bigdl_tpu.obs import access_log, cluster, compile, device, exporter, \
    mfu, registry, report, slo, trace, watchdog
from bigdl_tpu.obs.registry import registry as metric_registry


def describe_config() -> str:
    """One human-readable block of the active observability configuration
    (printed by the CLI at startup when ``BIGDL_TRACE`` is set)."""
    trace.configure_from_env()
    wd = os.environ.get("BIGDL_WATCHDOG_S", "")
    lines = [
        "observability:",
        f"  trace      = {'on' if trace.enabled() else 'off'}"
        f" (BIGDL_TRACE={os.environ.get('BIGDL_TRACE', '')!r})",
        f"  trace dir  = {trace.trace_dir() or '-'}",
        f"  chrome out = {trace.chrome_path() or '-'}",
        f"  event log  = {trace.jsonl_path() or '-'}"
        f" (BIGDL_OBS_LOG={os.environ.get('BIGDL_OBS_LOG', '')!r})",
        f"  watchdog   = {wd + 's hard timeout' if wd else 'off'}"
        f" (BIGDL_WATCHDOG_S)",
        f"  metrics    = "
        f"{'port ' + os.environ.get('BIGDL_METRICS_PORT') if os.environ.get('BIGDL_METRICS_PORT', '').strip() else 'off'}"
        f" (BIGDL_METRICS_PORT)",
    ]
    return "\n".join(lines)


__all__ = ["trace", "registry", "watchdog", "report", "exporter", "mfu",
           "slo", "device", "cluster", "access_log", "compile",
           "metric_registry", "describe_config"]
