"""Per-phase training metrics.

Reference parity (SURVEY.md §2.3, expected ``<dl>/optim/Metrics.scala`` — unverified): the
reference aggregates per-iteration phase timings (get weights / computing / aggregate
gradient / send weights) through Spark accumulators and logs them per epoch.

TPU-native: the phases collapse — weights never move (they live sharded/replicated on
device) and gradient aggregation is fused into the step — so the meaningful phase left on
the host side is the data feed (``put_batch``), logged at the end of training. Timings are
dispatch-side (async-safe); per-op device attribution comes from ``jax.profiler``.
"""

from __future__ import annotations

import threading
import time
from collections import defaultdict

from bigdl_tpu.obs.registry import registry as _obs_registry


class Metrics:
    """Thread-safe phase-timing accumulator (the producer thread times
    ``put_batch`` while the step loop times ``feed``/``step_dispatch``).
    Every add also publishes into the process-wide obs registry as
    ``phase/<name>`` — the unified run report reads one source."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._sums: dict[str, float] = defaultdict(float)
        self._counts: dict[str, int] = defaultdict(int)

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            self._sums[name] += seconds
            self._counts[name] += 1
        _obs_registry.histogram("phase/" + name).observe(seconds)

    def timer(self, name: str, span=None):
        """Time a phase; ``span`` (a ``trace.span(...)``) opens and closes on
        the same two clock reads, and ``.seconds`` holds the interval
        afterwards: one measurement for the phase, the span and whoever else
        asks."""
        return _Timer(self, name, span)

    def summary(self) -> dict[str, float]:
        """Mean seconds per phase occurrence."""
        with self._lock:
            return {k: self._sums[k] / max(self._counts[k], 1) for k in self._sums}

    def totals(self) -> dict[str, float]:
        """Total seconds per phase."""
        with self._lock:
            return dict(self._sums)

    def counts(self) -> dict[str, int]:
        """Occurrences per phase (feed-stage attribution needs sums AND
        counts to diff mean ms across a window)."""
        with self._lock:
            return dict(self._counts)

    def reset(self) -> None:
        with self._lock:
            self._sums.clear()
            self._counts.clear()

    def __repr__(self):
        parts = ", ".join(f"{k} {v * 1e3:.2f}ms" for k, v in sorted(self.summary().items()))
        return f"Metrics({parts})"


class _Timer:
    def __init__(self, metrics: Metrics, name: str, span):
        self.metrics, self.name, self.span = metrics, name, span
        self.seconds = 0.0

    def __enter__(self):
        self.t0 = time.perf_counter_ns()
        if self.span is not None:
            self.span.begin(self.t0)
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self.span is not None:
            self.span.end(t1)
        self.seconds = (t1 - self.t0) / 1e9
        self.metrics.add(self.name, self.seconds)
        return False
