"""Device-memory accounting — HBM gauges, live-buffer census, program memory.

Every scale claim the roadmap makes (paged-KV residency, multi-host curves)
is HBM-bound, yet nothing in the obs plane measured device memory. This
module closes that gap with three rails, all absent-not-wrong (a backend
that won't report memory yields no gauges, never fake ones):

- :func:`sample_device_memory` — one poll of ``device.memory_stats()`` per
  local device, published as ``device/hbm_bytes_in_use`` /
  ``device/hbm_peak_bytes`` (sums over local devices) and
  ``device/hbm_headroom`` (the WORST device's free fraction) registry
  gauges, plus per-device gauges ``device/<i>/hbm_bytes_in_use``;
- :func:`live_buffer_census` — count + bytes of every live jax array by
  dtype (``jax.live_arrays()``), the leak-hunting view;
- :func:`program_memory` — per-compiled-program attribution from XLA's
  ``memory_analysis()`` (temp/argument/output/code bytes), the memory twin
  of :func:`bigdl_tpu.obs.mfu.program_flops`. Costs one lowering+compile,
  so callers memoize per program-cache key exactly as they do for FLOPs.

:class:`DeviceMonitor` is the daemon that polls the first two on an
interval, mirrors serving occupancy (paged-KV ``free_page_ratio``, page /
prefix pool bytes) from registered engines into plain registry gauges, and
fires an ``hbm_pressure`` event (JSONL + robustness rail + counter) when
the worst device's headroom drops below ``BIGDL_HBM_PRESSURE_PCT`` percent.
The latest sample is registered as a watchdog context provider, so a stall
dump carries the memory picture of the moment the step wedged.

jax is imported lazily: the obs package must stay importable without it.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

from bigdl_tpu.obs import trace
from bigdl_tpu.obs import watchdog as obs_watchdog
from bigdl_tpu.obs.registry import registry

#: memory_stats() keys accepted for "bytes in use" / "peak" / "limit" —
#: backends disagree on naming (PJRT: bytes_in_use / peak_bytes_in_use /
#: bytes_limit; some report num_allocs only, which is useless here)
_IN_USE_KEYS = ("bytes_in_use",)
_PEAK_KEYS = ("peak_bytes_in_use", "largest_alloc_size")
_LIMIT_KEYS = ("bytes_limit", "bytes_reservable_limit")

_lock = threading.Lock()
_last_sample: Optional[list] = None   # latest sample_device_memory() result
_MONITOR: Optional["DeviceMonitor"] = None
_MONITOR_LOCK = threading.Lock()


def _pick(stats: dict, keys) -> Optional[int]:
    for k in keys:
        v = stats.get(k)
        if isinstance(v, (int, float)) and v >= 0:
            return int(v)
    return None


def sample_device_memory(publish: bool = True) -> list:
    """Poll ``memory_stats()`` on every local device.

    Returns ``[{"id", "kind", "bytes_in_use", "peak_bytes", "bytes_limit",
    "headroom"}]`` — entries only for devices that actually report; an empty
    list when the backend won't say (CPU without allocator stats). With
    ``publish`` the aggregate and per-device registry gauges are updated.
    """
    devices = []
    try:
        import jax
        devices = jax.local_devices()
    except Exception:
        return []
    out = []
    for d in devices:
        try:
            st = d.memory_stats()
        except Exception:
            st = None
        if not st:
            continue
        in_use = _pick(st, _IN_USE_KEYS)
        if in_use is None:
            continue
        peak = _pick(st, _PEAK_KEYS)
        limit = _pick(st, _LIMIT_KEYS)
        headroom = (max(0.0, 1.0 - in_use / limit)
                    if limit else None)
        out.append({"id": int(getattr(d, "id", len(out))),
                    "kind": getattr(d, "device_kind", "?"),
                    "bytes_in_use": in_use, "peak_bytes": peak,
                    "bytes_limit": limit, "headroom": headroom})
    global _last_sample
    with _lock:
        _last_sample = out
    if publish and out:
        registry.gauge("device/hbm_bytes_in_use").set(
            sum(e["bytes_in_use"] for e in out))
        peaks = [e["peak_bytes"] for e in out if e["peak_bytes"] is not None]
        if peaks:
            registry.gauge("device/hbm_peak_bytes").set(sum(peaks))
        rooms = [e["headroom"] for e in out if e["headroom"] is not None]
        if rooms:
            registry.gauge("device/hbm_headroom").set(min(rooms))
        for e in out:
            registry.gauge(
                "device/%d/hbm_bytes_in_use" % e["id"]).set(e["bytes_in_use"])
    return out


def last_sample() -> Optional[list]:
    """The most recent poll (None before the first), for /statusz and the
    watchdog context provider."""
    with _lock:
        return _last_sample


def live_buffer_census(publish: bool = True) -> dict:
    """Count + bytes of every live jax array, split by dtype:
    ``{"count", "bytes", "by_dtype": {dtype: {"count", "bytes"}}}``.
    Empty-shaped dict (zero counts) when jax is absent."""
    out = {"count": 0, "bytes": 0, "by_dtype": {}}
    try:
        import jax
        arrays = jax.live_arrays()
    except Exception:
        return out
    for a in arrays:
        try:
            nbytes = int(a.dtype.itemsize)
            for dim in a.shape:
                nbytes *= int(dim)
            key = str(a.dtype)
        except Exception:
            continue
        out["count"] += 1
        out["bytes"] += nbytes
        slot = out["by_dtype"].setdefault(key, {"count": 0, "bytes": 0})
        slot["count"] += 1
        slot["bytes"] += nbytes
    if publish:
        registry.gauge("device/live_buffers").set(out["count"])
        registry.gauge("device/live_buffer_bytes").set(out["bytes"])
    return out


def program_memory(fn, *args) -> Optional[dict]:
    """Per-program memory attribution from XLA ``memory_analysis()``:
    ``{"temp_bytes", "argument_bytes", "output_bytes",
    "generated_code_bytes"}`` (fields the backend reports; None when it
    reports nothing). ``fn`` is a jitted callable; only arg shapes/dtypes
    are used (ShapeDtypeStruct avals — donation-safe, same contract as
    :func:`~bigdl_tpu.obs.mfu.program_flops`). Costs one compile: callers
    memoize per program-cache key."""
    try:
        from bigdl_tpu.obs.mfu import avals_of
        ma = fn.lower(*avals_of(args)).compile().memory_analysis()
        if ma is None:
            return None
        out = {}
        for field, attr in (("temp_bytes", "temp_size_in_bytes"),
                            ("argument_bytes", "argument_size_in_bytes"),
                            ("output_bytes", "output_size_in_bytes"),
                            ("generated_code_bytes",
                             "generated_code_size_in_bytes")):
            v = getattr(ma, attr, None)
            if isinstance(v, (int, float)) and v >= 0:
                out[field] = int(v)
        return out or None
    except Exception:
        return None


def _pressure_pct() -> Optional[float]:
    raw = os.environ.get("BIGDL_HBM_PRESSURE_PCT", "").strip()
    if not raw:
        return None
    try:
        v = float(raw)
    except ValueError:
        return None
    return v if 0 < v < 100 else None


class DeviceMonitor:
    """Daemon polling device memory + live buffers into registry gauges.

    One instance per process (:func:`start_from_env`). Each poll also
    mirrors serving occupancy from registered engines — the paged-KV
    ``free_page_ratio`` (worst engine), total page-pool and prefix-pool
    bytes — into ``serve/*`` registry gauges so memory and occupancy sit
    on the same scrape. Below ``BIGDL_HBM_PRESSURE_PCT`` percent headroom
    an ``hbm_pressure`` event fires (once per excursion, re-armed when
    headroom recovers)."""

    def __init__(self, interval_s: float = 5.0,
                 pressure_pct: Optional[float] = None):
        self.interval_s = max(float(interval_s), 0.05)
        self.pressure_pct = pressure_pct
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._in_pressure = False
        self.polls = 0

    # one poll, callable synchronously from tests and from the daemon loop
    def poll_once(self) -> None:
        sample = sample_device_memory()
        live_buffer_census()
        self._mirror_serving()
        self.polls += 1
        self._check_pressure(sample)

    def _mirror_serving(self) -> None:
        from bigdl_tpu.obs import exporter
        ratios, page_bytes, prefix_bytes = [], 0, 0
        for eng in exporter.engines():
            try:
                st = eng.stats()
            except Exception:
                continue
            r = st.get("free_page_ratio")
            if isinstance(r, (int, float)):
                ratios.append(float(r))
            pb = st.get("page_pool_bytes")
            if isinstance(pb, (int, float)):
                page_bytes += int(pb)
            xb = st.get("prefix_bytes")
            if isinstance(xb, (int, float)):
                prefix_bytes += int(xb)
        if ratios:
            registry.gauge("serve/free_page_ratio").set(min(ratios))
        if page_bytes:
            registry.gauge("serve/page_pool_bytes").set(page_bytes)
        if prefix_bytes:
            registry.gauge("serve/prefix_pool_bytes").set(prefix_bytes)

    def _check_pressure(self, sample: list) -> None:
        pct = self.pressure_pct
        if pct is None:
            return
        rooms = [e["headroom"] for e in sample
                 if e.get("headroom") is not None]
        if not rooms:
            return
        worst = min(rooms)
        if worst * 100.0 < pct:
            if not self._in_pressure:
                self._in_pressure = True
                registry.counter("device/hbm_pressure_events").inc()
                trace.event("hbm_pressure", headroom=round(worst, 4),
                            threshold_pct=pct, devices=sample)
                from bigdl_tpu.utils.robustness import events
                events.record("hbm_pressure", headroom=round(worst, 4),
                              threshold_pct=pct)
        else:
            self._in_pressure = False

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.poll_once()
            except Exception:
                pass  # a flaky backend must never kill the monitor

    def start(self) -> "DeviceMonitor":
        if self._thread is None:
            self.poll_once()   # gauges exist before the first interval
            self._thread = threading.Thread(
                target=self._run, name="bigdl-device-monitor", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None


def _watchdog_context() -> dict:
    """Latest device-memory picture for watchdog stall dumps (empty when
    the backend reports nothing — absent, not fabricated)."""
    sample = last_sample()
    if not sample:
        return {}
    return {"device_memory": sample}


def monitor() -> Optional[DeviceMonitor]:
    return _MONITOR


def start_from_env(interval_s: Optional[float] = None) -> Optional[DeviceMonitor]:
    """Start (once per process) the monitor — always-on like the MFU rail:
    the daemon costs one memory_stats() + live_arrays() round per interval.
    Interval from ``BIGDL_DEVICE_POLL_S`` (default 5s; ``0`` disables);
    pressure threshold from ``BIGDL_HBM_PRESSURE_PCT`` (unset = no
    pressure events)."""
    global _MONITOR
    with _MONITOR_LOCK:
        if _MONITOR is not None:
            return _MONITOR
        if interval_s is None:
            try:
                interval_s = float(
                    os.environ.get("BIGDL_DEVICE_POLL_S", "5") or "5")
            except ValueError:
                interval_s = 5.0
            if interval_s <= 0:
                return None
        _MONITOR = DeviceMonitor(interval_s,
                                 pressure_pct=_pressure_pct()).start()
        obs_watchdog.add_context_provider(_watchdog_context)
        return _MONITOR


def stats() -> dict:
    """Device-memory block for /statusz."""
    return {"devices": last_sample() or [],
            "live_buffers": live_buffer_census(publish=False)}


def reset() -> None:
    """Test isolation: stop the daemon, forget the last sample."""
    global _MONITOR, _last_sample
    with _MONITOR_LOCK:
        if _MONITOR is not None:
            _MONITOR.stop()
        _MONITOR = None
    with _lock:
        _last_sample = None
