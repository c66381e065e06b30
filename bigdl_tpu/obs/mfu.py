"""Always-on MFU accounting — XLA cost-analysis FLOPs per compiled program.

Every compiled program the trainer and the serving engine dispatch (train
step, fused window, prefill buckets, decode step) self-reports its model
FLOPs once via ``jitted.lower(*avals).cost_analysis()`` (~ms, paid once per
program — callers memoize per program-cache key). Each dispatch then feeds
:func:`note`, which maintains an EWMA FLOPs/s per domain and publishes two
live gauges into the metric registry:

- ``<domain>/model_flops_per_sec`` — achieved model FLOPs per second
- ``<domain>/mfu``                 — the same divided by the backend's peak

so every run carries the MFU number, and the ``/metrics`` endpoint exposes it
to scrapers. The peak-FLOPs table below is the gauge's; ``BIGDL_PEAK_FLOPS``
overrides it (e.g. on backends the table does not know). The benchmark keeps
its own peaks in ``benchmarks/peaks.json``; ``tests/test_architecture.py``
holds the two equal for every device the benchmark names.

Lowering for cost analysis uses ``jax.ShapeDtypeStruct`` avals built from
the call's argument trees — never live buffers — so it composes with
``donate_argnums`` (the trainer donates params/state into each step; the
avals here are shapes only, nothing is retained or re-donated).

jax is imported lazily inside the functions that need it: the obs package
stays importable (and the registry/tracer usable) without jax present.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

#: peak dense (non-sparse) FLOPs/s per chip, matched by substring against
#: ``jax.devices()[0].device_kind.lower()``. Order matters: first match wins
#: ("v5 lite" before "v5").
PEAK_FLOPS = [
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5litepod", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]

#: EWMA weight for new FLOPs/s samples (matches the serving decode_tps EWMA)
_EW_ALPHA = 0.2

_lock = threading.Lock()
_ewma: dict = {}           # domain -> EWMA FLOPs/s
_UNSET = object()
_peak_cache = _UNSET       # cached table lookup for this process's backend


def peak_flops_for(device_kind: Optional[str]) -> Optional[float]:
    """Peak FLOPs/s for a device kind string, or None when unknown.

    ``BIGDL_PEAK_FLOPS`` (a float, FLOPs/s) wins over the table — the escape
    hatch for backends the table does not know, and how tests pin a peak on
    CPU."""
    raw = os.environ.get("BIGDL_PEAK_FLOPS", "").strip()
    if raw:
        try:
            v = float(raw)
            if v > 0:
                return v
        except ValueError:
            pass
    kind = (device_kind or "").lower()
    return next((v for sub, v in PEAK_FLOPS if sub in kind), None)


def device_peak() -> Optional[float]:
    """Peak FLOPs/s of this process's backend (None on CPU/unknown unless
    ``BIGDL_PEAK_FLOPS`` overrides). The table lookup is cached; the env
    override is consulted live so tests can flip it per-case."""
    global _peak_cache
    if os.environ.get("BIGDL_PEAK_FLOPS", "").strip():
        return peak_flops_for(None)
    if _peak_cache is _UNSET:
        kind = None
        try:
            import jax
            kind = jax.devices()[0].device_kind
        except Exception:
            kind = None
        _peak_cache = peak_flops_for(kind)
    return _peak_cache


def avals_of(args) -> tuple:
    """Argument tree → ShapeDtypeStruct avals: the donation-safe lowering
    inputs shared by :func:`program_flops` and
    :func:`bigdl_tpu.obs.device.program_memory` (shapes/dtypes only — live
    or donated buffers are never touched)."""
    import jax

    def _aval(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(_aval, args)


def program_flops(fn, *args) -> Optional[float]:
    """Model FLOPs of one compiled program, from XLA cost analysis.

    ``fn`` is a jitted callable, ``args`` the (or representative) call
    arguments — only their shapes/dtypes are used, via ShapeDtypeStruct
    avals, so donated buffers are never touched. Returns None when the
    backend provides no cost analysis (callers memoize either way: this
    re-traces, ~ms per program)."""
    try:
        ca = fn.lower(*avals_of(args)).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        f = ca.get("flops") if hasattr(ca, "get") else None
        if f is not None and f > 0:
            return float(f)
    except Exception:
        pass
    return None


def note(domain: str, flops: Optional[float], wall_s: float) -> None:
    """Record one dispatch: ``flops`` model FLOPs retired in ``wall_s``.

    Publishes ``<domain>/model_flops_per_sec`` (EWMA) always, and
    ``<domain>/mfu`` when the backend peak is known. No-op when the program's
    FLOPs are unknown — accounting degrades to absent, never to wrong."""
    if not flops or wall_s <= 0:
        return
    inst = flops / wall_s
    with _lock:
        prev = _ewma.get(domain)
        cur = inst if prev is None else (1.0 - _EW_ALPHA) * prev + _EW_ALPHA * inst
        _ewma[domain] = cur
    from bigdl_tpu.obs.registry import registry
    registry.gauge(domain + "/model_flops_per_sec").set(cur)
    peak = device_peak()
    if peak:
        registry.gauge(domain + "/mfu").set(cur / peak)


def stats() -> dict:
    """Current MFU accounting state for ``/statusz``."""
    with _lock:
        fps = dict(_ewma)
    peak = device_peak()
    out = {"peak_flops": peak, "flops_per_sec": fps}
    if peak:
        out["mfu"] = {d: v / peak for d, v in fps.items()}
    return out


def reset() -> None:
    """Test isolation: forget EWMAs and the cached backend peak."""
    global _peak_cache
    with _lock:
        _ewma.clear()
        _peak_cache = _UNSET
