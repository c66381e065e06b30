"""Thread-aware span tracer — Chrome-trace export + JSONL event log.

``span("train/step")`` context managers record a :class:`SpanRecord` each:
name, thread, start in Unix nanoseconds, duration, the span open beneath it
on its thread (``parent``) and ``args``. The Unix start puts a span on the
clock of a ``jax.profiler`` trace with no host tracer at all: the xplane's
``Task Environment`` plane carries ``profile_start_time`` in Unix nanoseconds
and every event's offset is relative to it. :func:`open_spans` exposes the
live per-thread stacks for the hang watchdog. The newest ``_MAX_SPANS``
records are kept (a ring; what falls out is counted), and
:func:`spans_between` is their one reader. Two outputs:

- **Chrome trace JSON** (:func:`export_chrome`): ``X`` complete events with
  microsecond ``ts``/``dur`` per thread, plus thread-name metadata — loads
  directly in ``chrome://tracing`` / Perfetto. ``ts`` counts from
  ``otherData.ts_zero_unix_ns``, so ``profile_start_time - ts_zero_unix_ns``
  shifts a profiler trace onto it.
- **JSONL event log** (:func:`event`): one JSON object per line for
  *structured* occurrences — watchdog dumps, robustness events, the end-of-run
  report — written immediately (a hung process must already have its dump on
  disk).

Gating: ``BIGDL_TRACE`` (truthy) enables span recording; ``BIGDL_TRACE_DIR``
picks the output directory (default ``./bigdl-trace``); ``BIGDL_OBS_LOG``
names the JSONL file explicitly (and enables the event log even with tracing
off — events then flow, spans don't). The disabled path is near-zero cost:
``span()`` returns a module-singleton no-op context manager and allocates
nothing — pinned by a counting test on ``_SPANS_CREATED``.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import NamedTuple, Optional

#: finished-span ring: the newest this many are kept, older ones are counted
_MAX_SPANS = 262_144

#: ``jax.named_scope`` names of the train step's phases (optim/optimizer.py
#: ``_make_step_fn``): a device operation carries its scope in the profile's
#: ``tf_op`` stat, where the trace readers match these
SCOPE_CAST = "bigdl_cast"              # compute-dtype casts around the model
SCOPE_LOSS = "bigdl_loss"              # criterion and added penalties
SCOPE_GRAD_SCALE = "bigdl_grad_scale"  # per-layer scales, accumulation mean, clipping
SCOPE_UPDATE = "bigdl_update"          # the optimizer method's update
#: and of the routed expert layer (parallel/moe.py, router="topk"): the whole
#: layer, and inside it the router with the sort and the gather, the grouped
#: products with the gate between them, and the weighted sum back
SCOPE_MOE = "bigdl_moe"
SCOPE_MOE_ROUTE = "bigdl_moe_route"
SCOPE_MOE_EXPERTS = "bigdl_moe_experts"
SCOPE_MOE_COMBINE = "bigdl_moe_combine"
#: and of ``ConfigDecoder``'s attention by the layer's kind, around the whole
#: module (projections, RoPE where the kind has it, the kernels, the output
#: projection): a layer that sees every key the mask allows, and one that
#: sees a sliding window of them
SCOPE_ATTN_FULL = "bigdl_attn_full"
SCOPE_ATTN_WINDOW = "bigdl_attn_window"


class SpanRecord(NamedTuple):
    """One finished span, on the Unix clock."""
    name: str
    tid: int
    thread: str
    start_unix_ns: int
    dur_ns: int
    parent: Optional[str]   # the span open beneath it on its thread
    args: Optional[dict]


_lock = threading.Lock()
_ENABLED = False
_EXPLICIT = False          # configure() wins over configure_from_env()
_TRACE_DIR: Optional[str] = None
_JSONL_PATH: Optional[str] = None
_JSONL_FILE = None

_finished: collections.deque = collections.deque(maxlen=_MAX_SPANS)  # SpanRecord
_dropped = 0
_totals: dict = {}         # name -> [count, total_seconds]
_threads: dict = {}        # tid -> thread name (as of first span)
_open_stacks: dict = {}    # tid -> [(name, t0_ns), ...] — owner-thread writes
#: Unix time of ``perf_counter_ns() == 0``, taken when tracing is configured:
#: a span reads ``perf_counter_ns`` alone
_UNIX_OFFSET_NS = 0


def _sync_clock() -> None:
    global _UNIX_OFFSET_NS
    _UNIX_OFFSET_NS = time.time_ns() - time.perf_counter_ns()

#: _Span instances ever constructed — the zero-alloc-when-disabled pin
_SPANS_CREATED = 0


def _truthy(raw: Optional[str]) -> bool:
    return (raw or "").strip().lower() not in ("", "0", "false", "no", "off")


def configure(enabled: Optional[bool] = None, trace_dir: Optional[str] = None,
              jsonl: Optional[str] = None) -> None:
    """Explicit configuration (tests, the benchmark's runner). Overrides the environment
    until :func:`reset`."""
    global _ENABLED, _EXPLICIT, _TRACE_DIR, _JSONL_PATH
    with _lock:
        _EXPLICIT = True
        _sync_clock()
        if trace_dir is not None:
            _TRACE_DIR = trace_dir
        if enabled is not None:
            _ENABLED = bool(enabled)
        if jsonl is not None:
            _set_jsonl(jsonl)
        elif _ENABLED and _JSONL_PATH is None:
            _set_jsonl(os.path.join(_dir_locked(), f"events-{os.getpid()}.jsonl"))


def configure_from_env() -> None:
    """Re-read ``BIGDL_TRACE`` / ``BIGDL_TRACE_DIR`` / ``BIGDL_OBS_LOG``.
    Called at the top of every training run (cheap); a prior explicit
    :func:`configure` sticks."""
    global _ENABLED, _TRACE_DIR, _JSONL_PATH
    if _EXPLICIT:
        return
    with _lock:
        if _EXPLICIT:
            return
        _sync_clock()
        _ENABLED = _truthy(os.environ.get("BIGDL_TRACE"))
        env_dir = os.environ.get("BIGDL_TRACE_DIR")
        if env_dir:
            _TRACE_DIR = env_dir
        env_log = os.environ.get("BIGDL_OBS_LOG")
        if env_log:
            _set_jsonl(env_log)
        elif _ENABLED and _JSONL_PATH is None:
            _set_jsonl(os.path.join(_dir_locked(), f"events-{os.getpid()}.jsonl"))


def _dir_locked() -> str:
    global _TRACE_DIR
    if _TRACE_DIR is None:
        _TRACE_DIR = os.environ.get("BIGDL_TRACE_DIR") or "bigdl-trace"
    return _TRACE_DIR


def _set_jsonl(path: str) -> None:
    global _JSONL_PATH, _JSONL_FILE
    if path == _JSONL_PATH:
        return
    if _JSONL_FILE is not None:
        try:
            _JSONL_FILE.close()
        except Exception:
            pass
    _JSONL_PATH = path
    _JSONL_FILE = None  # opened lazily on first event


def enabled() -> bool:
    return _ENABLED


def trace_dir() -> Optional[str]:
    return _TRACE_DIR


def jsonl_path() -> Optional[str]:
    return _JSONL_PATH


def chrome_path() -> Optional[str]:
    if not _ENABLED:
        return None
    return os.path.join(_dir_locked(), f"trace-{os.getpid()}.json")


# ------------------------------------------------------------------- spans
class _NullSpan:
    """Shared no-op context manager — the whole disabled hot path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def begin(self, t0_ns):
        pass

    def end(self, t1_ns):
        pass


_NULL = _NullSpan()


class _Span:
    __slots__ = ("name", "args", "_t0", "_tid", "_parent")

    def __init__(self, name: str, args):
        global _SPANS_CREATED
        _SPANS_CREATED += 1
        self.name = name
        self.args = args

    def __enter__(self):
        self.begin(time.perf_counter_ns())
        return self

    def __exit__(self, *exc):
        self.end(time.perf_counter_ns())
        return False

    def begin(self, t0_ns: int) -> None:
        """Open the span at a ``perf_counter_ns`` reading the caller already
        took (``optim/metrics.py`` times a phase and its span off one clock
        read an edge)."""
        tid = threading.get_ident()
        self._tid = tid
        stack = _open_stacks.get(tid)
        if stack is None:
            # first span on this thread: register its name for the trace
            _open_stacks[tid] = stack = []
            _threads[tid] = threading.current_thread().name
        self._parent = stack[-1][0] if stack else None
        self._t0 = t0_ns
        stack.append((self.name, t0_ns))

    def end(self, t1_ns: int) -> None:
        global _dropped
        stack = _open_stacks.get(self._tid)
        if stack:
            stack.pop()
        dur = t1_ns - self._t0
        rec = SpanRecord(self.name, self._tid, _threads.get(self._tid, "?"),
                         self._t0 + _UNIX_OFFSET_NS, dur, self._parent,
                         self.args)
        with _lock:
            tot = _totals.get(self.name)
            if tot is None:
                _totals[self.name] = [1, dur / 1e9]
            else:
                tot[0] += 1
                tot[1] += dur / 1e9
            if len(_finished) == _finished.maxlen:
                _dropped += 1       # the ring pushes its oldest out
            _finished.append(rec)


def span(name: str, args: Optional[dict] = None):
    """Context manager timing a named span on the current thread. When
    tracing is disabled this returns a module singleton — no allocation, no
    bookkeeping (``args`` must be passed as a dict, not ``**kwargs``, so the
    disabled call builds nothing)."""
    if not _ENABLED:
        return _NULL
    return _Span(name, args)


def span_totals() -> dict:
    """{name: {"count": n, "total_ms": ms}} aggregated over every finished
    span (survives :func:`export_chrome`; empty when tracing was off)."""
    with _lock:
        return {name: {"count": c, "total_ms": round(t * 1e3, 3)}
                for name, (c, t) in _totals.items()}


def spans_between(t0_unix_ns: int = 0, t1_unix_ns: Optional[int] = None) -> list:
    """The kept :class:`SpanRecord` s that overlap ``[t0, t1]`` (Unix
    nanoseconds; no ``t1``: up to now), oldest start first. The one reader of
    the ring: a trace reader hands it a profile's extent
    (``profile_start_time`` plus the events' offsets) and gets the program's
    spans on that clock."""
    with _lock:
        spans = list(_finished)
    if t1_unix_ns is None:
        t1_unix_ns = time.time_ns()
    return sorted((r for r in spans if r.start_unix_ns <= t1_unix_ns
                   and r.start_unix_ns + r.dur_ns >= t0_unix_ns),
                  key=lambda r: r.start_unix_ns)


def open_spans() -> dict:
    """Live per-thread open-span stacks (outermost first) with ages — the
    watchdog's view of what every thread is in the middle of."""
    now = time.perf_counter_ns()
    out = {}
    for tid, stack in list(_open_stacks.items()):
        entries = [{"name": n, "age_ms": round((now - t0) / 1e6, 1)}
                   for n, t0 in list(stack)]
        if entries:
            out[f"{_threads.get(tid, '?')} ({tid})"] = entries
    return out


# ------------------------------------------------------------- JSONL events
def event(kind: str, **payload) -> None:
    """Append one structured record to the JSONL event log (no-op when no
    log is configured). Flushed immediately: watchdog dumps and run reports
    must be on disk even if the process never exits cleanly."""
    global _JSONL_FILE
    if _JSONL_PATH is None:
        return
    rec = {"ts": time.time(), "kind": kind}
    rec.update(payload)
    line = json.dumps(rec, default=str) + "\n"
    with _lock:
        if _JSONL_FILE is None:
            d = os.path.dirname(_JSONL_PATH)
            if d:
                os.makedirs(d, exist_ok=True)
            _JSONL_FILE = open(_JSONL_PATH, "a")
        _JSONL_FILE.write(line)
        _JSONL_FILE.flush()


def read_events(path: str) -> list:
    """Decode a JSONL event log back into a list of dicts (the ``diag``
    subcommand's input; blank/truncated tail lines are skipped)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # torn tail line (crash mid-write)
    return out


# ------------------------------------------------------------ chrome export
def export_chrome(path: Optional[str] = None) -> Optional[str]:
    """Write every finished span as a Chrome-trace JSON file (``X`` complete
    events, per-thread ``tid``, thread-name metadata). Returns the path, or
    None when tracing is disabled. Idempotent — the span ring is kept."""
    if not _ENABLED:
        return None
    path = path or chrome_path()
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    pid = os.getpid()
    spans = spans_between()
    zero = _UNIX_OFFSET_NS      # ts counts from perf_counter's zero
    events = [{"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
               "args": {"name": "bigdl-tpu"}}]
    for tid, name in dict(_threads).items():
        events.append({"ph": "M", "name": "thread_name", "pid": pid,
                       "tid": tid, "args": {"name": name}})
    for r in spans:
        ev = {"name": r.name, "ph": "X", "cat": "bigdl",
              "ts": (r.start_unix_ns - zero) / 1e3, "dur": r.dur_ns / 1e3,
              "pid": pid, "tid": r.tid}
        if r.args:
            ev["args"] = r.args
        events.append(ev)
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms",
                   "otherData": {"ts_zero_unix_ns": zero}}, f)
    event("trace_exported", path=path, spans=len(spans), dropped=_dropped)
    return path


def reset() -> None:
    """Drop all recorded state and configuration (tests)."""
    global _ENABLED, _EXPLICIT, _TRACE_DIR, _JSONL_PATH, _JSONL_FILE, _dropped
    with _lock:
        _ENABLED = False
        _EXPLICIT = False
        _TRACE_DIR = None
        if _JSONL_FILE is not None:
            try:
                _JSONL_FILE.close()
            except Exception:
                pass
        _JSONL_PATH = None
        _JSONL_FILE = None
        _finished.clear()
        _totals.clear()
        _threads.clear()
        _open_stacks.clear()
        _dropped = 0


# initial configuration from the process environment (BIGDL_TRACE=1 runs)
configure_from_env()
