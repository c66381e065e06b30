"""Online serving engine: continuous batching over the KV-cached decode path.

The offline decode APIs (``nn.greedy_generate``) serve one padded batch per
call — between calls the chip idles, and a straggler holds the whole batch.
This engine turns per-request traffic into SATURATED static-shape device
programs:

- **Admission queue** (``utils.queues.ClosableQueue``): clients ``submit()``
  from any thread; one engine thread owns all device state.
- **Continuous decode batch**: a fixed grid of ``slots`` KV-cache rows with
  PER-SLOT positions (``install_decode_cache(per_slot=True)``). Every tick
  runs ONE decode program over the whole grid; each active row sits at its
  own depth.
- **Slot recycling**: a finished sequence's row is reset and reassigned to a
  waiting request mid-flight (``assign_cache_slot``) — the other rows never
  stop decoding. No drain-and-refill.
- **Static-shape buckets**: prompts prefill right-padded to a small
  length grid, so the engine compiles exactly ``len(buckets)`` prefill
  programs + 1 decode program + 1 slot-assign program — ever. ``stats()``
  counts them; the tests and ``chip_smoke.py`` assert the bound.
- **SLO knob** (``admit_wait_ms``): on an idle engine, wait this long for
  more arrivals before the first prefill — trades batch fill (throughput)
  against TTFT. 0 (default) = serve immediately.
- **Paged KV cache** (``pages=`` / BIGDL_KV_PAGES, ``page_tokens=`` /
  BIGDL_KV_PAGE): swap the per-slot cache rows for a shared page pool
  + per-slot page tables (``serving/paged_cache.py``) — resident sequences
  are then bounded by pooled TOKENS, not ``slots × max_len``, so short
  traffic packs many more concurrent sequences per chip. Decode stays
  bitwise-identical to the slot grid; pool exhaustion is backpressure
  (block admission / shed with ``pages_free`` / degrade), never a crash,
  with the youngest sequence preempted-and-requeued as the last resort so
  the oldest always progresses.

And a failure story (docs/robustness.md, "Serving"):

- **Deadlines** (``submit(..., deadline_ms=)`` / BIGDL_SERVE_DEADLINE_MS):
  an expired request fails with :class:`RequestTimeout` — checked while
  queued, at admission, and after every decode tick; an expired slot is
  recycled immediately instead of burning decode steps on a dead SLA.
- **Overload control** (BIGDL_SERVE_OVERLOAD=block|shed|degrade): ``block``
  (default) backpressures ``submit`` on the bounded queue; ``shed`` rejects
  with :class:`EngineOverloaded` (carrying queue depth + a token-rate-based
  wait estimate) instead of queueing work it cannot finish in time;
  ``degrade`` halves ``max_new_tokens`` under pressure so every client gets
  a shorter answer instead of some getting none.
- **Crash recovery**: a supervisor thread respawns a dead decode loop under
  BIGDL_SERVE_CRASH_BUDGET, rebuilds the slot grid, and re-prefills every
  in-flight request from its prompt + already-emitted tokens — callers see
  added latency, never a lost future, and the tokens stay bitwise-identical
  (the chunked-prefill == full-forward invariant).
- **Non-finite logit guard**: every program also returns per-row finiteness;
  a poisoned slot fails ITS request with :class:`NonFiniteLogitsError`, is
  reset before reuse, and co-batched slots never notice.
- **Graceful drain** (``shutdown(drain=True)`` / SIGTERM via
  :meth:`ServingEngine.install_signal_drain`): stop admission, finish
  in-flight sequences up to BIGDL_SERVE_DRAIN_S, abort the rest.
- **Health** (``stats()["health"]``: starting/ready/degraded/draining/dead)
  published as the ``serving/health`` gauge, with the obs hang watchdog
  armed on decode-loop silence while work is in flight.

Fault sites ``serve_prefill`` / ``serve_decode`` / ``serve_thread`` /
``serve_stall`` (``utils/faults.py``) make every path above deterministic
under test, and each recovery action is a ``Robustness/serving_*`` event.

Per-request latency lands in the obs metric registry (``serving/ttft_ms``,
``serving/tpot_ms``, ``serving/queue_wait_ms``, ``serving/e2e_ms``
histograms): p50/p99 TTFT and time-per-token are one ``registry.snapshot()``
away, the same rail the run report reads. Decode is greedy —
the bitwise-equality contract with ``nn.greedy_generate`` is pinned by
``tests/test_serving.py``.

Quantized snapshots serve through the same engine unchanged: ``quantize()``
swaps Linear for int8 modules but leaves the attention stack (and its cache)
intact — see ``serving/multitenant.py`` for several snapshots on one chip.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Optional, Sequence

import numpy as np

from bigdl_tpu.obs import access_log as obs_access_log
from bigdl_tpu.obs import exporter as obs_exporter
from bigdl_tpu.obs import mfu as obs_mfu
from bigdl_tpu.obs import slo as obs_slo
from bigdl_tpu.obs import trace
from bigdl_tpu.obs import watchdog as obs_watchdog
from bigdl_tpu.obs.registry import registry
from bigdl_tpu.serving import paged_cache
from bigdl_tpu.serving.paged_cache import TRASH_PAGE, PageAllocator
from bigdl_tpu.serving.prefix_cache import PrefixPool
from bigdl_tpu.serving.request import (
    FINISH_EOS, FINISH_LENGTH, Request, RequestHandle,
)
from bigdl_tpu.serving.scheduler import (
    SlotScheduler, default_buckets, pick_bucket, pick_seed_bucket,
)
from bigdl_tpu.serving.speculative import (
    build_spec_prefill, build_spec_step,
)
from bigdl_tpu.utils import faults
from bigdl_tpu.utils.faults import FaultError, check_fault, fault_point
from bigdl_tpu.utils.queues import CLOSED, EMPTY, ClosableQueue
from bigdl_tpu.utils.robustness import events

logger = logging.getLogger("bigdl_tpu.serving")


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def _parse_buckets(spec: str) -> tuple[int, ...]:
    return tuple(int(x) for x in spec.replace(" ", "").split(",") if x)


class EngineShutdown(RuntimeError):
    """Raised from ``RequestHandle.result()`` for requests the engine could
    not finish (shutdown or engine-thread failure), and from ``submit`` once
    the engine is shut down or draining."""


class RequestTimeout(RuntimeError):
    """The request's deadline (``deadline_ms``) passed before it finished —
    while queued, at admission, or mid-decode. The slot (if any) was
    recycled immediately."""


class EngineOverloaded(RuntimeError):
    """``submit`` rejected under BIGDL_SERVE_OVERLOAD=shed: the backlog is
    at capacity, or the token-rate estimate says the request cannot meet its
    deadline. Carries the same machine-readable load triple ``stats()``
    publishes — ``queue_depth`` / ``decode_rate`` / ``est_wait_ms`` (plus
    the legacy ``est_wait_s``) — so the fleet router and external load
    balancers dispatch off data, not exception strings."""

    def __init__(self, msg: str, queue_depth: int, est_wait_s: float,
                 decode_rate: float = 0.0,
                 pages_free: Optional[int] = None):
        super().__init__(msg)
        self.queue_depth = queue_depth
        self.est_wait_s = est_wait_s
        self.est_wait_ms = est_wait_s * 1e3
        self.decode_rate = decode_rate
        #: paged engines only: free pages at shed time, so a router can
        #: tell page-pool exhaustion from queue overload (None = unpaged)
        self.pages_free = pages_free


class EngineShutdownTimeout(RuntimeError):
    """``shutdown(wait=True)`` gave up waiting for the engine thread — the
    thread is LEAKED, not silently forgotten. The message carries the
    stack + open-span dump of the wedged thread."""


class NonFiniteLogitsError(RuntimeError):
    """The per-slot finiteness guard tripped: this request's logits went
    NaN/Inf (poisoned weights, numeric blowup, or an injected
    ``serve_decode=nonfinite`` fault). Only this request fails; its slot is
    reset before reuse and co-batched slots are unaffected."""


#: stats()["health"] states, published numerically as the serving/health gauge
_HEALTH_CODE = {"starting": 0, "ready": 1, "degraded": 2, "draining": 3,
                "dead": 4}

_OVERLOAD_MODES = ("block", "shed", "degrade")


class _Wake:
    """Queue sentinel: wakes an idle engine loop without carrying work —
    how ``swap_weights`` gets a blocked ``_gather`` back to the step
    boundary where the pending swap is serviced."""

    def __repr__(self):
        return "<WAKE>"


_WAKE = _Wake()


class SwapResult:
    """What :meth:`ServingEngine.swap_weights` returns: the installed
    version plus, per in-flight request, how many tokens it had emitted at
    the swap boundary — the split point of the bitwise contract (tokens
    before are the OLD weights' verbatim, tokens after are what the NEW
    weights produce from that prefix)."""

    __slots__ = ("version", "in_flight", "requeued", "duration_s")

    def __init__(self, version, in_flight, requeued, duration_s):
        self.version = version
        self.in_flight = in_flight      # {request_id: n_generated_at_swap}
        self.requeued = requeued
        self.duration_s = duration_s


class _SwapCommand:
    __slots__ = ("params", "version", "done", "error", "result")

    def __init__(self, params, version):
        self.params = params
        self.version = version
        self.done = threading.Event()
        self.error: Optional[BaseException] = None
        self.result: Optional[SwapResult] = None


class ServingEngine:
    """Continuous-batching request server over one model snapshot.

    ``model``: a causal LM built from cached-decode-capable modules
    (``MultiHeadAttention`` stacks — native or int8-quantized).
    ``max_len``: per-slot KV-cache length; every request needs
    ``prompt_len + max_new_tokens <= max_len``.
    ``slots``: decode-batch rows held on device (BIGDL_SERVE_SLOTS, def. 8).
    ``buckets``: static prefill-length grid (BIGDL_SERVE_BUCKETS, default
    a doubling grid up to ``max_len``); a prompt longer than the largest
    bucket is rejected at submit.
    ``eos_id``: optional stop token (per engine; None = length-capped only).
    ``admit_wait_ms``: idle batch-fill wait, the SLO knob
    (BIGDL_SERVE_ADMIT_WAIT_MS, default 0).
    ``deadline_ms``: default per-request deadline
    (BIGDL_SERVE_DEADLINE_MS; 0/unset = none).
    ``overload``: admission policy under pressure
    (BIGDL_SERVE_OVERLOAD=block|shed|degrade, default block).
    ``crash_budget``: engine-thread respawns before giving up
    (BIGDL_SERVE_CRASH_BUDGET, default 2).
    ``drain_s``: default drain deadline for ``shutdown(drain=True)``
    (BIGDL_SERVE_DRAIN_S, default 30).
    ``watchdog``: a :class:`~bigdl_tpu.obs.watchdog.HangWatchdog` to arm on
    decode-loop silence (default: built from BIGDL_WATCHDOG_S, often None).
    ``draft_model``: a small proposer LM over the same vocabulary — turns
    every decode tick into a speculative draft-verify round emitting 1..k+1
    tokens (``serving/speculative.py``), bitwise-identical output;
    ``spec_tokens`` is k (BIGDL_SPEC_TOKENS, default 4). With a draft, each
    request additionally needs ``prompt_len + max_new_tokens + spec_tokens
    <= max_len`` of cache headroom.
    ``prefix_pool``: entries of resident prefilled-prefix cache
    (``serving/prefix_cache.py``; BIGDL_PREFIX_POOL, default 0 = off) with
    ``prefix_chunk``-aligned keys (BIGDL_PREFIX_CHUNK, default 16) — shared
    prompt prefixes then seed new slots instead of re-prefilling.
    ``pages``: size of the shared KV page pool (BIGDL_KV_PAGES, default
    0 = slot-grid cache). When > 0 the decode cache becomes a paged pool of
    ``pages`` allocatable ``page_tokens``-token pages per attention layer
    (``serving/paged_cache.py``); pooled-token residency then bounds
    concurrency instead of ``slots × max_len``. ``page_tokens`` is the page
    size (BIGDL_KV_PAGE, default 16; must divide ``max_len``). Paged
    mode composes with the prefix pool (prefill stays contiguous) and
    with ``draft_model`` — the speculative verify writes its k+1 chunk
    through the page table (the target pages; the small draft keeps its
    slot grid), and ``BIGDL_KV_PAGED=0`` force-disables paging without
    touching the ``pages``/BIGDL_KV_PAGES setting (the rollback knob).
    """

    def __init__(self, model, max_len: int, slots: Optional[int] = None,
                 buckets: Optional[Sequence[int]] = None,
                 eos_id: Optional[int] = None,
                 admit_wait_ms: Optional[float] = None,
                 queue_depth: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 overload: Optional[str] = None,
                 crash_budget: Optional[int] = None,
                 drain_s: Optional[float] = None,
                 watchdog: Optional["obs_watchdog.HangWatchdog"] = None,
                 draft_model=None, spec_tokens: Optional[int] = None,
                 prefix_pool: Optional[int] = None,
                 prefix_chunk: Optional[int] = None,
                 pages: Optional[int] = None,
                 page_tokens: Optional[int] = None,
                 dtype=None, name: str = "serve"):
        import jax.numpy as jnp

        from bigdl_tpu import nn

        if slots is None:
            slots = _env_int("BIGDL_SERVE_SLOTS", 8)
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        if buckets is None:
            spec = os.environ.get("BIGDL_SERVE_BUCKETS", "")
            buckets = (_parse_buckets(spec) if spec
                       else default_buckets(max_len))
        buckets = tuple(sorted({int(b) for b in buckets}))
        if not buckets or buckets[0] < 1 or buckets[-1] > max_len:
            raise ValueError(
                f"buckets must be within [1, max_len={max_len}], "
                f"got {buckets}")
        if admit_wait_ms is None:
            admit_wait_ms = float(os.environ.get(
                "BIGDL_SERVE_ADMIT_WAIT_MS", "0"))
        if queue_depth is None:
            queue_depth = _env_int("BIGDL_SERVE_QUEUE_DEPTH", 256)
        if deadline_ms is None:
            deadline_ms = float(os.environ.get("BIGDL_SERVE_DEADLINE_MS", "0"))
        if overload is None:
            overload = os.environ.get("BIGDL_SERVE_OVERLOAD", "block")
        if overload not in _OVERLOAD_MODES:
            raise ValueError(
                f"overload must be one of {_OVERLOAD_MODES}, got {overload!r}"
                f" (BIGDL_SERVE_OVERLOAD)")
        if crash_budget is None:
            crash_budget = _env_int("BIGDL_SERVE_CRASH_BUDGET", 2)
        if drain_s is None:
            drain_s = float(os.environ.get("BIGDL_SERVE_DRAIN_S", "30"))
        if spec_tokens is None:
            spec_tokens = (_env_int("BIGDL_SPEC_TOKENS", 4)
                           if draft_model is not None else 0)
        if draft_model is not None and spec_tokens < 1:
            raise ValueError(
                f"spec_tokens must be >= 1 with a draft model, "
                f"got {spec_tokens}")
        if prefix_pool is None:
            prefix_pool = _env_int("BIGDL_PREFIX_POOL", 0)
        if prefix_chunk is None:
            prefix_chunk = _env_int("BIGDL_PREFIX_CHUNK", 16)
        if pages is None:
            pages = _env_int("BIGDL_KV_PAGES", 0)
        if page_tokens is None:
            page_tokens = _env_int("BIGDL_KV_PAGE", 16)
        # BIGDL_KV_PAGED=0 is the fleet-wide rollback switch: it forces the
        # slot grid even when pages= / BIGDL_KV_PAGES asks for a pool
        if _env_int("BIGDL_KV_PAGED", 1) == 0:
            pages = 0
        self.paged = bool(pages and pages > 0)
        self.pages = int(pages) if self.paged else 0
        self.page_tokens = int(page_tokens)
        if self.paged:
            # validates page_tokens | max_len; W pages tile one sequence
            self._page_w = paged_cache.logical_pages(max_len, page_tokens)
        else:
            self._page_w = 0
        self._model = model
        self._nn = nn
        self.name = name
        self.max_len = int(max_len)
        self.slots = int(slots)
        self.buckets = buckets
        self.eos_id = eos_id
        self.admit_wait_s = admit_wait_ms / 1000.0
        self.queue_depth = int(queue_depth)
        self.default_deadline_s: Optional[float] = (
            deadline_ms / 1000.0 if deadline_ms and deadline_ms > 0 else None)
        self.overload = overload
        self.crash_budget = int(crash_budget)
        self.drain_s = float(drain_s)
        self._dtype = jnp.float32 if dtype is None else dtype
        self._params = model.get_params()
        # paged-mode host bookkeeping: the allocator owns the free list,
        # _slot_pages maps slot index -> ordered physical page ids, and
        # _page_table is the HOST-authoritative (slots, W) table injected
        # into the device state before the next tick whenever it changed
        self._allocator = (PageAllocator(self.pages) if self.paged
                           else None)
        self._slot_pages: list[list[int]] = [[] for _ in range(self.slots)]
        self._page_table = np.full((self.slots, self._page_w or 1),
                                   TRASH_PAGE, np.int32)
        self._table_dirty = False
        self._page_evictions = 0
        # functional cache states: install → capture → clear, so the module
        # itself stays clean (the cached path branches on the PASSED state)
        self._dec_state = self._install_grid()
        self._pre_state0 = nn.install_decode_cache(
            model, 1, self.max_len, dtype=self._dtype, per_slot=True)
        nn.clear_decode_cache(model)
        # speculative decoding: the draft model gets a MIRROR slot grid +
        # batch-1 prefill state so both caches move through admission,
        # decode, and recovery in lock-step (serving/speculative.py)
        self._draft = draft_model
        self._spec = int(spec_tokens) if draft_model is not None else 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        if draft_model is not None:
            self._params_d = draft_model.get_params()
            self._dec_state_d = nn.install_decode_cache(
                draft_model, self.slots, self.max_len, dtype=self._dtype,
                per_slot=True)
            nn.clear_decode_cache(draft_model)
            self._pre_state0_d = nn.install_decode_cache(
                draft_model, 1, self.max_len, dtype=self._dtype,
                per_slot=True)
            nn.clear_decode_cache(draft_model)
        else:
            self._params_d = None
            self._dec_state_d = None
            self._pre_state0_d = None
        self._prefix = (PrefixPool(prefix_pool, prefix_chunk,
                                   page=(self.page_tokens if self.paged
                                         else None))
                        if prefix_pool and prefix_pool > 0 else None)

        self._queue: ClosableQueue = ClosableQueue(queue_depth)
        self._sched = SlotScheduler(self.slots)
        self._programs: set = set()      # distinct compiled-program keys used
        self._submitted = 0
        self._completed = 0
        self._start_lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None   # supervisor
        self._worker: Optional[threading.Thread] = None   # decode loop
        self._stop = threading.Event()
        self._drain = threading.Event()
        self._drain_deadline = 0.0
        self._failure: Optional[BaseException] = None
        self._pending: list[Request] = []
        self._backlog = 0                 # submitted, not yet in a slot
        self._backlog_lock = threading.Lock()
        self._respawns = 0
        self._prefill_inflight = 0        # disaggregation exports running
        self._timeouts = 0
        self._shed = 0
        self._degraded_admits = 0
        self._poisoned = 0
        self._rate_tps = 0.0              # EWMA decode tokens/s (all slots)
        self._tok_per_req = 0.0           # EWMA generated tokens per request
        self._watchdog = (watchdog if watchdog is not None
                          else obs_watchdog.from_env())
        self._health = "starting"
        self._slo_degraded = False        # set by obs.slo.SLOMonitor
        self._prog_flops: dict = {}       # program key -> FLOPs (or None)
        self._decode_flops: Optional[float] = None
        self._last_prefill_flops: Optional[float] = None
        # tail-sampling fraction: persist full span trees for the slowest
        # BIGDL_TRACE_SAMPLE fraction of requests (>= 1.0 = all, 0 = none)
        self._trace_sample = float(
            os.environ.get("BIGDL_TRACE_SAMPLE", "0.05"))
        # weight-swap plane (serving/lifecycle.py): the served registry
        # version (0 = the construction-time snapshot, never registered)
        # and the one-deep command mailbox the engine thread services at
        # decode-step boundaries
        self._model_version = 0
        self._swap_pending: Optional[_SwapCommand] = None
        self._swap_lock = threading.Lock()
        registry.gauge("serving/health").set(_HEALTH_CODE["starting"])
        if self.paged:
            registry.gauge("serve/page_evictions").set(0)
            self._publish_page_gauges()

    # -------------------------------------------------------------- paging
    def _install_grid(self):
        """Fresh zeroed decode grid — paged pool or slot grid — resetting
        the paging bookkeeping alongside (construction, crash recovery, and
        weight swap all rebuild through here so host and device state can
        never drift apart)."""
        nn = self._nn
        if self.paged:
            self._allocator.reset()
            self._slot_pages = [[] for _ in range(self.slots)]
            self._page_table[:] = TRASH_PAGE
            self._table_dirty = False
            self._publish_page_gauges()
            state = paged_cache.install_paged_cache(
                self._model, self.slots, self.max_len, self.pages,
                self.page_tokens, dtype=self._dtype)
        else:
            state = nn.install_decode_cache(
                self._model, self.slots, self.max_len, dtype=self._dtype,
                per_slot=True)
        nn.clear_decode_cache(self._model)
        return state

    def _publish_page_gauges(self) -> None:
        registry.gauge("serve/pages_used").set(self._allocator.used_count)
        registry.gauge("serve/pages_free").set(self._allocator.free_count)

    def _pages_needed(self, depth: int) -> int:
        """Pages a sequence at ``depth`` needs RESIDENT: its content pages
        plus the page its next decode write (position ``depth``) lands in —
        ``depth // page_tokens + 1`` covers both."""
        return depth // self.page_tokens + 1

    def _pages_row(self, index: int) -> np.ndarray:
        """Slot ``index``'s (W,) physical-page vector, trash-padded — the
        traced argument of the paged assign/reset programs."""
        row = self._slot_pages[index]
        return np.asarray(
            row + [TRASH_PAGE] * (self._page_w - len(row)), np.int32)

    def _free_slot_pages(self, index: int) -> None:
        """Return a slot's pages to the pool and point its table row at
        trash (finish/timeout/recycle — zero device cost: the freed pages'
        stale content is masked for the next owner and overwritten as it
        decodes; only the POISON path scrubs, via ``_reset_row``)."""
        if not self.paged or not self._slot_pages[index]:
            return
        self._allocator.free(self._slot_pages[index])
        self._slot_pages[index] = []
        self._page_table[index, :] = TRASH_PAGE
        self._table_dirty = True
        self._publish_page_gauges()

    def _sync_page_table(self) -> None:
        """Push the host-authoritative table to every layer's device copy.
        MUST run before a decode tick whenever allocation changed: a freed
        row's stale device table would let its free-riding dummy writes
        land in pages the allocator already handed to someone else."""
        import jax.numpy as jnp

        if self._table_dirty:
            self._dec_state = paged_cache.with_page_table(
                self._dec_state, jnp.asarray(self._page_table))
            self._table_dirty = False

    def _ensure_pages(self) -> None:
        """Grow every active sequence's page list to cover its next write,
        oldest admission first. On exhaustion the YOUNGEST active sequence
        is preempted — pages freed, request requeued at the front of
        pending (the crash-recovery re-prefill path, so its tokens stay
        bitwise-identical) — guaranteeing the oldest always progresses and
        a full pool can never deadlock the loop."""
        active = sorted(self._sched.active_slots(),
                        key=lambda s: (s.request.admit_t or 0.0, s.index))
        for slot in active:
            # a speculative tick writes positions depth .. depth+k (the
            # verify chunk), so the horizon reserves through the last one
            while slot.request is not None and \
                    self._pages_needed(slot.depth + self._spec) \
                    > len(self._slot_pages[slot.index]):
                got = self._allocator.alloc(1)
                if got is not None:
                    self._slot_pages[slot.index].extend(got)
                    self._page_table[
                        slot.index,
                        len(self._slot_pages[slot.index]) - 1] = got[0]
                    self._table_dirty = True
                    continue
                victims = [s for s in active if s.request is not None]
                victim = max(victims,
                             key=lambda s: (s.request.admit_t or 0.0,
                                            s.index))
                self._preempt(victim)
                if victim is slot:
                    break   # this row WAS the youngest: it yielded
        self._publish_page_gauges()

    def _preempt(self, slot) -> None:
        """Evict one active sequence to free its pages: requeued at the
        front of pending, it re-admits through the ordinary re-prefill
        path (prompt + already-emitted tokens) with its handle untouched —
        added latency, never a lost future, never different tokens."""
        req = slot.request
        self._page_evictions += 1
        registry.gauge("serve/page_evictions").set(self._page_evictions)
        events.record("serving_page_preempt", engine=self.name,
                      request_id=req.request_id, trace_id=req.trace_id,
                      slot=slot.index,
                      pages_freed=len(self._slot_pages[slot.index]),
                      generated=len(req.generated))
        logger.warning(
            "engine %r: page pool exhausted; preempting request %r "
            "(slot %d, %d pages) to the admission queue", self.name,
            req.request_id, slot.index, len(self._slot_pages[slot.index]))
        self._free_slot_pages(slot.index)
        self._sched.release(slot)
        self._pending.insert(0, req)

    # ------------------------------------------------------------ programs
    def _fn(self, key, build):
        """Get-or-compile a device program, counting distinct keys used —
        the compile-count ledger behind ``stats()['compiled_programs']``.
        Cached on the MODEL (like ``generate``'s scan), so engines over the
        same snapshot share programs."""
        import jax

        fn = self._model._apply_cache.get(key)
        if fn is None:
            fn = jax.jit(build())
            self._model._apply_cache[key] = fn
        self._programs.add(key)
        return fn

    def _dtype_name(self):
        import jax.numpy as jnp
        return jnp.dtype(self._dtype).name

    def _prefill(self, params, state, tokens):
        """(1, Lb) tokens → ((1, Lb) greedy next-token ids, all-finite flag,
        filled cache)."""
        import jax.numpy as jnp

        lb = tokens.shape[1]
        key = ("serve_prefill", lb, self.max_len, self._dtype_name())

        def build():
            def run(params, state, tokens):
                logits, st = self._model.apply(params, state, tokens,
                                               training=False, rng=None)
                ok = jnp.isfinite(logits).all()
                return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                        ok, st)
            return run

        fn = self._fn(key, build)
        out = fn(params, state, tokens)
        if key not in self._prog_flops:   # once per bucket, post-compile
            self._prog_flops[key] = obs_mfu.program_flops(
                fn, params, state, tokens)
        self._last_prefill_flops = self._prog_flops[key]
        return out

    def _prefill_spec(self, state, state_d, tokens):
        """Speculative form of :meth:`_prefill`: ONE fused program per
        bucket runs the target prefill AND fills the draft's cache from the
        same tokens, so speculation adds no ledger entries — the per-bucket
        prefill key simply becomes the fused one."""
        lb = tokens.shape[1]
        key = ("serve_prefill_spec", id(self._draft), lb, self.max_len,
               self._dtype_name())
        fn = self._fn(key, lambda: build_spec_prefill(
            self._model, self._draft))
        out = fn(self._params, self._params_d, state, state_d, tokens)
        if key not in self._prog_flops:
            self._prog_flops[key] = obs_mfu.program_flops(
                fn, self._params, self._params_d, state, state_d, tokens)
        self._last_prefill_flops = self._prog_flops[key]
        return out

    def _spec_step(self, tok):
        """One draft-propose / chunk-verify / accept / rewind round over
        the whole slot grid — the speculative engine's single decode
        program (replaces ``serve_decode`` in the ledger)."""
        key = ("serve_spec_step", id(self._draft), self.slots, self.max_len,
               self._spec, self._dtype_name())
        fn = self._fn(key, lambda: build_spec_step(
            self._model, self._draft, self._spec))
        out = fn(self._params, self._params_d, self._dec_state,
                 self._dec_state_d, tok)
        if key not in self._prog_flops:
            self._prog_flops[key] = obs_mfu.program_flops(
                fn, self._params, self._params_d, self._dec_state,
                self._dec_state_d, tok)
        self._decode_flops = self._prog_flops[key]
        return out

    def _decode(self, params, state, tok):
        """One continuous-batch tick: (S,) last tokens → ((S,) next tokens,
        (S,) per-slot all-finite flags) — the non-finite guard rides the
        same program, so the guard costs no extra dispatch."""
        import jax.numpy as jnp

        # the paged grid is a DIFFERENT program (page-table gather/scatter
        # instead of contiguous rows) but still exactly ONE ledger entry
        key = (("serve_decode_paged", self.slots, self.max_len, self.pages,
                self.page_tokens, self._dtype_name()) if self.paged else
               ("serve_decode", self.slots, self.max_len,
                self._dtype_name()))

        def build():
            def run(params, state, tok):
                logits, st = self._model.apply(params, state, tok[:, None],
                                               training=False, rng=None)
                row = logits[:, 0, :]
                ok = jnp.isfinite(row).all(axis=-1)
                return (jnp.argmax(row, axis=-1).astype(jnp.int32), ok, st)
            return run

        fn = self._fn(key, build)
        out = fn(params, state, tok)
        if key not in self._prog_flops:   # once, after the first real call
            self._prog_flops[key] = obs_mfu.program_flops(
                fn, params, state, tok)
        self._decode_flops = self._prog_flops[key]
        return out

    def _assign(self, states, slot, pos):
        """Scatter prefilled batch-1 cache(s) into decode row ``slot`` with
        TRUE prompt length ``pos`` — one program for every slot index.
        ``states`` is ``(filled,)`` or ``(filled, filled_draft)``; with a
        draft model the fused program scatters BOTH grids, keeping the
        ledger at one assign entry."""
        nn = self._nn
        if self.paged and self._spec:
            # fused: target prefill lands page-granularly, the draft's in
            # its contiguous slot row — one assign entry in the ledger
            key = ("serve_assign_paged_spec", id(self._draft), self.slots,
                   self.max_len, self.pages, self.page_tokens,
                   self._dtype_name())

            def build():
                def run(dst, src, pages, dst_d, src_d, slot, pos):
                    return (paged_cache.assign_cache_pages(
                                dst, src, pages, slot, pos),
                            nn.assign_cache_slot(dst_d, src_d, slot,
                                                 pos=pos))
                return run

            self._dec_state, self._dec_state_d = self._fn(key, build)(
                self._dec_state, states[0], self._pages_row(slot),
                self._dec_state_d, states[1], slot, pos)
        elif self.paged:
            # page-granular scatter: the (W,) trash-padded page row is a
            # traced argument, so ONE program serves every admission no
            # matter which physical pages the allocator handed out
            key = ("serve_assign_paged", self.slots, self.max_len,
                   self.pages, self.page_tokens, self._dtype_name())

            def build():
                def run(dst, src, pages, slot, pos):
                    return paged_cache.assign_cache_pages(
                        dst, src, pages, slot, pos)
                return run

            self._dec_state = self._fn(key, build)(
                self._dec_state, states[0], self._pages_row(slot), slot,
                pos)
        elif self._spec:
            key = ("serve_assign_spec", id(self._draft), self.slots,
                   self.max_len, self._dtype_name())

            def build():
                def run(dst, src, dst_d, src_d, slot, pos):
                    return (nn.assign_cache_slot(dst, src, slot, pos=pos),
                            nn.assign_cache_slot(dst_d, src_d, slot,
                                                 pos=pos))
                return run

            self._dec_state, self._dec_state_d = self._fn(key, build)(
                self._dec_state, states[0], self._dec_state_d, states[1],
                slot, pos)
        else:
            key = ("serve_assign", self.slots, self.max_len,
                   self._dtype_name())

            def build():
                def run(dst, src, slot, pos):
                    return nn.assign_cache_slot(dst, src, slot, pos=pos)
                return run

            self._dec_state = self._fn(key, build)(
                self._dec_state, states[0], slot, pos)

    def _reset_row(self, slot):
        """Wipe one poisoned cache row (K/V + position) before the slot is
        reused — both grids when a draft model rides along. Fault-path only
        — never compiled on a clean run, so the clean-run program bound
        stays ``len(buckets) + 2``."""
        nn = self._nn
        if self.paged:
            # the paged poison path ZEROES the listed pages (not just the
            # table row): a NaN in a freed page would otherwise ride a
            # 0-weight × NaN product into the next owner's logits
            key = ("serve_reset_paged", self.slots, self.max_len,
                   self.pages, self.page_tokens, self._dtype_name())

            def build():
                def run(state, pages, slot):
                    return paged_cache.reset_page_slot(state, pages, slot)
                return run

            self._dec_state = self._fn(key, build)(
                self._dec_state, self._pages_row(slot), slot)
            if self._spec:
                # the draft rides its own slot grid; scrub its row too
                dkey = ("serve_reset_paged_draft", id(self._draft),
                        self.slots, self.max_len, self._dtype_name())

                def dbuild():
                    def run(state_d, slot):
                        return nn.reset_decode_slot(state_d, slot)
                    return run

                self._dec_state_d = self._fn(dkey, dbuild)(
                    self._dec_state_d, slot)
            return
        if self._spec:
            key = ("serve_reset_spec", id(self._draft), self.slots,
                   self.max_len, self._dtype_name())

            def build():
                def run(state, state_d, slot):
                    return (nn.reset_decode_slot(state, slot),
                            nn.reset_decode_slot(state_d, slot))
                return run

            self._dec_state, self._dec_state_d = self._fn(key, build)(
                self._dec_state, self._dec_state_d, slot)
        else:
            key = ("serve_reset", self.slots, self.max_len,
                   self._dtype_name())

            def build():
                def run(state, slot):
                    return nn.reset_decode_slot(state, slot)
                return run

            self._dec_state = self._fn(key, build)(self._dec_state, slot)

    # ------------------------------------------------------------- clients
    def submit(self, prompt, max_new_tokens: int, request_id=None,
               deadline_ms: Optional[float] = None,
               trace_id: Optional[str] = None) -> RequestHandle:
        """Enqueue one request; returns immediately with a handle. Raises
        ``ValueError`` for requests that can never fit (cache length or
        bucket grid), ``EngineShutdown`` after :meth:`shutdown`, and
        ``EngineOverloaded`` under shed-mode pressure. ``deadline_ms``
        overrides the engine default (0 = no deadline). ``trace_id``
        (optional) reuses a caller-minted trace — the fleet router's
        retry-elsewhere path, where one trace must follow the request
        across replicas."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must have at least one token")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if prompt.size + max_new_tokens + self._spec > self.max_len:
            # the spec headroom is a hard bound: a verify chunk writes k+1
            # cache rows past the current depth, and dynamic_update_slice
            # CLAMPS out-of-bounds writes onto earlier positions
            spec_note = (f" + spec_tokens {self._spec}" if self._spec
                         else "")
            raise ValueError(
                f"prompt_len {prompt.size} + max_new_tokens {max_new_tokens}"
                f"{spec_note} "
                f"exceeds the engine's cache length max_len={self.max_len}")
        if pick_bucket(prompt.size, self.buckets) is None:
            raise ValueError(
                f"prompt_len {prompt.size} exceeds the largest prefill "
                f"bucket {self.buckets[-1]}; widen buckets= "
                f"(or BIGDL_SERVE_BUCKETS)")
        if self.paged:
            # peak residency: content pages at the deepest decode write
            # (a speculative round adds its k-deep verify chunk), plus the
            # page that write lands in — a request needing more than the
            # WHOLE pool can never run, even alone
            peak = ((prompt.size + max(max_new_tokens - 2, 0) + self._spec)
                    // self.page_tokens + 1)
            if peak > self.pages:
                raise ValueError(
                    f"prompt_len {prompt.size} + max_new_tokens "
                    f"{max_new_tokens} needs {peak} pages of "
                    f"{self.page_tokens} tokens, but the pool holds only "
                    f"{self.pages} (BIGDL_KV_PAGES)")
        if deadline_ms is None:
            deadline_s = self.default_deadline_s
        else:
            deadline_s = deadline_ms / 1000.0 if deadline_ms > 0 else None

        if self.overload == "shed":
            depth = self._backlog
            est = self.estimated_wait_s()
            if depth >= self.queue_depth or (
                    deadline_s is not None and est > deadline_s):
                self._reject_overloaded(depth, est)
            if self.paged and self._allocator.free_count \
                    < self._pages_needed(int(prompt.size)):
                # pool exhaustion is backpressure, not a crash: shed NOW
                # with pages_free so the router can tell page pressure
                # from queue overload (block mode queues instead, and the
                # loop's admission gate holds the request until pages free)
                self._reject_overloaded(
                    depth, est, pages_free=self._allocator.free_count)
        elif self.overload == "degrade":
            if self._backlog >= self.slots or (
                    self.paged and self._allocator.free_count
                    < self._pages_needed(int(prompt.size))):
                halved = max(1, max_new_tokens // 2)
                if halved < max_new_tokens:
                    self._degraded_admits += 1
                    registry.counter("serving/degraded_admits").inc()
                    events.record("serving_degraded", engine=self.name,
                                  max_new_tokens=halved,
                                  requested=max_new_tokens,
                                  backlog=self._backlog)
                    max_new_tokens = halved

        if request_id is None:
            request_id = self._submitted
        req = Request(request_id, prompt, max_new_tokens,
                      deadline_s=deadline_s, trace_id=trace_id)
        self.start()
        with self._backlog_lock:
            self._backlog += 1
        if self.overload == "shed":
            ok = self._queue.try_put(req)
        else:
            ok = self._queue.put(req)
        if not ok:
            self._backlog_dec()
            if self._queue.closed:
                raise EngineShutdown(f"engine {self.name!r} is shut down")
            self._reject_overloaded(self._backlog, self.estimated_wait_s())
        self._submitted += 1
        registry.counter("serving/requests").inc()
        return req.handle

    def _reject_overloaded(self, depth: int, est: float,
                           pages_free: Optional[int] = None) -> None:
        self._shed += 1
        registry.counter("serving/shed").inc()
        events.record("serving_shed", engine=self.name, queue_depth=depth,
                      est_wait_s=round(est, 4), pages_free=pages_free)
        why = (f"page pool exhausted ({pages_free} pages free)"
               if pages_free is not None else
               f"backlog {depth} (queue_depth {self.queue_depth})")
        raise EngineOverloaded(
            f"engine {self.name!r} overloaded: {why}, estimated wait "
            f"{est * 1e3:.0f} ms", queue_depth=depth, est_wait_s=est,
            decode_rate=self._rate_tps, pages_free=pages_free)

    # ------------------------------------------------- disaggregated prefill
    def prefill_export(self, prompt) -> tuple:
        """Run ONE bucketed prefill for ``prompt`` on THIS replica and
        return ``(next_token, states)`` — the prefill→decode handoff
        payload of disaggregated serving (``FleetRouter`` phases). Pure
        functional over the batch-1 prefill state: no slot is claimed, the
        decode grid is untouched, and it is safe from any thread — a
        prefill replica serves exports concurrently with (or instead of)
        its own decode loop. The states are the SAME pytrees the prefix
        pool stores, so a decode replica absorbs them via
        :meth:`seed_prefix` with no new device programs."""
        import jax.numpy as jnp

        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must have at least one token")
        lb = pick_bucket(prompt.size, self.buckets)
        if lb is None:
            raise ValueError(
                f"prompt_len {prompt.size} exceeds the largest prefill "
                f"bucket {self.buckets[-1]} on engine {self.name!r}")
        self._prefill_inflight += 1
        try:
            padded = np.zeros((1, lb), np.int32)
            padded[0, :prompt.size] = prompt
            with trace.span("serve/prefill_export", {"bucket": lb}):
                if self._spec:
                    next_all, ok, filled, filled_d = self._prefill_spec(
                        self._pre_state0, self._pre_state0_d,
                        jnp.asarray(padded))
                    states = (filled, filled_d)
                else:
                    next_all, ok, filled = self._prefill(
                        self._params, self._pre_state0,
                        jnp.asarray(padded))
                    states = (filled,)
            if not bool(np.asarray(ok)):
                raise NonFiniteLogitsError(
                    f"non-finite logits in prefill_export on engine "
                    f"{self.name!r}")
            return int(np.asarray(next_all)[0, prompt.size - 1]), states
        finally:
            self._prefill_inflight -= 1

    def seed_prefix(self, prompt, states, next_token: int) -> None:
        """Absorb a prefill handoff: pool ``states`` under ``prompt`` so
        the next ``submit`` of that prompt admits through the prefix pool —
        an EXACT hit runs no device program at all, which is what makes
        the disaggregated tokens bitwise-identical to single-engine
        serving. Requires this engine to have a prefix pool
        (``prefix_pool > 0`` / BIGDL_PREFIX_POOL)."""
        if self._prefix is None:
            raise ValueError(
                f"engine {self.name!r} has no prefix pool (prefix_pool=0 /"
                f" BIGDL_PREFIX_POOL unset); a decode-phase replica needs "
                f"one to absorb prefill handoffs")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        want = 2 if self._spec else 1
        if len(states) != want:
            raise ValueError(
                f"engine {self.name!r} expects {want} cache state(s) per "
                f"handoff, got {len(states)} — prefill and decode replicas "
                f"must agree on speculative decoding")
        self._prefix.insert(prompt, tuple(states), int(next_token))

    def estimated_wait_s(self) -> float:
        """Backlog drain estimate from the decode token-rate EWMA: backlog ×
        mean tokens/request ÷ aggregate tokens/s. 0 before any rate sample —
        shed never fires on the deadline rule until the engine has served."""
        rate = self._rate_tps
        if rate <= 0.0:
            return 0.0
        tpr = self._tok_per_req if self._tok_per_req > 0 else 1.0
        return self._backlog * tpr / rate

    def _backlog_dec(self) -> None:
        with self._backlog_lock:
            if self._backlog > 0:
                self._backlog -= 1

    def start(self) -> "ServingEngine":
        """Start the supervisor + engine thread (idempotent; ``submit``
        calls it)."""
        with self._start_lock:
            if self._thread is None:
                if self._stop.is_set() or self._drain.is_set():
                    raise EngineShutdown(
                        f"engine {self.name!r} is shut down")
                if self._watchdog is not None:
                    self._watchdog.start()
                # live-plane wiring: the endpoint (if configured) sees this
                # engine's stats() per tenant, and watchdog stall dumps gain
                # the trace IDs of whatever this engine has in flight
                obs_exporter.start_from_env()
                obs_slo.start_from_env()
                obs_exporter.register_engine(self)
                obs_watchdog.add_context_provider(self._watchdog_context)
                self._thread = threading.Thread(
                    target=self._supervise,
                    name=f"bigdl-serve-{self.name}", daemon=True)
                self._thread.start()
        return self

    def install_signal_drain(self) -> "ServingEngine":
        """Arm SIGTERM → ``shutdown(drain=True, wait=False)``, CHAINING any
        previously installed handler (the training side's preemption handler
        keeps working). Call from the main thread (a CPython signal rule).
        Idempotent per engine is NOT attempted — call once."""
        import signal

        prev = signal.getsignal(signal.SIGTERM)

        def _handler(signum, frame):
            logger.warning("SIGTERM: draining serving engine %r", self.name)
            self.shutdown(drain=True, wait=False)
            if callable(prev) and prev not in (signal.SIG_IGN,
                                               signal.SIG_DFL):
                prev(signum, frame)

        signal.signal(signal.SIGTERM, _handler)
        return self

    def shutdown(self, wait: bool = True, timeout: float = 30.0,
                 drain: bool = False,
                 drain_timeout: Optional[float] = None) -> None:
        """Stop accepting requests and bring the engine down.

        ``drain=False`` (default): abort everything unfinished — their
        handles raise :class:`EngineShutdown`. ``drain=True``: finish
        in-flight sequences first, up to ``drain_timeout`` seconds
        (default ``drain_s`` / BIGDL_SERVE_DRAIN_S); queued-but-unadmitted
        requests and anything still running at the deadline are aborted.

        ``wait=True`` joins the engine thread and raises
        :class:`EngineShutdownTimeout` — with a thread-stack + open-span
        dump — if it is still alive after ``timeout`` seconds, instead of
        silently leaking it."""
        if drain and not self._stop.is_set() and not self._drain.is_set():
            if drain_timeout is None:
                drain_timeout = self.drain_s
            self._drain_deadline = time.perf_counter() + drain_timeout
            self._drain.set()
            self._set_health("draining")
            # close WITHOUT dropping: a submit racing this close lands its
            # request in the queue, and the drain loop must find and abort
            # it — drop-on-close would strand that future forever
            self._queue.close(drain=True)
            events.record("serving_drain", engine=self.name,
                          in_flight=self._sched.active_count,
                          timeout_s=drain_timeout)
            if self._thread is None:   # never started: nothing to drain
                self._stop.set()
                self._set_health("dead")
        else:
            self._stop.set()
            self._queue.close(drain=True)
            if self._thread is None:
                # never started (lazy start): no supervisor will ever run
                # its finally-block, so flip health here — a fleet router
                # must see this replica as dead, not forever "starting"
                self._set_health("dead")
        t = self._thread
        if wait and t is not None and t is not threading.current_thread() \
                and t is not self._worker:
            budget = timeout + (drain_timeout if drain and drain_timeout
                                else 0.0)
            t.join(timeout=budget)
            if t.is_alive():
                stacks = obs_watchdog.HangWatchdog.thread_stacks()
                spans = trace.open_spans()
                lines = [f"engine {self.name!r} thread still alive "
                         f"{budget:.1f}s after shutdown — LEAKED"]
                for label, entries in spans.items():
                    chain = " > ".join(
                        f"{e['name']} ({e['age_ms']:.0f}ms)"
                        for e in entries)
                    lines.append(f"open spans [{label}]: {chain}")
                for label, stack in stacks.items():
                    if label.startswith("bigdl-serve"):
                        lines.append(f"--- thread {label} ---")
                        lines.append(stack.rstrip())
                msg = "\n".join(lines)
                logger.error("%s", msg)
                events.record("serving_shutdown_timeout", engine=self.name,
                              timeout_s=budget)
                raise EngineShutdownTimeout(msg)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False

    def stats(self) -> dict:
        """Engine-side ledger: compiled-program count (the bucket-reuse
        proof), slot recycles, completion counts, health + robustness
        counters. Latency percentiles live in the obs registry
        (``serving/*`` histograms)."""
        return {
            "name": self.name,
            "slots": self.slots,
            "buckets": self.buckets,
            "max_len": self.max_len,
            "compiled_programs": len(self._programs),
            "program_grid_bound": len(self.buckets) + 2,
            "slot_recycles": self._sched.recycles,
            "submitted": self._submitted,
            "completed": self._completed,
            "active_slots": self._sched.active_count,
            "queued": self._queue.qsize(),
            "health": self._health,
            "model_version": self._model_version,
            "overload": self.overload,
            "backlog": self._backlog,
            "respawns": self._respawns,
            "timeouts": self._timeouts,
            "shed": self._shed,
            "degraded_admits": self._degraded_admits,
            "poisoned_slots": self._poisoned,
            "decode_tps": round(self._rate_tps, 3),
            "est_wait_s": round(self.estimated_wait_s(), 6),
            "slo_degraded": self._slo_degraded,
            # machine-readable load triple — the fleet router's dispatch
            # signal and the EngineOverloaded payload, same numbers
            "queue_depth": self._backlog,
            "decode_rate": round(self._rate_tps, 3),
            "est_wait_ms": round(self.estimated_wait_s() * 1e3, 3),
            # speculative decoding (0s when no draft model)
            "spec_tokens": self._spec,
            "spec_proposed": self._spec_proposed,
            "spec_accepted": self._spec_accepted,
            "spec_acceptance": round(
                self._spec_accepted / self._spec_proposed, 4)
            if self._spec_proposed else 0.0,
            # prefix KV-cache pool (0s when the pool is off; ``is not None``
            # matters — an EMPTY pool is falsy via __len__ but still counts)
            "prefix_entries": (len(self._prefix)
                               if self._prefix is not None else 0),
            "prefix_hits": (self._prefix.hits
                            if self._prefix is not None else 0),
            "prefix_misses": (self._prefix.misses
                              if self._prefix is not None else 0),
            "prefix_evictions": (self._prefix.evictions
                                 if self._prefix is not None else 0),
            "prefix_tokens_saved": (self._prefix.tokens_saved
                                    if self._prefix is not None else 0),
            "prefix_bytes": (self._prefix.stats()["bytes"]
                             if self._prefix is not None else 0),
            # paged KV cache (slot-grid engines report paged=False + 0s)
            "paged": self.paged,
            "pages_total": self.pages,
            "page_tokens": self.page_tokens if self.paged else 0,
            "pages_used": (self._allocator.used_count
                           if self.paged else 0),
            "pages_free": (self._allocator.free_count
                           if self.paged else 0),
            # memory headroom the queue-depth load triple cannot see (a
            # short queue on a page-starved replica still stalls): free
            # pages / pool in paged mode, free slots / grid in legacy —
            # the router ranks memory-starved replicas last on this
            "free_page_ratio": round(
                (self._allocator.free_count / self.pages) if self.paged
                else ((self.slots - self._sched.active_count)
                      / self.slots), 4),
            "page_evictions": self._page_evictions,
            # disaggregation: prefill_export calls currently running (the
            # fleet router's prefill-replica load signal)
            "prefill_inflight": self._prefill_inflight,
        }

    # --------------------------------------------------------------- health
    def _set_health(self, state: str) -> None:
        if state == self._health:
            return
        self._health = state
        registry.gauge("serving/health").set(_HEALTH_CODE[state])
        trace.event("serving_health", engine=self.name, health=state)

    def _update_health(self) -> None:
        if self._drain.is_set() or self._stop.is_set():
            return
        pressure = self._backlog >= self.slots
        self._set_health(
            "degraded" if (pressure or self._respawns
                           or self._slo_degraded) else "ready")

    def set_slo_degraded(self, flag: bool) -> None:
        """SLO-monitor hook (obs/slo.py): a breach forces health to
        ``degraded`` until the rules recover. Safe from any thread — health
        writes are a gauge set + event, and the decode loop re-evaluates
        every iteration anyway."""
        flag = bool(flag)
        if flag == self._slo_degraded:
            return
        self._slo_degraded = flag
        if self._thread is not None:
            self._update_health()

    def _watchdog_context(self) -> dict:
        """Stall-dump context: the trace IDs + progress of every in-flight
        request, so a wedged decode loop names WHICH requests are stuck."""
        now = time.perf_counter()
        inflight = []
        for slot in self._sched.active_slots():
            r = slot.request
            inflight.append({
                "trace_id": r.trace_id, "request_id": r.request_id,
                "slot": slot.index, "generated": len(r.generated),
                "age_ms": round((now - r.submit_t) * 1e3, 1)})
        return {"engine": self.name, "health": self._health,
                "in_flight": inflight}

    # ---------------------------------------------------------- supervisor
    def _supervise(self) -> None:
        """Own the decode-loop thread: respawn it on abnormal death while
        the crash budget lasts, recovering in-flight requests first. Runs
        the final abort so no future is ever left unresolved."""
        budget = self.crash_budget
        try:
            while True:
                w = threading.Thread(
                    target=self._thread_main,
                    name=f"bigdl-serve-{self.name}-loop", daemon=True)
                self._worker = w
                w.start()
                w.join()
                err = self._failure
                if err is None or self._stop.is_set():
                    break
                if budget <= 0:
                    logger.error(
                        "engine %r thread died (%s: %s) with the crash "
                        "budget exhausted; aborting outstanding requests",
                        self.name, type(err).__name__, err)
                    events.record("serving_crash_budget_exhausted",
                                  engine=self.name,
                                  error=f"{type(err).__name__}: {err}")
                    break
                budget -= 1
                self._respawns += 1
                registry.counter("serving/thread_respawns").inc()
                events.record("serving_thread_respawn", engine=self.name,
                              error=f"{type(err).__name__}: {err}",
                              budget_left=budget)
                logger.warning(
                    "engine %r thread died (%s: %s); respawning "
                    "(%d respawns, budget left %d)", self.name,
                    type(err).__name__, err, self._respawns, budget)
                self._recover()
                self._failure = None
        finally:
            self._stop.set()
            self._abort_outstanding(self._pending)
            self._set_health("dead")
            obs_watchdog.remove_context_provider(self._watchdog_context)
            if self._watchdog is not None:
                self._watchdog.stop()

    def _thread_main(self) -> None:
        try:
            self._loop()
        except BaseException as e:  # noqa: BLE001 — fail handles, not silence
            self._failure = e
            trace.event("serving_engine_failure", engine=self.name,
                        error=f"{type(e).__name__}: {e}")

    def _recover(self) -> None:
        """Rebuild device state after a decode-loop death: fresh zeroed slot
        grid, every in-flight request pushed to the FRONT of pending so the
        respawned loop re-prefills it from prompt + already-emitted tokens.
        Re-prefilling the full context reproduces the incremental path
        bitwise (chunked-prefill == full-forward), so callers see added
        latency, never different tokens."""
        nn = self._nn
        evicted = self._sched.reset()
        self._dec_state = self._install_grid()
        if self._draft is not None:
            self._dec_state_d = nn.install_decode_cache(
                self._draft, self.slots, self.max_len, dtype=self._dtype,
                per_slot=True)
            nn.clear_decode_cache(self._draft)
        self._pending[:0] = evicted
        registry.gauge("serving/active_slots").set(0)
        events.record("serving_recovered", engine=self.name,
                      requeued=len(evicted), pending=len(self._pending))

    # ----------------------------------------------------------- hot swap
    def swap_weights(self, params, version: int = 0,
                     timeout: float = 60.0) -> SwapResult:
        """Install a new weight snapshot with ZERO dropped requests — the
        promotion plane's entry point (``serving/lifecycle.py``), callable
        from any thread.

        No drain: the engine thread pauses at the next decode-step
        boundary, installs ``params`` (same tree structure/shapes as the
        current snapshot — anything else raises ``ValueError`` and the old
        weights keep serving), rebuilds the slot grid, and re-prefills
        every in-flight sequence from prompt + already-emitted tokens in
        one chunk — the crash-recovery machinery, so tokens emitted before
        the swap are preserved verbatim and tokens after are bitwise what
        the new weights produce from that prefix. The prefill/decode
        program keys are unchanged (params are jit *arguments*), so
        ``stats()['compiled_programs']`` does not grow across a swap.

        Returns a :class:`SwapResult`; raises whatever made the swap fail
        (injected ``promote_swap`` faults included) with the previous
        weights still serving."""
        if self._stop.is_set() or self._drain.is_set():
            raise EngineShutdown(
                f"engine {self.name!r} is shut down or draining; "
                f"cannot swap weights")
        cmd = _SwapCommand(params, int(version))
        with self._swap_lock:
            if self._swap_pending is not None:
                raise RuntimeError(
                    f"engine {self.name!r}: a weight swap is already in "
                    f"progress")
            if self._thread is None:
                # lazy engine, never started: no decode loop, no in-flight
                # state — apply synchronously on the caller's thread
                with self._start_lock:
                    if self._thread is None:
                        self._execute_swap(cmd)
                        if cmd.error is not None:
                            raise cmd.error
                        return cmd.result
            self._swap_pending = cmd
        self._queue.try_put(_WAKE)   # unblock an idle _gather; full queue
        #                              is fine — the loop is awake anyway
        if not cmd.done.wait(timeout):
            with self._swap_lock:
                if self._swap_pending is cmd:   # never reached the loop
                    self._swap_pending = None
            raise EngineShutdownTimeout(
                f"engine {self.name!r}: weight swap not serviced within "
                f"{timeout:.1f}s")
        if cmd.error is not None:
            raise cmd.error
        return cmd.result

    def _check_tree(self, params):
        """Validate + coerce a candidate tree against the serving snapshot:
        identical flattened paths, identical shapes, leaves cast to the
        CURRENT leaf's dtype so the swap can never change the jit signature
        (a dtype drift would silently grow the program ledger)."""
        from bigdl_tpu.utils.model_registry import flatten_params

        cur = flatten_params(self._params)
        new = flatten_params(params)
        if set(cur) != set(new):
            missing = sorted(set(cur) - set(new))[:3]
            extra = sorted(set(new) - set(cur))[:3]
            raise ValueError(
                f"engine {self.name!r}: candidate params tree does not "
                f"match the serving snapshot (missing={missing}, "
                f"extra={extra})")
        out = {}
        for path, leaf in new.items():
            ref = cur[path]
            arr = np.asarray(leaf)
            if tuple(arr.shape) != tuple(ref.shape):
                raise ValueError(
                    f"engine {self.name!r}: candidate leaf {path!r} has "
                    f"shape {tuple(arr.shape)}, serving snapshot has "
                    f"{tuple(ref.shape)}")
            out[path] = arr.astype(ref.dtype, copy=False)
        # rebuild the nested tree in the snapshot's own structure
        def rebuild(node, prefix=""):
            if not isinstance(node, dict):
                return out[prefix]
            return {k: rebuild(v, f"{prefix}/{k}" if prefix else str(k))
                    for k, v in node.items()}
        return rebuild(self._params)

    def _execute_swap(self, cmd: "_SwapCommand") -> None:
        """The swap itself — runs at a decode-step boundary on the engine
        thread (or on the caller's thread for a never-started engine). Any
        failure leaves the previous snapshot fully serving."""
        nn = self._nn
        t0 = time.perf_counter()
        try:
            fault_point(faults.SITE_PROMOTE_SWAP)
            new_params = self._check_tree(cmd.params)
            in_flight = {s.request.request_id: len(s.request.generated)
                         for s in self._sched.active_slots()}
            evicted = self._sched.reset()
            self._params = new_params
            # fresh zeroed grids: the old rows' KV entries were computed
            # under the old weights and must not leak into new decodes
            self._dec_state = self._install_grid()
            if self._draft is not None:
                self._dec_state_d = nn.install_decode_cache(
                    self._draft, self.slots, self.max_len,
                    dtype=self._dtype, per_slot=True)
                nn.clear_decode_cache(self._draft)
            if self._prefix is not None:
                self._prefix.clear()   # pooled states encode the old weights
            self._pending[:0] = evicted
            registry.gauge("serving/active_slots").set(0)
            self._model_version = cmd.version
            registry.gauge("serve/model_version").set(cmd.version)
            dt = time.perf_counter() - t0
            events.record("serving_weight_swap", engine=self.name,
                          version=cmd.version, requeued=len(evicted),
                          duration_ms=round(dt * 1e3, 3))
            logger.info(
                "engine %r: weight swap to v%d (%d in-flight re-prefilled, "
                "%.1f ms)", self.name, cmd.version, len(evicted), dt * 1e3)
            cmd.result = SwapResult(cmd.version, in_flight, len(evicted),
                                    dt)
        except BaseException as e:  # noqa: BLE001 — fail the WAITER, not us
            events.record("serving_swap_failed", engine=self.name,
                          version=cmd.version,
                          error=f"{type(e).__name__}: {e}")
            logger.error("engine %r: weight swap to v%d failed: %s — old "
                         "weights keep serving", self.name, cmd.version, e)
            cmd.error = e
        finally:
            cmd.done.set()

    def _service_swap(self) -> None:
        with self._swap_lock:
            cmd, self._swap_pending = self._swap_pending, None
        if cmd is not None:
            self._execute_swap(cmd)

    @property
    def model_version(self) -> int:
        return self._model_version

    @property
    def params_snapshot(self):
        """The currently-serving weight tree (read-only: the promotion
        controller captures it before the first swap so rollback can
        restore a construction-time snapshot that was never registered)."""
        return self._params

    # -------------------------------------------------------- engine thread
    def _loop(self) -> None:
        self._set_health("degraded" if self._respawns else "ready")
        wd = self._watchdog
        while not self._stop.is_set():
            fault_point(faults.SITE_SERVE_THREAD)
            # decode-step boundary: service a pending weight swap before
            # admitting/ticking — in-flight rows land in _pending and
            # re-prefill below through the ordinary admission path
            if self._swap_pending is not None:
                self._service_swap()
            closed = self._gather(self._pending)
            if self._drain.is_set():
                self._drain_loop()
                return
            now = time.perf_counter()
            self._expire_pending(now)
            while self._pending and self._sched.has_free() \
                    and not self._stop.is_set():
                req = self._pending.pop(0)
                if not self._admit(req):
                    # page pool exhausted: head-of-line request waits (block
                    # semantics) — decode keeps ticking below, finishing
                    # sequences free pages, and admission retries next loop
                    self._pending.insert(0, req)
                    break
            self._update_health()
            if self._sched.any_active() and not self._stop.is_set():
                self._tick()
                self._expire_slots()
            elif closed:
                break
            if wd is not None and not self._sched.any_active():
                wd.disarm()

    def _gather(self, pending: list) -> bool:
        """Pull arrivals into ``pending``. Blocks only when the engine is
        fully idle; returns True once the queue is closed and drained."""
        if self._sched.any_active() or pending:
            while True:   # non-blocking drain between decode ticks
                item = self._queue.get(timeout=0)
                if item is EMPTY or item is CLOSED:
                    return item is CLOSED
                if isinstance(item, _Wake):
                    continue
                pending.append(item)
        item = self._queue.get()      # idle: sleep until traffic or shutdown
        if item is CLOSED:
            return True
        if isinstance(item, _Wake):
            return False   # swap wake-up: back to the loop top immediately
        pending.append(item)
        # SLO batch-fill wait: an idle engine lingers admit_wait_s for
        # co-batchable arrivals before paying the first prefill — higher
        # batch fill (throughput) for admit_wait of added TTFT
        if self.admit_wait_s > 0:
            deadline = time.perf_counter() + self.admit_wait_s
            while len(pending) < self.slots:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                nxt = self._queue.get(timeout=remaining)
                if nxt is EMPTY:
                    break
                if nxt is CLOSED:
                    return True
                if isinstance(nxt, _Wake):
                    break
                pending.append(nxt)
        return False

    def _drain_loop(self) -> None:
        """Graceful drain: abort everything NOT yet in a slot (it never
        started — EngineShutdown, retryable elsewhere), then keep ticking
        the in-flight sequences until they finish or the drain deadline
        passes. The supervisor's final abort covers anything left."""
        err = EngineShutdown(
            f"engine {self.name!r} is draining; request was not in flight")
        for req in self._pending:
            req.handle._fail(err)
            self._backlog_dec()
        self._pending.clear()
        while True:
            item = self._queue.get(timeout=0)
            if item is EMPTY or item is CLOSED:
                break
            if isinstance(item, _Wake):
                continue
            item.handle._fail(err)
            self._backlog_dec()
        while self._sched.any_active() and not self._stop.is_set():
            if time.perf_counter() >= self._drain_deadline:
                events.record("serving_drain_deadline", engine=self.name,
                              aborted=self._sched.active_count)
                logger.warning(
                    "engine %r drain deadline passed with %d sequences "
                    "in flight; aborting them", self.name,
                    self._sched.active_count)
                break
            self._tick()
            self._expire_slots()
        if not self._sched.any_active():
            events.record("serving_drain_complete", engine=self.name)
        self._stop.set()

    # ------------------------------------------------------------ deadlines
    def _timeout(self, req: Request, in_slot: bool) -> None:
        self._timeouts += 1
        registry.counter("serving/timeouts").inc()
        events.record("serving_timeout", engine=self.name,
                      request_id=req.request_id, trace_id=req.trace_id,
                      in_slot=in_slot, generated=len(req.generated))
        req.handle._fail(RequestTimeout(
            f"request {req.request_id} missed its deadline "
            f"({'mid-decode' if in_slot else 'while queued'}, "
            f"{len(req.generated)} tokens generated) "
            f"[trace {req.trace_id}]"))
        self._access_log(req, "timeout")
        if not in_slot:
            self._backlog_dec()

    def _expire_pending(self, now: float) -> None:
        if not self._pending:
            return
        keep = []
        for req in self._pending:
            if req.expired(now):
                self._timeout(req, in_slot=False)
            else:
                keep.append(req)
        self._pending[:] = keep

    def _expire_slots(self) -> None:
        """Recycle slots whose request blew its deadline mid-decode — the
        row is freed NOW (its stale cache is wiped on reassignment) instead
        of burning ticks on a request nobody is waiting for."""
        now = time.perf_counter()
        released = False
        for slot in self._sched.active_slots():
            if slot.request.expired(now):
                self._timeout(slot.request, in_slot=True)
                self._free_slot_pages(slot.index)
                self._sched.release(slot)
                released = True
        if released:
            registry.gauge("serving/active_slots").set(
                self._sched.active_count)

    # ------------------------------------------------------------ admission
    def _prefill_ctx(self, ctx, clen, hit, req):
        """Produce the filled batch-1 cache state(s) + first token for a
        context, via the cheapest path available:

        - exact prefix-pool hit: no device program at all — the pooled
          state and its stored next-token are the answer;
        - partial hit: rewrite the pooled state's positions to the matched
          depth and prefill only the REMAINDER through the same bucket
          programs (``pick_seed_bucket`` guarantees the write window fits);
        - miss / pool off: full bucketed prefill, then pool the result.

        Returns ``(next_token, states)`` where ``states`` is ``(filled,)``
        or ``(filled, filled_draft)`` with a draft model."""
        import jax.numpy as jnp

        def run_prefill(state, state_d, padded):
            if self._spec:
                next_all, ok, filled, filled_d = self._prefill_spec(
                    state, state_d, jnp.asarray(padded))
                states = (filled, filled_d)
            else:
                next_all, ok, filled = self._prefill(
                    self._params, state, jnp.asarray(padded))
                states = (filled,)
            if not bool(np.asarray(ok)):
                raise NonFiniteLogitsError(
                    f"non-finite logits prefilling request "
                    f"{req.request_id} [trace {req.trace_id}]")
            return next_all, states

        if hit is not None:
            entry, c = hit
            registry.counter("serving/prefix_hits").inc()
            registry.counter("serving/prefix_tokens_saved").inc(c)
            if c == clen:
                self._last_prefill_flops = None   # no compiled program ran
                # seeded() also restores page-truncated rows to the full
                # window (the assign scatter needs max_len-shaped leaves)
                return entry.next_token, PrefixPool.seeded(entry, c)
            seeded = PrefixPool.seeded(entry, c)
            rem = clen - c
            lb = pick_seed_bucket(rem, self.buckets, c, self.max_len)
            padded = np.zeros((1, lb), np.int32)
            padded[0, :rem] = ctx[c:]
            next_all, states = run_prefill(
                seeded[0], seeded[1] if self._spec else None, padded)
            nxt = int(np.asarray(next_all)[0, rem - 1])
        else:
            lb = pick_bucket(clen, self.buckets)
            if lb is None:
                lb = self.max_len   # recovery-only: context outgrew grid
            padded = np.zeros((1, lb), np.int32)
            padded[0, :clen] = ctx
            next_all, states = run_prefill(
                self._pre_state0, self._pre_state0_d, padded)
            nxt = int(np.asarray(next_all)[0, clen - 1])
        if self._prefix is not None:
            self._prefix.insert(ctx, states, nxt)
        return nxt, states

    def _admit(self, req: Request) -> bool:
        """Prefill ``req``'s context into a free slot: one bucketed prefill
        program, one slot-assign scatter — and the FIRST generated token
        falls out of the prefill logits (TTFT ends here). On the crash-
        recovery path the context is prompt + already-emitted tokens, so the
        re-prefilled slot resumes exactly where the dead loop stopped.

        Returns False ONLY when the page pool cannot back the context right
        now (paged mode): the request is untouched — the caller requeues it
        at the head and lets decode free pages. Every other failure fails
        the request's own handle and returns True."""
        if req.generated:
            ctx = np.concatenate(
                [req.prompt, np.asarray(req.generated, np.int32)])
        else:
            ctx = req.prompt
        clen = int(ctx.size)
        pages = None
        if self.paged:
            # CONTENT pages only (ceil(clen / page_tokens)): the page the
            # first decode write lands in is _ensure_pages's job, so the
            # lifetime-peak allocation matches submit's fit check exactly
            need = (clen - 1) // self.page_tokens + 1
            pages = self._allocator.alloc(need)
            if pages is None:
                events.record("serving_page_backpressure",
                              engine=self.name, request_id=req.request_id,
                              trace_id=req.trace_id, pages_needed=need,
                              pages_free=self._allocator.free_count)
                return False
        recycles_before = self._sched.recycles
        slot = self._sched.admit(req)
        if self._sched.recycles > recycles_before:
            registry.counter("serving/slot_recycles").inc()
        if self.paged:
            self._slot_pages[slot.index] = pages
            self._page_table[slot.index, :] = TRASH_PAGE
            self._page_table[slot.index, :len(pages)] = pages
            self._table_dirty = True
            slot.depth = clen
            self._publish_page_gauges()
        if req.admit_t is None:
            req.admit_t = time.perf_counter()
            self._backlog_dec()
            registry.histogram("serving/queue_wait_ms").observe(
                (req.admit_t - req.submit_t) * 1e3)
        lb = pick_bucket(clen, self.buckets)
        if lb is None:
            lb = self.max_len   # recovery-only: context outgrew the grid
        hit = (self._prefix.lookup(ctx, self.buckets, self.max_len)
               if self._prefix is not None else None)
        try:
            fault_point(faults.SITE_SERVE_PREFILL)
            pre_t0 = time.perf_counter()
            with trace.span("serve/prefill",
                            {"bucket": lb, "slot": slot.index,
                             "trace_id": req.trace_id,
                             "prefix_hit": hit[1] if hit else 0}):
                nxt, states = self._prefill_ctx(ctx, clen, hit, req)
                self._assign(states, slot.index, clen)
            obs_mfu.note("serve", self._last_prefill_flops,
                         time.perf_counter() - pre_t0)
        except (FaultError, NonFiniteLogitsError) as e:
            # this request fails loudly; the decode grid was never touched,
            # so co-batched slots are unaffected
            if isinstance(e, NonFiniteLogitsError):
                self._poisoned += 1
                registry.counter("serving/poisoned_slots").inc()
                events.record("serving_poisoned_slot", engine=self.name,
                              request_id=req.request_id,
                              trace_id=req.trace_id, phase="prefill")
            else:
                events.record("serving_prefill_failed", engine=self.name,
                              request_id=req.request_id,
                              trace_id=req.trace_id, error=str(e))
            logger.error("engine %r: request %r failed in prefill: %s",
                         self.name, req.request_id, e)
            req.handle._fail(e)
            self._free_slot_pages(slot.index)
            self._sched.release(slot)
            registry.gauge("serving/active_slots").set(
                self._sched.active_count)
            return True
        if req.first_token_t is None:
            req.first_token_t = time.perf_counter()
            registry.histogram("serving/ttft_ms").observe(
                (req.first_token_t - req.submit_t) * 1e3)
        req.generated.append(nxt)
        if self._finished(req, nxt):
            self._finish(slot, nxt)
        else:
            slot.last_token = nxt
        registry.gauge("serving/active_slots").set(self._sched.active_count)
        return True

    # --------------------------------------------------------------- decode
    def _tick(self) -> None:
        """One continuous-batch decode step over the whole slot grid. Free
        rows ride along with a dummy token (static shape!); their output is
        ignored and their stale cache is wiped on reassignment."""
        import jax.numpy as jnp

        if self._spec:
            self._tick_spec()
            return
        t0 = time.perf_counter()
        if self.paged:
            # grow page lists to cover this tick's writes (preempting the
            # youngest on exhaustion), then push the host table to the
            # device BEFORE the program runs — a freed row's stale device
            # table would scribble on someone else's pages
            self._ensure_pages()
            if not self._sched.any_active():
                return
            self._sync_page_table()
        active = self._sched.active_slots()
        tok = np.zeros((self.slots,), np.int32)
        for slot in active:
            tok[slot.index] = slot.last_token
        fault_point(faults.SITE_SERVE_STALL)   # "stall" sleeps right here
        with trace.span("serve/decode_step", {"active": len(active)}):
            nxt, ok, self._dec_state = self._decode(
                self._params, self._dec_state, jnp.asarray(tok))
            nxt = np.asarray(nxt)
            ok = np.asarray(ok)
        action = check_fault(faults.SITE_SERVE_DECODE)
        if action == "nonfinite" and active:
            # poison the lowest-index active slot: the guard below must fail
            # exactly that request and leave its co-batched rows untouched
            ok = ok.copy()
            ok[active[0].index] = False
        elif action is not None and action != "nonfinite":
            raise FaultError(
                f"injected fault at site {faults.SITE_SERVE_DECODE!r}")
        dt = time.perf_counter() - t0
        if dt > 0 and active:
            inst = len(active) / dt
            self._rate_tps = (inst if self._rate_tps == 0.0
                              else 0.8 * self._rate_tps + 0.2 * inst)
            obs_mfu.note("serve", self._decode_flops, dt)
        if self._watchdog is not None:
            self._watchdog.heartbeat(dt)
        for slot in active:
            req = slot.request
            slot.depth += 1   # mirrors the device pos advance this tick
            if not bool(ok[slot.index]):
                self._poison(slot)
                continue
            t = int(nxt[slot.index])
            req.generated.append(t)
            if self._finished(req, t):
                self._finish(slot, t)
            else:
                slot.last_token = t
        registry.gauge("serving/active_slots").set(self._sched.active_count)

    def _tick_spec(self) -> None:
        """Speculative decode tick: ONE fused program drafts k proposals
        per row, verifies them in a single t=k+1 chunked target forward
        (the last-position-logits invariant IS the verify), accepts the
        longest agreeing prefix, and rewinds both caches — each active row
        emits 1..k+1 tokens per tick, bitwise what plain greedy would have
        emitted. Free rows ride along; their drifting positions only ever
        touch their own (wiped-on-reassign) cache rows."""
        import jax.numpy as jnp

        t0 = time.perf_counter()
        if self.paged:
            # reserve through the verify chunk's deepest write and push the
            # host table before the fused program runs (same contract as
            # the plain paged tick)
            self._ensure_pages()
            if not self._sched.any_active():
                return
            self._sync_page_table()
        active = self._sched.active_slots()
        tok = np.zeros((self.slots,), np.int32)
        for slot in active:
            tok[slot.index] = slot.last_token
        fault_point(faults.SITE_SERVE_STALL)   # "stall" sleeps right here
        with trace.span("serve/spec_step",
                        {"active": len(active), "k": self._spec}):
            props, greedy, n_acc, ok, self._dec_state, self._dec_state_d = \
                self._spec_step(jnp.asarray(tok))
            props = np.asarray(props)
            greedy = np.asarray(greedy)
            n_acc = np.asarray(n_acc)
            ok = np.asarray(ok)
        action = check_fault(faults.SITE_SERVE_DECODE)
        if action == "nonfinite" and active:
            ok = ok.copy()
            ok[active[0].index] = False
        elif action is not None and action != "nonfinite":
            raise FaultError(
                f"injected fault at site {faults.SITE_SERVE_DECODE!r}")
        dt = time.perf_counter() - t0
        if dt > 0 and active:
            emitted = sum(int(n_acc[s.index]) + 1 for s in active)
            inst = emitted / dt
            self._rate_tps = (inst if self._rate_tps == 0.0
                              else 0.8 * self._rate_tps + 0.2 * inst)
            obs_mfu.note("serve", self._decode_flops, dt)
        if self._watchdog is not None:
            self._watchdog.heartbeat(dt)
        for slot in active:
            req = slot.request
            if not bool(ok[slot.index]):
                self._poison(slot)
                continue
            j = int(n_acc[slot.index])
            # the device pos advanced k+1 then rewound k-j: net 1+j rows
            slot.depth += j + 1
            self._spec_proposed += self._spec
            self._spec_accepted += j
            # accepted proposals, then the correction token; tokens past a
            # finish (eos / length cap) are exactly the greedy continuation
            # and are dropped, matching plain decode's stopping point
            toks = [int(props[slot.index, i]) for i in range(j)]
            toks.append(int(greedy[slot.index, j]))
            finished = False
            for t in toks:
                req.generated.append(t)
                if self._finished(req, t):
                    self._finish(slot, t)
                    finished = True
                    break
            if not finished:
                slot.last_token = req.generated[-1]
        registry.gauge("serving/active_slots").set(self._sched.active_count)

    def _poison(self, slot) -> None:
        """Per-slot non-finite guard tripped: fail THIS request, wipe the
        row before anyone reuses it, keep every other slot decoding."""
        req = slot.request
        self._poisoned += 1
        registry.counter("serving/poisoned_slots").inc()
        events.record("serving_poisoned_slot", engine=self.name,
                      request_id=req.request_id, trace_id=req.trace_id,
                      phase="decode", slot=slot.index)
        logger.error(
            "engine %r: non-finite logits in slot %d (request %r); "
            "failing the request and resetting the row",
            self.name, slot.index, req.request_id)
        req.handle._fail(NonFiniteLogitsError(
            f"non-finite logits decoding request {req.request_id} "
            f"(slot {slot.index}) [trace {req.trace_id}]"))
        self._access_log(req, "poisoned")
        self._reset_row(slot.index)   # paged: zeroes the pages themselves
        self._free_slot_pages(slot.index)
        self._sched.release(slot)

    def _finished(self, req: Request, token: int) -> bool:
        return ((self.eos_id is not None and token == self.eos_id)
                or len(req.generated) >= req.max_new_tokens)

    def _finish(self, slot, last_token: int) -> None:
        req = slot.request
        reason = (FINISH_EOS if (self.eos_id is not None
                                 and last_token == self.eos_id)
                  else FINISH_LENGTH)
        result = req.complete(reason)
        self._completed += 1
        registry.counter("serving/completed").inc()
        registry.histogram("serving/e2e_ms").observe(result.latency_s * 1e3)
        tpot = result.time_per_token_s()
        if tpot is not None:
            registry.histogram("serving/tpot_ms").observe(tpot * 1e3)
        n = result.n_generated
        self._tok_per_req = (float(n) if self._tok_per_req == 0.0
                             else 0.8 * self._tok_per_req + 0.2 * n)
        self._maybe_persist_trace(req, result)
        self._access_log(req, "ok", e2e_s=result.latency_s)
        self._free_slot_pages(slot.index)
        self._sched.release(slot)

    def _access_log(self, req: Request, outcome: str,
                    e2e_s: Optional[float] = None) -> None:
        """One structured access-log record per finished request
        (``obs/access_log.py``; free when ``BIGDL_ACCESS_LOG`` is unset).
        ``flops`` is the per-request estimate from the memoized program
        FLOPs: one prefill plus one decode step per generated token —
        None (absent, not wrong) when the backend reported neither."""
        n_out = len(req.generated)
        flops = None
        if self._last_prefill_flops is not None or \
                self._decode_flops is not None:
            flops = ((self._last_prefill_flops or 0.0)
                     + (self._decode_flops or 0.0) * n_out)
        now = time.perf_counter()
        obs_access_log.log_request(
            trace_id=req.trace_id, tenant=self.name,
            phase="decode" if req.admit_t is not None else "queue",
            prompt_tokens=req.prompt_len, output_tokens=n_out,
            ttft_ms=(round((req.first_token_t - req.submit_t) * 1e3, 3)
                     if req.first_token_t is not None else None),
            e2e_ms=round((e2e_s if e2e_s is not None
                          else now - req.submit_t) * 1e3, 3),
            flops=flops, outcome=outcome)

    def _maybe_persist_trace(self, req: Request, result) -> None:
        """Tail sampling: persist the request's span tree to the JSONL log
        only when it lands in the slowest ``BIGDL_TRACE_SAMPLE`` fraction of
        the ``serving/e2e_ms`` window (the request's own observation is
        already in the window). Keeps the log a gallery of outliers, not a
        firehose; ``>= 1.0`` persists every request."""
        if trace.jsonl_path() is None:
            return
        frac = self._trace_sample
        if frac <= 0:
            return
        e2e_ms = result.latency_s * 1e3
        if frac < 1.0:
            q = max(0.0, min(100.0, 100.0 * (1.0 - frac)))
            ps = registry.histogram("serving/e2e_ms").percentiles((q,))
            thr = ps.get(q)
            if thr is not None and e2e_ms < thr:
                return
        t0 = req.submit_t

        def ms(a, b):
            return round((b - a) * 1e3, 3)

        spans = []
        if req.admit_t is not None:
            spans.append({"name": "serve/queue", "start_ms": 0.0,
                          "dur_ms": ms(t0, req.admit_t)})
        if req.admit_t is not None and req.first_token_t is not None:
            spans.append({"name": "serve/prefill",
                          "start_ms": ms(t0, req.admit_t),
                          "dur_ms": ms(req.admit_t, req.first_token_t)})
        if req.first_token_t is not None:
            end_t = t0 + result.latency_s
            spans.append({"name": "serve/decode",
                          "start_ms": ms(t0, req.first_token_t),
                          "dur_ms": ms(req.first_token_t, end_t)})
        trace.event("request_trace", trace_id=req.trace_id,
                    request_id=req.request_id, engine=self.name,
                    e2e_ms=round(e2e_ms, 3), n_generated=result.n_generated,
                    finish=result.finish_reason, spans=spans)

    def _abort_outstanding(self, pending: list) -> None:
        err = self._failure or EngineShutdown(
            f"engine {self.name!r} shut down before the request finished")
        for slot in self._sched.active_slots():
            slot.request.handle._fail(err)
            self._access_log(slot.request, "aborted")
            self._free_slot_pages(slot.index)
            self._sched.release(slot)
        for req in pending:
            req.handle._fail(err)
            self._access_log(req, "aborted")
            self._backlog_dec()
        pending.clear()
        # the queue was closed with drain=True: items a racing submit
        # slipped in are still here, and each one's future fails NOW —
        # drop-on-close used to strand them forever
        while True:
            item = self._queue.get(timeout=0)
            if item is EMPTY or item is CLOSED:
                break
            if isinstance(item, _Wake):
                continue
            item.handle._fail(err)
            self._backlog_dec()
        self._queue.close()
        # a swap whose waiter is still blocked must fail NOW — the loop
        # that would have serviced it is gone
        with self._swap_lock:
            cmd, self._swap_pending = self._swap_pending, None
        if cmd is not None:
            cmd.error = err
            cmd.done.set()
