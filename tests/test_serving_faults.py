"""Serving-plane fault injection (bigdl_tpu/serving × utils/faults).

Every serving recovery path fired on demand: engine-thread death absorbed
by the supervisor's crash budget (with bitwise-identical tokens after the
re-prefill), the per-slot non-finite guard failing exactly one co-batched
request, prefill faults staying per-request, stalls tripping deadlines and
the hang watchdog, and a wedged shutdown raising EngineShutdownTimeout
instead of silently leaking the thread. Every test pins
``plan.unfired() == []`` — a plan that did not fully fire means a site was
never reached.
"""

import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.models.transformerlm import TransformerLM
from bigdl_tpu.serving import (
    EngineShutdown, EngineShutdownTimeout, NonFiniteLogitsError,
    RequestTimeout, ServingEngine,
)
from bigdl_tpu.utils import faults
from bigdl_tpu.utils.faults import FaultError, WorkerDeathError, inject_faults
from bigdl_tpu.utils.robustness import events

pytestmark = [pytest.mark.serving, pytest.mark.serving_faults]

VOCAB = 50


@pytest.fixture(scope="module")
def lm():
    return TransformerLM(VOCAB, embed_dim=16, num_heads=2, num_layers=2,
                         max_len=48).evaluate()


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, VOCAB, (n,)).astype(np.int32)


def _oracle(model, prompt, steps):
    return np.asarray(
        nn.greedy_generate(model, jnp.asarray(prompt)[None, :], steps))[0]


def _wait_active(eng, n, timeout=60):
    deadline = time.perf_counter() + timeout
    while eng.stats()["active_slots"] < n:
        if time.perf_counter() > deadline:
            raise AssertionError(
                f"never reached {n} active slots: {eng.stats()}")
        time.sleep(0.005)


# ------------------------------------------------------- thread crash paths
class TestThreadCrashRecovery:
    def test_env_plan_thread_crash_respawns_bitwise(self, lm, monkeypatch):
        """The acceptance scenario: BIGDL_FAULT_PLAN=serve_thread@1 kills
        the decode loop; the supervisor respawns it and every future
        completes with the same tokens as a fault-free run."""
        prompts = [_prompt(400 + i, 3 + i) for i in range(4)]
        oracles = [_oracle(lm, p, 8) for p in prompts]
        monkeypatch.setenv("BIGDL_FAULT_PLAN", "serve_thread@1")
        with ServingEngine(lm, max_len=48, slots=2, buckets=(8,)) as eng:
            handles = [eng.submit(p, 8) for p in prompts]
            for h, o in zip(handles, oracles):
                np.testing.assert_array_equal(h.result(timeout=180).tokens, o)
            assert eng.stats()["respawns"] == 1
        plan = faults.active_plan()
        assert plan is not None and plan.unfired() == []
        assert events.counts().get("serving_thread_respawn", 0) >= 1

    def test_midflight_crash_reprefills_inflight_bitwise(self, lm):
        """serve_thread@2 dies AFTER the first decode tick, with sequences
        mid-flight holding emitted tokens: the respawned loop re-prefills
        prompt + generated and the outputs stay bitwise-identical."""
        c0 = events.counts()
        prompts = [_prompt(410 + i, 4 + i) for i in range(3)]
        oracles = [_oracle(lm, p, 10) for p in prompts]
        with inject_faults("serve_thread@2") as plan:
            with ServingEngine(lm, max_len=48, slots=3, buckets=(8,)) as eng:
                handles = [eng.submit(p, 10) for p in prompts]
                for h, o in zip(handles, oracles):
                    np.testing.assert_array_equal(
                        h.result(timeout=180).tokens, o)
                stats = eng.stats()
            assert plan.unfired() == []
        assert stats["respawns"] == 1
        d = events.deltas(c0)
        assert d.get("serving_thread_respawn", 0) == 1
        assert d.get("serving_recovered", 0) == 1

    def test_crash_budget_exhausted_fails_loudly(self, lm):
        """Three scripted deaths against a budget of two: the engine gives
        up, every outstanding future raises the real WorkerDeathError, and
        the exhaustion is a robustness event — not silence."""
        c0 = events.counts()
        plan_spec = "serve_thread@1;serve_thread@2;serve_thread@3"
        with inject_faults(plan_spec) as plan:
            eng = ServingEngine(lm, max_len=48, slots=2, buckets=(8,),
                                crash_budget=2)
            h = eng.submit(_prompt(420, 4), 6)
            with pytest.raises(WorkerDeathError):
                h.result(timeout=180)
            assert plan.unfired() == []
        assert eng.stats()["respawns"] == 2
        assert eng.stats()["health"] == "dead"
        assert events.deltas(c0).get("serving_crash_budget_exhausted", 0) == 1
        eng.shutdown()
        with pytest.raises(EngineShutdown):
            eng.submit(_prompt(421, 4), 2)


# --------------------------------------------------- per-slot logit guard
class TestNonFiniteGuard:
    def test_nonfinite_fails_one_request_neighbors_bitwise(self, lm):
        """serve_decode@2=nonfinite poisons the lowest-index active slot on
        the second tick: exactly that request fails with
        NonFiniteLogitsError; co-batched slots produce bitwise-identical
        output to the clean baseline, and the reset row serves the next
        request bitwise too."""
        c0 = events.counts()
        prompts = [_prompt(430 + i, 4) for i in range(3)]
        oracles = [_oracle(lm, p, 8) for p in prompts]
        extra = _prompt(439, 5)
        extra_oracle = _oracle(lm, extra, 6)
        with inject_faults("serve_decode@2=nonfinite") as plan:
            with ServingEngine(lm, max_len=48, slots=3, buckets=(8,)) as eng:
                handles = [eng.submit(p, 8) for p in prompts]
                with pytest.raises(NonFiniteLogitsError):
                    handles[0].result(timeout=180)   # slot 0 was poisoned
                for h, o in zip(handles[1:], oracles[1:]):
                    np.testing.assert_array_equal(
                        h.result(timeout=180).tokens, o)
                # the wiped row serves the next request bitwise-correct
                np.testing.assert_array_equal(
                    eng.submit(extra, 6).result(timeout=180).tokens,
                    extra_oracle)
                assert eng.stats()["poisoned_slots"] == 1
            assert plan.unfired() == []
        assert events.deltas(c0).get("serving_poisoned_slot", 0) == 1

    def test_decode_error_action_crashes_and_recovers(self, lm):
        """serve_decode@1=error is the crash flavour: the tick raises, the
        supervisor absorbs it, and the request still completes bitwise."""
        prompt = _prompt(440, 4)
        oracle = _oracle(lm, prompt, 6)
        with inject_faults("serve_decode@1=error") as plan:
            with ServingEngine(lm, max_len=48, slots=2, buckets=(8,)) as eng:
                r = eng.submit(prompt, 6).result(timeout=180)
                assert eng.stats()["respawns"] == 1
            assert plan.unfired() == []
        np.testing.assert_array_equal(r.tokens, oracle)


# ------------------------------------------------------------ prefill fault
class TestPrefillFault:
    def test_prefill_fault_fails_only_that_request(self, lm):
        c0 = events.counts()
        good = _prompt(451, 4)
        oracle = _oracle(lm, good, 6)
        with inject_faults("serve_prefill@1") as plan:
            with ServingEngine(lm, max_len=48, slots=2, buckets=(8,)) as eng:
                bad_h = eng.submit(_prompt(450, 4), 6)
                with pytest.raises(FaultError):
                    bad_h.result(timeout=180)
                np.testing.assert_array_equal(
                    eng.submit(good, 6).result(timeout=180).tokens, oracle)
                assert eng.stats()["respawns"] == 0   # engine never died
            assert plan.unfired() == []
        assert events.deltas(c0).get("serving_prefill_failed", 0) == 1


# ------------------------------------------------------ stalls and deadlines
class TestStallDeadlineWatchdog:
    def test_stall_trips_middecode_deadline(self, lm, monkeypatch):
        """serve_stall@2 wedges the decode loop past the request's
        deadline: the request fails with RequestTimeout mid-decode (tokens
        already emitted) and its slot is recycled. The deadline (2 s) has to
        outlast admission and prefill on a loaded host, or the request dies
        "while queued"; the stall (3 s) has to outlast the deadline."""
        c0 = events.counts()
        monkeypatch.setenv("BIGDL_FAULT_STALL_S", "3.0")
        with ServingEngine(lm, max_len=48, slots=2, buckets=(8,)) as warm:
            warm.submit(_prompt(460, 4), 2).result(timeout=180)
        with inject_faults("serve_stall@2") as plan:
            with ServingEngine(lm, max_len=48, slots=2, buckets=(8,)) as eng:
                h = eng.submit(_prompt(461, 4), 20, deadline_ms=2000)
                with pytest.raises(RequestTimeout, match="mid-decode"):
                    h.result(timeout=180)
                assert eng.stats()["timeouts"] == 1
            assert plan.unfired() == []
        recent = [e for e in events.recent("serving_timeout")
                  if e.get("in_slot")]
        assert recent and recent[-1]["generated"] >= 1
        assert events.deltas(c0).get("serving_timeout", 0) == 1

    def test_stall_arms_watchdog_dump(self, lm, monkeypatch):
        """Decode-loop silence with work in flight must trip the hang
        watchdog: the stall happens between heartbeats and the dump lands
        in the sink with the serving thread's stack."""
        from bigdl_tpu.obs.watchdog import HangWatchdog
        monkeypatch.setenv("BIGDL_FAULT_STALL_S", "0.8")
        dumps = []
        wd = HangWatchdog(hard_s=0.2, poll_s=0.02, sink=dumps.append)
        with inject_faults("serve_stall@2") as plan:
            with ServingEngine(lm, max_len=48, slots=2, buckets=(8,),
                               watchdog=wd) as eng:
                r = eng.submit(_prompt(462, 4), 8).result(timeout=180)
                assert r.n_generated == 8     # a stall delays, not corrupts
            assert plan.unfired() == []
        assert wd.dumps >= 1
        assert dumps and "bigdl-serve" in dumps[0]

    def test_wedged_shutdown_raises_timeout_not_leak(self, lm, monkeypatch):
        """shutdown(wait) on a wedged loop: the failed join raises
        EngineShutdownTimeout with the stack dump instead of silently
        returning with the thread alive."""
        c0 = events.counts()
        monkeypatch.setenv("BIGDL_FAULT_STALL_S", "2.0")
        with inject_faults("serve_stall@1") as plan:
            eng = ServingEngine(lm, max_len=48, slots=2, buckets=(8,))
            h = eng.submit(_prompt(463, 4), 8)
            _wait_active(eng, 1)
            time.sleep(0.1)          # let the loop enter the stalled tick
            with pytest.raises(EngineShutdownTimeout, match="alive"):
                eng.shutdown(wait=True, timeout=0.2)
            assert events.deltas(c0).get("serving_shutdown_timeout", 0) == 1
            # once the stall passes, the loop honours the stop flag and the
            # supervisor resolves every future — the thread was slow, not lost
            eng.shutdown(wait=True, timeout=30)
            with pytest.raises(EngineShutdown):
                h.result(timeout=5)
            assert plan.unfired() == []
        assert not any(t.name.startswith("bigdl-serve") and t.is_alive()
                       for t in threading.enumerate())


# ------------------------------------------------------ page-pool pressure
class TestPageAllocFaults:
    """``serve_page_alloc`` (utils/faults.py): an injected allocator
    exhaustion must surface as graceful backpressure — the request waits
    and then completes bitwise — never as a crash or a lost future."""

    def test_alloc_fault_at_admission_backpressures_then_serves(self, lm):
        """The FIRST page allocation reports exhaustion: admission returns
        the request to the head of the queue, the next loop pass allocates
        for real, and the tokens match the oracle exactly."""
        c0 = events.counts()
        p = _prompt(470, 9)
        with inject_faults("serve_page_alloc@1") as plan:
            with ServingEngine(lm, max_len=48, slots=2, buckets=(16,),
                               pages=6, page_tokens=16) as eng:
                r = eng.submit(p, 6).result(timeout=180)
                st = eng.stats()
            assert plan.unfired() == []
        assert np.array_equal(
            np.asarray(r.tokens[9:]), _oracle(lm, p, 6)[9:])
        assert st["pages_used"] == 0          # drained clean afterwards
        d = events.deltas(c0)
        assert d.get("serving_page_alloc_fault", 0) == 1
        assert d.get("serving_page_backpressure", 0) >= 1

    def test_alloc_fault_midflight_preempts_not_crashes(self, lm):
        """Exhaustion during decode-time page growth fires the preemption
        path (youngest requeued, re-prefilled bitwise) instead of killing
        the engine thread — respawns stays 0 and both requests finish with
        oracle tokens."""
        p1, p2 = _prompt(471, 17), _prompt(472, 17)
        with inject_faults("serve_page_alloc@3") as plan:
            with ServingEngine(lm, max_len=48, slots=2, buckets=(8, 32),
                               pages=8, page_tokens=16) as eng:
                h1 = eng.submit(p1, 17)
                h2 = eng.submit(p2, 17)
                r1, r2 = h1.result(timeout=180), h2.result(timeout=180)
                st = eng.stats()
            assert plan.unfired() == []
        assert st["respawns"] == 0
        assert np.array_equal(
            np.asarray(r1.tokens[17:]), _oracle(lm, p1, 17)[17:])
        assert np.array_equal(
            np.asarray(r2.tokens[17:]), _oracle(lm, p2, 17)[17:])
        assert st["pages_used"] == 0
