"""The span tracer on the Unix clock and the step's device scopes (PR 26):
a finished span's record (Unix start, parent, args), the ring that keeps the
newest, `spans_between`, the window sequence number that joins the producer's
`feed/h2d` to the dispatch that consumed it, the `bigdl_*` named scopes in
the lowered step and window programs, and the disabled path.
"""

import collections
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import Engine, nn
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.dataset.sample import MiniBatch
from bigdl_tpu.obs import trace
from bigdl_tpu.obs.registry import registry as obs_registry
from bigdl_tpu.optim import DistriOptimizer, LocalOptimizer, SGD, Trigger

pytestmark = pytest.mark.obs

SCOPES = (trace.SCOPE_CAST, trace.SCOPE_LOSS, trace.SCOPE_GRAD_SCALE,
          trace.SCOPE_UPDATE)


def _batches(n=8, batch=8, dim=6, classes=3):
    rng = np.random.default_rng(0)
    return [MiniBatch(rng.normal(size=(batch, dim)).astype(np.float32),
                      rng.integers(0, classes, size=(batch,)).astype(np.int32))
            for _ in range(n)]


def _optimizer(cls=LocalOptimizer, fuse=1, n_iter=8, n_batches=8):
    Engine.reset()
    Engine.init(seed=5)
    model = nn.Sequential().add(nn.Linear(6, 3)).add(nn.LogSoftMax())
    return (cls(model, DataSet.array(_batches(n_batches)), nn.ClassNLLCriterion())
            .set_optim_method(SGD(learningrate=0.1, momentum=0.9))
            .set_fuse_steps(fuse)
            .set_end_when(Trigger.max_iteration(n_iter)))


# ------------------------------------------------------------ span records
def test_nested_spans_on_two_threads_record_unix_start_and_parent():
    trace.configure(enabled=True)
    before = time.time_ns()

    def work(tag):
        with trace.span("outer", {"tag": tag}):
            with trace.span("inner"):
                time.sleep(0.002)

    other = threading.Thread(target=work, args=("other",), name="other-thread")
    other.start()
    work("main")
    other.join()
    after = time.time_ns()
    spans = trace.spans_between(before, after)
    assert [s.name for s in spans].count("outer") == 2
    by_thread = collections.defaultdict(dict)
    for s in spans:
        by_thread[s.thread][s.name] = s
    assert set(by_thread) == {threading.current_thread().name, "other-thread"}
    for thread, got in by_thread.items():
        outer, inner = got["outer"], got["inner"]
        assert outer.parent is None and inner.parent == "outer"
        assert outer.tid == inner.tid and outer.thread == thread
        # on the Unix clock, and the child inside its parent
        assert before <= outer.start_unix_ns <= inner.start_unix_ns
        assert inner.start_unix_ns + inner.dur_ns \
            <= outer.start_unix_ns + outer.dur_ns <= after
        assert inner.dur_ns >= 2_000_000
    assert by_thread["other-thread"]["outer"].args == {"tag": "other"}


def test_the_ring_keeps_the_newest_and_counts_the_dropped(monkeypatch):
    monkeypatch.setattr(trace, "_finished", collections.deque(maxlen=4))
    trace.configure(enabled=True)
    for i in range(10):
        with trace.span("s", {"i": i}):
            pass
    kept = trace.spans_between()
    assert [s.args["i"] for s in kept] == [6, 7, 8, 9]
    assert trace._dropped == 6
    # the totals count every span, kept or not
    assert trace.span_totals()["s"]["count"] == 10


def test_spans_between_bounds():
    trace.configure(enabled=True)
    with trace.span("a"):
        time.sleep(0.001)
    time.sleep(0.001)
    with trace.span("b"):
        time.sleep(0.001)
    a, b = trace.spans_between()
    assert (a.name, b.name) == ("a", "b")
    a_end = a.start_unix_ns + a.dur_ns
    # a span that only touches the interval counts; one outside does not
    assert [s.name for s in trace.spans_between(0, a_end)] == ["a"]
    assert [s.name for s in trace.spans_between(a_end + 1, b.start_unix_ns - 1)] == []
    assert [s.name for s in trace.spans_between(a_end + 1)] == ["b"]
    mid_b = b.start_unix_ns + b.dur_ns // 2
    assert [s.name for s in trace.spans_between(a.start_unix_ns + 1, mid_b)] == ["a", "b"]


def test_chrome_export_counts_from_a_stated_unix_zero(tmp_path):
    trace.configure(enabled=True, trace_dir=str(tmp_path))
    with trace.span("a"):
        pass
    (rec,) = trace.spans_between()
    doc = json.load(open(trace.export_chrome()))
    (ev,) = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    zero = doc["otherData"]["ts_zero_unix_ns"]
    assert zero + round(ev["ts"] * 1e3) == rec.start_unix_ns
    assert round(ev["dur"] * 1e3) == rec.dur_ns


def test_a_timer_and_its_span_share_one_interval():
    trace.configure(enabled=True)
    opt = _optimizer()
    with opt.metrics.timer("feed", trace.span("train/feed_wait")) as waited:
        time.sleep(0.002)
    (rec,) = trace.spans_between()
    assert rec.dur_ns / 1e9 == waited.seconds == opt.metrics.totals()["feed"]


# --------------------------------------------------- the window's sequence
@pytest.mark.parametrize("fuse", [1, 2])
def test_seq_joins_a_copy_to_the_dispatch_that_consumed_it(fuse, tmp_path):
    trace.configure(enabled=True, trace_dir=str(tmp_path))
    bytes0 = obs_registry.snapshot()["counters"].get("feed/h2d_bytes", 0)
    opt = _optimizer(fuse=fuse, n_iter=8)
    opt.optimize()
    spans = trace.spans_between()
    copies = {s.args["seq"]: s for s in spans if s.name == "feed/h2d"}
    dispatches = [s for s in spans if s.name in ("train/step", "train/window")]
    assert copies and dispatches
    loop_threads = {s.tid for s in dispatches}
    assert len(loop_threads) == 1
    for d in dispatches:
        assert d.args["it"] >= 1
        # every dispatch was handed a window whose copy the producer had
        # started before it, off the loop's thread (8 distinct batches: no
        # cache hit in epoch 1)
        c = copies[d.args["seq"]]
        assert c.tid not in loop_threads
        assert c.start_unix_ns <= d.start_unix_ns
    its = sorted(d.args["it"] for d in dispatches)
    assert its[0] == 1 and len(set(its)) == len(its)
    if fuse > 1:
        stacks = {s.args["seq"] for s in spans if s.name == "feed/stack_window"}
        windows = [d for d in dispatches if d.name == "train/window"]
        assert windows and {d.args["seq"] for d in windows} <= stacks
        for w in windows:
            assert w.args["k"] == fuse
    # what was handed to device_put was counted: 8 batches of float32
    # features and int32 labels
    moved = obs_registry.snapshot()["counters"]["feed/h2d_bytes"] - bytes0
    assert moved == 8 * (8 * 6 * 4 + 8 * 4)


def test_the_producer_never_waits_for_its_copy(monkeypatch):
    """Spans off: no wait, no span. Spans on: the producer still does not
    wait; a watcher thread closes `feed/h2d` when the copy has landed."""
    trace.configure(enabled=False)
    made0 = trace._SPANS_CREATED
    waited_in = []
    real_wait = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready", lambda x: (
        waited_in.append(threading.current_thread().name), real_wait(x))[1])
    opt = _optimizer(fuse=2)
    placed = opt._put_window(_batches(2))
    assert jax.tree_util.tree_leaves(placed)[0].shape == (2, 8, 6)
    assert not waited_in and trace._SPANS_CREATED == made0
    assert trace.spans_between() == [] and opt._copy_watch is None
    trace.configure(enabled=True)
    t0 = time.time_ns()
    opt._put_window(_batches(2))
    opt._stop_copy_watcher()
    assert waited_in == ["bigdl-h2d-watch"]
    stack, copy = trace.spans_between()
    assert (stack.name, copy.name) == ("feed/stack_window", "feed/h2d")
    assert stack.thread == threading.current_thread().name
    assert copy.thread == "bigdl-h2d-watch" and copy.args == stack.args
    # the copy's span starts where the stack's ended, on the producer's clock
    assert t0 <= stack.start_unix_ns + stack.dur_ns <= copy.start_unix_ns
    # the phase the run report calls h2d is the enqueue alone
    assert opt.metrics.totals()["put_batch"] < copy.dur_ns / 1e9 + 1e-3


# ------------------------------------------------------- device scope names
@pytest.mark.parametrize("cls", [LocalOptimizer, DistriOptimizer])
@pytest.mark.parametrize("program", ["step", "window"])
def test_lowered_programs_carry_the_phase_scopes(cls, program):
    opt = _optimizer(cls, fuse=2)
    Engine.set_compute_dtype(jnp.bfloat16)      # the casts exist
    opt.set_gradient_clipping_by_l2_norm(1.0)   # the gradient phase has work
    params, mstate = opt.model.get_params(), opt.model.get_state()
    ostate = opt._effective_method().init_state_trimmed(
        params, opt._trainable_mask())
    x, t = _batches(1)[0].input, _batches(1)[0].target
    fn = opt._compile_step()
    if program == "window":
        fn = opt._compile_window(2)
        x, t = np.stack([x, x]), np.stack([t, t])
    text = fn.lower(params, mstate, ostate, jnp.asarray(0, jnp.int32), x, t,
                    jax.random.PRNGKey(0)).as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in text, f"{scope} is not in the lowered {program}"
    # backward operations carry the scope inside the transform's wrapper
    assert f"transpose(jvp({trace.SCOPE_LOSS}))" in text
