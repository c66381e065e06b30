"""`scoped_trace` on a profile recorded on a v5e chip with the step's scopes
in (`data/tiny_lm_scoped.xplane.pb`: the tiny LM cell of `conftest.TINY`, 12
steps of it over three epochs, the device's record alone; recorded by `record_scoped_fixture.py`,
my chip run, PR 26; the program's spans over the same extent beside it), and
on hand-made span lists. The old fixture has no scope and stays as it was."""

import json
import os

import pytest

import harness
import scoped_trace
import trace_reduce
from bigdl_tpu.obs.trace import SpanRecord

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "tiny_lm_scoped.xplane.pb")
UNSCOPED = os.path.join(DATA, "tiny_lm.xplane.pb")
MANIFEST = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
NEW = ["update_share.train", "conv_fusion_roofline.train", "h2d_ms.train",
       "dispatch_ms_p95.train", "idle_in_feed_wait_ms.train",
       "idle_epoch_end_ms.train"]

def recorded_spans(start_unix_ns):
    """The spans the program recorded with the fixture, as its records."""
    spans = json.load(open(os.path.join(DATA, "tiny_lm_scoped.spans.json")))
    return [SpanRecord(s["name"], s["tid"], "t", start_unix_ns + s["start_ps"] // 1000,
                   (s["end_ps"] - s["start_ps"]) // 1000, None, s["args"])
            for s in spans]


@pytest.fixture(scope="module")
def scoped():
    start = scoped_trace.Scoped(SCOPED, lambda a, b: []).start_unix_ns
    return scoped_trace.Scoped(
        SCOPED, lambda t0, t1: [r for r in recorded_spans(start)
                                if r.start_unix_ns <= t1
                                and r.start_unix_ns + r.dur_ns >= t0])


def hand_made(scoped, spans):
    """The fixture's operations under a hand-made list of (name, start, end)
    in picoseconds from the profile's start, all on the step loop's thread
    unless a tid is given."""
    out = object.__new__(scoped_trace.Scoped)
    out.__dict__.update(scoped.__dict__)
    out.spans = sorted((scoped_trace.Span(s[1], s[2], s[0], s[3] if len(s) > 3 else 1, {})
                        for s in spans), key=lambda s: (s.start, -s.end))
    return out


# ------------------------------------------------------------------- scopes
@pytest.mark.parametrize("tf_op, want", [
    ("jit(step)/bigdl_update/mul:", "bigdl_update"),
    ("jit(step)/jvp(bigdl_cast)/convert_element_type:", "bigdl_cast"),
    ("jit(step)/transpose(jvp(bigdl_loss))/jit(take_along_axis)/select_n:", "bigdl_loss"),
    ("jit(window)/while/body/bigdl_grad_scale/sqrt:", "bigdl_grad_scale"),
    ("jit(window)/while/body/transpose(jvp(bigdl_cast))/convert_element_type:", "bigdl_cast"),
    # two operations merged into one: the scope still counts
    ("jit(step)/jvp()/broadcast_in_dim;jit(step)/bigdl_update/sub:", "bigdl_update"),
    ("jit(step)/jvp(block1)/Sequential15/LayerNorm5/reshape:", scoped_trace.FORWARD),
    ("jit(step)/transpose(jvp(TimeDistributed46))/jit(log_softmax)/reduce_sum:",
     scoped_trace.BACKWARD),
    # a module that only resembles a phase's name is the model's
    ("jit(step)/jvp(bigdl_updater)/mul:", scoped_trace.FORWARD),
    ("", scoped_trace.NO_SCOPE),
])
def test_scope_matching_strips_the_wrappers(tf_op, want):
    assert scoped_trace.bill(tf_op) == want


def test_every_operation_is_billed_once_and_the_bills_sum_to_busy(scoped):
    bills = scoped.by_scope()
    assert set(bills) == set(scoped_trace.BILLS) and len(bills) == 7
    assert all(v >= 0 for v in bills.values())
    for scope in ("bigdl_update", "bigdl_loss", "bigdl_cast",
                  scoped_trace.FORWARD, scoped_trace.BACKWARD):
        assert bills[scope] > 0, scope
    assert sum(bills.values()) == pytest.approx(scoped.busy_s, rel=1e-12)
    # the union of the intervals, as the accepted reducer computes it (that
    # one reads times rounded to nanoseconds, and these operations are short)
    trace = trace_reduce.Trace(SCOPED)
    assert scoped.busy_s == pytest.approx(trace.busy_s, rel=0.02)
    assert sum(scoped.by_category().values()) == pytest.approx(scoped.busy_s, rel=1e-12)
    assert scoped.top_ops("bigdl_update", 3)[0][0] == "divide_subtract_fusion"


def test_fusions_that_hold_a_phase_and_are_billed_elsewhere(scoped):
    """XLA puts Adam's update of a weight matrix into the epilogue of the
    matrix product that makes its gradient: one fusion, named after the
    update (`divide_subtract_fusion`), a convolution by category, billed to
    the model's backward pass by its root's `tf_op`. The profile's HLO module
    says what it holds."""
    bills, held = scoped.by_scope(), scoped.held_elsewhere()
    assert set(held) <= set(scoped_trace.SCOPES)
    assert held["bigdl_update"] > bills["bigdl_update"] > 0
    assert held["bigdl_update"] < bills[scoped_trace.BACKWARD]
    mixed = [op for op in scoped.ops[0]
             if op.name.startswith("divide_subtract_fusion")
             and scoped_trace.bill(op.tf_op) == scoped_trace.BACKWARD]
    assert mixed and all(op.category == "convolution fusion" for op in mixed)
    assert all("bigdl_update" in scoped.fusion_bills[(op.program, op.name)]
               for op in mixed)
    # the old fixture's profile carries its module too, with no phase in it
    old = scoped_trace.Scoped(UNSCOPED, lambda a, b: [])
    assert old.fusion_bills and old.held_elsewhere() == {}


def test_a_trace_without_scopes_raises_and_names_the_cache():
    old = scoped_trace.Scoped(UNSCOPED, lambda a, b: [])
    assert old.busy_s > 0
    with pytest.raises(scoped_trace.TraceError, match="compile cache"):
        old.by_scope()


# --------------------------------------------------------------------- gaps
def test_the_recorded_spans_share_the_trace_s_clock(scoped):
    calls = scoped.dispatches()
    assert len(calls) >= 3 and all(s.name == "train/step" for s in calls)
    its = [s.args["it"] for s in calls]
    assert its == sorted(its) and len(set(its)) == len(its)
    # a step's execution starts after the call that dispatched it began, and
    # no later than 5 ms after the call returned (the first call of an
    # `optimize()` places the parameters and takes 11 ms itself)
    runs = [m for m in scoped.modules[0] if m[2].startswith("jit_step")]
    inside = [c for c in calls if scoped.t0 <= c.start and c.end <= scoped.t1]
    assert len(inside) >= 3
    for call in inside:
        later = [r for r in runs if r[0] >= call.start]
        assert later and later[0][0] - call.end < 5e9
    gaps = scoped.longest_gaps(10)
    assert len(gaps) == 10 and gaps[0][1] >= gaps[-1][1] > 0
    assert all("train/" in name or "between" in name for name, _ in gaps)


def test_a_gap_is_named_by_the_innermost_span(scoped):
    t0, t1 = scoped.t0, scoped.t1
    third = (t1 - t0) // 3
    made = hand_made(scoped, [
        ("train/epoch", t0 - 10, t1 + 10),
        ("train/feed_wait", t0 + 100, t0 + third),
        ("train/step", t0 + third + 50, t0 + 2 * third),
        ("feed/h2d", t0, t1, 2),                      # another thread: not the loop's
    ])
    assert made.name_gap(t0 + 200, t0 + 400) == "train/feed_wait"
    assert made.name_gap(t0 + third + 60, t0 + third + 80) == "train/step"
    # under the epoch alone: the epoch, and the spans on either side
    assert made.name_gap(t0 + third + 10, t0 + third + 30) == \
        "train/epoch: between train/feed_wait and train/step"
    assert made.name_gap(t0 + 2 * third + 10, t1) == \
        "train/epoch: between train/step and the end of the trace"
    # under no span at all
    bare = hand_made(scoped, [("train/step", t0 + 50, t0 + 60),
                              ("train/loss_fetch", t0 + 500, t0 + 600)])
    assert bare.name_gap(t0 + 100, t0 + 300) == "between train/step and train/loss_fetch"


def test_the_epoch_end_rule(scoped):
    gaps = sorted(scoped.gaps(), key=lambda g: g[0] - g[1])
    (a0, a1), (b0, b1) = sorted(gaps[:2])       # the two longest, in time order
    spans = [
        ("train/epoch", scoped.t0 - 10, a0 + 1),
        ("train/step", scoped.t0 - 5, scoped.t0 + 5),        # first epoch's last dispatch
        ("train/epoch", a0 + 2, scoped.t1 + 10),
        ("train/feed_wait", a0 + 3, a1 - 2),                 # the new epoch's first wait
        ("train/step", a1 - 1, a1 + 5),                      # and its first dispatch
        ("train/feed_wait", b0 - 5, b0 + (b1 - b0) // 4),    # a wait inside the epoch
        ("train/step", b1 - 1, b1 + 5),
    ]
    made = hand_made(scoped, spans)
    (bound,) = made.epoch_boundaries()
    assert bound == (scoped.t0 + 5, a1 + 5)
    at_end, in_wait = made.idle_split()
    # every gap that reaches into the boundary is the epoch's end, whole, and
    # none of it counts as feed wait although a wait lies over it
    reach = sum(g1 - g0 for g0, g1 in scoped.gaps() if g0 < bound[1] and g1 > bound[0])
    assert at_end == pytest.approx(reach / 1e12) and at_end >= (a1 - a0) / 1e12
    # of the other long gap, the quarter under the wait
    assert in_wait == pytest.approx(((b1 - b0) // 4) / 1e12, rel=0.05)
    # one epoch: no boundary, nothing at an epoch's end
    one = hand_made(scoped, [("train/epoch", scoped.t0 - 10, scoped.t1 + 10)] + spans[3:])
    assert one.epoch_boundaries() == [] and one.idle_split()[0] == 0


# ----------------------------------------------------------- the benchmark
def test_new_per_layer_entries_and_their_readers():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert set(NEW) <= set(by_name)     # later PRs append entries of their own
    for name in NEW:
        entry = by_name[name]
        assert entry["moves"] == "train_samples_per_s"
        assert hasattr(harness.load_module(
            os.path.join(harness.HERE, "metrics", name + ".py")), "read")
    assert by_name["conv_fusion_roofline.train"]["workloads"] == ["resnet50.train-stream"]
    assert by_name["update_share.train"]["layer"] == by_name["step_device_ms.train"]["layer"]
    assert by_name["h2d_ms.train"]["layer"] == by_name["feed_wait_ms.train"]["layer"]


def test_a_program_without_the_span_clock_gives_the_readers_nothing(monkeypatch):
    """The parent's program has no `spans_between`: each reader returns None
    and does not raise, so the line leaves the metric out."""
    from bigdl_tpu.obs import trace as program
    monkeypatch.delattr(program, "spans_between")
    run = type("Run", (), {"chips": 1, "dispatched_steps": 8, "spans": {}})()
    for name in NEW:
        reader = harness.load_module(os.path.join(harness.HERE, "metrics", name + ".py"))
        assert reader.read(run) is None


def test_the_convolutions_work_agrees_with_the_step_s():
    cfg = harness.load_json(os.path.join(harness.HERE, "configs", "resnet50.json"))
    traffic = harness.load_json(os.path.join(harness.HERE, "traffic", "train-stream.json"))
    step = harness.load_module(os.path.join(harness.HERE, "work", "resnet50.py"))
    convs = harness.load_module(os.path.join(harness.HERE, "work",
                                             "resnet50.convolutions.py"))
    parts = convs.convolution_step(cfg, traffic)
    assert sum(l[0] for l in convs._layers(cfg)) == step.forward_macs(cfg)
    # three passes a layer, the stem without its input's gradient
    assert len(parts) == 3 * len(convs._layers(cfg)) - 1
    flops = sum(f for f, _ in parts)
    whole = step.train_flops_per_sample(cfg, traffic) * traffic["batch"]
    assert 0.98 * whole < flops < whole
    assert all(f > 0 and b > 0 for f, b in parts)
