"""The reducer on a trace recorded on a v5e chip: the tiny LM cell of
`conftest.TINY`, a few milliseconds of it (`data/tiny_lm.xplane.pb`, my chip
run, PR 25)."""

import os

import pytest

import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "tiny_lm.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.Trace(TRACE, chips=1)


def test_busy_and_idle(trace):
    assert 0 < trace.busy_s < trace.window_s
    # a model this small leaves the chip idle most of the time
    assert 0.9 < 1 - trace.busy_s / trace.window_s < 1
    gaps = trace.idle_gaps()
    assert gaps and gaps[0][1] >= gaps[-1][1] > 0
    assert sum(s for _, s in gaps) <= trace.window_s - trace.busy_s + 1e-9


def test_a_named_kernels_time(trace):
    flash, n = trace.seconds_matching([r"^bigdl_flash_"])
    fwd, n_fwd = trace.seconds_matching([r"^bigdl_flash_fwd"])
    assert 0 < fwd < flash < trace.busy_s and 0 < n_fwd < n
    # an operand named after a kernel is not the kernel: only the operation's
    # own name counts
    assert trace_reduce.short_name(
        "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %bigdl_layer_norm.2)") == "fusion.3"
    ops = trace.op_seconds()
    assert abs(sum(v for k, v in ops.items() if k.startswith("bigdl_flash_")) - flash) < 1e-12
    assert trace.top_ops(3)[0][1] >= trace.top_ops(3)[2][1]


def test_a_pattern_that_matches_nothing_fails(trace):
    with pytest.raises(trace_reduce.TraceError):
        trace.seconds_matching([r"^no_such_kernel"])
    with pytest.raises(trace_reduce.TraceError):
        trace.program_rate([r"^jit_no_such_program"])


def test_step_programs_are_counted(trace):
    rate = trace.program_rate([r"^jit_step"])
    assert rate > 0
    # the steps in the window cannot take longer than the window
    assert rate * trace.window_s >= 1
