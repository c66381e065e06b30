"""Device busy time a step: the union of the device's operation intervals in
the traced window, by the steps the device ran in it."""

import trace_reduce


def read(run):
    steps = trace_reduce.steps_per_second(run) * run.trace.window_s
    return run.trace.busy_s * 1e3 / steps
