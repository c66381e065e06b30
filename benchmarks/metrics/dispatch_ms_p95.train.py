"""95th percentile of the time one dispatch call takes on the host: the
durations of the program's `train/step` or `train/window` spans in the traced
window. They time the call that hands a step (or a fused window of steps) to
the device, not the step."""

import numpy as np

import scoped_trace


def read(run):
    scoped = scoped_trace.load(run)
    if scoped is None:
        return None
    calls = [(s.end - s.start) / 1e9 for s in scoped.dispatches()]
    if not calls:
        raise scoped_trace.TraceError("no train/step or train/window span in the trace")
    return float(np.percentile(calls, 95))
