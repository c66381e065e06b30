"""Host-to-device copy time a step: the program's `feed/h2d` span (from the
clock read before `device_put` until the placed arrays are ready; the copy
runs beside the producer's next stack) over the traced window, by the steps
dispatched in it. 0.0 where no copy happened: the batches live on the device."""

import scoped_trace

SPAN = "feed/h2d"


def read(run):
    if scoped_trace.load(run) is None or run.dispatched_steps <= 0:
        return None
    return run.spans.get(SPAN, (0, 0.0))[1] * 1e3 / run.dispatched_steps
