"""Share of the device's busy time that the optimizer's update takes: device
seconds of the operations under the step's `bigdl_update` scope
(`method.update_trimmed`, `method.sparse_apply`) over all busy seconds."""

import scoped_trace


def read(run):
    scoped = scoped_trace.load(run)
    if scoped is None:
        return None
    return 100.0 * scoped.by_scope()["bigdl_update"] / scoped.busy_s
