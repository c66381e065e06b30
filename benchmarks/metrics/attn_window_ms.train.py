"""Device milliseconds a step under `bigdl_attn_window`: the attention module
of every layer that sees a sliding window of keys, whole (projections, RoPE,
the flash kernels, the output projection), forward, recomputed and backward."""

import scope_seconds
import scoped_trace


def read(run):
    under = scope_seconds.seconds(run, "bigdl_attn_window")
    if not under:       # no profile to join, or a program without the scope
        return None
    return 1e3 * under / scoped_trace.steps(run)
