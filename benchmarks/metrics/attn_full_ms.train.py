"""Device milliseconds a step under `bigdl_attn_full`: the attention module of
every layer that sees all the keys its mask allows, whole (projections, RoPE
where the layer has it, the flash kernels, the output projection), forward,
recomputed and backward."""

import scope_seconds
import scoped_trace


def read(run):
    under = scope_seconds.seconds(run, "bigdl_attn_full")
    if not under:       # no profile to join, or a program without the scope
        return None
    return 1e3 * under / scoped_trace.steps(run)
