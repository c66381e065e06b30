"""Device milliseconds a step under `bigdl_moe_route` and `bigdl_moe_combine`:
the router, top-k, the sort of the pairs, the gather of their rows and the
weighted sum back, forward, recomputed and backward. The part of the expert
layer that memory and latency bound."""

import scope_seconds
import scoped_trace


def read(run):
    under = scope_seconds.seconds(run, "bigdl_moe_route", "bigdl_moe_combine")
    if not under:
        return None
    return 1e3 * under / scoped_trace.steps(run)
