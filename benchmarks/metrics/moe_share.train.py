"""Share of the device's busy time under the routed expert layer's scope
`bigdl_moe` (router, sort, gather, grouped products, weighted sum back),
forward, recomputed and backward."""

import scope_seconds
import scoped_trace


def read(run):
    under = scope_seconds.seconds(run, "bigdl_moe")
    if not under:       # no profile to join, or a program without the scope
        return None
    return 100.0 * under / scoped_trace.load(run).busy_s
