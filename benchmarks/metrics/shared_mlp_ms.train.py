"""Device milliseconds a step under `bigdl_moe_shared` and `bigdl_mlp_dense`
together: the plain gated MLPs that every token passes through, the routed
layers' shared experts and the leading dense layer's feed-forward, forward,
recomputed and backward."""

import scope_seconds
import scoped_trace


def read(run):
    under = scope_seconds.seconds(run, "bigdl_moe_shared", "bigdl_mlp_dense")
    if not under:       # no profile to join, or a program without the scopes
        return None
    return 1e3 * under / scoped_trace.steps(run)
