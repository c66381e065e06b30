"""Device milliseconds a step under `bigdl_moe_latent`: the expert layer's
projections into the routed experts' latent space and back out of it,
forward, recomputed and backward. The whole expert layer is
`moe_share.train`'s; `None` on a program whose expert layer has no latent
projections."""

import scope_seconds
import scoped_trace


def read(run):
    under = scope_seconds.seconds(run, "bigdl_moe_latent")
    if not under:       # no profile to join, or no latent projections
        return None
    return 1e3 * under / scoped_trace.steps(run)
