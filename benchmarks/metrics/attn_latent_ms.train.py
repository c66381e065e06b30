"""Device milliseconds a step under `bigdl_attn_latent`: latent attention of
every layer, whole (the projections, the latent's RMSNorm, the up-projection,
YaRN, the flash kernels, the output projection), forward, recomputed and
backward."""

import scope_seconds
import scoped_trace


def read(run):
    under = scope_seconds.seconds(run, "bigdl_attn_latent")
    if not under:       # no profile to join, or a program without the scope
        return None
    return 1e3 * under / scoped_trace.steps(run)
