"""Device milliseconds a step under `bigdl_mamba`: Mamba-2's mixers whole
(input projection, convolution, chunked scan, gated norm, output projection),
forward, recomputed and backward."""

import scope_seconds
import scoped_trace


def read(run):
    under = scope_seconds.seconds(run, "bigdl_mamba")
    if not under:       # no profile to join, or a program without the scope
        return None
    return 1e3 * under / scoped_trace.steps(run)
