"""Share of the roofline that the flash-attention kernels reach under latent
attention, forward and backward kernels of every layer together: the least
time for each layer's causal live pairs' FLOPs (two products forward, four
backward, each at its own width: the query/key head for `q k^T`, `dq` and
`dk`, the value head for `p v`, `dv` and `dp`) and its q, k, v, o bytes at
those widths (`work/<config>.py`, `latent_attention_step`) over the kernels'
device time."""

import trace_reduce

PATTERNS = [r"^bigdl_flash_"]


def read(run):
    if not hasattr(run.work, "latent_attention_step"):
        return None
    return trace_reduce.roofline_share(
        run, run.work.latent_attention_step(run.config, run.traffic), PATTERNS)
