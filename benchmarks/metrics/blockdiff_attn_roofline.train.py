"""Share of the roofline that the flash-attention kernels reach under the
block-diffusion mask, forward and both backward kernels together: the least
time for the live pairs' FLOPs and the q, k, v, o bytes at the cell's shapes
(`work/<config>.py`, `blockdiff_attention_step`) over the kernels' device
time."""

import trace_reduce

PATTERNS = [r"^bigdl_flash_"]


def read(run):
    if not hasattr(run.work, "blockdiff_attention_step"):
        return None
    return trace_reduce.roofline_share(
        run, run.work.blockdiff_attention_step(run.config, run.traffic), PATTERNS)
