"""Share of the roofline that the flash-attention kernels reach, forward and
both backward kernels together: the least time for attention's required FLOPs
and bytes at the cell's shapes over the kernels' device time."""

import trace_reduce

PATTERNS = [r"^bigdl_flash_"]


def read(run):
    if not hasattr(run.work, "flash_attention_step"):
        return None
    return trace_reduce.roofline_share(
        run, run.work.flash_attention_step(run.config, run.traffic), PATTERNS)
