"""Share of the roofline that the flash-attention kernels reach where layers
under a causal window and layers under the causal mask share one decoder,
forward and both backward kernels of every layer together: the least time for
each layer's live pairs' FLOPs and its q, k, v, o bytes at the cell's shapes
(`work/<config>.py`, `mixed_attention_step`: a windowed layer is charged the
pairs inside its window alone) over the kernels' device time."""

import trace_reduce

PATTERNS = [r"^bigdl_flash_"]


def read(run):
    if not hasattr(run.work, "mixed_attention_step"):
        return None
    return trace_reduce.roofline_share(
        run, run.work.mixed_attention_step(run.config, run.traffic), PATTERNS)
