"""Share of the roofline that the held experts' grouped products reach: the
least time for their required FLOPs and bytes at the balanced expectation
(`work/<config>.py`, `grouped_matmul_step`) over the device time of the
operations under the scope `bigdl_gmm` (the kernels, and whatever their call
adds around them), forward, recomputed and backward."""

import scope_seconds
import scoped_trace


def read(run):
    if not hasattr(run.work, "grouped_matmul_step"):
        return None
    under = scope_seconds.seconds(run, "bigdl_gmm")
    if under is None:
        return None
    if not under:
        raise scoped_trace.TraceError("no device operation is under bigdl_gmm")
    least = sum(max(f / run.peak["flops_per_s"], b / run.peak["hbm_bytes_per_s"])
                for f, b in run.work.grouped_matmul_step(run.config, run.traffic))
    return 100.0 * least * scoped_trace.steps(run) / under
