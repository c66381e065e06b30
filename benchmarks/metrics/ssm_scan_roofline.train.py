"""Share of the roofline that the chunked state space scan reaches: the least
time for each Mamba layer's scan FLOPs and bytes, forward and backward
(`work/<config>.py`, `ssm_scan_step`), over the device time under the scope
`bigdl_ssm_scan`. The scan is XLA's own fusions, so the scope's time is read
(`scope_seconds.py`), not operation names; a recomputed forward is inside that
time and not in the required work."""

import scope_seconds
import scoped_trace


def read(run):
    if not hasattr(run.work, "ssm_scan_step"):
        return None
    under = scope_seconds.seconds(run, "bigdl_ssm_scan")
    if not under:       # no profile to join, or a program without the scope
        return None
    least = sum(max(f / run.peak["flops_per_s"], b / run.peak["hbm_bytes_per_s"])
                for f, b in run.work.ssm_scan_step(run.config, run.traffic))
    return 100.0 * least * scoped_trace.steps(run) / under
