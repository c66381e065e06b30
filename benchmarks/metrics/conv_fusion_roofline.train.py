"""Share of the roofline that XLA's convolution fusions reach: the least time
for the FLOPs and bytes the configuration's convolutions require in the steps
of the traced window (`work/<config>.convolutions.py`, from the shapes; never
XLA's own counts), over the device time of the operations whose
`hlo_category` is a convolution (plain or the root of a fusion, which then
holds whatever XLA fused around it: statistics, activation)."""

import os

import harness
import scoped_trace


def read(run):
    scoped = scoped_trace.load(run)
    if scoped is None:
        return None
    work = harness.load_module(os.path.join(
        harness.HERE, "work", run.config["name"] + ".convolutions.py"))
    least = sum(max(f / run.peak["flops_per_s"], b / run.peak["hbm_bytes_per_s"])
                for f, b in work.convolution_step(run.config, run.traffic))
    seconds = sum(s for c, s in scoped.by_category().items()
                  if c.startswith("convolution"))
    if not seconds:
        raise scoped_trace.TraceError("no device operation is a convolution")
    return 100.0 * least * scoped_trace.steps(run) / seconds
