"""The whole step's share of the chip's peak: the FLOPs that forward and
backward require for the samples of the traced window (from the shapes, in
`work/`; recomputation never counts), over the window and the chips' peak."""

import trace_reduce


def read(run):
    samples_per_s = trace_reduce.steps_per_second(run) * run.traffic["batch"]
    flops = run.work.train_flops_per_sample(run.config, run.traffic)
    return 100.0 * flops * samples_per_s / (run.chips * run.peak["flops_per_s"])
