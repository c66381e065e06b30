"""Seconds of lowering jaxprs to MLIR in set-up, where Pallas kernels lower to
Mosaic: jax's outermost lowering events (`compile/lower`)."""

import compile_phases


def read(run):
    found = compile_phases.totals()
    return None if found is None else found["lower"]
