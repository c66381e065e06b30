"""Seconds of backend compile requests in set-up, whether XLA compiled or the
persistent cache answered (`compile/backend`)."""

import compile_phases


def read(run):
    found = compile_phases.totals()
    return None if found is None else found["backend"]
