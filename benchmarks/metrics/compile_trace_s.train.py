"""Seconds of jaxpr tracing in set-up: jax's outermost trace events
(`compile/trace`), nested traces counted once."""

import compile_phases


def read(run):
    found = compile_phases.totals()
    return None if found is None else found["trace"]
