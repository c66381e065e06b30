"""Compile requests in set-up that the persistent cache did not answer
(`compile/cache_misses`, counted as jax writes the entry). 0 in a warm run: a
program that misses in every run recompiles in every run."""

import compile_phases


def read(run):
    found = compile_phases.totals()
    return None if found is None else found["cache_misses"]
