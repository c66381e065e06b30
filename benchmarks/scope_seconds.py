"""Device seconds of the operations under a `jax.named_scope` of the model's
own (`bigdl_moe`, `bigdl_gmm`, ...), for the per-layer metrics that read them.
`scoped_trace.py` bills every operation to one of the step's phases; a scope
inside the model is read here from the same profile: an operation belongs to
a scope that stands anywhere in its `tf_op` path, forward, recomputed or
backward (`jvp(..)`, `transpose(jvp(..))` and `checkpoint` are looked
through). Operations that only hold others (`while`) are left out."""

import re

import scoped_trace
import trace_reduce


def seconds(run, *scopes):
    """Seconds a chip (their mean) spent under any of `scopes` in the traced
    window, or None where the program's spans have no clock (no profile can
    be joined). A profile in which nothing carries the scope reads 0.0."""
    scoped = scoped_trace.load(run)
    if scoped is None:
        return None
    rx = re.compile(r"(?:^|[/(;])(?:%s)(?=[/):;]|$)" % "|".join(map(re.escape, scopes)))
    total = 0
    for ops in scoped.ops:
        total += sum(op.end - op.start for op in ops
                     if rx.search(op.tf_op) and not trace_reduce.CONTAINER.match(op.name))
    return total / 1e12 / len(scoped.ops)
