"""The optimizer method `SGD` for the check: the plain update that the
reference makes, and where the program's state holds the first gradient.

`SLOT` names the first-moment buffer, in the program's optimizer state and in
the reference's alike. After one step from zero it is the first gradient times
`gradient_scale(args)`.
"""

import functools

import jax
import jax.numpy as jnp

SLOT = "v"


def gradient_scale(args):
    return 1.0 - args.get("dampening", args.get("momentum", 0.0))


def make(args):
    """`step(params, grads, state, t) -> (params, state)` and `init(params)`."""
    lr, mu = args["learningrate"], args.get("momentum", 0.0)
    damp = args.get("dampening", mu)

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def step(p, g, s, t):
        v = jax.tree_util.tree_map(lambda v, g: mu * v + (1 - damp) * g, s["v"], g)
        return jax.tree_util.tree_map(lambda p, v: p - lr * v, p, v), {"v": v}
    return step, lambda p: {"v": jax.tree_util.tree_map(jnp.zeros_like, p)}
