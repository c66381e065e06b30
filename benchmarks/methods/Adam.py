"""The optimizer method `Adam` for the check: the plain update that the
reference makes, and where the program's state holds the first gradient
(see `SGD.py` for the names)."""

import functools

import jax
import jax.numpy as jnp

SLOT = "m"


def gradient_scale(args):
    return 1.0 - args.get("beta1", 0.9)


def make(args):
    lr = args["learningrate"]
    b1, b2 = args.get("beta1", 0.9), args.get("beta2", 0.999)
    eps = args.get("epsilon", 1e-8)

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def step(p, g, s, t):
        m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, s["m"], g)
        v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, s["v"], g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps), p, m, v)
        return p, {"m": m, "v": v}
    zeros = lambda p: jax.tree_util.tree_map(jnp.zeros_like, p)
    return step, lambda p: {"m": zeros(p), "v": zeros(p)}
