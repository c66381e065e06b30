"""`nemotron-3-super-120b-a12b` as the program runs it, and as the benchmark
names its weights.

`build` is the only function here that touches the program: it hands the
configuration's own keys to `ConfigDecoder.from_config` (which knows their
spelling: one block a layer from `hybrid_override_pattern`, the Mamba-2 mixer
from the `mamba_*`, `n_groups`, `ssm_state_size`, `conv_kernel` and
`chunk_size` keys, the latent expert space from `moe_latent_size`, the shared
expert from `moe_shared_expert_intermediate_size`, the routed scale from
`routed_scaling_factor`, ReLU squared from `mlp_hidden_act`) and adds what
`config.json` has no key for: the router's width apart from the experts held,
its sigmoid scores, and no per-head norm. `names` lists the weights in the
order the program's parameter tree holds them (paths sorted): each kind of
block's weights stacked, the layers of that kind in front.
"""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np


def build(cfg, traffic):
    from bigdl_tpu.models.transformerlm import ConfigDecoder
    from bigdl_tpu.nn import ChunkedSoftmaxCrossEntropy
    if traffic["seq_len"] > cfg["max_position_embeddings"]:
        raise ValueError("the traffic's seq_len is over max_position_embeddings")
    if any(cfg[k] for k in ("attention_bias", "mamba_proj_bias", "mlp_bias", "use_bias")) \
            or not cfg["use_conv_bias"] or cfg["mamba_hidden_act"] != "silu":
        raise ValueError("the program builds the family's layers without biases "
                         "but the convolution's, and with a SiLU in the Mamba mixer")
    model = ConfigDecoder.from_config(
        cfg, num_experts=cfg["router_experts"], held=tuple(cfg["held"]), qk_norm=False,
        scoring_func="sigmoid")
    # the slice's 16,384 rows in 4 chunks of 4,096: no padded row
    return model, ChunkedSoftmaxCrossEntropy(chunk_size=cfg["vocab_size"] // 4)


def counts(cfg):
    """Layers of each kind in the layers kept: Mamba, attention, experts."""
    kept = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    return kept.count("M"), kept.count("*"), kept.count("E")


def names(cfg):
    n_m, n_a, n_e = counts(cfg)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    mh, inner = cfg["mamba_num_heads"], cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    lat, h, held = cfg["moe_latent_size"], cfg["moe_intermediate_size"], cfg["held"][1]
    s = cfg["n_shared_experts"] * cfg["moe_shared_expert_intermediate_size"]
    return [("attention.kv", (n_a, 2 * kv * hd, d)),
            ("attention.out", (n_a, d, heads * hd)),
            ("attention.q", (n_a, heads * hd, d)),
            ("attention.norm.g", (n_a, d)),
            ("embed", (v, d)),
            ("experts.latent_down", (n_e, d, lat)),
            ("experts.latent_up", (n_e, lat, d)),
            ("experts.shared.in", (n_e, d, s)),
            ("experts.shared.out", (n_e, s, d)),
            ("experts.router", (n_e, d, cfg["router_experts"])),
            ("experts.in", (n_e, held, lat, h)),
            ("experts.out", (n_e, held, h, lat)),
            ("experts.norm.g", (n_e, d)),
            ("final_norm.g", (d,)),
            ("head", (v, d)),
            ("mamba.a_log", (n_m, mh)),
            ("mamba.conv.b", (n_m, conv)),
            ("mamba.conv.w", (n_m, cfg["conv_kernel"], conv)),
            ("mamba.d", (n_m, mh)),
            ("mamba.dt_bias", (n_m, mh)),
            ("mamba.in", (n_m, d, inner + conv + mh)),
            ("mamba.gate_norm.g", (n_m, inner)),
            ("mamba.out", (n_m, inner, d)),
            ("mamba.norm.g", (n_m, d))]


# the input embedding's range, where every other matrix takes
# `initializer_range` (`assumed.init` and `departures` in the configuration's
# file)
EMBED_RANGE = 1.0


def _draw(cfg, name, key, shape):
    """One weight as the configuration's `assumed` says it is made: gains 1,
    the Mamba leaves as the family's code makes them, the input embedding N(0,
    `EMBED_RANGE`), every other matrix N(0, `initializer_range`)."""
    if name.endswith(".g") or name == "mamba.d":
        return jnp.ones(shape, jnp.float32)
    if name == "mamba.a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "mamba.dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(cfg["time_step_min"]),
                                        math.log(cfg["time_step_max"])))
        dt = jnp.maximum(dt, cfg["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))            # softplus(dt_bias) = dt
    if name.startswith("mamba.conv."):
        bound = 1.0 / math.sqrt(cfg["conv_kernel"])
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    scale = EMBED_RANGE if name == "embed" else cfg["initializer_range"]
    return scale * jax.random.normal(key, shape, jnp.float32)


def make_weights(cfg, key):
    """Every weight from `key`, on the device, in one compiled call, and then
    the routers balanced (`balance_routers`) over a sequence drawn from
    `key` too."""
    spec = names(cfg)

    def make(key):
        return {name: _draw(cfg, name, jax.random.fold_in(key, i), shape)
                for i, (name, shape) in enumerate(spec)}

    return balance_routers(cfg, jax.jit(make)(key), jax.random.fold_in(key, len(spec)))


def balance_routers(cfg, weights, key):
    """`weights` with each router's columns cleared of their part along the
    mean of what that router reads: the normed input of its layer over one
    sequence of `router_calibration_tokens` ids drawn from `key`, uniform over
    the vocabulary's slice as the traffic's are, made by the reference in
    float32 a layer at a time from the first, through the routers already
    cleared (`assumed.router_balance` in the configuration's file).

    The layers' outputs share a direction (the convolution's bias, SiLU's and
    ReLU squared's positive means, carried out by each layer's output
    projection), and a router column's part along it lifts or sinks that
    expert for every position alike. Left as drawn, the experts' loads spread
    far past the draw of the positions (the held experts got 0.76-1.34 of
    their share of a step's pairs from seed to seed), as the deployment's
    correction bias would not let them."""
    from harness import load_module
    ref = load_module(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                   "reference", cfg["name"] + ".py"))
    x = jax.random.randint(key, (1, cfg["router_calibration_tokens"]), 0, cfg["vocab_size"])
    weights, eps = dict(weights), cfg["layer_norm_epsilon"]

    @jax.jit
    def clear(router, gain, h):
        m = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), -1, keepdims=True) + eps) * gain
        u = jnp.mean(m, axis=(0, 1))
        u = u / jnp.linalg.norm(u)
        return router - jnp.outer(u, u @ router)

    with jax.default_matmul_precision("highest"):
        for kind, i, h in ref.layer_inputs(weights, x, cfg):
            if kind == "E":
                routers = weights["experts.router"]
                weights["experts.router"] = routers.at[i].set(
                    clear(routers[i], weights["experts.norm.g"][i], h))
    return weights


def make_batches(cfg, traffic, rng):
    """`n_batches` of (tokens, next tokens): one document a sequence, its ids
    uniform over the vocabulary's slice, every row different."""
    b, t = traffic["batch"], traffic["seq_len"]
    out = []
    for _ in range(traffic["n_batches"]):
        tok = rng.integers(0, cfg["vocab_size"], size=(b, t + 1), dtype=np.int32)
        out.append((np.ascontiguousarray(tok[:, :-1]),
                    np.ascontiguousarray(tok[:, 1:])))
    return out
