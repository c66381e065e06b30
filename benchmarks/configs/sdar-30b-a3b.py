"""`sdar-30b-a3b` as the program runs it, and as the benchmark names its weights.

`build` is the only function here that touches the program. `names` lists the
weights in the order the program's parameter tree holds them (paths sorted):
the layers' weights are stacked, the depth in front, as the decoder scans
them. `make_batches` makes block diffusion's corruption on the host from the
seed, so that the timed step is deterministic and the reference follows the
same batches.
"""

import jax
import jax.numpy as jnp
import numpy as np


def build(cfg, traffic):
    from bigdl_tpu.models.transformerlm import ConfigDecoder, WeightedTokenCriterion
    if traffic["block_length"] != cfg["block_length"]:
        raise ValueError("the traffic's block_length is not the configuration's")
    model = ConfigDecoder.from_config(
        cfg, num_experts=cfg["router_experts"], held=tuple(cfg["held"]),
        block_diffusion=(traffic["seq_len"], cfg["block_length"]))
    # the slice's 18,992 rows in 4 chunks of 4,748: no padded row
    return model, WeightedTokenCriterion(chunk_size=cfg["vocab_size"] // 4)


def names(cfg):
    n, d, v = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    hd, heads, kv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h, held = cfg["moe_intermediate_size"], cfg["held"][1]
    return [("embed", (v, d)), ("final_norm.g", (d,)), ("head", (v, d)),
            ("layers.attn.k_norm.g", (n, hd)),
            ("layers.attn.kv", (n, 2 * kv * hd, d)),
            ("layers.attn.out", (n, d, heads * hd)),
            ("layers.attn.q_norm.g", (n, hd)),
            ("layers.attn.q", (n, heads * hd, d)),
            ("layers.attn_norm.g", (n, d)),
            ("layers.router", (n, d, cfg["router_experts"])),
            ("layers.experts.in", (n, held, d, 2 * h)),
            ("layers.experts.out", (n, held, h, d)),
            ("layers.moe_norm.g", (n, d))]


def make_weights(cfg, key):
    """Every weight from `key`, on the device, in one compiled call."""
    spec = names(cfg)

    def make(key):
        return {name: jnp.ones(shape, jnp.float32) if name.endswith(".g") else
                cfg["initializer_range"] * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                for i, (name, shape) in enumerate(spec)}

    return jax.jit(make)(key)


def make_batches(cfg, traffic, rng):
    """`n_batches` of (x, y). `x` (batch, 2L) int32 is `[x_t ; x_0]`: the clean
    sequence `x_0` of L tokens behind its noised copy, in which each token is
    `[MASK]` with probability `t`, `t` drawn per sequence. `y` (batch, 2, L)
    float32 packs what the criterion reads: `y[:, 0]` the clean token at the
    masked positions and -1 (ignored) elsewhere, `y[:, 1]` the weight `1/t` at
    the masked positions and 0 elsewhere."""
    b, length = traffic["batch"], traffic["seq_len"]
    t_min, t_max = traffic["noise_t"]
    mask_id = cfg["mask_token_id"]
    out = []
    for _ in range(traffic["n_batches"]):
        x0 = rng.integers(0, mask_id, size=(b, length), dtype=np.int32)
        t = rng.uniform(t_min, t_max, size=(b, 1))
        masked = rng.random((b, length)) < t
        xt = np.where(masked, np.int32(mask_id), x0)
        y = np.stack([np.where(masked, x0, -1).astype(np.float32),
                      np.where(masked, 1.0 / t, 0.0).astype(np.float32)], axis=1)
        out.append((np.concatenate([xt, x0], axis=1).astype(np.int32), y))
    return out
