"""`deepseek-v2-lite` as the program runs it, and as the benchmark names its
weights.

`build` is the only function here that touches the program: it hands the
configuration's own keys to `ConfigDecoder.from_config` (which knows their
spelling: latent attention from `kv_lora_rank` and the head sizes, YaRN from
`rope_scaling`, the leading dense layer from `first_k_dense_replace` and
`intermediate_size`, the shared experts from `n_shared_experts`) and adds
what `config.json` has no key for: the router's width apart from the experts
held, and no per-head norm. `names` lists the weights in the order the
program's parameter tree holds them (paths sorted): the leading dense layer's
and the routed layers' weights each stacked, the depth in front.
"""

import jax
import jax.numpy as jnp
import numpy as np


def build(cfg, traffic):
    from bigdl_tpu.models.transformerlm import ConfigDecoder
    from bigdl_tpu.nn import ChunkedSoftmaxCrossEntropy
    if traffic["seq_len"] > cfg["max_position_embeddings"]:
        raise ValueError("the traffic's seq_len is over max_position_embeddings")
    model = ConfigDecoder.from_config(
        cfg, num_experts=cfg["router_experts"], held=tuple(cfg["held"]), qk_norm=False)
    # the slice's 12,800 rows in 4 chunks of 3,200: no padded row
    return model, ChunkedSoftmaxCrossEntropy(chunk_size=cfg["vocab_size"] // 4)


def names(cfg):
    k = cfg["first_k_dense_replace"]
    n, d, v = cfg["num_hidden_layers"] - k, cfg["hidden_size"], cfg["vocab_size"]
    heads, latent = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, hv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    h, held = cfg["moe_intermediate_size"], cfg["held"][1]
    s, dense = cfg["n_shared_experts"] * h, cfg["intermediate_size"]

    def attention(group, m):
        return [(group + ".attn.kv_a", (m, latent + rope, d)),
                (group + ".attn.kv_b", (m, heads * (nope + hv), latent)),
                (group + ".attn.kv_norm.g", (m, latent)),
                (group + ".attn.out", (m, d, heads * hv)),
                (group + ".attn.q", (m, heads * (nope + rope), d)),
                (group + ".attn_norm.g", (m, d))]

    return (attention("dense", k)
            + [("dense.mlp.in", (k, d, 2 * dense)), ("dense.mlp.out", (k, dense, d)),
               ("dense.mlp_norm.g", (k, d)),
               ("embed", (v, d)), ("final_norm.g", (d,)), ("head", (v, d))]
            + attention("layers", n)
            + [("layers.shared.in", (n, d, 2 * s)), ("layers.shared.out", (n, s, d)),
               ("layers.router", (n, d, cfg["router_experts"])),
               ("layers.experts.in", (n, held, d, 2 * h)),
               ("layers.experts.out", (n, held, h, d)),
               ("layers.moe_norm.g", (n, d))])


# the input embedding's range, where every other matrix takes
# `initializer_range` (`assumed.init` and `departures` in the configuration's
# file; PERF.md section 6, PR 38, has the readings)
EMBED_RANGE = 1.0


def make_weights(cfg, key):
    """Every weight from `key`, on the device, in one compiled call: the gains
    1, the input embedding N(0, `EMBED_RANGE`), every other matrix N(0,
    `initializer_range`)."""
    spec = names(cfg)

    def make(key):
        return {name: jnp.ones(shape, jnp.float32) if name.endswith(".g") else
                (EMBED_RANGE if name == "embed" else cfg["initializer_range"])
                * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
                for i, (name, shape) in enumerate(spec)}

    return jax.jit(make)(key)


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
