"""`smallthinker-21b-a3b` as the program runs it, and as the benchmark names
its weights.

`build` is the only function here that touches the program: it hands the
configuration's own keys to `ConfigDecoder.from_config` (which knows their
spelling) and adds what `config.json` has no key for: the router's width
apart from the experts held, the router before attention, the ReLU gate, no
per-head norm. `names` lists the weights in the order the program's
parameter tree holds them (paths sorted): the layers' weights are stacked,
the depth in front, whatever a layer's kind, as the decoder scans them.
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
        cfg, num_experts=cfg["router_experts"], held=tuple(cfg["held"]),
        qk_norm=False, router_input="layer", expert_gate="relu")
    # the slice's 18,992 rows in 4 chunks of 4,748: no padded row
    return model, ChunkedSoftmaxCrossEntropy(chunk_size=cfg["vocab_size"] // 4)


def names(cfg):
    n, d, v = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    hd, heads, kv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h, held = cfg["moe_ffn_hidden_size"], cfg["held"][1]
    return [("embed", (v, d)), ("final_norm.g", (d,)), ("head", (v, d)),
            ("layers.attn.kv", (n, 2 * kv * hd, d)),
            ("layers.attn.out", (n, d, heads * hd)),
            ("layers.attn.q", (n, heads * hd, d)),
            ("layers.attn_norm.g", (n, d)),
            ("layers.router", (n, d, cfg["router_experts"])),
            ("layers.experts.in", (n, held, d, 2 * h)),
            ("layers.experts.out", (n, held, h, d)),
            ("layers.moe_norm.g", (n, d))]


# the input embedding's range, where every other matrix takes
# `initializer_range` (`assumed.init` and `departures` in the configuration's
# file; PERF.md section 6, PR 36, has the readings)
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
