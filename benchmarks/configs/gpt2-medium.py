"""`gpt2-medium` as the program runs it, and as the benchmark names its weights.

`build` is the only function here that touches the program. `names` lists the
weights in the order the program's parameter tree holds them (paths sorted
with numbers as numbers), so `harness.tree_from_names` can hand the
benchmark's weights to the model and read the model's back.
"""

import jax
import jax.numpy as jnp
import numpy as np


def build(cfg, traffic):
    from bigdl_tpu.models.transformerlm import TransformerLM, lm_criterion
    model = TransformerLM(cfg["vocab_size"], cfg["n_embd"], cfg["n_head"],
                          cfg["n_layer"], max_len=cfg["n_positions"])
    return model, lm_criterion()


def names(cfg):
    d, v, t = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    out = [("wte", (v, d)), ("wpe", (t, d))]
    for i in range(cfg["n_layer"]):
        out += [(f"h{i}.ln1.b", (d,)), (f"h{i}.ln1.g", (d,)),
                (f"h{i}.attn.out.b", (d,)), (f"h{i}.attn.out.w", (d, d)),
                (f"h{i}.attn.qkv.b", (3 * d,)), (f"h{i}.attn.qkv.w", (3 * d, d)),
                (f"h{i}.ln2.b", (d,)), (f"h{i}.ln2.g", (d,)),
                (f"h{i}.mlp.fc.b", (4 * d,)), (f"h{i}.mlp.fc.w", (4 * d, d)),
                (f"h{i}.mlp.proj.b", (d,)), (f"h{i}.mlp.proj.w", (d, 4 * d))]
    return out + [("lnf.b", (d,)), ("lnf.g", (d,)),
                  ("head.b", (v,)), ("head.w", (v, d))]


def make_weights(cfg, key):
    """Every weight from `key`, on the device, in one compiled call."""
    spec = names(cfg)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(spec):
            if name.endswith(".g"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith(".b"):
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                out[name] = cfg["initializer_range"] * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return jax.jit(make)(key)


def make_batches(cfg, traffic, rng):
    """`n_batches` of (tokens, next tokens), every row different."""
    b, t = traffic["batch"], traffic["seq_len"]
    out = []
    for _ in range(traffic["n_batches"]):
        tok = rng.integers(0, cfg["vocab_size"], size=(b, t + 1), dtype=np.int32)
        out.append((np.ascontiguousarray(tok[:, :-1]),
                    np.ascontiguousarray(tok[:, 1:])))
    return out
