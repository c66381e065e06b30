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


# the input embedding's range, where every other matrix takes the source's
# `initializer_range`, and how many rows drawn from the seed the `[MASK]` row
# is chosen among (`assumed.init`, `assumed.mask_row` and `departures` in the
# configuration's file; PERF.md section 6, PR 34, has the readings)
EMBED_RANGE = 1.0
MASK_ROW_CANDIDATES = 256


def make_weights(cfg, key):
    """Every weight from `key`, on the device, in one compiled call: the gains
    1, the input embedding N(0, `EMBED_RANGE`), every other matrix N(0,
    `initializer_range`); the `[MASK]` row is the candidate drawn from `key`
    whose own routing is nearest the balanced expectation in every layer
    (`_mask_row`)."""
    spec = names(cfg)

    def make(key):
        out = {name: jnp.ones(shape, jnp.float32) if name.endswith(".g") else
               (EMBED_RANGE if name == "embed" else cfg["initializer_range"])
               * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
               for i, (name, shape) in enumerate(spec)}
        row = _mask_row(cfg, jax.random.fold_in(key, len(spec)), out["layers.router"])
        out["embed"] = out["embed"].at[cfg["mask_token_id"]].set(row)
        return out

    return jax.jit(make)(key)


def _mask_row(cfg, key, routers):
    """The `[MASK]` embedding. Every masked position carries this one row, so
    where the embedding leads the hidden state they all reach the same 8
    experts of a layer, and each of those that is held here adds a pair for
    every masked position of the batch. Of `MASK_ROW_CANDIDATES` rows drawn
    from `key`, take the first whose own top 8 (the row under RMSNorm through
    each layer's router) hold as many held experts as the balanced
    expectation says, 8 x held / experts, or the nearest to it in the worst
    layer."""
    first, count = cfg["held"]
    k = cfg["num_experts_per_tok"]
    rows = EMBED_RANGE * jax.random.normal(
        key, (MASK_ROW_CANDIDATES, cfg["hidden_size"]), jnp.float32)
    unit = rows * jax.lax.rsqrt(jnp.mean(jnp.square(rows), -1, keepdims=True)
                                + cfg["rms_norm_eps"])
    top = jax.lax.top_k(jnp.einsum("cd,lde->lce", unit, routers), k)[1]
    held = jnp.sum((top >= first) & (top < first + count), -1)       # (layers, rows)
    off = jnp.abs(held - k * count / cfg["router_experts"])
    return rows[jnp.argmin(jnp.max(off, 0) + jnp.mean(off, 0) / (k + 1))]


def noise_levels(traffic, rng):
    """(n_batches, batch) noise levels `t`: the midpoints of as many equal
    strata of U(`noise_t`) as a run has sequences, the same values on every
    seed. They are dealt to the batches back and forth, lowest first, so that
    every batch holds the same sum of `t` (for an even batch) and masks as
    many positions as any other; which batch gets which hand, and the order
    inside a batch, are drawn from `rng`."""
    b, n = traffic["batch"], traffic["n_batches"]
    t_min, t_max = traffic["noise_t"]
    t = t_min + (t_max - t_min) * (np.arange(n * b) + 0.5) / (n * b)
    hands = t.reshape(b, n)
    hands[1::2] = hands[1::2, ::-1]
    return rng.permuted(hands.T[rng.permutation(n)], axis=1)


def make_batches(cfg, traffic, rng):
    """`n_batches` of (x, y). `x` (batch, 2L) int32 is `[x_t ; x_0]`: the clean
    sequence `x_0` of L tokens behind its noised copy, in which each token is
    `[MASK]` with probability `t`, `t` per sequence (`noise_levels`). `y`
    (batch, 2, L) float32 packs what the criterion reads: `y[:, 0]` the clean
    token at the masked positions and -1 (ignored) elsewhere, `y[:, 1]` the
    weight `1/t` at the masked positions and 0 elsewhere."""
    b, length = traffic["batch"], traffic["seq_len"]
    mask_id = cfg["mask_token_id"]
    out = []
    for t in noise_levels(traffic, rng)[:, :, None]:
        x0 = rng.integers(0, mask_id, size=(b, length), dtype=np.int32)
        masked = rng.random((b, length)) < t
        xt = np.where(masked, np.int32(mask_id), x0)
        y = np.stack([np.where(masked, x0, -1).astype(np.float32),
                      np.where(masked, 1.0 / t, 0.0).astype(np.float32)], axis=1)
        out.append((np.concatenate([xt, x0], axis=1).astype(np.int32), y))
    return out
