"""Plain reference for `deepseek-v2-lite`: DeepSeek-V2-Lite's decoder
(`config.json` of deepseek-ai/DeepSeek-V2-Lite, `model_type` `deepseek_v2`)
trained on next-token prediction, in float32 `jax.numpy`. Written from the
equations below and the published formulas; imports nothing of the program.

The equations. Layer `l`, input `h` (positions x 2048), positions
`pos = 0 .. T-1`:

- Attention, every layer (latent attention, no query low-rank path):
  `x = RMSNorm(h)`; `q = x Wq` as 16 heads of 192, split into `q_nope` (128)
  and `q_pe` (64); `[c ; k_pe] = x Wkv_a` with `c` 512 wide and `k_pe` 64
  (one head, shared by all 16); `c = RMSNorm_kv(c)` (a gain of 512);
  `[k_nope ; v] = c Wkv_b` as 16 heads of 128 + 128; `q_pe = YaRN(q_pe, pos)`,
  `k_pe = YaRN(k_pe, pos)`; `k_i = [k_nope_i ; k_pe]`;
  `o_i = softmax(s q_i k_i^T + causal) v_i` (128 wide) with
  `s = mscale^2 / sqrt(192)`, `mscale = 0.1 * mscale_all_dim * ln(factor) + 1`;
  `h = h + concat_i(o_i) Wo`. No biases.
- YaRN, as `DeepseekV2YarnRotaryEmbedding` computes it: over the 32
  frequencies `i` of 64 channels `f_extra = theta^(-2i/64)`,
  `f_inter = f_extra / factor`; the correction range `low = floor(r(beta_fast))`,
  `high = ceil(r(beta_slow))` with `r(b) = 64 ln(4096 / (b 2 pi)) / (2 ln theta)`
  (10 and 23); `ramp_i = clip((i - low) / (high - low), 0, 1)`;
  `inv_freq = f_inter ramp + f_extra (1 - ramp)`; cos and sin scaled by
  `mscale(factor, mscale) / mscale(factor, mscale_all_dim)` (1 here). The
  rope channels are read as interleaved pairs `(2j, 2j+1)`, put into halves
  (the evens, then the odds) and turned by halves, as the published
  `apply_rotary_pos_emb` does.
- Feed-forward. Layer 0 (`first_k_dense_replace` 1) is dense:
  `h = h + FFN_10944(RMSNorm(h))`, `FFN_w(x) = (SiLU(x Wg) * (x Wu)) Wd`.
  The routed layers: `m = RMSNorm(h)`; `p = softmax(m Wr)` over all 64; `S`
  the 6 largest; `w_e = p_e` (`norm_topk_prob` false, `routed_scaling_factor`
  1); `y = sum_{e in S and e held here} w_e FFN_1408,e(m) + FFN_2816,shared(m)`
  (the two shared experts as one MLP of width 2 x 1408); `h = h + y`. With all
  64 held this is the published layer; with 8 held (`held` = first, count)
  `y` is this chip's part with the shared experts counted once, and that
  partial result goes on to the next layer. Nothing stands in for the absent
  chips.
- After the last layer `RMSNorm`, an untied head, and the mean over all
  positions of `-log softmax(head(h_i))[x_{i+1}]` over the vocabulary's slice.

What the batches hold (`configs/deepseek-v2-lite.py`): `x` (batch, T) int32
tokens, `y` (batch, T) int32 the next tokens.

Weights arrive under the names `configs/deepseek-v2-lite.py` gives them, the
dense layer's and the routed layers' each stacked with the depth in front,
projection matrices as (out, in), the feed-forward matrices as (in, out).
Departures from the published model, as the configuration states them: 5 of
the 27 layers (the dense one and 4 routed), the 8 experts `held` of 64, a
slice of 12,800 rows of the vocabulary; `*.mlp.in`, `shared.in` and
`experts.in` hold a gate's matrix beside its up matrix.

`q` rounds the operands of every matrix product (identity in the reference;
`check.fp8` in the control). To fit beside the check's own copies of the
weights (the seeded weights, the parameters, Adam's two moments: four of 2.1
GB, and a mask; 5.8 GB are left free) a layer runs one sequence at a time and
is recomputed in the backward pass; attention runs a head at a time, in
blocks of `ROWS` query rows against every key under the causal mask written
out (16 heads x 16,384 x 16,384 scores in fp32 would be 17 GB); the
feed-forward parts (the dense layer's, the experts and the shared experts)
and the head run in blocks of `FFN_ROWS` positions, the experts one at a time
over every position of a block (a dense product with the routing weight, zero
for the positions not routed to it); and the backward pass runs a layer at a
time, each layer's program handing its weights' gradient back to the host
(`make_loss_and_grad`), as the SDAR and SmallThinker references hand back the
whole gradient and for the same reason: the check keeps a step's gradient
until the next step's is made. Whole, the gradient's program needs 3.6 GB of
temporaries beside its 2.1 GB of gradient; a layer's needs 1.2 GB beside 0.5
(compiled for a described v5e).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 1024
FFN_ROWS = 2048
ATTN_KEYS = ("attn.kv_a", "attn.kv_b", "attn.kv_norm.g", "attn.out", "attn.q",
             "attn_norm.g")
DENSE_KEYS = ATTN_KEYS + ("mlp.in", "mlp.out", "mlp_norm.g")
ROUTED_KEYS = ATTN_KEYS + ("shared.in", "shared.out", "router", "experts.in",
                           "experts.out", "moe_norm.g")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_frequencies(cfg):
    """(inv_freq (dim / 2,), the cos/sin factor, the softmax scale `s`) from
    the configuration's keys, by the published formulas."""
    y, dim = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    base, factor = float(cfg["rope_theta"]), float(y["factor"])
    original = y["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    exponents = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    freq_extra = 1.0 / base ** exponents
    freq_inter = 1.0 / (factor * base ** exponents)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low if high != low else 0.001), 0.0, 1.0)
    inv_freq = freq_inter * ramp + freq_extra * (1.0 - ramp)
    cos_sin = _yarn_get_mscale(factor, y["mscale"]) / _yarn_get_mscale(
        factor, y["mscale_all_dim"])
    m = _yarn_get_mscale(factor, y["mscale_all_dim"])
    scale = m * m / math.sqrt(cfg["qk_nope_head_dim"] + dim)
    return inv_freq, cos_sin, scale, (low, high)


def _yarn(x, pos, inv_freq, cos_sin):
    """(T, 64): the interleaved pairs put into halves, then turned by
    halves with `cos` and `sin` of `pos * inv_freq` (each repeated twice)."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    freqs = pos[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], -1)
    cos, sin = jnp.cos(emb) * cos_sin, jnp.sin(emb) * cos_sin
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def _attention(qh, kh, vh, scale, q):
    """softmax(s q k^T + causal) v for one head, q and k (T, d), v (T, d_v),
    `ROWS` query rows at a time."""
    t = qh.shape[0]
    rows = min(ROWS, t)
    cols = jnp.arange(t)

    @jax.checkpoint
    def block(i):
        sees = (i * rows + jnp.arange(rows))[:, None] >= cols[None, :]
        qb = jax.lax.dynamic_slice_in_dim(qh, i * rows, rows, axis=0)
        s = jnp.where(sees, (q(qb) @ q(kh).T) * scale, -jnp.inf)
        return q(jax.nn.softmax(s, -1)) @ q(vh)

    return jax.lax.map(block, jnp.arange(t // rows)).reshape(t, -1)


def _latent_attention(h, p, cfg, q):
    """The attention half of a layer over one sequence `h` (T, hidden), the
    heads one at a time."""
    eps = cfg["rms_norm_eps"]
    heads, latent = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, hv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    t, d = h.shape
    inv_freq, cos_sin, scale, _ = yarn_frequencies(cfg)
    pos = jnp.arange(t, dtype=jnp.float32)
    x = _rms(h, p["attn_norm.g"], eps)
    kv_a = q(x) @ q(p["attn.kv_a"]).T
    c = _rms(kv_a[:, :latent], p["attn.kv_norm.g"], eps)
    k_pe = _yarn(kv_a[:, latent:], pos, inv_freq, cos_sin)             # (T, 64), every head's

    @jax.checkpoint
    def head(w):
        w_q, w_kv = w
        qh = q(x) @ q(w_q).T                                             # (T, 192)
        kv = q(c) @ q(w_kv).T                                            # (T, 128 + 128)
        qh = jnp.concatenate([qh[:, :nope], _yarn(qh[:, nope:], pos, inv_freq, cos_sin)], -1)
        kh = jnp.concatenate([kv[:, :nope], k_pe], -1)
        return _attention(qh, kh, kv[:, nope:], scale, q)

    o = jax.lax.map(head, (p["attn.q"].reshape(heads, nope + rope, d),
                           p["attn.kv_b"].reshape(heads, nope + hv, latent)))
    return h + q(o.transpose(1, 0, 2).reshape(t, heads * hv)) @ q(p["attn.out"]).T


def _ffn(m, w_in, w_out, q):
    gu = q(m) @ q(w_in)
    width = w_out.shape[0]
    return q(jax.nn.silu(gu[:, :width]) * gu[:, width:]) @ q(w_out)


def _by_rows(f, *xs):
    """`f` over blocks of `FFN_ROWS` positions of `xs` (T, ...), each block
    recomputed in the backward pass; the blocks' results put back together."""
    t = xs[0].shape[0]
    rows = min(FFN_ROWS, t)
    out = jax.lax.map(jax.checkpoint(lambda b: f(*b)),
                      tuple(x.reshape((t // rows, rows) + x.shape[1:]) for x in xs))
    return jax.tree_util.tree_map(lambda a: a.reshape((t,) + a.shape[2:]), out)


def _dense_layer(h, p, cfg, q):
    h = _latent_attention(h, p, cfg, q)
    m = _rms(h, p["mlp_norm.g"], cfg["rms_norm_eps"])
    return h + _by_rows(lambda m: _ffn(m, p["mlp.in"], p["mlp.out"], q), m)


def _routed_layer(h, p, cfg, q):
    """One routed layer over one sequence `h` (T, hidden): its output, and
    the experts (T, k) that each position reaches."""
    h = _latent_attention(h, p, cfg, q)
    m = _rms(h, p["moe_norm.g"], cfg["rms_norm_eps"])
    y, top_e = _by_rows(lambda m: _experts(m, p, cfg, q), m)
    return h + y, top_e


def _experts(m, p, cfg, q):
    """The routed part and the shared experts over positions `m` (rows,
    hidden), and the experts (rows, k) each position reaches."""
    probs = jax.nn.softmax(q(m) @ q(p["router"]), -1)
    top_p, top_e = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    top_p = top_p * cfg["routed_scaling_factor"]
    first = cfg["held"][0]

    @jax.checkpoint
    def expert(y, e_w):
        e, w_in, w_out = e_w
        w = jnp.sum(jnp.where(top_e == first + e, top_p, 0.0), -1)
        return y + w[:, None] * _ffn(m, w_in, w_out, q), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(m), (
        jnp.arange(cfg["held"][1]), p["experts.in"], p["experts.out"]))
    return y + _ffn(m, p["shared.in"], p["shared.out"], q), top_e


def _head_loss(h, head, norm, y, cfg, q):
    """The mean next-token loss of the last layer's output `h` (batch, T,
    hidden) against `y` (batch, T)."""
    def token_losses(hs, ys):
        hn = _rms(hs, norm, cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(q(hn) @ q(head).T, -1)
        return -jnp.take_along_axis(logp, ys[:, None], -1)[:, 0]

    return jnp.mean(jax.lax.map(lambda hy: _by_rows(token_losses, *hy), (h, y)))


def routing(params, x, cfg):
    """(routed layers, batch, T, k) int32: the experts that each position of
    the batch `x` reaches in each routed layer, forward only: the dense layers
    and then the routed ones, each group a scan over its stacked weights, a
    layer one sequence at a time. The program's expert layer works over a
    bound of twice the balanced expectation of the pairs that reach a held
    expert; what a layer holds of them under given weights is counted from
    this (`drift.py`), apart from the program."""
    identity = lambda a: a
    by_sequence = lambda layer: lambda h, p: jax.lax.map(
        lambda hs: layer(hs, p, cfg, identity), h)
    stacked = lambda group, keys: {k: params[group + "." + k] for k in keys}
    h, _ = jax.lax.scan(lambda h, p: (by_sequence(_dense_layer)(h, p), None),
                        params["embed"][x], stacked("dense", DENSE_KEYS))
    return jax.lax.scan(by_sequence(_routed_layer), h, stacked("layers", ROUTED_KEYS))[1]


def make_loss_and_grad(cfg, q=lambda a: a):
    """`f(params, x, y) -> (loss, grads)` over the whole batch, the gradients
    as host arrays: the mean next-token loss of `x` (batch, T) against `y`
    (batch, T) and its gradient, with the backward pass run a layer at a
    time. The forward keeps each layer's input; then the head's program, and
    each layer's, last to first, makes its weights' gradient and hands it to
    the host and the gradient of its input to the next program, so that one
    layer's gradient and working set are on the chip at a time, beside the
    layers' inputs. Each kind of layer is one compiled program."""
    def by_sequence(layer):
        one = jax.checkpoint(lambda hs, p: layer(hs, p, cfg, q))
        return lambda h, p: jax.lax.map(lambda hs: one(hs, p), h)

    layer = {"dense": by_sequence(_dense_layer),
             "layers": by_sequence(lambda *a: _routed_layer(*a)[0])}
    keys = {"dense": DENSE_KEYS, "layers": ROUTED_KEYS}
    forward = {g: jax.jit(f) for g, f in layer.items()}
    backward = {g: jax.jit(lambda h, p, d, f=f: jax.vjp(f, h, p)[1](d))
                for g, f in layer.items()}
    head = jax.jit(jax.value_and_grad(
        lambda h, w, g, y: _head_loss(h, w, g, y, cfg, q), argnums=(0, 1, 2)))
    embed = jax.jit(lambda x, d, w: jnp.zeros_like(w).at[x].add(d))

    def loss_and_grad(params, x, y):
        weights = lambda g, l: {k: params[g + "." + k][l] for k in keys[g]}
        h, inputs = params["embed"][x], []
        for g in ("dense", "layers"):
            for l in range(params[g + ".attn.q"].shape[0]):
                inputs.append((g, l, h))
                h = forward[g](h, weights(g, l))
        value, (d, d_head, d_norm) = head(h, params["head"], params["final_norm.g"], y)
        grads = {"head": jax.device_get(d_head), "final_norm.g": jax.device_get(d_norm)}
        by_layer = {}
        for g, l, h in reversed(inputs):
            d, d_p = backward[g](h, weights(g, l), d)
            for k, v in jax.device_get(d_p).items():
                by_layer.setdefault(g + "." + k, {})[l] = v
        grads["embed"] = jax.device_get(embed(x, d, params["embed"]))
        for name, per in by_layer.items():
            grads[name] = np.stack([per[l] for l in sorted(per)])
        return value, grads

    return loss_and_grad
