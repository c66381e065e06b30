"""Plain reference for `sdar-30b-a3b`: SDAR-30B-A3B-Chat's decoder (`config.json`
of JetLM/SDAR-30B-A3B-Chat, `model_type` `sdar_moe`) trained by diffusion over
blocks (BD3-LMs, arXiv:2503.09573), in float32 `jax.numpy`. Written from the
equations below and imports nothing of the program.

The equations. Layer `l`, input `h` (positions x 2048), position ids `pos`:

- `a = RMSNorm(h)`; `q = a Wq` as 32 heads of 128, `k = a Wk`, `v = a Wv` as 4
  heads of 128; `q = RoPE(RMSNorm_128(q), pos)`, `k = RoPE(RMSNorm_128(k), pos)`
  (RoPE in the rotate-half form, theta 1e6; the per-head norms have gains of
  their own); query head `i` reads key/value head `i // 8`;
  `o = softmax(q k^T / sqrt(128) + M) v`; `h = h + concat(o) Wo`.
- `m = RMSNorm(h)`; `p = softmax(m Wr)` over all 128 experts; `S` = the 8
  largest of `p`; `w_e = p_e / sum_{e' in S} p_e'`;
  `y = sum_{e in S and e held here} w_e (SiLU(m Wg_e) * (m Wu_e)) Wd_e`;
  `h = h + y`. With all 128 held this is the published layer; with 16 held
  (`held` = first, count) `y` is this chip's part, and that partial result
  goes on to the next layer. Nothing stands in for the absent chips.
- Block diffusion: a clean sequence `x0` of `L` tokens in blocks of `b`; `t`
  drawn per sequence; each token of `x0` replaced by `[MASK]` with probability
  `t`, giving `xt`. The input is `[xt ; x0]`, `2L` positions with position ids
  `[0..L-1 ; 0..L-1]`. With `blk(i) = i // b` a query may see a key iff: both
  noised and `blk(k) == blk(q)`; query noised, key clean and `blk(k) < blk(q)`;
  both clean and `blk(k) <= blk(q)`; a clean query never sees a noised key.
  Loss: over the masked positions `i` of the noised half,
  `-(1/t) log softmax(head(RMSNorm(h_i)))[x0_i]`, summed and divided by `L` x
  batch; the logits at a position predict that position's own token. The
  clean half yields no logits.

What the batches hold (`configs/sdar-30b-a3b.py`): `x` (batch, 2L) int32 is
`[xt ; x0]`; `y` (batch, 2, L) float32 packs the targets (`y[:, 0]`: `x0_i` at
the masked positions, -1 elsewhere) and the weights (`y[:, 1]`: `1/t` at the
masked positions, 0 elsewhere).

Weights arrive under the names `configs/sdar-30b-a3b.py` gives them, the
layers' stacked with the depth in front, projection matrices as (out, in).
Departures from the published model, as the configuration states them: 5 of
the 48 layers, the 16 experts `held` of 128, a slice of 18,992 rows of the
vocabulary; `layers.attn.kv` holds `Wk` above `Wv`, `layers.experts.in` an
expert's gate `Wg_e` beside its up `Wu_e` (2048 x [768 | 768]).

`q` rounds the operands of every matrix product (identity in the reference;
`check.fp8` in the control). To fit beside the check's own copies of the
weights (the seeded weights, the parameters, Adam's two moments: four of 2.2
GB, and a mask) a layer runs one sequence at a time and is recomputed in the
backward pass, attention runs in blocks of `ROWS` query rows (32 heads x 8,192
x 8,192 scores in fp32 would be 8.6 GB), the experts one at a time over every
token (a dense product with the routing weight, zero for the tokens not routed
to it), the head one sequence at a time; and the gradient is handed back on
the host: the check keeps a step's gradient until the next step's is made,
and two of them beside the rest do not fit the chip.
"""

import jax
import jax.numpy as jnp

ROWS = 256
LAYER_KEYS = ("attn.k_norm.g", "attn.kv", "attn.out", "attn.q_norm.g", "attn.q",
              "attn_norm.g", "router", "experts.in", "experts.out", "moe_norm.g")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """Rotate-half RoPE over (heads, positions, d)."""
    half = x.shape[-1] // 2
    ang = pos[:, None] * (1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _may_see(rows, cols, length, b):
    """The block-diffusion mask M for query positions `rows` and key
    positions `cols` of the 2L axis, as booleans (rows x cols)."""
    qn, kn = (rows < length)[:, None], (cols < length)[None, :]
    qb, kb = ((rows % length) // b)[:, None], ((cols % length) // b)[None, :]
    return (qn & kn & (kb == qb)) | (qn & ~kn & (kb < qb)) | (~qn & ~kn & (kb <= qb))


def _attention(qh, kh, vh, length, b, q):
    """softmax(q k^T / sqrt(d) + M) v with `qh` (kv heads, group, T, d) and
    `kh`, `vh` (kv heads, T, d), `ROWS` query rows at a time."""
    t, d = qh.shape[-2:]
    rows = min(ROWS, t)
    cols = jnp.arange(t)

    @jax.checkpoint
    def block(i):
        at = i * rows + jnp.arange(rows)
        qb = jax.lax.dynamic_slice_in_dim(qh, i * rows, rows, axis=2)
        s = jnp.einsum("gjqd,gkd->gjqk", q(qb), q(kh)) / jnp.sqrt(float(d))
        s = jnp.where(_may_see(at, cols, length, b), s, -jnp.inf)
        return jnp.einsum("gjqk,gkd->gjqd", q(jax.nn.softmax(s, -1)), q(vh))

    o = jax.lax.map(block, jnp.arange(t // rows))       # (blocks, g, j, rows, d)
    return o.transpose(1, 2, 0, 3, 4).reshape(-1, t, d)  # (heads, T, d)


def _layer(h, p, cfg, q):
    """One layer over one sequence `h` (2L, hidden): its output, and the
    experts (2L, k) that each position reaches."""
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    t = h.shape[0]
    length = t // 2
    pos = jnp.tile(jnp.arange(length, dtype=jnp.float32), 2)
    a = _rms(h, p["attn_norm.g"], eps)
    wk, wv = p["attn.kv"][:kv * hd], p["attn.kv"][kv * hd:]
    split = lambda x, n: x.reshape(t, n, hd).transpose(1, 0, 2)
    qh = split(q(a) @ q(p["attn.q"]).T, heads)
    kh, vh = split(q(a) @ q(wk).T, kv), split(q(a) @ q(wv).T, kv)
    qh = _rope(_rms(qh, p["attn.q_norm.g"], eps), pos, theta)
    kh = _rope(_rms(kh, p["attn.k_norm.g"], eps), pos, theta)
    o = _attention(qh.reshape(kv, heads // kv, t, hd), kh, vh, length,
                   cfg["block_length"], q)
    h = h + q(o.transpose(1, 0, 2).reshape(t, heads * hd)) @ q(p["attn.out"]).T

    m = _rms(h, p["moe_norm.g"], eps)
    probs = jax.nn.softmax(q(m) @ q(p["router"]), -1)
    top_p, top_e = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    first, width = cfg["held"][0], cfg["moe_intermediate_size"]

    @jax.checkpoint
    def expert(y, e_w):
        e, w_in, w_out = e_w
        w = jnp.sum(jnp.where(top_e == first + e, top_p, 0.0), -1)
        gu = q(m) @ q(w_in)
        act = jax.nn.silu(gu[:, :width]) * gu[:, width:]
        return y + w[:, None] * (q(act) @ q(w_out)), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), (
        jnp.arange(cfg["held"][1]), p["experts.in"], p["experts.out"]))
    return h + y, top_e


def loss(params, x, y, cfg, q=lambda a: a):
    """The block-diffusion loss of `x` (batch, 2L), `y` (batch, 2, L)."""
    length = x.shape[1] // 2
    h = params["embed"][x]
    layers = {k: params["layers." + k] for k in LAYER_KEYS}
    one = jax.checkpoint(lambda hs, p: _layer(hs, p, cfg, q)[0])
    h, _ = jax.lax.scan(
        lambda h, p: (jax.lax.map(lambda hs: one(hs, p), h), None), h, layers)

    @jax.checkpoint
    def sequence_loss(hs_ys):
        hs, ys = hs_ys
        hn = _rms(hs[:length], params["final_norm.g"], cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(q(hn) @ q(params["head"]).T, -1)
        target = ys[0].astype(jnp.int32)
        nll = -jnp.take_along_axis(logp, jnp.maximum(target, 0)[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(target >= 0, ys[1] * nll, 0.0)) / length

    return jnp.mean(jax.lax.map(sequence_loss, (h, y)))


def routing(params, x, cfg):
    """(layers, batch, 2L, k) int32: the experts that each position of the
    batch `x` reaches in each layer, forward only. The program's expert layer
    works over a bound of twice the balanced expectation of the pairs that
    reach a held expert; what a layer holds of them under given weights is
    counted from this (`drift.py`), apart from the program."""
    layers = {k: params["layers." + k] for k in LAYER_KEYS}

    def layer(h, p):
        return jax.lax.map(lambda hs: _layer(hs, p, cfg, lambda a: a), h)

    return jax.lax.scan(layer, params["embed"][x], layers)[1]


def make_loss_and_grad(cfg, q=lambda a: a):
    """`f(params, x, y) -> (loss, grads)` over the whole batch, compiled once;
    the gradients as host arrays."""
    vg = jax.jit(jax.value_and_grad(lambda p, a, b: loss(p, a, b, cfg, q)))

    def loss_and_grad(params, x, y):
        value, grads = vg(params, x, y)
        return value, jax.device_get(grads)

    return loss_and_grad
