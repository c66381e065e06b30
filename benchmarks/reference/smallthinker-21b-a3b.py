"""Plain reference for `smallthinker-21b-a3b`: SmallThinker-21BA3B-Instruct's
decoder (`config.json` of PowerInfer/SmallThinker-21BA3B-Instruct) trained on
next-token prediction, in float32 `jax.numpy`. Written from the equations
below and imports nothing of the program.

The equations. Layer `l`, input `h` (positions x 2560), positions `0 .. T-1`,
`kind_l = sliding_window_layout[l]`:

- `r = h Wr`: 64 router logits from the layer's input as it enters, before
  its first RMSNorm (the router stands before attention); `p = softmax(r)`
  (`moe_primary_router_apply_softmax`); `S` = the 6 largest of `p`;
  `w_e = p_e / sum_{e' in S} p_e'` (`norm_topk_prob`).
- `a = RMSNorm(h)`; `q = a Wq` as 28 heads of 128, `k = a Wk`, `v = a Wv` as 4
  heads of 128, no biases, no per-head norm. Where `rope_layout[l] = 1`:
  `q, k = RoPE(q, pos), RoPE(k, pos)` (rotate-half, theta 1.5e6); where it is
  0, no positions at all. Query head `i` reads key/value head `i // 7`.
  `o = softmax(q k^T / sqrt(128) + M_l) v` with `M_l` causal where
  `kind_l = 0`, and causal within a window where `kind_l = 1`: query `i` sees
  key `j` iff `0 <= i - j < 4096` (`sliding_window_size` keys with its own).
  `h = h + concat(o) Wo`.
- `m = RMSNorm(h)`;
  `y = sum_{e in S and e held here} w_e ((ReLU(m Wg_e) * (m Wu_e)) Wd_e)`;
  `h = h + y`. With all 64 held this is the published layer; with 8 held
  (`held` = first, count) `y` is this chip's part, and that partial result
  goes on to the next layer. Nothing stands in for the absent chips.
- After the last layer `RMSNorm`, an untied head, and the mean over all
  positions of `-log softmax(head(h_i))[x_{i+1}]` over the vocabulary's slice.

What the batches hold (`configs/smallthinker-21b-a3b.py`): `x` (batch, T)
int32 tokens, `y` (batch, T) int32 the next tokens.

Weights arrive under the names `configs/smallthinker-21b-a3b.py` gives them,
the layers' stacked with the depth in front, projection matrices as (out,
in). Departures from the published model, as the configuration states them: 4
of the 52 layers (one period: full, window, window, window), the 8 experts
`held` of 64, a slice of 18,992 rows of the vocabulary; `layers.attn.kv`
holds `Wk` above `Wv`, `layers.experts.in` an expert's gate `Wg_e` beside its
up `Wu_e` (2560 x [768 | 768]).

`q` rounds the operands of every matrix product (identity in the reference;
`check.fp8` in the control). To fit beside the check's own copies of the
weights (the seeded weights, the parameters, Adam's two moments: four of 1.5
GB, and a mask) a layer runs one sequence at a time and is recomputed in the
backward pass, attention runs in blocks of `ROWS` query rows against every
key under the layer's mask written out (28 heads x 16,384 x 16,384 scores in
fp32 would be 30 GB), the experts one at a time over every token (a dense
product with the routing weight, zero for the tokens not routed to it), the
head one sequence at a time; and the gradient is handed back on the host, as
the SDAR reference does and for the same reason: the check keeps a step's
gradient until the next step's is made. The layers are written out one after
another (no scan: a layer's kind is static), each with its own mask and its
RoPE or none.
"""

import jax
import jax.numpy as jnp

ROWS = 256
LAYER_KEYS = ("attn.kv", "attn.out", "attn.q", "attn_norm.g", "router",
              "experts.in", "experts.out", "moe_norm.g")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """Rotate-half RoPE over (heads, positions, d)."""
    half = x.shape[-1] // 2
    ang = pos[:, None] * (1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(qh, kh, vh, window, q):
    """softmax(q k^T / sqrt(d) + M) v with `qh` (kv heads, group, T, d) and
    `kh`, `vh` (kv heads, T, d), `ROWS` query rows at a time; `M` lets query
    `i` see key `j` iff `0 <= i - j`, and `i - j < window` where a window is
    given."""
    t, d = qh.shape[-2:]
    rows = min(ROWS, t)
    cols = jnp.arange(t)

    @jax.checkpoint
    def block(i):
        lead = (i * rows + jnp.arange(rows))[:, None] - cols[None, :]
        sees = lead >= 0 if window is None else (lead >= 0) & (lead < window)
        qb = jax.lax.dynamic_slice_in_dim(qh, i * rows, rows, axis=2)
        s = jnp.einsum("gjqd,gkd->gjqk", q(qb), q(kh)) / jnp.sqrt(float(d))
        s = jnp.where(sees, s, -jnp.inf)
        return jnp.einsum("gjqk,gkd->gjqd", q(jax.nn.softmax(s, -1)), q(vh))

    o = jax.lax.map(block, jnp.arange(t // rows))       # (blocks, g, j, rows, d)
    return o.transpose(1, 2, 0, 3, 4).reshape(-1, t, d)  # (heads, T, d)


def _kind(cfg, l):
    """(window or None, RoPE or not) of layer `l`."""
    return (cfg["sliding_window_size"] if cfg["sliding_window_layout"][l] else None,
            bool(cfg["rope_layout"][l]))


def _layer(h, p, cfg, kind, q):
    """One layer of `kind` over one sequence `h` (T, hidden): its output, and
    the experts (T, k) that each position reaches."""
    window, turned = kind
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    t = h.shape[0]
    # the router reads the layer's input itself
    probs = jax.nn.softmax(q(h) @ q(p["router"]), -1)
    top_p, top_e = jax.lax.top_k(probs, cfg["moe_num_active_primary_experts"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)

    a = _rms(h, p["attn_norm.g"], eps)
    wk, wv = p["attn.kv"][:kv * hd], p["attn.kv"][kv * hd:]
    split = lambda x, n: x.reshape(t, n, hd).transpose(1, 0, 2)
    qh = split(q(a) @ q(p["attn.q"]).T, heads)
    kh, vh = split(q(a) @ q(wk).T, kv), split(q(a) @ q(wv).T, kv)
    if turned:
        pos = jnp.arange(t, dtype=jnp.float32)
        qh, kh = _rope(qh, pos, theta), _rope(kh, pos, theta)
    o = _attention(qh.reshape(kv, heads // kv, t, hd), kh, vh, window, q)
    h = h + q(o.transpose(1, 0, 2).reshape(t, heads * hd)) @ q(p["attn.out"]).T

    m = _rms(h, p["moe_norm.g"], eps)
    first, width = cfg["held"][0], cfg["moe_ffn_hidden_size"]

    @jax.checkpoint
    def expert(y, e_w):
        e, w_in, w_out = e_w
        w = jnp.sum(jnp.where(top_e == first + e, top_p, 0.0), -1)
        gu = q(m) @ q(w_in)
        act = jax.nn.relu(gu[:, :width]) * gu[:, width:]
        return y + w[:, None] * (q(act) @ q(w_out)), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), (
        jnp.arange(cfg["held"][1]), p["experts.in"], p["experts.out"]))
    return h + y, top_e


def _layers(params, cfg):
    """Each layer's weights and kind, the first `num_hidden_layers` of the
    layouts."""
    return [({k: params["layers." + k][l] for k in LAYER_KEYS}, _kind(cfg, l))
            for l in range(cfg["num_hidden_layers"])]


def loss(params, x, y, cfg, q=lambda a: a):
    """The mean next-token loss of `x` (batch, T) against `y` (batch, T)."""
    h = params["embed"][x]
    for p, kind in _layers(params, cfg):
        one = jax.checkpoint(lambda hs, p, kind=kind: _layer(hs, p, cfg, kind, q)[0])
        h = jax.lax.map(lambda hs: one(hs, p), h)

    @jax.checkpoint
    def sequence_loss(hs_ys):
        hs, ys = hs_ys
        hn = _rms(hs, params["final_norm.g"], cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(q(hn) @ q(params["head"]).T, -1)
        return -jnp.mean(jnp.take_along_axis(logp, ys[:, None], -1))

    return jnp.mean(jax.lax.map(sequence_loss, (h, y)))


def routing(params, x, cfg):
    """(layers, batch, T, k) int32: the experts that each position of the
    batch `x` reaches in each layer, forward only. The program's expert layer
    works over a bound of twice the balanced expectation of the pairs that
    reach a held expert; what a layer holds of them under given weights is
    counted from this (`drift.py`), apart from the program."""
    h, out = params["embed"][x], []
    for p, kind in _layers(params, cfg):
        h, top_e = jax.lax.map(lambda hs: _layer(hs, p, cfg, kind, lambda a: a), h)
        out.append(top_e)
    return jnp.stack(out)


def make_loss_and_grad(cfg, q=lambda a: a):
    """`f(params, x, y) -> (loss, grads)` over the whole batch, compiled once;
    the gradients as host arrays."""
    vg = jax.jit(jax.value_and_grad(lambda p, a, b: loss(p, a, b, cfg, q)))

    def loss_and_grad(params, x, y):
        value, grads = vg(params, x, y)
        return value, jax.device_get(grads)

    return loss_and_grad
