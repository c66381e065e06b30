"""Plain reference for `nemotron-3-super-120b-a12b`: Nemotron 3 Super's
decoder (`config.json` of nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16,
`model_type` `nemotron_h`) trained on next-token prediction, in float32
`jax.numpy`. Written from the equations below and the published description;
imports nothing of the program.

The equations. Every layer is `h = h + Mixer(RMSNorm(h))`, the mixer of the
layer's kind in `hybrid_override_pattern` (`M`, `*`, `E`); every RMSNorm takes
`layer_norm_epsilon`.

- `M`, Mamba-2, `x = RMSNorm(h)`: `[z ; xBC ; dt] = x W_in`;
  `xBC = SiLU(causal depthwise conv_K(xBC) + b)`, split into `x` (heads x
  head_dim), `B` and `C` (groups x state); head `i` reads group
  `i // (heads / groups)`; `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`;
  per head, from `S_0 = 0`, position by position:
  `S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`, `y_t = S_t C_t + D x_t`;
  `y = RMSNorm_grouped(y * SiLU(z)) g` over each group's channels (the gate
  before the norm); `out = y W_out`.
- `*`, attention, `x = RMSNorm(h)`: grouped-query, causal, `1/sqrt(head_dim)`,
  no bias, no per-head norm, and no positional rotation (the family's
  attention carries none; order comes from the Mamba layers).
- `E`, LatentMoE, `m = RMSNorm(h)`: `s = sigmoid(m W_r)` over all 512 experts;
  `S` the `num_experts_per_tok` largest of `s + b` (`b` the correction bias,
  a buffer that steers the choice alone: zero unless given);
  `w_e = routed_scaling_factor * s_e / (sum_{j in S} s_j + 1e-20)`;
  `l = m W_down`; `y = W_up(sum_{e in S, held here} w_e W2_e relu2(W1_e l))
  + W_sd relu2(W_su m)`, `relu2(u) = max(u, 0)^2`. With all experts held
  this is the published layer; with 8 held (`held` = first, count) it is this
  chip's part, the shared expert counted once, and that partial result goes on
  to the next layer. Nothing stands in for the absent chips.
- After the last layer `RMSNorm`, an untied head, and the mean over all
  positions of `-log softmax(head(h_i))[x_{i+1}]` over the vocabulary's slice.

What the batches hold (`configs/nemotron-3-super-120b-a12b.py`): `x` (batch,
T) int32 tokens, `y` (batch, T) int32 the next tokens. Weights arrive under
the names that file gives them, each kind's layers stacked in front,
attention's projections as (out, in), every other matrix as (in, out).

How it is computed, and where that departs from the plainest form (the
configuration's `departures` and `assumed` say the same): the Mamba layer runs
the **sequential recurrence** a position at a time, not the chunked
algorithm, under `jax.checkpoint` in blocks of `SCAN_BLOCK` positions, so that
its backward keeps a state a block; attention runs a head at a time in blocks
of `ROWS` query rows against every key under the causal mask written out; the
expert layer and the head run in blocks of `FFN_ROWS` positions, and each
held expert over its own rows of a block (gathered by position, the block's
rows the bound, so no row can be dropped); a layer runs one sequence at a
time and is recomputed in the backward pass, and the backward pass runs a
layer at a time, each layer's program handing its weights' gradient back to
the host (`make_loss_and_grad`), as the DeepSeek reference does and for the
same reason: the check keeps four copies of the weights beside it.

`q` rounds the operands of every matrix product, and the operands of the
recurrence (`x`, `B`, `C`), to the control's precision (identity in the
reference; `check.fp8` in the control).
"""

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 1024
FFN_ROWS = 2048
SCAN_BLOCK = 128
GROUPS = {"M": "mamba", "*": "attention", "E": "experts"}
KEYS = {"mamba": ("a_log", "conv.b", "conv.w", "d", "dt_bias", "in", "gate_norm.g",
                  "out", "norm.g"),
        "attention": ("kv", "out", "q", "norm.g"),
        "experts": ("latent_down", "latent_up", "shared.in", "shared.out", "router",
                    "in", "out", "norm.g")}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _relu2(u):
    return jnp.square(jnp.maximum(u, 0.0))


def recurrence(x, dt, a, b, c):
    """`y_t = S_t C_t` over one sequence, position by position, from
    `S_0 = 0`: `x` (T, H, P), `dt` (T, H), `a` (H,), `b`, `c` (T, G, S).
    Blocks of `SCAN_BLOCK` positions, each recomputed in the backward pass."""
    t, h, p = x.shape
    r = h // b.shape[1]
    b, c = jnp.repeat(b, r, axis=1), jnp.repeat(c, r, axis=1)     # head i: group i // r
    pad = -t % SCAN_BLOCK
    x, dt, b, c = (jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1)) for v in (x, dt, b, c))

    def one(state, at):
        xt, dtt, bt, ct = at
        state = (jnp.exp(dtt * a)[:, None, None] * state
                 + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return state, jnp.einsum("hps,hs->hp", state, ct)

    @jax.checkpoint
    def block(state, at):
        return jax.lax.scan(one, state, at)

    blocked = tuple(v.reshape((-1, SCAN_BLOCK) + v.shape[1:]) for v in (x, dt, b, c))
    _, y = jax.lax.scan(block, jnp.zeros((h, p, b.shape[-1]), jnp.float32), blocked)
    return y.reshape(-1, h, p)[:t]


def _conv(xbc, w, bias):
    """Causal depthwise convolution: tap `K - 1` on the current position."""
    k, t = w.shape[0], xbc.shape[0]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    return sum(w[j] * padded[j:j + t] for j in range(k)) + bias


def _mamba(h, p, cfg, q):
    """One Mamba layer over one sequence `h` (T, hidden)."""
    eps = cfg["layer_norm_epsilon"]
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, s = cfg["n_groups"], cfg["ssm_state_size"]
    inner, t = heads * hd, h.shape[0]
    x = _rms(h, p["norm.g"], eps)
    zxbcdt = q(x) @ q(p["in"])
    z, xbc, dt = zxbcdt[:, :inner], zxbcdt[:, inner:-heads], zxbcdt[:, -heads:]
    xbc = jax.nn.silu(_conv(xbc, p["conv.w"], p["conv.b"]))
    xs = xbc[:, :inner].reshape(t, heads, hd)
    b = xbc[:, inner:inner + g * s].reshape(t, g, s)
    c = xbc[:, inner + g * s:].reshape(t, g, s)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(q(xs), dt, -jnp.exp(p["a_log"]), q(b), q(c)) + p["d"][:, None] * xs
    y = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + eps)
    y = y.reshape(t, inner) * p["gate_norm.g"]
    return h + q(y) @ q(p["out"])


def _attention(h, p, cfg, q):
    """One attention layer over one sequence `h` (T, hidden), a query head
    at a time against the key/value head it reads, `ROWS` query rows at a
    time."""
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    t, d = h.shape
    rows = min(ROWS, t)
    x = _rms(h, p["norm.g"], cfg["layer_norm_epsilon"])
    k = (q(x) @ q(p["kv"][:kv * hd]).T).reshape(t, kv, hd)
    v = (q(x) @ q(p["kv"][kv * hd:]).T).reshape(t, kv, hd)
    cols = jnp.arange(t)

    @jax.checkpoint
    def head(i_w):
        i, w_q = i_w
        qh = q(x) @ q(w_q).T                                       # (T, hd)
        kh, vh = k[:, i // (heads // kv)], v[:, i // (heads // kv)]

        def block(j):
            sees = (j * rows + jnp.arange(rows))[:, None] >= cols[None, :]
            qb = jax.lax.dynamic_slice_in_dim(qh, j * rows, rows, axis=0)
            scores = jnp.where(sees, (q(qb) @ q(kh).T) / jnp.sqrt(float(hd)), -jnp.inf)
            return q(jax.nn.softmax(scores, -1)) @ q(vh)

        return jax.lax.map(jax.checkpoint(block), jnp.arange(t // rows)).reshape(t, hd)

    o = jax.lax.map(head, (jnp.arange(heads), p["q"].reshape(heads, hd, d)))
    return h + q(o.transpose(1, 0, 2).reshape(t, heads * hd)) @ q(p["out"]).T


def _route(m, p, bias, cfg, q):
    """The sigmoid router over positions `m` (rows, hidden): the experts each
    position reaches (rows, k) and their weights."""
    scores = jax.nn.sigmoid(q(m) @ q(p["router"]))
    _, top_e = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    top_s = jnp.take_along_axis(scores, top_e, -1)
    if cfg["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    return top_e, top_s * cfg["routed_scaling_factor"]


def _experts(m, p, bias, cfg, q):
    """The held experts' part in the latent space, brought back up, and the
    shared expert, over positions `m` (rows, hidden); and the experts (rows,
    k) each position reaches. Each held expert runs over its own rows."""
    top_e, top_w = _route(m, p, bias, cfg, q)
    first, rows = cfg["held"][0], m.shape[0]
    latent = q(m) @ q(p["latent_down"])

    @jax.checkpoint
    def expert(y, e_w):
        e, w1, w2 = e_w
        w = jnp.sum(jnp.where(top_e == first + e, top_w, 0.0), -1)
        mine = w > 0
        idx = jnp.nonzero(mine, size=rows, fill_value=0)[0]       # its rows first
        live = jnp.arange(rows) < jnp.sum(mine)
        out = _relu2(q(latent[idx]) @ q(w1)) @ q(w2)
        return y.at[idx].add(jnp.where(live, w[idx], 0.0)[:, None] * out), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(latent),
                        (jnp.arange(cfg["held"][1]), p["in"], p["out"]))
    shared = _relu2(q(m) @ q(p["shared.in"])) @ q(p["shared.out"])
    return q(y) @ q(p["latent_up"]) + shared, top_e


def _by_rows(f, *xs):
    """`f` over blocks of `FFN_ROWS` positions of `xs` (T, ...), each block
    recomputed in the backward pass; the blocks' results put back together."""
    t = xs[0].shape[0]
    rows = min(FFN_ROWS, t)
    out = jax.lax.map(jax.checkpoint(lambda blk: f(*blk)),
                      tuple(x.reshape((t // rows, rows) + x.shape[1:]) for x in xs))
    return jax.tree_util.tree_map(lambda a: a.reshape((t,) + a.shape[2:]), out)


def _expert_layer(h, p, bias, cfg, q):
    """One expert layer over one sequence: its output and the experts each
    position reaches."""
    m = _rms(h, p["norm.g"], cfg["layer_norm_epsilon"])
    y, top_e = _by_rows(lambda m: _experts(m, p, bias, cfg, q), m)
    return h + y, top_e


def _head_loss(h, head, norm, y, cfg, q):
    """The mean next-token loss of the last layer's output `h` (batch, T,
    hidden) against `y` (batch, T)."""
    def token_losses(hs, ys):
        hn = _rms(hs, norm, cfg["layer_norm_epsilon"])
        logp = jax.nn.log_softmax(q(hn) @ q(head).T, -1)
        return -jnp.take_along_axis(logp, ys[:, None], -1)[:, 0]

    return jnp.mean(jax.lax.map(lambda hy: _by_rows(token_losses, *hy), (h, y)))


def layers(cfg):
    """(kind, index within its kind) of every layer kept, in order."""
    seen, out = {}, []
    for kind in cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]:
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def _bias(cfg, bias):
    """The correction bias of each expert layer: zero unless given."""
    if bias is not None:
        return jnp.asarray(bias, jnp.float32)
    n = sum(kind == "E" for kind, _ in layers(cfg))
    return jnp.zeros((n, cfg["router_experts"]), jnp.float32)


def _layer_fns(cfg, q):
    """A layer's function by kind, over a batch, one sequence at a time, each
    recomputed in the backward pass: `(h, p, bias) -> h`."""
    def by_sequence(layer):
        one = jax.checkpoint(lambda hs, p, b: layer(hs, p, b))
        return lambda h, p, b: jax.lax.map(lambda hs: one(hs, p, b), h)

    return {"M": by_sequence(lambda h, p, b: _mamba(h, p, cfg, q)),
            "*": by_sequence(lambda h, p, b: _attention(h, p, cfg, q)),
            "E": by_sequence(lambda h, p, b: _expert_layer(h, p, b, cfg, q)[0])}


def _weights(params, kind, i):
    group = GROUPS[kind]
    return {k: params[group + "." + k][i] for k in KEYS[group]}


def layer_inputs(params, x, cfg, bias=None):
    """Each layer's input over the batch `x` (batch, T), forward only, a
    layer one sequence at a time: yields `(kind, index within its kind, h)`
    before the layer runs, and runs it on `params` as they stand when resumed,
    so that a caller may set a layer's weights from what reaches it (the
    configuration's `make_weights` sets the routers so)."""
    identity = lambda a: a
    bias = _bias(cfg, bias)
    fns = {k: jax.jit(f) for k, f in _layer_fns(cfg, identity).items()}
    h = params["embed"][x]
    for kind, i in layers(cfg):
        yield kind, i, h
        h = fns[kind](h, _weights(params, kind, i), bias[i] if kind == "E" else None)


def routing(params, x, cfg, bias=None):
    """(expert layers, batch, T, k) int32: the experts that each position of
    the batch `x` reaches in each expert layer, forward only, a layer one
    sequence at a time. The program's expert layer works over a bound of
    twice the balanced expectation of the pairs that reach a held expert; what
    a layer holds of them under given weights is counted from this
    (`drift.py`), apart from the program."""
    identity = lambda a: a
    b = _bias(cfg, bias)
    return jnp.stack([
        jax.lax.map(lambda hs: _expert_layer(hs, _weights(params, kind, i), b[i], cfg,
                                             identity)[1], h)
        for kind, i, h in layer_inputs(params, x, cfg, bias) if kind == "E"])


def make_loss_and_grad(cfg, q=lambda a: a, bias=None):
    """`f(params, x, y) -> (loss, grads)` over the whole batch, the gradients
    as host arrays: the mean next-token loss of `x` (batch, T) against `y`
    (batch, T) and its gradient, with the backward pass run a layer at a
    time. The forward keeps each layer's input; then the head's program, and
    each layer's, last to first, makes its weights' gradient and hands it to
    the host and the gradient of its input to the next program. Each kind of
    layer is one compiled program. `bias` (expert layers, experts) is the
    routers' correction bias, zero unless given."""
    fns = _layer_fns(cfg, q)
    bias = _bias(cfg, bias)
    forward = {k: jax.jit(f) for k, f in fns.items()}
    backward = {k: jax.jit(lambda h, p, b, d, f=f: jax.vjp(lambda h, p: f(h, p, b), h, p)[1](d))
                for k, f in fns.items()}
    head = jax.jit(jax.value_and_grad(
        lambda h, w, g, y: _head_loss(h, w, g, y, cfg, q), argnums=(0, 1, 2)))
    embed = jax.jit(lambda x, d, w: jnp.zeros_like(w).at[x].add(d))

    def loss_and_grad(params, x, y):
        h, inputs = params["embed"][x], []
        for kind, i in layers(cfg):
            inputs.append((kind, i, h))
            h = forward[kind](h, _weights(params, kind, i), bias[i] if kind == "E" else None)
        value, (d, d_head, d_norm) = head(h, params["head"], params["final_norm.g"], y)
        grads = {"head": jax.device_get(d_head), "final_norm.g": jax.device_get(d_norm)}
        by_layer = {}
        for kind, i, h in reversed(inputs):
            d, d_p = backward[kind](h, _weights(params, kind, i),
                                    bias[i] if kind == "E" else None, d)
            for k, v in jax.device_get(d_p).items():
                by_layer.setdefault(GROUPS[kind] + "." + k, {})[i] = v
        grads["embed"] = jax.device_get(embed(x, d, params["embed"]))
        for name, per in by_layer.items():
            grads[name] = np.stack([per[i] for i in sorted(per)])
        return value, grads

    return loss_and_grad
