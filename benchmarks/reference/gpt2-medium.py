"""Plain reference for `gpt2-medium`: GPT-2's decoder (Radford et al. 2019) in
float32 `jax.numpy`, written from the published description and imports
nothing of the program. Pre-LayerNorm blocks, learned positions, fused qkv
projection split as [q | k | v] and then into heads, causal softmax attention
scaled by 1/sqrt(head), tanh GELU, mean next-token negative log-likelihood.

Weights arrive under the names `configs/gpt2-medium.py` gives them, matrices as
(out, in). Departure from the published model, as the configuration states:
the head `head.w` is a matrix of its own.

`q` rounds the operands of every matrix product (identity in the reference;
`check.fp8` in the control). The gradient runs row block by row block with each
layer recomputed in the backward pass, so that 8 x 1024 tokens fit one chip.
"""

import jax
import jax.numpy as jnp

ROWS_PER_BLOCK = 2


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _block(x, p, n_head, eps, q):
    b, t, d = x.shape
    hd = d // n_head
    a = _ln(x, p["ln1.g"], p["ln1.b"], eps)
    qkv = q(a) @ q(p["attn.qkv.w"]).T + p["attn.qkv.b"]
    qh, kh, vh = (qkv.reshape(b, t, 3, n_head, hd)[:, :, i].transpose(0, 2, 1, 3)
                  for i in range(3))
    s = jnp.einsum("bhqd,bhkd->bhqk", q(qh), q(kh)) / jnp.sqrt(float(hd))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", q(jax.nn.softmax(s, -1)), q(vh))
    o = o.transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + q(o) @ q(p["attn.out.w"]).T + p["attn.out.b"]
    m = _ln(x, p["ln2.g"], p["ln2.b"], eps)
    m = jax.nn.gelu(q(m) @ q(p["mlp.fc.w"]).T + p["mlp.fc.b"], approximate=True)
    return x + q(m) @ q(p["mlp.proj.w"]).T + p["mlp.proj.b"]


LAYER_KEYS = ("ln1.g", "ln1.b", "attn.qkv.w", "attn.qkv.b", "attn.out.w",
              "attn.out.b", "ln2.g", "ln2.b", "mlp.fc.w", "mlp.fc.b",
              "mlp.proj.w", "mlp.proj.b")


def loss(params, x, y, cfg, q=lambda a: a):
    """Mean negative log-likelihood of `y` (b, T) given tokens `x` (b, T). The
    layers are one scanned body over their stacked weights (they are all
    alike), which compiles in a fraction of the time of 24 written out."""
    eps, n_head = cfg["layer_norm_epsilon"], cfg["n_head"]
    t = x.shape[1]
    h = params["wte"][x] + params["wpe"][None, :t]
    layers = {k: jnp.stack([params[f"h{i}.{k}"] for i in range(cfg["n_layer"])])
              for k in LAYER_KEYS}
    block = jax.checkpoint(lambda h, p: _block(h, p, n_head, eps, q))
    h, _ = jax.lax.scan(lambda h, p: (block(h, p), None), h, layers)
    h = _ln(h, params["lnf.g"], params["lnf.b"], eps)
    logits = q(h) @ q(params["head.w"]).T + params["head.b"]
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))


def make_loss_and_grad(cfg, q=lambda a: a):
    """`f(params, x, y) -> (loss, grads)` over the whole batch, compiled once."""
    vg = jax.jit(jax.value_and_grad(lambda p, a, b: loss(p, a, b, cfg, q)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))

    def loss_and_grad(params, x, y):
        n = x.shape[0]
        step = ROWS_PER_BLOCK if n % ROWS_PER_BLOCK == 0 else 1
        total = None
        for i in range(0, n, step):
            part = vg(params, x[i:i + step], y[i:i + step])
            total = part if total is None else add(total, part)
        return jax.tree_util.tree_map(lambda a: a / (n // step), total)

    return loss_and_grad
