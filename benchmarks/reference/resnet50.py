"""Plain reference for `resnet50`: ResNet-50 (He et al. 2015, bottleneck
blocks [3, 4, 6, 3], stride on the 3x3 convolution, projection shortcuts where
the shape changes) in float32 `jax.numpy`, imports nothing of the program.
Batch normalisation uses the batch's own biased variance (training mode);
max-pool 3x3/2 pad 1; global average pool; linear classifier; mean negative
log-likelihood over the batch.

Weights arrive under the names `configs/resnet50.py` gives them, convolutions
as (out, in, kh, kw). Departures, as the configuration states: NHWC input of
uint8 pixels, normalised here with the ImageNet mean and std, and the stem in
its space-to-depth form, a (64, 12, 4, 4) convolution over 2x2 pixel blocks
padded (2, 1).

`q` rounds every tensor that the configuration holds in its compute type: the
operands of each convolution and matrix product, and each activation that
leaves a normalisation, a ReLU or a block (identity in the reference;
`check.fp8` in the control). Each bottleneck is recomputed in
the backward pass: batch statistics tie the rows together, so the batch cannot
be cut into blocks of rows, and without that 256 images do not fit one chip in
float32.
"""

import jax
import jax.numpy as jnp

MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)


def _conv(x, w, stride, pad, q):
    return jax.lax.conv_general_dilated(
        q(x), q(w), (stride, stride), pad,
        dimension_numbers=("NHWC", "OIHW", "NHWC"))


def _bn(x, g, b, eps):
    mu = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mu), (0, 1, 2))
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _bottleneck(x, p, stride, eps, q):
    same = ((0, 0), (0, 0))
    y = q(jax.nn.relu(_bn(_conv(x, p["c1.w"], 1, same, q), p["bn1.g"], p["bn1.b"], eps)))
    y = q(jax.nn.relu(_bn(_conv(y, p["c2.w"], stride, ((1, 1), (1, 1)), q),
                          p["bn2.g"], p["bn2.b"], eps)))
    y = q(_bn(_conv(y, p["c3.w"], 1, same, q), p["bn3.g"], p["bn3.b"], eps))
    if "sc.w" in p:
        x = q(_bn(_conv(x, p["sc.w"], stride, same, q), p["scbn.g"], p["scbn.b"], eps))
    return q(jax.nn.relu(y + x))


def loss(params, x, y, cfg, q=lambda a: a):
    """Mean negative log-likelihood of labels `y` (b,) for uint8 images `x`
    (b, H, W, 3)."""
    eps = cfg["bn_eps"]
    h = (x.astype(jnp.float32) / 255.0 - jnp.asarray(MEAN)) / jnp.asarray(STD)
    n, hh, ww, c = h.shape
    h = h.reshape(n, hh // 2, 2, ww // 2, 2, c).transpose(0, 1, 3, 2, 4, 5) \
         .reshape(n, hh // 2, ww // 2, 4 * c)
    h = _conv(h, params["stem.w"], 1, ((2, 1), (2, 1)), q)
    h = q(jax.nn.relu(_bn(h, params["stem.bn.g"], params["stem.bn.b"], eps)))
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))
    for s, blocks in enumerate(cfg["stage_blocks"]):
        for b in range(blocks):
            pre = f"s{s}b{b}."
            stride = 2 if (s > 0 and b == 0) else 1
            block = jax.checkpoint(
                lambda h, p, stride=stride: _bottleneck(h, p, stride, eps, q))
            h = block(h, {k[len(pre):]: v for k, v in params.items()
                          if k.startswith(pre)})
    h = jnp.mean(h, (1, 2))
    logits = q(h) @ q(params["fc.w"]).T + params["fc.b"]
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))


def make_loss_and_grad(cfg, q=lambda a: a):
    """`f(params, x, y) -> (loss, grads)` over the whole batch, compiled once."""
    return jax.jit(jax.value_and_grad(lambda p, a, b: loss(p, a, b, cfg, q)))
