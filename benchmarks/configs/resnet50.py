"""`resnet50` as the program runs it, and as the benchmark names its weights.

`build` is the only function here that touches the program: the configuration
of `chip_smoke.py:build_resnet50` (NHWC, space-to-depth stem, uint8 feed
normalised on the device). `names` lists the weights in the order the
program's parameter tree holds them (paths sorted with numbers as numbers).
"""

import jax
import jax.numpy as jnp
import numpy as np


def build(cfg, traffic):
    from bigdl_tpu import nn
    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.nn import layout
    layout.set_image_format("NHWC")
    net = ResNet(cfg["class_num"], {"depth": cfg["depth"], "dataSet": "ImageNet",
                                    "conv1SpaceToDepth": True})
    model = nn.Sequential().add(nn.ImageNormalize()).add(net)
    return model, nn.ClassNLLCriterion()


def names(cfg):
    out = [("stem.w", (64, 4 * cfg["image_channels"], 4, 4)),
           ("stem.bn.b", (64,)), ("stem.bn.g", (64,))]
    n_in = 64
    for s, (blocks, mid) in enumerate(zip(cfg["stage_blocks"], cfg["stage_widths"])):
        n_out = mid * cfg["expansion"]
        for b in range(blocks):
            p = f"s{s}b{b}."
            out += [(p + "c1.w", (mid, n_in, 1, 1)), (p + "bn1.b", (mid,)), (p + "bn1.g", (mid,)),
                    (p + "c2.w", (mid, mid, 3, 3)), (p + "bn2.b", (mid,)), (p + "bn2.g", (mid,)),
                    (p + "c3.w", (n_out, mid, 1, 1)), (p + "bn3.b", (n_out,)), (p + "bn3.g", (n_out,))]
            if b == 0:
                out += [(p + "sc.w", (n_out, n_in, 1, 1)),
                        (p + "scbn.b", (n_out,)), (p + "scbn.g", (n_out,))]
            n_in = n_out
    return out + [("fc.b", (cfg["class_num"],)), ("fc.w", (cfg["class_num"], n_in))]


def make_weights(cfg, key):
    """Every weight from `key`, on the device, in one compiled call."""
    spec = names(cfg)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(spec):
            k = jax.random.fold_in(key, i)
            if name.endswith(".g"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith(".b"):
                out[name] = jnp.zeros(shape, jnp.float32)
            elif name == "fc.w":
                out[name] = 0.01 * jax.random.normal(k, shape, jnp.float32)
            else:   # He-normal over the fan-out
                std = (2.0 / (shape[0] * shape[2] * shape[3])) ** 0.5
                out[name] = std * jax.random.normal(k, shape, jnp.float32)
        return out

    return jax.jit(make)(key)


def make_batches(cfg, traffic, rng):
    """`n_batches` of (uint8 images NHWC, labels), every row different.

    An image is its class's coarse pattern (a 14x14 grid of colours, one for
    each of `label_classes` classes drawn from all the model has) under pixel
    noise of the same weight, so that the examples of a batch pull the weights
    the same way, as photographs of one class do. Pixels and labels drawn
    independently would give a mean gradient that is what is left when 256
    unrelated ones cancel, and rounding would be most of it."""
    b, s, c = traffic["batch"], cfg["image_size"], cfg["image_channels"]
    grid = 14
    classes = rng.choice(cfg["class_num"], size=traffic["label_classes"], replace=False)
    patterns = rng.integers(0, 128, size=(len(classes), grid, grid, c), dtype=np.uint8)
    rep = -(-s // grid)
    patterns = patterns.repeat(rep, axis=1).repeat(rep, axis=2)[:, :s, :s]
    out = []
    for _ in range(traffic["n_batches"]):
        pick = rng.integers(0, len(classes), size=b)
        x = rng.integers(0, 256, size=(b, s, s, c), dtype=np.uint8)
        x >>= 1
        x += patterns[pick]          # both halves under 128: no overflow
        out.append((x, classes[pick].astype(np.int32)))
    return out
