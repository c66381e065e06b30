"""FLOPs and bytes that the convolutions of `resnet50` require in one training
step, from their shapes alone: [(flops, bytes)], one entry a convolution and
pass. A multiply-add is 2 FLOP. Each layer runs three convolutions a step:
forward (reads input and weights, writes output), the input's gradient (reads
the output's gradient and weights, writes the input's) and the weights'
gradient (reads input and output's gradient, writes the weights'), each
tensor crossing memory once in the compute dtype; the stem needs no gradient
of the images and is counted as the published 7x7/2. The classifier is a
matrix product that the chip runs as a convolution, so it counts."""


def _layers(cfg):
    """(MACs, input, weight and output elements) a sample, a layer."""
    size = cfg["image_size"] // 2
    out = [(7 * 7 * cfg["image_channels"] * 64 * size * size,
            cfg["image_channels"] * cfg["image_size"] ** 2,
            7 * 7 * cfg["image_channels"] * 64, 64 * size * size)]
    size //= 2                                  # max-pool
    n_in = 64

    def conv(k, c_in, c_out, size_in, size_out):
        # a strided 1x1 needs only the pixels it lands on
        needed = size_in if k > 1 else size_out
        out.append((k * k * c_in * c_out * size_out ** 2, c_in * needed ** 2,
                    k * k * c_in * c_out, c_out * size_out ** 2))

    for s, (blocks, mid) in enumerate(zip(cfg["stage_blocks"], cfg["stage_widths"])):
        n_out = mid * cfg["expansion"]
        for b in range(blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            conv(1, n_in, mid, size, size)              # before the stride
            conv(3, mid, mid, size, size // stride)
            conv(1, mid, n_out, size // stride, size // stride)
            if b == 0:
                conv(1, n_in, n_out, size, size // stride)
            size //= stride
            n_in = n_out
    out.append((n_in * cfg["class_num"], n_in, n_in * cfg["class_num"],
                cfg["class_num"]))
    return out


def convolution_step(cfg, traffic):
    batch = traffic["batch"]
    width = {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]]
    parts = []
    for i, (macs, x, w, y) in enumerate(_layers(cfg)):
        flops = 2 * macs * batch
        x, y = x * batch, y * batch
        parts.append((flops, width * (x + w + y)))          # forward
        parts.append((flops, width * (x + y + w)))          # the weights' gradient
        if i:                                               # the input's gradient
            parts.append((flops, width * (y + w + x)))
    return parts
