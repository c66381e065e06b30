"""Work that `resnet50` requires, from its shapes alone. A multiply-add is 2
FLOP; the backward pass costs twice the forward; the stem is counted as the
published 7x7/2 convolution, whatever form it is computed in."""


def forward_macs(cfg):
    size = cfg["image_size"] // 2
    macs = 7 * 7 * cfg["image_channels"] * 64 * size * size
    size //= 2                                  # max-pool
    n_in = 64
    for s, (blocks, mid) in enumerate(zip(cfg["stage_blocks"], cfg["stage_widths"])):
        n_out = mid * cfg["expansion"]
        for b in range(blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            macs += n_in * mid * size * size    # 1x1, before the stride
            size //= stride
            macs += 9 * mid * mid * size * size + mid * n_out * size * size
            if b == 0:
                macs += n_in * n_out * size * size
            n_in = n_out
    return macs + n_in * cfg["class_num"]


def train_flops_per_sample(cfg, traffic):
    return 3 * 2 * forward_macs(cfg)
