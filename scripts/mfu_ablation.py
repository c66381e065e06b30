"""MFU ablation harness: where does the ResNet-50 step time go?

Round-3/4 review follow-up: the headline step is at MFU ~0.30 with
~1.5x headroom vs tuned TPU ResNet implementations. This script decomposes the
compiled step into its phases and sweeps the knobs that plausibly matter, each
measured as a SEPARATE jitted program on the live chip:

  fwd            forward + loss only
  fwd_bwd        value_and_grad (no optimizer update)
  full           value_and_grad + SGD-momentum update (the bench's step)

per batch in --batches (default "256,512"), NHWC layout, bf16 compute.

Usage:  python scripts/mfu_ablation.py [--batches 256,512,1024] [--iters 30]
Prints one JSON line per leg; exits 0 even on failure legs (error recorded).
"""
from __future__ import annotations

import argparse
import json
import time


def _time_compiled(fn, args, iters):
    out = fn(*args)  # compile
    jax_block(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax_block(out)
    return (time.perf_counter() - t0) / iters


def jax_block(tree):
    import jax
    jax.block_until_ready(tree)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batches", default="256,512")
    p.add_argument("--iters", type=int, default=30)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.nn.layout import set_image_format
    from bigdl_tpu.utils.engine import Engine

    Engine.reset()
    Engine.init(compute_dtype=jnp.bfloat16)
    dev = Engine.devices()[0]
    set_image_format("NHWC")

    from bigdl_tpu.models.resnet import ResNet

    # analytic fwd FLOPs/img for ResNet-50 @224 and the per-generation peak
    # table — same constants the bench uses
    from bigdl_tpu.benchmark import _ANALYTIC_STEP_FLOPS_PER_UNIT, _peak_flops
    step_flops_per_img = _ANALYTIC_STEP_FLOPS_PER_UNIT["resnet50"]
    peak = _peak_flops(Engine.devices()[0].device_kind)  # None -> mfu: null

    for batch in [int(b) for b in args.batches.split(",")]:
        model = ResNet(1000, {"depth": 50, "dataSet": "ImageNet",
                              "conv1SpaceToDepth": True})
        criterion = nn.ClassNLLCriterion()
        params = model.get_params()
        mstate = model.get_state()
        rng = np.random.default_rng(0)
        x = jax.device_put(
            jnp.asarray(rng.normal(size=(batch, 224, 224, 3)), jnp.bfloat16), dev)
        y = jax.device_put(jnp.asarray(
            rng.integers(0, 1000, size=(batch,)), jnp.int32), dev)
        params = jax.device_put(params, dev)
        mstate = jax.device_put(mstate, dev)

        def loss_fn(p, s, xx, yy):
            # mirror the optimizer's mixed-precision policy: fp32 masters,
            # bf16 compute (cast inside the step so grads come back fp32)
            from bigdl_tpu.nn.precision import cast_floating
            pb = cast_floating(p, jnp.bfloat16)
            out, s2 = model.apply(pb, s, xx, training=True, rng=None)
            return criterion.apply(out, yy), s2

        fwd = jax.jit(lambda p, s, xx, yy: loss_fn(p, s, xx, yy)[0])
        grad = jax.jit(lambda p, s, xx, yy: jax.value_and_grad(
            lambda pp: loss_fn(pp, s, xx, yy)[0])(p))

        mom = jax.tree.map(jnp.zeros_like, params)

        @jax.jit
        def full(p, m, s, xx, yy):
            l, g = jax.value_and_grad(lambda pp: loss_fn(pp, s, xx, yy)[0])(p)
            m2 = jax.tree.map(lambda mi, gi: 0.9 * mi + gi, m, g)
            p2 = jax.tree.map(lambda pi, mi: pi - 0.01 * mi, p, m2)
            return l, p2, m2

        legs = {}
        try:
            legs["fwd"] = _time_compiled(fwd, (params, mstate, x, y), args.iters)
            legs["fwd_bwd"] = _time_compiled(grad, (params, mstate, x, y), args.iters)
            legs["full"] = _time_compiled(full, (params, mom, mstate, x, y), args.iters)
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"batch": batch, "error": f"{type(e).__name__}: {e}"[:300]}))
            continue
        rec = {"batch": batch, "device": dev.device_kind}
        for k, v in legs.items():
            ips = batch / v
            rec[k + "_ms"] = round(v * 1e3, 2)
            rec[k + "_img_s"] = round(ips, 1)
        # MFU on the full step (the bench convention: fwd x3); null when the
        # device's peak is unknown — never computed against an assumed peak
        rec["full_mfu"] = (round(step_flops_per_img * rec["full_img_s"] / peak, 4)
                           if peak else None)
        # implied split: update cost = full - fwd_bwd; bwd cost = fwd_bwd - fwd
        rec["bwd_over_fwd"] = round(
            (legs["fwd_bwd"] - legs["fwd"]) / legs["fwd"], 2)
        rec["update_ms"] = round((legs["full"] - legs["fwd_bwd"]) * 1e3, 2)
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
