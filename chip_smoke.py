"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the full
width of the models the repo benchmarks, with seeded random weights:

  attach        Engine.init(); every device must be a TPU that
                benchmarks/peaks.json knows
  train-vision  ResNet-50 (ImageNet shape, bf16 compute / fp32 masters, b256,
                NHWC + space-to-depth stem) through LocalOptimizer.optimize():
                the per-step program and fused windows
  train-lm      TransformerLM d=512 L=6 V=32000 at b16 T=512; the lowered step
                must hold the flash forward and backward and the LayerNorm
                kernels as Mosaic custom calls; the kernels are compared with
                their jnp references on the chip
  serve         ServingEngine over the same-width LM, 8 slots, 8 requests of
                mixed length; tokens compared with nn.greedy_generate
  all-devices   the train-vision model through DistriOptimizer (allreduce,
                zero1) over Engine.mesh() — every device must hold its share

One process, no JAX children, nothing caught: any failed check raises and the
exit code is non-zero. Without an accelerator it exits non-zero before doing
any work. The last line of stdout is the result, one JSON object.

    python chip_smoke.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# normalised max error, |a - b|_inf / |b|_inf, of a chip kernel against its
# jnp reference on the same inputs. The reference runs at "highest" matmul
# precision; the kernels run as the model runs them. bf16 inputs carry 8
# mantissa bits (2^-8 = 3.9e-3 per rounding, a few roundings deep); fp32
# matmuls inside the flash kernel take the MXU's default bf16 passes, so the
# fp32 flash bound is the bf16 one. LayerNorm has no matmul: fp32 is exact to
# rounding, and all-bf16 pays for a backward (the reference's VJP) whose
# arithmetic is itself bf16.
TOL_FLASH = 2e-2
TOL_LN_BF16 = 3e-2
TOL_LN_F32 = 1e-5

# a served token may differ from greedy_generate's only where the model itself
# cannot tell the two apart: their log-probabilities, from one full forward on
# the chip, closer than this (nats)
TOL_TIE = 5e-2

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "benchmarks", "peaks.json")

CACHE_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
                "/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "writes"}


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def rel_err(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-30))


# ---------------------------------------------------------------- attach
def attach():
    from importlib.metadata import version

    import jax
    import jaxlib

    from bigdl_tpu import Engine, native

    Engine.init()
    devices = Engine.devices()
    platforms = sorted({d.platform for d in devices})
    if platforms != ["tpu"]:
        print(f"chip_smoke: no TPU — Engine.devices() are on {platforms}; "
              f"this script only runs on the chip", file=sys.stderr)
        raise SystemExit(1)
    kind = devices[0].device_kind
    with open(PEAKS) as f:
        peaks = json.load(f)["devices"]
    check(kind in peaks, f"benchmarks/peaks.json has no entry for {kind!r}; "
          f"add it there with its source")
    say("attach", platform="tpu", device_kind=kind, count=len(devices),
        jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=version("libtpu"),
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
        cache_dir_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        peak_flops=peaks[kind]["flops_per_s"],
        peak_hbm_bytes_per_s=peaks[kind]["hbm_bytes_per_s"],
        native_available=native.native_available())
    return devices


# ---------------------------------------------------------- train-vision
def build_resnet50(batch: int, n_batches: int):
    """ResNet-50 as the benchmark's ResNet cell trains it: NHWC, the
    space-to-depth stem, uint8 pixels normalised on the device. Labels are
    folded onto 8 classes: random pixels teach nothing, but a marginal over a
    few classes is learned in a handful of steps, so 'the loss fell' is a
    property of the trainer and not of luck."""
    from bigdl_tpu import nn
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.nn import layout

    layout.set_image_format("NHWC")
    model = ResNet(1000, {"depth": 50, "dataSet": "ImageNet",
                          "conv1SpaceToDepth": True})
    model = nn.Sequential().add(nn.ImageNormalize()).add(model)
    rng = np.random.default_rng(0)
    batches = []
    for _ in range(n_batches):
        x = rng.integers(0, 256, size=(batch, 224, 224, 3)).astype(np.uint8)
        y = rng.integers(0, 1000, size=(batch,)).astype(np.int32)
        batches.append(MiniBatch(x, y % 8))
    return model, DataSet.array(batches), nn.ClassNLLCriterion()


def _leaf_delta(before, after) -> float:
    import jax
    return float(sum(
        np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)).sum()
        for a, b in zip(jax.tree_util.tree_leaves(before),
                        jax.tree_util.tree_leaves(after))))


def train_vision(batch: int = 256):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import Engine
    from bigdl_tpu.optim import SGD, LocalOptimizer, Trigger

    t0 = time.perf_counter()
    Engine.set_compute_dtype(jnp.bfloat16)
    fuse = 8    # a window is the whole dataset, whatever BIGDL_FUSE_STEPS says
    model, dataset, criterion = build_resnet50(batch, n_batches=fuse)
    before = jax.tree_util.tree_map(np.asarray, model.get_params())
    opt = (LocalOptimizer(model, dataset, criterion)
           .set_optim_method(SGD(learningrate=0.1, momentum=0.9,
                                 dampening=0.0))
           .set_fuse_steps(fuse))
    # iteration 1 alone: the per-step program, and the loss to beat
    opt.set_end_when(Trigger.max_iteration(1)).optimize()
    loss0 = float(opt.state["loss"])
    # then two full fused windows
    opt.set_end_when(Trigger.max_iteration(1 + 2 * fuse)).optimize()
    loss1 = float(opt.state["loss"])
    check(opt._window_cache is not None and opt._step_cache is not None,
          "both the per-step and the fused-window program must have run")
    check(np.isfinite(loss0) and np.isfinite(loss1),
          f"non-finite loss: {loss0} -> {loss1}")
    check(loss1 < loss0, f"loss did not fall: {loss0} -> {loss1}")
    delta = _leaf_delta(before, model.get_params())
    check(delta > 0, "parameters did not change")
    dev = Engine.devices()[0]
    say("train-vision", platform=dev.platform, model="resnet50", batch=batch,
        compute_dtype="bfloat16", fuse_steps=fuse, iterations=1 + 2 * fuse,
        loss_first=round(loss0, 4), loss_last=round(loss1, 4),
        param_abs_delta=round(delta, 3),
        seconds_compile_included=round(time.perf_counter() - t0, 1))


# -------------------------------------------------------------- train-lm
KERNELS = ("bigdl_flash_fwd", "bigdl_flash_bwd", "bigdl_layer_norm")


def _kernel_numerics(shape=(16, 8, 512, 64)) -> dict:
    """One forward and gradient of each Pallas kernel on the chip against its
    jnp reference on the same inputs: flash attention at ``shape`` and at
    (8, 16, 1024, 64), which the benchmark's GPT-2 medium cell trains at;
    LayerNorm at ``shape``'s rows."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.kernels.flash_attention import (
        _reference_attention, flash_attention)
    from bigdl_tpu.kernels.layernorm import (
        _reference_layer_norm, fused_layer_norm)

    rng = np.random.default_rng(0)
    out = {}

    def sq(fn):
        return lambda *a: jnp.sum(jnp.square(fn(*a).astype(jnp.float32)))

    for qkv_shape in (shape, (8, 16, 1024, 64)):
        for name, dt in (("bf16", jnp.bfloat16), ("f32", jnp.float32)):
            q, k, v = (jnp.asarray(rng.normal(size=qkv_shape), dt)
                       for _ in range(3))
            got = jax.jit(lambda a, b, c: flash_attention(a, b, c, True))(
                q, k, v)
            ggot = jax.jit(jax.grad(sq(lambda a, b, c: flash_attention(
                a, b, c, True)), argnums=(0, 1, 2)))(q, k, v)
            with jax.default_matmul_precision("highest"):
                f32 = [x.astype(jnp.float32) for x in (q, k, v)]
                ref = _reference_attention(*f32, True)
                gref = jax.grad(sq(lambda a, b, c: _reference_attention(
                    a, b, c, True)), argnums=(0, 1, 2))(*f32)
            errs = [rel_err(got, ref)] + [rel_err(a, b)
                                          for a, b in zip(ggot, gref)]
            out[f"flash_{name}_t{qkv_shape[2]}"] = [float(f"{e:.3g}")
                                                    for e in errs]
            check(max(errs) <= TOL_FLASH,
                  f"flash attention ({name}, {qkv_shape}) off its reference: "
                  f"fwd/dq/dk/dv errors {errs} > {TOL_FLASH}")

    n, h = shape[0] * shape[2], shape[1] * shape[3]
    # bf16 throughout (the model under mixed precision), fp32 throughout, and
    # bf16 activations under fp32 gamma/beta (fp32 out, as the reference)
    for name, dt, pdt, tol in (("bf16", jnp.bfloat16, jnp.bfloat16, TOL_LN_BF16),
                               ("f32", jnp.float32, jnp.float32, TOL_LN_F32),
                               ("mixed", jnp.bfloat16, jnp.float32, TOL_LN_BF16)):
        g = jnp.asarray(rng.normal(size=(h,)), pdt)
        b = jnp.asarray(rng.normal(size=(h,)), pdt)
        g32, b32 = g.astype(jnp.float32), b.astype(jnp.float32)
        # 13 rows: a count no 8-row tile divides, the case that used to fail
        for rows in (n, 13):
            x = jnp.asarray(rng.normal(size=(rows, h)), dt)
            x32 = x.astype(jnp.float32)
            got = jax.jit(lambda a: fused_layer_norm(a, g, b))(x)
            ggot = jax.jit(jax.grad(sq(lambda a: fused_layer_norm(
                a, g, b))))(x)
            ref = _reference_layer_norm(x32, g32, b32, 1e-5)
            gref = jax.grad(sq(lambda a: _reference_layer_norm(
                a, g32, b32, 1e-5)))(x32)
            check(got.dtype == jnp.result_type(dt, pdt),
                  f"LayerNorm ({name}) returned {got.dtype}")
            errs = [rel_err(got, ref), rel_err(ggot, gref)]
            out[f"layer_norm_{name}_{rows}"] = [float(f"{e:.3g}")
                                                for e in errs]
            check(max(errs) <= tol,
                  f"LayerNorm ({name}, {rows} rows) off its reference: "
                  f"fwd/dx errors {errs} > {tol}")
    return out


def routed_kernels(length: int = 512, block: int = 4) -> None:
    """What the routed block-diffusion decoder adds, on the chip against its
    plain forms: the flash kernels under the block-diffusion mask at
    (2, 32/4, 1024, 128), grouped-query heads at their own count, the
    grouped product over held groups with rows past them (``ragged_dot``), and
    the routed expert layer itself (top-4 of 16, 4 held) against the dense
    form: at seeded routing (one pass over its bound on rows) and with every
    pair routed to a held expert (twice the bound: the pass repeated)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.kernels.flash_attention import (
        BlockDiffusion, _reference_attention, flash_attention)
    from bigdl_tpu.kernels.grouped_matmul import grouped_matmul
    from bigdl_tpu.parallel.moe import MoE

    t0 = time.perf_counter()
    rng = np.random.default_rng(2)
    mask = BlockDiffusion(length, block)
    q, k, v = (jnp.asarray(rng.normal(size=(2, h, 2 * length, 128)), jnp.bfloat16)
               for h in (32, 4, 4))

    def sq(fn):
        return lambda *a: jnp.sum(jnp.square(fn(*a).astype(jnp.float32)))

    kernel = lambda a, b, c: flash_attention(a, b, c, False, None, mask)
    got = [jax.jit(kernel)(q, k, v)] + list(
        jax.jit(jax.grad(sq(kernel), argnums=(0, 1, 2)))(q, k, v))
    with jax.default_matmul_precision("highest"):
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        dense = lambda a, b, c: _reference_attention(a, b, c, mask)
        want = [dense(*f32)] + list(jax.grad(sq(dense), argnums=(0, 1, 2))(*f32))
    flash = [rel_err(a, b) for a, b in zip(got, want)]
    check(max(flash) <= TOL_FLASH, f"flash attention under {mask} off its "
          f"reference: fwd/dq/dk/dv errors {flash} > {TOL_FLASH}")

    sizes = jnp.asarray([300, 0, 1024, 213], jnp.int32)
    rows = jnp.asarray(rng.normal(size=(4096, 2048)), jnp.bfloat16)
    mats = jnp.asarray(0.02 * rng.normal(size=(4, 2048, 1536)), jnp.bfloat16)
    product = lambda force: lambda a, b: grouped_matmul(a, b, sizes, force)
    got = [jax.jit(product(None))(rows, mats)] + list(
        jax.jit(jax.grad(sq(product(None)), argnums=(0, 1)))(rows, mats))
    with jax.default_matmul_precision("highest"):
        f32 = [rows.astype(jnp.float32), mats.astype(jnp.float32)]
        want = [product(False)(*f32)] + list(
            jax.grad(sq(product(False)), argnums=(0, 1))(*f32))
    grouped = [rel_err(a, b) for a, b in zip(got, want)]
    check(max(grouped) <= TOL_FLASH, f"grouped product off ragged_dot: "
          f"fwd/drows/dmats errors {grouped} > {TOL_FLASH}")
    check(float(jnp.max(jnp.abs(got[0][int(sizes.sum()):]))) == 0.0,
          "rows past the held groups are not zero")

    tokens, width, hidden, experts, top_k, (first, count) = 2048, 1024, 512, 16, 4, (4, 4)
    layer = MoE(width, hidden, experts, router="topk", top_k=top_k, held=(first, count))
    here = (np.arange(experts) >= first) & (np.arange(experts) < first + count)

    def routed(p, x):
        return layer.apply(p, layer.get_state(), x)

    def dense(p, x):
        """Every held expert over every token with its routing weight; the
        router as the layer writes it, so that both choose the same experts."""
        probs = jax.nn.softmax(jnp.dot(x, p["w_gate"], preferred_element_type=jnp.float32), -1)
        top_p, top_e = jax.lax.top_k(probs, top_k)
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
        y = jnp.zeros_like(x)
        for e in range(count):
            weight = jnp.sum(jnp.where(top_e == first + e, top_p, 0.0), -1)
            h = x @ p["w_in"][e]
            y = y + weight[:, None] * ((jax.nn.silu(h[:, :hidden]) * h[:, hidden:]) @ p["w_out"][e])
        return y

    routed_layer = {}
    for towards, passes in (("seeded", 1.0), ("held", 2.0)):
        x = rng.normal(size=(tokens, width))
        p = {"w_gate": 0.05 * rng.normal(size=(width, experts)),
             "w_in": 0.03 * rng.normal(size=(count, width, 2 * hidden)),
             "w_out": 0.03 * rng.normal(size=(count, hidden, width))}
        if towards == "held":       # a column of ones carries a bias through the router
            x[:, 0], p["w_gate"][0] = 1.0, np.where(here, 50.0, -50.0)
        x, p = jnp.asarray(x, jnp.bfloat16), {k: jnp.asarray(v, jnp.bfloat16) for k, v in p.items()}
        y, state = jax.jit(routed)(p, x)
        grads = jax.jit(jax.grad(sq(lambda p, x: routed(p, x)[0]), argnums=(0, 1)))(p, x)
        with jax.default_matmul_precision("highest"):
            p32, x32 = {k: v.astype(jnp.float32) for k, v in p.items()}, x.astype(jnp.float32)
            want = [dense(p32, x32)] + jax.tree_util.tree_leaves(
                jax.grad(sq(dense), argnums=(0, 1))(p32, x32))
        got = [y] + jax.tree_util.tree_leaves(grads)
        # the column that carries the bias is no input: its gradient is the
        # router's alone, +-50 times terms that cancel, at the chip's one
        # bf16 pass a product (0.8 of the largest dx; the CPU reads 0.003)
        got[-1], want[-1] = got[-1][:, 1:], want[-1][:, 1:]
        errs = [rel_err(a, b) for a, b in zip(got, want)]
        check(max(errs) <= TOL_FLASH, f"routed layer ({towards}) off the dense form: "
              f"fwd/dw_gate/dw_in/dw_out/dx errors {errs} > {TOL_FLASH}")
        check(float(state["row_passes"]) == passes and float(state["dropped_fraction"]) == 0.0,
              f"routed layer ({towards}): {float(state['row_passes'])} passes for "
              f"{float(state['pairs_held'])} held pairs, dropped {float(state['dropped_fraction'])}")
        routed_layer[towards] = [float(f"{e:.3g}") for e in errs]
    say("routed-kernels", flash_block_diffusion_rel_err=[float(f"{e:.3g}") for e in flash],
        grouped_matmul_rel_err=[float(f"{e:.3g}") for e in grouped],
        routed_layer_rel_err=routed_layer,
        seconds_compile_included=round(time.perf_counter() - t0, 1))


def train_lm(batch: int = 16, seq: int = 512, steps: int = 8,
             require_kernels: bool = True):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import Engine
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.models.transformerlm import TransformerLM, lm_criterion
    from bigdl_tpu.obs.mfu import avals_of
    from bigdl_tpu.optim import Adam, LocalOptimizer, Trigger

    t0 = time.perf_counter()
    Engine.set_compute_dtype(jnp.bfloat16)
    model = TransformerLM(32000, embed_dim=512, num_heads=8, num_layers=6,
                          max_len=seq)
    criterion = lm_criterion()
    # a map a model can learn: 64 tokens in use, target a function of input
    rng = np.random.default_rng(1)
    xs = [rng.integers(0, 64, size=(batch, seq)).astype(np.int32)
          for _ in range(4)]
    batches = [MiniBatch(x, (7 * x + 3) % 64) for x in xs]
    dataset = DataSet.array(batches)
    before = jax.tree_util.tree_map(np.asarray, model.get_params())
    opt = (LocalOptimizer(model, dataset, criterion)
           .set_optim_method(Adam(learningrate=1e-3)))
    opt.set_end_when(Trigger.max_iteration(1)).optimize()
    loss0 = float(opt.state["loss"])
    opt.set_end_when(Trigger.max_iteration(steps)).optimize()
    loss1 = float(opt.state["loss"])
    check(np.isfinite(loss0) and np.isfinite(loss1),
          f"non-finite loss: {loss0} -> {loss1}")
    check(loss1 < loss0, f"loss did not fall: {loss0} -> {loss1}")
    check(_leaf_delta(before, model.get_params()) > 0,
          "parameters did not change")

    # the program the optimizer ran, as lowered for this backend: each kernel
    # must be in it as a Mosaic custom call, so neither the interpreter nor a
    # jnp reference can stand in for one
    # (lowered from shapes alone: nothing is placed a second time)
    text = opt._step_cache.lower(*avals_of((
        model.get_params(), model.get_state(), opt._final_ostate,
        np.int32(0), batches[0].input, batches[0].target,
        opt._base_rng))).as_text()
    calls = {k: text.count(f'kernel_name = "{k}"') for k in KERNELS}
    if require_kernels:
        check("tpu_custom_call" in text and all(calls.values()),
              f"kernels missing from the lowered train step: {calls}")
    numerics = _kernel_numerics() if require_kernels else {}
    dev = Engine.devices()[0]
    say("train-lm", platform=dev.platform, batch=batch, seq=seq, steps=steps,
        compute_dtype="bfloat16", loss_first=round(loss0, 4),
        loss_last=round(loss1, 4), mosaic_custom_calls=calls,
        kernel_rel_err=numerics,
        tolerances={"flash": TOL_FLASH, "layer_norm_bf16": TOL_LN_BF16,
                    "layer_norm_f32": TOL_LN_F32},
        seconds_compile_included=round(time.perf_counter() - t0, 1))


# ----------------------------------------------------------------- serve
def _first_divergence(lm, served, oracle, prompt_len: int):
    """Where a served sequence leaves greedy_generate's, and how far apart
    the model holds the two tokens there: their log-probabilities under one
    full forward over the common prefix, on the chip."""
    import jax.numpy as jnp

    i = int(np.argmax(served != oracle))
    check(i >= prompt_len, "the served sequence does not start with its prompt")
    logp = np.asarray(lm.forward(jnp.asarray(oracle[:i])[None, :]),
                      np.float32)[0, -1]
    return {"position": i, "generated_index": i - prompt_len,
            "oracle_token": int(oracle[i]), "served_token": int(served[i]),
            "logp_oracle": float(logp[oracle[i]]),
            "logp_served": float(logp[served[i]]),
            "logp_max": float(logp.max()),
            "gap": float(abs(logp[oracle[i]] - logp[served[i]]))}


def serve(vocab: int = 32000, embed_dim: int = 512, num_heads: int = 8,
          num_layers: int = 6, max_new: int = 16):
    import jax.numpy as jnp

    from bigdl_tpu import Engine, nn
    from bigdl_tpu.models.transformerlm import TransformerLM
    from bigdl_tpu.serving import ServingEngine

    t0 = time.perf_counter()
    Engine.set_compute_dtype(jnp.float32)
    lm = TransformerLM(vocab, embed_dim=embed_dim, num_heads=num_heads,
                       num_layers=num_layers, max_len=512).evaluate()
    buckets = (16, 32, 64)
    lengths = (5, 12, 12, 23, 23, 37, 37, 50)     # none a multiple of 8
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, vocab, (n,)).astype(np.int32) for n in lengths]
    eng = ServingEngine(lm, max_len=128, slots=8, buckets=buckets)
    try:
        handles = [eng.submit(p, max_new) for p in prompts]
        results = [h.result(timeout=900) for h in handles]
        stats = eng.stats()
    finally:
        eng.shutdown()
    check(stats["compiled_programs"] <= len(buckets) + 2,
          f"compiled {stats['compiled_programs']} programs for "
          f"{len(buckets)} buckets")
    equal, divergences = 0, []
    for p, r in zip(prompts, results):
        served = np.asarray(r.tokens)
        check(served.shape == (len(p) + max_new,),
              f"request of {len(p)} tokens returned shape {served.shape}")
        check(0 <= served.min() and served.max() < vocab,
              "served token outside the vocabulary")
        oracle = np.asarray(nn.greedy_generate(
            lm, jnp.asarray(p)[None, :], max_new))[0]
        if np.array_equal(served, oracle):
            equal += 1
        else:
            divergences.append({"prompt_len": len(p),
                                **_first_divergence(lm, served, oracle,
                                                    len(p))})
    dev = Engine.devices()[0]
    say("serve", platform=dev.platform, requests=len(prompts),
        prompt_lengths=list(lengths), max_new_tokens=max_new, slots=8,
        buckets=list(buckets), completed=len(results),
        compiled_programs=stats["compiled_programs"],
        equal_to_greedy_generate=equal, divergences=divergences,
        tie_tolerance_nats=TOL_TIE,
        seconds_compile_included=round(time.perf_counter() - t0, 1))
    worst = max((d["gap"] for d in divergences), default=0.0)
    check(worst <= TOL_TIE,
          f"served tokens left greedy_generate's where the model separates "
          f"them by {worst:.4f} nats (> {TOL_TIE}): {divergences}")


# ----------------------------------------------------------- all devices
def all_devices(batch: int = 256, steps: int = 3):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import Engine
    from bigdl_tpu.optim import SGD, DistriOptimizer, Trigger

    mesh = Engine.mesh()
    devices = list(mesh.devices.flat)
    n = len(devices)
    Engine.set_compute_dtype(jnp.bfloat16)
    for sync in ("allreduce", "zero1"):
        t0 = time.perf_counter()
        model, dataset, criterion = build_resnet50(batch, n_batches=2)
        opt = (DistriOptimizer(model, dataset, criterion, parameter_sync=sync)
               .set_optim_method(SGD(learningrate=0.1, momentum=0.9,
                                     dampening=0.0))
               .set_end_when(Trigger.max_iteration(steps)))
        opt.optimize()
        loss = float(opt.state["loss"])
        check(np.isfinite(loss), f"{sync}: non-finite loss {loss}")
        # the batch as the feed places it: one equal slice on every device
        inp, _ = opt._place_batch(next(iter(dataset.data(train=False))))
        shards = {s.device: s.data.shape for s in inp.addressable_shards}
        check(set(shards) == set(devices)
              and all(sh[0] == batch // n for sh in shards.values()),
              f"{sync}: batch not laid out over every device: {shards}")
        # the slot layout the compiled step pins on its inputs and outputs
        slot_sh = jax.tree_util.tree_leaves(opt._shardings[2])
        slots = jax.tree_util.tree_leaves(opt._final_ostate)
        sliced = sum(
            1 for sh, leaf in zip(slot_sh, slots)
            if np.ndim(leaf) and set(sh.device_set) == set(devices)
            and sh.shard_shape(np.shape(leaf))[0] * n == np.shape(leaf)[0])
        if sync == "zero1":
            check(sliced > 0 or n == 1,
                  "zero1: no optimizer slot is sliced over the devices")
        in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                  for d in devices]
        check(all(b > 0 for b in in_use),
              f"{sync}: a device reports no memory in use: {in_use}")
        say("all-devices", platform=devices[0].platform, sync=sync,
            devices=n, batch=batch, per_device_batch=batch // n, steps=steps,
            loss_last=round(loss, 4),
            slots_sliced_over_devices=f"{sliced}/{len(slots)}",
            bytes_in_use=in_use,
            seconds_compile_included=round(time.perf_counter() - t0, 1))


def main() -> int:
    t0 = time.perf_counter()
    import jax

    cache = dict.fromkeys(CACHE_EVENTS.values(), 0)

    def on_event(event, **_):
        if event in CACHE_EVENTS:
            cache[CACHE_EVENTS[event]] += 1

    jax.monitoring.register_event_listener(on_event)
    devices = attach()
    train_vision()
    train_lm()
    routed_kernels()
    serve()
    all_devices()
    say("done", seconds_total=round(time.perf_counter() - t0, 1),
        compile_cache=cache)
    print(json.dumps({"ok": True,
                      "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
