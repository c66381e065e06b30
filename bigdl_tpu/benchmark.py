"""Benchmark harness (packaged; repo-root ``bench.py`` is the driver-contract shim). Prints ONE JSON line on stdout:
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}``

Primary metric (BASELINE.md): ResNet-50 ImageNet images/sec/chip, measured through the
framework's OWN training loop (LocalOptimizer + PrefetchingFeed — triggers, feed, loss
fetch and all), not a hand-rolled step. Also reports an MFU estimate (analytic FLOPs
table: 2*MACs forward x3 for the training step, ÷ chip peak) and the bf16:fp32
throughput ratio (measured in a separate subprocess so a comparison-leg failure can
never discard a good primary number).

No chip, no number: the measurement runs in a SUBPROCESS with a bounded timeout (the
orchestrating parent never touches JAX, so the child is the one process that holds the
chip). When that leg fails — the device probe does not answer, the child times out or
dies — the orchestrator prints a record with ``"value": null`` and the reason, and exits
non-zero. It never runs a leg on a platform the caller did not ask for; a CPU run
happens only under an explicit ``JAX_PLATFORMS=cpu``.

``vs_baseline`` stays null: the reference mount has been empty every round so far, so
there is no citable denominator (BASELINE.md).
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
import time

# chip peak bf16 FLOP/s by device_kind substring — single-sourced from the
# always-on MFU accounting (obs/mfu.py) so the bench and the live train/mfu
# gauge can never disagree about a chip's peak
from bigdl_tpu.obs.mfu import PEAK_FLOPS as _PEAK_FLOPS, table_lookup

# Analytic training-step FLOPs per unit (image/word/token): forward FLOPs x3
# for fwd+bwd. Forward numbers from XLA cost analysis of the jitted forward on
# CPU (except ptb-lstm: cost analysis counts a lax.scan body ONCE, so the LSTM
# is hand-derived: 2 layers x 4 gates x 2 matmuls x 2*650*650 + decoder
# 2*650*10000 = 26.5 MF/word).
_ANALYTIC_STEP_FLOPS_PER_UNIT = {
    "resnet50": 3 * 2 * 4.09e9,       # 4.09 GMACs fwd @ 224x224
    "lenet": 3 * 2 * 0.43e6,
    "inception": 3 * 3.288e9,         # Inception-v1 fwd @ 224x224
    "vgg16": 3 * 0.498e9,             # VGG-16 CIFAR-10 variant fwd @ 32x32
    "ptb-lstm": 3 * 26.5e6,           # per word (bptt window element)
    "transformerlm": 3 * 77.5e6,      # per token @ T=512, d=512, L=6
}
# filled in after _long_lm_flops is defined (depends on BIGDL_BENCH_SEQ)

# (unit-plural, units per sample) — images are 1/sample; LM samples are windows
_MODEL_UNITS = {
    "resnet50": ("images", 1), "lenet": ("images", 1),
    "inception": ("images", 1), "vgg16": ("images", 1),
    "ptb-lstm": ("words", 35), "transformerlm": ("tokens", 512),
}

# Long-context training leg (round-4 verdict #3: tokens/sec + peak memory at
# T=4096/8192, flash vs XLA attention). T from BIGDL_BENCH_SEQ (the env
# propagates into the measured subprocess); BIGDL_BENCH_ATTN=flash|full picks
# the attention implementation under test.
def _parse_long_seq():
    """Lenient at import (a typo must not break UNRELATED legs); the error
    is raised at long-leg build time so ITS line carries the reason."""
    raw = os.environ.get("BIGDL_BENCH_SEQ", "4096")
    try:
        v = int(raw)
        if v < 8:
            raise ValueError
        return v, None
    except ValueError:
        return 4096, f"BIGDL_BENCH_SEQ must be an integer >= 8, got {raw!r}"


_LONG_SEQ, _LONG_SEQ_ERROR = _parse_long_seq()
_MODEL_UNITS["transformerlm-long"] = ("tokens", _LONG_SEQ)


def _long_lm_flops(t: int, d: int = 512, n_layers: int = 6,
                   v: int = 32000) -> float:
    """Analytic fwd FLOPs/token x3 for the long-context TransformerLM:
    2·params for the weight matmuls (qkvo 4d² + mlp 8d² per layer, d·v
    head) + 4·T·d per layer for QKᵀ/AV (full-matrix convention — causal
    flash computes ~half, so its MFU reads conservatively)."""
    matmul_params = 12 * n_layers * d * d + d * v
    attn = 4 * t * d * n_layers
    return 3.0 * (2 * matmul_params + attn)


_ANALYTIC_STEP_FLOPS_PER_UNIT["transformerlm-long"] = _long_lm_flops(_LONG_SEQ)


def _long_attn() -> str:
    """The long leg's attention implementation, validated — ONE source for
    both the model build and the emitted line (a drifted default would
    mis-attribute the A/B number). 'auto' is rejected: the leg IS the
    flash-vs-XLA comparison."""
    impl = os.environ.get("BIGDL_BENCH_ATTN", "flash")
    if impl not in ("flash", "full"):
        raise ValueError(f"BIGDL_BENCH_ATTN must be flash|full for the "
                         f"long-context leg, got {impl!r}")
    return impl


_REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _provenance() -> dict:
    """timestamp + commit stamped onto every emitted line. The commit is
    absent where the tree is not a git checkout (an installed wheel, the chip
    machine's copy)."""
    out = {"timestamp": datetime.datetime.now(datetime.timezone.utc)
           .strftime("%Y-%m-%dT%H:%M:%SZ")}
    try:
        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10, cwd=_REPO_DIR)
        if rev.returncode == 0:
            out["git_commit"] = rev.stdout.strip()
    except (OSError, subprocess.SubprocessError):
        # a hung git (TimeoutExpired) must never cost us a measured number
        pass
    return out


# per-model default batch (samples/step) when --batch is not given
_DEFAULT_BATCH = {"resnet50": 256, "lenet": 256, "inception": 256,
                  "vgg16": 512, "ptb-lstm": 64, "transformerlm": 16,
                  "transformerlm-long": 1}


# HBM bandwidth by chip (roofline denominator for the ablation leg);
# alias list mirrors _PEAK_FLOPS — first substring match wins
_PEAK_HBM_BW = (("v6", 1640e9),
                ("v5p", 2765e9),
                ("v5 lite", 819e9), ("v5e", 819e9), ("v5litepod", 819e9),
                ("v5", 2765e9),
                ("v4", 1228e9),
                ("v3", 900e9),
                ("v2", 700e9))


def _peak_lookup(table, device_kind: str):
    """Table peak for ``device_kind``. The bench reads the tables only —
    ``BIGDL_PEAK_FLOPS`` is the live gauge's override, not a measurement's.
    A CPU has no peak (MFU is then null, under a caller-chosen
    ``JAX_PLATFORMS=cpu``); any other device the table does not know is an
    error, not a default."""
    peak = table_lookup(table, device_kind)
    if peak is None and device_kind.lower() != "cpu":
        raise ValueError(f"no peak entry for device_kind {device_kind!r}; "
                         f"add it to the table with its source")
    return peak


def _peak_flops(device_kind: str):
    return _peak_lookup(_PEAK_FLOPS, device_kind)


def _peak_hbm(device_kind: str):
    return _peak_lookup(_PEAK_HBM_BW, device_kind)


# Models the bench runs channels-last (the TPU-native fast path; numerics
# pinned equal to NCHW by tests/test_layout_nhwc.py). LeNet stays NCHW — its
# front Reshape([1,28,28]) hard-codes the reference layout, and it's a
# CPU-trivial config anyway. Opt out with BIGDL_BENCH_LAYOUT=nchw (reference-
# parity layout), BIGDL_BENCH_S2D=0 (plain 7x7 stride-2 stem).
_NHWC_MODELS = {"resnet50", "inception", "vgg16"}


def _bench_layout(model_name: str):
    """Layout the bench pins for ``model_name``: NHWC/NCHW for image models,
    None for sequence models (layout is irrelevant — leave the process
    setting alone)."""
    mode = os.environ.get("BIGDL_BENCH_LAYOUT", "auto").lower()
    if mode not in ("auto", "nchw", "nhwc"):
        raise ValueError(
            f"BIGDL_BENCH_LAYOUT must be auto|nchw|nhwc, got {mode!r}")
    if model_name in ("ptb-lstm", "transformerlm", "transformerlm-long"):
        return None
    if mode == "nchw" or model_name not in _NHWC_MODELS:
        return "NCHW"
    return "NHWC"


def _build(model_name: str, batch: int, n_batches: int, dtype: str):
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.nn import layout
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.sample import MiniBatch

    fmt = _bench_layout(model_name)
    if fmt is not None:
        layout.set_image_format(fmt)
    nhwc = fmt == "NHWC"

    def _img(c, h, w):
        return (batch, h, w, c) if nhwc else (batch, c, h, w)

    def _with_normalize(m, n_ch):
        # TPU-native input path: the feed stays uint8 (4x less wire traffic
        # than fp32 — what a real decode pipeline ships) and normalization
        # runs on device, fused into the first conv (nn.ImageNormalize).
        norm = (nn.ImageNormalize(mean=(0.1307,), std=(0.3081,)) if n_ch == 1
                else nn.ImageNormalize())
        return nn.Sequential().add(norm).add(m)

    criterion = nn.ClassNLLCriterion()
    seq = None
    if model_name == "resnet50":
        from bigdl_tpu.models.resnet import ResNet
        s2d = os.environ.get("BIGDL_BENCH_S2D", "1") != "0"
        model = ResNet(1000, {"depth": 50, "dataSet": "ImageNet",
                              "conv1SpaceToDepth": s2d})
        shape, n_classes = _img(3, 224, 224), 1000
    elif model_name == "lenet":
        from bigdl_tpu.models.lenet import LeNet5
        model = LeNet5(10)
        shape, n_classes = (batch, 1, 28, 28), 10
    elif model_name == "inception":
        from bigdl_tpu.models.inception import Inception_v1_NoAuxClassifier
        model = Inception_v1_NoAuxClassifier(1000, has_dropout=False)
        shape, n_classes = _img(3, 224, 224), 1000
    elif model_name == "vgg16":
        from bigdl_tpu.models.vgg import VggForCifar10
        model = VggForCifar10(10, has_dropout=False)
        shape, n_classes = _img(3, 32, 32), 10
    elif model_name == "ptb-lstm":
        from bigdl_tpu.models.rnn import PTBModel
        model = PTBModel(10000, 650, num_layers=2)
        seq, n_classes = _MODEL_UNITS[model_name][1], 10000
        shape = (batch, seq)
        criterion = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                                 size_average=True)
    elif model_name == "transformerlm":
        from bigdl_tpu.models.transformerlm import TransformerLM, lm_criterion
        seq, n_classes = _MODEL_UNITS[model_name][1], 32000
        # BIGDL_BENCH_FUSED_HEAD=1: A/B the chunked-vocab loss head (the
        # (B*T, 32k) logits tensor never materializes in training)
        fused = os.environ.get("BIGDL_BENCH_FUSED_HEAD", "0") == "1"
        model = TransformerLM(n_classes, embed_dim=512, num_heads=8,
                              num_layers=6, max_len=seq, fused_head=fused)
        shape = (batch, seq)
        criterion = lm_criterion(fused_head=fused)
    elif model_name == "transformerlm-long":
        # long-context training leg (verdict #3): flash vs XLA attention at
        # T = BIGDL_BENCH_SEQ; per-block remat + fused head keep the step
        # activation-bound, not logits-bound
        from bigdl_tpu.models.transformerlm import TransformerLM, lm_criterion
        if _LONG_SEQ_ERROR:
            raise ValueError(_LONG_SEQ_ERROR)
        seq, n_classes = _MODEL_UNITS[model_name][1], 32000
        impl = _long_attn()
        fused = os.environ.get("BIGDL_BENCH_FUSED_HEAD", "1") == "1"
        model = TransformerLM(n_classes, embed_dim=512, num_heads=8,
                              num_layers=6, max_len=seq, fused_head=fused,
                              attention_impl=impl, remat=True)
        shape = (batch, seq)
        criterion = lm_criterion(fused_head=fused)
    else:
        raise ValueError(f"unknown model {model_name!r}")

    if seq is None:
        n_ch = shape[3] if nhwc else shape[1]
        model = _with_normalize(model, n_ch)

    rng = np.random.default_rng(0)
    batches = []
    for _ in range(n_batches):
        if seq is None:  # image models: uint8 pixels (device-side normalize)
            x = rng.integers(0, 256, size=shape).astype(np.uint8)
            y = rng.integers(0, n_classes, size=(batch,)).astype(np.int32)
        else:  # language models: token ids in, next-token ids out
            x = rng.integers(0, n_classes, size=shape).astype(np.int32)
            y = rng.integers(0, n_classes, size=shape).astype(np.int32)
        batches.append(MiniBatch(x, y))
    return model, DataSet.array(batches), criterion


def _bench_fuse_steps() -> int:
    """Fused-window size for the bench's training legs (BIGDL_FUSE_STEPS,
    default 8 — the bench's in-memory dataset is 8 batches, so K=8 makes each
    epoch exactly one fused dispatch). 1 disables fusion."""
    raw = os.environ.get("BIGDL_FUSE_STEPS", "8")
    try:
        v = int(raw)
        if v < 1:
            raise ValueError
        return v
    except ValueError:
        raise ValueError(f"BIGDL_FUSE_STEPS must be an integer >= 1, got {raw!r}")


def _measure(model_name: str, batch: int, iters: int, warmup: int,
             dtype: str, streamed: bool = False,
             fuse_steps: int | None = None) -> dict:
    """Train `warmup` iters (compile + steady-state), then time `iters` more
    through the same LocalOptimizer (compiled-step cache keeps it warm).

    ``streamed=True`` disables the device batch cache, so every step pays the
    host→device transfer on the feed path (prefetch-overlapped) — the
    fresh-data-every-step number, vs the cached-RDD-analog headline.

    ``fuse_steps`` > 1 runs the timed leg through the fused multi-step
    dispatch path (one jitted scan per K steps) and ALSO times a per-step
    (K=1) comparison leg on the same warm optimizer, so the emitted line
    carries both the fused and the classic loop numbers."""
    import jax.numpy as jnp

    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.utils.engine import Engine

    if streamed:
        os.environ["BIGDL_DEVICE_CACHE"] = "0"
    Engine.reset()
    Engine.init(compute_dtype=jnp.bfloat16 if dtype == "bf16" else jnp.float32)
    dev = Engine.devices()[0]

    fuse = _bench_fuse_steps() if fuse_steps is None else fuse_steps
    model, dataset, criterion = _build(model_name, batch, n_batches=8, dtype=dtype)
    opt = LocalOptimizer(model, dataset, criterion)
    opt.set_optim_method(SGD(learningrate=0.01, momentum=0.9, dampening=0.0))
    opt.set_fuse_steps(fuse)
    opt.log_every = 10 ** 9  # no per-iter logging during warmup

    # with fusion the warmup must cover the per-step first window PLUS at
    # least one full fused window, so both programs are compiled before the
    # timed leg opens
    warmup = max(warmup, 2 * fuse) if fuse > 1 else warmup
    opt.set_end_when(Trigger.max_iteration(warmup))
    opt.optimize()

    # The loop logs windowed throughput; one window ending exactly at the last
    # iteration covers the post-warmup steps and EXCLUDES optimize()'s one-time
    # costs (first-step/window sync starts the window) and end-of-run teardown
    # (full param/state device_get) from the timing. Optimizer state (momentum)
    # carries over — optimize() on the same instance is a continuation.
    opt.log_every = warmup + iters
    opt.set_end_when(Trigger.max_iteration(warmup + iters))
    t0 = time.perf_counter()
    opt.optimize()
    dt = time.perf_counter() - t0
    unit, per_sample = _MODEL_UNITS.get(model_name, ("records", 1))
    samples_per_sec = opt.state.get("throughput") or (batch * iters / dt)
    units_per_sec = samples_per_sec * per_sample

    # per-step (K=1) comparison leg on the same warm optimizer: the classic
    # loop's number, so fused-vs-per-step is measured in ONE process on the
    # same compiled step
    perstep_units_per_sec = None
    if fuse > 1:
        n2 = max(iters // 2, 5)
        start = warmup + iters
        opt.set_fuse_steps(1)
        opt.log_every = start + n2
        opt.set_end_when(Trigger.max_iteration(start + n2))
        t1 = time.perf_counter()
        opt.optimize()
        dt2 = time.perf_counter() - t1
        sps2 = opt.state.get("throughput") or (batch * n2 / dt2)
        perstep_units_per_sec = sps2 * per_sample
        opt.set_fuse_steps(fuse)

    # device peak-memory telemetry (the long-context leg's memory claim needs
    # a measured number, not a trace assertion). Read IMMEDIATELY after the
    # timed training window: the direct-step cross-check below device_puts a
    # second copy of params/opt-state and would inflate the reading by
    # hundreds of MB. Absent on backends without memory_stats.
    peak_hbm_mb = None
    try:
        stats = dev.memory_stats()
        if stats and stats.get("peak_bytes_in_use"):
            peak_hbm_mb = round(stats["peak_bytes_in_use"] / 2 ** 20, 1)
    except Exception:
        pass

    # Direct-step cross-check leg (round-2 verdict item 1): drive the SAME
    # compiled step raw — pre-placed fixed batch, loss fetched only at the end.
    # This is the framework's step capability; if the loop number diverges from
    # it the harness must say so instead of publishing the worse one as truth.
    # Guarded: a cross-check failure must never discard the measured loop number.
    # Skipped for the streamed leg: feeding IS what that leg measures.
    step_units_per_sec, step_error = None, None
    if not streamed:
        try:
            step_units_per_sec = _measure_direct_step(opt, batch, iters) * per_sample
        except Exception as e:
            step_error = f"{type(e).__name__}: {e}"[:300]

    # analytic FLOPs per training step (fwd FLOPs x3 fwd+bwd) — BASELINE.md MFU
    # convention; re-lowering the compiled step for XLA cost analysis would pay
    # a second full compile for a number that should be shape-derived anyway
    per_unit = _ANALYTIC_STEP_FLOPS_PER_UNIT.get(model_name)
    flops_per_step = per_unit * batch * per_sample if per_unit else None

    peak = _peak_flops(dev.device_kind)

    def _mfu(ups):
        if not (flops_per_step and peak and ups):
            return None
        return flops_per_step * (ups / (batch * per_sample)) / peak

    return {
        "unit": unit,
        "units_per_sec": units_per_sec,
        "units_per_sec_perstep": perstep_units_per_sec,
        "fuse_steps": fuse,
        "units_per_sec_step": step_units_per_sec,
        "step_leg_error": step_error,
        "mfu": _mfu(units_per_sec),
        "mfu_step": _mfu(step_units_per_sec),
        "flops_per_step": flops_per_step,
        "peak_hbm_mb": peak_hbm_mb,
        "device_kind": dev.device_kind,
        "platform": dev.platform,
        "peak_flops": peak,
        "layout": _bench_layout(model_name),
        "feed_wait_ms": 1e3 * opt.metrics.summary().get("feed", 0.0),
    }


def _placed_step_inputs(opt):
    """Device-place everything the compiled step consumes: params, module
    state, optimizer state (post-run if available), one fixed batch, rng."""
    import jax

    from bigdl_tpu.utils.random_generator import RandomGenerator

    model, method = opt.model, opt._effective_method()
    params = jax.device_put(model.get_params())
    mstate = jax.device_put(model.get_state())
    ostate = jax.device_put(getattr(opt, "_final_ostate", None)
                            or method.init_state(params))
    inp = target = None
    for b in opt.dataset.data(train=True):
        inp = jax.device_put(b.input)
        target = jax.device_put(b.target)
        break
    return params, mstate, ostate, inp, target, RandomGenerator.next_key()


def _measure_direct_step(opt, batch: int, iters: int) -> float:
    """Drive the optimizer's own compiled train step in a bare loop: warm steps,
    then `iters` timed dispatches with ONE terminal loss fetch as the sync point.
    Measures step capability with zero loop/feed/logging overhead."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    step_fn = opt._step_cache
    params, mstate, ostate, inp, target, base_rng = _placed_step_inputs(opt)

    def run(n, start):
        nonlocal params, mstate, ostate
        loss = None
        for i in range(n):
            step_idx = jnp.asarray(start + i, jnp.int32)
            params, mstate, ostate, loss = step_fn(
                params, mstate, ostate, step_idx, inp, target, base_rng)
        return loss

    # warm: absorb placement + any recompile, and sync before timing
    float(jax.device_get(run(2, 0)))
    t0 = time.perf_counter()
    loss = run(iters, 2)
    float(jax.device_get(loss))  # terminal sync — the only host round trip
    dt = time.perf_counter() - t0
    return batch * iters / dt


def _measure_int8_infer(model_name: str, batch: int, iters: int) -> dict:
    """Inference micro-bench: bf16 forward vs int8-quantized forward on the
    same model (bigquant-analog done-criterion: int8 must not be slower)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.utils.engine import Engine

    Engine.reset()
    Engine.init(compute_dtype=jnp.bfloat16)
    model, dataset, _ = _build(model_name, batch, n_batches=1, dtype="bf16")
    model.evaluate()
    qmodel = model.quantize().evaluate()
    # the model's real input (image tensor or int32 token ids) comes from the
    # same builder the training legs use — no per-model shape special-casing
    x = jax.device_put(next(dataset.data(train=False)).input)

    def timed(m, cast_bf16):
        params = jax.device_put(m.get_params())
        mstate = jax.device_put(m.get_state())

        def fwd(p, s, xx):
            if cast_bf16:
                from bigdl_tpu.nn.precision import cast_floating
                p = cast_floating(p, jnp.bfloat16)
                xx = cast_floating(xx, jnp.bfloat16)
            out, _ = m.apply(p, s, xx, training=False, rng=None)
            return out
        jit_fwd = jax.jit(fwd)
        jax.block_until_ready(jit_fwd(params, mstate, x))  # compile
        float(jnp.sum(jit_fwd(params, mstate, x)))         # sync
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = jit_fwd(params, mstate, x)
        float(jnp.sum(out))  # terminal sync
        return batch * iters / (time.perf_counter() - t0)

    wmodel = model.quantize(mode="weight_only").evaluate()
    from bigdl_tpu.nn.quantized import calibrate
    smodel = model.quantize(mode="static").evaluate()
    calibrate(smodel, [np.asarray(x)])
    bf16_ips = timed(model, cast_bf16=True)
    int8_ips = timed(qmodel, cast_bf16=False)
    wonly_ips = timed(wmodel, cast_bf16=True)
    static_ips = timed(smodel, cast_bf16=False)
    return {"bf16_infer_ips": round(bf16_ips, 1),
            "int8_infer_ips": round(int8_ips, 1),
            "int8_bf16_ratio": round(int8_ips / bf16_ips, 2),
            "int8_weight_only_ips": round(wonly_ips, 1),
            "weight_only_bf16_ratio": round(wonly_ips / bf16_ips, 2),
            "int8_static_ips": round(static_ips, 1),
            "static_bf16_ratio": round(static_ips / bf16_ips, 2)}


def _measure_decode_infer(batch: int, prompt_len: int = 32,
                          decode_length: int = 96) -> dict:
    """LM decode serving leg: KV-cached greedy_generate tokens/sec vs the
    uncached static-block beam-1 search on the same TransformerLM — the
    O(L) vs O(L^2) per-token trade, measured."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.models.transformerlm import TransformerLM
    from bigdl_tpu.utils.engine import Engine

    Engine.reset()
    Engine.init(compute_dtype=jnp.bfloat16)
    total = prompt_len + decode_length
    lm = TransformerLM(32000, embed_dim=512, num_heads=8, num_layers=6,
                       max_len=total).evaluate()
    prompt = jnp.asarray(np.random.default_rng(0)
                         .integers(0, 32000, (batch, prompt_len)), jnp.int32)

    def timed(fn, reps=3):
        jax.block_until_ready(fn())  # compile + warm
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        jax.block_until_ready(out)
        return batch * decode_length * reps / (time.perf_counter() - t0)

    cached_tps = timed(lambda: nn.greedy_generate(lm, prompt, decode_length))
    bs = nn.SequenceBeamSearch(lm, 1, eos_id=-1,
                               decode_length=decode_length).evaluate()
    uncached_tps = timed(lambda: bs.forward(prompt)[1])
    beam_tps = timed(lambda: nn.beam_generate(
        lm, prompt, decode_length, beam_size=4, eos_id=-1)[0])
    return {"batch": batch, "prompt_len": prompt_len,
            "decode_length": decode_length,
            "cached_decode_tokens_per_sec": round(cached_tps, 1),
            "uncached_decode_tokens_per_sec": round(uncached_tps, 1),
            "cached_uncached_ratio": round(cached_tps / uncached_tps, 2),
            "cached_beam4_tokens_per_sec": round(beam_tps, 1)}


def _measure_eval(model_name: str, batch: int, iters: int) -> dict:
    """Eval-throughput leg: Evaluator.test through the device-resident
    fused-window path (BIGDL_EVAL_FUSE_STEPS stacked batches per jitted
    forward+fold scan, O(1) metric scalars fetched per pass) vs the per-batch
    path (fuse_steps=1) on the same warm model — plus the honest d2h
    accounting (``val_fetch_bytes_per_image``: accuracy-only eval fetches a
    couple of scalars per PASS, so this reads ~0, vs 4 x num_classes bytes
    per image when logits come home)."""
    import jax.numpy as jnp

    from bigdl_tpu.optim.evaluator import Evaluator, eval_fuse_steps
    from bigdl_tpu.optim.validation import Top1Accuracy
    from bigdl_tpu.utils.engine import Engine

    Engine.reset()
    Engine.init(compute_dtype=jnp.bfloat16)
    dev = Engine.devices()[0]
    n_batches = 8
    fuse = eval_fuse_steps(os.environ.get("BIGDL_EVAL_FUSE_STEPS", "8"))
    model, dataset, _ = _build(model_name, batch, n_batches=n_batches,
                               dtype="bf16")
    model.evaluate()
    evaluator = Evaluator(model)
    methods = [Top1Accuracy()]
    total = batch * n_batches

    def timed(fuse_steps):
        evaluator.test(dataset, methods, fuse_steps=fuse_steps)  # compile+warm
        t0 = time.perf_counter()
        for _ in range(iters):
            evaluator.test(dataset, methods, fuse_steps=fuse_steps)
        return total * iters / (time.perf_counter() - t0), evaluator.last_stats

    fused_sps, fused_stats = timed(fuse)
    perstep_sps, perstep_stats = timed(1)
    unit, per_sample = _MODEL_UNITS.get(model_name, ("records", 1))
    return {
        "value": round(fused_sps * per_sample, 1),
        "unit": f"{unit}/sec",
        "batch": batch,
        "dtype": "bf16",
        "eval_fuse_steps": fuse,
        f"eval_{unit}_per_sec_fused": round(fused_sps * per_sample, 1),
        f"eval_{unit}_per_sec_perstep": round(perstep_sps * per_sample, 1),
        "eval_fused_speedup": (round(fused_sps / perstep_sps, 3)
                               if perstep_sps else None),
        "val_fetch_bytes_per_image": round(
            fused_stats["fetch_bytes"] / total, 4),
        "val_fetch_bytes_per_image_perstep": round(
            perstep_stats["fetch_bytes"] / total, 4),
        "val_wait_ms": round(fused_stats["wait_ms"], 2),
        "fused_windows": fused_stats["fused_windows"],
        "device_kind": dev.device_kind,
        "platform": dev.platform,
    }


def _measure_pipeline(batch: int) -> dict:
    """Host input-pipeline leg: decode→augment→stack images/sec over a
    synthetic image folder, measured through the framework's own dataset
    pipeline (``DataSet.image_folder >> vision transformers >>
    SampleToMiniBatch``) at ``BIGDL_DATA_WORKERS`` = 0 (serial legacy chain),
    1, 4, and ``auto`` — plus per-stage ms so a regression in decode, augment
    or stack shows up as ITS stage, not a mystery slowdown. Host-only: no
    accelerator is touched, so this leg also runs on machines with no chip.

    Note the parallel legs can only beat serial when the host has cores to
    spare — ``cpu_count`` is emitted with the line so a flat speedup on a
    1-core container reads as the environment, not a regression."""
    import shutil
    import tempfile

    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.image_folder import write_synthetic_image_folder
    from bigdl_tpu.dataset.parallel import data_workers
    from bigdl_tpu.dataset.profiling import feed_stats, stage_deltas_ms
    from bigdl_tpu.dataset.sample import SampleToMiniBatch
    from bigdl_tpu.transform.vision.image import (
        ChannelNormalize, ImageFrameToSample, MatToTensor, RandomCrop,
        RandomHFlip, Resize,
    )
    from bigdl_tpu.utils.random_generator import RandomGenerator

    n_images = int(os.environ.get("BIGDL_BENCH_PIPELINE_IMAGES", "512"))
    size = 128
    tmp = tempfile.mkdtemp(prefix="bigdl-pipe-bench-")
    try:
        write_synthetic_image_folder(tmp, n_classes=4,
                                     n_per_class=max(n_images // 4, 1),
                                     size=size)

        def build():
            # fresh pipeline per leg (fresh pools/plans/ring); reseeded so the
            # transformer salt sequence restarts identically each leg
            RandomGenerator.set_seed(42)
            return (DataSet.image_folder(tmp, num_workers=4)
                    >> Resize(112, 112)
                    >> RandomCrop(96, 96)
                    >> RandomHFlip()
                    >> ChannelNormalize((123.0, 117.0, 104.0),
                                        (58.4, 57.1, 57.4))
                    >> MatToTensor()
                    >> ImageFrameToSample()
                    >> SampleToMiniBatch(batch, pad_last=False))

        def run(workers) -> tuple[float, dict]:
            prev = os.environ.get("BIGDL_DATA_WORKERS")
            os.environ["BIGDL_DATA_WORKERS"] = str(workers)
            try:
                ds = build()
                for b in ds.data(train=True):   # warm: page cache, pools
                    b.recycle()
                snap = feed_stats.snapshot()
                n = 0
                t0 = time.perf_counter()
                for b in ds.data(train=True):
                    n += b.valid
                    b.recycle()   # steady-state ring reuse, as the feed does
                dt = time.perf_counter() - t0
                stages = {s: round(d["ms"], 3)
                          for s, d in stage_deltas_ms(snap).items()}
                return (n / dt if dt > 0 else 0.0), stages
            finally:
                if prev is None:
                    os.environ.pop("BIGDL_DATA_WORKERS", None)
                else:
                    os.environ["BIGDL_DATA_WORKERS"] = prev

        serial_ips, serial_stages = run(0)
        w1_ips, _ = run(1)
        w4_ips, w4_stages = run(4)
        os.environ["BIGDL_DATA_WORKERS"] = "auto"
        try:
            auto_n = data_workers()
        finally:
            os.environ.pop("BIGDL_DATA_WORKERS", None)
        wauto_ips, wauto_stages = run("auto")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    return {
        "value": round(w4_ips, 1),
        "unit": "images/sec",
        "batch": batch,
        "n_images": n_images,
        "image_size": size,
        "cpu_count": os.cpu_count(),
        "pipeline_images_per_sec": round(w4_ips, 1),
        "pipeline_images_per_sec_serial": round(serial_ips, 1),
        "pipeline_images_per_sec_w1": round(w1_ips, 1),
        "pipeline_images_per_sec_w4": round(w4_ips, 1),
        "pipeline_images_per_sec_wauto": round(wauto_ips, 1),
        "workers_auto": auto_n,
        "pipeline_parallel_speedup": (round(w4_ips / serial_ips, 3)
                                      if serial_ips else None),
        "stage_ms_w4": w4_stages,
        "stage_ms_wauto": wauto_stages,
        "stage_ms_serial": serial_stages,
    }


def _measure_stream_bench(batch: int) -> dict:
    """Streaming-data-plane leg: a synthetic image folder is packed into
    ``BIGDL_STREAM_SHARDS`` ``.bdlrec`` shards, then streamed through
    ``DataSet.stream_shards`` (window shuffle + decoded-sample cache) twice —
    the COLD epoch decodes every record and builds the cache, the WARM epoch
    serves it back from the mmap. The published gate: warm ≥ 3× cold, with
    the ``decode`` stage absent from warm-epoch ``feed_stats`` (the ``cache``
    stage takes its place). Host-only — no accelerator is touched."""
    import shutil
    import tempfile

    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.image_folder import write_synthetic_image_folder
    from bigdl_tpu.dataset.profiling import feed_stats, stage_deltas_ms
    from bigdl_tpu.dataset.recordio import write_image_records
    from bigdl_tpu.dataset.sample import SampleToMiniBatch
    from bigdl_tpu.obs.registry import registry as obs_registry
    from bigdl_tpu.transform.vision.image import (
        ChannelNormalize, ImageFrameToSample, MatToTensor, Resize,
    )
    from bigdl_tpu.utils.random_generator import RandomGenerator

    n_images = int(os.environ.get("BIGDL_BENCH_STREAM_IMAGES", "512"))
    n_shards = int(os.environ.get("BIGDL_STREAM_SHARDS", "4"))
    size = 128
    tmp = tempfile.mkdtemp(prefix="bigdl-stream-bench-")
    try:
        img_root = os.path.join(tmp, "images")
        write_synthetic_image_folder(img_root, n_classes=4,
                                     n_per_class=max(n_images // 4, 1),
                                     size=size)
        shards = write_image_records(img_root, os.path.join(tmp, "shard"),
                                     shards=n_shards)
        cache_dir = os.path.join(tmp, "sample-cache")

        RandomGenerator.set_seed(42)
        # the cache stores DECODED + FUSED-TRANSFORM outputs: the whole
        # deterministic per-image chain (decode→resize→normalize→to-tensor→
        # Sample) runs inside the stream decoder, so a warm epoch replays
        # finished Samples from the mmap and only batch stacking remains.
        # (Random augments must stay OUTSIDE a cached decoder — caching
        # would freeze their draws.)
        from bigdl_tpu.dataset.recordio import image_record_decoder
        pre = [Resize(112, 112),
               ChannelNormalize((123.0, 117.0, 104.0), (58.4, 57.1, 57.4)),
               MatToTensor()]

        def decode_to_sample(payload):
            f = image_record_decoder(payload)
            for t in pre:
                f = t.transform_feature(f)
            return ImageFrameToSample._to_sample(f)

        ds = (DataSet.stream_shards(shards, decoder=decode_to_sample,
                                    num_workers=4,
                                    cache=True, cache_dir=cache_dir)
              >> SampleToMiniBatch(batch, pad_last=False))
        ds.shuffle()

        def epoch() -> tuple[float, dict]:
            snap = feed_stats.snapshot()
            n = 0
            t0 = time.perf_counter()
            for b in ds.data(train=True):
                n += b.valid
                b.recycle()
            dt = time.perf_counter() - t0
            stages = {s: round(d["ms"], 3)
                      for s, d in stage_deltas_ms(snap).items()}
            return (n / dt if dt > 0 else 0.0), stages

        hits0 = obs_registry.counter("feed/cache_hit").value
        cold_ips, cold_stages = epoch()     # decodes + builds the cache
        warm_ips, warm_stages = epoch()     # served from the mmap
        cache_hits = obs_registry.counter("feed/cache_hit").value - hits0
        cache_bytes = obs_registry.counter("feed/cache_bytes").value
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    return {
        "value": round(warm_ips, 1),
        "unit": "images/sec",
        "batch": batch,
        "n_images": n_images,
        "n_shards": n_shards,
        "image_size": size,
        "cpu_count": os.cpu_count(),
        "stream_images_per_sec_cold": round(cold_ips, 1),
        "stream_images_per_sec_warm": round(warm_ips, 1),
        "cache_speedup": round(warm_ips / cold_ips, 3) if cold_ips else None,
        "cache_hits": cache_hits,
        "cache_bytes": cache_bytes,
        # the acceptance signal: a warm epoch must never touch the decode pool
        "decode_absent_warm": "decode" not in warm_stages,
        "stage_ms_cold": cold_stages,
        "stage_ms_warm": warm_stages,
    }


def _measure_obs(batch: int, iters: int) -> dict:
    """Observability-overhead leg (CPU LeNet smoke): the SAME training loop
    with the span tracer off vs on, plus a validity check of the artifacts
    the traced leg produced (Chrome trace loads as JSON, the JSONL event log
    carries a run_report). The published gate: tracing on costs < 3% of
    images/sec — observability that taxes the hot path does not get left
    enabled, and then it observes nothing."""
    import json
    import shutil
    import tempfile

    import jax.numpy as jnp

    from bigdl_tpu.obs import trace
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.utils.engine import Engine

    Engine.reset()
    Engine.init(compute_dtype=jnp.float32)
    iters = max(iters, 12)
    warm = 3
    tmp = tempfile.mkdtemp(prefix="bigdl-obs-bench-")

    def leg(traced: bool) -> float:
        model, dataset, criterion = _build("lenet", batch, n_batches=8,
                                           dtype="fp32")
        opt = Optimizer(model, dataset, criterion)
        trace.reset()
        # explicit configure wins over any ambient BIGDL_TRACE: each leg
        # measures exactly the state its name claims
        if traced:
            trace.configure(enabled=True, trace_dir=tmp)
        else:
            trace.configure(enabled=False)
        opt.set_end_when(Trigger.max_iteration(warm))
        opt.optimize()  # compile + feed spin-up outside the timed window
        t0 = time.perf_counter()
        opt.set_end_when(Trigger.max_iteration(warm + iters))
        opt.optimize()
        dt = time.perf_counter() - t0
        return batch * iters / dt

    def exporter_leg() -> dict:
        """The SAME untraced loop with the /metrics endpoint live and a
        client scraping it at 1 Hz (10-15x a real Prometheus interval;
        back-to-back scraping with no think time would measure single-core
        GIL contention, not the endpoint) — scrape-under-load cost, plus
        validity of what the scraper saw (parseable Prometheus text
        carrying the train metrics and the live MFU gauge)."""
        import threading
        import urllib.request

        from bigdl_tpu.obs import exporter

        exp = exporter.MetricsExporter(0).start()
        stop_evt = threading.Event()
        scrapes = [0]
        last_body = [""]
        err = [None]

        def spam():
            url = exp.url + "/metrics"
            while not stop_evt.is_set():
                try:
                    with urllib.request.urlopen(url, timeout=5) as r:
                        last_body[0] = r.read().decode("utf-8")
                    scrapes[0] += 1
                except Exception as e:  # noqa: BLE001 — reported below
                    err[0] = f"{type(e).__name__}: {e}"
                stop_evt.wait(1.0)

        th = threading.Thread(target=spam, daemon=True)
        th.start()
        try:
            ips = leg(False)
        finally:
            stop_evt.set()
            th.join(timeout=5)
            exp.stop()
        parsed = {}
        parse_ok = False
        try:
            parsed = exporter.parse_metrics(last_body[0])
            parse_ok = bool(parsed)
        except ValueError:
            parse_ok = False
        return {"ips": ips, "scrapes": scrapes[0], "error": err[0],
                "parse_ok": parse_ok,
                "has_train_metrics": any(k.startswith("bigdl_train_")
                                         for k in parsed),
                "has_mfu_gauge": any(
                    k in ("bigdl_train_mfu",
                          "bigdl_train_model_flops_per_sec")
                    for k in parsed)}

    def cluster_leg() -> dict:
        """The SAME untraced loop with the whole cluster-obs plane live:
        DeviceMonitor polling at 0.2 s, the snapshot spool appending at
        0.2 s, and the access log absorbing ~100 request records/sec (a
        side thread standing in for a busy serving engine — the trainer
        itself writes no access records). Everything-on must clear the
        same <3% gate as the tracer."""
        import threading

        from bigdl_tpu.obs import access_log as obs_access_log
        from bigdl_tpu.obs import cluster as obs_cluster
        from bigdl_tpu.obs import device as obs_device

        spool_dir = os.path.join(tmp, "spool")
        log_dir = os.path.join(tmp, "alog")
        saved = os.environ.get("BIGDL_ACCESS_LOG")
        os.environ["BIGDL_ACCESS_LOG"] = log_dir
        obs_access_log.reset()
        mon = obs_device.DeviceMonitor(interval_s=0.2).start()
        writer = obs_cluster.SpoolWriter(spool_dir, host="bench",
                                         interval_s=0.2).start()
        stop_evt = threading.Event()

        def spam_log():
            while not stop_evt.is_set():
                obs_access_log.log_request(
                    trace_id="bench", tenant="bench", phase="decode",
                    prompt_tokens=128, output_tokens=64, ttft_ms=1.0,
                    e2e_ms=2.0, flops=1e9, outcome="ok")
                stop_evt.wait(0.01)

        th = threading.Thread(target=spam_log, daemon=True)
        th.start()
        try:
            ips = leg(False)
        finally:
            stop_evt.set()
            th.join(timeout=5)
            mon.stop()
            writer.stop()
            alog = obs_access_log.from_env()
            records = alog.records if alog is not None else 0
            log_ok = alog is not None and not alog.disabled
            obs_access_log.reset()
            if saved is None:
                os.environ.pop("BIGDL_ACCESS_LOG", None)
            else:
                os.environ["BIGDL_ACCESS_LOG"] = saved
        spooled = obs_cluster.read_spools(spool_dir, stale_after_s=3600.0)
        return {"ips": ips, "records": records, "log_ok": log_ok,
                "device_polls": mon.polls, "spool_writes": writer.writes,
                "spool_valid": ("bench" in spooled
                                and not spooled["bench"]["stale"])}

    try:
        off_a = leg(False)
        traced_a = leg(True)
        # artifact validity while the traced run's buffers are still live
        chrome = trace.export_chrome()
        with open(chrome) as f:
            tr = json.load(f)
        span_events = [e for e in tr["traceEvents"] if e.get("ph") == "X"]
        n_threads = len({e["tid"] for e in span_events})
        jsonl = trace.jsonl_path()
        kinds = {e.get("kind") for e in trace.read_events(jsonl)}
        trace.reset()
        exp_a = exporter_leg()
        # second round of all three legs, interleaved: this box's sustained
        # throughput drifts by double-digit percent over a process lifetime
        # (shared CPU), so a gate comparing one early leg against one late
        # leg measures the drift, not the tracer — best-of-two PER LEG
        # compares best case against best case and cancels it
        off_b = leg(False)
        traced_b = leg(True)
        trace.reset()
        exp_b = exporter_leg()
        cl_a = cluster_leg()
        cl_b = cluster_leg()
    finally:
        trace.reset()
        shutil.rmtree(tmp, ignore_errors=True)
    off_ips = max(off_a, off_b)
    traced_ips = max(traced_a, traced_b)
    exp_ips = max(exp_a["ips"], exp_b["ips"])
    cl_ips = max(cl_a["ips"], cl_b["ips"])
    cl_leg = cl_a if cl_a["log_ok"] and cl_a["spool_valid"] else cl_b
    cl_overhead = max(0.0, 1.0 - cl_ips / off_ips) if off_ips else 0.0
    exp_leg = exp_a if (exp_a["parse_ok"] and exp_a["error"] is None) \
        else exp_b
    exp_leg["scrapes"] = exp_a["scrapes"] + exp_b["scrapes"]
    overhead = max(0.0, 1.0 - traced_ips / off_ips) if off_ips else 0.0
    exp_overhead = max(0.0, 1.0 - exp_ips / off_ips) if off_ips else 0.0
    return {
        "value": round(traced_ips, 1),
        "unit": "images/sec",
        "batch": batch,
        "iters": iters,
        "dtype": "fp32",
        "obs_images_per_sec_traced": round(traced_ips, 1),
        "obs_images_per_sec_off": round(off_ips, 1),
        "obs_overhead_pct": round(100.0 * overhead, 2),
        "obs_overhead_ok": overhead < 0.03,
        "trace_span_events": len(span_events),
        "trace_threads": n_threads,
        "trace_valid": bool(span_events) and n_threads >= 2,
        "jsonl_has_run_report": "run_report" in kinds,
        # exporter-overhead leg: scraping /metrics during the run must stay
        # under the same <3% gate as the tracer
        "exporter_images_per_sec": round(exp_ips, 1),
        "exporter_scrapes": exp_leg["scrapes"],
        "exporter_overhead_pct": round(100.0 * exp_overhead, 2),
        "exporter_overhead_ok": exp_overhead < 0.03,
        "exporter_scrape_valid": bool(exp_leg["parse_ok"]
                                      and exp_leg["has_train_metrics"]
                                      and exp_leg["error"] is None),
        "exporter_has_mfu_gauge": exp_leg["has_mfu_gauge"],
        # everything-on leg: DeviceMonitor + access log + snapshot spool
        # together must clear the same <3% gate
        "access_log_images_per_sec": round(cl_ips, 1),
        "access_log_records": cl_leg["records"],
        "access_log_ok": bool(cl_leg["log_ok"]),
        "access_log_overhead_pct": round(100.0 * cl_overhead, 2),
        "access_log_overhead_ok": cl_overhead < 0.03,
        "cluster_device_polls": cl_leg["device_polls"],
        "cluster_spool_writes": cl_leg["spool_writes"],
        "cluster_spool_valid": bool(cl_leg["spool_valid"]),
    }


def _measure_kernel_bench(batch: int, iters: int) -> dict:
    """Kernel-fusion leg (CPU-capable smoke; the MFU campaign's regression
    rail): (1) fused conv-bn(-relu) inference — BN running stats folded into
    the conv weights (kernels/conv_bn.py) — vs the unfused stack, images/sec
    on a small conv tower; (2) flat-param optimizer update
    (kernels/fused_update.py) vs the per-leaf reference, update wall time on
    a LeNet-sized parameter tree; (3) the grad-accum / remat memory proxy:
    XLA ``memory_analysis().temp_size_in_bytes`` of the compiled train step
    at M∈{1,4} and remat∈{none,full} — the activation-memory claim as a
    compiler-reported number, no TPU required."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.kernels.fused_update import FlatParamUpdate
    from bigdl_tpu.nn.graph import fuse_conv_bn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random_generator import RandomGenerator

    Engine.reset()
    Engine.init(compute_dtype=jnp.float32)
    dev = Engine.devices()[0]
    out: dict = {"batch": batch, "dtype": "fp32"}

    # ---- (1) conv-bn fusion: unfused vs fused-folded inference forward
    def conv_tower():
        RandomGenerator.set_seed(7)
        m = nn.Sequential()
        for cin, cout in ((3, 16), (16, 32), (32, 32)):
            m.add(nn.SpatialConvolution(cin, cout, 3, 3, 1, 1, 1, 1,
                                        with_bias=False))
            m.add(nn.SpatialBatchNormalization(cout))
            m.add(nn.ReLU())
        return m.evaluate()

    x = jnp.asarray(np.random.default_rng(0)
                    .normal(size=(batch, 3, 32, 32)).astype(np.float32))

    def prep(m):
        params, mstate = m.get_params(), m.get_state()

        def f(p, s, xx):
            o, _ = m.apply(p, s, xx, training=False, rng=None)
            return o
        jf = jax.jit(f)
        jax.block_until_ready(jf(params, mstate, x))  # compile + warm
        return jf, params, mstate

    legs = {"unfused": prep(conv_tower()),
            "fused": prep(fuse_conv_bn(conv_tower()))}
    best = {k: float("inf") for k in legs}
    for _ in range(5):  # interleaved best-of-5: a scheduler hiccup or
        for k, (jf, p, s) in legs.items():  # thermal drift hits both legs
            t0 = time.perf_counter()
            o = None
            for _ in range(iters):
                o = jf(p, s, x)
            jax.block_until_ready(o)
            best[k] = min(best[k], time.perf_counter() - t0)
    unfused_ips = batch * iters / best["unfused"]
    fused_ips = batch * iters / best["fused"]
    out["convbn_unfused_images_per_sec"] = round(unfused_ips, 1)
    out["convbn_fused_images_per_sec"] = round(fused_ips, 1)
    out["convbn_fused_speedup"] = (round(fused_ips / unfused_ips, 3)
                                   if unfused_ips else None)
    try:  # deterministic supporting evidence: the folded program does
        # strictly fewer ops (the BN normalize is gone) — compiler-counted,
        # immune to timing noise
        def flops(key):
            jf, p, s = legs[key]
            ca = jf.lower(p, s, x).compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            return ca.get("flops")
        fu, ff = flops("unfused"), flops("fused")
        if fu and ff:
            out["convbn_fused_flops_ratio"] = round(ff / fu, 4)
    except Exception as e:
        out["convbn_cost_error"] = f"{type(e).__name__}: {e}"[:200]

    # ---- (2) flat vs per-leaf optimizer update wall time. Two trees: the
    # many-small-leaf shape the flat kernel exists for (a transformer-with-
    # norms profile — per-leaf launch bookkeeping dominates), and the LeNet
    # tree (few large leaves — the flat concat buys little; reported so the
    # trade is visible, not implied)
    from bigdl_tpu.models.lenet import LeNet5
    RandomGenerator.set_seed(7)
    method = SGD(learningrate=0.01, momentum=0.9, dampening=0.0)
    flat = FlatParamUpdate(method)
    rng = np.random.default_rng(0)
    many_params = {f"l{i}": {"weight": jnp.asarray(
        rng.normal(size=(256,)).astype(np.float32))} for i in range(192)}
    lenet_params = LeNet5(10).get_params()

    def upd_ms(m, params):
        grads = jax.tree_util.tree_map(lambda a: a * 0.1, params)
        st = jax.jit(m.init_state)(params)
        ju = jax.jit(m.update)
        zero = jnp.asarray(0, jnp.int32)
        jax.block_until_ready(jax.tree_util.tree_leaves(
            ju(params, grads, st, zero))[0])  # compile + warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            o = None
            for _ in range(iters):
                o = ju(params, grads, st, zero)
            jax.block_until_ready(jax.tree_util.tree_leaves(o)[0])
            best = min(best, time.perf_counter() - t0)
        return 1e3 * best / iters

    perleaf_ms, flat_ms = upd_ms(method, many_params), upd_ms(flat, many_params)
    out["update_ms_perleaf"] = round(perleaf_ms, 4)
    out["update_ms_flat"] = round(flat_ms, 4)
    out["flat_update_speedup"] = (round(perleaf_ms / flat_ms, 3)
                                  if flat_ms else None)
    out["param_leaves"] = len(jax.tree_util.tree_leaves(many_params))
    pl_ms, fl_ms = upd_ms(method, lenet_params), upd_ms(flat, lenet_params)
    out["flat_update_speedup_lenet"] = (round(pl_ms / fl_ms, 3)
                                        if fl_ms else None)
    out["param_leaves_lenet"] = len(jax.tree_util.tree_leaves(lenet_params))

    # ---- (3) grad-accum / remat activation-memory proxy (compiler-reported)
    def step_temp_bytes(accum, remat):
        from bigdl_tpu.dataset.dataset import DataSet
        from bigdl_tpu.dataset.sample import MiniBatch
        from bigdl_tpu.optim.optimizer import LocalOptimizer
        rng = np.random.default_rng(0)
        b = MiniBatch(rng.normal(size=(batch, 1, 28, 28)).astype(np.float32),
                      rng.integers(0, 10, size=(batch,)).astype(np.int32))
        RandomGenerator.set_seed(7)
        opt = LocalOptimizer(LeNet5(10), DataSet.array([b]),
                             nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learningrate=0.01))
        opt.set_gradient_accumulation(accum).set_remat(remat)
        step = jax.jit(opt._make_step_fn())  # no donation: lower() only
        p, ms = opt.model.get_params(), opt.model.get_state()
        os_ = opt.optim_method.init_state(p)
        lowered = step.lower(p, ms, os_, jnp.asarray(0, jnp.int32),
                             jnp.asarray(b.input), jnp.asarray(b.target),
                             jax.random.PRNGKey(0))
        ma = lowered.compile().memory_analysis()
        return int(getattr(ma, "temp_size_in_bytes", 0)) if ma else None

    try:
        m1 = step_temp_bytes(1, "none")
        m4 = step_temp_bytes(4, "none")
        r_full = step_temp_bytes(1, "full")
        out["grad_accum_temp_bytes_m1"] = m1
        out["grad_accum_temp_bytes_m4"] = m4
        if m1 and m4:
            out["grad_accum_temp_ratio"] = round(m4 / m1, 3)
        out["remat_full_temp_bytes"] = r_full
        if m1 and r_full:
            out["remat_temp_ratio"] = round(r_full / m1, 3)
    except Exception as e:  # memory analysis is best-effort diagnostics
        out["memory_proxy_error"] = f"{type(e).__name__}: {e}"[:200]

    out["value"] = out["convbn_fused_speedup"]
    out["unit"] = "fused/unfused speedup"
    out["device_kind"] = dev.device_kind
    out["platform"] = dev.platform
    return out


def _measure_precision(model_name: str, batch: int, iters: int) -> dict:
    """Low-precision step experiment: the SAME model's direct-step training
    throughput at fp32 vs bf16 (nn/precision.py master-weight policy), plus
    the quantized-forward family (nn/quantized.py int8 dynamic / weight-only)
    against the bf16 forward, and an fp8 forward probe (jnp.float8_e4m3fn
    cast at the step boundary — backends without fp8 lowering report the
    error instead of a number)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.utils.engine import Engine

    out: dict = {"batch": batch}

    def step_ips(dtype):
        Engine.reset()
        Engine.init(compute_dtype=jnp.bfloat16 if dtype == "bf16"
                    else jnp.float32)
        model, dataset, criterion = _build(model_name, batch, n_batches=2,
                                           dtype=dtype)
        opt = LocalOptimizer(model, dataset, criterion)
        opt.set_optim_method(SGD(learningrate=0.01, momentum=0.9,
                                 dampening=0.0))
        opt.log_every = 10 ** 9
        opt.set_end_when(Trigger.max_iteration(3))
        opt.optimize()  # compile + warm through the real loop
        return _measure_direct_step(opt, batch, iters)

    fp32_ips = step_ips("fp32")
    bf16_ips = step_ips("bf16")
    out["step_samples_per_sec_fp32"] = round(fp32_ips, 1)
    out["step_samples_per_sec_bf16"] = round(bf16_ips, 1)
    out["bf16_fp32_step_ratio"] = (round(bf16_ips / fp32_ips, 3)
                                   if fp32_ips else None)
    dev = Engine.devices()[0]
    out["device_kind"], out["platform"] = dev.device_kind, dev.platform

    # quantized forward family on the warm bf16 engine
    try:
        q = _measure_int8_infer(model_name, batch, max(iters, 10))
        for k in ("bf16_infer_ips", "int8_infer_ips", "int8_bf16_ratio",
                  "int8_weight_only_ips", "weight_only_bf16_ratio"):
            if k in q:
                out[k] = q[k]
    except Exception as e:
        out["int8_leg_error"] = f"{type(e).__name__}: {e}"[:300]

    # fp8 matmul probe: the dtype ladder's next rung after bf16, measured on
    # the op that would carry it (a dot with fp32 accumulation — the MXU
    # contract). The zoo models can't run fp8 end-to-end yet (normalize/BN
    # glue promotes to fp32), so this is the honest micro-experiment: is the
    # backend's fp8 matmul faster than bf16 at all? Backends without fp8
    # lowering report the error instead of a number.
    try:
        import numpy as np
        k = 1024
        base = jnp.asarray(np.random.default_rng(0)
                           .normal(size=(k, k)).astype(np.float32))

        def mm_ms(dt):
            a, b = base.astype(dt), base.T.astype(dt)
            f = jax.jit(lambda x, y: jnp.dot(
                x, y, preferred_element_type=jnp.float32))
            jax.block_until_ready(f(a, b))
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                o = None
                for _ in range(iters):
                    o = f(a, b)
                jax.block_until_ready(o)
                best = min(best, time.perf_counter() - t0)
            return 1e3 * best / iters

        bf16_ms = mm_ms(jnp.bfloat16)
        fp8_ms = mm_ms(jnp.float8_e4m3fn)
        out["bf16_matmul_ms"] = round(bf16_ms, 3)
        out["fp8_matmul_ms"] = round(fp8_ms, 3)
        out["fp8_bf16_matmul_speedup"] = (round(bf16_ms / fp8_ms, 3)
                                          if fp8_ms else None)
    except Exception as e:
        out["fp8_error"] = f"{type(e).__name__}: {e}"[:300]

    out["value"] = out["bf16_fp32_step_ratio"]
    out["unit"] = "bf16/fp32 step ratio"
    return out


def _measure_serving(model_name: str, batch: int, iters: int) -> dict:
    """Serving-path micro-bench: Predictor.predict and Evaluator.test
    throughput through the framework's own eval machinery (per-batch h2d,
    cached jitted forward, chunked d2h fetches) — the inference half of the
    reference's Evaluator/Predictor story."""
    import jax.numpy as jnp

    from bigdl_tpu.optim.evaluator import Evaluator, Predictor
    from bigdl_tpu.optim.validation import Top1Accuracy
    from bigdl_tpu.utils.engine import Engine

    Engine.reset()
    Engine.init(compute_dtype=jnp.bfloat16)
    n_batches = 4
    model, dataset, _ = _build(model_name, batch, n_batches=n_batches,
                               dtype="bf16")
    model.evaluate()
    predictor, evaluator = Predictor(model), Evaluator(model)
    total = batch * n_batches

    predictor.predict(dataset)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        predictor.predict(dataset)
    predict_sps = total * iters / (time.perf_counter() - t0)

    evaluator.test(dataset, [Top1Accuracy()])
    t0 = time.perf_counter()
    for _ in range(iters):
        evaluator.test(dataset, [Top1Accuracy()])
    eval_sps = total * iters / (time.perf_counter() - t0)

    return {"predict_samples_per_sec": round(predict_sps, 1),
            "evaluate_samples_per_sec": round(eval_sps, 1),
            "batch": batch, "dtype": "bf16"}


def _measure_serving_bench(n_requests: int = 24, slots: int = 8,
                           max_new: int = 16) -> dict:
    """Online serving-engine leg: sustained requests/sec through the
    continuous-batching engine vs the one-request-at-a-time baseline (a
    slots=1 engine — per-request decode through the same code path), with
    TTFT / per-token latency percentiles read from ONE obs-registry
    snapshot, and the compile-count assertion proving bucket reuse: the
    whole run must use at most ``len(buckets) + 2`` device programs
    (one prefill per bucket + one decode + one slot-assign) no matter how
    many distinct prompt lengths arrive."""
    import jax
    import numpy as np

    from bigdl_tpu.models.transformerlm import TransformerLM
    from bigdl_tpu.obs.registry import registry
    from bigdl_tpu.serving import ServingEngine

    dev = jax.devices()[0]
    buckets = (16, 32, 48)
    max_len = 64 + max_new
    lm = TransformerLM(1000, embed_dim=64, num_heads=4, num_layers=2,
                       max_len=max_len).evaluate()
    rng = np.random.default_rng(0)
    reqs = [rng.integers(0, 1000, (int(rng.integers(4, 49)),))
            .astype(np.int32) for _ in range(n_requests)]

    def pct(snap, name):
        h = snap["histograms"].get(name, {})
        return {q: (round(h[f"p{q}"], 2) if h.get(f"p{q}") is not None
                    else None) for q in (50, 99)}

    def run(n_slots, sequential):
        eng = ServingEngine(lm, max_len=max_len, slots=n_slots,
                            buckets=buckets)
        try:
            # compile + warm EVERY grid point (one prompt per prefill
            # bucket) so both timed legs are compile-free
            for plen in (8, 24, 40):
                warm = np.arange(plen, dtype=np.int32) % 1000
                eng.submit(warm, max_new).result(timeout=300)
            registry.reset()
            t0 = time.perf_counter()
            if sequential:
                for p in reqs:
                    eng.submit(p, max_new).result(timeout=300)
            else:
                for h in [eng.submit(p, max_new) for p in reqs]:
                    h.result(timeout=300)
            wall = time.perf_counter() - t0
            return n_requests / wall, registry.snapshot(), eng.stats()
        finally:
            eng.shutdown()

    # one-request-at-a-time baseline FIRST (its prefill programs are shared
    # with the batched engine via the model's apply cache — the timed window
    # of both legs is compile-free)
    seq_rps, seq_snap, _ = run(1, sequential=True)
    rps, snap, stats = run(slots, sequential=False)

    # degradation leg: the SAME traffic with scripted serving faults — one
    # mid-run engine-thread death (supervisor respawn + re-prefill) and one
    # non-finite slot (guard fails exactly that request). Sustained req/s
    # and p99 TTFT under faults vs the clean leg is the recovery-cost
    # number; a plan that does not fully fire or an unexpected failure
    # count stamps the degraded-record contract instead of passing quietly.
    from bigdl_tpu.serving import NonFiniteLogitsError
    from bigdl_tpu.utils.faults import inject_faults

    fault_spec = "serve_decode@5=nonfinite;serve_thread@10"
    eng = ServingEngine(lm, max_len=max_len, slots=slots, buckets=buckets)
    try:
        for plen in (8, 24, 40):
            warm = np.arange(plen, dtype=np.int32) % 1000
            eng.submit(warm, max_new).result(timeout=300)
        registry.reset()
        with inject_faults(fault_spec) as plan:
            t0 = time.perf_counter()
            n_failed = 0
            for h in [eng.submit(p, max_new) for p in reqs]:
                try:
                    h.result(timeout=300)
                except NonFiniteLogitsError:
                    n_failed += 1
            faulted_wall = time.perf_counter() - t0
            unfired = plan.unfired()
        faulted_rps = n_requests / faulted_wall
        faulted_snap, faulted_stats = registry.snapshot(), eng.stats()
    finally:
        eng.shutdown()

    grid_bound = len(buckets) + 2
    ttft, tpot = pct(snap, "serving/ttft_ms"), pct(snap, "serving/tpot_ms")
    faulted_ttft = pct(faulted_snap, "serving/ttft_ms")
    record_extra = {}
    if unfired or n_failed != 1 or faulted_stats["respawns"] != 1:
        reason = (f"serving degradation leg off-script: unfired={unfired} "
                  f"failed={n_failed} (want 1) "
                  f"respawns={faulted_stats['respawns']} (want 1)")
        print(f"bench: DEGRADED RUN — {reason}", file=sys.stderr)
        record_extra = {"degraded": True, "probe_error": reason}
    return {
        "value": round(rps, 2),
        "unit": "req/sec",
        "n_requests": n_requests,
        "slots": slots,
        "buckets": list(buckets),
        "max_new_tokens": max_new,
        "requests_per_sec": round(rps, 2),
        "requests_per_sec_sequential": round(seq_rps, 2),
        "serving_speedup": round(rps / seq_rps, 2) if seq_rps else None,
        "ttft_ms_p50": ttft[50], "ttft_ms_p99": ttft[99],
        "tpot_ms_p50": tpot[50], "tpot_ms_p99": tpot[99],
        "sequential_ttft_ms_p99": pct(seq_snap, "serving/ttft_ms")[99],
        "slot_recycles": stats["slot_recycles"],
        "compiled_programs": stats["compiled_programs"],
        "program_grid_bound": grid_bound,
        "compile_count_ok": stats["compiled_programs"] <= grid_bound,
        # degradation leg (docs/robustness.md "Serving"): same traffic under
        # serve_thread + serve_decode=nonfinite faults. compile_count_ok is
        # asserted on the clean legs only — the faulted leg legitimately
        # compiles the slot-reset program and any recovery re-prefill length.
        "fault_plan": fault_spec,
        "requests_per_sec_faulted": round(faulted_rps, 2),
        "degradation_ratio": round(faulted_rps / rps, 3) if rps else None,
        "faulted_ttft_ms_p99": faulted_ttft[99],
        "faulted_respawns": faulted_stats["respawns"],
        "faulted_poisoned_slots": faulted_stats["poisoned_slots"],
        "faulted_failed_requests": n_failed,
        "fault_plan_fired": not unfired,
        "device_kind": dev.device_kind,
        "platform": dev.platform,
        **record_extra,
    }


def _measure_promotion_bench(n_requests: int = 24, slots: int = 8,
                             max_new: int = 16) -> dict:
    """Promotion-lifecycle leg (docs/serving.md "Lifecycle"), three
    questions:

    1. **Swap flatness**: sustained req/s and TTFT p99 for a traffic window
       WITH a mid-window zero-downtime weight promotion vs the same window
       clean — the swap must drop zero requests, and the program ledger
       must not grow across it.
    2. **Gate drill**: a ``promote_eval@1=nonfinite`` fault plan poisons
       the candidate metric — the gate must reject it (and the plan must
       fully fire).
    3. **Rollback wall time**: a scripted bad promotion (NaN weights, gate
       bypassed) trips the watch-window quality probe; the auto-rollback
       swap-back is timed, and the post-rollback serving output must be
       bitwise what the pre-promotion version produced.

    Anything off-script stamps the degraded-record contract instead of
    passing quietly."""
    import tempfile

    import jax
    import numpy as np

    from bigdl_tpu.models.transformerlm import TransformerLM
    from bigdl_tpu.obs.registry import registry
    from bigdl_tpu.serving import PromotionController, ServingEngine
    from bigdl_tpu.utils.faults import inject_faults
    from bigdl_tpu.utils.model_registry import ModelRegistry

    dev = jax.devices()[0]
    # the 64 bucket is load-bearing: swap re-prefill replays prompt+emitted
    # tokens (up to 48+15 = 63), and an unwarmed length would compile
    # mid-window — exactly the stall this leg exists to rule out
    buckets = (16, 32, 48, 64)
    max_len = 64 + max_new
    lm = TransformerLM(1000, embed_dim=64, num_heads=4, num_layers=2,
                       max_len=max_len).evaluate()
    rng = np.random.default_rng(0)
    reqs = [rng.integers(0, 1000, (int(rng.integers(4, 49)),))
            .astype(np.int32) for _ in range(n_requests)]

    def tree_map(tree, f):
        return {k: (tree_map(v, f) if isinstance(v, dict) else f(v))
                for k, v in tree.items()}

    base = lm.get_params()
    noise = np.random.default_rng(7)
    good = tree_map(base, lambda a: np.asarray(a)
                    + noise.normal(0, 0.02, np.shape(a))
                    .astype(np.asarray(a).dtype))
    bad = tree_map(base, lambda a: np.full_like(np.asarray(a), np.nan))
    reg_dir = tempfile.mkdtemp(prefix="bigdl-promo-bench-")
    mreg = ModelRegistry(reg_dir, keep=4)
    v_good = mreg.publish(good, meta={"source": "bench"})
    v_bad = mreg.publish(bad, meta={"source": "bench"})

    def pct99(snap, name):
        h = snap["histograms"].get(name, {})
        return round(h["p99"], 2) if h.get("p99") is not None else None

    probe = np.arange(8, dtype=np.int32) % 1000
    eng = ServingEngine(lm, max_len=max_len, slots=slots, buckets=buckets)
    problems = []
    try:
        for plen in (8, 24, 40, 56):   # warm every grid point: timed legs
            warm = np.arange(plen, dtype=np.int32) % 1000   # are compile-free
            eng.submit(warm, max_new).result(timeout=300)
        ctrl = PromotionController(
            mreg, engine=eng, eval_fn=lambda p: 1.0,
            probe_prompts=[probe], watch_window_s=0.0, poll_s=0.01,
            rollback_budget=3)

        # clean window
        registry.reset()
        t0 = time.perf_counter()
        for h in [eng.submit(p, max_new) for p in reqs]:
            h.result(timeout=300)
        clean_wall = time.perf_counter() - t0
        clean_snap = registry.snapshot()

        # promotion window: same traffic, v_good swaps in mid-stream
        progs_before = eng.stats()["compiled_programs"]
        registry.reset()
        t0 = time.perf_counter()
        handles = [eng.submit(p, max_new) for p in reqs]
        promo = ctrl.promote(v_good, watch=False)
        dropped = 0
        for h in handles:
            try:
                h.result(timeout=300)
            except Exception:
                dropped += 1
        promo_wall = time.perf_counter() - t0
        promo_snap = registry.snapshot()
        progs_after = eng.stats()["compiled_programs"]
        post_promo = np.asarray(
            eng.submit(probe, max_new).result(timeout=300).tokens)
        if dropped:
            problems.append(f"swap dropped {dropped} requests")
        if progs_after > progs_before:
            problems.append(f"program ledger grew across swap "
                            f"({progs_before} -> {progs_after})")

        # gate drill: poisoned candidate metric must be rejected
        with inject_faults("promote_eval@1=nonfinite") as plan:
            ok, _metric, _reason = ctrl.gate(v_bad)
        if ok or plan.unfired():
            problems.append(f"gate drill off-script: accepted={ok} "
                            f"unfired={plan.unfired()}")

        # rollback drill: bad promotion bypassing the gate; the watch
        # window's quality probe trips on non-finite logits and the
        # previous version swaps back — timed, then bitwise-checked
        ctrl.promote(v_bad, gate=False, watch=False)
        t0 = time.perf_counter()
        rolled = ctrl.watch(window_s=5.0, poll_s=0.01)
        rollback_wall = time.perf_counter() - t0
        post_roll = np.asarray(
            eng.submit(probe, max_new).result(timeout=300).tokens)
        if not rolled:
            problems.append("watch window did not roll back")
        if not np.array_equal(post_roll, post_promo):
            problems.append("post-rollback output != pre-promotion output")
        final_stats = eng.stats()
    finally:
        eng.shutdown()

    rps_clean = n_requests / clean_wall
    rps_promo = n_requests / promo_wall
    record_extra = {}
    if problems:
        reason = "promotion leg off-script: " + "; ".join(problems)
        print(f"bench: DEGRADED RUN — {reason}", file=sys.stderr)
        record_extra = {"degraded": True, "probe_error": reason}
    return {
        "value": round(rps_promo, 2),
        "unit": "req/sec",
        "n_requests": n_requests,
        "slots": slots,
        "buckets": list(buckets),
        "max_new_tokens": max_new,
        "requests_per_sec_clean": round(rps_clean, 2),
        "requests_per_sec_promotion": round(rps_promo, 2),
        "promotion_flatness": (round(rps_promo / rps_clean, 3)
                               if rps_clean else None),
        "ttft_ms_p99_clean": pct99(clean_snap, "serving/ttft_ms"),
        "ttft_ms_p99_promotion": pct99(promo_snap, "serving/ttft_ms"),
        "swap_ms": round(promo.swap.duration_s * 1e3, 2),
        "swap_requeued": promo.swap.requeued,
        "dropped_requests": dropped,
        "rollback_ms": round(rollback_wall * 1e3, 2),
        "rollback_bitwise_ok": "post-rollback output != pre-promotion "
                               "output" not in problems,
        "compiled_programs": final_stats["compiled_programs"],
        "served_version": final_stats["model_version"],
        "device_kind": dev.device_kind,
        "platform": dev.platform,
        **record_extra,
    }


def _measure_fleet_bench(n_requests: int = 24, replicas: int = 2,
                         max_new: int = 16) -> dict:
    """Serving-fleet leg, three questions (docs/serving.md "Fleet"):

    1. **Churn throughput**: sustained req/s through an N-replica
       :class:`FleetRouter` with a scripted mid-run ``replica_down`` kill
       (retry-elsewhere recovers every affected request — zero lost) vs the
       same traffic through one replica.
    2. **Prefix reuse**: TTFT over shared-prefix traffic with the prefix
       KV-cache pool warm vs cold — warm hits skip re-prefill, so warm p50
       TTFT should be well under half of cold.
    3. **Speculative decode**: tokens/s with the target drafting for
       itself (acceptance PINNED at 100% — the upper bound of the win) vs
       plain engine decode, measured acceptance reported.

    A fault plan that does not fully fire, a lost request, or an acceptance
    off its pin stamps the degraded-record contract instead of passing
    quietly."""
    import jax
    import numpy as np

    from bigdl_tpu.models.transformerlm import TransformerLM
    from bigdl_tpu.obs.registry import registry
    from bigdl_tpu.serving import FleetRouter, ServingEngine
    from bigdl_tpu.utils.faults import inject_faults

    dev = jax.devices()[0]
    buckets = (16, 32, 48)
    max_len = 64 + max_new + 4      # +4: speculative overshoot headroom
    lm = TransformerLM(1000, embed_dim=64, num_heads=4, num_layers=2,
                       max_len=max_len).evaluate()
    rng = np.random.default_rng(0)
    reqs = [rng.integers(0, 1000, (int(rng.integers(4, 49)),))
            .astype(np.int32) for _ in range(n_requests)]
    off_script = []

    def warm(submit):
        # compile + warm every prefill bucket so timed windows are
        # compile-free (programs live on the shared model apply cache)
        for plen in (8, 24, 40):
            submit(np.arange(plen, dtype=np.int32) % 1000,
                   max_new).result(timeout=300)

    # ---- leg 1: fleet under churn vs one replica -------------------------
    with ServingEngine(lm, max_len=max_len, buckets=buckets) as eng:
        warm(eng.submit)
        t0 = time.perf_counter()
        for h in [eng.submit(p, max_new) for p in reqs]:
            h.result(timeout=300)
        solo_rps = n_requests / (time.perf_counter() - t0)

    kill_at = n_requests // 2
    fleet = FleetRouter.replicate(lm, max_len=max_len, replicas=replicas,
                                  buckets=buckets)
    try:
        warm(fleet.submit)
        with inject_faults(f"replica_down@{kill_at}") as plan:
            t0 = time.perf_counter()
            lost = 0
            for h in [fleet.submit(p, max_new) for p in reqs]:
                try:
                    h.result(timeout=300)
                except Exception:  # noqa: BLE001 — a loss is the metric
                    lost += 1
            churn_wall = time.perf_counter() - t0
            unfired = plan.unfired()
        churn_rps = n_requests / churn_wall
        fleet_stats = {k: v for k, v in fleet.stats().items()
                       if k != "replicas"}
    finally:
        fleet.shutdown()
    if unfired:
        off_script.append(f"fleet churn plan unfired: {unfired}")
    if lost:
        off_script.append(f"fleet churn lost {lost} requests (want 0)")

    # ---- leg 2: shared-prefix TTFT, pool warm vs cold --------------------
    shared = rng.integers(0, 1000, (40,)).astype(np.int32)
    tails = [rng.integers(0, 1000, (4,)).astype(np.int32)
             for _ in range(8)]

    def ttft_p50(pool):
        with ServingEngine(lm, max_len=max_len, buckets=buckets,
                           prefix_pool=pool, prefix_chunk=8) as eng:
            warm(eng.submit)
            eng.submit(shared, 1).result(timeout=300)   # pools the prefix
            registry.reset()
            for t in tails:
                eng.submit(np.concatenate([shared, t]),
                           max_new).result(timeout=300)
            snap = registry.snapshot()
            st = eng.stats()
        h = snap["histograms"].get("serving/ttft_ms", {})
        return h.get("p50"), st
    cold_ttft, _ = ttft_p50(pool=0)
    warm_ttft, pool_stats = ttft_p50(pool=8)
    prefix_ratio = (round(warm_ttft / cold_ttft, 3)
                    if warm_ttft and cold_ttft else None)
    if not pool_stats["prefix_hits"]:
        off_script.append("prefix leg saw zero pool hits")

    # ---- leg 3: speculative tokens/s at pinned acceptance ----------------
    from bigdl_tpu.serving.speculative import SpeculativeDecoder
    spec_prompt = np.stack([rng.integers(0, 1000, (8,)) for _ in range(4)]
                           ).astype(np.int32)
    decode_len = 32

    from bigdl_tpu import nn as _nn
    _ = _nn.greedy_generate(lm, spec_prompt, decode_len)      # compile
    t0 = time.perf_counter()
    _ = _nn.greedy_generate(lm, spec_prompt, decode_len)
    plain_tps = 4 * decode_len / (time.perf_counter() - t0)

    sd = SpeculativeDecoder(lm, lm, spec_tokens=4)
    sd.generate(spec_prompt, decode_len)                      # compile
    sd = SpeculativeDecoder(lm, lm, spec_tokens=4)
    t0 = time.perf_counter()
    sd.generate(spec_prompt, decode_len)
    spec_tps = 4 * decode_len / (time.perf_counter() - t0)
    acceptance = sd.stats()["acceptance_rate"]
    if acceptance != 1.0:
        off_script.append(
            f"self-draft acceptance {acceptance} (want 1.0)")

    record_extra = {}
    if off_script:
        reason = "fleet bench off-script: " + "; ".join(off_script)
        print(f"bench: DEGRADED RUN — {reason}", file=sys.stderr)
        record_extra = {"degraded": True, "probe_error": reason}
    return {
        "value": round(churn_rps, 2),
        "unit": "req/sec",
        "n_requests": n_requests,
        "replicas": replicas,
        "max_new_tokens": max_new,
        "buckets": list(buckets),
        # leg 1 — churn
        "fleet_requests_per_sec_churn": round(churn_rps, 2),
        "solo_requests_per_sec": round(solo_rps, 2),
        "churn_vs_solo": (round(churn_rps / solo_rps, 2)
                          if solo_rps else None),
        "fault_plan": f"replica_down@{kill_at}",
        "fault_plan_fired": not unfired,
        "requests_lost": lost,
        "fleet_retries": fleet_stats["retries"],
        "fleet_replica_downs": fleet_stats["replica_downs"],
        # leg 2 — prefix reuse
        "ttft_ms_p50_cold": (round(cold_ttft, 2)
                             if cold_ttft is not None else None),
        "ttft_ms_p50_warm": (round(warm_ttft, 2)
                             if warm_ttft is not None else None),
        "warm_cold_ttft_ratio": prefix_ratio,
        "prefix_hits": pool_stats["prefix_hits"],
        "prefix_tokens_saved": pool_stats["prefix_tokens_saved"],
        # leg 3 — speculative decode
        "spec_tokens_per_sec": round(spec_tps, 1),
        "plain_tokens_per_sec": round(plain_tps, 1),
        "spec_vs_plain": (round(spec_tps / plain_tps, 2)
                          if plain_tps else None),
        "spec_acceptance": acceptance,
        "spec_k": 4,
        "device_kind": dev.device_kind,
        "platform": dev.platform,
        **record_extra,
    }


def _measure_paging_bench(n_requests: int = 24, max_new: int = 16) -> dict:
    """Paged-serving leg, three questions (docs/serving.md "Paged KV cache
    & disaggregation"):

    1. **Residency at equal pooled KV bytes**: peak concurrently-resident
       sequences on a paged engine whose page pool holds EXACTLY the slot
       grid's KV bytes vs the grid itself — short traffic must pack >= 2x
       the sequences into the same memory.
    2. **Same-trace cost**: req/s + p99 TTFT over the serving-bench trace,
       paged vs grid, with the paged program ledger pinned at
       ``len(buckets) + 2`` (paging must not melt throughput or compile
       per-occupancy programs).
    3. **Disaggregation under burst**: p99 engine TTFT over a prompt burst
       through a 2-replica fleet, phases ``prefill,decode`` (handoff seeds
       the decode tier's prefix pool — admission is an exact pool hit) vs
       the same fleet fully mixed. Disaggregated must beat mixed, with
       zero lost requests on both.

    A residency ratio under 2x, a busted ledger, a lost request, a
    zero-handoff disaggregated run, or disaggregated p99 not beating mixed
    stamps the degraded-record contract instead of passing quietly."""
    import threading

    import jax
    import numpy as np

    from bigdl_tpu.models.transformerlm import TransformerLM
    from bigdl_tpu.obs.registry import registry
    from bigdl_tpu.serving import FleetRouter, ServingEngine

    dev = jax.devices()[0]
    buckets = (16, 32, 48)
    max_len = 64 + max_new
    page_tokens = 16
    lm = TransformerLM(1000, embed_dim=64, num_heads=4, num_layers=2,
                       max_len=max_len).evaluate()
    rng = np.random.default_rng(0)
    reqs = [rng.integers(0, 1000, (int(rng.integers(4, 49)),))
            .astype(np.int32) for _ in range(n_requests)]
    off_script = []

    def warm(submit):
        # compile + warm every prefill bucket so timed windows are
        # compile-free (programs live on the shared model apply cache)
        for plen in (8, 24, 40):
            submit(np.arange(plen, dtype=np.int32) % 1000,
                   max_new).result(timeout=300)

    def pct99(snap):
        h = snap["histograms"].get("serving/ttft_ms", {})
        return (round(h["p99"], 2) if h.get("p99") is not None else None)

    # ---- leg 1: resident sequences at equal pooled KV bytes --------------
    grid_slots = 4
    pool_pages = grid_slots * max_len // page_tokens   # same KV bytes
    n_short = 2 * grid_slots
    shorts = [rng.integers(0, 1000, (8,)).astype(np.int32)
              for _ in range(n_short)]

    def peak_resident(paged):
        kw = ({"slots": n_short, "pages": pool_pages,
               "page_tokens": page_tokens} if paged
              else {"slots": grid_slots})
        with ServingEngine(lm, max_len=max_len, buckets=buckets,
                           **kw) as eng:
            warm(eng.submit)
            peak, stop = [0], threading.Event()

            def poll():
                while not stop.is_set():
                    peak[0] = max(peak[0], eng.stats()["active_slots"])
                    time.sleep(0.001)

            th = threading.Thread(target=poll, daemon=True)
            th.start()
            try:
                for h in [eng.submit(p, max_new) for p in shorts]:
                    h.result(timeout=300)
            finally:
                stop.set()
                th.join(timeout=5)
            return peak[0], eng.stats()

    grid_peak, _ = peak_resident(paged=False)
    paged_peak, res_stats = peak_resident(paged=True)
    resident_ratio = (round(paged_peak / grid_peak, 2)
                      if grid_peak else None)
    if not resident_ratio or resident_ratio < 2.0:
        off_script.append(
            f"residency ratio {resident_ratio} (want >= 2.0) at equal "
            f"pooled KV bytes ({pool_pages} pages x {page_tokens} tok)")
    if res_stats["pages_used"]:
        off_script.append(
            f"{res_stats['pages_used']} pages still held after drain")

    # ---- leg 2: same trace, paged vs grid --------------------------------
    def trace_leg(paged):
        kw = ({"pages": 8 * ((max_len + page_tokens - 1) // page_tokens),
               "page_tokens": page_tokens} if paged else {})
        with ServingEngine(lm, max_len=max_len, slots=8, buckets=buckets,
                           **kw) as eng:
            warm(eng.submit)
            registry.reset()
            t0 = time.perf_counter()
            for h in [eng.submit(p, max_new) for p in reqs]:
                h.result(timeout=300)
            wall = time.perf_counter() - t0
            return n_requests / wall, registry.snapshot(), eng.stats()

    grid_rps, grid_snap, _ = trace_leg(paged=False)
    paged_rps, paged_snap, paged_stats = trace_leg(paged=True)
    grid_bound = len(buckets) + 2
    if paged_stats["compiled_programs"] > grid_bound:
        off_script.append(
            f"paged ledger {paged_stats['compiled_programs']} > "
            f"{grid_bound}")

    # ---- leg 3: prompt burst, disaggregated vs mixed ---------------------
    burst = [rng.integers(0, 1000, (40,)).astype(np.int32)
             for _ in range(12)]
    burst_new = 8

    def burst_leg(name, phases):
        kw = ({"prefix_pool": 16, "prefix_chunk": 8}
              if phases else {})
        fleet = FleetRouter.replicate(lm, max_len=max_len, replicas=2,
                                      buckets=buckets, name=name,
                                      phases=phases, **kw)
        try:
            warm(fleet.submit)
            registry.reset()
            lost = 0
            t0 = time.perf_counter()
            for h in [fleet.submit(p, burst_new) for p in burst]:
                try:
                    h.result(timeout=300)
                except Exception:  # noqa: BLE001 — a loss is the metric
                    lost += 1
            wall = time.perf_counter() - t0
            snap = registry.snapshot()
            st = {k: v for k, v in fleet.stats().items()
                  if k != "replicas"}
        finally:
            fleet.shutdown()
        return pct99(snap), lost, len(burst) / wall, st

    mixed_p99, mixed_lost, mixed_rps, _ = burst_leg("pgmix", None)
    dis_p99, dis_lost, dis_rps, dis_stats = burst_leg(
        "pgdis", "prefill,decode")
    if mixed_lost or dis_lost:
        off_script.append(
            f"burst lost requests: mixed={mixed_lost} disagg={dis_lost} "
            f"(want 0)")
    if not dis_stats["handoffs"]:
        off_script.append("disaggregated burst saw zero handoffs")
    if mixed_p99 is not None and dis_p99 is not None \
            and dis_p99 >= mixed_p99:
        off_script.append(
            f"disaggregated TTFT p99 {dis_p99} ms not under mixed "
            f"{mixed_p99} ms")

    record_extra = {}
    if off_script:
        reason = "paging bench off-script: " + "; ".join(off_script)
        print(f"bench: DEGRADED RUN — {reason}", file=sys.stderr)
        record_extra = {"degraded": True, "probe_error": reason}
    return {
        "value": round(paged_rps, 2),
        "unit": "req/sec",
        "n_requests": n_requests,
        "max_new_tokens": max_new,
        "buckets": list(buckets),
        "page_tokens": page_tokens,
        # leg 1 — residency at equal pooled KV bytes
        "pool_pages": pool_pages,
        "grid_slots": grid_slots,
        "peak_resident_grid": grid_peak,
        "peak_resident_paged": paged_peak,
        "resident_ratio": resident_ratio,
        "page_evictions": res_stats["page_evictions"],
        # leg 2 — same trace paged vs grid
        "requests_per_sec_paged": round(paged_rps, 2),
        "requests_per_sec_grid": round(grid_rps, 2),
        "paged_vs_grid": (round(paged_rps / grid_rps, 2)
                          if grid_rps else None),
        "ttft_ms_p99_paged": pct99(paged_snap),
        "ttft_ms_p99_grid": pct99(grid_snap),
        "compiled_programs": paged_stats["compiled_programs"],
        "program_grid_bound": grid_bound,
        "compile_count_ok":
            paged_stats["compiled_programs"] <= grid_bound,
        # leg 3 — burst TTFT with/without disaggregation
        "burst_requests": len(burst),
        "burst_ttft_ms_p99_mixed": mixed_p99,
        "burst_ttft_ms_p99_disagg": dis_p99,
        "burst_requests_per_sec_mixed": round(mixed_rps, 2),
        "burst_requests_per_sec_disagg": round(dis_rps, 2),
        "handoffs": dis_stats["handoffs"],
        "handoff_failures": dis_stats["handoff_failures"],
        "requests_lost": mixed_lost + dis_lost,
        "device_kind": dev.device_kind,
        "platform": dev.platform,
        **record_extra,
    }


def _measure_recsys_bench(batch: int = 256, iters: int = 10,
                          reps: int = 3) -> dict:
    """Sharded-embedding / recsys leg, three questions (docs/performance.md,
    "Sharded embeddings & sparse updates"):

    1. **Sparse vs dense step time**: an embedding-dominated train step over
       a (V, 64) table at V ∈ {1e5, 1e6} on batch-256 zipf ids. The dense
       baseline is the STRONGEST dense configuration (flat fused update over
       the full (V, 64) table); the sparse leg is ShardedEmbedding +
       SparseEmbeddingUpdate (per-row Adagrad on the deduped unique rows).
       Legs run best-of-interleaved so scheduler noise hits both equally;
       the headline ratio is dense/sparse step time at V=1e6.
    2. **Dedup hit-rate** of the zipf traffic — the fraction of gathers the
       per-batch unique pass eliminates.
    3. **Ranking serving**: RankingEngine sustained req/s over a small
       NeuralCF snapshot (the train→rank→serve loop's last leg), with its
       one-static-shape compile bound.
    """
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.models.ncf import NeuralCF
    from bigdl_tpu.optim import Adagrad, Trigger
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.parallel import ShardedEmbedding
    from bigdl_tpu.serving import RankingEngine
    from bigdl_tpu.utils.engine import Engine

    Engine.reset()
    Engine.init()   # fp32 — the sparse plan requires full-precision updates
    dev = Engine.devices()[0]
    dim = 64
    rng = np.random.default_rng(0)

    def zipf_batch(v):
        ids = rng.zipf(1.3, size=batch).astype(np.int64)   # power-law traffic
        return ((ids - 1) % v + 1).astype(np.int32)

    id_batches = {v: [zipf_batch(v) for _ in range(4)]
                  for v in (100_000, 1_000_000)}

    def build_opt(v, sparse):
        table = nn.LookupTable(v, dim)
        model = ShardedEmbedding(table) if sparse else table
        batches = [MiniBatch(ids, np.zeros((batch, dim), np.float32))
                   for ids in id_batches[v]]
        opt = LocalOptimizer(model, DataSet.array(batches), nn.MSECriterion())
        opt.set_optim_method(Adagrad(learningrate=0.01))
        if not sparse:
            opt.set_flat_update(True)   # strongest dense baseline
        opt.log_every = 10 ** 9
        opt.set_end_when(Trigger.max_iteration(2))
        opt.optimize()   # builds + warms the real compiled step
        return opt

    def step_ms(opt):
        ips = _measure_direct_step(opt, batch, iters)
        return 1e3 * batch / ips

    per_v, plan_ok = {}, True
    for v in (100_000, 1_000_000):
        dense_opt = build_opt(v, sparse=False)
        sparse_opt = build_opt(v, sparse=True)
        plan_ok = plan_ok and sparse_opt._sparse_plan() is not None
        dense_t, sparse_t = [], []
        for _ in range(reps):   # interleaved: noise hits both legs equally
            dense_t.append(step_ms(dense_opt))
            sparse_t.append(step_ms(sparse_opt))
        per_v[v] = (min(dense_t), min(sparse_t))

    # dedup hit-rate of the same traffic (host-side ground truth)
    uniq = [len(np.unique(ids)) for ids in id_batches[1_000_000]]
    dedup_hit_rate = 1.0 - sum(uniq) / (len(uniq) * batch)

    # ranking serving leg: small NCF snapshot, 64 coalesced requests
    n_rank, n_cand = 64, 50
    ncf = NeuralCF(200, 100, class_num=2)
    with RankingEngine(ncf, max_candidates=n_cand, max_batch=8) as eng:
        eng.rank(1, np.arange(1, n_cand + 1), timeout=300)   # compile + warm
        t0 = time.perf_counter()
        handles = [eng.submit(u % 200 + 1,
                              rng.integers(1, 101, size=n_cand))
                   for u in range(n_rank)]
        for h in handles:
            h.result(timeout=300)
        rank_rps = n_rank / (time.perf_counter() - t0)
        rank_stats = eng.stats()

    ratios = {v: (d / s if s else None) for v, (d, s) in per_v.items()}
    record_extra = {}
    if not plan_ok or (ratios[1_000_000] or 0.0) < 5.0:
        reason = ("recsys leg off-script: "
                  + ("sparse plan did not engage" if not plan_ok else
                     f"sparse speedup {ratios[1_000_000]:.2f}x at V=1e6 "
                     "(want >= 5x over the dense flat update)"))
        print(f"bench: DEGRADED RUN — {reason}", file=sys.stderr)
        record_extra = {"degraded": True, "probe_error": reason}
    return {
        "value": round(ratios[1_000_000], 2) if ratios[1_000_000] else None,
        "unit": "x dense/sparse step time (V=1e6)",
        "batch": batch,
        "embed_dim": dim,
        "iters": iters,
        "reps": reps,
        "dense_step_ms_100k": round(per_v[100_000][0], 3),
        "sparse_step_ms_100k": round(per_v[100_000][1], 3),
        "sparse_speedup_100k": round(ratios[100_000], 2),
        "dense_step_ms_1m": round(per_v[1_000_000][0], 3),
        "sparse_step_ms_1m": round(per_v[1_000_000][1], 3),
        "sparse_speedup_1m": round(ratios[1_000_000], 2),
        "dedup_hit_rate": round(dedup_hit_rate, 3),
        "ranking_requests_per_sec": round(rank_rps, 1),
        "ranking_mean_batch_fill": round(rank_stats["mean_batch_fill"], 2),
        "ranking_compiled_programs": rank_stats["compiled_programs"],
        "device_kind": dev.device_kind,
        "platform": dev.platform,
        **record_extra,
    }


def _measure_ckpt_bench(iters: int = 4) -> dict:
    """Elastic-checkpointing leg, two questions (docs/robustness.md,
    "Elastic training"):

    1. **Training-thread stall, sync vs async**: the same elastic save
       (sharded d2h snapshot → serialize → CRC+fsync → manifest) with
       BIGDL_CKPT_ASYNC=0 (training thread eats the whole write) vs =1
       (snapshot-only stall, write overlapped on the background writer).
       ``ckpt/stall_ms`` is the per-save training-thread cost; the headline
       is sync/async on the per-mode MINIMUM (the barrier-free save — later
       async saves can legitimately wait out the previous write at the hard
       barrier). The model is sized so the write is measurable (~17 MB of
       params+slots).
    2. **Resume-across-topology wall time**: a zero1 run checkpointed on the
       (2,4) data×model mesh restored on a 4-device data-only mesh (shrink)
       and vice versa (grow) — agreement + quarantine sweep + shard assembly
       + re-placement, timed end to end. Needs ≥ 8 local devices (the bench
       orchestrator forces them on CPU); skipped otherwise with a note.
    """
    import shutil
    import tempfile

    import jax
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.sample import Sample, SampleToMiniBatch
    from bigdl_tpu.obs.registry import registry
    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.utils import elastic_ckpt
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random_generator import RandomGenerator

    Engine.reset()
    Engine.init()
    dev = Engine.devices()[0]
    rng = np.random.default_rng(0)

    def wide_opt(ckpt_dir):
        # ~2.1M params; with momentum slots the elastic shard is ~17 MB
        RandomGenerator.set_seed(7)
        samples = [Sample(rng.normal(size=(1024,)).astype(np.float32),
                          np.int32(rng.integers(0, 10)))
                   for _ in range(128)]
        data = DataSet.array(samples) >> SampleToMiniBatch(64)
        model = nn.Sequential().add(nn.Linear(1024, 2048)).add(nn.ReLU()) \
            .add(nn.Linear(2048, 10)).add(nn.LogSoftMax())
        opt = (LocalOptimizer(model, data, nn.ClassNLLCriterion())
               .set_optim_method(SGD(learningrate=0.01, momentum=0.9))
               .set_end_when(Trigger.max_iteration(iters)))
        opt.log_every = 10 ** 9
        opt.set_checkpoint(ckpt_dir, Trigger.several_iteration(1),
                           backend="elastic")
        return opt

    def stall_leg(async_mode: bool) -> dict:
        prev = os.environ.get("BIGDL_CKPT_ASYNC")
        os.environ["BIGDL_CKPT_ASYNC"] = "1" if async_mode else "0"
        work = tempfile.mkdtemp(prefix="ckpt-bench-")
        registry.reset()
        try:
            opt = wide_opt(work)
            opt.optimize()
            opt._join_checkpoint_writer()
            snap = registry.snapshot()
            stall = snap["histograms"]["ckpt/stall_ms"]
            out = {"stall_ms_min": stall["min"],
                   "stall_ms_mean": stall["mean"],
                   "saves": stall["count"],
                   "bytes": snap["counters"].get("ckpt/bytes", 0)}
            wr = snap["histograms"].get("ckpt/async_write_ms")
            if wr:
                out["async_write_ms_mean"] = wr["mean"]
            return out
        finally:
            if prev is None:
                os.environ.pop("BIGDL_CKPT_ASYNC", None)
            else:
                os.environ["BIGDL_CKPT_ASYNC"] = prev
            shutil.rmtree(work, ignore_errors=True)

    sync = stall_leg(async_mode=False)
    async_ = stall_leg(async_mode=True)

    # ---- topology-portable resume wall time (shrink 8→4, grow 4→8 devices)
    def mesh_ckpt(ckpt_dir, **init_kw):
        Engine.reset()
        Engine.init(**init_kw)
        RandomGenerator.set_seed(5)
        r = np.random.default_rng(0)
        samples = [Sample(r.normal(size=(8,)).astype(np.float32),
                          np.int32(r.integers(0, 3))) for _ in range(64)]
        data = DataSet.array(samples, distributed=True) >> SampleToMiniBatch(16)
        model = nn.Sequential().add(nn.Linear(8, 16)).add(nn.ReLU()) \
            .add(nn.Linear(16, 3)).add(nn.LogSoftMax())
        opt = (DistriOptimizer(model, data, nn.ClassNLLCriterion(),
                               parameter_sync="zero1")
               .set_optim_method(SGD(learningrate=0.1, momentum=0.9)))
        opt.log_every = 10 ** 9
        opt.set_checkpoint(ckpt_dir, Trigger.several_iteration(2),
                           backend="elastic")
        return opt

    def resume_ms(ckpt_dir, **init_kw) -> float:
        opt = mesh_ckpt(ckpt_dir, **init_kw)
        t0 = time.perf_counter()
        opt._load_latest_checkpoint()
        return 1e3 * (time.perf_counter() - t0)

    topo = {}
    if jax.process_count() == 1 and jax.local_device_count() >= 8:
        big = {"mesh_shape": (2, 4), "mesh_axes": ("data", "model")}
        small = {"core_number": 4}
        for name, save_kw, load_kw in (("resume_shrink_8to4_ms", big, small),
                                       ("resume_grow_4to8_ms", small, big)):
            work = tempfile.mkdtemp(prefix="ckpt-bench-topo-")
            try:
                opt = mesh_ckpt(work, **save_kw)
                opt.set_end_when(Trigger.max_iteration(2))
                opt.optimize()
                opt._join_checkpoint_writer()
                assert elastic_ckpt.complete_versions(work)
                topo[name] = round(resume_ms(work, **load_kw), 1)
            finally:
                shutil.rmtree(work, ignore_errors=True)
        Engine.reset()
        Engine.init()
    else:
        topo["topology_note"] = (
            f"topology legs skipped: {jax.local_device_count()} local "
            f"devices (< 8)")

    ratio = (sync["stall_ms_min"] / async_["stall_ms_min"]
             if async_["stall_ms_min"] else None)
    record_extra = {}
    if ratio is None or ratio < 1.0:
        # degraded-record contract (PR 6): an async path that stalls the
        # training thread MORE than sync is off-script — say so loudly
        reason = (f"elastic ckpt leg off-script: async stall "
                  f"{async_['stall_ms_min']:.1f} ms >= sync "
                  f"{sync['stall_ms_min']:.1f} ms (overlap not engaging)")
        print(f"bench: DEGRADED RUN — {reason}", file=sys.stderr)
        record_extra = {"degraded": True, "probe_error": reason}
    return {
        "value": round(ratio, 2) if ratio else None,
        "unit": "x sync/async training-thread stall per save",
        "iters": iters,
        "sync_stall_ms_min": round(sync["stall_ms_min"], 2),
        "sync_stall_ms_mean": round(sync["stall_ms_mean"], 2),
        "async_stall_ms_min": round(async_["stall_ms_min"], 2),
        "async_stall_ms_mean": round(async_["stall_ms_mean"], 2),
        "async_write_ms_mean": round(async_.get("async_write_ms_mean", 0.0),
                                     2),
        "saves_per_leg": sync["saves"],
        "ckpt_bytes_per_leg": sync["bytes"],
        **topo,
        "device_kind": dev.device_kind,
        "platform": dev.platform,
        **record_extra,
    }


def _measure_ablation(model_name: str, batch: int, iters: int) -> dict:
    """Step-time attribution (the committed profile analysis): time the full
    compiled train step and its sub-programs — forward-only, forward+backward,
    optimizer-update-only — on the same placed batch, and read XLA's compiled
    cost analysis (flops / bytes accessed) to place the step on the chip's
    compute/HBM roofline. Answers "where does the non-MXU time go" without a
    trace viewer: bwd = fwdbwd − fwd, optimizer = step − fwdbwd, and the
    roofline ratio says how much of the remaining gap is memory-bound."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.nn.precision import cast_floating
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.utils.engine import Engine

    Engine.reset()
    Engine.init(compute_dtype=jnp.bfloat16)
    dev = Engine.devices()[0]

    model, dataset, criterion = _build(model_name, batch, n_batches=2,
                                       dtype="bf16")
    opt = LocalOptimizer(model, dataset, criterion)
    opt.set_optim_method(SGD(learningrate=0.01, momentum=0.9, dampening=0.0))
    opt.log_every = 10 ** 9
    opt.set_end_when(Trigger.max_iteration(3))
    opt.optimize()   # builds + warms the real compiled step

    # effective method: matches the slot layout _final_ostate carries (the
    # flat-update wrapper changes it when BIGDL_FLAT_UPDATE is on)
    method = opt._effective_method()
    params, mstate, ostate, inp, target, rng = _placed_step_inputs(opt)
    compute_dtype = Engine.compute_dtype()

    def loss_fn(p, x, t):
        pc = cast_floating(p, compute_dtype)
        xc = cast_floating(x, compute_dtype)
        out, new_ms = model.apply(pc, mstate, xc, training=True, rng=rng)
        return criterion.apply(cast_floating(out, jnp.float32), t)

    # no donation: every program re-runs on the SAME placed buffers
    step_fn = jax.jit(opt._make_step_fn())
    fwd_fn = jax.jit(loss_fn)
    bwd_fn = jax.jit(jax.value_and_grad(loss_fn))
    zero_i = jnp.asarray(0, jnp.int32)
    _, grads0 = bwd_fn(params, inp, target)
    grads0 = jax.device_put(jax.device_get(grads0))
    upd_fn = jax.jit(lambda p, g, os_: method.update(p, g, os_, zero_i))

    def timed(run, sync):
        sync(run())                      # warm + sync
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = run()
        sync(out)
        return (time.perf_counter() - t0) / iters * 1e3   # ms/iter

    leaf0 = lambda t: jax.tree_util.tree_leaves(t)[0].block_until_ready()
    step_ms = timed(lambda: step_fn(params, mstate, ostate, zero_i, inp,
                                    target, rng),
                    lambda o: float(jax.device_get(o[3])))
    fwd_ms = timed(lambda: fwd_fn(params, inp, target),
                   lambda o: float(jax.device_get(o)))
    bwd_ms = timed(lambda: bwd_fn(params, inp, target),
                   lambda o: float(jax.device_get(o[0])))
    upd_ms = timed(lambda: upd_fn(params, grads0, ostate), leaf0)

    # XLA's own cost model for the compiled step: flops + HBM traffic
    # (lower() on the ALREADY-jitted step_fn reuses its trace/compile cache)
    cost = {}
    try:
        lowered = step_fn.lower(params, mstate, ostate, zero_i, inp,
                                target, rng)
        compiled = lowered.compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        cost = {"xla_flops": ca.get("flops"),
                "xla_bytes_accessed": ca.get("bytes accessed")}
        try:   # memory telemetry separately: its failure must not discard
            ma = compiled.memory_analysis()    # the flops numbers above
            if ma is not None:
                for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                          "output_size_in_bytes",
                          "generated_code_size_in_bytes"):
                    v = getattr(ma, k, None)
                    if v is not None:
                        cost[k.replace("_in_bytes", "_bytes")] = int(v)
        except Exception as e:
            cost["memory_analysis_error"] = f"{type(e).__name__}: {e}"[:200]
    except Exception as e:  # cost analysis is best-effort diagnostics
        cost = {"cost_analysis_error": f"{type(e).__name__}: {e}"[:200]}

    peak, bw = _peak_flops(dev.device_kind), _peak_hbm(dev.device_kind)
    roofline = {}
    if cost.get("xla_flops") and peak:
        roofline["compute_bound_ms"] = 1e3 * cost["xla_flops"] / peak
    if cost.get("xla_bytes_accessed") and bw:
        roofline["memory_bound_ms"] = 1e3 * cost["xla_bytes_accessed"] / bw
    if roofline:
        floor = max(roofline.values())
        roofline["roofline_floor_ms"] = round(floor, 3)
        roofline["step_vs_roofline"] = round(step_ms / floor, 2)
        roofline["bound"] = ("memory"
                             if roofline.get("memory_bound_ms", 0)
                             >= roofline.get("compute_bound_ms", 0)
                             else "compute")

    per_unit = _ANALYTIC_STEP_FLOPS_PER_UNIT.get(model_name)
    unit, per_sample = _MODEL_UNITS.get(model_name, ("records", 1))
    units_per_sec = batch * per_sample / (step_ms / 1e3)
    out = {
        "value": round(step_ms, 3),
        "unit": "ms/step",
        "batch": batch,
        "step_ms": round(step_ms, 3),
        "fwd_ms": round(fwd_ms, 3),
        "fwdbwd_ms": round(bwd_ms, 3),
        "update_only_ms": round(upd_ms, 3),
        "bwd_delta_ms": round(bwd_ms - fwd_ms, 3),
        "optimizer_delta_ms": round(step_ms - bwd_ms, 3),
        f"{unit}_per_sec_step": round(units_per_sec, 1),
        "mfu_step": (round(per_unit * units_per_sec / peak, 4)
                     if per_unit and peak else None),
        "device_kind": dev.device_kind,
        "platform": dev.platform,
        **{k: round(v, 3) if isinstance(v, float) else v
           for k, v in roofline.items()},
        **cost,
    }
    return out


def _obs_record() -> dict:
    """End-of-leg observability snapshot embedded in every bench record.

    ``BENCH_*.json`` lines carry the metric registry (counters, gauges,
    compacted histogram stats) and the live MFU accounting, so stage
    timings and model-FLOPs utilisation ride along automatically — on the
    degraded path too, where the snapshot shows how far the leg got before
    it fell over."""
    from bigdl_tpu.obs import mfu
    from bigdl_tpu.obs.registry import registry

    def _r(v):
        # 4 significant digits: compact for both huge flops/s and tiny MFU
        return float(f"{v:.4g}") if isinstance(v, float) else v

    snap = registry.snapshot()
    mstats = mfu.stats()
    out = {
        "counters": dict(sorted(snap["counters"].items())),
        "gauges": {k: _r(v) for k, v in sorted(snap["gauges"].items())},
        "histograms": {
            name: {k: _r(v) for k, v in h.items()}
            for name, h in sorted(snap["histograms"].items())},
        "mfu": {
            "peak_flops": _r(mstats.get("peak_flops")),
            "flops_per_sec": {k: _r(v) for k, v in
                              sorted(mstats["flops_per_sec"].items())},
        },
    }
    if "mfu" in mstats:
        out["mfu"]["mfu"] = {k: _r(v) for k, v in sorted(mstats["mfu"].items())}
    return out


def _device_memory_record() -> dict:
    """Per-device HBM block embedded next to the ``obs`` snapshot in every
    record a measuring child emits (a backend that reports no memory_stats
    yields ``devices: []``, absent-not-wrong). Child-side only: it reaches
    ``jax.local_devices()``, and the orchestrating parent must not attach a
    backend of its own."""
    from bigdl_tpu.obs import device as obs_device

    try:
        devices = obs_device.sample_device_memory(publish=False)
    except Exception:
        devices = []
    return {
        "devices": [{"id": d["id"],
                     "hbm_bytes_in_use": d["bytes_in_use"],
                     "hbm_peak_bytes": d["peak_bytes"],
                     "hbm_bytes_limit": d["bytes_limit"]}
                    for d in devices],
        "hbm_bytes_in_use": sum(d["bytes_in_use"] for d in devices),
        "hbm_peak_bytes": sum(d["peak_bytes"] or 0 for d in devices),
    }


def run_worker(args) -> None:
    """The measured child process: ONE dtype, one JSON line, exit.

    Self-validation (round-2 verdict): the end-to-end loop number is published as
    `value` only when it is within 1.5x of the direct-step capability. On larger
    divergence the step number is published (`suspect: true`), with both legs
    reported — the harness never presents a broken-loop measurement as the
    framework's speed without saying so.
    """
    res = _measure(args.model, args.batch, args.iters, args.warmup, args.dtype)
    unit = res["unit"]
    loop_ups, step_ups = res["units_per_sec"], res["units_per_sec_step"]
    perstep_ups, fuse = res["units_per_sec_perstep"], res["fuse_steps"]
    if step_ups is None:
        ratio, suspect = None, False  # cross-check unavailable; loop stands alone
    else:
        # the primary loop number (fused when fusion is on) vs the raw compiled
        # step: ~1.0 means the loop itself costs nothing beyond the program
        ratio = (step_ups / loop_ups) if loop_ups else float("inf")
        suspect = ratio > 1.5
    value, mfu = (step_ups, res["mfu_step"]) if suspect else (loop_ups, res["mfu"])
    line = {
        "metric": f"{args.model}_train_{unit}_per_sec_per_chip",
        "value": round(value, 1),
        "unit": f"{unit}/sec",
        "vs_baseline": None,
        "dtype": args.dtype,
        "batch": args.batch,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "fuse_steps": fuse,
        f"{unit}_per_sec_loop": round(loop_ups, 1),
        f"{unit}_per_sec_step": round(step_ups, 1) if step_ups is not None else None,
        "loop_step_ratio": round(ratio, 2) if ratio is not None else None,
        "suspect": suspect,
        "device_kind": res["device_kind"],
        "platform": res["platform"],
        "feed_wait_ms": round(res["feed_wait_ms"], 2),
    }
    if fuse > 1 and perstep_ups is not None:
        # both dispatch legs, explicitly: the fused window loop and the
        # classic per-step loop, plus their ratio (the loop-overhead win)
        line[f"{unit}_per_sec_fused"] = round(loop_ups, 1)
        line[f"{unit}_per_sec_perstep"] = round(perstep_ups, 1)
        line["fused_speedup"] = (round(loop_ups / perstep_ups, 3)
                                 if perstep_ups else None)
        if step_ups is not None and perstep_ups:
            line["perstep_step_ratio"] = round(step_ups / perstep_ups, 2)
    if res.get("step_leg_error"):
        line["step_leg_error"] = res["step_leg_error"]
    if res.get("peak_hbm_mb") is not None:
        line["peak_hbm_mb"] = res["peak_hbm_mb"]
    if args.model == "transformerlm-long":
        line["seq_len"] = _LONG_SEQ
        line["attention_impl"] = _long_attn()
    if suspect:
        line["suspect_reason"] = (
            "optimize() loop >1.5x slower than the same compiled step driven "
            "raw; publishing step capability, loop number retained for diagnosis")
    if args.streamed:
        # fresh-transfer leg LAST (it flips the env for this process): the same
        # loop with the device batch cache off — h2d on the (prefetch-
        # overlapped) feed path every step, the real-streaming-data number
        try:
            sres = _measure(args.model, args.batch, max(args.iters // 2, 5),
                            max(args.warmup // 2, 3), args.dtype, streamed=True)
            line[f"{unit}_per_sec_streamed"] = round(sres["units_per_sec"], 1)
            line["streamed_feed_wait_ms"] = round(sres["feed_wait_ms"], 2)
        except Exception as e:
            line["streamed_leg_error"] = f"{type(e).__name__}: {e}"[:300]
    line["obs"] = _obs_record()
    line["device_memory"] = _device_memory_record()
    print(json.dumps(line))


def _probe_backend(env: dict, timeout: float, retries: int | None = None,
                   backoff: float | None = None, sleep=time.sleep) -> str | None:
    """Cheap bounded device probe with retry + exponential backoff — a fast
    failure, nothing else: when the backend does not answer, the orchestrator
    reports that and exits non-zero in seconds instead of waiting out a full
    measurement timeout.

    A TRANSIENT attach failure (libtpu still initialising, another process
    holding the chip) gets ``retries`` total attempts
    (BIGDL_BENCH_PROBE_RETRIES, default 3) spaced ``backoff · 2^(attempt-1)``
    seconds apart (BIGDL_BENCH_PROBE_BACKOFF, default 2 s). The probe child
    exits before the measuring child starts, so the two never hold the chip
    at once. Returns None when the backend answers, else the last failure
    reason."""
    if retries is None:
        retries = max(1, int(env.get("BIGDL_BENCH_PROBE_RETRIES", "3")))
    if backoff is None:
        backoff = float(env.get("BIGDL_BENCH_PROBE_BACKOFF", "2"))
    code = "import jax; print(jax.device_count(), jax.devices()[0].platform)"
    err = None
    for attempt in range(1, retries + 1):
        try:
            p = subprocess.run([sys.executable, "-c", code],
                               capture_output=True, text=True,
                               timeout=timeout, env=env)
            if p.returncode == 0:
                return None
            tail = (p.stderr or p.stdout or "").strip().splitlines()[-3:]
            err = (f"device probe rc={p.returncode}: "
                   + " | ".join(tail)[-300:])
        except subprocess.TimeoutExpired:
            err = f"device probe timed out after {timeout:.0f}s"
        except OSError as e:
            err = f"device probe failed to spawn: {e}"
        if attempt < retries:
            delay = backoff * (2 ** (attempt - 1))
            print(f"bench: probe attempt {attempt}/{retries} failed "
                  f"({err}); retrying in {delay:.0f}s", file=sys.stderr)
            sleep(delay)
    return f"{err} (after {retries} attempts)"


def _spawn(argv, env, timeout):
    # the child must import bigdl_tpu even when the package isn't installed and
    # cwd is elsewhere: prepend the parent's package root to PYTHONPATH
    env = dict(env)
    env["PYTHONPATH"] = _REPO_DIR + os.pathsep + env.get("PYTHONPATH", "")
    try:
        p = subprocess.run([sys.executable, "-m", "bigdl_tpu.benchmark"] + argv,
                           capture_output=True, text=True, timeout=timeout,
                           env=env)
    except subprocess.TimeoutExpired:
        return None, f"timeout after {timeout}s"
    for ln in reversed(p.stdout.strip().splitlines()):
        try:
            return json.loads(ln), None
        except json.JSONDecodeError:
            continue
    tail = (p.stderr or p.stdout or "").strip().splitlines()[-8:]
    return None, f"rc={p.returncode}: " + " | ".join(tail)[-600:]


# the side legs: (argparse attribute, worker flag, metric suffix of the
# leg's record). At most one is expected per invocation; the first set wins.
_SIDE_LEGS = (
    ("int8_infer", "--int8-infer", "int8_vs_bf16_infer"),
    ("serving", "--serving", "serving"),
    ("decode_infer", "--decode-infer", "decode_infer"),
    ("eval_bench", "--eval-bench", "eval_throughput"),
    ("pipeline_bench", "--pipeline-bench", "input_pipeline"),
    ("stream_bench", "--stream-bench", "stream_pipeline"),
    ("obs_bench", "--obs-bench", "obs_overhead"),
    ("kernel_bench", "--kernel-bench", "kernel_bench"),
    ("precision_bench", "--precision-bench", "precision_bench"),
    ("serving_bench", "--serving-bench", "serving_engine"),
    ("fleet_bench", "--fleet-bench", "serving_fleet"),
    ("recsys_bench", "--recsys-bench", "recsys_bench"),
    ("ckpt_bench", "--ckpt-bench", "ckpt_bench"),
    ("promotion_bench", "--promotion-bench", "promotion_bench"),
    ("paging_bench", "--paging-bench", "paging_bench"),
    ("ablate", "--ablate", "step_ablation"),
)


def run_orchestrator(args) -> int:
    """Spawn the measuring child and print its JSON line; returns the exit
    code. When the accelerator leg fails, print a ``"value": null`` record
    with the reason and return 1 — no leg runs on a platform the caller did
    not ask for. This parent never imports JAX: the child holds the chip."""
    # getattr: tolerate hand-built Namespaces (tests/drivers) predating a flag
    legs = [(flag, kind) for attr, flag, kind in _SIDE_LEGS
            if getattr(args, attr, False)]
    worker_argv = ["--run", "--model", args.model, "--batch", str(args.batch),
                   "--iters", str(args.iters), "--warmup", str(args.warmup),
                   "--dtype", args.dtype]
    # the worker re-parses with default=True, so absence can't express "off" —
    # always pass the streamed state explicitly
    worker_argv.append("--streamed" if args.streamed else "--no-streamed")
    worker_argv += [flag for flag, _ in legs]
    env = dict(os.environ)
    if getattr(args, "ckpt_bench", False) \
            and env.get("JAX_PLATFORMS") == "cpu" \
            and "xla_force_host_platform_device_count" \
            not in env.get("XLA_FLAGS", ""):
        # the topology-resume legs need an 8-device mesh; on CPU that means
        # forcing virtual devices before the worker's backend initializes
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8"
                            ).strip()
    # Fast-fail: one cheap bounded probe decides whether the backend answers
    # AT ALL before a full measurement is allowed to sink its timeout.
    # BIGDL_BENCH_PROBE_TIMEOUT=0 disables the probe.
    probe_timeout = float(env.get("BIGDL_BENCH_PROBE_TIMEOUT", "45"))
    probe_err = None
    if env.get("JAX_PLATFORMS") != "cpu" and probe_timeout > 0:
        probe_err = _probe_backend(env, probe_timeout)
    err = probe_err
    if probe_err is None:
        print(f"bench: {args.model} dtype={args.dtype} batch={args.batch}",
              file=sys.stderr)
        result, err = _spawn(worker_argv, env, args.timeout)
        if result is not None:
            # comparison leg in its OWN subprocess: its failure can never
            # discard the good primary number above
            if args.compare_dtypes and args.dtype == "bf16" and not legs:
                # the comparison leg only feeds the ratio — skip its streamed
                # measurement (it would be discarded)
                cmp_argv = ["--run", "--model", args.model,
                            "--batch", str(args.batch),
                            "--iters", str(max(args.iters // 2, 5)),
                            "--warmup", str(args.warmup), "--dtype", "fp32",
                            "--no-streamed"]
                cmp_res, cmp_err = _spawn(cmp_argv, env, args.timeout)
                unit = (result.get("unit") or "units/sec").split("/")[0]
                if cmp_res is not None and cmp_res.get("value"):
                    result[f"fp32_{unit}_per_sec"] = cmp_res["value"]
                    # compare like with like: both legs' loop numbers when both
                    # loops are healthy, else both step numbers — never a mix of
                    # methodologies
                    if not result.get("suspect") and not cmp_res.get("suspect"):
                        num, den, basis = (result[f"{unit}_per_sec_loop"],
                                           cmp_res[f"{unit}_per_sec_loop"], "loop")
                    else:
                        num, den, basis = (result.get(f"{unit}_per_sec_step"),
                                           cmp_res.get(f"{unit}_per_sec_step"),
                                           "step")
                    if num and den:
                        result["bf16_fp32_ratio"] = round(num / den, 2)
                        result["bf16_fp32_ratio_basis"] = basis
                elif cmp_err:
                    print(f"bench: fp32 comparison leg failed: {cmp_err}",
                          file=sys.stderr)
            result.update(_provenance())
            print(json.dumps(result))
            return 0

    print(f"bench: FAILED — {err}; no measurement was taken and none is "
          f"substituted", file=sys.stderr)
    record = {
        "metric": f"{args.model}_" + (legs[0][1] if legs else "train"),
        "value": None,
        "vs_baseline": None,
        "error": str(err)[-1200:],
    }
    if probe_err:
        record["probe_error"] = probe_err
    record.update(_provenance())
    print(json.dumps(record))
    return 1


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50",
                   choices=sorted(_MODEL_UNITS))
    # defaults measured on v5e: batch 256 beats 128 (1998 vs 1912 img/s loop,
    # MFU 0.249 vs 0.238); warmup 12 > the 8 in-memory batches so the device
    # cache is fully populated before the timed window opens
    p.add_argument("--batch", type=int, default=None,
                   help="samples/step (per-model default when omitted)")
    p.add_argument("--iters", type=int, default=24)
    p.add_argument("--warmup", type=int, default=12)
    p.add_argument("--dtype", default="bf16", choices=["bf16", "fp32"])
    p.add_argument("--compare-dtypes", action="store_true", default=True,
                   help="also run fp32 and report the bf16:fp32 ratio")
    p.add_argument("--no-compare-dtypes", dest="compare_dtypes",
                   action="store_false")
    p.add_argument("--streamed", action="store_true", default=True,
                   help="also measure with the device batch cache off "
                        "(fresh h2d transfer every step)")
    p.add_argument("--no-streamed", dest="streamed", action="store_false")
    p.add_argument("--timeout", type=int, default=1500,
                   help="per-attempt subprocess timeout (s)")
    p.add_argument("--int8-infer", action="store_true",
                   help="inference micro-bench: bf16 vs int8-quantized forward")
    p.add_argument("--serving", action="store_true",
                   help="serving-path micro-bench: Predictor.predict and "
                        "Evaluator.test samples/sec")
    p.add_argument("--decode-infer", action="store_true",
                   help="LM decode micro-bench: KV-cached greedy_generate "
                        "tokens/sec vs the uncached static-block search")
    p.add_argument("--ablate", action="store_true",
                   help="step-time attribution: fwd / fwd+bwd / update "
                        "sub-program timings + XLA cost-analysis roofline")
    p.add_argument("--eval-bench", action="store_true",
                   help="eval-throughput leg: device-resident fused eval "
                        "windows vs per-batch eval, plus d2h bytes/image")
    p.add_argument("--pipeline-bench", dest="pipeline_bench",
                   action="store_true",
                   help="host input-pipeline leg: decode→augment→stack "
                        "images/sec on a synthetic image folder at "
                        "BIGDL_DATA_WORKERS 0/1/4/auto, with per-stage ms")
    p.add_argument("--stream-bench", dest="stream_bench",
                   action="store_true",
                   help="streaming data-plane leg: sharded record stream "
                        "with the decoded-sample cache — cold (decode + "
                        "cache build) vs warm (mmap) epoch images/sec, "
                        "cache_speedup, per-stage ms")
    p.add_argument("--obs-bench", dest="obs_bench", action="store_true",
                   help="observability-overhead leg: CPU LeNet images/sec "
                        "with the span tracer off vs on (gate: <3% "
                        "overhead), plus trace/JSONL artifact validity")
    p.add_argument("--kernel-bench", dest="kernel_bench", action="store_true",
                   help="kernel-fusion leg: fused (BN-folded) vs unfused "
                        "conv-bn inference img/s, flat vs per-leaf optimizer "
                        "update wall time, grad-accum/remat activation-"
                        "memory proxy from XLA memory analysis")
    p.add_argument("--precision-bench", dest="precision_bench",
                   action="store_true",
                   help="low-precision step experiment: fp32 vs bf16 train-"
                        "step throughput, int8 quantized-forward family, "
                        "fp8 forward probe")
    p.add_argument("--serving-bench", dest="serving_bench",
                   action="store_true",
                   help="online serving-engine leg: continuous-batching "
                        "sustained req/s vs the one-request-at-a-time "
                        "baseline, TTFT/per-token p50/p99, compile-count "
                        "assertion proving prefill-bucket reuse")
    p.add_argument("--fleet-bench", dest="fleet_bench",
                   action="store_true",
                   help="serving-fleet leg: N-replica router req/s under "
                        "scripted replica_down churn (zero lost) vs one "
                        "replica, shared-prefix TTFT with the prefix "
                        "KV-cache pool warm vs cold, speculative-decode "
                        "tokens/s at pinned 100% acceptance vs plain")
    p.add_argument("--recsys-bench", dest="recsys_bench",
                   action="store_true",
                   help="sharded-embedding recsys leg: sparse vs dense "
                        "(flat-update) step time on a (V, 64) table at "
                        "V=1e5/1e6 with zipf ids, dedup hit-rate, and "
                        "RankingEngine req/s on a small NeuralCF")
    p.add_argument("--ckpt-bench", dest="ckpt_bench",
                   action="store_true",
                   help="elastic-checkpointing leg: training-thread stall "
                        "per save sync (BIGDL_CKPT_ASYNC=0) vs async, plus "
                        "resume-across-topology wall time for a zero1 "
                        "checkpoint restored on a shrunk (8→4) and grown "
                        "(4→8) device mesh")
    p.add_argument("--promotion-bench", dest="promotion_bench",
                   action="store_true",
                   help="promotion-lifecycle leg: sustained req/s + TTFT "
                        "p99 flatness across a mid-window zero-downtime "
                        "weight swap (zero dropped, program ledger "
                        "pinned), gate-rejection drill on a NaN-poisoned "
                        "candidate, and auto-rollback wall time with a "
                        "bitwise post-rollback output check")
    p.add_argument("--paging-bench", dest="paging_bench",
                   action="store_true",
                   help="paged-serving leg: peak resident sequences at "
                        "equal pooled KV bytes (paged pool vs slot grid, "
                        "want >= 2x), req/s + p99 TTFT over the same "
                        "trace with the paged program ledger pinned, and "
                        "p99 TTFT under a prompt burst through a "
                        "prefill/decode-disaggregated fleet vs mixed "
                        "(zero lost requests)")
    p.add_argument("--run", action="store_true",
                   help=argparse.SUPPRESS)  # internal: worker mode
    args = p.parse_args(argv)
    if args.batch is None:
        args.batch = _DEFAULT_BATCH.get(args.model, 256)
    if args.run:
        return _run_worker_modes(args)
    return run_orchestrator(args)


def _run_worker_modes(args) -> int:
    # worker mode: every leg rides the same bounded spawn path as the
    # training metric. JAX falls back to the CPU by itself when it finds no
    # accelerator; a measurement must not — a CPU leg is the caller's
    # explicit JAX_PLATFORMS=cpu, never a substitute.
    import jax
    if jax.devices()[0].platform == "cpu" \
            and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            "bench: JAX found no accelerator and fell back to the CPU; "
            "refusing to measure (set JAX_PLATFORMS=cpu to measure the CPU "
            "on purpose)")
    if args.int8_infer:
        res = _measure_int8_infer(args.model, args.batch,
                                  max(args.iters, 10))
        res["metric"] = f"{args.model}_int8_vs_bf16_infer"
    elif args.serving:
        res = _measure_serving(args.model, args.batch,
                               max(args.iters // 4, 3))
        res["metric"] = f"{args.model}_serving"
    elif args.decode_infer:
        res = _measure_decode_infer(min(args.batch, 16))
        res["metric"] = "transformerlm_decode_infer"
        res["vs_baseline"] = None
    elif args.eval_bench:
        res = _measure_eval(args.model, args.batch, max(args.iters // 4, 3))
        res["metric"] = f"{args.model}_eval_throughput"
        res["vs_baseline"] = None
    elif args.pipeline_bench:
        res = _measure_pipeline(min(args.batch, 32))
        res["metric"] = "input_pipeline_images_per_sec"
        res["vs_baseline"] = None
    elif getattr(args, "stream_bench", False):
        res = _measure_stream_bench(min(args.batch, 32))
        res["metric"] = "stream_pipeline_images_per_sec"
        res["vs_baseline"] = None
    elif getattr(args, "obs_bench", False):
        res = _measure_obs(min(args.batch, 128), args.iters)
        res["metric"] = "lenet_obs_overhead"
        res["vs_baseline"] = None
    elif getattr(args, "kernel_bench", False):
        res = _measure_kernel_bench(min(args.batch, 64),
                                    max(args.iters // 2, 8))
        res["metric"] = "kernel_bench"
        res["vs_baseline"] = None
    elif getattr(args, "precision_bench", False):
        res = _measure_precision(args.model, args.batch,
                                 max(args.iters // 2, 8))
        res["metric"] = f"{args.model}_precision_bench"
        res["vs_baseline"] = None
    elif getattr(args, "serving_bench", False):
        res = _measure_serving_bench()
        res["metric"] = "transformerlm_serving_engine"
        res["vs_baseline"] = None
    elif getattr(args, "fleet_bench", False):
        res = _measure_fleet_bench()
        res["metric"] = "transformerlm_serving_fleet"
        res["vs_baseline"] = None
    elif getattr(args, "recsys_bench", False):
        res = _measure_recsys_bench(iters=max(args.iters // 2, 5))
        res["metric"] = "ncf_recsys_bench"
        res["vs_baseline"] = None
    elif getattr(args, "ckpt_bench", False):
        res = _measure_ckpt_bench()
        res["metric"] = "elastic_ckpt_bench"
        res["vs_baseline"] = None
    elif getattr(args, "promotion_bench", False):
        res = _measure_promotion_bench()
        res["metric"] = "transformerlm_promotion"
        res["vs_baseline"] = None
    elif getattr(args, "paging_bench", False):
        res = _measure_paging_bench()
        res["metric"] = "transformerlm_paged_serving"
        res["vs_baseline"] = None
    elif args.ablate:
        res = _measure_ablation(args.model, args.batch,
                                max(args.iters // 2, 8))
        res["metric"] = f"{args.model}_step_ablation"
        res["vs_baseline"] = None
    else:
        run_worker(args)  # attaches its own end-of-leg obs snapshot
        return 0
    res["obs"] = _obs_record()
    res["device_memory"] = _device_memory_record()
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
