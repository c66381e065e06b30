"""Evaluator / Predictor — batched inference over datasets.

Reference parity (SURVEY.md §2.3/§3.5, expected ``<dl>/optim/Evaluator.scala`` and
``<dl>/optim/Predictor.scala`` — unverified): ``model.evaluate(rdd, methods,
batchSize)`` broadcasts the model and folds ValidationMethod partials per partition;
``model.predict`` / ``predictClass`` map a forward pass over samples.

TPU-native: no broadcast/partition machinery — one cached jit forward; batches stream
through ``SampleToMiniBatch`` (static shapes, padded tail with explicit valid count);
on a multi-device mesh the batch is sharded over the data axis so evaluation scales
the same way training does (the reference reused executor replicas; we reuse the SPMD
partitioner).

Device-resident evaluation (the eval mirror of the fused training windows):
``BIGDL_EVAL_FUSE_STEPS=K`` makes the eval loop disappear into the compiled
program the same way ``BIGDL_FUSE_STEPS`` does for training. The feed's
producer thread stacks K eval batches into a device super-batch (leading scan
axis), ONE jitted ``lax.scan`` runs K forwards and folds every device-capable
ValidationMethod's partials into an on-device carry, and the whole eval pass
fetches O(1) metric scalars at the end instead of O(batch x classes) logits
per batch. Methods without a device kernel (``has_device_fold() == False``,
e.g. MeanAveragePrecision) keep the host fold automatically — only then are
window outputs fetched, double-buffered so the d2h of window i overlaps the
forward of window i+1. Padded tails ride the existing ``valid`` counts as
boolean masks inside the fold.
"""

from __future__ import annotations

import os
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.dataset.dataset import AbstractDataSet, DataSet
from bigdl_tpu.dataset.prefetch import PrefetchingFeed
from bigdl_tpu.dataset.sample import Sample, SampleToMiniBatch
from bigdl_tpu.obs import trace
from bigdl_tpu.optim.validation import ValidationMethod, ValidationResult
from bigdl_tpu.utils.engine import Engine


def cached_forward_jit(model):
    """One jitted inference forward per (model, compute dtype) — repeat
    predict/evaluate calls (e.g. a serving loop) reuse the compiled executable
    instead of retracing. Container.add invalidates the cache on structure
    change. Inference honors the Engine compute dtype the same way training
    does: bf16 matmuls, fp32 outputs for the ValidationMethods."""
    from bigdl_tpu.nn.precision import cast_floating

    compute_dtype = Engine.compute_dtype()
    cache = model.__dict__.setdefault("_cached_fwd_jit", {})
    fn = cache.get(jnp.dtype(compute_dtype).name)
    if fn is None:
        mixed = compute_dtype != jnp.float32

        def fwd(params, mstate, inp):
            if mixed:
                params = cast_floating(params, compute_dtype)
                inp = cast_floating(inp, compute_dtype)
            out, _ = model.apply(params, mstate, inp, training=False, rng=None)
            return cast_floating(out, jnp.float32) if mixed else out

        fn = jax.jit(fwd)
        cache[jnp.dtype(compute_dtype).name] = fn
    return fn


def eval_fuse_steps(override: Optional[int] = None) -> int:
    """Eval-window size: ``override`` if given, else ``BIGDL_EVAL_FUSE_STEPS``
    (default 8). 1 disables fusion (per-batch dispatch, still double-buffered)."""
    raw = os.environ.get("BIGDL_EVAL_FUSE_STEPS", "8") if override is None \
        else override
    try:
        k = int(raw)
        if k < 1:
            raise ValueError
    except (TypeError, ValueError):
        raise ValueError(
            f"eval fuse steps must be an integer >= 1, got {raw!r}")
    return k


def _eval_unroll(k: int) -> int:
    """Scan unroll for the fused eval window — same policy (and knob,
    ``BIGDL_FUSE_UNROLL``) as the training windows: full unroll on CPU where
    XLA while-loop bodies codegen ~2x slower, rolled scan on TPU."""
    raw = os.environ.get("BIGDL_FUSE_UNROLL", "auto").strip().lower()
    if raw in ("auto", ""):
        return k if Engine.devices()[0].platform == "cpu" else 1
    return max(1, min(int(raw), k))


def _put_eval_batch(inp):
    """Place an inference batch (array or pytree of feature arrays): batch dim
    sharded over the mesh's data axis when it divides evenly (the SPMD
    partitioner then splits the forward like DistriOptimizer's step), else
    default device. The divisibility policy is shard_leading_axis — one copy."""
    mesh = Engine.mesh()
    if mesh is not None and Engine.DATA_AXIS in mesh.axis_names \
            and int(dict(mesh.shape)[Engine.DATA_AXIS]) > 1:
        from bigdl_tpu.parallel.sharding import shard_leading_axis
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(
                x, shard_leading_axis(mesh, np.shape(x), Engine.DATA_AXIS)), inp)
    return jax.device_put(inp)


def _put_eval_window(tree):
    """Place a STACKED eval super-batch (leading scan axis K, then batch):
    the scan axis stays unsharded and the batch axis shards over ``data`` —
    the same layout the fused training windows use, so the per-step SPMD
    partitioning is identical to per-batch eval with zero extra collectives."""
    mesh = Engine.mesh()
    if mesh is not None and Engine.DATA_AXIS in mesh.axis_names \
            and int(dict(mesh.shape)[Engine.DATA_AXIS]) > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        n = int(dict(mesh.shape)[Engine.DATA_AXIS])
        win_sh = NamedSharding(mesh, P(None, Engine.DATA_AXIS))

        def put(x):
            shape = np.shape(x)
            if len(shape) >= 2 and shape[1] % n == 0:
                return jax.device_put(x, win_sh)
            return jax.device_put(x)

        return jax.tree_util.tree_map(put, tree)
    return jax.device_put(tree)


def _fetch(out):
    """Device→host fetch that works under multi-process meshes: an output
    sharded over the GLOBAL mesh spans non-addressable devices, so gather it
    across processes first (every process then holds the full array — the
    reference's driver-side aggregation shape)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        out = multihost_utils.process_allgather(out, tiled=True)
    return jax.device_get(out)


def _nbytes(tree) -> int:
    """Byte size of a pytree from shape x dtype — never materializes device
    data on host (this feeds the ``val_fetch_bytes`` observability number)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
    return total


def _as_dataset(data, batch_size: Optional[int]) -> AbstractDataSet:
    """Accept a DataSet (already batched), a list of Samples, or a numpy array."""
    if isinstance(data, AbstractDataSet):
        return data
    if batch_size is None:
        raise ValueError("batch_size is required when passing raw samples/arrays")
    if isinstance(data, np.ndarray):
        # match the reference's JTensor coercion: integer image arrays arrive as
        # uint8 — cast to the float compute dtype before tracing
        if not np.issubdtype(data.dtype, np.floating):
            data = data.astype(np.float32)
        data = [Sample(x) for x in data]
    return DataSet.array(list(data)) >> SampleToMiniBatch(batch_size)


def _stack_host(xs: list):
    """Stack per-batch (possibly nested) host pytrees along a new leading scan
    axis — host-side, in the feed's producer thread, so the stacked
    super-batch ships as ONE h2d transfer (mirror of Optimizer._stack_window)."""
    return jax.tree_util.tree_map(lambda *leaves: np.stack(leaves), *xs)


def _prefetch_depth(depth: Optional[int]) -> int:
    return int(os.environ.get("BIGDL_PREFETCH", "2")) if depth is None else depth


# --------------------------------------------------------------------- engine
#: bound on cached eval programs per model (beyond it, oldest evicted — a
#: serving loop constructing fresh method objects every call must not grow
#: the trace cache without limit)
_EVAL_CACHE_MAX = 8


def _evict_eval_programs(cache: dict) -> None:
    tuple_keys = [k for k in cache if isinstance(k, tuple)]
    while len(tuple_keys) > _EVAL_CACHE_MAX:
        cache.pop(tuple_keys.pop(0), None)  # dict order = insertion = oldest


def _eval_programs(model, dev_methods: Sequence[ValidationMethod],
                   fuse: int, need_outs: bool):
    """(fold1, foldK) jitted forward+fold programs, cached on the model (same
    dict Container.add/pickling invalidate for the plain forward). fold1 runs
    one batch; foldK scans a K-stacked super-batch. Both thread the metric
    carry through so partials never leave the device."""
    fwd = cached_forward_jit(model)
    key = ("eval_fold", jnp.dtype(Engine.compute_dtype()).name,
           tuple(id(m) for m in dev_methods), fuse, need_outs)
    cache = model.__dict__.setdefault("_cached_fwd_jit", {})
    hit = cache.get(key)
    # id() can be recycled after GC — the cached entry pins the method objects
    # it was traced for and is only reused when they are THE SAME objects
    if hit is not None and all(a is b for a, b in zip(hit[0], dev_methods)):
        return hit[1], hit[2]

    def fold_one(params, mstate, carry, inp, target, mask):
        out = fwd(params, mstate, inp)
        part = tuple(m.device_fold(out, target, mask) for m in dev_methods)
        carry = tuple(m.merge(c, p)
                      for m, c, p in zip(dev_methods, carry, part))
        return carry, (out if need_outs else ())

    def fold_scan(params, mstate, carry, inp, target, mask):
        def body(c, xs):
            x, t, mk = xs
            return fold_one(params, mstate, c, x, t, mk)

        return jax.lax.scan(body, carry, (inp, target, mask),
                            unroll=_eval_unroll(fuse))

    fold1 = jax.jit(fold_one)
    foldK = jax.jit(fold_scan) if fuse > 1 else None
    cache[key] = (tuple(dev_methods), fold1, foldK)
    _evict_eval_programs(cache)
    return fold1, foldK


def _init_carry(model, dev_methods, params, mstate, batch):
    """Zero metric carry shaped by eval_shape of the first batch's fold — no
    device work, just abstract tracing."""
    if not dev_methods:
        return ()
    fwd = cached_forward_jit(model)

    def spec(x):
        a = np.asarray(x) if not hasattr(x, "shape") else x
        return jax.ShapeDtypeStruct(np.shape(a), np.dtype(a.dtype))

    inp_s = jax.tree_util.tree_map(spec, batch.input)
    tgt_s = jax.tree_util.tree_map(spec, batch.target)
    mask_s = jax.ShapeDtypeStruct((batch.size(),), np.dtype(bool))
    out_s = jax.eval_shape(fwd, params, mstate, inp_s)
    carry = []
    for m in dev_methods:
        part_s = jax.eval_shape(m.device_fold, out_s, tgt_s, mask_s)
        carry.append(jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), part_s))
    return tuple(carry)


def run_device_eval(model, params, mstate, dataset,
                    methods: Sequence[ValidationMethod],
                    fuse_steps: Optional[int] = None,
                    depth: Optional[int] = None,
                    allow_empty: bool = False):
    """One eval pass with device-resident metric folds.

    Returns ``(results, stats)`` — ``results`` aligned with ``methods``;
    ``stats`` is the observability pair the optimizer logs plus breakdowns:
    ``fetch_bytes`` (total d2h payload), ``wait_ms`` (host time blocked on
    fetches), ``fused_windows``, ``batches``. Shared by ``Evaluator.test``
    and the Optimizer's mid-training validation trigger, so both run the
    same compiled programs on the same feed."""
    fuse = eval_fuse_steps(fuse_steps)
    dev_methods = [m for m in methods if m.has_device_fold()]
    dev_idx = [i for i, m in enumerate(methods) if m.has_device_fold()]
    host_idx = [i for i, m in enumerate(methods) if not m.has_device_fold()]
    need_outs = bool(host_idx)
    fold1, foldK = _eval_programs(model, dev_methods, fuse, need_outs)
    stats = {"fetch_bytes": 0, "wait_ms": 0.0, "fused_windows": 0,
             "batches": 0, "samples": 0}
    results: list[Optional[ValidationResult]] = [None] * len(methods)
    carry = None
    pending = None  # (outs_dev, group, is_window) awaiting host fold

    def place(group):
        # runs in the feed's producer thread: h2d overlaps the forward
        # (window=1 feeds deliver bare batches, not lists)
        if not isinstance(group, list):
            group = [group]
        if len(group) == 1:
            b = group[0]
            inp = _put_eval_batch(b.input)
            tgt = _put_eval_batch(b.target) if dev_methods else ()
            mask = (_put_eval_batch(np.arange(b.size()) < b.valid)
                    if dev_methods else ())
            return inp, tgt, mask
        inp = _put_eval_window(_stack_host([b.input for b in group]))
        tgt = (_put_eval_window(_stack_host([b.target for b in group]))
               if dev_methods else ())
        mask = (_put_eval_window(np.stack(
                    [np.arange(b.size()) < b.valid for b in group]))
                if dev_methods else ())
        return inp, tgt, mask

    def drain(outs_dev, group, is_window):
        # host fold for methods without a device kernel: fetch the window's
        # outputs (the ONLY d2h logits traffic left) and apply per batch
        t0 = time.perf_counter()
        with trace.span("eval/fetch"):
            outs = _fetch(outs_dev)
        stats["wait_ms"] += (time.perf_counter() - t0) * 1e3
        stats["fetch_bytes"] += _nbytes(outs_dev)
        per_batch = outs if is_window else [outs]
        for out, b in zip(per_batch, group):
            target = np.asarray(b.target) if b.target is not None else None
            for i in host_idx:
                r = methods[i].apply(np.asarray(out), target, b.valid)
                results[i] = r if results[i] is None else results[i] + r

    feed = PrefetchingFeed(lambda: dataset.data(train=False), place,
                           depth=_prefetch_depth(depth),
                           window=fuse, train=False)
    with feed, trace.span("eval/pass"):
        for group, placed in feed:
            if not isinstance(group, list):
                group = [group]
            stats["batches"] += len(group)
            stats["samples"] += sum(b.valid for b in group)
            if carry is None:
                carry = _init_carry(model, dev_methods, params, mstate,
                                    group[0])
            inp, tgt, mask = placed
            if len(group) > 1:
                with trace.span("eval/window", {"k": len(group)}):
                    carry, outs = foldK(params, mstate, carry, inp, tgt,
                                        mask)
                stats["fused_windows"] += 1
            else:
                with trace.span("eval/batch"):
                    carry, outs = fold1(params, mstate, carry, inp, tgt,
                                        mask)
            if need_outs:
                if pending is not None:
                    # double-buffer: fetch window i-1 while window i computes
                    drain(*pending)
                pending = (outs, group, len(group) > 1)
    if pending is not None:
        drain(*pending)
    if stats["batches"] == 0:
        if allow_empty:  # mid-training validation: a drained val feed is a
            return results, stats  # no-op round, not a training abort
        raise ValueError("empty dataset")
    if dev_methods:
        t0 = time.perf_counter()
        host_carry = _fetch(carry)
        stats["wait_ms"] += (time.perf_counter() - t0) * 1e3
        stats["fetch_bytes"] += _nbytes(carry)
        for i, m, acc in zip(dev_idx, dev_methods, host_carry):
            results[i] = m.finalize(acc)
    if not allow_empty and any(r is None for r in results):
        raise ValueError("empty dataset")
    return results, stats


class Predictor:
    """Forward-only mapper. ``predict`` returns stacked outputs (padding rows
    dropped); ``predict_class`` the argmax class index per sample.

    ``predict`` keeps the per-window logits fetch (the outputs ARE the
    result) but runs fused K-batch forward windows and overlaps each
    window's d2h with the NEXT window's dispatch (double-buffered), with
    h2d placement on the feed's producer thread."""

    def __init__(self, model):
        self.model = model

    def _fwd(self):
        return cached_forward_jit(self.model)

    def _window_fwd(self, fuse: int):
        fwd = self._fwd()
        key = ("predict_window", jnp.dtype(Engine.compute_dtype()).name, fuse)
        cache = self.model.__dict__.setdefault("_cached_fwd_jit", {})
        fn = cache.get(key)
        if fn is None:
            def win(params, mstate, inp):
                def body(_, x):
                    return (), fwd(params, mstate, x)

                _, outs = jax.lax.scan(body, (), inp,
                                       unroll=_eval_unroll(fuse))
                return outs

            fn = cache[key] = jax.jit(win)
            _evict_eval_programs(cache)
        return fn

    def predict(self, data, batch_size: Optional[int] = None,
                fuse_steps: Optional[int] = None) -> np.ndarray:
        Engine._require_init()
        dataset = _as_dataset(data, batch_size)
        fuse = eval_fuse_steps(fuse_steps)
        fwd = self._fwd()
        win_fwd = self._window_fwd(fuse) if fuse > 1 else None
        params, mstate = self.model.get_params(), self.model.get_state()
        outs: list[np.ndarray] = []
        pending = None  # (outs_dev, group, is_window)

        def place(group):
            if not isinstance(group, list):
                group = [group]
            if len(group) == 1:
                return _put_eval_batch(group[0].input)
            return _put_eval_window(_stack_host([b.input for b in group]))

        def drain(dev, group, is_window):
            host = np.asarray(_fetch(dev)) if not is_window else _fetch(dev)
            per_batch = host if is_window else [host]
            for out, b in zip(per_batch, group):
                outs.append(np.asarray(out)[: b.valid])

        feed = PrefetchingFeed(lambda: dataset.data(train=False), place,
                               depth=_prefetch_depth(None),
                               window=fuse, train=False)
        with feed:
            for group, placed in feed:
                if not isinstance(group, list):
                    group = [group]
                if len(group) > 1:
                    cur = win_fwd(params, mstate, placed)
                else:
                    cur = fwd(params, mstate, placed)
                if pending is not None:
                    drain(*pending)  # overlaps with cur's device execution
                pending = (cur, group, len(group) > 1)
        if pending is not None:
            drain(*pending)
        if not outs:
            raise ValueError("empty dataset")
        return np.concatenate(outs, axis=0)

    def predict_class(self, data, batch_size: Optional[int] = None) -> np.ndarray:
        out = self.predict(data, batch_size)
        return out.reshape(out.shape[0], -1).argmax(axis=1).astype(np.int32)


class Evaluator:
    """Runs ValidationMethods over a dataset; partial results fold with ``+``.

    Device-capable methods (``has_device_fold()``) accumulate on device across
    fused eval windows and the pass fetches one small scalar pytree at the
    end; the rest fold on host from (double-buffered) output fetches. The last
    pass's observability numbers are kept on ``self.last_stats``."""

    def __init__(self, model):
        self.model = model
        self.last_stats: Optional[dict] = None

    def test(self, dataset, methods: Sequence[ValidationMethod],
             batch_size: Optional[int] = None,
             fuse_steps: Optional[int] = None):
        Engine._require_init()
        if not methods:
            raise ValueError(
                "methods is required: pass ValidationMethods, e.g. "
                "model.evaluate(ds, [Top1Accuracy()], batch_size=32)")
        dataset = _as_dataset(dataset, batch_size)
        params, mstate = self.model.get_params(), self.model.get_state()
        results, stats = run_device_eval(
            self.model, params, mstate, dataset, list(methods),
            fuse_steps=fuse_steps)
        self.last_stats = stats
        return list(zip(results, methods))
