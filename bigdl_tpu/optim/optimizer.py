"""Optimizer front-end + LocalOptimizer.

Reference parity (SURVEY.md §2.3/§3.1/§3.2, expected ``<dl>/optim/Optimizer.scala``,
``LocalOptimizer.scala`` — unverified): ``Optimizer(model, dataset, criterion)`` dispatches
Local vs Distri by dataset type; fluent config (``setOptimMethod``, ``setEndWhen``,
``setValidation``, ``setCheckpoint``, ``setTrainSummary``, ``setGradientClipping``);
``optimize()`` runs the loop and returns the trained model.

TPU-native redesign of the hot loop: where the reference's LocalOptimizer splits each batch
over per-core model replicas with thread pools and sums gradients (SURVEY.md §3.2), here the
ENTIRE iteration — forward, loss, backward, optimizer update — is ONE compiled XLA program
(``jit`` with donated buffers). Per-core replication is XLA's job on a single chip; across
chips the same step compiles over a mesh (DistriOptimizer). Checkpoint/retry semantics (§5.3)
are preserved in the loop. With ``BIGDL_FUSE_STEPS=K`` the loop itself fuses too: K steps
dispatch as one ``lax.scan`` over a device-stacked super-batch, with losses/metrics
accumulated on device and trigger boundaries kept exact (``Trigger.next_fire_in``).
"""

from __future__ import annotations

import collections
import itertools
import logging
import os
import queue
import re
import sys
import threading
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.dataset.dataset import (
    AbstractDataSet, TransformedDataSet, is_distributed,
)
from bigdl_tpu.dataset.sample import MiniBatch
from bigdl_tpu.nn.abstractnn import AbstractModule
from bigdl_tpu.nn.criterion import AbstractCriterion
from bigdl_tpu.obs import device as obs_device
from bigdl_tpu.obs import exporter as obs_exporter
from bigdl_tpu.obs import mfu as obs_mfu
from bigdl_tpu.obs import registry as obs_registry
from bigdl_tpu.obs import report as obs_report
from bigdl_tpu.obs import slo as obs_slo
from bigdl_tpu.obs import trace
from bigdl_tpu.obs import watchdog as obs_watchdog
from bigdl_tpu.optim.optim_method import OptimMethod, SGD
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.optim.validation import ValidationMethod
from bigdl_tpu.utils import faults, file as ckpt_file
from bigdl_tpu.utils.engine import Engine
from bigdl_tpu.utils.file import CheckpointCorruptError
from bigdl_tpu.utils.random_generator import RandomGenerator
from bigdl_tpu.utils.robustness import events

logger = logging.getLogger("bigdl_tpu.optim")

#: pickle-backend checkpoint file names: ``checkpoint.<neval>.pkl``
#: (versioned) or ``checkpoint.pkl`` (over_write_checkpoint rolling file)
_CKPT_RE = re.compile(r"^checkpoint(?:\.(\d+))?\.pkl$")


def _ckpt_version(name: str) -> Optional[int]:
    """Numeric version of a pickle checkpoint file name; the unversioned
    rolling file sorts below every versioned one; non-checkpoint names
    (quarantined ``*.corrupt``, tmp files) return None."""
    m = _CKPT_RE.match(name)
    if m is None:
        return None
    return int(m.group(1)) if m.group(1) is not None else -1


class TrainingPreempted(RuntimeError):
    """Raised by ``optimize()`` after a SIGTERM/SIGINT graceful stop: the run
    halted at a step boundary and (when a checkpoint path is configured) an
    emergency checkpoint with full resume state was made durable first.
    ``optimize(resume="auto")`` in a fresh process continues the run
    bitwise-identically. ``checkpoint_path`` is None when no checkpoint was
    configured (progress since the last external snapshot is lost)."""

    def __init__(self, message: str, checkpoint_path: Optional[str] = None,
                 iteration: int = 0):
        super().__init__(message)
        self.checkpoint_path = checkpoint_path
        self.iteration = iteration


class NonFiniteLossError(RuntimeError):
    """The fetched training loss was NaN/inf. ``optimize()`` responds by
    rolling back to the last good checkpoint, at most
    ``BIGDL_MAX_NAN_ROLLBACKS`` times (default 2), then aborts — a
    deterministic divergence must not burn the whole generic retry budget
    re-reaching the same NaN."""

    def __init__(self, message: str, iteration: int = 0):
        super().__init__(message)
        self.iteration = iteration

_PUT_ALIASES_HOST: Optional[bool] = None


def _batch_sig(*trees) -> tuple:
    """Hashable (shape, dtype) signature of pytrees of arrays, for the
    per-program FLOPs memo — multi-input models feed tuples of tensors, so
    the key cannot assume a bare ``.shape``."""
    return tuple((tuple(x.shape), str(x.dtype)) if hasattr(x, "shape")
                 else repr(x)
                 for x in jax.tree_util.tree_leaves(trees))


def _device_put_may_alias() -> bool:
    """Does ``jax.device_put`` of an aligned numpy array share the HOST buffer
    (PJRT zero-copy) instead of copying? Decides whether the feed may recycle
    a ring-assembled batch's buffers right after placement: under zero-copy
    the "device" buffer IS the host array for its whole lifetime, so reuse
    would corrupt an in-flight step. Probed once with a 64-byte-aligned array
    (the alignment PJRT requires before it will zero-copy)."""
    global _PUT_ALIASES_HOST
    if _PUT_ALIASES_HOST is None:
        try:
            raw = np.zeros(4096 + 64, np.uint8)
            off = (-raw.ctypes.data) % 64
            host = raw[off:off + 4096].view(np.float32)
            placed = jax.device_put(host)
            jax.block_until_ready(placed)
            _PUT_ALIASES_HOST = (int(placed.unsafe_buffer_pointer())
                                 == int(host.ctypes.data))
        except Exception:
            _PUT_ALIASES_HOST = True  # can't prove a copy → never recycle
    return _PUT_ALIASES_HOST


class Optimizer:
    """Front-end factory + shared trainer implementation."""

    # Module-state leaf names auto-logged as training scalars (TB tag =
    # "State/<path>"). Routing health for MoE (round-4 verdict weak #5: the
    # aux loss trained blind — capacity drops were invisible in logs), and
    # any future layer exposing a same-named scalar rides for free.
    OBSERVABLE_STATE_LEAVES = ("aux_loss", "router_z_loss",
                               "dropped_fraction", "expert_load_max",
                               "pairs_held", "row_passes", "pass_fill")

    def __new__(cls, model: AbstractModule = None, dataset: AbstractDataSet = None,
                criterion: AbstractCriterion = None, **kw):
        if cls is Optimizer and dataset is not None and is_distributed(dataset):
            from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
            return super().__new__(DistriOptimizer)
        if cls is Optimizer:
            return super().__new__(LocalOptimizer)
        return super().__new__(cls)

    def __init__(self, model: AbstractModule, dataset: AbstractDataSet,
                 criterion: AbstractCriterion):
        self.model = model
        self.dataset = dataset
        self.criterion = criterion
        self.optim_method: OptimMethod = SGD()
        self.end_when: Trigger = Trigger.max_iteration(sys.maxsize)
        self.val_trigger: Optional[Trigger] = None
        self.val_dataset: Optional[AbstractDataSet] = None
        self.val_methods: Sequence[ValidationMethod] = ()
        self.checkpoint_path: Optional[str] = None
        self.checkpoint_trigger: Optional[Trigger] = None
        # Reference parity: checkpoints are versioned per iteration by default;
        # over_write_checkpoint() opts into a single rolling file.
        self.overwrite_checkpoint: bool = False
        self.checkpoint_backend: str = "pickle"
        # serving-lifecycle handoff (set_model_registry / BIGDL_REGISTRY_DIR):
        # each durable checkpoint version additionally publishes its params
        # subtree to a utils/model_registry.ModelRegistry as a promotion
        # candidate — on the writer thread, never failing the trainer
        self.model_registry = None
        if os.environ.get("BIGDL_REGISTRY_DIR"):
            from bigdl_tpu.utils.model_registry import ModelRegistry
            self.model_registry = ModelRegistry(
                os.environ["BIGDL_REGISTRY_DIR"])
        self.train_summary = None
        self.val_summary = None
        self.summary_trigger: Optional[Trigger] = None
        self.grad_clip_const: Optional[tuple[float, float]] = None
        self.grad_clip_norm: Optional[float] = None
        # on-device microbatch accumulation (set_gradient_accumulation /
        # BIGDL_GRAD_ACCUM): M microbatches scanned inside the compiled step
        self.grad_accum: int = self._env_int("BIGDL_GRAD_ACCUM", 1)
        # rematerialization policy on the model apply (set_remat /
        # BIGDL_REMAT): "none" (default — save all activations), "dots"
        # (save matmul/conv results, recompute the elementwise glue), "full"
        # (recompute everything in backward — minimum activation memory)
        self.remat: str = self._env_remat()
        # flat-param optimizer update (set_flat_update / BIGDL_FLAT_UPDATE):
        # elementwise methods run over dtype-grouped flat vectors inside the
        # jitted step (kernels/fused_update.py) — bitwise-identical, one
        # fused vector kernel instead of per-leaf launches
        self.flat_update: bool = os.environ.get(
            "BIGDL_FLAT_UPDATE", "0") == "1"
        # sparse embedding updates (set_sparse_embeddings / BIGDL_EMBED_SPARSE):
        # models containing parallel/embedding.ShardedEmbedding tables step
        # only the rows each batch gathered (None = auto: on when the model
        # and method are eligible; "0"/"1" force)
        _sparse_env = os.environ.get("BIGDL_EMBED_SPARSE", "")
        self.sparse_embed: Optional[bool] = (
            None if _sparse_env not in ("0", "1") else _sparse_env == "1")
        self._sparse_plan_memo: Any = "_unset"
        # Auxiliary-loss convention: modules that declare an ``aux_loss`` leaf
        # in their state (MoE load balancing, parallel/moe.py) get it added to
        # the training objective scaled by this weight. 0.01 is the Switch
        # Transformer default; set_aux_loss_weight(0) trains without it.
        self.aux_loss_weight: float = float(
            os.environ.get("BIGDL_AUX_LOSS_WEIGHT", "0.01"))
        self.state: dict = {"epoch": 1, "neval": 1, "epoch_finished": False}
        self.log_every: int = 1
        from bigdl_tpu.optim.metrics import Metrics
        self.metrics = Metrics()
        # sequence number of the window (or batch) the feed is placing: it
        # rides the producer's spans and the dispatch span that consumes it
        self._feed_seq: int = 0
        # queue of the thread that waits for copies in flight while spans
        # are on (`_watch_copy`); None while no such thread runs
        self._copy_watch = None
        # feed pipeline depth (placed batches in flight); 0 = synchronous
        self.prefetch_depth: int = int(os.environ.get("BIGDL_PREFETCH", "2"))
        # jax.profiler trace window (set_profile / BIGDL_PROFILE_DIR)
        self.profile_dir: Optional[str] = os.environ.get("BIGDL_PROFILE_DIR")
        self.profile_start_iter: int = int(os.environ.get("BIGDL_PROFILE_START", "10"))
        self.profile_n_iters: int = int(os.environ.get("BIGDL_PROFILE_ITERS", "10"))
        # per-iteration device sync for true step-time metrics (debug only —
        # defeats async dispatch)
        self.sync_metrics: bool = os.environ.get("BIGDL_SYNC_METRICS", "0") == "1"
        # numerics sanitizer (SURVEY.md §5.2 analog): compile the step under
        # checkify float checks; NaN/inf anywhere in the step raises with the
        # generating op's location. Debug-only — adds checking ops to the trace.
        self.check_numerics: bool = os.environ.get("BIGDL_CHECK_NUMERICS", "0") == "1"
        # Device-side batch cache (the reference's cached-RDD analog, SURVEY
        # §2.2 CachedDistriDataSet): for in-memory datasets that re-yield the
        # SAME MiniBatch objects every epoch, each distinct batch is transferred
        # host→device once and the placed buffers are reused. On deployments
        # where the host↔device link is slow relative to compute (measured here:
        # dispatch-side timers hide a ~25 MB/s effective transfer path that
        # serializes with the compute stream), repeated per-epoch transfers
        # dominate the step; caching removes them entirely. Bounded by
        # BIGDL_DEVICE_CACHE_MB (default 2048); BIGDL_DEVICE_CACHE=0 disables.
        self.device_cache_mb: float = float(
            os.environ.get("BIGDL_DEVICE_CACHE_MB", "2048"))
        self._device_batch_cache: Optional[dict] = None
        # Fused multi-step dispatch (BIGDL_FUSE_STEPS / set_fuse_steps): K
        # consecutive optimizer steps run as ONE jitted lax.scan over a
        # device-stacked super-batch, with losses/metrics accumulated in the
        # scan outputs and fetched once per window — the per-step Python
        # dispatch and host round trip disappear into the compiled program.
        # 1 (default) preserves the classic per-step loop exactly.
        self.fuse_steps: int = int(os.environ.get("BIGDL_FUSE_STEPS", "1"))
        self._step_cache = self._window_cache = None
        self._window_cache_bytes = 0.0
        # False until one real step has run: the first-ever dispatch goes
        # per-step because module state may materialize structure on first
        # apply, which a fused window's scan carry cannot morph
        self._state_materialized = False
        # ------------------------------------------------ fault tolerance
        # keep-last-N retention for versioned pickle checkpoints
        # (BIGDL_CKPT_KEEP; 0 = keep everything, the classic behavior)
        self.ckpt_keep: int = int(os.environ.get("BIGDL_CKPT_KEEP", "0"))
        # preemption (SIGTERM/SIGINT graceful stop): set by the signal
        # handler, checked at step/window boundaries
        self._preempt: Optional[threading.Event] = None
        self._prev_handlers: dict = {}
        # mid-epoch resume bookkeeping: feed position + RNG/order snapshots
        # captured at each epoch start, carried in checkpoint payloads
        self._epoch_batches = 0
        self._epoch_rng: Optional[dict] = None
        self._epoch_order = None
        self._epoch_stream: Optional[dict] = None
        self._resume_feed: Optional[dict] = None
        self._resume_base_rng = None
        # hang watchdog (obs/watchdog.py, BIGDL_WATCHDOG_S): owned per
        # optimize() call; the loop heartbeats it per step/window
        self._watchdog = None

    # fluent config (reference API shape) ----------------------------------
    def set_model(self, model: AbstractModule) -> "Optimizer":
        """Swap the model (reference ``setModel`` — fine-tuning flows: train,
        swap in a modified network, continue). Invalidates the compiled step
        and the optimizer slots (new parameter tree)."""
        self.model = model
        self._step_cache = self._window_cache = None
        self._final_ostate = None
        self._state_materialized = False
        return self

    def set_criterion(self, criterion: AbstractCriterion) -> "Optimizer":
        """Swap the training criterion (reference ``setCriterion``)."""
        self.criterion = criterion
        self._step_cache = self._window_cache = None
        return self

    def set_train_data(self, dataset: AbstractDataSet) -> "Optimizer":
        """Swap the training dataset (reference ``setTrainData`` — curriculum
        phases). The device batch cache is dropped with the old data."""
        self.dataset = dataset
        self._device_batch_cache = None
        return self

    def set_optim_method(self, method: OptimMethod) -> "Optimizer":
        self.optim_method = method
        self._step_cache = self._window_cache = None
        # the old method's slot pytree must not leak into the new method's step
        self._final_ostate = None
        return self

    def set_optim_methods(self, methods: dict) -> "Optimizer":
        """Per-submodule optimizers (reference ``setOptimMethods``): ``methods``
        maps module names (``module.set_name``/``get_name``) to OptimMethods;
        each named module's parameter subtree updates with its own method, the
        rest with the current ``set_optim_method`` default. Stateful LR
        schedules (Plateau) are only observed on the default method."""
        from bigdl_tpu.nn.abstractnn import Container
        from bigdl_tpu.optim.optim_method import CompositeOptimMethod

        prefixes: dict[str, list] = {}

        def walk(m, path):
            if m.name in methods:
                prefixes.setdefault(m.name, []).append(path)
            if isinstance(m, Container):
                for idx, child in m.named_children():
                    walk(child, path + (idx,))

        walk(self.model, ())
        missing = set(methods) - set(prefixes)
        if missing:
            raise ValueError(
                f"set_optim_methods: module names not found in the model: "
                f"{sorted(missing)}")
        # duplicate names route ALL matches (one group per occurrence)
        groups = [(name, path, method)
                  for name, method in methods.items()
                  for path in prefixes[name]]
        default = self.optim_method
        if isinstance(default, CompositeOptimMethod):
            # repeated call: rebuild from the ORIGINAL default; new names
            # override previous groups, remaining previous groups carry over
            old = [(n, p, m) for n, p, m in default.groups if n not in methods]
            groups = old + groups
            default = default.default
        self.optim_method = CompositeOptimMethod(groups, default)
        self._step_cache = self._window_cache = None
        self._final_ostate = None
        return self

    def set_aux_loss_weight(self, weight: float) -> "Optimizer":
        """Scale for module-declared ``aux_loss`` state leaves added to the
        objective (MoE load balancing). 0 disables."""
        self.aux_loss_weight = float(weight)
        self._step_cache = self._window_cache = None
        return self

    def set_prefetch(self, depth: int) -> "Optimizer":
        """Feed-pipeline depth: placed batches kept in flight by the background
        producer (dataset/prefetch.py). 0 = synchronous feeding."""
        if depth < 0:
            raise ValueError("prefetch depth must be >= 0")
        self.prefetch_depth = depth
        return self

    def set_fuse_steps(self, k: int) -> "Optimizer":
        """Fused multi-step dispatch: run ``k`` consecutive optimizer steps as
        ONE jitted ``lax.scan`` over a device-stacked super-batch, fetching the
        per-step losses/metrics in a single host round trip per window. The
        window is trigger-aware — it is clipped (falling back to per-step
        dispatch) so that ``end_when`` / validation / checkpoint / parameter-
        histogram triggers still fire at their exact iteration boundaries.
        ``k=1`` (default) is exactly the classic per-step loop. Keep ``k=1``
        when debugging (per-step profiler windows, ``BIGDL_SYNC_METRICS``
        force it anyway)."""
        if k != int(k) or int(k) < 1:
            raise ValueError(f"fuse_steps must be a positive integer, got {k!r}")
        self.fuse_steps = int(k)
        self._window_cache = None
        return self

    def set_check_numerics(self, enabled: bool = True) -> "Optimizer":
        """Enable the numerics sanitizer: every step runs under
        ``jax.experimental.checkify`` float checks, and a NaN/inf produced
        anywhere in forward/backward/update raises at the next loss flush with
        the location of the generating op (the reference has no sanitizer —
        SURVEY.md §5.2 — this is the functional-JAX upgrade)."""
        self.check_numerics = enabled
        self._step_cache = self._window_cache = None
        return self

    def set_profile(self, trace_dir: str, start_iter: int = 10,
                    n_iters: int = 10) -> "Optimizer":
        """Capture a ``jax.profiler`` trace (TensorBoard-viewable) covering
        iterations ``[start_iter, start_iter + n_iters)`` — device-time
        attribution per op, the honest answer to where a slow step goes
        (SURVEY.md §5.1)."""
        self.profile_dir = trace_dir
        self.profile_start_iter = start_iter
        self.profile_n_iters = n_iters
        return self

    def set_end_when(self, trigger: Trigger) -> "Optimizer":
        self.end_when = trigger
        return self

    def set_validation(self, trigger: Trigger, dataset: AbstractDataSet,
                       methods: Sequence[ValidationMethod]) -> "Optimizer":
        self.val_trigger, self.val_dataset, self.val_methods = trigger, dataset, methods
        return self

    def set_checkpoint(self, path: str, trigger: Trigger,
                       backend: Optional[str] = None) -> "Optimizer":
        """``backend``: "pickle" (single file, background-thread write),
        "orbax" (orbax-checkpoint AsyncCheckpointer — per-leaf tensorstore
        layout), or "elastic" (``utils/elastic_ckpt`` — each process writes
        only the shards it addresses, manifest commits last, resume is
        topology-portable). None resolves from ``BIGDL_CKPT_SHARDED=1`` →
        elastic, else pickle."""
        if backend is None:
            backend = ("elastic"
                       if os.environ.get("BIGDL_CKPT_SHARDED", "0") == "1"
                       else "pickle")
        if backend not in ("pickle", "orbax", "elastic"):
            raise ValueError(
                "checkpoint backend must be 'pickle', 'orbax' or 'elastic'")
        self.checkpoint_path, self.checkpoint_trigger = path, trigger
        self.checkpoint_backend = backend
        return self

    def set_model_registry(self, registry) -> "Optimizer":
        """Publish every durable checkpoint's params to ``registry`` (a
        :class:`~bigdl_tpu.utils.model_registry.ModelRegistry` or a path) as
        a serving-lifecycle ``candidate`` version, gated + promoted by
        ``serving/lifecycle.py``. Publication runs on the checkpoint writer
        thread; its failures are logged, never raised into training."""
        if isinstance(registry, str):
            from bigdl_tpu.utils.model_registry import ModelRegistry
            registry = ModelRegistry(registry)
        self.model_registry = registry
        return self

    def over_write_checkpoint(self, overwrite: bool = True) -> "Optimizer":
        self.overwrite_checkpoint = overwrite
        return self

    def set_train_summary(self, summary) -> "Optimizer":
        self.train_summary = summary
        return self

    def set_val_summary(self, summary) -> "Optimizer":
        self.val_summary = summary
        return self

    def set_constant_gradient_clipping(self, min_v: float, max_v: float) -> "Optimizer":
        self.grad_clip_const = (min_v, max_v)
        self._step_cache = self._window_cache = None
        return self

    def set_gradient_clipping_by_l2_norm(self, clip_norm: float) -> "Optimizer":
        self.grad_clip_norm = clip_norm
        self._step_cache = self._window_cache = None
        return self

    def disable_gradient_clipping(self) -> "Optimizer":
        self.grad_clip_const = None
        self.grad_clip_norm = None
        self._step_cache = self._window_cache = None
        return self

    _REMAT_MODES = ("none", "dots", "full")

    @staticmethod
    def _env_int(name: str, default: int) -> int:
        raw = os.environ.get(name, str(default))
        try:
            v = int(raw)
            if v < 1:
                raise ValueError
        except ValueError:
            raise ValueError(f"{name} must be an integer >= 1, got {raw!r}")
        return v

    @classmethod
    def _env_remat(cls) -> str:
        mode = os.environ.get("BIGDL_REMAT", "none").strip().lower()
        if mode not in cls._REMAT_MODES:
            raise ValueError(
                f"BIGDL_REMAT must be one of {cls._REMAT_MODES}, got {mode!r}")
        return mode

    def set_remat(self, mode: str) -> "Optimizer":
        """Gradient rematerialization policy for the model apply inside the
        compiled step (``jax.checkpoint``): "none" keeps XLA's default (all
        activations live to backward), "dots" saves matmul/conv outputs and
        recomputes the elementwise glue, "full" recomputes the whole forward
        during backward — the activation-memory floor. Composes with
        gradient accumulation and the fused scan window; numerically the
        recomputation re-runs the identical ops."""
        mode = str(mode).strip().lower()
        if mode not in self._REMAT_MODES:
            raise ValueError(
                f"remat mode must be one of {self._REMAT_MODES}, got {mode!r}")
        self.remat = mode
        self._step_cache = self._window_cache = None
        return self

    def set_flat_update(self, enabled: bool = True) -> "Optimizer":
        """Run elementwise optimizer updates (SGD/Adam/…) over dtype-grouped
        FLAT parameter vectors inside the jitted step — a few fused vector
        kernels instead of one launch per parameter leaf, bitwise-identical
        to the per-leaf update (kernels/fused_update.py). Methods needing
        leaf structure (layer_lr_mults, LARS, L-BFGS, composite) silently
        keep the per-leaf path."""
        self.flat_update = bool(enabled)
        self._step_cache = self._window_cache = None
        self._final_ostate = None  # slot layout changes with the wrapper
        return self

    def _flat_update_ok(self) -> bool:
        """Subclass hook: may the flat update replace the per-leaf one under
        the current sharding configuration?"""
        return True

    def set_sparse_embeddings(self, enabled: bool = True) -> "Optimizer":
        """Step only the embedding rows each batch gathered, for models whose
        tables are wrapped in ``parallel/embedding.ShardedEmbedding``: the
        step differentiates a per-unique-row delta (no dense (V, D) gradient
        is materialized) and the method's ``sparse_update`` touches only
        those rows and their optimizer-slot rows — untouched rows stay
        bitwise-unchanged (lazy semantics). Auto-enabled when eligible;
        ``set_sparse_embeddings(False)`` forces the dense path."""
        self.sparse_embed = bool(enabled)
        self._sparse_plan_memo = "_unset"
        self._step_cache = self._window_cache = None
        self._final_ostate = None  # slot layout changes with the wrapper
        return self

    def _sparse_embed_ok(self) -> bool:
        """Subclass hook: may sparse embedding updates run under the current
        sharding configuration?"""
        return True

    def _sparse_plan(self):
        """The model's sparse-embedding plan, or None for the dense path.
        Memoized (and its fallback reason logged once) because the step
        builder, ostate init and resume-compat checks must all agree."""
        if self._sparse_plan_memo != "_unset":
            return self._sparse_plan_memo
        plan, reason = None, None
        if self.sparse_embed is not False:
            from bigdl_tpu.parallel.embedding import build_sparse_plan
            plan, reason = build_sparse_plan(self.model, self.optim_method)
            if plan is not None:
                if self.grad_accum > 1:
                    plan, reason = None, ("gradient accumulation scans need "
                                          "a dense gradient carry")
                elif Engine.compute_dtype() != jnp.float32:
                    plan, reason = None, "mixed precision casts the gathered rows"
                elif getattr(self.model, "schedule", None) == "1f1b":
                    plan, reason = None, "1f1b pipeline owns the train step"
                elif not self._sparse_embed_ok():
                    plan, reason = None, ("current parameter_sync/tensor-"
                                          "parallel configuration")
        if reason is not None and (self.sparse_embed or plan is None):
            logger.warning(
                "sparse embedding updates unavailable (%s); training the "
                "embedding tables densely", reason)
        if plan is not None:
            logger.info("sparse embedding updates active: %r", plan)
        self._sparse_plan_memo = plan
        return plan

    def _effective_method(self) -> OptimMethod:
        """The method the compiled step actually runs: the configured one,
        wrapped for sparse embedding updates and/or flat-vector updates when
        enabled and eligible (sparse wins — the flat wrapper has no sparse
        form)."""
        method = self.optim_method
        plan = self._sparse_plan()
        if plan is not None:
            from bigdl_tpu.parallel.embedding import SparseEmbeddingUpdate
            if self.flat_update:
                logger.warning(
                    "BIGDL_FLAT_UPDATE skipped: sparse embedding updates "
                    "wrap the method first")
            return SparseEmbeddingUpdate(method, plan)
        if self.flat_update and self._flat_update_ok():
            from bigdl_tpu.kernels.fused_update import (
                FlatParamUpdate, flat_supported,
            )
            if flat_supported(method):
                return FlatParamUpdate(method)
            logger.warning(
                "BIGDL_FLAT_UPDATE: %r has no elementwise flat form; "
                "keeping the per-leaf update", method)
        return method

    def set_gradient_accumulation(self, n_micro: int) -> "Optimizer":
        """Split every mini-batch into ``n_micro`` microbatches inside the
        compiled step (``lax.scan``), averaging gradients before the single
        optimizer update — ~1/n the activation memory, the TPU lever for
        large effective batches; no reference analog (the reference's
        effective batch grows with Spark partitions instead).

        Numerically the same update as the full batch for unweighted mean-
        or sum-reduced losses; criteria that normalize by a PER-BATCH
        quantity (class-weighted ClassNLL's weight-sum denominator, masked
        criteria's valid-count) divide per microbatch instead, so their
        accumulated update can differ under imbalance. Batch size must be
        divisible by ``n_micro``. BN batch statistics see each microbatch
        separately (the standard grad-accumulation semantics)."""
        if n_micro != int(n_micro) or int(n_micro) < 1:
            raise ValueError(f"n_micro must be a positive integer, got {n_micro!r}")
        self.grad_accum = int(n_micro)
        self._sparse_plan_memo = "_unset"  # accum > 1 disables the sparse path
        self._step_cache = self._window_cache = None
        return self

    # ------------------------------------------------------------- compile
    def _trainable_mask(self):
        """Params-structured pytree of static bools (False = frozen, grad
        scale 0) driving frozen-leaf optimizer-slot trimming — or None when
        everything trains. LoRA's memory story: no Adam moments on the
        frozen base."""
        scales = self.model.grad_scales()
        if not any(s == 0.0 for s in jax.tree_util.tree_leaves(scales)):
            return None
        return jax.tree_util.tree_map(lambda s: s != 0.0, scales)

    def _ostate_compatible(self, ostate, params, mask) -> bool:
        """Do carried/resumed slots structurally fit what the current
        freeze configuration would allocate?"""
        try:
            method = self._effective_method()
            expected = jax.eval_shape(
                lambda p: method.init_state_trimmed(p, mask), params)
        except Exception:
            return True   # can't predict (exotic method): let it ride
        exp_flat, exp_def = jax.tree_util.tree_flatten(expected)
        got_flat, got_def = jax.tree_util.tree_flatten(ostate)
        if exp_def != got_def:
            return False
        return all(np.shape(g) == e.shape for g, e in zip(got_flat, exp_flat))

    def _clip_grads(self, grads):
        if self.grad_clip_const is not None:
            lo, hi = self.grad_clip_const
            grads = jax.tree_util.tree_map(lambda g: jnp.clip(g, lo, hi), grads)
        if self.grad_clip_norm is not None:
            leaves = jax.tree_util.tree_leaves(grads)
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
            scale = jnp.minimum(1.0, self.grad_clip_norm / (norm + 1e-12))
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        return grads

    def _make_step_fn(self):
        from bigdl_tpu.nn.precision import cast_floating

        model, criterion = self.model, self.criterion
        method = self._effective_method()
        sparse_plan = self._sparse_plan()
        needs_rng = model.needs_rng()
        aux_w = self.aux_loss_weight
        # per-layer LR multipliers (setScaleW/setScaleB): static constants —
        # all-ones trees trace to exactly the unscaled program
        scale_tree = model.grad_scales()
        if all(s == 1.0 for s in jax.tree_util.tree_leaves(scale_tree)):
            scale_tree = None
        # frozen (scale==0) leaves: stop_gradient BEFORE the forward so XLA
        # dead-codes their whole backward — freeze()/LoRA then actually SKIP
        # the frozen backward compute instead of computing grads and zeroing
        # them. Numerically identical (stopped grads are exact zeros).
        has_frozen = scale_tree is not None and any(
            s == 0.0 for s in jax.tree_util.tree_leaves(scale_tree))
        # frozen leaves carry 0-size optimizer slots (see OptimMethod
        # .update_trimmed) — static, so unfrozen models trace unchanged
        trainable_mask = self._trainable_mask()

        def stop_frozen(p):
            if not has_frozen:
                return p
            return jax.tree_util.tree_map(
                lambda leaf, s: jax.lax.stop_gradient(leaf) if s == 0.0
                else leaf, p, scale_tree)
        # static: models without attached regularizers trace unchanged
        has_reg = model.has_regularizers()

        def collect_state_losses(ms):
            """Sum declared objective terms from the post-apply module state.
            Two conventions, by leaf name (presence is static pytree
            structure, so models without either trace to the old program):

            - ``aux_loss`` — scaled by the Optimizer's aux_loss_weight
              (MoE load balancing; the coefficient is a training-run knob);
            - ``penalty`` — added at FULL strength (ActivityRegularization /
              NegativeEntropyPenalty, whose coefficient belongs to the layer
              — keras semantics; the global knob must not rescale it).
            """
            from jax.tree_util import tree_flatten_with_path
            aux = pen = None
            for path, leaf in tree_flatten_with_path(ms)[0]:
                key = path and getattr(path[-1], "key", None)
                if key == "aux_loss":
                    aux = leaf if aux is None else aux + leaf
                elif key == "penalty":
                    pen = leaf if pen is None else pen + leaf
            return aux, pen
        # Mixed precision (nn/precision.py): params stay fp32 masters; the casts
        # below put the matmul/conv FLOPs in the compute dtype (bf16 → MXU double
        # rate) while the cast's transpose returns fp32 gradients, and the loss /
        # criterion softmax stays fp32.
        compute_dtype = Engine.compute_dtype()
        mixed = compute_dtype != jnp.float32

        accum = self.grad_accum

        # 1F1B pipeline: when the ROOT model is a GPipe(schedule="1f1b") on a
        # live pipe mesh, the pipeline owns the whole train step (loss inside
        # the schedule — the only way to interleave backwards with forwards);
        # grads/loss feed the same clip+update tail as the generic path.
        pipe_fn = None
        if getattr(model, "schedule", None) == "1f1b" \
                and hasattr(model, "pipeline_train_step"):
            mesh = Engine.mesh() if Engine.is_initialized() else None
            axes = dict(mesh.shape) if mesh is not None else {}
            if axes.get(model.axis_name, 1) == model.n_stages \
                    and model.n_stages > 1:
                if accum != 1:
                    raise ValueError(
                        "schedule='1f1b' already microbatches inside the "
                        "pipeline; combine via n_microbatches, not "
                        "set_gradient_accumulation")
                if needs_rng:
                    raise ValueError(
                        "1f1b stages must not need RNG (GPipe contract)")
                dax = Engine.DATA_AXIS \
                    if axes.get(Engine.DATA_AXIS, 1) > 1 else None

                def pipe_fn(p, x, t):
                    return model.pipeline_train_step(p, x, t, criterion,
                                                     mesh, dax)

        # rematerialization policy (set_remat / BIGDL_REMAT): wraps the whole
        # loss (model apply + criterion) in jax.checkpoint so backward
        # recomputes instead of holding activations — "dots" keeps matmul/
        # conv results (cheap to hold, expensive to recompute), "full" holds
        # nothing. Recomputation re-runs identical ops; composed with the
        # microbatch scan below this is what lets batch-256-equivalent
        # training fit in a fraction of the activation HBM.
        remat = self.remat
        remat_policy = (jax.checkpoint_policies.checkpoint_dots
                        if remat == "dots" else None)

        # the step's phases carry jax.named_scope names (obs/trace.py SCOPE_*):
        # a profile bills each device operation to its phase. Metadata only:
        # the compiled program is the same
        def scale_and_clip(grads, row_grads=None):
            with jax.named_scope(trace.SCOPE_GRAD_SCALE):
                if scale_tree is not None:
                    # sparse plan entries require scale 1.0 on the table
                    # weight, so only the dense leaves are scaled (0-size
                    # embed leaves pass through the map unchanged)
                    grads = jax.tree_util.tree_map(
                        lambda g, s: g * s, grads, scale_tree)
                if row_grads is None:
                    return self._clip_grads(grads)
                return self._clip_grads((grads, row_grads))

        def step(params, mstate, ostate, step_idx, inp, target, base_rng):
            rng0 = jax.random.fold_in(base_rng, step_idx) if needs_rng else None

            def loss_fn(p, ms, x, t, rng):
                p = stop_frozen(p)
                if mixed:
                    with jax.named_scope(trace.SCOPE_CAST):
                        p = cast_floating(p, compute_dtype)
                        x = cast_floating(x, compute_dtype)
                out, new_ms = model.apply(p, ms, x, training=True, rng=rng)
                if mixed:
                    with jax.named_scope(trace.SCOPE_CAST):
                        out = cast_floating(out, jnp.float32)
                        new_ms = cast_floating(new_ms, jnp.float32)
                with jax.named_scope(trace.SCOPE_LOSS):
                    loss = criterion.apply(out, t)
                    aux, pen = collect_state_losses(new_ms)
                    if aux is not None and aux_w:
                        loss = loss + aux_w * aux
                    if pen is not None:
                        loss = loss + pen
                    if has_reg:  # per-layer L1/L2 weight penalties (regularizer.py)
                        loss = loss + model.regularizer_penalty(p)
                return loss, new_ms

            if remat != "none":
                loss_fn = jax.checkpoint(loss_fn, policy=remat_policy)
            vg = jax.value_and_grad(loss_fn, has_aux=True)
            if sparse_plan is not None:
                # Sparse embedding step (parallel/embedding.py): differentiate
                # a zero per-unique-row delta injected through the module-state
                # channel — autodiff yields the exact (U, D) row gradient per
                # table; the table weights themselves sit under stop_gradient
                # inside ShardedEmbedding.apply, so their dense grads are
                # exact zeros that mask_embed trims before XLA sees them.
                def loss_fn_sparse(p_and_d, ms, x, t, rng):
                    p, deltas = p_and_d
                    return loss_fn(p, sparse_plan.inject(ms, deltas),
                                   x, t, rng)

                deltas0 = sparse_plan.zero_deltas(model, params, mstate,
                                                  inp, rng0)
                (loss, new_ms), (grads, row_grads) = jax.value_and_grad(
                    loss_fn_sparse, has_aux=True)(
                        (params, deltas0), mstate, inp, target, rng0)
                uids_map, new_ms = sparse_plan.pop_uids(new_ms)
                grads = sparse_plan.mask_embed(grads)
                grads, row_grads = scale_and_clip(grads, row_grads)
                with jax.named_scope(trace.SCOPE_UPDATE):
                    new_p, new_os = method.sparse_apply(
                        params, grads, row_grads, uids_map, ostate, step_idx,
                        trainable_mask)
                return new_p, new_ms, new_os, loss
            if pipe_fn is not None:
                # stages are stateless (GPipe contract) → mstate passes
                # through; frozen leaves stop-gradient through the flat rows
                loss, grads = pipe_fn(stop_frozen(params), inp, target)
                new_ms = mstate
                if has_reg:  # data-independent: differentiate it separately
                    pen, pgrads = jax.value_and_grad(
                        model.regularizer_penalty)(params)
                    loss = loss + pen
                    grads = jax.tree_util.tree_map(jnp.add, grads, pgrads)
            elif accum == 1:
                (loss, new_ms), grads = vg(params, mstate, inp, target, rng0)
            else:
                # gradient accumulation: scan microbatches, averaging grads —
                # one optimizer update, ~1/accum the activation memory
                def micro_split(t):
                    def split(a):
                        if a.shape[0] % accum:
                            raise ValueError(
                                f"batch size {a.shape[0]} is not divisible "
                                f"by set_gradient_accumulation({accum})")
                        # STRIDED split (microbatch i = rows i::accum): under
                        # DistriOptimizer's data-sharded batch each micro
                        # keeps rows on their original devices (a contiguous
                        # reshape would force a per-step all-to-all); the
                        # assignment is numerically irrelevant to the
                        # averaged gradient
                        return a.reshape((a.shape[0] // accum, accum)
                                         + a.shape[1:]).swapaxes(0, 1)
                    return jax.tree_util.tree_map(split, t)

                def body(carry, xt):
                    ms, gsum, lsum = carry
                    x_mb, t_mb, i = xt
                    rng = (jax.random.fold_in(rng0, i) if needs_rng else None)
                    (l, ms2), g = vg(params, ms, x_mb, t_mb, rng)
                    gsum = jax.tree_util.tree_map(jnp.add, gsum, g)
                    return (ms2, gsum, lsum + l), None

                xs = (micro_split(inp), micro_split(target),
                      jnp.arange(accum, dtype=jnp.int32))
                # microbatch 0 unrolled: some modules materialize state
                # structure on first apply, which a scan carry cannot morph
                first = jax.tree_util.tree_map(lambda a: a[0], xs)
                (l0, ms1), g0 = vg(params, mstate, first[0], first[1],
                                   (jax.random.fold_in(rng0, 0)
                                    if needs_rng else None))
                rest = jax.tree_util.tree_map(lambda a: a[1:], xs)
                (new_ms, gsum, lsum), _ = jax.lax.scan(
                    body, (ms1, g0, l0), rest)
                # averaging criteria: mean of micro means == full-batch mean;
                # summing criteria: the micro sums already ARE the full-batch
                # sum — dividing again would shrink the update accum-fold
                # criteria opt into sum semantics by exposing size_average=False;
                # a sum-reducing criterion without the attribute would silently
                # get its accumulated gradient divided by accum — say so once
                if not hasattr(criterion, "size_average"):
                    logger.warning(
                        "gradient accumulation: criterion %s does not expose "
                        "size_average; assuming mean reduction (micro-grads "
                        "averaged). Sum-reducing criteria must set "
                        "size_average=False.", type(criterion).__name__)
                crit_averages = bool(getattr(criterion, "size_average", True))
                if crit_averages:
                    with jax.named_scope(trace.SCOPE_GRAD_SCALE):
                        grads = jax.tree_util.tree_map(
                            lambda g: g / accum, gsum)
                    loss = lsum / accum
                else:
                    grads, loss = gsum, lsum
            grads = scale_and_clip(grads)
            with jax.named_scope(trace.SCOPE_UPDATE):
                new_p, new_os = method.update_trimmed(
                    params, grads, ostate, step_idx, trainable_mask)
            return new_p, new_ms, new_os, loss

        return step

    def _wrap_checkify(self, step):
        """Sanitizer wrap shared by Local and Distri compile paths: the step
        grows a 5th output (the checkify error) that _optimize_impl unpacks.
        float_checks flags NaN production; overflow to inf is NOT a NaN, so a
        diverging run is additionally guarded by an explicit finite-loss check."""
        from jax.experimental import checkify

        from bigdl_tpu.nn.embedding import checkify_ids_scope

        def step_guarded(*args):
            # BIGDL_CHECK_IDS composes here: tracing under this scope lets
            # embedding layers emit their out-of-range checkify.check calls,
            # which the functionalization below turns into runtime errors
            with checkify_ids_scope():
                new_p, new_ms, new_os, loss = step(*args)
            checkify.check(jnp.isfinite(loss),
                           "non-finite loss (divergence): {loss}", loss=loss)
            return new_p, new_ms, new_os, loss

        checked = checkify.checkify(
            step_guarded, errors=checkify.float_checks | checkify.user_checks)

        def step_with_err(*args):
            err, out = checked(*args)
            return (*out, err)

        return step_with_err

    def _compile_step(self):
        step = self._make_step_fn()
        if self.check_numerics:
            return jax.jit(self._wrap_checkify(step), donate_argnums=(0, 1, 2))
        return jax.jit(step, donate_argnums=(0, 1, 2))

    # ------------------------------------------------- fused window compile
    def _make_window_fn(self, k: int):
        """K optimizer steps as ONE program: ``lax.scan`` over the leading
        (window) axis of a stacked super-batch, params/model-state/optimizer-
        state in the carry, per-step losses and observable state scalars in
        the scan outputs — they stay device-resident until the loop's batched
        fetch, so a K-window costs one dispatch and zero per-step host syncs."""
        step = self._make_step_fn()
        unroll = self._window_unroll(k)

        def window(params, mstate, ostate, step_idx0, inp, target, base_rng):
            def body(carry, xs):
                p, ms, os_ = carry
                x, t, off = xs
                p, ms, os_, loss = step(p, ms, os_, step_idx0 + off, x, t,
                                        base_rng)
                sm = tuple(v for _, v in self._collect_state_metrics(ms))
                return (p, ms, os_), (loss, sm)

            (params, mstate, ostate), (losses, sms) = jax.lax.scan(
                body, (params, mstate, ostate),
                (inp, target, jnp.arange(k, dtype=jnp.int32)), unroll=unroll)
            return params, mstate, ostate, losses, sms

        return window

    @staticmethod
    def _window_unroll(k: int) -> int:
        """Scan unroll factor for the fused window (``BIGDL_FUSE_UNROLL``:
        "auto" | int, clamped to [1, K]). XLA:CPU codegens while-loop bodies
        ~2x slower than the same ops straight-line (measured here: LeNet step
        214 ms/step rolled vs 115 ms/step fully unrolled), so "auto" unrolls
        fully on CPU; TPU keeps the rolled scan — its loop codegen carries no
        such penalty and compile time scales with unroll x body size."""
        raw = os.environ.get("BIGDL_FUSE_UNROLL", "auto").strip().lower()
        if raw in ("auto", ""):
            return k if Engine.devices()[0].platform == "cpu" else 1
        return max(1, min(int(raw), k))

    def _wrap_checkify_window(self, window):
        """Sanitizer wrap for the fused path: the whole scanned window runs
        under checkify (checkify composes through ``lax.scan``), so a NaN/inf
        produced at ANY step of the window surfaces — with the generating
        op's location — at the window's loss flush."""
        from jax.experimental import checkify

        def window_guarded(*args):
            params, mstate, ostate, losses, sms = window(*args)
            checkify.check(jnp.all(jnp.isfinite(losses)),
                           "non-finite loss (divergence) in fused window: "
                           "min {loss}", loss=jnp.min(losses))
            return params, mstate, ostate, losses, sms

        checked = checkify.checkify(
            window_guarded,
            errors=checkify.float_checks | checkify.user_checks)

        def window_with_err(*args):
            err, out = checked(*args)
            return (*out, err)

        return window_with_err

    def _compile_window(self, k: int):
        window = self._make_window_fn(k)
        if self.check_numerics:
            window = self._wrap_checkify_window(window)
        return jax.jit(window, donate_argnums=(0, 1, 2))

    def _state_metric_tags(self, mstate) -> list:
        """Tags of the observable state scalars, in the same order the traced
        window's scan outputs carry their stacked values."""
        return [t for t, _ in self._collect_state_metrics(mstate)]

    def _fusible_steps(self, state: dict) -> int:
        """How many iterations, starting at ``state['neval']``, may run inside
        one fused dispatch without an in-loop trigger firing strictly before
        the window's end (a trigger firing exactly AT the window end is fine —
        triggers are evaluated after the window completes, at the same
        iteration a per-step loop would evaluate them). Per-step debug modes
        (profiler trace, synchronous metrics) force per-step dispatch."""
        if self.profile_dir is not None or getattr(self, "_profiling", False) \
                or self.sync_metrics:
            return 1
        bound = self.end_when.next_fire_in(state)
        for trig in (self.val_trigger, self.checkpoint_trigger):
            if trig is not None and self._in_scope(trig, boundary=False):
                bound = min(bound, trig.next_fire_in(state))
        if self.train_summary is not None \
                and hasattr(self.train_summary, "get_summary_trigger"):
            ptrig = self.train_summary.get_summary_trigger("Parameters")
            if ptrig is not None:
                bound = min(bound, ptrig.next_fire_in(state))
        return bound

    def _setup_device_cache(self) -> None:
        """Enable the device batch cache when the dataset re-yields identical
        MiniBatch objects (plain LocalDataSet — transformed pipelines build
        fresh batches every epoch, which would grow the cache unboundedly) and
        the whole dataset fits the configured budget. Re-validates whenever the
        dataset object changes (a kept cache must never outlive its dataset's
        eligibility)."""
        ds = self.dataset
        cdt = Engine.compute_dtype()
        if self._device_batch_cache is not None \
                and getattr(self, "_device_cache_ds", None) is ds \
                and getattr(self, "_device_cache_dtype", None) == cdt:
            return
        # dtype change invalidates too: cached inputs are placed pre-cast to
        # the compute dtype and must not leak into a different-precision run
        self._device_batch_cache = None
        self._window_cache_bytes = 0.0
        self._device_cache_ds = ds
        self._device_cache_dtype = cdt
        if os.environ.get("BIGDL_DEVICE_CACHE", "1") == "0":
            return
        from bigdl_tpu.dataset.dataset import LocalDataSet, TransformedDataSet
        if isinstance(ds, TransformedDataSet) or not isinstance(ds, LocalDataSet):
            return
        try:
            total = sum(getattr(b.input, "nbytes", 0)
                        + getattr(b.target, "nbytes", 0) for b in ds._data)
        except Exception:
            return
        if total <= self.device_cache_mb * 1e6:
            logger.info("device batch cache enabled (%.0f MB in-memory dataset)",
                        total / 1e6)
            self._device_batch_cache = {}

    def _put_batch(self, batch: MiniBatch):
        # runs in the prefetch producer thread: assembly already happened in the
        # dataset iterator; this just enqueues the h2d DMA (once per distinct
        # batch when the device cache is on)
        faults.fault_point(faults.SITE_H2D)  # scripted transfer failure
        cache = self._device_batch_cache
        if cache is not None:
            hit = cache.get(id(batch))
            if hit is not None and hit[0] is batch:
                return hit[1]
        # ring-assembled batch (SampleToMiniBatch): hand its buffers back for
        # reuse once the device owns the bytes. PJRT may keep reading the
        # host buffer until the transfer completes, so wait for the placed
        # arrays HERE in the producer thread (the step loop's overlap is
        # untouched) before the ring may overwrite them.
        recycle = cache is None \
            and getattr(batch, "_ring_slot", None) is not None \
            and not _device_put_may_alias()
        placed = self._timed_h2d(self._place_batch, (batch,), wait=recycle)
        if cache is not None:
            cache[id(batch)] = (batch, placed)
        elif recycle:
            batch.recycle()
        return placed

    def _timed_h2d(self, place, host, wait=False):
        """``place(*host)`` under the ``put_batch`` phase, and
        ``feed/h2d_bytes`` counts what was handed to ``device_put``.
        ``device_put`` only enqueues the copy. The ``feed/h2d`` span runs
        from the same clock read until the placed arrays are ready: with
        ``wait`` the producer thread waits for that itself; otherwise, with
        spans on, a watcher thread does and closes the span, so that the
        producer goes on to stack the next window while the copy runs, as
        it does with spans off (on the chip a producer that waited for its
        own copy became the feed's bottleneck and the traced run no longer
        looked like the job: ``PERF.md`` section 6, PR 26)."""
        span = trace.span("feed/h2d", self._seq_args())
        watched = trace.enabled() and not wait
        with self.metrics.timer("put_batch", span if not watched else None) as t:
            placed = place(*host)
            if wait:
                jax.block_until_ready(placed)
        if watched:
            self._watch_copy(span, t.t0, placed)
        obs_registry.registry.counter("feed/h2d_bytes").inc(sum(
            getattr(a, "nbytes", 0) for a in jax.tree_util.tree_leaves(placed)))
        return placed

    def _watch_copy(self, span, t0_ns: int, placed) -> None:
        """Hand a copy in flight to the watcher thread (started on first
        use, stopped by :meth:`_stop_copy_watcher` when ``optimize()`` ends):
        it opens ``span`` at ``t0_ns`` and closes it when ``placed`` is
        ready. Copies complete in the order they were enqueued, so one
        thread that waits for them in turn reads each end as it happens."""
        if self._copy_watch is None:
            q = self._copy_watch = queue.SimpleQueue()

            def watch():
                for span, t0_ns, placed in iter(q.get, None):
                    span.begin(t0_ns)
                    jax.block_until_ready(placed)
                    span.end(time.perf_counter_ns())

            self._copy_watcher = threading.Thread(
                target=watch, name="bigdl-h2d-watch", daemon=True)
            self._copy_watcher.start()
        self._copy_watch.put((span, t0_ns, placed))

    def _stop_copy_watcher(self) -> None:
        if self._copy_watch is not None:
            self._copy_watch.put(None)
            self._copy_watcher.join(timeout=30.0)  # a lost device never lands a copy
            self._copy_watch = None

    def _seq_args(self):
        """``args`` of the spans that follow one window through the feed:
        the sequence number `_optimize_impl` gave the window being placed."""
        return {"seq": self._feed_seq} if trace.enabled() else None

    def _place_batch(self, batch: MiniBatch):
        return (jax.device_put(self._feed_cast(batch.input)),
                jax.device_put(batch.target))

    @staticmethod
    def _stack_window(xs: list):
        """Stack a window of per-batch (possibly nested) host pytrees along a
        new leading scan axis — host-side, in the producer thread, so the
        stacked super-batch ships as ONE h2d transfer."""
        return jax.tree_util.tree_map(lambda *leaves: np.stack(leaves), *xs)

    def _put_window(self, batches: list):
        """Feed path for fused dispatch: a FULL window of ``fuse_steps``
        batches becomes one device-stacked super-batch (leading scan axis);
        a partial trailing window degrades to a list of per-batch placements
        (the loop runs those per-step). Stacked windows ride the device batch
        cache too, but keyed by batch-identity tuples — shuffled epochs form
        new windows, so the window cache is additionally byte-bounded by
        BIGDL_DEVICE_CACHE_MB (beyond it, windows place uncached)."""
        if len(batches) < self.fuse_steps:
            return [self._put_batch(b) for b in batches]
        faults.fault_point(faults.SITE_H2D)  # scripted transfer failure
        cache = self._device_batch_cache
        key = tuple(id(b) for b in batches)
        if cache is not None:
            hit = cache.get(key)
            if hit is not None and all(a is b for a, b in zip(hit[0], batches)):
                return hit[1]
        with trace.span("feed/stack_window", self._seq_args()):
            host = self._stack_and_cast(batches)
        placed = self._timed_h2d(self._place_window, host)
        if cache is not None:
            nbytes = sum(getattr(b.input, "nbytes", 0)
                         + getattr(b.target, "nbytes", 0) for b in batches)
            if self._window_cache_bytes + nbytes <= self.device_cache_mb * 1e6:
                cache[key] = (list(batches), placed)
                self._window_cache_bytes += nbytes
        else:
            # the stacked super-batch holds fresh copies (np.stack), so the
            # per-batch ring buffers are reusable regardless of whether the
            # device_put of the STACK zero-copies
            for b in batches:
                b.recycle()
        return placed

    def _stack_and_cast(self, batches: list):
        """The window on the host, ready for the copy: (input, target)."""
        inp = self._stack_window([b.input for b in batches])
        target = self._stack_window([b.target for b in batches])
        return jax.tree_util.tree_map(self._feed_cast, inp), target

    def _place_window(self, inp, target):
        return jax.device_put(inp), jax.device_put(target)

    @staticmethod
    def _feed_cast(x):
        """Cast float32 inputs to the compute dtype BEFORE the h2d transfer
        (producer thread). The jitted step casts inputs to the compute dtype
        anyway — identical numerics — but casting host-side halves the
        transfer bytes and the device-cache footprint under bf16."""
        cdt = Engine.compute_dtype()
        if cdt != jnp.float32 and getattr(x, "dtype", None) == np.float32:
            return np.asarray(x).astype(cdt)  # bf16 is a valid numpy dtype here
        return x

    # ------------------------------------------------------------ optimize
    def _stop_profiler_if_active(self) -> None:
        """Close a live jax.profiler trace (error paths must not leak it — the
        checkpoint-retry loop would otherwise call start_trace on an already
        active profiler and burn its retry budget on that)."""
        if getattr(self, "_profiling", False):
            try:
                jax.profiler.stop_trace()
            except Exception:
                logger.exception("failed to stop profiler trace")
            self._profiling = False

    @staticmethod
    def _is_nonfinite_failure(exc: BaseException) -> bool:
        """Classify a failure as loss divergence: the explicit finite-loss
        guard, or a checkify sanitizer error (user finite check or a
        float_checks NaN/inf from inside the step)."""
        if isinstance(exc, NonFiniteLossError):
            return True
        msg = str(exc)
        return ("non-finite loss" in msg or "nan generated by" in msg
                or "inf generated by" in msg)

    def optimize(self, resume: Optional[str] = None) -> AbstractModule:
        """Run the training loop. ``resume="auto"`` first restores the newest
        loadable checkpoint under ``set_checkpoint``'s path (corrupt files are
        quarantined, with automatic fallback to the previous version) and
        continues the run — including mid-epoch feed position, RNG streams,
        and trigger bookkeeping, so a preempted run restarts bitwise-
        identically to one that was never interrupted. With no checkpoint on
        disk, ``resume="auto"`` starts from scratch."""
        Engine._require_init()
        if resume not in (None, "auto"):
            raise ValueError(f"resume must be None or 'auto', got {resume!r}")
        # robustness-report baseline spans the WHOLE optimize() call —
        # resume/quarantine events during restore and rollback/retry events
        # between _optimize_impl attempts must all show in the final report
        self._rob_snap0 = events.snapshot()
        if resume == "auto" and self.checkpoint_path is not None \
                and self._has_checkpoint():
            self._load_latest_checkpoint()
            events.record("resume", path=self.checkpoint_path,
                          neval=self.state.get("neval", 0))
        retry_budget = Engine.config().failure_retry_times
        max_nan = int(os.environ.get("BIGDL_MAX_NAN_ROLLBACKS", "2"))
        nan_rollbacks = 0
        self._install_signal_handlers()
        # unified observability: re-read the BIGDL_TRACE/BIGDL_OBS_LOG config
        # and arm the hang watchdog (if BIGDL_WATCHDOG_S is set) for the
        # whole run, retries included
        trace.configure_from_env()
        self._watchdog = obs_watchdog.from_env()
        if self._watchdog is not None:
            self._watchdog.start()
        try:
            return self._optimize_with_retry(retry_budget, max_nan,
                                             nan_rollbacks)
        finally:
            if self._watchdog is not None:
                self._watchdog.stop()
            self._restore_signal_handlers()
            self._rob_snap0 = None

    def _optimize_with_retry(self, retry_budget: int, max_nan: int,
                             nan_rollbacks: int) -> AbstractModule:
        while True:
            try:
                return self._optimize_impl()
            except (KeyboardInterrupt, TrainingPreempted):
                self._stop_profiler_if_active()
                raise
            except Exception as e:
                self._stop_profiler_if_active()
                if self._is_nonfinite_failure(e):
                    # divergence gets its own bounded rollback counter: the
                    # last GOOD checkpoint is restored (the trigger path
                    # flushes losses before every write, so a poisoned state
                    # is never checkpointed), and a NaN that keeps coming
                    # back aborts instead of retrying forever
                    nan_rollbacks += 1
                    self.state["nan_rollbacks"] = nan_rollbacks
                    if nan_rollbacks > max_nan or not self._has_checkpoint():
                        raise
                    events.record("nan_rollback", rollbacks=nan_rollbacks)
                    logger.exception(
                        "non-finite loss; rolling back to last good "
                        "checkpoint (%d/%d rollbacks, BIGDL_MAX_NAN_ROLLBACKS)",
                        nan_rollbacks, max_nan)
                    self._load_latest_checkpoint()
                    # the reload replaced self.state wholesale — the rollback
                    # count must survive it (observability + tests)
                    self.state["nan_rollbacks"] = nan_rollbacks
                    continue
                retry_budget -= 1
                if retry_budget < 0 or not self._has_checkpoint():
                    raise  # no recovery point yet → surface the original failure
                events.record("retry_rollback", retries_left=retry_budget)
                logger.exception(
                    "training failed; retrying from last checkpoint "
                    "(%d retries left)", retry_budget)
                time.sleep(Engine.config().failure_retry_interval)
                self._load_latest_checkpoint()

    # ---------------------------------------------------------- preemption
    def _install_signal_handlers(self) -> None:
        """SIGTERM/SIGINT request a graceful stop at the next step/window
        boundary: the loop writes an emergency checkpoint (with full resume
        state) and raises :class:`TrainingPreempted`. A second SIGINT
        escalates to an immediate KeyboardInterrupt. Handlers can only be
        installed on the main thread; elsewhere preemption is disabled (the
        process's own main thread owns signal disposition)."""
        import signal

        evt = threading.Event()

        def _handler(signum, frame):
            if evt.is_set() and signum == signal.SIGINT:
                raise KeyboardInterrupt
            evt.set()
            logger.warning(
                "received %s: stopping gracefully at the next step boundary "
                "(emergency checkpoint%s)", signal.Signals(signum).name,
                "" if self.checkpoint_path else
                " SKIPPED — no checkpoint path configured")

        try:
            self._prev_handlers = {
                sig: signal.signal(sig, _handler)
                for sig in (signal.SIGTERM, signal.SIGINT)}
            self._preempt = evt
        except ValueError:  # not the main thread
            self._preempt = None
            self._prev_handlers = {}

    def _restore_signal_handlers(self) -> None:
        import signal

        for sig, h in self._prev_handlers.items():
            try:
                signal.signal(sig, h)
            except (ValueError, TypeError):
                pass
        self._prev_handlers = {}
        self._preempt = None

    def _preempt_requested(self) -> bool:
        return self._preempt is not None and self._preempt.is_set()

    def _do_preempt(self, params, mstate, ostate, state, pending) -> None:
        """Graceful-stop tail, run at a step/window boundary:
        flush device losses (a deferred NaN must surface before anything is
        persisted), write + land the emergency checkpoint, publish the
        trained state back onto the model, then raise TrainingPreempted."""
        self._flush_pending(pending, state, keep_last=False)
        path = None
        if self.checkpoint_path is not None:
            # state["neval"] is already the NEXT iteration at a boundary
            self._save_checkpoint(params, mstate, ostate, state,
                                  neval_next=state["neval"])
            self._join_checkpoint_writer()
            path = self.checkpoint_path
        self.model.set_params(jax.device_get(params))
        self.model.set_state(jax.device_get(mstate))
        self._final_ostate = jax.device_get(ostate)
        events.record("preemption", iteration=state["neval"],
                      checkpoint=path)
        logger.warning(
            "training preempted before iteration %d%s", state["neval"],
            f"; emergency checkpoint in {path}" if path
            else " (no checkpoint configured — progress not persisted)")
        raise TrainingPreempted(
            f"training preempted before iteration {state['neval']}",
            checkpoint_path=path, iteration=state["neval"])

    # ------------------------------------------------- resume bookkeeping
    def _feed_base(self):
        """Innermost dataset under the transformer spine (owner of the
        epoch-order permutation)."""
        ds = self.dataset
        while isinstance(ds, TransformedDataSet):
            ds = ds.base
        return ds

    def _base_order_copy(self):
        order = getattr(self._feed_base(), "_order", None)
        return None if order is None else np.array(order, copy=True)

    def _capture_stream_state(self):
        """Epoch-start stream identity of a streaming base dataset
        (``StreamingDataSet.stream_state``: shard order + epoch seed), or
        None for in-memory sources. A fresh process restoring mid-epoch has
        never run this epoch's ``shuffle()``, so the checkpoint must carry
        the stream's epoch identity explicitly — the RNG snapshot alone
        reproduces future draws, not the seed already drawn."""
        fn = getattr(self._feed_base(), "stream_state", None)
        return fn() if callable(fn) else None

    def _resume_info(self, state, neval_next: int) -> dict:
        """Everything beyond params/slots that bitwise mid-epoch resume
        needs: the absolute feed position inside the current epoch, the RNG
        state as of this epoch's shuffle (skipped batches re-run their
        transforms on resume, replaying the exact RNG stream), the epoch's
        shuffled order (shuffles COMPOSE across epochs, so replaying the
        permutation from scratch would not reproduce it), and the run's base
        PRNG key for traced randomness."""
        base_rng = getattr(self, "_base_rng", None)
        mid_epoch = not bool(state.get("epoch_finished", False))
        return {
            "neval_next": int(neval_next),
            "epoch": int(state.get("epoch", 1)),
            "mid_epoch": mid_epoch,
            "feed_pos": int(self._epoch_batches),
            # mid-epoch: the epoch-start snapshot (resume replays forward
            # from it); boundary: the CURRENT state — the feed is closed, so
            # this is race-free and includes every draw the finished epoch
            # made
            "epoch_rng": (self._epoch_rng if mid_epoch
                          else RandomGenerator.state_dict()),
            "epoch_order": self._epoch_order,
            # streamed feeds: shard order + window-shuffle seed of the epoch
            # in flight (boundary checkpoints re-derive both via shuffle())
            "stream": self._epoch_stream if mid_epoch else None,
            "base_rng": (None if base_rng is None
                         else np.asarray(jax.device_get(base_rng))),
        }

    def _apply_resume_info(self, resume: dict) -> None:
        self.state["neval"] = int(resume["neval_next"])
        self._resume_feed = resume
        if resume.get("base_rng") is not None:
            self._resume_base_rng = np.asarray(resume["base_rng"])

    def _has_checkpoint(self) -> bool:
        # land any in-flight write; a FAILED write logs (older files may still
        # offer a valid, if stale, recovery point for the retry loop)
        self._join_checkpoint_writer(raise_error=False)
        if self.checkpoint_path is None or not os.path.isdir(self.checkpoint_path):
            return False
        names = os.listdir(self.checkpoint_path)
        if self.checkpoint_backend == "orbax":
            return any(p.startswith("ckpt_orbax") and p.endswith(".meta.json")
                       for p in names)  # committed = meta marker present
        if self.checkpoint_backend == "elastic":
            from bigdl_tpu.utils import elastic_ckpt
            return bool(elastic_ckpt.complete_versions(self.checkpoint_path))
        return any(p.startswith("checkpoint") and p.endswith(".pkl")
                   for p in names)

    def _optimize_impl(self) -> AbstractModule:
        sched = getattr(self.optim_method, "learningrate_schedule", None)
        if getattr(sched, "stateful", False) \
                and getattr(sched, "monitor", "score") not in ("loss", "Loss") \
                and self.val_trigger is None:
            logger.warning(
                "Plateau monitoring a validation metric without set_validation never "
                "sees a value — the LR will stay at its base value; configure "
                "validation or use monitor='loss'")
        # conv-bn fusion pass (BIGDL_CONVBN_FUSE=1): rewrite adjacent
        # conv→bn(→relu) chains into FusedConvBNReLU modules once, before
        # the parameter checkout — the whole vision zoo picks it up with no
        # model changes. Off (default): the model is never touched.
        if os.environ.get("BIGDL_CONVBN_FUSE", "0") == "1" \
                and not getattr(self, "_convbn_fused", False):
            from bigdl_tpu.nn.graph import fuse_conv_bn
            self.model = fuse_conv_bn(self.model)
            self._convbn_fused = True
            self._step_cache = self._window_cache = None
            self._state_materialized = False
        self.model.training()
        params = self.model.get_params()
        mstate = self.model.get_state()
        # Optimizer-state continuity: a second optimize() on the same Optimizer is a
        # *continuation* (self.state persists), so momentum/Adam slots must carry
        # over — re-running init_state here would silently reset them (a round-2
        # bench bug: the timed leg trained with zeroed momentum).
        ostate = getattr(self, "_resume_ostate", None)
        if ostate is None and self.state.get("neval", 1) > 1:
            ostate = getattr(self, "_final_ostate", None)
        mask = self._trainable_mask()
        if ostate is not None and not self._ostate_compatible(ostate, params,
                                                              mask):
            # freeze/LoRA config changed since these slots were created (or an
            # untrimmed-era checkpoint meets a trimmed config): the slot shapes
            # no longer fit the compiled step. Restart moments — loudly.
            logger.warning(
                "optimizer-state shapes do not match the current freeze/scale "
                "configuration; resetting optimizer slots (momentum/Adam "
                "moments start fresh)")
            ostate = None
        if ostate is None:
            ostate = self._effective_method().init_state_trimmed(params, mask)
        self._resume_ostate = None
        # step cache is keyed on the Engine compute dtype (the casts are baked
        # into the trace) AND the model's gradient-scale fingerprint — freeze/
        # unfreeze/set_scale_* between optimize() calls change the program and
        # happen on the MODULE, where they can't clear this cache directly
        cdt = Engine.compute_dtype()
        scales_key = tuple(jax.tree_util.tree_leaves(self.model.grad_scales()))
        if (self._step_cache is None
                or getattr(self, "_step_cache_dtype", None) != cdt
                or getattr(self, "_step_cache_scales", None) != scales_key):
            self._step_cache = self._compile_step()
            self._step_cache_dtype = cdt
            self._step_cache_scales = scales_key
        step_fn = self._step_cache
        # fused-window program cache: keyed like the step cache plus the
        # window size (a new K is a new scan trip count = a new program)
        fuse = max(1, int(self.fuse_steps))
        window_fn = None
        if fuse > 1:
            wkey = (cdt, scales_key, fuse)
            if self._window_cache is None \
                    or getattr(self, "_window_cache_key", None) != wkey:
                self._window_cache = self._compile_window(fuse)
                self._window_cache_key = wkey
            window_fn = self._window_cache
        # traced-randomness base key: a resumed run reuses the interrupted
        # run's key (stored in the checkpoint) — drawing a fresh one would
        # change every dropout mask downstream of the resume point
        if self._resume_base_rng is not None:
            base_rng = jnp.asarray(self._resume_base_rng)
            self._resume_base_rng = None
        else:
            base_rng = RandomGenerator.next_key()
        self._base_rng = base_rng
        self._setup_device_cache()

        from bigdl_tpu.dataset.prefetch import PrefetchingFeed

        state = self.state
        records = 0
        # per-stage feed attribution baseline: every rail (decode/augment/
        # stack stage timers, the h2d put_batch phase, robustness counters)
        # publishes into the obs registry — ONE snapshot is the run baseline
        # for the summary curves and the end-of-run report.
        reg = obs_registry.registry
        reg_snap0 = reg.snapshot()
        step_hist = reg.histogram("train/step_wall")
        # live plane: bring up the /metrics endpoint and the SLO monitor
        # (both no-ops unless their BIGDL_* knobs are set) and the
        # per-program FLOPs memo behind the always-on MFU gauges (one ~ms
        # cost-analysis per compiled program, cached for the Optimizer's
        # lifetime)
        obs_exporter.start_from_env()
        obs_slo.start_from_env()
        # cluster-scope plane: device-memory gauges (HBM polls + pressure
        # events) and, under jax.distributed with BIGDL_OBS_SPOOL_DIR set,
        # the per-host snapshot spool process 0's exporter merges
        obs_device.start_from_env()
        from bigdl_tpu.obs import cluster as obs_cluster
        obs_cluster.start_from_env()
        if not hasattr(self, "_flops_memo"):
            self._flops_memo = {}
        if not hasattr(self, "_mem_memo"):
            self._mem_memo = {}
        rob_snap0 = getattr(self, "_rob_snap0", None)
        if rob_snap0 is None:  # _optimize_impl called outside optimize()
            rob_snap0 = events.snapshot()
        window_t0 = time.perf_counter()
        # device-side losses awaiting fetch: list of (neval, DeviceArray). Fetched
        # in batches every log_every iterations — this backend charges ~75 ms per
        # host<->device round trip, so a per-iteration fetch would dominate once
        # steps are fast (round-2 verdict, weak #3).
        pending: list = []
        run_iters = 0
        stop = False
        self._profiling = False

        def flush_and_log(start_it: int, end_it: int) -> None:
            """Log-boundary handling for completed iterations
            ``[start_it, end_it]``: when a ``log_every`` boundary was crossed,
            fetch all complete losses in one round trip; the newest entry stays
            pending so the fetch never stalls on the in-flight step or window
            (preserves the lagged logging semantics). The fetch doubles as the
            throughput window's device sync, so records (counted per flushed
            step) over dt is honest completion throughput, not host dispatch
            rate."""
            nonlocal records, window_t0
            if (end_it // self.log_every) <= ((start_it - 1) // self.log_every):
                return  # no log boundary inside [start_it, end_it]
            records += self._flush_pending(pending, state, keep_last=True)
            if "loss" in state and records > 0:
                dt = time.perf_counter() - window_t0
                thr = records / dt if dt > 0 else 0.0
                state["throughput"] = thr
                reg.gauge("train/throughput").set(thr)
                drops = [v for t, v in
                         (state.get("state_metrics") or {}).items()
                         if t.endswith("dropped_fraction")]
                logger.info(
                    "Epoch %d iter %d: loss %.6f, %.1f records/s%s",
                    state["epoch"], state["neval"], state["loss"],
                    thr,
                    (", moe drop %.1f%%" % (100 * max(drops))
                     if drops else ""))
                records = 0
                window_t0 = time.perf_counter()
            elif "loss" in state:
                # nothing fetched yet this window (e.g. the first
                # boundaries after a warm start) — loss only, and the
                # window keeps accumulating
                logger.info("Epoch %d iter %d: loss %.6f",
                            state["epoch"], state["neval"], state["loss"])
            stages = self._feed_stage_report(reg_snap0)
            if stages:
                # decode/augment are ms/IMAGE, stack/h2d ms/BATCH — per-stage
                # regressions show as their own training summary curves
                # instead of smearing into the single feed-wait number
                state["feed_stage_ms"] = stages
                if self.train_summary is not None:
                    for stage, ms in stages.items():
                        self.train_summary.add_scalar(
                            f"FeedStage/{stage}_ms", ms, state["neval"])
            # robustness events (skips/retries/rollbacks/respawns/...) ride
            # the same rails: cumulative per-kind counts as summary curves
            rob = events.deltas(rob_snap0)
            if rob and self.train_summary is not None:
                for kind, n in rob.items():
                    self.train_summary.add_scalar(
                        f"Robustness/{kind}", float(n), state["neval"])

        resume_feed, self._resume_feed = self._resume_feed, None
        iter_mark = time.perf_counter()
        while not stop:
            state["epoch_finished"] = False
            skip = 0
            if resume_feed is not None:
                # re-enter the interrupted epoch exactly: restore the RNG to
                # its state as of that epoch's shuffle and reinstall the
                # epoch's shuffled order (shuffles compose across epochs, so
                # re-deriving the permutation would not reproduce it). The
                # first `feed_pos` batches are then re-transformed and
                # DISCARDED below — replaying their RNG draws so everything
                # downstream of the resume point is bitwise-identical.
                if resume_feed.get("epoch_rng") is not None:
                    RandomGenerator.load_state_dict(resume_feed["epoch_rng"])
                if resume_feed.get("mid_epoch"):
                    base = self._feed_base()
                    order = resume_feed.get("epoch_order")
                    if order is not None and hasattr(base, "_order"):
                        base._order = np.array(order, copy=True)
                    # streamed feed: reinstall the interrupted epoch's stream
                    # identity (shard order + window-shuffle seed) — this
                    # process never ran that epoch's shuffle()
                    stream = resume_feed.get("stream")
                    if stream is not None and hasattr(base,
                                                      "restore_stream_state"):
                        base.restore_stream_state(stream)
                    skip = int(resume_feed.get("feed_pos", 0))
                    self._epoch_rng = resume_feed.get("epoch_rng")
                    self._epoch_order = self._base_order_copy()
                else:
                    # epoch-boundary checkpoint: the next shuffle is the
                    # first divergent draw — run it normally
                    self.dataset.shuffle()
                    self._epoch_rng = RandomGenerator.state_dict()
                    self._epoch_order = self._base_order_copy()
                resume_feed = None
            else:
                self.dataset.shuffle()
                self._epoch_rng = RandomGenerator.state_dict()
                self._epoch_order = self._base_order_copy()
            self._epoch_stream = self._capture_stream_state()
            self._epoch_batches = skip
            # a fully-consumed epoch resumed at its tail legitimately yields
            # no further batches
            epoch_had_data = skip > 0
            make_iter = ((lambda s=skip: itertools.islice(
                self.dataset.data(train=True), s, None)) if skip
                else (lambda: self.dataset.data(train=True)))
            place = self._put_window if fuse > 1 else self._put_batch
            # sequence numbers of the windows in flight: the producer notes
            # one before it places a window, the loop takes one with each
            # window it is handed (the feed's queue is first in, first out)
            seqs: collections.deque = collections.deque()

            def put(group, place=place, seqs=seqs):
                self._feed_seq += 1
                seqs.append(self._feed_seq)
                return place(group)

            feed = PrefetchingFeed(make_iter, put, self.prefetch_depth,
                                   window=fuse)
            with feed, trace.span("train/epoch",
                                  {"epoch": state["epoch"]}):
                feed_it = iter(feed)
                while True:
                    # endWhen is evaluated at loop top with the reference's 1-based
                    # neval, so maxIteration(n) runs exactly n iterations (SURVEY §3.1)
                    if self.end_when(state):
                        stop = True
                        break
                    # "feed" = time the step loop actually *waits* on data; in
                    # steady state the producer thread hides assembly + transfer
                    with self.metrics.timer(
                            "feed", trace.span("train/feed_wait")) as waited:
                        try:
                            item, placed = next(feed_it)
                        except StopIteration:
                            break
                    self._obs_feed_wait(waited.seconds, step_hist)
                    epoch_had_data = True
                    seq = seqs.popleft()

                    batches = item if fuse > 1 else [item]
                    # full windows arrive device-stacked (leading scan axis);
                    # partial trailing windows (and fuse==1) arrive as
                    # per-batch placements
                    stacked = singles = None
                    if fuse > 1 and not isinstance(placed, list):
                        stacked = placed
                    else:
                        singles = placed if fuse > 1 else [placed]

                    if stacked is not None \
                            and (run_iters > 0 or self._state_materialized) \
                            and self._fusible_steps(state) >= len(batches):
                        # -------- fused dispatch: K steps, ONE compiled scan,
                        # losses/metrics device-resident until the next flush
                        k = len(batches)
                        start_it = state["neval"]
                        step_idx0 = jnp.asarray(start_it - 1, jnp.int32)
                        inp, target = stacked
                        # times the dispatch call, not the K steps: the
                        # device runs them after the call returns
                        with self.metrics.timer("step_dispatch", trace.span(
                                "train/window",
                                {"k": k, "it": start_it, "seq": seq}
                                if trace.enabled() else None)):
                            out = window_fn(params, mstate, ostate, step_idx0,
                                            inp, target, base_rng)
                        if self.check_numerics:
                            params, mstate, ostate, losses, sms, err = out
                        else:
                            (params, mstate, ostate, losses, sms), err = \
                                out, None
                        first = run_iters == 0
                        run_iters += k
                        self._epoch_batches += k
                        tags = self._state_metric_tags(mstate)
                        if first:
                            # first dispatch of this (continuation) optimize():
                            # absorb compile/re-placement synchronously and
                            # start the throughput window at the window's end —
                            # one-time costs must not bill to steady state
                            vals, sm_vals = jax.device_get((losses, sms))
                            if err is not None:
                                jax.device_get(err).throw()
                            for i in range(k):
                                metrics = {t: float(s[i])
                                           for t, s in zip(tags, sm_vals)}
                                val = self._guard_loss(start_it + i,
                                                       float(vals[i]))
                                state["loss"] = val
                                if metrics:
                                    state["state_metrics"] = metrics
                                self._write_iter_summary(
                                    start_it + i, val, state, metrics)
                            records = 0
                            window_t0 = time.perf_counter()
                        else:
                            for i in range(k):
                                # per-step exactness survives fusion: every
                                # step's loss/metric scalars queue individually
                                # (summaries land with their true iteration);
                                # the window's joined checkify error rides the
                                # LAST entry so any flush covering the window
                                # surfaces it
                                pending.append(
                                    (start_it + i, losses[i], batches[i].valid,
                                     err if i == k - 1 else None,
                                     [(t, s[i]) for t, s in zip(tags, sms)],
                                     start_it))  # dispatch group = window start
                        state["neval"] = start_it + k - 1
                        flush_and_log(start_it, state["neval"])
                        # no in-loop trigger can have fired STRICTLY inside
                        # the window (_fusible_steps clipped it); evaluating
                        # once at the window end is per-step exact
                        self._fire_triggers(params, mstate, ostate, state,
                                            boundary=False, pending=pending)
                        for it in range(start_it, start_it + k):
                            faults.fault_point(faults.SITE_STALL, index=it)
                            faults.fault_point(faults.SITE_HOST_DOWN,
                                               index=it)
                        fired = any([
                            faults.fault_point(faults.SITE_SIGTERM,
                                               index=it) is not None
                            for it in range(start_it, start_it + k)])
                        if fired and self._preempt is not None:
                            self._preempt.wait(1.0)
                        state["neval"] += 1
                        # window-program FLOPs for the MFU gauge: lowered once
                        # per (program, shape) from NEW-tree avals (the old
                        # params/mstate/ostate buffers were donated into the
                        # dispatch above and must not be touched)
                        wf_key = ("window", cdt, scales_key, k,
                                  _batch_sig(inp, target))
                        if wf_key not in self._flops_memo:
                            self._flops_memo[wf_key] = obs_mfu.program_flops(
                                window_fn, params, mstate, ostate, step_idx0,
                                inp, target, base_rng)
                        self._note_program_memory(
                            wf_key, window_fn, params, mstate, ostate,
                            step_idx0, inp, target, base_rng)
                        now = time.perf_counter()
                        self._obs_step(now - iter_mark, k, step_hist,
                                       flops=self._flops_memo[wf_key])
                        iter_mark = now
                        if self._preempt_requested():
                            self._do_preempt(params, mstate, ostate, state,
                                             pending)
                        continue

                    # ---------- per-step dispatch: fuse==1, the run's first
                    # window (absorbs compile and may materialize module-state
                    # structure a scan carry could not morph), a partial
                    # trailing window, or a trigger boundary inside the window
                    for i, batch in enumerate(batches):
                        if i > 0 and self.end_when(state):
                            stop = True
                            break
                        if singles is not None:
                            inp, target = singles[i]
                        else:
                            # boundary fallback: slice this step's batch out of
                            # the stacked window (a device-side view; no h2d)
                            inp, target = jax.tree_util.tree_map(
                                lambda a: a[i], stacked)

                        if self.profile_dir is not None and not self._profiling \
                                and state["neval"] >= self.profile_start_iter:
                            jax.profiler.start_trace(self.profile_dir)
                            self._profiling = True
                            profile_stop_at = state["neval"] + self.profile_n_iters

                        step_idx = jnp.asarray(state["neval"] - 1, jnp.int32)
                        # times the dispatch call, not the step
                        with self.metrics.timer("step_dispatch", trace.span(
                                "train/step",
                                {"it": state["neval"], "seq": seq}
                                if trace.enabled() else None)):
                            out = step_fn(
                                params, mstate, ostate, step_idx, inp, target,
                                base_rng)
                        if self.check_numerics:
                            params, mstate, ostate, loss, err = out
                        else:
                            (params, mstate, ostate, loss), err = out, None
                        run_iters += 1
                        if self.sync_metrics:
                            with self.metrics.timer("step_device"):
                                jax.block_until_ready(loss)

                        if self._profiling and state["neval"] + 1 >= profile_stop_at:
                            jax.block_until_ready(loss)
                            jax.profiler.stop_trace()
                            self._profiling = False
                            self.profile_dir = None  # one window per optimize()
                            logger.info("profiler trace captured")

                        self._epoch_batches += 1
                        smetrics = self._collect_state_metrics(mstate)
                        if run_iters == 1:
                            # First step of this optimize() call absorbs compile, param
                            # re-placement, and feed spin-up. Wait for it, then start the
                            # throughput window — one-time costs must not be billed to
                            # steady-state throughput (round-2 bench bug).
                            val = float(jax.device_get(loss))
                            if err is not None:
                                jax.device_get(err).throw()
                            val = self._guard_loss(state["neval"], val)
                            state["loss"] = val
                            fetched = {t: float(jax.device_get(v))
                                       for t, v in smetrics}
                            if fetched:
                                state["state_metrics"] = fetched
                            self._write_iter_summary(state["neval"], val, state,
                                                     fetched)
                            # a full step completed: module state is
                            # materialized, future windows may fuse from item 1
                            self._state_materialized = True
                            records = 0
                            window_t0 = time.perf_counter()
                        else:
                            pending.append((state["neval"], loss, batch.valid,
                                            err, smetrics, state["neval"]))
                        flush_and_log(state["neval"], state["neval"])
                        self._fire_triggers(params, mstate, ostate, state,
                                            boundary=False, pending=pending)
                        faults.fault_point(faults.SITE_STALL,
                                           index=state["neval"])
                        faults.fault_point(faults.SITE_HOST_DOWN,
                                           index=state["neval"])
                        if faults.fault_point(faults.SITE_SIGTERM,
                                              index=state["neval"]) \
                                is not None and self._preempt is not None:
                            self._preempt.wait(1.0)
                        state["neval"] += 1
                        sf_key = ("step", cdt, scales_key,
                                  _batch_sig(inp, target))
                        if sf_key not in self._flops_memo:
                            self._flops_memo[sf_key] = obs_mfu.program_flops(
                                step_fn, params, mstate, ostate, step_idx,
                                inp, target, base_rng)
                        self._note_program_memory(
                            sf_key, step_fn, params, mstate, ostate,
                            step_idx, inp, target, base_rng)
                        now = time.perf_counter()
                        self._obs_step(now - iter_mark, 1, step_hist,
                                       flops=self._flops_memo[sf_key])
                        iter_mark = now
                        if self._preempt_requested():
                            self._do_preempt(params, mstate, ostate, state,
                                             pending)
                    if stop:
                        break
            if stop:
                break
            if not epoch_had_data:
                raise RuntimeError("dataset yielded no batches")
            state["epoch"] += 1
            state["epoch_finished"] = True
            self._epoch_batches = 0
            # full flush so Plateau(loss) sees the latest value; the records stay
            # in the running window (the next log boundary bills them)
            records += self._flush_pending(pending, state, keep_last=False)
            self._fire_triggers(params, mstate, ostate, state, boundary=True,
                                pending=pending)
            if self._preempt_requested():
                self._do_preempt(params, mstate, ostate, state, pending)
            if self.end_when(state):
                break

        self._stop_profiler_if_active()  # endWhen fired inside the trace window
        self._stop_copy_watcher()  # every feed/h2d span is recorded by now
        self._flush_pending(pending, state, keep_last=False)
        self._join_checkpoint_writer()  # optimize() returning implies ckpt durable
        self._publish(params, mstate, ostate)
        if self.metrics.summary():
            logger.info("phase timings (mean): %r", self.metrics)
        stages = self._feed_stage_report(reg_snap0)
        if stages:
            state["feed_stage_ms"] = stages
            logger.info(
                "feed stage attribution (mean ms — decode/augment per image, "
                "stack/h2d per batch): %r", stages)
        rob = events.deltas(rob_snap0)
        if rob:
            # end-of-run robustness report: a run that silently absorbed
            # faults must not look identical to a clean one
            state["robustness"] = rob
            logger.info("robustness report: %s", events.format_report(rob))
        # ---- unified run report: ONE merged view (step percentiles, feed
        # attribution, robustness counters, span totals) — logged here,
        # stored in state, appended to the JSONL event log (from which
        # `bigdl-tpu diag` re-renders the identical text), and the Chrome
        # trace exported alongside when tracing is on
        wd = self._watchdog
        run_report = obs_report.build_report(
            reg_snap0, reg.snapshot(), span_totals=trace.span_totals(),
            robustness=rob, watchdog_dumps=wd.dumps if wd is not None else 0)
        state["run_report"] = run_report
        logger.info("run report:\n%s", obs_report.format_report(run_report))
        trace.event("run_report", report=run_report)
        obs_exporter.publish_status("run_report", run_report)
        chrome = trace.export_chrome()
        if chrome is not None:
            logger.info("chrome trace written: %s (event log: %s)",
                        chrome, trace.jsonl_path())
        return self.model

    @staticmethod
    def _feed_stage_report(reg_snap0: dict) -> dict:
        """Mean ms per stage occurrence since the run's registry baseline.
        Every stage rail publishes into the obs registry (``feed/<stage>``
        from the dataset layer, ``phase/put_batch`` = h2d from the trainer's
        own timer), so ONE snapshot delta is the whole attribution."""
        snap1 = obs_registry.registry.snapshot()
        h0 = reg_snap0.get("histograms", {})
        out = {}
        for name, h in snap1.get("histograms", {}).items():
            if name.startswith("feed/"):
                stage = name[len("feed/"):]
            elif name == "phase/put_batch":
                stage = "h2d"
            else:
                continue
            base = h0.get(name, {})
            dc = h["count"] - base.get("count", 0)
            dt = h["total"] - base.get("total", 0.0)
            if dc > 0:
                out[stage] = round(1e3 * dt / dc, 3)
        return out

    # ------------------------------------------------------- observability
    def _note_program_memory(self, key, fn, *args) -> None:
        """Per-program device-memory attribution (the memory twin of the
        FLOPs memo): one ``memory_analysis()`` per program-cache key,
        published as ``train/program_*_bytes`` gauges and a /statusz
        block. Costs one extra AOT compile per program, so it is gated
        behind an active exporter (a scraped process) or
        ``BIGDL_PROGRAM_MEMORY=1`` — absent-not-wrong everywhere else."""
        if key in self._mem_memo:
            return
        if not (os.environ.get("BIGDL_PROGRAM_MEMORY", "").strip()
                or obs_exporter.active() is not None):
            return
        mem = obs_device.program_memory(fn, *args)
        self._mem_memo[key] = mem
        if mem:
            reg = obs_registry.registry
            for field, v in mem.items():
                reg.gauge("train/program_%s" % field).set(v)
            obs_exporter.publish_status(
                "program_memory",
                {"/".join(str(p) for p in k): v
                 for k, v in self._mem_memo.items() if v})

    def _obs_step(self, wall_s: float, k: int, step_hist,
                  flops: Optional[float] = None) -> None:
        """Per-step observability bookkeeping at a step/window boundary:
        record the per-step wall time (window wall / k) into the rolling
        ``train/step_wall`` histogram, feed the dispatch unit's model FLOPs
        into the live ``train/mfu`` accounting, and heartbeat the hang
        watchdog with the whole dispatch unit's duration."""
        per = wall_s / k
        for _ in range(k):
            step_hist.observe(per)
        obs_mfu.note("train", flops, wall_s)
        wd = self._watchdog
        if wd is not None:
            wd.heartbeat(wall_s)

    @staticmethod
    def _obs_feed_wait(wait_s: float, step_hist) -> None:
        """Feed-stall accounting: a step that waited on data longer than
        half the rolling median step time (and >10 ms) counts as a stall —
        the one number that says "the accelerator sat idle for the feed"."""
        med = step_hist.median()
        if med is not None and wait_s > max(0.010, 0.5 * med):
            obs_registry.registry.counter("train/feed_stall").inc()

    # ---------------------------------------------------------- loss flush
    def _guard_loss(self, it: int, v: float) -> float:
        """Finite-loss guard at every host loss fetch (fused and per-step,
        with or without the checkify sanitizer): NaN/inf raises
        :class:`NonFiniteLossError`, which ``optimize()`` answers with a
        bounded rollback to the last good checkpoint. The ``nonfinite_loss``
        fault site poisons the fetched value here for deterministic tests."""
        if faults.check_fault(faults.SITE_NONFINITE_LOSS, index=it) is not None:
            v = float("nan")
        if not np.isfinite(v):
            raise NonFiniteLossError(
                f"non-finite loss at iteration {it}: {v}", iteration=it)
        return v

    def _publish(self, params, mstate, ostate) -> None:
        """The trained state back onto the model, and the optimizer's slots
        kept for the next ``optimize()`` call, as the arrays the last step left
        on the device: a model holds device arrays from ``reset()`` on, and a
        fetch to the host here with the copy back at the next call's start is
        12 bytes a parameter each way under Adam (6.6 GB and 4.5 to 7 s a call
        for 551M parameters, of a window of 10 s; PERF.md, PR 29). Across
        processes an array is not wholly addressable, and the state is
        gathered to the host as before."""
        if jax.process_count() > 1:
            params, mstate, ostate = jax.device_get((params, mstate, ostate))
        self.model.set_params(params)
        self.model.set_state(mstate)
        self._final_ostate = ostate

    def _collect_state_metrics(self, mstate) -> list:
        """(tag, device_scalar) pairs for observable module-state leaves
        (OBSERVABLE_STATE_LEAVES — MoE routing health). The walk is cheap
        host work on a static structure; the values ride the batched loss
        fetch, so observability adds no extra device round trips."""
        from jax.tree_util import tree_flatten_with_path
        out = []
        for path, leaf in tree_flatten_with_path(mstate)[0]:
            keys = [str(getattr(p, "key", p)) for p in path]
            if keys and keys[-1] in self.OBSERVABLE_STATE_LEAVES \
                    and getattr(leaf, "shape", None) == ():
                out.append(("State/" + "/".join(keys), leaf))
        return out

    def _flush_pending(self, pending: list, state: dict, keep_last: bool) -> int:
        """Fetch queued device losses in ONE host round trip, write their exact
        per-iteration summary scalars, and update ``state['loss']``. With
        ``keep_last`` the newest DISPATCH stays queued while it is still in
        flight: one step in per-step mode, the whole newest window in fused
        mode — all of a window's scalars live in one program's outputs, so
        fetching any of them would sync the entire window. If the newest
        dispatch has already completed (``is_ready`` — always true under
        synchronous CPU dispatch), it is fetched too: the flush never stalls,
        and the throughput window's record count matches the work its wall
        clock actually covered.
        Returns the number of records covered by the fetched (= completed) steps."""
        if keep_last and pending:
            try:
                ready = bool(pending[-1][1].is_ready())
            except Exception:
                ready = False  # can't probe → conservatively keep it queued
            if ready:
                to_fetch = list(pending)
            else:
                last_group = pending[-1][5]
                to_fetch = [e for e in pending if e[5] != last_group]
        else:
            to_fetch = list(pending)
        if not to_fetch:
            return 0
        with self.metrics.timer("loss_fetch", trace.span("train/loss_fetch")):
            vals, errs, mvals = jax.device_get(
                ([l for _, l, _, _, _, _ in to_fetch],
                 [e for _, _, _, e, _, _ in to_fetch],
                 [[v for _, v in m] for _, _, _, _, m, _ in to_fetch]))
        records = 0
        for (it, _, valid, _, sm, _), v, err, mv in zip(to_fetch, vals, errs,
                                                        mvals):
            if err is not None:
                err.throw()  # checkify sanitizer: NaN/inf with op location
            # finite-loss guard rides every fetch path — deferred (pending)
            # losses included, or a NaN surfacing after a log boundary would
            # slip past the rollback machinery into state/checkpoints
            state["loss"] = self._guard_loss(it, float(v))
            records += valid
            metrics = {tag: float(x) for (tag, _), x in zip(sm, mv)}
            if metrics:
                state["state_metrics"] = metrics
            self._write_iter_summary(it, float(v), state, metrics)
        del pending[: len(to_fetch)]
        return records

    def _write_iter_summary(self, it: int, loss_val: float, state: dict,
                            metrics: Optional[dict] = None) -> None:
        """Per-iteration scalar summaries (Loss / LearningRate / Throughput), written
        at flush time with the iteration they belong to — lazy loss fetching must not
        change what lands in the event file."""
        if self.train_summary is None:
            return
        # per-tag triggers (set_summary_trigger) see the iteration being written,
        # not the loop's current head
        tag_state = {"neval": it, "epoch": state.get("epoch", 1),
                     "epoch_finished": False}

        def _tag_fires(name: str) -> bool:
            get = getattr(self.train_summary, "get_summary_trigger", None)
            trig = get(name) if get else None
            return trig is None or trig(tag_state)

        if _tag_fires("Loss"):
            self.train_summary.add_scalar("Loss", loss_val, it)
        if _tag_fires("LearningRate"):
            self.train_summary.add_scalar(
                "LearningRate", self.optim_method.get_learning_rate(it - 1), it)
        if "throughput" in state and _tag_fires("Throughput"):
            self.train_summary.add_scalar("Throughput", state["throughput"], it)
        for tag, val in (metrics or {}).items():
            if _tag_fires(tag):
                self.train_summary.add_scalar(tag, val, it)

    # ------------------------------------------------------------ triggers
    @staticmethod
    def _in_scope(trigger: Trigger, boundary: bool) -> bool:
        scope = getattr(trigger, "scope", "any")
        if scope == "any":
            return True
        return (scope == "epoch") == boundary

    def _fire_triggers(self, params, mstate, ostate, state, boundary: bool,
                       pending: Optional[list] = None) -> None:
        # Stateful-schedule (Plateau) cadence: monitor='score' is fed after each
        # validation round; monitor='loss' is fed exactly once per epoch boundary
        # (whether or not validation is configured) — never both for one metric.
        sched_monitor = getattr(
            getattr(self.optim_method, "learningrate_schedule", None), "monitor", None)
        if self.val_trigger is not None and self._in_scope(self.val_trigger, boundary) \
                and self.val_trigger(state):
            self._run_validation(params, mstate, state)
            # "score" and named-validation-metric monitors are both fed here
            if sched_monitor is not None and sched_monitor not in ("loss", "Loss"):
                self._update_stateful_schedule(ostate, state)
        if boundary and sched_monitor in ("loss", "Loss"):
            self._update_stateful_schedule(ostate, state)
        if self.checkpoint_trigger is not None and self.checkpoint_path is not None \
                and self._in_scope(self.checkpoint_trigger, boundary) \
                and self.checkpoint_trigger(state):
            if pending:
                # deferred losses (and any checkify error) must surface
                # BEFORE the write — a NaN-poisoned checkpoint would become
                # the retry loop's deterministic-failure resume point
                self._flush_pending(pending, state, keep_last=False)
            self._save_checkpoint(params, mstate, ostate, state)
        # scalar summaries (Loss/LearningRate/Throughput) are written by
        # _flush_pending with exact per-iteration values; only the opt-in
        # parameter histograms remain here (expensive: device→host pull of
        # every weight)
        if not boundary and self.train_summary is not None:
            ptrig = self.train_summary.get_summary_trigger("Parameters") \
                if hasattr(self.train_summary, "get_summary_trigger") else None
            if ptrig is not None and ptrig(state):
                from jax.tree_util import keystr, tree_flatten_with_path
                leaves, _ = tree_flatten_with_path(jax.device_get(params))
                for path, leaf in leaves:
                    self.train_summary.add_histogram(
                        keystr(path).strip("[]'\"").replace("']['", "/"),
                        leaf, state["neval"])

    def _update_stateful_schedule(self, ostate, state) -> None:
        """Feed the monitored metric to a stateful LR schedule (Plateau) and write
        the resulting LR into the live optimizer state — a traced leaf, so the LR
        drops without recompiling the step. With per-submodule optimizers the
        DEFAULT method's schedule is observed and its 'clr' lives under
        ostate['default']."""
        from bigdl_tpu.optim.optim_method import CompositeOptimMethod
        if isinstance(self.optim_method, CompositeOptimMethod):
            ostate = ostate.get("default", {})  # the default group's slots
        sched = getattr(self.optim_method, "learningrate_schedule", None)
        if not getattr(sched, "stateful", False) or "clr" not in ostate:
            return
        monitor = getattr(sched, "monitor", "score")
        if monitor in ("loss", "Loss"):
            value = state.get("loss")
        elif monitor == "score":
            value = state.get("score")
        else:
            # a validation method's name — not positional (round-2 weak #7)
            value = state.get("scores", {}).get(monitor)
            if value is None and "scores" in state:
                raise ValueError(
                    f"Plateau monitor {monitor!r} matches no validation method; "
                    f"available: {sorted(state['scores'])}")
        if value is None:
            return
        new_lr = sched.on_metric(float(value))
        ostate["clr"] = jnp.asarray(new_lr, jnp.float32)

    def _run_validation(self, params, mstate, state) -> None:
        if self.val_dataset is None or not self.val_methods:
            return
        # Device-resident evaluation (the eval mirror of the fused training
        # windows): the shared engine runs fused forward+fold windows on its
        # OWN feed — mid-training validation no longer drains the training
        # feed's pipelining — and device-capable methods fold on device, so
        # the pass fetches O(1) metric scalars instead of per-batch logits.
        from bigdl_tpu.optim.evaluator import run_device_eval
        with self.metrics.timer("validation", trace.span("train/validation")):
            results, stats = run_device_eval(
                self.model, params, mstate, self.val_dataset,
                list(self.val_methods), depth=self.prefetch_depth,
                allow_empty=True)
        # observability pair: how many bytes validation pulled off the device
        # and how long the loop was blocked on those fetches
        state["val_fetch_bytes"] = stats["fetch_bytes"]
        state["val_wait_ms"] = stats["wait_ms"]
        self.metrics.add("val_fetch_wait", stats["wait_ms"] / 1e3)
        logger.info(
            "Validation pass: %d batches (%d fused windows), "
            "val_fetch_bytes=%d, val_wait_ms=%.1f",
            stats["batches"], stats["fused_windows"], stats["fetch_bytes"],
            stats["wait_ms"])
        if self.val_summary is not None:
            self.val_summary.add_scalar("ValFetchBytes",
                                        float(stats["fetch_bytes"]),
                                        state["neval"])
            self.val_summary.add_scalar("ValWaitMs", float(stats["wait_ms"]),
                                        state["neval"])
        state.setdefault("scores", {})
        for m, r in zip(self.val_methods, results):
            if r is not None:
                v, c = r.result()
                logger.info("Validation %s: %.4f (%d samples)", m.name, v, c)
                state["scores"][m.name] = v
                if self.val_summary is not None:
                    self.val_summary.add_scalar(m.name, v, state["neval"])
        if results and results[0] is not None:
            state["score"] = results[0].result()[0]

    # ---------------------------------------------------------- checkpoint
    def _ckpt_file(self, state) -> str:
        tag = "" if self.overwrite_checkpoint else f".{state['neval']}"
        return os.path.join(self.checkpoint_path, f"checkpoint{tag}.pkl")

    def _save_checkpoint(self, params, mstate, ostate, state,
                         neval_next: Optional[int] = None) -> None:
        """Fetch on the loop thread (consistent snapshot), write on a background
        thread — the disk write must not stall the step loop (the reference's
        driver-side save had the same property via Spark async jobs). With
        backend="orbax" the write goes through orbax's AsyncCheckpointer
        instead. At most one write is in flight either way.

        ``neval_next`` is the first iteration a resumed run should execute;
        trigger-path saves default it from the loop's pre/post-increment
        convention (in-loop triggers fire with ``state["neval"]`` = the
        just-completed iteration; epoch-boundary and preemption saves see the
        counter already advanced). The payload carries full resume state —
        RNG snapshot, feed position, epoch order — so ``resume="auto"``
        restarts mid-epoch bitwise-identically; the bytes go through
        ``utils/file.py`` (CRC32 footer, fsync-before-rename).

        ``ckpt/stall_ms`` records how long the TRAINING thread was blocked
        here — snapshot-only when async (``BIGDL_CKPT_ASYNC``, default on),
        snapshot+write+fsync when sync."""
        os.makedirs(self.checkpoint_path, exist_ok=True)
        t0 = time.perf_counter()
        try:
            if self.checkpoint_backend == "orbax":
                self._save_checkpoint_orbax(params, mstate, ostate, state)
            elif self.checkpoint_backend == "elastic":
                self._save_checkpoint_elastic(params, mstate, ostate, state,
                                              neval_next)
            else:
                self._save_checkpoint_pickle(params, mstate, ostate, state,
                                             neval_next)
        finally:
            obs_registry.registry.histogram("ckpt/stall_ms").observe(
                (time.perf_counter() - t0) * 1e3)

    @staticmethod
    def _ckpt_async() -> bool:
        return os.environ.get("BIGDL_CKPT_ASYNC", "1") != "0"

    def _save_checkpoint_pickle(self, params, mstate, ostate, state,
                                neval_next: Optional[int] = None) -> None:
        if neval_next is None:
            neval_next = state["neval"] + \
                (0 if state.get("epoch_finished") else 1)
        payload = {
            "params": jax.device_get(params),
            "mstate": jax.device_get(mstate),
            "ostate": jax.device_get(ostate),
            "state": dict(state),
            "resume": self._resume_info(state, neval_next),
        }
        sched = getattr(self.optim_method, "learningrate_schedule", None)
        if getattr(sched, "stateful", False):
            payload["sched_state"] = sched.state_dict()
        path = self._ckpt_file(state)
        self._join_checkpoint_writer()

        def _write():
            try:
                # scripted write failures (fault suite): "torn" leaves a
                # truncated file at the FINAL path (simulating bit rot / a
                # pre-hardening writer — exercises quarantine-on-load),
                # "error" fails the write (surfaced at the next join),
                # "kill" SIGKILLs mid-write with only the tmp file dirty
                # (the atomic-rename protocol must keep the dir loadable)
                action = faults.check_fault(faults.SITE_CKPT_WRITE)
                data = ckpt_file.dumps(payload)
                if action == "torn":
                    with open(path, "wb") as f:
                        f.write(data[:max(len(ckpt_file.MAGIC) + 1,
                                          len(data) // 2)])
                    logger.warning("fault plan: torn checkpoint at %s", path)
                    return
                if action == "error":
                    raise faults.FaultError(
                        "injected checkpoint write failure")
                if action == "kill":
                    tmp = path + ".tmp"
                    with open(tmp, "wb") as f:
                        f.write(data[:len(data) // 2])
                        f.flush()
                        os.fsync(f.fileno())
                        import signal
                        os.kill(os.getpid(), signal.SIGKILL)
                with trace.span("ckpt/write", {"path": path}):
                    ckpt_file.save_bytes(data, path)
                obs_registry.registry.counter("ckpt/bytes").inc(len(data))
                self._prune_old_checkpoints()
                # payload["state"] is the eager copy — the live ``state``
                # dict may have advanced under the async writer
                self._publish_to_registry(int(payload["state"]["neval"]),
                                          params=payload["params"])
                logger.info("checkpoint written: %s", path)
            except BaseException as e:  # surfaced at the next join
                self._ckpt_error = e

        import threading
        t = threading.Thread(target=_write, name="bigdl-ckpt-writer", daemon=False)
        t.start()
        self._ckpt_thread = t
        if not self._ckpt_async():
            self._join_checkpoint_writer()

    def _save_checkpoint_elastic(self, params, mstate, ostate, state,
                                 neval_next: Optional[int] = None) -> None:
        """Sharded async save: the ONLY training-thread work is the d2h
        snapshot of this process's addressable blocks; serialization + fsync
        + the manifest-coverage rendezvous overlap the next fused window on
        the writer thread. The join at the top is the hard barrier — at most
        one write in flight, and the next checkpoint trigger (or an emergency
        checkpoint) waits for the previous write to land."""
        from bigdl_tpu.utils import elastic_ckpt

        if neval_next is None:
            neval_next = state["neval"] + \
                (0 if state.get("epoch_finished") else 1)
        self._join_checkpoint_writer()
        faults.fault_point(faults.SITE_CKPT_D2H)
        pidx, pcount = jax.process_index(), jax.process_count()
        with trace.span("ckpt/d2h"):
            skeleton, leaves, blocks = elastic_ckpt.snapshot_tree(
                {"params": params, "mstate": mstate, "ostate": ostate},
                process_index=pidx)
        meta = {"state": dict(state),
                "resume": self._resume_info(state, neval_next)}
        sched = getattr(self.optim_method, "learningrate_schedule", None)
        if getattr(sched, "stateful", False):
            meta["sched_state"] = sched.state_dict()
        minfo = elastic_ckpt.mesh_info(
            Engine.mesh() if Engine.is_initialized() else None, pcount)
        # captured eagerly: the async writer runs behind the next window,
        # by which time the training thread has advanced state["neval"]
        ckpt_version = int(state["neval"])
        dirpath = os.path.join(
            self.checkpoint_path,
            elastic_ckpt.version_dirname(ckpt_version))
        sync_timeout = float(
            os.environ.get("BIGDL_CKPT_SYNC_TIMEOUT", "60"))

        def _write():
            try:
                action = faults.check_fault(faults.SITE_CKPT_ASYNC)
                if action == "stall":
                    time.sleep(float(
                        os.environ.get("BIGDL_FAULT_STALL_S", "2")))
                elif action == "error":
                    raise faults.FaultError(
                        "injected elastic checkpoint write failure")
                t1 = time.perf_counter()
                with trace.span("ckpt/elastic_write", {"dir": dirpath}):
                    nbytes = elastic_ckpt.write_shard(dirpath, pidx, blocks)
                    if action == "torn":
                        # crash window between snapshot and commit: shards
                        # are durable but the manifest never lands — the
                        # version must stay invisible to every loader
                        logger.warning(
                            "fault plan: elastic manifest withheld at %s",
                            dirpath)
                        return
                    if pidx == 0:
                        committed = elastic_ckpt.commit_manifest(
                            dirpath, skeleton, leaves, minfo, meta,
                            timeout=sync_timeout)
                        if committed:
                            self._prune_old_checkpoints()
                            self._publish_to_registry(ckpt_version)
                reg = obs_registry.registry
                reg.histogram("ckpt/async_write_ms").observe(
                    (time.perf_counter() - t1) * 1e3)
                reg.counter("ckpt/bytes").inc(nbytes)
            except BaseException as e:  # surfaced at the next join
                self._ckpt_error = e

        import threading
        t = threading.Thread(target=_write, name="bigdl-ckpt-writer",
                             daemon=False)
        t.start()
        self._ckpt_thread = t
        if not self._ckpt_async():
            self._join_checkpoint_writer()

    def _publish_to_registry(self, version: int, params=None) -> None:
        """Serving-lifecycle handoff, on the checkpoint WRITER thread: hand
        the durable version's params to the model registry as a promotion
        candidate. Registry trouble is logged and dropped — it must never
        set ``_ckpt_error`` or otherwise reach the training thread (the
        gate quarantines candidates; the trainer just keeps publishing)."""
        reg = self.model_registry
        if reg is None:
            return
        try:
            if params is None:
                # elastic: re-assemble the manifest-committed version from
                # disk — registers exactly what a resume would load
                reg.register_from_elastic(
                    self.checkpoint_path, version,
                    meta={"source": "elastic"})
            elif version not in reg.versions():
                reg.publish(params, version=version,
                            meta={"source": self.checkpoint_backend,
                                  "neval": version})
        except Exception as e:  # noqa: BLE001 — never into the trainer
            logger.warning("model registry publication failed (v%s): %s",
                           version, e)

    def _prune_old_checkpoints(self) -> None:
        """Keep-last-N retention (``BIGDL_CKPT_KEEP``) for versioned
        checkpoints; 0 keeps everything. Runs on the writer thread after a
        successful write, so the newest version is always on disk before any
        older one is removed. Quarantined ``*.corrupt`` entries are pruned
        with their version. Elastic versions only count once COMPLETE
        (manifest committed): a manifest-less directory is another process's
        in-flight write — counting it would shrink the real retention window,
        deleting it would tear a checkpoint mid-commit."""
        keep = self.ckpt_keep
        if self.checkpoint_backend == "elastic":
            if keep <= 0 and self.overwrite_checkpoint:
                keep = 1  # rolling semantics: latest complete version only
            if keep <= 0:
                return
            from bigdl_tpu.utils import elastic_ckpt
            complete = elastic_ckpt.complete_versions(self.checkpoint_path)
            for v in complete[:-keep]:
                elastic_ckpt.remove_version(
                    self.checkpoint_path, elastic_ckpt.version_dirname(v))
            return
        if keep <= 0 or self.overwrite_checkpoint:
            return
        versioned = sorted(
            (p for p in os.listdir(self.checkpoint_path)
             if _CKPT_RE.match(p) and _ckpt_version(p) >= 0),
            key=_ckpt_version)
        for name in versioned[:-keep]:
            full = os.path.join(self.checkpoint_path, name)
            for victim in (full, full + ".corrupt"):
                try:
                    os.remove(victim)
                except OSError:
                    pass

    def _save_checkpoint_orbax(self, params, mstate, ostate, state) -> None:
        import json

        import orbax.checkpoint as ocp

        ckptr = getattr(self, "_orbax_ckptr", None)
        if ckptr is None:
            ckptr = self._orbax_ckptr = ocp.AsyncCheckpointer(
                ocp.StandardCheckpointHandler())
        # ALWAYS a fresh step-tagged dir — overwrite mode must not save over
        # the only committed checkpoint (force=True deletes it before the new
        # write is durable); rolling semantics happen as cleanup AFTER the next
        # commit instead (_join_checkpoint_writer)
        d = os.path.abspath(
            os.path.join(self.checkpoint_path, f"ckpt_orbax.{state['neval']}"))
        self._join_checkpoint_writer()  # one write in flight; commits its meta
        meta = {"state": dict(state)}
        sched = getattr(self.optim_method, "learningrate_schedule", None)
        if getattr(sched, "stateful", False):
            meta["sched_state"] = sched.state_dict()
        payload = {"params": params, "mstate": mstate, "ostate": ostate}
        ckptr.save(d, args=ocp.args.StandardSave(payload), force=True)
        # `.meta.json` is the COMMIT MARKER: written by the next join, only
        # after wait_until_finished confirms the array save is durable — a
        # crash mid-save leaves a dir without meta, which the loader skips
        self._orbax_pending_meta = (d, meta)
        logger.info("orbax checkpoint saving: %s", d)

    def _orbax_prune_older(self, keep_dir: str) -> None:
        """Rolling (over_write_checkpoint) semantics: once a new checkpoint is
        COMMITTED, older ones are pruned — meta marker first, so a crash
        mid-prune never leaves a marker pointing at a removed dir."""
        import shutil
        keep = os.path.basename(keep_dir)
        for p in os.listdir(self.checkpoint_path):
            if not p.startswith("ckpt_orbax") or p.endswith(".meta.json") \
                    or p == keep:
                continue
            full = os.path.join(self.checkpoint_path, p)
            try:
                if os.path.exists(full + ".meta.json"):
                    os.remove(full + ".meta.json")
                shutil.rmtree(full, ignore_errors=True)
            except OSError:
                logger.warning("failed to prune old checkpoint %s", full)

    def _load_latest_checkpoint_orbax(self) -> bool:
        import json

        import orbax.checkpoint as ocp

        # only COMMITTED checkpoints (meta marker present) are candidates —
        # crash-interrupted saves (orbax tmp dirs, array dirs without meta)
        # must not shadow older valid ones
        cand = sorted(
            (p for p in os.listdir(self.checkpoint_path)
             if p.startswith("ckpt_orbax") and not p.endswith(".meta.json")
             and "tmp" not in p
             and os.path.exists(os.path.join(self.checkpoint_path,
                                             p + ".meta.json"))),
            key=lambda p: os.path.getmtime(os.path.join(self.checkpoint_path, p)))
        if not cand:
            return False
        d = os.path.abspath(os.path.join(self.checkpoint_path, cand[-1]))
        ckptr = ocp.StandardCheckpointer()
        payload = ckptr.restore(d)
        with open(d + ".meta.json") as f:
            meta = json.load(f)
        self.model.set_params(payload["params"])
        self.model.set_state(payload["mstate"])
        self._resume_ostate = payload["ostate"]
        self.state = meta["state"]
        sched = getattr(self.optim_method, "learningrate_schedule", None)
        if getattr(sched, "stateful", False) and "sched_state" in meta:
            sched.load_state_dict(meta["sched_state"])
        logger.info("resumed from orbax checkpoint %s at iter %d", d,
                    self.state.get("neval", 0))
        return True

    def _join_checkpoint_writer(self, raise_error: bool = True) -> None:
        ckptr = getattr(self, "_orbax_ckptr", None)
        if ckptr is not None:
            import json
            pending = getattr(self, "_orbax_pending_meta", None)
            self._orbax_pending_meta = None
            try:
                ckptr.wait_until_finished()
            except Exception as e:
                # same contract as the pickle path: a failed background write
                # surfaces here (or logs, when the retry loop is probing) and
                # never gets a commit marker
                if raise_error:
                    raise RuntimeError(
                        "background orbax checkpoint write failed") from e
                logger.error("background orbax checkpoint write failed: %r", e)
            else:
                if pending is not None:
                    d, meta = pending
                    tmp = d + ".meta.json.tmp"
                    with open(tmp, "w") as f:
                        json.dump(meta, f)
                    os.replace(tmp, d + ".meta.json")
                    if self.overwrite_checkpoint:
                        self._orbax_prune_older(d)
        t = getattr(self, "_ckpt_thread", None)
        if t is not None:
            t.join()
            self._ckpt_thread = None
        err = getattr(self, "_ckpt_error", None)
        if err is not None:
            # a failed write must not read as a durable checkpoint (the retry
            # loop would silently resume from a stale file)
            self._ckpt_error = None
            if raise_error:
                raise RuntimeError("background checkpoint write failed") from err
            logger.error("background checkpoint write failed: %r", err)

    def _load_latest_checkpoint(self) -> None:
        """Restore the newest LOADABLE checkpoint. Version selection is
        numeric (``checkpoint.9.pkl`` < ``checkpoint.10.pkl`` — an mtime or
        lexicographic sort gets this wrong the moment neval crosses a digit
        boundary or a file is touched); a candidate that fails its CRC /
        truncation check is renamed aside as ``<name>.corrupt`` (quarantined
        for postmortem, never re-tried) and the previous version is used
        instead. Payloads carrying resume info re-arm the feed/RNG for
        bitwise mid-epoch continuation."""
        self._join_checkpoint_writer()  # in-flight write must land before reading
        if self.checkpoint_backend == "orbax":
            if self._load_latest_checkpoint_orbax():
                return
            raise RuntimeError(
                f"no orbax checkpoint found under {self.checkpoint_path}")
        if self.checkpoint_backend == "elastic":
            self._load_latest_checkpoint_elastic()
            return
        cand = sorted(
            (p for p in os.listdir(self.checkpoint_path)
             if _ckpt_version(p) is not None),
            key=_ckpt_version)
        if not cand:
            raise RuntimeError(f"no checkpoint found under {self.checkpoint_path}")
        payload = name = None
        while cand:
            name = cand.pop()  # newest remaining version
            full = os.path.join(self.checkpoint_path, name)
            try:
                payload = ckpt_file.load(full)
                break
            except CheckpointCorruptError as e:
                quarantined = full + ".corrupt"
                try:
                    os.replace(full, quarantined)
                except OSError:
                    quarantined = "<unremovable>"
                events.record("ckpt_quarantined", path=full, error=str(e))
                logger.error(
                    "corrupt checkpoint %s quarantined as %s (%s); falling "
                    "back to the previous version", full, quarantined, e)
        if payload is None:
            raise RuntimeError(
                f"no loadable checkpoint under {self.checkpoint_path} "
                f"(every candidate failed its integrity check and was "
                f"quarantined)")
        self.model.set_params(payload["params"])
        self.model.set_state(payload["mstate"])
        self._resume_ostate = payload["ostate"]
        self.state = payload["state"]
        sched = getattr(self.optim_method, "learningrate_schedule", None)
        if getattr(sched, "stateful", False) and "sched_state" in payload:
            sched.load_state_dict(payload["sched_state"])
        if payload.get("resume") is not None:
            self._apply_resume_info(payload["resume"])
        logger.info("resumed from checkpoint %s at iter %d", name,
                    self.state.get("neval", 0))

    def _load_latest_checkpoint_elastic(self) -> None:
        """Elastic resume: (1) cross-process AGREEMENT on which version to
        restore (quorum of newest-complete claims, min wins — every host
        resumes from the same version even on NFS-style shared dirs); (2)
        partial version dirs (interrupted writers, dead peers) quarantined
        ``*.corrupt`` with a ``ckpt_fallback`` event; (3) leaves assembled
        from shard files — bitwise what was saved; (4) if the topology
        changed since the save, leaves are re-placed under the CURRENT mesh's
        rules (``BIGDL_ELASTIC_RESUME=0`` makes a topology mismatch a hard
        error instead) and an ``elastic_resume`` event records the move."""
        from bigdl_tpu.utils import elastic_ckpt

        path = self.checkpoint_path
        pidx, pcount = jax.process_index(), jax.process_count()
        timeout = float(os.environ.get("BIGDL_CKPT_SYNC_TIMEOUT", "60"))
        agreed = elastic_ckpt.agree_version(path, pidx, pcount,
                                            timeout=timeout)
        if agreed is None:
            raise RuntimeError(
                f"no elastic checkpoint found under {path} (no complete "
                f"version visible to every process)")
        for dirname in elastic_ckpt.partial_versions(path):
            full = os.path.join(path, dirname)
            try:
                q = elastic_ckpt.quarantine(path, dirname)
            except OSError:
                q = "<unremovable>"
            events.record("ckpt_fallback", path=full,
                          reason="partial version (no manifest)")
            logger.error(
                "partial elastic checkpoint %s quarantined as %s (writer "
                "died before manifest commit)", full, q)
        tree = manifest = None
        version = agreed
        for v in sorted(
                (v for v in elastic_ckpt.complete_versions(path)
                 if v <= agreed), reverse=True):
            dirpath = os.path.join(path, elastic_ckpt.version_dirname(v))
            try:
                tree, spec_tree, manifest = elastic_ckpt.assemble(dirpath)
                version = v
                break
            except CheckpointCorruptError as e:
                try:
                    q = elastic_ckpt.quarantine(
                        path, elastic_ckpt.version_dirname(v))
                except OSError:
                    q = "<unremovable>"
                events.record("ckpt_fallback", path=dirpath, reason=str(e))
                logger.error(
                    "corrupt elastic checkpoint %s quarantined as %s (%s); "
                    "falling back to the previous version", dirpath, q, e)
        if tree is None:
            raise RuntimeError(
                f"no loadable elastic checkpoint under {path} (every "
                f"candidate failed integrity/coverage checks and was "
                f"quarantined)")
        saved = manifest.get("mesh") or {}
        cur_mesh = Engine.mesh() if Engine.is_initialized() else None
        now = elastic_ckpt.mesh_info(cur_mesh, pcount)
        topo_changed = (saved.get("shape") != now.get("shape")
                        or saved.get("axes") != now.get("axes")
                        or saved.get("process_count")
                        != now.get("process_count"))
        if topo_changed:
            if os.environ.get("BIGDL_ELASTIC_RESUME", "1") == "0":
                raise RuntimeError(
                    f"elastic checkpoint {path}/elastic.{version} was saved "
                    f"on topology {saved} but the current topology is {now} "
                    f"— topology-portable resume is disabled "
                    f"(BIGDL_ELASTIC_RESUME=0)")
            events.record("elastic_resume", version=int(version),
                          saved_mesh=saved, new_mesh=now)
            logger.warning(
                "elastic resume across topologies: saved on %s, resuming on "
                "%s — leaves re-placed under the new mesh's rules",
                saved, now)
            if cur_mesh is not None:
                try:
                    tree = elastic_ckpt.place_tree(tree, spec_tree, cur_mesh)
                except Exception:
                    logger.exception(
                        "elastic re-placement failed; resuming from host "
                        "arrays (the step's in_shardings will place them)")
        meta = manifest["meta"]
        self.model.set_params(tree["params"])
        self.model.set_state(tree["mstate"])
        self._resume_ostate = tree["ostate"]
        self.state = meta["state"]
        sched = getattr(self.optim_method, "learningrate_schedule", None)
        if getattr(sched, "stateful", False) and "sched_state" in meta:
            sched.load_state_dict(meta["sched_state"])
        if meta.get("resume") is not None:
            self._apply_resume_info(meta["resume"])
        logger.info("resumed from elastic checkpoint version %d at iter %d",
                    version, self.state.get("neval", 0))


class LocalOptimizer(Optimizer):
    """Single-process training on one chip (or CPU). The reference's per-core replica
    fan-out (SURVEY.md §3.2) is deleted: XLA owns intra-chip parallelism."""
