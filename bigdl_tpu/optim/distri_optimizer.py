"""DistriOptimizer — synchronous data-parallel training over the device mesh.

Reference parity (SURVEY.md §2.3/§3.1, expected ``<dl>/optim/DistriOptimizer.scala`` —
unverified): the reference runs one Spark job per iteration — broadcast model once, cache
per-executor replicas, pull weight slices from the BlockManager, compute, publish gradient
slices, slice-owned optimizer update, publish weight slices; plus driver-side validation/
checkpoint/summary and retry-from-checkpoint.

TPU-native redesign (SURVEY.md §5.8, §7.1): the entire per-iteration protocol is replaced
by ONE jitted SPMD program over the Engine mesh:

- the mini-batch is sharded over the ``data`` axis (NamedSharding);
- params/model-state are replicated; XLA's partitioner inserts the gradient all-reduce
  over ICI (the reference's all-to-all BlockManager slice pulls);
- with ``parameter_sync="zero1"`` the optimizer slots are sharded over ``data``, so the
  update computes on slices and new params are all-gathered — the exact ZeRO-1 structure
  of ``AllReduceParameter``'s slice-owned update;
- with ``parameter_sync="fsdp"`` the PARAMETERS themselves are stored sharded over
  ``data`` as well (ZeRO-3 / fully-sharded data parallelism — beyond the reference):
  GSPMD all-gathers each weight at its use site, reduce-scatters gradients into the
  slice-owned update, and per-device parameter + slot memory drops to ~1/N;
- there is no per-iteration driver scheduling at all (the reference's biggest fixed cost).

The training *loop* (triggers, checkpoint/retry, validation, summaries) is inherited
unchanged from ``Optimizer`` — only batch placement and program shardings differ.
"""

from __future__ import annotations

import logging

import jax
import numpy as np

from bigdl_tpu.optim.optimizer import Optimizer
from bigdl_tpu.parallel.sharding import batch_sharding, replicated, zero1_state_sharding
from bigdl_tpu.utils.engine import Engine

logger = logging.getLogger("bigdl_tpu.optim")


class DistriOptimizer(Optimizer):
    _SYNC_MODES = ("allreduce", "zero1", "fsdp")

    def __init__(self, model, dataset, criterion, parameter_sync: str = "allreduce"):
        super().__init__(model, dataset, criterion)
        if parameter_sync not in self._SYNC_MODES:
            raise ValueError(f"parameter_sync must be one of {self._SYNC_MODES}")
        self.parameter_sync = parameter_sync
        self._mesh = None
        self._batch_sh = None
        self.tp_rules = None

    def _flat_update_ok(self) -> bool:
        # ZeRO-1/FSDP shard slot leaves per PARAMETER over the data axis and
        # TP shards them per rule path — a dtype-grouped flat vector has
        # neither the leaf structure nor guaranteed divisibility, so the
        # flat update only rides the replicated (allreduce) configuration.
        if self.parameter_sync != "allreduce" or self.tp_rules is not None:
            if self.flat_update:
                logger.warning(
                    "BIGDL_FLAT_UPDATE ignored: flat-param updates need "
                    "replicated optimizer slots (parameter_sync='allreduce' "
                    "without tensor parallelism); got sync=%r tp=%s",
                    self.parameter_sync, self.tp_rules is not None)
            return False
        return True

    def _sparse_embed_ok(self) -> bool:
        # The sparse wrapper's slot tree ({"dense": ..., "embed": ...}) does
        # not match the param-path layouts ZeRO-1/FSDP/TP shard slots by, so
        # sparse embedding updates ride only the replicated-slot (allreduce,
        # no-TP) configuration; tensor-parallel row-sharded tables keep the
        # dense update (GSPMD still shards its gather/scatter).
        return self.parameter_sync == "allreduce" and self.tp_rules is None

    def set_parameter_sync(self, mode: str) -> "DistriOptimizer":
        if mode not in self._SYNC_MODES:
            raise ValueError(f"parameter_sync must be one of {self._SYNC_MODES}")
        self.parameter_sync = mode
        self._sparse_plan_memo = "_unset"
        self._step_cache = None
        return self

    def set_tensor_parallel(self, rules) -> "DistriOptimizer":
        """Enable tensor parallelism: ``rules`` is a
        :class:`~bigdl_tpu.parallel.TPRules` mapping parameter paths to
        PartitionSpecs over the mesh's ``model`` axis. XLA's SPMD partitioner
        splits the matmuls and inserts the activation collectives."""
        self.tp_rules = rules
        self._sparse_plan_memo = "_unset"
        self._step_cache = None
        return self

    # ------------------------------------------------------------- compile
    def _compile_step(self):
        self._mesh = Engine.mesh()
        if Engine.DATA_AXIS not in self._mesh.axis_names:
            raise ValueError(
                f"Engine mesh {self._mesh.axis_names} has no "
                f"'{Engine.DATA_AXIS}' axis")
        self._batch_sh = batch_sharding(self._mesh, Engine.DATA_AXIS)
        repl = replicated(self._mesh)

        params = self.model.get_params()
        # shapes only — no device allocation for the throwaway state
        method = self._effective_method()
        ostate_shapes = jax.eval_shape(
            lambda p: method.init_state_trimmed(
                p, self._trainable_mask()), params)
        if self.parameter_sync == "fsdp" and self.tp_rules is not None:
            raise ValueError(
                "parameter_sync='fsdp' cannot combine with tensor "
                "parallelism yet — pick one sharding of the weights")
        if self.parameter_sync == "fsdp":
            # ZeRO-3: weights themselves live sharded over the data axis;
            # GSPMD inserts the per-use all-gathers + gradient reduce-scatter
            param_sh = zero1_state_sharding(self._mesh, params,
                                            Engine.DATA_AXIS)
        elif self.tp_rules is not None:
            param_sh = self.tp_rules.param_shardings(params, self._mesh)
        else:
            param_sh = jax.tree_util.tree_map(lambda _: repl, params)
        mstate_sh = jax.tree_util.tree_map(lambda _: repl, self.model.get_state())
        if self.tp_rules is not None:
            # TP slots always mirror the param sharding; unmatched slots get
            # ZeRO-1 data sharding or replication per the sync mode
            dp_axis = Engine.DATA_AXIS if self.parameter_sync == "zero1" else None
            ostate_sh = self.tp_rules.slot_shardings(ostate_shapes, self._mesh,
                                                     dp_axis)
        elif self.parameter_sync in ("zero1", "fsdp"):
            # slots slice-owned over data (fsdp: mirroring the sharded params)
            ostate_sh = zero1_state_sharding(self._mesh, ostate_shapes,
                                             Engine.DATA_AXIS)
        else:
            ostate_sh = jax.tree_util.tree_map(lambda _: repl, ostate_shapes)
        self._shardings = (param_sh, mstate_sh, ostate_sh)

        step = self._make_step_fn()
        out_sh = (param_sh, mstate_sh, ostate_sh, None)
        if self.check_numerics:
            step = self._wrap_checkify(step)
            out_sh = (*out_sh, None)
        return jax.jit(
            step,
            in_shardings=(param_sh, mstate_sh, ostate_sh, None,
                          self._batch_sh, self._batch_sh, None),
            out_shardings=out_sh,
            donate_argnums=(0, 1, 2),
        )

    def _compile_window(self, k: int):
        """Fused K-step scan over the mesh: the stacked super-batch keeps the
        SAME ``data`` sharding per step — the leading scan axis is unsharded
        (every device owns its batch slice of all K steps), so the fused
        program runs the identical per-step SPMD partitioning with zero extra
        collectives, and the per-step gradient all-reduce pipelines across
        scan iterations instead of across Python dispatches."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        # the step compile (always performed first) established mesh/shardings
        param_sh, mstate_sh, ostate_sh = self._shardings
        self._window_sh = NamedSharding(self._mesh, P(None, Engine.DATA_AXIS))
        window = self._make_window_fn(k)
        # losses ([K]) and stacked state metrics replicate (scalar per step)
        out_sh = (param_sh, mstate_sh, ostate_sh, None, None)
        if self.check_numerics:
            window = self._wrap_checkify_window(window)
            out_sh = (*out_sh, None)
        return jax.jit(
            window,
            in_shardings=(param_sh, mstate_sh, ostate_sh, None,
                          self._window_sh, self._window_sh, None),
            out_shardings=out_sh,
            donate_argnums=(0, 1, 2),
        )

    @staticmethod
    def _put_sharded(x, sh):
        """Place a host batch under ``sh`` without issuing collectives.

        On a multi-process mesh ``jax.device_put(np_array, sharding)`` runs a
        cross-process ``assert_equal`` — a broadcast of the whole batch — to
        check every process passed the same value. That collective is issued
        from the prefetch producer thread and can interleave with the step
        collective in a different order on each process, which deadlocks the
        gloo transport (each side services its first-enqueued collective).
        The SPMD contract already guarantees identical batches per process,
        so assemble the global array from the locally addressable shards
        instead: pure h2d, no cross-process traffic, and the per-batch
        broadcast disappears from the feed path entirely.
        """
        if sh.is_fully_addressable:
            return jax.device_put(x, sh)

        def put_leaf(leaf):
            leaf = np.asarray(leaf)
            shards = [jax.device_put(leaf[idx], d) for d, idx in
                      sh.addressable_devices_indices_map(leaf.shape).items()]
            return jax.make_array_from_single_device_arrays(
                leaf.shape, sh, shards)

        return jax.tree_util.tree_map(put_leaf, x)

    def _place_batch(self, batch):
        n_dev = int(dict(self._mesh.shape)[Engine.DATA_AXIS])
        bsz = batch.size()
        if bsz % n_dev != 0:
            raise ValueError(
                f"batch size {bsz} not divisible by data-parallel size {n_dev}")
        inp = self._put_sharded(self._feed_cast(batch.input), self._batch_sh)
        target = self._put_sharded(batch.target, self._batch_sh)
        return inp, target

    def _stack_and_cast(self, batches):
        n_dev = int(dict(self._mesh.shape)[Engine.DATA_AXIS])
        for b in batches:
            if b.size() % n_dev != 0:
                raise ValueError(
                    f"batch size {b.size()} not divisible by data-parallel "
                    f"size {n_dev}")
        return super()._stack_and_cast(batches)

    def _place_window(self, inp, target):
        return (self._put_sharded(inp, self._window_sh),
                self._put_sharded(target, self._window_sh))

    def _optimize_impl(self):
        # compile path sets mesh/shardings before the first _put_batch
        logger.info("DistriOptimizer: mesh=%s sync=%s",
                    dict(Engine.mesh().shape), self.parameter_sync)
        return super()._optimize_impl()


class ParallelOptimizer(DistriOptimizer):
    """Layer-wise parameter sync — the ``ParallelOptimizer`` analog.

    Reference parity (SURVEY.md §2.3, expected ``<dl>/optim/ParallelOptimizer.scala``
    — unverified): the upstream variant replaces ``DistriOptimizer``'s flat
    slice all-reduce with a hand-built ``DistriParameterSynchronizer`` that
    syncs each layer's gradients as soon as its backward completes, hiding
    communication behind the remaining backward compute.

    TPU-native redesign (SURVEY.md §7.1): that schedule is what the XLA SPMD
    partitioner + schedulers emit for the jitted ``DistriOptimizer`` step
    already. Gradients are a pytree with one leaf per parameter, so the
    partitioner inserts collectives on the PER-LAYER leaves — never a flat
    concatenated vector (verified against the optimized HLO in
    ``tests/test_parallel_optimizer.py``); the all-reduce combiner then
    buckets small leaves up to a byte threshold (the same bucketing trick
    DDP-style layer-wise synchronizers hand-tune), and on TPU the
    latency-hiding scheduler starts each bucket's all-reduce the moment its
    producing backward ops finish, overlapping ICI traffic with the rest of
    the backward pass. There is no hand-built synchronizer to port: the
    layer-wise variant and the flagship collapse to the SAME compiled
    program, so this class is the upstream API name bound to that program
    (kept as a distinct class so ``ParallelOptimizer``-specific toggles have
    a home if the two ever diverge).
    """
