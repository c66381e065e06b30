"""Elastic checkpointing suite (`make t1-elastic`).

The elastic format (``utils/elastic_ckpt.py``) is the durability plane for
training that must survive losing a host: every process writes only the leaf
blocks it addresses, the manifest commits LAST via atomic rename (the version
exists iff the manifest does), and resume re-places leaves under whatever mesh
is still alive. This suite pins:

- format round-trip: sharded snapshot → shard files → assemble is bitwise,
  with dedup of replicated blocks and per-leaf spec recording;
- all-or-nothing: a crash between the d2h snapshot and the manifest commit
  (``ckpt_async=torn``) leaves the directory loadable at the PREVIOUS
  version — the partial dir is quarantined with a ``ckpt_fallback`` event;
- async overlap: the training thread's stall is snapshot-only while the
  serialize+fsync runs behind the next window (``ckpt_async=stall`` makes the
  overlap deterministic), and the next trigger's hard barrier waits;
- topology-portable resume: a run checkpointed on a (2,4) data×model mesh
  resumes on a 4-device data-only mesh with bitwise-equal leaves and a loss
  trajectory equal to the uninterrupted reference;
- keep-last-N retention counts only COMPLETE versions (a manifest-less dir is
  another writer's in-flight checkpoint);
- cross-process version agreement (two writers racing on an NFS-style shared
  dir) and the Engine distributed-client latch;
- the host-loss drill: a real 2-process ``jax.distributed`` run, one worker
  SIGKILLed mid-epoch by the ``host_down`` fault site, the survivor re-execs
  onto the shrunk topology and resumes from the last durable version.
"""

import json
import os
import shutil
import socket
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

from bigdl_tpu import Engine, nn
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.dataset.sample import Sample, SampleToMiniBatch
from bigdl_tpu.obs.registry import registry as obs_registry
from bigdl_tpu.optim import LocalOptimizer, SGD, Trigger
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.parallel.sharding import adapt_spec, spec_to_tuple
from bigdl_tpu.utils import elastic_ckpt, faults
from bigdl_tpu.utils import file as ckpt_file
from bigdl_tpu.utils.elastic_ckpt import ElasticCheckpointError
from bigdl_tpu.utils.random_generator import RandomGenerator
from bigdl_tpu.utils.robustness import events

pytestmark = pytest.mark.elastic

_WORKER = os.path.join(os.path.dirname(__file__), "multihost_worker.py")


def _params_equal(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _zero1_opt(ckpt_dir=None, ckpt_every=2, n_iter=4):
    """The multihost worker's model/data, single-process: 64 samples,
    batch 16 (4 iters/epoch), zero1 slot sharding over the data axis."""
    RandomGenerator.set_seed(5)
    rng = np.random.default_rng(0)
    samples = [Sample(rng.normal(size=(8,)).astype(np.float32),
                      np.int32(rng.integers(0, 3))) for _ in range(64)]
    data = DataSet.array(samples, distributed=True) >> SampleToMiniBatch(16)
    model = nn.Sequential().add(nn.Linear(8, 16)).add(nn.ReLU()) \
        .add(nn.Linear(16, 3)).add(nn.LogSoftMax())
    opt = (DistriOptimizer(model, data, nn.ClassNLLCriterion(),
                           parameter_sync="zero1")
           .set_optim_method(SGD(learningrate=0.1, momentum=0.9,
                                 dampening=0.0))
           .set_end_when(Trigger.max_iteration(n_iter)))
    if ckpt_dir is not None:
        opt.set_checkpoint(str(ckpt_dir),
                           Trigger.several_iteration(ckpt_every),
                           backend="elastic")
    return opt


def _local_opt(ckpt_dir, ckpt_every=1, n_iter=3):
    RandomGenerator.set_seed(3)
    rng = np.random.default_rng(0)
    samples = [Sample(rng.normal(size=(8,)).astype(np.float32),
                      np.int32(rng.integers(0, 3))) for _ in range(64)]
    data = DataSet.array(samples) >> SampleToMiniBatch(16)
    model = nn.Sequential().add(nn.Linear(8, 3)).add(nn.LogSoftMax())
    opt = (LocalOptimizer(model, data, nn.ClassNLLCriterion())
           .set_optim_method(SGD(learningrate=0.1))
           .set_end_when(Trigger.max_iteration(n_iter)))
    opt.set_checkpoint(str(ckpt_dir), Trigger.several_iteration(ckpt_every),
                       backend="elastic")
    return opt


# ------------------------------------------------------------ format layer
class TestElasticFormat:
    def _mesh_tree(self):
        """A pytree with every placement class the optimizer produces:
        2-D sharded, row-sharded (PR 13 embedding style), replicated, and a
        non-array leaf riding inline."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        Engine.init(backend="cpu", seed=1, mesh_shape=(2, 4),
                    mesh_axes=("data", "model"))
        mesh = Engine.mesh()
        rng = np.random.default_rng(7)

        def put(x, *spec):
            return jax.device_put(x, NamedSharding(mesh, P(*spec)))

        tree = {
            "w": put(rng.normal(size=(8, 4)).astype(np.float32), "model"),
            "rows": put(rng.normal(size=(16, 6)).astype(np.float32), "data"),
            "bias": put(rng.normal(size=(3,)).astype(np.float32)),
            "step": 7,
        }
        return mesh, tree

    def test_snapshot_roundtrip_bitwise(self, tmp_path):
        mesh, tree = self._mesh_tree()
        skel, leaves, blocks = elastic_ckpt.snapshot_tree(tree,
                                                          process_index=0)
        # replicated leaves dedup to ONE block; sharded leaves split
        wkey = next(k for k in leaves if "'w'" in k)
        bkey = next(k for k in leaves if "'bias'" in k)
        assert len(blocks[bkey]) == 1
        assert len(blocks[wkey]) == 4  # model axis = 4 slices
        assert leaves[wkey]["spec"][0] == "model"

        d = str(tmp_path / "elastic.3")
        os.makedirs(d)
        nbytes = elastic_ckpt.write_shard(d, 0, blocks)
        assert nbytes > 0
        # the version does not EXIST until the manifest commits
        assert elastic_ckpt.complete_versions(str(tmp_path)) == []
        assert elastic_ckpt.partial_versions(str(tmp_path)) == ["elastic.3"]
        assert elastic_ckpt.commit_manifest(
            d, skel, leaves, elastic_ckpt.mesh_info(mesh), {"neval": 3},
            timeout=5.0)
        assert elastic_ckpt.complete_versions(str(tmp_path)) == [3]

        out, spec_tree, manifest = elastic_ckpt.assemble(d)
        assert out["step"] == 7
        assert _params_equal({k: tree[k] for k in ("w", "rows", "bias")},
                             {k: out[k] for k in ("w", "rows", "bias")})
        assert manifest["mesh"]["shape"] == (2, 4)
        # re-place on the SAME mesh round-trips the placement too
        placed = elastic_ckpt.place_tree(out, spec_tree, mesh)
        assert _params_equal(placed["w"], tree["w"])
        assert spec_to_tuple(placed["w"].sharding) == ("model",)

    def test_incomplete_coverage_never_commits(self, tmp_path):
        """A shard set that does not cover every leaf (a dead peer's blocks
        missing) must time out WITHOUT committing — the version stays
        invisible."""
        mesh, tree = self._mesh_tree()
        skel, leaves, blocks = elastic_ckpt.snapshot_tree(tree)
        wkey = next(k for k in leaves if "'w'" in k)
        half = dict(blocks)
        half[wkey] = dict(list(blocks[wkey].items())[:2])  # drop 2 of 4 slices
        d = str(tmp_path / "elastic.1")
        os.makedirs(d)
        elastic_ckpt.write_shard(d, 0, half)
        assert not elastic_ckpt.commit_manifest(
            d, skel, leaves, None, {}, timeout=0.3)
        assert not os.path.exists(os.path.join(d, elastic_ckpt.MANIFEST))
        # ... and a loader that finds a manifest listing missing coverage
        # (manufactured here) refuses with the elastic error, not garbage
        ckpt_file.save({"format": 1, "skeleton": skel, "leaves": leaves,
                        "mesh": None, "meta": {}, "shards": ["shard-0.data"]},
                       os.path.join(d, elastic_ckpt.MANIFEST))
        with pytest.raises(ElasticCheckpointError):
            elastic_ckpt.assemble(d)

    def test_quarantine_and_listing(self, tmp_path):
        d = tmp_path / "elastic.5"
        d.mkdir()
        (d / "shard-0.data").write_bytes(b"torn")
        target = elastic_ckpt.quarantine(str(tmp_path), "elastic.5")
        assert target.endswith("elastic.5.corrupt")
        # quarantined dirs are invisible to every listing
        assert elastic_ckpt.list_versions(str(tmp_path)) == {}

    def test_adapt_spec_degrades_to_replication(self):
        Engine.init(backend="cpu", seed=1, core_number=4)
        mesh = Engine.mesh()  # data-only mesh: the "model" axis is GONE
        assert adapt_spec(("model", None), mesh, (8, 4)) == \
            jax.sharding.PartitionSpec()
        assert adapt_spec(("data",), mesh, (16,)) == \
            jax.sharding.PartitionSpec("data")
        # non-divisible dims degrade too (a 6-row leaf on a 4-way axis)
        assert adapt_spec(("data",), mesh, (6,)) == \
            jax.sharding.PartitionSpec()

    def test_agree_version_two_writers_race(self, tmp_path):
        """Two processes racing on a shared dir converge on the same version:
        each publishes its newest-complete claim, the min wins."""
        for v in (3, 5):
            d = tmp_path / f"elastic.{v}"
            d.mkdir()
            (d / elastic_ckpt.MANIFEST).write_bytes(b"x")
        (tmp_path / "elastic.7").mkdir()  # in-flight: no manifest
        out = {}

        def run(pid):
            out[pid] = elastic_ckpt.agree_version(str(tmp_path), pid, 2,
                                                  timeout=10.0)

        ts = [threading.Thread(target=run, args=(p,)) for p in (0, 1)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert out == {0: 5, 1: 5}
        # claims are load-time-only: cleaned up on exit
        assert not [n for n in os.listdir(tmp_path)
                    if n.startswith("resume-claim.")]

    def test_agree_version_timeout_uses_local_view(self, tmp_path):
        """A quorum that never forms (dead peer) times out to the local
        newest — the shrunk-fleet resume case."""
        d = tmp_path / "elastic.4"
        d.mkdir()
        (d / elastic_ckpt.MANIFEST).write_bytes(b"x")
        assert elastic_ckpt.agree_version(str(tmp_path), 0, 2,
                                          timeout=0.3) == 4


# ------------------------------------------------------- optimizer e2e path
class TestElasticOptimizer:
    def test_topology_portable_resume_trajectory(self, tmp_path):
        """The core elastic contract: checkpoint on a (2,4) data×model mesh,
        resume on a 4-device data-only mesh; restored leaves bitwise-equal,
        continued loss trajectory equal to the uninterrupted reference."""
        ck = str(tmp_path / "ck")
        Engine.init(backend="cpu", seed=5, mesh_shape=(2, 4),
                    mesh_axes=("data", "model"))
        opt = _zero1_opt(ck, ckpt_every=2, n_iter=4)
        opt.optimize()
        opt._join_checkpoint_writer()
        assert elastic_ckpt.complete_versions(ck) == [2, 4]
        saved_tree, _, _ = elastic_ckpt.assemble(
            os.path.join(ck, "elastic.4"))
        # version 4's leaves are bitwise the params after iteration 4
        assert _params_equal(saved_tree["params"], opt.model.get_params())

        # reference: same topology, resume="auto" → continue 5..8
        Engine.reset()
        Engine.init(backend="cpu", seed=5, mesh_shape=(2, 4),
                    mesh_axes=("data", "model"))
        snap = events.snapshot()
        ref = _zero1_opt(ck, ckpt_every=100, n_iter=8)
        ref.optimize(resume="auto")
        ref_loss = float(ref.state["loss"])
        d = events.deltas(snap)
        assert d.get("resume") == 1
        assert not d.get("elastic_resume")  # same mesh: no re-placement

        # elastic: resume the SAME state on a 4-device data-only mesh
        Engine.reset()
        Engine.init(backend="cpu", seed=5, core_number=4)
        snap = events.snapshot()
        new = _zero1_opt(ck, ckpt_every=100, n_iter=8)
        new._load_latest_checkpoint()  # explicit: bitwise check pre-training
        assert _params_equal(new.model.get_params(), saved_tree["params"])
        new.optimize(resume="auto")
        d = events.deltas(snap)
        assert d.get("elastic_resume", 0) >= 1
        assert float(new.state["loss"]) == ref_loss
        assert new.state["neval"] >= 8

    def test_topology_mismatch_hard_error_when_disabled(self, tmp_path,
                                                        monkeypatch):
        ck = str(tmp_path / "ck")
        Engine.init(backend="cpu", seed=5, mesh_shape=(2, 4),
                    mesh_axes=("data", "model"))
        opt = _zero1_opt(ck, ckpt_every=2, n_iter=2)
        opt.optimize()
        opt._join_checkpoint_writer()
        Engine.reset()
        Engine.init(backend="cpu", seed=5, core_number=4)
        monkeypatch.setenv("BIGDL_ELASTIC_RESUME", "0")
        new = _zero1_opt(ck, ckpt_every=100, n_iter=4)
        with pytest.raises(RuntimeError, match="topology"):
            new._load_latest_checkpoint()

    def test_async_overlap_and_hard_barrier(self, tmp_path, monkeypatch):
        """``ckpt_async@1=stall`` pins the overlap deterministically: the
        training thread's stall for save #1 is snapshot-only (far below the
        writer's stall), while save #2's hard barrier waits the stall out —
        both visible in the ``ckpt/stall_ms`` histogram."""
        monkeypatch.setenv("BIGDL_FAULT_STALL_S", "1.0")
        Engine.init(backend="cpu", seed=3)
        opt = _local_opt(tmp_path / "ck", ckpt_every=1, n_iter=3)
        with faults.inject_faults("ckpt_async@1=stall") as plan:
            opt.optimize()
            opt._join_checkpoint_writer()
        assert plan.unfired() == []
        hist = obs_registry.snapshot()["histograms"]
        stall = hist["ckpt/stall_ms"]
        assert stall["count"] == 3
        assert stall["min"] < 400    # save #1 returned while the writer slept
        assert stall["max"] >= 400   # save #2 hit the hard barrier
        assert hist["ckpt/async_write_ms"]["count"] == 3
        assert obs_registry.snapshot()["counters"]["ckpt/bytes"] > 0
        assert elastic_ckpt.complete_versions(str(tmp_path / "ck")) == \
            [1, 2, 3]

    def test_sync_mode_blocks_training_thread(self, tmp_path, monkeypatch):
        """BIGDL_CKPT_ASYNC=0 (the synchronous write): the training
        thread eats the whole write, stall ≥ the injected writer stall."""
        monkeypatch.setenv("BIGDL_FAULT_STALL_S", "0.5")
        monkeypatch.setenv("BIGDL_CKPT_ASYNC", "0")
        Engine.init(backend="cpu", seed=3)
        opt = _local_opt(tmp_path / "ck", ckpt_every=1, n_iter=2)
        with faults.inject_faults("ckpt_async@1=stall") as plan:
            opt.optimize()
        assert plan.unfired() == []
        stall = obs_registry.snapshot()["histograms"]["ckpt/stall_ms"]
        assert stall["max"] >= 500

    def test_d2h_fault_site_fires_on_training_thread(self, tmp_path,
                                                     monkeypatch):
        monkeypatch.setenv("BIGDL_FAILURE_RETRY_INTERVAL", "0")
        Engine.init(backend="cpu", seed=3)
        opt = _local_opt(tmp_path / "ck", ckpt_every=2, n_iter=4)
        # first save: d2h faults before anything durable exists → no
        # recovery point → the error surfaces (not silently retried)
        with faults.inject_faults("ckpt_d2h@1=error") as plan:
            with pytest.raises(faults.FaultError):
                opt.optimize()
        assert plan.unfired() == []

    def test_torn_manifest_is_all_or_nothing(self, tmp_path, monkeypatch):
        """Crash between the d2h snapshot and the manifest commit
        (``ckpt_async=torn``): shards land, the manifest never does. The
        directory must stay loadable at the PREVIOUS version; the partial
        dir is quarantined with a ``ckpt_fallback`` event."""
        monkeypatch.setenv("BIGDL_FAILURE_RETRY_INTERVAL", "0")
        ck = str(tmp_path / "ck")
        Engine.init(backend="cpu", seed=3)
        opt = _local_opt(ck, ckpt_every=2, n_iter=4)
        with faults.inject_faults("ckpt_async@2=torn") as plan:
            opt.optimize()
            opt._join_checkpoint_writer()
        assert plan.unfired() == []
        assert elastic_ckpt.complete_versions(ck) == [2]
        assert elastic_ckpt.partial_versions(ck) == ["elastic.4"]

        snap = events.snapshot()
        new = _local_opt(ck, ckpt_every=100, n_iter=4)
        new._load_latest_checkpoint()
        assert events.deltas(snap).get("ckpt_fallback", 0) >= 1
        assert new.state["neval"] == 3  # resumed AFTER iteration 2
        assert elastic_ckpt.partial_versions(ck) == []
        assert any(n.startswith("elastic.4.corrupt")
                   for n in os.listdir(ck))

    def test_keep_last_n_skips_inflight_versions(self, tmp_path, monkeypatch):
        """BIGDL_CKPT_KEEP must neither count nor delete manifest-less dirs:
        they are another process's in-flight writes (regression for the
        satellite — counting them shrinks the retention window, deleting
        them tears a checkpoint mid-commit)."""
        monkeypatch.setenv("BIGDL_CKPT_KEEP", "1")
        ck = tmp_path / "ck"
        inflight = ck / "elastic.99"
        inflight.mkdir(parents=True)
        (inflight / "shard-1.data").write_bytes(b"in-flight peer write")
        Engine.init(backend="cpu", seed=3)
        opt = _local_opt(ck, ckpt_every=2, n_iter=4)
        opt.optimize()
        opt._join_checkpoint_writer()
        # keep=1: version 2 pruned, version 4 kept; 99 (no manifest) is NOT
        # "newest" — untouched, not counted, not deleted
        assert elastic_ckpt.complete_versions(str(ck)) == [4]
        assert (inflight / "shard-1.data").exists()


# ------------------------------------------------------ engine latch
class TestEngineDistributedLatch:
    def test_reset_clears_latch_and_reinit_guard(self):
        from bigdl_tpu.utils import engine as engine_mod

        st = engine_mod._STATE
        try:
            st.distributed_initialized = True
            st.distributed_client_live = True
            Engine.reset()
            # reset clears the INIT latch (a fresh init may proceed) but the
            # old client object is still live in-process...
            assert st.distributed_initialized is False
            assert st.distributed_client_live is True
            # ...so re-init with a coordinator must refuse loudly instead of
            # crashing deep inside jax.distributed
            with pytest.raises(RuntimeError, match="still live"):
                Engine.init(backend="cpu", seed=1,
                            coordinator_address="localhost:1",
                            node_number=2, process_id=0)
            Engine.reset()
            # shutdown_distributed releases the client (jax.distributed
            # .shutdown errors on a never-initialized client are absorbed —
            # the latch still clears, which is the contract under test)
            Engine.shutdown_distributed(timeout=10)
            assert st.distributed_client_live is False
            assert st.distributed_initialized is False
        finally:
            st.distributed_initialized = False
            st.distributed_client_live = False
            Engine.reset()


# ------------------------------------------------------ host-loss drill
class TestHostLossDrill:
    def test_kill_one_host_mid_epoch_survivor_resumes(self, tmp_path):
        """The full drill: 2-process jax.distributed zero1 run with elastic
        checkpoints on a shared dir; the ``host_down`` fault site SIGKILLs
        process 1 mid-epoch; process 0's peer watcher re-execs it onto the
        shrunk (single-host, 4-device) topology where it resumes from the
        last durable version. A second, fresh resume from the same version
        must reproduce the survivor's continued trajectory exactly."""
        port = self._free_port()
        ck = str(tmp_path / "shared-ck")
        base_env = dict(os.environ)
        base_env.pop("XLA_FLAGS", None)
        base_env.update({
            "BIGDL_MH_MODE": "drill", "BIGDL_MH_CKPT_DIR": ck,
            "BIGDL_MH_ITERS": "8", "BIGDL_CKPT_SYNC_TIMEOUT": "5",
            "BIGDL_FAILURE_RETRY_TIMES": "0",
            "BIGDL_FAILURE_RETRY_INTERVAL": "0",
        })
        out0 = str(tmp_path / "worker0.json")
        out1 = str(tmp_path / "worker1.json")
        env1 = dict(base_env)
        env1["BIGDL_FAULT_PLAN"] = "host_down@3"  # SIGKILL mid-epoch
        p1 = subprocess.Popen(
            [sys.executable, _WORKER, str(port), "1", out1],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env1)
        env0 = dict(base_env)
        env0["BIGDL_MH_PEER_PID"] = str(p1.pid)
        p0 = subprocess.Popen(
            [sys.executable, _WORKER, str(port), "0", out0],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env0)
        try:
            s1, _ = p1.communicate(timeout=240)
            s0, _ = p0.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            p0.kill()
            p1.kill()
            pytest.fail("host-loss drill timed out")
        # the fault plan FIRED: process 1 died by SIGKILL, mid-epoch, and
        # therefore never reached the completion path (no out-file)
        assert p1.returncode == -9, f"worker1 survived:\n{s1[-3000:]}"
        assert not os.path.exists(out1)
        assert p0.returncode == 0, f"survivor failed:\n{s0[-3000:]}"
        with open(out0) as f:
            res = json.load(f)
        assert res["mode"] == "drill_resume"       # the re-exec happened
        assert res["process_count"] == 1           # shrunk topology
        assert res["bitwise_equal"] is True        # restored leaves bitwise
        assert res["elastic_resume_events"] >= 1   # surfaced as Robustness/*
        assert res["neval"] >= 8                   # ran to completion
        assert res["versions_seen"], res
        resumed_version = res["versions_seen"][-1]
        assert res["resumed_from"] > resumed_version >= 2

        # fresh 1-process run FROM THAT STATE: trim the dir copy back to the
        # version the survivor resumed from, resume again, compare losses
        ck2 = str(tmp_path / "replay-ck")
        shutil.copytree(ck, ck2)
        for name in os.listdir(ck2):
            v = elastic_ckpt.version_of(name)
            if v is None or v > resumed_version:
                shutil.rmtree(os.path.join(ck2, name), ignore_errors=True)
        out2 = str(tmp_path / "replay.json")
        env2 = dict(base_env)
        env2["BIGDL_MH_MODE"] = "drill_resume"
        env2["BIGDL_MH_CKPT_DIR"] = ck2
        p2 = subprocess.run(
            [sys.executable, _WORKER, str(port), "0", out2],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env2, timeout=240)
        assert p2.returncode == 0, p2.stdout[-3000:]
        with open(out2) as f:
            replay = json.load(f)
        assert replay["resumed_from"] == res["resumed_from"]
        assert replay["loss"] == res["loss"]

    @staticmethod
    def _free_port() -> int:
        with socket.socket() as s:
            s.bind(("localhost", 0))
            return s.getsockname()[1]
