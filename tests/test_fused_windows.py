"""Fused multi-step dispatch (BIGDL_FUSE_STEPS / set_fuse_steps): K optimizer
steps in one jitted lax.scan over a device-stacked super-batch.

Pins the tentpole contracts:
- K=4 and K=1 produce IDENTICAL parameters over a run crossing a checkpoint
  boundary, and fire every trigger at the same iterations;
- the trigger-boundary clipping rule (Trigger.next_fire_in) is exact for the
  schedule-driven factories and conservative for data-dependent ones;
- checkify numerics mode composes with fusion (a NaN injected mid-window
  surfaces);
- the feed's window assembly groups batches (with a partial trailing group)
  and the close() timeout path warns instead of leaking silently.
"""

import logging
import os
import threading

import numpy as np
import pytest

from bigdl_tpu import Engine, nn
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.dataset.prefetch import PrefetchingFeed
from bigdl_tpu.dataset.sample import MiniBatch
from bigdl_tpu.optim import LocalOptimizer, SGD, Trigger


def _batches(n=10, batch=8, dim=6, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    return [MiniBatch(rng.normal(size=(batch, dim)).astype(np.float32),
                      rng.integers(0, classes, size=(batch,)).astype(np.int32))
            for _ in range(n)]


def _recording(trigger, fired: list):
    """Record the iterations at which ``trigger`` returns True, preserving
    its next_fire_in schedule (so fusion stays enabled)."""
    orig = trigger._fn

    def fn(state):
        r = orig(state)
        if r:
            fired.append(state.get("neval"))
        return r

    trigger._fn = fn
    return trigger


def _train(fuse, ckpt_dir, n_iter=20, ckpt_every=8, unroll=None):
    if unroll is None:
        os.environ.pop("BIGDL_FUSE_UNROLL", None)
    else:
        os.environ["BIGDL_FUSE_UNROLL"] = str(unroll)
    Engine.reset()
    Engine.init(seed=11)
    model = nn.Sequential().add(nn.Linear(6, 3)).add(nn.LogSoftMax())
    fired = []
    opt = (LocalOptimizer(model, DataSet.array(_batches(n=12)),
                          nn.ClassNLLCriterion())
           .set_optim_method(SGD(learningrate=0.1, momentum=0.9))
           .set_fuse_steps(fuse)
           .set_checkpoint(ckpt_dir,
                           _recording(Trigger.several_iteration(ckpt_every),
                                      fired))
           .set_end_when(Trigger.max_iteration(n_iter)))
    # count fused dispatches so the K>1 leg can prove it actually fused
    dispatches = {"windows": 0}
    orig_compile = opt._compile_window

    def counted(k):
        fn = orig_compile(k)

        def wrapped(*args):
            dispatches["windows"] += 1
            return fn(*args)

        return wrapped

    opt._compile_window = counted
    opt.optimize()
    return model.get_params(), dict(opt.state), fired, dispatches["windows"]


class TestFusedEquivalence:
    def test_params_triggers_identical_across_checkpoint_boundary(self, tmp_path):
        """20 steps, checkpoint every 8, K=4: checkpoint iteration 8 lands at
        the END of fused window [5..8] and iteration 16 inside the run —
        params must be numerically identical to K=1 and every trigger must
        fire at the exact same iterations."""
        import jax

        d1, d4 = str(tmp_path / "k1"), str(tmp_path / "k4")
        # rolled scan (unroll=1, the TPU default) is BITWISE identical to the
        # per-step loop; full unroll (the CPU speed default) is exercised by
        # test_unrolled_windows_match_within_float below
        p1, s1, fired1, _ = _train(1, d1, unroll=1)
        p4, s4, fired4, nwin = _train(4, d4, unroll=1)
        assert nwin > 0, "K=4 run never dispatched a fused window"
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p4)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert s1["neval"] == s4["neval"] == 21
        assert s1["loss"] == s4["loss"]
        assert fired1 == fired4 == [8, 16]
        # versioned checkpoint files land at the same iterations
        names1 = sorted(f for f in os.listdir(d1) if f.endswith(".pkl"))
        names4 = sorted(f for f in os.listdir(d4) if f.endswith(".pkl"))
        assert names1 == names4 == ["checkpoint.16.pkl", "checkpoint.8.pkl"]

    def test_unrolled_windows_match_within_float(self, tmp_path):
        """The CPU fast path (fully unrolled scan) may codegen the step body
        marginally differently — params must still agree to float32 ulps and
        triggers must fire identically."""
        import jax

        d1, d4 = str(tmp_path / "k1"), str(tmp_path / "k4")
        p1, s1, fired1, _ = _train(1, d1)
        p4, s4, fired4, nwin = _train(4, d4, unroll=4)
        assert nwin > 0
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p4)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)
        assert fired1 == fired4 == [8, 16]
        assert s1["neval"] == s4["neval"] == 21

    def test_fuse_knob_validation(self):
        Engine.init(seed=0)
        model = nn.Sequential().add(nn.Linear(6, 3)).add(nn.LogSoftMax())
        opt = LocalOptimizer(model, DataSet.array(_batches()),
                             nn.ClassNLLCriterion())
        with pytest.raises(ValueError):
            opt.set_fuse_steps(0)
        assert opt.set_fuse_steps(3).fuse_steps == 3


class TestNextFireIn:
    def test_schedule_driven_factories_are_exact(self):
        t = Trigger.several_iteration(5)
        # at neval=1 the next fire is iter 5 → a 5-step window may cover it
        assert t.next_fire_in({"neval": 1}) == 5
        assert t.next_fire_in({"neval": 5}) == 1   # fires after this one
        assert t.next_fire_in({"neval": 6}) == 5
        t = Trigger.max_iteration(13)
        assert t.next_fire_in({"neval": 9}) == 5   # iters 9..13 may run
        assert t.next_fire_in({"neval": 13}) == 1
        assert Trigger.max_epoch(2).next_fire_in({"neval": 3}) \
            == Trigger.NEVER_IN_LOOP
        assert Trigger.every_epoch().next_fire_in({"neval": 3}) \
            == Trigger.NEVER_IN_LOOP

    def test_data_dependent_triggers_are_conservative(self):
        assert Trigger.min_loss(0.1).next_fire_in({"neval": 1}) == 1
        assert Trigger.max_score(0.9).next_fire_in({"neval": 1}) == 1

    def test_composition(self):
        s = {"neval": 1}
        ors = Trigger.or_(Trigger.several_iteration(5),
                          Trigger.max_iteration(3))
        assert ors.next_fire_in(s) == 3           # earliest child wins
        ands = Trigger.and_(Trigger.min_loss(0.1),
                            Trigger.several_iteration(5))
        assert ands.next_fire_in(s) == 5          # cannot fire before ALL can

    def test_min_loss_end_when_disables_fusion_not_correctness(self, tmp_path):
        """A data-dependent end_when keeps per-step dispatch (never overshoots
        the stop) rather than delaying it by up to K-1 steps."""
        Engine.reset()
        Engine.init(seed=11)
        model = nn.Sequential().add(nn.Linear(6, 3)).add(nn.LogSoftMax())
        opt = (LocalOptimizer(model, DataSet.array(_batches()),
                              nn.ClassNLLCriterion())
               .set_optim_method(SGD(learningrate=0.1))
               .set_fuse_steps(4)
               .set_end_when(Trigger.or_(Trigger.min_loss(1e9),
                                         Trigger.max_iteration(50))))
        assert opt._fusible_steps({"neval": 1, "loss": 2.0}) == 1


class TestFusedCheckify:
    def test_nan_inside_fused_window_raises(self, monkeypatch):
        """NaN injected at step 7 — inside the second (fused) window of a K=4
        run — must surface through the checkified scan."""
        monkeypatch.setenv("BIGDL_FAILURE_RETRY_TIMES", "0")
        Engine.reset()
        Engine.init(seed=3)
        batches = _batches(n=12)
        batches[6].input[:] = np.nan  # iteration 7: fused window [5..8]
        model = nn.Sequential().add(nn.Linear(6, 3)).add(nn.LogSoftMax())
        opt = (LocalOptimizer(model, DataSet.array(batches),
                              nn.ClassNLLCriterion())
               .set_optim_method(SGD(learningrate=0.1))
               .set_fuse_steps(4).set_check_numerics(True)
               .set_end_when(Trigger.max_iteration(12)))
        with pytest.raises(Exception, match="(?i)nan"):
            opt.optimize()

    def test_clean_fused_checkify_run(self):
        Engine.reset()
        Engine.init(seed=3)
        model = nn.Sequential().add(nn.Linear(6, 3)).add(nn.LogSoftMax())
        opt = (LocalOptimizer(model, DataSet.array(_batches()),
                              nn.ClassNLLCriterion())
               .set_optim_method(SGD(learningrate=0.1))
               .set_fuse_steps(4).set_check_numerics(True)
               .set_end_when(Trigger.max_iteration(12)))
        opt.optimize()
        assert np.isfinite(opt.state["loss"])
        assert opt.state["neval"] == 13


class TestWindowedFeed:
    def test_window_grouping_with_partial_tail(self):
        items = list(range(8))
        feed = PrefetchingFeed(lambda: iter(items), lambda g: list(g),
                               depth=2, window=3)
        got = [g for g, _ in feed]
        assert got == [[0, 1, 2], [3, 4, 5], [6, 7]]

    def test_window_grouping_synchronous(self):
        items = list(range(5))
        feed = PrefetchingFeed(lambda: iter(items), lambda g: list(g),
                               depth=0, window=2)
        got = [g for g, _ in feed]
        assert got == [[0, 1], [2, 3], [4]]

    def test_close_timeout_warns_and_breadcrumbs(self, caplog, monkeypatch):
        """A producer wedged in put_fn must be logged at close() (not silently
        leaked), and the next __iter__ must mention the leaked thread."""
        monkeypatch.setattr(PrefetchingFeed, "JOIN_TIMEOUT", 0.2)
        release = threading.Event()
        calls = {"n": 0}

        def wedged_put(batch):
            calls["n"] += 1
            if calls["n"] > 1:
                release.wait()  # ignores the feed's stop event
            return batch

        feed = PrefetchingFeed(lambda: iter(range(4)), wedged_put, depth=1)
        it = iter(feed)
        assert next(it) == (0, 0)  # producer is now wedged on batch 1
        with caplog.at_level(logging.WARNING, logger="bigdl_tpu.dataset"):
            feed.close()
        assert any("did not join" in r.getMessage() for r in caplog.records)
        caplog.clear()
        with caplog.at_level(logging.WARNING, logger="bigdl_tpu.dataset"):
            feed.put_fn = lambda b: b
            assert next(iter(feed)) == (0, 0)
            release.set()  # let the wedged thread exit
            feed.close()
        assert any("leaked producer thread" in r.getMessage()
                   for r in caplog.records)
