"""Sharded embedding engine suite (`make t1-recsys`).

Pins the contracts of parallel/embedding.py + the sparse optimizer path:

- ShardedEmbedding forward bitwise-equal to the wrapped LookupTable in every
  mode (plain / deduped / sparse-delta), including the dedup extremes;
- sharded NCF forward/backward bitwise-equal to the replicated model under
  the 8-device dryrun mesh with the table row-sharded over ``model``;
- sparse optimizer updates per method (SGD+momentum / Adagrad / Adam):
  touched rows exactly equal to the dense update, untouched rows
  bitwise-unchanged (lazy semantics — a constant per-step id set makes the
  dense and sparse trajectories coincide exactly);
- the padding-value sentinel semantics and the BIGDL_CHECK_IDS guard
  (host IndexError + checkify scope composition);
- HitRatio/NDCG device folds vs the host path, and their refusal cases;
- checkpoint round trip of a sharded model onto the dryrun mesh.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.dataset.sample import MiniBatch
from bigdl_tpu.models.ncf import NeuralCF
from bigdl_tpu.optim import (
    Adagrad, Adam, HitRatio, LocalOptimizer, NDCG, SGD, Trigger,
)
from bigdl_tpu.parallel.embedding import (
    ShardedEmbedding, build_sparse_plan, dedup_ids, find_sharded_embeddings,
    model_embedding_rules,
)
from bigdl_tpu.utils.engine import Engine

pytestmark = pytest.mark.recsys


def _leaves_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


# ------------------------------------------------------------------ dedup
def test_dedup_ids_inverse_and_sentinel():
    ids = jnp.asarray([7, 2, 7, 7, 2, 9], jnp.int32)
    uids, inv = dedup_ids(ids, n_rows=100)
    assert uids.shape == ids.shape and inv.shape == ids.shape
    # inverse map reconstructs the original ids exactly
    assert np.array_equal(np.asarray(uids)[np.asarray(inv)], np.asarray(ids))
    # padding is the out-of-range sentinel (n_rows), never referenced by inv
    pad = np.asarray(uids) == 100
    assert pad.sum() == ids.shape[0] - 3
    assert not np.isin(np.asarray(inv), np.flatnonzero(pad)).any()


@pytest.mark.parametrize("ids", [
    np.full(16, 7, np.int32),                 # all-equal: U = 1
    np.arange(1, 17, dtype=np.int32),         # all-unique: U = N
    np.asarray([3, 3, 1, 9, 1, 3, 20, 20], np.int32),
])
def test_sharded_forward_bitwise_all_modes(ids):
    table = nn.LookupTable(20, 6)
    ref, _ = table.apply(table.get_params(), {}, jnp.asarray(ids))
    for dedup in (False, True):
        sh = ShardedEmbedding(nn.LookupTable(20, 6), dedup=dedup)
        sh.set_params({"table": table.get_params()})
        out, st = sh.apply(sh.get_params(), sh.get_state(), jnp.asarray(ids))
        assert np.array_equal(np.asarray(out), np.asarray(ref))
        assert "uids" not in st
    # sparse-train mode (delta injected through the state channel)
    sh = ShardedEmbedding(nn.LookupTable(20, 6))
    sh.set_params({"table": table.get_params()})
    state = dict(sh.get_state())
    state["delta"] = None
    out, st = sh.apply(sh.get_params(), state, jnp.asarray(ids))
    assert np.array_equal(np.asarray(out), np.asarray(ref))
    assert "uids" in st and st["uids"].shape == (ids.size,)


def test_sharded_forward_respects_max_norm_and_2d_input():
    table = nn.LookupTable(10, 4, max_norm=0.5)
    sh = ShardedEmbedding(nn.LookupTable(10, 4, max_norm=0.5))
    sh.set_params({"table": table.get_params()})
    ids = jnp.asarray([[1, 5], [5, 9]], jnp.int32)
    ref, _ = table.apply(table.get_params(), {}, ids)
    out, _ = sh.apply(sh.get_params(), sh.get_state(), ids)
    assert out.shape == (2, 2, 4)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


# -------------------------------------------------- sharded NCF fwd/bwd
def test_sharded_ncf_bitwise_vs_replicated_on_mesh():
    """Row-sharded placement over the dryrun mesh's model axis changes the
    program layout, not the numbers: the placed (row-sharded) and unplaced
    (replicated) runs of the sharded model agree bitwise on loss and EVERY
    gradient leaf. Against the plain (unwrapped) model the loss and all four
    embedding-table gradients are bitwise-equal too; the MLP's dense-matmul
    grads are only float32-tight there, because the dedup subgraph shifts
    XLA's fusion/association choices for unrelated ops."""
    Engine.init(mesh_shape=(2, 4), mesh_axes=("data", "model"))
    mesh = Engine.mesh()
    sh_model = NeuralCF(64, 32, class_num=2, sharded=True)
    plain = NeuralCF(64, 32, class_num=2, sharded=False)
    sh_params = sh_model.get_params()
    table_keys = {k for k, v in sh_params.items()
                  if isinstance(v, dict) and set(v) == {"table"}}

    def strip(tree):
        return {k: (v["table"] if k in table_keys else v)
                for k, v in tree.items()}

    plain.set_params(strip(sh_params))
    crit = nn.ClassNLLCriterion()
    rng = np.random.default_rng(0)
    inp = jnp.asarray(np.stack([rng.integers(1, 65, 16),
                                rng.integers(1, 33, 16)], axis=1), jnp.int32)
    tgt = jnp.asarray(rng.integers(0, 2, 16), jnp.int32)

    def make_loss(model):
        def f(p, s, x, t):
            out, _ = model.apply(p, s, x, training=True, rng=None)
            return crit.apply(out, t)
        return jax.jit(jax.value_and_grad(f))

    pl_loss, pl_grads = make_loss(plain)(
        plain.get_params(), plain.get_state(), inp, tgt)
    # place the sharded model's tables row-sharded over `model` for real
    rules = model_embedding_rules(sh_model)
    placed = jax.device_put(sh_params, rules.param_shardings(sh_params, mesh))
    sh_loss, sh_grads = make_loss(sh_model)(
        placed, sh_model.get_state(), inp, tgt)
    # ...and run the very same model unplaced: placement is the ONLY variable
    un_loss, un_grads = make_loss(sh_model)(
        sh_params, sh_model.get_state(), inp, tgt)
    assert float(sh_loss) == float(un_loss) == float(pl_loss)
    assert _leaves_equal(jax.device_get(sh_grads), jax.device_get(un_grads))
    sg = strip(jax.device_get(sh_grads))
    pg = jax.device_get(pl_grads)
    for k in sg:
        if k in table_keys:  # the tentpole claim: table grads bitwise
            assert _leaves_equal(sg[k], pg[k]), k
        else:
            for x, y in zip(jax.tree_util.tree_leaves(sg[k]),
                            jax.tree_util.tree_leaves(pg[k])):
                np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                           rtol=1e-5, atol=1e-6)
    # the rules actually row-shard: each table weight spec is P("model", None)
    specs = rules.param_shardings(sh_params, mesh)
    tables = [p for p, _ in find_sharded_embeddings(sh_model)]
    assert len(tables) == 4
    assert {p[0] for p in tables} == table_keys
    for path in tables:
        sharding = specs[path[0]]["table"]["weight"]
        assert sharding.spec == jax.sharding.PartitionSpec("model", None)


# ---------------------------------------------------- sparse optimizer
def _train(model, method, ids, target, steps=4, criterion=None):
    batches = [MiniBatch(ids, target)]
    opt = LocalOptimizer(model, DataSet.array(batches),
                         criterion or nn.MSECriterion())
    opt.set_optim_method(method)
    opt.log_every = 10 ** 9
    opt.set_end_when(Trigger.max_iteration(steps))
    opt.optimize()
    return opt


@pytest.mark.parametrize("make_method", [
    lambda: SGD(learningrate=0.05, momentum=0.9, dampening=0.0),
    lambda: Adagrad(learningrate=0.05),
    lambda: Adam(learningrate=0.05),
], ids=["sgd-momentum", "adagrad", "adam"])
def test_sparse_update_matches_dense_on_touched_rows(make_method):
    """With a constant per-step duplicate-free id set the lazy sparse update
    coincides with the dense trajectory BITWISE on touched rows (each row's
    gradient is a single occurrence, so dense scatter-add and dedup
    segment-sum associate identically), and untouched rows are
    bitwise-unchanged from initialization. Duplicate ids reorder the
    per-occurrence sum — that last-ulp case is pinned separately below.

    Adam's touched rows are held to two units in the last place of the
    largest weight, not to the bit: the dense and the sparse step are two
    compiled programs, and XLA fuses the division and the square root of
    ``m / (sqrt(v) + eps)`` differently in each. The first step is bitwise;
    from the second on a few rows differ by one such unit (1.19e-07 on weights
    of order 1, at 4 steps and at 8), which on an element near zero is many of
    that element's own ulps, so the bound is absolute. SGD with momentum and
    Adagrad stay bitwise."""
    V, D, B = 50, 8, 32
    rng = np.random.default_rng(3)
    ids = rng.permutation(np.arange(2, 2 + B, dtype=np.int32))  # 1-based, const
    target = rng.normal(size=(B, D)).astype(np.float32)
    touched = np.unique(ids) - 1                                # 0-based rows

    dense_t = nn.LookupTable(V, D)
    w0 = np.asarray(dense_t.get_params()["weight"])
    sparse_t = ShardedEmbedding(nn.LookupTable(V, D))
    sparse_t.set_params({"table": {"weight": jnp.asarray(w0)}})

    _train(dense_t, make_method(), ids, target)
    opt = _train(sparse_t, make_method(), ids, target)
    assert opt._sparse_plan() is not None  # the sparse step actually engaged

    w_dense = np.asarray(dense_t.get_params()["weight"])
    w_sparse = np.asarray(sparse_t.get_params()["table"]["weight"])
    if isinstance(opt.optim_method, Adam):
        np.testing.assert_allclose(
            w_sparse[touched], w_dense[touched], rtol=0,
            atol=2 * np.spacing(np.abs(w_dense[touched]).max()))
    else:
        assert np.array_equal(w_sparse[touched], w_dense[touched])
    untouched = np.setdiff1d(np.arange(V), touched)
    assert np.array_equal(w_sparse[untouched], w0[untouched])
    assert not np.array_equal(w_sparse[touched], w0[touched])  # it DID train


def test_sparse_update_close_with_duplicate_ids():
    """Duplicate ids in a batch change only the ASSOCIATION ORDER of the
    per-occurrence gradient sum (dense gather-VJP scatter-add vs the dedup
    path's segment-sum), so sparse and dense trajectories agree to float32
    resolution — not bitwise — on touched rows; lazy semantics still hold
    untouched rows bitwise at initialization."""
    V, D, B = 50, 8, 32
    rng = np.random.default_rng(3)
    ids = rng.choice(np.arange(2, 12, dtype=np.int32), size=B)  # duplicates
    assert np.unique(ids).size < B
    target = rng.normal(size=(B, D)).astype(np.float32)
    touched = np.unique(ids) - 1

    dense_t = nn.LookupTable(V, D)
    w0 = np.asarray(dense_t.get_params()["weight"])
    sparse_t = ShardedEmbedding(nn.LookupTable(V, D))
    sparse_t.set_params({"table": {"weight": jnp.asarray(w0)}})

    _train(dense_t, Adagrad(learningrate=0.05), ids, target)
    opt = _train(sparse_t, Adagrad(learningrate=0.05), ids, target)
    assert opt._sparse_plan() is not None

    w_dense = np.asarray(dense_t.get_params()["weight"])
    w_sparse = np.asarray(sparse_t.get_params()["table"]["weight"])
    np.testing.assert_allclose(w_sparse[touched], w_dense[touched],
                               rtol=1e-5, atol=1e-6)
    untouched = np.setdiff1d(np.arange(V), touched)
    assert np.array_equal(w_sparse[untouched], w0[untouched])


def test_sparse_plan_exclusions():
    model = ShardedEmbedding(nn.LookupTable(10, 4))
    plan, reason = build_sparse_plan(model, Adam(learningrate=0.01))
    assert plan is not None and reason is None
    assert [e.key for e in plan.entries] == ["."]
    # frozen table → no sparse entries
    model.freeze()
    plan, reason = build_sparse_plan(model, Adam(learningrate=0.01))
    assert plan is None and "frozen" in reason
    # plain (unwrapped) model → no plan, no reason
    plan, reason = build_sparse_plan(nn.LookupTable(10, 4),
                                     Adam(learningrate=0.01))
    assert plan is None and reason is None


def test_sparse_falls_back_for_stateful_schedule():
    from bigdl_tpu.optim.schedules import Plateau
    method = SGD(learningrate=0.1,
                 learningrate_schedule=Plateau(factor=0.5, patience=1))
    assert not method.supports_sparse_update()
    plan, reason = build_sparse_plan(
        ShardedEmbedding(nn.LookupTable(10, 4)), method)
    assert plan is None and "sparse_update" in reason


# -------------------------------------------------------- padding guard
def test_padding_none_is_default_and_disables_masking():
    t = nn.LookupTable(5, 3)
    assert t.padding_value is None
    out, _ = t.apply(t.get_params(), {}, jnp.asarray([1], jnp.int32))
    assert not np.array_equal(np.asarray(out)[0], np.zeros(3))


def test_padding_zero_based_can_mask_row_zero():
    t = nn.LookupTable(5, 3, padding_value=0.0, zero_based=True)
    out, _ = t.apply(t.get_params(), {}, jnp.asarray([0, 2], jnp.int32))
    assert np.array_equal(np.asarray(out)[0], np.zeros(3))
    assert not np.array_equal(np.asarray(out)[1], np.zeros(3))


def test_padding_one_based_semantics_unchanged():
    # 1-based: padding_value=0 still means "no padding row"...
    t0 = nn.LookupTable(5, 3, padding_value=0.0)
    out, _ = t0.apply(t0.get_params(), {}, jnp.asarray([1, 2], jnp.int32))
    assert not np.array_equal(np.asarray(out)[0], np.zeros(3))
    # ...and a non-zero value masks that id, bitwise as before
    t1 = nn.LookupTable(5, 3, padding_value=2.0)
    out, _ = t1.apply(t1.get_params(), {}, jnp.asarray([2, 3], jnp.int32))
    assert np.array_equal(np.asarray(out)[0], np.zeros(3))
    assert not np.array_equal(np.asarray(out)[1], np.zeros(3))
    # the sharded wrapper masks identically (dedup path)
    sh = ShardedEmbedding(nn.LookupTable(5, 3, padding_value=2.0))
    sh.set_params({"table": t1.get_params()})
    sout, _ = sh.apply(sh.get_params(), sh.get_state(),
                       jnp.asarray([2, 3], jnp.int32))
    assert np.array_equal(np.asarray(sout), np.asarray(out))


# ------------------------------------------------------------- id guard
def test_check_ids_host_guard(monkeypatch):
    monkeypatch.setenv("BIGDL_CHECK_IDS", "1")
    t = nn.LookupTable(10, 4)
    with pytest.raises(IndexError, match="out of range"):
        t.forward(jnp.asarray([3, 11], jnp.int32))   # 11 → row 10, off the end
    with pytest.raises(IndexError, match="out of range"):
        t.forward(jnp.asarray([0], jnp.int32))       # 1-based id 0 → row -1
    # in-range ids pass untouched
    t.forward(jnp.asarray([1, 10], jnp.int32))


def test_check_ids_checkify_scope_composes(monkeypatch):
    from jax.experimental import checkify

    from bigdl_tpu.nn.embedding import checkify_ids_scope

    monkeypatch.setenv("BIGDL_CHECK_IDS", "1")
    t = nn.LookupTable(10, 4)
    params = t.get_params()

    def fwd(ids):
        out, _ = t.apply(params, {}, ids)
        return jnp.sum(out)

    checked = checkify.checkify(fwd, errors=checkify.user_checks)
    with checkify_ids_scope():
        err, _ = jax.jit(checked)(jnp.asarray([3, 42], jnp.int32))
    with pytest.raises(checkify.JaxRuntimeError, match="out of range"):
        err.throw()
    with checkify_ids_scope():
        err, _ = jax.jit(checked)(jnp.asarray([3, 9], jnp.int32))
    err.throw()  # clean ids: no error
    # without the scope, a traced guard is silently skipped (not a trace error)
    jax.jit(fwd)(jnp.asarray([3, 9], jnp.int32))


# -------------------------------------------------- HR/NDCG device fold
def _grouped_scores(groups=6, group=5, seed=0):
    rng = np.random.default_rng(seed)
    scores = rng.normal(size=groups * group).astype(np.float32)
    labels = np.zeros(groups * group, np.int32)
    for g in range(groups):
        labels[g * group + rng.integers(0, group)] = 1
    return scores, labels


@pytest.mark.parametrize("cls", [HitRatio, NDCG])
def test_device_fold_matches_host(cls):
    group = 5
    scores, labels = _grouped_scores(group=group)
    m = cls(k=3, neg_num=group - 1)
    host = m.apply(scores, labels, None)
    mask = jnp.ones(scores.size, bool)
    acc = m.device_fold(jnp.asarray(scores), jnp.asarray(labels), mask)
    res = m.finalize(jax.device_get(acc))
    hv, hn = host.result()
    dv, dn = res.result()
    assert hn == dn and hv == pytest.approx(dv)
    # 2-D (N, 2) outputs rank by the LAST column — the host loop's [:, 1]
    out2 = np.stack([-scores, scores], axis=1)
    acc2 = m.device_fold(jnp.asarray(out2), jnp.asarray(labels), mask)
    assert m.finalize(jax.device_get(acc2)).result() == (dv, dn)


def test_device_fold_group_validity_and_refusals():
    group = 5
    scores, labels = _grouped_scores(groups=4, group=group)
    m = HitRatio(k=3, neg_num=group - 1)
    # a partially-masked group is dropped whole
    mask = np.ones(scores.size, bool)
    mask[2] = False
    acc = m.device_fold(jnp.asarray(scores), jnp.asarray(labels),
                        jnp.asarray(mask))
    assert m.finalize(jax.device_get(acc)).result()[1] == 3
    # ragged batch (not a multiple of neg_num+1) refused at trace time
    with pytest.raises(ValueError, match="multiple"):
        m.device_fold(jnp.asarray(scores[:-1]), jnp.asarray(labels[:-1]),
                      jnp.ones(scores.size - 1, bool))
    # a valid group with no positive label is refused at finalize
    bad = labels.copy()
    bad[:group] = 0
    acc = m.device_fold(jnp.asarray(scores), jnp.asarray(bad),
                        jnp.ones(scores.size, bool))
    with pytest.raises(ValueError, match="no\\s+positive"):
        m.finalize(jax.device_get(acc))


def test_run_device_eval_matches_host_loop_on_ncf():
    from bigdl_tpu.models.ncf.train import build_eval_batches
    from bigdl_tpu.optim.evaluator import run_device_eval

    Engine.init()
    model = NeuralCF(30, 20, class_num=2).evaluate()
    rng = np.random.default_rng(1)
    users = rng.integers(0, 30, size=24)
    items = rng.integers(0, 20, size=24)
    batches = build_eval_batches(users, items, 20, neg_num=4, batch_groups=4)
    hr, ndcg = HitRatio(k=3, neg_num=4), NDCG(k=3, neg_num=4)
    assert hr.has_device_fold() and ndcg.has_device_fold()
    (hr_res, ndcg_res), _ = run_device_eval(
        model, model.get_params(), model.get_state(),
        DataSet.array(batches), [hr, ndcg])
    hr_host = ndcg_host = None
    for b in batches:
        scores = np.asarray(model.forward(jnp.asarray(b.input)))[:, 1]
        r1 = hr.apply(scores, b.target, b.valid)
        r2 = ndcg.apply(scores, b.target, b.valid)
        hr_host = r1 if hr_host is None else hr_host + r1
        ndcg_host = r2 if ndcg_host is None else ndcg_host + r2
    assert hr_res.result()[1] == hr_host.result()[1]
    assert hr_res.result()[0] == pytest.approx(hr_host.result()[0])
    assert ndcg_res.result()[0] == pytest.approx(ndcg_host.result()[0])


# ------------------------------------------------------------ checkpoint
def test_sharded_checkpoint_roundtrip_onto_mesh(tmp_path):
    Engine.init(mesh_shape=(2, 4), mesh_axes=("data", "model"))
    mesh = Engine.mesh()
    model = NeuralCF(64, 32, class_num=2, sharded=True)
    rng = np.random.default_rng(5)
    ids = np.stack([rng.integers(1, 65, 16), rng.integers(1, 33, 16)],
                   axis=1).astype(np.int32)
    tgt = rng.integers(0, 2, 16).astype(np.int32)

    # train a step so the checkpoint carries non-init weights via the
    # SPARSE path, then save
    opt = _train(model, Adam(learningrate=0.01), ids, tgt, steps=2,
                 criterion=nn.ClassNLLCriterion())
    assert opt._sparse_plan() is not None
    ref = np.asarray(model.forward(jnp.asarray(ids)))
    path = str(tmp_path / "ncf_sharded.bin")
    model.save(path)

    from bigdl_tpu.nn.abstractnn import AbstractModule
    loaded = AbstractModule.load(path)
    params = loaded.get_params()
    assert _leaves_equal(params, model.get_params())
    # resume onto the mesh: tables placed row-sharded, forward bitwise
    rules = model_embedding_rules(loaded)
    placed = jax.device_put(params, rules.param_shardings(params, mesh))
    out = jax.jit(lambda p, s, x: loaded.apply(p, s, x, training=False,
                                               rng=None)[0])(
        placed, loaded.get_state(), jnp.asarray(ids))
    assert np.array_equal(np.asarray(jax.device_get(out)), ref)
    # ...and keeps training sparsely after the round trip
    opt2 = _train(loaded, Adam(learningrate=0.01), ids, tgt, steps=1,
                  criterion=nn.ClassNLLCriterion())
    assert opt2._sparse_plan() is not None
