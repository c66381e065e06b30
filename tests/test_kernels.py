"""Kernel-equivalence suite (make t1-kernels): fused conv-bn(-relu) vs the
unfused stack (fp32 bitwise on the train/eval paths, tolerance on the folded
inference kernel), flat-param SGD/Adam updates vs the per-leaf reference
(jitted bitwise), grad-accum M∈{1,2,4} vs M=1 on the LeNet CPU smoke, and the
remat policies."""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu import Engine, nn
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.dataset.sample import MiniBatch
from bigdl_tpu.kernels.conv_bn import FusedConvBNReLU
from bigdl_tpu.kernels.fused_update import (
    FlatParamUpdate, FlatSpec, flat_supported,
)
from bigdl_tpu.optim import SGD, Trigger
from bigdl_tpu.optim.optim_method import Adam, LarsSGD
from bigdl_tpu.optim.optimizer import LocalOptimizer
from bigdl_tpu.utils.random_generator import RandomGenerator

pytestmark = pytest.mark.kernels


def _leaves(tree):
    return [(jax.tree_util.keystr(k), np.asarray(v))
            for k, v in jax.tree_util.tree_leaves_with_path(tree)]


def assert_tree_bitwise(a, b, msg=""):
    for (ka, va), (kb, vb) in zip(_leaves(a), _leaves(b)):
        assert va.shape == vb.shape, (ka, kb)
        np.testing.assert_array_equal(va, vb, err_msg=f"{msg} {ka}")


def assert_tree_close(a, b, rtol, atol, msg=""):
    for (ka, va), (_, vb) in zip(_leaves(a), _leaves(b)):
        np.testing.assert_allclose(va, vb, rtol=rtol, atol=atol,
                                   err_msg=f"{msg} {ka}")


# --------------------------------------------------------------- conv-bn
def _conv_bn_relu(seed=3, with_bias=False, relu=True):
    RandomGenerator.set_seed(seed)
    conv = nn.SpatialConvolution(3, 8, 3, 3, 1, 1, 1, 1, with_bias=with_bias)
    bn = nn.SpatialBatchNormalization(8)
    seq = nn.Sequential().add(conv).add(bn)
    if relu:
        seq.add(nn.ReLU())
    return conv, bn, seq


def _x(shape=(4, 3, 12, 12), seed=0):
    return jnp.asarray(np.random.default_rng(seed)
                       .normal(size=shape).astype(np.float32))


@pytest.mark.parametrize("with_bias", [False, True])
@pytest.mark.parametrize("relu", [True, False])
def test_fused_conv_bn_train_bitwise(with_bias, relu):
    conv, bn, seq = _conv_bn_relu(with_bias=with_bias, relu=relu)
    x = _x()
    ref, ref_state = seq.apply(seq.get_params(), seq.get_state(), x,
                               training=True)
    fused = conv.fuse_bn(bn, relu=relu)
    out, out_state = fused.apply(fused.get_params(), fused.get_state(), x,
                                 training=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    assert_tree_bitwise(ref_state["1"], out_state["1"], "bn state")


def test_fused_conv_bn_eval_paths():
    conv, bn, seq = _conv_bn_relu()
    x = _x()
    # materialize running stats with one training pass
    _, st = seq.apply(seq.get_params(), seq.get_state(), x, training=True)
    seq.set_state(st)
    ref, _ = seq.apply(seq.get_params(), seq.get_state(), x, training=False)
    bn_state = dict(st["1"])
    # unfolded eval: bitwise (same op sequence)
    unfolded = conv.fuse_bn(bn, relu=True, fold_inference=False)
    out_u, _ = unfolded.apply(unfolded.get_params(),
                              {"0": {}, "1": bn_state}, x, training=False)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out_u))
    # folded eval: ONE conv, equivalent within float tolerance
    folded = conv.fuse_bn(bn, relu=True, fold_inference=True)
    out_f, _ = folded.apply(folded.get_params(),
                            {"0": {}, "1": bn_state}, x, training=False)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out_f),
                               rtol=2e-5, atol=2e-5)


def test_fuse_pass_sequential_bitwise():
    from bigdl_tpu.models.resnet.resnet import conv_bn as resnet_conv_bn
    RandomGenerator.set_seed(5)
    m = (nn.Sequential()
         .add(resnet_conv_bn(3, 8, 3, 1, 1))
         .add(resnet_conv_bn(8, 8, 3, 1, 1, relu=False)))
    x = _x()
    ref, _ = m.apply(m.get_params(), m.get_state(), x, training=True)
    fused = nn.fuse_conv_bn(m)
    assert isinstance(fused[0][0], FusedConvBNReLU)
    assert fused[0][0].with_relu and not fused[1][0].with_relu
    out, _ = fused.apply(fused.get_params(), fused.get_state(), x,
                         training=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_fuse_pass_graph_bitwise():
    RandomGenerator.set_seed(7)
    inp = nn.Input()
    conv = nn.SpatialConvolution(3, 4, 3, 3, 1, 1, 1, 1)
    bn = nn.SpatialBatchNormalization(4)
    g = nn.Graph(inp, nn.ReLU().inputs(bn.inputs(conv.inputs(inp))))
    x = _x()
    ref, _ = g.apply(g.get_params(), g.get_state(), x, training=True)
    fused = nn.fuse_conv_bn(g)
    mods = [type(m).__name__ for m in fused.modules]
    assert mods == ["FusedConvBNReLU"], mods
    out, _ = fused.apply(fused.get_params(), fused.get_state(), x,
                         training=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_fuse_pass_skips_non_adjacent_and_branching():
    RandomGenerator.set_seed(9)
    # conv → pool → bn: not adjacent, must not fuse
    m = (nn.Sequential()
         .add(nn.SpatialConvolution(3, 4, 3, 3, 1, 1, 1, 1))
         .add(nn.SpatialMaxPooling(2, 2, 2, 2))
         .add(nn.SpatialBatchNormalization(4)))
    fused = nn.fuse_conv_bn(m)
    assert not any(isinstance(c, FusedConvBNReLU) for c in fused.modules)
    # graph where the conv feeds TWO consumers: must not fuse either
    inp = nn.Input()
    conv = nn.SpatialConvolution(3, 4, 3, 3, 1, 1, 1, 1)
    cn = conv.inputs(inp)
    bn_node = nn.SpatialBatchNormalization(4).inputs(cn)
    other = nn.ReLU().inputs(cn)
    g = nn.Graph(inp, [bn_node, other])
    fg = nn.fuse_conv_bn(g)
    assert not any(isinstance(mm, FusedConvBNReLU) for mm in fg.modules)


# ------------------------------------------------------------ flat update
def _param_tree(seed=1):
    rng = np.random.default_rng(seed)
    return {
        "0": {"weight": jnp.asarray(rng.normal(size=(9, 5))
                                    .astype(np.float32)),
              "bias": jnp.asarray(rng.normal(size=(5,)).astype(np.float32))},
        "1": {"weight": jnp.asarray(rng.normal(size=(5, 3))
                                    .astype(np.float32))},
    }


@pytest.mark.parametrize("method_fn", [
    lambda: SGD(0.1, momentum=0.9, dampening=0.0, weightdecay=1e-4),
    lambda: SGD(0.05),
    lambda: Adam(1e-3),
], ids=["sgd-momentum-wd", "sgd-plain", "adam"])
def test_flat_update_bitwise_vs_per_leaf(method_fn):
    params = _param_tree()
    grads = jax.tree_util.tree_map(lambda a: a * 0.37 + 0.013, params)
    method, flat = method_fn(), FlatParamUpdate(method_fn())
    assert flat_supported(method)
    u_ref, u_flat = jax.jit(method.update), jax.jit(flat.update)
    p1, s1 = params, method.init_state(params)
    p2, s2 = params, flat.init_state(params)
    for i in range(4):
        step = jnp.asarray(i, jnp.int32)
        p1, s1 = u_ref(p1, grads, s1, step)
        p2, s2 = u_flat(p2, grads, s2, step)
    assert_tree_bitwise(p1, p2, "flat vs per-leaf params")
    # slots stay FLAT: dtype-grouped vectors, not the model tree
    for leaf in jax.tree_util.tree_leaves(s2):
        assert np.asarray(leaf).ndim <= 1


def test_flat_spec_roundtrip_mixed_dtypes():
    tree = {"a": jnp.ones((3, 2), jnp.float32),
            "b": jnp.full((4,), 2.0, jnp.bfloat16),
            "c": jnp.arange(5, dtype=jnp.float32)}
    spec = FlatSpec(tree)
    flat = spec.flatten(tree)
    assert set(flat) == {"float32", "bfloat16"}
    assert flat["float32"].shape == (11,) and flat["bfloat16"].shape == (4,)
    assert_tree_bitwise(tree, spec.unflatten(flat), "roundtrip")


def test_flat_unsupported_methods_fall_back():
    assert not flat_supported(SGD(0.1, layer_lr_mults={"bias": 2.0}))
    assert not flat_supported(LarsSGD())
    assert not flat_supported(FlatParamUpdate(SGD(0.1)))


def _lin_model(seed=11):
    RandomGenerator.set_seed(seed)
    m = nn.Sequential()
    m.add(nn.Linear(10, 16))
    m.add(nn.ReLU())
    m.add(nn.Linear(16, 4))
    m.add(nn.LogSoftMax())
    return m


def _lin_data(batch=16, n=3, seed=0):
    rng = np.random.default_rng(seed)
    return DataSet.array([
        MiniBatch(rng.normal(size=(batch, 10)).astype(np.float32),
                  rng.integers(0, 4, size=(batch,)).astype(np.int32))
        for _ in range(n)])


def _train_lin(iters=5, model_fn=_lin_model, data_fn=_lin_data,
               method_fn=lambda: SGD(0.1, momentum=0.9, dampening=0.0),
               **env):
    old = {k: os.environ.get(k) for k in env}
    os.environ.update({k: str(v) for k, v in env.items()})
    try:
        Engine.reset()
        Engine.init(seed=0)
        opt = (LocalOptimizer(model_fn(), data_fn(), nn.ClassNLLCriterion())
               .set_optim_method(method_fn())
               .set_end_when(Trigger.max_iteration(iters)))
        opt.optimize()
        return opt
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_flat_update_end_to_end():
    """BIGDL_FLAT_UPDATE through the real compiled step: same training
    trajectory as the per-leaf path (to ~1 ulp — XLA may contract FMAs
    differently around the two update forms), flat slots in the final
    optimizer state."""
    ref = _train_lin()
    flat = _train_lin(BIGDL_FLAT_UPDATE="1")
    assert flat.state["loss"] == pytest.approx(ref.state["loss"], rel=1e-6)
    assert_tree_close(ref.model.get_params(), flat.model.get_params(),
                      rtol=2e-6, atol=1e-7, msg="flat e2e")
    # the carried slots are the flat {dtype: vector} layout
    v = flat._final_ostate["v"]
    assert set(v) == {"float32"} and np.asarray(v["float32"]).ndim == 1
    # per-leaf reference keeps the model-tree layout
    assert "0" in ref._final_ostate["v"]


def test_flat_update_ineligible_method_keeps_per_leaf_bitwise():
    mults = lambda: SGD(0.1, momentum=0.9, dampening=0.0,
                        layer_lr_mults={"bias": 0.5})
    ref = _train_lin(method_fn=mults)
    flat = _train_lin(method_fn=mults, BIGDL_FLAT_UPDATE="1")
    # not flat-eligible → identical per-leaf program, bitwise
    assert_tree_bitwise(ref.model.get_params(), flat.model.get_params())
    assert "0" in flat._final_ostate["v"]


# --------------------------------------------------- grad accum and remat
def _lenet_data(batch=32, n=2, seed=0):
    rng = np.random.default_rng(seed)
    return DataSet.array([
        MiniBatch(rng.normal(size=(batch, 1, 28, 28)).astype(np.float32),
                  rng.integers(0, 10, size=(batch,)).astype(np.int32))
        for _ in range(n)])


def _lenet():
    from bigdl_tpu.models.lenet import LeNet5
    RandomGenerator.set_seed(21)
    return LeNet5(10)


def test_grad_accum_env_knob_matches_setter_bitwise():
    """BIGDL_GRAD_ACCUM=M is the SAME code path as
    set_gradient_accumulation(M) — bitwise."""
    via_env = _train_lin(BIGDL_GRAD_ACCUM="2")
    Engine.reset()
    Engine.init(seed=0)
    opt = (LocalOptimizer(_lin_model(), _lin_data(), nn.ClassNLLCriterion())
           .set_optim_method(SGD(0.1, momentum=0.9, dampening=0.0))
           .set_gradient_accumulation(2)
           .set_end_when(Trigger.max_iteration(5)))
    opt.optimize()
    assert_tree_bitwise(via_env.model.get_params(), opt.model.get_params())


@pytest.mark.parametrize("accum", [2, 4])
def test_grad_accum_matches_m1_on_lenet(accum):
    """M∈{2,4} vs M=1 on the LeNet CPU smoke (BN-free, mean-reduced loss:
    microbatch accumulation is the same update up to summation order)."""
    ref = _train_lin(iters=4, model_fn=_lenet, data_fn=_lenet_data)
    acc = _train_lin(iters=4, model_fn=_lenet, data_fn=_lenet_data,
                     BIGDL_GRAD_ACCUM=str(accum))
    assert acc.state["loss"] == pytest.approx(ref.state["loss"], rel=1e-4)
    assert_tree_close(ref.model.get_params(), acc.model.get_params(),
                      rtol=1e-4, atol=1e-6, msg=f"accum={accum}")


@pytest.mark.parametrize("mode", ["dots", "full"])
def test_remat_matches_no_remat(mode):
    """jax.checkpoint recomputes the identical forward ops — the training
    trajectory matches the no-remat step to ~1 ulp."""
    ref = _train_lin()
    rem = _train_lin(BIGDL_REMAT=mode)
    assert rem.state["loss"] == pytest.approx(ref.state["loss"], rel=1e-6)
    assert_tree_close(ref.model.get_params(), rem.model.get_params(),
                      rtol=2e-6, atol=1e-7, msg=f"remat={mode}")


def test_remat_env_validation():
    os.environ["BIGDL_REMAT"] = "everything"
    try:
        Engine.reset()
        Engine.init(seed=0)
        with pytest.raises(ValueError, match="BIGDL_REMAT"):
            LocalOptimizer(_lin_model(), _lin_data(), nn.ClassNLLCriterion())
    finally:
        os.environ.pop("BIGDL_REMAT", None)
    with pytest.raises(ValueError, match="remat mode"):
        Engine.reset()
        Engine.init(seed=0)
        LocalOptimizer(_lin_model(), _lin_data(),
                       nn.ClassNLLCriterion()).set_remat("most")


def test_accum_remat_flat_compose_in_fused_window():
    """The whole MFU stack at once: microbatch accumulation + full remat +
    flat update inside a fused scan window tracks the plain accumulated
    step."""
    ref = _train_lin(iters=6, BIGDL_GRAD_ACCUM="2")
    stacked = _train_lin(iters=6, BIGDL_GRAD_ACCUM="2", BIGDL_REMAT="full",
                         BIGDL_FLAT_UPDATE="1", BIGDL_FUSE_STEPS="3")
    assert stacked.state["loss"] == pytest.approx(ref.state["loss"],
                                                  rel=1e-5)
    assert_tree_close(ref.model.get_params(), stacked.model.get_params(),
                      rtol=1e-5, atol=1e-6, msg="composed")


def test_convbn_fuse_env_knob_end_to_end():
    """BIGDL_CONVBN_FUSE=1 rewrites the model inside optimize(); the fused
    run's losses match the unfused run bitwise (fp32 training path)."""
    def conv_model():
        RandomGenerator.set_seed(31)
        m = nn.Sequential()
        m.add(nn.SpatialConvolution(1, 4, 3, 3, 1, 1, 1, 1, with_bias=False))
        m.add(nn.SpatialBatchNormalization(4))
        m.add(nn.ReLU())
        m.add(nn.Reshape([4 * 8 * 8]))
        m.add(nn.Linear(4 * 8 * 8, 4))
        m.add(nn.LogSoftMax())
        return m

    def conv_data(batch=8, n=2, seed=0):
        rng = np.random.default_rng(seed)
        return DataSet.array([
            MiniBatch(rng.normal(size=(batch, 1, 8, 8)).astype(np.float32),
                      rng.integers(0, 4, size=(batch,)).astype(np.int32))
            for _ in range(n)])

    ref = _train_lin(iters=4, model_fn=conv_model, data_fn=conv_data)
    fused = _train_lin(iters=4, model_fn=conv_model, data_fn=conv_data,
                       BIGDL_CONVBN_FUSE="1")
    assert fused.state["loss"] == ref.state["loss"]
    assert any(isinstance(m, FusedConvBNReLU)
               for m in fused.model.modules)
