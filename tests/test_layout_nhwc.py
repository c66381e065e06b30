"""NHWC image-format path (nn/layout.py) + conv1 space-to-depth stem.

Round-4 performance work: NCHW stays the reference-parity default; NHWC is the
channels-last layout the spatial layers can switch to process-wide. These tests
pin exact numerical equivalence between the two formats (same params, transposed
activations) and the s2d stem's equivalence to the plain 7x7 stride-2 conv.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu import nn
from bigdl_tpu.nn import layout


@pytest.fixture(autouse=True)
def _restore_format():
    yield
    layout.set_image_format(None)


def _tree_max_diff(a, b):
    d = jax.tree_util.tree_map(lambda x, y: float(jnp.max(jnp.abs(x - y))), a, b)
    return max(jax.tree_util.tree_leaves(d), default=0.0)


class TestLayerEquivalence:
    def _run_both(self, module, x_nchw, training=False):
        params, state = module.get_params(), module.get_state()
        layout.set_image_format("NCHW")
        out1, st1 = module.apply(params, state, jnp.asarray(x_nchw),
                                 training=training, rng=None)
        layout.set_image_format("NHWC")
        out2, st2 = module.apply(params, state,
                                 jnp.asarray(x_nchw.transpose(0, 2, 3, 1)),
                                 training=training, rng=None)
        return out1, st1, out2, st2

    def test_conv(self):
        rng = np.random.default_rng(0)
        m = nn.SpatialConvolution(3, 8, 3, 3, 2, 2, 1, 1)
        x = rng.normal(size=(2, 3, 16, 16)).astype(np.float32)
        o1, _, o2, _ = self._run_both(m, x)
        assert np.allclose(np.transpose(o1, (0, 2, 3, 1)), o2, atol=1e-6)

    def test_grouped_conv(self):
        rng = np.random.default_rng(1)
        m = nn.SpatialConvolution(8, 8, 3, 3, 1, 1, 1, 1, n_group=4)
        x = rng.normal(size=(2, 8, 10, 10)).astype(np.float32)
        o1, _, o2, _ = self._run_both(m, x)
        assert np.allclose(np.transpose(o1, (0, 2, 3, 1)), o2, atol=1e-6)

    def test_batchnorm_training_state(self):
        rng = np.random.default_rng(2)
        m = nn.SpatialBatchNormalization(5)
        x = rng.normal(size=(4, 5, 7, 7)).astype(np.float32)
        o1, st1, o2, st2 = self._run_both(m, x, training=True)
        assert np.allclose(np.transpose(o1, (0, 2, 3, 1)), o2, atol=1e-5)
        assert _tree_max_diff(st1, st2) < 1e-6

    def test_maxpool_ceil(self):
        rng = np.random.default_rng(3)
        m = nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1, ceil_mode=True)
        x = rng.normal(size=(2, 4, 11, 11)).astype(np.float32)
        o1, _, o2, _ = self._run_both(m, x)
        assert np.allclose(np.transpose(o1, (0, 2, 3, 1)), o2, atol=1e-6)

    def test_avgpool_pad_not_counted(self):
        rng = np.random.default_rng(4)
        m = nn.SpatialAveragePooling(3, 3, 2, 2, 1, 1, count_include_pad=False)
        x = rng.normal(size=(2, 4, 9, 9)).astype(np.float32)
        o1, _, o2, _ = self._run_both(m, x)
        assert np.allclose(np.transpose(o1, (0, 2, 3, 1)), o2, atol=1e-6)


class TestResNetEquivalence:
    def test_resnet18_forward_and_state(self):
        from bigdl_tpu.models.resnet import ResNet
        m = ResNet(10, {"depth": 18, "dataSet": "ImageNet"})
        params, state = m.get_params(), m.get_state()
        x = np.random.default_rng(0).normal(size=(2, 3, 64, 64)).astype(np.float32)
        layout.set_image_format("NCHW")
        o1, s1 = m.apply(params, state, jnp.asarray(x), training=True, rng=None)
        layout.set_image_format("NHWC")
        o2, s2 = m.apply(params, state, jnp.asarray(x.transpose(0, 2, 3, 1)),
                         training=True, rng=None)
        # classifier output is (N, classes) in both formats
        assert np.allclose(o1, o2, atol=1e-5)
        assert _tree_max_diff(s1, s2) < 1e-5


class TestConv1SpaceToDepth:
    def _models(self):
        from bigdl_tpu.models.resnet.resnet import _Conv1SpaceToDepth
        conv = nn.SpatialConvolution(3, 16, 7, 7, 2, 2, 3, 3, with_bias=False)
        s2d = _Conv1SpaceToDepth(16)
        w7 = np.asarray(conv.get_params()["weight"])
        s2d.set_params({"weight": jnp.asarray(_Conv1SpaceToDepth.transform_7x7(w7))})
        return conv, s2d

    def test_matches_plain_stem_nchw(self):
        conv, s2d = self._models()
        x = np.random.default_rng(0).normal(size=(2, 3, 32, 32)).astype(np.float32)
        ref, _ = conv.apply(conv.get_params(), {}, jnp.asarray(x))
        out, _ = s2d.apply(s2d.get_params(), {}, jnp.asarray(x))
        assert ref.shape == out.shape
        assert np.allclose(ref, out, atol=1e-5)

    def test_matches_plain_stem_nhwc(self):
        conv, s2d = self._models()
        x = np.random.default_rng(1).normal(size=(2, 3, 32, 32)).astype(np.float32)
        layout.set_image_format("NHWC")
        xh = jnp.asarray(x.transpose(0, 2, 3, 1))
        ref, _ = conv.apply(conv.get_params(), {}, xh)
        out, _ = s2d.apply(s2d.get_params(), {}, xh)
        assert np.allclose(ref, out, atol=1e-5)

    def test_resnet_builder_option(self):
        from bigdl_tpu.models.resnet import ResNet
        m = ResNet(10, {"depth": 18, "dataSet": "ImageNet",
                        "conv1SpaceToDepth": True})
        x = np.random.default_rng(2).normal(size=(2, 3, 64, 64)).astype(np.float32)
        out, _ = m.apply(m.get_params(), m.get_state(), jnp.asarray(x),
                         training=True, rng=None)
        assert out.shape == (2, 10)
        assert np.all(np.isfinite(out))


class TestOnePassBNParity:
    def test_one_pass_matches_two_pass(self):
        import os
        rng = np.random.default_rng(5)
        m = nn.SpatialBatchNormalization(6)
        x = jnp.asarray(rng.normal(size=(8, 6, 5, 5)).astype(np.float32) * 3 + 1)
        o1, s1 = m.apply(m.get_params(), m.get_state(), x, training=True)
        os.environ["BIGDL_BN_TWO_PASS"] = "1"
        try:
            o2, s2 = m.apply(m.get_params(), m.get_state(), x, training=True)
        finally:
            del os.environ["BIGDL_BN_TWO_PASS"]
        assert np.allclose(o1, o2, atol=1e-4)
        assert _tree_max_diff(s1, s2) < 1e-4


class TestConcatChannelAxis:
    """Concat(2) on a 4-D activation means the CHANNEL axis semantically —
    under NHWC it must resolve to axis 3, or Inception's branch blocks would
    concatenate along height (round-4 bench fast-path fix)."""

    def test_concat_branches_equivalent(self):
        rng = np.random.default_rng(7)
        cat = nn.Concat(2)
        cat.add(nn.SpatialConvolution(3, 4, 1, 1))
        cat.add(nn.SpatialConvolution(3, 6, 3, 3, 1, 1, 1, 1))
        params, state = cat.get_params(), cat.get_state()
        x = rng.normal(size=(2, 3, 8, 8)).astype(np.float32)
        layout.set_image_format("NCHW")
        o1, _ = cat.apply(params, state, jnp.asarray(x))
        layout.set_image_format("NHWC")
        o2, _ = cat.apply(params, state, jnp.asarray(x.transpose(0, 2, 3, 1)))
        assert o1.shape == (2, 10, 8, 8) and o2.shape == (2, 8, 8, 10)
        assert np.allclose(np.transpose(o1, (0, 2, 3, 1)), o2, atol=1e-5)

    def test_non_spatial_concat_unchanged(self):
        # 2-D inputs: dimension 2 is a plain feature axis in either format
        cat = nn.Concat(2).add(nn.Linear(4, 3)).add(nn.Linear(4, 5))
        x = jnp.asarray(np.random.default_rng(8).normal(size=(2, 4)),
                        jnp.float32)
        layout.set_image_format("NHWC")
        out, _ = cat.apply(cat.get_params(), cat.get_state(), x)
        assert out.shape == (2, 8)


class TestInceptionNHWC:
    def test_inception_v1_layer_equivalent(self):
        from bigdl_tpu.models.inception.inception import Inception_Layer_v1
        from bigdl_tpu.utils.table import T
        m = Inception_Layer_v1(16, T(T(8), T(4, 8), T(4, 8), T(8)), "inc/")
        params, state = m.get_params(), m.get_state()
        x = np.random.default_rng(9).normal(size=(2, 16, 14, 14)).astype(np.float32)
        layout.set_image_format("NCHW")
        o1, _ = m.apply(params, state, jnp.asarray(x))
        layout.set_image_format("NHWC")
        o2, _ = m.apply(params, state, jnp.asarray(x.transpose(0, 2, 3, 1)))
        assert np.allclose(np.transpose(o1, (0, 2, 3, 1)), o2, atol=1e-4)


def test_build_resnet50_is_nhwc_s2d():
    """ResNet-50 as the benchmark's ResNet cell and ``chip_smoke.py`` build it,
    on the public API: channels-last, the space-to-depth stem in the model, a
    uint8 batch normalised on the device by ``nn.ImageNormalize``."""
    from bigdl_tpu.models.resnet import ResNet
    layout.set_image_format("NHWC")
    net = ResNet(1000, {"depth": 50, "dataSet": "ImageNet",
                        "conv1SpaceToDepth": True})
    model = nn.Sequential().add(nn.ImageNormalize()).add(net)
    assert layout.image_format() == "NHWC"
    assert "_Conv1SpaceToDepth" in repr(model)
    x = np.random.default_rng(0).integers(0, 256, size=(2, 224, 224, 3)).astype(np.uint8)
    out, _ = model.apply(model.get_params(), model.get_state(),
                         jnp.asarray(x), training=True, rng=None)
    assert out.shape == (2, 1000) and out.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(out)))
