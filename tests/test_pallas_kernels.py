"""Pallas kernel layer (kernels/layernorm.py): interpreter-mode equality with
the jnp reference and torch, gradient correctness through the custom VJP."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
import torch.nn.functional as F

from bigdl_tpu import nn
from bigdl_tpu.kernels import fused_layer_norm
from bigdl_tpu.kernels.layernorm import _reference_layer_norm


def _np(*shape, seed=0):
    return np.random.default_rng(seed).normal(size=shape).astype(np.float32)


class TestFusedLayerNorm:
    def test_pallas_interpret_matches_reference(self):
        x = jnp.asarray(_np(16, 64))
        g = jnp.asarray(np.abs(_np(64, seed=1)) + 0.5)
        b = jnp.asarray(_np(64, seed=2))
        out_pallas = fused_layer_norm(x, g, b, 1e-5, True)   # forced pallas
        out_ref = _reference_layer_norm(x, g, b, 1e-5)
        np.testing.assert_allclose(np.asarray(out_pallas), np.asarray(out_ref),
                                   rtol=1e-5, atol=1e-6)

    def test_matches_torch(self):
        x, g, b = _np(8, 32), np.abs(_np(32, seed=1)) + 0.5, _np(32, seed=2)
        out = fused_layer_norm(jnp.asarray(x), jnp.asarray(g), jnp.asarray(b),
                               1e-5, True)
        ref = F.layer_norm(torch.tensor(x), (32,), torch.tensor(g),
                           torch.tensor(b), 1e-5).numpy()
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)

    def test_3d_input(self):
        x = jnp.asarray(_np(2, 6, 32))
        g = jnp.ones((32,))
        b = jnp.zeros((32,))
        out = fused_layer_norm(x, g, b, 1e-5, True)
        assert out.shape == (2, 6, 32)
        np.testing.assert_allclose(np.asarray(out).mean(-1), 0.0, atol=1e-5)

    def test_gradients_match_reference(self):
        x, g, b = (jnp.asarray(_np(8, 32)),
                   jnp.asarray(np.abs(_np(32, seed=1)) + 0.5),
                   jnp.asarray(_np(32, seed=2)))

        def loss_fused(x, g, b):
            return jnp.sum(jnp.square(fused_layer_norm(x, g, b, 1e-5, True)))

        def loss_ref(x, g, b):
            return jnp.sum(jnp.square(_reference_layer_norm(x, g, b, 1e-5)))

        gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x, g, b)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x, g, b)
        for a, r in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-4, atol=1e-5)

    def test_under_jit(self):
        x = jnp.asarray(_np(8, 128))
        g, b = jnp.ones((128,)), jnp.zeros((128,))
        f = jax.jit(lambda x: fused_layer_norm(x, g, b, 1e-5, True))
        np.testing.assert_allclose(
            np.asarray(f(x)),
            np.asarray(_reference_layer_norm(x, g, b, 1e-5)),
            rtol=1e-5, atol=1e-6)


class TestLayerNormModule:
    def test_layer_oracle(self):
        from bigdl_tpu.utils.random_generator import RandomGenerator
        RandomGenerator.set_seed(0)
        m = nn.LayerNorm(16).evaluate()
        x = _np(4, 16)
        out = np.asarray(m.forward(jnp.asarray(x)))
        ref = F.layer_norm(torch.tensor(x), (16,)).numpy()
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)

    def test_trains_in_model(self):
        from bigdl_tpu import Engine
        from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
        from bigdl_tpu.dataset.sample import Sample
        from bigdl_tpu.optim import LocalOptimizer, SGD, Trigger

        Engine.init(seed=0)
        rng = np.random.default_rng(0)
        data = DataSet.array(
            [Sample(rng.normal(size=(8,)).astype(np.float32),
                    np.int32(rng.integers(0, 3))) for _ in range(32)]
        ) >> SampleToMiniBatch(8)
        model = (nn.Sequential().add(nn.Linear(8, 16)).add(nn.LayerNorm(16))
                 .add(nn.ReLU()).add(nn.Linear(16, 3)).add(nn.LogSoftMax()))
        opt = (LocalOptimizer(model, data, nn.ClassNLLCriterion())
               .set_optim_method(SGD(learningrate=0.1))
               .set_end_when(Trigger.max_iteration(6)))
        opt.optimize()
        assert np.isfinite(opt.state["loss"])


class TestFlashAttention:
    """Interpret-mode validation of the flash kernel against plain attention."""

    def _qkv(self, b=2, h=2, t=32, d=16, seed=0):
        rng = np.random.default_rng(seed)
        mk = lambda s: jnp.asarray(
            rng.normal(size=(b, h, t, d)).astype(np.float32) * s)
        return mk(1.0), mk(1.0), mk(1.0)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        from bigdl_tpu.kernels.flash_attention import (
            _reference_attention, flash_attention,
        )
        q, k, v = self._qkv()
        out = flash_attention(q, k, v, causal, True)   # pallas interpret
        ref = _reference_attention(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_tags_are_the_identity_where_no_policy_names_them(self, monkeypatch):
        """``_fa_fwd`` names what the backward kernels read; without a
        ``jax.checkpoint`` policy that asks for the names, value and gradient
        are bitwise those of the untagged kernels."""
        from bigdl_tpu.kernels import flash_attention as fa
        q, k, v = self._qkv(t=64, d=16, seed=5)

        def value_and_grads():
            return jax.value_and_grad(
                lambda a, b, c: jnp.sum(jnp.sin(fa.flash_attention(a, b, c, True, True))),
                argnums=(0, 1, 2))(q, k, v)

        tagged = value_and_grads()
        monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
        for a, b in zip(jax.tree_util.tree_leaves(tagged),
                        jax.tree_util.tree_leaves(value_and_grads())):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_matches_full_attention_module_path(self):
        from bigdl_tpu.kernels.flash_attention import flash_attention
        from bigdl_tpu.parallel.ring_attention import full_attention
        q, k, v = self._qkv(t=64, d=8, seed=3)
        out = flash_attention(q, k, v, True, True)
        ref = full_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_large_scores_stable(self):
        """Streaming max must keep exp() in range for large logits."""
        from bigdl_tpu.kernels.flash_attention import (
            _reference_attention, flash_attention,
        )
        q, k, v = self._qkv(seed=1)
        q = q * 30.0
        out = flash_attention(q, k, v, False, True)
        ref = _reference_attention(q, k, v, False)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_gradients_match_reference(self):
        from bigdl_tpu.kernels.flash_attention import (
            _reference_attention, flash_attention,
        )
        q, k, v = self._qkv(t=16, d=8, seed=2)

        g1 = jax.grad(lambda a, b, c: jnp.sum(
            jnp.square(flash_attention(a, b, c, True, True))),
            argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda a, b, c: jnp.sum(
            jnp.square(_reference_attention(a, b, c, True))),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_mha_flash_impl(self):
        from bigdl_tpu import nn
        from bigdl_tpu.utils.random_generator import RandomGenerator
        RandomGenerator.set_seed(0)
        m1 = nn.MultiHeadAttention(16, 2, causal=True, attention_impl="flash")
        m2 = nn.MultiHeadAttention(16, 2, causal=True, attention_impl="full")
        m2.set_params(m1.get_params())
        x = jnp.asarray(np.random.default_rng(0)
                        .normal(size=(2, 32, 16)).astype(np.float32))
        np.testing.assert_allclose(np.asarray(m1.evaluate().forward(x)),
                                   np.asarray(m2.evaluate().forward(x)),
                                   rtol=1e-4, atol=1e-5)

    def test_odd_length_falls_back(self):
        """Non-power-of-two T can't tile; must silently use the reference."""
        from bigdl_tpu.kernels.flash_attention import (
            _reference_attention, flash_attention,
        )
        rng = np.random.default_rng(5)
        mk = lambda: jnp.asarray(rng.normal(size=(1, 2, 15, 8)).astype(np.float32))
        q, k, v = mk(), mk(), mk()
        out = flash_attention(q, k, v, False, True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_reference_attention(q, k, v, False)),
            rtol=1e-4, atol=1e-5)


def _max_rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-30))


class TestFlashTilings:
    """Interpreted parity of the forward and of all three gradients against
    ``_reference_attention`` at ``"highest"``, at tilings the one-tile tests
    above never reach: rectangular tiles the diagonal crosses, several spans
    of the resident operand, and the training cell's own T=1024, d=64."""

    TOL = {"float32": 1e-5, "bfloat16": 1e-2}

    def _check(self, shape, dtype, causal, seed=0):
        from bigdl_tpu.kernels.flash_attention import (
            _reference_attention, flash_attention,
        )
        rng = np.random.default_rng(seed)
        q, k, v = (jnp.asarray(rng.normal(size=shape), dtype)
                   for _ in range(3))

        def sq(fn):
            return lambda *a: jnp.sum(jnp.square(fn(*a).astype(jnp.float32)))

        got = flash_attention(q, k, v, causal, True)
        ggot = jax.grad(sq(lambda a, b, c: flash_attention(
            a, b, c, causal, True)), argnums=(0, 1, 2))(q, k, v)
        assert got.dtype == q.dtype
        assert [g.dtype for g in ggot] == [q.dtype] * 3
        with jax.default_matmul_precision("highest"):
            f32 = [x.astype(jnp.float32) for x in (q, k, v)]
            ref = _reference_attention(*f32, causal)
            gref = jax.grad(sq(lambda a, b, c: _reference_attention(
                a, b, c, causal)), argnums=(0, 1, 2))(*f32)
        errs = [_max_rel_err(got, ref)] + [
            _max_rel_err(a, b) for a, b in zip(ggot, gref)]
        assert max(errs) <= self.TOL[jnp.dtype(dtype).name], errs

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("tiles", [
        (256, 384, 768),    # 3 blocks x 2 chunks: the diagonal cuts chunks
        (384, 128, 768),    # 2 blocks x 6 chunks
        (128, 256, 256),    # 3 spans of one chunk each
        (256, 128, 384),    # 2 spans of 3 chunks, blocks that straddle them
    ])
    def test_rectangular_tiles_match_reference(self, monkeypatch, tiles,
                                               dtype, causal):
        from bigdl_tpu.kernels import flash_attention as fa
        monkeypatch.setattr(fa, "_tiles",
                            lambda t, d, itemsize: fa._Tiles(*tiles))
        self._check((1, 2, 768, 16), dtype, causal)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    def test_cell_shape_matches_reference(self, dtype, causal):
        """T=1024, d=64 as ``gpt2-medium.train-t1024`` runs it (two heads):
        the tiles are the rule's own, two blocks of 512 over one span."""
        from bigdl_tpu.kernels import flash_attention as fa
        assert fa._tiles(1024, 64, jnp.dtype(dtype).itemsize) == \
            fa._Tiles(512, 512, 1024)
        self._check((1, 2, 1024, 64), dtype, causal, seed=1)


# head_dim with the dtype it is run in: the GPT-2 cell's and the decoders'
WIDTHS = [(64, jnp.bfloat16), (128, jnp.float32)]


@pytest.mark.parametrize("d,dtype", WIDTHS)
@pytest.mark.parametrize("group", [1, 4, 7])
@pytest.mark.parametrize("mask", [None, "causal"])
def test_fused_backward_matches_the_reference_vjp(
        fused_backward_against_reference, mask, group, d, dtype):
    """One kernel, three gradients: two spans of queries a head, two key
    blocks of two chunks' length, so dq sums across key blocks in its scratch
    and dk/dv across the spans and the group's heads in theirs (a group of 1,
    of 4, and of 7, which is no power of two)."""
    fused_backward_against_reference(mask, (group, 1), 512, d, dtype,
                                     tiles=(256, 128, 256))


@pytest.mark.parametrize("d,dtype", WIDTHS)
def test_fused_backward_at_the_rules_own_tiles(
        fused_backward_against_reference, d, dtype):
    """Two key/value heads of two query heads each at T=1024: 512-row tiles,
    one span, every pass the last (a block of dk/dv is written as it is made)."""
    fused_backward_against_reference("causal", (4, 2), 1024, d, dtype)


class TestFlashBackwardMemory:
    """Training at long T must not scale
    O(T^2). Pinned by shape math — the traced grad program may not contain
    ANY (T, T)-shaped intermediate on the flash path (the reference-VJP path
    materialises scores/probs at exactly that shape, so the assertion
    separates the two)."""

    T = 8192

    def _quadratic_shapes(self, jaxpr, T):
        found = []

        def walk(jpr):
            for eqn in jpr.eqns:
                for var in eqn.outvars:
                    shape = tuple(getattr(var.aval, "shape", ()))
                    if shape.count(T) >= 2:
                        found.append((str(eqn.primitive), shape))
                for v in eqn.params.values():
                    for sub in (v if isinstance(v, (list, tuple)) else [v]):
                        inner = getattr(sub, "jaxpr", None)
                        if inner is not None and hasattr(inner, "eqns"):
                            walk(inner)
                        elif hasattr(sub, "eqns"):
                            walk(sub)

        walk(jaxpr.jaxpr)
        return found

    def _grad_jaxpr(self, force_pallas):
        from bigdl_tpu.kernels.flash_attention import flash_attention
        T, d = self.T, 64
        q = jnp.zeros((1, 1, T, d), jnp.bfloat16)

        def loss(a, b, c):
            return jnp.sum(
                flash_attention(a, b, c, True, force_pallas)
                .astype(jnp.float32))

        return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)

    def test_flash_backward_no_quadratic_intermediate(self):
        found = self._quadratic_shapes(self._grad_jaxpr(True), self.T)
        assert not found, f"O(T^2) intermediates on the flash path: {found}"

    def test_reference_path_is_quadratic(self):
        """Sanity: the assertion actually detects the O(T^2) pattern."""
        found = self._quadratic_shapes(self._grad_jaxpr(False), self.T)
        assert found, "reference VJP should materialise (T, T) scores"
