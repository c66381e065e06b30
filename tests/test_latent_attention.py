"""Latent attention (``nn.LatentAttention``, DeepSeek-V2's MLA without a query
low-rank path): the flash kernels (interpret mode) with a value head apart
from the query/key head and a softmax scale given, under every mask, against
plain attention; YaRN's frequencies and scale against the published formulas
written out; and the module against a plain float32 implementation, forward
and gradient, on the reference path and through the kernels."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.kernels import flash_attention as fa


def _plain_attention(q, k, v, mask, scale):
    """softmax(scale q k^T + M) v, key/value heads repeated over their group."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    s = jnp.where(fa.dense_mask(mask, q.shape[2]), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("mask,t", [
    ("causal", 640),                        # five 128-row tiles
    (fa.CausalWindow(100), 640),
    (fa.BlockDiffusion(512, 4), 1024),      # 512-row tiles, one each side of the middle
    ("causal", 64)])                        # whole-axis tiles
def test_flash_kernels_with_a_value_head_of_its_own(mask, t):
    """``d_qk`` 48 and ``d_v`` 32, a scale of 0.3, two query heads a
    key/value head: the output is ``d_v`` wide, and the gradients of q, k
    (48) and v (32) are plain attention's."""
    key = jax.random.PRNGKey(0)
    mk = lambda i, h, d: jax.random.normal(jax.random.fold_in(key, i), (1, h, t, d))
    q, k, v, g = mk(0, 4, 48), mk(1, 2, 48), mk(2, 2, 32), mk(3, 4, 32)
    kernel = lambda *a: fa.flash_attention(*a, False, True, mask, 0.3)
    plain = lambda *a: _plain_attention(*a, mask, 0.3)
    with jax.default_matmul_precision("highest"):
        out, pull = jax.vjp(kernel, q, k, v)
        want, pull_want = jax.vjp(plain, q, k, v)
        assert out.shape == (1, 4, t, 32)
        np.testing.assert_allclose(out, want, atol=5e-6)
        got, wanted = pull(g), pull_want(g)
    assert [a.shape for a in got] == [q.shape, k.shape, v.shape]
    for a, w in zip(got, wanted):
        np.testing.assert_allclose(a, w, atol=3e-5)
    # the reference path (off the chip's default) reads the same
    np.testing.assert_allclose(fa.flash_attention(q, k, v, False, False, mask, 0.3),
                               want, atol=5e-6)


def test_one_width_and_no_scale_is_the_plan_it_was():
    """A value head as wide as the query/key head and no scale given: the
    same tiles and the same VMEM as a call that names neither."""
    for t, d in ((16384, 128), (8192, 128), (1024, 64)):
        assert fa._tiles(t, d, 2, d) == fa._tiles(t, d, 2)
        tiles = fa._tiles(t, d, 2)
        assert fa._bwd_vmem_limit(t, d, 2, tiles, d) == fa._bwd_vmem_limit(t, d, 2, tiles)
    # latent attention's heads: spans of 4,096 (two operands of 192 and 128)
    assert fa._tiles(16384, 192, 2, 128) == fa._Tiles(512, 512, 4096)


def test_yarn_frequencies_and_scale_at_the_published_keys():
    """DeepSeek-V2's 64 rope channels, base 1e4, factor 40 over 4,096
    positions, betas 32 and 1: the correction range is 10 to 23, the
    frequencies below it kept, above it divided by 40, a line between; the
    softmax scale is ``mscale^2 / sqrt(192)`` with ``mscale = 0.1 * 0.707 *
    ln 40 + 1``."""
    turns = lambda beta: 64 * math.log(4096 / (beta * 2 * math.pi)) / (2 * math.log(10000))
    low, high = math.floor(turns(32)), math.ceil(turns(1))
    assert (low, high) == (10, 23)
    extra = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(nn.yarn_inv_freq(64, 10000, 40, 4096, 32, 1), want, rtol=1e-6)
    yarn = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
            "beta_fast": 32, "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707}
    m = nn.LatentAttention(2048, 16, 512, 128, 64, 128, rope_scaling=yarn)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert abs(mscale - 1.26080) < 1e-5
    assert abs(m.scale - mscale ** 2 / math.sqrt(192)) < 1e-12
    assert abs(m.scale - 0.114721) < 1e-6
    assert nn.LatentAttention(2048, 16, 512, 128, 64, 128).scale == 192 ** -0.5


YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707}
E, H, LATENT, NOPE, ROPE, V, T = 64, 4, 32, 16, 8, 16, 32


def _plain_latent(p, x, pos, scale, inv_freq):
    """The equations written out: the rope channels as complex numbers
    ``x_2j + i x_2j+1`` turned by ``exp(i pos f_j)`` (reals, then imaginary
    parts: the published halves), ``k_pe`` one head for all."""
    b, t, _ = x.shape

    def turn(r):            # (b, t, heads, ROPE)
        z = (r[..., 0::2] + 1j * r[..., 1::2]) * jnp.exp(1j * pos[:, None, None] * inv_freq)
        return jnp.concatenate([z.real, z.imag], -1)

    q = (x @ p["q_weight"].T).reshape(b, t, H, NOPE + ROPE)
    kv_a = x @ p["kv_a_weight"].T
    c = kv_a[..., :LATENT]
    c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + 1e-6) * p["kv_norm"]
    kv = (c @ p["kv_b_weight"].T).reshape(b, t, H, NOPE + V)
    q = jnp.concatenate([q[..., :NOPE], turn(q[..., NOPE:])], -1)
    k_pe = jnp.broadcast_to(turn(kv_a[:, :, None, LATENT:]), (b, t, H, ROPE))
    k = jnp.concatenate([kv[..., :NOPE], k_pe], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), kv[..., NOPE:])
    return o.reshape(b, t, H * V) @ p["out_weight"].T


@pytest.mark.parametrize("kernels", [False, True])
@pytest.mark.parametrize("rope_scaling", [YARN, None])
def test_the_module_is_the_equations(monkeypatch, kernels, rope_scaling):
    """Forward and the gradients of every parameter and of the input, at
    positions that start past 0, on the reference path and through the
    interpreted kernels at ``d_qk`` 24 and ``d_v`` 16."""
    if kernels:
        flash = fa.flash_attention
        monkeypatch.setattr(fa, "flash_attention",
                            lambda q, k, v, c, f, m, s: flash(q, k, v, c, True, m, s))
    m = nn.LatentAttention(E, H, LATENT, NOPE, ROPE, V, rope_scaling=rope_scaling)
    key = jax.random.PRNGKey(4)
    params = {k: (1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, i), a.shape)
                  if a.ndim == 1 else a)
              for i, (k, a) in enumerate(sorted(m.get_params().items()))}
    x = jax.random.normal(jax.random.fold_in(key, 99), (2, T, E))
    pos = jnp.arange(T) + 3000
    inv_freq = (nn.yarn_inv_freq(ROPE, 10000.0, 40, 4096) if rope_scaling else
                1.0 / 10000.0 ** (jnp.arange(0, ROPE, 2) / ROPE))
    module = lambda p, x: m.apply(p, {}, (x, pos))[0]
    plain = lambda p, x: _plain_latent(p, x, pos.astype(jnp.float32), m.scale, inv_freq)
    g = jax.random.normal(jax.random.fold_in(key, 7), (2, T, E))
    with jax.default_matmul_precision("highest"):
        out, pull = jax.vjp(module, params, x)
        want, pull_want = jax.vjp(plain, params, x)
        np.testing.assert_allclose(out, want, atol=2e-5)
        (gp, gx), (wp, wx) = pull(g), pull_want(g)
    np.testing.assert_allclose(gx, wx, atol=1e-4)
    for k in wp:
        np.testing.assert_allclose(gp[k], wp[k], atol=1e-4, err_msg=k)


def test_the_module_is_causal_and_turns_the_rope_channels_alone():
    """Position 20's output moves with a token before it and not with one
    after; a rope-free latent head (``k_nope``) sees no positions, so
    shifting every position leaves the scores, and the output, as they were."""
    m = nn.LatentAttention(E, H, LATENT, NOPE, ROPE, V, rope_scaling=YARN)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, T, E))
    out = lambda x, pos: m.apply(m.get_params(), {}, (x, pos))[0][0, 20]
    base = out(x, jnp.arange(T))
    np.testing.assert_allclose(out(x.at[0, 25].add(1.0), jnp.arange(T)), base, atol=1e-6)
    assert float(jnp.max(jnp.abs(out(x.at[0, 5].add(1.0), jnp.arange(T)) - base))) > 1e-4
    np.testing.assert_allclose(out(x, jnp.arange(T) + 1000), base, atol=2e-4)
