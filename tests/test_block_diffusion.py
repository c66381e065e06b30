"""The block-diffusion mask in the flash kernels (interpret mode) against
dense masked attention, grouped-query heads at their own count, and what the
attention layer gained for it: ``head_dim`` apart from the width, per-head
RMSNorm on queries and keys, RoPE by position ids that repeat."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.kernels import flash_attention as fa
from bigdl_tpu.nn.attention import rope_rotate


def _may_see(length, b):
    """The mask from its statement, position by position."""
    out = np.zeros((2 * length, 2 * length), bool)
    for q in range(2 * length):
        for k in range(2 * length):
            qn, kn = q < length, k < length
            qb, kb = (q % length) // b, (k % length) // b
            out[q, k] = (qn and kn and kb == qb) or (qn and not kn and kb < qb) \
                or (not qn and not kn and kb <= qb)
    return out


@pytest.mark.parametrize("length,b", [(8, 4), (24, 4), (32, 16)])
def test_dense_mask_is_the_statement(length, b):
    np.testing.assert_array_equal(
        np.asarray(fa.dense_mask(fa.BlockDiffusion(length, b), 2 * length)),
        _may_see(length, b))


@pytest.mark.parametrize("length,b,tile", [
    (4096, 4, 512), (1024, 4, 512), (640, 8, 128), (256, 32, 512), (512, 4, 256)])
def test_live_chunks_are_the_tiles_the_mask_touches(length, b, tile):
    """Each kernel's loop bounds, from the mask's two numbers: exactly the
    tiles that hold a live pair (80 of 256 at L 4096, b 4), by queries and by
    keys alike."""
    mask = fa.BlockDiffusion(length, b)
    tile = min(tile, 2 * length)
    n = 2 * length // tile
    dense = np.asarray(fa.dense_mask(mask, 2 * length))
    want = {(i, j) for i in range(n) for j in range(n)
            if dense[i * tile:(i + 1) * tile, j * tile:(j + 1) * tile].any()}
    by_rows, by_cols = set(), set()
    for i in range(n):
        for lo, hi in fa._live_keys(mask, jnp.int32(i * tile), tile, tile, n):
            by_rows |= {(i, j) for j in range(int(lo), int(hi))}
        for lo, hi in fa._live_queries(mask, jnp.int32(i * tile), tile, tile, n):
            by_cols |= {(j, i) for j in range(int(lo), int(hi))}
    assert by_rows == want == by_cols
    if (length, b, tile) == (4096, 4, 512):
        assert len(want) == 80 and int(dense.sum()) == 16_793_600


def _operands(length, hq, hkv, d, dtype=jnp.float32):
    key = jax.random.PRNGKey(0)
    mk = lambda i, h: jax.random.normal(jax.random.fold_in(key, i),
                                        (1, h, 2 * length, d), dtype)
    return mk(0, hq), mk(1, hkv), mk(2, hkv), mk(3, hq)


# whole-axis tiles, two tiles (one a half), several tiles a half
@pytest.mark.parametrize("length,b", [(32, 4), (512, 4), (1024, 16)])
def test_flash_kernels_under_block_diffusion(length, b):
    """Forward and all three gradients, 4 query heads a key/value head,
    head_dim 128."""
    q, k, v, g = _operands(length, 4, 1, 128)
    mask = fa.BlockDiffusion(length, b)
    tiles = fa._tiles_under(mask, 2 * length, 128, 4)
    assert tiles.block == min(512, 2 * length)
    with jax.default_matmul_precision("highest"):
        kernel = lambda *a: fa.flash_attention(*a, False, True, mask)
        dense = lambda *a: fa._reference_attention(*a, mask)
        np.testing.assert_allclose(kernel(q, k, v), dense(q, k, v), atol=5e-6)
        got = jax.vjp(kernel, q, k, v)[1](g)
        want = jax.vjp(dense, q, k, v)[1](g)
    assert got[1].shape == k.shape and got[2].shape == v.shape
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, atol=2e-5)


@pytest.mark.parametrize("d,dtype", [(64, jnp.bfloat16), (128, jnp.float32)])
@pytest.mark.parametrize("group", [1, 4, 7])
def test_fused_backward_under_block_diffusion(
        fused_backward_against_reference, group, d, dtype):
    """The one backward kernel under the block-diffusion mask: a clean half
    of 256 and its noised copy, four key blocks against two spans of two
    chunks (block and chunk alike, as ``_tiles_under`` makes them: the
    forward's first chunk has to hold a key of every row): two ranges of
    chunks a key block, walked in one loop and cut to each span."""
    fused_backward_against_reference(fa.BlockDiffusion(256, 4), (group, 1), 512,
                                     d, dtype, tiles=(128, 128, 256))


@pytest.mark.parametrize("mask", ["causal", None])
def test_grouped_query_heads_reach_the_kernels_at_their_own_count(mask):
    q, k, v, g = _operands(64, 4, 2, 16)
    with jax.default_matmul_precision("highest"):
        kernel = lambda *a: fa.flash_attention(*a, False, True, mask)
        wide = lambda a, b, c: fa._reference_attention(
            a, jnp.repeat(b, 2, 1), jnp.repeat(c, 2, 1), mask)
        np.testing.assert_allclose(kernel(q, k, v), wide(q, k, v), atol=5e-6)
        for a, w in zip(jax.vjp(kernel, q, k, v)[1](g), jax.vjp(wide, q, k, v)[1](g)):
            np.testing.assert_allclose(a, w, atol=2e-5)


def test_mask_over_another_length_is_refused():
    q, k, v, _ = _operands(32, 2, 2, 16)
    with pytest.raises(ValueError):
        fa.flash_attention(q, k, v, False, True, fa.BlockDiffusion(16, 4))


# ------------------------------------------------------ the attention layer
def _rms(x, g, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _attention_by_hand(p, x, pos, heads, kv, hd, mask, theta):
    b, t, _ = x.shape
    split = lambda y, n: y.reshape(b, t, n, hd).transpose(0, 2, 1, 3)
    q = split(x @ p["q_weight"].T, heads)
    k, v = (split(x @ w.T, kv) for w in jnp.split(p["kv_weight"], 2))
    if "q_norm" in p:
        q, k = _rms(q, p["q_norm"]), _rms(k, p["k_norm"])
    q, k = rope_rotate(q, pos, theta), rope_rotate(k, pos, theta)
    k, v = jnp.repeat(k, heads // kv, 1), jnp.repeat(v, heads // kv, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(hd)
    s = jnp.where(mask, s, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    return o.transpose(0, 2, 1, 3).reshape(b, t, heads * hd) @ p["out_weight"].T


@pytest.mark.parametrize("qk_norm", [True, False])
@pytest.mark.parametrize("impl", ["flash", "full"])
def test_head_dim_apart_from_the_width(qk_norm, impl):
    """4 heads of 32 over a width of 48 (4 x 32 = 128), two key/value heads,
    RoPE by repeated position ids, the block-diffusion mask."""
    length, width, heads, kv, hd = 16, 48, 4, 2, 32
    mask = fa.BlockDiffusion(length, 4)
    m = nn.MultiHeadAttention(width, heads, with_bias=False, num_kv_heads=kv,
                              rope=True, rope_base=1e6, head_dim=hd,
                              qk_norm=qk_norm, mask=mask, attention_impl=impl)
    p = m.get_params()
    assert p["q_weight"].shape == (128, 48) and p["out_weight"].shape == (48, 128)
    assert p["kv_weight"].shape == (2 * kv * hd, 48) and ("q_norm" in p) == qk_norm
    key = jax.random.PRNGKey(3)
    if qk_norm:
        p = dict(p, q_norm=1 + 0.3 * jax.random.normal(key, (hd,)),
                 k_norm=1 - 0.3 * jax.random.normal(key, (hd,)))
    x = jax.random.normal(key, (2, 2 * length, width))
    pos = jnp.tile(jnp.arange(length), 2)
    with jax.default_matmul_precision("highest"):
        got, _ = m.apply(p, {}, (x, pos))
        want = _attention_by_hand(p, x, pos, heads, kv, hd,
                                  fa.dense_mask(mask, 2 * length), 1e6)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_repeated_position_ids_turn_both_halves_alike():
    """The noised copy and the clean sequence share positions: with the ids
    repeated, a token's key is the same in both halves."""
    m = nn.MultiHeadAttention(32, 2, with_bias=False, num_kv_heads=1, rope=True,
                              head_dim=16, causal=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 32))
    x = jnp.concatenate([x, x], axis=1)
    b, t = 1, 16
    _, k_ids, _ = m._project_qkv(m.get_params(), x, b, t)
    turned = rope_rotate(k_ids, jnp.tile(jnp.arange(8), 2), m.rope_base)
    np.testing.assert_allclose(turned[:, :, :8], turned[:, :, 8:], atol=1e-6)
    plain = rope_rotate(k_ids, jnp.arange(16), m.rope_base)
    assert float(jnp.max(jnp.abs(plain[:, :, :8] - plain[:, :, 8:]))) > 1e-3


def test_default_head_dim_keeps_the_fused_layout():
    p = nn.MultiHeadAttention(32, 4).get_params()
    assert set(p) == {"qkv_weight", "qkv_bias", "out_weight", "out_bias"}
    assert nn.MultiHeadAttention(32, 4).head_dim == 8
