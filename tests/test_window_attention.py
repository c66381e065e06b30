"""The causal window in the flash kernels (interpret mode) against the dense
mask: the chunks each kernel walks, forward and all three gradients with
grouped-query heads, a window that does not divide the axis, one no shorter
than the axis (the causal mask), one under a chunk, several resident spans;
and ``MultiHeadAttention(window=)`` handing it to the kernels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.kernels import flash_attention as fa


@pytest.mark.parametrize("t,size", [(16, 5), (16, 1), (12, 40)])
def test_dense_mask_is_the_statement(t, size):
    want = np.array([[0 <= i - j < size for j in range(t)] for i in range(t)])
    np.testing.assert_array_equal(
        np.asarray(fa.dense_mask(fa.CausalWindow(size), t)), want)
    if size >= t:
        np.testing.assert_array_equal(want, np.asarray(fa.dense_mask("causal", t)))


@pytest.mark.parametrize("t,size,tile", [
    (16384, 4096, 512),     # the SmallThinker cell: 252 of the causal mask's 528 tiles
    (4096, 1000, 512),      # the window is no multiple of the tile, nor the axis of it
    (2048, 100, 512),       # a window under one chunk: the diagonal and one beside it
    (1024, 4096, 256),      # no shorter than the axis: the causal tiles
    (1536, 513, 128), (640, 128, 128), (512, 1, 128)])
def test_live_chunks_are_the_tiles_the_window_touches(t, size, tile):
    """Each kernel's loop bounds from the window's one number: exactly the
    tiles that hold a live pair, by queries and by keys alike, cut on both
    sides of the band."""
    mask = fa.CausalWindow(size)
    n = t // tile
    i, j = np.arange(n)[:, None], np.arange(n)[None, :]
    # a tile is live iff its nearest pair is (lead 0 on the diagonal, else the
    # tile's last key against its first row) and that pair is inside the band
    nearest = np.where(i == j, 0, i * tile - (j * tile + tile - 1))
    want = {(a, b) for a in range(n) for b in range(n)
            if a >= b and nearest[a, b] < size}
    if t <= 4096:
        dense = np.asarray(fa.dense_mask(mask, t))
        assert want == {(a, b) for a in range(n) for b in range(n)
                        if dense[a * tile:(a + 1) * tile, b * tile:(b + 1) * tile].any()}
    by_rows, by_cols = set(), set()
    for a in range(n):
        (lo, hi), = fa._live_keys(mask, jnp.int32(a * tile), tile, tile, n)
        by_rows |= {(a, b) for b in range(int(lo), int(hi))}
        (lo, hi), = fa._live_queries(mask, jnp.int32(a * tile), tile, tile, n)
        by_cols |= {(b, a) for b in range(int(lo), int(hi))}
    assert by_rows == want == by_cols
    if (t, size, tile) == (16384, 4096, 512):
        causal = {(a, b) for a in range(n) for b in range(a + 1)}
        assert len(want) == 252 and len(causal) == 528
    if size >= t:
        assert want == {(a, b) for a in range(n) for b in range(a + 1)}


def _operands(t, hq, hkv, d):
    key = jax.random.PRNGKey(0)
    mk = lambda i, h: jax.random.normal(jax.random.fold_in(key, i), (1, h, t, d),
                                        jnp.float32)
    return mk(0, hq), mk(1, hkv), mk(2, hkv), mk(3, hq)


def _against_dense(t, size, heads=(4, 2), d=128):
    q, k, v, g = _operands(t, *heads, d)
    mask = fa.CausalWindow(size)
    with jax.default_matmul_precision("highest"):
        kernel = lambda *a: fa.flash_attention(*a, False, True, mask)
        dense = lambda *a: fa._reference_attention(*a, mask)
        out = kernel(q, k, v)
        np.testing.assert_allclose(out, dense(q, k, v), atol=5e-6)
        got = jax.vjp(kernel, q, k, v)[1](g)
        want = jax.vjp(dense, q, k, v)[1](g)
    assert got[1].shape == k.shape and got[2].shape == v.shape
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, atol=3e-5)
    return out, got


@pytest.mark.parametrize("t,size", [
    (1024, 384),        # two tiles, the window under one
    (1536, 1000),       # three tiles: the axis is no multiple of the window
    (1024, 100),        # a window under one chunk
    (640, 200),         # 128-row tiles, five of them
    (64, 16)])          # whole-axis tiles
def test_flash_kernels_under_a_causal_window(t, size):
    """Forward and all three gradients, 2 query heads a key/value head,
    head_dim 128. A tile's later rows see nothing of the first chunk it
    walks: their running maximum stays finite (``_banded``)."""
    assert fa._tiles_under(fa.CausalWindow(size), t, 128, 4).block == \
        {1024: 512, 1536: 512, 640: 128, 64: 64}[t]
    _against_dense(t, size)


@pytest.mark.parametrize("d,dtype", [(64, jnp.bfloat16), (128, jnp.float32)])
@pytest.mark.parametrize("group", [1, 4, 7])
@pytest.mark.parametrize("size", [100, 300])
def test_fused_backward_under_a_causal_window(
        fused_backward_against_reference, size, group, d, dtype):
    """The one backward kernel under a window shorter than a tile (100 of 128)
    and one longer than a span (300 of 256): two spans of queries a head, key
    blocks of two chunks' length, the key blocks a span does not see clamped
    onto live ones."""
    fused_backward_against_reference(fa.CausalWindow(size), (group, 1), 512, d,
                                     dtype, tiles=(256, 128, 256))


@pytest.mark.parametrize("size", [1024, 4096])
def test_a_window_no_shorter_than_the_axis_is_the_causal_mask(size):
    out, grads = _against_dense(1024, size)
    q, k, v, g = _operands(1024, 4, 2, 128)
    with jax.default_matmul_precision("highest"):
        causal = lambda *a: fa.flash_attention(*a, True, True)
        np.testing.assert_allclose(out, causal(q, k, v), atol=5e-6)
        for a, w in zip(grads, jax.vjp(causal, q, k, v)[1](g)):
            np.testing.assert_allclose(a, w, atol=3e-5)


@pytest.mark.parametrize("t,size", [(1024, 300), (2048, 130), (1024, 2000)])
def test_spans_outside_the_band_are_clamped_onto_live_ones(monkeypatch, t, size):
    """Keys and values past the resident budget: four or eight spans of 256
    rows under 512-row blocks, the index maps clamped on both sides of the
    band, every span's loop cut to its part of it."""
    monkeypatch.setattr(fa, "_RESIDENT_BYTES", 4 * 128 * 4 * 256)
    assert fa._tiles(t, 128, 4) == fa._Tiles(512, 256, 256)
    _against_dense(t, size)


def test_window_reaches_the_kernels_as_the_masks_description(monkeypatch):
    """``MultiHeadAttention(window=)`` on the flash path hands the kernels
    ``CausalWindow``; the ``"full"`` path writes the same mask out."""
    from bigdl_tpu.kernels import flash_attention as module
    seen = []
    flash = module.flash_attention

    def spy(q, k, v, causal, force, mask):
        seen.append(mask)
        return flash(q, k, v, causal, True, mask)       # interpreted kernels
    monkeypatch.setattr(module, "flash_attention", spy)
    make = lambda impl: nn.MultiHeadAttention(
        64, 4, causal=True, with_bias=False, num_kv_heads=2, rope=True,
        window=24, head_dim=32, attention_impl=impl)
    m, full = make("flash"), make("full")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64))
    with jax.default_matmul_precision("highest"):
        got, _ = m.apply(m.get_params(), {}, x)
        want, _ = full.apply(m.get_params(), {}, x)
    assert seen == [fa.CausalWindow(24)]
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the default path off the chip: the same description, the reference's mask
    auto = nn.MultiHeadAttention(64, 4, causal=True, with_bias=False, num_kv_heads=2,
                                 rope=True, window=24, head_dim=32)
    monkeypatch.setattr(module, "flash_attention", flash)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(auto.apply(m.get_params(), {}, x)[0], want, atol=2e-5)
