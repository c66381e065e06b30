"""Pallas TPU kernel — single-chip flash attention.

Complements the multi-chip ring attention (parallel/ring_attention.py): ring
shards the SEQUENCE over mesh devices and rotates K/V over ICI; this kernel is
the intra-chip analog of the same streaming-softmax idea. Plain XLA attention
materialises the (T, T) score matrix in HBM twice (softmax in, probs out);
flash keeps one score tile at a time in VMEM with running max/sum statistics,
so HBM traffic drops from O(T^2) to O(T·d) and the two matmuls per tile stay
on the MXU.

Tile program (``_Tiles``, chosen by ``_tiles`` from T, head_dim and the
input's itemsize): the forward's grid is (batch*heads, blocks, spans). A grid
step owns one ``block`` of queries and has a ``span`` of keys and values
resident in VMEM: the whole axis wherever two operands of it,
double-buffered, fit ``_RESIDENT_BYTES`` (T up to 16k at d=64 in bf16), so
their block index moves only with the head and they are fetched once a head.
The backward's grid step owns a ``block`` of keys and has a ``span`` of
queries (q and dO) resident. Inside, a rolled ``fori_loop`` walks the span in
``chunk``s. Under a causal mask its trip count ends at the diagonal (a dead
chunk is no step at all, a dead span is clamped onto the resident one and
copies nothing) and every live chunk builds the mask, one compare of
``_lead`` against a scalar. A second body without the mask for the chunks
wholly below the diagonal was built and dropped: the compare and select hide
under the MXU's time (1,231 against 1,261 bundles a 512x512 forward chunk)
and it doubles the kernel's code. Block and chunk need not be equal. The mask
is a static description (``None``, ``"causal"``, ``CausalWindow(size)`` or
``BlockDiffusion(L, b)``): from it each kernel derives, a tile, the one or two
ranges of chunks that hold a live pair (``_live_keys`` forward,
``_live_queries`` backward) and walks them in the same one loop (``_walk``); a
live chunk under block diffusion builds its mask from two compares of block
indices. Under a causal window the range of chunks is cut on both sides of
the band: it starts at the chunk the tile's first row still reaches back to
and ends at the diagonal (by keys: from the diagonal to the last row that
still reaches the tile's keys), a span wholly outside the band is clamped
onto a live one and copies nothing, and a live chunk builds its mask from two
compares of ``_lead``. At T 16,384, a window of 4,096 and 512-row tiles that
is 252 tiles a head against the causal mask's 528. Keys and values may hold
fewer heads than the queries: the index maps read a group's shared head, and
the backward's grid walks the group's query heads. The forward's running
(m, l, acc) state lives in VMEM scratch across a block's chunks and spans.
Each kernel is a jitted function, so a model's layers share one trace and one
lowering of it.

Operands go to the MXU in the input's dtype with fp32 accumulation
(``preferred_element_type``): bf16 q/k/v/dO tiles as they are, ``p`` and ``ds``
cast to that dtype for the second product, fp32 inputs in fp32 (which Mosaic
runs as one bf16 pass at default precision: the fp32 kernel reads 3e-3 off a
``"highest"`` reference, and casting bf16 tiles to fp32 first, as this file
did until PR 27, cost nothing and bought nothing). The softmax scale rides
on one (block, d) operand a grid step, not on every score (exact for bf16
where head_dim is a power of 4, e.g. 64; otherwise one more rounding of q or
k at the operand's own precision). The forward keeps m and l with a row's
value in every lane of a register, and l as per-lane partial sums reduced
once at the flush: one lane reduction (the max) a chunk instead of two
reductions and two lane broadcasts. Statistics and accumulators are fp32.

Semantics: forward AND backward are Pallas kernels on TPU (interpreter
elsewhere — tests). The backward is the flash-2 scheme in one kernel
(``bigdl_flash_bwd``, PR 37; two kernels before it, dq and dk/dv, each of which
made ``s``, the mask, ``p``, ``dp`` and ``ds`` for itself: seven products a
tile). The forward additionally saves the per-row logsumexp L = m + log(l);
the backward walks each live tile once, recomputes the probability tile from
(q, k, L) in VMEM, in the transposed orientation (keys down the sublanes), and
from the one ``p_t`` and ``ds_t = p_t * (v·dO^T - D)`` adds to all three sums,
  dv += p_t · dO,   dk += ds_t · q,   dq += ds_t^T · k
five products a tile, the last the only one with a transposed left operand,
with D = rowsum(dO * O) precomputed in one fused elementwise pass — so
TRAINING memory is O(T·d) too, not just inference (the O(T^2) score matrix is
never materialised in either direction; asserted by test against the compiled
HLO). Off TPU, and at sequence lengths no legal tile covers (``_pick_block``),
the reference jnp attention and its recompute-form VJP run instead. On TPU a
kernel that does not build raises: nothing here catches a build error.

The two sums run across each other: dk and dv sum over queries and over the
group's query heads, dq over keys. The backward's grid is (key/value head,
the group's query heads, spans of queries, key blocks), the key blocks
innermost: dq's fp32 span sits in VMEM scratch across the key blocks and is
scaled, cast and written once a (query head, span); dk and dv sum in fp32
scratch as long as the head (2 x T x d x 4 bytes: 16 MB at T 16,384 and d 128,
8 MB at the SDAR cell's T 8,192, 0.5 MB at GPT-2's) across the spans and the
group's heads, and a block of them is written in the last of those passes
(until then their output's index map names block 0, which Pallas copies out
only when the index moves on, after that pass has filled it). Nothing partial
crosses HBM. The VMEM the kernel asks for is computed from the shapes
(``_bwd_vmem_limit``: 42 MiB at the SmallThinker cell's shape, 34 at SDAR's,
the forward's 32 at GPT-2's); a sequence whose head-long sums pass
``_VMEM_CEILING_BYTES`` (T 65,536 at d 128 still fits) raises by name.

Per-row residuals (logsumexp ``L``, ``D``) cross HBM in the orientation each
kernel broadcasts them in, so no kernel has to move a vector between
sublanes and lanes: the forward writes ``L`` as a column ``(bh, T, 1)`` (rows
of its ``(block, chunk)`` tile), the backward reads ``L`` and ``D`` as rows
``(bh, 1, T)`` (its tile is transposed; XLA gets from one to the other by a
bitcast). Both shapes meet Mosaic's block rule — the last two block dims
divide (8, 128) or span the array — which a ``(1, block)`` block over
``(bh, T)`` does not.

Measured on one v5e chip at (8, 16, 1024, 64) bf16 causal,
the shape of the benchmark's GPT-2 medium cell, 512-row blocks and chunks:
forward 0.49, dq 0.61, dk/dv 0.73 ms a call (PERF.md, PR 27; 1.05, 2.94 and
2.91 ms with the 256x512 forward and 128x128 backward tiles of before). The
loops' bodies are then within a fifth of what the MXU needs for products that
fill half of it (head_dim 64 against 128 rows). The one backward kernel
against the pair it replaced (PERF.md, PR 37; bf16, the backward of one call
with its rowsum pass, ms, and the loop body's bundles a 512x512 tile):
  (8, 16, 1024, 64) causal, GPT-2's            1.79 -> 1.28    3,452 -> 2,366
  (2, 32 on 4, 8192, 128) BlockDiffusion(4096, 4), SDAR's
                                               17.98 -> 10.69   3,422 -> 2,245
  (1, 28 on 4, 16384, 128) causal, SmallThinker's full layer
                                               42.84 -> 28.71   3,423 -> 2,158
  the same under CausalWindow(4096)            24.04 -> 14.57   3,564 -> 2,348
Tried beside it and not kept: dq summed transposed (``k^T ds_t`` with ``k^T``
made once a grid step: 1.24, 10.68, 28.94, 14.78 ms), ``ds_t`` transposed in
fp32 before its cast (2,440 bundles), and tiles of 512x256, 256x512,
1,024x512 and 512x1,024 (2,988, 2,800, 2,204 and 2,423 bundles a 512x512
tile's worth against 2,158).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bigdl_tpu.kernels.layernorm import _on_tpu, out_struct


class BlockDiffusion(NamedTuple):
    """The block-diffusion training mask (BD3-LMs, arXiv:2503.09573) over an
    axis of ``2 * length`` positions: a noised copy of a sequence in
    ``[0, length)`` and the clean sequence in ``[length, 2 * length)``, both in
    blocks of ``block`` tokens. With ``blk(i) = (i mod length) // block`` a
    query sees a key iff: both noised and ``blk(k) == blk(q)``; query noised,
    key clean and ``blk(k) < blk(q)``; both clean and ``blk(k) <= blk(q)``. A
    clean query never sees a noised key. Static and hashable: the kernels
    derive their live chunks and a chunk's mask from the two numbers."""
    length: int
    block: int


class CausalWindow(NamedTuple):
    """Causal attention within a sliding window: query ``i`` sees key ``j``
    iff ``0 <= i - j < size``, the ``size`` newest keys with its own. A window
    no shorter than the axis is the causal mask. Static and hashable: the
    kernels derive the band's chunks and a chunk's mask from the one number."""
    size: int


def _as_mask(causal, mask=None):
    """The one static description the kernels take: ``None`` (every key),
    ``"causal"``, a :class:`CausalWindow` or a :class:`BlockDiffusion`.
    ``causal`` is the older boolean spelling (a description is passed
    through)."""
    if mask is not None:
        return mask
    if isinstance(causal, (BlockDiffusion, CausalWindow, str)):
        return causal
    return "causal" if causal else None


def dense_mask(mask, t: int):
    """``(t, t)`` booleans, queries down the rows: the mask written out, for
    the reference path and the tests."""
    if mask is None:
        return jnp.ones((t, t), bool)
    if mask == "causal":
        return jnp.tril(jnp.ones((t, t), bool))
    pos = jnp.arange(t)
    if isinstance(mask, CausalWindow):
        lead = pos[:, None] - pos[None, :]
        return (lead >= 0) & (lead < mask.size)
    noised = pos < mask.length
    blk = (pos % mask.length) // mask.block
    qn, kn, qb, kb = noised[:, None], noised[None, :], blk[:, None], blk[None, :]
    return jnp.where(kn, qn & (kb == qb), jnp.where(qn, kb < qb, kb <= qb))


def _reference_attention(q, k, v, causal=False):
    """Plain jnp attention over (..., T, d) — the numerical oracle and VJP.
    ``causal`` is a boolean or a mask description; ``k`` and ``v`` may hold
    fewer heads than ``q`` (query head ``i`` reads head ``i // group``)."""
    mask = _as_mask(causal)
    d = q.shape[-1]
    if q.ndim == 4 and k.shape[1] != q.shape[1]:
        group = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(d, jnp.float32))
    if mask is not None:
        s = jnp.where(dense_mask(mask, s.shape[-1]), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v.astype(p.dtype)).astype(q.dtype)


class _Tiles(NamedTuple):
    """One kernel's tiling of a sequence axis of length ``t``. A grid step
    owns ``block`` rows of the operand that streams through the MXU (queries
    for the forward kernel, keys for the backward) and holds ``span`` rows
    of the other side resident in VMEM (the whole axis wherever it fits);
    a rolled loop walks the span in chunks of ``chunk`` rows."""
    block: int
    chunk: int
    span: int


# VMEM the forward kernel may take (v5e has 128 MiB; Mosaic's default scope is
# 16): its plan holds _RESIDENT_BYTES of resident operands (two of them,
# double-buffered) and about five fp32 temporaries of a block x chunk tile.
# The backward kernel asks for what its own plan needs, from the shapes
# (``_bwd_vmem_limit``), and no more than _VMEM_CEILING_BYTES.
_VMEM_LIMIT_BYTES = 32 * 2 ** 20
_VMEM_CEILING_BYTES = 100 * 2 ** 20
_RESIDENT_BYTES = 8 * 2 ** 20
# fp32 tiles of block x chunk the backward's loop body is given room for:
# s_t, p_t, dp_t, ds_t, the masks' iotas and the casts' copies
_TILE_TEMPORARIES = 8

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _part(ref, axis, j, size):
    """Part ``j`` of a ``(1, ·, ·)`` block cut along ``axis`` into parts of
    ``size``: a chunk's rows of a resident ``(1, span, d)`` operand, or its
    lanes of a ``(1, 1, span)`` row of residuals."""
    from jax.experimental import pallas as pl

    at = [0, slice(None), slice(None)]
    if ref.shape[axis] != size:
        at[axis] = pl.ds(pl.multiple_of(j * size, size), size)
    return ref[tuple(at)]


def _each(lo, hi, body):
    """Rolled loop over ``[lo, hi)``, run for what ``body`` writes to refs."""
    def step(j, carry):
        body(j)
        return carry
    jax.lax.fori_loop(lo, hi, step, 0)


def _within(chunk, s_idx, per_span, n_span):
    """Chunk ``chunk`` of the whole axis as a loop bound inside span
    ``s_idx``: numbered from the span's first chunk and cut to the span, so
    a span wholly on the dead side of it gets a loop of no step. The axis'
    two ends, given as Python numbers, stay Python numbers."""
    if isinstance(chunk, int) and chunk in (0, n_span * per_span):
        return per_span if chunk else 0
    return jnp.clip(chunk - s_idx * per_span, 0, per_span)


def _walk(ranges, s_idx, per_span, n_span, body):
    """``body(j)`` for every chunk ``j`` of span ``s_idx`` that lies in one of
    ``ranges``, one or two ``(lo, hi)`` of chunks of the whole axis in rising
    order: one rolled loop and one body either way (two ranges share the
    loop's counter, a scalar select picks the chunk)."""
    cut = [tuple(_within(c, s_idx, per_span, n_span) for c in r) for r in ranges]
    if len(cut) == 1:
        return _each(*cut[0], body)
    (lo1, hi1), (lo2, hi2) = cut
    n1 = hi1 - lo1
    _each(0, n1 + hi2 - lo2,
          lambda i: body(jnp.where(i < n1, lo1 + i, lo2 + i - n1)))


def _lead(shape, a, b):
    """``iota`` along dim ``a`` minus ``iota`` along dim ``b``: by how much one
    index of a tile leads the other. A chunk's causal mask is one compare of
    this against a scalar."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, a)
            - jax.lax.broadcasted_iota(jnp.int32, shape, b))


def _lanes(x, n):
    """A ``(rows, w)`` statistic whose lanes all hold the row's value, at
    width ``n``: a slice or a repeat of whole registers where the widths
    allow, no lane broadcast."""
    from jax.experimental.pallas import tpu as pltpu

    rows, w = x.shape
    if n <= w:
        return x[:, :n]
    if w == 128 and n % w == 0:
        return pltpu.repeat(x, n // w, axis=1)
    return jnp.broadcast_to(x[:, :1], (rows, n))


# ------------------------------------------------ the masks, tile by tile
def _live_keys(mask, row0, rows, chunk, n_chunks):
    """The chunks of keys that some query of ``[row0, row0 + rows)`` sees:
    one or two ``(lo, hi)`` for ``_walk``. Causal: the chunks up to the one
    the last row reaches; under a window, from the chunk that holds the
    oldest key the first row sees. Block diffusion: the noised keys of the rows' own
    blocks, then the clean keys from the axis' middle up to the last block a
    row sees (two ranges, the second cut where a tile straddles both)."""
    from jax.experimental import pallas as pl

    if mask is None:
        return [(0, n_chunks)]
    if mask == "causal":
        return [(0, pl.cdiv(row0 + rows, chunk))]
    if isinstance(mask, CausalWindow):
        return [(jnp.maximum(row0 - mask.size + 1, 0) // chunk,
                 pl.cdiv(row0 + rows, chunk))]
    length, b = mask
    # the last noised row's block: its clean keys stop before it, a clean
    # row's after it
    last_noised = (jnp.minimum(row0 + rows, length) - 1) // b
    last_clean = (row0 + rows - 1 - length) // b + 1
    has_noised, has_clean = row0 < length, row0 + rows > length
    clean_hi = length + b * jnp.where(
        has_clean, jnp.where(has_noised, jnp.maximum(last_clean, last_noised),
                             last_clean), last_noised)
    lo1 = jnp.where(has_noised, (row0 // b) * b // chunk, 0)
    hi1 = jnp.where(has_noised, pl.cdiv((last_noised + 1) * b, chunk), 0)
    lo2 = jnp.maximum(length // chunk, hi1)
    return [(lo1, hi1), (lo2, jnp.maximum(pl.cdiv(clean_hi, chunk), lo2))]


def _live_queries(mask, col0, cols, chunk, n_chunks):
    """The chunks of queries that see some key of ``[col0, col0 + cols)``.
    Causal: from the chunk the first key reaches to the end; under a window,
    to the chunk of the last row that still sees the last key. Block
    diffusion: a noised key is seen by its own block's noised rows; a clean
    key by the noised rows of later blocks and the clean rows from its own
    block on."""
    from jax.experimental import pallas as pl

    if mask is None:
        return [(0, n_chunks)]
    if mask == "causal":
        return [(col0 // chunk, n_chunks)]
    if isinstance(mask, CausalWindow):
        return [(col0 // chunk, jnp.minimum(
            pl.cdiv(col0 + cols + mask.size - 1, chunk), n_chunks))]
    length, b = mask
    has_noised, has_clean = col0 < length, col0 + cols > length
    first_clean = (jnp.maximum(col0, length) - length) // b
    last_noised = (jnp.minimum(col0 + cols, length) - 1) // b
    # noised rows: the hull of the noised keys' own blocks and of the
    # blocks after the first clean key's (one of the two unless a tile
    # straddles the middle)
    own = ((col0 // b) * b, (last_noised + 1) * b)
    later = ((first_clean + 1) * b, length)
    lo1 = jnp.where(has_noised, jnp.where(has_clean, jnp.minimum(
        own[0], later[0]), own[0]), later[0]) // chunk
    hi1 = pl.cdiv(jnp.where(has_clean, later[1], own[1]), chunk)
    hi1 = jnp.maximum(hi1, lo1)
    lo2 = jnp.where(has_clean, (length + first_clean * b) // chunk, n_chunks)
    lo2 = jnp.maximum(lo2, hi1)
    return [(lo1, hi1), (lo2, jnp.maximum(n_chunks, lo2))]


# A dead score under a causal window: finite, where the other masks write
# -inf (``_banded``).
_DEAD = -0.7 * float(jnp.finfo(jnp.float32).max)


def _banded(s, lead, diag, size: int):
    """A chunk's scores under a causal window of ``size`` keys. ``lead`` is
    the key's index in the tile minus the query's (``_lead``) and ``diag`` the
    tile's first row minus its first column: the key is not after the query
    iff ``lead <= diag`` and within its window iff ``lead > diag - size``. The
    dead scores get a finite value: a tile's later rows see no key of the
    first chunks the tile walks (those are there for its first rows), and a
    row's running maximum has to stay finite for ``exp(m_prev - m_new)``. What
    such a row gathers before its first live key (every ``p`` reads 1) that
    factor wipes, as exactly 0, when the key comes; in the backward kernel
    ``exp(_DEAD - lse)`` is 0."""
    return jnp.where((lead <= diag) & (lead > diag - size), s, _DEAD)


_NOISED = 1 << 30       # added to a noised position's block index


def _positions(n, axis, start):
    """Positions ``start ..`` of a tile's rows (a column, ``axis`` 0) or of
    its columns (a row, ``axis`` 1)."""
    shape = (n, 1) if axis == 0 else (1, n)
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis) + start


def _block_index(mask, pos):
    """(is noised, block index) of positions under a block-diffusion mask."""
    length, b = mask
    noised = pos < length
    at = jnp.where(noised, pos, pos - length)
    return noised, (at >> (b.bit_length() - 1) if b & (b - 1) == 0 else at // b)


def _query_keys(mask, pos):
    """What a query's row of the mask is compared with: the last clean block
    it sees, and its own noised block (no block for a clean query)."""
    noised, blk = _block_index(mask, pos)
    return (jnp.where(noised, blk - 1, blk),
            jnp.where(noised, blk + _NOISED, -1))


def _key_value(mask, pos):
    noised, blk = _block_index(mask, pos)
    return jnp.where(noised, blk + _NOISED, blk)


def _block_diffusion(s, query_keys, key_value):
    """A chunk's scores under the block-diffusion mask: two compares of block
    indices, a query's pair down one side of the tile against a key's value
    along the other."""
    clean_upto, own = query_keys
    return jnp.where((key_value <= clean_upto) | (key_value == own),
                     s, -jnp.inf)


# A kernel is a jitted function of its tiles: the layers of a model call it
# with equal shapes, and it is then traced and lowered to Mosaic once a
# program, not once a layer (6 s of an LM step's 8 s of lowering, 24 layers).
_kernel = functools.partial(
    jax.jit, static_argnames=("mask", "tiles", "interpret"))


def _kv_map(block_q, span, mask, group):
    """Index map of the keys' and values' resident span under a grid of
    (query heads, query blocks, spans): query head ``b`` reads key/value head
    ``b // group``. Under the causal mask a span above the diagonal names the
    last live one, which is already resident: no copy. Under a causal window
    a span before the band names the first live one likewise."""
    windowed = isinstance(mask, CausalWindow)

    def index(b, i, s):
        if mask == "causal" or windowed:
            s = jnp.minimum(s, (i * block_q + block_q - 1) // span)
        if windowed:
            s = jnp.maximum(s, jnp.maximum(i * block_q - mask.size + 1, 0) // span)
        return (b if group == 1 else b // group, s, 0)
    return index


@_kernel
def _pallas_flash_call(q3, k3, v3, mask, tiles, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q3.shape
    group = bh // k3.shape[0]
    block_q, block_k, span = tiles
    diffusion = isinstance(mask, BlockDiffusion)
    windowed = isinstance(mask, CausalWindow)
    scale = 1.0 / (d ** 0.5)
    n_span, per_span = t // span, span // block_k
    # the running max and sum keep a row's value in every lane of a register
    # (as the (block_q, block_k) tile meets them), not in one lane of 128
    width = min(block_k, 128)

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr):
        i, s_idx = pl.program_id(1), pl.program_id(2)

        @pl.when(s_idx == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

        # the softmax scale rides on the (block_q, d) operand, once a step
        q = q_ref[0] * scale
        row0 = i * block_q
        if diffusion:
            query_keys = _query_keys(mask, _positions(block_q, 0, row0))

        def step(j):
            k = _part(k_ref, 1, j, block_k)
            v = _part(v_ref, 1, j, block_k)
            s = jax.lax.dot_general(q, k, _NT,
                                    preferred_element_type=jnp.float32)
            col0 = (s_idx * per_span + j) * block_k
            if mask == "causal":
                s = jnp.where(_lead(s.shape, 1, 0) <= row0 - col0, s, -jnp.inf)
            elif windowed:
                s = _banded(s, _lead(s.shape, 1, 0), row0 - col0, mask.size)
            elif diffusion:
                s = _block_diffusion(s, query_keys, _key_value(
                    mask, _positions(block_k, 1, col0)))
            # every row has a live key in the first chunk it meets (the axis'
            # first under the causal mask, its own block's under block
            # diffusion) or reads a finite score there (a causal window's
            # dead scores, `_banded`), so from the first step on m is finite
            # and no exponent reads inf - inf
            m_prev = m_scr[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - _lanes(m_new, block_k))
            # l stays a sum by lane until the flush: adds, no lane reduction
            l_scr[:] = l_scr[:] * alpha + sum(
                p[:, c:c + width] for c in range(0, block_k, width))
            acc_scr[:] = acc_scr[:] * _lanes(alpha, d) + jax.lax.dot_general(
                p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
            m_scr[:] = m_new

        # under a mask the loop walks the live chunks alone: those above the
        # diagonal, or of blocks no row of the tile sees, are no steps at all
        _walk(_live_keys(mask, row0, block_q, block_k, n_span * per_span),
              s_idx, per_span, n_span, step)

        @pl.when(s_idx == n_span - 1)
        def _flush():
            l = jnp.sum(l_scr[:], axis=-1, keepdims=True)
            o_ref[0] = (acc_scr[:] * (1.0 / l)).astype(o_ref.dtype)
            # per-row logsumexp, the flash backward's residual
            lse_ref[0] = m_scr[:, :1] + jnp.log(l)

    kv_map = _kv_map(block_q, span, mask, group)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[out_struct((bh, t, d), q3.dtype, q3, k3, v3),
                   out_struct((bh, t, 1), jnp.float32, q3, k3, v3)],
        grid=(bh, t // block_q, n_span),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, s: (b, i, 0)),
            pl.BlockSpec((1, span, d), kv_map),
            pl.BlockSpec((1, span, d), kv_map),
        ],
        out_specs=[pl.BlockSpec((1, block_q, d), lambda b, i, s: (b, i, 0)),
                   pl.BlockSpec((1, block_q, 1), lambda b, i, s: (b, i, 0))],
        scratch_shapes=[
            pltpu.VMEM((block_q, width), jnp.float32),
            pltpu.VMEM((block_q, width), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="bigdl_flash_fwd",
    )(q3, k3, v3)
    return out, lse


def _rows(ref, j, size):
    """Rows ``[j * size, (j + 1) * size)`` of a 2-D scratch, as an index."""
    from jax.experimental import pallas as pl

    if ref.shape[0] == size:
        return slice(None)
    return pl.ds(pl.multiple_of(j * size, size), size)


def _bwd_vmem_limit(t: int, d: int, itemsize: int, tiles: _Tiles) -> int:
    """The VMEM the backward kernel asks for, from the shapes: what its plan
    holds (the resident spans of q and dO and the rows of their residuals,
    double-buffered, a block of k and of v, dq's fp32 span with its output
    block, the head-long fp32 sums of dk and dv with their output blocks, and
    ``_TILE_TEMPORARIES`` fp32 tiles of block x chunk; a row of fewer than 128
    lanes takes 128, a ``(1, span)`` row of residuals 8 sublanes), or the
    forward's limit where that is more. The temporaries are counted
    generously: Mosaic builds the SmallThinker cell's shape within 36 MiB of
    the plan's 42, the SDAR cell's within 28 of 34. A sequence whose head-long
    sums pass the ceiling is refused here, by name, not by the compiler."""
    block, chunk, span = tiles
    lanes = -(-d // 128) * 128
    operands = 2 * (2 * span + 2 * block) * lanes * itemsize
    residuals = 2 * 2 * 8 * span * 4
    dq = span * lanes * (4 + 2 * itemsize)
    dkv = 2 * (t * lanes * 4 + 2 * block * lanes * itemsize)
    need = (operands + residuals + dq + dkv
            + _TILE_TEMPORARIES * block * chunk * 4)
    if need > _VMEM_CEILING_BYTES:
        raise ValueError(
            f"flash attention's backward keeps fp32 sums of dk and dv as long "
            f"as the sequence in VMEM: T={t} at head_dim {d} needs "
            f"{need >> 20} MiB of {_VMEM_CEILING_BYTES >> 20}")
    return max(need, _VMEM_LIMIT_BYTES)


@_kernel
def _pallas_flash_bwd(q3, k3, v3, do3, lse_row, dd_row, mask, tiles, interpret):
    """All three gradients from one walk of the live tiles. A grid step owns a
    block of keys, in the transposed orientation (keys down the sublanes), and
    walks the chunks of the resident span of queries that see it; a tile makes
    ``s_t = k q^T``, the mask, ``p_t``, ``dp_t = v dO^T`` and ``ds_t`` once, and
    from them ``dv += p_t dO``, ``dk += ds_t q`` and ``dq += ds_t^T k``: five
    products. ``lse_row`` and ``dd_row`` are ``(bh, 1, t)``: queries along the
    lanes, as the transposed tile meets them.

    The grid is (key/value head, the group's query heads, spans of queries,
    key blocks): dq's fp32 span stays in scratch across the key blocks and is
    written once a (query head, span); dk and dv sum in fp32 scratch as long as
    the head across the spans and the group's heads, and a block of them is
    written in the last of those passes (until then the output's index map
    names block 0, which is not copied out before that pass has filled it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q3.shape
    bkv = k3.shape[0]
    group = bh // bkv
    block_k, block_q, span = tiles
    diffusion = isinstance(mask, BlockDiffusion)
    windowed = isinstance(mask, CausalWindow)
    scale = 1.0 / (d ** 0.5)
    n_span, per_span, n_block = t // span, span // block_q, t // block_k

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
               dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr):
        head, s_idx, j = (pl.program_id(a) for a in (1, 2, 3))
        first_pass = (head == 0) & (s_idx == 0)
        last_pass = (head == group - 1) & (s_idx == n_span - 1)
        own = _rows(dk_scr, j, block_k)       # the block's rows of dk and dv

        @pl.when(j == 0)
        def _init_dq():
            dq_scr[:] = jnp.zeros_like(dq_scr)

        @pl.when(first_pass)
        def _init_dkv():
            dk_scr[own] = jnp.zeros((block_k, d), jnp.float32)
            dv_scr[own] = jnp.zeros((block_k, d), jnp.float32)

        k = k_ref[0]
        k_scaled = k * scale
        v = v_ref[0]
        col0 = j * block_k
        if diffusion:
            key_value = _key_value(mask, _positions(block_k, 0, col0))

        def step(i):
            q = _part(q_ref, 1, i, block_q)
            do = _part(do_ref, 1, i, block_q)
            lse = _part(lse_ref, 2, i, block_q)        # (1, bq)
            dd = _part(dd_ref, 2, i, block_q)          # (1, bq)
            s_t = jax.lax.dot_general(k_scaled, q, _NT,   # (bk, bq)
                                      preferred_element_type=jnp.float32)
            row0 = (s_idx * per_span + i) * block_q
            if mask == "causal":
                s_t = jnp.where(_lead(s_t.shape, 0, 1) <= row0 - col0,
                                s_t, -jnp.inf)
            elif windowed:
                s_t = _banded(s_t, _lead(s_t.shape, 0, 1), row0 - col0, mask.size)
            elif diffusion:
                s_t = _block_diffusion(s_t, _query_keys(
                    mask, _positions(block_q, 1, row0)), key_value)
            p_t = jnp.exp(s_t - lse)
            dv_scr[own] += jax.lax.dot_general(
                p_t.astype(do.dtype), do, _NN,
                preferred_element_type=jnp.float32)
            dp_t = jax.lax.dot_general(v, do, _NT,
                                       preferred_element_type=jnp.float32)
            ds_t = (p_t * (dp_t - dd)).astype(q.dtype)
            dk_scr[own] += jax.lax.dot_general(
                ds_t, q, _NN, preferred_element_type=jnp.float32)
            # the one product with a transposed left operand
            dq_scr[_rows(dq_scr, i, block_q)] += jax.lax.dot_general(
                ds_t, k, _TN, preferred_element_type=jnp.float32)

        # under a mask the loop walks the query chunks that see the block's
        # keys: from the diagonal on (to the band's end under a window), or
        # the blocks' own and later rows
        _walk(_live_queries(mask, col0, block_k, block_q, n_span * per_span),
              s_idx, per_span, n_span, step)

        @pl.when(j == n_block - 1)
        def _flush_dq():
            # ds' own factor of the scale, once on the (span, d) sum
            dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)

        @pl.when(last_pass)
        def _flush_dkv():
            dk_ref[0] = (dk_scr[own] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[own].astype(dv_ref.dtype)

    # a block of keys wholly outside what the span's queries see names the
    # nearest live one, which is already there: no copy
    live_block = _kv_map(span, block_k, mask, 1)
    q_map = lambda b, h, s, j: (b * group + h, s, 0)
    row_map = lambda b, h, s, j: (b * group + h, 0, s)
    kv_map = lambda b, h, s, j: live_block(b, s, j)

    def out_map(b, h, s, j):
        return (b, jnp.where((h == group - 1) & (s == n_span - 1), j, 0), 0)

    return pl.pallas_call(
        kernel,
        out_shape=[out_struct((bh, t, d), q3.dtype, q3, k3, v3, do3),
                   out_struct((bkv, t, d), k3.dtype, q3, k3, v3, do3),
                   out_struct((bkv, t, d), v3.dtype, q3, k3, v3, do3)],
        grid=(bkv, group, n_span, n_block),
        in_specs=[
            pl.BlockSpec((1, span, d), q_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, span, d), q_map),
            pl.BlockSpec((1, 1, span), row_map),
            pl.BlockSpec((1, 1, span), row_map),
        ],
        out_specs=[pl.BlockSpec((1, span, d), q_map),
                   pl.BlockSpec((1, block_k, d), out_map),
                   pl.BlockSpec((1, block_k, d), out_map)],
        scratch_shapes=[pltpu.VMEM((span, d), jnp.float32),
                        pltpu.VMEM((t, d), jnp.float32),
                        pltpu.VMEM((t, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) + ("arbitrary",) * 3,
            vmem_limit_bytes=_bwd_vmem_limit(t, d, q3.dtype.itemsize, tiles)),
        interpret=interpret,
        name="bigdl_flash_bwd",
    )(q3, k3, v3, do3, lse_row, dd_row)


def _pick_block(t: int, target: int) -> int | None:
    """Tile length along a sequence axis of length ``t``: the whole axis when
    it fits in ``target``, else the largest multiple of 128 up to ``target``
    that divides ``t``. Both satisfy Mosaic's block rule in either residual
    orientation (sublane or lane). ``None`` when no such tile exists, or when
    ``t`` is not a multiple of the 8-row sublane tile — the caller then runs
    the reference."""
    if t < 8 or t % 8:
        return None
    if t <= target:
        return t
    for block in range(target - target % 128, 0, -128):
        if t % block == 0:
            return block
    return None


# Rows of a block and of a chunk that ``_tiles`` aims for, in all three
# kernels: the winner of a sweep of 128 to 1024 on a v5e at (8, 16, 1024, 64)
# bf16 causal (PERF.md, PR 27). Smaller tiles pay a loop step's fixed cost
# (about 200 bundles beside the MXU's 1,000 to 2,000) more often; larger ones
# run more dead columns on the diagonal.
_TILE_ROWS = 512


def _tiles(t: int, d: int, itemsize: int) -> _Tiles | None:
    """The kernels' tiles from what the call can see: ``block`` and ``chunk``
    by ``_pick_block`` towards ``_TILE_ROWS``, ``span`` the longest legal
    tile whose two resident operands, double-buffered, stay within
    ``_RESIDENT_BYTES``. ``None`` where ``_pick_block(t, 128)`` is: a chunk
    is then the whole axis or a multiple of 128 that divides the span."""
    if _pick_block(t, 128) is None:
        return None
    span = _pick_block(t, max(128, _RESIDENT_BYTES // (4 * d * itemsize)))
    return _Tiles(_pick_block(t, _TILE_ROWS), _pick_block(span, _TILE_ROWS),
                  span)


def _tiles_under(mask, t: int, d: int, itemsize: int) -> _Tiles | None:
    """``_tiles``, and under a block-diffusion mask over more than one tile a
    block and a chunk that divide the clean length: no tile then straddles
    the axis' middle, and the first chunk a row meets holds a key it sees."""
    tiles = _tiles(t, d, itemsize)
    if tiles is None or not isinstance(mask, BlockDiffusion) or tiles.block == t:
        return tiles
    tile = _pick_block(mask.length, _TILE_ROWS)
    if tile is None or tiles.span % tile:
        return None
    return _Tiles(tile, tile, tiles.span)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = False,
                    force_pallas: bool | None = None, mask=None):
    """Streaming-softmax attention: ``q`` (batch, heads, T, d), ``k`` and ``v``
    (batch, kv_heads, T, d) with ``kv_heads`` dividing ``heads`` (query head
    ``i`` reads key/value head ``i // (heads // kv_heads)``; the kernels' index
    maps do that, nothing is repeated in memory).

    ``mask``: ``None`` for what ``causal`` says, or a static description:
    ``"causal"``, :class:`CausalWindow` or :class:`BlockDiffusion`. ``force_pallas``: None = pallas on
    TPU, reference jnp elsewhere; True = pallas (interpreted off-TPU — tests);
    False = reference. Whatever the setting, a ``T`` that ``_pick_block``
    cannot tile runs the reference.
    """
    return _fa_fwd(q, k, v, causal, force_pallas, mask)[0]


#: What the backward kernel reads, by the names ``_fa_fwd`` tags them with
#: (``jax.ad_checkpoint.checkpoint_name``): a ``jax.checkpoint`` whose policy
#: saves these names keeps them and does not run the forward kernel again.
RESIDUAL_NAMES = ("flash_q", "flash_k", "flash_v", "flash_out", "flash_lse")


def _fa_fwd(q, k, v, causal, force_pallas, mask):
    """The forward kernel and what the backward kernel reads: ``q``, ``k``,
    ``v`` as they enter (after a caller's norms, RoPE and layout copies),
    ``out`` and the rows' logsumexp, each tagged with its name of
    ``RESIDUAL_NAMES``. A tag is the identity unless a ``jax.checkpoint``
    policy asks for its name (``ConfigDecoder``'s does): then these stay
    across the backward pass and the forward kernel is not run again, for
    ``2 * (2 * heads + 2 * kv_heads) * T * head_dim`` bytes a batch row in
    bf16 (q and out, k and v) and ``4 * heads * T`` of logsumexp. The
    logsumexp is tagged as the kernel writes it, ``(bh, T, 1)``, which pads to
    128 lanes only where a kernel reads it: XLA lays a stack of them out with
    the unit axis outermost, so a scan holds the numbers alone (PERF.md, PR
    32). On the reference path there is no logsumexp, and ``out`` alone is
    tagged."""
    mask = _as_mask(causal, mask)
    q, k, v = (checkpoint_name(q, "flash_q"), checkpoint_name(k, "flash_k"),
               checkpoint_name(v, "flash_v"))
    if isinstance(mask, BlockDiffusion) and q.shape[2] != 2 * mask.length:
        raise ValueError(f"{mask} is over {2 * mask.length} positions, the "
                         f"operands have {q.shape[2]}")
    use_pallas = _on_tpu() if force_pallas is None else force_pallas
    b, h, t, d = q.shape
    hkv = k.shape[1]
    tiles = _tiles_under(mask, t, d, q.dtype.itemsize)
    if not use_pallas or tiles is None:
        out = checkpoint_name(_reference_attention(q, k, v, mask), "flash_out")
        return out, (q, k, v, None, None)
    out, lse = _pallas_flash_call(
        q.reshape(b * h, t, d), k.reshape(b * hkv, t, d),
        v.reshape(b * hkv, t, d), mask, tiles, interpret=not _on_tpu())
    out = checkpoint_name(out.reshape(b, h, t, d), "flash_out")
    return out, (q, k, v, out, checkpoint_name(lse, "flash_lse"))


def _fa_bwd(causal, force_pallas, mask, res, g):
    mask = _as_mask(causal, mask)
    q, k, v, out, lse = res
    if lse is None:
        _, vjp = jax.vjp(
            lambda qq, kk, vv: _reference_attention(qq, kk, vv, mask),
            q, k, v)
        return vjp(g)
    return _flash_bwd(q, k, v, out, lse, g, mask)


def _flash_bwd(q, k, v, out, lse, g, mask):
    """Streaming flash-2 backward: O(T·d) memory, probability tiles recomputed
    from (q, k, lse) in VMEM."""
    t, d = q.shape[2:]
    tiles = _tiles_under(mask, t, d, q.dtype.itemsize)    # not None: _fa_fwd checked
    reshape = lambda a: a.reshape(-1, t, d)
    q3, k3, v3, do3 = reshape(q), reshape(k), reshape(v), reshape(g)
    # D_i = rowsum(dO * O): one fused elementwise pass, O(T·d) reads; with the
    # logsumexp it meets the kernel as a row, (bh, 1, t)
    dd = jnp.sum(do3.astype(jnp.float32) * reshape(out).astype(jnp.float32),
                 axis=-1)[:, None]
    dq, dk, dv = _pallas_flash_bwd(q3, k3, v3, do3, lse.reshape(dd.shape), dd,
                                   mask, tiles, interpret=not _on_tpu())
    unshape = lambda a, like: a.reshape(like.shape).astype(like.dtype)
    return unshape(dq, q), unshape(dk, k), unshape(dv, v)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
