"""The sum of a routed layer's rows back to their tokens, as one Pallas kernel.

``sum_by_token(rows, token_of, live, tokens, weight=None)`` is ``(tokens, D)``
in fp32: each live row ``r`` of ``rows`` (``(R, D)``), times ``weight[r]``
where a weight is given, added to token ``token_of[r]``; a token that no live
row names gets zeros. The routed layer (``parallel/moe.py``) calls it twice a
pass: weighted for its output and unweighted for its input's gradient.

The rows come in by expert. One XLA sort of ``R`` int32 keys orders them by
token, the dead rows last, and XLA's own gather moves them into that order: a
token then owns at most ``top_k`` consecutive rows, and a block of tokens one
run of them. The kernel, ``bigdl_moe_sum``, walks the (token block, row block)
pairs whose run and rows meet, every token block at least once (at most
``token blocks + row blocks - 1`` pairs, listed from the sorted keys and handed
in by scalar prefetch). A pair adds its row block to the token block's fp32
output as one product on the MXU: a ``(tokens, rows)`` matrix that holds a
row's weight (1 unweighted) where the row is the token's and 0 elsewhere, by
the ``(rows, D)`` block; dead rows and the neighbours' rows meet zeros. Rows in
bfloat16 meet the weights in three bfloat16 parts, whose products are exact in
fp32, so the sum is XLA's fp32 scatter-add by token up to the order of its
terms. Every output row is written once, and no tensor of ``R`` rows is made
in fp32.

Tiles and VMEM come from the shapes (``_tiles``), and the whole sum is a
jitted function of them: the layers of a model call it with equal shapes, so a
program traces it and lowers the kernel to Mosaic once a shape and weighting,
not once a call. Off TPU the kernel runs in the interpreter. On TPU a kernel
that does not build raises.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from bigdl_tpu.kernels.layernorm import _on_tpu, out_struct

NAME = "bigdl_moe_sum"

# Tokens and rows a block: the MXU's side. A pair's product costs
# `2 * tokens * rows * width` whatever its rows hold, and the pairs number
# `token blocks + row blocks`, so the smallest block wastes least.
_BLOCK = 128
# VMEM the kernel may take (Mosaic's default scope is 16 MiB); the feature
# axis is cut into blocks when a whole row's plan passes the budget
_VMEM_LIMIT_BYTES = 32 * 2 ** 20
_BUDGET_BYTES = 24 * 2 ** 20
# a dead row's key: after every token, and no token block's
_DEAD = jnp.iinfo(jnp.int32).max
_NN = (((1,), (0,)), ((), ()))      # a @ b


class _Tiles(NamedTuple):
    """A block of ``tokens`` output rows meets a block of ``rows`` sorted
    rows, ``width`` features at a time."""
    tokens: int
    rows: int
    width: int


def _vmem_bytes(tokens: int, rows: int, width: int, itemsize: int) -> int:
    """The plan of one grid step: the row block and the output block, each
    double-buffered, the product, and the ``(tokens, rows)`` matrix with its
    parts and masks."""
    return (2 * rows * width * itemsize + 3 * tokens * width * 4
            + 8 * tokens * rows * 4)


def _tiles(tokens: int, rows: int, d: int, itemsize: int) -> _Tiles:
    """Blocks of ``_BLOCK`` tokens and rows (the whole axis where it is
    shorter), and the widest block of whole 128-lane columns dividing ``d``
    whose plan fits the budget (all of ``d`` where it is no such multiple)."""
    block_t, block_r = min(tokens, _BLOCK), min(rows, _BLOCK)
    width = d
    if d % 128 == 0:
        fits = [w for w in range(d, 127, -128) if d % w == 0
                and _vmem_bytes(block_t, block_r, w, itemsize) <= _BUDGET_BYTES]
        width = fits[0] if fits else 128
    return _Tiles(block_t, block_r, width)


def _pairs(key, tokens: int, tiles: _Tiles):
    """The (token block, row block) pairs the kernel walks, in the order of
    the token blocks, and how many there are. A token block's rows are the run
    of sorted keys in its tokens; a block with none still gets one pair, so
    that its output is written. The list has its static length, the last pair
    repeated past the count."""
    n_t = -(-tokens // tiles.tokens)
    n_r = key.shape[0] // tiles.rows
    edges = jnp.sum(key[None, :] < jnp.arange(n_t + 1, dtype=jnp.int32)[:, None]
                    * tiles.tokens, axis=1, dtype=jnp.int32)
    first = jnp.minimum(edges[:-1] // tiles.rows, n_r - 1)
    last = jnp.maximum(jnp.minimum((edges[1:] - 1) // tiles.rows, n_r - 1), first)
    ends = jnp.cumsum(last - first + 1)
    at = jnp.minimum(jnp.arange(n_t + n_r - 1, dtype=jnp.int32), ends[-1] - 1)
    block = jnp.sum(ends[None, :] <= at[:, None], axis=1, dtype=jnp.int32)
    return block, last[block] - (ends[block] - 1 - at), ends[-1:]


def _product(mine, weight, rows):
    """``where(mine, weight, 0) @ rows`` in fp32, ``weight`` a row of the
    rows' weights (1 where None)."""
    if rows.dtype == jnp.bfloat16:
        if weight is None:
            return jax.lax.dot_general(mine.astype(rows.dtype), rows, _NN,
                                       preferred_element_type=jnp.float32)
        left, total = jnp.where(mine, weight, 0.0), 0.0
        for _ in range(3):
            part = left.astype(rows.dtype)
            total = total + jax.lax.dot_general(
                part, rows, _NN, preferred_element_type=jnp.float32)
            left = left - part.astype(jnp.float32)
        return total
    p = mine.astype(jnp.float32) if weight is None else jnp.where(mine, weight, 0.0)
    return jax.lax.dot_general(p, rows.astype(jnp.float32), _NN,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(block_of, rows_of, count, key, weight, rows, tokens: int,
            tiles: _Tiles, interpret: bool):
    """``bigdl_moe_sum`` over the listed pairs: ``key`` and ``weight`` (or
    None) are ``(1, R)`` rows, ``rows`` ``(R, D)``, all in token order."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_t, block_r, width = tiles
    d = rows.shape[1]
    weighted = weight is not None

    def kernel(block_ref, _, count_ref, key_ref, *refs):
        *weight_ref, row_ref, o_ref = refs
        g = pl.program_id(1)
        block = block_ref[g]

        @pl.when((g == 0) | (block != block_ref[jnp.maximum(g - 1, 0)]))
        def _zero():
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(g < count_ref[0])
        def _add():
            mine = key_ref[...] == block * block_t + jax.lax.broadcasted_iota(
                jnp.int32, (block_t, block_r), 0)
            o_ref[...] += _product(mine, weight_ref[0][...] if weighted else None,
                                   row_ref[...])

    by_row = pl.BlockSpec((1, block_r), lambda j, g, blocks, row_blocks, n: (0, row_blocks[g]))
    operands = (key,) + ((weight,) if weighted else ()) + (rows,)
    return pl.pallas_call(
        kernel,
        out_shape=out_struct((tokens, d), jnp.float32, *operands),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(d // width, block_of.shape[0]),
            in_specs=[by_row] * (1 + weighted) + [pl.BlockSpec(
                (block_r, width), lambda j, g, blocks, row_blocks, n: (row_blocks[g], j))],
            out_specs=pl.BlockSpec(
                (block_t, width), lambda j, g, blocks, row_blocks, n: (blocks[g], j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=NAME,
    )(block_of, rows_of, count, *operands)


def sum_by_token(rows, token_of, live, tokens: int, weight=None):
    """See the module docstring: ``rows`` (R, D), ``token_of`` and ``live``
    (R,), ``weight`` (R,) or None; (tokens, D) in fp32."""
    return _sum(rows, token_of, live, weight, tokens=tokens, interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("tokens", "interpret"))
def _sum(rows, token_of, live, weight, tokens: int, interpret: bool):
    """``sum_by_token``, jitted on its shapes: traced once a shape and
    weighting, its kernel lowered once a program."""
    n, d = rows.shape
    tiles = _tiles(tokens, n, d, rows.dtype.itemsize)
    key = jnp.where(live, token_of.astype(jnp.int32), _DEAD)
    key, order = jax.lax.sort((key, jnp.arange(n, dtype=jnp.int32)), num_keys=1)
    pad = -n % tiles.rows
    key = jnp.pad(key, (0, pad), constant_values=_DEAD)
    order = jnp.pad(order, (0, pad))
    if weight is not None:
        weight = weight.astype(jnp.float32)[order][None]
    return _kernel(*_pairs(key, tokens, tiles), key[None], weight, rows[order],
                   tokens, tiles, interpret)
