"""Grouped matrix product — the experts of a routed layer in one call.

``grouped_matmul(lhs, rhs, group_sizes)``: ``lhs`` is ``(m, k)`` with its rows
sorted by group, ``rhs`` ``(g, k, n)`` one matrix a group, ``group_sizes``
``(g,)`` int32; rows ``[sum(sizes[:i]), sum(sizes[:i + 1]))`` meet ``rhs[i]``
and the rows past ``sum(group_sizes)`` come out as zeros, with zero
gradients. ``m`` is static, the sizes are data. The routed layer
(``parallel/moe.py``) calls it over one pass's rows, its static bound of twice
the balanced expectation of held pairs (32,768 rows in the benchmark's SDAR
cell, not the 131,072 pairs there are), with sizes that cover every row: the
rows after the held pairs belong to the last held group, so the group of rows
that no held group owns is empty there.

On TPU this is the Pallas kernel jax ships (``jax.experimental.pallas.ops.tpu
.megablox``: a grid over the row tiles that hold a group's rows, found from
the sizes by scalar prefetch; its backward is the same kernel with ``rhs``
transposed and ``tgmm`` for the weights), called under the scope
``bigdl_gmm`` so that a profile finds it. Off TPU (the tests) it is
``jax.lax.ragged_dot``, the plain form; ``force_pallas=True`` runs the kernel
through the interpreter. On TPU a kernel that does not build raises.

The other candidate, ``jax.lax.ragged_dot`` on the chip, was read beside it at
the benchmark's SDAR shapes (PERF.md, PR 29) and not kept.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bigdl_tpu.kernels.layernorm import _on_tpu

SCOPE = "bigdl_gmm"

# The kernel's tiles (rows, contraction, columns), the best of two sweeps on a
# v5e at the benchmark's SDAR shapes (2048 x 1536 and 768 x 2048, 16 groups):
# at 131,072 rows (PERF.md, PR 29) and at a pass's 32,768 rows, about 1,024
# live rows a group and the rest in the last (PR 30: (128, 2048, 768) and
# (512, 2048, 512) within 3%, 512 or more rows by 768 columns or more out of
# VMEM). A group's first and last row tiles are shared with its neighbours
# and computed once for each, so a tile well under a group's rows wastes
# least; the whole contraction in one tile saves the accumulator's round trips.
_TILE_M, _TILE_K, _TILE_N = 256, 2048, 768


def _reference(lhs, rhs, group_sizes):
    """``ragged_dot`` with the rows past the groups masked going in and coming
    out: it promises nothing of them, in its result or in its gradient (on
    the chip the rows' gradient read garbage there; PERF.md, PR 29)."""
    held = (jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes))[:, None]
    out = jax.lax.ragged_dot(jnp.where(held, lhs, 0), rhs,
                             group_sizes.astype(jnp.int32),
                             preferred_element_type=jnp.float32)
    return jnp.where(held, out, 0.0).astype(lhs.dtype)


def _tile_m(m: int) -> int | None:
    """Rows of a tile: the kernel wants them to divide the rows."""
    return next((tile for tile in (_TILE_M, 128, 64, 32, 16, 8) if m % tile == 0),
                None)


def grouped_matmul(lhs, rhs, group_sizes, force_pallas: bool | None = None):
    """See the module docstring. The result has ``lhs``'s dtype (products
    accumulate in fp32)."""
    use_pallas = _on_tpu() if force_pallas is None else force_pallas
    m, k = lhs.shape
    n = rhs.shape[2]
    tile_m = _tile_m(m)
    if not use_pallas or tile_m is None:
        return _reference(lhs, rhs, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    # the rows no held group owns are one more group, which `rhs` does not
    # hold: the kernel leaves their tiles out and writes zeros there
    sizes = group_sizes.astype(jnp.int32)
    sizes = jnp.concatenate([sizes, (m - jnp.sum(sizes))[None]])
    with jax.named_scope(SCOPE):
        return gmm(lhs, rhs, sizes, lhs.dtype,
                   (tile_m, min(_TILE_K, k), min(_TILE_N, n)),
                   jnp.zeros((), jnp.int32), None, False, not _on_tpu())
