"""Pallas TPU kernels — fused LayerNorm.

This is the framework's Pallas layer (SURVEY.md §7.1: "Pallas reserved for true
gaps"): XLA fuses most elementwise chains into adjacent matmuls on its own, but
row-normalisation is a 3-pass pattern (mean, variance, scale) the compiler
sometimes leaves as separate HBM round trips on large rows. The kernel below
does all three passes in one VMEM residency per row-block: a (block_rows, H)
tile is loaded once, reduced on the VPU, normalised, scaled, and written once.

Semantics: forward is the Pallas kernel on TPU (interpreter elsewhere/on CPU
tests); the backward pass is the standard recompute-form VJP in plain jnp —
rematerialisation is the TPU-idiomatic trade (one extra fused forward instead
of stashing normalised activations in HBM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _reference_layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    return (x - mean) * inv * gamma + beta


def out_struct(shape, dtype, *inputs):
    """A kernel output's ``ShapeDtypeStruct``. Under ``shard_map`` (pipeline
    stages, ring attention) ``pallas_call`` must be told which manual mesh
    axes its output varies over: the union of its inputs'. Outside
    ``shard_map`` that set is empty."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in inputs))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _pallas_layer_norm(x2d, gamma, beta, eps, block_rows, interpret):
    from jax.experimental import pallas as pl

    n, h = x2d.shape

    def kernel(x_ref, g_ref, b_ref, o_ref):
        x = x_ref[:].astype(jnp.float32)        # (block_rows, H) in VMEM
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(var + eps)
        o_ref[:] = ((x - mean) * inv * g_ref[:] + b_ref[:]).astype(o_ref.dtype)

    # the reference's promotion (bf16 x under fp32 gamma gives fp32): the
    # backward is the reference's VJP, and its cotangent must have this dtype
    out_dtype = jnp.result_type(x2d.dtype, gamma.dtype, beta.dtype)
    # gamma/beta ride as (1, H) rows: they broadcast down the sublanes of the
    # (block_rows, H) tile with no in-kernel 1-D -> 2-D reshape. A last
    # block that overhangs n reads padding and has its overhang dropped on
    # write; rows are independent, so what the padding holds never matters.
    return pl.pallas_call(
        kernel,
        out_shape=out_struct((n, h), out_dtype, x2d, gamma, beta),
        grid=(pl.cdiv(n, block_rows),),
        in_specs=[
            pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
        interpret=interpret,
        name="bigdl_layer_norm",
    )(x2d, gamma.reshape(1, h), beta.reshape(1, h))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _row_block(n: int, h: int, itemsize: int) -> int:
    """Rows per tile. Mosaic wants a block's second-to-last dim to be a
    multiple of the sublane tile (8 rows of 32-bit, 16 of 16-bit) or to span
    the array, so: the whole array when it has few rows, else up to 256 rows
    rounded down to that multiple, held to ~2 MB of fp32 per tile so wide H
    still fits VMEM double-buffered."""
    sublane = 8 * max(1, 4 // itemsize)
    rows = max(sublane, min(256, (1 << 19) // h) // sublane * sublane)
    return n if n <= rows else rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_layer_norm(x, gamma, beta, eps: float = 1e-5,
                     force_pallas: bool | None = None):
    """LayerNorm over the last axis. ``force_pallas``: None = pallas on TPU,
    reference jnp elsewhere; True = pallas (interpreted off-TPU — tests);
    False = reference. On TPU a kernel that does not build raises."""
    return _fln_fwd(x, gamma, beta, eps, force_pallas)[0]


def _fln_fwd(x, gamma, beta, eps, force_pallas):
    use_pallas = _on_tpu() if force_pallas is None else force_pallas
    if use_pallas:
        h = x.shape[-1]
        x2d = x.reshape(-1, h)
        block = _row_block(x2d.shape[0], h, x.dtype.itemsize)
        out = _pallas_layer_norm(x2d, gamma, beta, eps, block,
                                 interpret=not _on_tpu()).reshape(x.shape)
    else:
        out = _reference_layer_norm(x, gamma, beta, eps)
    return out, (x, gamma, beta)


def _fln_bwd(eps, force_pallas, res, g):
    x, gamma, beta = res
    # recompute-form VJP of the reference formula (rematerialisation)
    _, vjp = jax.vjp(lambda xx, gg, bb: _reference_layer_norm(xx, gg, bb, eps),
                     x, gamma, beta)
    return vjp(g)


fused_layer_norm.defvjp(_fln_fwd, _fln_bwd)
