"""Pallas TPU kernel — single-chip flash attention.

Complements the multi-chip ring attention (parallel/ring_attention.py): ring
shards the SEQUENCE over mesh devices and rotates K/V over ICI; this kernel is
the intra-chip analog of the same streaming-softmax idea. Plain XLA attention
materialises the (T, T) score matrix in HBM twice (softmax in, probs out);
flash keeps one (block_q, block_k) score tile at a time in VMEM with running
max/sum statistics, so HBM traffic drops from O(T^2) to O(T·d) and the two
matmuls per tile stay on the MXU.

Grid layout (TPU grids execute sequentially, innermost-last): (batch*heads,
q_blocks, k_blocks) with the k-dim innermost; the running (m, l, acc) state
lives in VMEM scratch carried across k iterations, initialised at k==0 and
flushed to the output block at the last k step — the standard Pallas
accumulation pattern.

Semantics: forward AND backward are Pallas kernels on TPU (interpreter
elsewhere — tests). The backward is the standard flash-2 scheme: the forward
additionally saves the per-row logsumexp L = m + log(l); backward recomputes
each (block_q, block_k) probability tile from (q, k, L) in VMEM and streams
  dq += (p * (dO·v^T - D)) · k,   dv += p^T · dO,   dk += ds^T · q
with D = rowsum(dO * O) precomputed in one fused elementwise pass — so
TRAINING memory is O(T·d) too, not just inference (the O(T^2) score matrix is
never materialised in either direction; asserted by test against the compiled
HLO). Off TPU, and at sequence lengths no legal tile covers (``_pick_block``),
the reference jnp attention and its recompute-form VJP run instead. On TPU a
kernel that does not build raises: nothing here catches a build error.

Per-row residuals (logsumexp ``L``, ``D``) cross HBM in the orientation each
kernel broadcasts them in, so no kernel has to move a vector between
sublanes and lanes: a column ``(bh, T, 1)`` for the forward and dq kernels (rows of the
``(block_q, block_k)`` tile), a row ``(bh, 1, T)`` for the dk/dv kernel (its
tile is transposed). Both shapes meet Mosaic's block rule — the last two block
dims divide (8, 128) or span the array — which a ``(1, block_q)`` block over
``(bh, T)`` does not.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bigdl_tpu.kernels.layernorm import _on_tpu, out_struct


def _reference_attention(q, k, v, causal: bool):
    """Plain jnp attention over (..., T, d) — the numerical oracle and VJP."""
    d = q.shape[-1]
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(d, jnp.float32))
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((t_q, t_k), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v.astype(p.dtype)).astype(q.dtype)


def _pallas_flash_call(q3, k3, v3, causal, block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q3.shape
    scale = 1.0 / (d ** 0.5)
    n_k = t // block_k

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr):
        i = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

        # causal block skip: a k-block strictly above the diagonal contributes
        # nothing — skip its two matmuls entirely (halves causal FLOPs)
        live = (j * block_k <= i * block_q + block_q - 1) if causal else True

        @pl.when(live)
        def _step():
            q = q_ref[0].astype(jnp.float32)
            k = k_ref[0].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if causal:
                qi = i * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                kj = j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(kj <= qi, s, -jnp.inf)

            m_prev = m_scr[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            alpha = jnp.where(jnp.isfinite(m_prev),
                              jnp.exp(m_prev - safe_m), 0.0)
            p = jnp.exp(jnp.where(jnp.isfinite(s), s - safe_m, -jnp.inf))
            l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
                p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[:] = m_new

        @pl.when(j == n_k - 1)
        def _flush():
            denom = jnp.maximum(l_scr[:], 1e-37)
            o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
            # per-row logsumexp residual for the flash backward: rows with no
            # live block (cannot happen causally — the diagonal is live) would
            # be -inf; clamp through the same denom guard
            lse_ref[0] = m_scr[:] + jnp.log(denom)

    out, lse = pl.pallas_call(
        kernel,
        out_shape=[out_struct((bh, t, d), q3.dtype, q3, k3, v3),
                   out_struct((bh, t, 1), jnp.float32, q3, k3, v3)],
        grid=(bh, t // block_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="bigdl_flash_fwd",
    )(q3, k3, v3)
    return out, lse


def _pallas_flash_bwd_dq(q3, k3, v3, do3, lse_col, dd_col, causal,
                         block_q, block_k, interpret):
    """dq = Σ_j (p_ij * (dO_i·v_j^T - D_i)) · k_j * scale, streaming over j
    with the probability tile recomputed from (q, k, lse) in VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q3.shape
    scale = 1.0 / (d ** 0.5)
    n_k = t // block_k

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref, acc_scr):
        i = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            acc_scr[:] = jnp.zeros_like(acc_scr)

        live = (j * block_k <= i * block_q + block_q - 1) if causal else True

        @pl.when(live)
        def _step():
            q = q_ref[0].astype(jnp.float32)
            k = k_ref[0].astype(jnp.float32)
            v = v_ref[0].astype(jnp.float32)
            do = do_ref[0].astype(jnp.float32)
            lse = lse_ref[0]                              # (bq, 1)
            dd = dd_ref[0]                                # (bq, 1)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if causal:
                qi = i * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                kj = j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(kj <= qi, s, -jnp.inf)
            p = jnp.exp(s - lse)                          # (bq, bk)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - dd) * scale
            acc_scr[:] = acc_scr[:] + jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(j == n_k - 1)
        def _flush():
            dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=out_struct((bh, t, d), q3.dtype, q3, k3, v3, do3),
        grid=(bh, t // block_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="bigdl_flash_bwd_dq",
    )(q3, k3, v3, do3, lse_col, dd_col)


def _pallas_flash_bwd_dkv(q3, k3, v3, do3, lse_row, dd_row, causal,
                          block_q, block_k, interpret):
    """dv = Σ_i p_ij^T · dO_i ; dk = Σ_i ds_ij^T · q_i * scale — grid iterates
    k-blocks outer, q-blocks inner, with (dk, dv) accumulators in VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q3.shape
    scale = 1.0 / (d ** 0.5)
    n_q = t // block_q

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
               dk_ref, dv_ref, dk_scr, dv_scr):
        j = pl.program_id(1)   # k block
        i = pl.program_id(2)   # q block (innermost)

        @pl.when(i == 0)
        def _init():
            dk_scr[:] = jnp.zeros_like(dk_scr)
            dv_scr[:] = jnp.zeros_like(dv_scr)

        # causal: a q block entirely above this k block contributes nothing
        live = (i * block_q + block_q - 1 >= j * block_k) if causal else True

        @pl.when(live)
        def _step():
            q = q_ref[0].astype(jnp.float32)
            k = k_ref[0].astype(jnp.float32)
            v = v_ref[0].astype(jnp.float32)
            do = do_ref[0].astype(jnp.float32)
            lse = lse_ref[0]                              # (1, bq)
            dd = dd_ref[0]                                # (1, bq)
            # transposed orientation: s_T (bk, bq)
            s_t = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32) * scale
            if causal:
                kj = j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 0)
                qi = i * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 1)
                s_t = jnp.where(kj <= qi, s_t, -jnp.inf)
            p_t = jnp.exp(s_t - lse)                      # (bk, bq)
            dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
                p_t, do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp_t = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                       preferred_element_type=jnp.float32)
            ds_t = p_t * (dp_t - dd) * scale
            dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
                ds_t, q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(i == n_q - 1)
        def _flush():
            dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=[out_struct((bh, t, d), k3.dtype, q3, k3, v3, do3),
                   out_struct((bh, t, d), v3.dtype, q3, k3, v3, do3)],
        grid=(bh, t // block_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),
        ],
        out_specs=[pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
                   pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name="bigdl_flash_bwd_dkv",
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = False,
                    force_pallas: bool | None = None):
    """Streaming-softmax attention over (batch, heads, T, d) operands.

    ``force_pallas``: None = pallas on TPU, reference jnp elsewhere; True =
    pallas (interpreted off-TPU — tests); False = reference. Whatever the
    setting, a ``T`` that ``_pick_block`` cannot tile runs the reference.
    """
    return _fa_fwd(q, k, v, causal, force_pallas)[0]


def _fa_fwd(q, k, v, causal, force_pallas):
    use_pallas = _on_tpu() if force_pallas is None else force_pallas
    b, h, t, d = q.shape
    # the backward's 128 tile is the tightest: a T it covers, the forward's
    # 256/512 targets cover too
    if not use_pallas or _pick_block(t, 128) is None:
        return _reference_attention(q, k, v, causal), (q, k, v, None, None)
    # measured on v5e (T=2048, d=64): 256/512 tiles amortise grid-step
    # overhead ~30% better than 128/128 and beat XLA's fused attention;
    # VMEM stays comfortable (score tile 256x512 fp32 = 512 KB)
    block_q, block_k = _pick_block(t, 256), _pick_block(t, 512)
    out, lse = _pallas_flash_call(
        q.reshape(b * h, t, d), k.reshape(b * h, t, d),
        v.reshape(b * h, t, d), causal, block_q, block_k,
        interpret=not _on_tpu())
    out = out.reshape(b, h, t, d)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, force_pallas, res, g):
    q, k, v, out, lse = res
    if lse is None:
        _, vjp = jax.vjp(
            lambda qq, kk, vv: _reference_attention(qq, kk, vv, causal),
            q, k, v)
        return vjp(g)
    return _flash_bwd(q, k, v, out, lse, g, causal)


def _flash_bwd(q, k, v, out, lse, g, causal):
    """Streaming flash-2 backward: O(T·d) memory, probability tiles recomputed
    from (q, k, lse) in VMEM."""
    b, h, t, d = q.shape
    block_q = block_k = _pick_block(t, 128)   # not None: _fa_fwd checked
    reshape = lambda a: a.reshape(b * h, t, d)
    q3, k3, v3, do3 = reshape(q), reshape(k), reshape(v), reshape(g)
    # D_i = rowsum(dO * O): one fused elementwise pass, O(T·d) reads
    dd = jnp.sum(do3.astype(jnp.float32) * reshape(out).astype(jnp.float32),
                 axis=-1, keepdims=True)                    # (bh, t, 1)
    interp = not _on_tpu()
    dq = _pallas_flash_bwd_dq(q3, k3, v3, do3, lse, dd, causal,
                              block_q, block_k, interp)
    as_row = lambda a: a.reshape(b * h, 1, t)
    dk, dv = _pallas_flash_bwd_dkv(q3, k3, v3, do3, as_row(lse), as_row(dd),
                                   causal, block_q, block_k, interp)
    unshape = lambda a, like: a.reshape(b, h, t, d).astype(like.dtype)
    return unshape(dq, q), unshape(dk, k), unshape(dv, v)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
