"""Mamba-2's mixer: a selective state space layer computed by the chunked
"state space duality" algorithm (SSD; Dao and Gu, arXiv:2405.21060), as the
Nemotron-H family runs it between its attention and expert layers.

Per head ``i`` of ``head_dim`` channels, which reads group ``i // (heads /
groups)`` of ``B`` and ``C`` (``state_size`` wide each), from ``S_0 = 0``:

    S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t B_tᵀ          (head_dim x state_size)
    y_t = S_t C_t + D x_t

``ssd_scan`` computes the recurrence in chunks of ``chunk`` positions. Inside
a chunk it is ``C Bᵀ`` masked by the decays (the segment sums of ``Δ A``)
times ``Δ x``. Across chunks it is the recurrence over the chunk states, one
step a chunk (``lax.scan``). Decays, ``Δ`` and the states carried between
chunks are fp32; the products take their operands in the input's type with
fp32 accumulation. It is plain XLA, its backward by autodiff.

``Mamba2Mixer`` is the whole mixer over ``(N, T, hidden)``:

    [z ; xBC ; dt] = x W_in                              (no bias)
    xBC = SiLU(causal depthwise conv_K(xBC) + b)         split into x, B, C
    Δ = softplus(dt + dt_bias),  A = -exp(A_log)
    y = ssd_scan(x, Δ, A, B, C) + D x
    out = RMSNorm_grouped(y * SiLU(z)) W_out             (over each group's channels)

Its width is ``num_heads * head_dim`` with ``n_groups`` groups, not an
``expand`` times the hidden size. So a share of a layer's heads, with the
groups they read, is the same module at smaller numbers, and the shares'
outputs add up to the whole layer's (``tests/test_mamba.py``). The whole mixer
runs under the scope ``bigdl_mamba``, the chunked scan under
``bigdl_ssm_scan`` (``obs/trace.py``). The input projection's result is
tagged ``mamba_in`` (``MAMBA_NAMES``): a ``jax.checkpoint`` whose policy keeps
that name does not make the projection again in the backward pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bigdl_tpu.nn.abstractnn import TensorModule
from bigdl_tpu.obs import trace

#: What a rematerialised mixer may keep by name: ``[z ; xBC ; dt]``, the
#: input projection's result.
MAMBA_NAMES = ("mamba_in",)


def ssd_scan(x, dt, a, b, c, chunk: int):
    """``y_t = S_t C_t`` of the recurrence above, without the ``D`` term, in
    fp32. ``x`` (N, T, H, P); ``dt`` (N, T, H), Δ after its softplus; ``a``
    (H,), A (negative); ``b``, ``c`` (N, T, G, S). ``T`` need not be a
    multiple of ``chunk``: the tail is padded with Δ = 0, which neither
    decays nor feeds the state."""
    f32 = jnp.float32
    n, t, h, p = x.shape
    g, s = b.shape[2:]
    r = h // g
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, b, c))
    k, dtype = (t + pad) // chunk, x.dtype
    dt = dt.astype(f32).reshape(n, k, chunk, g, r)
    b = b.reshape(n, k, chunk, g, s)
    c = c.reshape(n, k, chunk, g, s)
    cum = jnp.cumsum(dt * a.astype(f32).reshape(g, r), axis=2)   # (n, k, L, g, r)
    xdt = x.astype(f32).reshape(n, k, chunk, g, r, p) * dt[..., None]
    # inside a chunk: y_l = sum_{m <= l} exp(cum_l - cum_m) (C_l . B_m) dt_m x_m
    heads_first = jnp.moveaxis(cum, 2, -1)                        # (n, k, g, r, L)
    live = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        live, heads_first[..., :, None] - heads_first[..., None, :], -jnp.inf))
    cb = jnp.einsum("nklgs,nkmgs->nkglm", c, b, preferred_element_type=f32)
    y = jnp.einsum("nkgrlm,nkmgrp->nklgrp", (cb[:, :, :, None] * decay).astype(dtype),
                   xdt.astype(dtype), preferred_element_type=f32)
    # each chunk's own state at its end, then the recurrence over the chunks:
    # the state entering chunk j+1 is the one entering j, decayed over j, plus j's
    to_end = jnp.exp(cum[:, :, -1:] - cum)
    own = jnp.einsum("nklgs,nklgrp->nkgrps", b, (xdt * to_end[..., None]).astype(dtype),
                     preferred_element_type=f32)

    def step(state, chunk_of):
        whole, new = chunk_of
        return whole[..., None, None] * state + new, state

    _, entering = jax.lax.scan(
        step, jnp.zeros_like(own[:, 0]),
        (jnp.exp(cum[:, :, -1]).swapaxes(0, 1), own.swapaxes(0, 1)))
    y = y + jnp.einsum("nklgs,nkgrps->nklgrp", c, entering.swapaxes(0, 1).astype(dtype),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    return y.reshape(n, k * chunk, h, p)[:, :t]


class Mamba2Mixer(TensorModule):
    """Input (N, T, hidden) → (N, T, hidden); see the module docstring.

    Parameters (by name): ``in`` (hidden, 2 I + 2 G S + H) holding ``W_z``,
    ``W_x``, ``W_B``, ``W_C``, ``W_dt`` side by side (I = heads x head_dim);
    ``conv_w`` (K, I + 2 G S), tap ``K - 1`` on the current position;
    ``conv_b`` (I + 2 G S,); ``dt_bias``, ``a_log``,
    ``d_skip`` (H,); ``norm`` (I,), the gated norm's gain; ``out`` (I,
    hidden). ``draw`` is the family's initialisation of each."""

    def __init__(self, hidden_size: int, num_heads: int, head_dim: int,
                 n_groups: int, state_size: int, conv_kernel: int = 4,
                 chunk_size: int = 128, norm_eps: float = 1e-5,
                 initializer_range: float = 0.02,
                 time_step_min: float = 0.001, time_step_max: float = 0.1,
                 time_step_floor: float = 1e-4):
        super().__init__()
        if num_heads % n_groups:
            raise ValueError(f"{num_heads} heads do not split into {n_groups} groups")
        self.hidden_size, self.num_heads = int(hidden_size), int(num_heads)
        self.head_dim, self.n_groups = int(head_dim), int(n_groups)
        self.state_size, self.conv_kernel = int(state_size), int(conv_kernel)
        self.chunk_size, self.norm_eps = int(chunk_size), float(norm_eps)
        self.initializer_range = float(initializer_range)
        self.time_step = (float(time_step_min), float(time_step_max),
                          float(time_step_floor))
        self.reset()

    @property
    def inner(self) -> int:
        return self.num_heads * self.head_dim

    def shapes(self) -> dict:
        d, i, h = self.hidden_size, self.inner, self.num_heads
        conv = i + 2 * self.n_groups * self.state_size
        return {"in": (d, i + conv + h), "conv_w": (self.conv_kernel, conv),
                "conv_b": (conv,), "dt_bias": (h,), "a_log": (h,), "d_skip": (h,),
                "norm": (i,), "out": (i, d)}

    def draw(self, name: str, key, shape) -> jax.Array:
        """Leaf ``name`` at ``shape`` (its own, or stacked with leading
        dims), as the family's code initialises it: ``A`` uniform in [1, 16],
        ``Δ`` log-uniform in [``time_step_min``, ``time_step_max``] floored
        at ``time_step_floor`` (``dt_bias`` its inverse softplus), ``D`` and
        the norm's gain 1, the convolution uniform in ±1/sqrt(K) (PyTorch's
        default for a depthwise ``Conv1d``), the projections N(0,
        ``initializer_range``)."""
        f32 = jnp.float32
        if name == "a_log":
            return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
        if name == "dt_bias":
            lo, hi, floor = self.time_step
            dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(lo), math.log(hi)))
            dt = jnp.maximum(dt, floor)
            return dt + jnp.log(-jnp.expm1(-dt))
        if name in ("d_skip", "norm"):
            return jnp.ones(shape, f32)
        if name in ("conv_w", "conv_b"):
            bound = 1.0 / math.sqrt(self.conv_kernel)
            return jax.random.uniform(key, shape, f32, -bound, bound)
        return self.initializer_range * jax.random.normal(key, shape, f32)

    def reset(self) -> None:
        from bigdl_tpu.utils.random_generator import RandomGenerator
        key = jax.random.PRNGKey(RandomGenerator.next_salt() & 0x7FFFFFFF)
        self._params = {name: self.draw(name, jax.random.fold_in(key, i), shape)
                        for i, (name, shape) in enumerate(sorted(self.shapes().items()))}
        self.zero_grad_parameters()

    def _conv(self, xbc, params):
        """The causal depthwise convolution, fp32: ``sum_j w_j x_{t-K+1+j} + b``."""
        f32, k, t = jnp.float32, self.conv_kernel, xbc.shape[1]
        padded = jnp.pad(xbc.astype(f32), ((0, 0), (k - 1, 0), (0, 0)))
        w = params["conv_w"].astype(f32)
        return sum(w[j] * padded[:, j:j + t] for j in range(k)) + params["conv_b"].astype(f32)

    def apply(self, params, state, input, *, training=False, rng=None):
        f32, dtype = jnp.float32, input.dtype
        n, t, _ = input.shape
        i, h, p = self.inner, self.num_heads, self.head_dim
        g, s = self.n_groups, self.state_size
        with jax.named_scope(trace.SCOPE_MAMBA):
            zxbcdt = checkpoint_name(input @ params["in"].astype(dtype), "mamba_in")
            z, xbc, dt = zxbcdt[..., :i], zxbcdt[..., i:-h], zxbcdt[..., -h:]
            xbc = jax.nn.silu(self._conv(xbc, params)).astype(dtype)
            x = xbc[..., :i].reshape(n, t, h, p)
            b = xbc[..., i:i + g * s].reshape(n, t, g, s)
            c = xbc[..., i + g * s:].reshape(n, t, g, s)
            dt = jax.nn.softplus(dt.astype(f32) + params["dt_bias"].astype(f32))
            a = -jnp.exp(params["a_log"].astype(f32))
            with jax.named_scope(trace.SCOPE_SSM_SCAN):
                y = ssd_scan(x, dt, a, b, c, self.chunk_size)
            y = y + params["d_skip"].astype(f32)[:, None] * x.astype(f32)
            # the gate before the norm, the norm over each group's channels
            y = (y.reshape(n, t, i) * jax.nn.silu(z.astype(f32))).reshape(n, t, g, i // g)
            y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + self.norm_eps)
            y = y.reshape(n, t, i) * params["norm"].astype(f32)
            return y.astype(dtype) @ params["out"].astype(dtype), state

    def __repr__(self):
        return (f"Mamba2Mixer({self.hidden_size}, heads={self.num_heads}x{self.head_dim}, "
                f"groups={self.n_groups}, state={self.state_size}, "
                f"conv={self.conv_kernel}, chunk={self.chunk_size})")


from bigdl_tpu.utils.serializer import register as _register_serializable  # noqa: E402

_register_serializable(Mamba2Mixer)
