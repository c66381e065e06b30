"""Normalization + regularisation layers.

Reference parity (SURVEY.md §2.1, expected ``<dl>/nn/BatchNormalization.scala``,
``SpatialBatchNormalization.scala``, ``Dropout.scala``, ``SpatialCrossMapLRN.scala``,
``Normalize.scala`` — unverified, mount empty): BatchNorm keeps running mean/var with
``momentum`` mixing (Torch convention: ``running = (1-momentum)*running + momentum*batch``),
normalises with biased batch variance in training and running stats in eval; affine
weight/bias optional. Dropout scales by ``1/(1-p)`` at train time.

TPU-native design: running stats are non-trainable buffers in the module ``state`` pytree —
the trainer threads them through the jitted step functionally, so there is no mutable-buffer
aliasing problem under ``jit``. Batch stats are computed per *program*: under plain
``jit`` over a mesh the global-batch reduction XLA emits matches the full-batch statistics,
and per-replica statistics (the reference's per-core BN, SURVEY.md §7.4) arise only inside
``shard_map`` bodies — there, ``BatchNormalization(sync=True)`` pmean's the batch moments
over the named mesh axis for global-batch statistics (tests/test_sync_batchnorm.py).

Dropout randomness comes from the ``rng`` key threaded by the trainer (per-step
``fold_in``; on a mesh XLA splits the key per shard automatically since the mask is computed
on the sharded activation shape).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.abstractnn import TensorModule
from bigdl_tpu.nn.initialization import InitializationMethod, Ones, RandomUniform, Zeros


class BatchNormalization(TensorModule):
    """BN over the feature axis of (N, F) input (reference ``nn.BatchNormalization``)."""

    _feature_axis = 1  # axis holding n_output; reduce over all other axes

    def __init__(self, n_output: int, eps: float = 1e-5, momentum: float = 0.1,
                 affine: bool = True,
                 init_weight: Optional[InitializationMethod] = None,
                 init_bias: Optional[InitializationMethod] = None,
                 sync: bool = False, sync_axis: str = "data"):
        super().__init__()
        self.n_output = n_output
        self.eps = eps
        self.momentum = momentum
        self.affine = affine
        self.init_weight = init_weight or RandomUniform(0.0, 1.0)
        self.init_bias = init_bias or Zeros()
        # Cross-replica sync-BN (SURVEY.md §7.4): with sync=True, batch
        # statistics are pmean'd over the named mesh axis, so per-shard batches
        # normalise with GLOBAL-batch statistics. Only meaningful inside a
        # shard_map body where `sync_axis` is bound (parallel/sharding.py); the
        # plain SPMD-jit DistriOptimizer path already computes global-batch
        # statistics by construction (the reduce spans the whole logical batch).
        # Default False = per-program stats (reference's per-worker BN).
        self.sync = sync
        self.sync_axis = sync_axis
        self.reset()

    def reset(self) -> None:
        n = self.n_output
        if self.affine:
            self._params = {
                "weight": jnp.asarray(self.init_weight.init((n,), n, n)),
                "bias": jnp.asarray(self.init_bias.init((n,), n, n)),
            }
        else:
            self._params = {}
        self._state = {
            "running_mean": jnp.zeros((n,), jnp.float32),
            "running_var": jnp.ones((n,), jnp.float32),
        }
        self.zero_grad_parameters()

    def _reduce_axes(self, x):
        return tuple(a for a in range(x.ndim) if a != self._feature_axis)

    def _bshape(self, x):
        return tuple(self.n_output if a == self._feature_axis else 1
                     for a in range(x.ndim))

    def apply(self, params, state, input, *, training=False, rng=None):
        x = input
        axes = self._reduce_axes(x)
        shape = self._bshape(x)
        # fp32 island under mixed precision: batch statistics are reductions over
        # the whole batch — computing them in bf16 loses ~3 decimal digits (and
        # measures SLOWER on v5e: the converts break the conv-epilogue fusion),
        # and the running buffers are fp32 masters anyway. Normalisation happens
        # in fp32; only the (cheap, fusable) elementwise tail is cast back.
        x32 = x.astype(jnp.float32) if x.dtype != jnp.float32 else x
        if training:
            import os
            if os.environ.get("BIGDL_BN_TWO_PASS", "0") == "1":
                # torch-exact accumulation order (centered two-pass variance);
                # raw second moment reconstructed only if sync needs it
                mean = jnp.mean(x32, axis=axes)
                var = jnp.var(x32, axis=axes)  # biased (Torch)
                mean2 = var + jnp.square(mean) if self.sync else None
            else:
                # Default: single-pass statistics (flax-style E[x^2]-E[x]^2 in
                # fp32) — one read of the activation instead of two. Worth ~10%
                # end-to-end on ResNet-50/v5e because both moments fuse into the
                # producing conv's epilogue (docs/performance.md, round 4).
                mean = jnp.mean(x32, axis=axes)
                mean2 = jnp.mean(jnp.square(x32), axis=axes)
            if self.sync:
                # global-batch statistics across the named mesh axis; combining
                # raw moments (not variances) is exact for equal shard sizes
                mean, mean2 = jax.lax.pmean((mean, mean2), self.sync_axis)
            if mean2 is not None:
                var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
            n = x.size // self.n_output
            if self.sync:
                n = n * jax.lax.axis_size(self.sync_axis)  # static axis size
            unbiased = var * (n / max(n - 1, 1))
            m = self.momentum
            new_state = {
                "running_mean": (1 - m) * state["running_mean"] + m * mean,
                "running_var": (1 - m) * state["running_var"] + m * unbiased,
            }
        else:
            mean, var = state["running_mean"], state["running_var"]
            new_state = state
        inv = jax.lax.rsqrt(var + self.eps).reshape(shape)
        out = (x32 - mean.reshape(shape)) * inv
        if self.affine:
            w = params["weight"].astype(jnp.float32)
            b = params["bias"].astype(jnp.float32)
            out = out * w.reshape(shape) + b.reshape(shape)
        return out.astype(x.dtype), new_state

    def __repr__(self):
        return f"{type(self).__name__}({self.n_output})"


class LayerNorm(TensorModule):
    """LayerNorm over the last axis, served by the fused Pallas kernel on TPU
    (kernels/layernorm.py) and the jnp reference elsewhere. Not in the
    reference's zoo (pre-dates it) — provided for the attention stack."""

    def __init__(self, n_output: int, eps: float = 1e-5):
        super().__init__()
        self.n_output = n_output
        self.eps = eps
        self.reset()

    def reset(self) -> None:
        self._params = {"weight": jnp.ones((self.n_output,), jnp.float32),
                        "bias": jnp.zeros((self.n_output,), jnp.float32)}
        self.zero_grad_parameters()

    def apply(self, params, state, input, *, training=False, rng=None):
        from bigdl_tpu.kernels import fused_layer_norm
        return fused_layer_norm(input, params["weight"], params["bias"],
                                self.eps), state

    def __repr__(self):
        return f"LayerNorm({self.n_output})"


class RMSNorm(TensorModule):
    """Root-mean-square norm over the last axis (no centering, no bias) —
    the llama-family LayerNorm variant; one fewer reduction pass than
    LayerNorm, which is exactly the kind of HBM saving that matters on TPU.
    No reference counterpart (pre-dates it); pairs with the transformer
    stack's ``norm="rms"`` option."""

    def __init__(self, n_output: int, eps: float = 1e-6):
        super().__init__()
        self.n_output = n_output
        self.eps = eps
        self.reset()

    def reset(self) -> None:
        self._params = {"weight": jnp.ones((self.n_output,), jnp.float32)}
        self.zero_grad_parameters()

    def apply(self, params, state, input, *, training=False, rng=None):
        ms = jnp.mean(jnp.square(input.astype(jnp.float32)), axis=-1,
                      keepdims=True)
        out = input * jax.lax.rsqrt(ms + self.eps).astype(input.dtype)
        return out * params["weight"], state

    def __repr__(self):
        return f"RMSNorm({self.n_output}, eps={self.eps})"


class SpatialBatchNormalization(BatchNormalization):
    """BN over the channel axis of spatial input (reference
    ``nn.SpatialBatchNormalization``; channel axis follows ``nn.layout``)."""

    def folded_scale_shift(self, params, state):
        """Per-channel (scale, shift) with ``bn(y) == y*scale + shift`` under
        the running statistics — what the conv-bn fusion kernel folds into
        the adjacent conv's weights (kernels/conv_bn.py)."""
        from bigdl_tpu.kernels.conv_bn import fold_bn_scale_shift
        return fold_bn_scale_shift(params, state, self.eps)

    def _reduce_axes(self, x):
        from bigdl_tpu.nn import layout
        ca = layout.channel_axis(x.ndim)
        return tuple(a for a in range(x.ndim) if a != ca)

    def _bshape(self, x):
        from bigdl_tpu.nn import layout
        ca = layout.channel_axis(x.ndim)
        return tuple(self.n_output if a == ca else 1 for a in range(x.ndim))


class Dropout(TensorModule):
    """Inverted dropout (reference ``nn.Dropout``: ``initP`` keep-drop prob, scale)."""

    def __init__(self, init_p: float = 0.5, inplace: bool = False, scale: bool = True):
        super().__init__()
        if not 0.0 <= init_p < 1.0:
            raise ValueError("dropout probability must be in [0, 1)")
        self.p = init_p
        self.scale = scale

    def needs_rng(self) -> bool:
        return True

    def apply(self, params, state, input, *, training=False, rng=None):
        if not training or self.p == 0.0:
            return input, state
        keep = 1.0 - self.p
        mask = jax.random.bernoulli(rng, keep, input.shape)
        out = jnp.where(mask, input, 0.0)
        if self.scale:
            out = out / keep
        return out, state

    def set_p(self, p: float) -> "Dropout":
        if not 0.0 <= p < 1.0:
            raise ValueError("dropout probability must be in [0, 1)")
        self.p = p
        self._apply_cache = {}  # p is baked into the jit trace — invalidate
        return self

    def __repr__(self):
        return f"Dropout({self.p})"


class SpatialDropout2D(TensorModule):
    """Drop whole channels of spatial input (reference ``nn.SpatialDropout2D``;
    channel axis follows ``nn.layout``)."""

    def __init__(self, init_p: float = 0.5):
        super().__init__()
        self.p = init_p

    def needs_rng(self) -> bool:
        return True

    def apply(self, params, state, input, *, training=False, rng=None):
        if not training or self.p == 0.0:
            return input, state
        from bigdl_tpu.nn import layout
        keep = 1.0 - self.p
        ca = layout.channel_axis(input.ndim)
        mask_shape = tuple(
            input.shape[a] if a == ca or (a == 0 and input.ndim == 4) else 1
            for a in range(input.ndim))
        mask = jax.random.bernoulli(rng, keep, mask_shape)
        return jnp.where(mask, input / keep, 0.0), state


class GaussianDropout(TensorModule):
    """Multiplicative unit-mean gaussian noise (reference ``nn.GaussianDropout``)."""

    def __init__(self, rate: float):
        super().__init__()
        self.rate = rate

    def needs_rng(self) -> bool:
        return True

    def apply(self, params, state, input, *, training=False, rng=None):
        if not training or self.rate == 0.0:
            return input, state
        stddev = jnp.sqrt(self.rate / (1.0 - self.rate))
        noise = 1.0 + stddev * jax.random.normal(rng, input.shape)
        return input * noise, state


class GaussianNoise(TensorModule):
    """Additive zero-mean gaussian noise (reference ``nn.GaussianNoise``)."""

    def __init__(self, stddev: float):
        super().__init__()
        self.stddev = stddev

    def needs_rng(self) -> bool:
        return True

    def apply(self, params, state, input, *, training=False, rng=None):
        if not training:
            return input, state
        return input + self.stddev * jax.random.normal(rng, input.shape), state


class SpatialCrossMapLRN(TensorModule):
    """Local response normalisation across channels (reference ``nn.SpatialCrossMapLRN``;
    used by Inception-v1/AlexNet-era models).

    ``out = x / (k + alpha/size * sum_{size local channels} x^2) ** beta``

    TPU-native: the windowed channel sum is one ``reduce_window`` — XLA fuses the whole
    expression; no im2col-style workspace needed.
    """

    def __init__(self, size: int = 5, alpha: float = 1.0, beta: float = 0.75,
                 k: float = 1.0):
        super().__init__()
        self.size = size
        self.alpha = alpha
        self.beta = beta
        self.k = k

    def apply(self, params, state, input, *, training=False, rng=None):
        sq = jnp.square(input)
        # Windowed sum over the channel axis of NCHW; Torch pads size//2 before and
        # (size-1)//2 after, which matters for even window sizes. Formulated as a banded
        # C×C 0/1 matmul: the asymmetric window is exact in the band itself (no padding
        # arithmetic to get wrong next to a fused conv), the op lands on the MXU, and it
        # is layout-agnostic — only the einsum's channel letter moves between NCHW/NHWC.
        from bigdl_tpu.nn import layout
        pre, post = self.size // 2, (self.size - 1) // 2
        c = sq.shape[layout.channel_axis(sq.ndim)]
        idx = jnp.arange(c)
        # band[i, j] = 1 where channel i falls in j's window [j - pre, j + post]
        band = ((idx[:, None] >= idx[None, :] - pre)
                & (idx[:, None] <= idx[None, :] + post)).astype(sq.dtype)
        eq = "nhwi,ij->nhwj" if layout.is_nhwc() else "nihw,ij->njhw"
        summed = jnp.einsum(eq, sq, band)
        denom = jnp.power(self.k + (self.alpha / self.size) * summed, self.beta)
        return input / denom, state

    def __repr__(self):
        return (f"SpatialCrossMapLRN({self.size}, {self.alpha}, "
                f"{self.beta}, {self.k})")


class Normalize(TensorModule):
    """Lp-normalise over the feature axis (reference ``nn.Normalize``)."""

    def __init__(self, p: float = 2.0, eps: float = 1e-10):
        super().__init__()
        self.p = p
        self.eps = eps

    def apply(self, params, state, input, *, training=False, rng=None):
        if self.p == float("inf"):
            norm = jnp.max(jnp.abs(input), axis=1, keepdims=True)
        else:
            norm = jnp.power(
                jnp.sum(jnp.power(jnp.abs(input), self.p), axis=1, keepdims=True),
                1.0 / self.p)
        return input / (norm + self.eps), state


class CMul(TensorModule):
    """Learnable per-element scale broadcast over the input (reference ``nn.CMul``)."""

    def __init__(self, size: tuple[int, ...]):
        super().__init__()
        self.size = tuple(size)
        self.reset()

    def reset(self) -> None:
        import numpy as np
        fan_in = int(np.prod(self.size))
        self._params = {"weight": jnp.asarray(
            RandomUniform().init(self.size, fan_in, fan_in))}
        self.zero_grad_parameters()

    def apply(self, params, state, input, *, training=False, rng=None):
        return input * params["weight"], state


class CAdd(TensorModule):
    """Learnable per-element bias broadcast over the input (reference ``nn.CAdd``)."""

    def __init__(self, size: tuple[int, ...]):
        super().__init__()
        self.size = tuple(size)
        self.reset()

    def reset(self) -> None:
        import numpy as np
        fan_in = int(np.prod(self.size))
        self._params = {"bias": jnp.asarray(
            RandomUniform().init(self.size, fan_in, fan_in))}
        self.zero_grad_parameters()

    def apply(self, params, state, input, *, training=False, rng=None):
        return input + params["bias"], state


class Mul(TensorModule):
    """Single learnable scalar gain (reference ``nn.Mul``)."""

    def __init__(self):
        super().__init__()
        self.reset()

    def reset(self) -> None:
        self._params = {"weight": jnp.asarray(RandomUniform().init((1,), 1, 1))}
        self.zero_grad_parameters()

    def apply(self, params, state, input, *, training=False, rng=None):
        return input * params["weight"][0], state


class Add(TensorModule):
    """Learnable bias vector added to (N, F) input (reference ``nn.Add``)."""

    def __init__(self, input_size: int):
        super().__init__()
        self.input_size = input_size
        self.reset()

    def reset(self) -> None:
        self._params = {"bias": jnp.asarray(
            RandomUniform().init((self.input_size,), self.input_size, self.input_size))}
        self.zero_grad_parameters()

    def apply(self, params, state, input, *, training=False, rng=None):
        return input + params["bias"], state


class SpatialWithinChannelLRN(TensorModule):
    """Within-channel local response normalisation (reference
    ``SpatialWithinChannelLRN``; Caffe WITHIN_CHANNEL mode):
    ``out = x / (1 + alpha/size^2 * sum_{size x size window} x^2) ** beta``
    per channel, SAME spatial padding. One ``reduce_window`` — XLA fuses it."""

    def __init__(self, size: int = 5, alpha: float = 1.0, beta: float = 0.75):
        super().__init__()
        if size % 2 == 0:
            raise ValueError("LRN window size must be odd")
        self.size, self.alpha, self.beta = size, alpha, beta

    def apply(self, params, state, input, *, training=False, rng=None):
        x = input
        squeeze = x.ndim == 3
        if squeeze:
            x = x[None]
        sq = jnp.square(x)
        s = self.size
        window = (1, 1, s, s)
        sums = jax.lax.reduce_window(
            sq, 0.0, jax.lax.add, window, (1, 1, 1, 1), "SAME")
        denom = (1.0 + (self.alpha / (s * s)) * sums) ** self.beta
        out = x / denom
        if squeeze:
            out = out[0]
        return out, state


def _check_odd_kernel(kernel, who: str) -> None:
    kh, kw = kernel.shape
    if kh % 2 == 0 or kw % 2 == 0:
        raise ValueError(
            f"{who}: kernel must have odd dimensions for SAME-centered "
            f"neighborhoods, got {kh}x{kw}")


def _neighborhood_mean(x, kernel, channels):
    """Border-corrected weighted neighborhood mean over ALL channels of NCHW
    ``x``: conv with the (normalised) kernel summed across channels, divided by
    the conv of ones (edge correction), giving a (N, 1, H, W) mean map."""
    kh, kw = kernel.shape
    k = (kernel / (kernel.sum() * channels)).astype(x.dtype)
    w = jnp.broadcast_to(k[None, None], (1, channels, kh, kw))
    pad = [(kh // 2, kh // 2), (kw // 2, kw // 2)]
    mean = jax.lax.conv_general_dilated(
        x, w, (1, 1), pad, dimension_numbers=("NCHW", "OIHW", "NCHW"))
    ones = jnp.ones_like(x)
    coef = jax.lax.conv_general_dilated(
        ones, w, (1, 1), pad, dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return mean / coef


class SpatialSubtractiveNormalization(TensorModule):
    """Subtract the weighted neighborhood mean (reference
    ``SpatialSubtractiveNormalization(nInputPlane, kernel)``; lua-torch
    semantics with border coefficient correction). Default kernel: 9x9 ones."""

    def __init__(self, n_input_plane: int = 1, kernel=None):
        super().__init__()
        self.n_input_plane = n_input_plane
        import numpy as _np
        self.kernel = _np.asarray(
            kernel if kernel is not None else _np.ones((9, 9)), _np.float32)
        if self.kernel.ndim == 1:  # separable 1-D kernel → outer product
            self.kernel = _np.outer(self.kernel, self.kernel).astype(_np.float32)
        _check_odd_kernel(self.kernel, "SpatialSubtractiveNormalization")

    def apply(self, params, state, input, *, training=False, rng=None):
        x = input
        squeeze = x.ndim == 3
        if squeeze:
            x = x[None]
        mean = _neighborhood_mean(x, jnp.asarray(self.kernel), self.n_input_plane)
        out = x - mean  # (N,1,H,W) broadcasts over channels
        if squeeze:
            out = out[0]
        return out, state


class SpatialDivisiveNormalization(TensorModule):
    """Divide by the local std-dev estimate (reference
    ``SpatialDivisiveNormalization``). With ``threshold`` given, lua-torch
    Threshold semantics: stds <= threshold are replaced by ``thresval``
    (default = threshold). Without it, the divisor is floored by its
    per-sample mean — a robust default for zero-variance regions."""

    def __init__(self, n_input_plane: int = 1, kernel=None,
                 threshold: float | None = None, thresval: float | None = None):
        super().__init__()
        self.n_input_plane = n_input_plane
        import numpy as _np
        self.kernel = _np.asarray(
            kernel if kernel is not None else _np.ones((9, 9)), _np.float32)
        if self.kernel.ndim == 1:
            self.kernel = _np.outer(self.kernel, self.kernel).astype(_np.float32)
        _check_odd_kernel(self.kernel, "SpatialDivisiveNormalization")
        self.threshold = threshold
        self.thresval = thresval if thresval is not None else threshold

    def apply(self, params, state, input, *, training=False, rng=None):
        x = input
        squeeze = x.ndim == 3
        if squeeze:
            x = x[None]
        var = _neighborhood_mean(jnp.square(x), jnp.asarray(self.kernel),
                                 self.n_input_plane)
        localstd = jnp.sqrt(jnp.maximum(var, 0.0))            # (N,1,H,W)
        if self.threshold is not None:
            divisor = jnp.where(localstd > self.threshold, localstd,
                                self.thresval)
        else:
            floor = jnp.mean(localstd, axis=(1, 2, 3), keepdims=True)
            divisor = jnp.maximum(localstd, floor)
        out = x / divisor
        if squeeze:
            out = out[0]
        return out, state


class SpatialContrastiveNormalization(TensorModule):
    """Subtractive then divisive normalisation (reference
    ``SpatialContrastiveNormalization``)."""

    def __init__(self, n_input_plane: int = 1, kernel=None,
                 threshold: float | None = None, thresval: float | None = None):
        super().__init__()
        self.sub = SpatialSubtractiveNormalization(n_input_plane, kernel)
        self.div = SpatialDivisiveNormalization(n_input_plane, kernel,
                                                threshold, thresval)

    def apply(self, params, state, input, *, training=False, rng=None):
        out, _ = self.sub.apply({}, {}, input, training=training, rng=None)
        out, _ = self.div.apply({}, {}, out, training=training, rng=None)
        return out, state


class SpatialDropout1D(TensorModule):
    """Drop whole feature channels of (N, T, C) input (reference
    ``SpatialDropout1D``; keras temporal convention)."""

    def __init__(self, init_p: float = 0.5):
        super().__init__()
        self.p = init_p

    def needs_rng(self) -> bool:
        return True

    def apply(self, params, state, input, *, training=False, rng=None):
        if not training or self.p == 0.0:
            return input, state
        keep = 1.0 - self.p
        shape = (input.shape[0], 1, input.shape[-1]) if input.ndim == 3 \
            else (1, input.shape[-1])
        mask = jax.random.bernoulli(rng, keep, shape)
        return jnp.where(mask, input / keep, 0.0), state


class SpatialDropout3D(TensorModule):
    """Drop whole channels of NCDHW input (reference ``SpatialDropout3D``)."""

    def __init__(self, init_p: float = 0.5):
        super().__init__()
        self.p = init_p

    def needs_rng(self) -> bool:
        return True

    def apply(self, params, state, input, *, training=False, rng=None):
        if not training or self.p == 0.0:
            return input, state
        keep = 1.0 - self.p
        mask_shape = input.shape[:2] + (1,) * (input.ndim - 2)
        mask = jax.random.bernoulli(rng, keep, mask_shape)
        return jnp.where(mask, input / keep, 0.0), state
