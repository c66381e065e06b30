"""Misc layer-zoo coverage: reductions, shrink/threshold activations, bilinear
forms, table algebra, upsampling.

Reference parity (SURVEY.md §2.1 layer zoo, expected one file per layer under
``<dl>/nn/`` — unverified, mount empty): these are the small single-op layers
that round out the ~200-layer surface. Each is one fused XLA op (VPU) or one
contraction (MXU); dims follow the reference's 1-based Torch convention.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from bigdl_tpu.nn.abstractnn import AbstractModule, TensorModule
from bigdl_tpu.nn.initialization import (
    InitializationMethod, RandomUniform, Xavier, Zeros,
)
from bigdl_tpu.utils.table import Table


def _axis(dim: int, ndim: int) -> int:
    return dim - 1 if dim > 0 else ndim + dim


class _Reduce(TensorModule):
    def __init__(self, dim: int = 1, n_input_dims: int = -1):
        super().__init__()
        self.dim = dim
        self.n_input_dims = n_input_dims

    def _resolve_axis(self, x) -> int:
        axis = _axis(self.dim, x.ndim)
        # a leading batch dim shifts POSITIVE dims only — negative dims count
        # from the end and are already layout-independent
        if self.dim > 0 and self.n_input_dims > 0 \
                and x.ndim == self.n_input_dims + 1:
            axis += 1
        return axis


class Max(_Reduce):
    """Max over dim (reference ``Max`` — returns values only)."""

    def apply(self, params, state, input, *, training=False, rng=None):
        return jnp.max(input, axis=self._resolve_axis(input)), state


class Min(_Reduce):
    def apply(self, params, state, input, *, training=False, rng=None):
        return jnp.min(input, axis=self._resolve_axis(input)), state


class Mean(_Reduce):
    def apply(self, params, state, input, *, training=False, rng=None):
        return jnp.mean(input, axis=self._resolve_axis(input)), state


class Sum(_Reduce):
    def __init__(self, dim: int = 1, n_input_dims: int = -1,
                 size_average: bool = False):
        super().__init__(dim, n_input_dims)
        self.size_average = size_average

    def apply(self, params, state, input, *, training=False, rng=None):
        axis = self._resolve_axis(input)
        out = jnp.sum(input, axis=axis)
        if self.size_average:
            out = out / input.shape[axis]
        return out, state


class Threshold(TensorModule):
    """``x if x > th else value`` (reference ``Threshold``)."""

    def __init__(self, threshold: float = 1e-6, value: float = 0.0,
                 inplace: bool = False):
        super().__init__()
        self.th, self.value = threshold, value

    def apply(self, params, state, input, *, training=False, rng=None):
        return jnp.where(input > self.th, input, self.value), state


class HardShrink(TensorModule):
    def __init__(self, lam: float = 0.5):
        super().__init__()
        self.lam = lam

    def apply(self, params, state, input, *, training=False, rng=None):
        return jnp.where(jnp.abs(input) > self.lam, input, 0.0), state


class SoftShrink(TensorModule):
    def __init__(self, lam: float = 0.5):
        super().__init__()
        self.lam = lam

    def apply(self, params, state, input, *, training=False, rng=None):
        return (jnp.where(input > self.lam, input - self.lam, 0.0)
                + jnp.where(input < -self.lam, input + self.lam, 0.0)), state


class RReLU(TensorModule):
    """Randomized leaky ReLU: negative slope ~ U(lower, upper) in training,
    the midpoint in eval (torch semantics)."""

    def __init__(self, lower: float = 1.0 / 8, upper: float = 1.0 / 3,
                 inplace: bool = False):
        super().__init__()
        self.lower, self.upper = lower, upper

    def needs_rng(self) -> bool:
        return True

    def apply(self, params, state, input, *, training=False, rng=None):
        if training and rng is not None:
            import jax
            a = jax.random.uniform(rng, input.shape, input.dtype,
                                   self.lower, self.upper)
        else:
            a = (self.lower + self.upper) / 2.0
        return jnp.where(input >= 0, input, a * input), state


class Negative(TensorModule):
    def __init__(self, inplace: bool = False):
        super().__init__()

    def apply(self, params, state, input, *, training=False, rng=None):
        return -input, state


class DotProduct(AbstractModule):
    """Rowwise dot product of a Table pair → (N,)."""

    def apply(self, params, state, input, *, training=False, rng=None):
        xs = input.values() if isinstance(input, Table) else list(input)
        return jnp.sum(xs[0] * xs[1], axis=-1), state


class MM(AbstractModule):
    """Matrix multiply of a Table pair, with optional transposes (reference
    ``MM(transA, transB)``); supports batched (N, a, b) operands."""

    def __init__(self, trans_a: bool = False, trans_b: bool = False):
        super().__init__()
        self.trans_a, self.trans_b = trans_a, trans_b

    def apply(self, params, state, input, *, training=False, rng=None):
        xs = input.values() if isinstance(input, Table) else list(input)
        a, b = xs[0], xs[1]
        if self.trans_a:
            a = jnp.swapaxes(a, -1, -2)
        if self.trans_b:
            b = jnp.swapaxes(b, -1, -2)
        return a @ b, state


class MV(AbstractModule):
    """Matrix-vector product of a Table (matrix, vector) pair (batched OK)."""

    def __init__(self, trans: bool = False):
        super().__init__()
        self.trans = trans

    def apply(self, params, state, input, *, training=False, rng=None):
        xs = input.values() if isinstance(input, Table) else list(input)
        m, v = xs[0], xs[1]
        if self.trans:
            m = jnp.swapaxes(m, -1, -2)
        return jnp.einsum("...ij,...j->...i", m, v), state


class Euclidean(TensorModule):
    """Distance to learnable centers: out[b, o] = ||x[b] - w[o]||_2 (reference
    ``Euclidean(inputSize, outputSize)``)."""

    def __init__(self, input_size: int, output_size: int,
                 w_init: Optional[InitializationMethod] = None):
        super().__init__()
        self.input_size, self.output_size = input_size, output_size
        self.w_init = w_init or RandomUniform()
        self.reset()

    def reset(self) -> None:
        self._params = {"weight": jnp.asarray(
            self.w_init.init((self.output_size, self.input_size),
                             fan_in=self.input_size, fan_out=self.output_size))}
        self.zero_grad_parameters()

    def apply(self, params, state, input, *, training=False, rng=None):
        x = input if input.ndim == 2 else input[None]
        d = x[:, None, :] - params["weight"][None, :, :]
        out = jnp.sqrt(jnp.sum(jnp.square(d), axis=-1) + 1e-12)
        if input.ndim == 1:
            out = out[0]
        return out, state


class Bilinear(AbstractModule):
    """Bilinear form over a Table pair: out[b,o] = x1[b] @ W[o] @ x2[b] + bias
    (reference ``Bilinear(in1, in2, out)``; torch ``nn.Bilinear`` semantics)."""

    def __init__(self, input_size1: int, input_size2: int, output_size: int,
                 bias_res: bool = True,
                 w_init: Optional[InitializationMethod] = None,
                 b_init: Optional[InitializationMethod] = None):
        super().__init__()
        self.input_size1 = input_size1
        self.input_size2 = input_size2
        self.output_size = output_size
        self.bias_res = bias_res
        self.w_init = w_init or RandomUniform()
        self.b_init = b_init or Zeros()
        self.reset()

    def reset(self) -> None:
        fan_in = self.input_size1 * self.input_size2
        self._params = {"weight": jnp.asarray(
            self.w_init.init((self.output_size, self.input_size1, self.input_size2),
                             fan_in=fan_in, fan_out=self.output_size))}
        if self.bias_res:
            self._params["bias"] = jnp.asarray(
                self.b_init.init((self.output_size,), fan_in=fan_in,
                                 fan_out=self.output_size))
        self.zero_grad_parameters()

    def apply(self, params, state, input, *, training=False, rng=None):
        xs = input.values() if isinstance(input, Table) else list(input)
        x1, x2 = xs[0], xs[1]
        out = jnp.einsum("bi,oij,bj->bo", x1, params["weight"], x2)
        if self.bias_res:
            out = out + params["bias"]
        return out, state


class Maxout(TensorModule):
    """Maxout over ``pool_size`` linear pieces (reference ``Maxout``): a single
    Linear to pool_size*output units followed by a max over the pieces — one
    matmul on the MXU plus a reshape-max."""

    def __init__(self, input_size: int, output_size: int, pool_size: int,
                 with_bias: bool = True,
                 w_init: Optional[InitializationMethod] = None,
                 b_init: Optional[InitializationMethod] = None):
        super().__init__()
        self.input_size, self.output_size, self.pool_size = \
            input_size, output_size, pool_size
        self.with_bias = with_bias
        self.w_init = w_init or RandomUniform()
        self.b_init = b_init or RandomUniform()
        self.reset()

    def reset(self) -> None:
        n_out = self.output_size * self.pool_size
        self._params = {"weight": jnp.asarray(
            self.w_init.init((n_out, self.input_size),
                             fan_in=self.input_size, fan_out=n_out))}
        if self.with_bias:
            self._params["bias"] = jnp.asarray(
                self.b_init.init((n_out,), fan_in=self.input_size, fan_out=n_out))
        self.zero_grad_parameters()

    def apply(self, params, state, input, *, training=False, rng=None):
        x = input if input.ndim == 2 else input[None]
        z = x @ params["weight"].T
        if self.with_bias:
            z = z + params["bias"]
        z = z.reshape(z.shape[0], self.output_size, self.pool_size)
        out = jnp.max(z, axis=-1)
        if input.ndim == 1:
            out = out[0]
        return out, state


class SpatialUpSamplingNearest(TensorModule):
    """Nearest-neighbor upsample by an integer scale, NCHW (reference
    ``SpatialUpSamplingNearest``)."""

    def __init__(self, scale: int):
        super().__init__()
        self.scale = int(scale)

    def apply(self, params, state, input, *, training=False, rng=None):
        out = jnp.repeat(jnp.repeat(input, self.scale, axis=-2),
                         self.scale, axis=-1)
        return out, state


class SpatialUpSamplingBilinear(TensorModule):
    """Bilinear upsample to scale*size, align_corners=True (torch
    ``UpsamplingBilinear2d`` / reference ``SpatialUpSamplingBilinear``)."""

    def __init__(self, scale: int):
        super().__init__()
        self.scale = int(scale)

    def apply(self, params, state, input, *, training=False, rng=None):
        x = input
        squeeze = x.ndim == 3
        if squeeze:
            x = x[None]
        n, c, h, w = x.shape
        oh, ow = h * self.scale, w * self.scale
        # align_corners=True sampling grid
        ys = jnp.linspace(0.0, h - 1.0, oh)
        xs_ = jnp.linspace(0.0, w - 1.0, ow)
        y0 = jnp.clip(jnp.floor(ys).astype(jnp.int32), 0, h - 1)
        x0 = jnp.clip(jnp.floor(xs_).astype(jnp.int32), 0, w - 1)
        y1 = jnp.minimum(y0 + 1, h - 1)
        x1 = jnp.minimum(x0 + 1, w - 1)
        wy = (ys - y0)[None, None, :, None]
        wx = (xs_ - x0)[None, None, None, :]
        g = lambda yy, xx: x[:, :, yy, :][:, :, :, xx]
        out = ((1 - wy) * (1 - wx) * g(y0, x0) + (1 - wy) * wx * g(y0, x1)
               + wy * (1 - wx) * g(y1, x0) + wy * wx * g(y1, x1))
        out = out.astype(x.dtype)
        if squeeze:
            out = out[0]
        return out, state


# ----------------------------------------------------------------- grad tricks
import jax as _jax


@_jax.custom_vjp
def _grad_reverse(x, lam):
    return x


def _grad_reverse_fwd(x, lam):
    return x, lam


def _grad_reverse_bwd(lam, g):
    return (-lam * g, None)


_grad_reverse.defvjp(_grad_reverse_fwd, _grad_reverse_bwd)


class GradientReversal(TensorModule):
    """Identity forward; backward multiplies the gradient by ``-lambda``
    (reference ``GradientReversal`` — domain-adversarial training). Implemented
    as a ``jax.custom_vjp`` so it works inside the one-jit training step."""

    def __init__(self, the_lambda: float = 1.0):
        super().__init__()
        self.the_lambda = float(the_lambda)

    def set_lambda(self, lam: float) -> "GradientReversal":
        self.the_lambda = float(lam)
        self._apply_cache = {}  # lambda is baked into the trace — invalidate
        # keep the recorded constructor args in sync (portable serializer
        # rebuilds from them; see pooling.ceil for the failure mode)
        args, _ = self._init_args
        self._init_args = ((), {"the_lambda": float(lam)})
        return self

    def apply(self, params, state, input, *, training=False, rng=None):
        return _grad_reverse(input, self.the_lambda), state


@_jax.custom_vjp
def _l1_penalty(x, strength):
    return x


def _l1_penalty_fwd(x, strength):
    return x, (jnp.sign(x), strength)


def _l1_penalty_bwd(res, g):
    sign, strength = res
    return (g + strength * sign.astype(g.dtype), None)


_l1_penalty.defvjp(_l1_penalty_fwd, _l1_penalty_bwd)


class L1Penalty(TensorModule):
    """Identity forward that adds an L1 sparsity gradient ``l1weight*sign(x)``
    on the way back (reference ``L1Penalty(l1weight, sizeAverage)``)."""

    def __init__(self, l1weight: float, size_average: bool = False,
                 provide_output: bool = True):
        super().__init__()
        self.l1weight = float(l1weight)
        self.size_average = size_average

    def apply(self, params, state, input, *, training=False, rng=None):
        strength = self.l1weight
        if self.size_average:
            strength = strength / input.size
        if training:
            return _l1_penalty(input, strength), state
        return input, state


class Scale(AbstractModule):
    """Elementwise affine y = x * w + b with weight/bias of shape ``size``
    broadcast over the batch (reference ``Scale`` = CMul + CAdd fused; the
    Caffe ``Scale`` layer analog)."""

    def __init__(self, size):
        super().__init__()
        self.size = tuple(int(s) for s in size)
        self.reset()

    def reset(self) -> None:
        self._params = {"weight": jnp.ones(self.size, jnp.float32),
                        "bias": jnp.zeros(self.size, jnp.float32)}
        self.zero_grad_parameters()

    def apply(self, params, state, input, *, training=False, rng=None):
        w, b = params["weight"], params["bias"]
        # broadcast (size) against (N, *size)-or-compatible input, torch-style
        shape = (1,) * (input.ndim - w.ndim) + w.shape
        return input * w.reshape(shape) + b.reshape(shape), state


class PairwiseDistance(AbstractModule):
    """p-norm distance between the two entries of a Table pair → (N,)
    (reference ``PairwiseDistance(norm)``; torch ``nn.PairwiseDistance``)."""

    def __init__(self, norm: int = 2):
        super().__init__()
        self.norm = norm

    def apply(self, params, state, input, *, training=False, rng=None):
        xs = input.values() if isinstance(input, Table) else list(input)
        d = xs[0] - xs[1]
        if d.ndim == 1:
            d = d[None]
        p = float(self.norm)
        # epsilon once on the summed value, not per element — identical inputs
        # stay ~0 regardless of feature count (torch semantics)
        out = (jnp.sum(jnp.abs(d) ** p, axis=-1) + 1e-12) ** (1.0 / p)
        return out, state


class GaussianSampler(AbstractModule):
    """Reparameterised sample from N(mu, exp(log_var)) given a Table
    (mu, log_var) (reference ``GaussianSampler`` — the VAE sampling layer)."""

    def needs_rng(self) -> bool:
        return True

    def apply(self, params, state, input, *, training=False, rng=None):
        xs = input.values() if isinstance(input, Table) else list(input)
        mu, log_var = xs[0], xs[1]
        if rng is None:
            return mu, state  # eval mode: the mean is the sample
        eps = _jax.random.normal(rng, mu.shape, mu.dtype)
        return mu + jnp.exp(0.5 * log_var) * eps, state


class Highway(AbstractModule):
    """Highway layer: ``t*g(Wx+b) + (1-t)*x`` with transform gate
    ``t = sigmoid(Wt x + bt)`` (reference ``Highway(size, withBias,
    activation)``). Two matmuls on the MXU, gating fused by XLA."""

    def __init__(self, size: int, with_bias: bool = True, activation=None,
                 w_init: Optional[InitializationMethod] = None,
                 b_init: Optional[InitializationMethod] = None):
        super().__init__()
        self.size = size
        self.with_bias = with_bias
        # Parameter-free AbstractModule or None → tanh. Parametric activations
        # (PReLU…) would need their params registered on this leaf module to
        # train; reject them loudly rather than silently freezing them.
        if activation is not None and activation.get_params():
            raise ValueError(
                "Highway only supports parameter-free activations (got "
                f"{type(activation).__name__} with trainable params); apply "
                "parametric activations as a separate layer after Highway")
        self.activation = activation
        self.w_init = w_init or Xavier()
        self.b_init = b_init or Zeros()
        self.reset()

    def reset(self) -> None:
        s = self.size
        self._params = {
            "weight": jnp.asarray(self.w_init.init((s, s), fan_in=s, fan_out=s)),
            "gate_weight": jnp.asarray(self.w_init.init((s, s), fan_in=s, fan_out=s)),
        }
        if self.with_bias:
            self._params["bias"] = jnp.asarray(
                self.b_init.init((s,), fan_in=s, fan_out=s))
            # negative gate bias opens the carry path early (standard practice)
            self._params["gate_bias"] = jnp.full((s,), -1.0, jnp.float32)
        self.zero_grad_parameters()

    def apply(self, params, state, input, *, training=False, rng=None):
        h = input @ params["weight"].T
        t = input @ params["gate_weight"].T
        if self.with_bias:
            h = h + params["bias"]
            t = t + params["gate_bias"]
        if self.activation is None:
            h = jnp.tanh(h)
        else:
            h, _ = self.activation.apply({}, {}, h, training=training, rng=None)
        t = _jax.nn.sigmoid(t)
        return t * h + (1.0 - t) * input, state


class UpSampling1D(TensorModule):
    """Repeat each temporal step ``length`` times: (N, T, C) → (N, T*length, C)
    (reference ``UpSampling1D``; keras temporal convention)."""

    def __init__(self, length: int = 2):
        super().__init__()
        self.length = int(length)

    def apply(self, params, state, input, *, training=False, rng=None):
        axis = input.ndim - 2
        return jnp.repeat(input, self.length, axis=axis), state


class UpSampling2D(TensorModule):
    """Nearest-neighbor upsample by (size_h, size_w) (reference
    ``UpSampling2D``; spatial axes follow ``nn.layout``)."""

    def __init__(self, size=(2, 2)):
        super().__init__()
        self.size = (int(size[0]), int(size[1]))

    def apply(self, params, state, input, *, training=False, rng=None):
        from bigdl_tpu.nn import layout
        ha, wa = layout.spatial_axes(input.ndim)
        out = jnp.repeat(input, self.size[0], axis=ha)
        return jnp.repeat(out, self.size[1], axis=wa), state


class UpSampling3D(TensorModule):
    """Nearest-neighbor upsample NCDHW by (d, h, w) (reference
    ``UpSampling3D``)."""

    def __init__(self, size=(2, 2, 2)):
        super().__init__()
        self.size = tuple(int(s) for s in size)

    def apply(self, params, state, input, *, training=False, rng=None):
        out = jnp.repeat(input, self.size[0], axis=-3)
        out = jnp.repeat(out, self.size[1], axis=-2)
        return jnp.repeat(out, self.size[2], axis=-1), state


def _bilinear_resize(x, oh, ow, align_corners):
    """NCHW bilinear resize via two gathers + lerp (XLA fuses the weights)."""
    n, c, h, w = x.shape

    def grid(out_size, in_size):
        if align_corners and out_size > 1:
            return jnp.linspace(0.0, in_size - 1.0, out_size)
        # half-pixel centers (torch align_corners=False / TF half_pixel)
        scale = in_size / out_size
        return jnp.clip((jnp.arange(out_size) + 0.5) * scale - 0.5,
                        0.0, in_size - 1.0)

    ys, xs_ = grid(oh, h), grid(ow, w)
    y0 = jnp.clip(jnp.floor(ys).astype(jnp.int32), 0, h - 1)
    x0 = jnp.clip(jnp.floor(xs_).astype(jnp.int32), 0, w - 1)
    y1 = jnp.minimum(y0 + 1, h - 1)
    x1 = jnp.minimum(x0 + 1, w - 1)
    wy = (ys - y0)[None, None, :, None]
    wx = (xs_ - x0)[None, None, None, :]
    g = lambda yy, xx: x[:, :, yy, :][:, :, :, xx]
    out = ((1 - wy) * (1 - wx) * g(y0, x0) + (1 - wy) * wx * g(y0, x1)
           + wy * (1 - wx) * g(y1, x0) + wy * wx * g(y1, x1))
    return out.astype(x.dtype)


class ResizeBilinear(TensorModule):
    """Bilinear resize to an arbitrary (output_height, output_width), NCHW
    (reference ``ResizeBilinear(outputHeight, outputWidth, alignCorners)``)."""

    def __init__(self, output_height: int, output_width: int,
                 align_corners: bool = False):
        super().__init__()
        self.output_height = int(output_height)
        self.output_width = int(output_width)
        self.align_corners = align_corners

    def apply(self, params, state, input, *, training=False, rng=None):
        x = input
        squeeze = x.ndim == 3
        if squeeze:
            x = x[None]
        out = _bilinear_resize(x, self.output_height, self.output_width,
                               self.align_corners)
        if squeeze:
            out = out[0]
        return out, state


class Cropping2D(TensorModule):
    """Crop (top, bottom) rows and (left, right) cols off NCHW input
    (reference ``Cropping2D``)."""

    def __init__(self, height_crop=(0, 0), width_crop=(0, 0)):
        super().__init__()
        self.height_crop = (int(height_crop[0]), int(height_crop[1]))
        self.width_crop = (int(width_crop[0]), int(width_crop[1]))

    def apply(self, params, state, input, *, training=False, rng=None):
        (t, b), (l, r) = self.height_crop, self.width_crop
        h, w = input.shape[-2], input.shape[-1]
        if t + b >= h or l + r >= w:
            raise ValueError(
                f"Cropping2D extents {self.height_crop}/{self.width_crop} "
                f"consume the whole {h}x{w} input")
        return input[..., t:h - b or None, l:w - r or None], state


class Cropping3D(TensorModule):
    """Crop symmetric-pair extents off the three spatial dims of NCDHW input
    (reference ``Cropping3D``)."""

    def __init__(self, dim1_crop=(0, 0), dim2_crop=(0, 0), dim3_crop=(0, 0)):
        super().__init__()
        self.dim1_crop = tuple(int(v) for v in dim1_crop)
        self.dim2_crop = tuple(int(v) for v in dim2_crop)
        self.dim3_crop = tuple(int(v) for v in dim3_crop)

    def apply(self, params, state, input, *, training=False, rng=None):
        (a0, a1), (b0, b1), (c0, c1) = \
            self.dim1_crop, self.dim2_crop, self.dim3_crop
        d, h, w = input.shape[-3], input.shape[-2], input.shape[-1]
        if a0 + a1 >= d or b0 + b1 >= h or c0 + c1 >= w:
            raise ValueError(
                f"Cropping3D extents {self.dim1_crop}/{self.dim2_crop}/"
                f"{self.dim3_crop} consume the whole {d}x{h}x{w} input")
        return input[..., a0:d - a1 or None, b0:h - b1 or None,
                     c0:w - c1 or None], state


class ActivityRegularization(TensorModule):
    """Identity forward that declares an L1/L2 activity penalty (reference
    ``ActivityRegularization``; keras semantics). Rides the framework's
    ``penalty`` state convention (optim/optimizer.py): added to the training
    objective at FULL strength — the coefficient lives HERE, unlike the
    globally-scaled ``aux_loss`` leaf MoE uses — so keras-ported models keep
    their penalty magnitudes and coexist with MoE in one model."""

    def __init__(self, l1: float = 0.0, l2: float = 0.0):
        super().__init__()
        self.l1, self.l2 = float(l1), float(l2)
        self._state = {"penalty": jnp.zeros((), jnp.float32)}

    def apply(self, params, state, input, *, training=False, rng=None):
        x = input.astype(jnp.float32)
        pen = self.l1 * jnp.sum(jnp.abs(x)) + self.l2 * jnp.sum(jnp.square(x))
        return input, {**state, "penalty": pen}


class NegativeEntropyPenalty(TensorModule):
    """Identity forward penalising low-entropy probability activations
    (reference ``NegativeEntropyPenalty``): penalty = beta * sum(p log p).
    Encourages exploration in probability outputs; full-strength ``penalty``
    leaf like ActivityRegularization (the coefficient is the layer's own)."""

    def __init__(self, beta: float = 0.01):
        super().__init__()
        self.beta = float(beta)
        self._state = {"penalty": jnp.zeros((), jnp.float32)}

    def apply(self, params, state, input, *, training=False, rng=None):
        p = input.astype(jnp.float32)
        ent = jnp.sum(p * jnp.log(jnp.clip(p, 1e-12, None)))
        return input, {**state, "penalty": self.beta * ent}


class CrossProduct(AbstractModule):
    """All pairwise dot products of a Table of N same-shape vectors →
    (batch, N*(N-1)/2) in (1,2),(1,3),...,(N-1,N) order (reference
    ``CrossProduct``, the DeepFM/feature-interaction building block)."""

    def __init__(self, num_tensor: int = 0, embedding_size: int = 0):
        super().__init__()
        self.num_tensor = num_tensor        # 0 = infer from input
        self.embedding_size = embedding_size  # 0 = any width

    def apply(self, params, state, input, *, training=False, rng=None):
        xs = input.values() if isinstance(input, Table) else list(input)
        if self.num_tensor and len(xs) != self.num_tensor:
            raise ValueError(
                f"CrossProduct expected {self.num_tensor} tensors, "
                f"got {len(xs)}")
        if self.embedding_size:
            bad = [x.shape[-1] for x in xs if x.shape[-1] != self.embedding_size]
            if bad:
                raise ValueError(
                    f"CrossProduct expected embedding size "
                    f"{self.embedding_size}, got {bad}")
        outs = [jnp.sum(xs[i] * xs[j], axis=-1)
                for i in range(len(xs)) for j in range(i + 1, len(xs))]
        return jnp.stack(outs, axis=-1), state


class ImageNormalize(TensorModule):
    """On-device image normalization: ``(x * scale - mean) / std`` per channel.

    The TPU-native input path (SURVEY.md §2.2 redesign): the reference's
    pipeline normalizes on the CPU and ships float32 activations to the
    compute tier; on TPU the host-to-device wire is the scarce resource, so the
    feed stays ``uint8`` (4x fewer bytes than fp32) and this layer casts +
    normalizes on device, where XLA fuses it into the first convolution's
    epilogue at zero marginal cost. Defaults are the ImageNet mean/std in
    0-1 range with ``scale=1/255`` (uint8 pixels); pass ``scale=1.0`` for
    pre-scaled float input. Channel broadcasting follows ``nn.layout``.
    """

    def __init__(self, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225),
                 scale: float = 1.0 / 255.0):
        super().__init__()
        mean = mean if isinstance(mean, (tuple, list)) else (mean,)
        std = std if isinstance(std, (tuple, list)) else (std,)
        self.mean = tuple(float(m) for m in mean)
        self.std = tuple(float(s) for s in std)
        if len(self.mean) != len(self.std):
            raise ValueError(
                f"ImageNormalize: mean has {len(self.mean)} channels but std "
                f"has {len(self.std)} — they must pair up")
        self.scale = float(scale)

    def apply(self, params, state, input, *, training=False, rng=None):
        from bigdl_tpu.nn import layout
        from bigdl_tpu.utils.engine import Engine
        x = jnp.asarray(input)
        if not jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(Engine.compute_dtype() if Engine.is_initialized()
                         else jnp.float32)
        shape = layout.bias_shape(len(self.mean), x.ndim) if x.ndim >= 3 \
            else (len(self.mean),)
        mean = jnp.asarray(self.mean, x.dtype).reshape(shape)
        std = jnp.asarray(self.std, x.dtype).reshape(shape)
        return (x * jnp.asarray(self.scale, x.dtype) - mean) / std, state

    def __repr__(self):
        return (f"ImageNormalize(mean={self.mean}, std={self.std}, "
                f"scale={self.scale:g})")
