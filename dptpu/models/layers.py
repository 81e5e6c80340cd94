"""Shared layer utilities: torchvision-matching initializers and pooling.

The reference builds models straight from ``torchvision.models``
(imagenet_ddp.py:108-114), so convergence parity depends on matching
torchvision's initialization conventions (SURVEY.md §7 hard part (c)):

* ``kaiming_normal_(mode='fan_out', nonlinearity='relu')`` for ResNet/VGG
  convs — here ``variance_scaling(2.0, 'fan_out', 'normal')`` (identical
  distribution; flax computes conv fan_out as out_channels × receptive
  field, same as torch).
* torch's default Linear/Conv init (``kaiming_uniform_(a=sqrt(5))`` +
  bias ``U(±1/sqrt(fan_in))``) for AlexNet and ResNet's fc layer — the
  kernel bound simplifies to exactly ``1/sqrt(fan_in)``.
* ``normal(0, 0.01)`` for VGG classifier Linears.

Layout is NHWC throughout (TPU-native — the MXU wants channels minor; this
is also what the reference's ``--channels-last`` flag asks for,
imagenet_ddp_apex.py:95,133-136).
"""

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

# kaiming_normal(mode='fan_out', nonlinearity='relu'): N(0, sqrt(2/fan_out))
kaiming_normal_fan_out = nn.initializers.variance_scaling(
    2.0, "fan_out", "normal"
)


def torch_default_kernel_init(key, shape, dtype=jnp.float32):
    """torch's default Linear/Conv kernel init: kaiming_uniform(a=sqrt(5)).

    bound = sqrt(6 / ((1 + a^2) * fan_in)) = 1/sqrt(fan_in).
    ``shape`` is flax convention: (..., fan_in, fan_out) for Dense,
    (kh, kw, in, out) for Conv (fan_in = in × kh × kw).
    """
    fan_in = int(np.prod(shape[:-1]))
    bound = 1.0 / np.sqrt(fan_in)
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def torch_default_bias_init(fan_in):
    """torch default bias init: U(±1/sqrt(fan_in)) with fan_in of the layer."""
    return uniform_bound_init(1.0 / np.sqrt(fan_in))


def torch_trunc_normal_init(std, bound=2.0):
    """``torch.nn.init.trunc_normal_(std=std)``: N(0, std²) truncated at
    ABSOLUTE ±bound (so ±bound/std sigmas — effectively untruncated for
    the std ≈ 0.02 used by ViT/Swin/ConvNeXt). jax's
    ``initializers.truncated_normal`` instead truncates at ±2σ without
    renormalizing (actual std ≈ 0.88·std), so it does NOT match."""

    def init(key, shape, dtype=jnp.float32):
        cut = bound / std
        return std * jax.random.truncated_normal(key, -cut, cut, shape, dtype)

    return init


def uniform_bound_init(bound):
    """U(±bound) initializer (torchvision's Linear init for EfficientNet
    and others uses U(±1/sqrt(out_features)))."""

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


class SqueezeExcite(nn.Module):
    """torchvision SqueezeExcitation: avg pool -> 1x1 reduce -> act ->
    1x1 expand -> gate (convs with bias). MobileNetV3 uses relu /
    hard_sigmoid, EfficientNet silu / sigmoid."""

    reduced: int
    conv: Any
    act: Any = nn.relu
    gate: Any = nn.sigmoid

    @nn.compact
    def __call__(self, x):
        s = x.mean(axis=(1, 2), keepdims=True)
        s = self.conv(self.reduced, (1, 1), use_bias=True, name="fc1")(s)
        s = self.act(s)
        s = self.conv(x.shape[-1], (1, 1), use_bias=True, name="fc2")(s)
        return x * self.gate(s)


class StochasticDepth(nn.Module):
    """torchvision ``StochasticDepth(p, mode="row")``: drop a residual
    branch per SAMPLE with probability ``p``, scaling survivors by
    ``1/(1-p)``. Identity when deterministic or p == 0 (so it traces to
    nothing at eval and for the un-scaled early blocks)."""

    rate: float
    deterministic: bool

    @nn.compact
    def __call__(self, x):
        if self.deterministic or self.rate == 0.0:
            return x
        keep = 1.0 - self.rate
        rng = self.make_rng("dropout")
        mask = jax.random.bernoulli(rng, keep, (x.shape[0],) + (1,) * (x.ndim - 1))
        return jnp.where(mask, x / keep, jnp.zeros_like(x)).astype(x.dtype)


def max_pool_same_as_torch(x, window, stride, padding):
    """``nn.MaxPool2d(window, stride, padding)`` on NHWC input.

    torch pads with -inf implicitly for max pooling; flax's ``nn.max_pool``
    pads with -inf as well when given explicit padding tuples.
    """
    return nn.max_pool(
        x,
        (window, window),
        strides=(stride, stride),
        padding=((padding, padding), (padding, padding)),
    )


def ceil_max_pool(x, window=3, stride=2):
    """``nn.MaxPool2d(window, stride, ceil_mode=True)`` on NHWC input —
    the ceil-rounded output grid, realized by -inf bottom/right padding
    exactly when needed (used by SqueezeNet and GoogLeNet)."""
    _, h, w, _ = x.shape
    oh = -(-(h - window) // stride) + 1
    ow = -(-(w - window) // stride) + 1
    pad_h = max(0, (oh - 1) * stride + window - h)
    pad_w = max(0, (ow - 1) * stride + window - w)
    return nn.max_pool(
        x, (window, window), strides=(stride, stride),
        padding=((0, pad_h), (0, pad_w)),
    )


def adaptive_avg_pool(x, output_size):
    """``nn.AdaptiveAvgPool2d(output_size)`` on NHWC input, torch semantics.

    Output bin i covers rows [floor(i*H/out), ceil((i+1)*H/out)). Fast paths:
    global pooling (out=1) is a plain mean; exact division is a reshape-mean
    (both fuse into the surrounding XLA program). The general path unrolls
    over the (static, small ≤7) output grid.
    """
    oh, ow = (output_size, output_size) if isinstance(output_size, int) else output_size
    _, h, w, _ = x.shape
    if (oh, ow) == (1, 1):
        return x.mean(axis=(1, 2), keepdims=True)
    if h == oh and w == ow:
        return x
    if h % oh == 0 and w % ow == 0:
        n, _, _, c = x.shape
        x = x.reshape(n, oh, h // oh, ow, w // ow, c)
        return x.mean(axis=(2, 4))
    if h < oh or w < ow:
        raise ValueError(
            f"adaptive_avg_pool upsampling ({h}x{w} -> {oh}x{ow}) unsupported; "
            "use input images >= 64x64 for AlexNet/VGG"
        )
    rows = []
    for i in range(oh):
        r0, r1 = (i * h) // oh, -(-((i + 1) * h) // oh)
        cols = []
        for j in range(ow):
            c0, c1 = (j * w) // ow, -(-((j + 1) * w) // ow)
            cols.append(x[:, r0:r1, c0:c1, :].mean(axis=(1, 2)))
        rows.append(jnp.stack(cols, axis=1))
    return jnp.stack(rows, axis=1)
