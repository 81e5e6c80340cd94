"""ResNet family (18/34/50/101/152), torchvision-architecture-exact, NHWC.

In the reference these come from ``torchvision.models.resnet*``
(imagenet_ddp.py:108-114; canonical arch is resnet50, imagenet_ddp.py:26-30).
This is a fresh Flax implementation matching torchvision's architecture
bit-for-bit in structure (verified by parameter count in
tests/test_models.py):

* 7×7/2 stem conv (no bias) → BN → ReLU → 3×3/2 max pool.
* BasicBlock (18/34) / Bottleneck (50/101/152) with expansion 4; the stride
  lives on the 3×3 conv (torchvision's ResNet "v1.5" placement).
* 1×1-conv + BN downsample on the first block of stages 2-4.
* Global average pool → Dense classifier.

TPU-first choices: NHWC layout (MXU-friendly, channels minor), a ``dtype``
compute policy (bf16 replaces Apex AMP, imagenet_ddp_apex.py:169-172) with
BatchNorm *statistics* always accumulated in fp32 (flax promotes the
reductions) while BN activation I/O follows the compute dtype unless
``bn_dtype=float32`` pins it (the strict ``keep_batchnorm_fp32`` analog,
imagenet_ddp_apex.py:93 — fp32 BN I/O between bf16 convs costs ~25%
throughput in extra HBM traffic), and an optional ``bn_axis_name`` that turns on
cross-replica (sync) BN via ``lax.pmean`` inside ``shard_map`` — the
``apex.parallel.convert_syncbn_model`` analog (imagenet_ddp_apex.py:146-148).
``bn_axis_name=None`` (default) keeps per-replica batch statistics, matching
DDP's default non-synced BN.
"""

from functools import partial
from typing import Any, Callable, Optional, Sequence, Type

import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from dptpu.models.layers import (
    kaiming_normal_fan_out,
    max_pool_same_as_torch,
    torch_default_bias_init,
    torch_default_kernel_init,
)
from dptpu.models.registry import register_model


class BasicBlock(nn.Module):
    planes: int
    stride: int
    conv: Callable
    norm: Callable
    expansion: int = 1

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(
            self.planes,
            (3, 3),
            strides=(self.stride, self.stride),
            padding=((1, 1), (1, 1)),
            name="conv1",
        )(x)
        y = self.norm(name="bn1")(y)
        y = nn.relu(y)
        y = self.conv(self.planes, (3, 3), padding=((1, 1), (1, 1)), name="conv2")(y)
        y = self.norm(name="bn2")(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.planes * self.expansion,
                (1, 1),
                strides=(self.stride, self.stride),
                name="downsample_conv",
            )(x)
            residual = self.norm(name="downsample_bn")(residual)
        return nn.relu((residual + y).astype(y.dtype))


class Bottleneck(nn.Module):
    planes: int
    stride: int
    conv: Callable
    norm: Callable
    expansion: int = 4
    # torchvision's width generalization: the 1x1/3x3 pair runs at
    # int(planes * base_width / 64) * groups channels, the 3x3 grouped —
    # (64, 1) is plain ResNet, (128, 1) wide_resnet*_2, (4, 32)
    # resnext50_32x4d, (8, 32) resnext101_32x8d
    base_width: int = 64
    groups: int = 1

    @nn.compact
    def __call__(self, x):
        residual = x
        width = int(self.planes * self.base_width / 64) * self.groups
        y = self.conv(width, (1, 1), name="conv1")(x)
        y = self.norm(name="bn1")(y)
        y = nn.relu(y)
        # stride on the 3x3 conv: torchvision ResNet v1.5
        y = self.conv(
            width,
            (3, 3),
            strides=(self.stride, self.stride),
            padding=((1, 1), (1, 1)),
            feature_group_count=self.groups,
            name="conv2",
        )(y)
        y = self.norm(name="bn2")(y)
        y = nn.relu(y)
        y = self.conv(self.planes * self.expansion, (1, 1), name="conv3")(y)
        y = self.norm(name="bn3")(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.planes * self.expansion,
                (1, 1),
                strides=(self.stride, self.stride),
                name="downsample_conv",
            )(x)
            residual = self.norm(name="downsample_bn")(residual)
        return nn.relu((residual + y).astype(y.dtype))


class _Stem(nn.Module):
    """The 7×7/2 stem conv on the torchvision-shaped
    ``kernel [7,7,3,64]``."""

    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", kaiming_normal_fan_out, (7, 7, 3, 64), self.param_dtype
        ).astype(self.dtype)
        return lax.conv_general_dilated(
            x.astype(self.dtype), kernel, (2, 2), ((3, 3), (3, 3)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: Type[nn.Module]
    num_classes: int = 1000
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    bn_axis_name: Optional[str] = None
    # BN I/O dtype. None → follow ``dtype``. Statistics/params stay fp32
    # either way (flax promotes reductions to f32), so this only controls
    # whether activations round-trip through f32 between bf16 convs —
    # keeping it bf16 preserves XLA fusion and halves BN HBM traffic while
    # retaining the keep_batchnorm_fp32 guarantee where it matters (the
    # running statistics and learned scale/shift).
    bn_dtype: Optional[Any] = None
    # Bottleneck width generalization (see Bottleneck): plain ResNet is
    # (64, 1); wide_resnet*_2 use base_width 128; resnext* use groups 32.
    base_width: int = 64
    groups: int = 1

    @nn.compact
    def __call__(self, x, train: bool = False):
        conv = partial(
            nn.Conv,
            use_bias=False,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=kaiming_normal_fan_out,
        )
        bn_momentum = 0.9  # torch BN momentum 0.1 == flax EMA decay 0.9
        bn_epsilon = 1e-5
        bn_io_dtype = self.bn_dtype if self.bn_dtype is not None else self.dtype
        norm = partial(
            nn.BatchNorm,
            use_running_average=not train,
            momentum=bn_momentum,
            epsilon=bn_epsilon,
            dtype=bn_io_dtype,
            param_dtype=jnp.float32,
            axis_name=self.bn_axis_name,
        )
        x = _Stem(
            dtype=self.dtype, param_dtype=self.param_dtype, name="conv1",
        )(x)
        x = norm(name="bn1")(x)
        x = nn.relu(x)
        x = max_pool_same_as_torch(x, 3, 2, 1)
        if self.block_cls is Bottleneck:
            width_kw = {"base_width": self.base_width, "groups": self.groups}
        else:
            if self.groups != 1 or self.base_width != 64:
                # torchvision raises the same way: BasicBlock has no width
                # generalization (only Bottleneck archs are wide/grouped)
                raise ValueError(
                    "BasicBlock only supports groups=1 and base_width=64"
                )
            width_kw = {}
        for i, n_blocks in enumerate(self.stage_sizes):
            for j in range(n_blocks):
                x = self.block_cls(
                    planes=64 * 2**i,
                    stride=2 if i > 0 and j == 0 else 1,
                    conv=conv,
                    norm=norm,
                    name=f"layer{i + 1}_block{j}",
                    **width_kw,
                )(x)
        x = x.mean(axis=(1, 2))  # AdaptiveAvgPool2d((1,1)) + flatten
        fan_in = x.shape[-1]
        x = nn.Dense(
            self.num_classes,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=torch_default_kernel_init,
            bias_init=torch_default_bias_init(fan_in),
            name="fc",
        )(x)
        return x


def _resnet(stage_sizes, block_cls, **kwargs):
    return ResNet(stage_sizes=stage_sizes, block_cls=block_cls, **kwargs)


@register_model
def resnet18(**kw):
    return _resnet([2, 2, 2, 2], BasicBlock, **kw)


@register_model
def resnet34(**kw):
    return _resnet([3, 4, 6, 3], BasicBlock, **kw)


@register_model
def resnet50(**kw):
    return _resnet([3, 4, 6, 3], Bottleneck, **kw)


@register_model
def resnet101(**kw):
    return _resnet([3, 4, 23, 3], Bottleneck, **kw)


@register_model
def resnet152(**kw):
    return _resnet([3, 8, 36, 3], Bottleneck, **kw)


@register_model
def wide_resnet50_2(**kw):
    return _resnet([3, 4, 6, 3], Bottleneck, base_width=128, **kw)


@register_model
def wide_resnet101_2(**kw):
    return _resnet([3, 4, 23, 3], Bottleneck, base_width=128, **kw)


@register_model
def resnext50_32x4d(**kw):
    return _resnet([3, 4, 6, 3], Bottleneck, base_width=4, groups=32, **kw)


@register_model
def resnext101_32x8d(**kw):
    return _resnet([3, 4, 23, 3], Bottleneck, base_width=8, groups=32, **kw)
