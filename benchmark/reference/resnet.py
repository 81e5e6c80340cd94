"""Plain reference: torchvision ResNet (He et al., arXiv:1512.03385, v1.5).

7x7/2 stem, BN, ReLU, 3x3/2 max pool; four stages of Bottleneck blocks
(1x1, 3x3 carrying the stride, 1x1 x4) or BasicBlocks (3x3, 3x3); a 1x1
projection where a block changes shape; global average pool; linear
classifier. BatchNorm in training mode normalizes by the batch's own mean
and biased variance. Running statistics do not enter a training step's
loss, gradient or update and are not followed here.

The configuration's ``model`` group gives ``stage_sizes`` and ``block``
(ResNet-50: [3, 4, 6, 3], "bottleneck"), so the file serves every depth of
the family; widths are the published ones and are not parameters.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import common

_EPS = 1e-5
_WIDTHS = (64, 128, 256, 512)


def _expansion(model) -> int:
    return {"bottleneck": 4, "basic": 1}[model["block"]]


def _blocks(model):
    """``(prefix, in_ch, planes, stride)`` for every residual block."""
    exp = _expansion(model)
    in_ch = 64
    for stage, n_blocks in enumerate(model["stage_sizes"]):
        planes = _WIDTHS[stage]
        for j in range(n_blocks):
            stride = 2 if stage > 0 and j == 0 else 1
            yield f"layer{stage + 1}.{j}", in_ch, planes, stride
            in_ch = planes * exp


def weight_spec(model):
    """``[(torch name, shape, init kind, scale)]``: torchvision's own
    initialization (He-normal fan-out kernels, BN at (1, 0), the
    classifier at torch's Linear default)."""
    exp = _expansion(model)
    spec = []

    def conv(name, out_ch, in_ch, k):
        shape = (out_ch, in_ch, k, k)
        spec.append((f"{name}.weight", shape, "normal",
                     common.fan_out_std(shape)))

    def bn(name, ch):
        spec.append((f"{name}.weight", (ch,), "const", 1.0))
        spec.append((f"{name}.bias", (ch,), "const", 0.0))
        spec.append((f"{name}.running_mean", (ch,), "const", 0.0))
        spec.append((f"{name}.running_var", (ch,), "const", 1.0))

    conv("conv1", 64, 3, 7)
    bn("bn1", 64)
    for prefix, in_ch, planes, stride in _blocks(model):
        if model["block"] == "bottleneck":
            conv(f"{prefix}.conv1", planes, in_ch, 1)
            bn(f"{prefix}.bn1", planes)
            conv(f"{prefix}.conv2", planes, planes, 3)
            bn(f"{prefix}.bn2", planes)
            conv(f"{prefix}.conv3", planes * exp, planes, 1)
            bn(f"{prefix}.bn3", planes * exp)
        else:
            conv(f"{prefix}.conv1", planes, in_ch, 3)
            bn(f"{prefix}.bn1", planes)
            conv(f"{prefix}.conv2", planes, planes, 3)
            bn(f"{prefix}.bn2", planes)
        if stride != 1 or in_ch != planes * exp:
            conv(f"{prefix}.downsample.0", planes * exp, in_ch, 1)
            bn(f"{prefix}.downsample.1", planes * exp)
    fan_in = 512 * exp
    bound = 1.0 / math.sqrt(fan_in)
    spec.append(("fc.weight", (model["num_classes"], fan_in), "uniform",
                 bound))
    spec.append(("fc.bias", (model["num_classes"],), "uniform", bound))
    return spec


def trainable(model):
    return [name for name, *_ in weight_spec(model)
            if not name.endswith(("running_mean", "running_var"))]


def _bn(w, name, x, mode):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) * lax.rsqrt(var + _EPS)
    return common.stored(y * w[f"{name}.weight"] + w[f"{name}.bias"], mode)


def _max_pool_3x3_s2(x):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)),
    )


def forward(model, w, x, mode: str = "f32"):
    """Training-mode forward: normalized float32 NHWC rows to logits."""
    bottleneck = model["block"] == "bottleneck"

    def block(w_blk, x, prefix, stride, project):
        if bottleneck:
            y = common.conv2d(x, w_blk[f"{prefix}.conv1.weight"], 1, 0, mode)
            y = jax.nn.relu(_bn(w_blk, f"{prefix}.bn1", y, mode))
            y = common.conv2d(y, w_blk[f"{prefix}.conv2.weight"], stride, 1,
                              mode)
            y = jax.nn.relu(_bn(w_blk, f"{prefix}.bn2", y, mode))
            y = common.conv2d(y, w_blk[f"{prefix}.conv3.weight"], 1, 0, mode)
            y = _bn(w_blk, f"{prefix}.bn3", y, mode)
        else:
            y = common.conv2d(x, w_blk[f"{prefix}.conv1.weight"], stride, 1,
                              mode)
            y = jax.nn.relu(_bn(w_blk, f"{prefix}.bn1", y, mode))
            y = common.conv2d(y, w_blk[f"{prefix}.conv2.weight"], 1, 1, mode)
            y = _bn(w_blk, f"{prefix}.bn2", y, mode)
        if project:
            x = common.conv2d(x, w_blk[f"{prefix}.downsample.0.weight"],
                              stride, 0, mode)
            x = _bn(w_blk, f"{prefix}.downsample.1", x, mode)
        return jax.nn.relu(x + y)

    x = common.conv2d(x, w["conv1.weight"], 2, 3, mode)
    x = jax.nn.relu(_bn(w, "bn1", x, mode))
    x = _max_pool_3x3_s2(x)
    exp = _expansion(model)
    for prefix, in_ch, planes, stride in _blocks(model):
        w_blk = {k: v for k, v in w.items() if k.startswith(prefix + ".")}
        project = stride != 1 or in_ch != planes * exp
        # rematerialized per block so that float32 at the timed batch fits
        x = jax.checkpoint(block, static_argnums=(2, 3, 4))(
            w_blk, x, prefix, stride, project)
    x = jnp.mean(x, axis=(1, 2))
    return common.linear(x, w["fc.weight"], w["fc.bias"], mode)


def loss(model, w, batch, mode: str = "f32"):
    """Mean cross-entropy of one block of rows (``images`` uint8 NHWC,
    ``labels``)."""
    return common.image_loss(forward, model, w, batch, mode)


def example_input(model):
    return common.image_example_input(model)


def train_flops(model, rows: int) -> float:
    """Operations one optimizer step of ``rows`` rows needs, forward and
    backward, from the shapes: 2 per multiply-add of every convolution (taps on the zero
    padding not counted) and the classifier, three times over (forward, input gradient, weight gradient) except the
    stem, whose input is the image and gets no gradient. Elementwise work
    (BN, ReLU, pooling, SGD) is not counted: it does not run on the MXU
    the peak is quoted for."""
    exp = _expansion(model)

    def taps(size, kernel, stride, padding):
        return common.valid_taps(size, kernel, stride, padding) ** 2

    image_size = model["image_size"]
    macs_stem = taps(image_size, 7, 2, 3) * 64 * 3
    size = (image_size + 2 * 3 - 7) // 2 + 1
    size = (size + 2 - 3) // 2 + 1
    macs = 0
    for _, in_ch, planes, stride in _blocks(model):
        out = (size - 1) // stride + 1
        if model["block"] == "bottleneck":
            macs += size * size * in_ch * planes
            macs += taps(size, 3, stride, 1) * planes * planes
            macs += out * out * planes * planes * exp
        else:
            macs += taps(size, 3, stride, 1) * in_ch * planes
            macs += taps(out, 3, 1, 1) * planes * planes
        if stride != 1 or in_ch != planes * exp:
            macs += out * out * in_ch * planes * exp
        size = out
    macs += 512 * exp * model["num_classes"]
    return float(rows) * 2.0 * (3 * macs + 2 * macs_stem)
