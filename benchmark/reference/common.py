"""What every plain reference shares: precision modes, seeded weights, the
image families' loss, the training driver.

A reference is the published architecture in straightforward ``jax.numpy``:
float32 at ``Precision.HIGHEST``, no kernels, torchvision's parameter
names and layouts (the published layout), nothing imported from ``dptpu``.
It is handed raw uint8 rows and labels that the benchmark generated itself,
and weights that the benchmark made itself from the seed.

Precision modes (``mode``), chosen per call:

* ``f32``  — the reference proper: float32 operands, HIGHEST matmuls.
* ``bf16`` — a witness that reads like the program: operands and stored
  activations rounded to bfloat16, float32 accumulation and statistics.
* ``fp8``  — the control: operands of every convolution and matrix product
  rounded to float8_e4m3 (the nearest precision below the bfloat16 that the
  configurations state), activations stored in bfloat16.

The training driver takes the family's loss on one block of rows and the
optimizer's plain update (``optimizers/<name>.py``) and knows neither. The
batch is taken in blocks of ``block_rows``: for a BatchNorm model a block
is one replica's batch (per-replica statistics, DDP's default), for others
any divisor of the batch, and the blocks' gradients are averaged.
"""

from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)
MODES = ("f32", "bf16", "fp8")
_FP8_MAX = 448.0  # float8_e4m3fn has no inf: clip, or an overflow is a NaN


def operand(x, mode: str):
    """One operand of a convolution or matrix product, in ``mode``."""
    if mode == "f32":
        return x.astype(jnp.float32)
    if mode == "bf16":
        return x.astype(jnp.bfloat16)
    if mode == "fp8":
        x = jnp.clip(x.astype(jnp.float32), -_FP8_MAX, _FP8_MAX)
        return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    raise ValueError(f"mode {mode!r} is not one of {MODES}")


def stored(x, mode: str):
    """An activation as it is kept between layers: rounded to bfloat16 in
    the low-precision modes, always handed on as float32."""
    if mode == "f32":
        return x
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def matmul(x, w, mode: str):
    """``x @ w``; in the low-precision modes the product comes back in
    bfloat16, as the program's layers hand it on."""
    precision = lax.Precision.HIGHEST if mode == "f32" else None
    y = jnp.matmul(operand(x, mode), operand(w, mode), precision=precision)
    return y.astype(jnp.float32)


def linear(x, weight, bias, mode: str):
    """torch ``nn.Linear``: ``weight`` is (out, in)."""
    return stored(matmul(x, weight.T, mode) + bias, mode)


def conv2d(x, weight, stride: int, padding: int, mode: str, groups: int = 1):
    """torch ``nn.Conv2d`` on NHWC activations; ``weight`` is OIHW."""
    precision = lax.Precision.HIGHEST if mode == "f32" else None
    y = lax.conv_general_dilated(
        operand(x, mode), operand(jnp.transpose(weight, (2, 3, 1, 0)), mode),
        (stride, stride), ((padding, padding), (padding, padding)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=precision,
    )
    return y.astype(jnp.float32)


def normalize(images_u8):
    """uint8 NHWC rows to torchvision-normalized float32."""
    mean = jnp.asarray(IMAGENET_MEAN, jnp.float32) * 255.0
    std = jnp.asarray(IMAGENET_STD, jnp.float32) * 255.0
    return (images_u8.astype(jnp.float32) - mean) / std


def cross_entropy(logits, labels):
    """Mean softmax cross-entropy with integer labels, in float32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)


def image_loss(forward: Callable, model: dict, weights, batch, mode: str):
    """What the image families share: the mean cross-entropy of
    ``forward`` on one block of raw uint8 rows with their labels."""
    logits = forward(model, weights, normalize(batch["images"]), mode)
    return cross_entropy(logits, batch["labels"])


def image_example_input(model: dict):
    """One float32 row of the configuration's image size, for the shapes
    of the program's ``model.init``."""
    size = model["image_size"]
    return jnp.zeros((1, size, size, 3), jnp.float32)


# ----------------------------------------------------------- seeded weights --


def _draw(key, shape, kind, scale):
    if kind == "normal":
        return scale * jax.random.normal(key, shape, jnp.float32)
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, -scale, scale)
    if kind == "const":
        return jnp.full(shape, scale, jnp.float32)
    raise ValueError(f"unknown init kind {kind!r}")


def make_weights(spec, seed: int) -> Dict[str, jax.Array]:
    """All leaves of ``spec`` (``[(name, shape, kind, scale), ...]``) in ONE
    jitted call on the device, float32 as the trainer holds them. The
    same seed gives the same weights; ``seed`` may exceed 2**31."""
    names = [s[0] for s in spec]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(spec))
        return [_draw(k, tuple(shape), kind, scale)
                for k, (_, shape, kind, scale) in zip(keys, spec)]

    # two 32-bit halves: PRNGKey alone would fold a seed above 2**32
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0xFFFFFFFF),
                             int(seed) >> 32)
    return dict(zip(names, build(key)))


# ------------------------------------------------------------ the training --


def train_steps(loss: Callable, optimizer, hyper: dict, trainable,
                weights: Dict[str, jax.Array], batches, *, lr: float,
                block_rows: int, mode: str = "f32"):
    """Follow ``len(batches)`` optimizer steps from ``weights``.

    ``loss(weights, block, mode) -> scalar`` is the family's, on one block
    of rows (``{key: array}``, the feed's keys); ``optimizer`` is a module
    of ``optimizers/`` and ``hyper`` the configuration's block for it;
    ``trainable`` names the leaves the optimizer moves (the rest are
    buffers). ``batches`` is ``[{key: numpy array}, ...]``. Returns
    ``{"loss": [per step], "trace1": {leaf: the first gradient as the
    optimizer got it, from its state after the first step}, "delta":
    {leaf: parameters after the last step minus the seeded ones}}`` —
    float32 numpy, the family's names.
    """
    trainable = list(trainable)
    buffers = {k: v for k, v in weights.items() if k not in trainable}

    def block_loss(params, block):
        return loss({**buffers, **params}, block, mode)

    grad_fn = jax.jit(jax.value_and_grad(block_loss))

    @jax.jit
    def update(params, state, total, scale):
        grads = {k: total[k] * scale for k in params}
        return optimizer.update(params, state, grads, lr, hyper)

    params = {k: jnp.asarray(weights[k], jnp.float32) for k in trainable}
    start = params
    state = optimizer.init(params)
    losses, trace1 = [], None
    for batch in batches:
        n = len(next(iter(batch.values())))
        if n % block_rows:
            raise ValueError(f"batch of {n} rows is not whole blocks of "
                             f"{block_rows}")
        n_blocks = n // block_rows
        total, loss_sum = None, 0.0
        for b in range(n_blocks):
            rows = slice(b * block_rows, (b + 1) * block_rows)
            value, grads = grad_fn(
                params, {k: jnp.asarray(v[rows]) for k, v in batch.items()})
            loss_sum = loss_sum + value
            total = grads if total is None else jax.tree_util.tree_map(
                jnp.add, total, grads)
        params, state = update(params, state, total, 1.0 / n_blocks)
        losses.append(float(loss_sum) / n_blocks)
        if trace1 is None:
            trace1 = {k: np.asarray(v)
                      for k, v in optimizer.trace1(state).items()}
    delta = {k: np.asarray(params[k] - start[k]) for k in params}
    return {"loss": losses, "trace1": trace1, "delta": delta}


def valid_taps(size: int, kernel: int, stride: int, padding: int) -> int:
    """Along one axis, the kernel taps that fall on the input and not on
    its zero padding, summed over the output positions: the
    multiply-adds a convolution needs, as XLA's cost analysis counts
    them (a 3x3 kernel over a 7x7 map needs 361 of 441)."""
    out = (size + 2 * padding - kernel) // stride + 1
    return sum(1 for o in range(out) for t in range(kernel)
               if 0 <= o * stride - padding + t < size)


def fan_out_std(shape) -> float:
    """He-normal, fan-out mode, for an OIHW kernel (torchvision ResNet)."""
    out_ch, _, kh, kw = shape
    return math.sqrt(2.0 / (out_ch * kh * kw))
