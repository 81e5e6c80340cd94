"""Plain reference: ViT (Dosovitskiy et al., arXiv:2010.11929), as
torchvision's ``vision_transformer.py`` lays it out.

Patchify by a patch-size/patch-stride convolution with bias, flattened
row-major; learned class token prepended; learned position embedding
added; pre-LN encoder layers (LayerNorm eps 1e-6): LN, multi-head
self-attention with one fused ``in_proj`` in torch's [q|k|v] order and an
output projection, residual; LN, Linear, exact GELU, Linear, residual;
final LN; the classifier reads the class token.

Departure from torchvision, on purpose: the class token and the head are
seeded with small normal values, not zeros, and biases with N(0, 0.02).
With a zero head the first gradient of every other leaf is exactly zero,
and a comparison of gradients would compare nothing.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import common

_EPS = 1e-6


def _layer(i: int) -> str:
    return f"encoder.layers.encoder_layer_{i}"


def _tokens(model, image_size: int) -> int:
    return (image_size // model["patch_size"]) ** 2 + 1


def weight_spec(model):
    """``[(torch name, shape, init kind, scale)]``."""
    h, mlp, p = model["hidden_size"], model["mlp_dim"], model["patch_size"]
    seq = _tokens(model, model["image_size"])
    spec = []

    def xavier(name, out_f, in_f):
        spec.append((name, (out_f, in_f), "uniform",
                     math.sqrt(6.0 / (in_f + out_f))))

    def ln(name):
        spec.append((f"{name}.weight", (h,), "const", 1.0))
        spec.append((f"{name}.bias", (h,), "const", 0.0))

    spec.append(("conv_proj.weight", (h, 3, p, p), "normal",
                 math.sqrt(1.0 / (3 * p * p))))
    spec.append(("conv_proj.bias", (h,), "normal", 0.02))
    spec.append(("class_token", (1, 1, h), "normal", 0.02))
    spec.append(("encoder.pos_embedding", (1, seq, h), "normal", 0.02))
    for i in range(model["num_layers"]):
        base = _layer(i)
        ln(f"{base}.ln_1")
        xavier(f"{base}.self_attention.in_proj_weight", 3 * h, h)
        spec.append((f"{base}.self_attention.in_proj_bias", (3 * h,),
                     "normal", 0.02))
        spec.append((f"{base}.self_attention.out_proj.weight", (h, h),
                     "uniform", 1.0 / math.sqrt(h)))
        spec.append((f"{base}.self_attention.out_proj.bias", (h,),
                     "normal", 0.02))
        ln(f"{base}.ln_2")
        xavier(f"{base}.mlp.0.weight", mlp, h)
        spec.append((f"{base}.mlp.0.bias", (mlp,), "normal", 0.02))
        xavier(f"{base}.mlp.3.weight", h, mlp)
        spec.append((f"{base}.mlp.3.bias", (h,), "normal", 0.02))
    ln("encoder.ln")
    spec.append(("heads.head.weight", (model["num_classes"], h), "normal",
                 0.02))
    spec.append(("heads.head.bias", (model["num_classes"],), "normal", 0.02))
    return spec


def trainable(model):
    return [name for name, *_ in weight_spec(model)]


def _ln(w, name, x, mode):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + _EPS)
    return common.stored(y * w[f"{name}.weight"] + w[f"{name}.bias"], mode)


def forward(model, w, x, mode: str = "f32"):
    """Normalized float32 NHWC rows to logits."""
    h, heads, p = model["hidden_size"], model["num_heads"], model["patch_size"]
    hd = h // heads
    n = x.shape[0]

    def encoder_layer(w_l, x, base):
        y = _ln(w_l, f"{base}.ln_1", x, mode)
        qkv = common.linear(y, w_l[f"{base}.self_attention.in_proj_weight"],
                            w_l[f"{base}.self_attention.in_proj_bias"], mode)
        q, k, v = (t.reshape(n, -1, heads, hd).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = common.matmul(q, k.transpose(0, 1, 3, 2), mode)
        attn = common.stored(
            jax.nn.softmax(scores / math.sqrt(hd), axis=-1), mode)
        y = common.matmul(attn, v, mode)
        y = y.transpose(0, 2, 1, 3).reshape(n, -1, h)
        y = common.linear(y, w_l[f"{base}.self_attention.out_proj.weight"],
                          w_l[f"{base}.self_attention.out_proj.bias"], mode)
        x = x + y
        y = _ln(w_l, f"{base}.ln_2", x, mode)
        y = common.linear(y, w_l[f"{base}.mlp.0.weight"],
                          w_l[f"{base}.mlp.0.bias"], mode)
        y = common.stored(jax.nn.gelu(y, approximate=False), mode)
        y = common.linear(y, w_l[f"{base}.mlp.3.weight"],
                          w_l[f"{base}.mlp.3.bias"], mode)
        return common.stored(x + y, mode)

    x = common.conv2d(x, w["conv_proj.weight"], p, 0, mode)
    x = common.stored(x + w["conv_proj.bias"], mode).reshape(n, -1, h)
    cls = jnp.broadcast_to(w["class_token"], (n, 1, h))
    x = jnp.concatenate([cls, x], axis=1)
    x = common.stored(x + w["encoder.pos_embedding"], mode)
    for i in range(model["num_layers"]):
        base = _layer(i)
        w_l = {k: v for k, v in w.items() if k.startswith(base + ".")}
        # rematerialized per layer so that float32 at the timed batch fits
        x = jax.checkpoint(encoder_layer, static_argnums=(2,))(w_l, x, base)
    x = _ln(w, "encoder.ln", x, mode)
    return common.linear(x[:, 0], w["heads.head.weight"],
                         w["heads.head.bias"], mode)


def loss(model, w, batch, mode: str = "f32"):
    """Mean cross-entropy of one block of rows (``images`` uint8 NHWC,
    ``labels``)."""
    return common.image_loss(forward, model, w, batch, mode)


def example_input(model):
    return common.image_example_input(model)


def train_flops(model, rows: int) -> float:
    """Operations one optimizer step of ``rows`` rows needs, forward and
    backward, from the shapes: 2 per multiply-add of the patch projection, every Linear, the
    two attention products and the head, three times over (forward, input
    gradient, weight gradient); the patch projection reads the image and
    gets no input gradient. LayerNorm, softmax, GELU and SGD are not
    counted: they do not run on the MXU the peak is quoted for."""
    h, mlp, p = model["hidden_size"], model["mlp_dim"], model["patch_size"]
    seq = _tokens(model, model["image_size"])
    macs_patch = (seq - 1) * h * 3 * p * p
    per_layer = seq * (h * 3 * h + h * h + 2 * h * mlp) + 2 * seq * seq * h
    macs = model["num_layers"] * per_layer + h * model["num_classes"]
    return float(rows) * 2.0 * (3 * macs + 2 * macs_patch)
