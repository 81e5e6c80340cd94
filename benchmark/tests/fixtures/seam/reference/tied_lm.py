"""A fixture family: an embedding, a scale, and the embedding again as
the head; the loss is the mean cross-entropy over the tokens the mask
keeps."""

import jax
import jax.numpy as jnp

from benchmark.reference import common


def weight_spec(model):
    v, h = model["vocab_size"], model["hidden_size"]
    return [("embed.weight", (v, h), "normal", 0.5),
            ("norm.weight", (h,), "const", 1.0),
            ("steps", (), "const", 0.0)]


def trainable(model):
    return ["embed.weight", "norm.weight"]  # ``steps`` is a buffer


def forward(model, w, tokens, mode: str = "f32"):
    x = w["embed.weight"][tokens] * w["norm.weight"]
    return common.matmul(x, w["embed.weight"].T, mode)


def loss(model, w, batch, mode: str = "f32"):
    logits = forward(model, w, batch["tokens"], mode)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, batch["labels"][..., None],
                                 axis=-1)[..., 0]
    mask = batch["mask"].astype(jnp.float32)
    return jnp.sum((logz - picked) * mask) / jnp.sum(mask)


def example_input(model):
    return jnp.zeros((1, model["sequence_length"]), jnp.int32)


def train_flops(model, rows: int) -> float:
    macs = model["sequence_length"] * model["vocab_size"] \
        * model["hidden_size"]
    return float(rows) * 2.0 * 3 * macs
