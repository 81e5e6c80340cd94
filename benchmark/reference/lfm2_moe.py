"""Plain reference for the ``lfm2_moe`` family (LiquidAI LFM2-8B-A1B,
https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json).

The published equations in straightforward ``jax.numpy``, float32 at
``Precision.HIGHEST``, the checkpoint's leaf names, nothing of ``dptpu``:

* block: ``x = x + mixer(rms(x, operator_norm))``;
  ``x = x + ffn(rms(x, ffn_norm))``; after the last block
  ``rms(x, embedding_norm)``, then the tied head ``x @ embed_tokens.T``.
* ``conv`` mixer: ``B, C, u = split3(in_proj x)``;
  ``out_proj(C * conv1d(B * u))``, ``conv1d`` depthwise, causal,
  ``conv_L_cache`` taps (torch ``[channels, 1, taps]``), no bias.
* ``full_attention`` mixer: q/k/v projections, RMSNorm over each head of
  q and k, rotary positions over the whole head (half-split convention),
  causal softmax with scale ``1/sqrt(head)``, ``out_proj``. PLAIN
  attention: one head's whole ``[S, S]`` scores at a time, the heads
  one after another under ``jax.checkpoint`` so that a row of 8,192
  tokens fits in float32.
* feed-forward: SwiGLU ``w2(silu(w1 x) * w3 x)`` in the leading dense
  layers; in the others the router ``s = sigmoid(gate x)`` (float32 in
  every mode, as the configuration states), the top k of
  ``s + expert_bias``, weights ``s`` at those k over their sum + 1e-6,
  and PLAINLY every held expert over every token, weighted by what the
  router gave it (0 where it was not chosen). The share: only the
  experts ``experts_first .. + experts_held`` exist here, and what the
  others would add is left out, as in the program.

The loss is on a block of rows, each row's mean cross-entropy over the
tokens its mask keeps, averaged over the block's rows (a row weighs the
same however long its kept part is, so the blocks' mean is the batch's).

Departures from the published model, all in the configuration's
``assumed``: tied embedding and head, sigmoid scoring with the 1e-6,
``expert_bias`` a buffer that nothing trains (``trainable`` says how the
driver is handed it), no auxiliary loss, the leaf names from memory.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import common

_P = "model."


def _layer_is_dense(model, i: int) -> bool:
    return i < model["num_dense_layers"]


def weight_spec(model):
    """Every leaf under its checkpoint name: matrices N(0, 0.02), norms'
    weights 1, the convolution's taps U(+-1/sqrt(taps)) (torch's Conv1d
    default), ``expert_bias`` N(0, 0.003): small beside the scores'
    spread, so that it decides a few tokens' experts and not the load."""
    h, d = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    taps = model["conv_L_cache"]
    spec = [(_P + "embed_tokens.weight", (model["vocab_size"], h),
             "normal", 0.02)]
    for i, kind in enumerate(model["layer_types"]):
        p = f"{_P}layers.{i}."
        spec.append((p + "operator_norm.weight", (h,), "const", 1.0))
        if kind == "conv":
            spec += [(p + "conv.in_proj.weight", (3 * h, h), "normal", 0.02),
                     (p + "conv.conv.weight", (h, 1, taps), "uniform",
                      1.0 / math.sqrt(taps)),
                     (p + "conv.out_proj.weight", (h, h), "normal", 0.02)]
        else:
            a = p + "self_attn."
            spec += [(a + "q_proj.weight", (heads * d, h), "normal", 0.02),
                     (a + "k_proj.weight", (kv * d, h), "normal", 0.02),
                     (a + "v_proj.weight", (kv * d, h), "normal", 0.02),
                     (a + "out_proj.weight", (h, heads * d), "normal", 0.02),
                     (a + "q_layernorm.weight", (d,), "const", 1.0),
                     (a + "k_layernorm.weight", (d,), "const", 1.0)]
        spec.append((p + "ffn_norm.weight", (h,), "const", 1.0))
        f = p + "feed_forward."
        if _layer_is_dense(model, i):
            width = model["intermediate_size"]
            spec += [(f + "w1.weight", (width, h), "normal", 0.02),
                     (f + "w3.weight", (width, h), "normal", 0.02),
                     (f + "w2.weight", (h, width), "normal", 0.02)]
            continue
        width, routed = model["moe_intermediate_size"], model["router_experts"]
        spec += [(f + "gate.weight", (routed, h), "normal", 0.02),
                 (f + "expert_bias", (routed,), "normal", 0.003)]
        for e in _held(model):
            x = f"{f}experts.{e}."
            spec += [(x + "w1.weight", (width, h), "normal", 0.02),
                     (x + "w3.weight", (width, h), "normal", 0.02),
                     (x + "w2.weight", (h, width), "normal", 0.02)]
    spec.append((_P + "embedding_norm.weight", (h,), "const", 1.0))
    return spec


def _held(model):
    first = model["experts_first"]
    return range(first, first + model["experts_held"])


def trainable(model):
    """Every leaf, ``expert_bias`` among them, although nothing trains
    it. The training driver closes over what is not listed here, so a
    seeded buffer would be a constant of the compiled loss: every seed
    another program, and a compile-cache miss worth a minute and a half
    a run. Listed, it is an argument. It enters only the CHOICE of
    experts, which has no derivative: its gradient is identically zero,
    AdamW's step on a zero gradient is zero and decays no vector, so it
    stays where it was seeded, bit for bit (tests/test_lfm2.py holds
    that), and the program keeps it as a buffer (``batch_stats``), off
    the compared trees on both sides."""
    return [name for name, *_ in weight_spec(model)]


def _rms(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * weight


def _linear(x, weight, mode):
    """torch ``nn.Linear`` without bias: ``weight`` is (out, in)."""
    return common.matmul(x, weight.T, mode)


def _rotary(x, theta):
    """``x`` is ``[S, heads, D]``: ``x * cos + rotate_half(x) * sin``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(angles) \
        + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(angles)


def _conv_mixer(model, w, p, x, mode):
    taps = model["conv_L_cache"]
    b, c, u = jnp.split(_linear(x, w[p + "conv.in_proj.weight"], mode), 3,
                        axis=-1)
    gated = jnp.pad(common.stored(b * u, mode), ((taps - 1, 0), (0, 0)))
    kernel = w[p + "conv.conv.weight"][:, 0, :]  # [channels, taps]
    length = x.shape[0]
    # torch's conv1d is a cross-correlation: tap k weighs the input
    # taps-1-k steps back
    conv = sum(gated[k:k + length] * kernel[:, k] for k in range(taps))
    return _linear(common.stored(c * common.stored(conv, mode), mode),
                   w[p + "conv.out_proj.weight"], mode)


def _attention(model, w, p, x, mode):
    heads, kv, d = (model["num_attention_heads"],
                    model["num_key_value_heads"], model["head_dim"])
    a = p + "self_attn."
    length = x.shape[0]
    q = _linear(x, w[a + "q_proj.weight"], mode).reshape(length, heads, d)
    k = _linear(x, w[a + "k_proj.weight"], mode).reshape(length, kv, d)
    v = _linear(x, w[a + "v_proj.weight"], mode).reshape(length, kv, d)
    eps, theta = model["norm_eps"], float(model["rope_theta"])
    q = common.stored(_rms(q, w[a + "q_layernorm.weight"], eps), mode)
    k = common.stored(_rms(k, w[a + "k_layernorm.weight"], eps), mode)
    q = common.stored(_rotary(q, theta), mode)
    k = common.stored(_rotary(k, theta), mode)
    causal = jnp.tril(jnp.ones((length, length), bool))

    @jax.checkpoint
    def one_head(q_h, k_h, v_h):
        scores = common.matmul(q_h, k_h.T, mode) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return common.matmul(probs, v_h, mode)

    group = heads // kv
    out = lax.map(
        lambda h: one_head(q[:, h], k[:, h // group], v[:, h // group]),
        jnp.arange(heads))  # [heads, S, D]
    out = common.stored(out, mode).transpose(1, 0, 2).reshape(length,
                                                              heads * d)
    return _linear(out, w[a + "out_proj.weight"], mode)


def _swiglu(x, w1, w3, w2, mode):
    hidden = common.stored(
        jax.nn.silu(_linear(x, w1, mode)) * _linear(x, w3, mode), mode)
    return _linear(hidden, w2, mode)


def route(model, scores, bias):
    """``(chosen [S, k], weights [S, k])``: the top k of ``scores + bias``
    weighted by ``scores`` itself."""
    _, chosen = lax.top_k(scores + bias, model["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if model["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    return chosen, weights * model["routed_scaling_factor"]


def expert_layer(model, w, f, x, mode, experts=None):
    """The part of the expert layer's result that ``experts`` (default:
    the ones held) give, each over every token, weighted by the router."""
    scores = jax.nn.sigmoid(jnp.matmul(
        x, w[f + "gate.weight"].T, precision=lax.Precision.HIGHEST))
    bias = w[f + "expert_bias"] if model["use_expert_bias"] else 0.0
    chosen, weights = route(model, scores, bias)
    held = list(_held(model) if experts is None else experts)
    stacked = [jnp.stack([w[f"{f}experts.{e}.{name}.weight"] for e in held])
               for name in ("w1", "w3", "w2")]

    def add_expert(out, one):
        e, w1, w3, w2 = one
        share = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        return out + share[:, None] * _swiglu(x, w1, w3, w2, mode), None

    # one expert after another (a scan, so that the compiler sees one
    # expert's program and not one a held expert a layer)
    out, _ = lax.scan(add_expert, jnp.zeros_like(x),
                      (jnp.asarray(held), *stacked))
    return out


def _layer(model, i, kind, mode, w, x):
    p = f"{_P}layers.{i}."
    eps = model["norm_eps"]
    normed = common.stored(_rms(x, w[p + "operator_norm.weight"], eps), mode)
    mixer = _conv_mixer if kind == "conv" else _attention
    x = common.stored(x + mixer(model, w, p, normed, mode), mode)
    normed = common.stored(_rms(x, w[p + "ffn_norm.weight"], eps), mode)
    f = p + "feed_forward."
    if _layer_is_dense(model, i):
        ffn = _swiglu(normed, w[f + "w1.weight"], w[f + "w3.weight"],
                      w[f + "w2.weight"], mode)
    else:
        ffn = common.stored(expert_layer(model, w, f, normed, mode), mode)
    return common.stored(x + ffn, mode)


def forward(model, w, tokens, mode: str = "f32"):
    """Float32 logits ``[S, vocabulary held]`` of ONE row of ids; every
    layer is rematerialised on the way back (``jax.checkpoint``)."""
    embed = w[_P + "embed_tokens.weight"]
    x = common.stored(embed[tokens], mode)
    for i, kind in enumerate(model["layer_types"]):
        x = jax.checkpoint(
            functools.partial(_layer, model, i, kind, mode))(w, x)
    x = common.stored(
        _rms(x, w[_P + "embedding_norm.weight"], model["norm_eps"]), mode)
    return common.matmul(x, embed.T, mode)


def loss(model, w, batch, mode: str = "f32"):
    """Mean over the block's rows of the row's mean cross-entropy over
    its kept tokens."""
    rows = []
    for tokens, labels, mask in zip(batch["tokens"], batch["labels"],
                                    batch["mask"]):
        logits = forward(model, w, tokens, mode)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - picked
        kept = mask.astype(jnp.float32)
        rows.append(jnp.sum(nll * kept) / jnp.sum(kept))
    return jnp.mean(jnp.stack(rows))


def example_input(model):
    """One row of ids, for the shapes of the program's ``model.init``."""
    return jnp.zeros((1, model["sequence_length"]), jnp.int32)


def forward_flops_per_token(model) -> float:
    """Multiply-adds x 2 of one token's forward pass, the MXU's share:
    the projections, the attention's two products over the causal half
    of the row, the experts at the MEAN load (k x held / routed experts a
    token a layer: what uniform routing gives; ``expert_local_slot_share``
    says how far that holds), the router, the head."""
    h, d = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    length = model["sequence_length"]
    total = 0.0
    for i, kind in enumerate(model["layer_types"]):
        if kind == "conv":
            total += 2.0 * h * (3 * h + h)
        else:
            total += 2.0 * h * (2 * heads * d + 2 * kv * d)
            total += 4.0 * (length / 2.0) * heads * d
        if _layer_is_dense(model, i):
            total += 6.0 * h * model["intermediate_size"]
        else:
            mean_experts = model["num_experts_per_tok"] \
                * model["experts_held"] / model["router_experts"]
            total += 2.0 * h * model["router_experts"]
            total += mean_experts * 6.0 * h * model["moe_intermediate_size"]
    return total + 2.0 * h * model["vocab_size"]


def train_flops(model, rows: int) -> float:
    """Operations of one step of ``rows`` rows: forward and backward
    (x 3), no recomputation."""
    return float(rows) * model["sequence_length"] * 3.0 \
        * forward_flops_per_token(model)
