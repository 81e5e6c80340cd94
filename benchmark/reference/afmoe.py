"""Plain reference for the ``afmoe`` family (Arcee Trinity-Mini,
https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json).

The published equations in straightforward ``jax.numpy``, float32 at
``Precision.HIGHEST``, the checkpoint's leaf names, nothing of ``dptpu``:

* ``x = embed_tokens[ids] * sqrt(hidden_size)`` (``mup_enabled``); every
  block ``x = x + rms(attn(rms(x, input_layernorm)),
  post_attention_layernorm)``, then ``x = x + rms(ffn(rms(x,
  pre_mlp_layernorm)), post_mlp_layernorm)``: four norms; after the last
  block ``rms(x, model.norm)``, then ``x @ lm_head.T`` (untied, unscaled).
* attention: ``q_proj``, ``k_proj``, ``v_proj`` and ``gate_proj`` (as
  wide as the heads' output), RMSNorm over each head of q and k
  (``q_norm``, ``k_norm``). A ``sliding_attention`` layer rotates q and k
  (rotary positions over the whole head, halves rotated) and shows query
  ``i`` the keys ``0 <= i - j < sliding_window``; a ``full_attention``
  layer takes NO positions and shows every key behind the query. Scores
  times ``head_dim ** -0.5``, softmax, ``o_proj(attn * sigmoid(gate))``.
  PLAIN attention: one head's whole ``[S, S]`` scores at a time under the
  mask, the heads one after another under ``jax.checkpoint``.
* feed-forward: SwiGLU ``down(silu(gate x) * up x)`` at
  ``intermediate_size`` in the layers numbered below
  ``first_expert_layer``; in the others ``shared_experts(x) +
  routed(x)``: the router ``s = sigmoid(router.gate x)`` (float32 in
  every mode), the top k of ``s + expert_bias``, weights ``s`` at those k
  over their sum + 1e-20 (``route_norm``), times ``route_scale``, and
  PLAINLY every held expert over every token, weighted by what the router
  gave it (0 where it was not chosen). The share: only the experts
  ``experts_first .. + experts_held`` exist here; what the others would
  add is left out, as in the program.

The loss is on a block of rows, each row's mean cross-entropy over the
tokens its mask keeps, averaged over the block's rows.

**What is compiled.** A run of the benchmark has 360 s and a float32
``Precision.HIGHEST`` product costs seconds to compile, so matrices that
multiply the same input lie side by side in ONE product (the attention's
four, ``gate_proj`` beside ``up_proj``), the heads are one ``lax.map``
and the held experts one ``lax.scan``: the same sums, fewer pieces. The
layers are walked one after another, each rematerialised on the way back
(``jax.checkpoint``): no stack of their leaves is made (a stack is a
copy of the weights beside the parameters, their gradient and the seeded
copy the driver keeps, 8.5 GB of the chip's 16.9 already).

Departures from the published model, all in the configuration's
``assumed``: ``expert_bias`` a buffer that nothing trains (``trainable``
says how the driver is handed it), no auxiliary loss, the equations and
the leaf names from memory of the family's released code.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import common

_P = "model."
_ROUTE_NORM_EPS = 1e-20
_SWIGLU = ("gate_proj", "up_proj", "down_proj")


def _numbered(model):
    """``(published number, type)`` of each layer held."""
    first = model["layers_first"]
    return [(first + k, kind) for k, kind in enumerate(model["layer_types"])]


def _held(model):
    first = model["experts_first"]
    return range(first, first + model["experts_held"])


def _is_dense(model, i: int) -> bool:
    return i < model["first_expert_layer"]


def _swiglu_spec(prefix, h, width):
    return [(prefix + "gate_proj.weight", (width, h), "normal", 0.02),
            (prefix + "up_proj.weight", (width, h), "normal", 0.02),
            (prefix + "down_proj.weight", (h, width), "normal", 0.02)]


def weight_spec(model):
    """Every leaf under its checkpoint name: matrices N(0, 0.02), norms'
    weights 1, ``expert_bias`` N(0, 0.003): small beside the scores'
    spread, so that it decides a few tokens' experts and not the load."""
    h, d = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    spec = [(_P + "embed_tokens.weight", (model["vocab_size"], h),
             "normal", 0.02)]
    for i, _ in _numbered(model):
        p = f"{_P}layers.{i}."
        a = p + "self_attn."
        spec += [
            (p + "input_layernorm.weight", (h,), "const", 1.0),
            (a + "q_proj.weight", (heads * d, h), "normal", 0.02),
            (a + "k_proj.weight", (kv * d, h), "normal", 0.02),
            (a + "v_proj.weight", (kv * d, h), "normal", 0.02),
            (a + "gate_proj.weight", (heads * d, h), "normal", 0.02),
            (a + "q_norm.weight", (d,), "const", 1.0),
            (a + "k_norm.weight", (d,), "const", 1.0),
            (a + "o_proj.weight", (h, heads * d), "normal", 0.02),
            (p + "post_attention_layernorm.weight", (h,), "const", 1.0),
            (p + "pre_mlp_layernorm.weight", (h,), "const", 1.0),
        ]
        f = p + "mlp."
        if _is_dense(model, i):
            spec += _swiglu_spec(f, h, model["intermediate_size"])
        else:
            width, routed = (model["moe_intermediate_size"],
                             model["router_experts"])
            spec += [(f + "router.gate.weight", (routed, h), "normal", 0.02),
                     (f + "expert_bias", (routed,), "normal", 0.003)]
            for e in _held(model):
                spec += _swiglu_spec(f"{f}experts.{e}.", h, width)
            spec += _swiglu_spec(f + "shared_experts.", h,
                                 width * model["num_shared_experts"])
        spec.append((p + "post_mlp_layernorm.weight", (h,), "const", 1.0))
    return spec + [(_P + "norm.weight", (h,), "const", 1.0),
                   ("lm_head.weight", (model["vocab_size"], h), "normal",
                    0.02)]


def trainable(model):
    """Every leaf, ``expert_bias`` among them, although nothing trains
    it: the training driver closes over what is not listed here, so a
    seeded buffer would be a constant of the compiled loss and every seed
    another program. Listed, it is an argument. It enters only the CHOICE
    of experts, which has no derivative: its gradient is identically
    zero, AdamW's step on a zero gradient is zero and decays no vector,
    so it stays where it was seeded, bit for bit, and the program keeps
    it as a buffer (``batch_stats``), off the compared trees on both
    sides."""
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


def visible(length: int, window=None):
    """``[S, S]``: whether key ``j`` is visible to query ``i``: behind it
    or at it, and under a window less than ``window`` behind."""
    behind = jnp.arange(length)[:, None] - jnp.arange(length)[None, :]
    mask = behind >= 0
    return mask if window is None else mask & (behind < window)


def _attention(model, w, a, sliding: bool, x, mode):
    """``a`` prefixes the attention's leaves in ``w``; the four matrices
    that multiply ``x`` side by side in ONE product (module docstring,
    "What is compiled")."""
    heads, kv, d = (model["num_attention_heads"],
                    model["num_key_value_heads"], model["head_dim"])
    eps, length = model["rms_norm_eps"], x.shape[0]
    sizes = np.cumsum([heads * d, kv * d, kv * d])
    q, k, v, gate = jnp.split(_linear(x, jnp.concatenate(
        [w[f"{a}{which}_proj.weight"] for which in ("q", "k", "v", "gate")]),
        mode), sizes, axis=-1)
    q = common.stored(_rms(q.reshape(length, heads, d),
                           w[a + "q_norm.weight"], eps), mode)
    k = common.stored(_rms(k.reshape(length, kv, d),
                           w[a + "k_norm.weight"], eps), mode)
    v = v.reshape(length, kv, d)
    if sliding:
        theta = float(model["rope_theta"])
        q = common.stored(_rotary(q, theta), mode)
        k = common.stored(_rotary(k, theta), mode)
    mask = visible(length, model["sliding_window"] if sliding else None)

    @jax.checkpoint
    def one_head(q_h, k_h, v_h):
        scores = common.matmul(q_h, k_h.T, mode) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return common.matmul(probs, v_h, mode)

    group = heads // kv
    out = lax.map(
        lambda h: one_head(q[:, h], k[:, h // group], v[:, h // group]),
        jnp.arange(heads))  # [heads, S, D]
    out = common.stored(out, mode).transpose(1, 0, 2).reshape(length,
                                                              heads * d)
    gated = common.stored(
        out * common.stored(jax.nn.sigmoid(gate), mode), mode)
    return _linear(gated, w[a + "o_proj.weight"], mode)


def _swiglu(x, w, prefix, mode):
    """``down(silu(gate x) * up x)``; ``gate_proj`` beside ``up_proj`` in
    one product."""
    gate = w[prefix + "gate_proj.weight"]
    both = _linear(x, jnp.concatenate([gate, w[prefix + "up_proj.weight"]]),
                   mode)
    width = gate.shape[0]
    hidden = common.stored(jax.nn.silu(both[:, :width]) * both[:, width:],
                           mode)
    return _linear(hidden, w[prefix + "down_proj.weight"], mode)


def route(model, scores, bias):
    """``(chosen [S, k], weights [S, k])``: the top k of ``scores + bias``
    (one group: nothing limits the choice) weighted by ``scores``
    itself."""
    _, chosen = lax.top_k(scores + bias, model["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if model["route_norm"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + _ROUTE_NORM_EPS)
    return chosen, weights * model["route_scale"]


def routed_experts(model, w, f, x, mode, experts=None):
    """The part of the routed result that ``experts`` (default: the ones
    held) give, each over every token, weighted by the router."""
    scores = jax.nn.sigmoid(jnp.matmul(
        x, w[f + "router.gate.weight"].T, precision=lax.Precision.HIGHEST))
    chosen, weights = route(model, scores, w[f + "expert_bias"])
    held = list(_held(model) if experts is None else experts)
    matrices = [[w[f"{f}experts.{e}.{name}.weight"] for e in held]
                for name in _SWIGLU]
    # a stack is a copy of the layer's experts (0.4 GB at 16 of them), and
    # what depends on the weights alone the compiler makes at the
    # program's start, every layer's at once, and keeps their gradients
    # to its end: tied to x, a layer's stack is made when the layer runs
    matrices, x = lax.optimization_barrier((matrices, x))
    stacked = [jnp.stack(ms) for ms in matrices]

    @jax.checkpoint
    def add_expert(out, one):
        e, *matrices = one
        share = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        one_w = {f"{name}.weight": m for name, m in zip(_SWIGLU, matrices)}
        return out + share[:, None] * _swiglu(x, one_w, "", mode), None

    # one expert after another (a scan, so that the compiler sees one
    # expert's program and not one a held expert a layer)
    out, _ = lax.scan(add_expert, jnp.zeros_like(x),
                      (jnp.asarray(held), *stacked))
    return out


def shared_expert(model, w, f, x, mode):
    """What every token takes, on every chip alike."""
    return _swiglu(x, w, f + "shared_experts.", mode)


def _block(model, w, i: int, kind: str, x, mode):
    """Published layer ``i`` of type ``kind``."""
    eps, p = model["rms_norm_eps"], f"{_P}layers.{i}."

    def rms(x, name):
        return common.stored(_rms(x, w[f"{p}{name}.weight"], eps), mode)

    attended = common.stored(_attention(
        model, w, p + "self_attn.", kind == "sliding_attention",
        rms(x, "input_layernorm"), mode), mode)
    x = common.stored(x + rms(attended, "post_attention_layernorm"), mode)
    normed, f = rms(x, "pre_mlp_layernorm"), p + "mlp."
    if _is_dense(model, i):
        ffn = _swiglu(normed, w, f, mode)
    else:
        ffn = shared_expert(model, w, f, normed, mode) \
            + common.stored(routed_experts(model, w, f, normed, mode), mode)
    return common.stored(
        x + rms(common.stored(ffn, mode), "post_mlp_layernorm"), mode)


def forward(model, w, tokens, mode: str = "f32"):
    """Float32 logits ``[S, vocabulary held]`` of ONE row of ids; every
    block is rematerialised on the way back."""
    x = common.stored(w[_P + "embed_tokens.weight"][tokens], mode)
    if model["mup_enabled"]:
        x = common.stored(x * math.sqrt(model["hidden_size"]), mode)
    for i, kind in _numbered(model):
        p = f"{_P}layers.{i}."
        here = {name: leaf for name, leaf in w.items() if name.startswith(p)}
        x = jax.checkpoint(
            lambda x, here, i=i, kind=kind: _block(model, here, i, kind, x,
                                                   mode))(x, here)
    x = common.stored(
        _rms(x, w[_P + "norm.weight"], model["rms_norm_eps"]), mode)
    return common.matmul(x, w["lm_head.weight"].T, mode)


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


def visible_keys(model, kind: str) -> int:
    """The (query, key) pairs the mask of a ``kind`` layer keeps in one
    row: ``sum_p min(p + 1, window)``, the whole causal triangle for a
    ``full_attention`` layer."""
    length = model["sequence_length"]
    window = min(model["sliding_window"], length) \
        if kind == "sliding_attention" else length
    return window * (window + 1) // 2 + (length - window) * window


def forward_flops_per_row(model) -> float:
    """Multiply-adds x 2 of one row's forward pass, the MXU's share: the
    attention's five projections (the gate's among them) and its two
    products over the pairs the mask KEEPS (a band under the window, not
    the causal half), the dense feed-forward, the router, the shared
    expert, the held experts at the MEAN load (k x held / routed experts
    a token a layer: what uniform routing gives;
    ``expert_local_slot_share`` says how far that holds), the head."""
    h, d = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    length = model["sequence_length"]
    projections = 2.0 * h * d * (3 * heads + 2 * kv)
    mean_experts = model["num_experts_per_tok"] * model["experts_held"] \
        / model["router_experts"]
    sparse = 2.0 * h * model["router_experts"] \
        + 6.0 * h * model["moe_intermediate_size"] \
        * (model["num_shared_experts"] + mean_experts)
    total = length * 2.0 * h * model["vocab_size"]
    for i, kind in _numbered(model):
        total += length * (projections + (
            6.0 * h * model["intermediate_size"] if _is_dense(model, i)
            else sparse))
        total += 2.0 * 2.0 * heads * d * visible_keys(model, kind)
    return total


def train_flops(model, rows: int) -> float:
    """Operations of one step of ``rows`` rows: forward and backward
    (x 3), no recomputation."""
    return float(rows) * 3.0 * forward_flops_per_row(model)
