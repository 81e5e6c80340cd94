"""LFM2 mixture-of-experts language model (``model_type`` ``lfm2_moe``).

Registry-discoverable as ``-a lfm2_8b_a1b``: LiquidAI's LFM2-8B-A1B as
its ``config.json`` gives it
(https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json),
built from a configuration object (``Lfm2Config``, whose defaults are
that file's) and not from a table of variants. The equations:

* block: ``x = x + mixer(RMSNorm_operator(x))``;
  ``x = x + ffn(RMSNorm_ffn(x))``; after the last block
  ``RMSNorm_embedding``, then the head, which is the embedding again
  (tied).
* ``conv`` mixer, the gated short convolution: ``B, C, u = split3(W_in x)``;
  ``y = W_out (C * conv1d(B * u))`` with a depthwise causal ``conv1d`` of
  ``conv_L_cache`` taps along the sequence, no bias.
* ``full_attention`` mixer: grouped-query attention, RMSNorm over each
  head of q and k (weights of the head's size), rotary positions over the
  whole head, causal softmax in float32 with scale ``1/sqrt(head)``
  (``dptpu.ops.attention``: blockwise, the scores are never held), no
  biases.
* feed-forward of the first ``num_dense_layers`` layers: SwiGLU,
  ``W2 (silu(W1 x) * W3 x)``; of the others: ``num_experts`` experts of
  the same SwiGLU at ``moe_intermediate_size``, ``num_experts_per_tok``
  a token. Router: ``s = sigmoid(W_g x)`` in float32; the experts chosen
  are the top k of ``s + expert_bias``; their weights are ``s`` itself at
  those k over their sum + 1e-6 (``norm_topk_prob``), times
  ``routed_scaling_factor``. No shared expert, no auxiliary loss.
  ``expert_bias`` is a buffer (``batch_stats`` collection): nothing here
  trains or updates it.

**A chip's share.** ``Lfm2Config.held`` cuts the model to what one chip
of an expert-parallel, vocabulary-parallel, pipelined deployment holds:
a run of the published layers (a pipeline stage), ``first:count`` of each
layer's experts and ``first:count`` rows of the vocabulary. No width
changes. The router keeps its published width and k: it routes every
token over ALL experts, and the expert layer computes the part of the
result that the experts held here give, for the tokens routed to them,
with no capacity limit and no dropped token; what the absent experts
would add is left out (their chips would add it after the exchange, and
there is no code here that stands in for them). Ids, logits and loss are
over the vocabulary rows held.

**The task.** ``task = "tokens"``: ``fit()`` feeds such a model token
rows (``tokens:<N>``) and the step builder takes its per-token loss.
Called with ``labels`` and ``mask`` the model returns sums, not logits:
the loss goes over row blocks of the head (``dptpu.ops.loss``), so the
``[tokens, vocabulary]`` logits of a whole batch never exist. Called
without, it returns the logits (tests, small sizes).

Parameters are float32; ``dtype`` is the compute dtype (bfloat16 under
``--opt-level O2``); the norms' statistics, the router, the softmax and
the loss are float32 either way.

**What a step holds.** Every block is rematerialised on the way back
(``nn.remat``): a step holds each block's input and, while a block's
gradient is made, that one block's activations. Left at that, the
backward pass runs every block's forward a second time. So the values
that are dear to make again and cheap to hold carry names
(``jax.ad_checkpoint.checkpoint_name``), in classes (``residual_classes``,
dearest per byte first), and the rematerialisation keeps the classes
that fit ``Lfm2.residual_budget`` bytes (``kept_residuals``: whole
classes, in that order, from the shapes of the step being traced). A
kept value is the value the backward pass would have made again, in the
same dtype: the mathematics is the same at every budget. The budget is 0
unless someone who knows the device's memory sets it (``fit()`` does:
``Lfm2.fitted_to``), and at 0 nothing is kept. The sums report the
megabytes kept (``kept_residual_mb``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from dptpu.models.layers import uniform_bound_init
from dptpu.models.registry import register_model
from dptpu.ops import attention as attention_op
from dptpu.ops.attention import causal_attention
from dptpu.ops.loss import token_cross_entropy_sums

_LFM2_8B_A1B_LAYERS = (
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "conv", "full_attention", "conv",
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "full_attention", "conv", "conv",
)


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """``config.json`` of LFM2-8B-A1B under its own keys, then what the
    trainer adds (the sequence length) and the chip's share."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    layer_types: Tuple[str, ...] = _LFM2_8B_A1B_LAYERS
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 128000
    # the trainer's: tokens in a row
    sequence_length: int = 8192
    # the chip's share: ``layer_types`` and ``vocab_size`` above are
    # already cut to it by ``held``; the experts keep their published
    # count (the router's width) beside the ``(first, count)`` held here
    # (None: all of them)
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"{len(self.layer_types)} layer types for "
                f"{self.num_hidden_layers} layers")
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads do not divide the hidden size, or "
                             "key/value heads the query heads")
        if self.conv_bias:
            raise ValueError("conv_bias is not implemented (LFM2 has none)")
        first, count = self.experts_here
        if not (0 <= first and 0 < count
                and first + count <= self.num_experts):
            raise ValueError(
                f"experts held {first}:{count} are not among the "
                f"{self.num_experts} experts")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def experts_here(self) -> Tuple[int, int]:
        """``(first, count)`` of the experts this chip holds."""
        return self.experts_held or (0, self.num_experts)

    def held(self, layers: Optional[Tuple[int, int]] = None,
             experts: Optional[Tuple[int, int]] = None,
             vocab: Optional[Tuple[int, int]] = None,
             sequence_length: Optional[int] = None) -> "Lfm2Config":
        """This configuration cut to a chip's share: ``(first, count)``
        of the layers (a pipeline stage: the leading dense layers that
        fall before it are not in it), of each layer's experts, of the
        vocabulary's rows."""
        changes = {}
        if layers is not None:
            first, count = layers
            if not (0 <= first and 0 < count
                    and first + count <= self.num_hidden_layers):
                raise ValueError(
                    f"layers {first}:{count} are not among the "
                    f"{self.num_hidden_layers} layers")
            changes.update(
                layer_types=self.layer_types[first:first + count],
                num_hidden_layers=count,
                num_dense_layers=min(max(self.num_dense_layers - first, 0),
                                     count))
        if experts is not None:
            changes["experts_held"] = tuple(experts)
        if vocab is not None:
            first, count = vocab
            if not (0 <= first and 0 < count
                    and first + count <= self.vocab_size):
                raise ValueError(
                    f"vocabulary rows {first}:{count} are not among the "
                    f"{self.vocab_size} rows")
            # ids are local to the slice (the data draws them below its
            # size), so only the count shapes anything on one chip
            changes["vocab_size"] = count
        if sequence_length is not None:
            if sequence_length < 1:
                raise ValueError("the sequence length must be positive")
            changes["sequence_length"] = int(sequence_length)
        return dataclasses.replace(self, **changes)


_dense_init = nn.initializers.normal(0.02)


# What a step takes on the device beside the train state and the kept
# residuals: the temporaries of the fully rematerialised step (3.42 GB at
# 16,384 tokens a step, 2.03 GB of it the gradients of a five-layer
# share) and 15% of a 16.9 GB chip left to the allocator. Fixed: kept
# residuals are bounded by the budget, so a longer row or a larger share
# keeps less and the step fits where it fitted without them.
STEP_HEADROOM_BYTES = 6_000_000_000


@dataclasses.dataclass(frozen=True)
class Kept:
    """What the blocks of one step keep through the rematerialisation."""

    classes: Tuple[str, ...] = ()
    names: Tuple[str, ...] = ()
    bytes: int = 0

    @property
    def megabytes(self) -> int:
        return round(self.bytes / 1e6)

    def notice(self, budget: int) -> str:
        kept = ", ".join(self.classes) or "nothing (every block's forward " \
            "is run again on the way back)"
        return (f"=> residuals kept through the rematerialisation: {kept} "
                f"({self.megabytes:,} MB a step of a budget of "
                f"{round(budget / 1e6):,} MB)")


def residual_classes(config: Lfm2Config, shape, dtype):
    """The residuals a rematerialised block can keep, by class: ``(what
    it is, the names it keeps, the bytes they hold over all layers)`` for
    a step on token rows of ``shape`` ``(rows, length)``. The order is
    the order of keeping: milliseconds of re-run forward saved per byte
    held on the chip, dearest first (310, 14, 10-12 and 9 ms a GB at
    16,384 tokens a step: PERF.md section 6, PR 34, which also says what
    was measured and left out: the routing, the experts' grouped
    products)."""
    rows, length = shape
    tokens, item = rows * length, jnp.dtype(dtype).itemsize
    attn = sum(t == "full_attention" for t in config.layer_types)
    conv = config.num_hidden_layers - attn
    heads, kv_heads, d = (config.num_attention_heads,
                          config.num_key_value_heads, config.head_dim)
    # the attention pads a row up to whole blocks, its residuals with it
    block = min(attention_op.DEFAULT_BLOCK, length)
    padded = rows * -(-length // block) * block
    return (
        ("attention out+lse", attention_op.RESIDUAL_NAMES,
         attn * padded * heads * (d * item + 4)),
        ("q/k/v projections", ("attention_q", "attention_k", "attention_v"),
         attn * tokens * (heads + 2 * kv_heads) * d * item),
        # a short convolution's in_proj is three hidden sizes wide
        ("mixer projections",
         ("conv_in_proj", "conv_out_proj", "attention_out_proj"),
         ((3 + 1) * conv + attn) * tokens * config.hidden_size * item),
        ("dense feed-forward", ("ffn_gate", "ffn_up"),
         config.num_dense_layers * 2 * tokens * config.intermediate_size
         * item),
    )


def kept_residuals(config: Lfm2Config, shape, dtype, budget: int) -> Kept:
    """The classes a step on token rows of ``shape`` keeps within
    ``budget`` bytes: whole classes (all layers or none), in the order of
    ``residual_classes``, up to the first that no longer fits. A class
    the share has no layer for holds nothing and is not listed."""
    classes, names, total = [], [], 0
    for what, class_names, size in residual_classes(config, shape, dtype):
        if total + size > budget:
            break
        if size:
            classes.append(what)
            names.extend(class_names)
            total += size
    return Kept(tuple(classes), tuple(names), total)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * weight`` over the last axis, the
    statistics in float32."""

    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * lax.rsqrt(var + self.eps) * scale).astype(self.dtype)


def _dense(features: int, name: str, dtype):
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    kernel_init=_dense_init, name=name)


def rotary(x, theta: float):
    """Rotary positions over the whole head of ``x`` ``[B, S, H, D]``
    (the half-split convention: ``x * cos + rotate_half(x) * sin``),
    in float32."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x32 * jnp.cos(angles) + rotated * jnp.sin(angles)).astype(x.dtype)


class ShortConv(nn.Module):
    """The gated short convolution."""

    config: Lfm2Config
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        taps = cfg.conv_L_cache
        with jax.named_scope("conv_mixer"):
            b, c, u = jnp.split(checkpoint_name(
                _dense(3 * cfg.hidden_size, "in_proj", self.dtype)(x),
                "conv_in_proj"), 3, axis=-1)
            # [taps, channels]: tap k weighs the input taps-1-k steps back
            kernel = self.param(
                "conv", uniform_bound_init(1.0 / np.sqrt(taps)),
                (taps, cfg.hidden_size)).astype(self.dtype)
            gated = jnp.pad(b * u, ((0, 0), (taps - 1, 0), (0, 0)))
            length = x.shape[1]
            conv = sum(gated[:, k:k + length] * kernel[k]
                       for k in range(taps))
            return checkpoint_name(
                _dense(cfg.hidden_size, "out_proj", self.dtype)(c * conv),
                "conv_out_proj")


class Attention(nn.Module):
    """Grouped-query attention with per-head q/k RMSNorm and rotary
    positions."""

    config: Lfm2Config
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        heads, kv_heads, d = (cfg.num_attention_heads,
                              cfg.num_key_value_heads, cfg.head_dim)
        batch, length, _ = x.shape
        with jax.named_scope("attention"):
            # named before the norms: their way back needs what went in
            q, k, v = (
                checkpoint_name(
                    _dense(n * d, f"{which}_proj", self.dtype)(x),
                    f"attention_{which}").reshape(batch, length, n, d)
                for which, n in (("q", heads), ("k", kv_heads),
                                 ("v", kv_heads)))
            q = RMSNorm(cfg.norm_eps, self.dtype, name="q_layernorm")(q)
            k = RMSNorm(cfg.norm_eps, self.dtype, name="k_layernorm")(k)
            q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
            out = causal_attention(q, k, v, scale=d ** -0.5)
            return checkpoint_name(
                _dense(cfg.hidden_size, "out_proj", self.dtype)(
                    out.reshape(batch, length, heads * d)),
                "attention_out_proj")


class SwiGLU(nn.Module):
    """``W2 (silu(W1 x) * W3 x)``."""

    config: Lfm2Config
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        with jax.named_scope("dense_ffn"):
            gate = checkpoint_name(
                _dense(cfg.intermediate_size, "w1", self.dtype)(x),
                "ffn_gate")
            up = checkpoint_name(
                _dense(cfg.intermediate_size, "w3", self.dtype)(x), "ffn_up")
            return _dense(cfg.hidden_size, "w2", self.dtype)(
                nn.silu(gate) * up)


class _Expert(nn.Module):
    """One expert's three matrices, under its published index."""

    hidden: int
    width: int

    @nn.compact
    def __call__(self):
        return (self.param("w1", _dense_init, (self.hidden, self.width)),
                self.param("w3", _dense_init, (self.hidden, self.width)),
                self.param("w2", _dense_init, (self.width, self.hidden)))


def route(scores, bias, k: int, norm_topk: bool, scaling: float):
    """The experts of each token and their weights: the top ``k`` of
    ``scores + bias``, weighted by ``scores`` itself at those k."""
    _, chosen = lax.top_k(scores + bias, k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm_topk:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    return chosen, weights * scaling


def held_expert_outputs(x, chosen, weights, w1, w3, w2, first: int):
    """What the experts held here (``first .. first + len(w1)``) give for
    the tokens routed to them: ``[tokens, hidden]`` in ``x``'s dtype, and
    the tokens each of them got.

    The ``tokens x k`` slots are sorted by expert, the slots of absent
    experts behind all others; the held ones form one run per expert, and
    three grouped matrix products (``lax.ragged_dot``) go over the runs.
    The buffer is the worst case, every slot on a held expert, so no
    capacity bounds a run and no token is dropped; rows behind the last
    run belong to no group and cost the grouped product nothing. The
    sorted rows go back to their tokens by the inverse
    permutation and are summed by their weights.
    """
    tokens, k = chosen.shape
    count = w1.shape[0]
    local = chosen - first
    key = jnp.where((local >= 0) & (local < count), local, count).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    # rows behind the last run are in no group: the grouped product on
    # the chip leaves what it does not compute as it finds it (whatever
    # the memory held), in the result and in the cotangents alike, so
    # those rows are zeroed going in and coming out, which zeroes their
    # cotangents too (a token's slot on an absent expert adds nothing to
    # the token's gradient)
    in_a_run = jnp.arange(tokens * k)[:, None] < jnp.sum(sizes)
    rows = jnp.where(in_a_run, x[order // k], 0)
    hidden = nn.silu(lax.ragged_dot(rows, w1, sizes)) \
        * lax.ragged_dot(rows, w3, sizes)
    out = lax.ragged_dot(jnp.where(in_a_run, hidden, 0), w2, sizes)
    out = jnp.where(in_a_run, out, 0)
    back = jnp.argsort(order)
    out = out[back].reshape(tokens, k, -1)
    mixed = jnp.einsum("tkh,tk->th", out, weights.astype(out.dtype),
                       preferred_element_type=jnp.float32)
    return mixed.astype(x.dtype), sizes


class SparseExperts(nn.Module):
    """The expert layer: routes over all experts, computes the held
    ones' part."""

    config: Lfm2Config
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        first, count = cfg.experts_here
        batch, length, hidden = x.shape
        flat = x.reshape(batch * length, hidden)
        with jax.named_scope("router"):
            gate = self.param("gate", _dense_init,
                              (hidden, cfg.num_experts))
            scores = jax.nn.sigmoid(jnp.matmul(
                flat.astype(jnp.float32), gate,
                precision=lax.Precision.HIGHEST))
            bias = 0.0
            if cfg.use_expert_bias:
                bias = self.variable(
                    "batch_stats", "expert_bias", jnp.zeros,
                    (cfg.num_experts,), jnp.float32).value
            chosen, weights = route(
                scores, bias, cfg.num_experts_per_tok, cfg.norm_topk_prob,
                cfg.routed_scaling_factor)
        with jax.named_scope("experts"):
            w1, w3, w2 = (
                jnp.stack(ws).astype(self.dtype) for ws in zip(*(
                    _Expert(hidden, cfg.moe_intermediate_size,
                            name=f"experts_{first + e}")()
                    for e in range(count))))
            out, sizes = held_expert_outputs(
                flat, chosen, weights, w1, w3, w2, first)
        return out.reshape(x.shape), sizes


class Block(nn.Module):
    """One layer: a mixer and a feed-forward, each behind its norm and
    on the residual path. Returns the tokens each held expert got (none
    for a dense layer)."""

    config: Lfm2Config
    layer_type: str
    dense: bool
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="operator_norm")(x)
        if self.layer_type == "full_attention":
            x = x + Attention(cfg, self.dtype, name="self_attn")(normed)
        elif self.layer_type == "conv":
            x = x + ShortConv(cfg, self.dtype, name="conv")(normed)
        else:
            raise ValueError(f"unknown layer type {self.layer_type!r}")
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="ffn_norm")(x)
        if self.dense:
            return x + SwiGLU(cfg, self.dtype, name="feed_forward")(normed), \
                jnp.zeros((0,), jnp.int32)
        out, sizes = SparseExperts(cfg, self.dtype,
                                   name="feed_forward")(normed)
        return x + out, sizes


class Lfm2(nn.Module):
    """The model. ``__call__(tokens)`` gives float32 logits
    ``[B, S, vocab held]``; with ``labels`` and ``mask`` it gives the
    sums of the per-token loss and accuracies over the kept tokens, and
    the expert layers' load:

    ``loss_sum, count, correct1, correct5`` (float32 scalars);
    ``moe_counts`` ``[expert layers, experts held]`` int32, the tokens
    each held expert got; ``moe_slots``, the slots routed in all (tokens
    x k x expert layers, held or not); ``moe_dropped``, the tokens
    dropped: a constant 0, there for the day a capacity scheme moves it;
    ``kept_residual_mb``, the megabytes this step's blocks keep through
    the rematerialisation: a constant of the traced program.

    ``residual_budget``: the bytes the blocks may keep (``kept_residuals``);
    0 keeps nothing.
    """

    config: Lfm2Config
    dtype: Any = jnp.float32
    residual_budget: int = 0

    task = "tokens"

    def example_input(self):
        """One row as ``init`` takes it."""
        return jnp.zeros((1, self.config.sequence_length), jnp.int32)

    def kept(self, rows: int) -> Kept:
        """What a step on ``rows`` rows keeps."""
        return kept_residuals(
            self.config, (rows, self.config.sequence_length), self.dtype,
            self.residual_budget)

    def fitted_to(self, device_bytes: int, state_bytes: int) -> "Lfm2":
        """This model with the budget a device of ``device_bytes`` leaves
        once the train state (``state_bytes``) and the step's own room
        are taken; 0 where the device reports no size."""
        budget = device_bytes - state_bytes - STEP_HEADROOM_BYTES
        return self.clone(
            residual_budget=max(budget, 0) if device_bytes else 0)

    @nn.compact
    def __call__(self, tokens, train: bool = False, labels=None, mask=None):
        del train  # no dropout, no statistics: the two modes are one
        cfg = self.config
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=self.dtype,
                         embedding_init=_dense_init, name="embed_tokens")
        with jax.named_scope("embed"):
            x = embed(tokens)
        kept = kept_residuals(cfg, tokens.shape, self.dtype,
                              self.residual_budget)
        policy = jax.checkpoint_policies.save_only_these_names(
            *kept.names) if kept.names else None
        counts = []
        for i, layer_type in enumerate(cfg.layer_types):
            x, sizes = nn.remat(Block, policy=policy)(
                cfg, layer_type, i < cfg.num_dense_layers, self.dtype,
                name=f"layers_{i}")(x)
            if sizes.shape[0]:
                counts.append(sizes)
        x = RMSNorm(cfg.norm_eps, self.dtype, name="embedding_norm")(x)
        with jax.named_scope("head"):
            if labels is None:
                return jnp.einsum(
                    "bsh,vh->bsv", x, embed.embedding.astype(self.dtype),
                    preferred_element_type=jnp.float32)
            sums = token_cross_entropy_sums(
                x.reshape(-1, cfg.hidden_size), embed.embedding,
                labels.reshape(-1), mask.reshape(-1))
        if counts:
            sums["moe_counts"] = jnp.stack(counts)
            sums["moe_slots"] = jnp.asarray(
                tokens.size * cfg.num_experts_per_tok * len(counts),
                jnp.int32)
            sums["moe_dropped"] = jnp.zeros((), jnp.int32)
        sums["kept_residual_mb"] = jnp.asarray(kept.megabytes, jnp.int32)
        return sums


def _pair(text: str, what: str) -> Tuple[int, int]:
    try:
        first, count = (int(part) for part in str(text).split(":"))
    except ValueError:
        raise ValueError(
            f"{what} {text!r} must be FIRST:COUNT, two whole numbers "
            f"(0:8 holds the first eight)") from None
    return first, count


def factory(name: str, published: Lfm2Config):
    """A registry factory for ``published``, whole or a chip's share of
    it: ``layers``, ``experts`` and ``vocab`` are ``"first:count"`` (the
    trainer's ``--layers``, ``--experts``, ``--vocab-rows``),
    ``sequence_length`` its ``--seq-len``. ``fit()`` reads ``task`` off
    the factory before it builds anything: the data source, the step's
    loss and the arguments a factory is handed follow from it."""

    def make(dtype=jnp.float32, layers=None, experts=None, vocab=None,
             sequence_length=None):
        return Lfm2(published.held(
            layers=_pair(layers, "--layers") if layers else None,
            experts=_pair(experts, "--experts") if experts else None,
            vocab=_pair(vocab, "--vocab-rows") if vocab else None,
            sequence_length=sequence_length), dtype=dtype)

    make.__name__ = name
    make.task = Lfm2.task
    return make


# LFM2-8B-A1B as its config.json gives it
register_model(factory("lfm2_8b_a1b", Lfm2Config()))
