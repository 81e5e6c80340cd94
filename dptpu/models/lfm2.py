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
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from dptpu.models import token_model
from dptpu.models.layers import uniform_bound_init
from dptpu.models.registry import register_model
from dptpu.models.token_model import (  # noqa: F401 (this model's names)
    Kept,
    RMSNorm,
    SparseExperts,
    held_expert_outputs,
    rotary,
)
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
        self.routing  # refuses experts held that are not among them

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def experts_here(self) -> Tuple[int, int]:
        """``(first, count)`` of the experts this chip holds."""
        return self.experts_held or (0, self.num_experts)

    @property
    def routing(self) -> token_model.Routing:
        """The expert layer's view of this configuration."""
        return token_model.Routing(
            experts=self.num_experts, held=self.experts_here,
            top_k=self.num_experts_per_tok, norm_topk=self.norm_topk_prob,
            norm_eps=ROUTE_NORM_EPS, scaling=self.routed_scaling_factor,
            use_bias=self.use_expert_bias,
            width=self.moe_intermediate_size)

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
            first, count = token_model.held_range(
                layers, self.num_hidden_layers, "layers")
            changes.update(
                layer_types=self.layer_types[first:first + count],
                num_hidden_layers=count,
                num_dense_layers=min(max(self.num_dense_layers - first, 0),
                                     count))
        if experts is not None:
            changes["experts_held"] = tuple(experts)
        if vocab is not None:
            _, count = token_model.held_range(
                vocab, self.vocab_size, "vocabulary rows")
            # ids are local to the slice (the data draws them below its
            # size), so only the count shapes anything on one chip
            changes["vocab_size"] = count
        if sequence_length is not None:
            if sequence_length < 1:
                raise ValueError("the sequence length must be positive")
            changes["sequence_length"] = int(sequence_length)
        return dataclasses.replace(self, **changes)


# the 1e-6 of the family's normalisation of a token's expert weights
ROUTE_NORM_EPS = 1e-6
route = functools.partial(token_model.route, eps=ROUTE_NORM_EPS)
_dense = token_model.dense


# What a step takes on the device beside the train state and the kept
# residuals: the temporaries of the fully rematerialised step (3.71 GB at
# 16,384 tokens a step; 3.42 before PR 42's compact buffers, 4.35 while
# they stood under the conditional: ``memory_analysis`` of the cell's
# step compiled for a described v5e at budget 0, PERF.md section 6, PR
# 44) and 15% of a 16.9 GB chip left to the allocator. Fixed: kept
# residuals are bounded by the budget, so a longer row or a larger share
# keeps less and the step fits where it fitted without them (a kept byte
# costs the compiled step 0.7 bytes there).
STEP_HEADROOM_BYTES = 6_300_000_000


def residual_classes(config: Lfm2Config, shape, dtype):
    """The residuals a rematerialised block can keep, by class: ``(what
    it is, the names it keeps, the bytes they hold over all layers)`` for
    a step on token rows of ``shape`` ``(rows, length)``. The order is
    the order of keeping: milliseconds of re-run forward saved per byte
    held on the chip, dearest first (310, 14, 10-12, 9-11 and 9 ms a GB
    at 16,384 tokens a step: PERF.md section 6, PR 34, which also says
    what was measured and left out, the routing; the expert layers'
    class by PR 44's readings of the layer under a block's
    rematerialisation, ``scripts/bench_experts.py``)."""
    rows, length = shape
    tokens, item = rows * length, jnp.dtype(dtype).itemsize
    attn = sum(t == "full_attention" for t in config.layer_types)
    conv = config.num_hidden_layers - attn
    heads, kv_heads, d = (config.num_attention_heads,
                          config.num_key_value_heads, config.head_dim)
    return (
        # the attention pads a row up to whole blocks, its residuals with it
        ("attention out+lse", attention_op.RESIDUAL_NAMES,
         attn * attention_op.residual_bytes(rows, length, heads, d, dtype)),
        ("q/k/v projections", ("attention_q", "attention_k", "attention_v"),
         attn * tokens * (heads + 2 * kv_heads) * d * item),
        # a short convolution's in_proj is three hidden sizes wide
        ("mixer projections",
         ("conv_in_proj", "conv_out_proj", "attention_out_proj"),
         ((3 + 1) * conv + attn) * tokens * config.hidden_size * item),
        token_model.expert_residuals(
            config.routing, tokens, config.hidden_size,
            config.num_hidden_layers - config.num_dense_layers, dtype),
        ("dense feed-forward", ("ffn_gate", "ffn_up"),
         config.num_dense_layers * 2 * tokens * config.intermediate_size
         * item),
    )


def kept_residuals(config: Lfm2Config, shape, dtype, budget: int) -> Kept:
    """The classes a step on token rows of ``shape`` keeps within
    ``budget`` bytes (``token_model.keep_within`` over
    ``residual_classes``)."""
    return token_model.keep_within(
        residual_classes(config, shape, dtype), budget)


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
            return x + token_model.SwiGLU(
                cfg.intermediate_size, self.dtype,
                name="feed_forward")(normed), *token_model.no_experts()
        out, sizes, compact = SparseExperts(cfg, self.dtype,
                                            name="feed_forward")(normed)
        return x + out, sizes, compact


class Lfm2(token_model.TokenModel):
    """The model: ``token_model.TokenModel`` says what ``__call__``
    takes and gives. The head is the embedding again (tied)."""

    config: Lfm2Config

    step_headroom_bytes = STEP_HEADROOM_BYTES

    def residual_classes(self, shape):
        return residual_classes(self.config, shape, self.dtype)

    @staticmethod
    def torch_key_map(variables):
        """The ``lfm2_moe`` checkpoint's names (``model.embed_tokens``,
        ``model.layers.N.{operator_norm, ffn_norm}``, ``.conv.{in_proj, conv,
        out_proj}``, ``.self_attn.{q_proj, k_proj, v_proj, out_proj,
        q_layernorm, k_layernorm}``, ``.feed_forward.{w1, w2, w3}``,
        ``.feed_forward.{gate, expert_bias}``,
        ``.feed_forward.experts.E.{w1, w2, w3}``, ``model.embedding_norm``;
        written from memory of the published layout, there is no network
        here). This file names its modules after them, so the map
        is mechanical: ``layers_N`` <-> ``layers.N``, ``experts_E`` <->
        ``experts.E``; every matrix is a torch Linear (OI <-> IO), the short
        convolution's taps are torch's depthwise ``[channels, 1, taps]``
        <-> ``[taps, channels]``, and ``expert_bias`` is a buffer with no
        ``.weight``."""
        out = {}
        for collection in ("params", "batch_stats"):
            flat = jax.tree_util.tree_flatten_with_path(
                variables.get(collection, {}))[0]
            for path, leaf in flat:
                names = tuple(p.key for p in path)
                mods = [n.replace("layers_", "layers.").replace(
                    "experts_", "experts.") for n in names]
                module = "model." + ".".join(mods[:-1])  # the leaf's module
                itself = "model." + ".".join(mods)  # a raw torch Parameter
                if names[-1] == "expert_bias":
                    key, kind = itself, "direct"
                elif names[-1] == "kernel":
                    key, kind = module + ".weight", "dense"
                elif names[-1] in ("scale", "embedding"):
                    key, kind = module + ".weight", "direct"
                elif names[-2:] == ("conv", "conv"):
                    key, kind = itself + ".weight", "conv1d_dw"
                elif leaf.ndim == 2:  # gate, an expert's w1/w2/w3
                    key, kind = itself + ".weight", "dense"
                else:
                    raise ValueError(f"no lfm2_moe key for {'/'.join(names)}")
                assert key not in out, f"duplicate torch key {key}"
                out[key] = (collection, names, kind)
        return out

    @nn.compact
    def __call__(self, tokens, train: bool = False, labels=None, mask=None):
        del train  # no dropout, no statistics: the two modes are one
        cfg = self.config
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=self.dtype,
                         embedding_init=token_model.dense_init,
                         name="embed_tokens")
        with jax.named_scope("embed"):
            x = embed(tokens)
        kept = self.kept_on(tokens.shape)
        block = token_model.rematerialised(Block, kept)
        loads = []
        for i, layer_type in enumerate(cfg.layer_types):
            x, *load = block(
                cfg, layer_type, i < cfg.num_dense_layers, self.dtype,
                name=f"layers_{i}")(x)
            if i >= cfg.num_dense_layers:
                loads.append(load)
        x = RMSNorm(cfg.norm_eps, self.dtype, name="embedding_norm")(x)
        with jax.named_scope("head"):
            if labels is None:
                return jnp.einsum(
                    "bsh,vh->bsv", x, embed.embedding.astype(self.dtype),
                    preferred_element_type=jnp.float32)
            sums = token_cross_entropy_sums(
                x.reshape(-1, cfg.hidden_size), embed.embedding,
                labels.reshape(-1), mask.reshape(-1))
        return token_model.with_counters(
            sums, loads,
            tokens.size * cfg.num_experts_per_tok * len(loads), kept,
            (None,) * sum(t == "full_attention" for t in cfg.layer_types),
            tokens.shape[1], attention_op.kernel_calls(
                tokens.shape[1], cfg.num_attention_heads,
                cfg.num_key_value_heads, cfg.head_dim, cfg.head_dim,
                self.dtype))


factory = functools.partial(token_model.factory, Lfm2)

# LFM2-8B-A1B as its config.json gives it
register_model(factory("lfm2_8b_a1b", Lfm2Config()))
