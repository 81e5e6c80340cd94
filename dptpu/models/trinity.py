"""Trinity (``model_type`` ``afmoe``): sliding-window and global attention
mixed, a sigmoid gate on the attention's output, four norms a block, a
shared expert beside the routed ones.

Registry-discoverable as ``-a trinity_mini``: Arcee's Trinity-Mini
(26B-A3B) as its ``config.json`` gives it
(https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json),
built from a configuration object (``TrinityConfig``, whose defaults are
that file's). The equations, as the family's released modeling code has
them:

* embedding times ``sqrt(hidden_size)`` (``mup_enabled``); after the last
  block ``RMSNorm_final``, then ``lm_head``, a ``[vocab, hidden]`` matrix
  of its own (``tie_word_embeddings`` false); the logits are not scaled.
  No bias anywhere.
* block, FOUR norms: ``x = x + RMSNorm_post_attention(Attn(RMSNorm_input(
  x)))``; ``x = x + RMSNorm_post_mlp(F(RMSNorm_pre_mlp(x)))``: each
  branch is normed going in and coming out.
* attention: grouped queries (32 heads over 4 key/value heads of 128),
  RMSNorm over each head of q and k, ``v`` and a GATE ``g = W_gate x``
  as wide as the heads' output. Layer ``i`` is ``layer_types[i]``:
  ``sliding_attention`` (three in four) rotates q and k (rotary positions
  over the whole head, halves rotated, ``rope_theta``) and shows query
  ``i`` the keys ``0 <= i - j < sliding_window``; ``full_attention``
  (every fourth) takes NO positions and shows every key behind the query.
  Scores times ``head_dim ** -0.5``, softmax in float32
  (``dptpu.ops.attention``: blockwise, the window a band of tiles), then
  ``W_o (attn * sigmoid(g))``.
* ``F`` of the first ``num_dense_layers`` layers: SwiGLU at
  ``intermediate_size``. Of the others: ``shared(x) + routed(x)``;
  ``shared`` a SwiGLU at ``moe_intermediate_size x num_shared_experts``
  that every token takes; ``routed`` the expert layer of ``token_model``:
  ``s = sigmoid(W_r x)`` in float32, the experts the top k of ``s +
  expert_bias`` (one group), weights ``s`` at those k over their sum +
  1e-20 (``route_norm``), times ``route_scale``. ``expert_bias`` is a
  buffer (``batch_stats``): nothing here trains or updates it
  (``load_balance_coeff`` is the rate of an update rule this repo has for
  no model), and there is no auxiliary loss.

**A chip's share** (``TrinityConfig.held``): a run of the published
layers under their published numbers (a pipeline stage: a layer's kind
and its being dense follow the published index, so ``--layers 1:5``
starts with the second dense layer and holds the full-attention layer
3), ``first:count`` of each layer's routed experts, ``first:count`` rows
of the vocabulary, embedding and head alike. No width changes; the router
routes over ALL experts and the layer computes the held ones' part
(``token_model``).

Parameters are float32; ``dtype`` is the compute dtype (bfloat16 under
``--opt-level O2``); the norms' statistics, the router, the softmax and
the loss are float32 either way. Every block is rematerialised on the way
back and keeps what ``residual_classes`` names within the model's budget
(``token_model.TokenModel``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from dptpu.models import token_model
from dptpu.models.registry import register_model
from dptpu.models.token_model import RMSNorm, SparseExperts, SwiGLU, rotary
from dptpu.ops import attention as attention_op
from dptpu.ops.attention import causal_attention
from dptpu.ops.loss import token_cross_entropy_sums

linear = token_model.dense
# the family's normalisation of a token's expert weights adds this
ROUTE_NORM_EPS = 1e-20

_MINI_LAYERS = tuple(
    "full_attention" if (i + 1) % 4 == 0 else "sliding_attention"
    for i in range(32))


@dataclasses.dataclass(frozen=True)
class TrinityConfig:
    """``config.json`` of Trinity-Mini under its own keys, then what the
    trainer adds (the sequence length) and the chip's share."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    layer_types: Tuple[str, ...] = _MINI_LAYERS
    sliding_window: int = 2048
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 131072
    # the trainer's: tokens in a row
    sequence_length: int = 8192
    # the chip's share: ``vocab_size`` above is already cut to it by
    # ``held``; layers and experts keep their published counts (the
    # layers' numbers and kinds, the router's width) beside the
    # ``(first, count)`` held here (None: all of them)
    layers_held: Optional[Tuple[int, int]] = None
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        unsupported = [
            what for what, fine in (
                ("n_group / topk_group other than 1",
                 self.n_group == 1 and self.topk_group == 1),
                ("a score_func other than sigmoid",
                 self.score_func == "sigmoid"),
                ("tied embedding and head", not self.tie_word_embeddings),
                ("rope_scaling", self.rope_scaling is None),
                ("layer types other than sliding_attention and "
                 "full_attention",
                 set(self.layer_types) <= {"sliding_attention",
                                           "full_attention"}),
                ("an odd head size", self.head_dim % 2 == 0),
            ) if not fine]
        if unsupported:
            raise ValueError("afmoe here does not implement "
                             + ", ".join(unsupported))
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"{len(self.layer_types)} layer types for "
                f"{self.num_hidden_layers} layers")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("key/value heads do not divide the query heads")
        token_model.held_range(self.layers_here, self.num_hidden_layers,
                               "layers")
        self.routing  # refuses experts held that are not among them

    @property
    def layers_here(self) -> Tuple[int, int]:
        """``(first, count)`` of the published layers this chip holds."""
        return self.layers_held or (0, self.num_hidden_layers)

    @property
    def numbers_here(self) -> range:
        """The published numbers of the layers held."""
        first, count = self.layers_here
        return range(first, first + count)

    def is_dense(self, layer: int) -> bool:
        """Whether published layer ``layer`` has a dense feed-forward."""
        return layer < self.num_dense_layers

    def window_of(self, layer: int) -> Optional[int]:
        """The window of published layer ``layer``'s attention: None for
        a ``full_attention`` layer, which also takes no positions."""
        return self.sliding_window \
            if self.layer_types[layer] == "sliding_attention" else None

    @property
    def experts_here(self) -> Tuple[int, int]:
        """``(first, count)`` of the routed experts this chip holds."""
        return self.experts_held or (0, self.num_experts)

    @property
    def routing(self) -> token_model.Routing:
        """The expert layer's view of this configuration."""
        return token_model.Routing(
            experts=self.num_experts, held=self.experts_here,
            top_k=self.num_experts_per_tok, norm_topk=self.route_norm,
            norm_eps=ROUTE_NORM_EPS, scaling=self.route_scale,
            use_bias=True, width=self.moe_intermediate_size)

    def held(self, layers: Optional[Tuple[int, int]] = None,
             experts: Optional[Tuple[int, int]] = None,
             vocab: Optional[Tuple[int, int]] = None,
             sequence_length: Optional[int] = None) -> "TrinityConfig":
        """This configuration cut to a chip's share: ``(first, count)``
        of the layers (a pipeline stage, under their published numbers),
        of each layer's routed experts, of the vocabulary's rows."""
        changes = {}
        if layers is not None:
            changes["layers_held"] = tuple(layers)
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


# What a step takes on the device beside the train state and the kept
# residuals: the temporaries of this model's fully rematerialised step at
# one row of 8,192 tokens and the cell's share of five layers (3.57 GB:
# ``memory_analysis`` of the cell's step compiled for a described v5e at
# budget 0, PERF.md section 6, PR 44) and 15% of a 16.9 GB chip left to
# the allocator. Fixed: kept residuals are bounded by the budget, so a
# longer row or a larger share keeps less and the step fits where it
# fitted without them.
STEP_HEADROOM_BYTES = 6_100_000_000


def residual_classes(config: TrinityConfig, shape, dtype):
    """The residuals a rematerialised block can keep, by class
    (``token_model.keep_within``'s), over the layers held. The order is
    JoyAI's, whose block this one resembles most (the attention's output
    and log-sum-exp, which only the forward kernel can make again, then
    the products a matrix makes again, then the expert layers' rows and
    products): of this model's own only the expert layers' class is
    measured (8-10 ms a GB: the layer under a block's rematerialisation
    at this cell's shape, ``scripts/bench_experts.py``, PERF.md section
    6, PR 44); its cell's budget holds all four classes, 1,516 MB."""
    rows, length = shape
    tokens, item = rows * length, jnp.dtype(dtype).itemsize
    layers = len(config.numbers_here)
    dense = sum(config.is_dense(i) for i in config.numbers_here)
    return (
        ("attention out+lse", attention_op.RESIDUAL_NAMES,
         layers * attention_op.residual_bytes(
             rows, length, config.num_attention_heads, config.head_dim,
             dtype)),
        ("dense feed-forward", ("ffn_gate", "ffn_up"),
         dense * 2 * tokens * config.intermediate_size * item),
        ("attention output projections", ("attention_out_proj",),
         layers * tokens * config.hidden_size * item),
        token_model.expert_residuals(
            config.routing, tokens, config.hidden_size, layers - dense,
            dtype),
    )


class GatedAttention(nn.Module):
    """Grouped-query attention with per-head q/k RMSNorm and a sigmoid
    gate on the heads' output; ``window`` None is a full-attention layer,
    which takes no positions."""

    config: TrinityConfig
    window: Optional[int]
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        heads, kv_heads, d = (cfg.num_attention_heads,
                              cfg.num_key_value_heads, cfg.head_dim)
        batch, length, _ = x.shape
        with jax.named_scope("attention"):
            q, k, v = (
                linear(n * d, f"{which}_proj", self.dtype)(x).reshape(
                    batch, length, n, d)
                for which, n in (("q", heads), ("k", kv_heads),
                                 ("v", kv_heads)))
            gate = linear(heads * d, "gate_proj", self.dtype)(x)
            q = RMSNorm(cfg.rms_norm_eps, self.dtype, name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, self.dtype, name="k_norm")(k)
            if self.window is not None:
                q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
            out = causal_attention(q, k, v, scale=d ** -0.5,
                                   window=self.window)
            out = out.reshape(batch, length, heads * d) * nn.sigmoid(gate)
            return checkpoint_name(
                linear(cfg.hidden_size, "o_proj", self.dtype)(out),
                "attention_out_proj")


class Block(nn.Module):
    """One layer: gated attention and a feed-forward (dense, or a shared
    expert beside the routed ones), each normed going in and coming out
    and added to the residual path. Returns the tokens each held expert
    got (none for a dense layer)."""

    config: TrinityConfig
    window: Optional[int]
    dense: bool
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, self.dtype,  # noqa: E731
                                    name=name)
        x = x + norm("post_attention_layernorm")(
            GatedAttention(cfg, self.window, self.dtype, name="self_attn")(
                norm("input_layernorm")(x)))
        normed = norm("pre_mlp_layernorm")(x)
        if self.dense:
            out = SwiGLU(cfg.intermediate_size, self.dtype,
                         name="mlp")(normed)
            load = token_model.no_experts()
        else:
            routed, *load = SparseExperts(cfg, self.dtype,
                                          name="mlp")(normed)
            out = routed + SwiGLU(
                cfg.moe_intermediate_size * cfg.num_shared_experts,
                self.dtype, trace_scope="shared_expert", keep=None,
                name="shared_experts")(normed)
        return (x + norm("post_mlp_layernorm")(out), *load)


# the family's names for what this repo's modules call w1 / w3 / w2
_CHECKPOINT_SWIGLU = {"w1": "gate_proj", "w3": "up_proj", "w2": "down_proj"}


class Trinity(token_model.TokenModel):
    """The model: ``token_model.TokenModel`` says what ``__call__`` takes
    and gives. Its attention's calls are of one shape and two kinds: the
    sums say how many have a window and how many key tiles all of them
    walk (``token_model.with_counters``)."""

    config: TrinityConfig

    step_headroom_bytes = STEP_HEADROOM_BYTES

    def residual_classes(self, shape):
        return residual_classes(self.config, shape, self.dtype)

    @staticmethod
    def torch_key_map(variables):
        """The ``afmoe`` checkpoint's names (``model.embed_tokens``,
        ``model.norm``, ``lm_head``, ``model.layers.N.{input_layernorm,
        post_attention_layernorm, pre_mlp_layernorm, post_mlp_layernorm}``,
        ``.self_attn.{q_proj, k_proj, v_proj, gate_proj, o_proj, q_norm,
        k_norm}``, ``.mlp.{gate_proj, up_proj, down_proj}`` (dense and
        ``.mlp.shared_experts`` alike), ``.mlp.router.gate``,
        ``.mlp.expert_bias``, ``.mlp.experts.E.{gate_proj, up_proj,
        down_proj}``; written from memory of the family's released code,
        there is no network here). This file shares its SwiGLU and its
        expert layer with the other token models, so three names differ
        from the checkpoint's: the products are ``w1 / w3 / w2``, the
        shared expert sits beside ``mlp`` and not in it, and the router's
        matrix is ``mlp/gate``. Every matrix is a torch Linear (OI <->
        IO) but ``lm_head`` and the embedding, which are held ``[vocab,
        hidden]`` as torch holds them; ``expert_bias`` is a buffer with no
        ``.weight``."""
        out = {}
        for collection in ("params", "batch_stats"):
            flat = jax.tree_util.tree_flatten_with_path(
                variables.get(collection, {}))[0]
            for path, leaf in flat:
                names = tuple(p.key for p in path)
                if names == ("lm_head",):
                    key, kind = "lm_head.weight", "direct"
                else:
                    mods = [n.replace("layers_", "layers.").replace(
                        "experts_", "experts.") for n in names]
                    if "shared_experts" in mods:
                        mods.insert(mods.index("shared_experts"), "mlp")
                    if mods[-1] == "kernel":
                        mods.pop()
                    kind = "dense" if leaf.ndim == 2 else "direct"
                    last = mods[-1]
                    if last in _CHECKPOINT_SWIGLU:
                        mods[-1] = _CHECKPOINT_SWIGLU[last]
                    elif mods[-2:] == ["mlp", "gate"]:
                        mods[-1:] = ["router", "gate"]
                    elif last in ("scale", "embedding"):
                        mods.pop()
                        kind = "direct"
                    key = "model." + ".".join(mods)
                    if last != "expert_bias":
                        key += ".weight"
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
            if cfg.mup_enabled:
                x = x * jnp.asarray(cfg.hidden_size ** 0.5, self.dtype)
        kept = self.kept_on(tokens.shape)
        block = token_model.rematerialised(Block, kept)
        loads = []
        for i in cfg.numbers_here:
            x, *load = block(cfg, cfg.window_of(i), cfg.is_dense(i),
                             self.dtype, name=f"layers_{i}")(x)
            if not cfg.is_dense(i):
                loads.append(load)
        x = RMSNorm(cfg.rms_norm_eps, self.dtype, name="norm")(x)
        head = self.param("lm_head", token_model.dense_init,
                          (cfg.vocab_size, cfg.hidden_size))
        with jax.named_scope("head"):
            if labels is None:
                return jnp.einsum(
                    "bsh,vh->bsv", x, head.astype(self.dtype),
                    preferred_element_type=jnp.float32)
            sums = token_cross_entropy_sums(
                x.reshape(-1, cfg.hidden_size), head, labels.reshape(-1),
                mask.reshape(-1))
        return token_model.with_counters(
            sums, loads,
            tokens.size * cfg.num_experts_per_tok * len(loads), kept,
            [cfg.window_of(i) for i in cfg.numbers_here], tokens.shape[1],
            attention_op.kernel_calls(
                tokens.shape[1], cfg.num_attention_heads,
                cfg.num_key_value_heads, cfg.head_dim, cfg.head_dim,
                self.dtype))


factory = functools.partial(token_model.factory, Trinity)

# Trinity-Mini as its config.json gives it
register_model(factory("trinity_mini", TrinityConfig()))
