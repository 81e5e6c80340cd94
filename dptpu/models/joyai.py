"""JoyAI-LLM-Flash (``model_type`` ``joyai_llm_flash``), a language model
of the DeepSeek-V3 family.

Registry-discoverable as ``-a joyai_llm_flash``: the model as its
``config.json`` gives it
(https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json),
built from a configuration object (``JoyaiConfig``, whose defaults are
that file's). The equations:

* block: ``x = x + MLA(RMSNorm_in(x))``; ``x = x + FFN(RMSNorm_post(x))``;
  after the last layer ``RMSNorm_final``, then ``lm_head``, a
  ``[vocab, hidden]`` matrix of its own (``tie_word_embeddings`` false).
  No bias anywhere.
* multi-head latent attention: ``c_q = RMSNorm(W_qa x)``
  (``q_lora_rank``), ``q = W_qb c_q``, per head ``[q_nope | q_rope]``;
  ``[c_kv | k_rope] = W_kva x``, ``c_kv = RMSNorm(c_kv)``
  (``kv_lora_rank``), ``W_kvb c_kv`` per head ``[k_nope | v]``. Rotary
  positions (``rope_theta``) on each head's ``q_rope`` and on the ONE
  ``k_rope``, which every head shares; the pairs are interleaved
  (``rope_interleave``: ``(x0, x1), (x2, x3), ...``), which is computed
  as the family's code does it, by de-interleaving q and k alike and
  rotating halves: the scores are the same. ``k = [k_nope | k_rope]``,
  scores ``q.k / sqrt(qk head)``, causal softmax in float32, ``out = P v``
  at the values' own head size (``dptpu.ops.attention``: blockwise, two
  head sizes), ``W_o``. Training computes this expanded multi-head form;
  nothing is absorbed into the latents.
* feed-forward of the first ``first_k_dense_replace`` layers: SwiGLU at
  ``intermediate_size``. Of the others: ``shared(x) + routed(x)``;
  ``shared`` a SwiGLU at ``moe_intermediate_size x n_shared_experts``
  that every token takes; ``routed`` the expert layer of
  ``token_model``: ``s = sigmoid(W_g x)`` in float32, the experts the
  top k of ``s + e_score_correction_bias`` (one group: ``n_group`` 1),
  weights ``s`` at those k over their sum + 1e-20, times
  ``routed_scaling_factor``. The bias is a buffer (``batch_stats``):
  nothing here trains or updates it.
* multi-token prediction (``num_nextn_predict_layers`` 1; the
  checkpoint's layer ``num_hidden_layers``): for position ``i``, with
  ``h_i`` the last main layer's output before ``RMSNorm_final``,
  ``x'_i = W_eh [RMSNorm_e(Emb(t_{i+1})) | RMSNorm_h(h_i)]``, one block
  as above (with experts), ``RMSNorm_shared_head``, then the SAME
  ``lm_head``; the embedding is the main model's too. Its loss is the
  cross-entropy against ``t_{i+2}``. In a row of the feed
  ``t_{i+1} = labels[i]``: the targets and their weights are the labels
  and the mask shifted by one, the last position weighs nothing.
  ``loss = main + mtp_loss_weight x mtp``.

**A chip's share** (``JoyaiConfig.held``): a run of the published layers
under their published numbers (a pipeline stage), ``first:count`` of each
layer's routed experts, ``first:count`` rows of the vocabulary, embedding
and head alike. No width changes; the router routes over ALL experts and
the layer computes the held ones' part (``token_model``). Every share
holds embedding and head, and with them the multi-token-prediction
module, which then reads the output of the last layer HELD (the first and
the last pipeline stage on one rank, as the family's report has it).

The sums carry ``mtp_loss_sum`` (before its weight) beside the rest
(``token_model.TokenModel``), and ``moe_counts`` has a row for the
module's expert layer too.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from dptpu.models import token_model
from dptpu.models.registry import register_model
from dptpu.models.token_model import RMSNorm, SparseExperts, SwiGLU
from dptpu.ops import attention as attention_op
from dptpu.ops.attention import causal_attention
from dptpu.ops.loss import token_cross_entropy_sums

linear = token_model.dense
# the family's normalisation of a token's expert weights adds this
ROUTE_NORM_EPS = 1e-20


@dataclasses.dataclass(frozen=True)
class JoyaiConfig:
    """``config.json`` of JoyAI-LLM-Flash under its own keys, then what
    the trainer adds (the sequence length, the second loss's weight) and
    the chip's share."""

    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    num_nextn_predict_layers: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 3.2e7
    rope_interleave: bool = True
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    max_position_embeddings: int = 131072
    # the trainer's: tokens in a row; the weight of the second loss (not
    # in config.json: the family's published late-phase value)
    sequence_length: int = 8192
    mtp_loss_weight: float = 0.1
    # the chip's share: ``vocab_size`` above is already cut to it by
    # ``held``; layers and experts keep their published counts (the
    # layers' numbers, the router's width) beside the ``(first, count)``
    # held here (None: all of them)
    layers_held: Optional[Tuple[int, int]] = None
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        unsupported = [
            what for what, fine in (
                ("n_group / topk_group other than 1",
                 self.n_group == 1 and self.topk_group == 1),
                ("a scoring_func other than sigmoid",
                 self.scoring_func == "sigmoid"),
                ("a moe_layer_freq other than 1", self.moe_layer_freq == 1),
                ("tied embedding and head", not self.tie_word_embeddings),
                ("attention_bias", not self.attention_bias),
                ("grouped key/value heads (latent attention has none)",
                 self.num_key_value_heads == self.num_attention_heads),
                ("more than one multi-token-prediction layer",
                 self.num_nextn_predict_layers in (0, 1)),
                ("an odd rotary head size", self.qk_rope_head_dim % 2 == 0),
            ) if not fine]
        if unsupported:
            raise ValueError("joyai_llm_flash does not implement "
                             + ", ".join(unsupported))
        token_model.held_range(self.layers_here, self.num_hidden_layers,
                               "layers")
        self.routing  # refuses experts held that are not among them

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def layers_here(self) -> Tuple[int, int]:
        """``(first, count)`` of the published layers this chip holds."""
        return self.layers_held or (0, self.num_hidden_layers)

    @property
    def attention_layers_here(self) -> int:
        """The main layers held and the multi-token-prediction block."""
        return self.layers_here[1] + self.num_nextn_predict_layers

    def is_dense(self, layer: int) -> bool:
        """Whether published layer ``layer`` has a dense feed-forward."""
        return layer < self.first_k_dense_replace

    @property
    def experts_here(self) -> Tuple[int, int]:
        """``(first, count)`` of the routed experts this chip holds."""
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def routing(self) -> token_model.Routing:
        """The expert layer's view of this configuration."""
        return token_model.Routing(
            experts=self.n_routed_experts, held=self.experts_here,
            top_k=self.num_experts_per_tok, norm_topk=self.norm_topk_prob,
            norm_eps=ROUTE_NORM_EPS, scaling=self.routed_scaling_factor,
            use_bias=self.topk_method == "noaux_tc",
            width=self.moe_intermediate_size)

    def held(self, layers: Optional[Tuple[int, int]] = None,
             experts: Optional[Tuple[int, int]] = None,
             vocab: Optional[Tuple[int, int]] = None,
             sequence_length: Optional[int] = None) -> "JoyaiConfig":
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
# residuals: the temporaries of THIS model's fully rematerialised step
# (2.96 GB at one row of 8,192 tokens, a share of five layers and the
# module; 3.339 before PR 37's attention kernels and PR 42's compact
# buffers: ``memory_analysis`` of the cell's step compiled for a
# described v5e at budget 0, PERF.md section 6, PR 44) and 15% of a
# 16.9 GB chip left to the allocator. Fixed: kept residuals are bounded
# by the budget, so a longer row or a larger share keeps less and the
# step fits where it fitted without them.
STEP_HEADROOM_BYTES = 5_500_000_000


def residual_classes(config: JoyaiConfig, shape, dtype):
    """The residuals a rematerialised block can keep, by class
    (``token_model.keep_within``'s), over the main layers held and the
    multi-token-prediction block. The order is the order of keeping:
    milliseconds of re-run forward saved per byte held on the chip,
    dearest first (490, 20, 16 and 10-11 ms a GB at one row of 8,192
    tokens: PERF.md section 6, PR 36, which also says what was measured
    and left out, the latents, the expanded queries, keys and values, the
    shared expert's products; the expert layers' class by PR 44's
    readings of the layer under a block's rematerialisation,
    ``scripts/bench_experts.py``)."""
    rows, length = shape
    tokens, item = rows * length, jnp.dtype(dtype).itemsize
    first, count = config.layers_here
    dense = sum(config.is_dense(i) for i in range(first, first + count))
    attention = config.attention_layers_here
    return (
        # out at the values' head size and lse: only the forward scan can
        # make them again
        ("attention out+lse", attention_op.RESIDUAL_NAMES,
         attention * attention_op.residual_bytes(
             rows, length, config.num_attention_heads, config.v_head_dim,
             dtype)),
        ("dense feed-forward", ("ffn_gate", "ffn_up"),
         dense * 2 * tokens * config.intermediate_size * item),
        ("attention output projections", ("attention_out_proj",),
         attention * tokens * config.hidden_size * item),
        # the module's block has an expert layer too
        token_model.expert_residuals(
            config.routing, tokens, config.hidden_size,
            attention - dense, dtype),
    )


def deinterleave(x):
    """``[x0, x1, x2, x3, ...]`` -> ``[x0, x2, ..., x1, x3, ...]`` along
    the last axis: the interleaved rotary pairs ``(x0, x1), (x2, x3)``
    become the half-split pairs ``(x_i, x_{i + d/2})``. Applied to
    queries and keys alike it leaves every score as it was."""
    *lead, d = x.shape
    return x.reshape(*lead, d // 2, 2).swapaxes(-1, -2).reshape(*lead, d)


def rotary(x, theta: float, interleaved: bool):
    """Rotary positions over the last axis of ``x`` ``[B, S, H, D]``,
    the pairs interleaved or half-split. The result is in the half-split
    layout either way (a fixed permutation of the interleaved one, the
    same for queries and keys)."""
    return token_model.rotary(deinterleave(x) if interleaved else x, theta)


class LatentAttention(nn.Module):
    """Multi-head latent attention in its expanded (training) form."""

    config: JoyaiConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        heads, nope, rope, v_dim = (
            cfg.num_attention_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim)
        batch, length, _ = x.shape
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, self.dtype,  # noqa: E731
                                    name=name)
        with jax.named_scope("attention"):
            c_q = norm("q_a_layernorm")(
                linear(cfg.q_lora_rank, "q_a_proj", self.dtype)(x))
            q = linear(heads * (nope + rope), "q_b_proj", self.dtype)(
                c_q).reshape(batch, length, heads, nope + rope)
            c_kv, k_rope = jnp.split(
                linear(cfg.kv_lora_rank + rope, "kv_a_proj_with_mqa",
                       self.dtype)(x), [cfg.kv_lora_rank], axis=-1)
            kv = linear(heads * (nope + v_dim), "kv_b_proj", self.dtype)(
                norm("kv_a_layernorm")(c_kv)).reshape(
                    batch, length, heads, nope + v_dim)
            q_nope, q_rope = jnp.split(q, [nope], axis=-1)
            k_nope, v = jnp.split(kv, [nope], axis=-1)
            q_rope = rotary(q_rope, cfg.rope_theta, cfg.rope_interleave)
            # one rotary key for all heads
            k_rope = rotary(k_rope[:, :, None, :], cfg.rope_theta,
                            cfg.rope_interleave)
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, q_rope.shape)], axis=-1)
            out = causal_attention(q, k, v, scale=cfg.qk_head_dim ** -0.5)
            return checkpoint_name(
                linear(cfg.hidden_size, "o_proj", self.dtype)(
                    out.reshape(batch, length, heads * v_dim)),
                "attention_out_proj")


class Block(nn.Module):
    """One layer: latent attention and a feed-forward (dense, or a shared
    expert beside the routed ones), each behind its norm and on the
    residual path. Returns the tokens each held expert got (none for a
    dense layer).

    ``nextn``: the multi-token-prediction module, which is such a layer
    with a merge before it and a norm after: called with the main model's
    last states and the embeddings of the tokens that follow, it returns
    the states its head norm hands to the shared head."""

    config: JoyaiConfig
    dense: bool
    dtype: jnp.dtype = jnp.float32
    nextn: bool = False

    @nn.compact
    def __call__(self, x, following=None):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, self.dtype,  # noqa: E731
                                    name=name)
        with contextlib.ExitStack() as scopes:
            if self.nextn:
                scopes.enter_context(jax.named_scope("mtp"))
                x = linear(cfg.hidden_size, "eh_proj", self.dtype)(
                    jnp.concatenate([norm("enorm")(following),
                                     norm("hnorm")(x)], axis=-1))
            x = x + LatentAttention(cfg, self.dtype, name="self_attn")(
                norm("input_layernorm")(x))
            normed = norm("post_attention_layernorm")(x)
            if self.dense:
                x = x + SwiGLU(cfg.intermediate_size, self.dtype,
                               name="mlp")(normed)
                load = token_model.no_experts()
            else:
                routed, *load = SparseExperts(cfg, self.dtype,
                                              name="mlp")(normed)
                shared = SwiGLU(
                    cfg.moe_intermediate_size * cfg.n_shared_experts,
                    self.dtype, trace_scope="shared_expert", keep=None,
                    name="shared_experts")(normed)
                x = x + (shared + routed)
            if self.nextn:
                x = norm("shared_head_norm")(x)
        return (x, *load)


def shifted(x):
    """``x`` ``[B, S]`` one position on: ``x[:, i + 1]`` at ``i``, zero
    (no token, no weight) at the last."""
    return jnp.pad(x[:, 1:], ((0, 0), (0, 1)))


# the family's names for what this repo's modules call w1 / w3 / w2
_CHECKPOINT_SWIGLU = {"w1": "gate_proj", "w3": "up_proj", "w2": "down_proj"}


class Joyai(token_model.TokenModel):
    """The model: ``token_model.TokenModel`` says what ``__call__`` takes
    and gives; with ``labels`` the sums also carry ``mtp_loss_sum``, the
    second loss before its weight, and ``loss_sum`` is ``main +
    mtp_loss_weight x mtp``. Without ``labels`` it gives the main head's
    logits (the module then reads the tokens that follow from ``tokens``
    itself, and nothing reads the module)."""

    config: JoyaiConfig

    step_headroom_bytes = STEP_HEADROOM_BYTES

    def residual_classes(self, shape):
        return residual_classes(self.config, shape, self.dtype)

    @staticmethod
    def torch_key_map(variables):
        """The ``joyai_llm_flash`` checkpoint's names, which are the
        DeepSeek-V3 family's (``model.embed_tokens``, ``model.norm``,
        ``lm_head``, ``model.layers.N.{input_layernorm,
        post_attention_layernorm}``, ``.self_attn.{q_a_proj, q_a_layernorm,
        q_b_proj, kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, o_proj}``,
        ``.mlp.{gate_proj, up_proj, down_proj}`` (dense and
        ``.mlp.shared_experts`` alike), ``.mlp.gate.{weight,
        e_score_correction_bias}``, ``.mlp.experts.E.{gate_proj, up_proj,
        down_proj}`` and, on the multi-token-prediction layer, ``.{enorm,
        hnorm, eh_proj, shared_head.norm}``; written from memory of the
        family's checkpoints, there is no network here).
        This file shares its SwiGLU and its expert layer with the other
        token model, so three names differ from the checkpoint's: the
        products are ``w1 / w3 / w2``, the shared expert sits beside ``mlp``
        and not in it, and the selection bias is ``expert_bias``. Every
        matrix is a torch Linear (OI <-> IO) but ``lm_head`` and the
        embedding, which are held ``[vocab, hidden]`` as torch holds them."""
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
                    elif last == "expert_bias":
                        mods[-1:] = ["gate", "e_score_correction_bias"]
                    elif last in ("scale", "embedding"):
                        mods.pop()
                        kind = "direct"
                    if mods[-1] == "shared_head_norm":
                        mods[-1:] = ["shared_head", "norm"]
                    key = "model." + ".".join(mods)
                    if not key.endswith("e_score_correction_bias"):
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
        kept = self.kept_on(tokens.shape)
        block = token_model.rematerialised(Block, kept)
        loads = []
        first, count = cfg.layers_here
        for i in range(first, first + count):
            x, *load = block(cfg, cfg.is_dense(i), self.dtype,
                             name=f"layers_{i}")(x)
            if not cfg.is_dense(i):
                loads.append(load)
        nextn = None
        if cfg.num_nextn_predict_layers:
            # t_{i+1} is the label of position i
            following = shifted(tokens) if labels is None else labels
            with jax.named_scope("embed"):
                after = embed(following)
            nextn, *load = block(cfg, False, self.dtype, True,
                                 name=f"layers_{cfg.num_hidden_layers}")(
                                     x, after)
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
            if nextn is not None:
                # the target of position i is t_{i+2} = labels[i + 1],
                # with that position's weight
                mtp = token_cross_entropy_sums(
                    nextn.reshape(-1, cfg.hidden_size), head,
                    shifted(labels).reshape(-1),
                    shifted(mask).reshape(-1))["loss_sum"]
                sums["mtp_loss_sum"] = mtp
                sums["loss_sum"] = sums["loss_sum"] \
                    + cfg.mtp_loss_weight * mtp
        return token_model.with_counters(
            sums, loads,
            tokens.size * cfg.num_experts_per_tok * len(loads), kept,
            (None,) * cfg.attention_layers_here, tokens.shape[1],
            attention_op.kernel_calls(
                tokens.shape[1], cfg.num_attention_heads,
                cfg.num_attention_heads, cfg.qk_head_dim, cfg.v_head_dim,
                self.dtype))


factory = functools.partial(token_model.factory, Joyai)

# JoyAI-LLM-Flash as its config.json gives it
register_model(factory("joyai_llm_flash", JoyaiConfig()))
