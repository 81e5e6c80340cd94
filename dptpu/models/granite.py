"""Granite 4.0-H (``model_type`` ``granitemoehybrid``): Mamba-2 state-space
layers with one attention layer in ten, and no experts.

Registry-discoverable as ``-a granite_4_0_h_micro``: IBM's
granite-4.0-h-micro as its ``config.json`` gives it
(https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json),
built from a configuration object (``GraniteConfig``, whose defaults are
that file's). The equations, as the family's released code has them:

* embedding times ``embedding_multiplier``; every block ``x = x +
  residual_multiplier * mixer(RMSNorm_in(x))``, then ``x = x +
  residual_multiplier * mlp(RMSNorm_post(x))``; after the last block
  ``RMSNorm_final``; logits ``h . E^T / logits_scaling`` over the tied
  embedding.
* ``mlp`` (``shared_mlp``; ``num_local_experts`` 0: no router, no
  experts): ``W_out (silu(g) * u)`` with ``[g | u] = W_in x``, ONE input
  matrix ``hidden -> 2 x shared_intermediate_size`` as the checkpoint holds
  it.
* ``mamba`` mixer (Mamba-2): ``[z | xBC | dt] = W_in u`` (``d_inner`` |
  ``d_inner + 2 x groups x state`` | heads, no bias); ``xBC =
  silu(conv1d(xBC))``, causal, depthwise, ``mamba_d_conv`` taps, with
  bias; ``[x | B | C] = xBC`` (``mamba_n_heads`` heads of
  ``mamba_d_head``; ``B``, ``C`` of ``mamba_d_state``, one group: every
  head shares them); ``Δ = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a
  scalar a head; ``h_t = exp(Δ_t A) h_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t =
  h_t C_t + D x_t`` from a zero state at the row's start
  (``dptpu.ops.ssd``: chunks of ``mamba_chunk_size``); ``y = RMSNorm(y *
  silu(z))`` over all of ``d_inner`` with its weight; ``W_out y``.
* ``attention`` mixer: grouped-query attention, no bias and NO positions
  (``position_embedding_type`` ``nope``: nothing rotates), scores times
  ``attention_multiplier`` (1/64 at a head of 64, not 1/8), causal softmax
  in float32 (``dptpu.ops.attention``).

**A chip's share** (``GraniteConfig.held``): a run of the published layers
under their published numbers (a pipeline stage: which layers are
attention follows the published index, so ``--layers 5:5`` starts with the
attention layer) and ``first:count`` rows of the tied vocabulary. No width
changes. There are no experts to hold: ``--experts`` is refused. Ids,
logits and loss are over the vocabulary rows held.

Parameters are float32; ``dtype`` is the compute dtype (bfloat16 under
``--opt-level O2``). The norms' statistics, ``Δ``, ``A``, the scan's
decays and its state, the softmax and the loss are float32 either way.
Every block is rematerialised on the way back and keeps what
``residual_classes`` names within the model's budget
(``token_model.TokenModel``).
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
from dptpu.models.token_model import RMSNorm
from dptpu.ops import attention as attention_op
from dptpu.ops import ssd as ssd_op
from dptpu.ops.attention import causal_attention
from dptpu.ops.loss import token_cross_entropy_sums

linear = token_model.dense

_MICRO_LAYERS = tuple(
    "attention" if i % 10 == 5 else "mamba" for i in range(40))


@dataclasses.dataclass(frozen=True)
class GraniteConfig:
    """``config.json`` of granite-4.0-h-micro under its own keys, then
    what the trainer adds (the sequence length) and the chip's share."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    shared_intermediate_size: int = 8192
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = _MICRO_LAYERS
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_bias: bool = False
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    num_local_experts: int = 0
    num_experts_per_tok: int = 0
    position_embedding_type: str = "nope"
    normalization_function: str = "rmsnorm"
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 131072
    # the trainer's: tokens in a row
    sequence_length: int = 8192
    # the chip's share: ``vocab_size`` above is already cut to it by
    # ``held``; the layers keep their published count and types beside
    # the ``(first, count)`` held here (None: all of them)
    layers_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        unsupported = [
            what for what, fine in (
                ("experts (num_local_experts other than 0)",
                 self.num_local_experts == 0),
                ("more than one group of B and C", self.mamba_n_groups == 1),
                ("positions other than nope",
                 self.position_embedding_type == "nope"),
                ("a norm other than rmsnorm",
                 self.normalization_function == "rmsnorm"),
                ("attention_bias", not self.attention_bias),
                ("mamba_proj_bias", not self.mamba_proj_bias),
                ("a convolution without bias", self.mamba_conv_bias),
                ("an untied head", self.tie_word_embeddings),
                ("layer types other than mamba and attention",
                 set(self.layer_types) <= {"mamba", "attention"}),
            ) if not fine]
        if unsupported:
            raise ValueError("granitemoehybrid here does not implement "
                             + ", ".join(unsupported))
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"{len(self.layer_types)} layer types for "
                f"{self.num_hidden_layers} layers")
        if self.mamba_n_heads * self.mamba_d_head != self.d_inner:
            raise ValueError("mamba heads x head size is not mamba_expand "
                             "x hidden size")
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads do not divide the hidden size, or "
                             "key/value heads the query heads")
        token_model.held_range(self.layers_here, self.num_hidden_layers,
                               "layers")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def conv_dim(self) -> int:
        """What the convolution runs over: ``[x | B | C]``."""
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def layers_here(self) -> Tuple[int, int]:
        """``(first, count)`` of the published layers this chip holds."""
        return self.layers_held or (0, self.num_hidden_layers)

    @property
    def types_here(self) -> Tuple[Tuple[int, str], ...]:
        """``(published number, type)`` of each layer held."""
        first, count = self.layers_here
        return tuple((i, self.layer_types[i])
                     for i in range(first, first + count))

    def count_here(self, layer_type: str) -> int:
        return sum(t == layer_type for _, t in self.types_here)

    def held(self, layers: Optional[Tuple[int, int]] = None,
             experts: Optional[Tuple[int, int]] = None,
             vocab: Optional[Tuple[int, int]] = None,
             sequence_length: Optional[int] = None) -> "GraniteConfig":
        """This configuration cut to a chip's share: ``(first, count)``
        of the layers (a pipeline stage, under their published numbers)
        and of the vocabulary's rows."""
        if experts is not None:
            raise ValueError("--experts: this model has no experts "
                             "(num_local_experts 0)")
        changes = {}
        if layers is not None:
            changes["layers_held"] = tuple(layers)
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
# residuals: the temporaries of this model's rematerialised step at one
# row of 8,192 tokens and a share of ten layers (2.74 GB: PERF.md section
# 4 has the bytes the chip reported), 15% of a 16.9 GB chip left to the
# allocator, and room to spare. At the cell's share (9.27 GB of state) it
# leaves a budget of 1.74 GB, of which the one class named below takes
# 35 MB.
STEP_HEADROOM_BYTES = 5_900_000_000


def residual_classes(config: GraniteConfig, shape, dtype):
    """The residuals a rematerialised block can keep, by class
    (``token_model.keep_within``'s). Only the attention's are named: its
    forward is the one part no product can make again cheaply; the order
    among the scan's candidates is not measured yet (the cell's share
    leaves no budget to measure it with)."""
    rows, length = shape
    return (
        ("attention out+lse", attention_op.RESIDUAL_NAMES,
         config.count_here("attention") * attention_op.residual_bytes(
             rows, length, config.num_attention_heads, config.head_dim,
             dtype)),
    )


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Mamba-2's: ``Δ`` log-uniform in [1e-3, 1e-1] at a zero input."""
    lo, hi = np.log(1e-3), np.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, lo, hi))
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log_init(key, shape, dtype=jnp.float32):
    """Mamba-2's: ``A`` uniform in [1, 16]."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class Mamba2(nn.Module):
    """The Mamba-2 mixer."""

    config: GraniteConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        heads, head, state = (cfg.mamba_n_heads, cfg.mamba_d_head,
                              cfg.mamba_d_state)
        taps, f32 = cfg.mamba_d_conv, jnp.float32
        batch, length, _ = u.shape
        with jax.named_scope("mamba"):
            z, xbc, dt = jnp.split(
                linear(cfg.d_inner + cfg.conv_dim + heads, "in_proj",
                       self.dtype)(u),
                [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1)
            # [taps, channels]: tap k weighs the input taps-1-k steps back
            kernel = self.param(
                "conv1d", uniform_bound_init(1.0 / np.sqrt(taps)),
                (taps, cfg.conv_dim)).astype(self.dtype)
            bias = self.param("conv1d_bias", nn.initializers.zeros,
                              (cfg.conv_dim,)).astype(self.dtype)
            with jax.named_scope("conv"):
                padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
                xbc = nn.silu(sum(padded[:, k:k + length] * kernel[k]
                                  for k in range(taps)) + bias)
            x, b, c = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + state],
                                axis=-1)
            dt_bias = self.param("dt_bias", _dt_bias_init, (heads,))
            a_log = self.param("A_log", _a_log_init, (heads,))
            d = self.param("D", nn.initializers.ones, (heads,))
            with jax.named_scope("ssd"):
                y = ssd_op.ssd(
                    x.reshape(batch, length, heads, head),
                    jax.nn.softplus(dt.astype(f32) + dt_bias),
                    -jnp.exp(a_log.astype(f32)), b, c, d,
                    chunk=cfg.mamba_chunk_size)
            y = y.reshape(batch, length, cfg.d_inner)
            gated = y.astype(f32) * nn.silu(z.astype(f32))
            y = RMSNorm(cfg.rms_norm_eps, self.dtype, name="norm")(gated)
            return linear(cfg.hidden_size, "out_proj", self.dtype)(y)


class Attention(nn.Module):
    """Grouped-query attention without positions."""

    config: GraniteConfig
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
            out = causal_attention(q, k, v, scale=cfg.attention_multiplier)
            return checkpoint_name(
                linear(cfg.hidden_size, "o_proj", self.dtype)(
                    out.reshape(batch, length, heads * d)),
                "attention_out_proj")


class SharedMlp(nn.Module):
    """``W_out (silu(g) * u)``, ``[g | u] = W_in x``: the checkpoint's one
    input matrix."""

    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        with jax.named_scope("dense_ffn"):
            gate, up = jnp.split(
                linear(2 * self.width, "input_linear", self.dtype)(x), 2,
                axis=-1)
            return linear(x.shape[-1], "output_linear", self.dtype)(
                nn.silu(gate) * up)


class Block(nn.Module):
    """One layer: a mixer and the feed-forward, each behind its norm and
    scaled on the residual path."""

    config: GraniteConfig
    layer_type: str
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        scale = jnp.asarray(cfg.residual_multiplier, self.dtype)
        normed = RMSNorm(cfg.rms_norm_eps, self.dtype,
                         name="input_layernorm")(x)
        if self.layer_type == "attention":
            mixed = Attention(cfg, self.dtype, name="self_attn")(normed)
        else:
            mixed = Mamba2(cfg, self.dtype, name="mamba")(normed)
        x = x + scale * mixed
        normed = RMSNorm(cfg.rms_norm_eps, self.dtype,
                         name="post_attention_layernorm")(x)
        return x + scale * SharedMlp(cfg.shared_intermediate_size,
                                     self.dtype, name="shared_mlp")(normed)


class Granite(token_model.TokenModel):
    """The model: ``token_model.TokenModel`` says what ``__call__`` takes
    and gives. The head is the embedding again (tied). The first dense
    token model: the sums carry no expert counts; they carry the scan's
    calls instead (``ssd_calls``, ``ssd_kernel_calls``, ``ssd_chunks``:
    constants of the lowered program)."""

    config: GraniteConfig

    step_headroom_bytes = STEP_HEADROOM_BYTES

    def residual_classes(self, shape):
        return residual_classes(self.config, shape, self.dtype)

    @staticmethod
    def torch_key_map(variables):
        """The ``granitemoehybrid`` checkpoint's names
        (``model.embed_tokens``, ``model.norm``,
        ``model.layers.N.{input_layernorm, post_attention_layernorm}``,
        ``.mamba.{in_proj, conv1d.{weight, bias}, dt_bias, A_log, D, norm,
        out_proj}``, ``.self_attn.{q_proj, k_proj, v_proj, o_proj}``,
        ``.shared_mlp.{input_linear, output_linear}``; written from memory
        of the family's checkpoints, there is no network here). This file
        names its modules after them; every matrix is a torch Linear (OI
        <-> IO), the convolution's taps are torch's depthwise ``[channels,
        1, taps]`` <-> ``[taps, channels]``, and ``dt_bias``, ``A_log``
        and ``D`` are raw Parameters with no ``.weight``."""
        out = {}
        flat = jax.tree_util.tree_flatten_with_path(
            variables.get("params", {}))[0]
        for path, leaf in flat:
            names = tuple(p.key for p in path)
            mods = [n.replace("layers_", "layers.") for n in names]
            last, kind = mods[-1], "direct"
            if last == "kernel":
                mods[-1], kind = "weight", "dense"
            elif last in ("scale", "embedding"):
                mods[-1] = "weight"
            elif last == "conv1d":
                mods[-1:], kind = ["conv1d", "weight"], "conv1d_dw"
            elif last == "conv1d_bias":
                mods[-1:] = ["conv1d", "bias"]
            elif last not in ("dt_bias", "A_log", "D"):
                raise ValueError(
                    f"no granitemoehybrid key for {'/'.join(names)}")
            key = "model." + ".".join(mods)
            assert key not in out, f"duplicate torch key {key}"
            out[key] = ("params", names, kind)
        return out

    @nn.compact
    def __call__(self, tokens, train: bool = False, labels=None, mask=None):
        del train  # no dropout, no statistics: the two modes are one
        cfg = self.config
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=self.dtype,
                         embedding_init=token_model.dense_init,
                         name="embed_tokens")
        with jax.named_scope("embed"):
            x = embed(tokens) * jnp.asarray(cfg.embedding_multiplier,
                                            self.dtype)
        kept = self.kept_on(tokens.shape)
        block = token_model.rematerialised(Block, kept)
        for i, layer_type in cfg.types_here:
            x = block(cfg, layer_type, self.dtype, name=f"layers_{i}")(x)
        x = RMSNorm(cfg.rms_norm_eps, self.dtype, name="norm")(x)
        with jax.named_scope("head"):
            # h . E^T / logits_scaling, the scale on the narrow side
            x = x / jnp.asarray(cfg.logits_scaling, self.dtype)
            if labels is None:
                return jnp.einsum(
                    "bsh,vh->bsv", x, embed.embedding.astype(self.dtype),
                    preferred_element_type=jnp.float32)
            sums = token_cross_entropy_sums(
                x.reshape(-1, cfg.hidden_size), embed.embedding,
                labels.reshape(-1), mask.reshape(-1))
        sums = token_model.with_counters(
            sums, [], 0, kept, (None,) * cfg.count_here("attention"),
            tokens.shape[1], attention_op.kernel_calls(
                tokens.shape[1], cfg.num_attention_heads,
                cfg.num_key_value_heads, cfg.head_dim, cfg.head_dim,
                self.dtype))
        return token_model.with_scan_counters(
            sums, cfg.count_here("mamba"), ssd_op.kernel_calls(),
            ssd_op.chunks_of(tokens.shape[1], cfg.mamba_chunk_size))


factory = functools.partial(token_model.factory, Granite)

# granite-4.0-h-micro as its config.json gives it
register_model(factory("granite_4_0_h_micro", GraniteConfig()))
