"""Vision Transformer (ViT B/L/H), torchvision-architecture-exact, NHWC.

Registry-discoverable (imagenet_ddp.py:19-21, ``-a vit_b_16``). Fresh
Flax build of torchvision's ``vision_transformer.py``:

* patchify via a patch-size/patch-stride conv WITH bias, flattened
  row-major over the spatial grid (the same order torch's
  ``reshape(B, hidden, S).permute`` produces, so converted pos
  embeddings line up);
* learned class token (zeros init) prepended, learned position
  embedding (N(0, 0.02)) added over the ``S + 1`` sequence;
* pre-LN encoder layers (LayerNorm eps 1e-6): LN -> multi-head
  self-attention (one fused qkv projection == torch's
  ``in_proj_weight``, out projection) -> residual; LN -> MLP
  (Linear -> GELU -> Linear, xavier-uniform weights, N(0, 1e-6)
  biases) -> residual;
* final LN, classify from the class token through a ZERO-initialized
  Linear head (torchvision zero-inits ``heads.head``).

Attention goes through ``dptpu.ops.sequence_parallel``: on one device
it is the plain scaled-dot-product (two einsums around an f32 softmax,
straight onto the MXU); with ``seq_axis_name`` set and the token axis
sharded over that mesh axis under ``shard_map``, it runs as Ulysses
all-to-all or ring attention (``seq_mode``). The embedding stage
(class token prepend + pos-embedding add) indexes absolute positions,
so shard the ENCODER: replicate up to the embedding output, then
partition the token axis (and ``encoder/pos_embedding``'s axis 1) with
the same spec — tests/test_sequence_parallel.py shows the pattern at
encoder-layer level. Param counts locked in tests/test_models.py
(vit_b_16 at 224 = 86,567,656).
"""

import math
from functools import partial
from typing import Any, Optional

import jax.numpy as jnp
from flax import linen as nn

from dptpu.models.layers import torch_trunc_normal_init, uniform_bound_init
from dptpu.models.registry import register_variants
from dptpu.ops.sequence_parallel import sequence_parallel_attention

# name -> (patch, layers, heads, hidden, mlp)
_VARIANTS = {
    "b_16": (16, 12, 12, 768, 3072),
    "b_32": (32, 12, 12, 768, 3072),
    "l_16": (16, 24, 16, 1024, 4096),
    "l_32": (32, 24, 16, 1024, 4096),
    "h_14": (14, 32, 16, 1280, 5120),
}


# torch's xavier_uniform_: U(±sqrt(6/(fan_in+fan_out))) — identical to
# flax's for the 2-D Dense kernels it is applied to
xavier_uniform = nn.initializers.xavier_uniform()


class SelfAttention(nn.Module):
    """torch ``nn.MultiheadAttention`` semantics: fused qkv projection
    (xavier-uniform, zero bias), scaled dot-product, out projection
    (torch Linear default init, zero bias).

    The fused projection's output axis is stored **head-major**:
    ``(head0: q,k,v)(head1: q,k,v)…`` — i.e. ``(h, heads, 3, hd)``
    flattened — NOT torch's ``[q|k|v]`` concatenation. Random init is
    layout-blind (iid columns) and the pretrained converter permutes
    torch's ``in_proj_weight/bias`` into this order
    (dptpu/models/pretrained.py, kind ``vit_qkv``). The payoff is
    tensor parallelism: a plain contiguous ``P(None, "model")`` split of
    the fused kernel is head-aligned for any mesh size dividing
    ``heads``, so GSPMD head-group attention TP (dptpu/parallel/gspmd.py
    ``vit_tp_specs``) needs no resharding — each device projects and
    attends its own head group, and the row-parallel out projection's
    psum is the block's single all-reduce.

    Migration: converted ``.npz`` weights and flax checkpoints both
    carry a ``qkv_layout`` marker now; unmarked (pre-round-4,
    [q|k|v]-major) ViT files are auto-permuted on load — params AND the
    momentum trace (``pretrained.load_pretrained_variables``,
    ``train.checkpoint.load_checkpoint``).

    ``seq_axis_name`` turns on sequence/context parallelism: under a
    ``shard_map`` whose in/out specs shard the token axis over that mesh
    axis, attention runs as Ulysses all-to-all or ring attention
    (``seq_mode``) — see dptpu/ops/sequence_parallel.py. Every other ViT
    sublayer is position-wise, so the encoder layer works on sequence
    shards unchanged."""

    heads: int
    dtype: Any
    param_dtype: Any
    seq_axis_name: Optional[str] = None
    seq_mode: str = "ulysses"

    @nn.compact
    def __call__(self, x, kv_mask=None):
        h = x.shape[-1]
        hd = h // self.heads
        dense = partial(
            nn.Dense, dtype=self.dtype, param_dtype=self.param_dtype
        )
        qkv = dense(
            3 * h, kernel_init=xavier_uniform,
            bias_init=nn.initializers.zeros, name="in_proj",
        )(x)
        # head-major layout (see class docstring): (…, heads, 3, hd)
        qkv = qkv.reshape(qkv.shape[:-1] + (self.heads, 3, hd))
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        y = sequence_parallel_attention(
            q, k, v, self.seq_axis_name, self.seq_mode, kv_mask=kv_mask
        )
        y = y.reshape(y.shape[:-2] + (h,))
        return dense(
            h,
            kernel_init=uniform_bound_init(1.0 / math.sqrt(h)),
            bias_init=nn.initializers.zeros,
            name="out_proj",
        )(y)


class EncoderLayer(nn.Module):
    heads: int
    mlp_dim: int
    dtype: Any
    param_dtype: Any
    seq_axis_name: Optional[str] = None
    seq_mode: str = "ulysses"

    @nn.compact
    def __call__(self, x, kv_mask=None):
        ln = partial(
            nn.LayerNorm, epsilon=1e-6, dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        dense = partial(
            nn.Dense, dtype=self.dtype, param_dtype=self.param_dtype,
            kernel_init=xavier_uniform,
            bias_init=nn.initializers.normal(1e-6),
        )
        y = ln(name="ln_1")(x)
        y = SelfAttention(
            heads=self.heads, dtype=self.dtype,
            param_dtype=self.param_dtype, name="self_attention",
            seq_axis_name=self.seq_axis_name, seq_mode=self.seq_mode,
        )(y, kv_mask=kv_mask)
        x = x + y
        y = ln(name="ln_2")(x)
        y = dense(self.mlp_dim, name="mlp_1")(y)
        y = nn.gelu(y, approximate=False)
        y = dense(x.shape[-1], name="mlp_2")(y)
        return x + y


class Encoder(nn.Module):
    """``seq_shard_tokens=False`` (default): tokens arrive however the
    caller laid them out — replicated on one device, or already
    token-sharded under a hand-written ``shard_map`` whose specs also
    shard ``pos_embedding``'s axis 1 (the library-level recipe,
    tests/test_sequence_parallel.py).

    ``seq_shard_tokens=True`` (the trainer's ``DPTPU_SP`` path —
    requires ``seq_axis_name``): tokens arrive REPLICATED over the
    sequence axis; the encoder adds the (replicated, exact) position
    embedding, right-pads the token axis to a multiple of the axis
    size, slices this device's chunk, and runs the layers
    sequence-parallel with a key-validity mask so padding never enters
    a softmax. Returns the LOCAL post-LN chunk — the caller recovers
    global tokens (VisionTransformer psums the device-0 cls row). No
    param is sharded, so state creation, checkpointing and eval reuse
    the plain replicated layout untouched."""

    layers: int
    heads: int
    mlp_dim: int
    dtype: Any
    param_dtype: Any
    seq_axis_name: Optional[str] = None
    seq_mode: str = "ulysses"
    seq_shard_tokens: bool = False

    @nn.compact
    def __call__(self, x):
        pos = self.param(
            "pos_embedding", nn.initializers.normal(0.02),
            (1, x.shape[1], x.shape[2]), jnp.float32,
        )
        x = x + pos.astype(x.dtype)
        kv_mask = None
        if self.seq_shard_tokens:
            from jax import lax

            if self.seq_axis_name is None:
                raise ValueError("seq_shard_tokens needs seq_axis_name")
            n = lax.axis_size(self.seq_axis_name)
            s_tot = x.shape[1]
            chunk = -(-s_tot // n)  # ceil: pad S+1 up to a multiple of n
            x = jnp.pad(x, ((0, 0), (0, chunk * n - s_tot), (0, 0)))
            idx = lax.axis_index(self.seq_axis_name)
            x = lax.dynamic_slice_in_dim(x, idx * chunk, chunk, axis=1)
            kv_mask = (idx * chunk + jnp.arange(chunk)) < s_tot
        for i in range(self.layers):
            x = EncoderLayer(
                heads=self.heads, mlp_dim=self.mlp_dim, dtype=self.dtype,
                param_dtype=self.param_dtype, name=f"encoder_layer_{i}",
                seq_axis_name=self.seq_axis_name, seq_mode=self.seq_mode,
            )(x, kv_mask=kv_mask)
        return nn.LayerNorm(
            epsilon=1e-6, dtype=self.dtype, param_dtype=self.param_dtype,
            name="ln",
        )(x)


class VisionTransformer(nn.Module):
    variant: str = "b_16"
    num_classes: int = 1000
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    bn_axis_name: Any = None  # no BN; accepted for API uniformity
    bn_dtype: Any = None  # likewise
    seq_axis_name: Optional[str] = None  # sequence parallelism (see above)
    seq_mode: str = "ulysses"
    seq_shard_tokens: bool = False  # trainer path: see Encoder docstring

    @nn.compact
    def __call__(self, x, train: bool = False):
        patch, layers, heads, hidden, mlp = _VARIANTS[self.variant]
        n, h, w, _ = x.shape
        if h % patch or w % patch:
            raise ValueError(
                f"vit_{self.variant} needs image size divisible by {patch}"
            )
        fan_in = 3 * patch * patch
        x = nn.Conv(
            hidden, (patch, patch), strides=(patch, patch), padding="VALID",
            use_bias=True, dtype=self.dtype, param_dtype=self.param_dtype,
            kernel_init=torch_trunc_normal_init(math.sqrt(1.0 / fan_in)),
            bias_init=nn.initializers.zeros,
            name="conv_proj",
        )(x)
        x = x.reshape(n, -1, hidden)  # row-major spatial flatten == torch
        cls = self.param(
            "class_token", nn.initializers.zeros, (1, 1, hidden), jnp.float32
        )
        x = jnp.concatenate(
            [jnp.broadcast_to(cls.astype(x.dtype), (n, 1, hidden)), x], axis=1
        )
        x = Encoder(
            layers=layers, heads=heads, mlp_dim=mlp, dtype=self.dtype,
            param_dtype=self.param_dtype, name="encoder",
            seq_axis_name=self.seq_axis_name, seq_mode=self.seq_mode,
            seq_shard_tokens=self.seq_shard_tokens,
        )(x)
        if self.seq_shard_tokens:
            # x is this device's LOCAL post-LN chunk; the cls token is
            # row 0 of sequence-rank 0's chunk — zero it elsewhere and
            # one psum replicates it, so the head (and loss) compute
            # identically on every sequence member
            from jax import lax

            idx = lax.axis_index(self.seq_axis_name)
            cls_tok = jnp.where(idx == 0, x[:, 0], jnp.zeros_like(x[:, 0]))
            pooled = lax.psum(cls_tok, self.seq_axis_name)
        else:
            pooled = x[:, 0]
        return nn.Dense(
            self.num_classes,
            dtype=self.dtype, param_dtype=self.param_dtype,
            kernel_init=nn.initializers.zeros,
            bias_init=nn.initializers.zeros,
            name="head",
        )(pooled)


register_variants(VisionTransformer, "vit", _VARIANTS)
