"""Sequence/context-parallel attention: Ulysses all-to-all + ring attention.

The reference has no sequence dimension at all (vision CNNs,
SURVEY.md §5 "long-context: absent by construction"), but this framework
ships attention families (ViT/Swin), and on TPU the idiomatic way to
scale their sequence axis past one chip's HBM is sequence parallelism
over a named mesh axis. Two standard schemes, both expressed as pure
functions over per-device shards for use inside ``shard_map``:

* **Ulysses** (all-to-all head scatter): each device holds a sequence
  shard of q/k/v with ALL heads; one ``lax.all_to_all`` per tensor
  re-shards to all-sequence/heads-split, plain attention runs locally,
  and one reverse all-to-all restores sequence sharding. Exact — the
  result is bitwise the unsharded attention (modulo reduction order).
  Communication rides the ICI as 3+1 all-to-alls of the activation size;
  requires ``heads % axis_size == 0``.

* **Ring attention** (k/v rotation with online softmax): k/v shards hop
  around the ring via ``lax.ppermute`` inside a ``lax.fori_loop`` while
  each device accumulates its queries' attention with the
  running-max/denominator (flash-attention style) update — the full
  (s, s) score matrix never materializes. The loop is double-buffered:
  each iteration ISSUES the permute fetching block i+1 before consuming
  block i, and neither depends on the other's output, so XLA's
  latency-hiding scheduler is free to run the ICI transfer under the
  block's einsums (structural overlap; actual overlap is the
  scheduler's call and has not been measured on multi-chip hardware —
  this environment has one chip). Works for any head count; memory per
  chip is O(s_local * d), enabling sequences that cannot fit on one
  chip.

Both schemes take an optional per-shard ``kv_mask`` (local key-validity
mask) so callers that PAD the token axis to a multiple of the axis size
— e.g. ViT's ``S + 1`` cls-prepended sequence in the trainer's
``DPTPU_SP`` path — get exact softmax over the real keys only.

Scaled dot-product convention matches ``dptpu.models.vit.SelfAttention``
(scale 1/sqrt(head_dim), f32 softmax). Equivalence against single-device
attention is locked in tests/test_sequence_parallel.py on the fake
8-device CPU mesh.
"""

import math
from typing import Optional

import jax
import jax.numpy as jnp


# Masked logits are set to a finite huge-negative instead of -inf:
# exp(-1e30 - m) is exactly 0.0 in f32 for any real row max m, while a
# fully-masked (padding) query row stays NaN-free through softmax and
# the online-softmax recurrence — its garbage output is sliced away by
# the caller and contributes zero cotangent.
_MASKED = -1e30




def full_attention(q, k, v, kv_mask=None):
    """Reference scaled-dot-product attention.

    q/k/v: (batch, seq, heads, head_dim) -> (batch, seq, heads, head_dim).
    ``kv_mask`` (seq,) bool marks valid KEY positions (False = padding).
    """
    hd = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    logits = logits.astype(jnp.float32)
    if kv_mask is not None:
        logits = jnp.where(kv_mask[None, None, None, :], logits, _MASKED)
    attn = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", attn.astype(q.dtype), v)


def ulysses_attention(q, k, v, axis_name: str, kv_mask=None):
    """All-to-all sequence-parallel attention (per-shard view).

    Inputs are the LOCAL sequence shard (batch, seq/N, heads, head_dim)
    on every device of ``axis_name`` (size N, ``heads % N == 0``).
    Internally re-shards to (batch, seq, heads/N, head_dim), runs plain
    attention, and re-shards back. Call under ``shard_map`` with the
    sequence axis of q/k/v partitioned over ``axis_name``. ``kv_mask``
    (seq/N,) bool marks this shard's valid key positions.
    """
    n = jax.lax.axis_size(axis_name)
    heads = q.shape[2]
    if heads % n:
        raise ValueError(
            f"ulysses needs heads ({heads}) divisible by axis size ({n})"
        )
    # (b, s/N, h, d) -> (b, s, h/N, d): scatter heads, gather sequence
    gather = lambda t: jax.lax.all_to_all(
        t, axis_name, split_axis=2, concat_axis=1, tiled=True
    )
    full_mask = (
        None
        if kv_mask is None
        else jax.lax.all_gather(kv_mask, axis_name, tiled=True)
    )
    out = full_attention(
        gather(q), gather(k), gather(v), kv_mask=full_mask
    )
    # (b, s, h/N, d) -> (b, s/N, h, d)
    return jax.lax.all_to_all(
        out, axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def ring_attention(q, k, v, axis_name: str, kv_mask=None):
    """Ring sequence-parallel attention with online softmax (per-shard).

    Inputs are the LOCAL sequence shard (batch, seq/N, heads, head_dim).
    k/v rotate N-1 times around the ring; the local q block folds each
    incoming k/v block into flash-style running statistics
    (row max ``m``, denominator ``l``, weighted accumulator ``o``), so
    peak memory is O(s_local^2) scores per step instead of O(s^2).

    Double-buffered: each loop iteration first ISSUES the ppermute that
    fetches block i+1, then consumes block i — the permute reads only
    the incoming buffer, never the block's outputs, so the ICI transfer
    and the einsums have no data dependence and XLA's latency-hiding
    scheduler may overlap them (whether it does is its call; single-chip
    hardware here cannot measure it). The final block is peeled out of
    the loop so exactly N-1 hops are issued.

    ``kv_mask`` (seq/N,) bool marks this shard's valid key positions;
    it rides the ring alongside its k/v block.
    """
    n = jax.lax.axis_size(axis_name)
    hd = q.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    qf = q.astype(jnp.float32) * scale
    # the mask (when given) rides the ring inside the rotated payload; a
    # default all-ones mask would be axis-INVARIANT and mismatch the
    # varying ppermute output in the loop carry, so unmasked callers get
    # a mask-free payload instead
    has_mask = kv_mask is not None

    def block(carry, kv):
        m, l, o = carry
        if has_mask:
            kb, vb, maskb = kv
        else:
            kb, vb = kv
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32))
        if has_mask:
            s = jnp.where(maskb[None, None, None, :], s, _MASKED)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)  # rescale of prior accumulator
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        o = o * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32)
        )
        return (m_new, l, o)

    # accumulators derived from qf so shard_map types them as varying
    # over the ring axis (plain constants would mismatch the loop carry).
    # m0 = _MASKED (not -inf): a fully-padded block then yields
    # alpha = exp(_MASKED - _MASKED) = 1, keeping pad-row garbage finite.
    zero = (qf * 0.0).sum(axis=-1).transpose(0, 2, 1)  # (b, h, s_local)
    m0 = zero + _MASKED
    l0 = zero
    o0 = qf.transpose(0, 2, 1, 3) * 0.0

    perm = [(i, (i + 1) % n) for i in range(n)]
    payload = (k, v, kv_mask) if has_mask else (k, v)

    def step(i, carry):
        m_l_o, kv = carry
        # issue the fetch of block i+1 FIRST; consume block i while the
        # permute is (potentially) in flight — no data dependence between
        # the two, so the scheduler may run them concurrently
        kv_next = jax.lax.ppermute(kv, axis_name, perm)
        m_l_o = block(m_l_o, kv)
        return (m_l_o, kv_next)

    m_l_o, kv = jax.lax.fori_loop(0, n - 1, step, ((m0, l0, o0), payload))
    m, l, o = block(m_l_o, kv)  # last block: no hop issued
    out = o / l[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (b, s/N, h, d)


def sequence_parallel_attention(
    q, k, v, axis_name: Optional[str], mode: str = "ulysses", kv_mask=None
):
    """Dispatch: plain attention when unsharded, else ulysses or ring."""
    if axis_name is None:
        return full_attention(q, k, v, kv_mask=kv_mask)
    if mode == "ulysses":
        return ulysses_attention(q, k, v, axis_name, kv_mask=kv_mask)
    if mode == "ring":
        return ring_attention(q, k, v, axis_name, kv_mask=kv_mask)
    raise ValueError(f"unknown sequence-parallel mode {mode!r}")
