"""Blockwise causal attention: never holds the ``[heads, S, S]`` scores.

``vit.py`` computes attention as two einsums round a float32 softmax with
the scores materialised, which is right at 197 tokens. A language model
at 8,192 tokens would hold 32 x 8192^2 x 4 B = 8.6 GB of scores a row.
Here the scores exist one ``[block, block]`` tile at a time, with a
running maximum and denominator per query (the online softmax of flash
attention), and the backward pass recomputes each tile from the saved
log-sum-exp instead of keeping it. The backward rule's residuals are
``(q, k, v, out, lse)``; the forward rule names the two that only the
forward scan can make (``RESIDUAL_NAMES``), so that a caller which
rematerialises the layer round this call can keep them
(``jax.checkpoint_policies.save_only_these_names``) and the scan is not
run a second time on the way back.

One ``lax.scan`` walks the tiles ``(i, j)`` with ``j <= i`` only (the
causal lower triangle, 136 of 256 tiles at 16 blocks), so no tile that
the mask would zero is computed; the diagonal tiles are masked by
absolute position. Grouped-query attention is native: the ``G = Hq / Hkv``
query heads that share a key/value head are folded into the tile's query
rows, so keys and values are never repeated. A length that is no multiple
of the block is padded up; padded keys lie behind every real query, so
the causal mask hides them, and padded queries are cut off the output.
Queries and keys share one head size and the values may have another
(latent attention: 192 against 128): the scores take the first, the
accumulator, the output and ``dv`` the second.

Plain XLA (matrix products, ``dynamic_slice``, one while loop each way):
the same program runs on the CPU tests and on the chip. Scores, softmax
statistics and the accumulators are float32; the two matrix products of
a tile take their operands in the inputs' dtype (bfloat16 under O2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

DEFAULT_BLOCK = 512
_MASKED = -1e30  # finite: exp(masked - max) underflows to 0, never NaN
# the residuals only the forward scan can make: the output in tile layout
# ``[B, Hkv, S * G, Dv]`` and the float32 log-sum-exp ``[B, Hkv, S * G]``
RESIDUAL_NAMES = ("attention_out", "attention_lse")


def residual_bytes(rows: int, length: int, heads: int, v_head: int,
                   dtype, block: int = DEFAULT_BLOCK) -> int:
    """The bytes ``RESIDUAL_NAMES`` hold for one call on ``rows`` rows of
    ``length`` tokens: ``out`` of the values' head size in ``dtype`` and
    the float32 ``lse``, a row padded up to whole blocks."""
    block = min(block, length)
    padded = rows * -(-length // block) * block
    return padded * heads * (v_head * jnp.dtype(dtype).itemsize + 4)


def _tile_pairs(num_blocks: int):
    """The causal lower triangle of tiles, row by row: ``(i, j <= i)``."""
    pairs = [(i, j) for i in range(num_blocks) for j in range(i + 1)]
    return (np.asarray([p[0] for p in pairs], np.int32),
            np.asarray([p[1] for p in pairs], np.int32))


def _block(x, index, size):
    """Rows ``[index * size, (index + 1) * size)`` of axis 2."""
    return lax.dynamic_slice_in_dim(x, index * size, size, axis=2)


def _put_block(x, update, index, size):
    return lax.dynamic_update_slice_in_dim(x, update, index * size, axis=2)


def _tile_scores(q_i, k_j, i, j, block, groups, scale):
    """``[B, Hkv, G * block, block]`` float32 scores of one tile, the
    diagonal tile masked by position (query row r of the tile is query
    ``r % block`` of its block: the G heads are stacked along the rows)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q_i, k_j,
                   preferred_element_type=jnp.float32) * scale
    q_pos = jnp.tile(jnp.arange(block), groups)[:, None]
    k_pos = jnp.arange(block)[None, :]
    visible = jnp.logical_or(j < i, k_pos <= q_pos)
    return jnp.where(visible, s, _MASKED)


def _fold(q, hkv):
    """``[B, S, Hq, D]`` -> ``[B, Hkv, G, S, D]``: the query heads
    grouped under the key/value head they share."""
    b, s, hq, d = q.shape
    return q.reshape(b, s, hkv, hq // hkv, d).transpose(0, 2, 3, 1, 4)


def _to_tiles(x, block):
    """``[B, Hkv, G, S, D]`` -> ``[B, Hkv, S * G, D]`` with each block's
    G heads stacked inside the block, so that a tile's query rows are one
    contiguous slice of ``G * block`` rows."""
    b, h, g, s, d = x.shape
    n = s // block
    x = x.reshape(b, h, g, n, block, d).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h, n * g * block, d)


def _from_tiles(x, block, groups):
    b, h, rows, d = x.shape
    n = rows // (groups * block)
    x = x.reshape(b, h, n, groups, block, d).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h, groups, n * block, d)


def _forward(q, k, v, block, groups, scale):
    """``q`` in tile layout ``[B, Hkv, S * G, D]``, ``k`` ``[B, Hkv, S,
    D]``, ``v`` ``[B, Hkv, S, Dv]``. Returns ``(out, lse)`` in tile
    layout, ``out`` of ``v``'s head size, ``lse`` ``[B, Hkv, S * G]``
    float32."""
    b, h, rows, _ = q.shape
    qb = groups * block
    ii, jj = _tile_pairs(k.shape[2] // block)

    def tile(carry, ij):
        m, l, acc = carry
        i, j = ij
        s = _tile_scores(_block(q, i, qb), _block(k, j, block), i, j,
                         block, groups, scale)
        m_i, l_i, acc_i = (_block(m, i, qb), _block(l, i, qb),
                           _block(acc, i, qb))
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_i - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_i * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc_i * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(v.dtype), _block(v, j, block),
            preferred_element_type=jnp.float32)
        return (_put_block(m, m_new, i, qb), _put_block(l, l_new, i, qb),
                _put_block(acc, acc_new, i, qb)), None

    init = (jnp.full((b, h, rows, 1), _MASKED, jnp.float32),
            jnp.zeros((b, h, rows, 1), jnp.float32),
            jnp.zeros((b, h, rows, v.shape[-1]), jnp.float32))
    (m, l, acc), _ = lax.scan(tile, init, (ii, jj))
    out = (acc / l).astype(q.dtype)
    return out, (m + jnp.log(l))[..., 0]


def _backward(q, k, v, out, lse, d_out, block, groups, scale):
    qb = groups * block
    ii, jj = _tile_pairs(k.shape[2] // block)
    # rowsum(dO * O): the softmax Jacobian's diagonal term, once per query
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    lse = lse[..., None]

    def tile(carry, ij):
        dq, dk, dv = carry
        i, j = ij
        q_i, k_j, v_j = _block(q, i, qb), _block(k, j, block), \
            _block(v, j, block)
        do_i = _block(d_out, i, qb)
        s = _tile_scores(q_i, k_j, i, j, block, groups, scale)
        p = jnp.exp(s - _block(lse, i, qb))
        dv_j = jnp.einsum("bhqk,bhqd->bhkd", p.astype(do_i.dtype), do_i,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do_i, v_j,
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - _block(delta, i, qb)) * scale).astype(q.dtype)
        dq_i = jnp.einsum("bhqk,bhkd->bhqd", ds, k_j,
                          preferred_element_type=jnp.float32)
        dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, q_i,
                          preferred_element_type=jnp.float32)
        return (_put_block(dq, _block(dq, i, qb) + dq_i, i, qb),
                _put_block(dk, _block(dk, j, block) + dk_j, j, block),
                _put_block(dv, _block(dv, j, block) + dv_j, j, block)), None

    init = (jnp.zeros(q.shape, jnp.float32),
            jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32))
    (dq, dk, dv), _ = lax.scan(tile, init, (ii, jj))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attend(q, k, v, block, groups, scale):
    return _forward(q, k, v, block, groups, scale)[0]


def _attend_fwd(q, k, v, block, groups, scale):
    # the names sit HERE, on both residuals: a name on the call's result
    # alone leaves ``lse`` to be made again, by the whole scan
    out, lse = map(checkpoint_name,
                   _forward(q, k, v, block, groups, scale), RESIDUAL_NAMES)
    return out, (q, k, v, out, lse)


def _attend_bwd(block, groups, scale, residuals, d_out):
    return _backward(*residuals, d_out, block, groups, scale)


_attend.defvjp(_attend_fwd, _attend_bwd)


def causal_attention(q, k, v, *, scale: float, block: int = DEFAULT_BLOCK):
    """Causal softmax attention, blockwise.

    ``q`` is ``[B, S, Hq, D]``, ``k`` ``[B, S, Hkv, D]`` and ``v``
    ``[B, S, Hkv, Dv]`` with ``Hq`` a multiple of ``Hkv`` (grouped
    queries); returns ``[B, S, Hq, Dv]`` in ``q``'s dtype.
    Differentiable: the backward pass is the tiled recomputation, not
    autodiff through the scan.
    """
    b, s, hq, _ = q.shape
    hkv, dv = k.shape[2], v.shape[-1]
    if hq % hkv:
        raise ValueError(f"{hq} query heads are not whole groups over "
                         f"{hkv} key/value heads")
    groups = hq // hkv
    block = min(block, s)
    padded = -(-s // block) * block
    if padded != s:
        pad = ((0, 0), (0, padded - s), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    out = _attend(_to_tiles(_fold(q, hkv), block),
                  k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                  block, groups, float(scale))
    out = _from_tiles(out, block, groups)  # [B, Hkv, G, S, Dv]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, padded, hq, dv)
    return out[:, :s]


def plain_causal_attention(q, k, v, *, scale: float):
    """The same result with the scores materialised: what the blockwise
    one is tested against (and fine at short lengths)."""
    b, s, hq, _ = q.shape
    hkv = k.shape[2]
    qg = _fold(q, hkv).astype(jnp.float32)
    kt = k.transpose(0, 2, 1, 3).astype(jnp.float32)
    vt = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kt) * scale
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, vt)
    return out.transpose(0, 3, 1, 2, 4).reshape(
        b, s, hq, v.shape[-1]).astype(q.dtype)
