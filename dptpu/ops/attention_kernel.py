"""``dptpu.ops.attention``'s two passes as Pallas kernels for the TPU.

The scan of ``attention.py`` is HBM-bound on traffic a fused kernel never
makes: a tile's float32 scores go out to HBM and back between the two
products, and the float32 carries are sliced out and put back each tile.
Here a ``[block_q, block_kv]`` score tile, the running maximum and
denominator and the output accumulator (forward), and the ``dq`` / ``dk``
/ ``dv`` accumulators (backward) live in VMEM from the first key block to
the last; HBM sees ``q``, ``k``, ``v``, ``d_out`` once a tile and
``out``, ``lse``, ``dq``, ``dk``, ``dv`` once.

Same mathematics, same tiles. ``attention.py`` hands over its tile
layout (``q``, ``out``, ``d_out``, ``dq`` as ``[B, Hkv, S * G, D]``, a
key/value head's G query heads stacked block by block; ``k``, ``v``
``[B, Hkv, S, D]``; ``lse`` one float32 a query) and the kernels read and
write every large array TRANSPOSED, tokens along the 128 lanes
(``[B, Hkv, D, S * G]``): a head size of 64 or 192 is then no padding in
HBM (a ``[.., 64]`` array is held as ``[.., 128]`` on the chip), the
softmax statistics, ``lse`` and ``delta`` are rows that broadcast down a
``[block_kv, block_q]`` score tile with no lane-broadcast copy of them
anywhere, and all five products of a tile are plain ``A @ B`` or
``A @ B.T``: no transpose in the kernels. The transposes are XLA's, which
makes them a matter of layout of the copies it makes anyway (the fold
into tiles, the way back). The grid walks the visible ``(row block, key
block)`` pairs only, handed in as prefetched scalars (the scan's
``_tile_pairs``): a row block is ``block_q`` rows of one query head, so
grouped queries are more row blocks over the same keys and nothing is
repeated. Scores, softmax statistics and accumulators are float32, the
scale multiplies the float32 scores, the products take their operands in
the inputs' dtype; at the scan's block sizes the sums run in the scan's
order, and on the chip every result is the scan's bit for bit (PERF.md).

The backward pass is ONE kernel (five products a tile, the scores
recomputed once): it walks the pairs key block by key block with ``dk``
and ``dv`` of that block in scratch, and holds the whole float32 ``dq``
of its ``(batch, key/value head)`` in VMEM until the last pair: that
is what bounds the shapes it takes (``attention.kernel_blocks``).

This module imports Pallas and Mosaic: ``attention.py`` imports it where
a TPU program is lowered and in the tests, nowhere else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MASKED = -1e30  # attention.py's: exp(masked - max) underflows to 0
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
# what one kernel may take of the chip's 128 MiB of VMEM (the resident
# float32 dq, its output block and the tiles in flight):
# ``attention.kernel_blocks`` keeps the shapes it hands over under it
VMEM_LIMIT_BYTES = 100 * 2 ** 20


def row_positions(rows: int, block: int, groups: int, block_q: int):
    """The first query position of each row block of the tile layout
    (``attention._to_tiles``: tile ``i`` holds queries ``[i * block,
    (i + 1) * block)`` of G heads, head after head)."""
    start = np.arange(rows // block_q) * block_q
    return (start // (groups * block) * block + start % block).astype(
        np.int32)


def _first_key(pos, window, maximum=jnp.maximum):
    """The first key a row block at ``pos`` sees: the farthest one
    visible to its first query."""
    return 0 if window is None else maximum(pos - (window - 1), 0)


def _pairs(positions, length: int, block_q: int, block_kv: int,
           by_key: bool, window=None):
    """The visible ``(row block, key block)`` pairs: row by row for the
    forward pass, key block by key block for the backward pass. Returns
    int32 arrays ``rows, cols, pos`` (the row block's first position) and
    ``first`` (1 on a key block's first pair)."""
    pairs = [(r, j) for r, p in enumerate(positions)
             for j in range(_first_key(int(p), window, max) // block_kv,
                            (int(p) + block_q - 1) // block_kv + 1)]
    assert {j for _, j in pairs} == set(range(length // block_kv))
    if by_key:
        pairs.sort(key=lambda rj: (rj[1], rj[0]))
    rows = np.asarray([r for r, _ in pairs], np.int32)
    cols = np.asarray([j for _, j in pairs], np.int32)
    first = np.r_[1, cols[1:] != cols[:-1]].astype(np.int32)
    return rows, cols, positions[rows], first


def _visible(pos, col, block_kv: int, shape, window):
    """Key position <= query position, and under a window less than
    ``window`` behind it, over a ``[block_kv, block_q]`` tile (keys down
    the rows, queries along the lanes)."""
    k_pos = col * block_kv + lax.broadcasted_iota(jnp.int32, shape, 0)
    q_pos = pos + lax.broadcasted_iota(jnp.int32, shape, 1)
    if window is None:
        return k_pos <= q_pos
    return jnp.logical_and(k_pos <= q_pos, q_pos - k_pos < window)


def _crossed(pos, col, block_q: int, block_kv: int, window):
    """Whether an edge of what is visible crosses the pair's tile: the
    diagonal (its last key lies past its first query) or the window's far
    side (its first key lies ``window`` or more behind its last query)."""
    diagonal = (col + 1) * block_kv - 1 > pos
    if window is None:
        return diagonal
    return jnp.logical_or(
        diagonal, pos + block_q - 1 - col * block_kv >= window)


def _either(needs_mask, tile):
    """``tile(True)`` on a pair an edge crosses, ``tile(False)`` (no
    mask computed) inside."""
    pl.when(needs_mask)(functools.partial(tile, True))
    pl.when(jnp.logical_not(needs_mask))(functools.partial(tile, False))


def _scores(k_ref, qt_ref, pos, col, scale, block_kv, masked, window):
    """The float32 scores of one tile, transposed: ``[block_kv,
    block_q]``."""
    s = jnp.dot(k_ref[...], qt_ref[...],
                preferred_element_type=jnp.float32) * scale
    if masked:
        s = jnp.where(_visible(pos, col, block_kv, s.shape, window), s,
                      _MASKED)
    return s


def _forward_kernel(rows_ref, cols_ref, pos_ref, qt_ref, k_ref, vt_ref,
                    outt_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                    scale: float, block_q: int, block_kv: int, window):
    del rows_ref  # the index maps' alone
    step = pl.program_id(2)
    col, pos = cols_ref[step], pos_ref[step]

    # a row block's first pair: key block 0, but for a window
    @pl.when(col == (0 if window is None else lax.div(
        _first_key(pos, window), block_kv)))
    def _():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(masked: bool):
        s = _scores(k_ref, qt_ref, pos, col, scale, block_kv, masked,
                    window)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=0, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            vt_ref[...], p.astype(vt_ref.dtype),
            preferred_element_type=jnp.float32)

    _either(_crossed(pos, col, block_q, block_kv, window), tile)

    @pl.when(col == lax.div(pos + block_q - 1, block_kv))
    def _():
        l = l_ref[...]
        outt_ref[...] = (acc_ref[...] / l).astype(outt_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _backward_kernel(rows_ref, cols_ref, pos_ref, first_ref, qt_ref, k_ref,
                     kt_ref, v_ref, dot_ref, lse_ref, delta_ref, dqt_ref,
                     dkt_ref, dvt_ref, dq_acc, dk_acc, dv_acc, *,
                     scale: float, block_q: int, block_kv: int, window):
    step = pl.program_id(2)
    row, col, pos = rows_ref[step], cols_ref[step], pos_ref[step]

    @pl.when(step == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(first_ref[step] == 1)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(masked: bool):
        qt, dot = qt_ref[...], dot_ref[...]
        s = _scores(k_ref, qt_ref, pos, col, scale, block_kv, masked,
                    window)
        p = jnp.exp(s - lse_ref[...])
        dv_acc[...] += lax.dot_general(dot, p.astype(dot.dtype), _NT,
                                       preferred_element_type=jnp.float32)
        dp = jnp.dot(v_ref[...], dot, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[...]) * scale).astype(qt.dtype)
        dk_acc[...] += lax.dot_general(qt, ds, _NT,
                                       preferred_element_type=jnp.float32)
        dq_acc[row] += jnp.dot(kt_ref[...], ds,
                               preferred_element_type=jnp.float32)

    _either(_crossed(pos, col, block_q, block_kv, window), tile)

    if window is None:
        # the last row block sees every key block: each column ends on it
        ends = row == dq_acc.shape[0] - 1
    else:
        # a column ends where the next pair starts another, or none follows
        after = jnp.minimum(step + 1, pl.num_programs(2) - 1)
        ends = jnp.logical_or(first_ref[after] == 1, after == step)

    @pl.when(ends)
    def _():
        dkt_ref[...] = dk_acc[...].astype(dkt_ref.dtype)
        dvt_ref[...] = dv_acc[...].astype(dvt_ref.dtype)

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        dqt_ref[...] = dq_acc[...].astype(dqt_ref.dtype)


def _rows_spec(width: int, block_q: int):
    """A ``[B, Hkv, width, rows]`` array (queries along the lanes) a row
    block at a time."""
    return pl.BlockSpec((None, None, width, block_q),
                        lambda b, h, t, rows, *_: (b, h, 0, rows[t]))


def _keys_spec(block_kv: int, width: int):
    """A ``[B, Hkv, S, width]`` array a key block at a time."""
    return pl.BlockSpec((None, None, block_kv, width),
                        lambda b, h, t, rows, cols, *_: (b, h, cols[t], 0))


def _keys_along_lanes_spec(width: int, block_kv: int):
    """A ``[B, Hkv, width, S]`` array a key block at a time."""
    return pl.BlockSpec((None, None, width, block_kv),
                        lambda b, h, t, rows, cols, *_: (b, h, 0, cols[t]))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _t(x):
    return jnp.swapaxes(x, -1, -2)


def forward(q, k, v, *, block: int, groups: int, scale: float,
            block_q: int, block_kv: int, window=None,
            interpret: bool = False):
    """``attention._forward``: ``(out, lse)`` in tile layout."""
    b, h, rows, d = q.shape
    length, dv = k.shape[2], v.shape[-1]
    positions = row_positions(rows, block, groups, block_q)
    pair_rows, cols, pos, _ = _pairs(positions, length, block_q, block_kv,
                                     by_key=False, window=window)
    kernel = functools.partial(_forward_kernel, scale=scale, window=window,
                               block_q=block_q, block_kv=block_kv)
    outt, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, h, len(cols)),
            in_specs=[_rows_spec(d, block_q), _keys_spec(block_kv, d),
                      _keys_along_lanes_spec(dv, block_kv)],
            out_specs=[_rows_spec(dv, block_q), _rows_spec(1, block_q)],
            scratch_shapes=[pltpu.VMEM((1, block_q), jnp.float32),
                            pltpu.VMEM((1, block_q), jnp.float32),
                            pltpu.VMEM((dv, block_q), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, h, dv, rows), q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, rows), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="causal_attention_forward",
    )(pair_rows, cols, pos, _t(q), k, _t(v))
    return _t(outt), lse.reshape(b, h, rows)


def backward(q, k, v, out, lse, d_out, *, block: int, groups: int,
             scale: float, block_q: int, block_kv: int, window=None,
             interpret: bool = False):
    """``attention._backward``: ``(dq, dk, dv)``."""
    b, h, rows, d = q.shape
    length, dv = k.shape[2], v.shape[-1]
    # rowsum(dO * O): the softmax Jacobian's diagonal term, once a query
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    positions = row_positions(rows, block, groups, block_q)
    pair_rows, cols, pos, first = _pairs(positions, length, block_q,
                                         block_kv, by_key=True,
                                         window=window)
    kernel = functools.partial(_backward_kernel, scale=scale, window=window,
                               block_q=block_q, block_kv=block_kv)
    blocks = rows // block_q
    # dq whole, a row block a leading index: [blocks, D, block_q]
    whole_dq = pl.BlockSpec((None, None, blocks, d, block_q),
                            lambda b, h, t, *_: (b, h, 0, 0, 0))
    dqt, dkt, dvt = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b, h, len(cols)),
            in_specs=[_rows_spec(d, block_q), _keys_spec(block_kv, d),
                      _keys_along_lanes_spec(d, block_kv),
                      _keys_spec(block_kv, dv), _rows_spec(dv, block_q),
                      _rows_spec(1, block_q), _rows_spec(1, block_q)],
            out_specs=[whole_dq, _keys_along_lanes_spec(d, block_kv),
                       _keys_along_lanes_spec(dv, block_kv)],
            scratch_shapes=[pltpu.VMEM((blocks, d, block_q), jnp.float32),
                            pltpu.VMEM((d, block_kv), jnp.float32),
                            pltpu.VMEM((dv, block_kv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, h, blocks, d, block_q), q.dtype),
                   jax.ShapeDtypeStruct((b, h, d, length), k.dtype),
                   jax.ShapeDtypeStruct((b, h, dv, length), v.dtype)],
        compiler_params=_params(), interpret=interpret,
        name="causal_attention_backward",
    )(pair_rows, cols, pos, first, _t(q), k, _t(k), v, _t(d_out),
      lse.reshape(b, h, 1, rows), delta.reshape(b, h, 1, rows))
    dq = dqt.transpose(0, 1, 2, 4, 3).reshape(q.shape)
    return dq, _t(dkt), _t(dvt)
