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
absolute position. With a ``window`` (sliding-window attention: key ``j``
is visible to query ``i`` where ``0 <= i - j < window``) the tiles walked
are a BAND of that triangle, those that hold a visible pair (70 of the
136 at a window of 2,048 and blocks of 512), and the tiles the window's
far side crosses are masked on that edge too; a window no shorter than
the row is the causal program itself. Grouped-query attention is native: the ``G = Hq / Hkv``
query heads that share a key/value head are folded into the tile's query
rows, so keys and values are never repeated. A length that is no multiple
of the block is padded up; padded keys lie behind every real query, so
the causal mask hides them, and padded queries are cut off the output.
Queries and keys share one head size and the values may have another
(latent attention: 192 against 128): the scores take the first, the
accumulator, the output and ``dv`` the second.

What runs where. The scan is plain XLA (matrix products,
``dynamic_slice``, one while loop each way) and runs wherever a program
is lowered for anything but a TPU: the CPU tests hold it to the plain
reference. In a program lowered for a TPU both passes are the Pallas
kernels of ``attention_kernel.py`` (the same tiles with scores, softmax
statistics and accumulators in VMEM; the scan is HBM-bound on traffic
they never make), for every call whose shapes they tile
(``kernel_blocks``: a block of whole 128 lanes, head sizes in whole 64s,
a row's float32 ``dq`` within the chip's VMEM); any other call keeps the
scan there too. The choice is made where the program is LOWERED, by the
platform it is lowered for (``_by_platform``: one primitive with two
lowerings), never by ``jax.default_backend()``: the tests lower for a
described v5e from a CPU process and get the kernels, the CPU gets the
scan's own program (no ``case`` on the platform round it; a model's
layers share one lowered body of it), and nothing imports Pallas until
a TPU program with such a call is lowered. Either way scores, softmax
statistics and the accumulators are float32, the scale multiplies the
float32 scores, and the products of a tile take their operands in the
inputs' dtype (bfloat16 under O2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax._src import dispatch
from jax.ad_checkpoint import checkpoint_name
from jax.extend.core import Primitive
from jax.interpreters import mlir

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


def band(window, length: int):
    """``window`` as the passes take it: None for a causal call, which a
    window no shorter than the row is too."""
    if window is not None and window < 1:
        raise ValueError(f"a window of {window} tokens shows a query "
                         f"nothing, not even itself")
    return None if window is None or window >= length else int(window)


def _tile_pairs(num_blocks: int, block: int = 0, window=None):
    """The causal lower triangle of tiles, row by row: ``(i, j <= i)``;
    under a window those of them that hold a visible pair (the nearest
    pair of tiles ``i`` and ``j`` lies ``(i - j - 1) * block + 1``
    apart)."""
    reach = num_blocks if window is None else (window - 2) // block + 1
    pairs = [(i, j) for i in range(num_blocks)
             for j in range(max(i - reach, 0), i + 1)]
    return (np.asarray([p[0] for p in pairs], np.int32),
            np.asarray([p[1] for p in pairs], np.int32))


def tiles_walked(length: int, window=None, block: int = DEFAULT_BLOCK) -> int:
    """The key tiles one pass of ``causal_attention`` walks over a row
    of ``length`` tokens (of one key/value head): a count from the
    shapes, for a model to report beside its calls."""
    block = min(block, length)
    return len(_tile_pairs(-(-length // block), block,
                           band(window, length))[0])


def _block(x, index, size):
    """Rows ``[index * size, (index + 1) * size)`` of axis 2."""
    return lax.dynamic_slice_in_dim(x, index * size, size, axis=2)


def _put_block(x, update, index, size):
    return lax.dynamic_update_slice_in_dim(x, update, index * size, axis=2)


def _tile_scores(q_i, k_j, i, j, block, groups, scale, window):
    """``[B, Hkv, G * block, block]`` float32 scores of one tile, the
    diagonal tile masked by position (query row r of the tile is query
    ``r % block`` of its block: the G heads are stacked along the rows),
    and under a window every tile by how far behind its query a key
    lies. A tile the window leaves nothing of for some query row (its
    first walked tile, where the window is no whole blocks) reads
    ``_MASKED`` all along that row: the running maximum stays at it, what
    is summed under it is wiped by ``exp(_MASKED - m)`` = 0 at the row's
    first visible score, and the backward pass's ``exp(_MASKED - lse)``
    is 0."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q_i, k_j,
                   preferred_element_type=jnp.float32) * scale
    q_pos = jnp.tile(jnp.arange(block), groups)[:, None]
    k_pos = jnp.arange(block)[None, :]
    if window is None:
        visible = jnp.logical_or(j < i, k_pos <= q_pos)
    else:
        behind = (i - j) * block + q_pos - k_pos
        visible = jnp.logical_and(behind >= 0, behind < window)
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


def _forward(q, k, v, block, groups, scale, window=None):
    """``q`` in tile layout ``[B, Hkv, S * G, D]``, ``k`` ``[B, Hkv, S,
    D]``, ``v`` ``[B, Hkv, S, Dv]``. Returns ``(out, lse)`` in tile
    layout, ``out`` of ``v``'s head size, ``lse`` ``[B, Hkv, S * G]``
    float32."""
    b, h, rows, _ = q.shape
    qb = groups * block
    ii, jj = _tile_pairs(k.shape[2] // block, block, window)

    def tile(carry, ij):
        m, l, acc = carry
        i, j = ij
        s = _tile_scores(_block(q, i, qb), _block(k, j, block), i, j,
                         block, groups, scale, window)
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


def _backward(q, k, v, out, lse, d_out, block, groups, scale, window=None):
    qb = groups * block
    ii, jj = _tile_pairs(k.shape[2] // block, block, window)
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
        s = _tile_scores(q_i, k_j, i, j, block, groups, scale, window)
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


# what the kernels may count on of the chip's 128 MiB of VMEM (their
# compiler limit is 100 MiB: ``attention_kernel.VMEM_LIMIT_BYTES``)
KERNEL_VMEM_BYTES = 80 * 2 ** 20
_LANES = 128


def _vmem_bytes(rows: int, qk_head: int, v_head: int, itemsize: int,
                block_q: int, block_kv: int) -> int:
    """What the backward kernel (the larger of the two) holds in VMEM for
    ``rows`` query rows a key/value head: ``dq`` whole in float32 and its
    output block twice, every streamed tile twice (tokens along the
    lanes, but for ``k`` and ``v``, whose head size is padded up to whole
    128 lanes), the key block's two accumulators and the float32 tiles
    of one step."""
    padded = -(-qk_head // _LANES) * _LANES + -(-v_head // _LANES) * _LANES
    dq = rows * qk_head * (4 + 2 * itemsize)
    streamed = 2 * itemsize * (block_q * (qk_head + v_head) + block_kv * (
        padded + 2 * qk_head + v_head))
    accumulators = 4 * block_kv * (qk_head + v_head)
    return dq + streamed + accumulators + 6 * block_q * block_kv * 4


def kernel_blocks(length: int, groups: int, qk_head: int, v_head: int,
                  dtype, block: int):
    """``(block_q, block_kv)`` for the kernels on rows of ``length``
    tokens (whole blocks), or None for a call they do not tile, which
    keeps the scan. A rule on the shapes alone."""
    if block % _LANES or qk_head % 64 or v_head % 64 \
            or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return None
    blocks = (block, block)
    if _vmem_bytes(length * groups, qk_head, v_head,
                   jnp.dtype(dtype).itemsize, *blocks) > KERNEL_VMEM_BYTES:
        return None
    return blocks


def _by_platform(name: str, shapes, default, tpu) -> Primitive:
    """A primitive that lowers to ``tpu(*args, **params)`` in a program
    for the TPU and to ``default(*args, **params)`` in any other:
    ``lax.platform_dependent``'s choice without its ``case`` round the
    branch taken and without tracing the branch not taken (no Pallas off
    the TPU)."""
    prim = Primitive(name)
    prim.multiple_results = True
    prim.def_impl(functools.partial(dispatch.apply_primitive, prim))
    prim.def_abstract_eval(
        lambda *avals, **params: [jax.core.ShapedArray(shape, dtype)
                                  for shape, dtype in shapes(*avals)])
    mlir.register_lowering(prim, mlir.lower_fun(default,
                                                multiple_results=True))
    mlir.register_lowering(prim, mlir.lower_fun(tpu, multiple_results=True),
                           platform="tpu")
    return prim


def _blocks_of(q, k, v, block, groups):
    return kernel_blocks(k.shape[2], groups, q.shape[-1], v.shape[-1],
                         q.dtype, block)


def _kernel(name: str, **how):
    """``attention_kernel.forward`` / ``backward`` under the scan's
    signature, at the block sizes the shapes give."""
    def run(q, k, v, *rest, block, groups, scale, window=None):
        # Pallas and Mosaic load here: where a TPU program is lowered
        from dptpu.ops import attention_kernel

        block_q, block_kv = _blocks_of(q, k, v, block, groups)
        return getattr(attention_kernel, name)(
            q, k, v, *rest, block=block, groups=groups, scale=scale,
            window=window, block_q=block_q, block_kv=block_kv, **how)

    return run


_forward_p = _by_platform(
    "causal_attention_forward",
    lambda q, k, v: ((q.shape[:-1] + v.shape[-1:], q.dtype),
                     (q.shape[:-1], jnp.float32)),
    _forward, _kernel("forward"))
_backward_p = _by_platform(
    "causal_attention_backward",
    lambda q, k, v, *_: ((q.shape, q.dtype), (k.shape, k.dtype),
                         (v.shape, v.dtype)),
    _backward, _kernel("backward"))
_on_tpu_p = _by_platform(
    "lowered_for_tpu", lambda: (((), jnp.int32),),
    lambda: [jnp.int32(0)], lambda: [jnp.int32(1)])


def _here(scan, prim, *arrays, block, groups, scale, window):
    """One pass by ``scan``, or by ``prim`` (the scan again, or the
    kernel where the program is lowered for a TPU) for the shapes the
    kernels take. The window changes which tiles a pass walks and not
    what it holds: the rule on the shapes does not read it."""
    if _blocks_of(*arrays[:3], block, groups) is None:
        return scan(*arrays, block, groups, scale, window)
    return prim.bind(*arrays, block=block, groups=groups, scale=scale,
                     window=window)


def kernel_calls(length: int, heads: int, kv_heads: int, qk_head: int,
                 v_head: int, dtype, block: int = DEFAULT_BLOCK):
    """1 where ``causal_attention`` on rows of ``length`` tokens takes
    the kernels in the program being lowered, 0 where it takes the scan
    (another platform, or a shape they do not tile): an int32 scalar of
    the traced program, for a model to count its calls with. One answer
    for causal and windowed calls alike: the rule reads the shapes."""
    block = min(block, length)
    blocks = kernel_blocks(-(-length // block) * block, heads // kv_heads,
                           qk_head, v_head, dtype, block)
    if blocks is None:
        return jnp.zeros((), jnp.int32)
    return _on_tpu_p.bind()[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attend(q, k, v, block, groups, scale, window):
    return _here(_forward, _forward_p, q, k, v, block=block, groups=groups,
                 scale=scale, window=window)[0]


def _attend_fwd(q, k, v, block, groups, scale, window):
    # the names sit HERE, on both residuals: a name on the call's result
    # alone leaves ``lse`` to be made again, by the whole forward pass
    out, lse = map(checkpoint_name,
                   _here(_forward, _forward_p, q, k, v, block=block,
                         groups=groups, scale=scale, window=window),
                   RESIDUAL_NAMES)
    return out, (q, k, v, out, lse)


def _attend_bwd(block, groups, scale, window, residuals, d_out):
    return _here(_backward, _backward_p, *residuals, d_out, block=block,
                 groups=groups, scale=scale, window=window)


_attend.defvjp(_attend_fwd, _attend_bwd)


def causal_attention(q, k, v, *, scale: float, window=None,
                     block: int = DEFAULT_BLOCK):
    """Causal softmax attention, blockwise.

    ``q`` is ``[B, S, Hq, D]``, ``k`` ``[B, S, Hkv, D]`` and ``v``
    ``[B, S, Hkv, Dv]`` with ``Hq`` a multiple of ``Hkv`` (grouped
    queries); returns ``[B, S, Hq, Dv]`` in ``q``'s dtype. ``window``:
    key ``j`` is visible to query ``i`` where ``0 <= i - j < window``
    (None: every key behind the query, and a window of ``S`` or more is
    that same program). Differentiable: the backward pass is the tiled
    recomputation, not autodiff through the scan.
    """
    b, s, hq, _ = q.shape
    hkv, dv = k.shape[2], v.shape[-1]
    if hq % hkv:
        raise ValueError(f"{hq} query heads are not whole groups over "
                         f"{hkv} key/value heads")
    groups = hq // hkv
    window = band(window, s)
    block = min(block, s)
    padded = -(-s // block) * block
    if padded != s:
        pad = ((0, 0), (0, padded - s), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    out = _attend(_to_tiles(_fold(q, hkv), block),
                  k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                  block, groups, float(scale), window)
    out = _from_tiles(out, block, groups)  # [B, Hkv, G, S, Dv]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, padded, hq, dv)
    return out[:, :s]


def plain_causal_attention(q, k, v, *, scale: float, window=None):
    """The same result with the scores materialised: what the blockwise
    one is tested against (and fine at short lengths)."""
    b, s, hq, _ = q.shape
    hkv = k.shape[2]
    qg = _fold(q, hkv).astype(jnp.float32)
    kt = k.transpose(0, 2, 1, 3).astype(jnp.float32)
    vt = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kt) * scale
    mask = jnp.tril(jnp.ones((s, s), bool))
    if window is not None:
        mask = jnp.logical_and(mask, ~jnp.tril(jnp.ones((s, s), bool),
                                               -window))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, vt)
    return out.transpose(0, 3, 1, 2, 4).reshape(
        b, s, hq, v.shape[-1]).astype(q.dtype)
