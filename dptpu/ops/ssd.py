"""The Mamba-2 state-space scan in its chunked form, forward and backward.

The recurrence, a head at a time (``x_t`` the head's ``P`` inputs, ``B_t``
and ``C_t`` the ``N`` state coordinates every head shares, ``Δ_t > 0`` the
head's step, ``A < 0`` its decay rate, ``D`` its skip):

    h_t = exp(Δ_t A) h_{t-1} + Δ_t x_t ⊗ B_t        (h: P x N, h_0 = 0)
    y_t = h_t C_t + D x_t

Run token by token that is 8,192 dependent steps of a rank-one update a
row. The same sums over chunks of ``Q`` tokens are matrix products. With
``a_t = Δ_t A <= 0`` and ``s_i`` its running sum inside a chunk (``s_Q``
the chunk's whole):

    Y_intra[i] = Σ_{j<=i} exp(s_i - s_j) (C_i . B_j) Δ_j x_j
    S_c        = Σ_j exp(s_Q - s_j) Δ_j x_j ⊗ B_j      (the chunk's own state)
    H_{c+1}    = exp(s_Q) H_c + S_c,  H_0 = 0            (across chunks)
    Y_inter[i] = exp(s_i) C_i . H_c
    y          = Y_intra + Y_inter + D x

**Numbers.** Every exponent is a difference of running sums taken FIRST
and is never positive where it is used (the masked upper triangle is set
to a large negative number before the exponential, so neither pass sees
an overflow): with trained weights ``Q x Δ x |A|`` reaches hundreds of
nats, and a ratio of two exponentials would be inf / inf. ``Δ``, ``A``,
the sums, the exponentials and the carried state are float32 whatever the
inputs' dtype; the products take their operands in ``x``'s dtype
(bfloat16 under O2) and accumulate in float32.

**Memory.** The decay matrices of all chunks and heads at once are
``chunks x H x Q^2`` float32 (537 MB a row at 32 x 64 x 256^2), and a
backward pass wants a few such arrays. So one ``lax.scan`` walks GROUPS of
chunks, as many as keep one group's decay matrices within
``DECAY_BYTES``; inside a group every chunk and head is one batched
product, and the state crosses the group's chunks by the recurrence
above. The backward pass is chunked too (``jax.custom_vjp``): the forward
keeps its inputs and the state that ENTERS each group (2 MB a row at
64 x 64 x 128 float32), and a reverse scan re-forms one group at a time,
differentiates that group alone (``jax.vjp`` of the group's function:
its decay matrices are made again, never kept) and hands the state's
cotangent to the group before. Nothing differentiates through a loop of
the row's length.

A length that is no multiple of the chunk is padded up with ``Δ = 0``
tokens, which leave the state as it is and are cut off the output. The
result does not depend on the chunk but for rounding.

What runs where: plain XLA everywhere (``kernel_calls`` is 0 for every
shape). A model counts its calls and its chunks for the ``fetch`` span
(``ssd_calls``, ``ssd_kernel_calls``, ``ssd_chunks``), so a kernel that
takes the scan off XLA one day moves a counter that is already read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

DEFAULT_CHUNK = 256
# one group's decay matrices ``[B, chunks, H, Q, Q]`` float32 stay within
# this; the backward of a group holds about five arrays of that size
DECAY_BYTES = 128 * 2 ** 20
_MASKED = -1e30  # finite: exp underflows to 0 and its derivative with it


def chunks_of(length: int, chunk: int = DEFAULT_CHUNK) -> int:
    """The chunks one row of ``length`` tokens is walked in."""
    return -(-length // min(chunk, length))


def kernel_calls(*_shape) -> int:
    """1 if a call of these shapes runs as a fused kernel in the program
    being lowered, else 0: there is no kernel, so 0."""
    return 0


def _group_size(rows: int, chunks: int, heads: int, chunk: int) -> int:
    """The largest divisor of ``chunks`` whose decay matrices fit
    ``DECAY_BYTES`` (one chunk at least)."""
    fit = max(DECAY_BYTES // (rows * heads * chunk * chunk * 4), 1)
    return max(k for k in range(1, chunks + 1)
               if chunks % k == 0 and k <= fit)


def _group(h_in, x, dt, b, c, a, d):
    """``k`` chunks at once: ``(state after them, their y)``.

    ``h_in`` ``[B, H, P, N]`` float32, the state entering the first of
    them; ``x`` ``[B, k, Q, H, P]``; ``dt`` ``[B, k, Q, H]`` float32;
    ``b``, ``c`` ``[B, k, Q, N]``; ``a``, ``d`` ``[H]`` float32."""
    dtype, f32 = x.dtype, jnp.float32
    q = x.shape[2]
    s = jnp.cumsum(dt * a, axis=2)                    # [B, k, Q, H], <= 0
    by_head = s.transpose(0, 1, 3, 2)                 # [B, k, H, Q]
    whole = by_head[..., -1]                          # [B, k, H]
    xd32 = dt[..., None] * x.astype(f32)              # Δ_j x_j
    xd = xd32.astype(dtype)
    # inside a chunk
    cb = jnp.einsum("bkin,bkjn->bkij", c, b, preferred_element_type=f32)
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(
        causal, by_head[..., :, None] - by_head[..., None, :], _MASKED))
    y = jnp.einsum("bkhij,bkjhp->bkihp",
                   (cb[:, :, None] * decay).astype(dtype), xd,
                   preferred_element_type=f32)
    # each chunk's own state, then the states entering each chunk
    to_end = jnp.exp(whole[..., None] - by_head).transpose(0, 1, 3, 2)
    own = jnp.einsum("bkjhp,bkjn->bkhpn",
                     (to_end[..., None] * xd32).astype(dtype), b,
                     preferred_element_type=f32)

    def cross(h, chunk):
        keep, add = chunk
        return keep[..., None, None] * h + add, h

    h_out, entering = lax.scan(
        cross, h_in, (jnp.exp(whole).swapaxes(0, 1), own.swapaxes(0, 1)))
    y = y + jnp.exp(s)[..., None] * jnp.einsum(
        "bkin,bkhpn->bkihp", c, entering.swapaxes(0, 1).astype(dtype),
        preferred_element_type=f32)
    return h_out, (y + d[:, None] * x.astype(f32)).astype(dtype)


def _grouped(array, chunks: int, group: int, chunk: int):
    """``[B, S, ...]`` -> ``[groups, B, k, Q, ...]``."""
    rows = array.shape[0]
    return jnp.moveaxis(array.reshape(
        rows, chunks // group, group, chunk, *array.shape[2:]), 1, 0)


def _ungrouped(array):
    """``[groups, B, k, Q, ...]`` -> ``[B, S, ...]``."""
    array = jnp.moveaxis(array, 0, 1)
    return array.reshape(array.shape[0], -1, *array.shape[4:])


def _forward(x, dt, a, b, c, d, chunk, group):
    rows, length, heads, width = x.shape
    split = functools.partial(_grouped, chunks=length // chunk, group=group,
                              chunk=chunk)

    def step(h, inputs):
        h_out, y = _group(h, *inputs, a, d)
        return h_out, (y, h)

    h0 = jnp.zeros((rows, heads, width, b.shape[-1]), jnp.float32)
    _, (y, entering) = lax.scan(step, h0,
                                (split(x), split(dt), split(b), split(c)))
    return _ungrouped(y), entering


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, dt, a, b, c, d, chunk, group):
    return _forward(x, dt, a, b, c, d, chunk, group)[0]


def _scan_fwd(x, dt, a, b, c, d, chunk, group):
    y, entering = _forward(x, dt, a, b, c, d, chunk, group)
    return y, (x, dt, a, b, c, d, entering)


def _scan_bwd(chunk, group, residuals, dy):
    x, dt, a, b, c, d, entering = residuals
    split = functools.partial(_grouped, chunks=x.shape[1] // chunk,
                              group=group, chunk=chunk)

    def step(carry, inputs):
        dh, da, dd = carry
        h_in, dy_g, *group_inputs = inputs
        _, back = jax.vjp(_group, h_in, *group_inputs, a, d)
        dh_in, dx, ddt, db, dc, da_g, dd_g = back((dh, dy_g))
        return (dh_in, da + da_g, dd + dd_g), (dx, ddt, db, dc)

    (_, da, dd), grads = lax.scan(
        step, (jnp.zeros_like(entering[0]), jnp.zeros_like(a),
               jnp.zeros_like(d)),
        (entering, split(dy), split(x), split(dt), split(b), split(c)),
        reverse=True)
    dx, ddt, db, dc = map(_ungrouped, grads)
    return dx, ddt, da, db, dc, dd


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd(x, dt, a, b, c, d, *, chunk: int = DEFAULT_CHUNK):
    """The scan of the module docstring over whole rows.

    Args:
      x: ``[B, S, H, P]`` inputs, ``H`` heads of ``P``; the products run
        in its dtype.
      dt: ``[B, S, H]`` the steps ``Δ > 0`` (after the softplus).
      a: ``[H]`` the decay rates ``A < 0``.
      b, c: ``[B, S, N]`` the state's input and output coordinates (one
        group: all heads share them).
      d: ``[H]`` the skip.
      chunk: tokens a chunk (a row shorter than one is one chunk).

    Returns ``y`` ``[B, S, H, P]`` in ``x``'s dtype; the state starts at
    zero at every row's start and is not returned.
    """
    rows, length, heads, _ = x.shape
    chunk = min(chunk, length)
    chunks = chunks_of(length, chunk)
    pad = chunks * chunk - length
    f32 = jnp.float32
    dt, a, d = dt.astype(f32), a.astype(f32), d.astype(f32)
    b, c = b.astype(x.dtype), c.astype(x.dtype)
    if pad:
        # Δ = 0: the state stays, nothing is added; cut off below
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    y = _scan(x, dt, a, b, c, d, chunk,
              _group_size(rows, chunks, heads, chunk))
    return y[:, :length]


def plain_ssd(x, dt, a, b, c, d):
    """The recurrence itself, one token at a time in float32: what the
    tests hold ``ssd`` to. ``O(S)`` dependent steps; small sizes only."""
    f32 = jnp.float32
    x32, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))

    def step(h, token):
        x_t, dt_t, b_t, c_t = token           # [B,H,P] [B,H] [B,N] [B,N]
        h = jnp.exp(dt_t * a)[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return h, jnp.einsum("bhpn,bn->bhp", h, c_t) + d[:, None] * x_t

    h0 = jnp.zeros((*x.shape[0:1], *x.shape[2:], b.shape[-1]), f32)
    _, y = lax.scan(step, h0, tuple(jnp.moveaxis(v, 1, 0)
                                    for v in (x32, dt, b, c)))
    return jnp.moveaxis(y, 0, 1).astype(x.dtype)
