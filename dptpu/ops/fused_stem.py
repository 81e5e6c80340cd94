"""Fused stem: BN-affine + ReLU + 3x3/2 max-pool as one custom-VJP region.

The reference's stem (torchvision resnet: conv7x7 -> BN -> ReLU ->
MaxPool2d(3,2,1), consumed via imagenet_ddp.py:108-114) is the single most
bandwidth-hungry non-conv piece of a ResNet train step on TPU: at batch 128
the 112x112x64 ReLU plane is 205 MB that the stock XLA program writes in
forward, re-reads for the pool, and walks twice more in backward
(``select_and_scatter`` + the BN/ReLU backward chain) — ~3 ms of a ~47 ms
step (PERF.md).

This module folds the whole post-conv stem into one custom-VJP region

    y = maxpool_3x3s2p1(relu(gamma_t * z + beta_t))

where ``gamma_t = scale * rsqrt(var + eps)`` and ``beta_t = bias -
mean * gamma_t`` are the BN affine with statistics pre-folded (batch stats
in train mode, running stats in eval). Because ReLU and the affine are
monotone per-channel maps, pooling commutes with them and the forward is a
single fusion ``z -> y``: the 112x112 ReLU plane is **never materialized**.

Backward exploits three identities:

* the pool's pre-ReLU window max ``best`` recomputed from ``z`` gives both
  the ReLU mask (``y > 0  <=>  best > 0``) and the winner;
* the winner of ``relu(affine(z))`` under first-max (select_and_scatter's
  GE tie-break) equals the winner of ``affine(z)`` whenever the window
  emits gradient (max > 0), so a 9-way first-strict-max scan yields the
  routing index ``widx``;
* each input position belongs to at most 4 windows with *statically known*
  offsets per (row, col) parity, so routing is a gather, not a scatter:
  ``dz[2u+a, 2v+b] = sum of g~ * [widx == offset]`` over <= 4 taps.

``d(gamma_t) = sum(g~ * z_win)`` and ``d(beta_t) = sum(g~)`` ride the small
56x56 grid (``z_win`` is tracked during the scan), so backward never
re-reads the input plane beyond the one scan pass.

Two implementations with identical semantics (parity-tested against
``nn.max_pool``'s select_and_scatter in tests/test_fused_stem.py):

* ``_*_xla``: pure lax ops — runs anywhere, used off the TPU and as
  the reference.
* ``_*_pallas``: TPU Pallas kernels gridded over the batch, one VMEM-
  resident image per program — XLA's fusion emitter handles the 9 strided
  window views poorly (measured +4.7 ms), Mosaic does not.

The op picks by backend: on the TPU it runs the Pallas kernels or raises
(no quiet XLA path under the fused name), elsewhere it runs the lax
reference. ``chip_smoke.py`` compiles both kernels at the ResNet-50 stem
shape on the chip and checks them against the reference. Whether the
resnet stem uses this op at all is **opt-in**: ``DPTPU_FUSED_STEM=1``
(handled in ``dptpu.train.fit``) or ``create_model(..., fused_stem=True)``
— it was measured slower than XLA's own stem lowering before PR 1 and has
not been re-measured (PERF.md), so the default stem remains the unfused
one.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# shared XLA forward (the off-TPU path and the reference)
# ---------------------------------------------------------------------------

def _fwd_xla(z, gamma_t, beta_t):
    # affine + pool in f32 (the Pallas kernels compute in f32 for Mosaic's
    # bf16 sublane-granularity rules; keeping the XLA path identical makes
    # winner selection — and therefore backward routing — bit-identical
    # across implementations), output cast back to the compute dtype
    a = gamma_t.astype(jnp.float32) * z.astype(jnp.float32) \
        + beta_t.astype(jnp.float32)
    pooled = lax.reduce_window(
        a, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)),
    )
    return jnp.maximum(pooled, 0.0).astype(z.dtype)


def _bwd_xla(z, gamma_t, beta_t, g):
    """Reference backward (pure lax). Returns (dz, dgamma_t, dbeta_t)."""
    b, h, w, c = z.shape
    oh, ow = g.shape[1], g.shape[2]
    dt = z.dtype

    a = gamma_t.astype(jnp.float32) * z.astype(jnp.float32) \
        + beta_t.astype(jnp.float32)
    ap = lax.pad(a, jnp.float32(-jnp.inf),
                 ((0, 0, 0), (1, 1, 0), (1, 1, 0), (0, 0, 0)))
    zp = lax.pad(z.astype(jnp.float32), jnp.float32(0),
                 ((0, 0, 0), (1, 1, 0), (1, 1, 0), (0, 0, 0)))
    best = widx = zwin = None
    for r in range(3):
        for s in range(3):
            k = 3 * r + s
            lim = (b, r + 2 * oh - 1, s + 2 * ow - 1, c)
            ars = lax.slice(ap, (0, r, s, 0), lim, (1, 2, 2, 1))
            zrs = lax.slice(zp, (0, r, s, 0), lim, (1, 2, 2, 1))
            if best is None:
                best, widx, zwin = ars, jnp.zeros(ars.shape, jnp.uint8), zrs
            else:
                gt = ars > best  # strict: the earlier offset keeps ties
                best = jnp.maximum(ars, best)
                widx = jnp.where(gt, jnp.uint8(k), widx)
                zwin = jnp.where(gt, zrs, zwin)

    # relu mask from the recomputed pre-ReLU max (== y > 0), f32 like the
    # Pallas kernel so multi-window sums round identically
    gm = jnp.where(best > 0, g.astype(jnp.float32), 0.0)
    dgamma_t = (gm * zwin).sum(axis=(0, 1, 2))
    dbeta_t = gm.sum(axis=(0, 1, 2))

    gp = lax.pad(gm, jnp.float32(0), ((0, 0, 0), (0, 1, 0), (0, 1, 0), (0, 0, 0)))
    wp = lax.pad(widx, jnp.uint8(255), ((0, 0, 0), (0, 1, 0), (0, 1, 0), (0, 0, 0)))

    def tap(di, dj, r, s):
        gs = lax.slice(gp, (0, di, dj, 0), (b, di + oh, dj + ow, c))
        ws = lax.slice(wp, (0, di, dj, 0), (b, di + oh, dj + ow, c))
        return jnp.where(ws == np.uint8(3 * r + s), gs, jnp.float32(0))

    dx00 = tap(0, 0, 1, 1)
    dx01 = tap(0, 0, 1, 2) + tap(0, 1, 1, 0)
    dx10 = tap(0, 0, 2, 1) + tap(1, 0, 0, 1)
    dx11 = tap(0, 0, 2, 2) + tap(0, 1, 2, 0) + tap(1, 0, 0, 2) + tap(1, 1, 0, 0)
    inner0 = jnp.stack([dx00, dx01], axis=3)
    inner1 = jnp.stack([dx10, dx11], axis=3)
    dy = jnp.stack([inner0, inner1], axis=2).reshape(b, 2 * oh, 2 * ow, c)
    dz = (gamma_t.astype(jnp.float32) * dy).astype(dt)
    return dz, dgamma_t.astype(gamma_t.dtype), dbeta_t.astype(beta_t.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernels (one batch image per grid step, image VMEM-resident)
# ---------------------------------------------------------------------------

def _window_view(ext, r, s, row0, nrows, oh, c):
    """Window-offset (r, s) rows [row0, row0+nrows) of an extended
    [2*rh, 2*(oh+1), c] plane as [nrows, oh, c], via parity reshapes +
    unit-stride slices (Mosaic has no stride-2 vector slices).

    Window row w covers ext rows [2w, 2w+3); offset r contributes ext row
    2w + r, which in the (rh, 2)-split is (w + r // 2, r % 2)."""
    rh = ext.shape[0] // 2
    oh1 = oh + 1
    x = ext.reshape(rh, 2, 2 * oh1, c)
    x = lax.slice(x, (row0 + r // 2, r % 2, 0, 0),
                  (row0 + r // 2 + nrows, r % 2 + 1, 2 * oh1, c))
    x = x.reshape(nrows, 2 * oh1, c)
    x = x.reshape(nrows, oh1, 2, c)
    x = lax.slice(x, (0, s // 2, s % 2, 0),
                  (nrows, s // 2 + oh, s % 2 + 1, c)).reshape(nrows, oh, c)
    return x


def _row_chunk(oh):
    """Output-row chunk size: bounds Mosaic's VMEM stack (live vector temps
    scale with the chunk) while keeping the static loop short."""
    return 8 if oh % 8 == 0 else oh


def _fwd_kernel(z_ref, gam_ref, bet_ref, y_ref, aext):
    # compute in f32: Mosaic's bf16 vectors need 16-multiple sublane dims,
    # which the 56/57-sized window views violate; f32 also upgrades the
    # affine's precision for free (one rounding at the output)
    h = z_ref.shape[1]
    oh = y_ref.shape[1]
    c = z_ref.shape[3]
    a = gam_ref[:] * z_ref[0].astype(jnp.float32) + bet_ref[:]
    aext[:] = jnp.full(aext.shape, -jnp.inf, jnp.float32)
    aext[1:h + 1, 1:h + 1, :] = a
    ext = aext[:]
    ch = _row_chunk(oh)
    for t in range(oh // ch):
        best = None
        for r in range(3):
            for s in range(3):
                ars = _window_view(ext, r, s, t * ch, ch, oh, c)
                best = ars if best is None else jnp.maximum(best, ars)
        y_ref[0, t * ch:(t + 1) * ch, :, :] = (
            jnp.maximum(best, 0.0).astype(y_ref.dtype)
        )


def _bwd_kernel(z_ref, g_ref, gam_ref, bet_ref,
                dz_ref, dgam_ref, dbet_ref,
                aext, zext, gscr, wscr):
    h = z_ref.shape[1]
    oh = g_ref.shape[1]
    c = z_ref.shape[3]

    @pl.when(pl.program_id(0) == 0)
    def _():
        dgam_ref[:] = jnp.zeros_like(dgam_ref)
        dbet_ref[:] = jnp.zeros_like(dbet_ref)

    z = z_ref[0].astype(jnp.float32)
    a = gam_ref[:] * z + bet_ref[:]
    # rows run to 2*(oh+2) so the phantom window row w == oh (needed by the
    # +1-row taps) reads -inf and contributes nothing
    aext[:] = jnp.full(aext.shape, -jnp.inf, jnp.float32)
    aext[1:h + 1, 1:h + 1, :] = a
    # zext borders are never selected (their affine is -inf): interior only
    zext[1:h + 1, 1:h + 1, :] = z
    aext_v, zext_v = aext[:], zext[:]

    ch = _row_chunk(oh)
    gam = gam_ref[:]
    for t in range(oh // ch):
        w0 = t * ch
        nw = ch + 1           # one extra window row for the di == 1 taps
        nreal = min(nw, oh - w0)

        best = widx = zwin = None
        for r in range(3):
            for s in range(3):
                k = 3 * r + s
                ars = _window_view(aext_v, r, s, w0, nw, oh, c)
                zrs = _window_view(zext_v, r, s, w0, nw, oh, c)
                if best is None:
                    best, zwin = ars, zrs
                    widx = jnp.zeros(ars.shape, jnp.int32)
                else:
                    gt = ars > best
                    best = jnp.maximum(ars, best)
                    widx = jnp.where(gt, jnp.int32(k), widx)
                    zwin = jnp.where(gt, zrs, zwin)

        gscr[:] = jnp.zeros(gscr.shape, jnp.float32)
        gscr[:nreal, :oh, :] = g_ref[0, w0:w0 + nreal, :, :].astype(jnp.float32)
        graw = gscr[:nw, :oh, :]
        gm = jnp.where(best > 0, graw, 0.0)
        # affine grads sum over THIS chunk's ch owned window rows only —
        # the +1 overlap row (needed by the di == 1 taps below) belongs to
        # the next chunk, which sums it itself
        dgam_ref[:] = dgam_ref[:] + (gm[:ch] * zwin[:ch]).sum(axis=(0, 1))
        dbet_ref[:] = dbet_ref[:] + gm[:ch].sum(axis=(0, 1))

        # re-store the masked gradient + winner index with a zero/255 apron
        # so the four parity taps can read one row/col beyond the chunk
        gscr[:] = jnp.zeros(gscr.shape, jnp.float32)
        gscr[:nw, :oh, :] = gm
        wscr[:] = jnp.full(wscr.shape, 255, jnp.int32)
        wscr[:nw, :oh, :] = widx
        gscr_v, wscr_v = gscr[:], wscr[:]

        def tap(di, dj, r, s):
            gs = lax.slice(gscr_v, (di, dj, 0), (di + ch, dj + oh, c))
            ws = lax.slice(wscr_v, (di, dj, 0), (di + ch, dj + oh, c))
            return jnp.where(ws == 3 * r + s, gs, 0.0)

        dx00 = tap(0, 0, 1, 1)
        dx01 = tap(0, 0, 1, 2) + tap(0, 1, 1, 0)
        dx10 = tap(0, 0, 2, 1) + tap(1, 0, 0, 1)
        dx11 = (tap(0, 0, 2, 2) + tap(0, 1, 2, 0)
                + tap(1, 0, 0, 2) + tap(1, 1, 0, 0))
        inner0 = jnp.stack([dx00, dx01], axis=2)
        inner1 = jnp.stack([dx10, dx11], axis=2)
        dy = jnp.stack([inner0, inner1], axis=1).reshape(2 * ch, 2 * oh, c)
        dz_ref[0, 2 * w0:2 * (w0 + ch), :, :] = (gam * dy).astype(dz_ref.dtype)


def _pallas_ok(z):
    b, h, w, c = z.shape
    # even square spatial dims; channel dim a clean lane multiple (the
    # resnet stem's 64) — Mosaic mishandles sub-8 lane dims
    return h == w and h % 2 == 0 and h >= 4 and c % 64 == 0


def _fwd_pallas(z, gamma_t, beta_t, interpret=False):
    b, h, w, c = z.shape
    oh = h // 2
    return pl.pallas_call(
        _fwd_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, w, c), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, oh, oh, c), lambda i: (i, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, oh, oh, c), z.dtype),
        scratch_shapes=[pltpu.VMEM((h + 2, h + 2, c), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(z, gamma_t.astype(jnp.float32), beta_t.astype(jnp.float32))


def _bwd_pallas(z, gamma_t, beta_t, g, interpret=False):
    b, h, w, c = z.shape
    oh = h // 2
    dz, dgam, dbet = pl.pallas_call(
        _bwd_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, w, c), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, oh, oh, c), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, h, w, c), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, w, c), z.dtype),
            jax.ShapeDtypeStruct((c,), jnp.float32),
            jax.ShapeDtypeStruct((c,), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((h + 4, h + 2, c), jnp.float32),
            pltpu.VMEM((h + 4, h + 2, c), jnp.float32),
            pltpu.VMEM((_row_chunk(oh) + 8, oh + 8, c), jnp.float32),
            pltpu.VMEM((_row_chunk(oh) + 8, oh + 8, c), jnp.int32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(z, g, gamma_t.astype(jnp.float32), beta_t.astype(jnp.float32))
    return dz, dgam.astype(gamma_t.dtype), dbet.astype(beta_t.dtype)


# ---------------------------------------------------------------------------
# public custom-VJP op
# ---------------------------------------------------------------------------

def _use_pallas(z):
    """Pallas on the TPU, the lax reference elsewhere. On the TPU there
    is no second path: a shape the kernels cannot take raises instead of
    quietly running the XLA composition under the fused-stem name."""
    if jax.default_backend() != "tpu":
        return False
    if not _pallas_ok(z):
        raise ValueError(
            f"fused stem: the Pallas kernels need even square spatial "
            f"dims >= 4 and a channel count divisible by 64, got "
            f"{z.shape} — unset DPTPU_FUSED_STEM (or fused_stem=False) "
            f"for this input size"
        )
    return True


@partial(jax.custom_vjp)
def _affine_relu_pool_even(z, gamma_t, beta_t):
    if _use_pallas(z):
        return _fwd_pallas(z, gamma_t, beta_t)
    return _fwd_xla(z, gamma_t, beta_t)


def _arp_fwd(z, gamma_t, beta_t):
    y = _affine_relu_pool_even(z, gamma_t, beta_t)
    # y is NOT a residual: backward recomputes the window max (which also
    # yields the relu mask), so the pooled activation can die after use
    return y, (z, gamma_t, beta_t)


def _arp_bwd(res, g):
    z, gamma_t, beta_t = res
    if _use_pallas(z):
        return _bwd_pallas(z, gamma_t, beta_t, g)
    return _bwd_xla(z, gamma_t, beta_t, g)


_affine_relu_pool_even.defvjp(_arp_fwd, _arp_bwd)


def affine_relu_pool(z, gamma_t, beta_t):
    """maxpool_3x3s2p1(relu(gamma_t * z + beta_t)) with a fused backward.

    ``z``: NHWC; ``gamma_t``/``beta_t``: per-channel affine. On the TPU
    this runs the Pallas kernels or raises (``_use_pallas``). Elsewhere
    even spatial dims run the custom-VJP region on the lax reference
    (identical semantics) and odd dims take the plain composition, whose
    backward is XLA's own select_and_scatter: the fused backward's
    parity interleave only reconstructs 2*oh x 2*ow planes.
    """
    on_tpu = _use_pallas(z)  # raises there for a shape the kernels refuse
    if not on_tpu and (z.shape[1] % 2 or z.shape[2] % 2):
        a = gamma_t.astype(jnp.float32) * z.astype(jnp.float32) \
            + beta_t.astype(jnp.float32)
        pooled = lax.reduce_window(
            a, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            ((0, 0), (1, 1), (1, 1), (0, 0)),
        )
        return jnp.maximum(pooled, 0.0).astype(z.dtype)
    return _affine_relu_pool_even(z, gamma_t, beta_t)
