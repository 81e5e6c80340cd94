"""Request preprocessing: image bytes -> model-ready uint8 HWC tensor.

ONE implementation of "what pixels does a request become", shared by the
serving engine, ``scripts/check_tv_parity.py`` and any offline caller:
the pixel-exact validation stack (``ValTransform`` — Resize(256) →
CenterCrop(224) as one fractional-box resample, dptpu/data/transforms.py)
applied to a PIL RGB decode of the bytes.

Bit-identity contract (locked by tests/test_serve.py): for a given image
file, ``preprocess_bytes(open(f,'rb').read())`` equals the row the
training/eval pipeline produces for that file —
``ImageFolderDataset(transform=ValTransform()).get(i)`` — byte for byte.
That holds because this IS the same code path: ``ValTransform`` sets
``native_ok = False``, so the val pipeline always decodes via PIL
(reproducing torchvision's published-accuracy pixels; the native fast
path's scaled decode + 2-tap lerp is augmentation-grade — see the
ValTransform docstring), and so does this function. A model served here
sees exactly the pixels its reported validation accuracy was measured
on.

Output stays uint8 HWC: like the training feed, normalization happens
on device inside the compiled forward (``normalize_images``) — x4 less
staging-buffer traffic and one fewer host-side float pass per request.
"""

from __future__ import annotations

import io
import sys
from typing import Optional

import numpy as np

from dptpu.data.transforms import ValTransform

# fused native serve-ingest (dptpu_serve_ingest in image_ops.cpp): JPEG
# bytes -> val pixels straight into the staging row, one native call, no
# PIL round trip. It is only ever used after PROVING bit-identity against
# the PIL path on this host's libjpeg (tri-state: None = not yet probed).
_NATIVE_INGEST_OK: Optional[bool] = None

_JPEG_MAGIC = b"\xff\xd8\xff"


def _pil_val_pixels(data: bytes, size: int, resize: int) -> np.ndarray:
    """The reference PIL path, non-recursively (what the probe compares
    the native kernel against)."""
    from PIL import Image

    tf = ValTransform(size, resize)
    with Image.open(io.BytesIO(data)) as img:
        return tf(img.convert("RGB"))


def _probe_native_ingest() -> bool:
    """Prove ``dptpu_serve_ingest`` bit-identical to the PIL path on THIS
    host before it may serve a single request. The probe JPEGs cover the
    geometries that exercise every branch of the resample (odd dims,
    portrait/landscape, grayscale->RGB replication, box-enlarge,
    progressive scan); any mismatching byte disables the kernel for the
    process, LOUDLY — served pixels silently diverging from the pixels
    accuracy was measured on is the one failure this path must not have.
    """
    from dptpu.native.build import load_library

    lib = load_library()
    if lib is None or not hasattr(lib, "dptpu_serve_ingest"):
        return False
    from PIL import Image

    rng = np.random.RandomState(0)
    cases = []
    for (w, h, mode, kw) in [
        (277, 179, "RGB", {"quality": 85}),
        (160, 240, "RGB", {"quality": 92}),
        (200, 200, "L", {"quality": 85}),
        (96, 80, "RGB", {"quality": 90}),   # resize=256 ENLARGES this one
        (230, 310, "RGB", {"quality": 85, "progressive": True}),
    ]:
        shape = (h, w, 3) if mode == "RGB" else (h, w)
        buf = io.BytesIO()
        Image.fromarray(rng.randint(0, 256, shape, np.uint8), mode).save(
            buf, "JPEG", **kw
        )
        cases.append(buf.getvalue())
    for size, resize in ((224, 256), (64, 73)):
        for data in cases:
            native = np.empty((size, size, 3), np.uint8)
            rc = lib.dptpu_serve_ingest(data, len(data), size, resize,
                                        native.ctypes.data)
            if rc != 0 or not np.array_equal(
                native, _pil_val_pixels(data, size, resize)
            ):
                print(
                    "=> dptpu serve-ingest native kernel FAILED the "
                    f"bit-identity probe (rc={rc}, size={size}) — this "
                    "host's libjpeg does not reproduce PIL's pixels; "
                    "serving stays on the PIL path (slower, identical "
                    "output)", file=sys.stderr, flush=True,
                )
                return False
    return True


def _native_ingest(data: bytes, size: int, resize: int,
                   out: Optional[np.ndarray]) -> Optional[np.ndarray]:
    """The fused path, or None when the caller must use PIL (probe
    failed, non-JPEG bytes, or a per-image bail like CMYK color)."""
    global _NATIVE_INGEST_OK
    if not data.startswith(_JPEG_MAGIC):
        return None
    if _NATIVE_INGEST_OK is None:
        _NATIVE_INGEST_OK = _probe_native_ingest()
    if not _NATIVE_INGEST_OK:
        return None
    from dptpu.native.build import load_library

    lib = load_library()
    if out is not None and (out.shape != (size, size, 3)
                            or out.dtype != np.uint8):
        raise ValueError(
            f"preprocess out buffer is {out.dtype}{out.shape}, "
            f"expected uint8{(size, size, 3)}"
        )
    dst = out if (out is not None and out.flags.c_contiguous) else \
        np.empty((size, size, 3), np.uint8)
    rc = lib.dptpu_serve_ingest(data, len(data), size, resize,
                                dst.ctypes.data)
    if rc != 0:
        return None  # corrupt/CMYK/etc: PIL decides (and 400s cleanly)
    if out is not None and dst is not out:
        np.copyto(out, dst)
        return out
    return dst


def val_resize_for(size: int) -> int:
    """The val pipeline's resize edge for a crop of ``size``: the
    reference 256-resize-then-224-crop ratio, scaled (dptpu/data/feed.py
    builds the val dataset with exactly this formula — 256 at the
    standard 224).
    Serving MUST use the same formula or a non-224 engine would crop a
    different fraction of the image than the accuracy was measured on."""
    return int(size * 256 / 224)


def preprocess_bytes(data: bytes, size: int = 224,
                     resize: Optional[int] = None,
                     out: Optional[np.ndarray] = None,
                     _transform: Optional[ValTransform] = None
                     ) -> np.ndarray:
    """Decode + val-transform one request's image bytes.

    ``resize`` defaults to ``val_resize_for(size)`` — the val
    pipeline's own edge, at EVERY size, not just 224.

    ``out`` (uint8 ``(size, size, 3)``) lets the batcher write the pixels
    straight into a staging-ring row — the request-side analog of the
    loader's decode-into-slot path; anything else allocates. JPEG, PNG
    and every other PIL-decodable container are accepted (requests are
    not guaranteed to be JPEG); undecodable bytes raise ``ValueError``
    naming the cause, so a bad request 400s instead of crashing a batch.

    ``_transform`` lets a hot caller reuse one ``ValTransform`` (it is
    stateless; the default constructs per call for the one-shot case).

    JPEG requests take the fused native serve-ingest kernel
    (``dptpu_serve_ingest``) when — and only when — it has PROVED
    bit-identity with the PIL path on this host (probe at first use,
    loud stderr fallback): one native call decodes and box-resamples
    straight into ``out``, so the identical pixels arrive without the
    PIL round trip or any intermediate fp32 buffer. Every other
    container, and every native bail (CMYK, corrupt bytes), lands on
    the PIL path below — same pixels either way, that is the contract.
    """
    from PIL import Image, UnidentifiedImageError

    if resize is None:
        resize = val_resize_for(size)
    fast = _native_ingest(data, size, resize, out)
    if fast is not None:
        return fast
    tf = _transform if _transform is not None else ValTransform(size, resize)
    try:
        with Image.open(io.BytesIO(data)) as img:
            arr = tf(img.convert("RGB"))
    except (UnidentifiedImageError, OSError) as e:
        raise ValueError(f"undecodable image bytes: {e}") from None
    if out is not None:
        if out.shape != arr.shape or out.dtype != np.uint8:
            raise ValueError(
                f"preprocess out buffer is {out.dtype}{out.shape}, "
                f"expected uint8{arr.shape}"
            )
        np.copyto(out, arr)
        return out
    return arr


def preprocess_array(img: np.ndarray, size: int = 224,
                     resize: Optional[int] = None,
                     out: Optional[np.ndarray] = None) -> np.ndarray:
    """Same val stack over an already-decoded uint8 HWC array (the
    bench's synthetic-request path — no container round trip)."""
    from PIL import Image

    tf = ValTransform(size, resize if resize is not None
                      else val_resize_for(size))
    arr = tf(Image.fromarray(img))
    if out is not None:
        np.copyto(out, arr)
        return out
    return arr
