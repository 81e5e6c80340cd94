"""Build + load the native image-ops shared library.

Compiled lazily with g++ (no pybind11 — plain C ABI via ctypes) into
``_build/libdptpu_image-<key>.so``, where ``<key>`` hashes the source
file and the compiler command — a binary built from other source or
other flags has another name and can never load. Thread-safe; a failed
build is cached (one attempt per process) and LOUD: one stderr line
names the compiler error, then the pipeline stays on PIL and fit's
``=> input pipeline:`` banner says ``native=False``.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
from typing import Optional

_SRC = os.path.join(os.path.dirname(__file__), "src", "image_ops.cpp")
_BUILD_DIR = os.path.join(os.path.dirname(__file__), "_build")
_CXX = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17"]
_LIBS = ["-ljpeg"]

_lock = threading.Lock()
_cached: Optional[ctypes.CDLL] = None
_attempted = False


def library_path() -> str:
    """``_build/libdptpu_image-<sha256(source + compiler command)>.so``."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_CXX + _LIBS).encode())
    return os.path.join(
        _BUILD_DIR, f"libdptpu_image-{h.hexdigest()[:16]}.so"
    )


def _compile() -> Optional[str]:
    """Path of the built library, or None after saying why on stderr."""
    lib = library_path()
    if os.path.exists(lib):
        return lib
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # pid-unique temp: loader worker PROCESSES may race to build; each
    # compiles to its own file and the replace is atomic
    tmp = f"{lib}.{os.getpid()}.tmp"
    cmd = _CXX + ["-o", tmp, _SRC] + _LIBS
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError) as e:
        stderr = (getattr(e, "stderr", None) or b"").decode(errors="replace")
        first = next((ln for ln in stderr.splitlines() if ln.strip()), "")
        print(
            f"dptpu.native: build failed, JPEG decode stays on PIL — "
            f"{first or repr(e)}",
            file=sys.stderr,
        )
        return None
    os.replace(tmp, lib)
    return lib


def load_library() -> Optional[ctypes.CDLL]:
    """Return the ctypes handle to the native lib, building if needed."""
    global _cached, _attempted
    with _lock:
        if _cached is not None or _attempted:
            return _cached
        _attempted = True
        path = _compile()
        if path is None:
            return None
        lib = ctypes.CDLL(path)
        lib.dptpu_jpeg_dims.restype = ctypes.c_int
        lib.dptpu_jpeg_dims.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ]
        lib.dptpu_jpeg_decode_crop_resize.restype = ctypes.c_int
        lib.dptpu_jpeg_decode_crop_resize.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            # fractional crop box (exact-val-pipeline boxes are floats)
            ctypes.c_double, ctypes.c_double, ctypes.c_double,
            ctypes.c_double,
            ctypes.c_int, ctypes.c_int, ctypes.c_void_p,
        ]
        # decode-cache entry points: full-res decode into a caller buffer
        # (cache fill) and crop-resize from a raw RGB buffer (cache hit)
        lib.dptpu_jpeg_decode_rgb.restype = ctypes.c_int
        lib.dptpu_jpeg_decode_rgb.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_int, ctypes.c_int, ctypes.c_void_p,
        ]
        lib.dptpu_crop_resize_rgb.restype = ctypes.c_int
        lib.dptpu_crop_resize_rgb.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
            ctypes.c_double, ctypes.c_double, ctypes.c_double,
            ctypes.c_double,
            ctypes.c_int, ctypes.c_int, ctypes.c_void_p,
        ]
        # fused serve-ingest: JPEG bytes -> val-pipeline pixels into a
        # staging row, BIT-identical to the PIL path (probe-verified at
        # first use by dptpu/serve/preprocess.py)
        lib.dptpu_serve_ingest.restype = ctypes.c_int
        lib.dptpu_serve_ingest.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_int, ctypes.c_int, ctypes.c_void_p,
        ]
        # cold-epoch byte readahead: posix_fadvise(WILLNEED) the JPEG
        # files of pre-issued spans (parent-side, GIL released)
        lib.dptpu_file_readahead.restype = ctypes.c_longlong
        lib.dptpu_file_readahead.argtypes = [ctypes.c_char_p]
        _cached = lib
        return _cached
