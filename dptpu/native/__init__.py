"""Native (C++) runtime components.

The reference's capabilities rest on out-of-repo native code (torch
DataLoader C++ workers, PIL's C decoders — SURVEY.md §2b); dptpu carries its
native pieces in-tree. Currently: libjpeg-backed image ops
(``src/image_ops.cpp``) — header-only dims probe and a fused
decode+crop+resize+flip used by the data pipeline's hot path.

``load_library()`` compiles the shared object on first use (g++, cached
under ``_build/`` keyed by a hash of the source and the compiler command)
and returns the ctypes handle, or None — after one stderr line naming the
compiler error — when the toolchain/libjpeg is unavailable; callers then
fall back to PIL.
"""

from dptpu.native.build import load_library

__all__ = ["load_library"]
