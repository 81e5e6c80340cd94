"""Persistent XLA compile cache, placed from outside or at a fixed path.

A ResNet-50 train step takes tens of seconds to compile and every chip
machine starts cold, so each entry point (``fit()``, ``dptpu serve`` /
``quantize`` / ``tune``, ``bench.py``, ``chip_smoke.py``,
``__graft_entry__``) calls ``enable_compile_cache()`` once before its
first compile. The cache directory is part of nothing's key but must not
move between runs, so it is never derived from ``tempfile``, a pid or a
timestamp:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
  this module sets nothing;
* where it is not, the cache lives in ``<checkout>/.jax_cache`` — the
  directory that holds the ``dptpu`` package (git-ignored).
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``, from the package location alone."""
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(checkout, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in
    use. Idempotent; never called at import time."""
    placed = os.environ.get(_ENV)
    if placed:
        return placed
    import jax

    path = default_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
