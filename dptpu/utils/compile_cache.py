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

The same call registers, once per process, the program's compile
listener: every trace, lowering and backend compile (a cache load counts)
that jax reports through ``jax.monitoring`` becomes a ``compile`` span of
the run's tracer (``dptpu/obs``), on the thread that compiled, with the
event's name, the function's name and, where jax says so, whether the
persistent cache served it. Outside a run the tracer is the inert
``NullTracer`` and the listener does nothing; jax offers no public way to
take a listener back, so none is tried.
"""

from __future__ import annotations

import os
import threading
import time

from dptpu import obs

_ENV = "JAX_COMPILATION_CACHE_DIR"
# the duration events chip_smoke.py's CompileMeter meters
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jaxpr_to_mlir",
    _BACKEND_COMPILE: "backend_compile",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": True,
    "/jax/compilation_cache/cache_misses": False,
}
_listening = False
# jax reports a cache hit or miss on the compiling thread just before
# that compile's duration: carried from one listener to the other here
_last_cache_event = threading.local()


def _on_cache_event(event, **_):
    hit = _CACHE_EVENTS.get(event)
    if hit is not None:
        _last_cache_event.hit = hit


def _on_duration(event, seconds, **kw):
    short = _COMPILE_EVENTS.get(event)
    if short is None:
        return
    hit = None
    if event == _BACKEND_COMPILE:
        # taken even outside a run: it belongs to this compile alone
        hit = getattr(_last_cache_event, "hit", None)
        _last_cache_event.hit = None
    tracer = obs.get_tracer()
    if not tracer.enabled:
        return
    attrs = {"event": short}
    if kw.get("fun_name"):
        attrs["fun"] = str(kw["fun_name"])
    if hit is not None:
        attrs["cache_hit"] = hit
    seconds = float(seconds)
    tracer.record("compile", time.perf_counter() - seconds, seconds,
                  attrs=attrs)


def install_compile_listener() -> bool:
    """Register the ``compile``-span listener; True the first time in a
    process, False (and nothing registered) ever after."""
    global _listening
    if _listening:
        return False
    _listening = True
    import jax.monitoring as monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_cache_event)
    return True


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``, from the package location alone."""
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(checkout, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in
    use. Idempotent; never called at import time."""
    install_compile_listener()
    placed = os.environ.get(_ENV)
    if placed:
        return placed
    import jax

    path = default_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
