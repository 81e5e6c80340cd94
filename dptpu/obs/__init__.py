"""dptpu.obs — unified step-phase tracing, metrics registry, and
on-demand in-flight profiling.

One subsystem replaces the previously uncorrelated surfaces (console
meters, ``feed_stats`` threading, the ``writer.add_scalar`` ladder,
manual ``profile_device_time`` sessions):

* :class:`Tracer` — ``span("data_wait") / span("h2d") / span("step") /
  span("ckpt")`` context managers over a preallocated ring, drained to
  a per-host JSONL log + Chrome-trace JSON (opens in Perfetto next to
  XLA device traces);
* :class:`Registry` — one namespace of counters/gauges/histograms with
  sink fan-out (console / TensorBoard / JSONL);
* :class:`ProfileTrigger` — SIGUSR2 or a sentinel file arms
  ``jax.profiler.trace`` for the next N steps of a LIVE ``fit()`` and
  emits a merged host-span + device-op attribution table;
* :func:`attribute_epoch` — the per-epoch data-wait/h2d/device/ckpt/
  other breakdown with p50/p90/max step time and an anomalous-step log.

Module-level accessors (``get_tracer``/``get_registry``) let every
layer publish without threading handles through constructors; ``fit()``
configures real instances per run and ``reset()`` restores the inert
defaults afterward. The package root is stdlib-only (the data layer
imports it; spawned decode workers must never see JAX).

Env knobs (validated fail-fast by :func:`obs_knobs`, the locked knob
contract):

* ``DPTPU_OBS`` — enable tracing + the epoch attribution report
  (default on; overhead is gated < 2% by scripts/run_obsbench.py);
* ``DPTPU_OBS_RING`` — span ring capacity (default 65536, >= 64);
* ``DPTPU_OBS_DIR`` — directory for the JSONL span/metric log and the
  Chrome trace (unset = in-memory attribution only);
* ``DPTPU_OBS_TRACE_STEPS`` — steps per on-demand trace window
  (default 8, >= 1);
* ``DPTPU_OBS_TRIGGER`` — sentinel file path armed by ``touch`` (the
  non-signal trigger path, e.g. from a container exec);
* ``DPTPU_OBS_ANOMALY`` — anomalous-step threshold as a multiple of
  the p50 step time (default 3.0, > 1).
"""

from __future__ import annotations

import os

from dptpu.envknob import env_bool, env_float, env_int, env_str
from dptpu.obs.metrics import (
    ConsoleSink,
    Counter,
    Gauge,
    Histogram,
    JsonlSink,
    Registry,
    TensorBoardSink,
)
from dptpu.obs.report import (
    SPAN_CATEGORY,
    P2Quantile,
    attribute_epoch,
    attribute_spans,
    exclusive_durations,
    format_report,
    format_setup,
    merge_pod_timeline,
    setup_report,
)
from dptpu.obs.trace import (
    NullTracer,
    Tracer,
    TraceSink,
    spans_to_chrome_events,
)
from dptpu.obs.trigger import ProfileTrigger

__all__ = [
    "Tracer", "NullTracer", "TraceSink", "spans_to_chrome_events",
    "Registry", "Counter", "Gauge", "Histogram",
    "TensorBoardSink", "JsonlSink", "ConsoleSink",
    "ProfileTrigger",
    "attribute_epoch", "attribute_spans", "exclusive_durations",
    "format_report", "SPAN_CATEGORY", "P2Quantile", "merge_pod_timeline",
    "setup_report", "format_setup",
    "get_tracer", "set_tracer", "get_registry", "set_registry",
    "reset", "obs_knobs",
]

# ------------------------------------------------- module-level instances ----

_tracer = NullTracer()
_registry = Registry()


def get_tracer():
    """The process-wide tracer (a :class:`NullTracer` until ``fit()`` —
    or a test — installs a real one)."""
    return _tracer


def set_tracer(tracer):
    global _tracer
    _tracer = tracer
    return tracer


def get_registry() -> Registry:
    """The process-wide metrics registry (always usable; sinks are only
    attached by a configured run)."""
    return _registry


def set_registry(registry: Registry) -> Registry:
    global _registry
    _registry = registry
    return registry


def reset():
    """Restore the inert defaults (run teardown / test isolation)."""
    set_tracer(NullTracer())
    set_registry(Registry())


# ----------------------------------------------------------------- knobs ----


def obs_knobs(environ=None) -> dict:
    """Validated ``DPTPU_OBS_*`` env knobs (the locked fail-fast
    contract: unset means default, every explicit-but-invalid value
    raises with an actionable message)."""
    env = environ if environ is not None else os.environ
    enabled = env_bool("DPTPU_OBS", True, environ=env)
    ring = env_int("DPTPU_OBS_RING", 65536, environ=env)
    if ring < 64:
        raise ValueError(
            f"DPTPU_OBS_RING={ring} must be >= 64 spans (the ring holds "
            f"~6 spans/step; smaller rings drop the epoch's head)"
        )
    trace_steps = env_int("DPTPU_OBS_TRACE_STEPS", 8, environ=env)
    if trace_steps < 1:
        raise ValueError(
            f"DPTPU_OBS_TRACE_STEPS={trace_steps} must be >= 1 step "
            f"per on-demand trace window"
        )
    anomaly = env_float("DPTPU_OBS_ANOMALY", 3.0, environ=env)
    if anomaly <= 1.0:
        raise ValueError(
            f"DPTPU_OBS_ANOMALY={anomaly} must be > 1 (a multiple of "
            f"the p50 step time; e.g. DPTPU_OBS_ANOMALY=3)"
        )
    return {
        "enabled": enabled,
        "ring": ring,
        "dir": env_str("DPTPU_OBS_DIR", None, environ=env),
        "trace_steps": trace_steps,
        "trigger": env_str("DPTPU_OBS_TRIGGER", None, environ=env),
        "anomaly": anomaly,
    }
