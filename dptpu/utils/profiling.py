"""Device-time profiling: per-op durations from the hardware's own trace.

A host clock around dispatched work measures the enqueue plus whatever
the host was doing; the XLA device trace gives each op's duration on the
device, and those sum to the device's share of the step. dptpu's
attribution tables (PERF.md) and bench.py's plausibility cross-check are
built on it.

``profile_device_time(fn, *args)`` runs ``fn`` a few times under
``jax.profiler.trace``, parses the perfetto export, and returns per-op
device milliseconds. This is the tool behind PERF.md's attribution
tables and the recommended first step for any "why is my step slow"
investigation — before believing any wall-clock number.

The reference's observability story is wall-clock meters plus explicit
``torch.cuda.synchronize()`` before reads (imagenet_ddp_apex.py:406,
SURVEY.md §5); meters remain the console surface here
(dptpu/utils/meters.py), this module is the layer beneath them.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import tempfile
from typing import Callable, Dict, Tuple


def device_profile_options():
    """``jax.profiler.ProfileOptions`` for every profiler session the
    program opens: host and Python tracers off, device planes only.

    jax's default (host tracer level 2) records the runtime's own threads:
    on a live ResNet-50 run about 1.8 M host events a second, a stall of
    dispatch of 0.8-1.35 s at ``start_trace`` and a ``stop_trace`` of
    150-206 s for two seconds traced (PERF.md section 6, chip runs of PR
    26). Nothing here reads a host event: the device's operations are on
    the device planes, and the host half of every report comes from the
    program's own spans (``dptpu/obs``)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 0
    options.python_tracer_level = 0
    return options


def parse_perfetto_trace(trace: dict, iters: int = 1) -> Tuple[float, Dict[str, float]]:
    """Sum device-side op durations from a loaded perfetto trace.

    Returns ``(total_ms_per_iter, {op_name: ms_per_iter})``. Host-side
    tracks are excluded; the per-core duplicate tracks TPU traces carry
    are collapsed by taking the maximum-duration track per op name.

    A trace with NO device-side events raises ``RuntimeError`` instead
    of silently reporting ``(0, {})`` — a zero would read as "the device
    did no work" when the real cause is almost always that no device
    tracks matched: a host-only trace (backend whose PJRT plugin exports
    no device timeline: the CPU backend is one, its operations run on
    host threads and every session this program opens has the host
    tracer off, ``device_profile_options``), a traced region that
    dispatched nothing, or a track-naming scheme this parser doesn't
    know.
    """
    events = trace.get("traceEvents", [])
    pid_names = {
        e["pid"]: e.get("args", {}).get("name", "") for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    dev_pids = {
        p for p, n in pid_names.items()
        if ("TPU" in n or "/device" in n or "Device" in n) and "Host" not in n
    }

    def _collect(want):
        tracks: dict = collections.defaultdict(lambda: collections.Counter())
        for e in events:
            if e.get("ph") == "X" and want(e):
                tracks[(e["pid"], e.get("tid"))][e.get("name", "")] += (
                    e.get("dur", 0) / 1000.0
                )
        return tracks

    per_track = _collect(lambda e: e.get("pid") in dev_pids)
    if not per_track:
        tracks = sorted(set(pid_names.values())) or ["<no process_name metadata>"]
        raise RuntimeError(
            "no device tracks matched in this trace — likely a host-only "
            "trace (the backend exports no device timeline) or a traced "
            "region that dispatched no device work. Process tracks seen: " + ", ".join(
                repr(t) for t in tracks[:8]
            )
        )
    by_op: collections.Counter = collections.Counter()
    for track in per_track.values():
        for name, ms in track.items():
            by_op[name] = max(by_op[name], ms)
    per_iter = {k: v / iters for k, v in by_op.items()}
    # XLA module-level spans (named "jit_<fn>(...)") CONTAIN the op events:
    # they are the authoritative totals (one per jitted module — summed, in
    # case the profiled fn dispatches several distinct modules), and they
    # are filtered out of the per-op table so op shares don't double-count
    # against it. NOTE the max-collapse above makes multi-replica semantics
    # "the slowest replica's time" per op: SPMD workers run the same
    # program, so the max is the critical-path one.
    modules = {k: v for k, v in per_iter.items() if k.startswith("jit_")}
    ops = {k: v for k, v in per_iter.items() if k not in modules}
    if modules:
        return sum(modules.values()), ops
    return sum(ops.values()), ops


def load_trace_dir(path: str) -> dict:
    """Load + merge every perfetto export under ``path`` into one trace.

    One ``*.trace.json.gz`` per host on multi-process runs. Perfetto
    pids are only unique within a file, so namespace them per source
    file before merging — otherwise host tracks from one file can
    masquerade as device tracks of another. The parser's max-collapse
    then yields the slowest replica's per-op time (the SPMD critical
    path). Raises ``RuntimeError`` when no trace file exists under
    ``path``.
    """
    paths = sorted(
        glob.glob(os.path.join(path, "**", "*.trace.json.gz"),
                  recursive=True)
    )
    if not paths:
        raise RuntimeError(f"no trace written under {path}")
    merged = {"traceEvents": []}
    for i, p in enumerate(paths):
        with gzip.open(p, "rt") as f:
            for e in json.load(f).get("traceEvents", []):
                if "pid" in e:
                    e = dict(e, pid=(i, e["pid"]))
                merged["traceEvents"].append(e)
    return merged


def profile_device_time(fn: Callable, *args, iters: int = 6,
                        fence: Callable = None):
    """Trace ``iters`` calls of ``fn(*args)`` and return per-op device time.

    ``fn`` should be a compiled callable whose outputs carry at least one
    array; ``fence`` (default: fetch the first output leaf to the
    host) forces completion before the trace closes.
    """
    import jax

    def default_fence(out):
        leaf = jax.tree_util.tree_leaves(out)[0]
        float(leaf.ravel()[0])

    fence = fence or default_fence
    out = fn(*args)
    fence(out)  # warm / compile outside the trace
    tmp = tempfile.mkdtemp(prefix="dptpu_prof_")
    try:
        with jax.profiler.trace(
                tmp, profiler_options=device_profile_options()):
            for _ in range(iters):
                out = fn(*args)
            fence(out)
        return parse_perfetto_trace(load_trace_dir(tmp), iters=iters)
    finally:
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)
