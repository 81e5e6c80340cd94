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


def parse_perfetto_trace(trace: dict, iters: int = 1) -> Tuple[float, Dict[str, float]]:
    """Sum device-side op durations from a loaded perfetto trace.

    Returns ``(total_ms_per_iter, {op_name: ms_per_iter})``. Host-side
    tracks are excluded; the per-core duplicate tracks TPU traces carry
    are collapsed by taking the maximum-duration track per op name.

    A trace with NO device-side events raises ``RuntimeError`` instead
    of silently reporting ``(0, {})`` — a zero would read as "the device
    did no work" when the real cause is almost always that no device
    tracks matched: a host-only trace (backend whose PJRT plugin exports
    no device timeline), a traced region that dispatched nothing, or a
    track-naming scheme this parser doesn't know.

    CPU-PJRT fallback: the CPU backend has no ``/device:*`` track — its
    XLA ops execute on the ``tf_XLAEigen`` threadpool of the
    ``/host:CPU`` process track, interleaved with Python tracemes and
    compiler passes on OTHER threads of the same pid. When (and only
    when) no real device track matched, op events from those Eigen
    threads are used instead, so CPU-only runs still get a per-op table
    (approximate: thread-parallel op time max-collapses to the busiest
    thread, like the multi-replica rule).
    """
    events = trace.get("traceEvents", [])
    pid_names, thread_names = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            pid_names[e["pid"]] = e.get("args", {}).get("name", "")
        elif e.get("name") == "thread_name":
            thread_names[(e.get("pid"), e.get("tid"))] = (
                e.get("args", {}).get("name", "")
            )
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
        xla_cpu = {
            (p, t) for (p, t), n in thread_names.items()
            if str(pid_names.get(p, "")).startswith("/host:")
            and str(n).startswith("tf_XLAEigen")
        }
        per_track = _collect(
            lambda e: (e.get("pid"), e.get("tid")) in xla_cpu
        )
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
        with jax.profiler.trace(tmp):
            for _ in range(iters):
                out = fn(*args)
            fence(out)
        return parse_perfetto_trace(load_trace_dir(tmp), iters=iters)
    finally:
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)
