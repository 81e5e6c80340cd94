"""Reduction of a profiler trace to device busy time, idle gaps, per-step
device time and exposed collective time — the benchmark's own, so that
every PR computes these numbers the same way.

A trace is held as plain data, whatever wrote it::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

``load_xplane`` fills it from the ``.xplane.pb`` that ``jax.profiler``
writes (read with ``jax.profiler.ProfileData``, nothing but jax); the
tests fill it from a small recorded JSON. Busy time is the UNION of the
intervals in which an operation ran on a device, never a sum of
durations: nested and overlapping events are counted once.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[int, int]

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OP_LINE = "XLA Ops"              # what the core executes, one at a time
ASYNC_LINE = "Async XLA Ops"     # start-to-done spans of asynchronous ops
MODULE_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")
ANCHOR = "bench_anchor"


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_xplane(path: str) -> dict:
    """The lines this module reads, and no others: a second of ResNet-50
    is a million events, each named by its whole HLO text. Operation names
    are shortened as they are read (``short_name``)."""
    from jax.profiler import ProfileData

    device_lines = (OP_LINE, ASYNC_LINE, MODULE_LINE)
    short: Dict[str, str] = {}
    planes = []
    for plane in ProfileData.from_file(path).planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if on_device and line.name not in device_lines:
                continue
            events = []
            for e in line.events:
                name = e.name
                if on_device and line.name != MODULE_LINE:
                    name = short.get(name) or short.setdefault(
                        name, short_name(name))
                elif not on_device and name != ANCHOR:
                    continue
                events.append([name, int(e.start_ns), int(e.duration_ns)])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ------------------------------------------------------------- intervals ----


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    merged: List[Interval] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def total(merged: List[Interval]) -> int:
    return sum(end - start for start, end in merged)


def clip(merged: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of merged ``a`` that merged ``b`` does not cover."""
    out: List[Interval] = []
    j = 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def gaps(merged: List[Interval], lo: int, hi: int) -> List[Interval]:
    return subtract([(lo, hi)], clip(merged, lo, hi))


# ---------------------------------------------------------- device planes ---


def device_planes(trace: dict) -> List[dict]:
    return sorted((p for p in trace["planes"]
                   if DEVICE_PLANE.match(p["name"])),
                  key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(2)))


def _line(plane: dict, name: str) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _spans(events) -> List[Interval]:
    return [(start, start + dur) for _, start, dur in events]


def step_module(plane: dict) -> Optional[str]:
    """The module (compiled program) that took most device time: in a
    training window that is the train step."""
    per: Dict[str, int] = {}
    for name, _, dur in _line(plane, MODULE_LINE):
        per[name] = per.get(name, 0) + dur
    return max(per, key=per.get) if per else None


def reduce_device(plane: dict, bounds: Optional[Interval] = None):
    """One device's numbers over the stretch of whole traced steps.

    The stretch runs from the start of the first to the end of the last
    complete execution of the step module that lies inside ``bounds``
    (the harness's two anchors, in the trace's clock; the whole trace
    without them), so the profiler's own start-up and tear-down stalls
    lie outside it. Returns None when the plane holds no operation.
    """
    ops = _line(plane, OP_LINE)
    if not ops:
        return None
    module = step_module(plane)
    runs = sorted((start, start + dur)
                  for name, start, dur in _line(plane, MODULE_LINE)
                  if name == module and (
                      bounds is None
                      or (start >= bounds[0] and start + dur <= bounds[1])))
    if runs:
        lo, hi, steps = runs[0][0], runs[-1][1], len(runs)
    elif bounds is not None:
        lo, hi, steps = bounds[0], bounds[1], 0
    else:  # no module line: the stretch is what the operations span
        lo = min(s for _, s, _ in ops)
        hi = max(s + d for _, s, d in ops)
        steps = 0
    # a collective shows either as an operation of the core or, when it is
    # asynchronous, as a start-to-done span beside the core's operations
    collective = [e for e in ops + _line(plane, ASYNC_LINE)
                  if COLLECTIVE.match(e[0])]
    compute = [e for e in ops if not COLLECTIVE.match(e[0])]
    busy = clip(union(_spans(ops)), lo, hi)
    exposed = subtract(clip(union(_spans(collective)), lo, hi),
                       clip(union(_spans(compute)), lo, hi))
    return {
        "name": plane["name"], "module": module, "steps": steps,
        "lo_ns": lo, "hi_ns": hi, "busy": busy,
        "busy_ns": total(busy), "window_ns": hi - lo,
        "collective_exposed_ns": total(exposed),
        "has_collectives": bool(collective),
    }


def anchor_starts(trace: dict) -> List[int]:
    """Trace timestamps (ns) of the harness's ``bench_anchor``
    annotations, from the host planes."""
    return sorted(
        start for plane in trace["planes"]
        if not DEVICE_PLANE.match(plane["name"])
        for line in plane["lines"] for name, start, _ in line["events"]
        if name == ANCHOR)


def reduce_trace(trace: dict, anchor_walls: List[float] = ()):
    """All devices. ``busy_s`` is averaged over the devices used,
    ``window_s`` is the traced stretch; per-step numbers are of the
    busiest device. ``anchor_walls`` are the ``time.time()`` stamps of the
    harness's anchors: with two anchors found in the trace the stretch
    lies between them, and their stamps align the trace's clock with the
    host's (``offset_s``)."""
    starts = anchor_starts(trace)
    bounds = (starts[0], starts[-1]) if len(starts) >= 2 else None
    devices = [d for d in (reduce_device(p, bounds)
                           for p in device_planes(trace)) if d]
    if bounds and any(d["steps"] < 2 for d in devices):
        # the profiler's stall fell between the anchors after all: read
        # every whole step the trace holds rather than none (the idle
        # share then holds the stall; the per-step numbers do not)
        bounds = None
        devices = [d for d in (reduce_device(p)
                               for p in device_planes(trace)) if d]
    if not devices:
        return None
    offset_s = None
    if starts and len(starts) == len(anchor_walls):
        offsets = sorted(w - s / 1e9
                         for w, s in zip(sorted(anchor_walls), starts))
        offset_s = offsets[len(offsets) // 2]
    busiest = max(devices, key=lambda d: d["busy_ns"])
    planes = {p["name"]: p for p in trace["planes"]}
    return {
        "devices": devices,
        "busiest": busiest,
        "busy_s": sum(d["busy_ns"] for d in devices) / len(devices) / 1e9,
        "window_s": max(d["window_ns"] for d in devices) / 1e9,
        "steps": busiest["steps"],
        "top_ops": top_ops(planes[busiest["name"]], busiest),
        "offset_s": offset_s,
        "stretch": "between the anchors" if bounds else "the whole trace",
    }


_SHAPE = re.compile(r"[a-z]+[0-9]*\[([0-9,]*)\]")


def short_name(op: str) -> str:
    """``%fusion.12 = bf16[128,56,56,256]{...} fusion(%a, ...)`` (the TPU
    trace names an operation by its whole HLO text) as
    ``fusion.12 bf16[128,56,56,256]``: the instruction and the largest
    array of its result."""
    head, sep, rest = op.partition(" = ")
    head = head.lstrip("%")
    if not sep:
        return head[:120]
    best, best_size = "", -1
    for m in _SHAPE.finditer(rest.split("%", 1)[0]):
        size = 1
        for d in filter(None, m.group(1).split(",")):
            size *= int(d)
        if size > best_size:
            best, best_size = m.group(0), size
    return f"{head} {best}".strip()[:120]


def top_ops(plane: dict, dev: dict, n: int = 10) -> List[list]:
    """The operations that took most device time in the stretch, summed by
    name, in seconds."""
    per: Dict[str, int] = {}
    for name, start, dur in _line(plane, OP_LINE):
        if start >= dev["lo_ns"] and start + dur <= dev["hi_ns"]:
            per[name] = per.get(name, 0) + dur
    ranked = sorted(per.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


# ------------------------------------------------- idle gaps, attributed ----


def idle_gaps(dev: dict, host_spans: List[dict], offset_s,
              n: int = 10) -> List[list]:
    """The longest idle gaps of one device, each named by the host span
    (``data_wait`` / ``step`` / ``fetch`` / ``ckpt``, else ``other``) that
    covered most of it; ``unattributed`` without a sound clock offset."""
    found = sorted(gaps(dev["busy"], dev["lo_ns"], dev["hi_ns"]),
                   key=lambda g: g[0] - g[1])[:n]
    out = []
    for start, end in found:
        label = "unattributed"
        if offset_s is not None:
            lo, hi = start / 1e9 + offset_s, end / 1e9 + offset_s
            cover: Dict[str, float] = {}
            for s in host_spans:
                if s["name"] == "iter":
                    continue
                ov = min(hi, s["ts"] + s["dur_s"]) - max(lo, s["ts"])
                if ov > 0:
                    cover[s["name"]] = cover.get(s["name"], 0.0) + ov
            label = max(cover, key=cover.get) if cover else "other"
            if cover and cover[label] < 0.5 * (hi - lo):
                label = "other"
        out.append([label, (end - start) / 1e9])
    return out
