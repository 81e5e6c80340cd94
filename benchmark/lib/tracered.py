"""Reduction of a profiler trace to device busy time, idle gaps, per-step
device time and exposed collective time — the benchmark's own, so that
every PR computes these numbers the same way.

A trace is held as plain data, whatever wrote it::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

``load_xplane`` fills it from the ``.xplane.pb`` that ``jax.profiler``
writes (read with ``jax.profiler.ProfileData``, nothing but jax), device
planes only; the tests fill it from a small recorded JSON. Busy time is
the UNION of the intervals in which an operation ran on a device, never a
sum of durations: nested and overlapping events are counted once.

The stretch that is read lies between two marker programs the harness
runs on the device (``MARKER_OPEN`` behind the profiler's start-up stall,
``MARKER_CLOSE`` before it stops the profiler), found on the ``XLA
Modules`` line by the names of their jitted functions. ONE CLOCK: the
markers run on one device and bound every device's plane, and the idle
gaps of all planes are laid over each other; both lean on the profiler
writing the planes of one ``.xplane.pb`` on one clock, with a skew
between chips far under a step (the four planes of a recorded four-chip
step start within 20 us of each other).
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
MARKER_OPEN = "bench_marker_open"    # the jitted functions' names: the
MARKER_CLOSE = "bench_marker_close"  # modules are ``jit_<name>(<id>)``


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    return read_planes(ProfileData.from_file(path).planes)


def read_planes(planes) -> dict:
    """The device planes' three lines this module reads, and nothing else:
    no line of a host plane is iterated (the runtime's threads put a
    million events a second there when the host tracer is on). ``planes``
    are ``ProfileData`` planes or anything shaped alike. Operation names
    are shortened as they are read (``short_name``): the TPU trace names
    an operation by its whole HLO text."""
    device_lines = (OP_LINE, ASYNC_LINE, MODULE_LINE)
    short: Dict[str, str] = {}
    out = []
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = []
        for line in plane.lines:
            if line.name not in device_lines:
                continue
            events = []
            for e in line.events:
                name = e.name
                if line.name != MODULE_LINE:
                    name = short.get(name) or short.setdefault(
                        name, short_name(name))
                events.append([name, int(e.start_ns), int(e.duration_ns)])
            lines.append({"name": line.name, "events": events})
        out.append({"name": plane.name, "lines": lines})
    return {"planes": out}


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


def step_runs(plane: dict, module: Optional[str],
              bounds: Optional[Interval] = None) -> List[Interval]:
    """Whole executions of ``module`` on this device, inside ``bounds``."""
    return sorted((start, start + dur)
                  for name, start, dur in _line(plane, MODULE_LINE)
                  if name == module and (
                      bounds is None
                      or (start >= bounds[0] and start + dur <= bounds[1])))


def reduce_device(plane: dict, bounds: Optional[Interval] = None):
    """One device's numbers over the stretch of whole traced steps.

    The stretch is made of whole PERIODS of the step module inside
    ``bounds`` (in the trace's clock; the whole trace without them): from
    the start of the first complete execution to the start of the last,
    so each step counts with the wait that followed it, and ``steps`` is
    one less than the executions found. (From first start to last END a
    stretch of N steps holds N - 1 waits, and reads a host-bound device
    too busy: two steps of 50 ms 258 ms apart would read 72% idle for
    84%.) One lone execution is its own stretch. Returns None when the
    plane holds no operation.
    """
    ops = _line(plane, OP_LINE)
    if not ops:
        return None
    module = step_module(plane)
    runs = step_runs(plane, module, bounds)
    if len(runs) >= 2:
        lo, hi, steps = runs[0][0], runs[-1][0], len(runs) - 1
    elif runs:
        lo, hi, steps = runs[0][0], runs[0][1], 1
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


def marker_runs(trace: dict, marker: str) -> List[Interval]:
    """Executions of the harness's marker program ``marker`` (module
    ``jit_<marker>(<id>)``) on whichever device ran it."""
    return sorted((start, start + dur) for plane in device_planes(trace)
                  for name, start, dur in _line(plane, MODULE_LINE)
                  if name.partition("(")[0] == "jit_" + marker)


def marker_bounds(trace: dict) -> Optional[Interval]:
    """From the end of the open marker to the start of the close marker
    (to the last operation's end where the run gave up before its close
    marker). None without an open marker. The device executes in order,
    so the profiler's start-up stall lies before the open marker, and
    what ``stop_trace`` does to the device after the close marker."""
    opened, closed = (marker_runs(trace, m)
                      for m in (MARKER_OPEN, MARKER_CLOSE))
    if not opened:
        return None
    lo = opened[-1][1]
    later = [start for start, _ in closed if start > lo]
    if later:
        return lo, later[-1]
    ends = [start + dur for plane in device_planes(trace)
            for _, start, dur in _line(plane, OP_LINE)]
    return (lo, max(ends)) if ends and max(ends) > lo else None


def marker_offset(trace: dict, walls: Dict[str, float]) -> Optional[float]:
    """Seconds to add to a trace timestamp to get the host's wall clock.
    ``walls`` are the ``time.time()`` at which the harness saw each marker
    program's result ready (``{MARKER_OPEN: ..., MARKER_CLOSE: ...}``):
    that is the end of its module event plus the host's delay in seeing
    it, so the marker seen soonest gives the offset."""
    seen = []
    for marker, wall in walls.items():
        runs = marker_runs(trace, marker)
        if runs:
            seen.append(wall - runs[-1][1] / 1e9)
    return min(seen) if seen else None


def stalls(trace: dict, bounds: Interval, max_gap_ns: int) -> List[Interval]:
    """Where inside ``bounds`` ANY device sat idle for longer than
    ``max_gap_ns`` at a time: merged intervals, on the one clock."""
    found: List[Interval] = []
    for plane in device_planes(trace):
        busy = union(_spans(_line(plane, OP_LINE)))
        found += [g for g in gaps(busy, *bounds) if g[1] - g[0] > max_gap_ns]
    return union(found)


def _reduce_all(trace: dict, bounds: Optional[Interval]) -> List[dict]:
    return [d for d in (reduce_device(p, bounds)
                        for p in device_planes(trace)) if d]


def clean_stretch(trace: dict, bounds: Interval, read_ns: int,
                  max_gap_ns: int):
    """The LAST ``read_ns`` between ``bounds`` that no stall touches and
    that hold two whole steps or more on every device, as ``(devices,
    note)``. Where no clean piece is that long, the longest clean piece
    that still holds two whole steps; ``(None, note)`` where there is
    none."""
    found = stalls(trace, bounds, max_gap_ns)
    longest = max((e - s for s, e in found), default=0) / 1e9
    best = None
    for lo, hi in reversed(subtract([bounds], found)):
        devices = _reduce_all(trace, (max(lo, hi - read_ns), hi))
        if not devices or any(d["steps"] < 2 for d in devices):
            continue
        if hi - lo >= read_ns:
            return devices, "clean"
        if best is None or hi - lo > best[0]:
            best = (hi - lo, devices)
    if best is None:
        return None, (f"holding a stall of {longest:.2f} s: no two whole "
                      f"steps lie clear of one")
    note = f"clean for {best[0] / 1e9:.2f} s of {read_ns / 1e9:.2f} s"
    if found:
        note += f", beside a stall of {longest:.2f} s"
    return best[1], note


def reduce_trace(trace: dict, marker_walls: Dict[str, float] = None,
                 read_s: float = None, max_gap_s: float = None):
    """All devices. ``busy_s`` is averaged over the devices used,
    ``window_s`` is the stretch that was read; per-step numbers are of the
    busiest device. The stretch lies between the harness's two marker
    programs; with ``read_s`` and ``max_gap_s`` it is the last ``read_s``
    there in which no device sat idle for longer than ``max_gap_s`` at a
    time (``clean_stretch``). ``stretch`` says what was read: if the
    profiler's stall could not be kept out, it names the stall.
    ``marker_walls`` align the trace's clock with the host's
    (``marker_offset``)."""
    bounds = marker_bounds(trace)
    devices, stretch = None, "the whole trace: no marker program found"
    if bounds:
        stretch = "between the markers"
        if read_s and max_gap_s:
            devices, note = clean_stretch(trace, bounds, int(read_s * 1e9),
                                          int(max_gap_s * 1e9))
            stretch += ", " + note
        if devices is None:
            devices = _reduce_all(trace, bounds)
        if any(d["steps"] < 2 for d in devices):
            # the stall outlasted the markers: read every whole step the
            # trace holds rather than none (the idle share then holds
            # the stall; the per-step numbers do not)
            devices = None
            stretch = ("the whole trace: under two whole steps between "
                       "the markers")
    if devices is None:
        devices = _reduce_all(trace, None)
    if not devices:
        return None
    busiest = max(devices, key=lambda d: d["busy_ns"])
    planes = {p["name"]: p for p in trace["planes"]}
    events = sum(len(line["events"]) for p in trace["planes"]
                 for line in p["lines"])
    modules: Dict[str, int] = {}
    for p in device_planes(trace):
        for name, _, _ in _line(p, MODULE_LINE):
            name = name.partition("(")[0]
            modules[name] = modules.get(name, 0) + 1
    return {
        "devices": devices,
        "busiest": busiest,
        "busy_s": sum(d["busy_ns"] for d in devices) / len(devices) / 1e9,
        "window_s": max(d["window_ns"] for d in devices) / 1e9,
        "steps": busiest["steps"],
        "top_ops": top_ops(planes[busiest["name"]], busiest),
        "offset_s": marker_offset(trace, marker_walls or {}),
        "stretch": stretch,
        "events_read": events,
        "modules": modules,  # executions in the whole trace, all devices
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
