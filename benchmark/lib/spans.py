"""From the trainer's own span log to the window and its host metrics.

``fit()`` records ``data_wait`` / ``step`` / ``fetch`` / ``iter`` spans on
``time.perf_counter`` (``dptpu/obs``) and, with ``DPTPU_OBS_DIR`` set,
writes them as one JSON object per line with a wall-clock ``ts``, a
``dur_s`` and the 0-based ``step`` of the epoch. This module reads that log
and nothing else of the program.

The window: iterations ``0 .. warmup-1`` are set-up. The window opens at
the start of iteration ``warmup`` and closes at the FENCE, the end of the
last ``fetch`` span: the loop's epoch-tail ``device_get`` of the pending
metrics, which returns only when every step dispatched before it has run
on the device. Every iteration in between is counted, stalled or not.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from typing import List


def read_log(path: str) -> List[dict]:
    """The ``kind == "span"`` records of an obs JSONL log, in file order."""
    spans = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            if rec.get("kind") == "span":
                spans.append(rec)
    return spans


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), q in [0, 100]."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclass(frozen=True)
class Window:
    t_first_iter: float  # wall start of iteration 0 (loop entry)
    t_start: float       # wall start of the first timed iteration
    t_end: float         # the fence
    iters: tuple         # the timed iter spans
    spans: tuple         # every span inside [t_start, t_end]

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start

    @property
    def steps(self) -> int:
        return len(self.iters)


def window(spans: List[dict], warmup: int) -> Window:
    """Cut the timed window out of a run's spans (train loop only)."""
    iters = sorted((s for s in spans if s["name"] == "iter"),
                   key=lambda s: s["ts"])
    if len(iters) <= warmup:
        raise ValueError(f"the log holds {len(iters)} iterations, not more "
                         f"than the {warmup} of warm-up: no window")
    timed = iters[warmup:]
    t_start = timed[0]["ts"]
    fences = [s["ts"] + s["dur_s"] for s in spans
              if s["name"] == "fetch" and s["ts"] >= t_start]
    last_iter_end = timed[-1]["ts"] + timed[-1]["dur_s"]
    if not fences or max(fences) < last_iter_end:
        raise ValueError("no fetch span closes the window after its last "
                         "iteration: the steps counted are not fenced")
    t_end = max(fences)
    inside = tuple(s for s in spans
                   if s["ts"] >= t_start and s["ts"] + s["dur_s"] <= t_end)
    return Window(iters[0]["ts"], t_start, t_end, tuple(timed), inside)


def before(win: Window, wall: float) -> Window:
    """The part of ``win`` that ended before ``wall``: in a traced run,
    what the profiler did not touch."""
    iters = tuple(s for s in win.iters if s["ts"] + s["dur_s"] <= wall)
    if not iters:
        return win
    return Window(win.t_first_iter, win.t_start, wall, iters,
                  tuple(s for s in win.spans if s["ts"] + s["dur_s"] <= wall))


def images_per_second_per_chip(win: Window, global_batch: int,
                               chips: int) -> float:
    """All images of all steps the window completed, over all its seconds
    (fence included), per chip."""
    return win.steps * global_batch / win.seconds / chips


def step_ms_p95(win: Window) -> float:
    """95th percentile of the wall time of every timed loop iteration."""
    return percentile([s["dur_s"] * 1e3 for s in win.iters], 95.0)


def durations_ms(win: Window, name: str):
    return [s["dur_s"] * 1e3 for s in win.spans if s["name"] == name]
