"""Step-phase span tracing: preallocated ring + JSONL / Chrome-trace export.

The reference's observability is wall-clock meters plus explicit device
syncs (imagenet_ddp_apex.py:406, SURVEY §5); dptpu's device side is
covered by XLA traces (dptpu/utils/profiling.py). What was missing is
the HOST timeline that correlates them: where did each step's wall time
go — waiting on the loader, blocking on the H2D transfer, dispatching
the step, stalled on a checkpoint flush? ``Tracer`` answers that with
named spans recorded into a preallocated ring (no allocation churn on
the hot path beyond one tuple, no I/O until a drain), exported as

* a per-host JSONL event log (one span per line — greppable, diffable),
* a Chrome ``trace_event`` JSON that opens in Perfetto/chrome://tracing
  NEXT TO the XLA device trace, so a whole epoch's host phases and
  device ops sit on one timeline.

Span names are free-form; the canonical step phases the train loop
emits are ``data_wait`` / ``h2d`` / ``step`` / ``fetch`` / ``ckpt``
(see dptpu/obs/report.py for the category mapping). This module is
stdlib-only — it is imported by the data layer, which must stay
importable inside spawned decode workers (never JAX).
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time
from typing import List, Optional

from dptpu.utils.sync import OrderedLock


class _SpanCM:
    """Context-manager form of a span; ``record()`` is the hot-path API.
    What the work inside measures of itself goes into ``attrs`` (``with
    tracer.span("ckpt") as span: ... span.attrs[...] = ...``) and is
    recorded with the span; left empty, the span carries none."""

    __slots__ = ("_tracer", "_name", "_step", "_t0", "attrs")

    def __init__(self, tracer: "Tracer", name: str, step: int):
        self._tracer = tracer
        self._name = name
        self._step = step
        self.attrs = {}

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._tracer.record(self._name, self._t0, t1 - self._t0,
                            step=self._step, attrs=self.attrs or None)
        return False


class _NullCM:
    __slots__ = ()

    @property
    def attrs(self) -> dict:
        return {}  # written to and dropped

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_CM = _NullCM()


class NullTracer:
    """Disabled tracer: every call is a near-zero no-op (shared null
    context manager, no lock, no storage)."""

    enabled = False
    dropped = 0

    def span(self, name: str, step: int = -1):
        return _NULL_CM

    def record(self, name: str, t0: float, dur_s: float, step: int = -1,
               attrs: Optional[dict] = None):
        pass

    def reanchor(self):
        pass

    def snapshot(self) -> List[dict]:
        return []

    def drain(self) -> List[dict]:
        return []


class Tracer:
    """Span recorder over a preallocated ring buffer.

    * ``record(name, t0, dur_s, step=, attrs=)`` — hot path: one tuple +
      one locked ring store (~1 µs). ``t0`` is in the
      ``time.perf_counter`` domain; the tracer anchors that to wall time
      at construction and at every ``reanchor()`` (the train loop calls
      it at each epoch's entry, so ``ts`` never extrapolates over more
      than an epoch), and a span keeps the anchor it was recorded under.
      ``attrs`` is an optional small dict of counts and states measured
      where the work happens (``cpu_s``, ``inflight``, ``rows``, ...):
      exported under ``attrs``, left out altogether when None.
    * ``span(name)`` — context-manager sugar over ``record``.
    * ``drain()`` — spans since the last drain, oldest first, and
      resets the ring (the per-epoch consumption pattern);
      ``snapshot()`` reads without clearing (the in-flight profiling
      trigger's window read).
    * ring overflow OVERWRITES the oldest span and counts ``dropped``
      — tracing must never grow unbounded or stall the step loop.
    """

    enabled = True

    def __init__(self, capacity: int = 65536):
        if capacity < 2:
            raise ValueError(f"tracer capacity={capacity} must be >= 2")
        self.capacity = capacity
        self._buf: list = [None] * capacity  # guarded-by: _lock
        self._head = 0  # guarded-by: _lock
        self._count = 0  # guarded-by: _lock
        self.dropped = 0  # guarded-by: _lock
        # record() is called from EVERY thread (step loop, dispatcher,
        # prefetcher, writer), often while the caller holds its own
        # lock: the ring lock is the innermost rank by design
        self._lock = OrderedLock("obs.trace_ring")
        # anchor: wall = anchor_wall + (t_perf - anchor_perf); ONE tuple,
        # swapped whole, so a record racing a reanchor() reads a pair
        self._anchor = (time.time(), time.perf_counter())  # dptpu: allow-guarded-by(one immutable tuple replaced by a single attribute store: a record call on another thread reads the old pair or the new one, never a torn one, and taking the ring lock here would put a second acquisition on the hot path for nothing)

    def reanchor(self):
        """Retake the (wall, perf_counter) anchor. Spans already in the
        ring keep the ``ts`` computed under the anchor they were
        recorded with."""
        self._anchor = (time.time(), time.perf_counter())

    def span(self, name: str, step: int = -1) -> _SpanCM:
        return _SpanCM(self, name, step)

    def record(self, name: str, t0: float, dur_s: float, step: int = -1,
               attrs: Optional[dict] = None):
        wall, perf = self._anchor
        rec = (name, t0, dur_s, step, threading.get_ident(),
               wall + (t0 - perf), attrs)
        with self._lock:
            self._buf[self._head] = rec
            self._head = (self._head + 1) % self.capacity
            if self._count < self.capacity:
                self._count += 1
            else:
                self.dropped += 1

    def _read_locked(self) -> List[tuple]:
        start = (self._head - self._count) % self.capacity
        return [
            self._buf[(start + i) % self.capacity]
            for i in range(self._count)
        ]

    def snapshot(self) -> List[dict]:
        """Spans currently in the ring (oldest first), without clearing."""
        with self._lock:
            recs = self._read_locked()
        return [self._to_dict(r) for r in recs]

    def drain(self) -> List[dict]:
        """Spans since the last drain (oldest first); resets the ring."""
        with self._lock:
            recs = self._read_locked()
            self._head = 0
            self._count = 0
        return [self._to_dict(r) for r in recs]

    @staticmethod
    def _to_dict(rec: tuple) -> dict:
        name, t0, dur_s, step, tid, ts, attrs = rec
        out = {
            "name": name,
            "ts": ts,
            "t0": t0,  # perf_counter domain, for window filtering
            "dur_s": dur_s,
            "step": step,
            "tid": tid,
        }
        if attrs is not None:
            out["attrs"] = attrs
        return out


# ------------------------------------------------------------- exporters ----


def spans_to_chrome_events(spans, pid: Optional[int] = None) -> List[dict]:
    """Spans → Chrome ``trace_event`` objects (``ph: "X"`` complete
    events, µs timestamps) plus a process-name metadata record.

    The process is deliberately named ``dptpu Host spans`` so the device
    -trace parser (dptpu/utils/profiling.py) can never mistake the host
    track for a device track when both land in one merged timeline.
    """
    pid = os.getpid() if pid is None else pid
    events: List[dict] = [{
        "ph": "M", "pid": pid, "name": "process_name",
        "args": {"name": f"dptpu Host spans ({socket.gethostname()})"},
    }]
    for s in spans:
        events.append({
            "name": s["name"],
            "ph": "X",
            "pid": pid,
            "tid": s["tid"] % (1 << 31),  # chrome wants small-ish ints
            "ts": s["ts"] * 1e6,
            "dur": s["dur_s"] * 1e6,
            "args": {"step": s["step"], **s.get("attrs", {})},
        })
    return events


class TraceSink:
    """Per-host span persistence under one directory.

    * ``<dir>/obs-<host>.jsonl`` — appended per ``add_spans`` call (one
      span per line: ``name, ts, dur_s, step, tid, kind`` and, where the
      span carries any, an ``attrs`` object) plus any structured events (``log_event``): the
      greppable log.
    * ``<dir>/obs-<host>.trace.json`` — Chrome trace_event JSON,
      STREAMED: events are appended as they arrive (no per-run buffer —
      a 90-epoch run must not hold a million event dicts in RAM or
      rewrite a growing file once per epoch) and the array is closed at
      ``close()``. A killed run leaves the array unterminated, which
      Perfetto's JSON importer accepts (trailing data is tolerated by
      design in the trace_event format).
    """

    def __init__(self, directory: str):
        os.makedirs(directory, exist_ok=True)
        self.directory = directory
        host = socket.gethostname()
        self.jsonl_path = os.path.join(directory, f"obs-{host}.jsonl")
        self.chrome_path = os.path.join(directory, f"obs-{host}.trace.json")
        self._jsonl = open(self.jsonl_path, "a")
        if os.path.exists(self.chrome_path):
            # a resumed run must not truncate the preempted run's
            # timeline (the JSONL sibling appends; the Chrome file is
            # one JSON document per run, so rotate the old one aside)
            i = 1
            while os.path.exists(f"{self.chrome_path}.{i}"):
                i += 1
            os.replace(self.chrome_path, f"{self.chrome_path}.{i}")
        self._chrome = open(self.chrome_path, "w")
        self._chrome.write('{"displayTimeUnit": "ms", "traceEvents": [\n')
        self._chrome.write(json.dumps(spans_to_chrome_events([])[0]))
        self._chrome.flush()
        self._closed = False

    @property
    def jsonl_file(self):
        """The shared append handle (metric sinks write through it so
        spans and metric flushes interleave in ONE per-host log)."""
        return self._jsonl

    def add_spans(self, spans):
        if self._closed or not spans:
            return
        for s in spans:
            rec = {k: s[k] for k in ("name", "ts", "dur_s", "step", "tid")}
            rec["kind"] = "span"
            if "attrs" in s:
                rec["attrs"] = s["attrs"]
            self._jsonl.write(json.dumps(rec) + "\n")
        self._jsonl.flush()
        for e in spans_to_chrome_events(spans):
            if e["ph"] == "X":
                self._chrome.write(",\n" + json.dumps(e))
        self._chrome.flush()

    def log_event(self, kind: str, payload: dict):
        """Structured non-span record (metric flushes, reports)."""
        if self._closed:
            return
        self._jsonl.write(
            json.dumps({"kind": kind, "ts": time.time(), **payload}) + "\n"
        )
        self._jsonl.flush()

    def close(self):
        if self._closed:
            return
        self._chrome.write("\n]}\n")
        self._chrome.close()
        self._jsonl.close()
        self._closed = True
