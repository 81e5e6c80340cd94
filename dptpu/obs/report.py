"""Epoch attribution: host spans → "where did this epoch's time go".

Consumes one epoch's drained spans (dptpu/obs/trace.py) and produces the
per-phase breakdown large-scale ImageNet runs live and die by (straggler
and input-starvation diagnosis — Mikami et al. 1811.05233, Ying et al.
2004.13336 both lean on exactly this per-phase step accounting):

* ``data_wait`` — host blocked waiting for the loader (collect/lease
  included);
* ``h2d`` — host-to-device transfer (the DevicePrefetcher's put/block);
* ``device`` — step dispatch, the loop's wait for the device where the
  run-ahead is bounded (``pace``) and the lagged metric fetch (host time
  spent feeding/syncing the device; the DEVICE-side truth lives in XLA traces
  — dptpu/utils/profiling.py — which these host spans complement, never
  replace);
* ``ckpt`` — checkpoint submits/flushes on the step thread (async
  writer time off-thread is reported separately, it overlaps compute);
* ``compile`` — tracing, lowering and backend compiles (a cache load
  counts) that jax reported while the epoch ran
  (dptpu/utils/compile_cache.py): a compile inside a ``step`` call is
  taken out of ``device``;
* ``other`` — the residual against epoch wall time (loop bookkeeping,
  pipeline construction). A healthy tracer keeps coverage >= 95%.

Nested spans are handled by EXCLUSIVE-time accounting (a ``data_wait``
interval containing an ``h2d`` interval contributes only the
non-overlapped part), so categories sum to at most wall time instead of
double-counting. Per-step totals come from the loop's ``iter`` spans:
p50/p90/max step time plus an anomalous-step log (steps slower than
``anomaly_x`` × p50, with their own phase breakdown) — the "why is step
41k slow" first answer without a profiler session.

The spans' attributes (``attrs``, measured where the work happens) give
the report's third line, ``step_call`` and ``feed``: how long the
dispatch call took and how much of that the loop thread was on the CPU,
how many earlier steps were still in flight, how often the loop had to
wait for the device first (``paced``) and whether the batch had landed
when it was made; whether the feed's workers were done when the
loop came for a batch, and the CPU and wall time a row cost them.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from dptpu.obs.metrics import _quantile

# span name -> attribution category. "iter" is the per-step envelope —
# used for step statistics, excluded from category accounting (it would
# double-count every phase it contains).
SPAN_CATEGORY = {
    "data_wait": "data_wait",
    "collect": "data_wait",
    "h2d": "h2d",
    "pace": "device",
    "step": "device",
    "fetch": "device",
    "eval_step": "device",
    "ckpt": "ckpt",
    "ckpt_flush": "ckpt",
    "compile": "compile",
}
CATEGORIES = ("data_wait", "h2d", "device", "ckpt", "compile")
# spans that run on helper threads by design and therefore OVERLAP the
# step timeline: reported separately, never part of the wall budget
ASYNC_SPANS = ("ckpt_write",)


def exclusive_durations(spans: List[dict]) -> List[tuple]:
    """Per-span exclusive duration: ``dur_s`` minus time covered by
    spans nested inside it (same thread, interval containment). Returns
    ``[(span, exclusive_s), ...]``. O(n log n) sweep per thread."""
    out = []
    by_tid: Dict[int, List[dict]] = {}
    for s in spans:
        by_tid.setdefault(s["tid"], []).append(s)
    for tid_spans in by_tid.values():
        # sort by start, longest first on ties → parents precede children
        tid_spans.sort(key=lambda s: (s["t0"], -s["dur_s"]))
        stack: List[list] = []  # [span, child_time]
        for s in tid_spans:
            while stack and s["t0"] >= stack[-1][0]["t0"] + \
                    stack[-1][0]["dur_s"] - 1e-12:
                top, child_time = stack.pop()
                out.append((top, max(top["dur_s"] - child_time, 0.0)))
            if stack:
                stack[-1][1] += s["dur_s"]
            stack.append([s, 0.0])
        while stack:
            top, child_time = stack.pop()
            out.append((top, max(top["dur_s"] - child_time, 0.0)))
    return out


def _categorized_exclusive(spans: List[dict]) -> List[tuple]:
    """``[(span, category, exclusive_s), ...]`` for every categorized
    budget span ("iter" envelopes and async-thread spans excluded)."""
    out = []
    for span, excl in exclusive_durations(
        [s for s in spans
         if s["name"] != "iter" and s["name"] not in ASYNC_SPANS]
    ):
        cat = SPAN_CATEGORY.get(span["name"])
        if cat is not None:
            out.append((span, cat, excl))
    return out


def attribute_spans(spans: List[dict]) -> Dict[str, float]:
    """Category → exclusive seconds over an arbitrary span window (the
    epoch report and the in-flight trigger both use this)."""
    sums = {c: 0.0 for c in CATEGORIES}
    for _, cat, excl in _categorized_exclusive(spans):
        sums[cat] += excl
    return sums


def _mean(values) -> float:
    values = list(values)
    return sum(values) / len(values) if values else 0.0


def summarize_attrs(spans: List[dict]) -> dict:
    """The loop's and the feed's own account of an epoch, from the
    attributes on their spans; a key is absent where no span carried
    its attribute (tracing written by an older run, process-mode feed)."""
    out: Dict[str, dict] = {}
    calls = [s for s in spans
             if s["name"] == "step" and "cpu_s" in s.get("attrs", ())]
    if calls:
        durs = sorted(s["dur_s"] for s in calls)
        cpu = _mean(s["attrs"]["cpu_s"] for s in calls)
        out["step_call"] = {
            "p50_ms": round(_quantile(durs, 0.50) * 1e3, 3),
            "cpu_ms": round(cpu * 1e3, 3),
            "blocked_ms": round((_mean(durs) - cpu) * 1e3, 3),
            "inflight_p50": _quantile(
                sorted(s["attrs"]["inflight"] for s in calls), 0.50),
            "input_ready_pct": round(100.0 * _mean(
                s["attrs"]["input_ready"] for s in calls), 1),
        }
        paced = [s["attrs"]["paced"] for s in calls if "paced" in s["attrs"]]
        if paced:
            out["step_call"]["paced_pct"] = round(100.0 * _mean(paced), 1)
    collects = [s for s in spans
                if s["name"] == "collect" and "ready" in s.get("attrs", ())]
    if collects:
        feed = {"ready_pct": round(100.0 * _mean(
            s["attrs"]["ready"] for s in collects), 1)}
        for key, per_row in (("cpu_s", "row_cpu_us"),
                             ("wall_s", "row_wall_us")):
            timed = [s["attrs"] for s in collects
                     if key in s["attrs"] and s["attrs"].get("rows")]
            rows = sum(a["rows"] for a in timed)
            if rows:
                feed[per_row] = round(
                    1e6 * sum(a[key] for a in timed) / rows, 2)
        out["feed"] = feed
    return out


def attribute_epoch(spans: List[dict], wall_s: float,
                    anomaly_x: float = 3.0,
                    max_anomalies: int = 10) -> dict:
    """One epoch's attribution report (see module docstring)."""
    categorized = _categorized_exclusive(spans)
    sums = {c: 0.0 for c in CATEGORIES}
    for _, cat, excl in categorized:
        sums[cat] += excl
    accounted = sum(sums.values())
    other = max(wall_s - accounted, 0.0)
    iters = [s for s in spans if s["name"] == "iter"]
    durs = sorted(s["dur_s"] for s in iters)
    p50 = _quantile(durs, 0.50)
    anomalies = []
    if p50 > 0:
        slow = sorted(
            (s for s in iters if s["dur_s"] > anomaly_x * p50),
            key=lambda s: -s["dur_s"],
        )[:max_anomalies]
        # per-step breakdown from the SAME exclusive accounting as the
        # category totals — raw durations would double-count a nested
        # collect inside its data_wait and print phases > step time
        by_step: Dict[int, Dict[str, float]] = {}
        for s, cat, excl in categorized:
            if s["step"] >= 0:
                d = by_step.setdefault(s["step"], {})
                d[cat] = d.get(cat, 0.0) + excl
        step_attrs = {s["step"]: s["attrs"] for s in spans
                      if s["name"] == "step" and "attrs" in s}
        # backend compiles only (a cache load counts): the tracing and
        # lowering events around each would drown the line
        compiles = [s for s in spans if s["name"] == "compile"
                    and s.get("attrs", {}).get("event") == "backend_compile"]
        for s in slow:
            a = {
                "step": s["step"],
                "dur_s": round(s["dur_s"], 4),
                "x_p50": round(s["dur_s"] / p50, 2),
                "phases": {k: round(v, 4)
                           for k, v in by_step.get(s["step"], {}).items()},
            }
            # what the loop saw when it dispatched this step, and any
            # compile that ran inside the iteration (on any thread)
            if s["step"] in step_attrs:
                a["step_call"] = {
                    k: round(v, 4) if isinstance(v, float) else v
                    for k, v in step_attrs[s["step"]].items()}
            inside = sorted(
                (c for c in compiles
                 if s["t0"] <= c["t0"] + c["dur_s"]
                 and c["t0"] <= s["t0"] + s["dur_s"]),
                key=lambda c: -c["dur_s"])
            if inside:
                a["compiles"] = {
                    "count": len(inside),
                    "longest": [{"dur_s": round(c["dur_s"], 4),
                                 **c.get("attrs", {})}
                                for c in inside[:3]],
                }
            anomalies.append(a)
    async_ckpt = sum(
        s["dur_s"] for s in spans if s["name"] in ASYNC_SPANS
    )
    return {
        "wall_s": round(wall_s, 4),
        "data_wait_s": round(sums["data_wait"], 4),
        "h2d_s": round(sums["h2d"], 4),
        "device_s": round(sums["device"], 4),
        "ckpt_s": round(sums["ckpt"], 4),
        "compile_s": round(sums["compile"], 4),
        "other_s": round(other, 4),
        "coverage": round(accounted / wall_s, 4) if wall_s > 0 else 0.0,
        "ckpt_async_s": round(async_ckpt, 4),  # overlapped, not in budget
        "steps": len(iters),
        "step_p50_s": round(p50, 4),
        "step_p90_s": round(_quantile(durs, 0.90), 4),
        "step_max_s": round(durs[-1] if durs else 0.0, 4),
        "anomalous_steps": anomalies,
        "span_count": len(spans),
        **summarize_attrs(spans),
    }


def setup_report(spans: List[dict]) -> dict:
    """``fit()``'s set-up, from its ``setup.*`` spans (consecutive, from
    its first line to loop entry; a phase entered twice is one row) and
    the ``compile`` spans inside them: per phase its seconds, the backend compiles in it (a cache load
    counts) and the compile seconds (tracing and lowering included,
    nested events counted once), and how many of those compiles took
    under a second, the ones jax's persistent cache does not store."""
    compiles = exclusive_durations(
        [s for s in spans if s["name"] == "compile"])
    phases: Dict[str, dict] = {}  # by name, in order of first entry
    for p in sorted((s for s in spans if s["name"].startswith("setup.")),
                    key=lambda s: s["t0"]):
        lo, hi = p["t0"], p["t0"] + p["dur_s"]
        inside = [(c, excl) for c, excl in compiles
                  if lo <= c["t0"] + c["dur_s"] / 2.0 < hi]
        backend = [c for c, _ in inside
                   if c.get("attrs", {}).get("event") == "backend_compile"]
        small = [c for c in backend if c["dur_s"] < 1.0]
        name = p["name"][len("setup."):]
        row = phases.setdefault(name, dict.fromkeys(
            ("s", "compile_s", "compiles", "compiles_under_1s",
             "compiles_under_1s_s", "cache_hits"), 0))
        row["s"] += p["dur_s"]
        row["compile_s"] += sum(excl for _, excl in inside)
        row["compiles"] += len(backend)
        row["compiles_under_1s"] += len(small)
        row["compiles_under_1s_s"] += sum(c["dur_s"] for c in small)
        row["cache_hits"] += sum(
            1 for c in backend if c["attrs"].get("cache_hit"))
    rows = [{"phase": name,
             **{k: round(v, 3) if isinstance(v, float) else v
                for k, v in row.items()}}
            for name, row in phases.items()]
    return {"total_s": round(sum(r["s"] for r in rows), 3), "phases": rows}


def format_setup(report: dict) -> str:
    """The one ``=> set-up:`` line ``fit()`` prints at loop entry."""
    parts = []
    for p in report["phases"]:
        text = f"{p['phase']} {p['s']:.1f}s"
        if p["compiles"] or p["compile_s"] >= 0.05:
            text += (f" (compile {p['compile_s']:.1f}s in {p['compiles']}"
                     + (f", {p['cache_hits']} from the cache"
                        if p["cache_hits"] else "")
                     + (f", {p['compiles_under_1s']} under 1s: "
                        f"{p['compiles_under_1s_s']:.1f}s"
                        if p["compiles_under_1s"] else "") + ")")
        parts.append(text)
    return (f"=> set-up: {report['total_s']:.1f}s to loop entry | "
            + " | ".join(parts))


class P2Quantile:
    """Streaming quantile estimate via the P² algorithm (Jain &
    Chlamtac, CACM 1985): five markers, O(1) memory and O(1) per
    observation — the right shape for a pod timeline that may span a
    90-epoch run's worth of step spans. Exact (sorted interpolation)
    below five observations; the classic parabolic/linear marker update
    beyond. ``value()`` is the current estimate of quantile ``q``."""

    __slots__ = ("q", "count", "_heights", "_pos", "_want", "_inc")

    def __init__(self, q: float = 0.5):
        if not 0.0 < q < 1.0:
            raise ValueError(f"P2Quantile q={q} must be in (0, 1)")
        self.q = q
        self.count = 0
        self._heights: List[float] = []  # marker heights q0..q4
        self._pos = [1.0, 2.0, 3.0, 4.0, 5.0]  # actual positions n_i
        self._want = [1.0, 1.0 + 2 * q, 1.0 + 4 * q, 3.0 + 2 * q, 5.0]
        self._inc = [0.0, q / 2.0, q, (1.0 + q) / 2.0, 1.0]

    def add(self, x: float):
        x = float(x)
        self.count += 1
        h = self._heights
        if self.count <= 5:
            h.append(x)
            h.sort()
            return
        # locate the cell; extremes extend the end markers
        if x < h[0]:
            h[0] = x
            k = 0
        elif x >= h[4]:
            h[4] = x
            k = 3
        else:
            k = 0
            while k < 3 and x >= h[k + 1]:
                k += 1
        for i in range(k + 1, 5):
            self._pos[i] += 1.0
        for i in range(5):
            self._want[i] += self._inc[i]
        # adjust the three interior markers toward their desired spots
        for i in (1, 2, 3):
            d = self._want[i] - self._pos[i]
            if (d >= 1.0 and self._pos[i + 1] - self._pos[i] > 1.0) or \
                    (d <= -1.0 and self._pos[i - 1] - self._pos[i] < -1.0):
                s = 1.0 if d >= 1.0 else -1.0
                # parabolic (P²) estimate; fall back to linear if it
                # would break marker monotonicity
                nl, ni, nr = self._pos[i - 1], self._pos[i], self._pos[i + 1]
                hp = h[i] + s / (nr - nl) * (
                    (ni - nl + s) * (h[i + 1] - h[i]) / (nr - ni)
                    + (nr - ni - s) * (h[i] - h[i - 1]) / (ni - nl)
                )
                if not h[i - 1] < hp < h[i + 1]:
                    j = i + int(s)
                    hp = h[i] + s * (h[j] - h[i]) / (self._pos[j] - ni)
                h[i] = hp
                self._pos[i] += s

    def value(self) -> float:
        if self.count == 0:
            return 0.0
        if self.count <= 5:
            return _quantile(sorted(self._heights), self.q)
        return self._heights[2]


# merged-timeline temp files still on disk (conftest leak guard: every
# merge must either complete its atomic rename or unlink its temp)
_LIVE_MERGE_TMPS: set = set()


def live_merge_tmp_count() -> int:
    return len(_LIVE_MERGE_TMPS)


def merge_pod_timeline(directory: str, out_path: Optional[str] = None,
                       window_s: float = 60.0,
                       straggler_factor: float = 1.5) -> dict:
    """Chief-side collector: merge every per-host ``obs-<host>.jsonl``
    under ``directory`` into ONE pod timeline (ROADMAP item 3c).

    Streaming pass — constant memory per host via :class:`P2Quantile`,
    so a week-long pod log merges without loading it: per-host p50/p90
    for every span category, per-host ``iter`` (step-time) quantiles
    bucketed into ``window_s`` wall-clock windows ("what changed at
    14:07" = the window whose p50 jumped), the epoch reports each host
    logged, and a straggler verdict (hosts whose step p50 exceeds
    ``straggler_factor`` × the pod-wide p50 — only meaningful with >= 2
    hosts; a 1-host pod reports an empty list).

    ``out_path`` (optional) writes the merged timeline atomically
    (tempfile + rename in the target directory; the temp is tracked so
    the test suite's leak guard can prove none is ever left behind).
    """
    hosts: Dict[str, dict] = {}
    for path in sorted(glob.glob(os.path.join(directory, "obs-*.jsonl"))):
        host = os.path.basename(path)[len("obs-"):-len(".jsonl")]
        h = hosts.setdefault(host, {
            "spans": {},  # name -> {count, p50 P2, p90 P2}
            "iter_p50": P2Quantile(0.5), "iter_p90": P2Quantile(0.9),
            "iter_count": 0,
            "windows": {},  # int(ts // window_s) -> {count, p50 P2}
            "epochs": [],
            "events": 0,
            "bad_lines": 0,
        })
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    h["bad_lines"] += 1
                    continue
                kind = rec.get("kind")
                if kind == "span":
                    name, dur = rec.get("name"), rec.get("dur_s", 0.0)
                    s = h["spans"].setdefault(
                        name,
                        {"count": 0, "p50": P2Quantile(0.5),
                         "p90": P2Quantile(0.9)},
                    )
                    s["count"] += 1
                    s["p50"].add(dur)
                    s["p90"].add(dur)
                    if name == "iter":
                        h["iter_p50"].add(dur)
                        h["iter_p90"].add(dur)
                        h["iter_count"] += 1
                        w = h["windows"].setdefault(
                            int(rec.get("ts", 0.0) // window_s),
                            {"count": 0, "p50": P2Quantile(0.5)},
                        )
                        w["count"] += 1
                        w["p50"].add(dur)
                elif kind == "epoch_report":
                    h["epochs"].append({
                        k: rec[k] for k in
                        ("epoch", "wall_s", "data_wait_s", "device_s",
                         "step_p50_s")
                        if k in rec
                    })
                else:
                    h["events"] += 1
    pod_p50 = P2Quantile(0.5)
    out_hosts = {}
    for host, h in hosts.items():
        windows = []
        prev = None
        for wk in sorted(h["windows"]):
            w = h["windows"][wk]
            p50 = round(w["p50"].value(), 6)
            windows.append({
                "t0": wk * window_s,
                "steps": w["count"],
                "step_p50_s": p50,
                # the "what changed at 14:07" hook: this window's p50
                # relative to the previous window's
                "vs_prev": round(p50 / prev, 3) if prev else 1.0,
            })
            prev = p50 or prev
        out_hosts[host] = {
            "steps": h["iter_count"],
            "step_p50_s": round(h["iter_p50"].value(), 6),
            "step_p90_s": round(h["iter_p90"].value(), 6),
            "spans": {
                name: {"count": s["count"],
                       "p50_s": round(s["p50"].value(), 6),
                       "p90_s": round(s["p90"].value(), 6)}
                for name, s in sorted(h["spans"].items())
            },
            "windows": windows,
            "epochs": h["epochs"],
            "bad_lines": h["bad_lines"],
        }
        if h["iter_count"]:
            pod_p50.add(h["iter_p50"].value())
    pod = round(pod_p50.value(), 6)
    stragglers = []
    if len([h for h in out_hosts.values() if h["steps"]]) >= 2 and pod > 0:
        stragglers = sorted(
            host for host, h in out_hosts.items()
            if h["steps"] and h["step_p50_s"] > straggler_factor * pod
        )
    timeline = {
        "directory": directory,
        "window_s": window_s,
        "hosts": out_hosts,
        "pod_step_p50_s": pod,
        "straggler_factor": straggler_factor,
        "stragglers": stragglers,
    }
    if out_path is not None:
        tmp = out_path + ".tmp"
        _LIVE_MERGE_TMPS.add(tmp)
        try:
            with open(tmp, "w") as f:
                json.dump(timeline, f, indent=1)
                f.write("\n")
            os.replace(tmp, out_path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        finally:
            _LIVE_MERGE_TMPS.discard(tmp)
    return timeline


def format_report(report: dict, epoch: Optional[int] = None) -> str:
    """Console rendering of :func:`attribute_epoch` (one block per
    epoch, additive next to the reference's contractual meter lines)."""
    wall = max(report["wall_s"], 1e-9)
    head = f"== obs epoch {epoch}" if epoch is not None else "== obs"
    parts = [
        f"{head}: wall {report['wall_s']:.1f}s | "
        + " | ".join(
            f"{k[:-2]} {report[k]:.2f}s "
            f"({100.0 * report[k] / wall:.1f}%)"
            for k in ("data_wait_s", "h2d_s", "device_s", "ckpt_s",
                      "compile_s", "other_s")
            if k != "compile_s" or report.get(k)
        )
        + f" | coverage {100.0 * report['coverage']:.1f}%"
    ]
    parts.append(
        f"   step time p50 {report['step_p50_s'] * 1e3:.1f}ms "
        f"p90 {report['step_p90_s'] * 1e3:.1f}ms "
        f"max {report['step_max_s'] * 1e3:.1f}ms "
        f"over {report['steps']} steps"
        + (f" | async ckpt {report['ckpt_async_s']:.2f}s overlapped"
           if report["ckpt_async_s"] else "")
    )
    calls, feed = report.get("step_call"), report.get("feed")
    if calls or feed:
        bits = []
        if calls:
            bits.append(
                f"step call p50 {calls['p50_ms']:.1f}ms (mean: cpu "
                f"{calls['cpu_ms']:.1f}ms + blocked "
                f"{calls['blocked_ms']:.1f}ms), in flight p50 "
                f"{calls['inflight_p50']:g}"
                + (f", paced {calls['paced_pct']:.0f}%"
                   if "paced_pct" in calls else "")
                + f", input ready {calls['input_ready_pct']:.0f}%"
            )
        if feed:
            bits.append(
                f"feed ready {feed['ready_pct']:.0f}%"
                + "".join(f", {what} {feed[k]:.0f}us/row"
                          for k, what in (("row_cpu_us", "cpu"),
                                          ("row_wall_us", "wall"))
                          if k in feed)
            )
        parts.append("   " + " | ".join(bits))
    for a in report["anomalous_steps"]:
        phases = " ".join(f"{k}={v:.3f}s" for k, v in a["phases"].items())
        call = a.get("step_call")
        if call:
            phases += " | " + " ".join(f"{k}={v}" for k, v in call.items())
        if "compiles" in a:
            phases += (
                f" | {a['compiles']['count']} compile(s), longest: "
                + ", ".join(f"{c.get('fun', '?')} {c['dur_s']:.3f}s"
                            for c in a["compiles"]["longest"]))
        parts.append(
            f"   anomalous step {a['step']}: {a['dur_s']:.3f}s "
            f"({a['x_p50']}x p50) {phases}"
        )
    return "\n".join(parts)
