"""On-demand in-flight profiling: arm a device trace on a LIVE run.

"Why is step 41k slow" used to require killing the job and restarting
it under ``DPTPU_PROFILE`` / a ``profile_device_time`` session. The
:class:`ProfileTrigger` removes the restart: send the training process
``SIGUSR2`` (or touch the ``DPTPU_OBS_TRIGGER`` sentinel file) and the
NEXT ``DPTPU_OBS_TRACE_STEPS`` steps of the running ``fit()`` are traced
with ``jax.profiler.trace``; when the window closes the trigger parses
the XLA trace (dptpu/utils/profiling.py), snapshots the host spans that
covered the same window (dptpu/obs/trace.py), and writes + prints one
MERGED host-phase + device-op attribution table — no restart, no lost
training time beyond the trace itself.

States: idle → armed (signal/sentinel seen) → active (trace running,
counting steps) → idle. ``tick()`` is called once per training step by
the loop's ``on_step`` hook; in the idle state with no sentinel it is a
single attribute check. The signal handler only sets a flag (handlers
must stay async-signal-safe); all profiler work happens on the step
thread inside ``tick()``.

JAX is imported lazily — this module is reachable from the data layer's
package but must never pull jax into spawned decode workers.
"""

from __future__ import annotations

import json
import os
import signal
import threading
import time
from typing import Optional

from dptpu.obs.report import attribute_spans


class ProfileTrigger:
    """Arm-on-demand ``jax.profiler`` window over a live step loop."""

    def __init__(self, out_dir: str, trace_steps: int = 8, tracer=None,
                 sentinel: Optional[str] = None, verbose: bool = True,
                 signum: int = signal.SIGUSR2):
        if trace_steps < 1:
            raise ValueError(
                f"trace_steps={trace_steps} must be >= 1 step"
            )
        self.out_dir = out_dir
        self.trace_steps = trace_steps
        self.tracer = tracer
        self.sentinel = sentinel
        self.verbose = verbose
        self.signum = signum
        # set by the SIGUSR2 handler (or arm()/the sentinel on the step
        # thread) and consumed by tick(): an async-signal flag on
        # purpose — a lock inside a signal handler could self-deadlock
        # the main thread it interrupts, and the worst a torn flip can
        # do is arm one extra capture
        self._armed = False  # dptpu: allow-guarded-by(async-signal flag: the handler may only SET it and tick consumes it; taking a lock inside a signal handler could self-deadlock the interrupted main thread, and a torn flip at worst arms one extra capture)
        self._active = False
        self._ticks = 0  # steps seen since install (the fallback label)
        self._disabled_reason: Optional[str] = None
        self._steps_in_window = 0
        self._window_t0 = 0.0
        self._window_step0 = -1
        self._window_spans: list = []  # drained-past-us spans (absorb)
        self._captures = 0
        self._old_handler = None
        self._installed = False
        self._sentinel_mtime: Optional[float] = None
        self.last_report: Optional[dict] = None

    # ------------------------------------------------------------ arming ----

    def _handle(self, signum, frame):
        self._armed = True

    def install(self):
        """Install the SIGUSR2 handler (main thread only — elsewhere the
        sentinel file remains the arming path, same as every signal-based
        guard in dptpu)."""
        if threading.current_thread() is threading.main_thread():
            self._old_handler = signal.signal(self.signum, self._handle)
            self._installed = True
        return self

    def uninstall(self):
        if self._installed:
            signal.signal(self.signum, self._old_handler)
            self._installed = False
        if self._active:
            # never leave a dangling profiler session behind a dying fit
            try:
                self._stop_window(aborted=True)
            except Exception:
                pass

    def arm(self):
        """Programmatic arming (benches/tests; signal and sentinel are
        the operational paths)."""
        self._armed = True

    def absorb(self, spans):
        """Called by whoever DRAINS the shared tracer (fit's epoch
        report does) while a window may be open: spans inside the
        window are kept here so the merged report still covers them —
        a window straddling an epoch boundary must not lose its first
        steps to the boundary drain."""
        if self._active:
            self._window_spans.extend(
                s for s in spans if s["t0"] >= self._window_t0
            )

    def _sentinel_fired(self) -> bool:
        if self.sentinel is None:
            return False
        try:
            mtime = os.path.getmtime(self.sentinel)
        except OSError:
            return False
        # consume the sentinel so one touch = one capture; if the file
        # can't be removed (read-only dir), fall back to mtime edge
        # detection so it doesn't re-trigger forever
        try:
            os.remove(self.sentinel)
        except OSError:
            if self._sentinel_mtime == mtime:
                return False
            self._sentinel_mtime = mtime
        return True

    # ----------------------------------------------------------- stepping ----

    def tick(self, step: int = -1):
        """Called once per completed training step. ``step`` is an
        optional label; callers that don't track one (the loop's
        argument-less ``on_step`` hook) get the trigger's own count of
        steps seen since install."""
        self._ticks += 1
        if self._disabled_reason is not None:
            return
        if self._active:
            self._steps_in_window += 1
            if self._steps_in_window >= self.trace_steps:
                self._stop_window()
            return
        if self._armed or self._sentinel_fired():
            self._armed = False
            self._start_window(step if step >= 0 else self._ticks)

    def _trace_dir(self) -> str:
        return os.path.join(
            self.out_dir, f"ondemand-{self._captures:03d}"
        )

    def _start_window(self, step: int):
        import jax

        from dptpu.utils.profiling import device_profile_options

        path = self._active_dir = self._trace_dir()
        os.makedirs(path, exist_ok=True)
        try:
            # device planes only: the host half of the report is the
            # tracer's own spans (_build_report), and the host tracer
            # would stall the live run it is meant to observe
            jax.profiler.start_trace(
                path, profiler_options=device_profile_options())
        except Exception as e:
            # e.g. another trace is already running (DPTPU_PROFILE epoch
            # trace): stand down for this run instead of crashing a live
            # training job over observability
            self._disabled_reason = str(e)
            if self.verbose:
                print(
                    f"=> obs trigger: cannot start device trace "
                    f"({e}); on-demand profiling disabled for this run"
                )
            return
        self._active = True
        self._steps_in_window = 0
        self._window_t0 = time.perf_counter()
        self._window_step0 = step
        self._window_spans = []
        if self.verbose:
            print(
                f"=> obs trigger: device trace armed for the next "
                f"{self.trace_steps} steps -> {path}"
            )

    def _stop_window(self, aborted: bool = False):
        import jax

        jax.profiler.stop_trace()
        self._active = False
        self._captures += 1
        if aborted:
            return
        window_s = time.perf_counter() - self._window_t0
        path = self._active_dir
        report = self._build_report(path, window_s)
        self._window_spans = []
        out_path = os.path.join(path, "attribution.json")
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
        self.last_report = report
        if self.verbose:
            print(self.format_report(report))
            print(f"=> obs trigger: wrote {out_path}")

    # ------------------------------------------------------------ reports ----

    def _build_report(self, trace_path: str, window_s: float) -> dict:
        # host side: spans whose start falls inside the traced window —
        # any absorbed (drained-past-us) spans first, then what's still
        # in the ring
        cutoff = self._window_t0
        spans = list(self._window_spans)
        if self.tracer is not None:
            spans += [s for s in self.tracer.snapshot()
                      if s["t0"] >= cutoff]
        host = attribute_spans(spans)
        iters = sorted(
            s["dur_s"] for s in spans if s["name"] == "iter"
        )
        report = {
            "trace_dir": trace_path,
            "window_s": round(window_s, 4),
            "steps": self.trace_steps,
            "first_step": self._window_step0,
            "host_phases_s": {k: round(v, 4) for k, v in host.items()},
            "host_step_p50_s": round(
                iters[len(iters) // 2], 4) if iters else 0.0,
        }
        # device side: parse the XLA trace; a host-only trace (backend
        # exports no device tracks) degrades to host-span attribution
        # with the parser's explanation attached instead of failing the
        # live run
        try:
            from dptpu.utils.profiling import (
                load_trace_dir,
                parse_perfetto_trace,
            )

            merged = load_trace_dir(trace_path)
            total_ms, per_op = parse_perfetto_trace(
                merged, iters=self.trace_steps
            )
            top = sorted(per_op.items(), key=lambda kv: -kv[1])[:12]
            report["device_ms_per_step"] = round(total_ms, 3)
            report["device_top_ops_ms"] = {
                k: round(v, 3) for k, v in top
            }
        except (RuntimeError, OSError) as e:
            report["device_trace_error"] = str(e)
        return report

    @staticmethod
    def format_report(report: dict) -> str:
        lines = [
            f"== on-demand profile: {report['steps']} steps from step "
            f"{report['first_step']} ({report['window_s']:.2f}s wall)"
        ]
        host = report["host_phases_s"]
        lines.append(
            "   host: " + " | ".join(
                f"{k} {v:.3f}s" for k, v in host.items()
            )
            + f" | step p50 {report['host_step_p50_s'] * 1e3:.1f}ms"
        )
        if "device_ms_per_step" in report:
            lines.append(
                f"   device: {report['device_ms_per_step']:.3f} "
                f"ms/step across top ops:"
            )
            for op, ms in report["device_top_ops_ms"].items():
                lines.append(f"     {op[:48]:48s} {ms:8.3f} ms")
        else:
            lines.append(
                f"   device: unavailable — "
                f"{report.get('device_trace_error', 'no trace')}"
            )
        return "\n".join(lines)
