#!/usr/bin/env python3
"""What the program's own on-demand capture costs a live run.

    python3 benchmark/tools/trigger_probe.py --workload <name> --seed <n> \
        --host-tracer off|jax-default --out <json>

ONE ``main_apex`` -> ``fit()`` run of the cell (the same weights, tap and
feed as ``run.py`` drives). Once it is warm the clock thread sends this
process SIGUSR2, as an operator would: the program's ``ProfileTrigger``
traces its next ``DPTPU_OBS_TRACE_STEPS`` (eight) steps, stops the
profiler on the loop thread and writes its merged report. Measured from
outside: the seconds ``start_trace`` and ``stop_trace`` took, the longest
pause between two returns of the step call while the trace was open (the
start-up stall) and the one around ``stop_trace`` and the report (which
the loop thread runs itself), the size of the ``.xplane.pb`` and its
events by plane, and the report's ``device_ms_per_step``.

``--host-tracer off`` is the program as it is (every session it opens
takes ``dptpu.utils.profiling.device_profile_options``: host and Python
tracers off). ``jax-default`` stands jax's own default options in that
helper's place for the run: what the program did before PR 27, when it
passed none.
"""

import argparse
import json
import os
import shutil
import signal
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--host-tracer", choices=("off", "jax-default"),
                        default="off")
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))

    import jax

    from benchmark.lib import cells, drive, tracered
    from benchmark.run import require_chips
    from benchmark.tools.profiler_probe import count_trace
    from dptpu.utils import profiling

    if not hasattr(profiling, "device_profile_options"):
        raise SystemExit("this program opens its profiler sessions without "
                         "options: nothing to probe")
    if args.host_tracer == "jax-default":
        profiling.device_profile_options = jax.profiler.ProfileOptions

    cell = cells.load_cell(args.workload)
    require_chips(cell.chips)
    work = tempfile.mkdtemp(prefix="dptpu_trigger_")
    returns = []  # perf_counter at every return of the step call
    row = {"host_tracer": args.host_tracer}
    stopped = threading.Event()
    start_trace, stop_trace = jax.profiler.start_trace, jax.profiler.stop_trace

    def timed_start(*a, **kw):
        t = time.perf_counter()
        try:
            return start_trace(*a, **kw)
        finally:
            row["start_trace_s"] = time.perf_counter() - t
            options = kw.get("profiler_options")
            row["host_tracer_level"] = getattr(
                options, "host_tracer_level", None)

    def timed_stop():
        t = time.perf_counter()
        try:
            return stop_trace()
        finally:
            row["stop_trace_s"] = time.perf_counter() - t
            row["t_stopped"] = time.perf_counter()
            stopped.set()

    class Tap(drive.StepTap):
        def wrap(self, train_step):
            inner = super().wrap(train_step)

            def step(state, batch):
                out = inner(state, batch)
                returns.append(time.perf_counter())
                return out

            return step

    class Probe(drive._Clock):
        """The harness's clock thread with another errand."""

        def _traced_end(self, t_end):
            time.sleep(3.0)  # a steady loop first
            row["t_signal"] = time.perf_counter()
            os.kill(os.getpid(), signal.SIGUSR2)
            row["captured"] = stopped.wait(1500.0)
            time.sleep(3.0)  # and a steady loop after
            self._signal()

    tap = Tap(cell)
    budget = drive.TailBudget(time.time(), limit_s=3600.0, after_s=0.0)
    probe = Probe(tap, 0.0, budget, os.path.join(work, "unused"),
                  {"trace_read_s": 1.0, "trace_stall_cap_s": 20.0})
    jax.profiler.start_trace, jax.profiler.stop_trace = timed_start, timed_stop
    try:
        drive.run_fit(cell, args.seed, work,
                      drive.make_weights(cell, args.seed),
                      drive.program_template(cell.config), tap, probe)
        capture = os.path.join(work, "obs", "ondemand-000")
        with open(os.path.join(capture, "attribution.json")) as f:
            report = json.load(f)
        row["report"] = {k: report.get(k) for k in (
            "steps", "window_s", "host_phases_s", "host_step_p50_s",
            "device_ms_per_step", "device_trace_error")}
        t0, t1 = row.pop("t_signal"), row.pop("t_stopped")
        t_stop = t1 - row["stop_trace_s"]
        gaps = lambda ts: [b - a for a, b in zip(ts, ts[1:])]  # noqa: E731
        steady = [t for t in returns if t0 - 3.0 <= t < t0]
        tracing = [t for t in returns if t0 <= t <= t_stop]
        # the loop thread runs stop_trace and the report itself: from
        # the last return before it to the first one after
        around = [t for t in returns if t <= t_stop][-1:] + \
            [t for t in returns if t > t1][:1]
        row["steady_pause_s"] = max(gaps(steady), default=None)
        row["longest_pause_while_tracing_s"] = max(gaps(tracing),
                                                   default=None)
        row["pause_around_stop_s"] = max(gaps(around), default=None)
        path = tracered.find_xplane(capture)
        if path:
            row["xplane_bytes"] = os.path.getsize(path)
            counted = count_trace(path)
            row.update(events=counted["events"],
                       device_events=counted["device_events"],
                       planes={n: p["events"]
                               for n, p in counted["planes"].items()})
        print("TRIGGER_PROBE", json.dumps(row), flush=True)
    finally:
        jax.profiler.start_trace, jax.profiler.stop_trace = \
            start_trace, stop_trace
        shutil.rmtree(work, ignore_errors=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload,
                   "device": drive.device_info(), "row": row}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
