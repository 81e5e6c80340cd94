#!/usr/bin/env python3
"""What each profiler setting records and costs, under a cell's own loop.

    python3 benchmark/tools/profiler_probe.py --workload <name> --seed <n> \
        --settings host1,host0,host0+TRACE_ONLY_XLA --traced 2.0 --out <json>

ONE ``main_apex`` -> ``fit()`` run of the cell (the same weights, tap and
feed as ``run.py`` drives); once it is warm a thread opens and stops the
profiler once per setting while the loop runs on, with a marker program
behind the start-up stall and one before the stop. After the loop has
ended each trace is counted: events by plane and line, the marker
modules, the seconds traced, the device's longest idle gaps, the longest
pause between two returns of the step call (the dispatch stall), the
seconds ``stop_trace`` took and the size of the ``.xplane.pb``. The table
in PERF.md §6 is this tool's output. A setting is ``host<level>`` with an
optional ``+<tpu_trace_mode>``.
"""

import argparse
import collections
import json
import os
import re
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def profile_options(setting: str):
    from benchmark.lib import drive

    host, _, mode = setting.partition("+")
    opts = drive.trace_options()
    opts.host_tracer_level = int(host[len("host"):])
    if mode:
        opts.advanced_configuration = {"tpu_trace_mode": mode}
    return opts


def count_trace(path: str) -> dict:
    """Events by plane and by line (thread numbers stripped), the module
    names, and the longest idle gaps of each device plane."""
    from jax.profiler import ProfileData

    from benchmark.lib import tracered

    planes, modules, devices = {}, collections.Counter(), {}
    for plane in ProfileData.from_file(path).planes:
        lines = collections.Counter()
        ops = []
        on_device = bool(tracered.DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            group = re.sub(r"[/_-]?\d+$", "", line.name)
            for e in line.events:
                lines[group] += 1
                if on_device and line.name == tracered.MODULE_LINE:
                    modules[re.sub(r"\(\d+\)$", "", e.name)] += 1
                if on_device and line.name == tracered.OP_LINE:
                    ops.append((int(e.start_ns),
                                int(e.start_ns + e.duration_ns)))
        planes[plane.name] = {"events": sum(lines.values()),
                              "lines": dict(lines.most_common(8))}
        if ops:
            busy = tracered.union(ops)
            lo, hi = busy[0][0], busy[-1][1]
            longest = sorted(tracered.gaps(busy, lo, hi),
                             key=lambda g: g[0] - g[1])[:3]
            devices[plane.name] = {
                "traced_s": (hi - lo) / 1e9,
                "busy_s": tracered.total(busy) / 1e9,
                "longest_gaps_s": [[(s - lo) / 1e9, (e - s) / 1e9]
                                   for s, e in longest]}
    return {"planes": planes, "modules": dict(modules), "devices": devices,
            "events": sum(p["events"] for p in planes.values()),
            "device_events": sum(
                p["events"] for n, p in planes.items()
                if tracered.DEVICE_PLANE.match(n))}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--settings", default="host1,host0")
    parser.add_argument("--traced", type=float, default=2.0)
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))

    import jax

    from benchmark.lib import cells, drive, tracered
    from benchmark.run import require_chips

    cell = cells.load_cell(args.workload)
    require_chips(cell.chips)
    work = tempfile.mkdtemp(prefix="dptpu_probe_")
    returns = []  # perf_counter at every return of the step call

    class Tap(drive.StepTap):
        def wrap(self, train_step):
            inner = super().wrap(train_step)

            def step(state, batch):
                out = inner(state, batch)
                returns.append(time.perf_counter())
                return out

            return step

    class Probe(drive._Clock):
        """The harness's clock thread with another errand: its markers,
        its bounded waits, its way of ending the run."""

        rows = []

        def _one(self, k: int, setting: str) -> dict:
            row = {"setting": setting}
            self.rows.append(row)
            directory = os.path.join(work, f"trace{k}")
            self.marker_walls.clear()
            calls = self.tap.calls
            t0 = time.perf_counter()
            try:
                jax.profiler.start_trace(
                    directory, profiler_options=profile_options(setting))
            except Exception as exc:  # a mode this runtime does not know
                row["error"] = repr(exc)
                return row
            t1, wall1 = time.perf_counter(), time.time()
            row["marker_ran"] = self._behind_the_stall(calls)
            if row["marker_ran"]:
                row["marker_after_start_s"] = \
                    self.marker_walls[tracered.MARKER_OPEN] - wall1
            time.sleep(self.keep_s)
            self._marker_ran(tracered.MARKER_CLOSE, self.stall_cap_s)
            t3 = time.perf_counter()
            jax.profiler.stop_trace()
            t4 = time.perf_counter()
            inside = [t for t in returns if t0 <= t <= t3]
            pauses = [b - a for a, b in zip(inside, inside[1:])]
            row.update(start_trace_s=t1 - t0, open_s=t3 - t0,
                       stop_trace_s=t4 - t3, step_returns=len(inside),
                       longest_pause_between_returns_s=max(pauses,
                                                           default=None),
                       directory=directory)
            return row

        def _traced_end(self, t_end):
            for k, setting in enumerate(args.settings.split(",")):
                time.sleep(2.0)  # the loop recovers from the last stop
                self._one(k, setting)
            self._signal()

    tap = Tap(cell)
    # no run's limit holds here: one stop_trace at level 1 is minutes
    budget = drive.TailBudget(time.time(), limit_s=3600.0, after_s=0.0)
    probe = Probe(tap, 0.0, budget, os.path.join(work, "trace"),
                  {"trace_read_s": args.traced - drive.TRACE_MARGIN_S,
                   "trace_stall_cap_s": 20.0})
    try:
        drive.run_fit(cell, args.seed, work, drive.make_weights(cell, args.seed),
                      drive.program_template(cell.config), tap, probe)
        for row in probe.rows:
            directory = row.pop("directory", None)
            path = tracered.find_xplane(directory) if directory else None
            if path is None:
                row.setdefault("error", "no .xplane.pb")
                continue
            row["xplane_bytes"] = os.path.getsize(path)
            t = time.perf_counter()
            row.update(count_trace(path))
            row["count_s"] = time.perf_counter() - t
            print("PROBE", json.dumps(row), flush=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "traced_s": args.traced,
                   "device": drive.device_info(), "rows": probe.rows}, f,
                  indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
