"""Drive one cell once: seeded weights, ``main_apex`` -> ``fit()``, the
window, the fence, the comparison, the result line.

The trainer runs as a user runs it: ``dptpu.cli.main_apex`` in this
process, ``WORLD_SIZE=1``, the arguments that the configuration and the
traffic file state (``fit_argv``), the program's defaults for whatever
they do not. What belongs to one kind of data, one family of models or
one optimizer is not in this file: the traffic file's ``data`` names the
feed (``feeds/``), the configuration's ``reference`` the family and its
``optimizer.name`` the optimizer (``reference/``, ``reference/
optimizers/``), each found by ``cells``. The harness adds three things
around the trainer and edits nothing:

* **weights from the seed.** The apex CLI has no ``--seed``; what a user
  can hand it is weights (``--pretrained`` with ``DPTPU_PRETRAINED_DIR``).
  The reference module makes a torchvision-layout state dict from
  ``--seed`` on the device in one jitted call; the program's own
  converter (``convert_state_dict`` / ``save_npz``, what
  ``python -m dptpu.tools.convert_torchvision`` calls) writes it where
  ``--pretrained`` looks. ``--seed`` also picks the feed's number of rows
  (``dataset_images + seed % 128``), so each seed sees its own rows in
  its own order.
* **a tap on the step**, at the seam between ``fit()`` and its loop
  (``train_one_epoch(state, train_step, batches, ...)``): the wrapped
  ``train_step`` copies out, during the first ``check_steps`` calls, the
  delivered batch (by the feed's keys), the step's loss, the first
  gradient as the optimizer got it (where the optimizer's module finds it
  in the state after the first step) and the parameters after the last,
  then counts calls. The same step, state and feed go on into the window.
* **a clock thread**: once ``warmup_iters`` calls have returned it waits
  ``--seconds``, then sends this process SIGTERM. That is the trainer's
  documented preemption path: the loop finishes the step in flight,
  leaves, and fetches the pending metrics (the fence). In a traced run the
  thread also holds a ``jax.profiler`` trace open over the window's last
  seconds: device planes only, the stretch that is read bounded by two
  marker programs, every wait cut from one limit (``TailBudget``).
"""

from __future__ import annotations

import contextlib
import functools
import importlib
import json
import math
import os
import shlex
import shutil
import signal
import socket
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..reference import common as reference_common
from . import cells, check, spans as spans_mod, tracered

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SETUP_LIMIT_S = 1100.0  # a cold first run may compile for many minutes
# The driver stops a run 360 s after its process started (ledger, PR 25:
# ``run_timed_out``). Every wait of a traced run is cut from this one
# number: ``TailBudget``.
RUN_LIMIT_S = 360.0
# What a traced run still has to do once it has told the profiler to stop,
# in a run that compiles its reference (chip runs, PR 25 and PR 26):
# stop_trace and the reduction (seconds, device planes only), the read-out
# (5-10 s), the reference (77-94 s where it compiles, 9-30 s warm).
AFTER_TRACE_S = 120.0
# stop_trace cannot be interrupted: the join waits this long at least
STOP_TRACE_FLOOR_S = 30.0
# The trace stays open this much longer than the stretch that is read, so
# that the reduction has a second to move the stretch off a stall.
TRACE_MARGIN_S = 1.0


class CompileMeter:
    """Backend-compile seconds (a cache load counts) with the wall time of
    each, from jax's own monitoring events — as ``chip_smoke.py`` meters
    them."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.events: List[tuple] = []  # (wall time at end, seconds)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, seconds, **_):
        if event == _COMPILE_EVENT:
            self.events.append((time.time(), float(seconds)))

    def seconds_before(self, wall: float) -> float:
        return sum(s for t, s in self.events if t <= wall)

    def count_between(self, lo: float, hi: float) -> int:
        return sum(1 for t, _ in self.events if lo < t <= hi)


def _flat(tree) -> Dict[str, np.ndarray]:
    """A params-shaped pytree as ``{"a/b/c": array}``."""
    return {"/".join(str(getattr(k, "key", k)) for k in path): np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


class StepTap:
    """Wraps the loop's ``train_step``; see the module docstring. The
    cell gives the checked steps and the warm-up (its traffic), the keys
    of a batch (its feed) and where the first gradient sits in the
    program's optimizer state (its optimizer)."""

    def __init__(self, cell):
        self.check_steps = int(cell.traffic["check_steps"])
        self.warmup_iters = int(cell.traffic["warmup_iters"])
        if self.warmup_iters < self.check_steps:
            raise ValueError("warm-up must cover the checked steps")
        self.keys = tuple(cell.feed.KEYS)
        self.program_trace1 = cell.optimizer.program_trace1
        self.calls = 0
        self.batches: List[tuple] = []
        self.losses: List[float] = []
        self.trace1: Optional[Dict[str, np.ndarray]] = None
        self.params_after: Optional[Dict[str, np.ndarray]] = None
        self.warm = threading.Event()
        self.step_fn = None    # the loop's own jitted step
        self.step_avals = None  # shapes and placements of its arguments

    def wrap(self, train_step):
        self.step_fn = train_step

        def step(state, batch):
            i = self.calls
            checked = i < self.check_steps
            if i == 0:
                self.step_avals = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape, x.dtype, sharding=x.sharding),
                    (state, batch))
            if checked:
                host = jax.device_get(batch)
                self.batches.append(tuple(np.asarray(host[k])
                                          for k in self.keys))
            out = train_step(state, batch)
            if checked:
                new_state, metrics = out
                self.losses.append(float(jax.device_get(metrics["loss"])))
                if i == 0:
                    self.trace1 = _flat(jax.device_get(
                        self.program_trace1(new_state.opt_state)))
                if i == self.check_steps - 1:
                    self.params_after = _flat(
                        jax.device_get(new_state.params))
            self.calls = i + 1
            if self.calls == self.warmup_iters:
                self.warm.set()
            return out

        return step


@contextlib.contextmanager
def _environ(**overrides):
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@contextlib.contextmanager
def _tapped_loop(tap: StepTap):
    """``fit()`` looks ``train_one_epoch`` up in its own module: stand a
    wrapper there for the length of one run."""
    # import_module gives the module; the attribute dptpu.train.fit is the
    # function that dptpu.train re-exports under the same name
    fit_module = importlib.import_module("dptpu.train.fit")
    original = fit_module.train_one_epoch

    def train_one_epoch(state, train_step, batches, **kwargs):
        return original(state, tap.wrap(train_step), batches, **kwargs)

    fit_module.train_one_epoch = train_one_epoch
    try:
        yield
    finally:
        fit_module.train_one_epoch = original


class TailBudget:
    """The seconds a traced run may still wait before it has to tell the
    profiler to stop: ``limit_s`` after the process started, less what
    the run still has to do after that (``after_s``). Every wait of the
    clock thread goes through ``wait``, cut by its own cap AND by what is
    left here; the join on the clock thread waits ``to_limit``. So a
    profiler that stalls for longer than in any run so far, or a set-up
    that already used the time up, costs the run device metrics and never
    its result line. ``clock`` and ``sleep`` are the host's; a test hands
    in its own."""

    def __init__(self, started_wall: float, limit_s: float = RUN_LIMIT_S,
                 after_s: float = AFTER_TRACE_S, clock=time.time,
                 sleep=time.sleep):
        self.started_wall = started_wall
        self.limit_s, self.after_s = limit_s, after_s
        self.clock, self.sleep = clock, sleep

    def left(self) -> float:
        """Until ``stop_trace`` has to have been called."""
        return self.to_limit() - self.after_s

    def to_limit(self) -> float:
        return self.started_wall + self.limit_s - self.clock()

    def wait(self, ready, cap_s: float, poll_s: float = 0.001) -> bool:
        """Poll ``ready`` until it says yes (True), or ``cap_s`` have
        passed, or the budget is spent (False): never longer."""
        deadline = self.clock() + min(cap_s, self.left())
        while not ready():
            if self.clock() >= deadline:
                return False
            self.sleep(poll_s)
        return True


def trace_options():
    """Device planes only. At ``host_tracer_level`` 1 the runtime's own
    threads (``pjrt-tpu-tasks/<tid>``) put a million events a second on
    the host plane, 98% of the trace, and ``stop_trace`` pays ~29 us for
    each (PERF.md, Findings, PR 26); the reduction reads none of them."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def _marker_program(name: str, constant: int):
    """A program of one scalar operation whose module the trace shows as
    ``jit_<name>``: the reduction finds it by that name. Each marker adds
    a ``constant`` of its own, an odd one: the runtime knows a program by
    what it computes, and two that compute the same show under the name
    of the first (both markers read ``jit_bench_marker_open`` when both
    added 1; chip run, PR 26)."""
    def marker(x):
        return x + constant

    marker.__name__ = marker.__qualname__ = name
    return jax.jit(marker)


class _Clock(threading.Thread):
    """Closes the window from outside; traces the last seconds of it if
    asked."""

    def __init__(self, tap: StepTap, seconds: float, budget: TailBudget,
                 trace_dir: str = None, traffic: dict = None):
        super().__init__(name="bench-clock", daemon=True)
        self.tap, self.seconds, self.budget = tap, seconds, budget
        self.trace_dir = trace_dir
        if trace_dir:
            self.keep_s = float(traffic["trace_read_s"]) + TRACE_MARGIN_S
            self.stall_cap_s = float(traffic["trace_stall_cap_s"])
        self.done = threading.Event()
        # time.time() of: trace_open (before start_trace), stretch_open
        # (the open marker seen), stop_begin, stop_end
        self.marks: Dict[str, float] = {}
        self.marker_walls: Dict[str, float] = {}  # marker -> seen ready
        self.signalled = False
        self.error: Optional[BaseException] = None

    def _sleep_until(self, t: float) -> bool:
        """False if the run ended first."""
        return not self.done.wait(max(t - time.perf_counter(), 0.0))

    def _signal(self):
        self.signalled = True
        os.kill(os.getpid(), signal.SIGTERM)

    def _marker_ran(self, name: str, cap_s: float) -> bool:
        """Enqueue marker ``name`` and watch for its result, ``cap_s`` at
        most. The wall time at which it is seen ready is the end of its
        module event in the trace, plus the poll."""
        out = self._markers[name](self._marker_arg)

        def ready():
            if out.is_ready():
                self.marker_walls.setdefault(name, time.time())
                return True
            return self.done.is_set()

        self.budget.wait(ready, cap_s, poll_s=0.0002)
        return name in self.marker_walls

    def _behind_the_stall(self, calls_at_start: int) -> bool:
        """Starting the TPU profiler stalls the next dispatch and, once
        the steps queued before it have drained, the device (one to four
        seconds together). The stretch opens once two more calls of the
        step have returned AND the open marker, enqueued after them, has
        run: the device executes in order, so the stall lies behind it.
        False if that takes longer than ``trace_stall_cap_s`` or than the
        budget leaves, or the run ended first: the caller then closes
        the trace at once."""
        t = self.budget.clock()
        resumed = self.budget.wait(
            lambda: self.done.is_set()
            or self.tap.calls >= calls_at_start + 2, self.stall_cap_s)
        if not resumed or self.done.is_set():
            return False
        spent = self.budget.clock() - t
        return self._marker_ran(tracered.MARKER_OPEN,
                                self.stall_cap_s - spent) \
            and not self.done.is_set()

    def _traced_end(self, t_end: float):
        """The profiler over the window's last seconds: opened
        ``trace_read_s`` and a margin before the window's end, kept open
        that long behind the open marker (or as long as the budget
        leaves), closed behind the close marker. The reduction takes the last clean
        ``trace_read_s`` between the two. A traced window ends when the
        trace does; ``stop_trace`` runs after the window is closed."""
        if not self._sleep_until(t_end - self.keep_s):
            return
        self.marks["trace_open"] = time.time()
        calls = self.tap.calls
        jax.profiler.start_trace(self.trace_dir,
                                 profiler_options=trace_options())
        started = time.time()
        try:
            if self._behind_the_stall(calls):
                self.marks["stretch_open"] = \
                    self.marker_walls[tracered.MARKER_OPEN]
                self.done.wait(max(min(self.keep_s, self.budget.left()), 0.0))
                if not self.done.is_set():
                    self._marker_ran(tracered.MARKER_CLOSE, self.stall_cap_s)
            if not self.done.is_set():
                self._signal()  # the window closes; the trace is written after
        finally:
            self.marks["stop_begin"] = time.time()
            jax.profiler.stop_trace()
            self.marks["stop_end"] = time.time()
        m = self.marks
        if "stretch_open" in m:
            opened = (f"opened {m['stretch_open'] - started:.2f} s after it, "
                      f"kept {m['stop_begin'] - m['stretch_open']:.2f} s")
        else:
            opened = (f"never opened (no marker within "
                      f"{min(self.stall_cap_s, m['stop_begin'] - started):.1f}"
                      f" s)")
        print(f"benchmark: start_trace {started - m['trace_open']:.2f} s, "
              f"stretch {opened}, stop_trace "
              f"{m['stop_end'] - m['stop_begin']:.2f} s", file=sys.stderr)

    def run(self):
        try:
            if self.trace_dir:
                # compiled and run once during set-up, never in the window
                self._markers = {
                    name: _marker_program(name, 26001 + 2 * k)
                    for k, name in enumerate((tracered.MARKER_OPEN,
                                              tracered.MARKER_CLOSE))}
                self._marker_arg = jnp.zeros((), jnp.int32)
                for marker in self._markers.values():
                    marker(self._marker_arg).block_until_ready()
            if not self.tap.warm.wait(SETUP_LIMIT_S) or self.done.is_set():
                return
            t_end = time.perf_counter() + self.seconds
            if self.trace_dir:
                # what comes before the profiler opens is untouched by it:
                # a traced run's host-span metrics are read from that part
                self._traced_end(t_end)
            elif self._sleep_until(t_end):
                self._signal()
        except BaseException as exc:  # surfaced by run_fit, never lost
            self.error = exc
            os.kill(os.getpid(), signal.SIGTERM)


def program_template(config: dict):
    """Shapes of the program's ``{"params", "batch_stats"}`` for
    ``config`` — no weights are made. The configuration says how the
    program's model is made (``arch``, ``create_kwargs``), its family what
    one row of input looks like."""
    from dptpu.models import create_model

    model = create_model(config["arch"], **config["create_kwargs"])
    example = cells.reference(config).example_input
    return jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), example(config["model"]), train=False))


def to_program_layout(config: dict, template, named: Dict[str, np.ndarray],
                      fill: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Torch-named leaves through the program's public import path, as
    ``{"a/b/c": array}`` of its ``params``. ``fill`` supplies the leaves
    (buffers) that ``named`` lacks, which the converter insists on."""
    from dptpu.models.pretrained import convert_state_dict

    full = {k: np.zeros_like(v) for k, v in fill.items()}
    full.update(named)
    return _flat(convert_state_dict(config["arch"], full, template)["params"])


def write_pretrained(config: dict, template, weights: Dict[str, np.ndarray],
                     directory: str) -> None:
    """``weights`` where ``--pretrained`` looks, by the program's own
    converter (what ``dptpu.tools.convert_torchvision`` calls)."""
    from dptpu.models.pretrained import convert_state_dict, save_npz

    os.makedirs(directory, exist_ok=True)
    save_npz(os.path.join(directory, f"{config['arch']}.npz"),
             convert_state_dict(config["arch"], weights, template))


def dataset_images(traffic: dict, seed: int) -> int:
    """The feed's number of rows: up to 127 more than the traffic
    file's, by the seed. The epoch's order depends on N; the steps per
    epoch, which the learning-rate schedule bakes into the step program,
    do not (the base is whole steps of 128 and of 512 rows less 384)."""
    return int(traffic["dataset_images"]) + int(seed) % 128


def fit_argv(cell, num_rows: int) -> List[str]:
    """The trainer's command line: the feed's argument, the architecture,
    the precision, the batch, the scaled rate, the optimizer's arguments,
    then what the traffic file adds."""
    cfg, traffic = cell.config, cell.traffic
    # the apex CLI scales --lr by global_batch / 256
    lr = float(traffic["effective_lr"]) * 256.0 / cell.global_batch
    argv = [cell.feed.argument(num_rows), "-a", cfg["arch"],
            "--opt-level", cfg["precision"]["opt_level"],
            "-b", str(cfg["per_chip_batch"]), "--lr", repr(lr),
            *cell.optimizer.argv(cfg["optimizer"]),
            "--start-epoch", str(traffic["start_epoch"]), "--pretrained"]
    return argv + [str(a) for a in traffic.get("extra_argv", [])]


def run_fit(cell, seed: int, work: str, weights: Dict[str, np.ndarray],
            template, tap: StepTap, clock: _Clock):
    """One ``main_apex`` call with the tap and the clock; returns
    ``(result, obs_log_path)``."""
    from dptpu.cli import main_apex

    obs_dir = os.path.join(work, "obs")
    write_pretrained(cell.config, template, weights,
                     os.path.join(work, "pretrained"))
    argv = fit_argv(cell, dataset_images(cell.traffic, seed))
    print(f"benchmark: main_apex {shlex.join(argv)}", file=sys.stderr)
    cwd = os.getcwd()
    os.chdir(work)  # checkpoints and runs/ land in the scratch directory
    clock.start()
    try:
        with _environ(WORLD_SIZE="1", DPTPU_OBS_DIR=obs_dir,
                      DPTPU_PRETRAINED_DIR=os.path.join(work, "pretrained")), \
                _tapped_loop(tap), contextlib.redirect_stdout(sys.stderr):
            result = main_apex(argv)
    finally:
        clock.done.set()
        # a traced run is still writing its trace: to the run's limit, no
        # longer (stop_trace cannot be cut short; it gets its floor)
        clock.join(timeout=max(clock.budget.to_limit(), STOP_TRACE_FLOOR_S))
        os.chdir(cwd)
    if clock.is_alive():
        raise RuntimeError("the clock thread did not end: stop_trace "
                           "outlasted the run's limit")
    if clock.error is not None:
        raise RuntimeError(f"the clock thread failed: {clock.error!r}")
    if not clock.signalled or not result.get("preempted"):
        raise RuntimeError(
            "fit() returned before the window was closed from outside "
            f"(signalled={clock.signalled}, "
            f"preempted={result.get('preempted')}): the epoch is shorter "
            f"than the window, or set-up outlasted {SETUP_LIMIT_S:.0f} s")
    return result, os.path.join(obs_dir, f"obs-{socket.gethostname()}.jsonl")


def reference_run(cell, weights, batches, mode: str = "f32") -> dict:
    """The plain reference over ``batches`` (the rows the benchmark
    regenerated itself, one tuple a step in the order of the feed's
    keys), from ``weights``: the family's loss under the optimizer's
    plain update. The family's names."""
    cfg, family = cell.config, cell.family
    keys = cell.feed.KEYS
    return reference_common.train_steps(
        functools.partial(family.loss, cfg["model"]), cell.optimizer,
        cfg["optimizer"], family.trainable(cfg["model"]), weights,
        [dict(zip(keys, arrays)) for arrays in batches],
        lr=float(cell.traffic["effective_lr"]),
        block_rows=int(cfg["reference_block_rows"]), mode=mode)


def regenerate_batches(cell, seed: int):
    """The checked steps' rows as the feed should have delivered them:
    one tuple a step, one array per key of the feed."""
    traffic, feed = cell.traffic, cell.feed
    order = feed.epoch_order(dataset_images(traffic, seed),
                             int(traffic["sampler_seed"]),
                             int(traffic["start_epoch"]))
    return [feed.batch(order, k, cell.global_batch, cell.config["model"])
            for k in range(int(traffic["check_steps"]))]


def make_weights(cell, seed: int) -> Dict[str, np.ndarray]:
    made = reference_common.make_weights(
        cell.family.weight_spec(cell.config["model"]), seed)
    return {k: np.asarray(v) for k, v in jax.device_get(made).items()}


def count_nonfinite(params) -> int:
    flags = jax.jit(lambda t: [jnp.logical_not(jnp.all(jnp.isfinite(x)))
                               for x in jax.tree_util.tree_leaves(t)])(params)
    return int(sum(bool(f) for f in jax.device_get(flags)))


def allocator_peak_bytes() -> int:
    """``peak_bytes_in_use`` of the fullest chip: arrays the process held
    (state, batches in flight). On this runtime it leaves out the
    temporaries a running program takes (a ResNet-50 step at 128 rows
    reads 0.37 GB; chip run, PR 24)."""
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def step_temp_bytes(tap: StepTap) -> int:
    """Per-chip temporaries of the very step the window drove, from the
    compiler's own account of it (``memory_analysis`` of the loop's jitted
    step at the shapes and placements it was called with; the program is
    served from the compile cache)."""
    if tap.step_fn is None or tap.step_avals is None \
            or not hasattr(tap.step_fn, "lower"):
        return 0
    analysis = tap.step_fn.lower(*tap.step_avals).compile().memory_analysis()
    return int(getattr(analysis, "temp_size_in_bytes", 0) or 0)


def device_info() -> dict:
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def program_record(cell, tap: StepTap, weights, template, nonfinite: int,
                   regenerated) -> dict:
    """What the timed path produced, as ``check.compare`` wants it."""
    if tap.trace1 is None or tap.params_after is None \
            or len(tap.losses) != tap.check_steps:
        raise RuntimeError("the tap did not see the checked steps")
    start = to_program_layout(cell.config, template, weights, weights)
    delta = {k: tap.params_after[k].astype(np.float64) - start[k]
             for k in start}
    return {"loss": tap.losses, "trace1": tap.trace1, "delta": delta,
            "feed_mismatch": check.feed_mismatch(tap.batches, regenerated),
            "nonfinite": nonfinite}


def host_metrics(cell, win, meter: CompileMeter, started_wall: float) -> dict:
    """Every number the host's clock and the span log give, by name; the
    readers pick theirs."""
    compile_s = meter.seconds_before(win.t_start)
    loop_entry_compile = meter.seconds_before(win.t_first_iter)
    return {
        "train_img_s_chip": spans_mod.images_per_second_per_chip(
            win, cell.global_batch, cell.chips),
        "step_ms_p95": spans_mod.step_ms_p95(win),
        "setup_s": win.t_start - started_wall,
        "compile_s": compile_s,
        # process start to loop entry, less the compile seconds in it
        "init_s": (win.t_first_iter - started_wall) - loop_entry_compile,
        "window_s": win.seconds,
        "window_steps": win.steps,
        "compiles_in_window": meter.count_between(win.t_start, win.t_end),
    }


def read_trace(work: str, clock: _Clock, read_s: float, max_gap_s: float,
               host: dict):
    """The reduced trace of a traced run (None where the profiler wrote
    nothing to read), with what the trace cost into ``host``."""
    xplane = tracered.find_xplane(os.path.join(work, "trace"))
    if xplane is None:
        return None
    host["xplane_bytes"] = os.path.getsize(xplane)
    reduced = tracered.reduce_trace(tracered.load_xplane(xplane),
                                    clock.marker_walls, read_s, max_gap_s)
    if reduced:
        host["trace_events"] = reduced["events_read"]
        host["trace_modules"] = reduced["modules"]
    return reduced


def phases(started_wall: float, win, clock: _Clock, t_reference: float,
           host: dict) -> dict:
    """Where the run's seconds went, in order, without overlap: they add
    up to ``total`` (process start to the result) but for the comparison
    and the printing. ``window`` is the untraced part of a traced run's
    window; ``stop_trace`` runs beside the fence and ``fit()``'s return;
    ``readout`` is the rest between the window (or ``stop_trace``) and
    the reference: ``fit()``'s return, span log, memory, the count of
    non-finite leaves, the regenerated rows."""
    m = clock.marks
    out = {"setup": win.t_start - started_wall}
    if "trace_open" in m:
        opened = m.get("stretch_open", m["stop_begin"])
        out.update(window=m["trace_open"] - win.t_start,
                   trace_open_to_stretch=opened - m["trace_open"],
                   traced=m["stop_begin"] - opened,
                   stop_trace=m["stop_end"] - m["stop_begin"],
                   load_reduce=host.get("load_reduce_s", 0.0))
        after = m["stop_end"] + out["load_reduce"]
    else:
        out["window"] = win.t_end - win.t_start
        after = win.t_end
    out.update(readout=t_reference - after, reference=host["reference_s"],
               total=time.time() - started_wall)
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool,
             started_wall: float, tap_factory=StepTap) -> dict:
    """Everything of one run but the look for a chip; returns the result
    object (its ``numbers`` come last)."""
    traffic = cell.traffic
    meter = CompileMeter()
    work = tempfile.mkdtemp(prefix="dptpu_bench_")
    try:
        template = program_template(cell.config)
        weights = make_weights(cell, seed)
        tap = tap_factory(cell)
        clock = _Clock(tap, seconds, TailBudget(started_wall),
                       os.path.join(work, "trace") if trace else None,
                       traffic)
        result, log = run_fit(cell, seed, work, weights, template, tap, clock)
        all_spans = spans_mod.read_log(log)
        win = spans_mod.window(all_spans, int(traffic["warmup_iters"]))
        untraced = win
        if "trace_open" in clock.marks:
            untraced = spans_mod.before(win, clock.marks["trace_open"])
        host = host_metrics(cell, win, meter, started_wall)
        held = allocator_peak_bytes()
        nonfinite = count_nonfinite(result["state"].params)
        temps = step_temp_bytes(tap) if held else 0
        device = dict(device_info(), memory_peak_bytes=held + temps)
        host["allocator_peak_bytes"], host["step_temp_bytes"] = held, temps
        reduced = None
        if trace:
            t_load = time.perf_counter()
            # a stall: a device idle for longer than two ordinary
            # iterations of the untraced window at a time
            iter_p50_s = spans_mod.percentile(
                [s["dur_s"] for s in untraced.iters], 50.0)
            reduced = read_trace(work, clock, float(traffic["trace_read_s"]),
                                 2.0 * iter_p50_s, host)
            host["load_reduce_s"] = time.perf_counter() - t_load
        # the program's state goes before the reference comes: the peak is
        # read, and float32 at the timed batch wants the room
        del result
        jax.clear_caches()
        regenerated = regenerate_batches(cell, seed)
        program = program_record(cell, tap, weights, template, nonfinite,
                                 regenerated)
        t_reference = time.time()
        ref_named = reference_run(cell, weights, regenerated)
        reference = {
            "loss": ref_named["loss"],
            "trace1": to_program_layout(cell.config, template,
                                        ref_named["trace1"], weights),
            "delta": to_program_layout(cell.config, template,
                                       ref_named["delta"], weights),
        }
        verdict = check.compare(program, reference, cell.config["limits"])
        host["reference_s"] = time.time() - t_reference
    finally:
        shutil.rmtree(work, ignore_errors=True)
    host["phases_s"] = phases(started_wall, win, clock, t_reference, host)
    return assemble(cell, trace, host, device, reduced, win, untraced, verdict)


def assemble(cell, trace: bool, host: dict, device: dict, reduced, win,
             untraced, verdict: dict) -> dict:
    """The result object: ``end_to_end`` metrics untraced, ``per_layer``
    metrics traced (each from its own reader; one that finds nothing to
    read is left out). ``untraced`` is the part of the window ``win`` that
    the profiler did not touch: the readers' spans."""
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": host[m["name"]], "unit": m["unit"]}
    else:
        context = {"cell": cell, "host": host, "device": device,
                   "trace": reduced, "window": untraced,
                   "peaks": cells.peaks(device["kind"])
                   if device["platform"] == "tpu" else None}
        for m in cell.per_layer:
            value = cells.reader(m["name"]).read(context)
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {
        "correct": bool(verdict["correct"]),
        "attempted": host["window_steps"],
        "failed": host["window_steps"]
        if verdict["numbers"]["nonfinite"]["value"] else 0,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = reduced["busy_s"] if reduced else 0.0
        device["window_s"] = reduced["window_s"] if reduced else 0.0
        if reduced:
            device["trace_stretch"] = reduced["stretch"]
            out["breakdown"] = {
                "device_ops": reduced["top_ops"],
                "idle_gaps": tracered.idle_gaps(
                    reduced["busiest"], list(win.spans),
                    reduced.get("offset_s")),
            }
    out["window"] = {k: host[k] for k in (
        "window_s", "window_steps", "compiles_in_window", "reference_s",
        "allocator_peak_bytes", "step_temp_bytes", "xplane_bytes",
        "trace_events", "trace_modules", "phases_s") if k in host}
    out["worst_leaf"] = verdict["worst_leaf"]
    out["numbers"] = verdict["numbers"]
    return out


def print_numbers(numbers: dict, stream=sys.stderr):
    """Each number compared beside its limit, as the last lines."""
    for name, n in numbers.items():
        mark = "ok" if n["value"] <= n["limit"] else "OVER"
        print(f"compared {name}: value={n['value']!r} limit={n['limit']!r} "
              f"{mark}", file=stream)
    stream.flush()


def dumps(result: dict) -> str:
    return json.dumps(result, separators=(", ", ": "))
