#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that dptpu still starts on the chip.

Drives the normal entry points once, in ONE process (a chip belongs to
one process at a time), at the full width of ResNet-50:

* **train** — ``dptpu.cli.main_apex`` (``WORLD_SIZE=1``, ``synthetic:<N>``,
  ``--opt-level O2``, ``-b 128`` per chip, 224 px, 1000 classes, bf16)
  for 40 optimizer steps plus the validation passes, once with thread
  workers and once with ``DPTPU_WORKERS_MODE=process`` (spawn workers,
  leased zero-copy slots, the ``block_until_ready`` H2D gate). The loss
  must be finite and fall; the state must live on TPU devices. The
  second pass compiles the same programs, so its compile seconds are
  the warm-cache reading next to the first pass's cold one.
* **serve** — ``dptpu.cli.main(['serve', '-a', 'resnet50', '--selftest',
  '32'])`` at the default bucket ladder and 224 px; 32 completed, 0
  failed, from the returned stats.
* **mesh** (only when more than one device is visible) — the train phase
  already ran over the default mesh; this adds the batch/params placement
  check and the **update-parity** check: one fp32 optimizer step of
  resnet18 on the mesh vs ``--gpu 0`` at the same global batch and seed,
  comparing ``||params_after - params_before||``. A gradient reduced
  twice moves the params N times too far on N chips; the ratio must sit
  inside ``PARITY_BOUNDS``. ``DPTPU_ZERO1=1``, ``--accum-steps 2`` and
  the two together take one step each under the same bound.

It refuses to run where ``jax.default_backend()`` is not ``"tpu"`` and
exits non-zero when any phase fails; no phase failure is caught and
turned into exit 0. The last stdout line is the result object
``{"ok": true, "device": {...}}``, printed only on success.

    python chip_smoke.py        # from the checkout root, on the chip
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import sys
import tempfile
import time
import traceback

ARCH = "resnet50"
PER_CHIP_BATCH = 128
STEPS_PER_EPOCH = 10
EPOCHS = 4
# LR after the apex x(global batch / 256) scaling, at any chip count.
# Synthetic data is noise with random labels — a memorization task — and
# the default (0.05 on one chip) is unstable on it without warmup: epoch
# losses 7.07, 6.95, 7.25 (chip run, PR 21). The smoke needs a loss that
# falls, not a recipe.
EFFECTIVE_LR = 0.01
SELFTEST_REQUESTS = 32
# update-parity: resnet18/fp32, this many images per chip on the mesh
PARITY_ARCH = "resnet18"
PARITY_PER_CHIP = 32
# ||dparams(mesh)|| / ||dparams(one device)||: per-replica BatchNorm
# moves it a few percent off 1; a doubled reduction puts it at N >= 2
PARITY_BOUNDS = (0.8, 1.25)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class SmokeFailure(AssertionError):
    """A phase ran to the end and what came out is wrong."""


def check(cond, message: str) -> None:
    """``assert`` that survives ``python -O``."""
    if not cond:
        raise SmokeFailure(message)


def update_norm(before, after) -> float:
    """Global L2 norm of ``after - before`` over two param pytrees."""
    import jax
    import numpy as np

    total = 0.0
    for b, a in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        d = np.asarray(a, np.float64) - np.asarray(b, np.float64)
        total += float((d * d).sum())
    return math.sqrt(total)


def check_update_parity(before, after, after_reference, what: str) -> float:
    """The update-parity check: ``after`` (the path under test) must have
    moved the params as far as ``after_reference`` (one device, same
    global batch, same seed) did, within ``PARITY_BOUNDS``."""
    ref = update_norm(before, after_reference)
    check(ref > 0 and math.isfinite(ref),
          f"{what}: the single-device reference update norm is {ref}")
    ratio = update_norm(before, after) / ref
    lo, hi = PARITY_BOUNDS
    check(lo <= ratio <= hi,
          f"{what}: update norm is {ratio:.3f}x the single-device "
          f"update at the same global batch (allowed [{lo}, {hi}]) — "
          f"the gradient is not reduced exactly once")
    return ratio


class CompileMeter:
    """Per-phase compile seconds and persistent-cache hits/misses, from
    jax's own monitoring events (backend compile covers a cache load)."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.compile_s = 0.0
        self.trace_s = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == _COMPILE_EVENT:
            self.compile_s += seconds
        elif event in _TRACE_EVENTS:
            self.trace_s += seconds

    def _on_event(self, event, **_):
        if event == _HIT_EVENT:
            self.hits += 1
        elif event == _MISS_EVENT:
            self.misses += 1

    def snapshot(self):
        return (self.compile_s, self.trace_s, self.hits, self.misses)


class Smoke:
    """Runs named phases, keeps their numbers, remembers failures."""

    def __init__(self, meter: CompileMeter):
        self.meter = meter
        self.phases = {}
        self.failed = []

    def run(self, name: str, fn, *args, **kwargs):
        c0, t0, h0, m0 = self.meter.snapshot()
        wall0 = time.perf_counter()
        record = {"ok": False}
        self.phases[name] = record
        try:
            record.update(fn(*args, **kwargs) or {})
            record["ok"] = True
        except Exception as exc:  # reported below, never turned into exit 0
            traceback.print_exc()
            record["error"] = f"{type(exc).__name__}: {exc}"[:500]
            self.failed.append(name)
        wall = time.perf_counter() - wall0
        c1, t1, h1, m1 = self.meter.snapshot()
        record.update(
            wall_s=round(wall, 2),
            compile_s=round(c1 - c0, 2),
            trace_lower_s=round(t1 - t0, 2),
            run_s=round(max(wall - (c1 - c0) - (t1 - t0), 0.0), 2),
            cache_hits=h1 - h0,
            cache_misses=m1 - m0,
        )
        print(f"chip_smoke phase={name} " + " ".join(
            f"{k}={v}" for k, v in record.items()), flush=True)
        return record


@contextlib.contextmanager
def environ(**overrides):
    """Set env vars for one phase, then put back what was there."""
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


def _on_tpu(tree) -> bool:
    import jax

    return all(
        d.platform == "tpu"
        for leaf in jax.tree_util.tree_leaves(tree)
        for d in leaf.devices()
    )


def _spread_over_all(tree, n: int) -> bool:
    """Every leaf holds a shard on each of the ``n`` devices."""
    import jax

    return all(
        len({s.device for s in leaf.addressable_shards}) == n
        for leaf in jax.tree_util.tree_leaves(tree)
    )


def train_phase(workers_mode: str, n_chips: int, *, arch: str = ARCH,
                per_chip_batch: int = PER_CHIP_BATCH,
                steps_per_epoch: int = STEPS_PER_EPOCH,
                epochs: int = EPOCHS) -> dict:
    from dptpu.cli import main_apex

    global_batch = per_chip_batch * n_chips
    n = global_batch * steps_per_epoch
    argv = [f"synthetic:{n}", "-a", arch, "--opt-level", "O2",
            "-b", str(per_chip_batch), "--epochs", str(epochs),
            "--lr", repr(EFFECTIVE_LR * 256 / global_batch),
            "-j", "8", "-p", "5"]
    with environ(WORLD_SIZE="1", DPTPU_WORKERS_MODE=workers_mode):
        result = main_apex(argv)
    history = result["history"]
    losses = [float(h["train_loss"]) for h in history]
    steps = sum(int(h["train_steps_done"]) for h in history)
    check(steps >= 10, f"only {steps} optimizer steps ran")
    check(all(math.isfinite(x) for x in losses),
          f"train loss is not finite: {losses}")
    check(losses[-1] < losses[0],
          f"train loss did not fall over {steps} steps: {losses}")
    val = [float(h["val_loss"]) for h in history]
    check(all(math.isfinite(x) for x in val),
          f"validation loss is not finite: {val}")
    last = history[-1]
    check(last["train_workers_mode"] == workers_mode,
          f"asked for {workers_mode} workers, the loader ended in "
          f"{last['train_workers_mode']} mode (degraded pool)")
    if workers_mode == "process":
        check(last.get("train_leased") is True, "process feed not leased")
        check(last["train_bytes_copied_per_batch"] == 0,
              f"leased feed copied "
              f"{last['train_bytes_copied_per_batch']} bytes per batch")
    state = result["state"]
    check(_on_tpu(state.params), "result['state'] does not live on TPU")
    if n_chips > 1:
        check(_spread_over_all(state.params, n_chips),
              f"params are not spread over all {n_chips} devices")
    return {
        "steps": steps,
        "train_loss": [round(x, 4) for x in losses],
        "val_loss": round(val[-1], 4),
        # the host loop's seconds per step and the share of them spent
        # waiting for data — dispatch is asynchronous, so neither is a
        # device time
        "host_step_s": round(float(last["train_batch_time"]), 4),
        "starvation": round(float(last["train_starvation"]), 3),
    }


def serve_phase(arch: str = ARCH, n: int = SELFTEST_REQUESTS) -> dict:
    from dptpu.cli import main

    stats = main(["serve", "-a", arch, "--selftest", str(n)])
    check(stats["completed"] == n and stats["failed"] == 0,
          f"serve selftest: {stats['completed']} completed, "
          f"{stats['failed']} failed of {n}")
    return {
        "completed": stats["completed"],
        "failed": stats["failed"],
        "p50_ms": round(float(stats["latency_ms"]["p50"]), 2),
        "p99_ms": round(float(stats["latency_ms"]["p99"]), 2),
        "buckets": {str(k): v for k, v in stats["bucket_counts"].items()},
    }


def placement_phase(n_chips: int) -> dict:
    """The batch really is split over every chip (fit's own ``put``)."""
    import numpy as np

    from dptpu.parallel import make_mesh, shard_host_batch

    mesh = make_mesh()
    global_batch = PER_CHIP_BATCH * n_chips
    batch = shard_host_batch(
        {"images": np.zeros((global_batch, 224, 224, 3), np.uint8),
         "labels": np.zeros((global_batch,), np.int32)},
        mesh,
    )
    check(_spread_over_all(batch, n_chips),
          f"batch is not spread over all {n_chips} devices")
    for leaf in batch.values():
        shapes = {s.data.shape[0] for s in leaf.addressable_shards}
        check(shapes == {PER_CHIP_BATCH},
              f"per-chip batch shards are {shapes}, not {PER_CHIP_BATCH}")
    return {"mesh": dict(mesh.shape)}


def _one_step_nd(global_batch: int, *extra, **env) -> dict:
    from dptpu.cli import main_nd

    argv = [f"synthetic:{global_batch}", "-a", PARITY_ARCH,
            "-b", str(global_batch), "--epochs", "1", "--seed", "0",
            "-j", "4", "-p", "1", *extra]
    with environ(**env):
        return main_nd(argv)


def parity_phase(n_chips: int) -> dict:
    """One fp32 optimizer step: mesh paths vs ``--gpu 0``, same global
    batch, same seed — the check that sees the UPDATE, which a first-step
    loss (computed before the update) cannot."""
    import jax

    global_batch = PARITY_PER_CHIP * n_chips
    host = lambda r: jax.device_get(r["state"].params)  # noqa: E731
    before = host(_one_step_nd(global_batch, "--gpu", "0", "-e"))
    single = host(_one_step_nd(global_batch, "--gpu", "0"))
    out = {}
    runs = {
        "ddp": _one_step_nd(global_batch),
        "zero1": _one_step_nd(global_batch, DPTPU_ZERO1="1"),
        "accum2": _one_step_nd(global_batch, "--accum-steps", "2"),
        # collectives inside the microbatch scan: the combination
        # XLA:TPU refused on the first four-chip run (PR 21)
        "zero1_accum2": _one_step_nd(global_batch, "--accum-steps", "2",
                                     DPTPU_ZERO1="1"),
    }
    for name, result in runs.items():
        loss = float(result["history"][0]["train_loss"])
        check(math.isfinite(loss), f"{name}: step loss is {loss}")
        out[f"{name}_ratio"] = round(check_update_parity(
            before, host(result), single, f"{name} on {n_chips} chips"
        ), 4)
    check(_spread_over_all(runs["ddp"]["state"].params, n_chips),
          f"mesh params are not spread over all {n_chips} devices")
    return out


def stop_children() -> int:
    """Every process this script started must be gone when it exits."""
    import multiprocessing

    alive = multiprocessing.active_children()
    for p in alive:
        p.terminate()
    for p in alive:
        p.join(timeout=10)
    return len(alive)


def main() -> int:
    try:
        import jax
    except ImportError as exc:
        print(f"chip_smoke: cannot import jax ({exc})", file=sys.stderr)
        return 1
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: jax.default_backend() is {backend!r}, not "
              f"'tpu' — this smoke only passes on the chip; refusing to "
              f"run (and to compile) anywhere else", file=sys.stderr)
        return 1
    try:
        from dptpu.data import native_image
        from dptpu.utils.compile_cache import enable_compile_cache
        from dptpu.utils.provenance import device_summary
    except ImportError as exc:
        print(f"chip_smoke: the dptpu package is not next to this script "
              f"({exc})", file=sys.stderr)
        return 1

    import jaxlib

    cache_dir = enable_compile_cache()
    cache_entries = (len(os.listdir(cache_dir))
                     if os.path.isdir(cache_dir) else 0)
    device = device_summary()
    n_chips = device["count"]
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not importable"
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']} count={n_chips} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu_version} native={native_image.available()} "
          f"compile_cache={cache_dir} "
          f"({cache_entries} entries at start)", flush=True)

    smoke = Smoke(CompileMeter())
    cwd = os.getcwd()
    t0 = time.perf_counter()
    # runs/, checkpoints and TensorBoard events land in a scratch dir,
    # never in the checkout
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        os.chdir(work)
        try:
            smoke.run("train_thread", train_phase, "thread", n_chips)
            smoke.run("train_process", train_phase, "process", n_chips)
            smoke.run("serve", serve_phase)
            if n_chips > 1:
                smoke.run("placement", placement_phase, n_chips)
                smoke.run("update_parity", parity_phase, n_chips)
        finally:
            os.chdir(cwd)
            leaked = stop_children()
    if leaked:
        smoke.failed.append(f"{leaked} child process(es) left running")
    print("chip_smoke summary: " + json.dumps({
        "total_s": round(time.perf_counter() - t0, 1),
        "compile_cache": {"dir": cache_dir, "entries_at_start": cache_entries},
        "phases": smoke.phases,
    }), flush=True)
    if smoke.failed:
        print(f"chip_smoke: FAILED — {', '.join(smoke.failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
