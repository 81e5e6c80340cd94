"""``fit()``'s default train feed: process workers, started under set-up.

What a user who sets nothing gets (ISSUE 31): the batches of
``DPTPU_WORKERS_MODE=thread`` bit for bit, from a pool of worker processes
that ``fit()`` starts in its ``data`` phase and the first ``epoch()`` finds
up; ``collect`` spans that carry the workers' own CPU seconds, which the
benchmark's reader turns into a number; and a log that says so when the
pool gave up and threads stood in. Every case has a time limit of its own.
"""

import contextlib
import functools
import hashlib
import os
import signal
import sys
import types

import numpy as np
import pytest

from benchmark.lib import spans as bench_spans
from benchmark.readers import feed_row_cpu_us, feed_row_wall_us
from dptpu import obs
from dptpu.data import DataLoader, SyntheticDataset
from dptpu.data import shm as shm_mod
from dptpu.data.tokens import TokenDataset


@contextlib.contextmanager
def time_limit(seconds: float):
    """Fail, not hang: the case (or fixture) raises once its seconds are
    spent. Pytest runs cases on the main thread, where the alarm lands."""
    def late(signum, frame):
        raise TimeoutError(f"over its own limit of {seconds:g} s")

    before = signal.signal(signal.SIGALRM, late)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


def limited(seconds: float):
    def wrap(test):
        @functools.wraps(test)
        def run(*args, **kwargs):
            with time_limit(seconds):
                return test(*args, **kwargs)
        return run
    return wrap


def _digest(batch: dict) -> tuple:
    """Keys, dtypes, shapes and bytes of one host batch."""
    return tuple(
        (k, str(np.asarray(v).dtype), np.asarray(v).shape,
         hashlib.blake2b(np.ascontiguousarray(v).tobytes(),
                         digest_size=16).hexdigest())
        for k, v in sorted(batch.items()) if k != "_lease")


# ------------------------------------------------------------ through fit --

_IMAGE_CFG = dict(data="synthetic:96", arch="resnet18", epochs=1,
                  batch_size=24, lr=0.02, workers=1, print_freq=100, seed=1,
                  gpu=0)  # one device: 4 steps of 24, then 9 rows to validate
_TOKEN_ARGS = ["tokens:64", "-a", "lfm2_test_tiny", "--optimizer", "adamw",
               "--beta2", "0.95", "--wd", "0.1", "--lr", "0.08", "-b", "2",
               "--layers", "1:3", "--experts", "0:4", "--vocab-rows", "0:128",
               "--seq-len", "32", "--opt-level", "O2", "-p", "100",
               "--epochs", "1",  # the fake pod's 8 devices: 4 steps of 16
               # these runs only need A pool: one worker a chip, on any
               # host, not the -j 4 a chip its cores would allow
               "-j", "1"]


_RING_KNOBS = ("DPTPU_DECODE_AHEAD", "DPTPU_RING_DEPTH", "DPTPU_LEASE_DEPTH",
               "DPTPU_LEASE")


def _fit_images(ckpt_dir):
    from dptpu.config import Config
    from dptpu.train import fit

    return fit(Config(**_IMAGE_CFG, ckpt_dir=ckpt_dir), image_size=32,
               verbose=False)


def _fit_tokens(ckpt_dir):
    from dptpu.cli import main_apex

    return main_apex([*_TOKEN_ARGS, "--ckpt-dir", ckpt_dir])


def _fit_recorded(run, ckpt_dir, obs_dir):
    """One ``fit()``: the digest of every host batch in the order the
    loop's prefetcher took it (train, then validation), how many worker
    pools were made (a pool that a loaded host's watchdog restarts is
    still one pool), and the run's span log."""
    from dptpu.data import DevicePrefetcher

    fit_mod = sys.modules["dptpu.train.fit"]
    taken, spawned = [], []

    class Recording(DevicePrefetcher):
        def __init__(self, batches, *args, **kwargs):
            def tee():
                for batch in batches:
                    taken.append(_digest(batch))
                    yield batch
            super().__init__(tee(), *args, **kwargs)

    real_start = shm_mod.ShmBatchPipeline._start_workers

    def counted_start(pipe):
        if not any(pipe is p for p in spawned):
            spawned.append(pipe)
        return real_start(pipe)

    real_prefetcher = fit_mod.DevicePrefetcher
    os.environ["DPTPU_OBS_DIR"] = str(obs_dir)
    fit_mod.DevicePrefetcher = Recording
    shm_mod.ShmBatchPipeline._start_workers = counted_start
    try:
        result = run(str(ckpt_dir))
    finally:
        fit_mod.DevicePrefetcher = real_prefetcher
        shm_mod.ShmBatchPipeline._start_workers = real_start
        os.environ.pop("DPTPU_OBS_DIR", None)
    (log,) = [p for p in os.listdir(obs_dir) if p.endswith(".jsonl")]
    return {"taken": taken, "pools": len(spawned), "result": result,
            "spans": bench_spans.read_log(os.path.join(obs_dir, log))}


on_a_host_with_cores = pytest.mark.skipif(
    len(os.sched_getaffinity(0)) <= 2,
    reason="the default is thread mode on a host with two cores or fewer")


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """``fit()`` on ``synthetic:<N>`` and on ``tokens:<N>``, each with
    nothing set and with ``DPTPU_WORKERS_MODE=thread``."""
    import tests.test_lfm2  # noqa: F401 (registers lfm2_test_tiny)

    d = tmp_path_factory.mktemp("feed_default")
    cwd = os.getcwd()
    # what sizes the ring and the pool is shed for the module: a run
    # under a tuning artifact env-injects its knobs for good, and a file
    # that shared this worker may have left one (DPTPU_DECODE_AHEAD=6
    # made these rings 9 slots deep in the driver's run)
    saved = {k: os.environ.pop(k, None)
             for k in ("DPTPU_WORKERS_MODE", "WORLD_SIZE", *_RING_KNOBS)}
    os.environ["WORLD_SIZE"] = "1"
    os.chdir(d)
    out = {}
    try:
        with time_limit(420):
            for source, run in (("synthetic", _fit_images),
                                ("tokens", _fit_tokens)):
                for mode in (None, "thread"):
                    if mode:
                        os.environ["DPTPU_WORKERS_MODE"] = mode
                    else:
                        os.environ.pop("DPTPU_WORKERS_MODE", None)
                    name = f"{source}-{mode or 'default'}"
                    out[name] = _fit_recorded(run, d / name,
                                              d / f"obs-{name}")
    finally:
        os.chdir(cwd)
        os.environ.pop("DPTPU_WORKERS_MODE", None)
        os.environ.pop("WORLD_SIZE", None)
        for k, v in saved.items():
            if v is not None:
                os.environ[k] = v
    return out


@on_a_host_with_cores
@pytest.mark.parametrize("source", ["synthetic", "tokens"])
@limited(30)
def test_fit_default_yields_thread_modes_batches(runs, source):
    default, thread = runs[f"{source}-default"], runs[f"{source}-thread"]
    assert len(default["taken"]) >= 4  # train steps and a validation pass
    assert default["taken"] == thread["taken"]
    # same rows, same arithmetic: the runs cannot be told apart
    (ours,), (theirs,) = (default["result"]["history"],
                          thread["result"]["history"])
    for key in ("train_loss", "train_top1", "train_top5", "val_loss",
                "val_top1", "val_count", "train_steps_done"):
        assert ours[key] == theirs[key], key
    assert (ours["train_workers_mode"], theirs["train_workers_mode"]) == (
        "process", "thread")
    # ... but for who made the rows
    assert thread["pools"] == 0
    assert not [s for s in thread["spans"] if s["name"] == "feed_start"]
    assert [s for s in default["spans"] if s["name"] == "feed_start"]


@on_a_host_with_cores
@pytest.mark.parametrize("source", ["synthetic", "tokens"])
@limited(30)
def test_fit_starts_the_train_pool_in_its_data_phase_once(runs, source):
    run = runs[f"{source}-default"]
    # one pool for the train loader, one (lazy) for validation: the first
    # epoch() spawned nothing of its own
    assert run["pools"] == 2
    (start,) = [s for s in run["spans"] if s["name"] == "feed_start"]
    # -j is per chip, the pool per host (dptpu.data.feed.pool_size):
    # -j 1, one worker for each of the fake pod's eight devices
    assert start["attrs"] == {"mode": "process", "workers": 8, "slots": 7}
    (data,) = [s for s in run["spans"] if s["name"] == "setup.data"]
    assert data["ts"] <= start["ts"]
    assert start["ts"] + start["dur_s"] <= data["ts"] + data["dur_s"] + 1e-6
    # ... and it does not wait for the workers: the phases that make the
    # weights and the step come after it, and every batch after those
    started = start["ts"] + start["dur_s"]
    later = [s for s in run["spans"]
             if s["name"] in ("setup.model_init", "setup.step_build")]
    assert {s["name"] for s in later} == {"setup.model_init",
                                          "setup.step_build"}
    assert all(started <= s["ts"] for s in later)
    first_collect = min(s["ts"] for s in run["spans"]
                        if s["name"] == "collect")
    assert max(s["ts"] + s["dur_s"] for s in later) <= first_collect


@on_a_host_with_cores
@pytest.mark.parametrize("source,rows", [("synthetic", 24), ("tokens", 16)])
@limited(30)
def test_default_collect_spans_feed_the_benchmarks_readers(runs, source, rows):
    """The log of a default run, as the benchmark cuts it: the window's
    ``collect`` spans carry ``cpu_s`` beside ``wall_s``, and the readers
    of ``benchmark/readers`` make a number of each."""
    spans = runs[f"{source}-default"]["spans"]
    window = bench_spans.window(spans, warmup=1)
    collects = [s for s in window.spans if s["name"] == "collect"]
    # the train loader's, and the validation pass's behind them
    assert [s for s in collects if s["attrs"]["rows"] == rows]
    for s in collects:
        assert {"ready", "rows", "cpu_s", "wall_s"} <= set(s["attrs"])
        assert s["attrs"]["workers"] == 8  # the pool's size, on each
        assert "degraded" not in s["attrs"]
    context = {"window": window}
    cpu, wall = feed_row_cpu_us.read(context), feed_row_wall_us.read(context)
    assert cpu is not None and wall is not None
    assert 0.0 < cpu <= wall * 1.05 + 50.0


def test_a_log_without_cpu_seconds_reads_nothing():
    """What the parent's log looks like to the same reader: nothing to
    read, and no error."""
    span = {"name": "collect", "ts": 1.0, "dur_s": 0.1, "step": 1,
            "attrs": {"rows": 4, "ready": True, "wall_s": 0.01}}
    context = {"window": types.SimpleNamespace(spans=(span,))}
    assert feed_row_cpu_us.read(context) is None
    assert feed_row_wall_us.read(context) == pytest.approx(2500.0)


# -------------------------------------------------------- the loader alone --

@pytest.fixture
def tracer():
    t = obs.set_tracer(obs.Tracer(capacity=4096))
    yield t
    obs.reset()


def _token_loader(mode, **kwargs):
    ds = TokenDataset(24, 48, 300, 5)
    return DataLoader(ds, 4, num_workers=2, seed=3, workers_mode=mode,
                      **kwargs)


@limited(60)
def test_started_pool_is_the_one_the_first_epoch_uses(tracer, monkeypatch):
    for knob in _RING_KNOBS:
        monkeypatch.delenv(knob, raising=False)
    loader = _token_loader("process", leased=True, drop_last=True,
                           pad_final=False)
    thread = _token_loader("thread", drop_last=True, pad_final=False)
    try:
        loader.start()
        pipe = loader._pipeline
        assert pipe is not None and pipe.slots == 7
        assert pipe.item_dtype == np.int32  # the probe's, before any epoch
        pids = [p.pid for p in pipe._procs]
        loader.start()  # up already: nothing happens
        assert loader._pipeline is pipe
        got = [_digest(b) for b in loader.epoch(0)]
        assert loader._pipeline is pipe
        assert [p.pid for p in pipe._procs] == pids
        assert got == [_digest(b) for b in thread.epoch(0)]
        starts = [s for s in tracer.drain() if s["name"] == "feed_start"]
        assert len(starts) == 1
    finally:
        loader.close()
        thread.close()


@limited(30)
def test_start_is_nothing_in_thread_mode_and_on_an_empty_shard(tracer):
    thread = _token_loader("thread")
    empty = DataLoader(SyntheticDataset(0, 8, 4), 4, num_workers=2,
                       workers_mode="process")
    try:
        thread.start()
        empty.start()
        assert thread._pipeline is None and empty._pipeline is None
        assert not [s for s in tracer.drain() if s["name"] == "feed_start"]
    finally:
        thread.close()
        empty.close()


@limited(60)
def test_close_does_not_wait_out_busy_workers():
    """An abandoned epoch leaves pre-issued spans on the workers' queues;
    ``close()`` gives the pool one short grace, not a second a worker."""
    import time

    ds = SyntheticDataset(4096, 64, 10)
    loader = DataLoader(ds, 256, num_workers=4, workers_mode="process",
                        leased=True)
    try:
        it = loader.epoch(0)
        next(it)
        procs = list(loader._pipeline._procs)
        t0 = time.monotonic()
        del it
    finally:
        loader.close()
    assert time.monotonic() - t0 < 2.0
    assert not any(p.is_alive() for p in procs)


@limited(90)
def test_a_degraded_run_shows_in_the_span_log(tracer, monkeypatch):
    """Threads that stand in for a pool that gave up say so on every
    ``collect`` span: a run that gained nothing from the pool can be told
    from a run that never had one."""
    monkeypatch.setenv("DPTPU_FAULT", "worker_hang@index=13")
    monkeypatch.setenv("DPTPU_POOL_RESTARTS", "0")
    monkeypatch.delenv("DPTPU_WORKER_TIMEOUT_S", raising=False)
    ds = SyntheticDataset(32, 8, 10)
    loader = DataLoader(ds, 4, num_workers=2, seed=3,
                        workers_mode="process")
    try:
        batches = loader.epoch(0)
        # the pool's two interpreters start and import under the default
        # watchdog (two minutes), and batch 0 says they deliver: on a
        # loaded host that alone has taken over a second, and a watchdog
        # of one second then broke the pool before it had made a batch.
        # Only then is the watchdog drawn in to a second, counted from a
        # warm pool; row 13, in batch 3, is where a worker hangs.
        taken = [next(batches)]
        loader._pipeline.timeout_s = 1.0
        taken.extend(batches)
        assert len(taken) == 8
        assert loader.feed_stats()["degraded"] is True
    finally:
        loader.close()
    collects = [s for s in tracer.drain() if s["name"] == "collect"]
    flagged = [s for s in collects if s["attrs"].get("degraded")]
    assert flagged and len(flagged) < len(collects)
    # the pool made the first batches, the threads every one from the
    # batch that broke it on, in order
    steps = [s["step"] for s in flagged]
    assert steps == list(range(steps[0], 8)) and steps[0] >= 1
    assert all({"cpu_s", "wall_s", "rows", "ready"} <= set(s["attrs"])
               for s in collects)
