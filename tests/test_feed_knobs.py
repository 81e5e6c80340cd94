"""The locked fail-fast env-knob contract, input-pipeline edition.

Every explicitly-set-but-invalid knob value must raise with an
actionable message — including the previously-silent ``DPTPU_TP=0`` /
``DPTPU_SP=0`` (ADVICE r5: 0 was the one value that got neither the
no-op notice nor the error).
"""

import os

import pytest

from dptpu.data import feed as feed_mod
from dptpu.data.feed import feed_knobs, host_cores, pool_notice, pool_size
from dptpu.envknob import env_axis, env_int


def test_unset_knob_is_none_then_off(monkeypatch):
    monkeypatch.delenv("DPTPU_TP", raising=False)
    assert env_int("DPTPU_TP", None) is None
    assert env_axis("DPTPU_TP", "model-axis size") == 0


def test_axis_zero_raises_like_negatives(monkeypatch):
    for bad in ("0", "-2"):
        monkeypatch.setenv("DPTPU_TP", bad)
        with pytest.raises(ValueError, match="DPTPU_TP"):
            env_axis("DPTPU_TP", "model-axis size")
    monkeypatch.setenv("DPTPU_SP", "0")
    with pytest.raises(ValueError, match="DPTPU_SP"):
        env_axis("DPTPU_SP", "seq-axis size")


def test_axis_junk_raises(monkeypatch):
    monkeypatch.setenv("DPTPU_TP", "two")
    with pytest.raises(ValueError, match="not an integer"):
        env_axis("DPTPU_TP", "model-axis size")


def _host_with(monkeypatch, cores: int):
    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda pid: set(range(cores)), raising=False)


@pytest.mark.parametrize("cores,mode,scope", [
    # worker processes leave the loop's interpreter wherever they can
    # run beside it; their cache default is the pooled cross-process slab
    (30, "process", "pooled"), (8, "process", "pooled"),
    (3, "process", "pooled"),
    # two cores or fewer: processes buy nothing there (HOSTBENCH's host),
    # so the pool stays in the interpreter, where the in-process cache
    # is already pooled ("sharded" = the plain DecodeCache)
    (2, "thread", "sharded"), (1, "thread", "sharded"),
])
def test_unset_workers_mode_reads_the_hosts_cores(monkeypatch, cores, mode,
                                                  scope):
    for k in ("DPTPU_WORKERS_MODE", "DPTPU_CACHE_BYTES",
              "DPTPU_CACHE_SCOPE", "DPTPU_LEASE"):
        monkeypatch.delenv(k, raising=False)
    _host_with(monkeypatch, cores)
    assert feed_knobs() == (mode, 0, scope, True)


@pytest.mark.parametrize("cores", [1, 30])
@pytest.mark.parametrize("asked", ["thread", "process"])
def test_an_explicit_workers_mode_wins_on_any_host(monkeypatch, cores, asked):
    monkeypatch.delenv("DPTPU_CACHE_SCOPE", raising=False)
    _host_with(monkeypatch, cores)
    monkeypatch.setenv("DPTPU_WORKERS_MODE", asked)
    assert feed_knobs()[0] == asked


@pytest.mark.parametrize("workers,chips,cores,mode,size,says", [
    # one local chip: -j, whatever the cores (the parent's number)
    (4, 1, 13, "process", 4, "workers=4 (4 a chip x 1 chip; 13 cores)"),
    (4, 1, 3, "process", 4, "workers=4 (4 a chip x 1 chip; 3 cores)"),
    (16, 1, 4, "process", 16, "workers=16 (16 a chip x 1 chip; 4 cores)"),
    # the four-chip host: every chip keeps the four it has alone
    (4, 4, 30, "process", 16, "workers=16 (4 a chip x 4 chips; 30 cores)"),
    # ... as far as the host has cores beside the loop's
    (4, 4, 8, "process", 6,
     "workers=6 (4 a chip x 4 chips asks 16; 8 cores less 2 kept for the "
     "loop)"),
    (4, 8, 30, "process", 28,
     "workers=28 (4 a chip x 8 chips asks 32; 30 cores less 2 kept for "
     "the loop)"),
    (8, 4, 30, "process", 28,
     "workers=28 (8 a chip x 4 chips asks 32; 30 cores less 2 kept for "
     "the loop)"),
    # ... and never below the parent's ceil(j / chips) x chips
    (4, 4, 3, "process", 4,
     "workers=4 (the floor ceil(4 / 4) a chip x 4 chips; 3 cores)"),
    (4, 8, 8, "process", 8,
     "workers=8 (the floor ceil(4 / 8) a chip x 8 chips; 8 cores)"),
    (6, 4, 9, "process", 8,
     "workers=8 (the floor ceil(6 / 4) a chip x 4 chips; 9 cores)"),
    (1, 4, 30, "process", 4, "workers=4 (1 a chip x 4 chips; 30 cores)"),
    (1, 8, 4, "process", 8, "workers=8 (1 a chip x 8 chips; 4 cores)"),
    (0, 4, 30, "process", 0, "workers=0 (0 a chip x 4 chips; 30 cores)"),
    # pool threads share the loop's interpreter: the parent's count on
    # any host (a host of two cores is thread mode by default)
    (4, 4, 2, "thread", 4,
     "workers=4 (thread mode keeps ceil(4 / 4) a chip x 4 chips)"),
    (4, 4, 30, "thread", 4,
     "workers=4 (thread mode keeps ceil(4 / 4) a chip x 4 chips)"),
    (6, 4, 30, "thread", 8,
     "workers=8 (thread mode keeps ceil(6 / 4) a chip x 4 chips)"),
    (4, 1, 30, "thread", 4,
     "workers=4 (thread mode keeps ceil(4 / 1) a chip x 1 chip)"),
    (0, 4, 30, "thread", 0,
     "workers=0 (thread mode keeps ceil(0 / 4) a chip x 4 chips)"),
])
def test_pool_size_is_j_a_chip_within_the_cores_and_over_the_floor(
        workers, chips, cores, mode, size, says):
    assert pool_size(workers, chips, cores, mode) == size
    assert pool_notice(workers, chips, cores, mode) == says
    # what the parent gave every host: never less, and exactly that
    # with one local chip or in thread mode
    parent = -(-workers // chips) * chips
    assert size >= parent
    if chips == 1 or mode == "thread":
        assert size == parent
    else:
        assert size <= max(parent, workers * chips)


def test_build_feed_gives_both_loaders_the_pool_and_says_so(monkeypatch):
    """Through ``build_feed`` on the fake pod's eight devices: the train
    pool (started) and the validation loader get ``pool_size``'s number,
    the ``=> input pipeline:`` line says it with its inputs, and every
    ``collect`` span carries it."""
    import jax

    from dptpu import obs
    from dptpu.config import Config, derive

    for k in ("DPTPU_WORKERS_MODE", "DPTPU_CACHE_BYTES",
              "DPTPU_CACHE_SCOPE", "DPTPU_LEASE", "DPTPU_DECODE_AHEAD",
              "DPTPU_RING_DEPTH", "DPTPU_LEASE_DEPTH"):
        monkeypatch.delenv(k, raising=False)
    # a host with room for -j a chip, whatever this machine has; the
    # test says -j itself (1 a chip) so that the pool stays small
    monkeypatch.setattr(feed_mod, "host_cores", lambda: 30)
    chips = jax.local_device_count()
    cfg = Config(data="synthetic:64", arch="resnet18", batch_size=2 * chips,
                 workers=1, seed=1)
    derived = derive(cfg, local_device_count=chips)
    tracer = obs.set_tracer(obs.Tracer(capacity=1024))
    feed = None
    try:
        feed = feed_mod.build_feed(cfg, derived, task="images",
                                   model_config=None, image_size=8)
        assert feed.workers_mode == "process"
        assert feed.train_loader.num_workers == chips
        assert feed.val_loader.num_workers == chips
        assert (f"workers={chips} (1 a chip x {chips} chips; 30 cores)"
                in feed.notices[0])
        assert len(list(feed.train_loader.epoch(0))) == 64 // (2 * chips)
        spans = tracer.drain()
    finally:
        obs.reset()
        if feed is not None:
            feed.train_loader.close()
            feed.val_loader.close()
    (start,) = [s for s in spans if s["name"] == "feed_start"]
    assert start["attrs"]["workers"] == chips
    collects = [s for s in spans if s["name"] == "collect"]
    assert len(collects) == 64 // (2 * chips)
    assert all(s["attrs"]["workers"] == chips for s in collects)


def test_host_cores_without_an_affinity_call(monkeypatch):
    monkeypatch.delattr(os, "sched_getaffinity", raising=False)
    monkeypatch.setattr(os, "cpu_count", lambda: 6)
    assert host_cores() == 6
    monkeypatch.setattr(os, "cpu_count", lambda: None)
    assert host_cores() == 1


def test_feed_knobs_defaults_and_validation(monkeypatch):
    for k in ("DPTPU_WORKERS_MODE", "DPTPU_CACHE_BYTES",
              "DPTPU_CACHE_SCOPE", "DPTPU_LEASE"):
        monkeypatch.delenv(k, raising=False)
    _host_with(monkeypatch, 8)
    # process workers are what a user who types nothing gets: their
    # cache default is the pooled cross-process slab
    assert feed_knobs() == ("process", 0, "pooled", True)

    monkeypatch.setenv("DPTPU_WORKERS_MODE", "thread")
    # thread mode: the in-process cache is already pooled, so the scope
    # default is the plain DecodeCache ("sharded")
    assert feed_knobs() == ("thread", 0, "sharded", True)

    monkeypatch.setenv("DPTPU_WORKERS_MODE", "process")
    monkeypatch.setenv("DPTPU_CACHE_BYTES", str(1 << 20))
    # process mode defaults to the pooled cross-process slab
    assert feed_knobs() == ("process", 1 << 20, "pooled", True)

    monkeypatch.setenv("DPTPU_CACHE_BYTES", "0")  # explicit off is valid
    assert feed_knobs() == ("process", 0, "pooled", True)

    monkeypatch.setenv("DPTPU_WORKERS_MODE", "gevent")
    with pytest.raises(ValueError, match="DPTPU_WORKERS_MODE"):
        feed_knobs()

    monkeypatch.setenv("DPTPU_WORKERS_MODE", "thread")
    monkeypatch.setenv("DPTPU_CACHE_BYTES", "-1")
    with pytest.raises(ValueError, match="DPTPU_CACHE_BYTES"):
        feed_knobs()

    monkeypatch.setenv("DPTPU_CACHE_BYTES", "lots")
    with pytest.raises(ValueError, match="not an integer"):
        feed_knobs()


def test_cache_scope_and_lease_knobs(monkeypatch):
    monkeypatch.setenv("DPTPU_WORKERS_MODE", "process")
    monkeypatch.delenv("DPTPU_CACHE_BYTES", raising=False)

    monkeypatch.setenv("DPTPU_CACHE_SCOPE", "sharded")  # explicit override
    monkeypatch.setenv("DPTPU_LEASE", "0")
    assert feed_knobs() == ("process", 0, "sharded", False)

    monkeypatch.setenv("DPTPU_CACHE_SCOPE", "pooled")
    monkeypatch.setenv("DPTPU_LEASE", "true")
    assert feed_knobs() == ("process", 0, "pooled", True)

    monkeypatch.setenv("DPTPU_CACHE_SCOPE", "global")
    with pytest.raises(ValueError, match="DPTPU_CACHE_SCOPE"):
        feed_knobs()

    monkeypatch.setenv("DPTPU_CACHE_SCOPE", "pooled")
    monkeypatch.setenv("DPTPU_LEASE", "maybe")
    with pytest.raises(ValueError, match="DPTPU_LEASE"):
        feed_knobs()


def test_lease_depth_knob_validated():
    from dptpu.data import DataLoader, SyntheticDataset

    with pytest.raises(ValueError, match="DPTPU_LEASE_DEPTH"):
        DataLoader(SyntheticDataset(8, 8, 4), 4, lease_depth=0)


def test_ring_depth_and_decode_ahead_knobs_validated(monkeypatch):
    """The decode-ahead pipeline knobs under the locked fail-fast
    contract: 0, negatives and garbage all raise with the knob's name —
    the DPTPU_TP=0 discipline, not a silent fallback."""
    from dptpu.data import DataLoader, SyntheticDataset

    ds = SyntheticDataset(8, 8, 4)
    for knob, ctor_kw, bads in (
        ("DPTPU_RING_DEPTH", "ring_depth", ("0", "1", "-3")),
        ("DPTPU_DECODE_AHEAD", "decode_ahead", ("0", "-1")),
    ):
        for bad in bads:
            monkeypatch.setenv(knob, bad)
            with pytest.raises(ValueError, match=knob):
                DataLoader(ds, 4)
            monkeypatch.delenv(knob)
            # ctor args hit the same validation as the env knob
            with pytest.raises(ValueError, match=knob):
                DataLoader(ds, 4, **{ctor_kw: int(bad)})
        monkeypatch.setenv(knob, "plenty")
        with pytest.raises(ValueError, match="not an integer"):
            DataLoader(ds, 4)
        monkeypatch.delenv(knob)
    # valid explicit values construct fine and land on the loader
    monkeypatch.setenv("DPTPU_RING_DEPTH", "8")
    monkeypatch.setenv("DPTPU_DECODE_AHEAD", "1")
    dl = DataLoader(ds, 4)
    assert (dl.ring_depth, dl.decode_ahead) == (8, 1)
    dl.close()


def test_speculate_and_readahead_knobs_validated(monkeypatch):
    from dptpu.data import DataLoader, SyntheticDataset

    ds = SyntheticDataset(8, 8, 4)
    for knob in ("DPTPU_SPECULATE", "DPTPU_READAHEAD"):
        monkeypatch.setenv(knob, "maybe")
        with pytest.raises(ValueError, match=knob):
            DataLoader(ds, 4)
        monkeypatch.setenv(knob, "0")
        dl = DataLoader(ds, 4)
        assert getattr(dl, knob.split("_", 1)[1].lower()) is False
        dl.close()
        monkeypatch.delenv(knob)
    dl = DataLoader(ds, 4)  # defaults: speculation + readahead on
    assert dl.speculate is True and dl.readahead is True
    dl.close()


def test_env_bool_and_choice_contract(monkeypatch):
    from dptpu.envknob import env_bool, env_choice

    monkeypatch.delenv("DPTPU_X", raising=False)
    assert env_bool("DPTPU_X", True) is True
    assert env_choice("DPTPU_X", ("a", "b"), "a") == "a"
    monkeypatch.setenv("DPTPU_X", "off")
    assert env_bool("DPTPU_X") is False
    monkeypatch.setenv("DPTPU_X", "flase")
    with pytest.raises(ValueError, match="DPTPU_X"):
        env_bool("DPTPU_X")
    with pytest.raises(ValueError, match="DPTPU_X"):
        env_choice("DPTPU_X", ("a", "b"))


def test_a_spawned_worker_imports_no_jax():
    """What a feed worker's start-up rests on (0.53 s of imports on the
    chip host, 2.1 s with jax; PERF.md §6, PR 31): a fresh interpreter
    that imports what ``spawn`` makes a worker import, and the module
    that builds the feed, has no jax among its modules."""
    import subprocess
    import sys

    probe = ("import sys, dptpu.data, dptpu.data.shm, dptpu.data.feed; "
             "print(sorted(m for m in sys.modules "
             "if m == 'jax' or m.startswith(('jax.', 'jaxlib', 'flax'))))")
    out = subprocess.run([sys.executable, "-c", probe], check=True,
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.strip() == "[]", out.stdout
