"""The locked fail-fast env-knob contract, input-pipeline edition.

Every explicitly-set-but-invalid knob value must raise with an
actionable message — including the previously-silent ``DPTPU_TP=0`` /
``DPTPU_SP=0`` (ADVICE r5: 0 was the one value that got neither the
no-op notice nor the error).
"""

import os

import pytest

from dptpu.data.feed import feed_knobs, host_cores
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
