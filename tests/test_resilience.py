"""Resilience primitives: fault specs, checkpoint integrity, rotation,
corrupt-file fallback, preemption guard.

The contracts under test (dptpu/resilience + train/checkpoint.py):

* checkpoints carry a CRC content footer; a flipped byte or a truncated
  tail is DETECTED, never silently loaded;
* an empty checkpoint file raises a FileNotFoundError-derived error
  (warn-and-continue resume treats it like absence);
* rotated step checkpoints keep exactly ``keep`` files and resume
  falls back PAST corrupt files to the newest verifiable one;
* ``DPTPU_FAULT`` specs parse strictly (typos fail before training);
* the preemption guard converts the first SIGTERM into a flag, not a
  crash.
"""

import os
import signal

import jax.numpy as jnp
import numpy as np
import pytest

from dptpu.resilience import (
    CheckpointManager,
    FaultPlan,
    PreemptionGuard,
    find_resumable,
    step_checkpoint_name,
    verify_checkpoint,
)
from dptpu.train.checkpoint import (
    CorruptCheckpointError,
    EmptyCheckpointError,
    load_checkpoint,
    save_checkpoint,
)
from dptpu.train.state import TrainState, make_optimizer


def tiny_state(value: float = 1.0) -> TrainState:
    """A real TrainState over a toy param tree — no model, no compile."""
    params = {"dense": {"kernel": np.full((4, 3), value, np.float32),
                        "bias": np.zeros((3,), np.float32)}}
    tx = make_optimizer()
    return TrainState(
        step=jnp.asarray(0, jnp.int32),
        params=params,
        batch_stats={},
        opt_state=tx.init(params),
        apply_fn=lambda *a, **k: None,
        tx=tx,
    )


# -- fault spec parsing ------------------------------------------------------

def test_fault_spec_parses_all_kinds():
    p = FaultPlan("sigterm@step=12,worker_kill@step=7,ckpt_truncate@save=2,"
                  "io_error:p=0.1,worker_hang@index=4")
    kinds = [f.kind for f in p.faults]
    assert kinds == ["sigterm", "worker_kill", "ckpt_truncate", "io_error",
                     "worker_hang"]
    assert p.faults[0].step == 12
    assert p.faults[2].save == 2
    assert p.faults[3].p == pytest.approx(0.1)
    assert p.faults[4].index == 4


@pytest.mark.parametrize("bad", [
    "explode@step=1",       # unknown kind
    "io_error:p=nope",      # non-numeric probability
    "io_error:p=1.5",       # probability out of range
    "sigterm",              # missing required @step
    "worker_hang",          # missing required @index
    "sigterm@tick=3",       # unknown modifier key
    "worker_hang@index=2@s=0",     # straggler sleep must be > 0
    "worker_hang@index=2@s=soon",  # non-numeric sleep
    "serve_exception",      # missing required @request
    "preprocess_crash",     # missing required @request
    "serve_exception@request=0",   # request index is 1-based
    "serve_exception@request=abc", # non-numeric request index
    "slow_model",           # missing required :factor
    "slow_model:factor=1",  # factor must be > 1
])
def test_fault_spec_rejects_typos(bad):
    with pytest.raises(ValueError):
        FaultPlan(bad)


def test_serve_fault_kinds_parse_and_fire():
    p = FaultPlan("serve_exception@request=3,preprocess_crash@request=5,"
                  "slow_model:factor=4,canary_drift")
    assert [f.kind for f in p.faults] == [
        "serve_exception", "preprocess_crash", "slow_model",
        "canary_drift",
    ]
    # submit hook: fires ONCE at the matching 1-based index
    p.on_serve_submit(1)
    p.on_serve_submit(2)
    with pytest.raises(RuntimeError, match="serve_exception on request 3"):
        p.on_serve_submit(3)
    p.on_serve_submit(3)  # fired flag: one-shot
    # preprocess hook
    p.on_serve_preprocess(4)
    with pytest.raises(RuntimeError,
                       match="preprocess_crash on request 5"):
        p.on_serve_preprocess(5)
    p.on_serve_preprocess(5)
    # model delay: base x factor, summed over armed slow_model faults
    assert p.serve_model_delay_s() == pytest.approx(0.02 * 4)
    assert p.canary_drift_armed()
    assert not FaultPlan("slow_model:factor=2").canary_drift_armed()
    assert FaultPlan("canary_drift").serve_model_delay_s() == 0.0


def test_worker_hang_straggler_modifiers(monkeypatch):
    """``s=``/``worker=`` turn the forever-hang into a bounded straggler
    restricted to one worker id — the decode-ahead speculation A/B's
    injection vehicle."""
    import time as _time

    p = FaultPlan("worker_hang@index=4@s=0.05@worker=1")
    f = p.faults[0]
    assert (f.index, f.seconds, f.worker) == (4, pytest.approx(0.05), 1)
    t0 = _time.monotonic()
    p.worker_decode_hook(worker_id=0, index=4)  # wrong worker: no hang
    p.worker_decode_hook(worker_id=1, index=3)  # wrong index: no hang
    assert _time.monotonic() - t0 < 0.04
    t0 = _time.monotonic()
    p.worker_decode_hook(worker_id=1, index=4)  # the straggler
    assert _time.monotonic() - t0 >= 0.05


def test_fault_plan_from_env(monkeypatch):
    monkeypatch.delenv("DPTPU_FAULT", raising=False)
    assert FaultPlan.from_env() is None
    monkeypatch.setenv("DPTPU_FAULT", "sigterm@step=5")
    plan = FaultPlan.from_env()
    assert plan is not None and plan.faults[0].step == 5


def test_ckpt_truncate_fault_fires_on_armed_save(tmp_path):
    plan = FaultPlan("ckpt_truncate@save=2")
    manager = CheckpointManager(directory=str(tmp_path), keep=5,
                                arch="toy", fault_plan=plan)
    p1 = manager.save_step(tiny_state(), epoch=0, step_in_epoch=1)
    p2 = manager.save_step(tiny_state(), epoch=0, step_in_epoch=2)
    ok1, _ = verify_checkpoint(p1)
    ok2, reason2 = verify_checkpoint(p2)
    assert ok1
    assert not ok2, reason2  # the armed (2nd) save was torn in place


# -- checkpoint integrity ----------------------------------------------------

def test_checkpoint_roundtrip_carries_resume_coordinates(tmp_path):
    state = tiny_state(2.5)
    path = save_checkpoint(
        state, epoch=3, arch="toy", best_acc1=12.5, is_best=False,
        directory=str(tmp_path), step_in_epoch=17, data_position=17 * 24,
    )
    ok, reason = verify_checkpoint(path)
    assert ok, reason
    new, meta = load_checkpoint(path, tiny_state(0.0))
    assert meta["epoch"] == 3
    assert meta["step_in_epoch"] == 17
    assert meta["data_position"] == 17 * 24
    np.testing.assert_array_equal(
        new.params["dense"]["kernel"], state.params["dense"]["kernel"]
    )


def test_bitflip_fails_checksum(tmp_path):
    path = save_checkpoint(tiny_state(), epoch=1, arch="toy", best_acc1=0.0,
                           is_best=False, directory=str(tmp_path))
    raw = bytearray(open(path, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    ok, reason = verify_checkpoint(path)
    assert not ok and "checksum" in reason
    with pytest.raises(CorruptCheckpointError, match="checksum"):
        load_checkpoint(path, tiny_state())


def test_truncation_detected_even_without_footer(tmp_path):
    """Truncation removes the CRC footer too — the scanner must not
    mistake the stump for a healthy legacy (footerless) file."""
    path = save_checkpoint(tiny_state(), epoch=1, arch="toy", best_acc1=0.0,
                           is_best=False, directory=str(tmp_path))
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size // 2)
    ok, reason = verify_checkpoint(path)
    assert not ok


def test_empty_checkpoint_raises_filenotfound_subclass(tmp_path):
    path = str(tmp_path / "checkpoint.pth.tar")
    open(path, "wb").close()
    with pytest.raises(FileNotFoundError, match="empty"):
        load_checkpoint(path, tiny_state())
    with pytest.raises(EmptyCheckpointError):
        load_checkpoint(path, tiny_state())
    ok, reason = verify_checkpoint(path)
    assert not ok and "empty" in reason


def test_legacy_footerless_checkpoint_still_loads(tmp_path):
    """A pre-resilience file (no CRC footer, no resume coordinates) loads
    with defaulted coordinates — old runs keep resuming."""
    import jax
    from flax import serialization

    state = tiny_state(1.5)
    legacy_payload = {
        "epoch": 2,
        "arch": "toy",
        "best_acc1": 5.0,
        "step": jax.device_get(state.step),
        "params": jax.device_get(state.params),
        "batch_stats": {},
        "opt_state": jax.device_get(state.opt_state),
        "training_time": -1.0,
        "qkv_layout": "",
    }
    path = str(tmp_path / "checkpoint.pth.tar")
    open(path, "wb").write(serialization.to_bytes(legacy_payload))
    ok, reason = verify_checkpoint(path)
    assert ok and "legacy" in reason
    new, meta = load_checkpoint(path, tiny_state())
    assert meta["epoch"] == 2
    assert meta["step_in_epoch"] == 0  # defaulted: boundary semantics
    np.testing.assert_array_equal(
        new.params["dense"]["kernel"], state.params["dense"]["kernel"]
    )


# -- the streamed save ---------------------------------------------------------

def _mixed_payload():
    """A payload as ``save_checkpoint`` builds one: plain values beside
    trees whose leaves have 0, 1 and over 2**20 entries, in mixed dtypes
    and layouts, scalars of numpy's, a tuple and a list among the nodes."""
    import ml_dtypes

    rng = np.random.RandomState(0)
    return {
        "epoch": 3, "arch": "toy", "best_acc1": 1.5, "flag": True,
        "none": None, "cplx": 1 + 2j,
        # a float32 scalar packs to 16 bytes, one of msgpack's fixed-size
        # extensions (1, 2, 4, 8, 16), which have a header of their own
        "step": np.asarray(7, np.int32), "generation": np.float32(2.5),
        "params": {
            "empty": rng.randn(0).astype(np.float32),
            "hollow": rng.randn(2, 0, 3).astype(np.float16),
            "one": rng.randn(1).astype(np.float32),
            "big": rng.randn(1025, 1031).astype(np.float32),  # 2**20 + 7,351
            "bf16": rng.randn(4, 5).astype(ml_dtypes.bfloat16),
            "flags": rng.randint(0, 2, (300,)).astype(bool),
            "int8": rng.randint(0, 9, (70000,)).astype(np.int8),
            "fortran": np.asfortranarray(rng.randn(7, 9)),
            "device": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "i8x1": np.zeros((1,), np.int8), "i8x8": np.zeros((8,), np.int8),
        },
        "opt_state": ({"mu": rng.randn(3, 3).astype(np.float32)}, (), [1, 2]),
    }


@pytest.mark.parametrize("chunk_bytes", [None, 1000],
                         ids=["whole-leaves", "chunked-leaves"])
def test_the_streamed_save_is_the_old_forms_bytes(monkeypatch, chunk_bytes):
    import io

    from flax import serialization

    from dptpu.train import checkpoint as ckpt

    if chunk_bytes:  # flax cuts a leaf over this into a dict of chunks
        monkeypatch.setattr(serialization, "MAX_CHUNK_SIZE", chunk_bytes)
    payload = _mixed_payload()
    want = ckpt.seal_payload(serialization.to_bytes(payload))
    out = io.BytesIO()
    written, encode_s = ckpt.stream_sealed(payload, out.write)
    assert out.getvalue() == want
    assert written == len(want) and encode_s >= 0
    body, verified = ckpt.split_payload(out.getvalue())
    assert verified and body == want[:-ckpt._FOOTER_LEN]


def test_save_checkpoint_writes_the_old_forms_file_and_reports(tmp_path,
                                                               monkeypatch):
    from flax import serialization

    from dptpu.train import checkpoint as ckpt

    seen = []
    stream = ckpt.stream_sealed
    monkeypatch.setattr(
        ckpt, "stream_sealed",
        lambda payload, write: seen.append(payload) or stream(payload, write))
    report = {}
    path = save_checkpoint(tiny_state(2.5), epoch=3, arch="toy",
                           best_acc1=12.5, is_best=True,
                           directory=str(tmp_path), report=report)
    (payload,) = seen
    want = ckpt.seal_payload(serialization.to_bytes(payload))
    assert open(path, "rb").read() == want
    assert open(tmp_path / "model_best.pth.tar", "rb").read() == want
    assert not os.path.exists(path + ".tmp")
    assert report["bytes"] == len(want)
    assert min(report["fetch_s"], report["encode_s"], report["store_s"]) >= 0


def test_a_truncated_stream_fails_its_crc():
    from dptpu.train import checkpoint as ckpt

    pieces = []
    ckpt.stream_sealed(_mixed_payload(), lambda p: pieces.append(bytes(p)))
    whole = b"".join(pieces)
    assert ckpt.split_payload(whole)[1]
    longest = max(range(len(pieces)), key=lambda i: len(pieces[i]))
    # a leaf that never reached the file, and a leaf cut short
    for torn in (b"".join(pieces[:longest] + pieces[longest + 1:]),
                 b"".join(pieces[:longest] + [pieces[longest][:-64]]
                          + pieces[longest + 1:])):
        with pytest.raises(CorruptCheckpointError, match="checksum"):
            ckpt.split_payload(torn)


def test_the_streamed_save_holds_less_than_two_leaves_beside_the_state(
        tmp_path):
    """Six leaves of 4 MB: the old form held the state twice more
    (``to_bytes`` and the sealed copy); the stream holds headers."""
    import tracemalloc

    from flax import serialization

    from dptpu.data.store import open_store
    from dptpu.train import checkpoint as ckpt

    leaf = 4 * 2 ** 20
    payload = {"params": {f"w{i}": np.full((leaf // 4,), i, np.float32)
                          for i in range(6)}, "epoch": 1}

    def peak_of(save):
        tracemalloc.start()
        try:
            save()
            return tracemalloc.get_traced_memory()[1]
        finally:
            tracemalloc.stop()

    store = open_store(str(tmp_path))
    streamed = peak_of(lambda: store.put_stream(
        "new.bin", lambda write: ckpt.stream_sealed(payload, write)))
    old = peak_of(lambda: store.put_bytes(
        "old.bin", ckpt.seal_payload(serialization.to_bytes(payload))))
    assert streamed < 2 * leaf, streamed
    assert old > 2 * 6 * leaf, old
    assert open(tmp_path / "new.bin", "rb").read() \
        == open(tmp_path / "old.bin", "rb").read()


# -- rotation + fallback -----------------------------------------------------

def test_rotation_keeps_last_k(tmp_path):
    manager = CheckpointManager(directory=str(tmp_path), keep=2, arch="toy")
    for step in range(1, 5):
        manager.save_step(tiny_state(float(step)), epoch=0,
                          step_in_epoch=step)
    names = sorted(f for f in os.listdir(tmp_path) if "checkpoint-e" in f)
    assert names == [step_checkpoint_name(0, 3), step_checkpoint_name(0, 4)]


def test_find_resumable_falls_back_past_corrupt(tmp_path):
    manager = CheckpointManager(directory=str(tmp_path), keep=3, arch="toy")
    paths = [
        manager.save_step(tiny_state(float(s)), epoch=0, step_in_epoch=s)
        for s in (1, 2, 3)
    ]
    assert find_resumable(str(tmp_path)) == paths[-1]
    with open(paths[-1], "r+b") as f:  # tear the newest
        f.truncate(os.path.getsize(paths[-1]) // 2)
    assert find_resumable(str(tmp_path)) == paths[-2]
    # an explicitly-named corrupt FILE also falls back to its siblings
    assert find_resumable(paths[-1]) == paths[-2]
    # resume coordinates of the survivor point at step 2
    _, meta = load_checkpoint(find_resumable(str(tmp_path)), tiny_state())
    assert meta["step_in_epoch"] == 2


def test_find_resumable_missing_paths(tmp_path):
    assert find_resumable(str(tmp_path / "nope.pth.tar")) is None
    assert find_resumable(str(tmp_path)) is None  # empty dir


# -- preemption guard --------------------------------------------------------

def test_preemption_guard_catches_sigterm_and_restores_handler():
    before = signal.getsignal(signal.SIGTERM)
    with PreemptionGuard() as guard:
        assert not guard.requested
        os.kill(os.getpid(), signal.SIGTERM)
        for _ in range(10000):  # let the Python-level handler run
            if guard.requested:
                break
        assert guard.requested
        assert guard.signal_name == "SIGTERM"
    assert signal.getsignal(signal.SIGTERM) is before


def test_preemption_guard_second_signal_aborts():
    with PreemptionGuard() as guard:
        guard._handler(signal.SIGTERM, None)
        assert guard.requested
        with pytest.raises(KeyboardInterrupt):
            guard._handler(signal.SIGTERM, None)


# -- async checkpoint writer -------------------------------------------------

def test_async_saves_land_identical_in_order_and_rotate(tmp_path):
    """Cadence saves through the writer thread must produce the same
    verifiable files a synchronous manager writes, in submission order,
    with rotation applied."""
    from dptpu.train.checkpoint import AsyncCheckpointWriter

    w = AsyncCheckpointWriter()
    manager = CheckpointManager(directory=str(tmp_path), keep=2,
                                batch_size=4, async_writer=w)
    paths = [
        manager.save_step(tiny_state(float(s)), epoch=0, step_in_epoch=s)
        for s in (1, 2, 3)
    ]
    w.flush()
    # rotation kept the newest two; every survivor verifies and carries
    # its exact resume coordinates
    assert not os.path.exists(paths[0])
    for s, p in zip((2, 3), paths[1:]):
        ok, reason = verify_checkpoint(p)
        assert ok, reason
        restored, meta = load_checkpoint(p, tiny_state())
        assert meta["step_in_epoch"] == s
        assert meta["data_position"] == s * 4
        np.testing.assert_array_equal(
            restored.params["dense"]["kernel"],
            tiny_state(float(s)).params["dense"]["kernel"],
        )
    w.close()


def test_sync_save_drains_queue_first_so_newest_wins(tmp_path):
    """A preemption/emergency save (sync=True) must flush queued async
    saves before writing, so the newest-mtime file — what find_resumable
    trusts — is the true latest position."""
    from dptpu.train.checkpoint import AsyncCheckpointWriter

    w = AsyncCheckpointWriter()
    manager = CheckpointManager(directory=str(tmp_path), keep=3,
                                async_writer=w)
    manager.save_step(tiny_state(1.0), epoch=0, step_in_epoch=1)
    final = manager.save_step(tiny_state(2.0), epoch=0, step_in_epoch=2,
                              sync=True)
    assert os.path.exists(final)  # durable the moment the call returns
    assert find_resumable(str(tmp_path), verbose=False) == final
    w.close()


def test_async_write_error_surfaces_on_next_call(tmp_path):
    """A failed background write must fail the run loudly on the next
    checkpoint call — never vanish."""
    from dptpu.train.checkpoint import AsyncCheckpointWriter

    w = AsyncCheckpointWriter()
    # a write closure that raises — the manager enqueues through the
    # identical submit path
    w.submit(lambda: (_ for _ in ()).throw(OSError("disk on fire")))
    with pytest.raises(RuntimeError, match="async checkpoint write failed"):
        w.flush()
    # the writer recovers: later saves work again
    ok_dir = tmp_path / "ok"
    manager2 = CheckpointManager(directory=str(ok_dir), keep=2,
                                 async_writer=w)
    p = manager2.save_step(tiny_state(), epoch=0, step_in_epoch=1)
    w.flush()
    assert os.path.exists(p)
    w.close()


def test_ckpt_truncate_fault_counts_async_writes_in_order(tmp_path):
    """The ckpt_truncate@save=N fault hook rides the writer thread, so
    'the N-th checkpoint written' keeps meaning write order under async
    saves."""
    from dptpu.train.checkpoint import AsyncCheckpointWriter

    plan = FaultPlan("ckpt_truncate@save=2")
    w = AsyncCheckpointWriter()
    manager = CheckpointManager(directory=str(tmp_path), keep=3,
                                fault_plan=plan, async_writer=w)
    p1 = manager.save_step(tiny_state(1.0), epoch=0, step_in_epoch=1)
    p2 = manager.save_step(tiny_state(2.0), epoch=0, step_in_epoch=2)
    w.flush()
    ok1, _ = verify_checkpoint(p1)
    ok2, reason2 = verify_checkpoint(p2)
    assert ok1
    assert not ok2, "save #2 should have been torn by the fault"
    w.close()
