"""Store abstraction (dptpu/data/store.py): local + HTTP range fetch,
retry/backoff, fault injection, and checkpoint-through-store round trips
(the --ckpt-dir satellite's contract: CRC footer + fallback scan,
bit-for-bit, whichever backend holds the bytes)."""

import os

import numpy as np
import pytest

from dptpu.data.store import (
    HTTPStore,
    LocalStore,
    ShardByteCache,
    StoreError,
    dev_store_server,
    is_store_url,
    open_store,
    split_store_url,
)


@pytest.fixture()
def served(tmp_path):
    root = tmp_path / "objs"
    root.mkdir()
    server, url = dev_store_server(str(root))
    yield str(root), url
    server.shutdown()


def test_open_store_dispatch(tmp_path):
    assert isinstance(open_store(str(tmp_path)), LocalStore)
    assert isinstance(open_store(f"file://{tmp_path}"), LocalStore)
    assert isinstance(open_store("http://h:1/x"), HTTPStore)
    assert is_store_url("https://h/x") and not is_store_url(str(tmp_path))
    assert split_store_url("http://h:1/a/b/c.bin") == ("http://h:1/a/b",
                                                      "c.bin")


def test_local_store_roundtrip(tmp_path):
    s = LocalStore(str(tmp_path / "sub"))
    s.put_bytes("a.bin", b"hello world")
    assert s.get_bytes("a.bin") == b"hello world"
    assert s.get_range("a.bin", 6, 5) == b"world"
    assert s.size("a.bin") == 11
    s.copy("a.bin", "b.bin")
    names = {n for n, _ in s.list()}
    assert names == {"a.bin", "b.bin"}
    s.delete("b.bin")
    assert {n for n, _ in s.list()} == {"a.bin"}
    # put is atomic-overwrite: no .tmp litter
    s.put_bytes("a.bin", b"v2")
    assert s.get_bytes("a.bin") == b"v2"
    assert not any(n.endswith(".tmp") for n, _ in s.list())
    with pytest.raises(FileNotFoundError):
        s.get_bytes("missing.bin")


@pytest.mark.parametrize("kind", ["local", "http"])
def test_put_stream_writes_the_pieces_in_order(served, kind):
    """An object handed over piece by piece (bytes, a view of an array's
    memory) is the pieces joined; a directory takes them straight into
    its temporary file, a backend without a streaming write gathers
    them."""
    import numpy as np

    root, url = served
    s = LocalStore(root) if kind == "local" else HTTPStore(url)
    array = np.arange(1000, dtype=np.float32)
    calls = []

    def produce(write):
        calls.append(1)
        write(b"head")
        write(memoryview(array.view(np.uint8)))
        write(b"")
        write(b"tail")

    s.put_stream("piecewise.bin", produce)
    assert s.get_bytes("piecewise.bin") == b"head" + array.tobytes() + b"tail"
    assert calls == [1] and os.listdir(root) == ["piecewise.bin"]


def test_http_store_roundtrip_and_ranges(served):
    root, url = served
    s = HTTPStore(url)
    s.put_bytes("x/data.bin", bytes(range(200)))
    assert s.get_bytes("x/data.bin") == bytes(range(200))
    assert s.get_range("x/data.bin", 10, 5) == bytes(range(10, 15))
    assert s.size("x/data.bin") == 200
    sub = HTTPStore(f"{url}/x")
    assert {n for n, _ in sub.list()} == {"data.bin"}
    sub.delete("data.bin")
    with pytest.raises(FileNotFoundError):
        sub.get_bytes("data.bin")
    assert s.retry_count == 0  # 404/absence is an answer, never retried


def test_http_store_retries_transient_5xx(tmp_path):
    root = tmp_path / "objs"
    root.mkdir()
    (root / "a.bin").write_bytes(b"payload")
    server, url = dev_store_server(str(root), fail_first=2)
    try:
        s = HTTPStore(url, retries=4, backoff_s=0.01)
        assert s.get_bytes("a.bin") == b"payload"
        assert s.retry_count == 2  # burned exactly the two injected 503s
        assert s.wait_s > 0.0
    finally:
        server.shutdown()


def test_http_store_exhausted_retries_raise(tmp_path):
    root = tmp_path / "objs"
    root.mkdir()
    (root / "a.bin").write_bytes(b"payload")
    server, url = dev_store_server(str(root), fail_first=50)
    try:
        s = HTTPStore(url, retries=2, backoff_s=0.0)
        with pytest.raises(StoreError, match="after 3 attempt"):
            s.get_bytes("a.bin")
    finally:
        server.shutdown()


def test_fault_injected_io_error_is_retried(tmp_path, monkeypatch):
    """DPTPU_FAULT=io_error:p=F injects OSError into store ops through
    FaultPlan.on_store_io; the retry engine absorbs them — the chaos
    contract FAULTBENCH's shard scenario runs at fit() scale."""
    monkeypatch.setenv("DPTPU_FAULT", "io_error:p=0.5")
    monkeypatch.setenv("DPTPU_FAULT_SEED", "3")
    s = LocalStore(str(tmp_path), retries=50, backoff_s=0.0)
    s.put_bytes("a.bin", b"x" * 64)
    total_retries = 0
    for _ in range(20):
        assert s.get_bytes("a.bin") == b"x" * 64
    total_retries = s.retry_count
    assert total_retries > 0, "p=0.5 over 20+ ops must inject at least once"


def test_store_knob_validation(monkeypatch):
    monkeypatch.setenv("DPTPU_STORE_RETRIES", "-1")
    with pytest.raises(ValueError, match="DPTPU_STORE_RETRIES"):
        LocalStore(".")
    monkeypatch.setenv("DPTPU_STORE_RETRIES", "junk")
    with pytest.raises(ValueError, match="not an integer"):
        LocalStore(".")
    monkeypatch.delenv("DPTPU_STORE_RETRIES")
    monkeypatch.setenv("DPTPU_STORE_BACKOFF_S", "-0.5")
    with pytest.raises(ValueError, match="DPTPU_STORE_BACKOFF_S"):
        LocalStore(".")


def test_shard_byte_cache_roundtrip_odd_lengths():
    cache = ShardByteCache(1 << 20)
    try:
        for n in (1, 2, 3, 7, 1024, 12345):
            payload = bytes((i * 31) % 256 for i in range(n))
            assert cache.put(("k", n), payload)
            assert cache.get(("k", n), n) == payload
        assert cache.get(("absent", 0), 16) is None
        stats = cache.stats()
        assert stats["shard_slab_hits"] >= 6
        assert stats["shard_slab_budget_bytes"] == 1 << 20
    finally:
        cache.close()


# ---- checkpoints through the store ----------------------------------------


def _tiny_state():
    import jax
    import optax
    from flax import linen as nn

    from dptpu.train.state import create_train_state

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(4)(x.reshape((x.shape[0], -1)))

    return create_train_state(
        jax.random.PRNGKey(0), Tiny(), optax.sgd(0.1),
        input_shape=(1, 4, 4, 3),
    )


def test_checkpoint_roundtrip_via_http_store(served):
    import jax
    import numpy as np

    from dptpu.resilience import find_resumable
    from dptpu.train.checkpoint import load_checkpoint, save_checkpoint

    root, url = served
    state = _tiny_state()
    ckpt_url = f"{url}/run"
    path = save_checkpoint(
        state, epoch=3, arch="tiny", best_acc1=1.0, is_best=True,
        directory=ckpt_url, step_in_epoch=5, data_position=40,
    )
    assert path == f"{ckpt_url}/checkpoint.pth.tar"
    # the bytes on the far side carry the CRC footer: the store changed,
    # the seal did not
    raw = open(os.path.join(root, "run", "checkpoint.pth.tar"), "rb").read()
    from dptpu.train.checkpoint import CRC_MAGIC, split_payload

    _, verified = split_payload(raw)
    assert verified and CRC_MAGIC in raw[-12:]
    # is_best copied model_best alongside
    assert os.path.exists(os.path.join(root, "run", "model_best.pth.tar"))

    resolved = find_resumable(ckpt_url, verbose=False)
    assert resolved == path
    restored, meta = load_checkpoint(resolved, _tiny_state())
    assert meta["epoch"] == 3 and meta["step_in_epoch"] == 5
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(state.params)),
                    jax.tree_util.tree_leaves(
                        jax.device_get(restored.params))):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_store_resume_falls_back_past_corrupt(served):
    """The find_resumable fallback-scan contract over a store URL: the
    newest object is torn (truncated behind the server), the scan skips
    it and lands on the older verifiable save."""
    import time

    from dptpu.resilience import find_resumable, step_checkpoint_name
    from dptpu.train.checkpoint import save_checkpoint

    root, url = served
    state = _tiny_state()
    ckpt_url = f"{url}/run"
    save_checkpoint(state, epoch=0, arch="tiny", best_acc1=0.0,
                    is_best=False, directory=ckpt_url,
                    filename=step_checkpoint_name(0, 2), step_in_epoch=2)
    time.sleep(0.05)  # distinct mtimes: the scan orders by save time
    save_checkpoint(state, epoch=0, arch="tiny", best_acc1=0.0,
                    is_best=False, directory=ckpt_url,
                    filename=step_checkpoint_name(0, 4), step_in_epoch=4)
    newest = os.path.join(root, "run", step_checkpoint_name(0, 4))
    with open(newest, "r+b") as f:
        f.truncate(os.path.getsize(newest) // 2)
    resolved = find_resumable(ckpt_url, verbose=False)
    assert resolved == f"{ckpt_url}/{step_checkpoint_name(0, 2)}"
    # a direct file URL that verifies resolves to itself
    assert find_resumable(resolved, verbose=False) == resolved


def test_checkpoint_manager_rotation_over_store(served):
    from dptpu.resilience import CheckpointManager, step_checkpoint_name

    root, url = served
    state = _tiny_state()
    mgr = CheckpointManager(directory=f"{url}/run", keep=2, arch="tiny")
    for step in (1, 2, 3):
        mgr.save_step(state, epoch=0, step_in_epoch=step, sync=True)
    names = sorted(os.listdir(os.path.join(root, "run")))
    assert step_checkpoint_name(0, 1) not in names  # rotated away
    assert step_checkpoint_name(0, 2) in names
    assert step_checkpoint_name(0, 3) in names
