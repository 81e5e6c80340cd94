"""Process-mode (shared-memory ring) loader: parity, cache, errors.

The contract under test (dptpu/data/shm.py + loader.py): for the same
``(seed, epoch, index)`` RNG, ``workers_mode="process"`` must yield
BATCHES BIT-IDENTICAL to thread mode — same pixels, labels, pad/mask
semantics — because workers run the exact same span-decode path, only
into shared memory instead of a same-process array. A worker decode
error must surface as a parent-side exception carrying the worker's
traceback, never a hang.

JPEG fixtures are 52×44 (< 48·8/7): the native scale picker then stays
at full resolution, which also makes cache-on/off comparisons bit-exact
(see ImageFolderDataset docstring).
"""

import numpy as np
import pytest
from PIL import Image

from dptpu.data import (
    DataLoader,
    ImageFolderDataset,
    train_transform,
)


@pytest.fixture(scope="module")
def jpeg_folder(tmp_path_factory):
    root = tmp_path_factory.mktemp("shmjpeg")
    rng = np.random.RandomState(0)
    for cls in ["c0", "c1"]:
        d = root / cls
        d.mkdir()
        for i in range(9):
            low = rng.randint(0, 255, (8, 7, 3), np.uint8)
            img = Image.fromarray(low).resize((52, 44), Image.BILINEAR)
            img.save(str(d / f"{i}.jpg"), quality=85)
    return str(root)


class CrashAtFive:
    """Decode-error fixture — module level so spawn can pickle it."""

    def __len__(self):
        return 12

    def get(self, index, rng=None):
        if index == 5:
            raise ValueError("decode exploded on sample 5")
        return np.full((8, 8, 3), index, np.uint8), index

    def get_into(self, index, rng, out):
        img, lab = self.get(index, rng)
        np.copyto(out, img)
        return lab

    def __getitem__(self, index):
        return self.get(index)


def _assert_batches_equal(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["images"], y["images"])
        np.testing.assert_array_equal(x["labels"], y["labels"])
        assert ("mask" in x) == ("mask" in y)
        if "mask" in x:
            np.testing.assert_array_equal(x["mask"], y["mask"])


def test_process_loader_bit_identical_to_thread(jpeg_folder):
    ds = ImageFolderDataset(jpeg_folder, train_transform(48))  # 18 samples
    th = DataLoader(ds, 4, num_workers=2, seed=5)
    pr = DataLoader(ds, 4, num_workers=2, seed=5, workers_mode="process")
    try:
        for epoch in (0, 1):
            a, b = list(th.epoch(epoch)), list(pr.epoch(epoch))
            assert len(a) == 5  # ceil(18/4): padded+masked tail included
            _assert_batches_equal(a, b)
        # abandoning an epoch mid-flight must not wedge the slot ring
        it = pr.epoch(2)
        next(it)
        del it
        _assert_batches_equal(list(th.epoch(3)), list(pr.epoch(3)))
    finally:
        th.close()
        pr.close()


@pytest.mark.parametrize("leased", [False, True])
@pytest.mark.parametrize("pad_final", [False, True])
def test_process_loader_bit_identical_to_thread_on_token_rows(leased,
                                                              pad_final):
    """The same contract for rows that are no image: int32 ids through
    the ring's ``item_dtype`` and the dataset's own ``collate`` (three
    arrays a batch, the row mask folded into the token mask), on the
    copy path and on leased views, with and without a padded tail."""
    from dptpu.data.tokens import KEYS, TokenDataset

    ds = TokenDataset(22, 40, 500, 3)  # 22 rows: 5 batches of 4 and a tail
    kwargs = dict(num_workers=2, seed=9, drop_last=not pad_final,
                  pad_final=pad_final)
    th = DataLoader(ds, 4, **kwargs)
    pr = DataLoader(ds, 4, workers_mode="process", leased=leased, **kwargs)
    try:
        for epoch in (0, 1):
            want = list(th.epoch(epoch))
            # a leased batch is a view that lives until the next one is
            # taken: copy it out as a consumer that releases would
            got = [{k: np.array(v) for k, v in b.items() if k != "_lease"}
                   for b in pr.epoch(epoch)]
            assert len(want) == len(got) == (6 if pad_final else 5)
            for a, b in zip(want, got):
                assert set(a) == set(b) == set(KEYS)
                for key in KEYS:
                    assert a[key].dtype == b[key].dtype, key
                    np.testing.assert_array_equal(a[key], b[key])
        assert pr.feed_stats()["bytes_copied_per_batch"] == (
            0.0 if leased else 4 * (41 * 4 + 4))
    finally:
        th.close()
        pr.close()


def test_process_loader_cache_parity_and_stats(jpeg_folder):
    """Per-worker decode caches change nothing about the pixels (hit and
    miss resample the same decoded buffer) and aggregate into
    ``feed_stats`` through the done-message piggyback."""
    ds_th = ImageFolderDataset(jpeg_folder, train_transform(48),
                               cache_bytes=32 << 20)
    ds_pr = ImageFolderDataset(jpeg_folder, train_transform(48),
                               cache_bytes=32 << 20)
    th = DataLoader(ds_th, 4, num_workers=2, seed=5)
    pr = DataLoader(ds_pr, 4, num_workers=2, seed=5,
                    workers_mode="process")
    try:
        for epoch in (0, 1):
            _assert_batches_equal(list(th.epoch(epoch)),
                                  list(pr.epoch(epoch)))
        fs = pr.feed_stats()
        assert fs["workers_mode"] == "process"
        assert fs["cache_hits"] > 0
        assert 0.0 < fs["cache_hit_rate"] <= 1.0
    finally:
        th.close()
        pr.close()


def test_worker_decode_error_propagates_with_traceback():
    loader = DataLoader(CrashAtFive(), 4, num_workers=2, seed=0,
                        workers_mode="process")
    try:
        with pytest.raises(RuntimeError, match="decode exploded on sample 5"):
            list(loader.epoch(0))
    finally:
        loader.close()


def test_invalid_workers_mode_rejected():
    with pytest.raises(ValueError, match="workers_mode"):
        DataLoader(CrashAtFive(), 4, workers_mode="greenlet")


# -- consumer-leased zero-copy slots ---------------------------------------

def test_leased_slot_not_recycled_while_put_in_flight(jpeg_folder):
    """The lease-lifetime contract: with a SLOW ``put`` (simulating the
    device transfer) the ring must not recycle the leased slot — the
    batch bytes read after the sleep must equal thread mode's, bit for
    bit, and the parent must have copied nothing."""
    import time

    from dptpu.data import DevicePrefetcher

    ds = ImageFolderDataset(jpeg_folder, train_transform(48))
    th = DataLoader(ds, 4, num_workers=2, seed=5)
    pr = DataLoader(ds, 4, num_workers=2, seed=5, workers_mode="process",
                    leased=True)
    try:
        ref = list(th.epoch(0))

        def slow_put(batch):
            # while we sleep, the loader keeps submitting ahead — only
            # the lease protocol stops a worker from overwriting these
            # exact rows before we read them
            time.sleep(0.1)
            return {k: np.array(v) for k, v in batch.items()}

        got = list(DevicePrefetcher(pr.epoch(0), put=slow_put,
                                    copy_before_put=False))
        _assert_batches_equal(ref, got)
        fs = pr.feed_stats()
        assert fs["leased"] is True
        assert fs["bytes_copied_per_batch"] == 0.0
        # epoch 2: the ring and its leases recycle cleanly
        _assert_batches_equal(
            list(th.epoch(1)),
            list(DevicePrefetcher(pr.epoch(1), put=slow_put,
                                  copy_before_put=False)),
        )
    finally:
        th.close()
        pr.close()


def test_leased_through_real_jax_put_bit_identical(jpeg_folder):
    """End-to-end through jax.device_put: on the CPU test backend the
    prefetcher must detect host-buffer aliasing and defend (copy before
    put); batches on 'device' must match thread mode after the ring has
    long recycled the slots."""
    import jax

    from dptpu.data import DevicePrefetcher

    ds = ImageFolderDataset(jpeg_folder, train_transform(48))
    th = DataLoader(ds, 4, num_workers=2, seed=9)
    pr = DataLoader(ds, 4, num_workers=2, seed=9, workers_mode="process",
                    leased=True)
    try:
        ref = list(th.epoch(0))
        dev = list(DevicePrefetcher(pr.epoch(0), put=jax.device_put))
        assert len(ref) == len(dev)
        for a, b in zip(ref, dev):
            np.testing.assert_array_equal(a["images"],
                                          np.asarray(b["images"]))
            np.testing.assert_array_equal(a["labels"],
                                          np.asarray(b["labels"]))
            assert "_lease" not in b  # the prefetcher consumed the token
    finally:
        th.close()
        pr.close()


def test_lease_release_is_idempotent_and_generation_checked():
    from dptpu.data import SyntheticDataset

    ds = SyntheticDataset(24, 8, 10)
    pr = DataLoader(ds, 8, num_workers=2, seed=0, workers_mode="process",
                    leased=True)
    try:
        it = pr.epoch(0)
        b0 = next(it)
        lease = b0["_lease"]
        lease.release()
        lease.release()  # double release: no-op
        rest = list(it)  # backstop releases ride the generator
        assert len(rest) == 2
        lease.release()  # stale (slot long recycled): generation no-op
        # the ring is fully free again: a fresh epoch works
        assert len(list(pr.epoch(1))) == 3
    finally:
        pr.close()


def test_affinity_spans_cover_batch_and_balance():
    from dptpu.data.shm import _affinity_of, _affinity_spans

    idxs = list(range(1000, 1064))
    spans = _affinity_spans(idxs, 4)
    seen = {}
    for wid, offsets, span_idxs in spans:
        assert len(offsets) == len(span_idxs)
        assert len(offsets) <= -(-64 // 4)  # rebalanced to cap
        for o, i in zip(offsets, span_idxs):
            assert o not in seen
            seen[o] = (wid, i)
    assert sorted(seen) == list(range(64))  # every row exactly once
    assert sorted(i for _, i in seen.values()) == idxs
    # determinism: the same index routes to the same worker every time
    assert _affinity_spans(idxs, 4) == spans
    for i in idxs:
        assert _affinity_of(i, 4) == _affinity_of(i, 4)


def test_shard_affinity_routes_whole_shard_to_one_worker():
    """Shard-level decode-cache affinity (ISSUE 10 satellite): with an
    ``affinity_key`` (a packed-shard dataset's ``shard_of``), every
    sample of one shard hashes to the SAME worker — stable in the
    SHARD id, so the routing survives any sampler reshuffle — up to
    the ceil(B/N) rebalance cap (utilization still beats affinity for
    overflow)."""
    from dptpu.data.shm import _affinity_of, _affinity_spans

    shard_of = lambda i: i // 16  # noqa: E731 — 16-sample shards
    # pick 4 shards that hash to 4 DISTINCT workers (no collision, so
    # no rebalance overflow): each worker gets exactly ceil(B/N) and
    # every shard must stay whole
    shards, targets = [], set()
    for s in range(64):
        w = _affinity_of(s, 4)
        if w not in targets:
            targets.add(w)
            shards.append(s)
        if len(shards) == 4:
            break
    idxs = [s * 16 + j for j in range(8) for s in shards]  # interleaved
    spans = _affinity_spans(idxs, 4, shard_of)
    worker_of = {}
    for wid, offsets, span_idxs in spans:
        assert len(offsets) <= -(-len(idxs) // 4)  # rebalance cap holds
        for i in span_idxs:
            worker_of[i] = wid
    assert sorted(worker_of) == sorted(idxs)
    for s in shards:
        workers = {worker_of[s * 16 + j] for j in range(8)}
        assert workers == {_affinity_of(s, 4)}  # whole shard, one worker
    # with hash collisions the ceil(B/N) rebalance may split ONLY the
    # overflow (utilization beats affinity there): cap still holds and
    # non-overflowing shards stay whole
    mixed = [s * 16 + j for j in range(8) for s in range(8)]
    mixed_spans = _affinity_spans(mixed, 4, shard_of)
    loads = {}
    for s in range(8):
        loads.setdefault(_affinity_of(s, 4), []).append(s)
    whole = {i: w for w, offs, sidx in mixed_spans for i, w in
             zip(sidx, [w] * len(sidx))}
    for w, ss in loads.items():
        if len(ss) * 8 <= -(-64 // 4):  # this worker never overflowed
            for s in ss:
                assert {whole[s * 16 + j] for j in range(8)} == {w}
    # and the grouping is BY SHARD, not by index: two samples of one
    # shard with very different indices share a worker pre-rebalance
    for s in range(8):
        assert _affinity_of(shard_of(s * 16), 4) == \
            _affinity_of(shard_of(s * 16 + 7), 4)


def test_feed_stats_records_span_routing(tmp_path):
    """The routing mode is observable: ``span_routing`` reads "shard"
    for a dataset exposing shard_of, "index" otherwise, "contiguous"
    with affinity off — before AND after the lazy pipeline exists."""
    from dptpu.data.loader import DataLoader
    from dptpu.data.sampler import ShardedSampler

    class _FakeShardDS:
        """Minimal dataset surface; never decoded (no epochs run)."""

        def __len__(self):
            return 32

        def shard_of(self, i):
            return i // 8

    class _FakeDS:
        def __len__(self):
            return 32

    for ds, affinity, expect in (
        (_FakeShardDS(), True, "shard"),
        (_FakeDS(), True, "index"),
        (_FakeShardDS(), False, "contiguous"),
    ):
        dl = DataLoader(
            ds, 8, sampler=ShardedSampler(32, shuffle=False),
            num_workers=2, workers_mode="process",
            span_affinity=affinity,
        )
        try:
            assert dl.feed_stats()["span_routing"] == expect
        finally:
            dl.close()


def test_degrade_to_thread_with_leases_held(monkeypatch):
    """A pool that hangs past its restart budget must degrade to thread
    mode even mid-leased-epoch: the retiring pipeline tolerates the
    consumer's outstanding views (BufferError-safe close, generation-
    checked lease release) and the thread path re-decodes the unyielded
    tail — batches stay bit-identical across the hand-off."""
    from dptpu.data import DevicePrefetcher, SyntheticDataset

    monkeypatch.setenv("DPTPU_FAULT", "worker_hang@index=3")
    monkeypatch.setenv("DPTPU_WORKER_TIMEOUT_S", "1")
    monkeypatch.setenv("DPTPU_POOL_RESTARTS", "1")
    ds = SyntheticDataset(32, 8, 10)
    th = DataLoader(ds, 4, num_workers=2, seed=3)
    pr = DataLoader(ds, 4, num_workers=2, seed=3, workers_mode="process",
                    leased=True)
    try:
        ref = list(th.epoch(0))

        def put(batch):
            return {k: np.array(v) for k, v in batch.items()}

        got = list(DevicePrefetcher(pr.epoch(0), put=put,
                                    copy_before_put=False))
        assert len(got) == len(ref)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a["images"], b["images"])
            np.testing.assert_array_equal(a["labels"], b["labels"])
        assert pr.workers_mode == "thread"
        assert pr.feed_stats()["degraded"] is True
    finally:
        th.close()
        pr.close()


# -- decode-ahead pipelined feed -------------------------------------------

def test_decode_ahead_bit_identical_across_depths(jpeg_folder):
    """The tentpole contract: deep multi-batch span pre-issue (out-of-
    order completion, workers rolling across batch boundaries) changes
    NOTHING about the bytes — decode_ahead=1 (batch-serial baseline),
    a deep ring, and thread mode all agree bit for bit, epoch after
    epoch."""
    ds = ImageFolderDataset(jpeg_folder, train_transform(48))
    th = DataLoader(ds, 4, num_workers=2, seed=5)
    serial = DataLoader(ds, 4, num_workers=2, seed=5,
                        workers_mode="process", decode_ahead=1)
    deep = DataLoader(ds, 4, num_workers=2, seed=5,
                      workers_mode="process", decode_ahead=5, ring_depth=8)
    try:
        for epoch in (0, 1):
            ref = list(th.epoch(epoch))
            _assert_batches_equal(ref, list(serial.epoch(epoch)))
            _assert_batches_equal(ref, list(deep.epoch(epoch)))
        fs = deep.feed_stats()
        assert fs["ring_depth"] == 8
        # the pump actually ran ahead (5 batches of lookahead over the
        # 5-batch epoch: every non-tail collect saw > 1 pre-issued)
        assert fs["issue_ahead_depth"] > 1.0
        assert serial.feed_stats()["issue_ahead_depth"] == 1.0
    finally:
        th.close()
        serial.close()
        deep.close()


def test_straggler_speculation_keeps_bit_identity(monkeypatch):
    """A worker stalled mid-span (worker_hang straggler mode: only
    worker 0, bounded sleep) must not gate the epoch: speculation
    re-issues its spans to a healthy worker, first-writer-wins, and the
    late twin's ghost ack is absorbed without corrupting any later
    batch — everything stays bit-identical, including the NEXT epoch
    (whose slots must not be recycled under a still-writing ghost)."""
    from dptpu.data import SyntheticDataset
    from dptpu.data.shm import _affinity_of

    ds = SyntheticDataset(48, 8, 10)
    th = DataLoader(ds, 8, num_workers=2, seed=3)
    stall = next(i for i in range(48) if _affinity_of(i, 2) == 0)
    monkeypatch.setenv("DPTPU_FAULT",
                       f"worker_hang@index={stall}@s=1@worker=0")
    monkeypatch.setenv("DPTPU_WORKER_TIMEOUT_S", "30")
    pr = DataLoader(ds, 8, num_workers=2, seed=3, workers_mode="process",
                    decode_ahead=4, ring_depth=8, speculate_after_s=0.1)
    try:
        ref0, ref1 = list(th.epoch(0)), list(th.epoch(1))
        _assert_batches_equal(ref0, list(pr.epoch(0)))
        fs = pr.feed_stats()
        assert fs["straggler_reissues"] >= 1
        # epoch 1 re-stalls on the same index; the ring keeps flowing
        # and the bytes keep matching (ghost quarantine did its job)
        _assert_batches_equal(ref1, list(pr.epoch(1)))
        assert pr.workers_mode == "process"  # no restart exhaustion
    finally:
        th.close()
        pr.close()


def test_duplicate_span_completion_is_ghosted():
    """Unit-level dup-ack safety: a second 'done' for an already-
    completed span (the speculative twin finishing late) must not drive
    the slot's completion counter negative or double-free the slot."""
    from dptpu.data import SyntheticDataset

    ds = SyntheticDataset(16, 8, 10)
    pr = DataLoader(ds, 8, num_workers=2, seed=0, workers_mode="process",
                    decode_ahead=1)
    try:
        batches = list(pr.epoch(0))
        assert len(batches) == 2
        pipe = pr._pipeline
        free_before = pipe.free_slot_count()
        # forge the late twin's acks: done AND error flavors of a span
        # that was already completed and whose slot was recycled
        pipe._extra_issues[0] = 2
        pipe._handle(("done", 0, 0, 0, 0, 0), mode="normal")
        pipe._handle(("error", 1, 0, 0, "late twin traceback"),
                     mode="normal")
        assert pipe._outstanding[0] == 0  # never went negative
        assert pipe._extra_issues[0] == 0  # both ghosts absorbed
        assert pipe.free_slot_count() == free_before  # no double-free
        # the ring still works end to end after the ghosts
        assert len(list(pr.epoch(1))) == 2
    finally:
        pr.close()


def test_pool_restart_with_preissued_spans_in_flight():
    """Supervisor restart under deep lookahead: killing a worker while
    spans for several future batches sit in its queue must re-enqueue
    ALL of them (the _pending map spans every pre-issued slot) and the
    epoch must complete bit-identically."""
    from dptpu.data import SyntheticDataset

    ds = SyntheticDataset(48, 8, 10)
    th = DataLoader(ds, 8, num_workers=2, seed=3)
    pr = DataLoader(ds, 8, num_workers=2, seed=3, workers_mode="process",
                    decode_ahead=5, ring_depth=8)
    try:
        ref = list(th.epoch(0))
        it = pr.epoch(0)
        got = [next(it)]  # the pump has now pre-issued deep lookahead
        assert pr.kill_one_worker() is not None
        got += list(it)
        _assert_batches_equal(ref, got)
        fs = pr.feed_stats()
        assert fs["pool_restarts"] >= 1
        assert "degraded" not in fs
    finally:
        th.close()
        pr.close()


def test_ring_rebuild_handles_shrink_and_lease_carryover():
    """The epoch ring-rebuild fix: a depth change between epochs
    rebuilds the ring in BOTH directions (growth AND shrink — the old
    code only grew), and a lease carried over from an abandoned epoch
    is revoked by the loader-initiated rebuild, not reported as a
    leak; its late release voids against the closed pipeline."""
    import dptpu.data.shm as shm
    from dptpu.data import SyntheticDataset

    leaks_before = shm.leaked_lease_count()
    ds = SyntheticDataset(48, 8, 10)
    th = DataLoader(ds, 8, num_workers=2, seed=3)
    pr = DataLoader(ds, 8, num_workers=2, seed=3, workers_mode="process",
                    leased=True)
    try:
        ref = list(th.epoch(1))
        it0 = pr.epoch(0, prefetch_batches=8)  # window 9 → deep ring
        b0 = next(it0)
        big = pr._pipeline.slots
        lease = b0["_lease"]  # deliberately NOT released; epoch abandoned
        # shrink: the next epoch wants a much smaller window. Leased
        # batches are views — copy before advancing (the lease contract)
        got = [
            {"images": np.array(b["images"]), "labels": np.array(b["labels"])}
            for b in pr.epoch(1, prefetch_batches=0)
        ]
        small = pr._pipeline.slots
        assert small < big  # the ring actually rebuilt downward
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a["images"], b["images"])
            np.testing.assert_array_equal(a["labels"], b["labels"])
        lease.release()  # stale: voids against the closed old pipeline
        assert shm.leaked_lease_count() == leaks_before  # forgiven
    finally:
        del it0
        th.close()
        pr.close()
    assert shm.leaked_lease_count() == leaks_before


def test_close_with_unreleased_lease_counts_as_leak():
    """The conftest lease-leak guard's hook: closing the loader while a
    consumer still holds an unreleased lease (no reset/rebuild ever
    revoked it) must advance the module leak counter."""
    import dptpu.data.shm as shm
    from dptpu.data import SyntheticDataset

    before = shm.leaked_lease_count()
    ds = SyntheticDataset(24, 8, 10)
    pr = DataLoader(ds, 8, num_workers=2, seed=0, workers_mode="process",
                    leased=True)
    it = pr.epoch(0)
    batch = next(it)  # generator suspended: the backstop has NOT run
    pr.close()
    assert shm.leaked_lease_count() == before + 1
    # this leak was deliberate — restore the counter so the session
    # fixture keeps policing the REST of the suite
    shm._LEASE_LEAKS = before
    del batch, it


def test_affinity_off_still_bit_identical(jpeg_folder):
    ds = ImageFolderDataset(jpeg_folder, train_transform(48))
    th = DataLoader(ds, 4, num_workers=2, seed=3)
    pr = DataLoader(ds, 4, num_workers=2, seed=3, workers_mode="process",
                    span_affinity=False)
    try:
        for epoch in (0, 1):
            _assert_batches_equal(list(th.epoch(epoch)),
                                  list(pr.epoch(epoch)))
    finally:
        th.close()
        pr.close()


def test_a_pools_size_leaves_the_bytes_alone(jpeg_folder):
    """What lets ``build_feed`` size the pool by the host: process pools
    of 1, 2 and 5 workers (5 divides neither the batch of 4 nor its
    tail of 2) yield the same bytes for the same seed, the bytes of the
    thread pool. A row's randomness is ``(seed, epoch, index)``, never
    the worker that made it."""
    ds = ImageFolderDataset(jpeg_folder, train_transform(48))  # 18 samples
    th = DataLoader(ds, 4, num_workers=2, seed=11)
    pools = [DataLoader(ds, 4, num_workers=n, seed=11,
                        workers_mode="process") for n in (1, 2, 5)]
    try:
        for epoch in (0, 1):
            want = list(th.epoch(epoch))
            assert len(want) == 5
            for pr in pools:
                _assert_batches_equal(want, list(pr.epoch(epoch)))
        assert [pr.feed_stats()["num_workers"] for pr in pools] == [1, 2, 5]
    finally:
        th.close()
        for pr in pools:
            pr.close()
