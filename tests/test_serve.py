"""dptpu/serve acceptance locks (ISSUE 7).

* padded-bucket LOGIT IDENTITY — a request answered via bucket 16 with
  3 real rows equals the bucket-1 answer bit-for-bit (max|Δlogit| = 0),
  across a CNN (resnet18, BatchNorm trunk) and a ViT family (vit_b_32,
  LayerNorm/attention) — the engine's batch-invariant-numerics design
  (execution floor + single-thread-Eigen compile, dptpu/serve/engine.py);
* hot-swap DRAINING — swapping weights never drops an in-flight
  request, no batch is served with mixed-generation weights, and a
  superseded generation's buffers are dropped once its last batch lands;
* ``preprocess_bytes`` BIT-IDENTITY — request preprocessing equals the
  training/eval val pipeline's pixels for the same file;
* the continuous batcher's coalescing / backpressure / bad-request
  behavior and the staging ring's lease hygiene.
"""

import io
import os
import time

import numpy as np
import pytest

import jax

from dptpu.serve import DynamicBatcher, ServeEngine, preprocess_bytes
from dptpu.serve import staging as serve_staging
from dptpu.serve.engine import BUCKET_PARITY_ATOL


def _rand_images(n, size, seed=0):
    return np.random.RandomState(seed).randint(
        0, 256, (n, size, size, 3), np.uint8
    )


def _fresh_variables(engine, seed):
    init = engine.model.init(
        jax.random.PRNGKey(seed),
        np.zeros((1, engine.image_size, engine.image_size, 3), np.float32),
        train=False,
    )
    return {"params": init["params"],
            "batch_stats": init.get("batch_stats", {})}


@pytest.fixture(scope="module")
def cnn_engine():
    # buckets 1 and 16: the ISSUE's exact parity scenario; exec sizes
    # dedup to {2, 16}
    return ServeEngine("resnet18", buckets=(1, 4, 16), num_classes=8,
                       image_size=32)


@pytest.fixture(scope="module")
def vit_engine():
    # vit_b_32 at 64px (5 tokens) — the cheap ViT; auto placement takes
    # TP on the fake 8-device pod (tp_rule vit_tp_specs)
    return ServeEngine("vit_b_32", buckets=(1, 16), num_classes=8,
                       image_size=64)


# ---------------------------------------------------------------- parity ----


def _assert_bucket_parity(got, want):
    """Same row through two DIFFERENT exec sizes: fp32-rounding close
    (``BUCKET_PARITY_ATOL``; XLA:CPU's thread pool splits reductions by
    shape, so it is bit-exact only on a one-core host). Same-exec-size
    comparisons elsewhere in this module stay ``assert_array_equal``."""
    np.testing.assert_allclose(got, want, rtol=0, atol=BUCKET_PARITY_ATOL)


@pytest.mark.parametrize("fixture", ["cnn_engine", "vit_engine"])
def test_padded_bucket_logit_identity(fixture, request):
    """Bucket 16 with 3 real rows agrees with the bucket-1 answers."""
    engine = request.getfixturevalue(fixture)
    x = _rand_images(3, engine.image_size)
    solo = np.concatenate(
        [engine.infer(x[i:i + 1]) for i in range(3)]
    )  # three bucket-1 answers
    via16 = engine.infer(x)  # coalesced: bucket 16, 13 pad rows
    assert engine.bucket_for(3) in (4, 16)
    _assert_bucket_parity(via16, solo)


def test_pad_content_cannot_perturb_real_rows(cnn_engine):
    """Row independence: the same 3 real rows padded with DIFFERENT
    garbage give identical logits (the padded-execution contract is not
    'pads happen to be row-0')."""
    x = _rand_images(3, 32, seed=1)
    nexec = cnn_engine.exec_batch(16)
    a = np.concatenate([x, np.zeros((nexec - 3, 32, 32, 3), np.uint8)])
    b = np.concatenate([x, _rand_images(nexec - 3, 32, seed=9)])
    np.testing.assert_array_equal(
        cnn_engine.run_bucket(16, a, 3), cnn_engine.run_bucket(16, b, 3)
    )


def test_tp_placement_matches_replicated(vit_engine):
    if vit_engine.placement != "tp":
        pytest.skip("needs the multi-device fake pod")
    rep = ServeEngine(
        "vit_b_32", buckets=(1,), num_classes=8, image_size=64,
        placement="replicated",
        variables=jax.device_get(vit_engine._weights[
            vit_engine.current_generation]),
    )
    x = _rand_images(1, 64, seed=3)
    np.testing.assert_array_equal(vit_engine.infer(x), rep.infer(x))


def test_tp_per_shard_loading_matches_gathered(vit_engine):
    """ISSUE 18 satellite lock: TP weights load per-shard from the
    rules projection (``jax.make_array_from_callback``, no full-array
    gather) and the result is value- AND layout-identical to the old
    gather-then-reshard path, with max|Δlogit| = 0 through the compiled
    forward."""
    if vit_engine.placement != "tp":
        pytest.skip("needs the multi-device fake pod")
    gen = vit_engine.current_generation
    host = jax.device_get(vit_engine._weights[gen])
    per_shard = vit_engine._place(host)
    gathered = vit_engine._place_gathered(host)
    for a, b in zip(jax.tree_util.tree_leaves(per_shard),
                    jax.tree_util.tree_leaves(gathered)):
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    nexec = vit_engine.exec_batch(1)
    x = np.repeat(_rand_images(1, 64, seed=7), nexec, axis=0)
    out_a = np.asarray(vit_engine._compiled[("fp32", nexec)](per_shard, x))
    out_b = np.asarray(vit_engine._compiled[("fp32", nexec)](gathered, x))
    np.testing.assert_array_equal(out_a, out_b)  # max|Δlogit| = 0


def test_bucket_ladder_aot_and_bounds(cnn_engine):
    # the ladder is compiled up front: every bucket's exec size has an
    # executable before any request arrives
    assert set(cnn_engine._compiled) == {
        ("fp32", 2), ("fp32", 4), ("fp32", 16)
    }
    assert cnn_engine.bucket_for(1) == 1
    assert cnn_engine.bucket_for(5) == 16
    with pytest.raises(ValueError, match="largest bucket"):
        cnn_engine.bucket_for(17)


# -------------------------------------------------------------- batching ----


def test_batcher_parity_and_coalescing(cnn_engine):
    x = _rand_images(8, 32, seed=2)
    solo = np.concatenate(
        [cnn_engine.infer(x[i:i + 1]) for i in range(8)]
    )
    b = DynamicBatcher(cnn_engine, max_delay_ms=5.0, slots=3)
    try:
        futs = [b.submit_array(x[i % 8]) for i in range(32)]
        for i, f in enumerate(futs):
            _assert_bucket_parity(f.result(timeout=60), solo[i % 8])
        st = b.stats()
        assert st["completed"] == 32 and st["failed"] == 0
        # coalescing happened: fewer batches than requests, and some
        # batch used a multi-row bucket
        assert st["batches"] < 32
        assert any(k > 1 for k in st["bucket_counts"])
        assert 0.0 <= st["padding_waste"] < 1.0
    finally:
        b.close()


def test_batcher_zero_delay_serves_immediately(cnn_engine):
    b = DynamicBatcher(cnn_engine, max_delay_ms=0.0, slots=2)
    try:
        x = _rand_images(1, 32, seed=4)
        f = b.submit_array(x[0])
        out = f.result(timeout=60)
        np.testing.assert_array_equal(out, cnn_engine.infer(x)[0])
        assert f.timings["bucket"] == 1
    finally:
        b.close()


def test_bad_request_fails_alone_not_the_batch(cnn_engine):
    b = DynamicBatcher(cnn_engine, max_delay_ms=20.0, slots=2)
    try:
        x = _rand_images(2, 32, seed=5)
        good1 = b.submit_array(x[0])
        bad = b.submit_bytes(b"not an image")
        good2 = b.submit_array(x[1])
        with pytest.raises(ValueError, match="undecodable"):
            bad.result(timeout=60)
        solo = np.concatenate(
            [cnn_engine.infer(x[i:i + 1]) for i in range(2)]
        )
        _assert_bucket_parity(good1.result(timeout=60), solo[0])
        _assert_bucket_parity(good2.result(timeout=60), solo[1])
    finally:
        b.close()


# -------------------------------------------------------------- hot swap ----


def test_hot_swap_drains_without_mixing(cnn_engine):
    """Generation contract: a batch dispatched on gen G is served by G
    even if a swap lands mid-flight; every batch sees exactly one
    generation; the superseded generation drops once drained."""
    engine = ServeEngine("resnet18", buckets=(4,), num_classes=8,
                         image_size=32)
    x = _rand_images(4, 32, seed=6)
    g1 = engine.current_generation
    out_g1 = engine.infer(x)
    # pin g1 as an in-flight batch would, then swap under it
    pinned = engine.acquire_generation()
    assert pinned == g1
    g2 = engine.swap_weights(_fresh_variables(engine, seed=7))
    assert engine.generations() == (g1, g2)  # old gen still draining
    # the pinned batch still serves g1's weights, bit-identically
    np.testing.assert_array_equal(
        engine.run_bucket(4, x, 4, gen=pinned), out_g1
    )
    engine.release_generation(pinned)
    assert engine.generations() == (g2,)  # drained -> dropped
    out_g2 = engine.infer(x)
    assert not np.array_equal(out_g1, out_g2)  # weights really changed


def test_batcher_swap_under_load_single_generation_per_batch():
    engine = ServeEngine("resnet18", buckets=(1, 4), num_classes=8,
                         image_size=32)
    b = DynamicBatcher(engine, max_delay_ms=2.0, slots=3)
    try:
        x = _rand_images(4, 32, seed=8)
        futs = [b.submit_array(x[i % 4]) for i in range(12)]
        engine.swap_weights(_fresh_variables(engine, seed=9))
        futs += [b.submit_array(x[i % 4]) for i in range(12)]
        by_batch = {}
        for f in futs:
            f.result(timeout=60)
            by_batch.setdefault(
                f.timings["batch_index"], set()
            ).add(f.generation)
        # NO batch was served with mixed-generation weights
        assert all(len(gens) == 1 for gens in by_batch.values()), by_batch
        # both generations actually served traffic across the swap
        assert {g for gens in by_batch.values() for g in gens} == {1, 2}
        # old generation fully drained away
        assert engine.generations() == (2,)
    finally:
        b.close()


# ------------------------------------------------- request preprocessing ----


def test_preprocess_bytes_bit_identical_to_val_pipeline(tmp_path):
    """The serving preprocessing path IS the eval pipeline: same file,
    same pixels, byte for byte."""
    from PIL import Image

    from dptpu.data.dataset import ImageFolderDataset
    from dptpu.data.transforms import ValTransform

    cls = tmp_path / "cat"
    cls.mkdir()
    rng = np.random.RandomState(0)
    for i, (w, h) in enumerate([(320, 240), (240, 320), (300, 300)]):
        Image.fromarray(
            rng.randint(0, 256, (h, w, 3), np.uint8)
        ).save(cls / f"{i}.jpg", quality=90)
    ds = ImageFolderDataset(str(tmp_path), transform=ValTransform(224))
    for i in range(len(ds)):
        want, _ = ds.get(i)
        with open(ds.samples[i][0], "rb") as f:
            got = preprocess_bytes(f.read(), size=224)
        np.testing.assert_array_equal(got, want)
    # the in-place staging-row write path produces the same bytes
    out = np.empty((224, 224, 3), np.uint8)
    with open(ds.samples[0][0], "rb") as f:
        data = f.read()
    assert preprocess_bytes(data, out=out) is out
    np.testing.assert_array_equal(out, ds.get(0)[0])


def test_preprocess_matches_val_pipeline_at_non_224_sizes(tmp_path):
    """The resize edge must SCALE with the crop (fit.py's
    int(size*256/224) formula): a 64px engine crops the same fraction
    of the image the val loader would, not a 64/256 center zoom."""
    from PIL import Image

    from dptpu.data.dataset import ImageFolderDataset
    from dptpu.data.transforms import ValTransform
    from dptpu.serve.preprocess import val_resize_for

    assert val_resize_for(224) == 256  # the reference pair, unchanged
    cls = tmp_path / "dog"
    cls.mkdir()
    rng = np.random.RandomState(3)
    Image.fromarray(rng.randint(0, 256, (300, 260, 3), np.uint8)).save(
        cls / "0.jpg", quality=90
    )
    for size in (64, 160):
        ds = ImageFolderDataset(
            str(tmp_path),
            transform=ValTransform(size, int(size * 256 / 224)),
        )
        want, _ = ds.get(0)
        with open(ds.samples[0][0], "rb") as f:
            got = preprocess_bytes(f.read(), size=size)
        np.testing.assert_array_equal(got, want)


def test_bucket1_only_ladder_serves_concurrent_requests():
    """A 1-only ladder still executes at the >= 2 floor, but admission
    caps at the BUCKET (the floor rows are pad-only): two concurrent
    submits must both resolve via bucket 1, never a dead dispatcher."""
    engine = ServeEngine("resnet18", buckets=(1,), num_classes=8,
                         image_size=32)
    b = DynamicBatcher(engine, max_delay_ms=20.0, slots=3)
    try:
        x = _rand_images(2, 32, seed=11)
        futs = [b.submit_array(x[0]), b.submit_array(x[1])]
        for i, f in enumerate(futs):
            np.testing.assert_array_equal(
                f.result(timeout=60), engine.infer(x[i:i + 1])[0]
            )
            assert f.timings["bucket"] == 1
        st = b.stats()
        assert st["completed"] == 2 and st["failed"] == 0
    finally:
        b.close()


def test_preprocess_rejects_garbage():
    with pytest.raises(ValueError, match="undecodable"):
        preprocess_bytes(b"\x00\x01\x02")


# ------------------------------------------------------- staging hygiene ----


def test_staging_ring_lease_lifecycle():
    ring = serve_staging.StagingRing(2, 4, (8, 8, 3))
    try:
        s0 = ring.acquire()
        s1 = ring.acquire()
        assert ring.acquire() is None  # backpressure: ring exhausted
        lease = ring.lease(s0)
        assert ring.leased_count() == 1
        lease.release()
        lease.release()  # double release is a no-op (SlotLease contract)
        assert ring.leased_count() == 0 and ring.free_count() == 1
        ring.abandon(s1)
        assert ring.free_count() == 2
    finally:
        ring.close()


def test_staging_close_with_lease_counts_as_leak():
    before = serve_staging.leaked_lease_count()
    ring = serve_staging.StagingRing(2, 4, (8, 8, 3))
    slot = ring.acquire()
    lease = ring.lease(slot)
    name = ring._shm.name.lstrip("/")
    assert name in serve_staging.live_segment_names()
    ring.close()
    assert serve_staging.leaked_lease_count() == before + 1
    assert name not in serve_staging.live_segment_names()
    lease.release()  # late release against a closed ring: no-op
    # restore the module counter so the conftest session guard (which
    # polices REAL leaks) stays meaningful
    serve_staging._LEASE_LEAKS = before
    if os.path.isdir("/dev/shm"):
        assert not os.path.exists(f"/dev/shm/{name}")  # unlinked


# ------------------------------------------------- request lifecycle ----
# ISSUE 17: deadlines, cancellation, and dead-request hygiene — a
# cancelled/expired request must free its admission slot, stop anchoring
# the coalescing timer, and occupy ZERO bucket rows at execution.


def test_cancel_pre_dispatch_frees_rows(cnn_engine):
    from dptpu.serve import ServeCancelled

    b = DynamicBatcher(cnn_engine, max_delay_ms=400.0, slots=2)
    try:
        imgs = _rand_images(6, 32, seed=11)
        futs = [b.submit_array(imgs[i]) for i in range(6)]
        # withdraw 4 of 6 while the batch is still coalescing
        for f in futs[1:5]:
            assert f.cancel()
        for f in futs[1:5]:
            with pytest.raises(ServeCancelled):
                f.result(timeout=5)
            assert not f.cancel()  # already done: cancel() is False
        r0 = futs[0].result(timeout=30)
        r5 = futs[5].result(timeout=30)
        # dead-request hygiene: 2 live rows execute at bucket 4 (claimed
        # count 6 would have needed bucket 16)
        assert futs[0].timings["bucket"] == 4
        assert futs[5].timings["bucket"] == 4
        # the two live requests still get THEIR pixels' logits: parity
        # against a fresh batcher proves compaction moved the right rows
        b2 = DynamicBatcher(cnn_engine, max_delay_ms=0.0, slots=2)
        try:
            want0 = b2.submit_array(imgs[0]).result(timeout=30)
            want5 = b2.submit_array(imgs[5]).result(timeout=30)
        finally:
            b2.close()
        _assert_bucket_parity(r0, want0)
        _assert_bucket_parity(r5, want5)
        s = b.stats()
        assert s["cancelled"] == 4
        assert s["dead_rows"] == 4
        assert s["completed"] == 2
    finally:
        b.close()


def test_cancel_whole_batch_abandons_slot(cnn_engine):
    from dptpu.serve import ServeCancelled

    b = DynamicBatcher(cnn_engine, max_delay_ms=400.0, slots=2)
    try:
        futs = [b.submit_array(_rand_images(1, 32, seed=i)[0])
                for i in range(3)]
        for f in futs:
            f.cancel()
        for f in futs:
            with pytest.raises(ServeCancelled):
                f.result(timeout=5)
        deadline = time.perf_counter() + 10
        while time.perf_counter() < deadline:
            if b.stats(reset_window=False)["dead_rows"] == 3:
                break
            time.sleep(0.02)
        s = b.stats()
        assert s["dead_rows"] == 3 and s["batches"] == 0
        # the slot was abandoned, not leaked: a new request still serves
        out = b.submit_array(_rand_images(1, 32, seed=9)[0])
        assert out.result(timeout=30).shape == (8,)
    finally:
        b.close()


def test_deadline_evicted_while_coalescing(cnn_engine):
    from dptpu.serve import DeadlineExceeded

    b = DynamicBatcher(cnn_engine, max_delay_ms=5000.0, slots=2)
    try:
        img = _rand_images(1, 32, seed=3)[0]
        fut = b.submit_array(img,
                             deadline=time.perf_counter() + 0.05)
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=10)
        s = b.stats()
        assert s["expired"] == 1
        assert s["completed"] == 0
    finally:
        b.close(drain=False)


def test_cancel_after_dispatch_returns_false(cnn_engine):
    b = DynamicBatcher(cnn_engine, max_delay_ms=0.0, slots=2)
    try:
        fut = b.submit_array(_rand_images(1, 32, seed=4)[0])
        fut.result(timeout=30)
        assert not fut.cancel()  # device work cannot be unclaimed
    finally:
        b.close()


def test_timer_reanchors_to_oldest_live_request(cnn_engine):
    """Cancelling the OLDEST request must re-anchor the max_delay_ms
    coalescing timer onto the next-oldest LIVE request — the batch must
    NOT dispatch at the dead request's (earlier) budget expiry."""
    from dptpu.serve import ServeCancelled

    delay_ms = 700.0
    b = DynamicBatcher(cnn_engine, max_delay_ms=delay_ms, slots=2)
    try:
        old = b.submit_array(_rand_images(1, 32, seed=5)[0])
        time.sleep(0.35)  # half the budget later...
        young = b.submit_array(_rand_images(1, 32, seed=6)[0])
        t_young = time.perf_counter()
        old.cancel()
        with pytest.raises(ServeCancelled):
            old.result(timeout=5)
        young.result(timeout=30)
        served_after = time.perf_counter() - t_young
        # anchored to the dead request, the batch would have gone out
        # ~0.35 s after `young` arrived; re-anchored it waits the full
        # budget from young's t_ready
        assert served_after >= delay_ms / 1e3 - 0.05, (
            f"dispatched {served_after:.3f}s after the live request — "
            f"timer still anchored to the cancelled one"
        )
        s = b.stats()
        assert s["cancelled"] == 1 and s["completed"] == 1
    finally:
        b.close()
