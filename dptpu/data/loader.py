"""Batch loader (thread- or process-backed) + double-buffered prefetcher.

The torch ``DataLoader(num_workers=j)`` + Apex ``fast_collate`` +
``DataPrefetcher`` trio (reference imagenet_ddp.py:178-194;
imagenet_ddp_apex.py:26-39,304-351), rebuilt for the TPU host model:

* decode/transform on a worker pool. ``workers_mode="thread"`` uses a
  thread pool (PIL/libjpeg release the GIL for the pixel work);
  ``workers_mode="process"`` uses spawned worker processes writing into
  a shared-memory batch ring (``dptpu/data/shm.py``) — the GIL caps the
  thread pool at ~1 core of useful decode on real hosts (HOSTBENCH r5:
  542.8 img/s at 8 threads vs 516.6 at 1), while processes scale with
  host cores and pixels still never get pickled. This constructor's
  default stays ``thread`` (a consumer that retains batches relies on
  it); ``fit()`` asks for ``process`` unless ``DPTPU_WORKERS_MODE``
  says otherwise or the host has too few cores for it to pay
  (``dptpu/data/feed.py::feed_knobs``), and brings the pool up during
  set-up with ``start()``: the loop's own thread then shares its
  interpreter with no worker (four pool threads held the loop's
  dispatch call for 65 ms of a 69 ms ResNet-50 iteration, PERF.md);
* CHUNKED submission, decoded in place: each batch submits one span per
  worker (not one task per image), and each worker decodes its span of
  samples DIRECTLY into the preallocated uint8 NHWC batch
  (``dataset.get_into`` → the native decoder's caller-supplied output
  buffer) — fast_collate's "no float conversion on CPU" insight (×4 less
  H2D traffic) without the per-image dispatch + intermediate-array
  memcpy that round 4's HOSTBENCH measured as ~19% of a decode core;
* keep ``prefetch_batches`` batches in flight so decode overlaps step time;
* per-item augmentation RNG derived from ``(seed, epoch, sample_index)`` —
  reproducible regardless of worker scheduling OR workers_mode: thread
  and process loaders yield bit-identical batches for the same seed (the
  ``--seed`` contract, nd_imagenet.py:68-69, without torch's
  worker_init_fn caveats; locked in tests/test_shm_loader.py);
* FIXED-SHAPE contract: the first sample's transformed shape is probed
  once and every batch is preallocated to it — all samples must share
  one shape (use a sizing transform). A mismatched sample raises a
  ``ValueError`` naming the offending index, not a broadcast error.
* ``DevicePrefetcher`` stays one batch ahead on-device: ``device_put`` /
  ``make_array_from_process_local_data`` dispatch is async in JAX, so the
  H2D copy of batch N+1 rides under the compute of batch N — the CUDA
  side-stream trick (imagenet_ddp_apex.py:310,329-340) without streams, and
  normalization already lives inside the compiled step.
* ZERO-COPY LEASED FEED (process mode, ``leased=True`` /
  ``DPTPU_LEASE``): batches are numpy VIEWS into the shared-memory ring
  plus a ``"_lease"`` token; ``DevicePrefetcher`` releases the lease
  after the device transfer of that batch completes, and the ring
  recycles only released slots — the parent's per-batch copy-out is
  gone (``feed_stats``: ``bytes_copied_per_batch = 0``). Consumers that
  RETAIN batches (``list(loader.epoch(0))``) must keep the default
  ``leased=False`` copy path: a leased batch's bytes are only stable
  until the iterator advances past it (the after-yield backstop then
  reclaims the slot).
* DECODE-AHEAD PIPELINED FEED (process mode): the ring depth is its
  own knob (``DPTPU_RING_DEPTH``, decoupled from prefetch and lease
  depth) and a pre-issue pump keeps spans for up to
  ``DPTPU_DECODE_AHEAD`` batches queued on the workers the moment
  slots free — workers never drain at batch boundaries, a straggler
  span delays only its own batch's collect (and ``DPTPU_SPECULATE``
  re-issues it to an idle worker after ``speculate_after_s``), and the
  pre-issue moment doubles as the cold-epoch JPEG readahead hook
  (``DPTPU_READAHEAD`` — ``posix_fadvise(WILLNEED)`` so worker reads
  land in a warm page cache). All of it preserves the bit-identity,
  lease and restart/resume contracts (dptpu/data/shm.py docstring).
"""

from __future__ import annotations

import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, Optional

import numpy as np

from dptpu import obs
from dptpu.data.sampler import ShardedSampler


class DataLoader:
    """Batches of ``{"images": uint8 [B,H,W,C], "labels": int32 [B]}``,
    or of whatever a dataset with a ``collate`` makes of its items
    (``dptpu.data.tokens``: ``tokens``, ``labels``, ``mask``).

    Final-batch policy when the shard doesn't divide evenly:
      * ``drop_last=True`` — drop the remainder (train default in fit).
      * ``pad_final=True`` — pad by repeating sample 0 and attach a ``mask``
        (1.0 = real): static shapes for jit, exact masked eval.
      * ``pad_final=False`` — yield the short batch as-is (costs one extra
        jit specialization for the tail shape).
    """

    def __init__(self, dataset, batch_size: int,
                 sampler: Optional[ShardedSampler] = None,
                 num_workers: int = 4, drop_last: bool = False,
                 pad_final: bool = True, seed: int = 0,
                 workers_mode: str = "thread", mp_start: str = "spawn",
                 leased: bool = False, lease_depth: Optional[int] = None,
                 span_affinity: Optional[bool] = None,
                 ring_depth: Optional[int] = None,
                 decode_ahead: Optional[int] = None,
                 speculate: Optional[bool] = None,
                 speculate_after_s: float = 0.5,
                 readahead: Optional[bool] = None):
        from dptpu.envknob import env_bool, env_int

        if workers_mode not in ("thread", "process"):
            raise ValueError(
                f"workers_mode={workers_mode!r} must be 'thread' or "
                f"'process'"
            )
        self.dataset = dataset
        self.batch_size = batch_size
        self.sampler = sampler or ShardedSampler(len(dataset), shuffle=False)
        self.num_workers = max(1, num_workers)
        self.drop_last = drop_last
        self.pad_final = pad_final
        self.seed = seed
        self.workers_mode = workers_mode
        self.mp_start = mp_start
        # zero-copy leased handoff (process mode): opt-in — the consumer
        # must release (DevicePrefetcher does) or advance promptly
        self.leased = leased
        self.lease_depth = (
            lease_depth if lease_depth is not None
            else env_int("DPTPU_LEASE_DEPTH", 2)
        )
        if self.lease_depth < 1:
            raise ValueError(
                f"DPTPU_LEASE_DEPTH={self.lease_depth} must be >= 1 "
                f"extra ring slot"
            )
        self.span_affinity = (
            span_affinity if span_affinity is not None
            else env_bool("DPTPU_SPAN_AFFINITY", True)
        )
        # decode-ahead pipelining knobs (process mode; locked fail-fast
        # contract — every explicit-but-invalid value raises):
        # * ring_depth — TOTAL batch slots in the shared-memory ring;
        #   None derives it from the issue window + lease depth;
        # * decode_ahead — batches whose spans may be pre-issued ahead
        #   of the consume point. Explicit values are EXACT (=1 is the
        #   batch-serial baseline the benches A/B against); None keeps
        #   at least the legacy prefetch window, deepened to >= 4.
        self.ring_depth = (
            ring_depth if ring_depth is not None
            else env_int("DPTPU_RING_DEPTH", None)
        )
        if self.ring_depth is not None and self.ring_depth < 2:
            raise ValueError(
                f"DPTPU_RING_DEPTH={self.ring_depth} must be >= 2 batch "
                f"slots (one collecting + one in flight)"
            )
        self.decode_ahead = (
            decode_ahead if decode_ahead is not None
            else env_int("DPTPU_DECODE_AHEAD", None)
        )
        if self.decode_ahead is not None and self.decode_ahead < 1:
            raise ValueError(
                f"DPTPU_DECODE_AHEAD={self.decode_ahead} must be >= 1 "
                f"batch in flight (1 = batch-serial issue, no lookahead)"
            )
        self.speculate = (
            speculate if speculate is not None
            else env_bool("DPTPU_SPECULATE", True)
        )
        self.speculate_after_s = speculate_after_s
        self.readahead = (
            readahead if readahead is not None
            else env_bool("DPTPU_READAHEAD", True)
        )
        self._get = getattr(dataset, "get", None)
        self._get_into = getattr(dataset, "get_into", None)
        # shard-streaming hook (dptpu/data/stream.py): the dataset owns
        # its I/O engine; pre-issue stages extents into the byte slab.
        # Thread mode calls it at submit time; process mode routes it
        # through the shm pipeline's pre-issue pump.
        self._prefetch_extents = getattr(dataset, "prefetch_extents", None)
        self._item_shape = None  # probed from the first sample
        self._item_dtype = np.dtype(np.uint8)  # the probe's, likewise
        # a dataset whose rows are not one image and one label (token
        # rows) says how a batch is made of the items and their scalars
        self._collate = getattr(dataset, "collate", None)
        self._probe = None  # owned-by: caller — (index, epoch, img, label) probe, consumed at submit time
        self._pipeline = None  # lazy shm ring (process mode)
        self._prev_cache_counts = (0, 0)  # feed_stats interval baseline
        self._degraded = False  # process pool gave up → thread fallback
        self._supervision = {"pool_restarts": 0, "span_retries": 0,
                             "straggler_resplits": 0,
                             "worker_evictions": 0}
        self._copy_totals = {"bytes_copied": 0, "collects": 0}
        # ring telemetry folded across pipeline rebuilds (same
        # survive-rebuilds discipline as _supervision/_copy_totals)
        self._ring_totals = {"occupancy_sum": 0, "occupancy_samples": 0,
                             "io_wait_s": 0.0, "straggler_reissues": 0}
        self._prev_io_wait = 0.0  # feed_stats interval baseline
        self._issue_ahead_sum = 0  # pre-issued batches, sampled per batch
        self._issue_ahead_n = 0
        self._pool = (
            ThreadPoolExecutor(
                max_workers=self.num_workers, thread_name_prefix="dptpu-data"
            )
            if workers_mode == "thread"
            else None
        )

    def __len__(self) -> int:
        n = len(self.sampler)
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    def _load_one(self, index: int, epoch: int):
        if self._get is None:
            return self.dataset[index]
        rng = np.random.default_rng([self.seed, epoch, index])
        return self._get(index, rng)

    def _load_span(self, idxs, epoch, imgs, labels, offset, skip=(),
                   timed=False):
        """Decode a span of samples directly into rows
        ``offset..offset+len(idxs)`` of the shared batch arrays — the
        per-worker unit of a chunked submission (disjoint rows, so
        concurrent spans never race). ``skip`` rows were already filled
        by the caller (the shape probe's reused decode). ``timed``
        (tracing on) returns this worker's own ``(cpu_s, wall_s)`` for
        the span through the future: the ``collect`` span sums them, the
        worker records no span of its own."""
        from dptpu.data.dataset import _copy_checked

        if timed:
            c0, t0 = time.thread_time(), time.perf_counter()
        get_into = self._get_into
        for j, index in enumerate(idxs):
            index = int(index)
            if offset + j in skip:
                continue
            if get_into is not None:
                rng = np.random.default_rng([self.seed, epoch, index])
                labels[offset + j] = get_into(index, rng, imgs[offset + j])
            else:
                img, label = self._load_one(index, epoch)
                _copy_checked(imgs[offset + j], img, index)
                labels[offset + j] = label
        if timed:
            return time.thread_time() - c0, time.perf_counter() - t0
        return None

    def _submit_batch(self, batch_indices, epoch):
        """Preallocate one batch and fan its samples out as ONE future
        per worker (each decoding in place via ``_load_span``) — not one
        per image: HOSTBENCH r4 measured the per-image dispatch +
        intermediate memcpy at ~19% of a decode core."""
        n_valid = len(batch_indices)
        if self._prefetch_extents is not None and self.readahead:
            # stage this batch's shard extents now — it decodes
            # ``prefetch_batches`` from now, so the bytes land first
            self._prefetch_extents(batch_indices)
        out_size = self.batch_size if self.pad_final else n_valid
        imgs = np.empty((out_size,) + self._item_shape, self._item_dtype)
        labels = np.zeros((out_size,), np.int32)
        # the shape probe already decoded one sample of this epoch with
        # its exact rng: reuse it HERE, on the caller thread, so _probe
        # stays single-writer caller state (the decode spans run on the
        # pool — guarded-by discipline, dptpu check)
        skip = ()
        probe = self._probe
        if probe is not None and probe[1] == epoch:
            for j, index in enumerate(batch_indices):
                if int(index) == probe[0]:
                    self._probe = None
                    imgs[j] = probe[2]
                    labels[j] = probe[3]
                    skip = (j,)
                    break
        span = -(-n_valid // self.num_workers)
        timed = obs.get_tracer().enabled
        futs = [
            self._pool.submit(
                self._load_span, batch_indices[o:o + span], epoch,
                imgs, labels, o, skip, timed,
            )
            for o in range(0, n_valid, span)
        ]
        return futs, imgs, labels, n_valid

    def _finalize(self, futs, imgs, labels, n_valid, valid=None, step=-1):
        # the parent-blocked-on-decode moment, thread edition (the
        # process path's equivalent wait is spanned around collect).
        # ``step`` is the batch's index in the epoch: the step that will
        # consume it. The span says whether the workers were done when
        # the loop came for the batch, what the batch cost them, and how
        # many of them the pool has.
        tracer = obs.get_tracer()
        if not tracer.enabled:
            for f in futs:
                f.result()  # wait + propagate decode errors
            return self._assemble(imgs, labels, n_valid, valid)
        attrs = {"ready": all(f.done() for f in futs), "rows": n_valid,
                 "workers": self.num_workers}
        if self._degraded:
            # these threads stand in for a process pool that gave up: a
            # log that shows it tells a run that gained nothing from the
            # pool apart from a run that never had one
            attrs["degraded"] = True
        t0 = time.perf_counter()
        spent = [f.result() for f in futs]
        if all(r is not None for r in spent):
            attrs["cpu_s"] = sum(r[0] for r in spent)
            attrs["wall_s"] = sum(r[1] for r in spent)
        tracer.record("collect", t0, time.perf_counter() - t0, step=step,
                      attrs=attrs)
        return self._assemble(imgs, labels, n_valid, valid)

    def _assemble(self, imgs, labels, n_valid, valid=None):
        """Pad/mask policy shared by the thread and process backends."""
        out_size = imgs.shape[0]
        # the eval mask flags positions an exact aggregation must skip:
        # batch-tail padding AND the sampler's wrap-around duplicates
        # (samplers pad shards to equal length, imagenet_ddp.py:175-183).
        # Wrap-dup masking rides the pad_final (exact-eval) mode only:
        # train batches keep DistributedSampler's duplicate-sample
        # semantics and a stable pytree (no mid-epoch mask key).
        need_mask = n_valid < out_size or (
            self.pad_final and valid is not None and not valid.all()
        )
        if n_valid < out_size:  # pad tail by repeating sample 0
            imgs[n_valid:] = imgs[0]
            labels[n_valid:] = labels[0]
        mask = None
        if need_mask:
            mask = np.zeros((out_size,), np.float32)
            mask[:n_valid] = (
                1.0 if valid is None else valid.astype(np.float32)
            )
        if self._collate is not None:
            # the dataset's own batch layout; the rows the mask leaves
            # out are left out of whatever it counts
            return self._collate(imgs, labels, mask)
        batch = {"images": imgs, "labels": labels}
        if mask is not None:
            batch["mask"] = mask
        return batch

    def epoch(self, epoch: int = 0, prefetch_batches: int = 2,
              start_batch: int = 0) -> Iterator[dict]:
        """Iterate one epoch's batches (``epoch`` reseeds the shuffle —
        the set_epoch analog, imagenet_ddp.py:202).

        ``start_batch`` replays the sampler to a mid-epoch resume point
        (dptpu.resilience): the FULL epoch permutation is rebuilt from
        ``(seed, epoch)`` exactly as an uninterrupted run would, then the
        first ``start_batch`` batches are skipped WITHOUT decoding — the
        remaining batches are bit-identical to what the uninterrupted
        epoch would have yielded from that position.
        """
        indices, valid = self.sampler.indices_and_validity(epoch)
        nb = len(self)
        sl = lambda b: slice(b * self.batch_size, (b + 1) * self.batch_size)  # noqa: E731
        chunks = [(indices[sl(b)], valid[sl(b)]) for b in range(nb)]
        if start_batch:
            if not 0 <= start_batch <= nb:
                raise ValueError(
                    f"start_batch={start_batch} outside this epoch's "
                    f"[0, {nb}] batches — checkpoint from a different "
                    f"batch size or dataset?"
                )
            chunks = chunks[start_batch:]
        if self._item_shape is None and chunks:
            # cached on the loader: only the first epoch() call pays (or
            # start() did), and thread mode reuses the decode for the
            # sample's row
            self._probe_item(int(chunks[0][0][0]), epoch)

        ahead = 1 + max(0, prefetch_batches)
        if self.workers_mode == "process":
            yield from self._epoch_process(chunks, epoch, ahead,
                                           start_batch)
            return
        yield from self._epoch_thread(chunks, epoch, ahead, start_batch)

    def _probe_item(self, index: int, epoch: int):
        """One probe decode fixes the item shape and dtype that every
        batch is preallocated to."""
        img, label = self._load_one(index, epoch)
        img = np.asarray(img)
        self._item_shape = img.shape
        self._item_dtype = img.dtype
        self._probe = (index, epoch, img, label)

    def _ring_slots(self, ahead: int) -> tuple:
        """``(issue window, ring slots)`` of a process-mode epoch that
        keeps ``ahead`` batches in flight. An explicit decode_ahead is
        exact (=1 is the batch-serial baseline); the default keeps at
        least the legacy prefetch window, deepened to 4 for multi-batch
        lookahead."""
        window = (
            self.decode_ahead if self.decode_ahead is not None
            else max(ahead, 4)
        )
        slots = (
            self.ring_depth if self.ring_depth is not None
            else window + 1 + (self.lease_depth if self.leased else 0)
        )
        return window, slots

    def start(self, prefetch_batches: int = 2):
        """Bring the process pool up NOW, without waiting for it: probe
        the item shape, create the ring and spawn the workers, whose
        interpreters then start and import beside whatever the caller
        does next (``fit()``: weights, state, the step's compile). The
        first ``epoch()`` with the same ``prefetch_batches`` finds this
        pool and spawns nothing. A no-op in thread mode, on an empty
        shard, and when the pool is already up."""
        if self.workers_mode != "process" or self._pipeline is not None:
            return
        t0 = time.perf_counter()
        if self._item_shape is None:
            # any row of this host's shard has the shape of all of them
            indices, _ = self.sampler.indices_and_validity(0)
            if not len(indices):
                return
            self._probe_item(int(indices[0]), 0)
        _, slots = self._ring_slots(1 + max(0, prefetch_batches))
        self._ensure_pipeline(slots=slots)
        obs.get_tracer().record(
            "feed_start", t0, time.perf_counter() - t0,
            attrs={"mode": self.workers_mode, "workers": self.num_workers,
                   "slots": slots},
        )

    def _epoch_thread(self, chunks, epoch, ahead, first=0):
        """Thread-pool epoch over an explicit chunk list (also the landing
        path when a broken process pool degrades mid-epoch). ``first`` is
        the epoch index of ``chunks[0]`` (the label of its spans)."""
        nb = len(chunks)
        pending = deque()
        for chunk, _ in chunks[:ahead]:
            pending.append(self._submit_batch(chunk, epoch))
        next_idx = ahead
        for b in range(nb):
            item = pending.popleft()
            if next_idx < nb:
                pending.append(self._submit_batch(chunks[next_idx][0], epoch))
                next_idx += 1
            yield self._finalize(*item, valid=chunks[b][1], step=first + b)

    def _epoch_process(self, chunks, epoch, ahead, first=0):
        """Process-mode epoch: drive the shared-memory slot ring
        (dptpu/data/shm.py) as a DECODE-AHEAD pipeline — a pump keeps up
        to ``issue window`` batches' spans pre-issued into the per-worker
        queues, refilling the moment slots free, so workers roll straight
        across batch boundaries while ``collect`` consumes in batch
        order (spans complete out of order against per-slot counters).
        ``leased=True`` yields zero-copy slot views carrying a
        ``"_lease"`` token; an after-yield backstop reclaims any lease
        the consumer didn't release, so the ring keeps flowing even for
        consumers unaware of the protocol (their batch bytes are then
        only stable until they advance — retaining consumers must use
        the copy path). If the supervised pool exhausts its restart
        budget (``WorkerPoolBroken``), degrade to thread mode for the
        rest of the run instead of killing the job — batches are
        bit-identical between modes, so the hand-off is seamless."""
        from dptpu.data.shm import WorkerPoolBroken

        if not chunks:
            return
        self._probe = None  # workers decode row 0 themselves
        nb = len(chunks)
        b = 0
        try:
            window, slots = self._ring_slots(ahead)
            pipe = self._ensure_pipeline(slots=slots)
            pipe.reset()  # reclaim slots from an abandoned prior epoch
            pending = deque()
            next_idx = 0
            for b in range(nb):
                # the pre-issue pump: fill every free slot up to the
                # issue window before blocking on the in-order collect
                while True:
                    while next_idx < nb and len(pending) < window \
                            and pipe.free_slot_count() > 0:
                        pending.append(
                            pipe.submit(chunks[next_idx][0], epoch))
                        next_idx += 1
                    if pending:
                        break
                    if pipe.ghost_issues_in_flight():
                        # every free slot is ghost-quarantined: the
                        # pending ghost acks (or a watchdog restart)
                        # will free one — drain instead of raising on
                        # a ring that is merely small
                        pipe.drain_one_ack()
                        continue
                    # only unreleased LEASES can still be holding the
                    # ring: those the consumer must release
                    raise RuntimeError(
                        f"decode-ahead ring stalled: all "
                        f"{pipe.slots} slots are held by unreleased "
                        f"leases with no batch in flight — release "
                        f"leases promptly or raise DPTPU_RING_DEPTH"
                    )
                self._issue_ahead_sum += len(pending)
                self._issue_ahead_n += 1
                slot, n_valid = pending.popleft()
                out_size = self.batch_size if self.pad_final else n_valid
                # the parent-blocked-on-spans moment (the ring's own
                # io_wait_s counter measures the same wait cumulatively;
                # the span places each wait on the step timeline)
                tracer = obs.get_tracer()
                t_collect = time.perf_counter()
                imgs, labels, lease = pipe.collect(
                    slot, out_size, leased=self.leased
                )
                if tracer.enabled:
                    # ready, cpu_s, wall_s: the workers' own clocks, off
                    # their acks (the thread path's attributes exactly)
                    tracer.record(
                        "collect", t_collect,
                        time.perf_counter() - t_collect, step=first + b,
                        attrs={"rows": n_valid,
                               "workers": self.num_workers,
                               **pipe.last_collect},
                    )
                batch = self._assemble(imgs, labels, n_valid,
                                       valid=chunks[b][1])
                if lease is not None:
                    batch["_lease"] = lease
                try:
                    yield batch
                finally:
                    if lease is not None:
                        # backstop: the consumer moved on (or abandoned
                        # the epoch — GeneratorExit lands here too)
                        # without releasing; no-op when DevicePrefetcher
                        # already did
                        lease.release()
        except WorkerPoolBroken as e:
            self._degrade_to_thread(str(e))
            # batch b was never yielded; re-decode from it on threads
            # (pre-issued batches beyond b die with the pool — the
            # thread path re-earns them)
            yield from self._epoch_thread(chunks[b:], epoch, ahead,
                                          first + b)

    def _retire_pipeline(self, forgive_leases: bool = False):
        """Close the pipeline, folding its supervision counters into the
        loader's base first — feed_stats' survive-rebuilds invariant has
        exactly one implementation.

        ``forgive_leases``: a loader-initiated retirement (ring-depth
        rebuild between epochs, degrade-to-thread) REVOKES any lease
        carried over from an abandoned epoch — the consumer's late
        ``release()`` voids against the closed pipeline — instead of
        reporting it as a protocol leak; only ``close()`` (the consumer
        said it was done) treats an unreleased lease as a bug for the
        conftest leak guard to fail on."""
        if self._pipeline is not None:
            if forgive_leases:
                self._pipeline._leased.clear()
            for k, v in self._pipeline.supervision_stats().items():
                self._supervision[k] += v
            for k, v in self._pipeline.copy_stats().items():
                self._copy_totals[k] += v
            for k, v in self._pipeline.ring_stats().items():
                if k in self._ring_totals:
                    self._ring_totals[k] += v
            self._pipeline.close()
            self._pipeline = None

    def _degrade_to_thread(self, reason: str):
        """Graceful degradation: give up on worker processes for the rest
        of this run, loudly, instead of dying mid-job."""
        import sys

        print(
            f"WARNING: dptpu process-mode data pipeline is degrading to "
            f"thread mode (slower, but alive): {reason}",
            file=sys.stderr,
        )
        self._retire_pipeline(forgive_leases=True)
        self.workers_mode = "thread"
        self._degraded = True
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=self.num_workers, thread_name_prefix="dptpu-data"
            )

    def kill_one_worker(self):
        """Fault-injection/debug hook (``DPTPU_FAULT=worker_kill@step=N``):
        SIGKILL one live decode worker; no-op in thread mode."""
        if self._pipeline is not None:
            return self._pipeline.kill_worker()
        return None

    # -- straggler-control seam (dptpu/resilience/elastic.py) ---------------
    # All three no-op safely in thread mode / before the lazy pipeline
    # exists, so the controller may always be armed.

    def worker_latency_observations(self):
        """Span issue→ack latencies ``[(worker_id, seconds), ...]``
        accumulated since the last call (process mode only)."""
        if self._pipeline is not None:
            return self._pipeline.drain_latency_observations()
        return []

    def resplit_worker(self, worker_id: int) -> int:
        """Re-issue a slow worker's pending span tail to healthy workers
        and route future affinity away from it; returns spans re-issued."""
        if self._pipeline is not None:
            return self._pipeline.resplit_worker(worker_id)
        return 0

    def restore_worker(self, worker_id: int):
        """Let a recovered worker rejoin the affinity router."""
        if self._pipeline is not None:
            self._pipeline.restore_worker(worker_id)

    def evict_worker(self, worker_id: int):
        """Escalate to the supervisor's eviction policy: kill the worker
        (the watchdog restart re-enqueues its work); returns the pid."""
        if self._pipeline is not None:
            return self._pipeline.evict_worker(worker_id)
        return None

    def _ensure_pipeline(self, slots: int):
        from dptpu.data.shm import ShmBatchPipeline

        if self._pipeline is not None and self._pipeline.slots != slots:
            # ring depth changed between epochs — GREW (deeper prefetch/
            # decode-ahead: the old ring cannot hold the window) or
            # SHRANK (a smaller window would silently pin the surplus
            # slots' memory forever): rebuild either way. Leased slots
            # carried over from the old ring are safe: retire closes the
            # pipeline, so a consumer's late release() voids against the
            # closed/generation check instead of touching the new ring,
            # and close_segment unlinks the segment NAME even while the
            # stale views keep their mapping alive.
            self._retire_pipeline(forgive_leases=True)
        if self._pipeline is None:
            self._pipeline = ShmBatchPipeline(
                self.dataset, self.batch_size, self._item_shape,
                num_workers=self.num_workers, seed=self.seed, slots=slots,
                mp_start=self.mp_start, span_affinity=self.span_affinity,
                speculate=self.speculate,
                speculate_after_s=self.speculate_after_s,
                readahead=self.readahead,
                item_dtype=self._item_dtype,
            )
            # fresh workers count from zero: re-baseline the interval
            # hit-rate bookkeeping in feed_stats
            self._prev_cache_counts = (0, 0)
        return self._pipeline

    def io_wait_total_s(self) -> float:
        """Cumulative parent-blocked-on-spans seconds (process mode;
        0.0 in thread mode), read WITHOUT consuming the ``feed_stats``
        interval baseline — the tune controller's decode-ahead actuator
        computes its own intervals, and the obs per-epoch interval must
        stay exactly what it was."""
        total = float(self._ring_totals["io_wait_s"])
        if self._pipeline is not None:
            total += float(self._pipeline.ring_stats()["io_wait_s"])
        return total

    def grow_decode_ahead(self, max_ahead: int = 16):
        """Bounded decode-ahead step (the tune controller's actuator
        seam, ISSUE 19): deepen the issue window by ONE batch. Takes
        effect at the next epoch's pipeline build — ``_epoch_process``
        derives the slot count there and ``_ensure_pipeline`` rebuilds
        the ring when it grew, so no mid-epoch slot protocol is ever
        resized under in-flight leases. Returns the new window, or None
        at the bound / in thread mode (the actuator reads None as "no
        headroom" and disarms cleanly)."""
        if self.workers_mode != "process":
            return None
        # default window is max(legacy prefetch, 4) — start the bounded
        # climb from the deepened floor, never below it
        cur = self.decode_ahead if self.decode_ahead is not None else 4
        if self.ring_depth is not None:
            # an explicit ring depth caps the usable window: the pump
            # can never hold more pending batches than free slots
            cap = self.ring_depth - 1 \
                - (self.lease_depth if self.leased else 0)
            max_ahead = min(max_ahead, cap)
        if cur >= max_ahead:
            return None
        self.decode_ahead = cur + 1
        return self.decode_ahead

    def feed_stats(self) -> dict:
        """Pipeline telemetry for the train loop: worker configuration +
        decode-cache counters (pool-aggregated in process mode).

        ``cache_hits``/``cache_misses`` are cumulative since loader
        creation; ``cache_hit_rate`` covers the INTERVAL since the
        previous ``feed_stats()`` call (→ per-epoch when called once per
        epoch, as the train loop does), so a warm epoch reads ~1.0
        instead of being diluted by epoch-0 fill misses."""
        stats = {
            "workers_mode": self.workers_mode,
            "num_workers": self.num_workers,
        }
        # supervision counters survive pool rebuilds and degradation:
        # the loader folds closed pipelines' totals into its own base
        restarts = dict(self._supervision)
        if self._pipeline is not None:
            for k, v in self._pipeline.supervision_stats().items():
                restarts[k] += v
        if any(restarts.values()) or self._degraded:
            stats.update(restarts)
        if self._degraded:
            stats["degraded"] = True
        if self.workers_mode == "process":
            stats["leased"] = self.leased
            stats["span_affinity"] = self.span_affinity
            # which affinity key routes spans to workers (the shared
            # shm.routing_of derivation, so the lazy-pipeline fallback
            # can never diverge from what the pipeline actually does)
            from dptpu.data.shm import routing_of

            stats["span_routing"] = (
                self._pipeline.routing if self._pipeline is not None
                else routing_of(self.dataset, self.span_affinity)
            )
            copied = dict(self._copy_totals)
            ring = dict(self._ring_totals)
            if self._pipeline is not None:
                stats.update(self._pipeline.cache_stats())
                for k, v in self._pipeline.copy_stats().items():
                    copied[k] += v
                pipe_ring = self._pipeline.ring_stats()
                for k in ring:
                    ring[k] += pipe_ring[k]
                stats["ring_depth"] = pipe_ring["ring_depth"]
            # the zero-copy contract, measured: parent-side copy-out
            # bytes per collected batch (0 when every collect was leased)
            stats["bytes_copied_per_batch"] = (
                copied["bytes_copied"] / copied["collects"]
                if copied["collects"] else 0.0
            )
            # decode-ahead telemetry: mean in-flight slots at collect
            # time, mean pre-issued batches, speculative re-issues, and
            # the INTERVAL parent-blocked-on-spans time (per-epoch when
            # feed_stats is called once per epoch, like the train loop)
            stats["ring_occupancy"] = (
                ring["occupancy_sum"] / ring["occupancy_samples"]
                if ring["occupancy_samples"] else 0.0
            )
            stats["issue_ahead_depth"] = (
                self._issue_ahead_sum / self._issue_ahead_n
                if self._issue_ahead_n else 0.0
            )
            stats["straggler_reissues"] = ring["straggler_reissues"]
            stats["io_wait_s"] = ring["io_wait_s"] - self._prev_io_wait
            self._prev_io_wait = ring["io_wait_s"]
        else:
            cache = getattr(self.dataset, "decode_cache", None)
            if cache is not None:
                stats.update(cache.stats())
        # shard-streaming telemetry (dptpu/data/stream.py): byte-ring /
        # store-fetch counters, plus the I/O-ownership invariant. The
        # fadvise readahead and the shard engine must NEVER both be
        # armed — WILLNEED would repopulate the page cache the O_DIRECT
        # ring exists to bypass — so feed_stats ASSERTS the exclusion
        # rather than just reporting it.
        io_fn = getattr(self.dataset, "io_stats", None)
        shard_owns_io = self._prefetch_extents is not None
        fadvise_active = (
            self.readahead and not shard_owns_io
            and self.workers_mode == "process"
            and getattr(self.dataset, "samples", None) is not None
        )
        if shard_owns_io and self.readahead \
                and getattr(self.dataset, "samples", None) is not None:
            raise RuntimeError(
                "feed invariant violated: the dataset exposes BOTH "
                "prefetch_extents (shard engine owns the I/O) and a "
                "samples path list (the fadvise readahead target) — "
                "the two byte-prefetch paths must be mutually exclusive"
            )
        stats["readahead_active"] = fadvise_active
        if io_fn is not None:
            stats.update(io_fn())
            assert not (stats["readahead_active"]
                        and stats.get("odirect_active")), (
                "fadvise readahead and the O_DIRECT shard ring are both "
                "active — mutually exclusive by contract"
            )
        if "cache_hits" in stats:
            dh = stats["cache_hits"] - self._prev_cache_counts[0]
            dm = stats["cache_misses"] - self._prev_cache_counts[1]
            self._prev_cache_counts = (
                stats["cache_hits"], stats["cache_misses"]
            )
            stats["cache_hit_rate"] = dh / (dh + dm) if dh + dm else 0.0
        return stats

    def close(self):
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)
        self._retire_pipeline()


class DevicePrefetcher:
    """Keep one batch resident on device ahead of the consumer.

    ``put`` is either ``jax.device_put`` (single host) or
    ``dptpu.parallel.shard_host_batch`` partially applied with the mesh.
    JAX dispatches the transfer asynchronously, so the copy of batch N+1
    overlaps the compiled step running on batch N — the DataPrefetcher's
    double-buffering (imagenet_ddp_apex.py:304-351) with zero custom
    stream code.

    LEASED batches (a ``"_lease"`` token from the process-mode loader's
    zero-copy path) are the prefetcher's responsibility to release —
    only then may the shared-memory ring recycle the slot:

    * on an accelerator backend, ``put`` DMAs the host views to device
      memory; the prefetcher blocks until that transfer completes, then
      releases — the blocking overlaps the PREVIOUS step's device
      compute, and no host-side byte is ever copied;
    * on the CPU backend, ``jax.device_put`` may ZERO-COPY ALIAS the
      host buffer (measured on this toolchain: a mutated source mutates
      the "device" array), so recycling after a mere block would corrupt
      the batch mid-step. The prefetcher therefore copies the views once
      before ``put`` and releases immediately — the same cost as the
      legacy copy-out, paid only where physics offers no transfer.
      ``copy_before_put`` overrides the backend auto-detection (tests
      use it to drive the raw lease protocol with a custom ``put``).
    """

    def __init__(self, batches: Iterator[dict], put=None,
                 copy_before_put: Optional[bool] = None,
                 first_step: int = 0):
        # jax is imported where the device is touched, never at this
        # module's top: a spawned decode worker imports dptpu.data (for
        # its dataset) and must not pay for, or see, jax
        import jax

        self._it = iter(batches)
        self._put = jax.device_put if put is None else put
        self._copy = copy_before_put
        # epoch index of the next batch: its ``h2d`` span carries the
        # step that will consume it (``first_step`` = the resume point)
        self._step = first_step
        self._next = self._advance()

    def _advance(self):
        import jax

        tracer = obs.get_tracer()
        try:
            batch = next(self._it)
        except StopIteration:
            return None
        step = self._step
        self._step += 1
        lease = batch.pop("_lease", None)
        t0 = time.perf_counter()
        if lease is None:
            out = self._put(batch)
        else:
            if self._copy is None:
                # CPU PJRT zero-copies suitably-shaped numpy buffers — the
                # device array then aliases the ring slot for its lifetime
                self._copy = jax.default_backend() == "cpu"
            if self._copy:
                batch = {k: np.array(v) for k, v in batch.items()}  # dptpu: allow-host-sync(the documented CPU-backend defense: device_put zero-copy-aliases host buffers there, so recycling the slot would corrupt the in-flight batch — copy once, host to host)
                out = self._put(batch)
            else:
                out = self._put(batch)
                # the H2D read must finish before the slot may be
                # overwritten; this wait overlaps the previous step's
                # device compute
                jax.block_until_ready(out)  # dptpu: allow-host-sync(H2D completion gate before the leased slot may be recycled; the wait overlaps the PREVIOUS step's device compute)
        if tracer.enabled:
            tracer.record(
                "h2d", t0, time.perf_counter() - t0, step=step,
                attrs={"bytes": sum(getattr(v, "nbytes", 0)
                                    for v in batch.values())},
            )
        if lease is not None:
            lease.release()
        return out

    def __iter__(self):
        return self

    def __next__(self):
        if self._next is None:
            raise StopIteration
        current, self._next = self._next, self._advance()
        return current
