"""Multi-process shared-memory batch ring for the input pipeline.

The thread-pool loader flatlines on multi-core hosts: PIL/libjpeg release
the GIL for the pixel work, but header parsing, RNG, numpy bookkeeping and
the futures machinery all serialize on it (HOSTBENCH r5: 542.8 img/s at 8
threads vs 516.6 at 1 — the pool buys ~5%). Worker PROCESSES sidestep the
GIL entirely; the classic cost of torch-style workers — pickling every
decoded batch through a pipe — is removed by giving the workers the
loader's preallocated batch memory itself:

* a ring of ``slots`` batch buffers lives in ONE
  ``multiprocessing.shared_memory`` segment per array (images uint8
  ``[slots, B, H, W, C]``, labels int32 ``[slots, B]``), named
  ``dptpu_ring_*`` so /dev/shm hygiene checks can attribute them;
* workers run the SAME span-decode path as thread mode
  (``dataset.get_into`` → the native decoder's caller-supplied output
  row), writing JPEG decodes directly into their slot's rows — pixels
  never cross a pipe, only tiny ``(slot, task, offsets, indices,
  epoch)`` tasks and ``(done, ...)`` acks do;
* per-item augmentation RNG is derived from ``(seed, epoch, index)``
  exactly as in thread mode, so process and thread loaders yield
  BIT-IDENTICAL batches for the same seed (tests/test_shm_loader.py);
* CACHE-AFFINITY SPAN ROUTING: each worker owns a task queue, and
  ``submit`` routes every sample index to the worker picked by a stable
  hash of the index — so when the decode cache is per-worker sharded
  (``DPTPU_CACHE_SCOPE=sharded``) the same worker re-decodes the same
  images every epoch and its shard stays warm across reshuffles
  (previously ~1/N of hits landed on the wrong shard and re-decoded).
  Groups are rebalanced down to ``ceil(B/N)`` items so one hot worker
  cannot serialize a batch — the moved items decode cold in sharded
  scope and hit anyway in pooled scope;
* ZERO-COPY HANDOFF: ``collect(leased=True)`` returns numpy VIEWS into
  the slot plus a :class:`SlotLease`; the slot re-enters the free ring
  only when the lease is released (``DevicePrefetcher`` releases it
  after the device transfer of that batch completes), eliminating the
  parent's per-batch copy-out entirely — ``feed_stats`` reports
  ``bytes_copied_per_batch = 0``. The legacy copy-out path remains the
  default for consumers that retain batches (``leased=False``);
* DECODE-AHEAD PIPELINING: the ring depth is decoupled from the lease
  depth (``DPTPU_RING_DEPTH``) and the DataLoader pre-issues spans for
  up to ``DPTPU_DECODE_AHEAD`` batches the moment slots free, so the
  per-worker queues always hold the NEXT batches' spans — workers roll
  straight across batch boundaries instead of draining while the
  parent collects, and per-slot completion counters absorb spans
  finishing out of batch order. ``collect`` still consumes in batch
  order (the epoch contract is unchanged);
* SPECULATIVE STRAGGLER RE-ISSUE (``DPTPU_SPECULATE``, default on):
  when a collect has waited ``speculate_after_s`` on a slot whose last
  spans sit on a stalled worker, the parent re-issues those spans to
  IDLE workers. First-writer-wins is safe under the ``(seed, epoch,
  index)`` bit-identity contract — both copies write the SAME bytes
  into the SAME disjoint rows, so even racing writes cannot tear. The
  late twin's ack is recognized as a GHOST (its task is no longer
  pending) and, until it arrives, the slot is QUARANTINED rather than
  recycled: a ghost still writing its (old, identical) bytes must
  never overlap a NEW batch decoded into a reused slot;
* COLD-EPOCH BYTE READAHEAD (``DPTPU_READAHEAD``, default on): at span
  pre-issue time the parent advises the kernel
  (``posix_fadvise(WILLNEED)`` via the native ``dptpu_file_readahead``
  or the ``os`` fallback) to start pulling the JPEG bytes of the
  pre-issued batches into the page cache — the workers' reads land
  warm ``DPTPU_DECODE_AHEAD`` batches later, hiding cold-epoch I/O
  latency under decode of the current batches.

SUPERVISION (dptpu.resilience): the pool is watched, not trusted. Every
result wait runs under a deadline (``DPTPU_WORKER_TIMEOUT_S``); a dead
worker (OOM-kill, native crash, SIGKILL) or a silent hang triggers a pool
restart — workers are killed, queues rebuilt, and every UNACKED span
re-enqueued to its assigned worker, which is safe because spans are
deterministic pure writes into disjoint rows (re-decoding produces the
same bytes). A span that ERRORS is retried ``DPTPU_SPAN_RETRIES`` times
(covers transient I/O) before the worker's traceback is re-raised in the
parent. After ``DPTPU_POOL_RESTARTS`` CONSECUTIVE restarts without
progress the pool raises :class:`WorkerPoolBroken`, and the DataLoader
degrades to thread mode with a loud warning instead of killing a
multi-hour job. An ``atexit`` hook unlinks the SharedMemory segments of
any pipeline the parent abandons without ``close()`` (an aborted run
must not leak ``/dev/shm`` until reboot).

Workers are spawned (not forked) by default: the parent holds JAX/XLA
runtime threads whose locks must not be forked mid-flight. Spawn pickles
the dataset once per worker; a sharded ``DecodeCache`` crosses that
boundary as budget-only (each worker warms its own shard, budget divided
by the pool size — see ``dptpu/data/cache.py``), while a pooled
``ShmDecodeCache`` crosses as an attach spec to the one shared slab that
also SURVIVES pool restarts warm (``dptpu/data/shm_cache.py``).
"""

from __future__ import annotations

import atexit
import queue as _queue
import sys
import time
import traceback
import weakref
from typing import Optional, Tuple

import numpy as np

from dptpu.data.dataset import _copy_checked
from dptpu.data.shm_cache import close_segment, create_named_segment
from dptpu.envknob import env_float, env_int
from dptpu.resilience.faults import FaultPlan

SEGMENT_PREFIX = "dptpu_ring"

_LIVE_PIPELINES: "weakref.WeakSet" = weakref.WeakSet()
_ATEXIT_REGISTERED = False

# slots still leased (never released by the consumer, never revoked by a
# reset) when their pipeline closed — a consumer-side protocol bug; the
# conftest session fixture fails the suite when this moves
_LEASE_LEAKS = 0


def leaked_lease_count() -> int:
    """Slots that were still leased when their pipeline closed, summed
    over every pipeline this process has closed. A lease the consumer
    released (or a ``reset`` revoked — the abandoned-epoch path) never
    counts; only close-with-lease-outstanding does."""
    return _LEASE_LEAKS


def _atexit_close_all():
    """Unlink shared-memory segments of pipelines the parent never closed
    (otherwise an aborted run leaks /dev/shm until reboot)."""
    for pipe in list(_LIVE_PIPELINES):
        try:
            pipe.close()
        except Exception:
            pass


def _register_pipeline(pipe):
    global _ATEXIT_REGISTERED
    _LIVE_PIPELINES.add(pipe)
    if not _ATEXIT_REGISTERED:
        atexit.register(_atexit_close_all)
        _ATEXIT_REGISTERED = True


def live_segment_names():
    """Ring segment names owned by still-open pipelines in THIS process
    (the conftest /dev/shm leak guard's allowlist)."""
    out = set()
    for pipe in list(_LIVE_PIPELINES):
        if not pipe._closed:
            out.add(pipe._shm_imgs.name.lstrip("/"))
            out.add(pipe._shm_labels.name.lstrip("/"))
    return out


class WorkerPoolBroken(RuntimeError):
    """The pool failed ``max_restarts`` consecutive times — the caller
    should degrade to thread mode rather than keep flogging it."""


def _affinity_of(index: int, num_workers: int) -> int:
    """Stable index → worker hash (Fibonacci multiplicative): identical
    across epochs, runs and pool restarts, so a worker's sharded cache
    keeps seeing the same images no matter how the sampler reshuffles."""
    return ((index * 2654435761) >> 7) % num_workers


def routing_of(dataset, span_affinity: bool) -> str:
    """Which affinity key routes spans to workers: ``"shard"`` (a
    packed-shard dataset exposing ``shard_of`` — whole-shard-per-worker
    routing on a stable hash of the shard id), ``"index"`` (per-sample
    hash) or ``"contiguous"`` (affinity off). The ONE derivation —
    ``ShmBatchPipeline`` routes by it and ``DataLoader.feed_stats``
    reports it (also before the lazy pipeline exists), so the reported
    mode can never diverge from the mode actually used."""
    if not span_affinity:
        return "contiguous"
    return "shard" if getattr(dataset, "shard_of", None) is not None \
        else "index"


def _affinity_spans(batch_indices, num_workers: int, affinity_key=None):
    """Split one batch into per-worker spans by affinity, then
    rebalance any group above ``ceil(B/N)`` down to the least-loaded
    workers (the idle-worker fallback: utilization beats affinity for
    the overflow items). Returns ``[(wid, offsets, indices), ...]``.

    ``affinity_key`` maps a sample index to the value that is hashed
    (default: the index itself). Packed-shard datasets pass their
    ``shard_of`` so a WHOLE shard's extents land on one worker — the
    shard-level decode-cache affinity (ROADMAP data-plane follow-on):
    the hash is stable in the SHARD id, so a shard's samples stay
    together no matter how the sampler interleaves shards, instead of
    scattering one shard's extent stream across every worker."""
    n = len(batch_indices)
    if num_workers <= 1:
        return [(0, tuple(range(n)),
                 tuple(int(i) for i in batch_indices))]
    groups = [([], []) for _ in range(num_workers)]
    for o, raw in enumerate(batch_indices):
        idx = int(raw)
        key = idx if affinity_key is None else affinity_key(idx)
        g = groups[_affinity_of(int(key), num_workers)]
        g[0].append(o)
        g[1].append(idx)
    cap = -(-n // num_workers)
    sizes = [len(g[0]) for g in groups]
    for w in range(num_workers):
        while sizes[w] > cap:
            t = min(range(num_workers), key=lambda k: sizes[k])
            if sizes[t] >= cap:
                break
            groups[t][0].append(groups[w][0].pop())
            groups[t][1].append(groups[w][1].pop())
            sizes[w] -= 1
            sizes[t] += 1
    return [
        (w, tuple(offs), tuple(idxs))
        for w, (offs, idxs) in enumerate(groups)
        if offs
    ]


def _contiguous_spans(batch_indices, num_workers: int):
    """Legacy span split (affinity off): contiguous ceil(B/N) chunks,
    chunk k → worker k."""
    n = len(batch_indices)
    span = -(-n // num_workers)
    out = []
    for k, o in enumerate(range(0, n, span)):
        idxs = tuple(int(i) for i in batch_indices[o:o + span])
        out.append((k % num_workers, tuple(range(o, o + len(idxs))), idxs))
    return out


def _worker_main(worker_id, dataset, imgs_name, labels_name, slots,
                 batch_size, item_shape, seed, num_workers, task_q, res_q,
                 item_dtype="uint8"):
    """Decode-worker loop: pull ``(slot, task, offsets, indices, epoch)``
    spans from THIS worker's queue, write pixels/labels straight into the
    shared ring, ack on ``res_q``.

    Runs in a spawned child — keep imports local and never touch JAX
    (``_copy_checked`` comes from the module import: dataset.py is
    numpy/stdlib-only, so hoisting it out of the hot loop is safe).
    """
    from multiprocessing import shared_memory

    # NOTE: attaching re-registers the names with the resource tracker the
    # children inherit from the parent — an idempotent set-add, so the
    # parent's close()+unlink() still cleans up exactly once. Do NOT
    # unregister here: that would strip the parent's registration and leak
    # the segments if the parent dies uncleanly.
    shm_imgs = shared_memory.SharedMemory(name=imgs_name)
    shm_labels = shared_memory.SharedMemory(name=labels_name)
    imgs = np.ndarray((slots, batch_size) + tuple(item_shape),
                      np.dtype(item_dtype), buffer=shm_imgs.buf)
    labels = np.ndarray((slots, batch_size), np.int32,
                        buffer=shm_labels.buf)
    cache = getattr(dataset, "decode_cache", None)
    if cache is not None and num_workers > 1:
        # keep the configured cache_bytes a TOTAL budget across the pool
        # (a pooled ShmDecodeCache makes this a documented no-op: its
        # slab is already one shared budget)
        cache.scale_budget(num_workers)
    get_into = getattr(dataset, "get_into", None)
    get = getattr(dataset, "get", None)
    # worker-side fault injection (io_error / worker_hang) re-parses the
    # inherited DPTPU_FAULT env — nothing fault-related crosses the pickle
    try:
        fault_plan = FaultPlan.from_env()
    except ValueError:
        fault_plan = None  # the parent raises the parse error loudly
    try:
        while True:
            task = task_q.get()
            if task is None:
                break
            slot, task_id, offsets, idxs, epoch = task
            try:
                t_span, c_span = time.monotonic(), time.thread_time()
                for off, index in zip(offsets, idxs):
                    if fault_plan is not None:
                        fault_plan.worker_decode_hook(worker_id, index)
                    rng = np.random.default_rng([seed, epoch, index])
                    row = imgs[slot, off]
                    if get_into is not None:
                        labels[slot, off] = get_into(index, rng, row)
                    else:
                        if get is not None:
                            img, lab = get(index, rng)
                        else:
                            img, lab = dataset[index]
                        _copy_checked(row, img, index)
                        labels[slot, off] = lab
                hits, misses = (cache.hits, cache.misses) if cache else (0, 0)
                # the span's own decode wall time rides the ack — the
                # straggler controller's per-worker speed signal,
                # unpolluted by queue wait or the parent's drain cadence
                # — and this thread's CPU seconds beside it: what the
                # rows cost against what they took (the ``collect``
                # span's ``cpu_s`` / ``wall_s``, as in thread mode)
                res_q.put(("done", worker_id, slot, task_id, hits, misses,
                           time.monotonic() - t_span,
                           time.thread_time() - c_span))
            except BaseException:
                res_q.put(
                    ("error", worker_id, slot, task_id,
                     traceback.format_exc())
                )
    except (EOFError, OSError, KeyboardInterrupt):
        pass  # parent went away / interrupt: exit quietly
    finally:
        imgs = labels = None
        shm_imgs.close()
        shm_labels.close()


class SlotLease:
    """Consumer-held claim on one ring slot: the views ``collect``
    returned stay byte-stable until ``release()``. Releasing twice (or
    after the ring reset/retired the slot underneath — the generation
    check) is a no-op, so the DataLoader's after-yield backstop and the
    DevicePrefetcher's after-transfer release compose safely."""

    __slots__ = ("_pipe", "slot", "_gen", "released")

    def __init__(self, pipe, slot: int, gen: int):
        self._pipe = pipe
        self.slot = slot
        self._gen = gen
        # single-writer handoff: only the consumer that holds the lease
        # flips it (idempotence guard); the ring side never writes it —
        # revocation happens through the generation counter instead
        self.released = False  # owned-by: consumer

    def release(self):
        if self.released:
            return
        self.released = True
        self._pipe._release_slot(self.slot, self._gen)


class ShmBatchPipeline:
    """The process-mode backend of ``DataLoader``: shared-memory slot ring
    + supervised persistent worker pool + per-worker task queues (span
    affinity) + one shared ack queue.

    Protocol (driven by ``DataLoader._epoch_process``): ``submit`` fans a
    batch's indices out as one span task per worker into a free slot;
    ``collect`` blocks until that slot's spans are acked, then either
    copies the rows out and recycles the slot immediately (legacy), or —
    ``leased=True`` — hands back zero-copy views plus a
    :class:`SlotLease` and recycles only on release. ``reset`` drains an
    abandoned epoch's in-flight work, revokes outstanding leases (their
    late ``release()`` calls no-op via the generation check) and marks
    every slot free.

    Supervision bookkeeping: ``_pending[slot][task_id] = task`` holds
    every unacked span — exactly what a pool restart must re-enqueue; it
    is the single source of truth for "work the consumer is still owed".
    """

    def __init__(self, dataset, batch_size: int, item_shape: Tuple[int, ...],
                 num_workers: int, seed: int, slots: int,
                 mp_start: str = "spawn",
                 timeout_s: Optional[float] = None,
                 max_restarts: Optional[int] = None,
                 span_retries: Optional[int] = None,
                 span_affinity: bool = True,
                 speculate: bool = True,
                 speculate_after_s: float = 0.5,
                 readahead: bool = True,
                 item_dtype="uint8"):
        import multiprocessing as mp

        self.batch_size = batch_size
        self.item_shape = tuple(int(d) for d in item_shape)
        # uint8 pixels, or what a row of another kind is made of (int32
        # token ids): the ring holds items of one shape and one dtype
        self.item_dtype = np.dtype(item_dtype)
        self.num_workers = max(1, num_workers)
        self.slots = max(2, slots)
        self.span_affinity = span_affinity
        # shard-level cache affinity: a packed-shard dataset exposes
        # shard_of, and hashing THAT (not the sample index) routes a
        # whole shard's extents to one worker (see _affinity_spans)
        self.routing = routing_of(dataset, span_affinity)
        self._affinity_key = (
            dataset.shard_of if self.routing == "shard" else None
        )
        self._dataset = dataset
        self._seed = seed
        self._has_cache = getattr(dataset, "decode_cache", None) is not None
        # supervision knobs (ctor beats env beats default)
        self.timeout_s = (
            timeout_s if timeout_s is not None
            else env_float("DPTPU_WORKER_TIMEOUT_S", 120.0)
        )
        self.max_restarts = (
            max_restarts if max_restarts is not None
            else env_int("DPTPU_POOL_RESTARTS", 3)
        )
        self.span_retries = (
            span_retries if span_retries is not None
            else env_int("DPTPU_SPAN_RETRIES", 2)
        )
        if self.timeout_s <= 0:
            raise ValueError(
                f"DPTPU_WORKER_TIMEOUT_S={self.timeout_s} must be > 0 "
                f"seconds"
            )
        if self.max_restarts < 0 or self.span_retries < 0:
            raise ValueError(
                "DPTPU_POOL_RESTARTS and DPTPU_SPAN_RETRIES must be >= 0"
            )
        item_bytes = int(np.prod(self.item_shape)) * self.item_dtype.itemsize
        self._ctx = mp.get_context(mp_start)
        self._shm_imgs = create_named_segment(
            SEGMENT_PREFIX,
            max(1, self.slots * batch_size * item_bytes),
        )
        self._shm_labels = create_named_segment(
            SEGMENT_PREFIX, self.slots * batch_size * 4
        )
        self._imgs = np.ndarray(
            (self.slots, batch_size) + self.item_shape, self.item_dtype,
            buffer=self._shm_imgs.buf,
        )
        self._labels = np.ndarray(
            (self.slots, batch_size), np.int32, buffer=self._shm_labels.buf
        )
        self._outstanding = [0] * self.slots  # span acks still in flight
        # workers' own decode seconds of each slot's batch, wall and
        # CPU, from the acks
        self._slot_wall_s = [0.0] * self.slots
        self._slot_cpu_s = [0.0] * self.slots
        # what the last collect() found (the loader's collect span)
        self.last_collect = {"ready": True, "cpu_s": 0.0, "wall_s": 0.0}
        self._pending = {s: {} for s in range(self.slots)}  # task_id -> task
        self._retries = {}  # (slot, task_id) -> attempts so far
        self._free = list(range(self.slots))
        self._leased = set()  # slots held by unreleased SlotLeases
        self._slot_gen = [0] * self.slots  # stale-lease guard
        self._worker_cache = {}  # worker_id -> latest (hits, misses)
        self._cache_base = [0, 0]  # counts folded in from killed pools
        self._restarts_total = 0
        self._span_retries_total = 0
        self._consec_failures = 0
        self._bytes_copied = 0  # parent-side copy-out bytes (legacy path)
        self._collects = 0
        # decode-ahead / speculation bookkeeping
        self.speculate = speculate and self.num_workers > 1
        self.speculate_after_s = speculate_after_s
        self._worker_load = [0] * self.num_workers  # unacked issues per q
        self._extra_issues = [0] * self.slots  # unacked DUPLICATE issues
        self._quarantine = set()  # freed slots awaiting ghost acks
        self._speculated = set()  # (slot, task_id) already re-issued
        self._straggler_reissues_total = 0
        # straggler-control seam (dptpu/resilience/elastic.py): every
        # done ack carries the span's worker-side decode duration —
        # charged to the worker that DID the decode — drained by the
        # controller each tick; a re-split routes future affinity AWAY
        # from a slow worker and the eviction hook feeds the
        # supervisor's restart policy
        self._latency_obs = []  # [(acking_worker, span_decode_s), ...]
        self._routed_away = set()  # workers the affinity router avoids
        self._resplits_total = 0
        self._evictions_total = 0
        self._io_wait_s = 0.0  # parent time blocked in collect waits
        self._occ_sum = 0  # ring-occupancy accumulator (sampled at collect)
        self._occ_n = 0
        # cold-epoch byte readahead: fadvise the pre-issued spans' JPEG
        # files so worker reads land in a warm page cache (file-backed
        # datasets only — synthetic ones have no paths to advise).
        # Advised-once dedup is a per-index BITMAP, not a set of path
        # strings: at ImageNet scale the strings would pin hundreds of
        # MB of parent RSS for the pipeline's lifetime
        self._readahead = readahead
        self._sample_paths = getattr(dataset, "samples", None)
        # a STREAMING shard dataset owns its own I/O engine (O_DIRECT
        # ring / store range fetch into the /dev/shm byte slab,
        # dptpu/data/stream.py): pre-issue routes to its
        # ``prefetch_extents`` INSTEAD of fadvise — WILLNEED would
        # repopulate the page cache the O_DIRECT ring just bypassed.
        # Such datasets expose no ``samples`` path list, so the two
        # paths are mutually exclusive by construction (and asserted in
        # DataLoader.feed_stats).
        self._prefetch_extents = getattr(dataset, "prefetch_extents", None)
        self._readahead_done = (
            bytearray(len(self._sample_paths))
            if self._sample_paths is not None
            and self._prefetch_extents is None else None
        )
        self._closed = False
        self._start_workers()
        _register_pipeline(self)

    def _start_workers(self):
        """(Re)create the task/ack queues and spawn the worker pool —
        queues are rebuilt with the pool because a SIGKILLed worker can
        leave a queue's internal pipe in a torn state."""
        # straggler detection baseline: a worker is SUSPECT once it has
        # gone speculate_after_s without acking (reset with the pool)
        self._worker_last_ack = [time.monotonic()] * self.num_workers
        self._task_qs = [self._ctx.Queue() for _ in range(self.num_workers)]
        self._res_q = self._ctx.Queue()
        self._procs = [
            self._ctx.Process(
                target=_worker_main,
                args=(wid, self._dataset, self._shm_imgs.name,
                      self._shm_labels.name, self.slots, self.batch_size,
                      self.item_shape, self._seed, self.num_workers,
                      self._task_qs[wid], self._res_q, self.item_dtype.str),
                daemon=True,
                name=f"dptpu-data-{wid}",
            )
            for wid in range(self.num_workers)
        ]
        for p in self._procs:
            p.start()

    # -- submission / collection -------------------------------------------

    def free_slot_count(self) -> int:
        """Slots available to ``submit`` right now (the DataLoader's
        pre-issue pump gates on this instead of racing the exception)."""
        return len(self._free)

    def ghost_issues_in_flight(self) -> bool:
        """True while any speculated duplicate issue is still unacked —
        quarantined slots can only re-enter the free ring once these
        drain (or a pool restart vaporizes them)."""
        return any(self._extra_issues)

    def drain_one_ack(self):
        """Process ONE worker ack under the watchdog — the pump's
        escape hatch when every free slot is ghost-quarantined: the ack
        (or the watchdog's restart) is what frees a slot."""
        self._handle(self._next_result(), mode="normal")

    def submit(self, batch_indices, epoch: int) -> Tuple[int, int]:
        """Fan one batch out as affinity-routed span tasks into a free
        slot; returns ``(slot, n_valid)``. The caller's issue-ahead
        window plus its unreleased leases must not exceed ``slots``
        (DataLoader sizes the ring accordingly)."""
        if not self._free:
            raise RuntimeError(
                f"no free batch slot (ring of {self.slots}, "
                f"{len(self._leased)} leased, {len(self._quarantine)} "
                f"ghost-quarantined, rest in flight) — issue-ahead depth "
                f"plus unreleased leases exceeded the ring size"
            )
        slot = self._free.pop()
        # drop the previous tenant's speculation records: (slot, task_id)
        # pairs recur when slots are reused, and a stale entry would
        # silently veto re-issue for the NEW batch's spans (safe to drop
        # here — a slot re-enters the free ring only once its ghost
        # issues have fully drained)
        self._speculated = {k for k in self._speculated if k[0] != slot}
        spans = (
            _affinity_spans(batch_indices, self.num_workers,
                            self._affinity_key)
            if self.span_affinity
            else _contiguous_spans(batch_indices, self.num_workers)
        )
        if self._routed_away:
            # straggler route-away (the affinity seam): spans headed for
            # a worker the controller re-split divert to the least-
            # loaded healthy workers — planned loads tracked per span,
            # so a batch's diverted spans SPREAD instead of all landing
            # on whoever was idlest at remap time. Affinity resumes
            # when the controller restores the worker (recovered) or a
            # pool restart installs a fresh one.
            healthy = [w for w in range(self.num_workers)
                       if w not in self._routed_away]
            if healthy:
                planned = dict.fromkeys(healthy, 0)
                remapped = []
                for wid, offs, idxs in spans:
                    if wid in self._routed_away:
                        t = min(healthy, key=lambda k:
                                self._worker_load[k] + planned[k])
                        planned[t] += 1
                        wid = t
                    remapped.append((wid, offs, idxs))
                spans = remapped
        for task_id, (wid, offsets, idxs) in enumerate(spans):
            task = (slot, task_id, offsets, idxs, epoch, wid)
            self._pending[slot][task_id] = task
            self._task_qs[wid].put(task[:5])
            self._worker_load[wid] += 1
        self._outstanding[slot] = len(self._pending[slot])
        self._slot_wall_s[slot] = 0.0
        self._slot_cpu_s[slot] = 0.0
        if self._readahead:
            self._issue_readahead(batch_indices)
        return slot, len(batch_indices)

    def _issue_readahead(self, batch_indices):
        """Parent-side cold-epoch byte prefetch: advise the kernel to
        start reading this (pre-issued) batch's JPEG files NOW, so the
        worker that decodes them ``decode_ahead`` batches from now finds
        the bytes already in the page cache. Each path is advised once
        per pipeline — after the first epoch the cache is as warm as it
        will get and repeated advice is pure syscall overhead.

        Shard-streaming datasets take the OTHER branch: their extents
        are staged into the /dev/shm byte slab by their own engine
        (every pre-issue, not once — the slab evicts), and fadvise
        never runs."""
        if self._prefetch_extents is not None:
            self._prefetch_extents(batch_indices)
            return
        samples = self._sample_paths
        if samples is None:
            return
        from dptpu.data.native_image import file_readahead

        done = self._readahead_done
        for raw in batch_indices:
            i = int(raw)
            if done[i]:
                continue
            done[i] = 1
            file_readahead(samples[i][0])

    def collect(self, slot: int, out_rows: int, leased: bool = False):
        """Wait for ``slot``'s spans, then hand the rows to the consumer:
        ``leased=False`` copies them out (consumer owns the copies, slot
        recycles immediately); ``leased=True`` returns zero-copy VIEWS
        plus a :class:`SlotLease` — the slot recycles only on
        ``lease.release()``. Raises the worker's decode error, with its
        traceback, once its retry budget is spent.

        Acks are processed for WHATEVER slot they belong to while
        waiting (out-of-order span completion); and once the wait has
        lasted ``speculate_after_s``, the remaining spans of THIS slot
        are re-issued to idle workers (straggler speculation)."""
        t0 = time.monotonic()
        # acks that are already here count as done: the batch was ready
        # iff nothing is left to wait for once they are read
        while self._outstanding[slot] > 0:
            try:
                msg = self._res_q.get_nowait()
            except _queue.Empty:
                break
            self._handle(msg, mode="normal")
        ready = self._outstanding[slot] <= 0

        def _tick():
            # re-checked every poll (a no-op pass is a few comparisons):
            # the first attempt may find no healthy target yet — e.g.
            # every worker still busy or warming up — and a straggler is
            # only recognizable once its peers pull ahead
            if self.speculate and time.monotonic() - t0 \
                    >= self.speculate_after_s:
                self._speculate_slot(slot)

        while self._outstanding[slot] > 0:
            self._handle(self._next_result(tick=_tick), mode="normal")
        self._io_wait_s += time.monotonic() - t0
        self.last_collect = {"ready": ready,
                             "cpu_s": self._slot_cpu_s[slot],
                             "wall_s": self._slot_wall_s[slot]}
        self._occ_sum += self.slots - len(self._free)
        self._occ_n += 1
        self._collects += 1
        if leased:
            self._leased.add(slot)
            return (self._imgs[slot, :out_rows],
                    self._labels[slot, :out_rows],
                    SlotLease(self, slot, self._slot_gen[slot]))
        imgs = np.array(self._imgs[slot, :out_rows])
        labels = np.array(self._labels[slot, :out_rows])
        self._bytes_copied += imgs.nbytes + labels.nbytes
        self._recycle_slot(slot)
        return imgs, labels, None

    def _recycle_slot(self, slot: int):
        """Return a consumed slot to the free ring — unless a speculated
        ghost write may still be in flight for it, in which case it is
        QUARANTINED until the ghost acks (``_ghost_ack``): the ghost's
        bytes are identical to what the slot held, but would corrupt a
        NEW batch decoded into the reused slot."""
        if self._extra_issues[slot] > 0:
            self._quarantine.add(slot)
        else:
            self._free.append(slot)

    def _ghost_ack(self, slot: int):
        """Account one DUPLICATE ack (speculated twin, or the late ack
        of a span a retry/salvage already satisfied) and release the
        slot from quarantine once no ghost writer remains."""
        if self._extra_issues[slot] > 0:
            self._extra_issues[slot] -= 1
        if slot in self._quarantine and self._extra_issues[slot] == 0:
            self._quarantine.discard(slot)
            self._free.append(slot)

    def _speculate_slot(self, slot: int):
        """Re-issue ``slot``'s still-pending spans when their assigned
        worker looks STALLED — no ack from it within the speculation
        window — to the least-loaded HEALTHY worker (one duplicate per
        span, ever). The assigned worker keeps its copy — whichever
        finishes first completes the span (identical bytes, so even a
        racing write is benign) and the loser's ack is absorbed as a
        ghost. Healthy-target gating is what keeps this safe on a
        uniformly slow cold batch: when every worker is busy-but-acking
        there is no suspect, and when every worker is suspect there is
        no target — either way no decode work is doubled."""
        now = time.monotonic()
        # suspect = OWES work and has not acked within the window; a
        # worker with nothing queued is idle-HEALTHY (a drained queue
        # also goes quiet, and it is exactly the re-issue target)
        suspect = [
            self._worker_load[w] > 0
            and now - self._worker_last_ack[w] >= self.speculate_after_s
            for w in range(self.num_workers)
        ]
        healthy = [w for w in range(self.num_workers) if not suspect[w]]
        if not healthy:
            return
        for task_id, task in list(self._pending[slot].items()):
            if (slot, task_id) in self._speculated:
                continue
            assigned = task[5]
            if not suspect[assigned]:
                continue  # its worker is alive and acking: just slow us
            targets = [w for w in healthy if w != assigned]
            if not targets:
                continue
            w = min(targets, key=lambda k: self._worker_load[k])
            self._speculated.add((slot, task_id))
            self._extra_issues[slot] += 1
            self._worker_load[w] += 1
            self._straggler_reissues_total += 1
            self._task_qs[w].put(task[:5])

    def _release_slot(self, slot: int, gen: int):
        """SlotLease callback: recycle a leased slot. Generation-checked
        so a lease that outlived a ``reset``/``close`` (abandoned epoch,
        degrade-to-thread) is silently void instead of double-freeing."""
        if self._closed or gen != self._slot_gen[slot] \
                or slot not in self._leased:
            return
        self._leased.discard(slot)
        self._slot_gen[slot] += 1
        self._recycle_slot(slot)

    def reset(self):
        """Reclaim the ring after an abandoned epoch: wait out (or, on a
        restart, simply drop) in-flight work — INCLUDING ghost acks from
        speculated twins, which must drain before a slot may be reused —
        revoke outstanding leases, and mark every slot free. Errors for
        batches nobody will consume are discarded."""
        while any(self._outstanding) or any(self._extra_issues):
            self._handle(self._next_result(requeue=False), mode="discard")
        self._free = list(range(self.slots))
        self._quarantine.clear()
        self._leased.clear()
        self._slot_gen = [g + 1 for g in self._slot_gen]
        for spans in self._pending.values():
            spans.clear()
        self._retries.clear()
        self._speculated.clear()

    def kill_worker(self, index: int = 0) -> Optional[int]:
        """Fault-injection/debug hook: SIGKILL one live worker process
        (the supervisor must then restart the pool and re-enqueue its
        span). Returns the killed pid, or None if nothing was alive.

        Synchronous by design: the join guarantees the death is visible
        to the very next liveness check, so a chaos run deterministically
        exercises the restart path instead of racing a fast epoch."""
        alive = [p for p in self._procs if p.is_alive()]
        if not alive:
            return None
        p = alive[index % len(alive)]
        pid = p.pid
        p.kill()
        p.join(timeout=5.0)
        return pid

    # -- straggler control seam (dptpu/resilience/elastic.py) ---------------

    def drain_latency_observations(self):
        """``[(worker_id, span decode seconds), ...]`` since the last
        drain — the straggler controller's input. Durations are
        measured INSIDE the worker (stamped on the ack), so the signal
        reads pure per-worker decode speed, never the parent's drain
        cadence or queue depth."""
        obs, self._latency_obs = self._latency_obs, []
        return obs

    def resplit_worker(self, worker_id: int) -> int:
        """Controller escalation 1: re-issue worker ``worker_id``'s
        entire pending span tail to the least-loaded healthy workers NOW
        (the speculation machinery without its time gate — duplicate
        acks absorb as ghosts, first-writer-wins keeps bit-identity)
        and steer future affinity away from it until it is evicted or
        recovers. Returns the number of spans re-issued."""
        if not 0 <= worker_id < self.num_workers:
            raise ValueError(
                f"resplit_worker({worker_id}): pool has "
                f"{self.num_workers} workers"
            )
        targets = [w for w in range(self.num_workers)
                   if w != worker_id and w not in self._routed_away]
        if not targets:
            return 0  # nobody healthy to take the tail
        n = 0
        for slot, spans in self._pending.items():
            for task_id, task in list(spans.items()):
                if task[5] != worker_id \
                        or (slot, task_id) in self._speculated:
                    continue
                t = min(targets, key=lambda k: self._worker_load[k])
                self._speculated.add((slot, task_id))
                self._extra_issues[slot] += 1
                self._worker_load[t] += 1
                self._straggler_reissues_total += 1
                self._task_qs[t].put(task[:5])
                n += 1
        self._routed_away.add(worker_id)
        self._resplits_total += 1
        return n

    def restore_worker(self, worker_id: int):
        """Controller de-escalation: a re-split worker whose fresh
        observations read healthy again rejoins the affinity router."""
        self._routed_away.discard(worker_id)

    def evict_worker(self, worker_id: int) -> Optional[int]:
        """Controller escalation 2 — the supervisor's eviction policy:
        SIGKILL the worker; the watchdog's pool restart re-enqueues its
        unacked spans (the proven worker_kill recovery path). The dead
        worker stays routed-away until the restart actually installs
        its replacement (``_restart_pool`` clears the set) — routing
        spans at a corpse's queue would stall every batch behind the
        speculation window."""
        if not 0 <= worker_id < len(self._procs):
            return None
        p = self._procs[worker_id]
        pid = p.pid if p.is_alive() else None
        if pid is not None:
            p.kill()
            p.join(timeout=5.0)
        self._evictions_total += 1
        return pid

    # -- supervision --------------------------------------------------------

    def _next_result(self, requeue: bool = True, tick=None):
        """Wait for one worker ack under the watchdog: a dead worker or a
        deadline with zero progress restarts the pool (re-enqueueing the
        unacked spans unless ``requeue`` is off — the reset path drops
        them instead). Liveness is checked BEFORE every wait, not only on
        timeout: a worker that dies idle would otherwise silently shrink
        the pool forever. ``tick`` (optional) is called once per poll
        interval — the straggler-speculation trigger rides it, since a
        stalled span means no result arrives to return control."""
        deadline = time.monotonic() + self.timeout_s
        while True:
            if tick is not None:
                tick()
            dead = [p for p in self._procs if not p.is_alive()]
            if dead:
                p = dead[0]
                self._restart_pool(
                    f"worker {p.name} (pid {p.pid}) died with exit "
                    f"code {p.exitcode} — killed, OOM-reaped, or a "
                    f"native crash in the decoder",
                    requeue=requeue,
                )
            elif time.monotonic() > deadline:
                self._restart_pool(
                    f"no worker progress for {self.timeout_s:.1f}s "
                    f"with {sum(self._outstanding)} span(s) in flight "
                    f"— worker hang suspected",
                    requeue=requeue,
                )
            else:
                try:
                    return self._res_q.get(timeout=min(0.2, self.timeout_s))
                except _queue.Empty:
                    continue
            if not any(self._outstanding) and not any(self._extra_issues):
                # a restart dropped all pending work AND vaporized every
                # ghost issue (the queues died with the pool); nothing
                # will ever ack, so hand back a sentinel the _handle
                # modes understand as "no-op"
                return ("none",)
            deadline = time.monotonic() + self.timeout_s

    def _restart_pool(self, reason: str, requeue: bool = True):
        """Kill + respawn the pool; re-enqueue every unacked span to its
        assigned worker (safe: spans are deterministic pure writes into
        disjoint rows — and a pooled decode cache slab survives the
        restart warm, since it belongs to the parent's dataset)."""
        self._consec_failures += 1
        if self._consec_failures > self.max_restarts:
            raise WorkerPoolBroken(
                f"data-worker pool failed {self._consec_failures} "
                f"consecutive times (budget {self.max_restarts}); last "
                f"failure: {reason}"
            )
        self._restarts_total += 1
        print(
            f"WARNING: dptpu data-worker pool restart "
            f"{self._consec_failures}/{self.max_restarts}: {reason}",
            file=sys.stderr,
        )
        for p in self._procs:
            if p.is_alive():
                p.kill()
        for p in self._procs:
            p.join(timeout=2.0)
        # salvage acks already delivered before the failure, then drop
        # the torn queues (a SIGKILL mid-put can wedge them)
        while True:
            try:
                msg = self._res_q.get_nowait()
            except Exception:
                # Empty, or a torn message from the killed worker's
                # feeder thread (UnpicklingError & friends) — either way
                # the queue is done yielding salvage; the restart's span
                # re-enqueue covers whatever was lost
                break
            if msg[0] == "done":
                self._handle(msg, mode="normal")
            # drained error acks stay pending: the restart re-enqueues
            # them, which is exactly a retry
        for q in self._task_qs + [self._res_q]:
            try:
                q.close()
                q.cancel_join_thread()
            except Exception:
                pass
        # respawned workers count hits/misses from zero: fold the dead
        # pool's last-known counts into a base so the cumulative numbers
        # feed_stats differences stay MONOTONIC across restarts (else a
        # warm post-restart epoch reads a bogus 0.0 interval hit rate)
        self._cache_base[0] += sum(
            h for h, _ in self._worker_cache.values())
        self._cache_base[1] += sum(
            m for _, m in self._worker_cache.values())
        self._worker_cache.clear()
        self._start_workers()
        # the old queues died with the pool: every in-flight issue —
        # including speculated twins — is gone, so ghost accounting
        # resets and quarantined slots are safe to reuse immediately
        self._speculated.clear()
        self._extra_issues = [0] * self.slots
        if self._quarantine:
            self._free.extend(sorted(self._quarantine))
            self._quarantine.clear()
        self._worker_load = [0] * self.num_workers
        # the whole pool is fresh: straggler verdicts start over
        self._routed_away.clear()
        if requeue:
            for spans in self._pending.values():
                for task in spans.values():
                    self._task_qs[task[5]].put(task[:5])
                    self._worker_load[task[5]] += 1
        else:
            for spans in self._pending.values():
                spans.clear()
            self._outstanding = [0] * self.slots
            self._retries.clear()

    def _handle(self, msg, mode: str = "normal"):
        """Apply one worker ack. Modes: ``normal`` (collect path — retry
        errored spans up to the budget, then raise with the worker's
        traceback), ``discard`` (reset path — drop errored spans).

        An ack for a task NO LONGER PENDING is a GHOST — the speculated
        twin (or a retry the twin beat) finishing late. Ghosts never
        touch the completion counters (a second decrement would send
        ``_outstanding`` negative and wedge ``reset``); they only settle
        the slot's quarantine accounting."""
        kind = msg[0]
        if kind == "none":  # restart-with-drop sentinel from _next_result
            return
        worker_id, slot, task_id = msg[1], msg[2], msg[3]
        if worker_id < len(self._worker_load):
            if self._worker_load[worker_id] > 0:
                self._worker_load[worker_id] -= 1
            self._worker_last_ack[worker_id] = time.monotonic()
        if kind == "done":
            self._consec_failures = 0  # the pool is making progress
            self._worker_cache[worker_id] = (msg[4], msg[5])
            if len(msg) > 6:
                # the span's worker-side decode duration, charged to
                # whichever worker actually decoded it (ghost twins
                # included — their decode speed is real signal too)
                self._latency_obs.append((worker_id, float(msg[6])))
                if len(self._latency_obs) > 4096:
                    del self._latency_obs[:2048]
            if self._pending[slot].pop(task_id, None) is None:
                self._ghost_ack(slot)
                return
            self._outstanding[slot] -= 1
            if len(msg) > 6:
                self._slot_wall_s[slot] += float(msg[6])
            if len(msg) > 7:
                self._slot_cpu_s[slot] += float(msg[7])
            self._retries.pop((slot, task_id), None)
            return
        # kind == "error"
        task = self._pending[slot].get(task_id)
        if task is None:  # ghost twin errored after the span completed
            self._ghost_ack(slot)
            return
        if mode == "discard":
            self._outstanding[slot] -= 1
            self._pending[slot].pop(task_id, None)
            self._retries.pop((slot, task_id), None)
            return
        attempts = self._retries.get((slot, task_id), 0)
        if attempts < self.span_retries:
            self._retries[(slot, task_id)] = attempts + 1
            self._span_retries_total += 1
            print(
                f"WARNING: dptpu data worker {worker_id} errored on batch "
                f"slot {slot} span {task_id}; retrying span "
                f"({attempts + 1}/{self.span_retries})",
                file=sys.stderr,
            )
            self._task_qs[task[5]].put(task[:5])
            self._worker_load[task[5]] += 1
            # the errored copy may have been the speculated twin while
            # the assigned worker is STILL stalled: forget the
            # speculation record so a later tick may re-issue — without
            # this, the retry sits behind the stall and the span can
            # only complete via watchdog pool restart
            self._speculated.discard((slot, task_id))
            return
        raise RuntimeError(
            f"data worker {worker_id} failed while decoding (batch "
            f"slot {slot}, span {task_id}"
            + (f", after {attempts} retries" if attempts else "")
            + f"); worker traceback:\n{msg[4]}"
        )

    # -- telemetry ----------------------------------------------------------

    def cache_stats(self) -> dict:
        """Pool-wide decode-cache counters, aggregated from the latest
        per-worker ack (workers piggyback cumulative counts on every
        ``done`` message — no extra round trip)."""
        if not self._has_cache:
            return {}
        hits = self._cache_base[0] + sum(
            h for h, _ in self._worker_cache.values())
        misses = self._cache_base[1] + sum(
            m for _, m in self._worker_cache.values())
        total = hits + misses
        stats = {
            "cache_hits": hits,
            "cache_misses": misses,
            "cache_hit_rate": (hits / total) if total else 0.0,
        }
        scope = getattr(self._dataset.decode_cache, "scope", "sharded")
        stats["cache_scope"] = scope
        return stats

    def supervision_stats(self) -> dict:
        """Watchdog + straggler-control counters for feed telemetry."""
        return {
            "pool_restarts": self._restarts_total,
            "span_retries": self._span_retries_total,
            "straggler_resplits": self._resplits_total,
            "worker_evictions": self._evictions_total,
        }

    def copy_stats(self) -> dict:
        """Parent-side copy-out accounting: ``bytes_copied`` stays 0 when
        every collect was leased (the zero-copy contract the feed_stats
        ``bytes_copied_per_batch`` field reports)."""
        return {
            "bytes_copied": self._bytes_copied,
            "collects": self._collects,
        }

    def ring_stats(self) -> dict:
        """Decode-ahead telemetry, cumulative since pipeline start (the
        DataLoader folds closed pipelines' totals and turns ``io_wait_s``
        into a per-feed_stats-call interval): occupancy is sampled at
        every collect (slots in flight + leased + quarantined, out of
        ``slots``), ``io_wait_s`` is parent wall time blocked waiting
        for a slot's spans, and ``straggler_reissues`` counts
        speculative re-issues to idle workers."""
        return {
            "ring_depth": self.slots,
            "occupancy_sum": self._occ_sum,
            "occupancy_samples": self._occ_n,
            "io_wait_s": self._io_wait_s,
            "straggler_reissues": self._straggler_reissues_total,
        }

    # -- lifecycle ----------------------------------------------------------

    def close(self):
        if self._closed:
            return
        self._closed = True
        # lease-leak bookkeeping for the conftest session guard: a slot
        # still leased HERE was neither released by its consumer nor
        # revoked by a reset — a protocol bug worth failing CI over
        # (the segments themselves are still unlinked below regardless)
        global _LEASE_LEAKS
        _LEASE_LEAKS += len(self._leased)
        for q in self._task_qs:
            try:
                q.put(None)
            except Exception:
                pass
        # an idle worker leaves on the sentinel within milliseconds. One
        # that still has pre-issued spans queued (an abandoned epoch)
        # would decode them all first, for nobody: it gets what is left
        # of ONE short grace shared by the pool, then SIGTERM. Safe at
        # any point: its rows land in a ring that is unlinked below, and
        # a pooled cache slab recovers a lock whose owner died.
        grace_end = time.monotonic() + 0.25
        for p in self._procs:
            p.join(timeout=max(0.0, grace_end - time.monotonic()))
        for p in self._procs:
            if p.is_alive():
                p.terminate()
        for p in self._procs:
            p.join(timeout=2.0)
            if p.is_alive():  # hung in non-interruptible state: no mercy
                p.kill()
                p.join(timeout=2.0)
        for q in self._task_qs + [self._res_q]:
            try:
                q.close()
                q.cancel_join_thread()
            except Exception:
                pass
        self._imgs = self._labels = None  # release buffer exports first
        for shm in (self._shm_imgs, self._shm_labels):
            # an unreleased lease view makes mmap.close() raise
            # BufferError; the NAME is unlinked regardless, so nothing
            # outlives the process (see shm_cache.close_segment)
            close_segment(shm, unlink=True)
        _LIVE_PIPELINES.discard(self)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
