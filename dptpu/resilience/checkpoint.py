"""Mid-epoch checkpoint rotation + corrupt-file-tolerant resume scanning.

The trainer's epoch-boundary ``checkpoint.pth.tar`` (the reference's
contract) stays untouched; this module adds rotated STEP checkpoints —
``checkpoint-e0003-s000120.pth.tar`` = "epoch 3, 120 batches consumed" —
written every ``--ckpt-steps`` steps and on preemption, keeping the last
``--ckpt-keep``. Resume goes through :func:`find_resumable`, which accepts
a file OR a directory, verifies candidates (content CRC when present,
structural parse otherwise), and falls back past corrupt/truncated files
to the newest verifiable one — under the deterministic ``(seed, epoch,
index)`` data contract, resuming from an OLDER position is always safe
(the replay reproduces the exact same trajectory, just re-earns some
steps), whereas trusting a torn file is not.
"""

from __future__ import annotations

import os
import re
import sys
from typing import Optional

# NOTE: dptpu.train.checkpoint is imported lazily inside the functions
# below — importing it at module scope runs dptpu.train.__init__, which
# imports fit, which imports this package: a cycle. The names this module
# needs (save_checkpoint, split_payload, CHECKPOINT_NAME, ...) are stable.

CHECKPOINT_NAME = "checkpoint.pth.tar"  # mirrors dptpu.train.checkpoint
STEP_CHECKPOINT_RE = re.compile(r"^checkpoint-e(\d+)-s(\d+)\.pth\.tar$")


def step_checkpoint_name(epoch: int, step_in_epoch: int) -> str:
    return f"checkpoint-e{epoch:04d}-s{step_in_epoch:06d}.pth.tar"


def verify_checkpoint_bytes(raw: bytes, name: str = "<bytes>") -> tuple:
    """The byte-level half of :func:`verify_checkpoint` — shared by the
    local path and the store-URL path (a remote checkpoint is verified
    from its fetched bytes with the IDENTICAL rules)."""
    from dptpu.train.checkpoint import CorruptCheckpointError, split_payload

    if not raw:
        return False, "empty file (0 bytes)"
    if raw[:4] == b"PK\x03\x04" or raw[:2] == b"\x80\x02":
        return True, "torch-format (unverifiable, accepted)"
    try:
        payload, verified = split_payload(raw, name)
    except CorruptCheckpointError as e:
        return False, str(e)
    if verified:
        return True, "crc ok"
    try:
        from flax import serialization

        restored = serialization.msgpack_restore(payload)
    except Exception as e:
        return False, f"no crc footer and msgpack parse failed: {e}"
    if not isinstance(restored, dict):
        return False, "no crc footer and payload is not a dict"
    return True, "legacy footerless (structurally intact, accepted)"


def verify_checkpoint(path: str) -> tuple:
    """Cheap integrity triage without building a state template; returns
    ``(ok, reason)``. ``path`` may be a local file or a store URL.

    * empty file → rejected (crashed write);
    * dptpu file with CRC footer → CRC decides;
    * footerless flax file (pre-resilience) → accepted iff the msgpack
      envelope still parses to a dict (catches truncation, which also
      removes the footer a new-format file would have had);
    * reference torch file (zip / legacy-pickle magic) → accepted
    (no checksum to check; ``load_checkpoint`` handles the rest).
    """
    from dptpu.data.store import is_store_url, open_store, split_store_url

    if is_store_url(path):
        base, name = split_store_url(path)
        try:
            raw = open_store(base).get_bytes(name)
        except OSError as e:
            return False, f"unreadable: {e}"
        return verify_checkpoint_bytes(raw, path)
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError as e:
        return False, f"unreadable: {e}"
    return verify_checkpoint_bytes(raw, path)


def _candidates(directory: str):
    """Checkpoint files in ``directory`` (a local dir or a store URL),
    newest-first by mtime (the save order). ``model_best`` is a copy,
    not a resume point — excluded."""
    from dptpu.data.store import open_store

    store = open_store(directory)
    out = []
    try:
        entries = store.list()
    except OSError:
        return out
    for name, mtime in entries:
        if name == CHECKPOINT_NAME or STEP_CHECKPOINT_RE.match(name):
            out.append((mtime, name))
    out.sort(reverse=True)
    return [store.path_for(name) for _, name in out]


def find_resumable(path: str, verbose: bool = True) -> Optional[str]:
    """Resolve ``--resume PATH`` to the newest VERIFIABLE checkpoint.

    ``path`` may name a file (used if it verifies; otherwise its siblings
    are scanned) or a directory (scanned directly) — or the store-URL
    equivalent of either (``.pth.tar`` URLs are files, any other URL is
    scanned as a store prefix), with the IDENTICAL verify + fall-back-
    past-corrupt contract. Returns None when nothing loadable exists —
    the caller keeps the reference's warn-and-continue behavior
    (imagenet_ddp.py:152-153).
    """
    from dptpu.data.store import is_store_url, split_store_url

    tried = []
    if is_store_url(path):
        if path.endswith(".pth.tar"):
            ok, reason = verify_checkpoint(path)
            if ok:
                return path
            tried.append((path, reason))
            directory = split_store_url(path)[0]
        else:
            directory = path.rstrip("/")
    elif os.path.isfile(path):
        ok, reason = verify_checkpoint(path)
        if ok:
            return path
        tried.append((path, reason))
        directory = os.path.dirname(path) or "."
    elif os.path.isdir(path):
        directory = path
    else:
        return None
    for cand in _candidates(directory):
        if any(cand == t for t, _ in tried):
            continue
        ok, reason = verify_checkpoint(cand)
        if ok:
            if tried and verbose:
                skipped = ", ".join(
                    f"'{t}' ({r})" for t, r in tried
                )
                print(
                    f"=> resume fell back to '{cand}' — skipped corrupt "
                    f"checkpoint(s): {skipped}",
                    file=sys.stderr,
                )
            return cand
        tried.append((cand, reason))
    if tried and verbose:
        print(
            f"=> no verifiable checkpoint under '{directory}' — "
            + "; ".join(f"'{t}': {r}" for t, r in tried),
            file=sys.stderr,
        )
    return None


class CheckpointManager:
    """Rotated step-checkpoint writer (chief-only, like every other save).

    ``save_step`` writes ``checkpoint-e{epoch}-s{step}.pth.tar`` through
    the same atomic+fsync'd+CRC'd ``save_checkpoint`` path as boundary
    saves, runs the ``ckpt_truncate`` fault hook when a plan is armed,
    and prunes rotated files beyond ``keep`` (oldest first; the
    epoch-boundary ``checkpoint.pth.tar``/``model_best`` are never
    rotation victims).

    With an ``async_writer`` (dptpu.train.checkpoint
    .AsyncCheckpointWriter), cadence saves run entirely on the writer
    thread — device_get included — so ``--ckpt-steps`` stops stalling
    the step loop. ``sync=True`` (emergency/preemption saves) first
    drains the writer, then writes on the calling thread: the
    newest-mtime file the resume scanner trusts is always the true
    latest position, and a preempting process never exits before its
    final save is durable.
    """

    def __init__(self, directory: str = ".", keep: int = 3,
                 is_chief: bool = True, arch: str = "",
                 batch_size: Optional[int] = None, fault_plan=None,
                 async_writer=None, geometry=None, sharding: str = ""):
        if keep < 1:
            raise ValueError(f"ckpt keep={keep} must be >= 1")
        self.directory = directory
        self.keep = keep
        self.is_chief = is_chief
        self.arch = arch
        self.batch_size = batch_size
        self.fault_plan = fault_plan
        self.async_writer = async_writer
        # (world_size, global_batch, accum) stamped into every step
        # save so a changed-geometry --resume can name both tuples
        self.geometry = geometry
        # the run's sharding fingerprint ("<rules-hash>:<placement>" /
        # "replicated" — dptpu/train/plan.py computes it), stamped so a
        # --resume under a changed sharding config can name both
        # fingerprints
        self.sharding = sharding

    def save_step(self, state, *, epoch: int, step_in_epoch: int,
                  best_acc1: float = 0.0, sync: bool = False
                  ) -> Optional[str]:
        from dptpu import obs
        from dptpu.train.checkpoint import save_checkpoint

        if not self.is_chief:
            return None
        tracer = obs.get_tracer()
        # span labels use the 0-based index of the step whose completion
        # triggered the save (step_in_epoch counts steps CONSUMED) so
        # the attribution report's per-step join lines up with the
        # loop's data_wait/step/iter labels
        span_step = step_in_epoch - 1
        filename = step_checkpoint_name(epoch, step_in_epoch)
        from dptpu.data.store import is_store_url, open_store

        path = open_store(self.directory).path_for(filename)
        remote = is_store_url(path)
        run_async = self.async_writer is not None and not sync
        if run_async:
            import jax

            # the train step DONATES the old state's buffers to the next
            # step, so an enqueued snapshot must not reference them: take
            # device-side copies (async dispatch, ordered BEFORE the
            # donating step). The step loop still never blocks on a host
            # gather — the writer thread pays the device_get.
            state = jax.tree_util.tree_map(
                lambda x: x.copy() if hasattr(x, "copy") else x, state
            )

        # span naming decides attribution: "ckpt_write" marks work on
        # the WRITER thread (overlaps device compute → reported as
        # async, outside the wall budget); the same closure running
        # INLINE on a sync save stalls the step thread, so it records
        # as plain "ckpt" (nested in the outer ckpt span — exclusive
        # accounting keeps the sum exact)
        write_span = "ckpt_write" if run_async else "ckpt"

        def _write():
            with tracer.span(write_span, step=span_step) as span:
                save_checkpoint(
                    state,
                    epoch=epoch,
                    arch=self.arch,
                    best_acc1=best_acc1,
                    is_best=False,
                    directory=self.directory,
                    is_chief=True,
                    filename=filename,
                    step_in_epoch=step_in_epoch,
                    data_position=(
                        step_in_epoch * self.batch_size
                        if self.batch_size is not None else None
                    ),
                    geometry=self.geometry,
                    sharding=self.sharding,
                    report=span.attrs,
                )
                if self.fault_plan is not None and not remote:
                    # fault hooks (ckpt_truncate@save=N) count ACTUAL
                    # writes in write order, so they ride the writer
                    # thread too. ckpt_truncate tears the LOCAL file in
                    # place — a store URL has no file to tear, so the
                    # hook stands down there (never silently miscounts:
                    # the chaos benches always run against local dirs)
                    self.fault_plan.on_checkpoint_saved(path)
                self._rotate()

        if run_async:
            # submit may BLOCK on writer backpressure (max_pending):
            # that stall bills to the step thread, so span it
            with tracer.span("ckpt", step=span_step):
                self.async_writer.submit(_write)
            obs.get_registry().gauge("Obs/ckpt_queue_depth").set(
                self.async_writer.pending()
            )
            return path
        with tracer.span("ckpt", step=span_step):
            if self.async_writer is not None:
                # drain first: keep mtime order == save order (the
                # flush stall is recorded as a ckpt_flush span)
                self.async_writer.flush()
            _write()
        return path

    def flush(self):
        """Drain any queued async saves (no-op without a writer)."""
        if self.async_writer is not None:
            self.async_writer.flush()

    def _rotate(self):
        # prune by mtime (save order), NOT by (epoch, step): after a
        # corrupt-fallback resume an old torn higher-step file can still
        # sit in the directory, and position-ordering would keep it while
        # evicting the fresh valid saves — mtime matches find_resumable's
        # newest-first scan, so rotation and resume agree on "newest".
        # Listing + deletion go through the Store, so rotation works
        # identically against a --ckpt-dir store URL.
        from dptpu.data.store import open_store

        store = open_store(self.directory)
        files = []
        try:
            entries = store.list()
        except OSError:
            return
        for name, mtime in entries:
            m = STEP_CHECKPOINT_RE.match(name)
            if m:
                files.append((mtime, int(m.group(1)), int(m.group(2)), name))
        files.sort()  # oldest save first
        for _, _, _, name in files[: max(len(files) - self.keep, 0)]:
            try:
                store.delete(name)
            except OSError:
                pass
