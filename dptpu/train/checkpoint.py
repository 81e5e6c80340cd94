"""Checkpoint save / best-copy / resume with the reference's exact contract.

Payload mirrors the reference's dict {epoch, arch, state_dict, best_acc1,
optimizer} (imagenet_ddp.py:216-222), carried as a flax-serialized pytree:
{epoch, arch, params, batch_stats, opt_state, step, best_acc1, and
training_time when early-stop records it (imagenet_ddp.py:227-234)}.
Filenames match (``checkpoint.pth.tar`` → copy ``model_best.pth.tar`` when
best, imagenet_ddp.py:327-330); writes are single-writer (the
``rank % ngpus == 0`` guard, imagenet_ddp.py:215 — here ``process_index==0``)
and atomic (tmp + rename), which the reference is not. Unlike torch.load
there is no ``map_location`` dance: restored arrays are host numpy until the
next step's sharded ``device_put`` places them.

``--resume`` also accepts the REFERENCE'S OWN checkpoints
(imagenet_ddp.py:216-222: ``torch.save({epoch, arch, state_dict,
best_acc1, optimizer})`` with DDP's ``module.``-prefixed keys): a file
that is not a flax-serialized payload routes through the torchvision key
map (dptpu/models/pretrained.py) to restore params/batch_stats, and the
SGD ``momentum_buffer``s map onto the optax trace (same semantics:
both store ``buf`` with ``p -= lr·buf``), closing SURVEY §3.5 caveat
(d). The global step is rebuilt as ``epoch · steps_per_epoch`` so the
LR schedule resumes on the reference's epoch boundary.
"""

from __future__ import annotations

import queue
import struct
import threading
import time
import zlib
from typing import Optional

import jax
import msgpack
import numpy as np
from flax import serialization

from dptpu.models.pretrained import QKV_LAYOUT, qkv_needs_migration
from dptpu.train.state import map_momentum
from dptpu.utils.sync import OrderedLock

CHECKPOINT_NAME = "checkpoint.pth.tar"
BEST_NAME = "model_best.pth.tar"

# Content-checksum footer: ``payload || CRC_MAGIC || crc32(payload)``.
# Appended (not prepended) so pre-footer files and the reference's torch
# files keep loading unchanged; a truncated write loses the footer and a
# bit-flip fails the CRC — both are detected before flax ever parses.
CRC_MAGIC = b"DPTPUCRC"
_FOOTER_LEN = len(CRC_MAGIC) + 4


class EmptyCheckpointError(FileNotFoundError):
    """A checkpoint file that exists but holds zero bytes — the signature
    of a crash between ``open`` and the first write (or a power loss with
    no fsync). Derives from FileNotFoundError so warn-and-continue resume
    paths can treat 'empty' like 'absent'."""


class CorruptCheckpointError(ValueError):
    """Checkpoint bytes fail their content checksum or parse."""


def seal_payload(payload: bytes) -> bytes:
    """Append the CRC footer to serialized checkpoint bytes."""
    return payload + CRC_MAGIC + struct.pack(
        "<I", zlib.crc32(payload) & 0xFFFFFFFF
    )


def split_payload(raw: bytes, path: str = "<bytes>") -> tuple:
    """Strip + verify the CRC footer; returns ``(payload, verified)``.

    ``verified`` is False for pre-footer (legacy) files, which pass
    through untouched; a present-but-wrong CRC raises
    :class:`CorruptCheckpointError`.
    """
    if len(raw) >= _FOOTER_LEN and raw[-_FOOTER_LEN:-4] == CRC_MAGIC:
        payload, crc = raw[:-_FOOTER_LEN], raw[-4:]
        if struct.unpack("<I", crc)[0] != (zlib.crc32(payload) & 0xFFFFFFFF):
            raise CorruptCheckpointError(
                f"{path}: checkpoint content checksum mismatch — the file "
                f"is corrupt (bit rot or a partial overwrite)"
            )
        return payload, True
    return raw, False


# ``serialization.to_bytes`` leaf by leaf. flax packs an array as the
# msgpack extension ``ndarray`` (1) around ``packb((shape, dtype name,
# raw bytes))`` (a zero-rank numpy scalar as ``npscalar``, 3), an array
# over ``MAX_CHUNK_SIZE`` bytes as a dict of flat chunks, and makes three
# copies of every leaf on the way (``tobytes``, the inner ``packb``, the
# outer one). The same bytes are produced here as the few header bytes
# msgpack would write and then the array's own memory.
_EXT_NDARRAY, _EXT_COMPLEX, _EXT_NPSCALAR = 1, 2, 3
_FIXEXT = {1: 0xD4, 2: 0xD5, 4: 0xD6, 8: 0xD7, 16: 0xD8}


def _sized(length: int, codes: tuple) -> bytes:
    """msgpack's header of a ``bin`` or ``ext`` of ``length`` bytes: the
    code of the narrowest of the three widths (8, 16, 32 bits), then the
    length, big-endian."""
    for code, fmt, limit in zip(codes, (">B", ">H", ">I"),
                                (0xFF, 0xFFFF, 0xFFFFFFFF)):
        if length <= limit:
            return bytes([code]) + struct.pack(fmt, length)
    raise ValueError(f"{length} bytes do not fit one msgpack object")


def _ext_header(code: int, length: int) -> bytes:
    if length in _FIXEXT:
        return bytes([_FIXEXT[length], code])
    return _sized(length, (0xC7, 0xC8, 0xC9)) + bytes([code])


def _array_pieces(array, code: int = _EXT_NDARRAY):
    """``_msgpack_ext_pack(array)`` as pieces: the headers, then the
    array's memory itself (a copy only of an array that is not
    C-contiguous)."""
    if array.dtype.hasobject or array.dtype.isalignedstruct:
        raise ValueError("Object and structured dtypes not supported "
                         "for serialization of ndarrays.")
    if not array.flags.c_contiguous:
        array = np.array(array, order="C")
    raw = memoryview(array.reshape(-1).view(np.uint8))
    inner = b"\x93" + msgpack.packb(array.shape) \
        + msgpack.packb(array.dtype.name) \
        + _sized(raw.nbytes, (0xC4, 0xC5, 0xC6))
    yield _ext_header(code, len(inner) + raw.nbytes) + inner
    if raw.nbytes:
        yield raw


def _chunked(array) -> dict:
    """flax's ``_chunk``: an oversized array as a dict of flat chunks
    (views, not copies)."""
    per_chunk = max(1, int(serialization.MAX_CHUNK_SIZE
                           / array.dtype.itemsize))
    flat = array.reshape(-1)
    as_dict = lambda items: {str(i): v for i, v in enumerate(items)}  # noqa: E731
    return {"__msgpack_chunked_array__": True,
            "shape": as_dict(array.shape),
            "chunks": as_dict(flat[i:i + per_chunk]
                              for i in range(0, flat.size, per_chunk))}


def state_dict_pieces(tree):
    """The bytes of ``serialization.msgpack_serialize(tree)`` (``tree``: a
    state dict, as ``serialization.to_state_dict`` gives it) as a stream
    of pieces, in order; an array leaf is yielded as a view of its own
    memory."""
    if isinstance(tree, dict):
        yield msgpack.Packer().pack_map_header(len(tree))
        for key, value in tree.items():
            yield msgpack.packb(key, strict_types=True)
            yield from state_dict_pieces(value)
    elif isinstance(tree, (np.ndarray, jax.Array)):
        array = np.asarray(tree)
        if array.nbytes > serialization.MAX_CHUNK_SIZE:
            yield from state_dict_pieces(_chunked(array))
        else:
            yield from _array_pieces(array)
    elif isinstance(tree, np.generic):
        yield from _array_pieces(np.asarray(tree), _EXT_NPSCALAR)
    elif isinstance(tree, complex):
        packed = msgpack.packb((tree.real, tree.imag))
        yield _ext_header(_EXT_COMPLEX, len(packed)) + packed
    else:
        yield msgpack.packb(tree, strict_types=True)


def stream_sealed(payload, write) -> tuple:
    """``write`` every piece of ``seal_payload(serialization.to_bytes(
    payload))``, in order, without ever holding those bytes: the
    serialized leaves one at a time under a running CRC, then the footer.
    Returns ``(bytes written, seconds spent encoding and summing)``: the
    rest of the caller's time went into ``write``."""
    crc, total, spent = 0, 0, 0.0
    t = time.perf_counter()
    for piece in state_dict_pieces(serialization.to_state_dict(payload)):
        crc = zlib.crc32(piece, crc)
        total += len(piece)
        spent += time.perf_counter() - t
        write(piece)
        t = time.perf_counter()
    write(CRC_MAGIC + struct.pack("<I", crc & 0xFFFFFFFF))
    return total + _FOOTER_LEN, spent


class AsyncCheckpointWriter:
    """One background thread that performs whole checkpoint saves —
    device_get + serialize + CRC + fsync + rename — off the step thread.

    ``--ckpt-steps`` at small N used to cost a device_get stall per save
    (the gather drains the dispatch queue and the step loop eats the
    ~100 ms refill, PERF.md); submitting the save here lets the step
    loop keep dispatching while the writer thread blocks on the gather.
    JAX arrays are immutable values, so the enqueued state is a
    consistent snapshot no matter how far the step thread races ahead.

    Guarantees:

    * FIFO — saves land in submission order (one thread, one queue);
    * bounded memory — at most ``max_pending`` snapshots queued
      (``submit`` blocks beyond that: backpressure, not OOM);
    * error surfacing — a failed write re-raises on the NEXT
      ``submit``/``flush``/``close``, never silently;
    * ``flush()`` drains the queue — emergency/preemption saves call it
      first and then write SYNCHRONOUSLY, so the newest-mtime file the
      resume scanner picks is always the true latest position.
    """

    def __init__(self, max_pending: int = 2):
        self._q: "queue.Queue" = queue.Queue(maxsize=max_pending)
        self._lock = OrderedLock("train.ckpt_writer")
        self._exc: Optional[BaseException] = None  # guarded-by: _lock
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="dptpu-ckpt-writer"
        )
        self._thread.start()

    def _run(self):
        while True:
            fn = self._q.get()
            try:
                if fn is None:
                    return
                fn()
            except BaseException as e:  # surfaced on the next call-in
                with self._lock:
                    self._exc = e
            finally:
                self._q.task_done()

    def _raise_pending(self):
        with self._lock:
            exc, self._exc = self._exc, None
        if exc is not None:
            raise RuntimeError(
                "async checkpoint write failed (surfacing on the next "
                "checkpoint call — the failed file never replaced a "
                "good one: writes are tmp+rename)"
            ) from exc

    def submit(self, fn) -> None:
        """Enqueue one save closure; blocks when ``max_pending`` saves
        are already in flight (bounded snapshot memory)."""
        self._raise_pending()
        if not self._thread.is_alive():
            raise RuntimeError("AsyncCheckpointWriter is closed")
        self._q.put(fn)

    def pending(self) -> int:
        """Queued-but-unwritten saves (approximate; the observability
        layer publishes this as ``Obs/ckpt_queue_depth`` — a depth that
        sits at ``max_pending`` means the step loop is blocking on
        checkpoint backpressure)."""
        return self._q.qsize()

    def flush(self) -> None:
        """Block until every queued save has hit disk. The wait is
        recorded as a ``ckpt_flush`` span — this is exactly the stall a
        preemption/emergency save pays before its synchronous write."""
        from dptpu import obs

        with obs.get_tracer().span("ckpt_flush"):
            self._q.join()
        self._raise_pending()

    def close(self) -> None:
        """Drain, stop the thread, surface any pending error."""
        if self._thread.is_alive():
            self._q.put(None)
            self._thread.join()
        self._raise_pending()


def save_checkpoint(
    state,
    *,
    epoch: int,
    arch: str,
    best_acc1: float,
    is_best: bool,
    directory: str = ".",
    is_chief: bool = True,
    training_time: Optional[float] = None,
    filename: str = CHECKPOINT_NAME,
    step_in_epoch: int = 0,
    data_position: Optional[int] = None,
    geometry: Optional[tuple] = None,
    sharding: str = "",
    report: Optional[dict] = None,
) -> Optional[str]:
    """Serialize state; copy to model_best when ``is_best``. Chief-only.

    The file's bytes are ``seal_payload(serialization.to_bytes(payload))``
    and are never held: the state is fetched to the host once and goes
    into the store's temporary file leaf by leaf under a running CRC
    (``stream_sealed``; ``Store.put_stream``), so a save holds the
    fetched state and no second or third copy of it. ``report``, a dict
    (a ``ckpt`` span's ``attrs``), is given the ``bytes`` written and the
    seconds of the save's three parts: ``fetch_s`` (device to host),
    ``encode_s`` (headers and checksum), ``store_s`` (write, sync,
    rename).

    ``step_in_epoch``/``data_position`` are the mid-epoch resume
    coordinates (dptpu/resilience): batches already consumed from epoch
    ``epoch`` and samples consumed per shard. 0 means an epoch boundary
    (the reference's only save point, imagenet_ddp.py:216-222).

    ``geometry`` is the run's ``(world_size, global_batch, accum)``
    tuple. Saving it lets a mid-epoch ``--resume`` under a CHANGED
    batch geometry fail fast naming both the saved and current tuples
    (the groundwork for elastic resume, ROADMAP item 3b: a remapper
    needs exactly these coordinates) instead of a bare mismatch.

    ``sharding`` is the run's sharding fingerprint —
    ``"<rules-table-hash>:zero<stage>"`` for the rules-driven sharded
    families (dptpu/parallel/rules.py), ``"replicated"`` for the
    replicated steps, ``""`` for contexts with no placement to stamp.
    A mid-epoch ``--resume`` under a CHANGED sharding fails fast naming
    both fingerprints (fit.py) unless DPTPU_ELASTIC opts into
    re-sharding; epoch-boundary resumes re-shard freely (checkpoints
    always hold the gathered full-leaf state, so the stamp is
    provenance, not a storage format).
    """
    if not is_chief:
        return None
    geom = tuple(int(g) for g in geometry) if geometry is not None \
        else (-1, -1, -1)
    t_fetch = time.perf_counter()
    payload = {
        "epoch": epoch,
        "arch": arch,
        "best_acc1": float(best_acc1),
        "step": jax.device_get(state.step),
        "params": jax.device_get(state.params),
        "batch_stats": jax.device_get(state.batch_stats),
        "opt_state": jax.device_get(state.opt_state),
        "training_time": -1.0 if training_time is None else float(training_time),
        # attention-storage layout marker: lets a future layout change
        # (like round 4's [q|k|v]-major -> head-major move) detect and
        # migrate old files instead of silently scrambling them
        "qkv_layout": QKV_LAYOUT,
        "step_in_epoch": int(step_in_epoch),
        "data_position": int(
            data_position if data_position is not None else -1
        ),
        "world_size": geom[0],
        "global_batch": geom[1],
        "accum_steps": geom[2],
        "sharding": str(sharding),
    }
    # EVERY checkpoint write goes through the Store abstraction
    # (dptpu/data/store.py): a plain directory routes to LocalStore —
    # whose put_stream is the exact tmp+flush+fsync+rename+dirent-fsync
    # discipline this function used to inline, bit-for-bit — and a
    # store URL (--ckpt-dir file:///... or http(s)://...) routes to the
    # matching backend with retry/backoff. The CRC footer is the last
    # piece the store is handed, so the verify/fallback contract is
    # backend-independent.
    from dptpu.data.store import open_store

    t_store = time.perf_counter()
    done = {}

    def produce(write):  # again from the start if the store retries
        done["bytes"], done["encode_s"] = stream_sealed(payload, write)

    store = open_store(directory or ".")
    store.put_stream(filename, produce)
    if report is not None:
        report.update(
            bytes=done["bytes"], fetch_s=t_store - t_fetch,
            encode_s=done["encode_s"],
            store_s=time.perf_counter() - t_store - done["encode_s"])
    if is_best:
        store.copy(filename, BEST_NAME)
    return store.path_for(filename)


def load_checkpoint(path: str, state, arch: Optional[str] = None,
                    steps_per_epoch: Optional[int] = None):
    """Resume: restore state + bookkeeping from a checkpoint file.

    The reference restores start_epoch/best_acc1/model/optimizer
    (imagenet_ddp.py:138-153). Returns ``(state, meta)`` where meta has
    ``epoch`` (resume start epoch), ``arch``, ``best_acc1``.

    Accepts dptpu's flax-serialized payload OR a reference-produced
    ``torch.save`` checkpoint (detected by failed flax deserialization;
    see module docstring). ``arch`` names the key map for the torch
    path (the checkpoint's own ``arch`` field wins when present);
    ``steps_per_epoch`` rebuilds the global step from the torch
    checkpoint's epoch, which stores no step count.
    """
    from dptpu.data.store import is_store_url, open_store, split_store_url

    if is_store_url(path):
        base, name = split_store_url(path)
        raw = open_store(base).get_bytes(name)
    else:
        with open(path, "rb") as f:
            raw = f.read()
    if not raw:
        raise EmptyCheckpointError(
            f"{path}: checkpoint file is empty (0 bytes) — a crashed or "
            f"power-lost write; resume from an older checkpoint (the "
            f"resilience scanner, dptpu.resilience.find_resumable, does "
            f"this automatically)"
        )
    # dispatch on the file's magic, not on a failed parse: a torch file is
    # a zip (PK..) or legacy pickle (protocol-2 \x80 prefix); anything
    # else goes to flax so a genuinely corrupt/mismatched flax payload
    # surfaces its own precise error instead of an unpickling one (and
    # the torch path never pays for building the flax template)
    if raw[:4] == b"PK\x03\x04" or raw[:2] == b"\x80\x02":
        return _load_torch_checkpoint(path, state, arch, steps_per_epoch,
                                      raw=raw)
    raw, _verified = split_payload(raw, path)
    template = {
        "epoch": 0,
        "arch": "",
        "best_acc1": 0.0,
        "step": jax.device_get(state.step),
        "params": jax.device_get(state.params),
        "batch_stats": jax.device_get(state.batch_stats),
        "opt_state": jax.device_get(state.opt_state),
        "training_time": -1.0,
        "qkv_layout": "",
        "step_in_epoch": 0,
        "data_position": -1,
        "world_size": -1,
        "global_batch": -1,
        "accum_steps": -1,
        "sharding": "",
    }
    # Optional bookkeeping fields, defaulted when absent so every older
    # payload generation parses: pre-round-4 files lack qkv_layout (and
    # get the ViT attention-column migration below), pre-resilience files
    # lack the mid-epoch resume coordinates, pre-hierarchy files lack
    # the (world_size, global_batch, accum) geometry tuple.
    _OPTIONAL = ("qkv_layout", "step_in_epoch", "data_position",
                 "world_size", "global_batch", "accum_steps", "sharding")
    # structural legacy detection, single decode: restore the msgpack
    # tree once (raises its precise error on a corrupt file), pick the
    # template by the payload's own top-level keys, and validate with
    # from_state_dict (from_bytes is exactly restore + from_state_dict).
    restored = serialization.msgpack_restore(raw)
    if not isinstance(restored, dict):
        raise CorruptCheckpointError(
            f"{path}: checkpoint payload is {type(restored).__name__}, "
            "not a dict — corrupt or not a dptpu checkpoint"
        )
    present = {
        k: v for k, v in template.items()
        if k not in _OPTIONAL or k in restored
    }
    payload = serialization.from_state_dict(present, restored)
    for k in _OPTIONAL:
        payload.setdefault(k, template[k])
    params = payload["params"]
    opt_state = payload["opt_state"]
    ckpt_arch = payload["arch"] or arch or ""
    if qkv_needs_migration(ckpt_arch, payload["qkv_layout"]):
        from dptpu.models.pretrained import _qkv_to_head_major

        params = _qkv_to_head_major(ckpt_arch, params)
        opt_state = map_momentum(
            opt_state, lambda t: _qkv_to_head_major(ckpt_arch, t)
        )
    new_state = state.replace(
        step=payload["step"],
        params=params,
        batch_stats=payload["batch_stats"],
        opt_state=opt_state,
    )
    meta = {
        "epoch": int(payload["epoch"]),
        "arch": payload["arch"],
        "best_acc1": float(payload["best_acc1"]),
        "training_time": float(payload["training_time"]),
        "step_in_epoch": int(payload["step_in_epoch"]),
        "data_position": int(payload["data_position"]),
        # (world_size, global_batch, accum) at save time; (-1,-1,-1)
        # for pre-hierarchy files (resume then falls back to the
        # data_position cross-check)
        "geometry": (int(payload["world_size"]),
                     int(payload["global_batch"]),
                     int(payload["accum_steps"])),
        # sharding fingerprint at save time; "" for files from before
        # the rules engine (resume then skips the sharding cross-check)
        "sharding": str(payload["sharding"]),
    }
    return new_state, meta


def _load_torch_checkpoint(path: str, state, arch: Optional[str],
                           steps_per_epoch: Optional[int],
                           raw: Optional[bytes] = None):
    """Resume from the reference's own ``torch.save`` checkpoint
    (imagenet_ddp.py:216-222): ``module.``-prefixed state dict through
    the torchvision key map, SGD momentum buffers onto the optax trace.
    ``raw`` carries already-fetched bytes (store-URL resumes have no
    local file for torch to open)."""
    import io

    import numpy as np
    import torch

    from dptpu.models.pretrained import (
        _from_torch,
        convert_state_dict,
        torch_key_map,
    )

    ckpt = torch.load(
        io.BytesIO(raw) if raw is not None else path,
        map_location="cpu", weights_only=False,
    )
    arch = str(ckpt.get("arch") or arch or "")
    if not arch:
        raise ValueError(
            f"{path}: torch-format checkpoint carries no 'arch' and none "
            "was passed — cannot build the key map"
        )
    raw_sd = ckpt["state_dict"]
    template = {
        "params": jax.device_get(state.params),
        "batch_stats": jax.device_get(state.batch_stats),
    }
    kmap = torch_key_map(arch, template)
    sd = {}
    # torch parameters() order == state-dict key order restricted to
    # keys the map resolves into the 'params' collection — this excludes
    # EVERY registered buffer generically (BN running stats/bookkeeping,
    # Swin's relative_position_index/attn_mask, ...), not just the BN
    # suffixes, so the param-index mapping below cannot desync on archs
    # with exotic buffers
    param_keys = []
    for k, v in raw_sd.items():
        k = k[len("module."):] if k.startswith("module.") else k
        if k.endswith("num_batches_tracked"):
            continue  # torch BN bookkeeping; no dptpu equivalent
        sd[k] = v.detach().cpu().numpy()
        if k in kmap and kmap[k][0] == "params":
            param_keys.append(k)
    variables = convert_state_dict(arch, sd, template, kmap=kmap)

    # SGD momentum: torch keys state entries by global param index in
    # param_groups order — identical to parameters() order (param_keys)
    opt_sd = ckpt.get("optimizer") or {}
    indices = [
        i for g in opt_sd.get("param_groups", []) for i in g["params"]
    ]
    if indices and len(indices) != len(param_keys):
        # a silent skip here would partially restore momentum after a
        # desync; refuse loudly instead
        raise ValueError(
            f"{path}: torch optimizer tracks {len(indices)} params but "
            f"the key map resolves {len(param_keys)} trainable keys for "
            f"'{arch}' — the param-index mapping would desync, so "
            f"momentum cannot be restored safely"
        )
    torch_state = opt_sd.get("state", {})
    buffers = {}
    for pos, idx in enumerate(indices):
        buf = torch_state.get(idx, {}).get("momentum_buffer")
        if buf is None:
            continue  # torch SGD momentum starts lazily per-param
        collection, names, kind = kmap[param_keys[pos]]
        buffers[names] = _from_torch(
            buf.detach().cpu().numpy(), kind
        ).astype(np.float32)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        variables["params"]
    )
    trace_leaves = []
    for leaf_path, leaf in flat:
        names = tuple(p.key for p in leaf_path)
        buf = buffers.get(names)
        if buf is not None and buf.shape != leaf.shape:
            raise ValueError(
                f"momentum buffer for {'.'.join(names)}: shape "
                f"{buf.shape} != param {leaf.shape}"
            )
        trace_leaves.append(
            np.zeros_like(leaf) if buf is None else buf
        )
    new_trace = jax.tree_util.tree_unflatten(treedef, trace_leaves)

    epoch = int(ckpt.get("epoch", 0))
    step = jax.device_get(state.step)
    if steps_per_epoch is not None:
        step = np.asarray(epoch * int(steps_per_epoch), dtype=step.dtype)
    new_state = state.replace(
        step=step,
        params=variables["params"],
        batch_stats=variables["batch_stats"],
        opt_state=map_momentum(
            jax.device_get(state.opt_state), lambda _: new_trace
        ),
    )
    meta = {
        "epoch": epoch,
        "arch": arch,
        "best_acc1": float(ckpt.get("best_acc1", 0.0)),
        "training_time": float(ckpt.get("training_time", -1.0)),
        # the reference only saves on epoch boundaries
        "step_in_epoch": 0,
        "data_position": -1,
        "geometry": (-1, -1, -1),
        "sharding": "",
    }
    return new_state, meta
