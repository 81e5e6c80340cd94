"""Byte-store abstraction: local files and HTTP range fetch, one API.

Production datasets (and checkpoints) live in object stores, not on the
training host's disk. ``Store`` is the minimal byte-addressed interface
the data plane needs — whole objects, byte ranges, durable puts, listing
— with two backends:

* :class:`LocalStore` — a directory. ``put_bytes`` is the checkpoint
  writer's exact durability discipline (tmp + flush + fsync + atomic
  rename + best-effort directory fsync), hoisted here so checkpoint
  writes "through the store" stay bit-for-bit what they were.
* :class:`HTTPStore` — an HTTP(S) prefix. ``get_range`` issues RFC 7233
  ``Range:`` requests (the object-store read primitive); ``put_bytes``/
  ``delete`` map to PUT/DELETE, ``list`` to a JSON directory GET (the
  bundled dev server speaks all four; S3/GCS adapters are a follow-on —
  the interface is the contract).

RETRY/BACKOFF is the store's job, not the caller's: every remote op runs
under ``_io`` — up to ``DPTPU_STORE_RETRIES`` retries with exponential
backoff from ``DPTPU_STORE_BACKOFF_S`` — because a transient fetch error
mid-epoch must cost milliseconds, not the run. The ``DPTPU_FAULT
io_error:p=F`` chaos spec injects ``OSError`` into store ops through the
same hook the decode workers use (:meth:`FaultPlan.on_store_io`), so
FAULTBENCH can prove a fault-injected range fetch retries to a
bit-identical run. Non-retryable outcomes (404 → ``FileNotFoundError``)
fail immediately. Counters (``retries``, ``wait_s``, ``bytes_fetched``)
feed the loader's ``feed_stats`` → ``Feed/store_*`` metrics.

This module is imported inside spawned decode workers: stdlib + numpy
only, never JAX. Stores pickle by spec (root/URL + knobs), never by
handle — each process re-opens its own connections.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
import urllib.error
import urllib.request
from typing import List, Optional, Tuple

import numpy as np

from dptpu.envknob import env_float, env_int, env_str
from dptpu.utils.sync import OrderedLock

_SCHEMES = ("http://", "https://", "file://")


def is_store_url(path: str) -> bool:
    """True when ``path`` is a store URL rather than a plain filesystem
    path (``http://``/``https://``/``file://``)."""
    return isinstance(path, str) and path.startswith(_SCHEMES)


def open_store(location: str) -> "Store":
    """A :class:`Store` rooted at ``location``: HTTP(S) URLs get an
    :class:`HTTPStore`, ``file://`` and plain paths a :class:`LocalStore`."""
    if location.startswith(("http://", "https://")):
        return HTTPStore(location)
    if location.startswith("file://"):
        return LocalStore(location[len("file://"):])
    return LocalStore(location)


def split_store_url(url: str) -> Tuple[str, str]:
    """Split a store URL naming one OBJECT into ``(base, name)`` — the
    store root and the object's name inside it."""
    base, _, name = url.rstrip("/").rpartition("/")
    return base, name


class StoreError(OSError):
    """A store operation failed after exhausting its retry budget."""


# ONE fault plan (and thus ONE advancing injection rng) per process,
# shared by every Store instance: checkpoint paths build a fresh Store
# per operation, and a per-instance plan would re-seed the rng each
# time — every op would replay the identical draw sequence, turning
# "transient with probability p" into deterministic all-or-nothing
# (a p=0.6 spec would kill EVERY save despite retries). Keyed by the
# (spec, seed) env pair so chaos benches that re-scope DPTPU_FAULT
# between runs get a fresh plan.
_FAULT_CACHE = {"key": None, "plan": None}


def _shared_fault_plan():
    key = (env_str("DPTPU_FAULT", ""), env_str("DPTPU_FAULT_SEED", ""))
    if _FAULT_CACHE["key"] != key:
        from dptpu.resilience.faults import FaultPlan

        try:
            plan = FaultPlan.from_env()
        except ValueError:
            plan = None  # the trainer raises the parse error loudly
        _FAULT_CACHE["key"] = key
        _FAULT_CACHE["plan"] = plan
    return _FAULT_CACHE["plan"]


class Store:
    """Byte-store interface + the shared retry/backoff/fault-injection
    engine. Subclasses implement the raw ``_get_range``/``_get_bytes``/
    ``_size``/``_put_bytes``/``_copy``/``_delete``/``_list`` primitives;
    every public op runs them under :meth:`_io`."""

    scheme = "abstract"

    def __init__(self, retries: Optional[int] = None,
                 backoff_s: Optional[float] = None):
        self.retries = (
            retries if retries is not None
            else env_int("DPTPU_STORE_RETRIES", 3)
        )
        self.backoff_s = (
            backoff_s if backoff_s is not None
            else env_float("DPTPU_STORE_BACKOFF_S", 0.05)
        )
        if self.retries < 0:
            raise ValueError(
                f"DPTPU_STORE_RETRIES={self.retries} must be >= 0 retries"
            )
        if self.backoff_s < 0:
            raise ValueError(
                f"DPTPU_STORE_BACKOFF_S={self.backoff_s} must be >= 0 "
                f"seconds"
            )
        # telemetry (per-process; the loader aggregates into feed_stats)
        # — fetched from the parent's prefetcher thread AND the
        # consumer's decode path concurrently
        self.retry_count = 0  # guarded-by: _lock
        self.wait_s = 0.0  # guarded-by: _lock
        self.bytes_fetched = 0  # guarded-by: _lock
        self._lock = OrderedLock("data.store")

    # -- retry engine -------------------------------------------------------

    def _plan(self):
        """The process-shared DPTPU_FAULT plan (workers re-parse the
        inherited env — same discipline as dptpu/data/shm.py's decode
        workers; shared across Store instances so the injection rng
        ADVANCES, see _shared_fault_plan)."""
        return _shared_fault_plan()

    def _io(self, desc: str, fn):
        """Run one store primitive under retry/backoff + fault injection.
        ``FileNotFoundError`` is never retried (absence is an answer, not
        a fault); any other ``OSError`` — including the injected ones —
        burns one attempt and backs off exponentially."""
        t0 = time.monotonic()
        delay = self.backoff_s
        try:
            for attempt in range(self.retries + 1):
                try:
                    plan = self._plan()
                    if plan is not None:
                        plan.on_store_io(desc)
                    return fn()
                except FileNotFoundError:
                    raise
                except (OSError, urllib.error.URLError) as e:
                    if attempt >= self.retries:
                        raise StoreError(
                            f"store op {desc!r} failed after "
                            f"{attempt + 1} attempt(s): {e}"
                        ) from e
                    with self._lock:
                        self.retry_count += 1
                    time.sleep(delay)
                    delay *= 2
        finally:
            with self._lock:
                self.wait_s += time.monotonic() - t0

    # -- public API ---------------------------------------------------------

    def get_bytes(self, name: str) -> bytes:
        data = self._io(f"get {name}", lambda: self._get_bytes(name))
        with self._lock:
            self.bytes_fetched += len(data)
        return data

    def get_range(self, name: str, offset: int, length: int) -> bytes:
        data = self._io(
            f"get_range {name}[{offset}:{offset + length}]",
            lambda: self._get_range(name, offset, length),
        )
        with self._lock:
            self.bytes_fetched += len(data)
        return data

    def size(self, name: str) -> int:
        return self._io(f"size {name}", lambda: self._size(name))

    def put_bytes(self, name: str, data: bytes) -> None:
        self._io(f"put {name}", lambda: self._put_bytes(name, data))

    def put_stream(self, name: str, produce) -> None:
        """``put_bytes`` of an object that is never held whole:
        ``produce(write)`` hands its bytes to ``write`` piece by piece
        (anything with a buffer: ``bytes``, a ``memoryview`` of an array),
        in order. A retry calls ``produce`` again from the start, so it
        has to give the same bytes each time. A backend without a
        streaming write gathers the pieces and puts them at once."""
        self._io(f"put {name}", lambda: self._put_stream(name, produce))

    def _put_stream(self, name: str, produce) -> None:
        pieces = []
        produce(pieces.append)
        self._put_bytes(name, b"".join(pieces))

    def copy(self, src: str, dst: str) -> None:
        self._io(f"copy {src} -> {dst}", lambda: self._copy(src, dst))

    def delete(self, name: str) -> None:
        self._io(f"delete {name}", lambda: self._delete(name))

    def list(self) -> List[Tuple[str, float]]:
        """``[(name, mtime), ...]`` of the objects under the root."""
        return self._io("list", self._list)

    def stats(self) -> dict:
        with self._lock:
            return {
                "store_scheme": self.scheme,
                "store_retries": self.retry_count,
                "store_wait_s": self.wait_s,
                "store_bytes_fetched": self.bytes_fetched,
            }

    def path_for(self, name: str) -> str:
        raise NotImplementedError


class LocalStore(Store):
    """A directory as a store. Reads are plain (p)reads; ``put_bytes``
    is the atomic+durable checkpoint write discipline."""

    scheme = "file"

    def __init__(self, root: str, retries: Optional[int] = None,
                 backoff_s: Optional[float] = None):
        super().__init__(retries=retries, backoff_s=backoff_s)
        self.root = root

    def __reduce__(self):
        return (LocalStore, (self.root, self.retries, self.backoff_s))

    def path_for(self, name: str) -> str:
        return os.path.join(self.root, name)

    def _get_bytes(self, name: str) -> bytes:
        with open(self.path_for(name), "rb") as f:
            return f.read()

    def _get_range(self, name: str, offset: int, length: int) -> bytes:
        with open(self.path_for(name), "rb") as f:
            return os.pread(f.fileno(), length, offset)

    def _size(self, name: str) -> int:
        return os.path.getsize(self.path_for(name))

    def _put_bytes(self, name: str, data: bytes) -> None:
        self._put_stream(name, lambda write: write(data))

    def _put_stream(self, name: str, produce) -> None:
        # the checkpoint writer's durability discipline, verbatim
        # (dptpu/train/checkpoint.py): tmp + flush + fsync + atomic
        # rename + best-effort dirent fsync — a power loss can yield the
        # old object or the new one, never a torn mix. The pieces go
        # straight into the temporary file: the object is never held
        # whole beside what it was made from
        os.makedirs(self.root, exist_ok=True)
        path = self.path_for(name)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            produce(f.write)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        try:
            dirfd = os.open(os.path.dirname(path) or ".", os.O_RDONLY)
            try:
                os.fsync(dirfd)
            finally:
                os.close(dirfd)
        except OSError:
            pass  # filesystems/platforms that refuse directory fds

    def _copy(self, src: str, dst: str) -> None:
        shutil.copyfile(self.path_for(src), self.path_for(dst))

    def _delete(self, name: str) -> None:
        os.remove(self.path_for(name))

    def _list(self) -> List[Tuple[str, float]]:
        out = []
        try:
            names = os.listdir(self.root)
        except OSError:
            return out
        for n in names:
            try:
                out.append((n, os.path.getmtime(self.path_for(n))))
            except OSError:
                continue
        return out


class HTTPStore(Store):
    """An HTTP(S) prefix as a store: ``Range:`` GETs for extents, PUT /
    DELETE for checkpoint writes, a JSON directory GET for listing. 404
    maps to ``FileNotFoundError`` (never retried); connection errors and
    5xx retry under the shared backoff."""

    scheme = "http"

    def __init__(self, base_url: str, retries: Optional[int] = None,
                 backoff_s: Optional[float] = None,
                 timeout_s: float = 30.0):
        super().__init__(retries=retries, backoff_s=backoff_s)
        self.base_url = base_url.rstrip("/")
        self.timeout_s = timeout_s
        self._range_unsupported = False  # guarded-by: _lock

    def __reduce__(self):
        return (HTTPStore,
                (self.base_url, self.retries, self.backoff_s,
                 self.timeout_s))

    def path_for(self, name: str) -> str:
        return f"{self.base_url}/{name}"

    def _request(self, name: str, method: str = "GET", headers=None,
                 data: Optional[bytes] = None) -> bytes:
        req = urllib.request.Request(
            self.path_for(name), method=method, data=data,
            headers=headers or {},
        )
        try:
            with urllib.request.urlopen(req, timeout=self.timeout_s) as r:
                return r.read()
        except urllib.error.HTTPError as e:
            if e.code == 404:
                raise FileNotFoundError(
                    f"{self.path_for(name)}: HTTP 404"
                ) from e
            raise OSError(
                f"{self.path_for(name)}: HTTP {e.code} {e.reason}"
            ) from e

    def _get_bytes(self, name: str) -> bytes:
        return self._request(name)

    def _get_range(self, name: str, offset: int, length: int) -> bytes:
        data = self._request(
            name, headers={"Range": f"bytes={offset}-{offset + length - 1}"}
        )
        if len(data) > length:  # server ignored Range: slice locally
            # account the WASTE (the public wrapper adds only the slice
            # length) and warn once — a rangeless server turns every
            # extent read into a whole-object download, and telemetry
            # must show that, not hide it
            with self._lock:
                self.bytes_fetched += len(data) - length
                if not self._range_unsupported:
                    self._range_unsupported = True
                    import sys

                    print(
                        f"WARNING: dptpu store {self.base_url} ignored a "
                        f"Range request ({len(data)} bytes returned for a "
                        f"{length}-byte extent) — every extent read now "
                        f"downloads the whole object; prefer "
                        f"DPTPU_STORE_FETCH=shard or a range-capable "
                        f"store",
                        file=sys.stderr,
                    )
            data = data[offset:offset + length]
        return data

    def _size(self, name: str) -> int:
        req = urllib.request.Request(self.path_for(name), method="HEAD")
        try:
            with urllib.request.urlopen(req, timeout=self.timeout_s) as r:
                return int(r.headers.get("Content-Length", 0))
        except urllib.error.HTTPError as e:
            if e.code == 404:
                raise FileNotFoundError(
                    f"{self.path_for(name)}: HTTP 404"
                ) from e
            raise OSError(
                f"{self.path_for(name)}: HTTP {e.code} {e.reason}"
            ) from e

    def _put_bytes(self, name: str, data: bytes) -> None:
        self._request(name, method="PUT", data=data,
                      headers={"Content-Length": str(len(data))})

    def _copy(self, src: str, dst: str) -> None:
        self._put_bytes(dst, self._get_bytes(src))

    def _delete(self, name: str) -> None:
        self._request(name, method="DELETE")

    def stats(self) -> dict:
        s = super().stats()
        with self._lock:
            range_unsupported = self._range_unsupported
        if range_unsupported:
            s["store_range_unsupported"] = True
        return s

    def _list(self) -> List[Tuple[str, float]]:
        raw = self._request("")
        try:
            entries = json.loads(raw.decode("utf-8"))
        except (ValueError, UnicodeDecodeError) as e:
            raise OSError(
                f"{self.base_url}/: listing is not the JSON index this "
                f"store expects (a generic object store needs a list "
                f"adapter): {e}"
            ) from e
        return [(e["name"], float(e.get("mtime", 0.0))) for e in entries]


# ---- pooled shard byte cache ----------------------------------------------


class ShardByteCache:
    """The pooled /dev/shm slab (dptpu/data/shm_cache.py) reused as a
    SHARD BYTE cache: raw JPEG/PNG extents, fetched once by the parent's
    prefetcher (O_DIRECT ring or store range fetch), hit by every decode
    worker. Segments are named ``dptpu_shard_*`` so the conftest
    /dev/shm leak guard can police them separately from the decoded-
    pixel slabs.

    The slab stores uint8 HWC arrays; byte payloads ride as
    ``(ceil(n/3), 1, 3)`` views with the real length carried by the
    caller (the shard index knows every extent's exact size). Same
    budget/eviction/crash-recovery semantics as the decode cache —
    including surviving worker pool restarts warm.
    """

    def __init__(self, budget_bytes: int):
        from dptpu.data.shm_cache import ShmDecodeCache

        self._cache = ShmDecodeCache(
            budget_bytes, segment_prefix="dptpu_shard"
        )

    def contains(self, key) -> bool:
        """Staged-already check without copying the payload out."""
        return self._cache.contains(key)

    def get(self, key, length: int) -> Optional[bytes]:
        arr = self._cache.get(key)
        if arr is None:
            return None
        flat = arr.reshape(-1)
        if flat.size < length:
            return None  # torn/foreign entry: treat as a miss
        return flat[:length].tobytes()

    def put(self, key, data: bytes) -> bool:
        n = len(data)
        pad = (-n) % 3
        arr = np.frombuffer(data + b"\x00" * pad, np.uint8)
        return self._cache.put(key, arr.reshape(-1, 1, 3))

    def stats(self) -> dict:
        # slab-level keys are namespaced shard_slab_* so they can never
        # clobber the ENGINE-level shard_cache_hits/misses (sample-level
        # staging effectiveness) in io_stats
        s = self._cache.stats()
        return {
            "shard_slab_hits": s["cache_hits"],
            "shard_slab_misses": s["cache_misses"],
            "shard_slab_bytes_in_use": s["cache_bytes_in_use"],
            "shard_slab_budget_bytes": s["cache_budget_bytes"],
        }

    @property
    def closed(self) -> bool:
        return self._cache.closed

    def close(self):
        self._cache.close()


# ---- dev range server (tests + DATABENCH) ---------------------------------


def dev_store_server(root: str, latency_s: float = 0.0,
                     fail_first: int = 0):
    """A threaded HTTP store server over ``root`` for tests and the
    DATABENCH remote arms: GET (with ``Range:``), HEAD, PUT, DELETE, and
    a JSON directory listing. ``latency_s`` sleeps before every response
    (the latency-injection curve); ``fail_first`` 500s the first N GETs
    (the network-flake retry path). Returns ``(server, base_url)`` —
    call ``server.shutdown()`` when done."""
    import http.server
    import socketserver

    state = {"fails_left": int(fail_first)}

    class Handler(http.server.BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):  # quiet
            pass

        def _path(self):
            rel = self.path.lstrip("/")
            p = os.path.normpath(os.path.join(root, rel))
            if not p.startswith(os.path.normpath(root)):
                return None
            return p

        def _maybe_flake(self) -> bool:
            if state["fails_left"] > 0:
                state["fails_left"] -= 1
                self.send_response(503)
                self.send_header("Content-Length", "0")
                self.end_headers()
                return True
            return False

        def do_GET(self):
            if latency_s:
                time.sleep(latency_s)
            if self._maybe_flake():
                return
            p = self._path()
            if p is None or not os.path.exists(p):
                self.send_response(404)
                self.send_header("Content-Length", "0")
                self.end_headers()
                return
            if os.path.isdir(p):
                entries = []
                for n in sorted(os.listdir(p)):
                    fp = os.path.join(p, n)
                    if os.path.isfile(fp):
                        entries.append({
                            "name": n, "mtime": os.path.getmtime(fp),
                            "size": os.path.getsize(fp),
                        })
                body = json.dumps(entries).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            size = os.path.getsize(p)
            rng = self.headers.get("Range")
            start, end = 0, size - 1
            status = 200
            if rng and rng.startswith("bytes="):
                spec = rng[len("bytes="):].split("-")
                start = int(spec[0]) if spec[0] else 0
                if spec[1]:
                    end = min(int(spec[1]), size - 1)
                status = 206
            length = max(end - start + 1, 0)
            with open(p, "rb") as f:
                body = os.pread(f.fileno(), length, start)
            self.send_response(status)
            if status == 206:
                self.send_header(
                    "Content-Range", f"bytes {start}-{end}/{size}"
                )
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_HEAD(self):
            p = self._path()
            if p is None or not os.path.isfile(p):
                self.send_response(404)
                self.send_header("Content-Length", "0")
                self.end_headers()
                return
            self.send_response(200)
            self.send_header("Content-Length", str(os.path.getsize(p)))
            self.end_headers()

        def do_PUT(self):
            p = self._path()
            n = int(self.headers.get("Content-Length", 0))
            data = self.rfile.read(n)
            os.makedirs(os.path.dirname(p), exist_ok=True)
            tmp = p + ".tmp"
            with open(tmp, "wb") as f:
                f.write(data)
            os.replace(tmp, p)
            self.send_response(201)
            self.send_header("Content-Length", "0")
            self.end_headers()

        def do_DELETE(self):
            p = self._path()
            if p is None or not os.path.isfile(p):
                self.send_response(404)
            else:
                os.remove(p)
                self.send_response(204)
            self.send_header("Content-Length", "0")
            self.end_headers()

    class Server(socketserver.ThreadingMixIn, http.server.HTTPServer):
        daemon_threads = True
        allow_reuse_address = True

    server = Server(("127.0.0.1", 0), Handler)
    thread = threading.Thread(
        target=server.serve_forever, daemon=True, name="dptpu-dev-store"
    )
    thread.start()
    host, port = server.server_address
    return server, f"http://{host}:{port}"
