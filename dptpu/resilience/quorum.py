"""Quorum mid-epoch saves: barrier-with-deadline over the pod's
coordination seam.

The gap (open since PR 2, fit.py's ``_preempt_save_ok``): on a sharded
multi-host run, a preemption signal that reaches only ONE host cannot
safely save — the gathered checkpoint is a collective, and a host that
enters it alone hangs the pod. Today that host just skips the save and
the boundary checkpoint stands, losing up to an epoch.

This module closes it with a tiny agreement protocol over a key-value
store (the "coordination seam" — on a real pod the jax.distributed
coordination service every rank already rendezvoused through; on one
machine, or in tests, a shared directory):

1. the host that caught the signal posts a STOP REQUEST;
2. every host polls the store once per optimizer step; on seeing the
   request each posts READY = its own completed-step count;
3. once all ``num_hosts`` READY keys exist, the agreed stop step is
   ``max(ready)`` — every host keeps stepping to exactly that step
   (deterministic: all hosts train the same global step sequence), so
   the pod stops POD-CONSISTENTLY and the chief's mid-epoch save names
   a position every host actually reached;
4. a barrier-with-deadline guards the gathered save itself: only when
   every host checked in does anyone enter the collective.

On seeing the request a host posts READY and HOLDS inside the tick
until the pod agrees — a fast host must not dispatch past the agreed
step, or the pod would stop at different dispatch counts. Every wait is
bounded by ``DPTPU_QUORUM_DEADLINE_S``: a host that never answers (it
is the one being preempted to death, after all) degrades the protocol
loudly — the requester stops at its own step and the save falls back to
the PR-2 rules (skip the gathered save rather than hang). A single-host
run degenerates exactly to the PreemptionGuard path: the request, READY
and barrier are all satisfied by the one host in the same tick, and the
save lands at the same step a plain SIGTERM would have produced.

KNOWN LIMIT (multi-host, recorded in ROADMAP item 3 residuals): ticks
run on the host thread between steps, so a peer whose host thread is
parked inside a blocking device fetch (a metric sync of a step the
holding host has not dispatched, a synchronous checkpoint gather)
cannot post READY until that fetch resolves — if it never does, the
holder degrades at the deadline and the parked peer stays inside its
fetch. The train loop's lagged metric fetches and its bounded
run-ahead make the window small (both wait only for steps two behind
the newest, which every host has already dispatched, except the
epoch-opening display), but closing it fully needs a tick source off
the host thread — real multi-host hardware work.

Transports:

* :class:`FileKVStore` — atomic-rename files under a shared directory
  (``DPTPU_QUORUM_DIR``). The test/bench seam, and a real option for
  single-machine multi-process pods or NFS-shared clusters.
* :class:`JaxKVStore` — the jax.distributed coordination service's
  key-value API, when a multi-host session is live. Best-effort by
  construction (the API is private); unavailable transports make
  :func:`make_coordinator` return None and fit keeps PR-2 behavior.
"""

from __future__ import annotations

import json
import os
import signal
import tempfile
import threading
import time
from typing import Dict, Optional

from dptpu.envknob import env_float
from dptpu.utils.sync import StopToken


def quorum_deadline_knob(environ=None) -> float:
    """``DPTPU_QUORUM_DEADLINE_S`` under the locked fail-fast contract:
    how long any quorum wait (READY collection, save barrier) may block
    before degrading. Default 30 s — short enough to fit inside every
    cloud provider's preemption grace window with room for the save."""
    deadline = env_float("DPTPU_QUORUM_DEADLINE_S", 30.0, environ)
    if deadline <= 0:
        raise ValueError(
            f"DPTPU_QUORUM_DEADLINE_S={deadline} must be > 0 seconds "
            f"(the bound on every quorum wait; e.g. "
            f"DPTPU_QUORUM_DEADLINE_S=30)"
        )
    return float(deadline)


class FileKVStore:
    """Key-value store over a shared directory: one file per key,
    written atomically (tempfile + rename in the same directory), so a
    reader never sees a torn value. Keys are flat names (the
    coordinator uses ``/``-free keys)."""

    def __init__(self, directory: str):
        self.directory = directory
        os.makedirs(directory, exist_ok=True)

    def _path(self, key: str) -> str:
        return os.path.join(self.directory, key)

    def put(self, key: str, value: str):
        fd, tmp = tempfile.mkstemp(prefix=f".{key}.", dir=self.directory)
        try:
            with os.fdopen(fd, "w") as f:
                f.write(value)
            os.replace(tmp, self._path(key))
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def get(self, key: str) -> Optional[str]:
        try:
            with open(self._path(key)) as f:
                return f.read()
        except OSError:
            return None

    def scan(self, prefix: str) -> Dict[str, str]:
        out = {}
        try:
            names = os.listdir(self.directory)
        except OSError:
            return out
        for name in names:
            if name.startswith(prefix) and not name.startswith("."):
                v = self.get(name)
                if v is not None:
                    out[name] = v
        return out


class JaxKVStore:
    """The jax.distributed coordination service as a KV transport.

    Uses the private client the rendezvous already established — the
    same seam every multi-host collective rides. ``available()`` gates
    construction; any API drift degrades to "no coordinator" rather
    than crashing a preempting pod."""

    def __init__(self, prefix: str = "dptpu_quorum/"):
        from jax._src.distributed import global_state

        if global_state.client is None:
            raise RuntimeError("jax.distributed client is not initialized")
        self._client = global_state.client
        self._prefix = prefix

    @staticmethod
    def available() -> bool:
        try:
            from jax._src.distributed import global_state

            return global_state.client is not None
        except Exception:
            return False

    def put(self, key: str, value: str):
        self._client.key_value_set(self._prefix + key, value)

    def get(self, key: str) -> Optional[str]:
        try:
            # non-blocking probe; absent keys raise in this API
            return self._client.key_value_try_get(self._prefix + key)
        except Exception:
            return None


class QuorumCoordinator:
    """The agreement protocol over a KV transport (see module doc).

    Host-indexed keys: ``stop`` (the request), ``ready-<h>`` (each
    host's completed step when it saw the request), ``barrier-<tag>-<h>``
    (save barrier check-ins), ``beat-<h>`` (liveness heartbeats for the
    chief-side lost-host verdict). All values are JSON with wall-clock
    timestamps, so deadline accounting works across hosts with roughly
    synchronized clocks (cloud pods are NTP-disciplined)."""

    def __init__(self, store, host_id: int, num_hosts: int,
                 deadline_s: float = 30.0, namespace: str = ""):
        if num_hosts < 1 or not 0 <= host_id < num_hosts:
            raise ValueError(
                f"quorum host_id {host_id} must be in [0, {num_hosts})"
            )
        self.store = store
        self.host_id = host_id
        self.num_hosts = num_hosts
        self.deadline_s = deadline_s
        # per-run-attempt key prefix: a restart pointed at the SAME
        # store (DPTPU_QUORUM_DIR is a config knob — it survives the
        # resume) must not re-read the previous attempt's stop request
        # and immediately re-preempt itself forever. fit derives the
        # namespace from the resume position, which every host shares.
        # Heartbeats stay UN-namespaced: liveness spans attempts and
        # missing_hosts already ages stale beats out by timestamp.
        self.namespace = namespace

    def _key(self, key: str) -> str:
        return self.namespace + key

    # -- stop request / agreement ------------------------------------------

    def request_stop(self, step: int, reason: str = "sigterm"):
        """Post the stop request (idempotent: first writer wins the
        ``reason``; later writers only confirm it exists)."""
        if self.store.get(self._key("stop")) is None:
            self.store.put(self._key("stop"), json.dumps({
                "reason": reason, "host": self.host_id, "step": int(step),
                "ts": time.time(),  # dptpu: allow-determinism(stop-record timestamp is operator telemetry; replay keys on step, never on ts)
            }))

    def pending_stop(self) -> Optional[dict]:
        raw = self.store.get(self._key("stop"))
        if raw is None:
            return None
        try:
            return json.loads(raw)
        except ValueError:
            return {"reason": "unparseable", "ts": 0.0}

    def post_ready(self, step: int):
        self.store.put(self._key(f"ready-{self.host_id}"), json.dumps({
            "step": int(step), "ts": time.time(),  # dptpu: allow-determinism(ready-record timestamp is telemetry; the quorum agrees on the max ready STEP, never on ts)
        }))

    def ready_steps(self) -> Dict[int, int]:
        out = {}
        for h in range(self.num_hosts):
            raw = self.store.get(self._key(f"ready-{h}"))
            if raw is None:
                continue
            try:
                out[h] = int(json.loads(raw)["step"])
            except (ValueError, KeyError, TypeError):
                continue
        return out

    def agreed_step(self) -> Optional[int]:
        """``max(ready)`` once every host posted READY; None before.
        Deadline handling lives in the caller (QuorumSession), which
        knows when the request was first seen."""
        ready = self.ready_steps()
        if len(ready) < self.num_hosts:
            return None
        return max(ready.values())

    # -- save barrier -------------------------------------------------------

    def barrier(self, tag: str, timeout_s: Optional[float] = None,
                poll_s: float = 0.02) -> bool:
        """Check in and wait (bounded) for every host; True only when
        the full pod arrived — the caller may then enter the gathered
        save knowing no host joins the collective alone."""
        timeout_s = self.deadline_s if timeout_s is None else timeout_s
        self.store.put(self._key(f"barrier-{tag}-{self.host_id}"),
                       json.dumps({"ts": time.time()}))  # dptpu: allow-determinism(barrier arrival stamp is telemetry; the barrier itself runs on monotonic deadlines)
        deadline = time.monotonic() + timeout_s
        while True:
            present = sum(
                1 for h in range(self.num_hosts)
                if self.store.get(self._key(f"barrier-{tag}-{h}"))
                is not None
            )
            if present >= self.num_hosts:
                return True
            if time.monotonic() > deadline:
                return False
            time.sleep(poll_s)

    # -- liveness (chief-side lost-host verdict) ---------------------------

    def heartbeat(self, step: int):
        self.store.put(f"beat-{self.host_id}", json.dumps({
            "step": int(step), "ts": time.time(),  # dptpu: allow-determinism(heartbeat liveness IS wall-clock by design — staleness ages out by real elapsed time)
        }))

    def missing_hosts(self, timeout_s: Optional[float] = None) -> list:
        """Hosts with no heartbeat within ``timeout_s`` — the chief's
        "gone for good" input that ultimately triggers elastic resume
        (a host that never beat at all counts as missing too)."""
        timeout_s = self.deadline_s if timeout_s is None else timeout_s
        now = time.time()  # dptpu: allow-determinism(liveness aging compares heartbeat wall-clock stamps; no replayed value derives from it)
        gone = []
        for h in range(self.num_hosts):
            raw = self.store.get(f"beat-{h}")
            ts = None
            if raw is not None:
                try:
                    ts = float(json.loads(raw)["ts"])
                except (ValueError, KeyError, TypeError):
                    ts = None
            if ts is None or now - ts > timeout_s:
                gone.append(h)
        return gone


def make_coordinator(num_hosts: int, host_id: int, deadline_s: float,
                     directory: Optional[str] = None,
                     namespace: str = ""
                     ) -> Optional[QuorumCoordinator]:
    """Build the pod coordinator over the best available transport:
    an explicit shared directory (``DPTPU_QUORUM_DIR`` — tests, benches,
    single-machine pods, NFS clusters) wins; else the live
    jax.distributed KV service on a multi-host run; else None (fit
    keeps the PR-2 single-signal rules). ``namespace`` scopes the
    protocol keys to one run attempt (see QuorumCoordinator)."""
    if directory:
        return QuorumCoordinator(
            FileKVStore(directory), host_id, num_hosts, deadline_s,
            namespace=namespace,
        )
    if num_hosts > 1 and JaxKVStore.available():
        try:
            return QuorumCoordinator(
                JaxKVStore(), host_id, num_hosts, deadline_s,
                namespace=namespace,
            )
        except Exception:
            return None
    return None


class QuorumHeartbeat:
    """Liveness beats from a dedicated thread — the tick source OFF the
    host thread that ROADMAP item 3 residual (d) called for: a peer
    parked inside a blocking device fetch keeps beating, so the chief's
    ``missing_hosts`` verdict distinguishes "slow step" from "gone".

    Teardown rides the shared :class:`dptpu.utils.sync.StopToken`
    idiom: the loop blocks in ``Event.wait(interval)`` (never a bare
    ``time.sleep`` + flag poll), so ``close()`` wakes it immediately
    and joins promptly — the conftest thread census never sees a
    lingering beat thread.
    """

    def __init__(self, coordinator: QuorumCoordinator, step_fn,
                 interval_s: float = 1.0):
        if interval_s <= 0:
            raise ValueError(
                f"heartbeat interval_s={interval_s} must be > 0 seconds"
            )
        self.coord = coordinator
        self.interval_s = float(interval_s)
        self._step_fn = step_fn
        self._stop = StopToken()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="dptpu-quorum-heartbeat"
        )
        self._thread.start()

    def _run(self):
        while not self._stop.wait(self.interval_s):
            try:
                self.coord.heartbeat(int(self._step_fn()))
            except Exception:
                # liveness is best-effort by design: a flaky KV write
                # must never kill the beat loop (a missing beat ages
                # out; a dead beat thread looks like a dead host)
                pass

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()

    def close(self):
        self._stop.stop()
        self._thread.join(timeout=5.0)


class QuorumSession:
    """Per-``fit()`` driver of the protocol: one ``tick()`` per
    completed optimizer step (riding the same post-step hook as fault
    injection), one ``should_stop()`` consult per loop iteration, one
    ``save_barrier()`` before the gathered preemption save.

    State machine: idle → (local signal or store-side request) READY
    posted → (all hosts ready) ARMED at ``max(ready)`` → (reached it)
    STOP. The deadline starts when this host first sees the request; on
    expiry it degrades — stop at the local step, remember
    ``degraded=True`` so ``save_barrier`` refuses and the PR-2 fallback
    rules decide the save."""

    def __init__(self, coordinator: QuorumCoordinator, guard,
                 deadline_s: Optional[float] = None):
        self.coord = coordinator
        self.guard = guard  # PreemptionGuard: .requested / .signum
        self.deadline_s = (
            coordinator.deadline_s if deadline_s is None else deadline_s
        )
        self.epoch = 0
        self.step = 0  # completed steps this epoch (position coords)
        self._posted_request = False
        self._ready_step: Optional[int] = None
        self._agreed: Optional[int] = None
        self._degraded = False
        self._stop = False
        self._reason = ""
        # heartbeats are throttled: liveness needs ~1 Hz, not one KV
        # write per optimizer step (the store may be the pod's real
        # coordination service). start_heartbeat() moves them onto a
        # dedicated QuorumHeartbeat thread; the inline tick beats are
        # the fallback when no thread was started (unit tests driving
        # tick() directly keep their behavior).
        self._beat_every_s = 1.0
        self._last_beat = 0.0
        self._hb: Optional[QuorumHeartbeat] = None

    # -- off-thread liveness ------------------------------------------------

    def start_heartbeat(self, interval_s: float = 1.0) -> QuorumHeartbeat:
        """Move liveness beats onto a dedicated thread (fit() does this
        right after arming the session). Idempotent."""
        if self._hb is None:
            # reading self.step from the beat thread is a single int
            # load of caller-owned state: atomic under the GIL, and a
            # one-step-stale beat is indistinguishable from a beat that
            # raced the step boundary
            self._hb = QuorumHeartbeat(
                self.coord, lambda: self.step, interval_s
            )
        return self._hb

    def close(self):
        """Stop the heartbeat thread (prompt — StopToken teardown)."""
        if self._hb is not None:
            self._hb.close()
            self._hb = None

    # -- position ----------------------------------------------------------

    def epoch_start(self, epoch: int, step: int):
        self.epoch = epoch
        self.step = step

    # -- the per-step tick --------------------------------------------------

    def tick(self):
        """Called once after every completed optimizer step."""
        self.step += 1
        if self._hb is None:
            now = time.monotonic()
            if now - self._last_beat >= self._beat_every_s:
                self.coord.heartbeat(self.step)
                self._last_beat = now
        if self._stop:
            return
        if self.guard is not None and self.guard.requested \
                and not self._posted_request:
            # this host caught the signal: make it pod-visible
            sig = getattr(self.guard, "signum", None)
            self.coord.request_stop(
                self.step,
                reason=signal.Signals(sig).name if sig else "local",
            )
            self._posted_request = True
        if self._ready_step is None:
            req = self.coord.pending_stop()
            if req is None:
                return
            self._reason = str(req.get("reason", ""))
            self._ready_step = self.step
            self.coord.post_ready(self.step)
            # the barrier-with-deadline on the READY set, INSIDE the
            # tick: this host must not dispatch another step until the
            # pod agrees on max(ready) — a fast host that kept stepping
            # could pass the agreed step before learning it, and the
            # pod would stop at different dispatch counts (the gather
            # would then wait on steps some hosts never dispatched).
            # The wait is bounded: a host that never answers degrades
            # the protocol instead of eating the whole grace window.
            deadline = time.monotonic() + self.deadline_s
            while self._agreed is None:
                self._agreed = self.coord.agreed_step()
                if self._agreed is not None:
                    break
                if time.monotonic() > deadline:
                    # stop at the local step, remember the degrade —
                    # the PR-2 save rules decide (no consistency claim)
                    self._degraded = True
                    self._agreed = self.step
                    break
                time.sleep(0.01)
        if self._agreed is not None and self.step >= self._agreed:
            self._stop = True

    # -- fault / control hooks ----------------------------------------------

    def request_remote(self, reason: str = "sigterm_one_host"):
        """Model a request arriving from ANOTHER host (the
        ``sigterm_one_host`` fault: this host catches nothing — it
        learns of the preemption from the store on its next tick)."""
        self.coord.request_stop(self.step, reason=reason)

    # -- loop consults ------------------------------------------------------

    def should_stop(self) -> bool:
        return self._stop

    def stop_signaled(self) -> bool:
        """A stop request exists (agreed or not) — the between-epoch
        check, where waiting for a formal agreement would pay another
        epoch's first step inside the grace window. Probes the STORE
        too: a remote request that landed while this host was inside
        validation or a boundary save (no ticks run there) must be
        visible before the next epoch's first step is paid."""
        if self._stop or self._ready_step is not None \
                or (self.guard is not None and self.guard.requested):
            return True
        return self.coord.pending_stop() is not None

    def save_barrier(self) -> bool:
        """True only when the whole pod checked in within the deadline:
        the gathered mid-epoch save is then safe even though only one
        host caught the signal. Degraded protocols refuse."""
        if self._degraded:
            return False
        return self.coord.barrier(f"save-e{self.epoch}-s{self.step}",
                                  timeout_s=self.deadline_s)

    def stats(self) -> dict:
        return {
            "hosts": self.coord.num_hosts,
            "reason": self._reason,
            "ready_step": self._ready_step,
            "agreed_step": self._agreed,
            "stopped_at": self.step if self._stop else None,
            "degraded": self._degraded,
        }
