"""Test harness: fake TPU pod on CPU.

Multi-chip hardware is not available in CI, so every test runs on a virtual
8-device CPU mesh — the standard JAX fake-cluster trick (SURVEY.md §4). Run
the suite with ``env JAX_PLATFORMS=cpu`` (the variable is honoured); the
in-process ``jax.config.update`` below is belt and braces for a bare
``pytest`` on a machine that has a chip. ``XLA_FLAGS`` takes effect because
the CPU PJRT client is created lazily, at the first backend use — which is
after this conftest. The chip itself is reached with ``python chip_smoke.py``.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Arm the lock-order sanitizer for the WHOLE suite (ISSUE 14): every
# OrderedLock built during tests records per-thread acquisition stacks
# and asserts the declared LOCK_RANKS order, so tier-1 exercises the
# real lock orders under load — an inverted acquisition fails the test
# that performed it, with both stacks in the message. Must be set
# BEFORE any dptpu module constructs a lock (the knob is read at lock
# construction, which is what keeps the disabled mode zero-cost).
os.environ.setdefault("DPTPU_SYNC_CHECK", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Two-tier suite: `-m fast` is the quick all-unit check (~1-2 min on one
# CPU, at most one tiny-model compile); everything else is the
# compile-heavy `slow` tier. Modules are the marking unit — a whole file
# is fast only if none of its tests build/compile a zoo model or run
# fit(). Deliberate exception: test_fault_resume (ONE resnet18@32 compile,
# reused by every run in the module) — the resilience acceptance bar
# "SIGTERM'd run resumes bit-identically" must hold in tier 1, and it can
# only be asserted through fit().
_FAST_MODULES = {
    # token-sequence model (ISSUE 30): LFM2 against its plain reference
    # at hidden 64 (a dozen small jits), the tokens:<N> feed against the
    # benchmark's copy with ONE fit()-driven case at the same size (train,
    # validate, resume), and the benchmark's seam cases (11 s)
    "test_lfm2", "test_tokens_feed", "test_benchmark_seams",
    # the expert layer's compact buffer against its worst-case path
    # (ISSUE 42): 1,024 tokens at hidden 16, a dozen small jits, 20 s
    "test_expert_buffer",
    # a second token model (ISSUE 36): JoyAI-LLM-Flash against its plain
    # reference at hidden 64, ONE case through main_apex (two fit() runs
    # of 16 steps at 32 tokens); under a minute of one worker
    "test_joyai",
    # granite-4.0-h-micro (ISSUE 39): the model, the scan and the
    # reference at toy widths, two minutes; the cell's rehearsal at the
    # published widths on two layers and 96 tokens in a one-device child,
    # a minute and a half more, in a file of its own
    "test_granite", "test_granite_cell",
    # Trinity-Mini (ISSUE 43): the attention's window in the scan and in
    # the interpreted kernels against the plain attention (a minute), the
    # model and its reference at toy widths (two minutes), and the cell's
    # rehearsal at the published widths on two layers and 96 tokens in a
    # one-device child, in a file of its own
    "test_attention_window", "test_trinity", "test_trinity_cell",
    # fit()'s default train feed (ISSUE 31): ONE module fixture runs
    # fit() four times at the sizes above (resnet18@32 and the tiny
    # token model, 4-8 steps, default and thread mode); the rest drives
    # loaders of a few dozen rows
    "test_feed_default",
    # the expert layer and the blockwise attention compiled for a
    # described v5e at the cell's widths (no chip; 25 s; skips where the
    # TPU's compiler cannot describe the chip)
    "test_tpu_compile",
    # the attention's Pallas kernels against the plain attention in
    # interpret mode (ISSUE 37): a dozen grids of a few steps, 20 s
    "test_attention_kernel",
    "test_bench_logic", "test_config", "test_schedules", "test_metrics",
    "test_meters", "test_data", "test_tensorboard", "test_native",
    "test_cache", "test_shm_loader", "test_feed_knobs", "test_tv_template",
    "test_resilience", "test_shm_supervision", "test_fault_resume",
    # observability tier (PR 5): obs unit tests are pure-fast; the
    # obsbench smoke is the second deliberate fit()-driven exception —
    # the overhead/coverage/trigger gates must hold in tier 1, and they
    # can only be asserted through fit() (one subprocess, tiny preset)
    "test_obs", "test_obs_knobs", "test_profiling", "test_obsbench_smoke",
    # span attributes (ISSUE 27): fake-step loop, row-set feed and report
    # units are pure-fast; ONE module fixture runs fit() twice at the
    # test_fault_resume size (resnet18@32, 4 steps) for the set-up spans
    "test_obs_attrs", "test_setup_tracing",
    # bounded run-ahead (ISSUE 28): the real loop on a stand-in device,
    # no jit, milliseconds a case
    "test_loop_pacing",
    # large-batch engine (PR 6): knob validation is pure; the recipe-math
    # module is pure optax math plus TinyNet-sized jits (the
    # test_fault_resume precedent) — the accumulation/trust-ratio locks
    # must hold in tier 1
    "test_opt_knobs", "test_optimizers",
    # the step family, the mesh and the notices (ISSUE 33): one table
    # over dptpu.train.plan.decide, a pure function; no compile
    "test_train_plan",
    # serving (PR 7): knob validation is pure; test_serve compiles only
    # tiny-model bucket ladders (resnet18@32 / vit_b_32@64 — the
    # test_fault_resume precedent) and holds the ISSUE acceptance bar —
    # padded-bucket logit identity and hot-swap draining MUST hold in
    # tier 1; the servebench smoke is the third fit-shaped exception
    # (one subprocess, --smoke preset, same gates as SERVEBENCH.json)
    "test_serve", "test_serve_knobs", "test_servebench_smoke",
    # streaming data plane (PR 8): store/shard units are pure-fast;
    # test_shards holds the bit-identity + resume-on-shards acceptance
    # bars (ONE resnet18@48 compile, the test_fault_resume precedent);
    # the databench smoke is the fourth fit-shaped exception (one
    # subprocess, --smoke preset, same gates as DATABENCH.json)
    "test_shards", "test_store", "test_databench_smoke",
    # hierarchical comms (PR 10): knob/parser units are pure; the
    # parity + HLO locks compile only TinyDense-sized shard_map steps
    # (the test_optimizers precedent) and hold the ISSUE acceptance
    # bars — pure-hop Δ=0 parity and per-axis byte counts MUST hold in
    # tier 1; the commbench smoke is the fifth fit-shaped exception
    # (one subprocess, --smoke preset, same gates as COMMBENCH.json)
    "test_hierarchy", "test_commbench_smoke",
    # elastic pod lifecycle (PR 11): remap/quorum/straggler units are
    # pure-fast (one pre-compile fail-fast fit); the faultbench smoke
    # is the sixth fit-shaped exception — the shrink-resume, quorum and
    # straggler chaos gates MUST hold in tier 1 (one subprocess,
    # --smoke preset, same gates as FAULTBENCH.json)
    "test_elastic", "test_faultbench_smoke",
    # static analysis (PR 12): the lint units are pure stdlib; the
    # repo gate compiles only the four TinyDense-sized budget configs
    # (the test_hierarchy precedent, cached module-wide) — the
    # zero-findings + HLO-budget acceptance bars MUST hold in tier 1
    "test_analysis", "test_analysis_repo",
    # concurrency analyzer (ISSUE 14): the three lint rules are pure
    # stdlib; the runtime OrderedLock/StopToken/heartbeat units are
    # sub-second thread exercises — the ABBA and unguarded-shared-write
    # acceptance bars MUST hold in tier 1
    "test_concurrency",
    # unified partition rules (ISSUE 16): the matcher/projection units
    # and the dptpu-check partition-rules gate are eval_shape-only (no
    # weights allocated, no step compiles) — the one-table-many-views
    # equivalence locks MUST hold in tier 1
    "test_rules",
    # overlapped gradient comms (ISSUE 13): partitioner/evidence units
    # are pure; the parity ladder compiles TinyDense-sized shard_map
    # steps (the test_hierarchy precedent) and holds the acceptance
    # bars — overlap Δ=0 for DDP/ZeRO-1/slices MUST hold in tier 1;
    # the racebench smoke is the seventh fit-shaped exception (one
    # subprocess, --smoke preset, same gates as RACEBENCH.json)
    "test_overlap", "test_racebench_smoke",
    # robust serving tier (ISSUE 17): admission/canary/router units and
    # the HTTP surface reuse the tiny resnet18@32 ladder (the test_serve
    # precedent) — the shed/rollback/disconnect-hygiene acceptance bars
    # MUST hold in tier 1
    "test_serve_admission", "test_serve_http",
    # quantized serving + fleet (ISSUE 18): quant/calibration units and
    # the canary top-1 gate reuse the tiny resnet18@32 ladder (the
    # test_serve precedent; the CLI end-to-end is opted out per-test);
    # the fleet tier is pure stdlib threads + loopback HTTP — the
    # zero-failed-failover acceptance bar MUST hold in tier 1
    "test_serve_quant", "test_fleet",
    # self-tuning control plane (ISSUE 19): artifact/controller/search
    # units are pure; the precedence locks ride tiny resnet18@32 fits
    # (the test_fault_resume precedent) and the cost-model extraction
    # lock is analytic — explicit-knobs-win and bounded-actuation bars
    # MUST hold in tier 1; the tunebench smoke is the eighth fit-shaped
    # exception (one subprocess, --smoke preset, same gates as
    # TUNEBENCH.json)
    "test_tune", "test_tune_costmodel", "test_tunebench_smoke",
    # bring-up locks (ISSUE 21): the checker-off / update-parity locks
    # jit one 3-layer net on the fake pod; the rest is pure
    "test_bringup",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        name = item.module.__name__.rsplit(".", 1)[-1] if item.module else ""
        item.add_marker(
            pytest.mark.fast if name in _FAST_MODULES else pytest.mark.slow
        )


@pytest.fixture(scope="session", autouse=True)
def dptpu_shm_leak_guard():
    """CI gate on /dev/shm hygiene: every dptpu segment (batch-slot ring
    ``dptpu_ring_*``, pooled decode-cache slab ``dptpu_cache_*``) that
    appears during the suite must be gone — or still owned by a live,
    registered object whose atexit hook will unlink it — by session end.
    A segment that is neither was abandoned without ``close()`` and
    would leak host RAM until reboot in production.

    Also policed: LEASES — the feed ring's AND the serve staging ring's
    (``dptpu_serve_*``, dptpu/serve/staging.py — same SlotLease
    protocol). A slot still leased when its pipeline/ring closed was
    neither released by the consumer nor revoked by an epoch reset /
    loader-initiated rebuild — a zero-copy protocol bug that would pin
    (and, worse, silently recycle under) live batch views in
    production. The ``leaked_lease_count()``s only advance on
    close-with-lease-outstanding, so abandoned epochs whose leases the
    generator backstop or a reset reclaimed stay clean.

    And the chief collector's merged-timeline temp files
    (dptpu/obs/report.py ``merge_pod_timeline``): every merge must
    either finish its atomic rename or unlink its temp — a temp still
    tracked at session end was abandoned mid-write."""
    import glob

    from dptpu.data import shm as _shm
    from dptpu.data import stream as _stream
    from dptpu.obs import report as _obs_report
    from dptpu.serve import staging as _serve_staging

    def lease_leaks():
        return (_shm.leaked_lease_count()
                + _serve_staging.leaked_lease_count())

    leases_before = lease_leaks()
    merge_tmps_before = _obs_report.live_merge_tmp_count()
    # shard-file descriptors (the O_DIRECT/pread byte ring,
    # dptpu/data/stream.py): every reader a test opens must be closed
    # (dataset.close() or GC) by session end, or the suite fails
    fds_before = _stream.open_fd_count()
    if not os.path.isdir("/dev/shm"):
        yield  # platform without a tmpfs view; segments can't be policed
        import gc

        gc.collect()
        assert lease_leaks() == leases_before, (
            "slots were still leased when their pipeline/ring closed "
            "(consumer never released, no reset revoked) — a zero-copy "
            "lease leak"
        )
        assert _stream.open_fd_count() <= fds_before, (
            "shard-file descriptors leaked past dataset close()"
        )
        return
    # segment names embed their CREATOR pid (dptpu_{kind}_{pid}_{hex});
    # only this process creates segments for this suite (workers merely
    # attach), so scoping to our pid keeps concurrent dptpu runs on the
    # same host from tripping the guard
    mine = (f"/dev/shm/dptpu_ring_{os.getpid()}_*",
            f"/dev/shm/dptpu_cache_{os.getpid()}_*",
            f"/dev/shm/dptpu_serve_{os.getpid()}_*",
            f"/dev/shm/dptpu_shard_{os.getpid()}_*")
    snapshot = lambda: {p for pat in mine for p in glob.glob(pat)}  # noqa: E731
    before = snapshot()
    yield
    import gc

    gc.collect()  # run __del__ for dropped loaders/datasets first
    from dptpu.data import shm_cache as _shm_cache

    live = {
        "/dev/shm/" + n.lstrip("/")
        for n in (_shm.live_segment_names()
                  | _shm_cache.live_segment_names()
                  | _serve_staging.live_segment_names())
    }
    leaked = snapshot() - before - live
    assert not leaked, (
        f"leaked /dev/shm segments (created during the suite, not "
        f"closed, not owned by any live pipeline/cache/staging ring): "
        f"{sorted(leaked)}"
    )
    assert lease_leaks() == leases_before, (
        "slots were still leased when their pipeline/ring closed "
        "(consumer never released, no reset revoked) — a zero-copy "
        "lease leak"
    )
    assert _stream.open_fd_count() <= fds_before, (
        "shard-file descriptors leaked: a ShardFileReader opened during "
        "the suite was never closed (dataset.close() missing?)"
    )
    assert _obs_report.live_merge_tmp_count() == merge_tmps_before, (
        "pod-timeline merge temp files leaked: a merge_pod_timeline "
        "call neither completed its atomic rename nor unlinked its temp"
    )


@pytest.fixture(scope="session", autouse=True)
def dptpu_thread_census():
    """CI gate on thread hygiene (the shm-segment/fd/lease censuses'
    sibling, ISSUE 14): every ``dptpu``-named thread started during the
    suite must be stopped by session end. A leaked NON-daemon thread
    blocks interpreter exit in production; a leaked daemon thread —
    and all of dptpu's service threads are daemon by design — keeps
    touching shared state (posting heartbeats for a dead host,
    dispatching against a closed ring) long after its owner died, so
    daemons are policed too, with a short join grace for pools mid-
    ``shutdown(wait=False)``. The census names the thread and its
    target so the leak is attributable; the static half (``dptpu
    check``'s thread-hygiene rule) enforces the dptpu- name prefix it
    keys on."""
    import threading

    def census():
        return [
            t for t in threading.enumerate()
            if t is not threading.main_thread() and t.is_alive()
            and t.name.startswith("dptpu")
        ]

    before = {id(t) for t in census()}
    yield
    import gc

    gc.collect()  # run __del__ teardown for dropped owners first
    leaked = []
    for t in census():
        if id(t) in before:
            continue
        t.join(timeout=2.0)  # grace for executor shutdown(wait=False)
        if t.is_alive():
            leaked.append(t)
    assert not leaked, (
        "leaked dptpu threads alive at session end (started during "
        "the suite, never stopped/joined): "
        + ", ".join(
            f"{t.name}"
            f" ({'daemon' if t.daemon else 'NON-DAEMON'},"
            f" target={getattr(getattr(t, '_target', None), '__qualname__', getattr(t, '_target', None))!r})"
            for t in leaked
        )
    )


@pytest.fixture(scope="session")
def eight_devices():
    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 fake devices, got {len(devices)}"
    return devices[:8]


@pytest.fixture(scope="session")
def tiny_imagenet(tmp_path_factory):
    """ImageFolder-shaped 3-class dataset with class-separable means —
    shared by the fit()-level integration tests."""
    import numpy as np
    from PIL import Image

    root = tmp_path_factory.mktemp("tinyimg")
    rng = np.random.RandomState(0)
    for split, per_class in [("train", 24), ("val", 8)]:
        for cls in range(3):
            d = root / split / f"class{cls}"
            d.mkdir(parents=True)
            for i in range(per_class):
                # class-dependent mean so the model can actually learn
                base = np.full((40, 40, 3), 60 + 70 * cls, np.uint8)
                noise = rng.randint(0, 40, base.shape, dtype=np.uint8)
                Image.fromarray(base + noise).save(d / f"{i}.png")
    return str(root)
