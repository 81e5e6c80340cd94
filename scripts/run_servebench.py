#!/usr/bin/env python3
"""SERVEBENCH: the serving subsystem's own gate — latency × offered
load, saturation throughput, bucket utilization, and the tail gate,
measured through the REAL serve stack (ServeEngine AOT buckets +
DynamicBatcher + leased staging ring; dptpu/serve).

Two load models, both driven against one engine:

1. **Closed loop** — ``c`` client threads, each submitting the next
   request the moment its previous answer lands (think: ``c`` busy
   front-end workers). Sweeping ``c`` traces the throughput-vs-latency
   frontier; the sweep's best achieved qps is the SATURATION throughput.
2. **Open loop** — requests arrive on a Poisson clock at a FIXED
   offered rate, a set fraction of the measured saturation, regardless
   of how the server is doing (think: the internet). This is the load
   model latency SLOs live under: queueing delay shows up here and not
   in a closed loop, which self-throttles. The > 1x point is the
   honest overload case — the staging ring's backpressure bounds the
   queue, so latency plateaus at ring depth instead of diverging, and
   achieved qps pins at saturation.

Per point: achieved qps, p50/p99 latency (per-request submit->logits,
from the batcher's own timings), bucket-utilization breakdown
(dispatch counts per bucket, mean occupancy, padding waste), and
mean per-phase times (queue / batch-wait / device).

Gates (exit non-zero on failure unless ``--no-gate``):

* **tail** — at the 0.5x-saturation open-loop point (the SLO-typical
  operating regime), ``p99 <= max(--tail-floor-ms, --tail-factor x
  p50)``: a no-pathological-tail claim that self-calibrates to the
  host instead of hard-coding a ms budget a 2-core box cannot meet.
* **parity** — padded-bucket serving agrees with the single-request
  path (3 real rows through the largest bucket vs three bucket-1
  calls, max|dlogit| <= ``BUCKET_PARITY_ATOL``: fp32 rounding, exactly
  0 on a one-core host) — the engine's bucket-invariant-numerics
  contract, re-proven on the bench engine.

Plus the ISSUE 18 arms, both gated: the **quantized** arm rolls an
int8 calibration artifact out through the canary's artifact-armed
drift/top-1 gate and reports residency + throughput vs fp32, and the
**fleet** arm hard-kills one of two member hosts mid-load and requires
zero failed requests while the router fails over and the staleness
verdict auto-drains the corpse (drain curve on record).

Also measured: ``preprocess_bytes`` cost (the bytes->pixels ingest,
amortized over repeats) so the curves' decode-free request path
(``submit_array``) is an EXPLICIT choice with the excluded cost on
record, not a hidden one.

Writes SERVEBENCH.json at the repo root (or ``--out``). ``--smoke`` is
the tier-1 CI preset (tests/test_servebench_smoke.py): tiny model,
short points, same code path and gates.

Usage: python scripts/run_servebench.py [--smoke] [--arch resnet18]
           [--image-size 64] [--buckets 1,4,16] [--requests N]
           [--tail-factor 10] [--tail-floor-ms 250] [--no-gate]
"""

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _point_summary(futures, wall_s, batcher_stats):
    # ONE quantile definition repo-wide: the registry histogram's
    # nearest-rank, so the bench's p99 and Serve/p99_ms agree on
    # identical data
    from dptpu.obs.metrics import _quantile as _percentile

    lats = sorted(f.timings["total_ms"] for f in futures)
    phases = {k: sum(f.timings[k] for f in futures) / len(futures)
              for k in ("queue_ms", "batch_wait_ms", "device_ms")}
    return {
        "requests": len(futures),
        "wall_s": round(wall_s, 3),
        "achieved_qps": round(len(futures) / wall_s, 2),
        "p50_ms": round(_percentile(lats, 0.50), 2),
        "p90_ms": round(_percentile(lats, 0.90), 2),
        "p99_ms": round(_percentile(lats, 0.99), 2),
        "max_ms": round(lats[-1], 2),
        "phase_means_ms": {k: round(v, 2) for k, v in phases.items()},
        "bucket_counts": batcher_stats["bucket_counts"],
        "mean_bucket_occupancy": round(
            batcher_stats["mean_bucket_occupancy"], 3),
        "padding_waste": round(batcher_stats["padding_waste"], 3),
    }


def closed_loop_point(engine, knobs, pool, concurrency, n_requests):
    """``concurrency`` synchronous clients, ``n_requests`` total."""
    from dptpu.serve import DynamicBatcher

    b = DynamicBatcher(engine, max_delay_ms=knobs.max_delay_ms,
                       slots=knobs.slots)
    try:
        done, errs = [], []
        lock = threading.Lock()
        remaining = [n_requests]

        def client(tid):
            i = tid
            while True:
                with lock:
                    if remaining[0] <= 0:
                        return
                    remaining[0] -= 1
                try:
                    f = b.submit_array(pool[i % len(pool)])
                    f.result(timeout=300)
                    with lock:
                        done.append(f)
                except Exception as e:  # pragma: no cover - surfaced below
                    with lock:
                        errs.append(e)
                    return
                i += concurrency

        # warm the dispatch path (engine is AOT-compiled already; this
        # covers first-touch of the staging slab + thread ramp)
        b.submit_array(pool[0]).result(timeout=300)
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errs:
            raise RuntimeError(f"closed-loop client failed: {errs[0]}")
        return _point_summary(done, wall, b.stats())
    finally:
        b.close()


def open_loop_point(engine, knobs, pool, offered_qps, n_requests, seed=0):
    """Poisson arrivals at ``offered_qps``; submissions never wait for
    answers (a waiter thread collects them)."""
    from dptpu.serve import DynamicBatcher

    b = DynamicBatcher(engine, max_delay_ms=knobs.max_delay_ms,
                       slots=knobs.slots)
    try:
        rng = np.random.RandomState(seed)
        gaps = rng.exponential(1.0 / offered_qps, size=n_requests)
        futs = []
        b.submit_array(pool[0]).result(timeout=300)  # warm
        t0 = time.perf_counter()
        t_next = t0
        for i in range(n_requests):
            t_next += gaps[i]
            delay = t_next - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            # submit_array blocks when every staging slot is leased —
            # the ring's backpressure IS the overload behavior under
            # measurement, so the block is part of the request's clock
            futs.append(b.submit_array(pool[i % len(pool)]))
        for f in futs:
            f.result(timeout=300)
        wall = time.perf_counter() - t0
        return dict(_point_summary(futs, wall, b.stats()),
                    offered_qps=round(offered_qps, 2))
    finally:
        b.close()


def parity_check(engine, pool):
    """The engine's bucket-parity contract on THIS bench configuration:
    3 real rows through the largest bucket vs three bucket-1 calls."""
    x = np.stack(pool[:3])
    solo = np.concatenate([engine.infer(x[i:i + 1]) for i in range(3)])
    nexec = engine.exec_batch(engine.max_bucket)
    padded = np.concatenate(
        [x, np.broadcast_to(x[0], (nexec - 3,) + x.shape[1:])]
    )
    via_max = engine.run_bucket(engine.max_bucket, padded, 3)
    return float(np.abs(via_max.astype(np.float64)
                        - solo.astype(np.float64)).max())


def measure_preprocess(image_size, reps=20):
    import io

    from PIL import Image

    from dptpu.serve import preprocess_bytes

    rng = np.random.RandomState(0)
    buf = io.BytesIO()
    Image.fromarray(
        rng.randint(0, 256, (image_size * 2, image_size * 2, 3), np.uint8)
    ).save(buf, format="JPEG", quality=90)
    data = buf.getvalue()
    out = np.empty((image_size, image_size, 3), np.uint8)
    preprocess_bytes(data, size=image_size, out=out)  # warm PIL
    t0 = time.perf_counter()
    for _ in range(reps):
        preprocess_bytes(data, size=image_size, out=out)
    return (time.perf_counter() - t0) / reps * 1e3


# -- robustness arms (ISSUE 17) ------------------------------------------


def overload_shedding_arm(engine, knobs, pool, saturation_qps, n_requests,
                          budget_ms, seed=7):
    """Offer 2x the measured saturation THROUGH the admission gate:
    the p99 of ADMITTED requests must stay bounded (occupancy is capped,
    so queueing cannot diverge) and every shed decision must land in
    well under a service time (the whole point of shedding over
    blocking)."""
    from dptpu.obs.metrics import _quantile
    from dptpu.serve import DynamicBatcher
    from dptpu.serve.admission import AdmissionController, AdmissionError

    b = DynamicBatcher(engine, max_delay_ms=knobs.max_delay_ms,
                       slots=knobs.slots)
    # depth below the ring's row capacity: admission must shed BEFORE
    # the ring's blocking backpressure would stall the arrival clock
    depth = max(4, knobs.slots * engine.exec_batch(engine.max_bucket) // 2)
    adm = AdmissionController(depth=depth, priorities=knobs.priorities,
                              name="overload")
    try:
        b.submit_array(pool[0]).result(timeout=300)  # warm
        offered = 2.0 * max(saturation_qps, 1.0)
        gaps = np.random.RandomState(seed).exponential(
            1.0 / offered, size=n_requests)
        admitted, shed_ms = [], []
        t_next = time.perf_counter()
        for i in range(n_requests):
            t_next += gaps[i]
            delay = t_next - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            t_a = time.perf_counter()
            try:
                ticket = adm.try_admit("normal")
            except AdmissionError:
                shed_ms.append((time.perf_counter() - t_a) * 1e3)
                continue

            def _rel(f, _t=ticket, _a=adm):
                _a.release(_t, service_ms=f.timings.get("total_ms"))

            fut = b.submit_array(pool[i % len(pool)])
            fut.add_done_callback(_rel)
            admitted.append(fut)
        for f in admitted:
            f.result(timeout=300)
        lats = sorted(f.timings["total_ms"] for f in admitted)
        p50 = _quantile(lats, 0.50)
        p99 = _quantile(lats, 0.99)
        shed_p99 = _quantile(sorted(shed_ms), 0.99) if shed_ms else 0.0
        return {
            "offered_qps": round(offered, 2),
            "admission_depth": depth,
            "admitted": len(admitted),
            "shed": len(shed_ms),
            "admitted_p50_ms": round(p50, 2),
            "admitted_p99_ms": round(p99, 2),
            "admitted_p99_budget_ms": round(budget_ms, 1),
            "shed_decision_p99_ms": round(shed_p99, 4),
            "admission_stats": adm.stats(),
            "ok": bool(
                shed_ms
                and p99 <= budget_ms
                and shed_p99 < p50  # reject in < p50 of service time
            ),
        }
    finally:
        b.close()


def multi_model_arm(engine_a, knobs, pool, arch, image_size, num_classes,
                    n_requests):
    """Two co-resident engines on one host's device budget, concurrent
    closed-loop load on both, per-model p99s on record — a saturated
    neighbour must not take the other model down."""
    from dptpu.serve import ServeEngine

    engine_b = ServeEngine(arch, buckets=(1, 4), num_classes=num_classes,
                           image_size=image_size)
    results, errs = {}, []

    def run(name, engine):
        try:
            results[name] = closed_loop_point(engine, knobs, pool, 2,
                                              n_requests)
        except Exception as e:  # pragma: no cover - surfaced below
            errs.append((name, e))

    threads = [threading.Thread(target=run, args=("a", engine_a)),
               threading.Thread(target=run, args=("b", engine_b))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errs:
        raise RuntimeError(f"multi-model client failed: {errs[0]}")
    return {
        "models": {
            name: {k: p[k] for k in
                   ("requests", "achieved_qps", "p50_ms", "p99_ms")}
            for name, p in results.items()
        },
        "ok": all(p["requests"] == n_requests for p in results.values()),
    }


def canary_rollback_arm(engine, knobs, pool, n_requests=40):
    """Injected ``canary_drift``: stage bit-identical weights that the
    fault perturbs, prove the shadow-eval gate rolls the canary back,
    and that no response was ever computed from a mixed or discarded
    generation."""
    import jax.tree_util as jtu

    from dptpu.resilience.faults import FaultPlan
    from dptpu.serve import DynamicBatcher
    from dptpu.serve.canary import CanaryController

    plan = FaultPlan("canary_drift")
    canary = CanaryController(engine, fraction=0.5,
                              drift_limit=knobs.canary_drift,
                              lat_factor=knobs.canary_lat_factor,
                              fault_plan=plan)
    b = DynamicBatcher(engine, max_delay_ms=0.0, slots=knobs.slots,
                       canary=canary)
    try:
        base = engine.current_generation
        weights = jtu.tree_map(lambda x: np.array(x),
                               engine._weights[base])
        gen = canary.start(weights)
        mixed = served = 0
        for i in range(n_requests):
            f = b.submit_array(pool[i % len(pool)])
            f.result(timeout=300)
            served += 1
            if f.generation not in (base, gen):
                mixed += 1
            canary.drain_evals(timeout=60)
            if canary.status()["state"] == "rolled_back":
                break
        st = canary.status()
        post = b.submit_array(pool[0])
        post.result(timeout=300)
        return {
            "injected_fault": "canary_drift",
            "requests_served": served,
            "state": st["state"],
            "rollbacks": st["rollbacks"],
            "rollback_reason": st["rollback_reason"],
            # an all-params perturbation can push logits to inf; keep
            # the artifact strict-JSON by stringifying non-finite drift
            "max_drift": round(st["max_drift"], 3)
            if np.isfinite(st["max_drift"]) else str(st["max_drift"]),
            "drift_limit": knobs.canary_drift,
            "mixed_generation_responses": mixed,
            "post_rollback_serves_base": post.generation == base,
            "ok": bool(st["state"] == "rolled_back"
                       and st["rollbacks"] == 1
                       and mixed == 0
                       and post.generation == base),
        }
    finally:
        b.close()
        canary.close()


def dead_request_hygiene_arm(engine, knobs, pool):
    """Submit 6 into one coalescing batch, cancel 4: the batch must
    execute at the LIVE count's bucket — the padding-waste accounting
    proves the dead rows occupied zero bucket rows."""
    from dptpu.serve import DynamicBatcher

    b = DynamicBatcher(engine, max_delay_ms=10_000.0, slots=knobs.slots)
    futs = [b.submit_array(pool[i]) for i in range(6)]
    for f in futs[:4]:
        if not f.cancel():
            raise RuntimeError("cancel refused pre-dispatch")
    b.close(drain=True)  # closing dispatches the coalescing batch NOW
    outs = [f.result(timeout=300) for f in futs[4:]]
    s = b.stats()
    live_bucket = engine.bucket_for(2)
    exec_rows = engine.exec_batch(live_bucket)
    claimed_bucket = engine.bucket_for(6)
    waste = (exec_rows - 2) / exec_rows
    return {
        "submitted": 6,
        "cancelled": 4,
        "claimed_bucket": claimed_bucket,
        "dispatched_bucket": futs[4].timings["bucket"],
        "exec_rows": exec_rows,
        "dead_rows": s["dead_rows"],
        "padding_waste": round(s["padding_waste"], 3),
        "ok": bool(
            len(outs) == 2
            and s["dead_rows"] == 4
            and s["batches"] == 1
            and futs[4].timings["bucket"] == live_bucket
            and live_bucket < claimed_bucket
            and abs(s["padding_waste"] - waste) < 1e-9
        ),
    }


def serve_faults_arm(engine, knobs, pool):
    """The serve-side DPTPU_FAULT grammar, each kind proven through the
    real stack: an injected submit exception rejects ONE request, a
    preprocess crash fails alone while its batchmates answer, and a
    slow model is shed by admission instead of blocking the ring."""
    from dptpu.resilience.faults import FaultPlan
    from dptpu.serve import DynamicBatcher
    from dptpu.serve.admission import AdmissionController, AdmissionError
    from dptpu.serve.batcher import ServeError

    results = {}

    b = DynamicBatcher(engine, max_delay_ms=0.0, slots=2,
                       fault_plan=FaultPlan("serve_exception@request=2"))
    try:
        rejected = served = 0
        for i in range(4):
            try:
                f = b.submit_array(pool[i])
            except ServeError:
                rejected += 1
                continue
            f.result(timeout=300)
            served += 1
        results["serve_exception"] = {
            "rejected": rejected, "served": served,
            "ok": rejected == 1 and served == 3,
        }
    finally:
        b.close()

    b = DynamicBatcher(engine, max_delay_ms=100.0, slots=2,
                       fault_plan=FaultPlan("preprocess_crash@request=2"))
    try:
        futs = [b.submit_array(pool[i]) for i in range(4)]
        failed = served = 0
        for f in futs:
            try:
                f.result(timeout=300)
                served += 1
            except ServeError:
                failed += 1
        results["preprocess_crash"] = {
            "failed": failed, "served": served,
            "ok": failed == 1 and served == 3,
        }
    finally:
        b.close()

    b = DynamicBatcher(engine, max_delay_ms=0.0, slots=2,
                       fault_plan=FaultPlan("slow_model:factor=25"))
    adm = AdmissionController(depth=4, name="slow")
    try:
        def _rel(f, _t, _a=adm):
            _a.release(_t, service_ms=f.timings.get("total_ms"))

        # two completions teach the EWMA how slow the model really is
        for i in range(2):
            t = adm.try_admit("normal")
            f = b.submit_array(pool[i])
            f.add_done_callback(lambda g, _t=t: _rel(g, _t))
            f.result(timeout=300)
        # burst without waiting: occupancy crosses the normal mark and
        # sheds in microseconds while batches take a slow-model beat
        shed, shed_ms, held = 0, [], []
        for i in range(8):
            t_a = time.perf_counter()
            try:
                t = adm.try_admit("normal")
            except AdmissionError:
                shed += 1
                shed_ms.append((time.perf_counter() - t_a) * 1e3)
                continue
            f = b.submit_array(pool[i % len(pool)])
            f.add_done_callback(lambda g, _t=t: _rel(g, _t))
            held.append(f)
        for f in held:
            f.result(timeout=300)
        ewma = adm.stats()["service_ewma_ms"]
        results["slow_model"] = {
            "factor": 25, "shed": shed,
            "service_ewma_ms": round(ewma, 1),
            "max_shed_decision_ms": round(max(shed_ms), 4) if shed_ms
            else None,
            "ok": shed > 0 and bool(shed_ms)
            and max(shed_ms) < ewma,
        }
    finally:
        b.close()

    results["ok"] = all(v["ok"] for v in results.values())
    return results


# -- quantized serving + fleet arms (ISSUE 18) ---------------------------


def quantized_serving_arm(engine, knobs, pool, n_requests, workdir,
                          baseline_qps, concurrency):
    """Post-training int8 through the REAL rollout path: calibrate from
    the engine's live fp32 weights (same scales/seal/bounds policy as
    ``dptpu quantize``), roll the artifact out via the canary's
    artifact-armed gate — promotion must be EARNED by the shadow evals,
    not assumed — then measure the promoted generation's closed-loop
    throughput and weight residency against fp32.

    The acceptance lever is throughput >= 1.3x OR resident-bytes cut
    >= 40%. On a CPU host the residency cut is the honest lever: this
    backend has no int8/bf16 gemm kernels (every sub-fp32 dot is
    convert+f32-dot after float normalization), so the compute win is
    a TPU claim — gated STATICALLY by the serve-quant HLO budget row
    (requested dot dtypes + s8 parameter count), not by this arm."""
    from dptpu.ops.quant import tree_nbytes
    from dptpu.serve import DynamicBatcher
    from dptpu.serve.canary import CanaryController
    from dptpu.serve.quant import (measure_drift, quantize_variables,
                                   save_calibration)

    base_gen = engine.current_generation
    sample = np.stack(pool[:8])
    bucket = engine.bucket_for(len(sample))
    nexec = engine.exec_batch(bucket)
    padded = np.concatenate(
        [sample, np.broadcast_to(sample[0],
                                 (nexec - len(sample),) + sample.shape[1:])]
    ) if nexec > len(sample) else sample
    base_logits = engine.run_bucket(bucket, padded, len(sample))

    # calibration: quantize the host fp32 weights, measure drift on the
    # sample through a throwaway staged generation, derive the gate
    # bounds with the CLI's margin policy, seal the artifact
    qvars = quantize_variables(engine._host_variables, "int8")
    tmp_gen = engine.stage_weights(qvars, precision="int8")
    q_logits = engine.run_bucket(bucket, padded, len(sample), gen=tmp_gen)
    engine.discard_staged(tmp_gen)
    agree, drift = measure_drift(base_logits, q_logits)
    bounds = {"max_abs_dlogit": max(drift * 2.0, 1e-3),
              "min_top1_agreement": max(0.5, agree - 0.05)}
    calib = os.path.join(workdir, "servebench-calib.msgpack")
    save_calibration(
        calib, arch=engine.arch, params=engine._host_variables["params"],
        stats={"top1_agreement": agree, "max_abs_dlogit": drift},
        bounds=bounds, num_classes=engine.num_classes,
        image_size=engine.image_size, sample_n=len(sample),
    )

    fp32_bytes = engine.resident_bytes()[base_gen]
    bf16_bytes = tree_nbytes(
        quantize_variables(engine._host_variables, "bf16"))

    # the rollout: canary-gated promotion under the artifact's bounds.
    # min_batches is set in ROWS so the co-resident interference point
    # below runs entirely inside the canary phase (fp32 and int8 both
    # resident and both serving), then the extra submissions afterwards
    # earn the promotion through the same shadow evals.
    canary = CanaryController(engine, fraction=0.5,
                              min_batches=max(n_requests, 20))
    b = DynamicBatcher(engine, max_delay_ms=0.0, slots=knobs.slots,
                       canary=canary)
    try:
        gen = canary.start_quantized(calib, precision="int8")
        int8_bytes = engine.resident_bytes()[gen]

        # co-resident interference: closed-loop through the canary
        # batcher while HALF the batches pin int8 and every int8 batch
        # is shadow-replayed at fp32 — the quantized+fp32-coresident
        # load the multi-model router would see mid-rollout
        done, errs = [], []
        lock = threading.Lock()
        remaining = [n_requests]

        def co_client(tid):
            i = tid
            while True:
                with lock:
                    if remaining[0] <= 0:
                        return
                    remaining[0] -= 1
                try:
                    f = b.submit_array(pool[i % len(pool)])
                    f.result(timeout=300)
                    with lock:
                        done.append(f)
                except Exception as e:  # pragma: no cover
                    with lock:
                        errs.append(e)
                    return
                i += 4

        t0 = time.perf_counter()
        co_threads = [threading.Thread(target=co_client, args=(t,))
                      for t in range(4)]
        for t in co_threads:
            t.start()
        for t in co_threads:
            t.join()
        co_wall = time.perf_counter() - t0
        if errs:
            raise RuntimeError(f"co-resident client failed: {errs[0]}")
        by_gen = {}
        for f in done:
            key = "int8" if f.generation == gen else "fp32"
            by_gen[key] = by_gen.get(key, 0) + 1
        coresident = {
            "requests": len(done),
            "qps": round(len(done) / co_wall, 2),
            "by_generation": by_gen,
            "state_during": canary.status()["state"],
        }

        shadow = len(done)
        for i in range(8 * max(n_requests, 20)):
            b.submit_array(pool[i % len(pool)]).result(timeout=300)
            shadow += 1
            canary.drain_evals(timeout=60)
            if canary.status()["state"] != "canary":
                break
        st = canary.status()
    finally:
        b.close()
        canary.close()
    promoted = st["state"] == "promoted" \
        and engine.generation_precision() == "int8"

    quant_point = None
    speedup = 0.0
    if promoted:
        # default traffic now serves int8: same closed-loop point as
        # the fp32 saturation concurrency, same request pool
        quant_point = closed_loop_point(engine, knobs, pool, concurrency,
                                        n_requests)
        speedup = quant_point["achieved_qps"] / max(baseline_qps, 1e-9)
        # restore fp32 so later arms measure the base configuration
        back = engine.stage_weights(engine._host_variables)
        engine.promote(back)

    residency_cut = 1.0 - int8_bytes / max(fp32_bytes, 1)
    return {
        "calibration": {
            "sample_n": len(sample),
            "top1_agreement": round(agree, 4),
            "max_abs_dlogit": round(drift, 5),
            "bounds": {k: round(v, 5) for k, v in bounds.items()},
        },
        "rollout": {
            "state": st["state"],
            "shadow_requests": shadow,
            "max_drift": round(st["max_drift"], 5),
            "drift_limit": st["drift_limit"],
            "top1_agreement": st["top1_agreement"],
            "top1_floor": st["top1_floor"],
            "rollbacks": st["rollbacks"],
        },
        "coresident": coresident,
        "resident_bytes": {"fp32": fp32_bytes, "bf16": bf16_bytes,
                           "int8": int8_bytes},
        "residency_cut": round(residency_cut, 4),
        "int8_closed_loop": quant_point,
        "fp32_qps": baseline_qps,
        "int8_qps": quant_point["achieved_qps"] if quant_point else None,
        "speedup": round(speedup, 3),
        "lever": ("residency" if residency_cut >= 0.40 else
                  "throughput" if speedup >= 1.3 else "none"),
        "caveat": ("CPU host dequantizes to bf16-requested dots that the "
                   "backend rewrites as f32 — the compute speedup is a "
                   "TPU claim; the HLO budget row serve_quant gates the "
                   "requested dtypes statically"),
        "ok": bool(promoted
                   and drift <= bounds["max_abs_dlogit"]
                   and agree >= bounds["min_top1_agreement"]
                   and (speedup >= 1.3 or residency_cut >= 0.40)),
    }


def fleet_arm(engine, knobs, pool, n_requests, workdir):
    """The multi-host serve fleet, in-process: two member HTTP servers
    (threads sharing this bench's engine — the routing tier is what is
    under measurement, not a second model replica), a FleetRouter
    fronted by fleet-wide admission, closed-loop load through
    ``submit``, then the acceptance scenario: HARD-kill one member
    mid-load (listener closed, heartbeat stopped, NO tombstone — crash
    semantics) and require ZERO failed requests while the router fails
    over in-flight forwards and the staleness verdict auto-drains the
    dead member. The drain curve (healthy-member count over time) is
    on record."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from dptpu import obs
    from dptpu.serve.fleet import FleetMember, FleetRouter

    fleet_dir = os.path.join(workdir, "fleet")
    os.makedirs(fleet_dir, exist_ok=True)
    shape = pool[0].shape

    def _member_server(member_id):
        class H(BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                x = np.frombuffer(self.rfile.read(n),
                                  np.uint8).reshape(shape)
                logits = engine.infer(x[None])
                payload = json.dumps({
                    "member": member_id,
                    "argmax": int(np.argmax(logits[0])),
                }).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def log_message(self, fmt, *args):
                pass

        srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv

    beat_s, stale_s = 0.15, 0.6
    servers = {m: _member_server(m) for m in ("host-a", "host-b")}
    members = {
        m: FleetMember(fleet_dir, host="127.0.0.1",
                       port=srv.server_address[1], member_id=m,
                       heartbeat_s=beat_s)
        for m, srv in servers.items()
    }
    router = FleetRouter(fleet_dir, deadline_s=stale_s, poll_s=0.1,
                         retries=2)
    scalars0 = obs.get_registry().scalars()
    failovers0 = scalars0.get("Fleet/failovers", 0)

    outcomes, errs = [], []
    lock = threading.Lock()
    kill_at = n_requests // 3
    killed = [None]  # [kill wall-clock ts]

    def client(tid, total, t0):
        i = tid
        while True:
            with lock:
                if total[0] <= 0:
                    return
                total[0] -= 1
                seq = n_requests - total[0]
            if seq == kill_at and killed[0] is None:
                # crash host-a: listener gone (transport death for every
                # in-flight and future forward), heartbeat silenced
                # without a tombstone — only staleness can drain it
                servers["host-a"].shutdown()
                servers["host-a"].server_close()
                members["host-a"]._stop.stop()
                killed[0] = time.perf_counter()
            body = pool[i % len(pool)].tobytes()
            try:
                status, data = router.submit("/predict/bench", body)
                with lock:
                    outcomes.append(
                        (time.perf_counter() - t0, status,
                         json.loads(data)["member"]))
            except Exception as e:
                with lock:
                    errs.append(repr(e))
                return
            i += 4

    # warm both member endpoints directly (JSQ with zero load would
    # send consecutive router warms to the same lexicographic-min host)
    import http.client
    for srv in servers.values():
        conn = http.client.HTTPConnection(
            "127.0.0.1", srv.server_address[1], timeout=60)
        conn.request("POST", "/predict/bench", body=pool[0].tobytes())
        assert conn.getresponse().read()
        conn.close()

    curve = []
    stop_sampler = threading.Event()

    def sampler(t0):
        while not stop_sampler.wait(0.05):
            curve.append({"t_s": round(time.perf_counter() - t0, 3),
                          "members": len(router.members())})

    total = [n_requests]
    t0 = time.perf_counter()
    threading.Thread(target=sampler, args=(t0,), daemon=True).start()
    threads = [threading.Thread(target=client, args=(t, total, t0))
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    # the staleness verdict needs one more beat-deadline to land if the
    # load finished fast; wait it out (the sampler keeps running, so the
    # drain curve records the drop either way), then read the route table
    deadline = time.time() + stale_s + 0.5
    while "host-a" in router.members() and time.time() < deadline:
        time.sleep(0.05)
    time.sleep(0.1)  # two sampler periods: the drained state is on record
    stop_sampler.set()
    alive = sorted(router.members())
    drained_after_s = None
    if killed[0] is not None:
        drain_samples = [p["t_s"] for p in curve if p["members"] < 2
                         and p["t_s"] > killed[0] - t0]
        if drain_samples:
            drained_after_s = round(
                drain_samples[0] - (killed[0] - t0), 3)
    failovers = obs.get_registry().scalars().get("Fleet/failovers", 0) \
        - failovers0
    by_member = {}
    for _, _, m in outcomes:
        by_member[m] = by_member.get(m, 0) + 1
    stats = router.stats()
    ready, _ = router.readiness()

    router.close()
    members["host-b"].close()
    servers["host-b"].shutdown()
    servers["host-b"].server_close()

    failed = len(errs) + sum(1 for _, s, _ in outcomes if s != 200)
    return {
        "members": 2,
        "requests": len(outcomes),
        "fleet_qps": round(len(outcomes) / wall, 2),
        "by_member": by_member,
        "killed_member": "host-a",
        "killed_at_request": kill_at,
        "failed_requests": failed,
        "client_errors": errs[:3],
        "failovers": failovers,
        "drains": stats["drains"],
        "drained_after_s": drained_after_s,
        "drain_curve": curve,
        "survivors": alive,
        "ready_after_drain": ready,
        "admission": stats["admission"],
        "ok": bool(failed == 0
                   and len(outcomes) == n_requests
                   and alive == ["host-b"]
                   and failovers >= 1
                   and stats["drains"] >= 1
                   and ready),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI preset: tiny model, short points, same gates")
    ap.add_argument("--arch", default="resnet18")
    ap.add_argument("--image-size", type=int, default=None)
    ap.add_argument("--num-classes", type=int, default=None)
    ap.add_argument("--buckets", default=None,
                    help="bench bucket ladder (default 1,4,16; smoke 1,4,8)")
    ap.add_argument("--max-delay-ms", type=float, default=5.0)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=None,
                    help="requests per load point")
    ap.add_argument("--concurrency", default=None,
                    help="closed-loop client sweep (default 1,2,4,8,16)")
    ap.add_argument("--load-fracs", default=None,
                    help="open-loop offered rates as fractions of "
                         "saturation (default .25,.5,.75,.9,1.2)")
    ap.add_argument("--tail-factor", type=float, default=10.0,
                    help="tail gate: p99 <= factor x p50 at 0.5x sat")
    ap.add_argument("--tail-floor-ms", type=float, default=250.0,
                    help="p99 under this always passes the tail gate")
    ap.add_argument("--no-gate", action="store_true")
    ap.add_argument("--out", default="SERVEBENCH.json")
    args = ap.parse_args()

    image_size = args.image_size or (32 if args.smoke else 64)
    num_classes = args.num_classes or (100 if args.smoke else 1000)
    buckets = args.buckets or ("1,4,8" if args.smoke else "1,4,16")
    n_req = args.requests or (40 if args.smoke else 200)
    conc = [int(c) for c in
            (args.concurrency or ("1,4" if args.smoke else "1,2,4,8,16")
             ).split(",")]
    fracs = [float(f) for f in
             (args.load_fracs or ("0.5,0.9" if args.smoke
                                  else "0.25,0.5,0.75,0.9,1.2")).split(",")]

    import jax

    from dptpu.serve import ServeEngine, serve_knobs
    from dptpu.serve.engine import BUCKET_PARITY_ATOL

    knobs = serve_knobs(buckets=buckets, max_delay_ms=args.max_delay_ms,
                        slots=args.slots)
    t_bench = time.time()
    t0 = time.perf_counter()
    engine = ServeEngine(args.arch, buckets=knobs.buckets,
                         placement=knobs.placement,
                         num_classes=num_classes, image_size=image_size)
    compile_s = time.perf_counter() - t0
    pool = list(np.random.RandomState(0).randint(
        0, 256, (32, image_size, image_size, 3), np.uint8))

    max_dlogit = parity_check(engine, pool)
    preprocess_ms = measure_preprocess(image_size)
    print(f"servebench: {args.arch}@{image_size} buckets "
          f"{list(knobs.buckets)} compiled in {compile_s:.1f}s; "
          f"parity max|dlogit|={max_dlogit:g}, "
          f"preprocess_bytes {preprocess_ms:.1f}ms")

    closed = {}
    for c in conc:
        closed[c] = closed_loop_point(engine, knobs, pool, c, n_req)
        print(f"closed c={c}: {closed[c]['achieved_qps']} qps, "
              f"p50 {closed[c]['p50_ms']}ms p99 {closed[c]['p99_ms']}ms "
              f"buckets {closed[c]['bucket_counts']}")
    saturation_qps = max(p["achieved_qps"] for p in closed.values())
    sat_at = max(closed, key=lambda c: closed[c]["achieved_qps"])

    open_points = {}
    for frac in fracs:
        p = open_loop_point(engine, knobs, pool,
                            max(frac * saturation_qps, 0.5), n_req,
                            seed=int(frac * 100))
        open_points[frac] = p
        print(f"open {frac}x sat ({p['offered_qps']} qps offered): "
              f"{p['achieved_qps']} achieved, p50 {p['p50_ms']}ms "
              f"p99 {p['p99_ms']}ms")

    # tail gate at the 0.5x-saturation point (closest offered frac)
    gate_frac = min(open_points, key=lambda f: abs(f - 0.5))
    gp = open_points[gate_frac]
    tail_budget_ms = max(args.tail_floor_ms,
                         args.tail_factor * gp["p50_ms"])
    gates = {
        "tail_ok": gp["p99_ms"] <= tail_budget_ms,
        "parity_ok": max_dlogit <= BUCKET_PARITY_ATOL,
    }

    # robustness arms (ISSUE 17): overload shedding, co-resident
    # multi-model interference, canary auto-rollback, dead-request
    # hygiene, and the serve-side fault grammar — same engine, same
    # gates in smoke and full runs
    shed = overload_shedding_arm(engine, knobs, pool, saturation_qps,
                                 n_req, budget_ms=2 * tail_budget_ms)
    print(f"overload 2x sat: {shed['admitted']} admitted / "
          f"{shed['shed']} shed, admitted p99 {shed['admitted_p99_ms']}ms"
          f" (budget {shed['admitted_p99_budget_ms']}ms), shed decision "
          f"p99 {shed['shed_decision_p99_ms']}ms")
    mm = multi_model_arm(engine, knobs, pool, args.arch, image_size,
                         num_classes, max(n_req // 2, 10))
    print(f"multi-model: " + ", ".join(
        f"{name} p99 {p['p99_ms']}ms ({p['achieved_qps']} qps)"
        for name, p in mm["models"].items()))
    can = canary_rollback_arm(engine, knobs, pool)
    print(f"canary: {can['state']} after {can['requests_served']} "
          f"requests (drift {can['max_drift']} > {can['drift_limit']}), "
          f"mixed-generation responses {can['mixed_generation_responses']}")
    hyg = dead_request_hygiene_arm(engine, knobs, pool)
    print(f"hygiene: 6 claimed / 4 cancelled -> bucket "
          f"{hyg['dispatched_bucket']} (claimed-count bucket "
          f"{hyg['claimed_bucket']}), padding_waste "
          f"{hyg['padding_waste']}")
    flt = serve_faults_arm(engine, knobs, pool)
    print(f"serve faults: " + ", ".join(
        f"{k}={'ok' if v['ok'] else 'FAIL'}"
        for k, v in flt.items() if k != "ok"))
    gates.update({
        "shed_ok": shed["ok"],
        "multi_model_ok": mm["ok"],
        "canary_ok": can["ok"],
        "hygiene_ok": hyg["ok"],
        "faults_ok": flt["ok"],
    })

    # quantized serving + fleet arms (ISSUE 18): the int8 rollout
    # through the canary's artifact-armed gate, then the routing tier's
    # dead-host acceptance scenario — both in a scratch workdir so the
    # calibration artifact and fleet KV dir never land in the repo
    import tempfile

    with tempfile.TemporaryDirectory(prefix="servebench-") as workdir:
        quant = quantized_serving_arm(engine, knobs, pool, n_req,
                                      workdir, saturation_qps, sat_at)
        print(f"quantized: rollout {quant['rollout']['state']} after "
              f"{quant['rollout']['shadow_requests']} shadow requests, "
              f"drift {quant['calibration']['max_abs_dlogit']} "
              f"(bound {quant['calibration']['bounds']['max_abs_dlogit']})"
              f", residency cut {quant['residency_cut']:.1%}, "
              f"int8 {quant['int8_qps']} qps vs fp32 "
              f"{quant['fp32_qps']} qps, coresident "
              f"{quant['coresident']['qps']} qps "
              f"{quant['coresident']['by_generation']} "
              f"(lever: {quant['lever']})")
        fleet = fleet_arm(engine, knobs, pool, max(n_req, 30), workdir)
        print(f"fleet: {fleet['requests']} requests over "
              f"{fleet['members']} members at {fleet['fleet_qps']} qps, "
              f"killed {fleet['killed_member']} at request "
              f"{fleet['killed_at_request']} -> {fleet['failed_requests']}"
              f" failed, {fleet['failovers']} failovers, drained in "
              f"{fleet['drained_after_s']}s, survivors "
              f"{fleet['survivors']}")
    gates.update({"quant_ok": quant["ok"], "fleet_ok": fleet["ok"]})

    out = {
        "round": 13,
        "what": ("serve latency x offered load (closed + open loop), "
                 "saturation throughput, bucket utilization, tail + "
                 "padded-parity gates, the robustness arms — "
                 "overload shedding, multi-model interference, canary "
                 "auto-rollback, dead-request hygiene, serve faults — "
                 "plus the int8 quantized rollout (calibration artifact "
                 "-> canary-gated promotion -> residency/throughput) "
                 "and the multi-host fleet dead-host drain scenario, "
                 "through ServeEngine+DynamicBatcher+admission+fleet"),
        "smoke": bool(args.smoke),
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "host_cpu_count": os.cpu_count(),
        "caveat": ("2-core CPU host: device forward, dispatch thread "
                   "and clients share cores, so absolute ms are "
                   "pessimistic and the open-loop clock jitters; "
                   "curve SHAPES and gates are the claim (HOSTBENCH "
                   "caveat, serving edition)"),
        "arch": args.arch,
        "image_size": image_size,
        "num_classes": num_classes,
        "buckets": list(knobs.buckets),
        "max_delay_ms": knobs.max_delay_ms,
        "slots": knobs.slots,
        "requests_per_point": n_req,
        "aot_compile_s": round(compile_s, 2),
        "preprocess_bytes_ms": round(preprocess_ms, 2),
        "request_path_note": ("curves use the decode-free submit_array "
                              "path; add preprocess_bytes_ms for the "
                              "bytes ingress path"),
        "parity_max_abs_dlogit": max_dlogit,
        "closed_loop": {str(c): p for c, p in closed.items()},
        "saturation_qps": saturation_qps,
        "saturation_concurrency": sat_at,
        "open_loop": {str(f): p for f, p in open_points.items()},
        "tail_gate": {
            "at_offered_frac": gate_frac,
            "p50_ms": gp["p50_ms"],
            "p99_ms": gp["p99_ms"],
            "budget_ms": round(tail_budget_ms, 1),
            "factor": args.tail_factor,
            "floor_ms": args.tail_floor_ms,
        },
        "robustness": {
            "overload_shedding": shed,
            "multi_model": mm,
            "canary_rollback": can,
            "dead_request_hygiene": hyg,
            "serve_faults": flt,
        },
        "quantized": quant,
        "fleet": fleet,
        "gates": gates,
        "bench_wall_s": round(time.time() - t_bench, 1),
    }
    from bench_util import host_provenance

    out["host"] = host_provenance()
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"saturation_qps": saturation_qps,
                      "tail_gate": out["tail_gate"], "gates": gates}))
    print(f"wrote {args.out}")
    if not args.no_gate and not all(gates.values()):
        print(f"SERVEBENCH gate FAILED: {gates}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
