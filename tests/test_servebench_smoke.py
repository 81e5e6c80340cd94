"""Tier-1 smoke of scripts/run_servebench.py (the pattern of
test_obsbench_smoke.py): the serving stack's latency/throughput curves,
bucket accounting, padded-parity gate, tail gate and the ISSUE 17
robustness arms (overload shedding, multi-model, canary auto-rollback,
dead-request hygiene, serve faults) are continuously checked — one
subprocess, smallest preset, same gate logic as the committed
SERVEBENCH.json."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_servebench_smoke_gates(tmp_path):
    out = str(tmp_path / "SERVEBENCH.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # real single-CPU topology, like the obsbench smoke: the fake
    # 8-device pod the conftest forces is a training-suite fixture; the
    # serving gates being smoked are topology-independent
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "run_servebench.py"),
         "--smoke", "--out", out],
        capture_output=True, text=True, timeout=480, env=env,
        cwd=str(tmp_path),
    )
    assert proc.returncode == 0, (
        f"servebench gate failed\nstdout:\n{proc.stdout[-4000:]}\n"
        f"stderr:\n{proc.stderr[-4000:]}"
    )
    with open(out) as f:
        bench = json.load(f)
    # the acceptance contract: padded-bucket serving is logit-identical
    # to the single-request path, EXACTLY
    assert bench["parity_max_abs_dlogit"] <= 1e-5  # BUCKET_PARITY_ATOL
    assert bench["gates"]["parity_ok"] and bench["gates"]["tail_ok"]
    # both load models produced complete points
    for point in list(bench["closed_loop"].values()) \
            + list(bench["open_loop"].values()):
        assert point["requests"] > 0
        assert point["p50_ms"] <= point["p99_ms"] <= point["max_ms"]
        # every dispatched batch is accounted to a configured bucket
        assert all(int(b) in bench["buckets"]
                   for b in point["bucket_counts"])
        assert 0.0 <= point["padding_waste"] < 1.0
    # open-loop points record what was offered (the load model's knob)
    assert all("offered_qps" in p for p in bench["open_loop"].values())
    assert bench["saturation_qps"] > 0
    # the tail gate is evaluated at the SLO-typical 0.5x-saturation point
    assert bench["tail_gate"]["at_offered_frac"] == 0.5
    assert bench["tail_gate"]["p99_ms"] <= bench["tail_gate"]["budget_ms"]
    # robustness arms (ISSUE 17), all gated
    g = bench["gates"]
    assert g["shed_ok"] and g["multi_model_ok"] and g["canary_ok"]
    assert g["hygiene_ok"] and g["faults_ok"]
    rb = bench["robustness"]
    # overload: 2x saturation through admission actually shed, admitted
    # p99 stayed bounded, and every shed decision beat a service time
    shed = rb["overload_shedding"]
    assert shed["shed"] > 0 and shed["admitted"] > 0
    assert shed["admitted_p99_ms"] <= shed["admitted_p99_budget_ms"]
    assert shed["shed_decision_p99_ms"] < shed["admitted_p50_ms"]
    # multi-model: two co-resident engines both completed under
    # concurrent load, per-model p99s on record
    mm = rb["multi_model"]["models"]
    assert set(mm) == {"a", "b"}
    assert all(m["p99_ms"] > 0 and m["requests"] > 0 for m in mm.values())
    # canary: the injected drift triggered EXACTLY one loud rollback and
    # no response ever mixed generations
    can = rb["canary_rollback"]
    assert can["state"] == "rolled_back" and can["rollbacks"] == 1
    assert can["mixed_generation_responses"] == 0
    assert can["post_rollback_serves_base"]
    assert "ROLLED BACK" in proc.stderr
    # hygiene: 4 cancelled of 6 claimed -> dispatched at the LIVE
    # count's bucket; padding-waste accounting proves zero dead rows
    hyg = rb["dead_request_hygiene"]
    assert hyg["dead_rows"] == 4
    assert hyg["dispatched_bucket"] < hyg["claimed_bucket"]
    # every serve fault scenario green
    flt = rb["serve_faults"]
    assert flt["serve_exception"]["ok"]
    assert flt["preprocess_crash"]["ok"]
    assert flt["slow_model"]["ok"]
    # quantized arm (ISSUE 18): the int8 rollout PROMOTED through the
    # canary's artifact-armed gate (never assumed), measured drift sits
    # inside the artifact's own bounds, and the acceptance lever held —
    # on this CPU host that is the >= 40% resident-bytes cut (compute
    # speedup is a TPU claim, gated statically by the serve-quant HLO
    # budget row)
    assert g["quant_ok"]
    quant = bench["quantized"]
    assert quant["rollout"]["state"] == "promoted"
    assert quant["rollout"]["rollbacks"] == 0
    cal = quant["calibration"]
    assert cal["max_abs_dlogit"] <= cal["bounds"]["max_abs_dlogit"]
    assert cal["top1_agreement"] >= cal["bounds"]["min_top1_agreement"]
    rb_bytes = quant["resident_bytes"]
    assert rb_bytes["int8"] < rb_bytes["bf16"] < rb_bytes["fp32"]
    assert quant["residency_cut"] >= 0.40 or quant["speedup"] >= 1.3
    # the co-resident interference point ran with BOTH generations
    # serving (the deterministic 0.5-fraction pick guarantees both)
    co = quant["coresident"]
    assert co["requests"] > 0 and co["qps"] > 0
    assert set(co["by_generation"]) == {"fp32", "int8"}
    # fleet arm (ISSUE 18): hard-killing one of two member hosts
    # mid-load lost ZERO requests — the router failed over in-flight
    # forwards and the staleness verdict auto-drained the corpse
    assert g["fleet_ok"]
    fleet = bench["fleet"]
    assert fleet["failed_requests"] == 0 and not fleet["client_errors"]
    assert fleet["requests"] > fleet["killed_at_request"]
    assert fleet["failovers"] >= 1 and fleet["drains"] >= 1
    assert fleet["survivors"] == ["host-b"]
    assert fleet["ready_after_drain"]
    # the drain curve recorded the member count dropping to 1
    assert any(p["members"] == 1 for p in fleet["drain_curve"])
    assert "DRAINED member host-a" in proc.stderr
