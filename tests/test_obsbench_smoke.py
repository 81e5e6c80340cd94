"""Tier-1-adjacent smoke of scripts/run_obsbench.py: the tracer's
overhead/coverage/trigger gates are continuously checked, not just on
the bench host. One subprocess, smallest preset, same gate logic."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_obsbench_smoke_gates(tmp_path):
    out = str(tmp_path / "OBSBENCH.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # run the bench on the REAL single-CPU topology: the fake 8-device
    # pod the test harness forces (conftest XLA_FLAGS) would route the
    # subprocess into the shard_map DDP step, which fails its
    # replication check under this container's jax (pre-existing at the
    # seed — ROADMAP resilience follow-on (d)); the tracer gates being
    # smoked here are topology-independent
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    # the smallest honest run: 2 interleaved off/on pairs + trigger run.
    # One retry: with reps=2 the off arms can TIE exactly (rates round
    # to 0.1 img/s), collapsing the noise-widening to zero right when a
    # 1-CPU host drifts — seen once in-suite at 10% phantom overhead
    # with off-arm spread 0.0; two consecutive failing benches are a
    # real regression, one unlucky window is not
    for attempt in (0, 1):
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "run_obsbench.py"),
             "--smoke", "--images", "256", "--batch", "32", "--epochs",
             "2", "--reps", "2", "--out", out],
            capture_output=True, text=True, timeout=480, env=env,
            cwd=str(tmp_path),
        )
        if proc.returncode == 0:
            break
    assert proc.returncode == 0, (
        f"obsbench gate failed twice\nstdout:\n{proc.stdout[-4000:]}\n"
        f"stderr:\n{proc.stderr[-4000:]}"
    )
    with open(out) as f:
        bench = json.load(f)
    # coverage gate: attribution accounts for >= 95% of epoch wall time
    assert bench["attribution_coverage"] >= 0.95
    attr = bench["attribution"]
    accounted = (attr["data_wait_s"] + attr["h2d_s"] + attr["device_s"]
                 + attr["ckpt_s"] + attr["compile_s"])
    assert accounted + attr["other_s"] == \
        __import__("pytest").approx(attr["wall_s"], rel=0.02)
    # overhead gate: the drift-hardened form — overhead is the MEDIAN
    # of per-rep paired (off-on)/off deltas, pairs run in ABBA order
    # (adjacent pairs cancel between-pair drift; the alternating order
    # cancels monotonic drift, which a fixed order converts into a
    # phantom consistent overhead) and the gate widens to the measured
    # noise floor (off-arm spread AND paired-delta spread), so the
    # gate holds both in isolation and under full-suite load on a
    # drifting host
    assert bench["gates"]["overhead_ok"], bench
    assert len(bench["paired_deltas_pct"]) == bench["reps"]
    assert bench["effective_gate_pct"] >= bench["gate_pct"]
    assert bench["effective_gate_pct"] >= bench["paired_spread_pct"]
    # the live sentinel trigger captured an in-flight window and wrote
    # the merged attribution report — without restarting the run
    assert bench["ondemand_trigger"]["captured"], bench["ondemand_trigger"]
    rep = bench["ondemand_trigger"]["report"]
    assert rep["steps"] == 4 and "host_phases_s" in rep
    # device attribution when the backend exports device tracks, an
    # explained degradation otherwise — never a silent zero
    assert ("device_ms_per_step" in rep) or ("device_trace_error" in rep)
