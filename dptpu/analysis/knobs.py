"""The declared ``DPTPU_*`` knob registry — the knob-contract rule's
source of truth.

Every ``DPTPU_*`` name the code reads MUST have an entry here, and every
non-internal entry MUST appear in README's knob docs (the knob-contract
lint enforces both directions, so a knob can no longer ship undocumented
the way DPTPU_SERVE_SLOTS / DPTPU_NO_LHS did before ISSUE 12). ``kind``
names the envknob helper that parses the value — the fail-fast contract
(dptpu/envknob.py) is what makes a typo'd knob raise instead of silently
falling back.

``internal=True`` marks child-process sentinels the bench drivers set
for their own subprocesses (never user-facing, so README documentation
would be noise); the registry entry still declares them so the lint can
tell a sentinel from a typo'd knob.
"""

from __future__ import annotations


def _k(kind: str, area: str, internal: bool = False) -> dict:
    return {"kind": kind, "area": area, "internal": internal}


# name -> {"kind": envknob parser, "area": owning subsystem, "internal"}
KNOB_REGISTRY = {
    # train / optimizer recipe
    "DPTPU_OPT": _k("choice", "train"),
    "DPTPU_ACCUM": _k("int", "train"),
    "DPTPU_WARMUP_EPOCHS": _k("int", "train"),
    "DPTPU_WARMUP_POLY": _k("float", "train"),
    "DPTPU_BATCH_RAMP": _k("str", "train"),
    "DPTPU_DIST_EVAL": _k("bool", "train"),
    "DPTPU_LABEL_SMOOTH": _k("float", "train"),
    "DPTPU_NO_LHS": _k("bool", "train"),
    "DPTPU_PROFILE": _k("str", "train"),
    "DPTPU_ASYNC_CKPT": _k("bool", "train"),
    "DPTPU_PRETRAINED_DIR": _k("str", "models"),
    # parallelism
    "DPTPU_TP": _k("int", "parallel"),
    "DPTPU_SP": _k("int", "parallel"),
    "DPTPU_SP_MODE": _k("choice", "parallel"),
    "DPTPU_ZERO1": _k("bool", "parallel"),
    "DPTPU_ZERO": _k("int", "parallel"),
    "DPTPU_FSDP": _k("bool", "parallel"),
    "DPTPU_RULES": _k("choice", "parallel"),
    "DPTPU_GSPMD": _k("bool", "parallel"),
    "DPTPU_SLICES": _k("int", "parallel"),
    "DPTPU_DCN_DTYPE": _k("choice", "parallel"),
    "DPTPU_OVERLAP": _k("bool", "parallel"),
    "DPTPU_BUCKET_MB": _k("float", "parallel"),
    "DPTPU_RENDEZVOUS_TIMEOUT": _k("int", "parallel"),
    # data plane
    "DPTPU_WORKERS_MODE": _k("choice", "data"),
    "DPTPU_CACHE_BYTES": _k("int", "data"),
    "DPTPU_CACHE_SCOPE": _k("choice", "data"),
    "DPTPU_LEASE": _k("bool", "data"),
    "DPTPU_LEASE_DEPTH": _k("int", "data"),
    "DPTPU_RING_DEPTH": _k("int", "data"),
    "DPTPU_DECODE_AHEAD": _k("int", "data"),
    "DPTPU_SPECULATE": _k("bool", "data"),
    "DPTPU_READAHEAD": _k("bool", "data"),
    "DPTPU_SPAN_AFFINITY": _k("bool", "data"),
    "DPTPU_SPAN_RETRIES": _k("int", "data"),
    "DPTPU_POOL_RESTARTS": _k("int", "data"),
    "DPTPU_WORKER_TIMEOUT_S": _k("float", "data"),
    "DPTPU_SHARD_LOCALITY": _k("bool", "data"),
    "DPTPU_SHARD_CACHE_BYTES": _k("int", "data"),
    "DPTPU_ODIRECT": _k("bool", "data"),
    "DPTPU_STORE_FETCH": _k("choice", "data"),
    "DPTPU_STORE_RETRIES": _k("int", "data"),
    "DPTPU_STORE_BACKOFF_S": _k("float", "data"),
    # resilience
    "DPTPU_FAULT": _k("str", "resilience"),
    "DPTPU_FAULT_SEED": _k("int", "resilience"),
    "DPTPU_ELASTIC": _k("bool", "resilience"),
    "DPTPU_QUORUM_DIR": _k("str", "resilience"),
    "DPTPU_QUORUM_DEADLINE_S": _k("float", "resilience"),
    "DPTPU_STRAGGLER_FACTOR": _k("float", "resilience"),
    "DPTPU_STRAGGLER_PERSIST": _k("int", "resilience"),
    # observability
    "DPTPU_OBS": _k("bool", "obs"),
    "DPTPU_OBS_RING": _k("int", "obs"),
    "DPTPU_OBS_DIR": _k("str", "obs"),
    "DPTPU_OBS_TRACE_STEPS": _k("int", "obs"),
    "DPTPU_OBS_TRIGGER": _k("str", "obs"),
    "DPTPU_OBS_ANOMALY": _k("float", "obs"),
    # serving
    "DPTPU_SERVE_BUCKETS": _k("str", "serve"),
    "DPTPU_SERVE_MAX_DELAY_MS": _k("float", "serve"),
    "DPTPU_SERVE_PLACEMENT": _k("choice", "serve"),
    "DPTPU_SERVE_SLOTS": _k("int", "serve"),
    "DPTPU_SERVE_QUEUE_DEPTH": _k("int", "serve"),
    "DPTPU_SERVE_PRIORITIES": _k("str", "serve"),
    "DPTPU_SERVE_DEADLINE_MS": _k("float", "serve"),
    "DPTPU_SERVE_CANARY_FRACTION": _k("float", "serve"),
    "DPTPU_SERVE_CANARY_DRIFT": _k("float", "serve"),
    "DPTPU_SERVE_CANARY_LAT_FACTOR": _k("float", "serve"),
    # quantized serving
    "DPTPU_QUANT_PRECISION": _k("choice", "serve"),
    "DPTPU_QUANT_CALIB": _k("str", "serve"),
    "DPTPU_QUANT_DRIFT": _k("float", "serve"),
    "DPTPU_QUANT_TOP1_MIN": _k("float", "serve"),
    # serve fleet
    "DPTPU_FLEET_DIR": _k("str", "serve"),
    "DPTPU_FLEET_HEARTBEAT_S": _k("float", "serve"),
    "DPTPU_FLEET_DEADLINE_S": _k("float", "serve"),
    "DPTPU_FLEET_RETRIES": _k("int", "serve"),
    # self-tuning control plane (dptpu/tune)
    "DPTPU_TUNE_ARTIFACT": _k("str", "tune"),
    "DPTPU_TUNE_CONTROL": _k("str", "tune"),
    "DPTPU_TUNE_INTERVAL_S": _k("float", "tune"),
    # analysis / sanitizers
    "DPTPU_SYNC_CHECK": _k("bool", "analysis"),
    # bench-driver child sentinels (subprocess re-entry guards)
    "DPTPU_NUMERICS_CHILD": _k("str", "bench", internal=True),
    "DPTPU_SCALEBENCH_CHILD": _k("str", "bench", internal=True),
    "DPTPU_COMMBENCH_CHILD": _k("str", "bench", internal=True),
    "DPTPU_RACEBENCH_CHILD": _k("str", "bench", internal=True),
}


def knob_census() -> dict:
    """Registry summary for ANALYSIS.json."""
    internal = sorted(k for k, v in KNOB_REGISTRY.items() if v["internal"])
    return {
        "declared": len(KNOB_REGISTRY),
        "internal": internal,
        "by_area": {
            area: sorted(
                k for k, v in KNOB_REGISTRY.items() if v["area"] == area
            )
            for area in sorted({v["area"] for v in KNOB_REGISTRY.values()})
        },
    }
