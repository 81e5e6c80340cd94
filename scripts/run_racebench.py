#!/usr/bin/env python3
"""Time-to-accuracy race harness → RACEBENCH.json (+ the ``minutes``
recipe merged into CONVERGENCE.json).

The ImageNet-in-minutes systems (PAPERS.md: arXiv:1711.04325,
1711.00705, 1811.05233, 1903.12650) win on two axes this repo now owns
end to end: a step architecture whose gradient communication OVERLAPS
backward compute (``DPTPU_OVERLAP=1``, dptpu/parallel/overlap.py), and
a recipe — LARS + batch ramp + polynomial warmup + distributed eval —
that converges at the resulting giant batches. This bench locks both:

1. **Parity** — the overlap engine is a pure regrouping: 5 real steps
   of the bucketed hierarchical step are params-Δ=0 against the
   unbucketed step (and ZeRO-1 × overlap likewise, full mode). The
   same contract COMMBENCH and tests/test_overlap.py gate.

2. **Simulated-pod wall-clock model** — virtual CPU devices share one
   memory bus, so the overlap win CANNOT appear as local wall clock
   (the PARALLELISM.md honesty note). Instead the model combines what
   IS measurable here with what is analytic:

   * measured: the real compiled step's compute time (fwd + bwd +
     update) on this host, split per bucket in proportion to bucket
     bytes (recorded assumption: backward FLOPs track parameter
     count);
   * analytic: per-bucket DCN time = ``2(S-1)/S · bytes/I / BW + L``
     (ring all-reduce of the ICI-scattered shard across slices at
     ``--dcn-gbps`` with ``--dcn-latency-us`` per collective);
   * simulated: a bucket's reduction may start once its backward
     segment finished AND the (serial, FIFO) DCN channel is free —
     reverse-layer order, exactly the engine's issue order. Serial =
     all compute, then all communication (today's step). Per-leaf =
     the pre-overlap transport: one collective per parameter leaf,
     latency-dominated.

   Gates: ``overlapped < serial`` at the modeled bandwidth, and
   ``bucketed per-leaf transport < per-leaf`` (the latency
   amortization), swept over bucket sizes × bandwidths so the
   crossover is on record.

3. **``--recipe minutes``** (full mode) — the composed recipe run
   through the REAL fit() path on the deterministic 10-class proxy
   (scripts/run_convergence.py's dataset): LARS, polynomial warmup,
   batch ramp mid-run (loader + step rebuilt, LR rescaled, geometry
   re-stamped), distributed eval, overlap armed. Merged into
   CONVERGENCE.json under ``minutes`` with a WALL-CLOCK-to-top1 curve
   (per-epoch wall from the run's own meters, normalized to the
   measured total), gated on the shared TOP1 bar.

Usage: python scripts/run_racebench.py [--smoke] [--recipe minutes]
       [--arch resnet18] [--slices 2] [--chips-per-slice 2]
       [--bucket-mb 1 8 25] [--dcn-gbps 25] [--dcn-latency-us 15]
       [--out RACEBENCH.json]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

from bench_util import ensure_cpu_pool  # noqa: E402

_CHILD_ENV = "DPTPU_RACEBENCH_CHILD"

TOP1_BAR = 80.0  # the shared convergence bar (scripts/run_convergence.py)


# the wall-clock model itself lives in dptpu/tune/costmodel.py since
# ISSUE 19 (the autotuner scores candidates against the same model);
# tests/test_tune_costmodel.py locks the extraction against the
# committed RACEBENCH.json rows
from dptpu.tune.costmodel import model_row, simulate_pod  # noqa: E402,F401


def run_minutes_recipe(args, repo_root):
    """The composed extreme-scale recipe through the real fit() path;
    returns the CONVERGENCE ``minutes`` section."""
    import tempfile

    from run_convergence import make_dataset

    import jax

    from dptpu.config import Config
    from dptpu.train import fit

    data = tempfile.mkdtemp(prefix="dptpu_racebench_data_")
    make_dataset(data, seed=0)
    ckpt = tempfile.mkdtemp(prefix="dptpu_racebench_ckpt_")
    cwd = os.getcwd()
    os.chdir(ckpt)

    recipe_env = {
        "DPTPU_OVERLAP": "1",
        "DPTPU_BATCH_RAMP": "6:2",       # double the batch once stable
        "DPTPU_WARMUP_POLY": "2",        # 1811.05233's polynomial ramp
        "DPTPU_DIST_EVAL": "1",          # sharded val for every variant
    }
    saved = {k: os.environ.get(k) for k in recipe_env}
    os.environ.update(recipe_env)
    try:
        # the apex variant reads -b PER DEVICE: divide the recipe's
        # base global batch of 256 over however many (virtual) chips
        # this run sees, so the linear-scaled peak LR is geometry-free
        per_device = max(256 // jax.device_count(), 2)
        cfg = Config(
            data=data,
            arch="resnet18",
            epochs=args.recipe_epochs,
            batch_size=per_device,
            # apex linear scaling: peak 3.0 at the base global batch
            # of 256, 6.0 after the ramp (the rule extends per phase)
            lr=3.0,
            momentum=0.9,
            weight_decay=1e-4,
            workers=8,
            print_freq=50,
            seed=args.seed,
            variant="apex",
            opt_level="O0",
            dist_url="env://",
            optimizer="lars",
            accum_steps=2,
            warmup_epochs=2,
            label_smoothing=0.1,
        )
        t0 = time.time()
        result = fit(cfg, image_size=32, verbose=False)
        wall = time.time() - t0
    finally:
        os.chdir(cwd)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        import shutil

        shutil.rmtree(data, ignore_errors=True)
        shutil.rmtree(ckpt, ignore_errors=True)

    # wall-clock-to-top1 axis: per-epoch wall from the run's own
    # meters (train batch_time x batches + val batch_time x batches),
    # normalized so the curve's total equals the measured fit() wall —
    # the normalization factor is on record
    raw = []
    for h in result["history"]:
        t = (h["train_batch_time"] * h["train_num_batches"]
             + h["val_batch_time"] * max(h["val_count"] / 256.0, 1.0))
        raw.append(t)
    scale = wall / max(sum(raw), 1e-9)
    curve, acc = [], 0.0
    for h, t in zip(result["history"], raw):
        acc += t * scale
        curve.append({"wall_s": round(acc, 2),
                      "top1": round(h["val_top1"], 2)})
    best = result["best_acc1"]
    to_bar = next((c["wall_s"] for c in curve if c["top1"] >= TOP1_BAR),
                  None)
    return {
        "recipe": {
            "optimizer": "lars",
            "warmup_epochs": 2,
            "warmup_poly": 2.0,
            "batch_ramp": "6:2",
            "base_global_batch": 256,
            "ramped_global_batch": 512,
            "accum_steps": 2,
            "label_smoothing": 0.1,
            "peak_lr_base": 3.0,
            "overlap": True,
            "dist_eval": True,
            "dtype": "float32",
        },
        "epochs": args.recipe_epochs,
        "best_top1": best,
        "final_top1": result["history"][-1]["val_top1"],
        "top1_bar": TOP1_BAR,
        "wall_seconds": round(wall, 1),
        "wall_to_top1": curve,
        "wall_normalization": round(scale, 4),
        "seconds_to_bar": to_bar,
        "batch_ramp_record": result.get("batch_ramp"),
        "device": str(jax.devices()[0].device_kind),
        "backend": jax.default_backend(),
        "pass": bool(best >= TOP1_BAR
                     and result.get("batch_ramp") is not None
                     and len(result["batch_ramp"]) >= 2),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="resnet18")
    ap.add_argument("--image", type=int, default=32)
    ap.add_argument("--slices", type=int, default=2)
    ap.add_argument("--chips-per-slice", type=int, default=2)
    ap.add_argument("--per-chip-batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--time-reps", type=int, default=6)
    ap.add_argument("--bucket-mb", type=float, nargs="+",
                    default=[1.0, 8.0, 25.0])
    ap.add_argument("--dcn-gbps", type=float, nargs="+",
                    default=[12.5, 25.0, 100.0],
                    help="modeled per-chip DCN bandwidths (GB/s); the "
                         "first is the headline gate's")
    ap.add_argument("--dcn-latency-us", type=float, default=15.0)
    ap.add_argument("--chip-img-per-s", type=float, default=2734.0,
                    help="one-chip step rate anchoring the "
                         "chip-equivalent compute rows (2734 img/s/chip "
                         "was measured before PR 1; not re-measured on "
                         "the current code, PERF.md)")
    ap.add_argument("--smoke", action="store_true",
                    help="gates only: one bucket size, no ZeRO-1 arm, "
                         "no recipe run (the tier-1 preset)")
    ap.add_argument("--recipe", choices=("none", "minutes"),
                    default=None,
                    help="default: minutes in full mode, none in "
                         "--smoke")
    ap.add_argument("--recipe-epochs", type=int, default=12)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default="RACEBENCH.json")
    args = ap.parse_args()
    S, I = args.slices, args.chips_per_slice
    N = S * I
    if args.smoke:
        args.bucket_mb = args.bucket_mb[:1]
    if args.recipe is None:
        args.recipe = "none" if args.smoke else "minutes"
    ensure_cpu_pool(N, _CHILD_ENV)

    import jax

    from dptpu.models import create_model
    from dptpu.parallel import (
        gather_state,
        make_hierarchical_mesh,
        make_zero1_train_step,
        replicated_sharding,
        shard_host_batch,
        shard_zero1_state,
    )
    from dptpu.parallel.hlo_accounting import overlap_evidence
    from dptpu.parallel.overlap import bucket_sizes_bytes, partition_buckets
    from dptpu.train import create_train_state, make_optimizer, make_train_step

    devs = jax.devices()[:N]
    mesh = make_hierarchical_mesh(S, devs)
    model = create_model(args.arch, num_classes=16)
    tx = make_optimizer(0.9, 1e-4)

    def fresh_state():
        return create_train_state(
            jax.random.PRNGKey(0), model, tx,
            input_shape=(1, args.image, args.image, 3),
        )

    rng = np.random.RandomState(0)
    batches = [
        {
            "images": rng.randint(
                0, 256, (args.per_chip_batch * N, args.image, args.image, 3)
            ).astype(np.uint8),
            "labels": rng.randint(
                0, 16, (args.per_chip_batch * N,)
            ).astype(np.int32),
        }
        for _ in range(args.steps)
    ]

    def run_arm(compiled, steps, zero1=False):
        st = fresh_state()
        st = shard_zero1_state(st, mesh) if zero1 else \
            jax.tree_util.tree_map(
                lambda x: jax.device_put(x, replicated_sharding(mesh)), st
            )
        for k in range(steps):
            st, _m = compiled(st, shard_host_batch(batches[k], mesh))
        if zero1:
            st = gather_state(st, mesh)
        return jax.device_get(st.params)

    def max_abs_diff(a, b):
        return max(
            float(np.abs(np.asarray(x) - np.asarray(y)).max())
            for x, y in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b))
        )

    print(f"=> compiling {args.arch}@{args.image} on {S}x{I}: serial + "
          f"{len(args.bucket_mb)} overlap arm(s)", file=sys.stderr)
    serial_step = make_train_step(mesh)
    overlap_steps = {
        mb: make_train_step(mesh, overlap=True,
                            bucket_bytes=int(mb * 1e6))
        for mb in args.bucket_mb
    }
    # ONE compile serves timing and parity; evidence parses its text
    b0 = shard_host_batch(batches[0], mesh)
    sharded0 = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, replicated_sharding(mesh)),
        fresh_state(),
    )
    serial_c = serial_step.lower(sharded0, b0).compile()
    evidence = {}
    overlap_c = {}
    for mb, stp in overlap_steps.items():
        sh = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, replicated_sharding(mesh)),
            fresh_state(),
        )
        lowered = stp.lower(sh, b0)
        c = lowered.compile()
        overlap_c[mb] = c
        evidence[str(mb)] = overlap_evidence(c.as_text())

    # ---- parity gates ------------------------------------------------
    params_serial = run_arm(serial_c, args.steps)
    parity = {"steps": args.steps}
    for mb, c in overlap_c.items():
        parity[f"overlap_{mb}mb_max_delta"] = max_abs_diff(
            run_arm(c, args.steps), params_serial
        )
    parity_ok = all(
        v == 0.0 for k, v in parity.items() if k.endswith("_max_delta")
    )
    if not args.smoke:
        from functools import partial

        def z(overlap):
            st = fresh_state()
            return make_zero1_train_step(
                mesh, st,
                tx_factory=partial(make_optimizer, 0.9, 1e-4, "sgd"),
                overlap=overlap,
                bucket_bytes=int(args.bucket_mb[0] * 1e6),
            ).lower(
                shard_zero1_state(st, mesh), b0
            ).compile()

        zd = max_abs_diff(run_arm(z(True), args.steps, zero1=True),
                          run_arm(z(False), args.steps, zero1=True))
        parity["zero1_overlap_max_delta"] = zd
        parity_ok = parity_ok and zd == 0.0

    # ---- measured compute -------------------------------------------
    def time_compiled(c):
        st = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, replicated_sharding(mesh)),
            fresh_state(),
        )
        st, m = c(st, b0)  # warm
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(args.time_reps):
            st, m = c(st, b0)
        jax.block_until_ready(m["loss"])
        return (time.perf_counter() - t0) / args.time_reps

    t_step = time_compiled(serial_c)
    t_overlap_local = {str(mb): round(time_compiled(c) * 1e3, 2)
                       for mb, c in overlap_c.items()}

    # ---- the simulated-pod model ------------------------------------
    params = fresh_state().params
    leaves = jax.tree_util.tree_leaves(params)
    grad_bytes = sum(
        int(np.prod(l.shape)) * 4 if l.shape else 4 for l in leaves
    )
    latency_s = args.dcn_latency_us * 1e-6
    # two compute anchors: this host's measured step (compute ~50-100x
    # a real chip's, so the comm/compute ratio — and with it the
    # overlap win — is badly UNDERSTATED), and the chip-equivalent
    # step time from --chip-img-per-s, which is the regime the race
    # actually runs in
    t_chip = args.per_chip_batch / args.chip_img_per_s
    perleaf_sizes = [int(np.prod(l.shape)) * 4 if l.shape else 4
                     for l in reversed(leaves)]
    model_rows = []
    for anchor, t_compute in (("measured_host", t_step),
                              ("chip_equivalent", t_chip)):
        for mb in args.bucket_mb:
            buckets = partition_buckets(params, int(mb * 1e6))
            sizes = bucket_sizes_bytes(params, buckets)
            for bw in args.dcn_gbps:
                model_rows.append(model_row(
                    anchor, t_compute, mb, sizes, perleaf_sizes,
                    bw, latency_s, S, I,
                ))
    # headline: the chip-equivalent regime at the first bandwidth and
    # bucket size. overlapped < serial is trivially true for any
    # multi-bucket partition, so the gate binds on the hidden-comm
    # fraction: the pipeline must hide at least half the communication
    # at the headline point (measured: > 0.9)
    head = next(r for r in model_rows
                if r["compute_anchor"] == "chip_equivalent")
    host_head = model_rows[0]
    overlap_win = (head["overlapped_ms"] < head["serial_ms"]
                   and head["hidden_comm_fraction"] >= 0.5
                   and host_head["overlapped_ms"]
                   < host_head["serial_ms"])
    bucket_win = head["serial_ms"] < head["perleaf_serial_ms"]

    report = {
        "bench": "time-to-accuracy race harness (scripts/run_racebench.py)",
        "arch": args.arch,
        "image": args.image,
        "slices": S,
        "chips_per_slice": I,
        "per_chip_batch": args.per_chip_batch,
        "backend": jax.default_backend(),
        "grad_bytes": grad_bytes,
        "param_leaves": len(leaves),
        "measured_step_s": round(t_step, 4),
        "overlap_local_step_ms": t_overlap_local,
        "local_caveat": (
            "virtual CPU devices share one memory bus: the local "
            "overlap-arm step times CANNOT show the overlap win (the "
            "'network' is a memcpy) and are recorded only to show the "
            "bucketing machinery costs ~nothing locally. The win is "
            "the simulated-pod model + the HLO schedule evidence."
        ),
        "model_assumptions": {
            "compute_split": "per-bucket backward compute proportional "
                             "to bucket bytes (FLOPs track parameter "
                             "count)",
            "dcn_time": "2(S-1)/S x (bucket_bytes/I) / BW + latency "
                        "per collective; serial FIFO DCN channel",
            "dcn_latency_us": args.dcn_latency_us,
        },
        "simulated_pod": model_rows,
        "hlo_evidence": evidence,
        "parity": parity,
        "gates": {
            "parity_ok": bool(parity_ok),
            "parity_gate": f"overlap params Δ=0 vs serial after "
                           f"{args.steps} steps (every bucket size"
                           + ("" if args.smoke else " + ZeRO-1 x overlap")
                           + ")",
            "overlap_win_ok": bool(overlap_win),
            "overlap_win_gate": (
                f"modeled overlapped step < serial step AND >= 50% of "
                f"the communication hidden under backward at "
                f"{head['dcn_gbps']} GB/s DCN, bucket "
                f"{head['bucket_mb']} MB (hidden_comm_fraction "
                f"{head['hidden_comm_fraction']})"
            ),
            "bucketing_win_ok": bool(bucket_win),
            "bucketing_win_gate": (
                "bucketed serial transport < per-leaf serial transport "
                "(latency amortization over the bucket)"
            ),
            "evidence_ok": bool(all(
                e["reductions"] >= 2 and e["interleaved_gaps"] >= 1
                for e in evidence.values()
            )),
            "evidence_gate": ">= 2 per-bucket reductions interleaved "
                             "with compute in every overlap arm's "
                             "compiled schedule",
        },
    }

    if args.recipe == "minutes":
        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))
        minutes = run_minutes_recipe(args, repo_root)
        report["minutes"] = {
            "best_top1": minutes["best_top1"],
            "wall_seconds": minutes["wall_seconds"],
            "pass": minutes["pass"],
        }
        report["gates"]["minutes_ok"] = bool(minutes["pass"])
        report["gates"]["minutes_gate"] = (
            f"composed recipe (LARS + ramp + poly warmup + dist eval + "
            f"overlap) best top1 >= {TOP1_BAR} through the real fit() "
            f"path, with the ramp actually engaging"
        )
        # merge into CONVERGENCE.json, preserving the other sections'
        # provenance (the run_convergence --recipe large-batch pattern)
        conv = os.path.join(repo_root, "CONVERGENCE.json")
        conv_report = {}
        if os.path.exists(conv):
            with open(conv) as f:
                conv_report = json.load(f)
        conv_report["minutes"] = minutes
        if "pass" in conv_report:
            ref_pass = bool(conv_report["pass"])
            if "pass_top1_bar" in conv_report \
                    or "pass_bf16_delta" in conv_report:
                ref_pass = (
                    bool(conv_report.get("pass_top1_bar", True))
                    and bool(conv_report.get("pass_bf16_delta", True)))
            lb = conv_report.get("large_batch", {})
            conv_report["pass"] = (
                ref_pass and bool(lb.get("pass", True))
                and minutes["pass"])
        from bench_util import host_provenance

        conv_report["host"] = host_provenance()
        with open(conv, "w") as f:
            json.dump(conv_report, f, indent=1)
        print(f"minutes recipe best top1 {minutes['best_top1']:.2f} "
              f"(bar {TOP1_BAR}) in {minutes['wall_seconds']}s; merged "
              f"into {conv}", file=sys.stderr)

    out = args.out if os.path.isabs(args.out) else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        args.out,
    )
    from bench_util import host_provenance

    report["host"] = host_provenance()
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    ok = all(v for k, v in report["gates"].items() if k.endswith("_ok"))
    print(json.dumps({
        "headline": {k: head[k] for k in (
            "bucket_mb", "buckets", "dcn_gbps", "serial_ms",
            "overlapped_ms", "speedup")},
        "parity": parity,
        "gates_ok": ok,
        "out": out,
    }))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
