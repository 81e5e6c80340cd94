"""``dptpu tune``: the offline autotuner → committed TUNING.json.

The artifact is the only way tuned knobs enter a run, and it enters at
the LOWEST precedence: ``fit()``/``dptpu serve`` load it via
``DPTPU_TUNE_ARTIFACT`` and env-inject only knobs nothing else set
(:func:`dptpu.tune.artifact.apply_tuning` — explicit env/CLI always
wins, and a loud banner names every tuned value actually applied).

Search strategy (dptpu/tune/search.py):

* ``DPTPU_BUCKET_MB`` — full candidate sweep against the RACEBENCH
  simulated-pod cost model for the target geometry/DCN (analytic:
  microseconds per candidate).
* ``DPTPU_SERVE_BUCKETS`` — candidate ladders scored analytically
  against a request-size mix; ``--serve-probe`` re-checks the winner
  through a real ``ServeEngine`` + ``DynamicBatcher`` replay.
* ``DPTPU_DECODE_AHEAD`` / ``DPTPU_RING_DEPTH`` / ``DPTPU_CACHE_SCOPE``
  / ``DPTPU_ACCUM`` — measured A/B probes through real ``fit()`` runs
  on synthetic data, interleaved default/candidate pairs in ABBA order;
  a candidate is adopted only when its median paired gain clears the
  default arm's own noise floor (``--probe none`` skips these).

Usage::

    dptpu tune --out TUNING.json [--arch resnet18] [--smoke]
               [--slices 2 --chips-per-slice 2 --dcn-gbps 12.5]
               [--probe quick|none|full] [--serve-probe]

Then: ``DPTPU_TUNE_ARTIFACT=TUNING.json python imagenet_apex.py ...``
"""

from __future__ import annotations

import argparse
import json
import sys


def build_tune_parser():
    p = argparse.ArgumentParser(
        prog="dptpu tune",
        description="offline knob autotuner: cost-model sweep + short "
                    "measured probes -> CRC-sealed TUNING.json "
                    "(loaded via DPTPU_TUNE_ARTIFACT; explicit "
                    "env/CLI knobs always win)",
    )
    p.add_argument("-o", "--out", default="TUNING.json", metavar="PATH",
                   help="artifact output path (default TUNING.json)")
    p.add_argument("-a", "--arch", default="resnet18",
                   help="architecture whose gradient layout the bucket "
                        "sweep scores (default resnet18)")
    p.add_argument("--image-size", type=int, default=32)
    p.add_argument("--num-classes", type=int, default=16)
    p.add_argument("--slices", type=int, default=2,
                   help="modeled pod slices (cost model)")
    p.add_argument("--chips-per-slice", type=int, default=2)
    p.add_argument("--per-chip-batch", type=int, default=8)
    p.add_argument("--dcn-gbps", type=float, default=12.5,
                   help="modeled per-chip DCN bandwidth (GB/s)")
    p.add_argument("--dcn-latency-us", type=float, default=15.0)
    p.add_argument("--chip-img-per-s", type=float, default=2734.0,
                   help="chip-equivalent compute anchor (the pre-PR-1 one-chip "
                        "rate; not re-measured on the current code)")
    p.add_argument("--probe", choices=("none", "quick", "full"),
                   default="quick",
                   help="measured fit() probes: none = cost model "
                        "only; quick = decode-ahead + accum; full = "
                        "adds ring depth + cache scope")
    p.add_argument("--probe-images", type=int, default=None)
    p.add_argument("--probe-batch", type=int, default=32)
    p.add_argument("--probe-epochs", type=int, default=None)
    p.add_argument("--probe-reps", type=int, default=2,
                   help="interleaved default/candidate pairs per knob")
    p.add_argument("--serve-probe", action="store_true",
                   help="re-check the chosen serve ladder through a "
                        "real ServeEngine replay (one AOT compile per "
                        "bucket — the expensive probe)")
    p.add_argument("--max-bucket", type=int, default=64,
                   help="serve ladder admission bound to tune within")
    p.add_argument("--smoke", action="store_true",
                   help="CI preset: cost-model + analytic ladder only, "
                        "one tiny measured probe, no serve compile")
    return p


def main_tune(argv=None):
    from dptpu.tune.artifact import save_tuning
    from dptpu.tune.search import (
        default_request_mix,
        model_leaf_sizes,
        probe_knob_paired,
        probe_serve_ladder,
        search_bucket_mb,
        search_serve_buckets,
    )

    from dptpu.utils.compile_cache import enable_compile_cache

    args = build_tune_parser().parse_args(argv)
    enable_compile_cache()
    if args.smoke:
        args.probe = "quick" if args.probe != "none" else "none"
        args.serve_probe = False
    probe_images = args.probe_images or (128 if args.smoke else 512)
    probe_epochs = args.probe_epochs or (1 if args.smoke else 2)

    knobs = {}
    probes = {}

    # 1. DPTPU_BUCKET_MB: analytic sweep over the cost model ----------
    print(f"=> tune: scoring DPTPU_BUCKET_MB candidates against the "
          f"simulated pod ({args.slices}x{args.chips_per_slice}, "
          f"{args.dcn_gbps} GB/s DCN, {args.arch} gradient layout)")
    perleaf = model_leaf_sizes(
        args.arch, image_size=args.image_size,
        num_classes=args.num_classes,
    )
    t_chip = args.per_chip_batch / args.chip_img_per_s
    bucket = search_bucket_mb(
        perleaf, t_chip,
        dcn_gbps=args.dcn_gbps,
        latency_s=args.dcn_latency_us * 1e-6,
        slices=args.slices, inner=args.chips_per_slice,
    )
    knobs["DPTPU_BUCKET_MB"] = f"{bucket['best_bucket_mb']:g}"
    probes["bucket_mb"] = {
        "kind": "cost_model",
        "grad_bytes": sum(perleaf),
        "best": bucket["best_row"],
        "rows": bucket["rows"],
    }
    print(f"   best DPTPU_BUCKET_MB={knobs['DPTPU_BUCKET_MB']} "
          f"(overlapped {bucket['best_row']['overlapped_ms']} ms, "
          f"speedup {bucket['best_row']['speedup']}x over serial)")

    # 2. DPTPU_SERVE_BUCKETS: analytic ladder search ------------------
    mix = default_request_mix(args.max_bucket)
    ladder = search_serve_buckets(mix)
    default_waste = next(
        r["waste"] for r in ladder["rows"]
        if r["ladder"] == [1, 4, 16, 64]
    )
    probes["serve_buckets"] = {
        "kind": "analytic_padding",
        "request_mix_len": len(mix),
        "default_waste": default_waste,
        "best": {"ladder": ladder["best_ladder"],
                 "waste": ladder["best_waste"]},
        "rows": ladder["rows"],
    }
    if ladder["best_ladder"] != [1, 4, 16, 64]:
        knobs["DPTPU_SERVE_BUCKETS"] = ",".join(
            str(b) for b in ladder["best_ladder"]
        )
        print(f"   best DPTPU_SERVE_BUCKETS="
              f"{knobs['DPTPU_SERVE_BUCKETS']} (padding waste "
              f"{ladder['best_waste']:.1%} vs default "
              f"{default_waste:.1%})")
    else:
        print(f"   serve ladder: default [1,4,16,64] already best "
              f"({default_waste:.1%} waste) — not emitting")
    if args.serve_probe:
        probes["serve_buckets"]["measured"] = probe_serve_ladder(
            ladder["best_ladder"], mix[:64], arch=args.arch,
            image_size=args.image_size, num_classes=args.num_classes,
        )
        print(f"   measured ladder waste "
              f"{probes['serve_buckets']['measured']['measured_waste']:.1%}")

    # 3. measured fit() probes ----------------------------------------
    if args.probe != "none":
        plan = [("DPTPU_DECODE_AHEAD", "8",
                 {"DPTPU_WORKERS_MODE": "process"}),
                ("DPTPU_ACCUM", "2", {})]
        if args.probe == "full":
            plan += [("DPTPU_RING_DEPTH", "12",
                      {"DPTPU_WORKERS_MODE": "process"}),
                     ("DPTPU_CACHE_SCOPE", "sharded",
                      {"DPTPU_CACHE_BYTES": str(256 << 20),
                       "DPTPU_WORKERS_MODE": "process"})]
        if args.smoke:
            plan = plan[:1]
        for knob, candidate, base_env in plan:
            print(f"=> tune: measured probe {knob}={candidate} "
                  f"({args.probe_reps} ABBA pairs, {probe_images} "
                  f"synthetic images)")
            verdict = probe_knob_paired(
                knob, candidate, base_env,
                reps=args.probe_reps, arch=args.arch,
                images=probe_images, batch=args.probe_batch,
                epochs=probe_epochs, image_size=args.image_size,
            )
            probes[knob.lower()] = {"kind": "measured_fit", **verdict}
            if verdict["adopt"]:
                knobs[knob] = candidate
                for k, v in base_env.items():
                    # a knob that only wins inside its enabling
                    # context carries that context (tunable ones only)
                    from dptpu.tune.artifact import TUNABLE_KNOBS

                    if k in TUNABLE_KNOBS:
                        knobs.setdefault(k, v)
                print(f"   ADOPT {knob}={candidate} "
                      f"(+{verdict['gain_pct']:.1f}% median, noise "
                      f"{verdict['noise_pct']:.1f}%)")
            else:
                print(f"   keep default for {knob} "
                      f"({verdict['gain_pct']:+.1f}% median does not "
                      f"clear noise {verdict['noise_pct']:.1f}%)")

    objective = {
        "cost_model": {
            "slices": args.slices,
            "chips_per_slice": args.chips_per_slice,
            "per_chip_batch": args.per_chip_batch,
            "dcn_gbps": args.dcn_gbps,
            "dcn_latency_us": args.dcn_latency_us,
            "chip_img_per_s": args.chip_img_per_s,
            "arch": args.arch,
        },
        "probe_preset": args.probe,
        "smoke": bool(args.smoke),
    }
    from dptpu.utils.provenance import host_provenance

    host = host_provenance()
    payload = save_tuning(args.out, knobs, objective, probes, host=host)
    print(json.dumps({"out": args.out, "knobs": knobs,
                      "crc32": payload["crc32"]}))
    print(f"wrote {args.out} — load with "
          f"DPTPU_TUNE_ARTIFACT={args.out} (explicit env/CLI knobs "
          f"always win)")
    return 0


if __name__ == "__main__":
    sys.exit(main_tune())
