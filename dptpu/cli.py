"""Console entry points — shared by the repo-root reference-parity scripts
and the installed ``dptpu-*`` commands (pyproject [project.scripts]).

Besides the three reference-parity trainers, the ``dptpu`` multi-command
(``main``) fronts the dptpu-native subsystems; its first subcommand is
``dptpu serve`` — the batched inference engine (dptpu/serve)."""

from dptpu.config import parse_config
from dptpu.train import fit


def _report_preemption(result):
    """A graceful preemption is a SUCCESS (exit 0): the mid-epoch
    checkpoint is on disk and a ``--resume`` run replays the sampler to
    the exact saved position (bit-identical trajectory — see
    dptpu/resilience)."""
    if result.get("preempted"):
        print(
            "preempted: mid-epoch checkpoint saved; rerun with "
            "--resume <run dir> to continue where this run stopped"
        )


def main_ddp(argv=None):
    """imagenet_ddp.py: multi-host data-parallel training."""
    cfg = parse_config(argv, variant="ddp")
    result = fit(cfg)
    if result.get("early_stopped"):
        print(f"early stop: training_time {result['training_time']:.1f}s")
    _report_preemption(result)
    return result


def main_nd(argv=None):
    """nd_imagenet.py: single-device / fallback-everything training."""
    cfg = parse_config(argv, variant="nd")
    result = fit(cfg)
    _report_preemption(result)
    return result


def main_apex(argv=None):
    """imagenet_ddp_apex.py: bf16 mixed-precision training (env:// rendezvous)."""
    cfg = parse_config(argv, variant="apex").replace(dist_url="env://")
    result = fit(cfg)
    _report_preemption(result)
    return result


# Installed-command wrappers (pyproject [project.scripts]): setuptools
# wraps an entry point as ``sys.exit(fn())``, and ``sys.exit(<dict>)``
# exits 1 — which would break the exit-0 contract graceful preemption
# (and every successful run) depends on. The repo-root scripts and tests
# keep calling the result-returning ``main_*`` directly.

def build_serve_parser():
    """``dptpu serve`` flags. Env twins (``DPTPU_SERVE_*``) WIN over
    these when set — the precedence every dptpu knob follows — and BOTH
    sources go through the same ``serve_knobs`` validation, so a typo'd
    value fails fast pre-compile whichever way it arrived."""
    import argparse

    p = argparse.ArgumentParser(
        prog="dptpu serve",
        description="batched inference: AOT bucket compilation + "
                    "continuous dynamic batching (dptpu/serve)",
    )
    p.add_argument("-a", "--arch", default="resnet50", metavar="ARCH",
                   help="registry architecture, or a comma list of "
                        "[name=]arch entries to co-serve several models "
                        "behind one router (e.g. 'resnet50,tiny=resnet18')")
    p.add_argument("--buckets", default=None, metavar="N,N,...",
                   help="AOT batch-size bucket ladder (default 1,4,16,64; "
                        "env DPTPU_SERVE_BUCKETS)")
    p.add_argument("--max-delay-ms", type=float, default=None,
                   help="batcher coalescing budget (default 5.0; env "
                        "DPTPU_SERVE_MAX_DELAY_MS)")
    p.add_argument("--placement", default=None,
                   help="auto | replicated | tp (default auto; env "
                        "DPTPU_SERVE_PLACEMENT)")
    p.add_argument("--slots", type=int, default=None,
                   help="staging-ring depth (default 4; env "
                        "DPTPU_SERVE_SLOTS)")
    p.add_argument("--queue-depth", type=int, default=None,
                   help="admission bound: max admitted-but-unanswered "
                        "requests per model (default 64; env "
                        "DPTPU_SERVE_QUEUE_DEPTH)")
    p.add_argument("--priorities", default=None, metavar="H,N,L",
                   help="shed water marks as fractions of the queue "
                        "depth, high,normal,low (default 1.0,0.85,0.6; "
                        "env DPTPU_SERVE_PRIORITIES)")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="default per-request deadline, 0 = none "
                        "(default 0; env DPTPU_SERVE_DEADLINE_MS)")
    p.add_argument("--canary-fraction", type=float, default=None,
                   help="traffic fraction routed to a staged canary "
                        "generation (default 0.1; env "
                        "DPTPU_SERVE_CANARY_FRACTION)")
    p.add_argument("--canary-drift", type=float, default=None,
                   help="max|dlogit| vs baseline before auto-rollback "
                        "(default 50.0; env DPTPU_SERVE_CANARY_DRIFT)")
    p.add_argument("--canary-lat-factor", type=float, default=None,
                   help="canary/baseline batch-latency multiple before "
                        "auto-rollback (default 5.0; env "
                        "DPTPU_SERVE_CANARY_LAT_FACTOR)")
    p.add_argument("--precision", default=None,
                   help="serve precision: fp32 | bf16 | int8 (default "
                        "fp32; below fp32 needs --calib and deploys "
                        "through the canary drift gate; env "
                        "DPTPU_QUANT_PRECISION)")
    p.add_argument("--calib", default=None, metavar="PATH",
                   help="calibration artifact from `dptpu quantize` "
                        "(required for --precision bf16/int8; env "
                        "DPTPU_QUANT_CALIB)")
    p.add_argument("--quant-drift", type=float, default=None,
                   help="override the quantized rollout's max|dlogit| "
                        "gate (default 0 = the artifact's bound; env "
                        "DPTPU_QUANT_DRIFT)")
    p.add_argument("--quant-top1-min", type=float, default=None,
                   help="override the quantized rollout's top-1 "
                        "agreement floor (default 0 = the artifact's "
                        "bound; env DPTPU_QUANT_TOP1_MIN)")
    p.add_argument("--fleet", action="store_true",
                   help="run the FLEET FRONT instead of a local engine: "
                        "route requests over the serving hosts "
                        "registered in --fleet-dir (members are plain "
                        "`dptpu serve --fleet-dir ...` processes)")
    p.add_argument("--fleet-dir", default=None, metavar="DIR",
                   help="shared fleet membership directory (quorum KV); "
                        "setting it on a serving host registers that "
                        "host in the fleet (env DPTPU_FLEET_DIR)")
    p.add_argument("--fleet-heartbeat-s", type=float, default=None,
                   help="fleet member heartbeat period (default 1.0; "
                        "env DPTPU_FLEET_HEARTBEAT_S)")
    p.add_argument("--fleet-deadline-s", type=float, default=None,
                   help="heartbeat staleness before a member is "
                        "auto-drained from routing (default 3.0; env "
                        "DPTPU_FLEET_DEADLINE_S)")
    p.add_argument("--fleet-retries", type=int, default=None,
                   help="failover retries when a member connection "
                        "dies mid-request (default 2; env "
                        "DPTPU_FLEET_RETRIES)")
    p.add_argument("--pretrained", action="store_true",
                   help="load converted torchvision weights "
                        "($DPTPU_PRETRAINED_DIR/<arch>.npz)")
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--selftest", type=int, default=0, metavar="N",
                   help="serve N synthetic requests through the full "
                        "bytes->batcher->engine path and exit (no "
                        "listener) — the smoke/readiness mode")
    return p


def parse_model_specs(raw: str):
    """``[name=]arch[,...]`` -> ordered (name, arch) pairs; the first
    entry is the router's default route. A bare arch names itself, so
    co-serving the same arch twice needs explicit names."""
    from dptpu.models import model_names, model_task

    pairs = []
    for spec in str(raw).split(","):
        spec = spec.strip()
        if not spec:
            continue
        name, _, arch = spec.rpartition("=")
        name = name or arch
        if arch not in model_names():
            raise ValueError(
                f"--arch={arch!r} is not a registry architecture "
                f"(e.g. {', '.join(model_names()[:4])}, ...; full list: "
                f"python -c 'from dptpu.models import model_names; "
                f"print(model_names())')"
            )
        if model_task(arch) != "images":
            raise ValueError(
                f"--arch={arch!r} is a token-sequence model: dptpu serve "
                f"classifies images (it has no cache of keys, values or "
                f"convolution state and no decode loop); train it with "
                f"the imagenet_ddp*.py entry points on tokens:<N>"
            )
        if name in (n for n, _ in pairs):
            raise ValueError(
                f"--arch names model {name!r} twice (use name=arch to "
                f"co-serve one arch under distinct names)"
            )
        pairs.append((name, arch))
    if not pairs:
        raise ValueError("--arch needs at least one [name=]arch entry")
    return pairs


def serve_args_to_knobs(args):
    """CLI namespace -> validated ServeKnobs + arch check (the fail-fast
    moment: every bad knob OR unknown name raises BEFORE any compile)."""
    from dptpu.serve import serve_knobs

    knobs = serve_knobs(
        buckets=args.buckets, max_delay_ms=args.max_delay_ms,
        placement=args.placement, slots=args.slots,
        queue_depth=args.queue_depth, priorities=args.priorities,
        deadline_ms=args.deadline_ms,
        canary_fraction=args.canary_fraction,
        canary_drift=args.canary_drift,
        canary_lat_factor=args.canary_lat_factor,
        precision=args.precision, calib=args.calib,
        quant_drift=args.quant_drift,
        quant_top1_min=args.quant_top1_min,
        fleet_dir=args.fleet_dir,
        fleet_heartbeat_s=args.fleet_heartbeat_s,
        fleet_deadline_s=args.fleet_deadline_s,
        fleet_retries=args.fleet_retries,
    )
    parse_model_specs(args.arch)
    return knobs


def main_serve(argv=None):
    """``dptpu serve``: load the model(s), AOT-compile each bucket
    ladder, and serve — over HTTP, or ``--selftest N`` synthetic
    requests. ``--fleet`` skips the local engine entirely and runs the
    fleet ROUTING TIER over the hosts registered in the fleet dir."""
    args = build_serve_parser().parse_args(argv)

    from dptpu.tune.artifact import apply_tuning, tune_knobs

    # the offline tuning artifact applies BEFORE knob resolution so
    # serve_knobs sees the tuned ladder — and only for knobs nothing
    # else set: env twins and explicit CLI flags always win (ISSUE 19)
    tune_conf = tune_knobs()
    if tune_conf["artifact"]:
        cli_set = set()
        if args.buckets is not None:
            cli_set.add("DPTPU_SERVE_BUCKETS")  # explicit --buckets wins
        apply_tuning(tune_conf["artifact"], cli_set=cli_set)
    knobs = serve_args_to_knobs(args)  # fail fast, pre-jax-compile

    if args.fleet:
        return _serve_fleet_front(args, knobs)
    specs = parse_model_specs(args.arch)

    from dptpu.serve import ModelRouter, build_served_model
    from dptpu.utils.compile_cache import enable_compile_cache
    from dptpu.utils.provenance import device_banner

    enable_compile_cache()
    print(device_banner())

    router = ModelRouter([
        build_served_model(
            name, arch, knobs, num_classes=args.num_classes,
            image_size=args.image_size, pretrained=args.pretrained,
            verbose=True,
        )
        for name, arch in specs
    ])
    if "serve_ladder" in tune_conf["control"]:
        from dptpu.tune.controller import (
            Controller,
            serve_ladder_actuator,
        )

        # one controller per model, ticked on that model's dispatch
        # thread between batches: sustained padding waste densifies the
        # ladder's widest gap (compile-before-publish, bounded budget)
        for name, m in router.models.items():
            m.batcher.attach_controller(Controller([
                serve_ladder_actuator(
                    m.engine, m.batcher,
                    interval_s=tune_conf["interval_s"],
                ),
            ]))
        print(f"=> tune control armed: serve_ladder on "
              f"{', '.join(router.models)} (interval "
              f"{tune_conf['interval_s']:g}s; disarm with "
              f"DPTPU_TUNE_CONTROL=off)")
    member = None
    try:
        if knobs.precision != "fp32":
            for name in router.models:
                gen = router.start_quantized(knobs, name)
                print(f"=> serve: staged {knobs.precision} generation "
                      f"{gen} for {name!r} behind the canary drift gate "
                      f"({knobs.calib})")
        if args.selftest:
            return _serve_selftest(router, args.selftest)
        if knobs.fleet_dir:
            from dptpu.serve.fleet import FleetMember

            member = FleetMember(
                knobs.fleet_dir, host=args.host, port=args.port,
                heartbeat_s=knobs.fleet_heartbeat_s,
            )
            print(f"=> serve: registered fleet member "
                  f"{member.member_id!r} in {knobs.fleet_dir}")
        print(
            f"=> dptpu serve: "
            f"{', '.join(f'{n} ({a})' for n, a in specs)} (buckets "
            f"{list(knobs.buckets)}) on http://{args.host}:{args.port} "
            f"— POST /predict[/<model>], GET /healthz, GET /readyz, "
            f"GET /metrics"
        )
        from dptpu.serve.http import serve_forever

        serve_forever(router, args.host, args.port)
        return {
            name: m.batcher.stats()["completed"]
            for name, m in router.models.items()
        }
    finally:
        if member is not None:
            member.close()
        router.close()


def _serve_fleet_front(args, knobs):
    """The ``--fleet`` routing tier: no local engine — requests fan out
    over the registered member hosts, a stale heartbeat auto-drains a
    member, and the PR-17 admission layer fronts the whole fleet."""
    if not knobs.fleet_dir:
        raise SystemExit(
            "--fleet needs the membership directory: set "
            "DPTPU_FLEET_DIR/--fleet-dir to the shared quorum-KV path "
            "the serving hosts register in"
        )
    from dptpu.serve.fleet import FleetRouter, serve_fleet_forever

    fleet = FleetRouter(
        knobs.fleet_dir, deadline_s=knobs.fleet_deadline_s,
        poll_s=knobs.fleet_heartbeat_s, retries=knobs.fleet_retries,
        queue_depth=knobs.queue_depth, priorities=knobs.priorities,
        deadline_ms=knobs.deadline_ms,
    )
    try:
        print(
            f"=> dptpu serve --fleet: routing over {knobs.fleet_dir} "
            f"on http://{args.host}:{args.port} (drain after "
            f"{knobs.fleet_deadline_s}s heartbeat silence, "
            f"{knobs.fleet_retries} failover retries)"
        )
        serve_fleet_forever(fleet, args.host, args.port)
        return fleet.stats()
    finally:
        fleet.close()


def _serve_selftest(router, n: int):
    """Readiness probe: N JPEG-encoded synthetic requests per model
    through the full admission -> bytes -> preprocess -> staging ->
    bucket -> logits path."""
    import io

    import numpy as np
    from PIL import Image

    out = {}
    for name, m in router.models.items():
        rng = np.random.RandomState(0)
        size = m.engine.image_size
        # keep outstanding work under the admission water mark: the
        # selftest proves the path, it must not shed itself
        window = max(1, m.admission.thresholds["normal"] // 2)
        futs = []
        for _ in range(n):
            buf = io.BytesIO()
            Image.fromarray(
                rng.randint(0, 256, (size, size, 3), dtype=np.uint8)
            ).save(buf, format="JPEG")
            if len(futs) >= window:
                futs.pop(0).result(timeout=120.0)
            futs.append(router.submit(data=buf.getvalue(), model=name))
        for f in futs:
            f.result(timeout=120.0)
        stats = m.batcher.stats()
        print(
            f"serve selftest [{name}]: {stats['completed']} ok, "
            f"{stats['failed']} failed, p50 "
            f"{stats['latency_ms']['p50']:.1f}ms p99 "
            f"{stats['latency_ms']['p99']:.1f}ms, buckets "
            f"{stats['bucket_counts']}"
        )
        out[name] = stats
    return out if len(out) > 1 else next(iter(out.values()))


def build_quantize_parser():
    """``dptpu quantize`` flags: offline post-training calibration of a
    serve model into a CRC-sealed artifact (dptpu/serve/quant.py)."""
    import argparse

    p = argparse.ArgumentParser(
        prog="dptpu quantize",
        description="calibrate per-channel int8 scales for a serve "
                    "model from a shard sample and commit them as a "
                    "provenance-stamped, CRC-sealed calibration "
                    "artifact (the only key that unlocks sub-fp32 "
                    "serving)",
    )
    p.add_argument("-a", "--arch", default="resnet50", metavar="ARCH",
                   help="registry architecture to calibrate")
    p.add_argument("-o", "--out", required=True, metavar="PATH",
                   help="calibration artifact output path")
    p.add_argument("--pretrained", action="store_true",
                   help="calibrate the converted torchvision weights "
                        "($DPTPU_PRETRAINED_DIR/<arch>.npz)")
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--data", default=None, metavar="DIR",
                   help="packed-shard or ImageFolder directory to draw "
                        "the calibration sample from (default: "
                        "deterministic synthetic sample)")
    p.add_argument("--sample", type=int, default=64, metavar="N",
                   help="calibration sample size (default 64)")
    p.add_argument("--drift-bound", type=float, default=None,
                   help="max|dlogit| bound to stamp into the artifact "
                        "(default: measured max drift x 2 margin)")
    p.add_argument("--top1-min", type=float, default=None,
                   help="top-1 agreement floor to stamp into the "
                        "artifact (default: measured agreement less a "
                        "0.05 margin, floored at 0.5)")
    return p


def main_quantize(argv=None):
    """``dptpu quantize``: build the fp32 model, quantize, replay the
    calibration sample through BOTH forwards, and seal scales + the
    measured drift gate bounds into the artifact."""
    import numpy as np

    args = build_quantize_parser().parse_args(argv)
    if args.sample < 1:
        raise SystemExit(f"--sample {args.sample} must be >= 1")
    if args.arch is not None:
        parse_model_specs(args.arch.split(",")[0])

    from dptpu.serve.engine import ServeEngine
    from dptpu.serve.quant import (
        DRIFT_MARGIN,
        measure_drift,
        quantize_variables,
        save_calibration,
    )
    from dptpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # one fp32 engine, replicated (quantized serving is replicated-only)
    bucket = max(2, min(16, args.sample))
    engine = ServeEngine(
        args.arch, buckets=(bucket,), placement="replicated",
        num_classes=args.num_classes, image_size=args.image_size,
        pretrained=args.pretrained, verbose=True,
    )
    sample = _calibration_sample(
        args.data, args.sample, args.image_size
    )

    params = engine._host_variables["params"]
    gen_q = engine.stage_weights(
        quantize_variables(engine._host_variables, "int8"),
        precision="int8",
    )
    try:
        base_parts, q_parts = [], []
        for i in range(0, len(sample), bucket):
            chunk = sample[i:i + bucket]
            n = len(chunk)
            if n < bucket:
                pad = np.broadcast_to(
                    chunk[0], (bucket - n,) + chunk.shape[1:]
                )
                chunk = np.concatenate([chunk, pad], axis=0)
            base_parts.append(engine.run_bucket(bucket, chunk, n))
            q_parts.append(engine.run_bucket(bucket, chunk, n, gen=gen_q))
        base = np.concatenate(base_parts, axis=0)
        quant = np.concatenate(q_parts, axis=0)
    finally:
        engine.discard_staged(gen_q)
    agree, drift = measure_drift(base, quant)

    drift_bound = (args.drift_bound if args.drift_bound is not None
                   else max(drift * DRIFT_MARGIN, 1e-3))
    top1_min = (args.top1_min if args.top1_min is not None
                else max(0.5, agree - 0.05))
    payload = save_calibration(
        args.out, arch=args.arch, params=params,
        stats={"top1_agreement": agree, "max_abs_dlogit": drift},
        bounds={"max_abs_dlogit": drift_bound,
                "min_top1_agreement": top1_min},
        num_classes=args.num_classes, image_size=args.image_size,
        sample_n=len(sample),
    )
    meta = payload["meta"]
    print(
        f"=> dptpu quantize: {args.arch} -> {args.out} "
        f"(weights {meta['weights_fingerprint']}, sample "
        f"{len(sample)}: top-1 agreement {agree:.3f}, max|dlogit| "
        f"{drift:.3g}; gate bounds: agreement >= {top1_min:.3f}, "
        f"drift <= {drift_bound:.3g})"
    )
    return meta


def _calibration_sample(data, n: int, image_size: int):
    """uint8 NHWC calibration batch: decoded val-pipeline rows from a
    packed-shard/ImageFolder dir when given, else a deterministic
    synthetic sample (load-test engines are random-init anyway — what
    matters is that serve-time traffic statistics see the SAME scales
    the gate bounds were measured with)."""
    import numpy as np

    if data is None:
        rng = np.random.RandomState(0)
        return rng.randint(
            0, 256, (n, image_size, image_size, 3), np.uint8
        )
    from dptpu.serve.preprocess import preprocess_bytes

    rows = []
    for path in _iter_image_files(data):
        with open(path, "rb") as f:
            try:
                rows.append(preprocess_bytes(f.read(), size=image_size))
            except ValueError:
                continue  # non-image file in the tree
        if len(rows) >= n:
            break
    if not rows:
        raise SystemExit(
            f"--data {data}: no decodable images found for the "
            f"calibration sample"
        )
    return np.stack(rows, axis=0)


def _iter_image_files(root):
    import os

    for dirpath, _, files in sorted(os.walk(root)):
        for f in sorted(files):
            yield os.path.join(dirpath, f)


def build_pack_parser():
    """``dptpu pack`` flags: ImageFolder tree → packed sequential
    shards (dptpu/data/shards.py). Deterministic: the same tree always
    packs to byte-identical shards."""
    import argparse

    p = argparse.ArgumentParser(
        prog="dptpu pack",
        description="pack an ImageFolder tree into CRC-sealed "
                    "sequential shards (+ manifest) that the streaming "
                    "data plane reads locally (O_DIRECT byte ring) or "
                    "over a store URL (HTTP range fetch)",
    )
    p.add_argument("src", metavar="SRC",
                   help="ImageFolder root — either one split "
                        "(class dirs directly inside) or a tree with "
                        "train/ and val/ splits (both are packed)")
    p.add_argument("dest", metavar="DEST",
                   help="output directory (split layout is mirrored)")
    p.add_argument("--shards", type=int, default=8, metavar="N",
                   help="shards per split (default 8)")
    p.add_argument("--verify", action="store_true",
                   help="deep-verify every written shard (header, "
                        "index and every sample extent CRC)")
    return p


def main_pack(argv=None):
    """``dptpu pack``: convert an ImageFolder tree into packed shards."""
    import os

    from dptpu.data.shards import verify_shard, write_shards

    args = build_pack_parser().parse_args(argv)
    if args.shards < 1:
        raise SystemExit(f"--shards {args.shards} must be >= 1")
    splits = [
        s for s in ("train", "val")
        if os.path.isdir(os.path.join(args.src, s))
    ]
    pairs = (
        [(os.path.join(args.src, s), os.path.join(args.dest, s))
         for s in splits]
        if splits else [(args.src, args.dest)]
    )
    out = {}
    for src, dest in pairs:
        print(f"=> packing {src} -> {dest} ({args.shards} shards)")
        manifest = write_shards(src, dest, args.shards, verbose=True)
        if args.verify:
            for s in manifest["shards"]:
                ok, reason = verify_shard(
                    os.path.join(dest, s["name"]), deep=True
                )
                if not ok:
                    raise SystemExit(f"verify failed: {reason}")
            print(f"   verified {len(manifest['shards'])} shards deep")
        out[dest] = manifest
    return out


def main(argv=None):
    """The ``dptpu`` multi-command: ``dptpu serve|pack|check [...]``."""
    import sys

    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: dptpu <subcommand> [args]\n\nsubcommands:\n"
              "  serve     batched inference engine (dptpu/serve)\n"
              "  quantize  offline int8 calibration -> CRC-sealed "
              "artifact (dptpu/serve/quant.py)\n"
              "  pack      ImageFolder -> packed sequential shards "
              "(dptpu/data/shards.py)\n"
              "  check     repo-invariant static analysis: AST lints + "
              "HLO budget gates (dptpu/analysis)\n"
              "  tune      offline knob autotuner -> CRC-sealed "
              "TUNING.json artifact (dptpu/tune)")
        return 0 if argv else 2
    cmd, rest = argv[0], argv[1:]
    if cmd == "serve":
        return main_serve(rest)
    if cmd == "quantize":
        return main_quantize(rest)
    if cmd == "pack":
        return main_pack(rest)
    if cmd == "check":
        from dptpu.analysis.cli import main_check

        return main_check(rest)
    if cmd == "tune":
        from dptpu.tune.cli import main_tune

        return main_tune(rest)
    raise SystemExit(
        f"dptpu: unknown subcommand {cmd!r} "
        f"(available: serve, quantize, pack, check, tune)"
    )


def console_main(argv=None) -> int:
    out = main(argv)
    return out if isinstance(out, int) else 0


def console_ddp(argv=None) -> int:
    main_ddp(argv)
    return 0


def console_nd(argv=None) -> int:
    main_nd(argv)
    return 0


def console_apex(argv=None) -> int:
    main_apex(argv)
    return 0
