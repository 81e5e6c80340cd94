"""End-to-end training driver: the ``main_worker`` analog for every CLI.

One function covers the reference's three worker paths
(imagenet_ddp.py:89-236, imagenet_ddp_apex.py:101-301,
nd_imagenet.py:116-263): rendezvous → mesh → model/optimizer → resume →
loaders → epoch loop with checkpoint-best, ``--evaluate`` short-circuit, and
``--desired-acc`` early stop recording ``training_time``.

Differences by design (TPU-first):
* one process per host drives all local chips through a mesh — there is no
  mp.spawn ladder; single-device is just a 1-device mesh-less jit.
* the number of classes is inferred from the dataset (ImageFolder classes),
  so tiny fixtures train tiny heads; ImageNet layouts get the usual 1000.
* ``data`` may be ``synthetic[:N]`` for a decode-free pipeline (benchmarks,
  integration tests) — N samples of 224×224×3 across 1000 classes.
"""

from __future__ import annotations

import os
import sys
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from dptpu import obs
from dptpu.config import Config, derive
from dptpu.data import DevicePrefetcher
from dptpu.data.feed import THREAD_MODE_WHEN, build_feed
from dptpu.data.store import is_store_url
from dptpu.envknob import env_bool, env_choice, env_float, env_int, env_str
from dptpu.models import create_model, model_task
from dptpu.models.registry import token_model_kwargs
from dptpu.ops.schedules import (
    make_ramp_phase_schedule,
    make_step_decay_schedule,
    make_warmup_cosine_schedule,
    make_warmup_step_decay_schedule,
    parse_batch_ramp,
    ramp_multiplier,
    ramp_phase_start,
)
from dptpu.parallel import (
    SLICE_AXIS,
    data_axis_names,
    initialize_distributed,
    shard_host_batch,
    squeeze_axes,
)
from dptpu.resilience import (
    CheckpointManager,
    FaultPlan,
    PreemptionGuard,
    find_resumable,
)
from dptpu.train import plan as step_plan
from dptpu.train.checkpoint import (
    AsyncCheckpointWriter,
    load_checkpoint,
    save_checkpoint,
)
from dptpu.train.loop import train_one_epoch, validate
from dptpu.train.state import create_train_state, make_optimizer
from dptpu.utils.compile_cache import enable_compile_cache
from dptpu.utils.provenance import device_banner


def opt_knobs(cfg: Config) -> tuple:
    """The large-batch training-engine knobs, under the locked fail-fast
    contract (every explicit-but-invalid value raises, pre-compile).

    Returns ``(optimizer, accum_steps, warmup_epochs, label_smoothing)``.
    Each ``DPTPU_*`` env twin OVERRIDES its CLI/config field when set —
    same precedence as the feed knobs — and config values passed
    programmatically get the identical validation as env values:

    * ``DPTPU_OPT`` / ``--optimizer`` — ``sgd`` (reference), ``lars``,
      ``lamb``, ``adamw`` (dptpu/ops/optimizers.py);
    * ``DPTPU_ACCUM`` / ``--accum-steps`` — microbatches per update,
      >= 1 (1 = the exact unaccumulated step);
    * ``DPTPU_WARMUP_EPOCHS`` / ``--warmup-epochs`` — > 0 selects the
      linear-warmup + cosine schedule;
    * ``DPTPU_LABEL_SMOOTH`` / ``--label-smoothing`` — in [0, 1).
    """
    names = ("sgd", "lars", "lamb", "adamw")
    name = env_choice("DPTPU_OPT", names)
    if name is None:
        name = cfg.optimizer
        if name not in names:
            raise ValueError(
                f"--optimizer {name!r} must be one of "
                + "/".join(repr(n) for n in names)
            )
    accum = env_int("DPTPU_ACCUM", None)
    if accum is None:
        accum = cfg.accum_steps
    if accum < 1:
        raise ValueError(
            f"DPTPU_ACCUM/--accum-steps {accum} must be >= 1 (1 disables "
            f"gradient accumulation)"
        )
    warmup = env_int("DPTPU_WARMUP_EPOCHS", None)
    if warmup is None:
        warmup = cfg.warmup_epochs
    if warmup < 0:
        raise ValueError(
            f"DPTPU_WARMUP_EPOCHS/--warmup-epochs {warmup} must be >= 0 "
            f"(0 keeps the variant's reference schedule)"
        )
    if 0 < cfg.epochs <= warmup:
        # make_warmup_cosine_schedule would clamp the cosine phase away
        # and the whole run would sit below peak LR — silently-worse
        # training, so it fails fast like every other invalid knob
        raise ValueError(
            f"DPTPU_WARMUP_EPOCHS/--warmup-epochs {warmup} must be < "
            f"--epochs {cfg.epochs}: the run would end mid-warmup and "
            f"never reach peak LR or the cosine decay"
        )
    smooth = env_float("DPTPU_LABEL_SMOOTH", None)
    if smooth is None:
        smooth = float(cfg.label_smoothing)
    if not 0.0 <= smooth < 1.0:
        raise ValueError(
            f"DPTPU_LABEL_SMOOTH/--label-smoothing {smooth} must be in "
            f"[0, 1) (0 disables smoothing)"
        )
    return name, int(accum), int(warmup), float(smooth)


def ramp_knobs(cfg: Config, warmup_epochs: int) -> tuple:
    """The extreme-scale recipe's knobs (arXiv:1811.05233) under the
    same contract: ``(batch_ramp, warmup_poly)``, the parsed
    ``DPTPU_BATCH_RAMP`` phase table and the ``DPTPU_WARMUP_POLY``
    exponent, each None when unset."""
    spec = env_str("DPTPU_BATCH_RAMP")
    batch_ramp = parse_batch_ramp(spec) if spec else None
    warmup_poly = env_float("DPTPU_WARMUP_POLY", None)
    if warmup_poly is not None and warmup_poly <= 0:
        raise ValueError(
            f"DPTPU_WARMUP_POLY={warmup_poly} must be > 0 (the warmup "
            f"exponent; 1 is the linear ramp, 2 the 1811.05233 "
            f"polynomial)"
        )
    if warmup_poly is not None and warmup_epochs == 0 \
            and not cfg.evaluate:
        # composition check only where a schedule is built: --evaluate
        # trains nothing, so a training env's exported knob must not
        # block a pure evaluation (the DPTPU_BATCH_RAMP treatment)
        raise ValueError(
            f"DPTPU_WARMUP_POLY={warmup_poly} needs a warmup phase to "
            f"shape — set --warmup-epochs/DPTPU_WARMUP_EPOCHS > 0"
        )
    if batch_ramp is not None and not cfg.evaluate:
        if warmup_epochs == 0:
            raise ValueError(
                "DPTPU_BATCH_RAMP is the large-batch recipe's ramp and "
                "needs the warmup->cosine schedule — set "
                "--warmup-epochs/DPTPU_WARMUP_EPOCHS > 0"
            )
        if cfg.epochs > 0 and batch_ramp[-1][0] >= cfg.epochs:
            raise ValueError(
                f"DPTPU_BATCH_RAMP names epoch {batch_ramp[-1][0]} but "
                f"the run ends at --epochs {cfg.epochs} — that phase "
                f"would never train"
            )
    return batch_ramp, warmup_poly


def _epoch_scalars(train_stats, val_stats, obs_report, *, train_batch,
                   val_batch, accum_steps, opt_shard_bytes,
                   tracer_dropped) -> dict:
    """One epoch's scalars under their dashboard tags: the reference's
    eleven (imagenet_ddp_apex.py:280-290), the feed's telemetry, the
    optimizer's and the experts' where the run has them, and the
    step-phase attribution. ``fit()`` publishes them into the metrics
    registry, which flushes ONCE per epoch to every attached sink (TB
    writer, the per-host JSONL log, the console Obs line)."""
    bt = max(train_stats["batch_time"], 1e-9)
    val_bt = max(val_stats.get("batch_time", bt), 1e-9)
    scalars = {
        "Throughput/train": train_batch / bt,
        "Throughput/val": val_batch / val_bt,
        "Time/train": train_stats["batch_time"],
        "Time/val": val_bt,
        # feed-rate accounting: loader wait per step + the fraction of
        # the epoch the chip spent starved for data
        "Time/data": train_stats["data_time"],
        "Starvation/train": train_stats["starvation"],
        "Loss/train": train_stats["loss"],
        "Loss/val": val_stats["loss"],
        "Top1/train": train_stats["top1"],
        "Top1/val": val_stats["top1"],
        "Top5/train": train_stats["top5"],
        "Top5/val": val_stats["top5"],
        "Lr": train_stats["lr"],
    }
    for tag, key in (
        # decode-cache + zero-copy + decode-ahead ring telemetry
        # (bytes_copied_per_batch = 0 is the zero-copy contract on a
        # dashboard)
        ("Cache/hit_rate", "cache_hit_rate"),
        ("Feed/bytes_copied_per_batch", "bytes_copied_per_batch"),
        ("Feed/ring_occupancy", "ring_occupancy"),
        ("Feed/issue_ahead_depth", "issue_ahead_depth"),
        ("Feed/straggler_reissues", "straggler_reissues"),
        ("Feed/io_wait_s", "io_wait_s"),
        # packed-shard streaming plane (dptpu/data/stream.py):
        # byte-ring vs fadvise ownership, store fetch health
        ("Feed/odirect_active", "odirect_active"),
        ("Feed/shard_bytes_read", "shard_bytes_read"),
        ("Feed/shard_extents_read", "shard_extents_read"),
        ("Feed/store_wait_s", "store_wait_s"),
        ("Feed/store_retries", "store_retries"),
    ):
        if key in train_stats:
            scalars[tag] = float(train_stats[key])
    scalars["Opt/accum_steps"] = accum_steps
    for tag, key in (
        # the layer-wise trust-ratio spread, from the optimizer's norms
        ("Opt/trust_ratio_min", "trust_min"),
        ("Opt/trust_ratio_mean", "trust_mean"),
        ("Opt/trust_ratio_max", "trust_max"),
    ):
        if key in train_stats:
            scalars[tag] = train_stats[key]
    if opt_shard_bytes is not None:
        # under a sharded weight update, the bytes of optimizer state
        # one chip touches per update (the 1/N claim on a dashboard)
        scalars["Opt/update_shard_bytes"] = opt_shard_bytes
    for tag, key in (
        # expert layers (a model that has them): the load of the
        # experts held here, a step and a layer, over the epoch
        ("Moe/load_max", "moe_load_max"),
        ("Moe/load_mean", "moe_load_mean"),
        ("Moe/local_slot_share", "moe_local_slot_share"),
        ("Moe/dropped_tokens", "moe_dropped"),
        ("Moe/compact_share", "moe_compact_share"),
        # a model with a second loss term: it alone, before its weight
        ("Loss/train_mtp", "mtp_loss"),
    ):
        if key in train_stats:
            scalars[tag] = train_stats[key]
    if obs_report is not None:
        scalars.update({
            "Obs/data_wait_s": obs_report["data_wait_s"],
            "Obs/h2d_s": obs_report["h2d_s"],
            "Obs/device_s": obs_report["device_s"],
            "Obs/ckpt_s": obs_report["ckpt_s"],
            "Obs/other_s": obs_report["other_s"],
            "Obs/coverage": obs_report["coverage"],
            "Obs/step_p50_s": obs_report["step_p50_s"],
            "Obs/step_p90_s": obs_report["step_p90_s"],
            "Obs/step_max_s": obs_report["step_max_s"],
            "Obs/anomalous_steps": len(obs_report["anomalous_steps"]),
            "Obs/tracer_dropped": tracer_dropped,
        })
    return scalars


def fit(cfg: Config, *, image_size: int = 224, verbose: Optional[bool] = None):
    """Train (or evaluate) per the config; returns a result dict."""
    try:
        return _fit(cfg, image_size=image_size, verbose=verbose)
    finally:
        # the tracer is installed on _fit's first lines, so that set-up
        # can be spanned: every way out of it (a knob that fails fast,
        # --evaluate's early return) puts the inert defaults back
        obs.reset()


def _fit(cfg: Config, *, image_size: int, verbose: Optional[bool]):
    t_fit0 = time.perf_counter()
    # self-tuning control plane (ISSUE 19): the offline artifact applies
    # FIRST — it env-injects ONLY knobs nothing else set, so every
    # fail-fast parse below sees the tuned values while explicit
    # env/CLI knobs always win; the banner names every applied value
    from dptpu.tune.artifact import apply_tuning, tune_knobs

    tune_conf = tune_knobs()
    tuning = None
    if tune_conf["artifact"]:
        cli_set = set()
        if cfg.accum_steps != 1:
            cli_set.add("DPTPU_ACCUM")  # explicit --accum-steps wins
        tuning = apply_tuning(tune_conf["artifact"], cli_set=cli_set)
    # resilience knobs fail fast, before any compile (the locked contract)
    if cfg.ckpt_steps < 0:
        raise ValueError(
            f"--ckpt-steps {cfg.ckpt_steps} must be >= 0 (0 disables "
            f"mid-epoch checkpoints)"
        )
    if cfg.ckpt_keep < 1:
        raise ValueError(f"--ckpt-keep {cfg.ckpt_keep} must be >= 1")
    fault_plan = FaultPlan.from_env()  # raises on a typo'd DPTPU_FAULT
    obs_conf = obs.obs_knobs()  # DPTPU_OBS_* knobs fail fast too
    # --- observability (dptpu/obs): one tracer, one metrics registry,
    # one sink fan-out, installed HERE so that set-up (two thirds of a
    # short run) is spanned too: ``setup.*`` phases, consecutive from
    # this function's first line to loop entry, with the ``compile``
    # spans of dptpu/utils/compile_cache.py's listener inside them.
    tracer = obs.set_tracer(
        obs.Tracer(capacity=obs_conf["ring"])
        if obs_conf["enabled"] else obs.NullTracer()
    )
    registry = obs.set_registry(obs.Registry())
    if tracer.enabled:
        # what ran before this function: the process's start, by the
        # kernel's clock, to the first line above (for a CLI user the
        # interpreter's start, imports and argument parsing; under a
        # harness whatever it did first). Left out where the host does
        # not say when the process started.
        age = obs.process_age_s()
        if age is not None:
            born = time.perf_counter() - age
            tracer.record("setup.before_fit", born, t_fit0 - born)
    setup_open = ["knobs_mesh", t_fit0]

    def _setup_phase(name):
        # close the set-up phase that is running as a ``setup.<name>``
        # span and open the next (None: set-up is over)
        now = time.perf_counter()
        tracer.record("setup." + setup_open[0], setup_open[1],
                      now - setup_open[1])
        setup_open[:] = [name, now]

    # elastic-lifecycle knobs (DPTPU_ELASTIC / DPTPU_QUORUM_DEADLINE_S /
    # DPTPU_STRAGGLER_*) fail fast pre-compile under the same contract
    from dptpu.resilience.elastic import elastic_knobs

    el_conf = elastic_knobs()
    # large-batch engine knobs (optimizer / accumulation / warmup /
    # smoothing) fail fast pre-compile under the same locked contract
    opt_name, accum_steps, warmup_epochs, label_smooth = opt_knobs(cfg)
    # what the model is trained on decides the data source, the step's
    # loss and what its factory is handed (dptpu/models/registry.py)
    task = model_task(cfg.arch)
    token_kwargs = token_model_kwargs(cfg, task)
    batch_ramp, warmup_poly = ramp_knobs(cfg, warmup_epochs)
    enable_compile_cache()
    initialize_distributed(cfg)
    derived = derive(
        cfg,
        local_device_count=jax.local_device_count(),
        num_processes=jax.process_count(),
        process_index=jax.process_index(),
    )
    if verbose is None:
        verbose = derived.is_chief

    def _say(lines):
        # what a layer has to tell the user, on the chief
        for line in lines if verbose else ():
            print(line)

    _say([device_banner()])
    if not cfg.evaluate and derived.per_device_batch_size % accum_steps:
        raise ValueError(
            f"--accum-steps/DPTPU_ACCUM {accum_steps} does not divide the "
            f"per-device batch of {derived.per_device_batch_size} — the "
            f"microbatch is per-device-batch/K, so pick a divisor (or "
            f"raise the batch size)"
        )

    single_device = cfg.gpu is not None or jax.device_count() == 1
    # THE run geometry tuple, built once: stamped into every checkpoint
    # (CheckpointManager / boundary saves) AND compared by the
    # mid-epoch resume cross-check — one construction site, so the
    # saved tuple and the checked tuple cannot desynchronize
    run_geom = (derived.global_device_count, derived.global_batch_size,
                accum_steps)
    if batch_ramp is not None and cfg.evaluate:
        _say(["=> DPTPU_BATCH_RAMP ignored: --evaluate does not train"])
        batch_ramp = None
    # which step family runs, on which mesh (dptpu/train/plan.py): the
    # parallelism knobs fail fast here, and the notices say what each
    # request came to
    plan = step_plan.decide(
        cfg, derived, task=task, n_devices=jax.device_count(),
        accum_steps=accum_steps, batch_ramp=batch_ramp,
    )
    mesh = step_plan.open_mesh(
        plan, elastic_resume=bool(el_conf["elastic"] and cfg.resume))
    _say(plan.notices)
    if cfg.multiprocessing_distributed and verbose:
        # accepted-and-mapped, never silent: the reference forks one
        # process per GPU (nd_imagenet.py:72-76); dptpu is one process
        # per HOST driving every local chip through the mesh, so the
        # flag's intent (use all local accelerators) is already the
        # default and spawning would only duplicate work.
        print(
            "=> --multiprocessing-distributed noted: dptpu always drives "
            "all local chips from one process per host (SPMD mesh); no "
            "worker processes are spawned"
        )
    if cfg.variant == "apex" and cfg.local_rank is not None and verbose:
        # accepted-and-mapped, never silent (imagenet_ddp_apex.py:88,
        # 120-123): the launcher's per-GPU pinning flag has no per-chip
        # process here — one process per HOST drives every local chip
        print(
            f"=> --local_rank {cfg.local_rank} noted: dptpu is one "
            "process per host (SPMD mesh), so per-device process "
            "pinning is not needed; all local chips are driven together"
        )
    put = (
        partial(jax.device_put, device=jax.local_devices()[cfg.gpu or 0])
        if single_device
        else partial(shard_host_batch, mesh=mesh)
    )

    if cfg.variant == "apex" and cfg.arch == "inception_v3":
        # reference parity: the Apex script rejects inception_v3 by name
        # (imagenet_ddp_apex.py:209-210); ddp/nd train its main head here
        raise RuntimeError(
            "Currently, inception_v3 is not supported by this example."
        )

    _setup_phase("data")

    def _ramp_mult(epoch: int) -> int:
        return (ramp_multiplier(batch_ramp, epoch)
                if batch_ramp is not None else 1)

    ramp_mult = _ramp_mult(cfg.start_epoch)
    # workers, cache, sampler and the validation split are the feed's
    # (dptpu/data/feed.py); its train pool starts here, beside the
    # weights, the state and the step's compile
    feed = build_feed(
        cfg, derived, task=task,
        # the rows' length and the ids' range are the model's: its
        # configuration is read before any weight is made
        model_config=(create_model(cfg.arch, **token_kwargs).config
                      if task == "tokens" else None),
        image_size=image_size, ramp_mult=ramp_mult,
    )
    _say(feed.notices)
    train_loader, val_loader = feed.train_loader, feed.val_loader
    num_classes = feed.num_classes
    host_batch = derived.per_host_batch_size

    def _spe(mult: int) -> int:
        # mirrors DataLoader.__len__ under drop_last=True — the phase
        # table must be computable WITHOUT building a loader per phase
        return max(len(feed.train_sampler) // (host_batch * mult), 1)

    def _cum_steps(epoch: int) -> int:
        # optimizer steps completed before `epoch` starts — the phase
        # schedule's step anchor and the ramped --start-epoch offset
        return sum(_spe(_ramp_mult(e)) for e in range(epoch))

    steps_per_epoch = max(len(train_loader), 1)

    compute_dtype = jnp.bfloat16 if derived.use_bf16 else jnp.float32
    # BN activations follow the compute dtype (statistics always accumulate
    # in fp32 inside flax) unless --keep-batchnorm-fp32 True pins BN I/O to
    # fp32 — the Apex flag's strictest reading (imagenet_ddp_apex.py:93).
    keep_bn_fp32 = str(cfg.keep_batchnorm_fp32).lower() in ("true", "1")
    # SyncBN spans EVERY replica: on a hierarchical mesh the BatchNorm
    # statistics pmean over both data axes (slice × dp_in_slice) — the
    # flax axis_name accepts the tuple like any jax collective. Under
    # GSPMD the global batch is one logical program: BN statistics are
    # always global and the model carries no shard-local axis name.
    _bn_axis = None
    if derived.sync_bn and mesh is not None and plan.family != "gspmd":
        _bn_axis = squeeze_axes(data_axis_names(mesh))
    _setup_phase("model_init")
    # a factory takes what applies to it and is not handed the rest: a
    # token-sequence model its share and length, an image model the
    # classes and the BatchNorm policy
    model = create_model(
        cfg.arch, pretrained=cfg.pretrained, dtype=compute_dtype,
        **token_kwargs,
    ) if task == "tokens" else create_model(
        cfg.arch,
        pretrained=cfg.pretrained,
        num_classes=num_classes,
        dtype=compute_dtype,
        bn_axis_name=_bn_axis,
        bn_dtype=jnp.float32 if keep_bn_fp32 else None,
    )
    # LR schedule: --warmup-epochs > 0 selects the large-batch recipe's
    # linear-warmup + cosine decay (every ImageNet-in-minutes paper's
    # shape); otherwise each variant keeps its reference schedule.
    # Accumulation does NOT rescale the LR: --accum-steps splits the
    # global batch the user already chose into K microbatches (the
    # optimizer still steps on exactly global_batch samples), so the
    # apex linear-scaling rule's global_batch/256 factor already
    # carries the full batch scale.
    sched_lr = derived.scaled_lr

    def _phase_schedule(mult: int, epoch: int):
        # ONE ramp phase's warmup->cosine in fractional epochs: the
        # anchor (phase-start epoch, cumulative step count) is derived
        # from the ramp table alone, so a resumed run reconstructs the
        # identical schedule; the peak scales x mult per the
        # linear-scaling rule (the batch grew x mult)
        e0 = ramp_phase_start(batch_ramp, epoch)
        return make_ramp_phase_schedule(
            sched_lr * mult, _spe(mult), cfg.epochs, warmup_epochs,
            epoch0=e0, step0=_cum_steps(e0),
            power=warmup_poly if warmup_poly is not None else 1.0,
        )

    if batch_ramp is not None:
        schedule = _phase_schedule(ramp_mult, cfg.start_epoch)
    elif warmup_epochs > 0:
        schedule = make_warmup_cosine_schedule(
            sched_lr, steps_per_epoch, cfg.epochs, warmup_epochs,
            power=warmup_poly if warmup_poly is not None else 1.0,
        )
    elif cfg.variant == "apex":
        schedule = make_warmup_step_decay_schedule(sched_lr, steps_per_epoch)
    else:
        schedule = make_step_decay_schedule(sched_lr, steps_per_epoch)
    adam_kw = {"betas": (cfg.beta1, cfg.beta2), "eps": cfg.eps}
    tx = make_optimizer(cfg.momentum, cfg.weight_decay, name=opt_name,
                        **adam_kw)
    if verbose and (opt_name != "sgd" or accum_steps > 1 or warmup_epochs
                    or label_smooth):
        print(
            f"=> large-batch engine: optimizer={opt_name}, "
            f"accum={accum_steps} (global batch "
            f"{derived.global_batch_size} in microbatches of "
            f"{derived.per_device_batch_size // accum_steps}/chip — "
            f"emulates {accum_steps}x the DP width), "
            f"warmup={warmup_epochs} epochs"
            + (" (linear->cosine)" if warmup_epochs else "")
            + f", label smoothing {label_smooth}"
        )
    seed = cfg.seed if cfg.seed is not None else 0
    rng = jax.random.PRNGKey(seed)
    # one row as ``model.init`` takes it: an image, or a row of token ids
    # of the model's own length
    input_shape, input_dtype = (
        ((1, model.config.sequence_length), jnp.int32)
        if task == "tokens"
        else ((1, image_size, image_size, 3), jnp.float32)
    )
    pretrained_vars = None
    if cfg.pretrained:
        # converted-torchvision weights (imagenet_ddp.py:109-111); see
        # dptpu/models/pretrained.py for the offline conversion workflow
        from dptpu.models.pretrained import load_pretrained_variables

        # the loader makes its own ``model.init`` template to check the
        # weights against: under --pretrained the init's many small
        # compiles show inside this phase
        _setup_phase("pretrained")
        pretrained_vars = load_pretrained_variables(
            cfg.arch, model, input_shape=input_shape,
            input_dtype=input_dtype,
        )
        if verbose:
            print(f"=> using pre-trained model '{cfg.arch}'")
        _setup_phase("state_commit")
    state = create_train_state(
        rng,
        model,
        tx,
        input_shape=input_shape,
        input_dtype=input_dtype,
        # --start-epoch without --resume still lands on the reference's
        # epoch-N learning rate (the schedule reads the global step);
        # under a batch ramp the offset is the cumulative step count
        # over the earlier (differently-sized) phases
        initial_step=(_cum_steps(cfg.start_epoch)
                      if batch_ramp is not None
                      else cfg.start_epoch * steps_per_epoch),
        variables=pretrained_vars,
    )
    if pretrained_vars is None:
        # create_train_state ran ``model.init``: that was model_init
        _setup_phase("state_commit")
    # the state holds the loaded leaves now: once it is placed on the
    # device the host's copy goes, and does not ride along to the end of
    # the run (3 GB beside a 9 GB save for a 770 M-parameter model)
    del pretrained_vars
    if hasattr(model, "fitted_to"):
        # a model that can keep residuals through its rematerialisation
        # learns what the chip has beside the train state; a device that
        # reports no size (the CPU) leaves it keeping nothing
        stats = jax.local_devices()[0].memory_stats() or {}
        model = model.fitted_to(
            stats.get("bytes_limit", 0),
            sum(x.nbytes for x in jax.tree_util.tree_leaves(state)))
        state = state.replace(apply_fn=model.apply)
        # (the step decides again from the rows it is traced on)
        _say([model.kept(derived.per_device_batch_size)
              .notice(model.residual_budget)])

    best_acc1, start_epoch, resume_step = 0.0, cfg.start_epoch, 0
    elastic_resume = None  # set when DPTPU_ELASTIC re-maps a geometry
    if cfg.resume:
        # --resume accepts a file OR a directory; corrupt/truncated files
        # fall back to the newest VERIFIABLE checkpoint (CRC footer /
        # structural check — dptpu/resilience/checkpoint.py)
        resolved = find_resumable(cfg.resume, verbose=verbose)
        if resolved is not None:
            # arch + steps_per_epoch let a reference-produced torch
            # checkpoint resume too (key-mapped params/momentum, step
            # rebuilt on the epoch boundary — see train/checkpoint.py)
            state, meta = load_checkpoint(
                resolved, state, arch=cfg.arch,
                steps_per_epoch=steps_per_epoch,
            )
            if cfg.start_epoch == 0:
                start_epoch = meta["epoch"]
                resume_step = max(int(meta.get("step_in_epoch", 0)), 0)
                # geometry cross-check: a mid-epoch replay is only
                # exact when the run that resumes has the SAME batch
                # geometry as the run that saved — UNLESS DPTPU_ELASTIC
                # opts into re-mapping the position onto this run's
                # geometry (dptpu/resilience/elastic.py): the sampler's
                # interleaved shard assignment makes the visited-index
                # prefix geometry-independent, so the remainder replays
                # exactly on the new world. Without the opt-in the
                # fail-fast names BOTH tuples. Pre-geometry files fall
                # back to the data_position cross-check below.
                saved_geom = tuple(meta.get("geometry", (-1, -1, -1)))
                # under a batch ramp the geometry THIS run trains
                # epoch N at is the ramped one — the stamp a mid-phase
                # (or phase-boundary) save carries, so the cross-check
                # compares ramped-to-ramped and a ramp boundary
                # resumes exactly (ISSUE 13 satellite)
                expect_geom = (
                    (run_geom[0],
                     run_geom[1] * _ramp_mult(meta["epoch"]),
                     run_geom[2])
                    if batch_ramp is not None else run_geom
                )
                if resume_step and saved_geom[0] >= 0 \
                        and saved_geom != expect_geom \
                        and batch_ramp is not None:
                    raise ValueError(
                        f"'{resolved}' was saved mid-epoch (step "
                        f"{resume_step}) at geometry {saved_geom}, but "
                        f"this run's DPTPU_BATCH_RAMP puts epoch "
                        f"{meta['epoch']} at {expect_geom} — resume "
                        f"with the ramp spec the save was made under "
                        f"(DPTPU_ELASTIC does not compose with "
                        f"DPTPU_BATCH_RAMP), or pass --start-epoch to "
                        f"restart from an epoch boundary."
                    )
                if resume_step and saved_geom[0] >= 0 \
                        and saved_geom != expect_geom \
                        and not el_conf["elastic"]:
                    raise ValueError(
                        f"'{resolved}' was saved mid-epoch (step "
                        f"{resume_step}) by a run with (world_size, "
                        f"global_batch, accum) = {saved_geom}, but this "
                        f"run is {run_geom} — the batch geometry "
                        f"changed, so the exact mid-epoch replay is "
                        f"impossible. Resume on the saved geometry, "
                        f"pass --start-epoch to restart from an epoch "
                        f"boundary, or set DPTPU_ELASTIC=1 to re-map "
                        f"the saved position onto this geometry "
                        f"(shrink/grow resume — the remainder of the "
                        f"epoch replays exactly; the LR is rescaled "
                        f"per the linear-scaling rule)."
                    )
                # sharding fingerprint cross-check (ISSUE 16): the
                # checkpoint always holds gathered full leaves, so ANY
                # placement can load it — but a mid-epoch replay under
                # a silently-changed sharding config (a different ZeRO
                # stage, an edited rules table) is a config drift the
                # operator should confirm, not discover post-hoc in a
                # diverged curve. DPTPU_ELASTIC is the confirmation:
                # the full-leaf state simply re-shards onto the new
                # placement (shard_zero3_state et al. device_put). ""
                # means a pre-rules file — no stamp, no check.
                saved_sharding = str(meta.get("sharding", ""))
                if resume_step and saved_sharding \
                        and saved_sharding != plan.fingerprint \
                        and not el_conf["elastic"]:
                    raise ValueError(
                        f"'{resolved}' was saved mid-epoch (step "
                        f"{resume_step}) under sharding "
                        f"'{saved_sharding}' but this run places as "
                        f"'{plan.fingerprint}' — the sharding config (ZeRO "
                        f"stage, TP rule, or the partition-rules table "
                        f"itself) changed. Resume with the saved "
                        f"config, pass --start-epoch to restart from "
                        f"an epoch boundary, or set DPTPU_ELASTIC=1 to "
                        f"re-shard the full-leaf checkpoint onto the "
                        f"new placement."
                    )
                if saved_sharding and saved_sharding != plan.fingerprint \
                        and el_conf["elastic"] and verbose:
                    print(f"=> elastic re-shard: checkpoint sharding "
                          f"'{saved_sharding}' -> '{plan.fingerprint}' "
                          f"(full-leaf state re-places on load)")
                if resume_step and saved_geom[0] >= 0 \
                        and saved_geom != expect_geom:
                    # the elastic shrink/grow remap (ROADMAP item 3a)
                    from dptpu.resilience.elastic import (
                        remap_resume_position,
                    )

                    remap = remap_resume_position(
                        saved_geom, run_geom, resume_step,
                        # the slices constraint binds only when the
                        # hierarchical mesh is actually in play: the
                        # remap must not fail over a knob the plan
                        # declared a no-op
                        slices=plan.mesh_axes.get(SLICE_AXIS, 1),
                        num_examples=len(feed.train_ds),
                    )
                    # what the SAVED run trained at under the linear-
                    # scaling rule — reconstructed from THIS run's base
                    # --lr, since checkpoints do not stamp it: accurate
                    # when the base LR is unchanged between attempts
                    # (the normal elastic restart), labeled as such
                    old_lr = (
                        cfg.lr * saved_geom[1] / 256.0
                        if cfg.variant == "apex" else cfg.lr
                    )
                    elastic_resume = {
                        "saved_geometry": list(saved_geom),
                        "new_geometry": list(run_geom),
                        "consumed": remap.consumed,
                        "resume_step_saved": resume_step,
                        "resume_step": remap.new_step,
                        "lr_saved": old_lr,  # assumes an unchanged base --lr
                        "lr": derived.scaled_lr,
                        "accum_changed": remap.accum_changed,
                    }
                    resume_step = remap.new_step
                    # LOUD by contract, not verbose-gated: an elastic
                    # restart changes the optimization trajectory (the
                    # batch, and with it the linear-scaled LR) and that
                    # must never scroll by silently
                    print(
                        f"=> ELASTIC RESUME: geometry {saved_geom} -> "
                        f"{run_geom}; {remap.consumed} samples of the "
                        f"epoch already trained, replaying the "
                        f"remainder from step {remap.new_step} (was "
                        f"step {elastic_resume['resume_step_saved']}); "
                        f"LR {old_lr:g} -> {derived.scaled_lr:g} per "
                        f"the linear-scaling rule (saved-run LR "
                        f"reconstructed from this run's base --lr)"
                        + (" ; accumulation depth changed — microbatch "
                           "virtual-replica streams differ from the "
                           "saved run" if remap.accum_changed else ""),
                        file=sys.stderr,
                    )
                # legacy (pre-geometry) files: the checkpoint's
                # data_position (samples consumed per host) must agree
                # with step x THIS run's host batch, or the replay
                # contract is void — resuming would re-train (or skip)
                # part of the epoch silently. (An elastic remap above
                # already re-expressed the position in THIS geometry.)
                meta_dp = int(meta.get("data_position", -1))
                resume_host_batch = host_batch * _ramp_mult(meta["epoch"])
                if elastic_resume is None and resume_step \
                        and meta_dp >= 0 \
                        and meta_dp != resume_step * resume_host_batch:
                    raise ValueError(
                        f"'{resolved}' was saved at step {resume_step} "
                        f"with {meta_dp} samples consumed per host, but "
                        f"this run's per-host batch is "
                        f"{resume_host_batch} "
                        f"({resume_step} x {resume_host_batch} = "
                        f"{resume_step * resume_host_batch}) — the batch "
                        f"geometry changed, so the exact mid-epoch "
                        f"replay is impossible. Resume with the "
                        f"original batch size, or pass --start-epoch "
                        f"to restart from an epoch boundary."
                    )
                if resume_step >= (_spe(_ramp_mult(start_epoch))
                                   if batch_ramp is not None
                                   else steps_per_epoch):
                    # a mid-epoch save from a run with MORE steps/epoch
                    # (different batch size/dataset): the exact replay
                    # contract is void, so land on the next boundary
                    start_epoch += 1
                    resume_step = 0
            else:
                start_epoch = cfg.start_epoch
            best_acc1 = meta["best_acc1"]
            if verbose:
                pos = (f", step {resume_step}" if resume_step else "")
                print(f"=> loaded checkpoint '{resolved}' "
                      f"(epoch {meta['epoch']}{pos})")
        else:
            # warn-and-continue, reference behavior (imagenet_ddp.py:152-153)
            if verbose:
                print(f"=> no checkpoint found at '{cfg.resume}'")

    if batch_ramp is not None and _ramp_mult(start_epoch) != ramp_mult:
        # the resume landed in a different ramp phase than the loaders
        # were provisionally built for: re-enter the resumed phase
        # BEFORE any step compiles (the loop-top switcher handles
        # later boundaries; this handles the entry point)
        ramp_mult = _ramp_mult(start_epoch)
        train_loader.close()
        train_loader = feed.make_train_loader(host_batch * ramp_mult)
        train_loader.start()
        steps_per_epoch = max(len(train_loader), 1)
        schedule = _phase_schedule(ramp_mult, start_epoch)

    _setup_phase("step_build")
    built = step_plan.build(
        plan, mesh, state, schedule, arch=cfg.arch, task=task,
        num_classes=num_classes, compute_dtype=compute_dtype,
        seed=seed, accum_steps=accum_steps, label_smoothing=label_smooth,
        tx_factory=partial(make_optimizer, cfg.momentum, cfg.weight_decay,
                           opt_name, **adam_kw),
        put=put, setup_phase=_setup_phase,
    )
    _say(built.notices)
    state, train_step, eval_step = (built.state, built.train_step,
                                    built.eval_step)
    eval_view = built.eval_view

    def _close_feed():
        train_loader.close()
        val_loader.close()
        for ds in (feed.train_ds, feed.val_ds):
            # streaming datasets own fds + /dev/shm staging slabs;
            # release them at the end of the run (ImageFolder/Synthetic
            # have no close — their caches are reclaimed by the atexit
            # sweeps)
            if hasattr(ds, "close"):
                ds.close()

    if cfg.evaluate:
        stats = validate(
            eval_view(state),
            eval_step,
            DevicePrefetcher(val_loader.epoch(0), put),
            num_batches=len(val_loader),
            print_freq=cfg.print_freq,
            verbose=verbose,
            count_divisor=feed.val_count_divisor,
        )
        _close_feed()
        return {"val": stats, "state": state, "epochs_run": 0}


    # rank-0-only TensorBoard with the reference's run-config comment tag
    # (imagenet_ddp_apex.py:152-159); apex variant only, like the reference
    writer = None
    ckpt_dir = "."
    if cfg.variant == "apex" and derived.is_chief:
        from dptpu.utils.tensorboard import SummaryWriter

        writer = SummaryWriter(
            comment="_{}_chipx{}_b{}_cpu{}_opt{}".format(
                cfg.arch,
                derived.global_device_count,
                cfg.batch_size,
                cfg.workers,
                cfg.opt_level or "bf16",
            )
        )
        ckpt_dir = writer.log_dir  # apex checkpoints into the run dir (:271-277)
    if cfg.ckpt_dir:
        # explicit --ckpt-dir wins over both defaults; may be a plain
        # directory OR a store URL (file:// / http(s)://) — every save,
        # the rotation scan and --resume route through dptpu.data.store
        # with the CRC-footer + fallback-scan contract unchanged
        ckpt_dir = cfg.ckpt_dir

    # structured tracing (SURVEY.md §5: the reference has only wall-clock
    # meters; dptpu adds an opt-in XLA profile): DPTPU_PROFILE=<dir> traces
    # the first training epoch into a TensorBoard-viewable profile.
    profile_dir = env_str("DPTPU_PROFILE")
    if profile_dir and derived.is_chief:
        from dptpu.utils.profiling import device_profile_options

        jax.profiler.start_trace(
            profile_dir, profiler_options=device_profile_options())

    # --- observability sinks (the tracer and registry exist since this
    # function's first lines). Step phases (data_wait/h2d/step/ckpt)
    # record into the span ring; every per-epoch scalar publishes into
    # the registry and flushes once to console + TB + JSONL; SIGUSR2 (or
    # the DPTPU_OBS_TRIGGER sentinel) arms an in-flight device trace of
    # the next DPTPU_OBS_TRACE_STEPS steps — no restart required.
    trace_sink = None
    if obs_conf["dir"]:
        # deliberately PER-HOST, not chief-only: the files are named
        # obs-<host>.* and pod-wide straggler analysis needs every
        # host's timeline (ROADMAP observability follow-on (a))
        trace_sink = obs.TraceSink(obs_conf["dir"])
        registry.add_sink(obs.JsonlSink(trace_sink.jsonl_file))
    if writer is not None:
        registry.add_sink(obs.TensorBoardSink(writer))
    if verbose:
        registry.add_sink(obs.ConsoleSink())
    trigger = None
    if obs_conf["enabled"]:
        trigger = obs.ProfileTrigger(
            obs_conf["dir"] or ckpt_dir,
            trace_steps=obs_conf["trace_steps"],
            tracer=tracer,
            sentinel=obs_conf["trigger"],
            verbose=verbose,
        ).install()

    start_time = time.time()
    # resilience wiring (dptpu/resilience): a preemption guard turns
    # SIGTERM/SIGINT into a cooperative stop (finish the in-flight step,
    # save a mid-epoch checkpoint, return cleanly → exit 0), and the
    # checkpoint manager rotates --ckpt-steps step saves so losing a
    # host costs at most ckpt_steps steps, not an epoch.
    # --ckpt-steps cadence saves run on a background writer thread
    # (device_get + serialize + fsync + rename all off the step loop —
    # ROADMAP resilience follow-on (b)); emergency/preemption saves stay
    # synchronous, draining the writer first so "newest file" == "latest
    # position". DPTPU_ASYNC_CKPT=0 restores fully synchronous saves.
    ckpt_writer = (
        AsyncCheckpointWriter()
        if cfg.ckpt_steps and env_bool("DPTPU_ASYNC_CKPT", True)
        else None
    )
    manager = CheckpointManager(
        directory=ckpt_dir,
        keep=cfg.ckpt_keep,
        is_chief=derived.is_chief,
        arch=cfg.arch,
        # data_position stamps samples-consumed-per-host: under a ramp
        # that is the PHASE batch (kept current by the phase switcher)
        batch_size=host_batch * ramp_mult,
        fault_plan=fault_plan,
        async_writer=ckpt_writer,
        # under a batch ramp every save stamps the PHASE geometry (the
        # global batch actually trained at that epoch), so a resume
        # cross-checks ramped-to-ramped and a ramp boundary resumes
        # exactly; the loop-top phase switcher keeps this current
        geometry=(run_geom[0], run_geom[1] * ramp_mult, run_geom[2])
        if batch_ramp is not None else run_geom,
        sharding=plan.fingerprint,
    )
    guard = PreemptionGuard()
    # quorum coordination (dptpu/resilience/quorum.py): when a
    # transport exists — DPTPU_QUORUM_DIR (tests/benches/single-machine
    # pods) or the live jax.distributed KV service — a preemption that
    # reaches only ONE host propagates through the store, the pod
    # agrees on a common stop step, and the gathered mid-epoch save
    # happens behind a barrier-with-deadline. No transport = the PR-2
    # single-signal rules, unchanged; a single host degenerates to the
    # plain PreemptionGuard path at the identical save position.
    from dptpu.resilience.quorum import QuorumSession, make_coordinator

    _quorum_dir = env_str("DPTPU_QUORUM_DIR")
    _coord = make_coordinator(
        derived.num_processes, derived.process_index,
        el_conf["quorum_deadline_s"], directory=_quorum_dir,
        # protocol keys scoped to this run ATTEMPT: the resume position
        # is the one value every host derives identically, and it moves
        # with each preemption — a restart pointed at the same store
        # must not re-read the previous attempt's stop request
        namespace=f"e{start_epoch:04d}s{resume_step:06d}-",
    )
    qs = QuorumSession(_coord, guard) if _coord is not None else None
    if qs is not None and verbose:
        print(
            f"=> quorum save armed: {derived.num_processes} host(s), "
            f"deadline {el_conf['quorum_deadline_s']:g}s"
            + (f", store dir {_quorum_dir}" if _quorum_dir else
               " over the jax.distributed KV service")
        )
    # host-lost verdict (the "gone for good" trigger for elastic
    # resume): the fault harness — or, on a real pod, the chief's
    # heartbeat monitor — flips this flag; the loop then stops cleanly,
    # saves synchronously at the exact position, and the run reports
    # host_lost so the operator restarts shrunk with DPTPU_ELASTIC=1.
    lost = {"flag": False}

    def _host_lost():
        lost["flag"] = True
        print(
            "WARNING: host marked LOST (gone for good) — stopping with "
            "a sync save at the current position; restart on the "
            "shrunk world with DPTPU_ELASTIC=1 to replay the remainder",
            file=sys.stderr,
        )

    if fault_plan is not None:
        fault_plan.bind_worker_kill(train_loader.kill_one_worker)
        fault_plan.bind_host_lost(_host_lost)
        if qs is not None:
            fault_plan.bind_quorum_request(qs.request_remote)
        if verbose:
            print(f"=> fault injection armed: DPTPU_FAULT={fault_plan.spec}")
    # Emergency (single-host-initiated) saves must not enter a cross-host
    # gather: on a divergent failure only the raising host reaches the
    # handler, and a collective it enters alone hangs the job instead of
    # surfacing the error. Graceful preemption is different — cluster
    # SIGTERM reaches every host, so hosts converge on the same save
    # (full consensus is ROADMAP open item (a)).
    emergency_ok = derived.num_processes == 1 or not built.eval_view_gathers

    def _preempt_save_ok() -> bool:
        # Graceful-preemption saves may gather when the signal plausibly
        # reached every host: cluster preemption broadcasts SIGTERM, so
        # all hosts converge on the same save. A SIGINT (operator Ctrl-C
        # on ONE host) must not enter a collective alone — UNLESS the
        # quorum barrier proves the whole pod checked in within the
        # deadline (dptpu/resilience/quorum.py): then every host enters
        # the gather together and the save is pod-consistent even for a
        # single-host signal. No quorum / barrier timeout = skip the
        # gathered save (the boundary checkpoint stands) instead of
        # hanging the pod.
        import signal as _signal

        if emergency_ok:
            # no collective in this save (single host, or state never
            # gathers): nothing to coordinate
            return True
        if qs is not None:
            # EVERY host goes through the barrier — including the one
            # that caught the SIGTERM. If the signal host skipped it
            # (the pre-quorum rule below), its peers would wait for a
            # check-in that never comes, time out, skip the save, and
            # the signal host would enter the gather alone: the exact
            # hang this module exists to prevent. All hosts stopped at
            # the same agreed step, so the barrier tag matches.
            return qs.save_barrier()
        return guard.signum == _signal.SIGTERM

    def _drain_spans():
        # every drain of the shared tracer flows through here so an
        # on-demand profile window straddling the drain point keeps its
        # early spans (ProfileTrigger.absorb)
        spans = tracer.drain()
        if trigger is not None:
            trigger.absorb(spans)
        return spans

    # straggler-driven control (dptpu/resilience/elastic.py): armed by
    # DPTPU_STRAGGLER_FACTOR on a process-mode feed — per-worker span
    # latencies stream into P² quantiles and a persistently-slow worker
    # escalates re-split → eviction through the loader seam. Thread
    # mode has no worker pool to steer: the explicit knob gets a
    # notice, never silence (the locked contract).
    straggler = None
    if el_conf["straggler_factor"] is not None and not cfg.evaluate:
        if feed.workers_mode == "process":
            from dptpu.resilience.elastic import StragglerController

            straggler = StragglerController(
                train_loader,
                el_conf["straggler_factor"],
                persist=el_conf["straggler_persist"],
                on_event=(trace_sink.log_event if trace_sink is not None
                          else None),
            )
            if verbose:
                print(
                    f"=> straggler control armed: re-split at "
                    f"{el_conf['straggler_factor']:g}x the healthiest "
                    f"worker's span p50 for "
                    f"{el_conf['straggler_persist']} consecutive "
                    f"verdicts, eviction at 2x that"
                )
        elif verbose:
            print(f"=> DPTPU_STRAGGLER_FACTOR ignored: thread-mode feed "
                  f"({THREAD_MODE_WHEN}: no worker pool the controller "
                  f"could re-split/evict)")

    # online tune control (dptpu/tune/controller.py, ISSUE 19): armed
    # by DPTPU_TUNE_CONTROL, each actuator bounded, rate-limited, and
    # individually disarmable. No new thread: they tick on the host
    # thread in the same post-step hook as the straggler controller.
    tune_ctl = None
    if tune_conf["control"] and not cfg.evaluate:
        from dptpu.tune.controller import (
            Controller,
            decode_ahead_actuator,
            host_lost_actuator,
        )

        _tune_evt = (trace_sink.log_event if trace_sink is not None
                     else None)
        tune_ctl = Controller()
        if "host_lost" in tune_conf["control"] and qs is not None \
                and derived.is_chief:
            # chief-only, like the manual missing_hosts verdict it
            # automates: one declaration, then the elastic restart
            tune_ctl.add(host_lost_actuator(
                qs.coord, lambda missing: _host_lost(),
                deadline_s=el_conf["quorum_deadline_s"],
                interval_s=tune_conf["interval_s"], on_event=_tune_evt,
            ))
        if "decode_ahead" in tune_conf["control"]:
            if feed.workers_mode == "process":
                # callable indirection: the ramp phase switch rebuilds
                # the loader and the actuator must follow it, not a
                # closed one
                tune_ctl.add(decode_ahead_actuator(
                    lambda: train_loader,
                    interval_s=tune_conf["interval_s"],
                    on_event=_tune_evt,
                ))
            elif verbose:
                print(f"=> tune control: decode_ahead ignored on a "
                      f"thread-mode feed ({THREAD_MODE_WHEN}: no ring to "
                      f"deepen)")
        if not tune_ctl.actuators:
            tune_ctl = None
        elif verbose:
            print(
                f"=> tune control armed: "
                f"{', '.join(a.name for a in tune_ctl.actuators)} "
                f"(interval {tune_conf['interval_s']:g}s; disarm with "
                f"DPTPU_TUNE_CONTROL=off)"
            )

    # per-step tick: the profiling trigger, fault injection, the quorum
    # protocol and the straggler/tune controllers all ride ONE post-step
    # hook (order matters: faults fire before quorum reads the guard, so
    # a same-step signal reaches agreement on the step it landed)
    _ticks = [t for t in (
        trigger.tick if trigger is not None else None,
        fault_plan.on_step if fault_plan is not None else None,
        qs.tick if qs is not None else None,
        straggler.tick if straggler is not None else None,
        tune_ctl.tick if tune_ctl is not None else None,
    ) if t is not None]
    if not _ticks:
        obs_tick = None
    elif len(_ticks) == 1:
        obs_tick = _ticks[0]
    else:
        def obs_tick():
            for t in _ticks:
                t()

    def _stop_requested() -> bool:
        # quorum runs defer the stop to the AGREED step so the pod
        # stays consistent; without a coordinator the local guard (or
        # the host-lost verdict) decides alone, as before
        if lost["flag"]:
            return True
        if qs is not None:
            return qs.should_stop()
        return guard.requested

    def _stop_reason() -> str:
        if guard.signum is not None:
            return guard.signal_name
        if lost["flag"]:
            return "host_lost"
        if qs is not None and qs.stats()["reason"]:
            return f"quorum:{qs.stats()['reason']}"
        return "stop"

    result = {"history": [], "early_stopped": False, "training_time": None,
              "preempted": False}
    ramp_record = []
    if batch_ramp is not None:
        ramp_record.append({
            "epoch": start_epoch, "mult": ramp_mult,
            "global_batch": run_geom[1] * ramp_mult,
            "steps_per_epoch": steps_per_epoch,
            "peak_lr": sched_lr * ramp_mult,
        })

    def _enter_ramp_phase(m: int, epoch: int):
        # the batch-size ramp's phase switch (arXiv:1811.05233): bigger
        # per-host batch, fewer steps/epoch, peak LR x m per the
        # linear-scaling rule, geometry stamp updated so checkpoints
        # carry the phase they were trained at. LOUD by contract — a
        # changed batch/LR must never scroll by silently.
        nonlocal train_loader, train_step, schedule, steps_per_epoch
        nonlocal ramp_mult
        old_batch = host_batch * ramp_mult
        old_ahead = train_loader.decode_ahead
        ramp_mult = m
        train_loader.close()
        train_loader = feed.make_train_loader(host_batch * m)
        if old_ahead is not None and (
                train_loader.decode_ahead is None
                or train_loader.decode_ahead < old_ahead):
            # a controller-deepened issue window survives the rebuild
            # (the ctor already re-applied any explicit env value; only
            # carry forward what grew beyond it)
            train_loader.decode_ahead = old_ahead
        steps_per_epoch = max(len(train_loader), 1)
        schedule = _phase_schedule(m, epoch)
        train_step = built.rebuild(schedule)
        manager.geometry = (run_geom[0], run_geom[1] * m, run_geom[2])
        manager.batch_size = host_batch * m
        if fault_plan is not None:
            fault_plan.bind_worker_kill(train_loader.kill_one_worker)
        if straggler is not None:
            # fresh estimator windows over the REBUILT pool — a stale
            # verdict must never convict a fresh worker
            straggler.rebind(train_loader)
        ramp_record.append({
            "epoch": epoch, "mult": m,
            "global_batch": run_geom[1] * m,
            "steps_per_epoch": steps_per_epoch,
            "peak_lr": sched_lr * m,
        })
        print(
            f"=> BATCH RAMP at epoch {epoch}: per-host batch "
            f"{old_batch} -> {host_batch * m} (global "
            f"{run_geom[1] * m}), {steps_per_epoch} steps/epoch, peak "
            f"LR -> {sched_lr * m:g} per the linear-scaling rule",
            file=sys.stderr,
        )
    def _boundary_saved(path):
        # boundary saves count toward ckpt_truncate@save=N too — the
        # fault targets "the N-th checkpoint written", not only the
        # rotated step files. Store-URL saves have no local file to
        # tear, so the hook stands down there (the CheckpointManager
        # applies the same guard)
        if fault_plan is not None and path and not is_store_url(path):
            fault_plan.on_checkpoint_saved(path)

    # last position at which `state` is known consistent — the boundary
    # fallback for the best-effort save below (mid-epoch exceptions save
    # their exact position through train_one_epoch's emergency_cb)
    current_pos = {"epoch": start_epoch, "step": resume_step}
    emergency = {"saved": False}
    # loop entry: set-up is over. Its spans leave the ring here (into
    # the sink and the one ``=> set-up:`` line), so that the first
    # epoch's attribution sees its own spans only.
    _setup_phase(None)
    # the run's set-up account (dptpu/obs/report.py): what ran before
    # this function, its phases, the feed's pool; the loop adds the
    # step's first call and hands the account on with every later fetch
    setup_report = setup_account = None
    if tracer.enabled:
        setup_spans = tracer.drain()
        setup_report = obs.setup_report(setup_spans,
                                        feed=train_loader.pool())
        setup_account = {k: v for k, v in setup_report.items()
                         if k != "phases"}
        if verbose:
            print(obs.format_setup(setup_report))
        if trace_sink is not None:
            trace_sink.add_spans(setup_spans)
            trace_sink.log_event("setup_report", setup_report)
    try:
      # liveness beats ride a dedicated thread (StopToken teardown), so
      # a host parked inside a blocking device fetch keeps beating and
      # the chief's missing_hosts verdict stays meaningful (ROADMAP
      # item 3 residual (d)). Started INSIDE the try: every exit path
      # — including a setup failure below — reaches the finally that
      # closes it, so a dead host can never keep beating.
      if qs is not None:
          qs.start_heartbeat()
      with guard:
        for epoch in range(start_epoch, cfg.epochs):
            if batch_ramp is not None \
                    and _ramp_mult(epoch) != ramp_mult:
                _enter_ramp_phase(_ramp_mult(epoch), epoch)
            start_step = resume_step if epoch == start_epoch else 0
            current_pos = {"epoch": epoch, "step": start_step}
            if qs is not None:
                qs.epoch_start(epoch, start_step)
            if guard.requested or lost["flag"] \
                    or (qs is not None and qs.stop_signaled()):
                # the signal landed OUTSIDE the training loop (during the
                # previous epoch's validation/boundary save): act on it
                # before paying for another epoch's first step — the
                # grace window may not cover it
                path = None
                if _preempt_save_ok():
                    path = manager.save_step(
                        eval_view(state), epoch=epoch,
                        step_in_epoch=start_step, best_acc1=best_acc1,
                        sync=True,
                    )
                result["preempted"] = True
                if verbose:
                    print(
                        f"=> preempted ({_stop_reason()}) between "
                        f"epochs: "
                        + (f"saved '{path}' at epoch {epoch} step "
                           f"{start_step}" if path else
                           "skipped the gathered save (single-host "
                           "signal on a sharded multi-host run); the "
                           "epoch-boundary checkpoint stands")
                    )
                break

            def _save_step(s, steps, _e=epoch, sync=False):
                return manager.save_step(
                    eval_view(s), epoch=_e, step_in_epoch=steps,
                    best_acc1=best_acc1, sync=sync,
                )

            def _emergency(s, steps, _e=epoch):
                path = _save_step(s, steps, _e, sync=True)
                # flag only AFTER the save succeeded: if it raised (disk
                # full, transient I/O), the outer boundary fallback below
                # still gets its own attempt
                emergency["saved"] = True
                return path

            ep_t0 = time.time()
            # the prefetcher takes its first batch as it is made
            batches = DevicePrefetcher(
                train_loader.epoch(epoch, start_batch=start_step), put,
                first_step=start_step,
            )
            if setup_account is not None \
                    and "first_step_s" not in setup_account:
                # the run's first batch is on the device: what loop entry
                # itself took (the set-up line, the spans into the log)
                # and the prefetcher's first wait end here, and the
                # loop's first iteration begins
                setup_account["first_batch_s"] = round(
                    time.perf_counter() - setup_open[1], 3)
            state, train_stats = train_one_epoch(
                state,
                train_step,
                batches,
                epoch=epoch,
                num_batches=steps_per_epoch,
                print_freq=cfg.print_freq,
                verbose=verbose,
                feed_stats=train_loader.feed_stats,
                start_step=start_step,
                should_stop=_stop_requested,
                on_step=obs_tick,
                ckpt_every=cfg.ckpt_steps,
                ckpt_cb=_save_step if cfg.ckpt_steps else None,
                emergency_cb=_emergency if emergency_ok else None,
                setup=setup_account,
                # the epoch's own running median, the first reading of
                # the host's counters taken here
                slow_step=(obs.SlowStepWitness(obs_conf["anomaly"]).saw
                           if tracer.enabled else None),
            )
            ep_wall = time.time() - ep_t0
            # epoch attribution: drain this epoch's spans, account the
            # wall time (data_wait / h2d / device / ckpt / other), and
            # persist the timeline — the answer to "where did this
            # epoch's time go" without a profiler session
            ep_spans = _drain_spans()
            obs_report = None
            if tracer.enabled:
                obs_report = obs.attribute_epoch(
                    ep_spans, ep_wall, anomaly_x=obs_conf["anomaly"]
                )
                if verbose:
                    print(obs.format_report(obs_report, epoch))
            if trace_sink is not None:
                trace_sink.add_spans(ep_spans)
                if setup_report is not None \
                        and "first_step_s" in setup_account:
                    # the account again, whole (once): the loop closed
                    # the step's first call into it
                    trace_sink.log_event(
                        "setup_report", {**setup_account,
                                         "phases": setup_report["phases"]})
                    setup_report = None
                if obs_report is not None:
                    # the attribution block, machine-readable, in the
                    # same per-host log as the spans it summarizes
                    trace_sink.log_event(
                        "epoch_report", {"epoch": epoch, **obs_report}
                    )
            # update the fallback position the moment the state advances:
            # if anything below (the preemption save itself, a profiler
            # stop, validate) raises, the outer best-effort save must
            # label `state` with the steps it actually contains — a stale
            # start-of-epoch label would make resume re-train k batches
            # already baked into the weights
            current_pos = {"epoch": epoch,
                           "step": train_stats["steps_done"]}
            if profile_dir and derived.is_chief and epoch == start_epoch:
                jax.profiler.stop_trace()
                profile_dir = None
            if train_stats.get("preempted"):
                path = None
                if _preempt_save_ok():
                    path = manager.save_step(
                        eval_view(state), epoch=epoch,
                        step_in_epoch=train_stats["steps_done"],
                        best_acc1=best_acc1, sync=True,
                    )
                result["preempted"] = True
                if verbose:
                    print(
                        f"=> preempted ({_stop_reason()}): "
                        + (f"saved '{path}' at epoch {epoch} step "
                           f"{train_stats['steps_done']}; --resume "
                           f"replays the sampler to this exact position"
                           if path else
                           "skipped the gathered mid-epoch save "
                           "(single-host signal on a sharded multi-host "
                           "run); the last boundary checkpoint stands")
                    )
                break
            current_pos = {"epoch": epoch + 1, "step": 0}
            gathered = eval_view(state)  # one ZeRO-1 all-gather per epoch
            val_stats = validate(
                gathered,
                eval_step,
                DevicePrefetcher(val_loader.epoch(0), put),
                num_batches=len(val_loader),
                print_freq=cfg.print_freq,
                verbose=verbose,
                count_divisor=feed.val_count_divisor,
            )
            acc1 = val_stats["top1"]
            is_best = acc1 > best_acc1
            best_acc1 = max(acc1, best_acc1)
            result["history"].append({
                "epoch": epoch,
                **{f"train_{k}": v for k, v in train_stats.items()},
                **{f"val_{k}": v for k, v in val_stats.items()},
                **({"obs": obs_report} if obs_report is not None else {}),
            })
            with tracer.span("ckpt") as ckpt_span:
                boundary_path = save_checkpoint(
                    gathered,
                    epoch=epoch + 1,
                    arch=cfg.arch,
                    best_acc1=best_acc1,
                    is_best=is_best,
                    is_chief=derived.is_chief,
                    directory=ckpt_dir,
                    geometry=manager.geometry,
                    sharding=plan.fingerprint,
                    report=ckpt_span.attrs,
                )
            _boundary_saved(boundary_path)
            if verbose and "moe_load_max" in train_stats:
                print(
                    "Moe: busiest held expert {moe_load_max:.1f} tokens a "
                    "layer a step (mean {moe_load_mean:.1f}), "
                    "{moe_local_slot_share:.2f}% of the routed slots on "
                    "held experts ({moe_compact_share:.1f}% of the layer "
                    "steps in the compact buffer), {moe_dropped:.0f} "
                    "tokens dropped".format(**train_stats)
                )
            if verbose and "mtp_loss" in train_stats:
                print("Mtp: multi-token-prediction loss {mtp_loss:.4f} "
                      "(before its weight) in the epoch's loss "
                      "{loss:.4f}".format(**train_stats))
            registry.set_scalars(_epoch_scalars(
                train_stats, val_stats, obs_report,
                # under a batch ramp a step consumes the PHASE batch
                # (validation keeps the base batch)
                train_batch=derived.global_batch_size * ramp_mult,
                val_batch=derived.global_batch_size,
                accum_steps=accum_steps,
                opt_shard_bytes=built.opt_shard_bytes,
                tracer_dropped=tracer.dropped,
            ))
            registry.flush(epoch + 1)
            # validation + boundary-save spans: persisted to the
            # timeline, but never billed to the NEXT epoch's report
            val_spans = _drain_spans()
            if trace_sink is not None:
                trace_sink.add_spans(val_spans)
            # --desired-acc early stop, fractional like the reference
            # (README --desired-acc 0.75 vs top1 in percent, imagenet_ddp.py:224-236);
            # values > 1 are read as percent directly (documented in --help)
            target_pct = (
                None
                if cfg.desired_acc is None
                else cfg.desired_acc * 100.0
                if cfg.desired_acc <= 1.0
                else cfg.desired_acc
            )
            if target_pct is not None and best_acc1 >= target_pct:
                training_time = time.time() - start_time
                early_path = save_checkpoint(
                    gathered,
                    epoch=epoch + 1,
                    arch=cfg.arch,
                    best_acc1=best_acc1,
                    is_best=False,
                    is_chief=derived.is_chief,
                    training_time=training_time,
                    directory=ckpt_dir,
                    geometry=manager.geometry,
                    sharding=plan.fingerprint,
                )
                _boundary_saved(early_path)
                if verbose:
                    print(
                        f"top-1 accuracy {best_acc1:.3f} reached desired "
                        f"{target_pct:.3f} after {training_time:.1f}s"
                    )
                result["early_stopped"] = True
                result["training_time"] = training_time
                break
    except BaseException:
        # best-effort safety net (never masks the original error).
        # Mid-epoch failures already saved their exact position via
        # emergency_cb; anything else (validate, TB, checkpoint-best)
        # saves the last consistent boundary position here.
        if not emergency["saved"] and emergency_ok:
            try:
                manager.save_step(
                    eval_view(state),
                    epoch=current_pos["epoch"],
                    step_in_epoch=current_pos["step"],
                    best_acc1=best_acc1,
                    sync=True,
                )
            except Exception:
                pass
        raise
    finally:
        # Teardown is loud on the NORMAL path and silent only while
        # another error propagates (probe for an in-flight exception
        # BEFORE any close attempt: inside an except clause
        # sys.exc_info() would report the close error itself, never
        # None). Order: profiler trigger (may need to stop a live jax
        # trace), span/metric sinks, the TB writer — closing it HERE
        # covers the exception/preemption paths too, so a preempted
        # run's last-epoch scalars are never lost in a buffer — then
        # the checkpoint writer thread (exception paths already saved
        # synchronously, which drains the queue; a failed cadence write
        # must fail the run, not vanish).
        propagating = sys.exc_info()[0] is not None
        teardown_errors = []
        if qs is not None:
            # stop the heartbeat thread on EVERY exit path: a dead
            # host whose beat thread keeps posting would mask the
            # chief's missing_hosts verdict — the exact signal the
            # off-thread heartbeat exists to make meaningful
            try:
                qs.close()
            except Exception as e:
                teardown_errors.append(e)
        if trigger is not None:
            try:
                trigger.uninstall()
            except Exception:
                pass
        try:
            if trace_sink is not None:
                trace_sink.add_spans(tracer.drain())
                trace_sink.close()
        except Exception as e:
            teardown_errors.append(e)
        if trace_sink is not None and derived.is_chief:
            # the chief-side collector (ROADMAP item 3c): merge every
            # host's obs-<host>.jsonl under the obs dir into ONE pod
            # timeline — per-host streaming quantiles, windowed step
            # p50s ("what changed at 14:07"), straggler verdicts —
            # written atomically next to the logs it summarizes
            try:
                obs.merge_pod_timeline(
                    trace_sink.directory,
                    os.path.join(trace_sink.directory,
                                 "pod-timeline.json"),
                )
            except Exception as e:
                teardown_errors.append(e)
        obs.reset()
        if writer is not None:
            try:
                writer.close()
            except Exception as e:
                teardown_errors.append(e)
        if ckpt_writer is not None:
            # ALWAYS attempted, whatever the sinks above did: close() is
            # the one place a failed async cadence write surfaces — an
            # obs I/O error must never swallow a lost checkpoint
            try:
                ckpt_writer.close()
            except Exception as e:
                teardown_errors.append(e)
        if teardown_errors:
            # every failure gets at least a stderr line — raising can
            # only surface one, and under a propagating exception none
            for e in teardown_errors:
                print(f"WARNING: teardown close failed: {e!r}",
                      file=sys.stderr)
            if not propagating:
                # the LAST error is the checkpoint writer's when it
                # failed — the one that must win the raise
                raise teardown_errors[-1]
    if writer is not None:
        # final wall-clock report (imagenet_ddp_apex.py:292-300)
        elapsed = time.time() - start_time
        mins, secs = divmod(elapsed, 60)
        hrs, mins = divmod(mins, 60)
        print(
            "### Training Time: {:.2f} hrs {:.2f} mins {:.2f} secs "
            "| {:.2f} secs".format(hrs, mins, secs, elapsed)
        )
    _close_feed()
    result.update({"state": state, "best_acc1": best_acc1,
                   "epochs_run": len(result["history"])})
    # elastic-lifecycle report: what the remap did, what the quorum
    # agreed, what the straggler controller escalated — the benches'
    # (and an operator's post-mortem's) machine-readable record
    if elastic_resume is not None:
        result["elastic"] = elastic_resume
    if batch_ramp is not None:
        result["batch_ramp"] = ramp_record
    if lost["flag"]:
        result["host_lost"] = True
    if qs is not None:
        result["quorum"] = qs.stats()
    if straggler is not None:
        result["straggler"] = straggler.stats()
    if tuning is not None:
        result["tuning"] = tuning
    if tune_ctl is not None:
        result["tune_control"] = tune_ctl.stats()
    return result