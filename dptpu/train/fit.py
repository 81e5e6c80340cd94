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
from dptpu.data import (
    DataLoader,
    DevicePrefetcher,
    ImageFolderDataset,
    ShardedSampler,
    SyntheticDataset,
    train_transform,
    val_transform,
)
from dptpu.models import create_model, model_task
from dptpu.ops.schedules import (
    make_step_decay_schedule,
    make_warmup_cosine_schedule,
    make_warmup_step_decay_schedule,
)
from dptpu.parallel import (
    gather_state,
    initialize_distributed,
    make_mesh,
    make_zero1_train_step,
    replicated_sharding,
    shard_host_batch,
    shard_zero1_state,
)
from dptpu.resilience import (
    CheckpointManager,
    FaultPlan,
    PreemptionGuard,
    find_resumable,
)
from dptpu.train.checkpoint import load_checkpoint, save_checkpoint
from dptpu.train.loop import train_one_epoch, validate
from dptpu.train.state import create_train_state, make_optimizer
from dptpu.train.step import make_eval_step, make_train_step
from dptpu.utils.compile_cache import enable_compile_cache
from dptpu.utils.provenance import device_banner


def _os_environ_flag(name: str) -> bool:
    """Boolean env knob under the fail-fast contract (dptpu/envknob.py):
    unset/empty → False, junk raises actionably — DPTPU_ZERO1=flase must
    never silently mean 'off' (the knob-contract lint, dptpu/analysis,
    polices that no raw os.environ read can reintroduce the fallback)."""
    from dptpu.envknob import env_bool

    return bool(env_bool(name, False))


def _os_environ_int(name: str):
    """Integer env knob; unset/empty → None (so callers can tell an
    explicit 0 from absence — the fail-fast knob contract), junk →
    actionable error. One shared implementation: dptpu/envknob.py."""
    from dptpu.envknob import env_int

    return env_int(name, None)


def _axis_env_knob(name: str, what: str) -> int:
    """Parallelism-axis env knob: unset → 0 (off); any explicit value
    ≤ 0 raises — 0 gets the same fail-fast treatment as negatives (the
    locked knob contract: every explicit value produces feedback, =1
    additionally prints a no-op notice at the call site)."""
    n = _os_environ_int(name)
    if n is not None and n <= 0:
        raise ValueError(
            f"{name}={n} must be a positive {what} (e.g. {name}=2)"
        )
    return n or 0


def _shard_source(data: str):
    """``(train_loc, val_loc)`` when ``data`` names a PACKED-shard tree
    (``dptpu pack`` layout: train/ + val/ each holding a manifest) —
    either a store URL (http(s)://, file://) or a local directory with
    manifests — else None (plain ImageFolder)."""
    import os

    from dptpu.data.shards import MANIFEST_NAME
    from dptpu.data.store import is_store_url

    if is_store_url(data):
        base = data.rstrip("/")
        return f"{base}/train", f"{base}/val"
    if os.path.exists(os.path.join(data, "train", MANIFEST_NAME)):
        return os.path.join(data, "train"), os.path.join(data, "val")
    return None


def _token_model_kwargs(cfg: Config, task: str) -> dict:
    """The arguments a token-sequence model's factory takes from the
    command line (``--seq-len``, ``--layers``, ``--experts``,
    ``--vocab-rows``); an image model is handed none of them and a run
    that gives it one fails here, before anything is built."""
    given = {"sequence_length": cfg.seq_len or None,
             "layers": cfg.layers or None, "experts": cfg.experts or None,
             "vocab": cfg.vocab_rows or None}
    given = {k: v for k, v in given.items() if v is not None}
    if task != "tokens" and given:
        raise ValueError(
            f"--seq-len/--layers/--experts/--vocab-rows are a "
            f"token-sequence model's arguments, and '{cfg.arch}' is "
            f"trained on images"
        )
    return given


def _build_token_datasets(cfg: Config, task: str, model_config):
    """``tokens:<N>[@first]`` for a token-sequence model: rows of the
    model's sequence length over the vocabulary rows it holds;
    validation on the N/10 rows behind the training rows."""
    from dptpu.data.tokens import TokenDataset, parse_source

    source = parse_source(cfg.data)
    if task != "tokens" or source is None:
        raise ValueError(
            f"'{cfg.arch}' is trained on "
            + ("token rows: give tokens:<N> as the data source, not "
               f"{cfg.data!r}" if task == "tokens" else
               f"images, and {cfg.data!r} is a source of token rows "
               f"(for a token-sequence model such as lfm2_8b_a1b)")
        )
    rows, first = source
    length, vocab = model_config.sequence_length, model_config.vocab_size
    return (TokenDataset(rows, length, vocab, first),
            TokenDataset(max(rows // 10, 1), length, vocab, first + rows),
            vocab)


def _build_datasets(cfg: Config, image_size: int, cache_bytes: int = 0,
                    cache_scope: str = "sharded"):
    import os

    if cfg.data.startswith("synthetic"):
        n = int(cfg.data.split(":", 1)[1]) if ":" in cfg.data else 2048
        train_ds = SyntheticDataset(n, image_size, 1000)
        val_ds = SyntheticDataset(max(n // 10, 1), image_size, 1000)
        return train_ds, val_ds, 1000
    # DPTPU_CACHE_BYTES is a PER-DATASET budget: train and val each keep
    # their own decoded-pixel cache (val redecodes the same files every
    # epoch, so it benefits at least as much per byte)
    shards = _shard_source(cfg.data)
    if shards is not None:
        # packed-shard streaming data plane (dptpu/data/stream.py):
        # pixels are bit-identical to the ImageFolder path by
        # construction, so --data may point at either form of the same
        # dataset and a seeded run cannot tell the difference
        from dptpu.data import ShardStreamDataset

        train_ds = ShardStreamDataset(
            shards[0], train_transform(image_size),
            cache_bytes=cache_bytes, cache_scope=cache_scope,
        )
        val_ds = ShardStreamDataset(
            shards[1],
            val_transform(image_size, resize=int(image_size * 256 / 224)),
            cache_bytes=cache_bytes, cache_scope=cache_scope,
        )
        return train_ds, val_ds, len(train_ds.classes)
    traindir = os.path.join(cfg.data, "train")
    valdir = os.path.join(cfg.data, "val")
    train_ds = ImageFolderDataset(
        traindir, train_transform(image_size), cache_bytes=cache_bytes,
        cache_scope=cache_scope,
    )
    val_ds = ImageFolderDataset(
        valdir, val_transform(image_size, resize=int(image_size * 256 / 224)),
        cache_bytes=cache_bytes, cache_scope=cache_scope,
    )
    return train_ds, val_ds, len(train_ds.classes)


def _host_cores() -> int:
    """The cores this process may run on (its affinity mask, which a
    container or ``taskset`` narrows; the machine's count where the
    platform has no such call)."""
    try:
        return len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return os.cpu_count() or 1


def _feed_knobs() -> tuple:
    """The input-pipeline env knobs, under the locked fail-fast contract:
    every explicit-but-invalid value raises with the accepted values.

    Returns ``(workers_mode, cache_bytes, cache_scope, leased)``:

    * ``DPTPU_WORKERS_MODE`` — ``process`` (spawned decode workers
      writing into the shared-memory ring) or ``thread`` (a pool inside
      this interpreter). Unset, it is ``process``: pool threads share
      the interpreter lock with the loop's own thread, and four of them
      held its dispatch call for 65 ms of a 69 ms ResNet-50 iteration
      (PERF.md §6, PR 31). The one exception reads the host, nothing
      else: with two cores or fewer to run on, worker processes cannot
      run beside the loop anyway and only add their start-up, so the
      default there is ``thread``. Thread and process batches are
      bit-identical, and a process pool that keeps failing degrades to
      threads by itself (``DataLoader._degrade_to_thread``);
    * ``DPTPU_CACHE_SCOPE`` — ``pooled`` (one cross-process /dev/shm
      slab, the process-mode default) or ``sharded`` (in-process
      ``DecodeCache``, split N ways by a worker pool; the thread-mode
      default, where in-process already means pooled);
    * ``DPTPU_LEASE`` — zero-copy consumer-leased batch slots in process
      mode (default on; the copy-out path remains for ``=0``).
    """
    from dptpu.envknob import env_bool, env_choice

    workers_mode = env_choice(
        "DPTPU_WORKERS_MODE", ("thread", "process"),
        default="process" if _host_cores() > 2 else "thread",
    )
    cache_bytes = _os_environ_int("DPTPU_CACHE_BYTES")
    if cache_bytes is not None and cache_bytes < 0:
        raise ValueError(
            f"DPTPU_CACHE_BYTES={cache_bytes} must be >= 0 bytes "
            f"(0/unset disables the decode cache)"
        )
    cache_scope = env_choice(
        "DPTPU_CACHE_SCOPE", ("pooled", "sharded"),
        default="pooled" if workers_mode == "process" else "sharded",
    )
    leased = env_bool("DPTPU_LEASE", True)
    return workers_mode, cache_bytes or 0, cache_scope, leased


def _opt_knobs(cfg: Config) -> tuple:
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
    from dptpu.envknob import env_choice, env_float, env_int

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
    opt_name, accum_steps, warmup_epochs, label_smooth = _opt_knobs(cfg)
    # what the model is trained on decides the data source, the step's
    # loss and what its factory is handed (dptpu/models/registry.py)
    task = model_task(cfg.arch)
    token_kwargs = _token_model_kwargs(cfg, task)
    # hierarchical-comms knobs (--slices/DPTPU_SLICES, DPTPU_DCN_DTYPE)
    # fail fast pre-compile too; divisibility is checked against the
    # device count once the mesh is factored below
    from dptpu.parallel.hierarchy import hierarchy_knobs

    slices, dcn_dtype = hierarchy_knobs(cfg)
    # overlapped gradient comms (DPTPU_OVERLAP / DPTPU_BUCKET_MB,
    # dptpu/parallel/overlap.py) — validated here even when off
    from dptpu.envknob import env_float as _env_float
    from dptpu.envknob import env_str as _ramp_env_str
    from dptpu.parallel.overlap import overlap_knobs

    want_overlap, bucket_bytes, _bucket_explicit = overlap_knobs()
    # extreme-scale recipe knobs (ISSUE 13): the batch-size ramp and
    # the polynomial warmup exponent (arXiv:1811.05233), both under
    # the locked fail-fast contract, both pre-compile
    from dptpu.ops.schedules import (
        parse_batch_ramp,
        ramp_multiplier,
        ramp_phase_start,
    )

    _ramp_spec = _ramp_env_str("DPTPU_BATCH_RAMP")
    batch_ramp = parse_batch_ramp(_ramp_spec) if _ramp_spec else None
    warmup_poly = _env_float("DPTPU_WARMUP_POLY", None)
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
    if verbose:
        print(device_banner())
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
    # DPTPU_TP=N opens a model axis of size N on the mesh and routes
    # training through the GSPMD tensor-parallel step (specs picked by
    # arch below). The model axis is INNER: on multi-host pods the
    # hierarchical mesh keeps its collectives on ICI (make_mesh guards
    # the DCN crossing). This is the trainer-level entry for the
    # vit/swin TP sharding rules in dptpu/parallel/gspmd.py.
    tp_n = _axis_env_knob("DPTPU_TP", "model-axis size")
    if tp_n == 1 and verbose:
        print("=> DPTPU_TP=1 is a no-op: a one-way model axis is just "
              "data parallelism")
    use_tp = tp_n > 1 and not single_device and not cfg.evaluate
    if tp_n > 1 and not use_tp and verbose:
        why = (
            "--evaluate does not train"
            if cfg.evaluate and not single_device
            else "single-device run (no mesh to open a model axis on)"
        )
        print(f"=> DPTPU_TP ignored: {why}")
    # Arch rule decided BEFORE mesh construction: an arch with no TP rule
    # (CNNs, MaxViT) gets the flat full-width data mesh — factoring a
    # model axis it cannot use would make those devices compute 100%
    # redundantly instead of joining the data axis.
    tp_fallback = False
    if use_tp:
        from dptpu.parallel.gspmd import tp_rule_for_arch

        tp_fallback = tp_rule_for_arch(cfg.arch) == "dp_specs"
    if tp_fallback:
        # demote the TP request entirely: with no rule for this arch
        # there is nothing for a model axis to do, so later precedence
        # checks (DPTPU_ZERO1 etc.) must not see an inert TP claim
        if verbose:
            print(
                f"=> DPTPU_TP={tp_n}: no tensor-parallel rule for "
                f"'{cfg.arch}' (TP ships for vit_*/swin*/convnext_*; classic "
                f"CNNs and MaxViT keep the data axis — see dp_specs "
                f"docstring) — "
                f"running data parallelism over all "
                f"{jax.device_count()} devices instead"
            )
        use_tp = False
    if use_tp and jax.device_count() % tp_n != 0:
        raise ValueError(
            f"DPTPU_TP={tp_n} does not divide the {jax.device_count()} "
            f"available devices — pick a divisor so the "
            f"{{data, model}} mesh factors"
        )
    # DPTPU_SP=N: sequence/context parallelism — a {data, seq: N} mesh,
    # the ViT token axis sharded over the inner seq axis with Ulysses or
    # ring attention (DPTPU_SP_MODE, default ulysses). ViT-only: Swin's
    # windowed attention is already local and parallelizes spatially via
    # the data axis (README); CNNs have no token axis at all.
    from dptpu.envknob import env_choice

    sp_n = _axis_env_knob("DPTPU_SP", "seq-axis size")
    # fail-fast even when SP is off: a typo'd mode must not sit silently
    # in the environment waiting for the day DPTPU_SP is turned on
    sp_mode = env_choice("DPTPU_SP_MODE", ("ulysses", "ring"), "ulysses")
    if sp_n == 1 and verbose:
        print("=> DPTPU_SP=1 is a no-op: a one-way seq axis is just "
              "data parallelism")
    use_sp = (
        sp_n > 1 and not single_device and not cfg.evaluate and not use_tp
    )
    if sp_n > 1 and not use_sp and verbose:
        why = (
            "DPTPU_TP takes precedence (TP x SP composition is not "
            "implemented)"
            if use_tp
            else "--evaluate does not train"
            if cfg.evaluate and not single_device
            else "single-device run (no mesh to open a seq axis on)"
        )
        print(f"=> DPTPU_SP ignored: {why}")
    if use_sp and not cfg.arch.startswith("vit_"):
        if verbose:
            print(
                f"=> DPTPU_SP={sp_n}: no sequence-parallel path for "
                f"'{cfg.arch}' (global-attention ViTs only; Swin windows "
                f"are spatially local, CNNs have no token axis) — "
                f"running plain data parallelism over all "
                f"{jax.device_count()} devices instead"
            )
        use_sp = False
    if use_sp and jax.device_count() % sp_n != 0:
        raise ValueError(
            f"DPTPU_SP={sp_n} does not divide the {jax.device_count()} "
            f"available devices — pick a divisor so the "
            f"{{data, seq}} mesh factors"
        )
    if use_sp and accum_steps > 1:
        # fail fast rather than silently changing the effective batch:
        # the sequence-parallel step has no microbatch scan (its token
        # axis already divides the work another way). Name the offending
        # knob AND the supported alternatives (message locked by
        # tests/test_opt_knobs.py::test_sp_accum_error_names_knob_and_alternative)
        raise ValueError(
            f"--accum-steps/DPTPU_ACCUM={accum_steps} has no "
            f"sequence-parallel implementation (DPTPU_SP={sp_n} replaces "
            f"the microbatch scan with a token-axis split); supported "
            f"alternatives: set DPTPU_ACCUM=1 and keep DPTPU_SP={sp_n}, "
            f"or unset DPTPU_SP to get data-parallel gradient "
            f"accumulation"
        )
    # DPTPU_SLICES/--slices > 1: two-level hierarchical data
    # parallelism (dptpu/parallel/hierarchy.py) — the gradient
    # all-reduce decomposes into reduce-scatter(ICI) + shard-sized
    # all-reduce(DCN) + all-gather(ICI). Composes with the default DDP
    # step, with DPTPU_ZERO1/DPTPU_ZERO=3 (state shards over the
    # intra-slice axis, so the weight all-gather stays on ICI), AND
    # with DPTPU_GSPMD (the {slice, data}-factored mesh + rules-table
    # FSDP placement make the partitioner derive its own DCN-aware
    # decomposition); TP/SP keep their own single-level topologies
    # (explicit requests win, with a notice — the repo-wide precedence
    # discipline).
    want_hier = slices > 1
    want_gspmd_early = _os_environ_flag("DPTPU_GSPMD")
    use_hier = (
        want_hier and not single_device and not cfg.evaluate
        and not use_tp and not use_sp
    )
    if slices == 1 and _os_environ_int("DPTPU_SLICES") == 1 and verbose:
        print("=> DPTPU_SLICES=1 is a no-op: one slice is the flat "
              "single-level data mesh")
    if want_hier and not use_hier and verbose:
        why = (
            "DPTPU_TP drives the GSPMD tensor-parallel step"
            if use_tp
            else "DPTPU_SP drives the sequence-parallel step"
            if use_sp
            else "--evaluate does not train"
            if cfg.evaluate and not single_device
            else "single-device run (no DCN hop to factor)"
        )
        print(f"=> DPTPU_SLICES={slices} ignored: {why}")
    if dcn_dtype != "fp32" and not use_hier and verbose:
        print(f"=> DPTPU_DCN_DTYPE={dcn_dtype} ignored: no hierarchical "
              f"mesh (set DPTPU_SLICES >= 2), so there is no DCN-only "
              f"hop to compress")
    if single_device:
        mesh = None
    elif use_tp:
        from dptpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

        mesh = make_mesh(mesh_shape={DATA_AXIS: -1, MODEL_AXIS: tp_n})
    elif use_sp:
        from dptpu.parallel.mesh import DATA_AXIS
        from dptpu.parallel.sequence import SEQ_AXIS

        mesh = make_mesh(mesh_shape={DATA_AXIS: -1, SEQ_AXIS: sp_n})
    elif use_hier:
        from dptpu.parallel import make_hierarchical_mesh

        if el_conf["elastic"] and cfg.resume:
            # elastic composition first: a shrunk world that no longer
            # divides --slices gets the message naming the knob AND
            # both fallbacks (drop slices / pick a dividing S) instead
            # of the generic mesh-factoring error. Gated on --resume:
            # a FRESH run with DPTPU_ELASTIC exported (a job env knob
            # that must survive restarts) is a plain slices
            # misconfiguration and deserves the generic message, not a
            # phantom elastic-restart diagnosis.
            from dptpu.parallel.hierarchy import elastic_slices_check

            elastic_slices_check(jax.device_count(), slices)
        # raises when slices does not divide the device count (or the
        # host count, multi-process) — the locked fail-fast contract
        mesh = make_hierarchical_mesh(slices)
        if verbose:
            import jax as _jax

            if want_gspmd_early or tp_fallback:
                print(
                    f"=> hierarchical data parallelism: {slices} slices "
                    f"x {_jax.device_count() // slices} chips/slice — "
                    f"the SPMD partitioner derives the per-link "
                    f"decomposition from the {{slice, data}}-factored "
                    f"mesh + rules-table FSDP placement"
                )
            else:
                print(
                    f"=> hierarchical data parallelism: {slices} slices x "
                    f"{_jax.device_count() // slices} chips/slice — "
                    f"gradient reduction is reduce-scatter(ICI) + "
                    f"shard-sized all-reduce(DCN, {dcn_dtype}) + "
                    f"all-gather(ICI)"
                )
    else:
        mesh = make_mesh()
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

    # Decode runs in the shared-memory worker-process ring
    # (dptpu/data/shm.py) unless DPTPU_WORKERS_MODE=thread asks for the
    # in-interpreter pool (or the host has two cores or fewer) — same
    # batches bit-for-bit, but decode scales with host cores and the
    # loop's thread shares the interpreter lock with no worker;
    # DPTPU_CACHE_BYTES budgets a decoded-pixel cache so epoch 1+ skips
    # JPEG Huffman decode (DPTPU_CACHE_SCOPE picks pooled-slab vs
    # per-worker-sharded), and DPTPU_LEASE keeps process-mode batches
    # zero-copy end to end.
    _setup_phase("data")
    workers_mode, cache_bytes, cache_scope, leased = _feed_knobs()
    if verbose:
        from dptpu.data import native_image

        print(
            f"=> input pipeline: workers_mode={workers_mode}, "
            f"decode cache "
            + (f"{cache_bytes / 1e6:.0f} MB per dataset ({cache_scope})"
               if cache_bytes else "off")
            + (", leased slots" if leased and workers_mode == "process"
               else "")
            # which JPEG decoder is live: the native libjpeg ops, or PIL
            # after a failed build (dptpu/native/build.py says why)
            + f", native={native_image.available()}"
        )
    if task == "tokens" or cfg.data.startswith("tokens"):
        # the rows' length and the ids' range are the model's: its
        # configuration is read before any weight is made
        train_ds, val_ds, num_classes = _build_token_datasets(
            cfg, task,
            create_model(cfg.arch, **token_kwargs).config
            if task == "tokens" else None,
        )
    else:
        train_ds, val_ds, num_classes = _build_datasets(
            cfg, image_size, cache_bytes=cache_bytes,
            cache_scope=cache_scope
        )

    # per-host loaders over disjoint shards (DistributedSampler contract);
    # batches are per-HOST (global batch = per_host × hosts).
    # DPTPU_SHARD_LOCALITY=1 (packed-shard data only; opt-in — it
    # REORDERS the epoch visit, so the trajectory diverges from the
    # ImageFolder-identical default) swaps the global permutation for
    # the seeded shard-level shuffle + in-shard shuffle: sequential
    # extent I/O, one shard resident at a time, still pure in
    # (seed, epoch) so mid-epoch --resume replays exactly.
    from dptpu.envknob import env_bool as _sl_bool

    want_locality = _sl_bool("DPTPU_SHARD_LOCALITY", False)
    use_locality = want_locality and hasattr(train_ds, "shard_set")
    if want_locality and not use_locality and verbose:
        print("=> DPTPU_SHARD_LOCALITY ignored: --data is not a "
              "packed-shard tree (dptpu pack)")
    if use_locality and verbose:
        print("=> shard-locality sampling: seeded shard-level shuffle "
              "+ in-shard shuffle (sequential extent I/O; trajectory "
              "differs from the global-permutation default)")
    host_batch = derived.per_host_batch_size
    if use_locality:
        from dptpu.data import ShardLocalitySampler

        train_sampler = ShardLocalitySampler(
            train_ds.shard_set,
            num_shards=derived.num_processes,
            shard_index=derived.process_index,
            shuffle=True,
            seed=cfg.seed if cfg.seed is not None else 0,
        )
    else:
        train_sampler = ShardedSampler(
            len(train_ds),
            num_shards=derived.num_processes,
            shard_index=derived.process_index,
            shuffle=True,
            seed=cfg.seed if cfg.seed is not None else 0,
        )
    if batch_ramp is not None and cfg.evaluate:
        if verbose:
            print("=> DPTPU_BATCH_RAMP ignored: --evaluate does not train")
        batch_ramp = None

    def _ramp_mult(epoch: int) -> int:
        return (ramp_multiplier(batch_ramp, epoch)
                if batch_ramp is not None else 1)

    def _spe(mult: int) -> int:
        # mirrors DataLoader.__len__ under drop_last=True — the phase
        # table must be computable WITHOUT building a loader per phase
        return max(len(train_sampler) // (host_batch * mult), 1)

    def _cum_steps(epoch: int) -> int:
        # optimizer steps completed before `epoch` starts — the phase
        # schedule's step anchor and the ramped --start-epoch offset
        return sum(_spe(_ramp_mult(e)) for e in range(epoch))

    def _make_train_loader(batch: int) -> DataLoader:
        return DataLoader(
            train_ds,
            batch,
            sampler=train_sampler,
            # the sum of the reference's per-GPU worker pools: each of
            # the n_local device-slots gets ceil(workers / n_local)
            # decode threads (imagenet_ddp.py:126), pooled per host
            num_workers=(derived.workers_per_device
                         * derived.local_device_count),
            drop_last=True,
            pad_final=False,
            seed=cfg.seed if cfg.seed is not None else 0,
            workers_mode=workers_mode,
            leased=leased,
        )

    ramp_mult = _ramp_mult(cfg.start_epoch)
    train_loader = _make_train_loader(host_batch * ramp_mult)
    if not cfg.evaluate:
        # the workers' interpreters start and import HERE, beside the
        # weights, the state and the step's compile, and not on the
        # loop's first iteration; the validation loader below keeps
        # building its pool at its first pass
        train_loader.start()
    # Validation sharding follows the reference's split behavior:
    # * ddp/nd validate the FULL val set on every rank with no cross-rank
    #   reduction (imagenet_ddp.py:186-194, nd_imagenet.py) — here every
    #   HOST loads the full set; the in-step psum then counts each sample
    #   once per host, so the reported count is divided back down and the
    #   averages are bit-identical on every host by construction;
    # * apex shards val and all-reduces the sums — exact aggregation
    #   (imagenet_ddp_apex.py:232-234,457-460).
    # DPTPU_DIST_EVAL=1 (ISSUE 13 satellite): shard validation over the
    # hosts for EVERY variant — the ddp/nd default feeds the FULL val
    # set to every host (replicated work: N hosts decode N copies), the
    # apex variant already shards. The in-step psum'd
    # correct/count sums make the sharded aggregate EXACT, and on one
    # host the shard IS the full set, so top1 is bit-identical to the
    # single-stream pass by construction (locked in
    # tests/test_overlap.py).
    dist_eval = _os_environ_flag("DPTPU_DIST_EVAL")
    full_val = cfg.variant in ("ddp", "nd") and not dist_eval
    if dist_eval and verbose:
        if cfg.variant in ("ddp", "nd") and derived.num_processes > 1:
            print(
                f"=> distributed eval: val set sharded over "
                f"{derived.num_processes} hosts (exact psum-aggregated "
                f"top1; each host decodes 1/{derived.num_processes} of "
                f"the set instead of all of it)"
            )
        elif cfg.variant == "apex":
            print("=> DPTPU_DIST_EVAL noted: the apex variant already "
                  "shards validation (imagenet_ddp_apex.py:232-234)")
    val_loader = DataLoader(
        val_ds,
        host_batch,
        sampler=(
            ShardedSampler(len(val_ds), num_shards=1, shard_index=0,
                           shuffle=False)
            if full_val
            else ShardedSampler(
                len(val_ds),
                num_shards=derived.num_processes,
                shard_index=derived.process_index,
                shuffle=False,
            )
        ),
        num_workers=derived.workers_per_device * derived.local_device_count,
        workers_mode=workers_mode,
        leased=leased,
    )
    val_count_divisor = derived.num_processes if full_val else 1
    steps_per_epoch = max(len(train_loader), 1)

    compute_dtype = jnp.bfloat16 if derived.use_bf16 else jnp.float32
    # BN activations follow the compute dtype (statistics always accumulate
    # in fp32 inside flax) unless --keep-batchnorm-fp32 True pins BN I/O to
    # fp32 — the Apex flag's strictest reading (imagenet_ddp_apex.py:93).
    keep_bn_fp32 = str(cfg.keep_batchnorm_fp32).lower() in ("true", "1")
    want_s2d = _os_environ_flag("DPTPU_S2D")
    _resnet_family = cfg.arch.startswith(("resnet", "wide_resnet", "resnext"))
    use_s2d = want_s2d and _resnet_family and image_size % 2 == 0
    if want_s2d and not use_s2d and verbose:
        print(
            f"=> DPTPU_S2D ignored: requires a resnet arch and even input "
            f"size (got arch={cfg.arch}, image_size={image_size})"
        )
    # DPTPU_GSPMD=1: run the single-program GSPMD/pjit data-parallel step
    # (dp_specs) instead of the shard_map DDP step. Read before model
    # build because BN semantics differ: under GSPMD the global batch is
    # one logical program, so BN statistics are ALWAYS global (SyncBN
    # behavior) and the model must not carry a shard-local axis name.
    want_gspmd = _os_environ_flag("DPTPU_GSPMD")
    # DPTPU_ZERO selects the ZeRO stage by number: 1 is the shipped
    # weight-update sharding (same as DPTPU_ZERO1=1), 3 the full
    # param+grad+optimizer sharding driven by the arch's partition
    # rules table (dptpu/parallel/rules.py); DPTPU_FSDP=1 is the
    # synonym the FSDP literature spells stage 3 with. Read once; the
    # step-selection blocks below reuse these so the precedence rule
    # has one source.
    _zero_stage = _os_environ_int("DPTPU_ZERO")
    if _zero_stage not in (None, 0, 1, 3):
        raise ValueError(
            f"DPTPU_ZERO={_zero_stage} is not a supported stage — use 1 "
            f"(weight-update sharding, the DPTPU_ZERO1=1 alias), 3 "
            f"(param+grad+optimizer sharding, the DPTPU_FSDP=1 alias), "
            f"or 0/unset for replicated data parallelism"
        )
    want_zero3 = _zero_stage == 3 or _os_environ_flag("DPTPU_FSDP")
    want_zero1 = _os_environ_flag("DPTPU_ZERO1") or _zero_stage == 1
    # Precedence: DPTPU_TP (an explicit topology request — the mesh was
    # already factored for it) > DPTPU_SP > DPTPU_ZERO=3 > DPTPU_ZERO1
    # > DPTPU_GSPMD.
    use_zero3 = (
        want_zero3 and mesh is not None and not cfg.evaluate
        and not use_tp and not use_sp
    )
    use_zero1 = (
        want_zero1 and mesh is not None and not cfg.evaluate and not use_tp
        and not use_sp and not use_zero3
    )
    if want_zero3 and use_tp and verbose:
        print("=> DPTPU_ZERO=3/DPTPU_FSDP ignored: DPTPU_TP drives the "
              "GSPMD tensor-parallel step (params shard over the model "
              "axis per the same rules table)")
    elif want_zero3 and use_sp and verbose:
        print("=> DPTPU_ZERO=3/DPTPU_FSDP ignored: DPTPU_SP drives the "
              "sequence-parallel step")
    if want_zero1 and use_zero3 and verbose:
        print("=> DPTPU_ZERO1 noted: DPTPU_ZERO=3 supersedes it (stage "
              "3 shards everything stage 1 shards, plus the params)")
    elif want_zero1 and use_tp and verbose:
        print("=> DPTPU_ZERO1 ignored: DPTPU_TP drives the GSPMD "
              "tensor-parallel step (params shard over the model axis, "
              "not the optimizer state over data)")
    elif want_zero1 and use_sp and verbose:
        print("=> DPTPU_ZERO1 ignored: DPTPU_SP drives the "
              "sequence-parallel step")
    use_gspmd = (
        (want_gspmd or use_tp or tp_fallback)
        and mesh is not None and not cfg.evaluate
        and not use_zero3 and not use_zero1 and not use_sp
    )
    if task == "tokens" and (use_tp or use_sp or use_zero3 or use_zero1
                             or use_gspmd or batch_ramp is not None):
        raise ValueError(
            f"'{cfg.arch}' is a token-sequence model: it trains on the "
            f"replicated data-parallel step only (one chip, or "
            f"--slices over a data mesh); unset DPTPU_TP / DPTPU_SP / "
            f"DPTPU_ZERO* / DPTPU_FSDP / DPTPU_GSPMD / DPTPU_BATCH_RAMP"
        )
    if want_gspmd and use_sp and verbose:
        print("=> DPTPU_GSPMD ignored: DPTPU_SP drives the "
              "sequence-parallel step")
    if want_gspmd and not use_gspmd and not use_sp and verbose:
        # name a ZeRO stage as the reason only when it will actually run
        why = (
            "DPTPU_ZERO=3 takes precedence"
            if use_zero3
            else "DPTPU_ZERO1 takes precedence"
            if use_zero1
            else "--evaluate does not train"
            if cfg.evaluate
            else "single-device run (no mesh)"
        )
        print(f"=> DPTPU_GSPMD ignored: {why}")
    if use_gspmd and derived.sync_bn and verbose:
        print("=> --sync-bn is implicit under DPTPU_GSPMD: BatchNorm "
              "always sees the global batch in the single-program step")
    # Bucketed backward-overlapped gradient comms (DPTPU_OVERLAP=1,
    # dptpu/parallel/overlap.py): composes with the shard_map step
    # families (DDP, ZeRO-1/3, --slices, --accum-steps) AND the plain
    # GSPMD path (per-bucket sharding-constraint boundaries — the
    # partitioner already interleaves per-leaf reductions, so the
    # buckets bound its regrouping freedom rather than create overlap
    # from nothing); TP/SP place their own collectives, and a mesh-less
    # single-device step has none to overlap.
    use_overlap = (
        want_overlap and mesh is not None and not cfg.evaluate
        and not use_tp and not use_sp
    )
    if want_overlap and not use_overlap and verbose:
        why = (
            "DPTPU_TP drives the GSPMD tensor-parallel step"
            if use_tp
            else "DPTPU_SP drives the sequence-parallel step"
            if use_sp
            else "--evaluate does not train"
            if cfg.evaluate and mesh is not None
            else "single-device run (no gradient collective to overlap)"
        )
        print(f"=> DPTPU_OVERLAP ignored: {why}")
    if _bucket_explicit and not want_overlap and verbose:
        print(f"=> DPTPU_BUCKET_MB={bucket_bytes / 1e6:g} noted: the "
              f"bucket bound only applies with DPTPU_OVERLAP=1")
    if use_overlap and verbose:
        print(
            f"=> overlapped gradient comms: reverse-layer buckets of "
            f"<= {bucket_bytes / 1e6:g} MB, each reduced as one fused "
            f"collective issued inside backward (bit-identical to the "
            f"unbucketed step)"
        )
    # The sharding fingerprint this run stamps into checkpoints:
    # "<rules-table-hash>:<placement>" for the sharded placements (the
    # hash pins the TABLE the placement came from, so editing a
    # family's rules reads as a sharding change on resume), plain
    # "replicated" for the replicated-param steps. The mid-epoch
    # --resume cross-check below fail-fasts on a mismatch naming both
    # fingerprints unless DPTPU_ELASTIC opts into re-sharding.
    from dptpu.models.registry import (
        GENERIC_RULES,
        partition_rules_for_arch,
    )
    from dptpu.parallel.rules import rules_fingerprint

    _arch_fp = rules_fingerprint(partition_rules_for_arch(cfg.arch))
    sharding_tag = (
        f"{_arch_fp}:zero3" if use_zero3
        # ZeRO-1 places per-leaf over data via the GENERIC table's
        # AUTO_FSDP row — its fingerprint must not move when a
        # family's TP rules are edited
        else f"{rules_fingerprint(GENERIC_RULES)}:zero1" if use_zero1
        else f"{_arch_fp}:tp{tp_n}" if use_tp
        else f"{_arch_fp}:fsdp" if (use_gspmd and use_hier)
        else "replicated"
    )
    # ramp x parallel-topology composition: the ramp rebuilds the
    # loader + step per phase, which only the shard_map families
    # support — fail fast naming the knobs and both alternatives
    if batch_ramp is not None and (use_tp or use_sp or use_gspmd):
        who = ("DPTPU_TP" if use_tp else
               "DPTPU_SP" if use_sp else "DPTPU_GSPMD")
        raise ValueError(
            f"DPTPU_BATCH_RAMP has no {who} composition (the ramp "
            f"rebuilds the loader and step per phase; only the "
            f"shard_map DDP/ZeRO-1/--slices families support that); "
            f"supported alternatives: unset DPTPU_BATCH_RAMP and keep "
            f"{who}, or unset {who} to run the ramped data-parallel "
            f"recipe"
        )
    # SyncBN spans EVERY replica: on a hierarchical mesh the BatchNorm
    # statistics pmean over both data axes (slice × dp_in_slice) — the
    # flax axis_name accepts the tuple like any jax collective
    _bn_axis = None
    if derived.sync_bn and mesh is not None and not use_gspmd:
        from dptpu.parallel.mesh import data_axis_names, squeeze_axes

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
        # space-to-depth stem: identical math + identical params (checkpoints
        # interchange freely; parity locked in tests/test_models.py). Opt-in
        # via DPTPU_S2D=1: measured ~1.3% SLOWER than the 7x7/2 stem on
        # v5e-1 (order-balanced interleaved A/B, 6 reps) — XLA's native
        # small-channel conv handling already covers this chip.
        **({"stem_space_to_depth": True} if use_s2d else {}),
        # fused Pallas stem (bn1+relu+maxpool custom-VJP region): opt-in,
        # parity-tested; slower than XLA's stem on v5e Mosaic (PERF.md)
        **({"fused_stem": True}
           if _os_environ_flag("DPTPU_FUSED_STEM") and _resnet_family
           else {}),
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
        from dptpu.ops.schedules import make_ramp_phase_schedule

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
    rng = jax.random.PRNGKey(cfg.seed if cfg.seed is not None else 0)
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

    import os

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
                        and saved_sharding != sharding_tag \
                        and not el_conf["elastic"]:
                    raise ValueError(
                        f"'{resolved}' was saved mid-epoch (step "
                        f"{resume_step}) under sharding "
                        f"'{saved_sharding}' but this run places as "
                        f"'{sharding_tag}' — the sharding config (ZeRO "
                        f"stage, TP rule, or the partition-rules table "
                        f"itself) changed. Resume with the saved "
                        f"config, pass --start-epoch to restart from "
                        f"an epoch boundary, or set DPTPU_ELASTIC=1 to "
                        f"re-shard the full-leaf checkpoint onto the "
                        f"new placement."
                    )
                if saved_sharding and saved_sharding != sharding_tag \
                        and el_conf["elastic"] and verbose:
                    print(f"=> elastic re-shard: checkpoint sharding "
                          f"'{saved_sharding}' -> '{sharding_tag}' "
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
                        # hierarchical mesh is actually in play: a
                        # single-device / TP / SP / GSPMD resume just
                        # declared DPTPU_SLICES a no-op above, and the
                        # remap must not fail over an ignored knob
                        slices=slices if use_hier else 1,
                        num_examples=len(train_ds),
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
        train_loader = _make_train_loader(host_batch * ramp_mult)
        train_loader.start()
        steps_per_epoch = max(len(train_loader), 1)
        schedule = _phase_schedule(ramp_mult, start_epoch)

    # want_zero*/use_zero* were computed once, before model build (the
    # GSPMD-precedence block) — reused here so the rule cannot desync.
    # --evaluate never trains: sharding the state only to re-gather it
    # for validation would be two pointless full-state device_put rounds.
    if want_zero3 and mesh is None and verbose:
        print("=> DPTPU_ZERO=3/DPTPU_FSDP ignored: single-device run "
              "(no mesh to shard the params over)")
    elif want_zero3 and cfg.evaluate and verbose:
        print("=> DPTPU_ZERO=3/DPTPU_FSDP ignored: --evaluate does not "
              "train")
    if want_zero1 and mesh is None and verbose:
        print("=> DPTPU_ZERO1 ignored: single-device run (no mesh to "
              "shard the optimizer state over)")
    elif want_zero1 and cfg.evaluate and not want_zero3 and verbose:
        print("=> DPTPU_ZERO1 ignored: --evaluate does not train")
    opt_shard_bytes = None
    _setup_phase("step_build")
    if use_zero3:
        # ZeRO-3/FSDP: params, gradients AND optimizer state live
        # sharded over the (intra-slice) data axis — placement comes
        # from the arch's partition-rules table projected onto the
        # data axis (dptpu/parallel/rules.py), the forward/backward
        # all-gather-on-use boundary is the _zero3_gather custom VJP
        # (its backward IS the reduce-scatter), and the entire update
        # runs on the local shard exactly like ZeRO-1. Same collective
        # volume as DDP (gather + scatter = the all-reduce bytes), so
        # the win is memory: ~1/N persistent bytes per chip for the
        # whole params+opt-state footprint (tests/test_zero1.py locks
        # parity and the byte ratio; SCALEBENCH reports it).
        from dptpu.parallel import (
            make_zero3_train_step,
            shard_zero3_state,
            state_shard_bytes,
            zero3_param_specs,
            zero3_state_specs,
        )

        z3_param_specs = zero3_param_specs(cfg.arch, state.params, mesh)

        def _build_train_step(sched):
            # `state` binds late: a ramp-phase rebuild mid-run passes
            # the LIVE sharded state as the template (same structure)
            return make_zero3_train_step(
                mesh, state, z3_param_specs, compute_dtype,
                lr_schedule=sched,
                seed=cfg.seed if cfg.seed is not None else 0,
                accum_steps=accum_steps, label_smoothing=label_smooth,
                tx_factory=partial(
                    make_optimizer, cfg.momentum, cfg.weight_decay,
                    opt_name, **adam_kw
                ),
                dcn_dtype=dcn_dtype if use_hier else "fp32",
                overlap=use_overlap, bucket_bytes=bucket_bytes,
            )

        train_step = _build_train_step(schedule)
        opt_shard_bytes = state_shard_bytes(
            state, mesh, zero3_state_specs(state, mesh, z3_param_specs)
        )
        state = shard_zero3_state(state, mesh, z3_param_specs)
        # one all-gather per validation pass / checkpoint write (the
        # ZeRO-1 discipline) — sharded leaves are global jax.Arrays,
        # so the gather is transparent to eval and the writer
        eval_view = lambda s: gather_state(s, mesh)  # noqa: E731
        eval_view_gathers = True  # collective: every host must join
        if verbose:
            print("=> ZeRO-3 param+grad+optimizer sharding over the "
                  f"data axis (rules table; persistent state "
                  f"{opt_shard_bytes / 1e6:.1f} MB/chip)")
    elif use_zero1:
        # ZeRO-1 weight-update sharding: params + optimizer state live
        # sharded over the data axis (~1/N persistent memory per chip),
        # gradients arrive reduce-scattered through the all-gather VJP,
        # and the ENTIRE update — including LARS/LAMB trust-ratio norms,
        # completed shard-locally with one small psum via the injected
        # tx_factory — runs on the local shard (arXiv:2004.13336;
        # tests/test_zero1.py). Checkpoints and eval read the state
        # transparently (sharded leaves are global jax.Arrays);
        # eval/checkpoint gathers are per-epoch, not per-step.
        def _build_train_step(sched):
            # `state` binds late: a ramp-phase rebuild mid-run passes
            # the LIVE sharded state as the template (same structure)
            return make_zero1_train_step(
                mesh, state, compute_dtype, lr_schedule=sched,
                seed=cfg.seed if cfg.seed is not None else 0,
                accum_steps=accum_steps, label_smoothing=label_smooth,
                tx_factory=partial(
                    make_optimizer, cfg.momentum, cfg.weight_decay,
                    opt_name, **adam_kw
                ),
                dcn_dtype=dcn_dtype if use_hier else "fp32",
                overlap=use_overlap, bucket_bytes=bucket_bytes,
            )

        train_step = _build_train_step(schedule)
        from dptpu.parallel import zero1_update_shard_bytes

        opt_shard_bytes = zero1_update_shard_bytes(state, mesh)
        state = shard_zero1_state(state, mesh)
        # one all-gather per validation pass / checkpoint write (instead
        # of per eval step), and multi-host save stays fully addressable
        eval_view = lambda s: gather_state(s, mesh)  # noqa: E731
        eval_view_gathers = True  # collective: every host must join
        if verbose:
            print("=> ZeRO-1 optimizer-state sharding over the data axis"
                  f" (update touches {opt_shard_bytes / 1e6:.1f} MB/chip)")
    elif use_gspmd:
        # single-program GSPMD/pjit path: shardings annotated on jit, the
        # partitioner derives every collective (gradient all-reduce over
        # data; under TP, one all-reduce per MLP/attention block over
        # model). Batch stays batch-dim-sharded over the data axes — the
        # layout shard_host_batch already produces — so loaders are
        # unchanged. On a hierarchical mesh (--slices > 1) params take
        # the rules-table FSDP placement over the intra-slice axis, so
        # the partitioner's decomposition is DCN-aware (the per-link
        # budget gspmd_hier in HLO_BUDGETS.json locks the shape).
        from dptpu.parallel.gspmd import (
            dp_specs,
            gspmd_specs_for_arch,
            make_gspmd_train_step,
            shard_gspmd_state,
            tp_specs_for_arch,
        )

        if use_tp:
            # a demoted (no-rule) TP request never reaches here — the
            # fallback cleared use_tp at mesh time, so the rule is real
            rule, specs = tp_specs_for_arch(cfg.arch, state.params)
            if verbose:
                print(
                    f"=> tensor parallelism: {rule} over model axis of "
                    f"{tp_n} × data axis of {int(mesh.shape['data'])}"
                )
        elif use_hier:
            rule = "gspmd_fsdp"
            specs = gspmd_specs_for_arch(
                cfg.arch, state.params, mesh, fsdp=True
            )
            if verbose:
                print("=> GSPMD hierarchical data parallelism: "
                      "rules-table FSDP placement over the intra-slice "
                      "axis; the partitioner derives the per-link "
                      "collective decomposition")
            if dcn_dtype != "fp32":
                print(f"=> DPTPU_DCN_DTYPE={dcn_dtype} ignored: the "
                      f"GSPMD partitioner schedules its own DCN "
                      f"collectives (the compressed hop is "
                      f"shard_map-only)")
        else:
            rule, specs = "dp_specs", dp_specs(state.params)
            if verbose:
                print("=> GSPMD single-program data parallelism (dp_specs)")
        train_step = make_gspmd_train_step(
            mesh, state, specs, compute_dtype, lr_schedule=schedule,
            seed=cfg.seed if cfg.seed is not None else 0,
            accum_steps=accum_steps, label_smoothing=label_smooth,
            overlap=use_overlap, bucket_bytes=bucket_bytes,
        )
        state = shard_gspmd_state(state, mesh, specs)
        if rule == "dp_specs":
            eval_view = lambda s: s  # noqa: E731
            eval_view_gathers = False
        else:
            # TP-sharded params: one all-gather per validation pass /
            # checkpoint write (the ZeRO-1 discipline) so the replicated-
            # spec eval step and the checkpoint writer see full leaves
            eval_view = lambda s: gather_state(s, mesh)  # noqa: E731
            eval_view_gathers = True
    elif use_sp:
        # sequence-parallel step: token axis over the inner seq axis,
        # batch over data. Params stay replicated (no sharded state, no
        # gather needed) — the SAME TrainState trains here and evals
        # through the standard replicated eval step below. The step's
        # model is a second ViT instance with the seq flags on; its
        # param tree is identical (the flags add no params).
        from dptpu.parallel.sequence import SEQ_AXIS, make_seq_train_step

        seq_model = create_model(
            cfg.arch,
            num_classes=num_classes,
            dtype=compute_dtype,
            seq_axis_name=SEQ_AXIS,
            seq_mode=sp_mode,
            seq_shard_tokens=True,
        )
        train_step = make_seq_train_step(
            mesh, seq_model, compute_dtype, lr_schedule=schedule,
            label_smoothing=label_smooth,
        )
        eval_view = lambda s: s  # noqa: E731
        eval_view_gathers = False
        if verbose:
            print(
                f"=> sequence parallelism: {sp_mode} attention over seq "
                f"axis of {sp_n} × data axis of {int(mesh.shape['data'])} "
                f"(tokens pad to multiples of {sp_n}; cls psum-recovered)"
            )
    else:
        def _build_train_step(sched):
            return make_train_step(
                mesh, compute_dtype, lr_schedule=sched,
                seed=cfg.seed if cfg.seed is not None else 0,
                accum_steps=accum_steps, label_smoothing=label_smooth,
                dcn_dtype=dcn_dtype if use_hier else "fp32",
                overlap=use_overlap, bucket_bytes=bucket_bytes,
                task=task,
            )

        train_step = _build_train_step(schedule)
        eval_view = lambda s: s  # noqa: E731
        eval_view_gathers = False
        if jax.process_count() == 1:
            _setup_phase("state_commit")
            # commit the state to where the step will leave it (this
            # device, or replicated over the mesh): the uncommitted
            # state of the first call and the committed one the step
            # returns are different jit cache keys, and the second would
            # compile the whole step again at step 1 (chip run, PR 21:
            # 44.8 s then 21.6 s for ResNet-50). One process only: a
            # host-local state is not placed on a mesh that spans hosts.
            state = (put(state) if single_device
                     else jax.device_put(state, replicated_sharding(mesh)))
            _setup_phase("step_build")
    eval_step = make_eval_step(mesh, compute_dtype, task=task)

    if cfg.evaluate:
        stats = validate(
            eval_view(state),
            eval_step,
            DevicePrefetcher(val_loader.epoch(0), put),
            num_batches=len(val_loader),
            print_freq=cfg.print_freq,
            verbose=verbose,
            count_divisor=val_count_divisor,
        )
        train_loader.close()
        val_loader.close()
        for ds in (train_ds, val_ds):
            if hasattr(ds, "close"):
                ds.close()
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
    from dptpu.envknob import env_str

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
    from dptpu.envknob import env_bool as _env_bool
    from dptpu.train.checkpoint import AsyncCheckpointWriter

    ckpt_writer = (
        AsyncCheckpointWriter()
        if cfg.ckpt_steps and _env_bool("DPTPU_ASYNC_CKPT", True)
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
        sharding=sharding_tag,
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

    from dptpu.envknob import env_str as _env_str

    _quorum_dir = _env_str("DPTPU_QUORUM_DIR")
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
    emergency_ok = derived.num_processes == 1 or not eval_view_gathers

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
        if workers_mode == "process":
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
            print("=> DPTPU_STRAGGLER_FACTOR ignored: thread-mode feed "
                  "(DPTPU_WORKERS_MODE=thread, or a host with two "
                  "cores or fewer: no worker pool the controller "
                  "could re-split/evict)")

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
            if workers_mode == "process":
                # callable indirection: the ramp phase switch rebuilds
                # the loader and the actuator must follow it, not a
                # closed one
                tune_ctl.add(decode_ahead_actuator(
                    lambda: train_loader,
                    interval_s=tune_conf["interval_s"],
                    on_event=_tune_evt,
                ))
            elif verbose:
                print("=> tune control: decode_ahead ignored on a "
                      "thread-mode feed (DPTPU_WORKERS_MODE=thread, or "
                      "a host with two cores or fewer: no ring to "
                      "deepen)")
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
        train_loader = _make_train_loader(host_batch * m)
        if old_ahead is not None and (
                train_loader.decode_ahead is None
                or train_loader.decode_ahead < old_ahead):
            # a controller-deepened issue window survives the rebuild
            # (the ctor already re-applied any explicit env value; only
            # carry forward what grew beyond it)
            train_loader.decode_ahead = old_ahead
        steps_per_epoch = max(len(train_loader), 1)
        schedule = _phase_schedule(m, epoch)
        train_step = _build_train_step(schedule)
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
    # last position at which `state` is known consistent — the boundary
    # fallback for the best-effort save below (mid-epoch exceptions save
    # their exact position through train_one_epoch's emergency_cb)
    current_pos = {"epoch": start_epoch, "step": resume_step}
    emergency = {"saved": False}
    # loop entry: set-up is over. Its spans leave the ring here (into
    # the sink and the one ``=> set-up:`` line), so that the first
    # epoch's attribution sees its own spans only.
    _setup_phase(None)
    if tracer.enabled:
        setup_spans = tracer.drain()
        setup_report = obs.setup_report(setup_spans)
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
            state, train_stats = train_one_epoch(
                state,
                train_step,
                DevicePrefetcher(
                    train_loader.epoch(epoch, start_batch=start_step), put,
                    first_step=start_step,
                ),
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
                count_divisor=val_count_divisor,
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
            with tracer.span("ckpt"):
                boundary_path = save_checkpoint(
                    gathered,
                    epoch=epoch + 1,
                    arch=cfg.arch,
                    best_acc1=best_acc1,
                    is_best=is_best,
                    is_chief=derived.is_chief,
                    directory=ckpt_dir,
                    geometry=manager.geometry,
                    sharding=sharding_tag,
                )
            if fault_plan is not None and boundary_path:
                # boundary saves count toward ckpt_truncate@save=N too —
                # the fault targets "the N-th checkpoint written", not
                # only the rotated step files. Store-URL saves have no
                # local file to tear, so the hook stands down there
                # (the CheckpointManager applies the same guard)
                from dptpu.data.store import is_store_url as _is_url

                if not _is_url(boundary_path):
                    fault_plan.on_checkpoint_saved(boundary_path)
            # one registry, one fan-out (dptpu/obs): the reference's 11
            # scalars/epoch (imagenet_ddp_apex.py:280-290), the feed
            # telemetry, and the step-phase attribution all publish into
            # the metrics registry and flush ONCE per epoch to every
            # attached sink — TB writer (chief, apex), the per-host
            # JSONL log (DPTPU_OBS_DIR), and the console Obs line —
            # replacing the three parallel plumbing paths that used to
            # carry them. Tags are unchanged: dashboards keep working.
            bt = max(train_stats["batch_time"], 1e-9)
            val_bt = max(val_stats.get("batch_time", bt), 1e-9)
            # under a batch ramp a step consumes the PHASE batch —
            # ramp_mult follows the switcher, so throughput stays
            # honest across phases (val keeps the base batch)
            scalars = {
                "Throughput/train":
                    derived.global_batch_size * ramp_mult / bt,
                "Throughput/val": derived.global_batch_size / val_bt,
                "Time/train": train_stats["batch_time"],
                "Time/val": val_bt,
                # feed-rate accounting: loader wait per step + the
                # fraction of the epoch the chip spent starved for data
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
            # decode-cache + zero-copy + decode-ahead ring telemetry
            # (bytes_copied_per_batch = 0 is the zero-copy contract on
            # a dashboard)
            for tag, key in (
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
            # large-batch engine telemetry (Opt/*): accumulation depth,
            # the layer-wise trust-ratio spread (min/mean/max over
            # layers, from the optimizer's own norms), and — under the
            # sharded weight update — the bytes of optimizer state one
            # chip actually touches per update (the 1/N claim on a
            # dashboard)
            scalars["Opt/accum_steps"] = accum_steps
            for tag, key in (
                ("Opt/trust_ratio_min", "trust_min"),
                ("Opt/trust_ratio_mean", "trust_mean"),
                ("Opt/trust_ratio_max", "trust_max"),
            ):
                if key in train_stats:
                    scalars[tag] = train_stats[key]
            if opt_shard_bytes is not None:
                scalars["Opt/update_shard_bytes"] = opt_shard_bytes
            # expert layers (a model that has them): the load of the
            # experts held here, a step and a layer, over the epoch
            for tag, key in (
                ("Moe/load_max", "moe_load_max"),
                ("Moe/load_mean", "moe_load_mean"),
                ("Moe/local_slot_share", "moe_local_slot_share"),
                ("Moe/dropped_tokens", "moe_dropped"),
            ):
                if key in train_stats:
                    scalars[tag] = train_stats[key]
            if verbose and "moe_load_max" in train_stats:
                print(
                    "Moe: busiest held expert {moe_load_max:.1f} tokens a "
                    "layer a step (mean {moe_load_mean:.1f}), "
                    "{moe_local_slot_share:.2f}% of the routed slots on "
                    "held experts, {moe_dropped:.0f} tokens "
                    "dropped".format(**train_stats)
                )
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
                    "Obs/anomalous_steps":
                        len(obs_report["anomalous_steps"]),
                    "Obs/tracer_dropped": tracer.dropped,
                })
            registry.set_scalars(scalars)
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
                    sharding=sharding_tag,
                )
                if fault_plan is not None and early_path:
                    from dptpu.data.store import is_store_url as _is_url

                    if not _is_url(early_path):
                        fault_plan.on_checkpoint_saved(early_path)
                if verbose:
                    print(
                        f"top-1 accuracy {best_acc1:.3f} reached desired "
                        f"{target_pct:.3f} after {training_time:.1f}s"
                    )
                result["early_stopped"] = True
                result["training_time"] = training_time
                break
    except BaseException:
        # best-effort safety net (never masks the original error): an
        # unexpected exception or KeyboardInterrupt between epoch-boundary
        # saves used to lose everything since the last boundary. Mid-epoch
        # failures already saved their exact position via emergency_cb;
        # anything else (validate, TB, checkpoint-best) saves the last
        # consistent boundary position here.
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
    train_loader.close()
    val_loader.close()
    for ds in (train_ds, val_ds):
        # streaming datasets own fds + /dev/shm staging slabs; release
        # them at the end of the run (ImageFolder/Synthetic have no
        # close — their caches are reclaimed by the atexit sweeps)
        if hasattr(ds, "close"):
            ds.close()
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
