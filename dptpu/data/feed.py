"""The feed of one ``fit()``: which workers, which cache, which sampler.

``build_feed`` reads the input-pipeline knobs under the fail-fast
contract, makes the two data sets a source names, shards them over the
hosts and starts the train loader's worker pool. Everything it
configures lives beside it in ``dptpu/data``. A worker process imports
this package, so neither this module nor anything it imports at its top
imports jax.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Callable

from dptpu.data.dataset import ImageFolderDataset, SyntheticDataset
from dptpu.data.loader import DataLoader
from dptpu.data.sampler import ShardedSampler
from dptpu.data.transforms import train_transform, val_transform


# when a feed has no worker pool, for whoever says what needed one
THREAD_MODE_WHEN = ("DPTPU_WORKERS_MODE=thread, or a host with two cores "
                    "or fewer")


@dataclass
class Feed:
    train_ds: object
    val_ds: object
    num_classes: int
    train_sampler: object
    # the per-host train loader at another batch (a batch-ramp phase)
    make_train_loader: Callable
    train_loader: DataLoader  # its pool started, unless --evaluate
    val_loader: DataLoader
    # every host of a ddp/nd run validates the FULL set, so the psum'd
    # count is this many times the set's
    val_count_divisor: int
    workers_mode: str
    notices: tuple  # the "=> input pipeline:" line first


def shard_source(data: str):
    """``(train_loc, val_loc)`` when ``data`` names a PACKED-shard tree
    (``dptpu pack`` layout: train/ + val/ each holding a manifest) —
    either a store URL (http(s)://, file://) or a local directory with
    manifests — else None (plain ImageFolder)."""
    from dptpu.data.shards import MANIFEST_NAME
    from dptpu.data.store import is_store_url

    if is_store_url(data):
        base = data.rstrip("/")
        return f"{base}/train", f"{base}/val"
    if os.path.exists(os.path.join(data, "train", MANIFEST_NAME)):
        return os.path.join(data, "train"), os.path.join(data, "val")
    return None


def build_token_datasets(cfg, task: str, model_config):
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


def build_datasets(cfg, image_size: int, cache_bytes: int = 0,
                   cache_scope: str = "sharded"):
    if cfg.data.startswith("synthetic"):
        n = int(cfg.data.split(":", 1)[1]) if ":" in cfg.data else 2048
        train_ds = SyntheticDataset(n, image_size, 1000)
        val_ds = SyntheticDataset(max(n // 10, 1), image_size, 1000)
        return train_ds, val_ds, 1000
    # DPTPU_CACHE_BYTES is a PER-DATASET budget: train and val each keep
    # their own decoded-pixel cache (val redecodes the same files every
    # epoch, so it benefits at least as much per byte)
    shards = shard_source(cfg.data)
    if shards is not None:
        # packed-shard streaming data plane (dptpu/data/stream.py):
        # pixels are bit-identical to the ImageFolder path by
        # construction, so --data may point at either form of the same
        # dataset and a seeded run cannot tell the difference
        from dptpu.data.stream import ShardStreamDataset

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


def host_cores() -> int:
    """The cores this process may run on (its affinity mask, which a
    container or ``taskset`` narrows; the machine's count where the
    platform has no such call)."""
    try:
        return len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return os.cpu_count() or 1


# cores a process pool leaves to the loop's own process: its thread,
# the runtime's transfer threads, the ring's pump. On the four-chip host
# that process took 1.6 cores beside four workers and 1.9, 2.1, 2.1
# beside eight, twelve, sixteen (512 rows and a 77 MB put a step, twenty
# steps a second; PERF.md section 6, PR 40). Workers that are ahead of
# the loop sleep on a full ring, so the count need not be generous.
POOL_RESERVE_CORES = 2


def pool_size(workers: int, local_chips: int, cores: int,
              workers_mode: str) -> int:
    """How many workers this host's feed gets for ``-j workers``.

    The reference gives each of a node's GPU processes
    ``ceil(workers / ngpus)`` loader workers (imagenet_ddp.py:126); one
    process drives all local chips here, so the host's pool is at least
    that times the chips: the FLOOR, what every host got before PR 40, and
    the whole answer in thread mode, where pool threads share the
    interpreter with the loop (four of them held its dispatch call for
    65 ms of 69, PERF.md section 6, PR 31) and more would cost more.

    A pool of worker PROCESSES gives a local chip the ``workers`` it
    has when it is alone on a host: ``workers`` for each local chip, as
    far as the host has cores for them beside ``POOL_RESERVE_CORES``,
    and never below the floor. Four workers for four chips made 512
    rows in 58.9 ms against a device step of 49.6 (PERF.md section 6,
    PR 40); launched as four processes of one chip each the same host
    always got sixteen. With one local chip it is ``workers`` whatever
    the cores; ``-j 0`` stays 0 (the loader then keeps one worker)."""
    floor = -(-workers // local_chips) * local_chips
    if workers_mode != "process":
        return floor
    return max(floor, min(workers * local_chips,
                          cores - POOL_RESERVE_CORES))


def pool_notice(workers: int, local_chips: int, cores: int,
                workers_mode: str) -> str:
    """``workers=N (how N came about)`` for the ``=> input pipeline:``
    line: what was asked a chip, what bounded it."""
    size = pool_size(workers, local_chips, cores, workers_mode)
    asked = workers * local_chips
    chips = f"{local_chips} chip" + ("s" if local_chips != 1 else "")
    if workers_mode != "process":
        how = (f"thread mode keeps ceil({workers} / {local_chips}) a chip "
               f"x {chips}")
    elif size == asked:
        how = f"{workers} a chip x {chips}; {cores} cores"
    elif size == cores - POOL_RESERVE_CORES:
        how = (f"{workers} a chip x {chips} asks {asked}; {cores} cores "
               f"less {POOL_RESERVE_CORES} kept for the loop")
    else:
        how = (f"the floor ceil({workers} / {local_chips}) a chip x "
               f"{chips}; {cores} cores")
    return f"workers={size} ({how})"


def feed_knobs() -> tuple:
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
    * ``DPTPU_CACHE_BYTES`` — budget of the decoded-pixel cache, so
      that epoch 1+ skips JPEG Huffman decode (0/unset: off);
    * ``DPTPU_CACHE_SCOPE`` — ``pooled`` (one cross-process /dev/shm
      slab, the process-mode default) or ``sharded`` (in-process
      ``DecodeCache``, split N ways by a worker pool; the thread-mode
      default, where in-process already means pooled);
    * ``DPTPU_LEASE`` — zero-copy consumer-leased batch slots in process
      mode (default on; the copy-out path remains for ``=0``).
    """
    from dptpu.envknob import env_bool, env_choice, env_int

    workers_mode = env_choice(
        "DPTPU_WORKERS_MODE", ("thread", "process"),
        default="process" if host_cores() > 2 else "thread",
    )
    cache_bytes = env_int("DPTPU_CACHE_BYTES", None)
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


def build_feed(cfg, derived, *, task: str, model_config, image_size: int,
               ramp_mult: int = 1) -> Feed:
    """The train and validation feed of this host. ``model_config``: a
    token-sequence model's configuration (the rows' length and the ids'
    range are the model's), None for an image model. ``ramp_mult``: the
    multiple of the per-host batch the first epoch trains at (a batch
    ramp's phase)."""
    from dptpu.data import native_image
    from dptpu.envknob import env_bool

    workers_mode, cache_bytes, cache_scope, leased = feed_knobs()
    # this host's pool, train and validation alike (they feed the same
    # chips): the one place that decides it
    pool = (cfg.workers, derived.local_device_count, host_cores(),
            workers_mode)
    num_workers = pool_size(*pool)
    notices = [
        f"=> input pipeline: workers_mode={workers_mode}, "
        f"{pool_notice(*pool)}, decode cache "
        + (f"{cache_bytes / 1e6:.0f} MB per dataset ({cache_scope})"
           if cache_bytes else "off")
        + (", leased slots" if leased and workers_mode == "process"
           else "")
        # which JPEG decoder is live: the native libjpeg ops, or PIL
        # after a failed build (dptpu/native/build.py says why)
        + f", native={native_image.available()}"
    ]
    if task == "tokens" or cfg.data.startswith("tokens"):
        train_ds, val_ds, num_classes = build_token_datasets(
            cfg, task, model_config)
    else:
        train_ds, val_ds, num_classes = build_datasets(
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
    want_locality = env_bool("DPTPU_SHARD_LOCALITY", False)
    use_locality = want_locality and hasattr(train_ds, "shard_set")
    if want_locality and not use_locality:
        notices.append("=> DPTPU_SHARD_LOCALITY ignored: --data is not a "
                       "packed-shard tree (dptpu pack)")
    seed = cfg.seed if cfg.seed is not None else 0
    if use_locality:
        from dptpu.data.shards import ShardLocalitySampler

        notices.append("=> shard-locality sampling: seeded shard-level "
                       "shuffle + in-shard shuffle (sequential extent I/O; "
                       "trajectory differs from the global-permutation "
                       "default)")
        train_sampler = ShardLocalitySampler(
            train_ds.shard_set,
            num_shards=derived.num_processes,
            shard_index=derived.process_index,
            shuffle=True,
            seed=seed,
        )
    else:
        train_sampler = ShardedSampler(
            len(train_ds),
            num_shards=derived.num_processes,
            shard_index=derived.process_index,
            shuffle=True,
            seed=seed,
        )

    def make_train_loader(batch: int) -> DataLoader:
        return DataLoader(
            train_ds,
            batch,
            sampler=train_sampler,
            num_workers=num_workers,
            drop_last=True,
            pad_final=False,
            seed=seed,
            workers_mode=workers_mode,
            leased=leased,
        )

    host_batch = derived.per_host_batch_size
    train_loader = make_train_loader(host_batch * ramp_mult)
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
    # DPTPU_DIST_EVAL=1 shards validation over the hosts for EVERY
    # variant. The in-step psum'd correct/count sums make the sharded
    # aggregate EXACT, and on one host the shard IS the full set, so
    # top1 is bit-identical to the single-stream pass by construction
    # (locked in tests/test_overlap.py).
    dist_eval = bool(env_bool("DPTPU_DIST_EVAL", False))
    full_val = cfg.variant in ("ddp", "nd") and not dist_eval
    if dist_eval:
        if cfg.variant in ("ddp", "nd") and derived.num_processes > 1:
            notices.append(
                f"=> distributed eval: val set sharded over "
                f"{derived.num_processes} hosts (exact psum-aggregated "
                f"top1; each host decodes 1/{derived.num_processes} of "
                f"the set instead of all of it)"
            )
        elif cfg.variant == "apex":
            notices.append(
                "=> DPTPU_DIST_EVAL noted: the apex variant already "
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
        num_workers=num_workers,
        workers_mode=workers_mode,
        leased=leased,
    )
    return Feed(
        train_ds=train_ds,
        val_ds=val_ds,
        num_classes=num_classes,
        train_sampler=train_sampler,
        make_train_loader=make_train_loader,
        train_loader=train_loader,
        val_loader=val_loader,
        val_count_divisor=derived.num_processes if full_val else 1,
        workers_mode=workers_mode,
        notices=tuple(notices),
    )
