"""Config layer: the reference's three argparse surfaces, made immutable.

The reference carries three near-identical CLI schemas (imagenet_ddp.py:23-67;
imagenet_ddp_apex.py:42-98; nd_imagenet.py:26-76) and then *mutates* the
parsed ``args`` at runtime (per-GPU batch/worker rescaling
imagenet_ddp.py:125-126, linear LR scaling imagenet_ddp_apex.py:161-162,
world-size rescaling imagenet_ddp.py:76-81). Here the same flags parse into a
frozen :class:`Config` and every derived quantity is computed once, purely, in
:class:`DerivedConfig` — nothing downstream ever rewrites configuration.

CUDA-specific flags (``--dist-backend nccl``, ``--opt-level O2``,
``--loss-scale``, ``--channels-last``, ``--gpu``) are **accepted and mapped,
never a crash** (SURVEY.md §7 hard part (e)): on TPU, NCCL becomes XLA ICI/DCN
collectives, any Apex opt-level ≥ O1 becomes the bf16 compute policy (loss
scaling is unnecessary in bf16 — same exponent range as fp32), channels_last
is a no-op because the zoo is already NHWC, and ``--gpu`` pins
``jax.local_devices()[gpu]``.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from typing import Optional

# Flag spec table: (args, kwargs) per flag, keyed by which CLI variants carry
# it. Variants: "ddp" = imagenet_ddp.py, "apex" = imagenet_ddp_apex.py,
# "nd" = nd_imagenet.py. Defaults that differ per variant are resolved in
# build_parser.
_VARIANTS = ("ddp", "apex", "nd")

# Per-variant default overrides (reference: arch resnet18 + batch 256 in nd,
# nd_imagenet.py:29,40; batch 224 *per GPU* in apex, imagenet_ddp_apex.py:63-67).
_DEFAULTS = {
    "ddp": {"arch": "resnet50", "batch_size": 1024},
    "apex": {"arch": "resnet50", "batch_size": 224},
    "nd": {"arch": "resnet18", "batch_size": 256},
}


@dataclasses.dataclass(frozen=True)
class Config:
    """Union of the three reference CLI schemas, immutable.

    Field names follow the reference's ``dest`` names exactly so downstream
    code reads like the reference's ``args.*`` accesses.
    """

    data: str
    arch: str = "resnet50"
    workers: int = 4
    epochs: int = 90
    start_epoch: int = 0
    batch_size: int = 1024
    lr: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 1e-4
    print_freq: int = 10
    resume: str = ""
    evaluate: bool = False
    pretrained: bool = False
    # resilience (dptpu extension, all variants): mid-epoch checkpoint
    # cadence + rotation depth (dptpu/resilience). 0 = epoch-boundary
    # saves only, the reference's behavior (imagenet_ddp.py:216-222).
    ckpt_steps: int = 0
    ckpt_keep: int = 3
    # checkpoint destination: a directory OR a store URL (file:// /
    # http(s)://) routed through dptpu.data.store — object-store
    # checkpointing with the same CRC-footer + fallback-scan contract.
    # Empty keeps the legacy default (CWD; apex: the TB run dir).
    ckpt_dir: str = ""
    # large-batch training engine (dptpu extension, all variants):
    # optimizer recipe, gradient-accumulation microbatching, warmup
    # schedule and label smoothing (dptpu/ops/optimizers.py,
    # dptpu/train/step.py). Defaults reproduce the reference exactly.
    optimizer: str = "sgd"
    # adamw's moments (torch AdamW's defaults)
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    accum_steps: int = 1
    warmup_epochs: int = 0
    label_smoothing: float = 0.0
    # hierarchical data parallelism (dptpu extension, all variants):
    # factor the data axis into {slice: S, dp_in_slice} so gradient
    # reduction runs reduce-scatter on ICI and only a shard-sized
    # all-reduce on DCN (dptpu/parallel/hierarchy.py). 1 = flat mesh,
    # the reference topology. Env twin DPTPU_SLICES wins when set;
    # DPTPU_DCN_DTYPE=bf16 additionally compresses the DCN hop.
    slices: int = 1
    # a token-sequence model (dptpu extension, all variants; ``-a`` names
    # one, e.g. lfm2_8b_a1b): tokens in a row, and the share of the
    # model this chip holds in an expert-parallel, vocabulary-parallel,
    # pipelined deployment, each FIRST:COUNT over the published model.
    # Empty/0 = the model's own (its default length, all of it).
    seq_len: int = 0
    layers: str = ""
    experts: str = ""
    vocab_rows: str = ""
    # distributed (ddp/nd; apex uses env:// exclusively)
    world_size: int = -1
    rank: int = -1
    dist_url: str = "tcp://224.66.41.62:23456"
    dist_backend: str = "nccl"
    desired_acc: Optional[float] = None
    # nd extras (nd_imagenet.py:68-76)
    seed: Optional[int] = None
    gpu: Optional[int] = None
    multiprocessing_distributed: bool = False
    # apex extras (imagenet_ddp_apex.py:88-95). local_rank's REFERENCE
    # default is 0; None here just distinguishes "not passed" so the
    # accepted-and-mapped notice can fire even for an explicit 0 (the
    # launcher's first worker) — behavior is identical either way.
    local_rank: Optional[int] = None
    sync_bn: bool = False
    opt_level: Optional[str] = None
    keep_batchnorm_fp32: Optional[str] = None
    loss_scale: Optional[str] = None
    channels_last: bool = False
    # which CLI variant parsed this config (drives batch semantics + schedule)
    variant: str = "ddp"

    def replace(self, **kw) -> "Config":
        return dataclasses.replace(self, **kw)


def build_parser(variant: str = "ddp", model_names=None) -> argparse.ArgumentParser:
    """Build the argparse surface for one reference CLI variant.

    Flag names, aliases, types, and defaults match the reference schema for
    that variant (SURVEY.md §2 #1/#12/#20) so published run commands
    (README.md:64-99) parse unchanged.
    """
    if variant not in _VARIANTS:
        raise ValueError(f"variant must be one of {_VARIANTS}, got {variant!r}")
    if model_names is None:
        from dptpu.models import model_names as _mn

        model_names = _mn()
    d = _DEFAULTS[variant]

    p = argparse.ArgumentParser(description="TPU-native ImageNet Training (dptpu)")
    p.add_argument("data", metavar="DIR", help="path to dataset")
    p.add_argument(
        "-a",
        "--arch",
        metavar="ARCH",
        default=d["arch"],
        choices=model_names,
        help="model architecture: " + " | ".join(model_names),
    )
    p.add_argument("-j", "--workers", default=4, type=int, metavar="N",
                   help="number of data loading workers")
    p.add_argument("--epochs", default=90, type=int, metavar="N")
    p.add_argument("--start-epoch", default=0, type=int, metavar="N",
                   help="manual epoch number (useful on restarts)")
    batch_help = (
        "per-device mini-batch size"
        if variant == "apex"
        else "total batch size across all local devices"
    )
    p.add_argument("-b", "--batch-size", default=d["batch_size"], type=int,
                   metavar="N", help=batch_help)
    p.add_argument("--lr", "--learning-rate", default=0.1, type=float,
                   metavar="LR", dest="lr", help="initial learning rate")
    p.add_argument("--momentum", default=0.9, type=float, metavar="M")
    p.add_argument("--wd", "--weight-decay", default=1e-4, type=float,
                   metavar="W", dest="weight_decay")
    p.add_argument("-p", "--print-freq", default=10, type=int, metavar="N")
    p.add_argument("--resume", default="", type=str, metavar="PATH",
                   help="path to latest checkpoint — a FILE (used if it "
                        "verifies; corrupt files fall back to the newest "
                        "verifiable sibling) or a DIRECTORY to scan")
    # dptpu resilience extension (not a reference flag): preemption-safe
    # mid-epoch checkpoints; resume replays the deterministic sampler to
    # the saved (epoch, step) so the trajectory stays bit-identical
    p.add_argument("--ckpt-steps", default=0, type=int, metavar="N",
                   help="also save a rotated mid-epoch checkpoint every N "
                        "steps (0 disables; SIGTERM/SIGINT always trigger "
                        "one final mid-epoch save)")
    p.add_argument("--ckpt-keep", default=3, type=int, metavar="K",
                   help="how many rotated mid-epoch checkpoints to keep")
    p.add_argument("--ckpt-dir", default="", type=str, metavar="DIR_OR_URL",
                   help="where checkpoints go: a directory or a store "
                        "URL (file:// or http(s)://, dptpu.data.store) — "
                        "writes keep the CRC footer and --resume keeps "
                        "the corrupt-fallback scan either way")
    # dptpu large-batch extension (not reference flags): the
    # ImageNet-in-minutes recipe — LARS/LAMB trust-ratio optimizers,
    # emulated large batches via gradient accumulation, linear-warmup +
    # cosine LR, label smoothing. Env twins: DPTPU_OPT / DPTPU_ACCUM /
    # DPTPU_WARMUP_EPOCHS / DPTPU_LABEL_SMOOTH (env wins when set).
    p.add_argument("--optimizer", default="sgd",
                   choices=("sgd", "lars", "lamb", "adamw"),
                   help="update rule: reference SGD (default), the "
                        "large-batch layer-wise trust-ratio optimizers "
                        "LARS/LAMB, or AdamW (decoupled weight decay on "
                        "matrices only; --beta1/--beta2/--eps, --wd)")
    p.add_argument("--beta1", default=0.9, type=float, metavar="B",
                   help="adamw: decay of the first moment")
    p.add_argument("--beta2", default=0.999, type=float, metavar="B",
                   help="adamw: decay of the second moment")
    p.add_argument("--eps", default=1e-8, type=float, metavar="E",
                   help="adamw: added to the root of the second moment")
    # dptpu token-sequence extension (not reference flags): what a
    # language model (-a lfm2_8b_a1b) needs beside the image flags
    p.add_argument("--seq-len", default=0, type=int, metavar="S",
                   help="tokens in a row of a token-sequence model "
                        "(0 = the model's default)")
    p.add_argument("--layers", default="", type=str, metavar="FIRST:COUNT",
                   help="token-sequence models: the published layers this "
                        "chip holds (a pipeline stage); empty = all")
    p.add_argument("--experts", default="", type=str, metavar="FIRST:COUNT",
                   help="token-sequence models: the experts of each expert "
                        "layer this chip holds (expert parallelism: the "
                        "router still routes over all); empty = all")
    p.add_argument("--vocab-rows", default="", type=str,
                   metavar="FIRST:COUNT",
                   help="token-sequence models: the vocabulary rows this "
                        "chip holds (ids, logits and loss are over them); "
                        "empty = all")
    p.add_argument("--accum-steps", default=1, type=int, metavar="K",
                   help="gradient-accumulation microbatches per step: "
                        "each replica's batch splits into K fp32-"
                        "accumulated microbatches before one optimizer "
                        "update, so -b can exceed per-chip activation "
                        "memory (the global batch is unchanged; K "
                        "emulates a K x wider pod at microbatch b/K)")
    p.add_argument("--warmup-epochs", default=0, type=int, metavar="N",
                   help="N > 0 selects the large-batch schedule: linear "
                        "LR warmup over N epochs then cosine decay "
                        "(0 keeps the variant's reference schedule)")
    p.add_argument("--label-smoothing", default=0.0, type=float,
                   metavar="S",
                   help="label-smoothing mass in [0, 1) for the training "
                        "loss (0 = reference hard-target CE)")
    # dptpu hierarchical-comms extension (not a reference flag): on a
    # multi-slice pod the DCN hop between slices is ~10x slower than
    # ICI; --slices S rewrites the gradient all-reduce as
    # reduce-scatter(ICI) -> shard-sized all-reduce(DCN) ->
    # all-gather(ICI), cutting per-chip DCN bytes to ~1/(N/S). Env twin:
    # DPTPU_SLICES (wins when set); DPTPU_DCN_DTYPE=bf16 halves the DCN
    # bytes again (fp32 accumulation).
    p.add_argument("--slices", default=1, type=int, metavar="S",
                   help="factor the data-parallel mesh into S "
                        "DCN-connected slices for two-level gradient "
                        "reduction (1 = flat mesh; S must divide the "
                        "device count)")
    p.add_argument("-e", "--evaluate", dest="evaluate", action="store_true",
                   help="evaluate model on validation set")
    p.add_argument("--pretrained", dest="pretrained", action="store_true")

    if variant in ("ddp", "nd"):
        p.add_argument("--world-size", default=-1, type=int,
                       help="number of nodes for distributed training")
        p.add_argument("--rank", default=-1, type=int,
                       help="node rank for distributed training")
        p.add_argument("--dist-url", default="tcp://224.66.41.62:23456",
                       type=str, help="rendezvous url (host:port of node 0)")
        p.add_argument("--dist-backend", default="nccl", type=str,
                       help="accepted for CLI parity; TPU always uses XLA "
                            "collectives over ICI/DCN")
    if variant == "ddp":
        p.add_argument("--desired-acc", default=None, type=float,
                       help="stop training once val top-1 reaches this "
                            "FRACTION (e.g. 0.75 = 75%% top-1, the README's "
                            "canonical bar); values > 1 are read as percent")
    if variant == "nd":
        p.add_argument("--seed", default=None, type=int,
                       help="seed for initializing training")
        p.add_argument("--gpu", default=None, type=int,
                       help="device id to pin (single-device mode)")
        p.add_argument("--multiprocessing-distributed", action="store_true")
    if variant == "apex":
        p.add_argument("--local_rank", default=None, type=int)
        p.add_argument("--sync-bn", action="store_true",
                       help="cross-replica BatchNorm statistics")
        p.add_argument("--opt-level", type=str, default=None,
                       help="Apex O0-O3; O1+ maps to the bf16 compute policy")
        p.add_argument("--keep-batchnorm-fp32", type=str, default=None)
        p.add_argument("--loss-scale", type=str, default=None,
                       help="accepted for parity; bf16 needs no loss scaling")
        # type=bool quirk preserved: any non-empty value parses truthy,
        # matching the reference flag exactly (imagenet_ddp_apex.py:95)
        p.add_argument("--channels-last", type=bool, default=False,
                       help="no-op: dptpu models are NHWC already")
    return p


def parse_config(argv=None, variant: str = "ddp") -> Config:
    """Parse argv through the variant's reference-parity schema into a Config."""
    ns = build_parser(variant).parse_args(argv)
    fields = {f.name for f in dataclasses.fields(Config)}
    kw = {k: v for k, v in vars(ns).items() if k in fields}
    return Config(variant=variant, **kw)


@dataclasses.dataclass(frozen=True)
class DerivedConfig:
    """Every runtime-derived quantity, computed once and immutably.

    Replaces the reference's in-place args mutation:

    * ``world_size = ngpus_per_node * nnodes``  (imagenet_ddp.py:76-81)
    * ``rank = node_rank * ngpus + gpu``        (imagenet_ddp.py:103)
    * ``batch_size //= ngpus``                  (imagenet_ddp.py:125)
    * ``lr *= global_batch/256`` (apex only)    (imagenet_ddp_apex.py:161-162)

    Not here: ``workers = ceil(workers / ngpus)`` (imagenet_ddp.py:126).
    One process drives all local chips, so a host's feed has ONE pool,
    and ``dptpu.data.feed.pool_size`` sizes it from ``-j``, the local
    chips, the cores and the workers mode. It keeps the reference's
    rule as its floor (``ceil(workers / chips) x chips``, the sum of
    what the per-GPU loaders would spawn) and not the division itself:
    on a host with cores for ``-j`` workers a chip that left four chips
    with the four workers that feed one (PERF.md section 6, PR 40).
    """

    num_processes: int  # hosts (JAX processes), = reference's nnodes
    process_index: int  # this host's index, = node rank
    local_device_count: int  # chips on this host, = ngpus_per_node
    global_device_count: int  # total chips, = reference world_size after rescale
    per_device_batch_size: int
    global_batch_size: int
    per_host_batch_size: int
    scaled_lr: float
    use_bf16: bool
    sync_bn: bool

    @property
    def is_chief(self) -> bool:
        """Single-writer guard, the ``rank % ngpus_per_node == 0`` /
        rank-0 analog (imagenet_ddp.py:215; imagenet_ddp_apex.py:268)."""
        return self.process_index == 0


def derive(cfg: Config, *, local_device_count: int,
           num_processes: int = 1, process_index: int = 0) -> DerivedConfig:
    """Compute the DerivedConfig for this host.

    Batch semantics per variant (the reference's own split):
      * ddp/nd: ``-b`` is the total batch for all local devices
        (imagenet_ddp.py:37-41) → per-device = b // local_devices.
      * apex: ``-b`` is already per-device (imagenet_ddp_apex.py:63-67).
    """
    n_local = local_device_count
    global_devices = n_local * num_processes
    if cfg.variant == "apex":
        per_device = cfg.batch_size
    else:
        per_device = max(1, cfg.batch_size // n_local)
    global_batch = per_device * global_devices

    use_bf16 = cfg.variant == "apex" and (cfg.opt_level or "O2") != "O0"
    scaled_lr = cfg.lr
    if cfg.variant == "apex":
        scaled_lr = cfg.lr * float(global_batch) / 256.0

    # NOTE: the reference's ``args.distributed`` switch (DDP vs
    # DataParallel vs single device, nd_imagenet.py:101,140-169) has no
    # derived field here BY DESIGN: the rendezvous decision lives in
    # ``initialize_distributed`` (which reads the config directly, before
    # jax process info exists) and the placement ladder collapses into
    # "mesh over however many devices there are".
    return DerivedConfig(
        num_processes=num_processes,
        process_index=process_index,
        local_device_count=n_local,
        global_device_count=global_devices,
        per_device_batch_size=per_device,
        global_batch_size=global_batch,
        per_host_batch_size=per_device * n_local,
        scaled_lr=scaled_lr,
        use_bf16=use_bf16,
        sync_bn=cfg.sync_bn,
    )
