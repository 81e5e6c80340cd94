"""Config parity tests: the reference's published commands must parse.

Checks that the exact CLI invocations from the reference README
(/root/reference/README.md:64-99) and each variant's defaults
(imagenet_ddp.py:23-67; imagenet_ddp_apex.py:42-98; nd_imagenet.py:26-76)
round-trip through dptpu's parsers, and that derived values reproduce the
reference's in-place rescaling math.
"""

import pytest

from dptpu.config import Config, build_parser, derive, parse_config


def test_readme_ddp_command_parses():
    # README.md:74-99 canonical 4-node launch (node 0 shown)
    argv = (
        "-a resnet50 --dist-url tcp://192.168.0.1:8888 --world-size 4 "
        "--rank 0 --desired-acc 0.75 /data/imagenet".split()
    )
    cfg = parse_config(argv, "ddp")
    assert cfg.arch == "resnet50"
    assert cfg.world_size == 4 and cfg.rank == 0
    assert cfg.desired_acc == 0.75
    assert cfg.data == "/data/imagenet"
    # untouched defaults
    assert cfg.batch_size == 1024 and cfg.lr == 0.1
    assert cfg.momentum == 0.9 and cfg.weight_decay == 1e-4
    assert cfg.epochs == 90 and cfg.print_freq == 10


def test_variant_defaults():
    assert parse_config(["d"], "ddp").arch == "resnet50"
    assert parse_config(["d"], "ddp").batch_size == 1024
    assert parse_config(["d"], "nd").arch == "resnet18"
    assert parse_config(["d"], "nd").batch_size == 256
    assert parse_config(["d"], "apex").batch_size == 224


def test_flag_aliases_and_dests():
    cfg = parse_config(
        ["--learning-rate", "0.4", "--weight-decay", "2e-4", "-p", "50", "d"],
        "ddp",
    )
    assert cfg.lr == 0.4 and cfg.weight_decay == 2e-4 and cfg.print_freq == 50


def test_cuda_specific_flags_accepted_not_fatal():
    # --dist-backend nccl and apex opt flags must be accepted and mapped
    cfg = parse_config(["--dist-backend", "nccl", "d"], "ddp")
    assert cfg.dist_backend == "nccl"
    cfg = parse_config(
        ["--opt-level", "O2", "--loss-scale", "128.0",
         "--keep-batchnorm-fp32", "True", "d"],
        "apex",
    )
    assert cfg.opt_level == "O2" and cfg.loss_scale == "128.0"


def test_nd_extras():
    cfg = parse_config(
        ["--seed", "1", "--gpu", "0", "--multiprocessing-distributed", "d"],
        "nd",
    )
    assert cfg.seed == 1 and cfg.gpu == 0 and cfg.multiprocessing_distributed


def test_unknown_arch_rejected():
    with pytest.raises(SystemExit):
        build_parser("ddp").parse_args(["-a", "nosuchnet", "d"])


def test_derive_ddp_batch_split():
    # imagenet_ddp.py:125-126: total per-node batch split across local devices
    cfg = Config(data="d", batch_size=1024, workers=4)
    d = derive(cfg, local_device_count=4, num_processes=4, process_index=1)
    assert d.per_device_batch_size == 256
    assert d.per_host_batch_size == 1024
    assert d.global_device_count == 16
    assert d.global_batch_size == 4096
    # imagenet_ddp.py:126's ceil(workers / ngpus) a chip is the FLOOR of
    # the host's one pool, which the feed sizes (dptpu.data.feed)
    from dptpu.data.feed import pool_size

    assert pool_size(cfg.workers, d.local_device_count, 2, "thread") == 4
    assert pool_size(cfg.workers, d.local_device_count, 30, "process") == 16
    assert not d.is_chief


def test_derive_apex_per_device_batch_and_lr_scaling():
    # imagenet_ddp_apex.py:63-67 (per-GPU batch) + :161-162 (linear LR rule)
    cfg = Config(data="d", batch_size=224, lr=0.1, variant="apex")
    d = derive(cfg, local_device_count=4, num_processes=4)
    assert d.per_device_batch_size == 224
    assert d.global_batch_size == 224 * 16
    assert d.scaled_lr == pytest.approx(0.1 * 224 * 16 / 256.0)
    assert d.use_bf16  # default opt level O2 → bf16 policy


def test_derive_apex_o0_disables_bf16():
    cfg = Config(data="d", variant="apex", opt_level="O0")
    assert not derive(cfg, local_device_count=1).use_bf16


def test_derive_single_device():
    cfg = Config(data="d", batch_size=256)
    d = derive(cfg, local_device_count=1)
    assert d.per_device_batch_size == 256
    assert d.global_batch_size == 256
    assert d.is_chief
