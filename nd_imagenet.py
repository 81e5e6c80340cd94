#!/usr/bin/env python3
"""Single-device / fallback-everything ImageNet training on TPU — the
``nd_imagenet.py`` entry point (reference: /root/reference/nd_imagenet.py),
CLI-compatible.

The reference's 5-way device-placement ladder (CPU → pinned GPU → DDP →
DataParallel, nd_imagenet.py:140-169) collapses on TPU: ``--gpu N`` pins one
local chip, otherwise all visible devices join a mesh. The same program
runs on the CPU backend (the tests do) — jax drops there with only a
warning when it finds no chip, so every run prints one ``=> devices:``
line saying where it is. ``--seed`` gives end-to-end
reproducibility (XLA is deterministic by default — no cudnn.deterministic
trade-off, nd_imagenet.py:84-92).
"""

from dptpu.cli import main_nd

if __name__ == "__main__":
    main_nd()
