"""Shared bench-artifact helpers.

``host_provenance()`` is stamped into EVERY committed bench artifact
(``run_*bench.py`` all call it): ROADMAP's standing caveat — "every
number since r6 is from a throttled 2-core host" — becomes a
machine-readable field instead of prose, so a future reader (or a
re-run on a real TPU box) can tell at a glance which hardware produced
which number, and automated comparisons can refuse to diff artifacts
from different host classes. The implementation now lives in
``dptpu.utils.provenance`` (ANALYSIS.json stamps itself the same way);
this re-export keeps every ``run_*bench.py`` import working.
"""

from __future__ import annotations

import os

from dptpu.utils.provenance import host_provenance  # noqa: F401


def make_jpeg_imagefolder(root: str, n_images: int, n_classes: int = 2,
                          px=(96, 80), low=(12, 10),
                          quality: int = 85) -> None:
    """Synthetic JPEG ImageFolder split (class dirs directly under
    ``root``): low-res noise upscaled so files have realistic JPEG
    structure; deterministic per class. Shared by run_databench and
    run_faultbench — keep ``px`` under ``out_size * 8/7`` when an arm
    needs the native scale picker pinned at 8/8 (the cache-arm
    bit-exactness discipline; faultbench passes (52, 44) for 48 px)."""
    import numpy as np
    from PIL import Image

    per = max(1, n_images // n_classes)
    for c in range(n_classes):
        d = os.path.join(root, f"class{c}")
        os.makedirs(d, exist_ok=True)
        rng = np.random.RandomState(c)
        for i in range(per):
            noise = rng.randint(0, 255, (low[1], low[0], 3), np.uint8)
            img = Image.fromarray(noise).resize(px, Image.BILINEAR)
            img.save(os.path.join(d, f"{i}.jpg"), quality=quality)


def ensure_cpu_pool(n: int, child_env: str):
    """Re-exec into a child with an n-device virtual CPU pool unless
    this process already sees n devices — the shared bootstrap for the
    multi-chip benches (scalebench/commbench/racebench). Asking for the
    device count initializes this process's backend (on a chip machine
    the parent then holds the chip); the child is held to the CPU
    platform by ``JAX_PLATFORMS=cpu`` and never asks for the chip.
    ``child_env`` is the bench's registered re-entry sentinel
    (dptpu/analysis/knobs.py); the child VERIFIES the pool instead of
    trusting the env vars."""
    import subprocess
    import sys

    import __graft_entry__ as ge

    import jax

    from dptpu.envknob import env_str

    if env_str(child_env):
        if jax.device_count() < n:
            raise RuntimeError(
                f"re-exec'd child still sees {jax.device_count()} "
                f"device(s) on {jax.default_backend()}, need {n} — "
                "JAX_PLATFORMS/XLA_FLAGS did not take effect"
            )
        return
    if jax.device_count() >= n:
        return
    env = dict(os.environ)
    env[child_env] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ge._with_device_count_flag(
        env.get("XLA_FLAGS", ""), n
    )
    rc = subprocess.run([sys.executable] + sys.argv, env=env).returncode
    sys.exit(rc)
