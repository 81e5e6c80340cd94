"""Host provenance — the fingerprint every committed artifact carries.

Moved here from scripts/bench_util.py (which re-exports it) so the
static-analysis report (ANALYSIS.json, dptpu/analysis/report.py) can
stamp itself the way every bench artifact does without importing the
scripts tree: ROADMAP's standing caveat — "every number since r6 is
from a throttled 2-core host" — stays a machine-readable field, and
automated comparisons can refuse to diff artifacts from different host
classes.
"""

from __future__ import annotations

import os
import platform
import sys


def host_provenance() -> dict:
    """The host fingerprint every committed artifact carries: CPU
    budget, platform triple, interpreter and jax/XLA versions. Cheap,
    pure, and safe to call before OR after jax initializes a backend.
    The jax version is read from ``sys.modules`` WITHOUT importing jax:
    a lint-only ``dptpu check --no-hlo`` run (or a spawned data worker)
    must stay genuinely jax-free — every caller that benches jax code
    has already imported it. A process that never loaded jax (a feed
    bench: ``dptpu.data`` imports none since PR 31) stamps the
    INSTALLED version, read from the package's metadata, which imports
    nothing either (``None`` = no jax installed)."""
    jax_version = getattr(sys.modules.get("jax"), "__version__", None)
    if jax_version is None:
        from importlib import metadata

        try:
            jax_version = metadata.version("jax")
        except metadata.PackageNotFoundError:
            pass
    affinity = None
    if hasattr(os, "sched_getaffinity"):
        try:
            affinity = len(os.sched_getaffinity(0))
        except OSError:
            affinity = None
    return {
        "cpu_count": os.cpu_count(),
        "cpu_affinity": affinity,
        "platform": platform.platform(),
        "machine": platform.machine(),
        "python": sys.version.split()[0],
        "jax": jax_version,
    }


def device_summary() -> dict:
    """Where this process's jax runs, as jax reports it — the ``device``
    object of every chip result and the one-line banner ``fit()`` and
    ``dptpu serve`` print, so a silent CPU fallback (jax with libtpu
    installed drops to CPU with only a warning when it finds no chip)
    is visible in every log. Initializes the backend."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def device_banner() -> str:
    d = device_summary()
    return (f"=> devices: platform={d['platform']} "
            f"device_kind={d['kind']} count={d['count']}")
