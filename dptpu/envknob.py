"""Fail-fast environment-knob parsing, shared by every layer.

The locked knob contract (SURVEY §7 / PR 1): an UNSET or empty knob means
"use the default", but every EXPLICIT value must parse or raise an
actionable error — a typo'd knob must never silently fall back. One
implementation serves the trainer (``dptpu/train``), the data
pipeline's supervision knobs (``dptpu/data/shm.py``) and the fault
harness (``dptpu/resilience/faults.py``); this module is imported inside
spawned data workers, so it stays stdlib-only — never JAX.
"""

from __future__ import annotations

import os
from typing import Optional


def env_int(name: str, default: Optional[int] = None,
            environ=None) -> Optional[int]:
    """Integer env knob; unset/empty → ``default`` (pass None so callers
    can tell an explicit 0 from absence), junk → actionable error."""
    raw = (environ if environ is not None else os.environ).get(
        name, "").strip()
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        raise ValueError(
            f"{name}={raw!r} is not an integer (expected e.g. {name}=2)"
        ) from None


def env_axis(name: str, what: str, environ=None) -> int:
    """Parallelism-axis env knob (``DPTPU_TP``, ``DPTPU_SP``): unset → 0
    (off); any explicit value ≤ 0 raises — 0 gets the same fail-fast
    treatment as negatives (every explicit value produces feedback; =1
    gets a no-op notice where the knob is read)."""
    n = env_int(name, None, environ)
    if n is not None and n <= 0:
        raise ValueError(
            f"{name}={n} must be a positive {what} (e.g. {name}=2)"
        )
    return n or 0


def env_float(name: str, default: Optional[float] = None,
              environ=None) -> Optional[float]:
    """Float env knob; unset/empty → ``default``, junk → actionable error."""
    raw = (environ if environ is not None else os.environ).get(
        name, "").strip()
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        raise ValueError(
            f"{name}={raw!r} is not a number (expected e.g. {name}=2.5)"
        ) from None


def env_choice(name: str, choices, default: Optional[str] = None,
               environ=None) -> Optional[str]:
    """Enumerated env knob; unset/empty → ``default``, any explicit value
    must be one of ``choices`` or the knob raises with the accepted set."""
    raw = (environ if environ is not None else os.environ).get(
        name, "").strip()
    if not raw:
        return default
    if raw not in choices:
        raise ValueError(
            f"{name}={raw!r} must be one of "
            + "/".join(repr(c) for c in choices)
        )
    return raw


def env_str(name: str, default: Optional[str] = None,
            environ=None) -> Optional[str]:
    """String env knob (paths, specs, sentinels); unset/empty →
    ``default``. Any explicit value is legal — the helper exists so
    free-form knobs still flow through ONE read point (the knob-contract
    lint, dptpu/analysis, flags raw ``os.environ`` reads) and so their
    names land in the declared registry + README like every other knob."""
    raw = (environ if environ is not None else os.environ).get(
        name, "").strip()
    return raw if raw else default


_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


def env_bool(name: str, default: Optional[bool] = None,
             environ=None) -> Optional[bool]:
    """Boolean env knob; unset/empty → ``default``, anything outside the
    1/0/true/false/yes/no/on/off vocabulary → actionable error (the same
    fail-fast contract as the numeric knobs — a typo'd 'flase' must not
    silently mean anything)."""
    raw = (environ if environ is not None else os.environ).get(
        name, "").strip().lower()
    if not raw:
        return default
    if raw in _TRUE:
        return True
    if raw in _FALSE:
        return False
    raise ValueError(
        f"{name}={raw!r} is not a boolean (expected e.g. {name}=1 or "
        f"{name}=0)"
    )
