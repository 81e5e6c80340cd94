"""The repo-invariant lint rules (registered into dptpu.analysis.lint).

Each rule machine-checks one contract the repo previously enforced only
by convention and by whichever test happened to exercise it:

* ``knob-contract`` — every ``DPTPU_*`` read flows through
  dptpu/envknob.py (fail-fast: a typo'd value raises, never silently
  falls back) or names a declared registry entry
  (dptpu/analysis/knobs.py), and every non-internal registry knob is
  documented in README.
* ``determinism`` — no wall-clock, unseeded RNG, ``os.urandom`` or
  set-iteration-ordering hazards inside the ``(seed, epoch, index)``
  bit-identity surfaces (dptpu/data/, dptpu/resilience/).
* ``host-sync`` — no device→host syncs (``.item()``, ``float(arr)``,
  ``np.asarray``/``np.array``, ``jax.device_get``,
  ``block_until_ready``) inside the hot-loop files' step bodies and the
  DevicePrefetcher.
* ``shm-hygiene`` — every /dev/shm segment creation goes through
  ``create_named_segment`` with a prefix the conftest leak-guard census
  knows, so an abandoned segment is attributable and policed.
* ``shard-map`` — step bodies go through ``shard_map_nocheck``
  (collectives placed EXPLICITLY under ``check_vma=False``) and thread
  ``axis_names`` through ``train_step_body`` so the hierarchical mesh
  cannot be silently dropped.

Stdlib-only, like the engine.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator, Optional, Tuple

from dptpu.analysis.lint import FileContext, register

_KNOB_RE = re.compile(r"^DPTPU_[A-Z0-9_]+$")

# the /dev/shm attribution prefixes the tests/conftest.py leak-guard
# census polices (dptpu_{kind}_{pid}_{hex}) — a new kind must be added
# BOTH there and here, which is the point: the census can't drift
SHM_CENSUS_PREFIXES = ("dptpu_ring", "dptpu_cache", "dptpu_serve",
                      "dptpu_shard")

# the bit-identity surfaces: everything the (seed, epoch, index) replay
# contract flows through
_DETERMINISM_DIRS = ("dptpu/data/", "dptpu/resilience/")

# the hot-path files the host-sync rule guards
_HOT_FILES = ("dptpu/train/loop.py", "dptpu/train/step.py",
              "dptpu/data/loader.py")


def _qualname(node: ast.AST) -> Optional[str]:
    """Dotted name for a Name/Attribute chain (``np.random.randint``)."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _qualname(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def _in_package(relpath: str) -> bool:
    return relpath.startswith(("dptpu/", "scripts/"))


# ------------------------------------------------------------ knob-contract


def _knob_scope(relpath: str) -> bool:
    # envknob.py IS the sanctioned read point
    return _in_package(relpath) and relpath != "dptpu/envknob.py"


@register(
    "knob-contract", _knob_scope,
    "DPTPU_* knobs: reads go through dptpu/envknob helpers (fail-fast, "
    "no silent fallback), names are declared in the registry "
    "(dptpu/analysis/knobs.py), and non-internal knobs are documented "
    "in README",
)
def knob_contract(ctx: FileContext) -> Iterator[Tuple[int, str]]:
    reg = ctx.repo.knobs
    if reg is None:
        from dptpu.analysis.knobs import KNOB_REGISTRY as reg  # noqa: N811
    for node in ast.walk(ctx.tree):
        # raw read with silent fallback: environ.get("DPTPU_X"[, default]),
        # os.getenv("DPTPU_X"[, default]), environ.setdefault(...)
        if isinstance(node, ast.Call):
            f = node.func
            q = _qualname(f) or ""
            raw_read = False
            if (isinstance(f, ast.Attribute)
                    and f.attr in ("get", "setdefault") and node.args):
                recv = _qualname(f.value) or ""
                raw_read = (recv.endswith("environ")
                            or recv in ("env", "environ"))
            elif q in ("os.getenv", "getenv") and node.args:
                raw_read = True
            if raw_read:
                knob = ctx.resolve_str(node.args[0])
                if knob and _KNOB_RE.match(knob):
                    yield node.lineno, (
                        f"raw environ read of {knob} bypasses the "
                        f"fail-fast knob contract — use the "
                        f"dptpu.envknob helper for its kind "
                        f"(env_int/env_float/env_bool/env_choice/"
                        f"env_str)"
                    )
        # raw subscript read: environ["DPTPU_X"] (writes/pops are the
        # bench drivers legitimately SETTING knobs for children)
        if (isinstance(node, ast.Subscript)
                and isinstance(node.ctx, ast.Load)):
            recv = _qualname(node.value) or ""
            knob = ctx.resolve_str(node.slice)
            if (knob and _KNOB_RE.match(knob)
                    and (recv.endswith("environ")
                         or recv in ("env", "environ"))):
                yield node.lineno, (
                    f"raw environ[{knob!r}] read bypasses the fail-fast "
                    f"knob contract — use a dptpu.envknob helper"
                )
        # every DPTPU_* literal must be declared (or be a declared-knob
        # prefix scan, e.g. "DPTPU_OBS_")
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            lit = node.value
            if not _KNOB_RE.match(lit):
                continue
            if lit.endswith("_"):
                if not any(k.startswith(lit) for k in reg):
                    yield node.lineno, (
                        f"knob prefix {lit!r} matches no declared "
                        f"registry knob (dptpu/analysis/knobs.py)"
                    )
            elif lit not in reg:
                yield node.lineno, (
                    f"undeclared knob {lit} — add a registry entry in "
                    f"dptpu/analysis/knobs.py (and README docs unless "
                    f"internal)"
                )
    # registry ↔ README cross-check, anchored at each entry's line in
    # the registry file itself
    if (ctx.relpath == "dptpu/analysis/knobs.py"
            and ctx.repo.readme_text is not None):
        lines = ctx.source.splitlines()
        for name, meta in sorted(reg.items()):
            if meta.get("internal"):
                continue
            # boundary match: DPTPU_SP documented must mean DPTPU_SP
            # itself, not a substring hit inside DPTPU_SP_MODE
            if not re.search(rf"{name}(?![A-Z0-9_])",
                             ctx.repo.readme_text):
                lineno = next(
                    (i for i, text in enumerate(lines, start=1)
                     if name in text), 1,
                )
                yield lineno, (
                    f"declared knob {name} is not documented in "
                    f"README's knob docs — document it (or mark the "
                    f"registry entry internal=True if it is a "
                    f"child-process sentinel)"
                )


# ------------------------------------------------------------- determinism


_SEEDED_NP_CTORS = {"RandomState", "default_rng", "Generator",
                    "SeedSequence", "PCG64", "Philox", "MT19937"}


def _determinism_scope(relpath: str) -> bool:
    return relpath.startswith(_DETERMINISM_DIRS)


@register(
    "determinism", _determinism_scope,
    "no wall-clock (time.time), unseeded random/np.random, os.urandom, "
    "or set-iteration-ordering hazards inside the (seed, epoch, index) "
    "bit-identity surfaces (dptpu/data/, dptpu/resilience/)",
)
def determinism(ctx: FileContext) -> Iterator[Tuple[int, str]]:
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            q = _qualname(node.func) or ""
            if q in ("time.time", "time.time_ns"):
                yield node.lineno, (
                    "wall-clock read in a bit-identity surface — replay "
                    "must not depend on when it runs (derive from "
                    "(seed, epoch, index), or use time.monotonic for "
                    "pure deadlines)"
                )
            elif q == "os.urandom":
                yield node.lineno, (
                    "os.urandom in a bit-identity surface — draw from a "
                    "seeded generator keyed by (seed, epoch, index)"
                )
            elif q in ("random.Random", "random.SystemRandom"):
                if q.endswith("SystemRandom") or not (
                        node.args or node.keywords):
                    yield node.lineno, (
                        f"{q}() without a seed in a bit-identity "
                        f"surface — seed it from (seed, epoch, index)"
                    )
            elif q.startswith("random.") and q[7:8].islower():
                yield node.lineno, (
                    f"{q}() draws from the process-global unseeded RNG "
                    f"— use a random.Random(seed) instance keyed by "
                    f"(seed, epoch, index)"
                )
            elif (q.startswith(("np.random.", "numpy.random."))
                  and q.rsplit(".", 1)[-1] not in _SEEDED_NP_CTORS):
                yield node.lineno, (
                    f"{q}() uses numpy's global RNG — use an explicit "
                    f"np.random.Generator/RandomState seeded from "
                    f"(seed, epoch, index)"
                )
            elif (q.startswith(("np.random.", "numpy.random."))
                  and q.rsplit(".", 1)[-1] in _SEEDED_NP_CTORS
                  and not (node.args or node.keywords)):
                yield node.lineno, (
                    f"{q}() without a seed is entropy-seeded — pass a "
                    f"seed derived from (seed, epoch, index)"
                )
        iters = []
        if isinstance(node, ast.For):
            iters = [node.iter]
        elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                               ast.GeneratorExp)):
            iters = [g.iter for g in node.generators]
        for it in iters:
            is_set = isinstance(it, (ast.Set, ast.SetComp)) or (
                isinstance(it, ast.Call)
                and isinstance(it.func, ast.Name)
                and it.func.id in ("set", "frozenset")
            )
            if is_set:
                yield it.lineno, (
                    "iterating a set in a bit-identity surface — set "
                    "order depends on PYTHONHASHSEED across processes; "
                    "iterate sorted(...) instead"
                )


# --------------------------------------------------------------- host-sync


def _host_sync_scope(relpath: str) -> bool:
    return relpath in _HOT_FILES


@register(
    "host-sync", _host_sync_scope,
    "no device→host syncs (.item(), float(arr), np.asarray/np.array, "
    "jax.device_get, block_until_ready) in the hot-loop files' step "
    "bodies and DevicePrefetcher — a sync drains the dispatch queue "
    "and stalls the chip",
)
def host_sync(ctx: FileContext) -> Iterator[Tuple[int, str]]:
    # loader.py is scanned only inside DevicePrefetcher (the loader's
    # worker plumbing is host-side by definition); float()/np.*array
    # are additionally skipped in loop.py, whose floats convert
    # already-fetched host scalars — there the device_get sites ARE the
    # sync points this rule polices.
    in_loader = ctx.relpath == "dptpu/data/loader.py"
    flag_float = ctx.relpath != "dptpu/train/loop.py"
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        if in_loader and "DevicePrefetcher" not in ctx.enclosing_functions(
                node):
            continue
        q = _qualname(node.func) or ""
        attr = node.func.attr if isinstance(node.func, ast.Attribute) \
            else None
        if q == "jax.device_get":
            yield node.lineno, (
                "jax.device_get blocks the host on the device stream — "
                "buffer device values and fetch once per interval (the "
                "loop.py lagged-fetch pattern)"
            )
        elif attr == "block_until_ready" or q == "jax.block_until_ready":
            yield node.lineno, (
                "block_until_ready drains the dispatch queue — only the "
                "measured bench harnesses may sync the stream"
            )
        elif attr == "item" and not node.args:
            yield node.lineno, (
                ".item() is a per-value device sync (the reference's "
                "per-batch stall, imagenet_ddp.py:267) — keep values on "
                "device and batch the fetch"
            )
        elif flag_float and q in ("np.asarray", "numpy.asarray",
                                  "np.array", "numpy.array"):
            yield node.lineno, (
                f"{q} on a device value copies through the host — keep "
                f"the math in jnp inside compiled code"
            )
        elif flag_float and q == "float" and node.args and not isinstance(
                node.args[0], ast.Constant):
            yield node.lineno, (
                "float(x) forces a device→host sync when x is a device "
                "array — keep scalars on device until the batched fetch"
            )


# ------------------------------------------------------------- shm-hygiene


def _dptpu_only(relpath: str) -> bool:
    return relpath.startswith("dptpu/")


@register(
    "shm-hygiene", _dptpu_only,
    "every /dev/shm segment creation goes through create_named_segment "
    "with a prefix in the conftest leak-guard census "
    f"({', '.join(SHM_CENSUS_PREFIXES)})",
)
def shm_hygiene(ctx: FileContext) -> Iterator[Tuple[int, str]]:
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        q = _qualname(node.func) or ""
        if q.rsplit(".", 1)[-1] == "SharedMemory":
            creating = any(
                kw.arg == "create"
                and isinstance(kw.value, ast.Constant)
                and kw.value.value is True
                for kw in node.keywords
            )
            blessed = "create_named_segment" in ctx.enclosing_functions(
                node)
            if creating and not blessed:
                yield node.lineno, (
                    "direct SharedMemory(create=True) — allocate through "
                    "dptpu.data.shm_cache.create_named_segment so the "
                    "segment gets a census-attributable dptpu_* name "
                    "the conftest leak guard can police"
                )
        elif q.rsplit(".", 1)[-1] == "create_named_segment":
            prefix_node = node.args[0] if node.args else next(
                (kw.value for kw in node.keywords
                 if kw.arg == "prefix"), None,
            )
            prefix = ctx.resolve_str(prefix_node) \
                if prefix_node is not None else None
            if prefix is None:
                yield node.lineno, (
                    "create_named_segment prefix is not statically "
                    "resolvable — the leak-guard census cannot "
                    "attribute the segment kind"
                )
            elif not prefix.startswith(SHM_CENSUS_PREFIXES):
                yield node.lineno, (
                    f"segment prefix {prefix!r} is outside the conftest "
                    f"leak-guard census ({', '.join(SHM_CENSUS_PREFIXES)}"
                    f") — add the kind to BOTH the census and "
                    f"dptpu/analysis/rules.py"
                )


# --------------------------------------------------------------- shard-map


@register(
    "shard-map", _dptpu_only,
    "step bodies go through shard_map_nocheck (explicit collectives "
    "under check_vma=False) and thread axis_names through "
    "train_step_body",
)
def shard_map_discipline(ctx: FileContext) -> Iterator[Tuple[int, str]]:
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        q = (_qualname(node.func) or "").rsplit(".", 1)[-1]
        if q == "shard_map":
            if "shard_map_nocheck" not in ctx.enclosing_functions(node):
                yield node.lineno, (
                    "raw shard_map call — go through "
                    "dptpu.train.step.shard_map_nocheck: the steps "
                    "place their collectives explicitly, and with the "
                    "checker on its implicit psum would reduce the "
                    "gradient a second time — check_vma=False"
                )
        elif q == "train_step_body":
            if not any(kw.arg == "axis_names" for kw in node.keywords):
                yield node.lineno, (
                    "train_step_body called without axis_names — the "
                    "hierarchical {slice, data} mesh depends on the "
                    "axes being threaded through the step body"
                )
