"""Two-level (ICI × DCN) hierarchical gradient reduction.

On a multi-slice TPU pod the DCN hop between slices is an order of
magnitude slower than the ICI links inside a slice, but a flat
data-parallel all-reduce treats every link as equal: each chip moves
``2·(N-1)/N·P`` gradient bytes through a ring that crosses DCN at full
gradient width. Every ImageNet-in-minutes system reduces
hierarchically instead (Mikami et al., arXiv:1811.05233 — the 2D-Torus
reduce-scatter-first scheme; Yamazaki et al., arXiv:1903.12650 adds
reduced-precision exchange on the slow hop):

1. **reduce-scatter inside the slice (ICI)** — each of the ``I`` chips
   in a slice ends up with the slice-local sum of one ``1/I`` shard;
2. **all-reduce across slices (DCN)** — shard-sized: per-chip DCN
   traffic drops to ``~1/I`` of the flat all-reduce;
3. **all-gather inside the slice (ICI)** — every chip recovers the
   full globally-reduced gradient.

ICI bytes stay at the flat all-reduce's volume (the reduce-scatter +
all-gather pair IS a decomposed all-reduce); only the slow hop shrinks.
The engine is expressed with EXPLICIT collectives in the shard_map step
bodies (``check_vma=False``, the repo-wide discipline), over the
``{slice: S, data: N/S}`` mesh ``make_hierarchical_mesh`` builds.

**bf16 DCN compression** (``DPTPU_DCN_DTYPE=bf16``, opt-in; default
fp32): the shard is rounded to bf16 ONCE, all-gathered across slices,
and the ``S`` partials are summed locally in fp32 — bf16 on the wire,
fp32 accumulation (a bf16 ``psum`` would accumulate in bf16 on the
wire's reduction tree, compounding rounding with S). Gather-based
compression halves DCN bytes at S=2 and breaks even with the fp32
all-reduce at S=4 (``(S-1)·P/(2I)`` vs ``2·(S-1)/S·P/I``) — the
realistic multi-slice regime for this engine is 2-4 slices, and
COMMBENCH records the crossover. Only scatterable (shard-sized) leaves
compress; the replicated remainder (tiny biases, a rounding error of
the bytes) always reduces in fp32.

**Numerics / parity contract** (locked by tests/test_hierarchy.py and
the COMMBENCH parity gates): each hop is bit-identical to the flat
all-reduce in isolation — a pure-ICI mesh (1 slice) and a pure-DCN mesh
(chips/slice = 1) both produce params Δ=0 against the flat DDP step
over ≥5 fp32 steps, because XLA's all-reduce, reduce-scatter and the
slice-axis psum all sum linearly from rank 0. The COMPOSED two-level
reduction regroups the sum as (slice-0 partial) + (slice-1 partial) + …
where the flat all-reduce folds ranks in one linear chain, so composed
parity is exact-to-grouping: ≤1 ulp per addition, measured and bounded
(never hidden) in COMMBENCH. bf16-DCN drift is bounded separately.

ZeRO-1 composes for free (``dptpu/parallel/zero.py``): params/optimizer
state shard over the INTRA-slice axis, so the per-microbatch weight
all-gather stays on ICI, the all-gather VJP's psum_scatter IS hop 1,
and hop 2 runs once per UPDATE on the shard-sized gradient — the
reduce-scatter output is exactly the 1/I update shard, and the
all-gather moves weights, never gradients.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from dptpu.parallel.mesh import (
    DATA_AXIS,
    SLICE_AXIS,
    largest_divisible_dim,
)

DCN_DTYPES = ("fp32", "bf16", "bf16_a2a")


def hierarchy_knobs(cfg=None) -> tuple:
    """``(slices, dcn_dtype)`` under the locked fail-fast knob contract.

    * ``DPTPU_SLICES`` / ``--slices`` — number of DCN-connected slices
      the data axis factors into; the env twin OVERRIDES the CLI/config
      field (the repo-wide precedence). Must be >= 1 (1 = the flat
      single-level mesh) and must divide the world size (checked where
      the device count is known: ``make_hierarchical_mesh``).
    * ``DPTPU_DCN_DTYPE`` — ``fp32`` (default: the DCN all-reduce runs
      at full precision), ``bf16`` (gather-based compression of the
      cross-slice hop, fp32 accumulation; see module docstring), or
      ``bf16_a2a`` (all-to-all + local-accumulate: per-chip DCN bytes
      ~half the fp32 all-reduce at ANY slice count — gather-bf16's
      ``(S-1)·m`` receive volume loses to the fp32 all-reduce past S=4,
      the documented ceiling this mode breaks — at the cost of a second
      bf16 rounding on the reduced sum; see ``dcn_reduce_shard``).
    """
    from dptpu.envknob import env_choice, env_int

    slices = env_int("DPTPU_SLICES", None)
    if slices is None:
        slices = getattr(cfg, "slices", 1) if cfg is not None else 1
    if slices < 1:
        raise ValueError(
            f"DPTPU_SLICES/--slices {slices} must be >= 1 (1 keeps the "
            f"flat single-level data mesh)"
        )
    dcn_dtype = env_choice("DPTPU_DCN_DTYPE", DCN_DTYPES, default="fp32")
    return int(slices), dcn_dtype


def elastic_slices_check(world_size: int, slices: int):
    """Elastic-resume × ``--slices`` composition (ROADMAP item 3,
    elastic satellite): a SHRUNK world must still factor into the
    configured slice count, or the hierarchical mesh cannot build. The
    generic ``make_hierarchical_mesh`` divisibility error names only
    the mismatch; an elastic restart deserves the two actionable
    fallbacks, so this check runs FIRST on the elastic path and its
    message is locked by tests (tests/test_elastic.py).
    """
    if slices > 1 and world_size % slices != 0:
        divisors = [s for s in range(2, world_size + 1)
                    if world_size % s == 0]
        example = f"DPTPU_SLICES={divisors[0]}" if divisors \
            else "no slice count > 1 divides it"
        raise ValueError(
            f"elastic resume: the shrunk world of {world_size} devices "
            f"does not divide into DPTPU_SLICES/--slices={slices} "
            f"slices, so the hierarchical mesh cannot factor. Fix one "
            f"knob: drop slices (unset DPTPU_SLICES to run the flat "
            f"single-level data mesh) or pick a slice count that "
            f"divides {world_size} (e.g. {example})."
        )


def is_hierarchical(mesh: Optional[Mesh]) -> bool:
    return mesh is not None and SLICE_AXIS in mesh.axis_names


def _scatter_dim(shape, n: int) -> int:
    """The scatter dim for one gradient leaf: the SHARED
    ``mesh.largest_divisible_dim`` rule ZeRO-1 shards state by
    (``zero._leaf_spec`` resolves through the same function), so the
    gradient shard the reduce-scatter produces here is exactly the
    update shard ZeRO-1 owns — by construction, not by parallel
    maintenance. -1 when no dim divides (the leaf reduces unscattered).
    """
    return largest_divisible_dim(shape, n)


def dcn_reduce_shard(x, slices_axis: str = SLICE_AXIS,
                     dcn_dtype: str = "fp32", slices: Optional[int] = None):
    """The cross-slice (DCN) hop for one already-scattered shard.

    fp32: a plain shard-sized ``psum`` over the slice axis. bf16: round
    the shard to bf16 once, all-gather the S partials (bf16 on the
    wire — gather moves data without arithmetic, so no backend promotes
    it), and sum them locally in fp32, slice-major — fp32 accumulation
    with a deterministic order. Non-float32 shards (none in practice:
    grads follow the f32 params) pass through the fp32 path.

    bf16_a2a (arXiv:1903.12650's reduced-precision exchange married to
    a scatter-reduce): the shard flattens, pads to a multiple of S and
    splits into S chunks; one bf16 **all-to-all** gives each slice the
    S partials of ITS chunk, which it sums locally in fp32 (slice-major,
    deterministic), then a chunk-sized bf16 all-gather redistributes the
    reduced chunks. Per-chip DCN receive bytes are ``2·(S-1)/S·m`` bf16
    ≈ HALF the fp32 all-reduce's ``2·(S-1)/S·m`` fp32 at ANY S — unlike
    gather-bf16, whose ``(S-1)·m`` receive volume crosses the fp32
    all-reduce at S=4 (the ceiling this mode breaks). The price is a
    SECOND rounding: the fp32-accumulated chunk sum rounds to bf16 for
    the gather hop, where gather-bf16 rounds only the inputs. Needs the
    concrete slice count (``slices`` — a reshape extent; callers read it
    off the mesh) because axis sizes are not Python ints under tracing.
    """
    if dcn_dtype == "bf16" and x.dtype == jnp.float32:
        parts = lax.all_gather(
            x.astype(jnp.bfloat16), slices_axis, axis=0, tiled=False
        )
        return jnp.sum(parts.astype(jnp.float32), axis=0)
    if dcn_dtype == "bf16_a2a" and x.dtype == jnp.float32:
        if not slices or slices < 1:
            raise ValueError(
                "dcn_dtype='bf16_a2a' needs the concrete slice count: "
                "pass slices=int(mesh.shape['slice']) (the chunk split "
                "is a reshape, and axis sizes are traced values inside "
                "shard_map)"
            )
        if slices == 1:
            return x  # single slice: the DCN hop is the identity
        shape = x.shape
        flat = x.reshape(-1)
        m = flat.shape[0]
        pad = (-m) % slices
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad,), flat.dtype)]
            )
        chunks = flat.reshape(slices, -1).astype(jnp.bfloat16)
        # chunk j of every slice travels to slice j: row k of the result
        # is slice k's partial of MY chunk
        parts = lax.all_to_all(
            chunks, slices_axis, split_axis=0, concat_axis=0, tiled=True
        ).reshape(slices, -1)
        mine = jnp.sum(parts.astype(jnp.float32), axis=0)
        # second rounding: the reduced chunk goes back over DCN in bf16
        full = lax.all_gather(
            mine.astype(jnp.bfloat16), slices_axis, axis=0, tiled=False
        ).astype(jnp.float32).reshape(-1)
        if pad:
            full = full[:m]
        return full.reshape(shape)
    return lax.psum(x, slices_axis)


def make_hierarchical_reduce(mesh: Mesh, dcn_dtype: str = "fp32"):
    """Build the DDP gradient-reduction hook for a hierarchical mesh:
    per leaf, reduce-scatter(ICI) → shard-sized all-reduce(DCN) →
    all-gather(ICI). Leaves with no dim the intra-slice width divides
    (tiny biases) psum over ICI and take the fp32 DCN hop whole —
    correct, and a rounding error of the bytes.

    Used by ``make_train_step``; ZeRO-1 does NOT use this — its
    all-gather VJP already delivers the intra-slice reduce-scatter, so
    it applies only ``dcn_reduce_shard`` (see make_zero1_train_step).
    """
    if dcn_dtype not in DCN_DTYPES:
        raise ValueError(
            f"DPTPU_DCN_DTYPE={dcn_dtype!r} must be one of "
            + "/".join(repr(d) for d in DCN_DTYPES)
        )
    n_in = int(mesh.shape[DATA_AXIS])
    n_slices = int(mesh.shape[SLICE_AXIS])

    def reduce_grads(grads):
        def red(g):
            d = _scatter_dim(getattr(g, "shape", ()), n_in)
            if d < 0:
                # unscatterable remainder: ICI psum + fp32 DCN psum
                return lax.psum(lax.psum(g, DATA_AXIS), SLICE_AXIS)
            sh = lax.psum_scatter(
                g, DATA_AXIS, scatter_dimension=d, tiled=True
            )
            sh = dcn_reduce_shard(sh, SLICE_AXIS, dcn_dtype,
                                  slices=n_slices)
            return lax.all_gather(sh, DATA_AXIS, axis=d, tiled=True)

        return jax.tree_util.tree_map(red, grads)

    return reduce_grads


def flat_replica_index(axis_names) -> jax.Array:
    """This shard's GLOBAL data-parallel replica id, flattened over the
    (possibly hierarchical) data axes in major-to-minor order — on a
    ``{slice, data}`` mesh, ``slice_idx · I + idx_in_slice``, which
    equals the flat mesh's ``axis_index("data")`` for the same chip (the
    slice-major batch layout), so dropout streams are geometry-stable.
    Uses the portable ``psum(1)`` axis-size spelling (``lax.axis_size``
    is missing in this container's jax — ROADMAP known constraint)."""
    idx = None
    for name in axis_names:
        i = lax.axis_index(name)
        idx = i if idx is None else idx * lax.psum(1, name) + i
    return idx
