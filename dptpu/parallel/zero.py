"""ZeRO-1 / cross-replica weight-update sharding over the data axis.

The reference replicates optimizer state per process (SGD per rank,
imagenet_ddp.py:133-135; SURVEY.md §2c lists sharded optimizers as an
optional later optimization). On TPU the classic upgrade — Xu et al.'s
weight-update sharding, the PAPERS.md retrieval — falls out of the same
``shard_map`` step dptpu already uses for DDP:

* params and optimizer state live SHARDED along the data axis: each
  leaf splits on its LARGEST dimension that the axis size divides
  (lowest index on ties), replicated only when no dimension divides.
  Dim 0 alone would miss conv nets almost entirely — HWIO kernels
  lead with kernel height (1/3/7) — whereas the channel dims are
  near-always divisible, so ≥99% of params+momentum bytes shard for
  both resnet50 and vit_b_16 (asserted in tests/test_zero1.py via
  ``zero1_sharded_fraction``). Persistent per-chip memory for params
  + momentum drops ~1/N;
* inside the step each device ``all_gather``s the full params for
  forward/backward. The VJP of a tiled all-gather is ``psum_scatter``,
  so the gradient arrives REDUCE-SCATTERED — each device holds exactly
  its shard's global-sum gradient. Total collective traffic
  (all-gather + reduce-scatter) equals DDP's all-reduce; XLA overlaps
  both with compute;
* the ENTIRE optimizer update runs on the local shard — this is Xu et
  al.'s weight-update sharding (arXiv:2004.13336) in full: SGD's chain
  (momentum, weight decay, LR) is elementwise and needs nothing more;
  the LARS/LAMB trust ratios (dptpu/ops/optimizers.py) need per-LAYER
  norms, which each device completes from its shard-local partial
  sums with ONE psum of a tiny ``[L, 2]`` stack (``zero1_sumsq_reduce``
  below) — so optimizer FLOPs AND optimizer-state bytes scale 1/N with
  DP width while the per-step collective bytes stay at DDP's
  all-reduce volume plus those 2·L floats (at accum_steps=1; under
  gradient accumulation the all-gather + reduce-scatter pair runs once
  per MICROBATCH — K× the param bytes per step — where DDP's single
  post-scan psum does not scale with K). Identical math to the
  replicated update, locked by tests/test_zero1.py against the
  single-device big-batch step;
* the few leaves no dimension divides (tiny biases — a rounding error
  of the bytes) stay replicated; their gradients take an explicit
  ``lax.psum`` (the steps run ``check_vma=False``, so no implicit
  collective exists to cover them — see
  dptpu.train.step.shard_map_nocheck).

Checkpointing/eval work unchanged: sharded arrays are still global
jax.Arrays — ``np.asarray`` gathers for ``torch.save``-style
serialization, and the replicated-spec eval step reshards on entry (use
``gather_state`` once per validation pass to avoid re-gathering every
eval step).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dptpu.parallel.mesh import (
    DATA_AXIS,
    SLICE_AXIS,
    data_axis_names,
    data_parallel_width,
    squeeze_axes,
)

# NOTE: dptpu.train is imported lazily inside make_zero1_train_step —
# a module-level import would close the cycle parallel/__init__ -> zero
# -> train/__init__ -> fit -> parallel/__init__ (partially initialized)
# whenever dptpu.parallel is imported before dptpu.train.


def _leaf_spec(leaf, n: int) -> P:
    """Shard the largest evenly-divisible dim over the data axis.

    Any divisible dim yields the same 1/N byte saving; the largest one
    (lowest index on ties) keeps per-device shards from degenerating to
    width-1 slices on mixed-shape leaves. Leaves with no divisible dim
    (tiny biases, scalars) stay replicated — they are a rounding error
    of the total (see ``zero1_sharded_fraction``). The dim-selection
    rule is the SHARED ``mesh.largest_divisible_dim`` — the
    hierarchical reduce-scatter resolves through the same function, so
    its gradient shard is the update shard by construction. Delegates
    to ``rules.fsdp_auto_spec`` — the same resolver the rules tables'
    ``AUTO_FSDP`` fallback uses — so the ZeRO-1 layout and the
    table-driven placements share one implementation."""
    from dptpu.parallel.rules import fsdp_auto_spec

    return fsdp_auto_spec(getattr(leaf, "shape", ()), n)


def _sharded_axis(spec: P) -> int:
    """Index of the data-sharded dim in a ``_leaf_spec`` result, -1 if
    replicated."""
    for d, name in enumerate(spec):
        if name == DATA_AXIS:
            return d
    return -1


def _iter_state_bytes(state, mesh: Mesh):
    """Yield ``(nbytes, is_sharded)`` for every params/opt_state leaf
    under this state's ``zero1_state_specs`` — the ONE byte-accounting
    walk behind ``zero1_sharded_fraction`` and
    ``zero1_update_shard_bytes`` (a second copy of the zip would let
    the telemetry silently diverge from the headline claim). Accepts a
    real TrainState or a ``jax.eval_shape`` ShapeDtypeStruct tree."""
    specs = zero1_state_specs(state, mesh)
    for part in ("params", "opt_state"):
        leaves = jax.tree_util.tree_leaves(getattr(state, part))
        spec_leaves = jax.tree_util.tree_leaves(
            getattr(specs, part), is_leaf=lambda x: isinstance(x, P)
        )
        for leaf, spec in zip(leaves, spec_leaves):
            nbytes = int(np.prod(leaf.shape) if leaf.shape else 1) * (
                jnp.dtype(leaf.dtype).itemsize
            )
            yield nbytes, _sharded_axis(spec) >= 0


def zero1_sharded_fraction(state, mesh: Mesh) -> float:
    """Fraction of params+opt_state BYTES that actually shard 1/N.

    This is the feature's headline claim made measurable: ~1/N
    persistent HBM per chip holds only if this is ≈1.0."""
    total = 0
    sharded = 0
    for nbytes, is_sharded in _iter_state_bytes(state, mesh):
        total += nbytes
        if is_sharded:
            sharded += nbytes
    return sharded / max(total, 1)


def zero1_state_specs(state, mesh: Mesh):
    """TrainState-shaped PartitionSpec tree: each params/opt_state leaf
    sharded on its largest evenly-divisible dim (``_leaf_spec``),
    everything else (step, batch_stats) replicated."""
    n = int(mesh.shape[DATA_AXIS])
    return state.replace(
        step=P(),
        params=jax.tree_util.tree_map(
            lambda l: _leaf_spec(l, n), state.params),
        batch_stats=jax.tree_util.tree_map(lambda _: P(), state.batch_stats),
        opt_state=jax.tree_util.tree_map(
            lambda l: _leaf_spec(l, n), state.opt_state),
    )


def shard_zero1_state(state, mesh: Mesh):
    """Place a (replicated) TrainState into the ZeRO-1 layout: each
    sharded leaf stores 1/N per device. Values are unchanged. NOTE:
    ``device_put`` may alias the input's buffers — after sharding, step
    only the returned state (the train steps donate their inputs)."""
    specs = zero1_state_specs(state, mesh)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), state, specs
    )


def gather_state(state, mesh: Mesh):
    """Re-replicate a ZeRO-1 state (e.g. once before a validation pass,
    so the replicated-spec eval step doesn't all-gather every batch)."""
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())), state
    )


def zero1_sumsq_reduce(param_specs):
    """Build the trust-ratio norm completer for the sharded update.

    The trust-ratio transforms (dptpu/ops/optimizers.py) hand over a
    params-structured tree of ``[sum(w²), sum(u²)]`` pairs computed on
    the LOCAL shard. Sharded leaves' partials sum across the data axis;
    replicated leaves' are already global (psum'ing them would count
    each copy N times). ALL pairs stack into one ``[L, 2]`` array so the
    completion is a single psum of ~2·L floats — the "one small psum"
    that keeps the whole optimizer math shard-local (arXiv:2004.13336).
    """
    spec_leaves = jax.tree_util.tree_leaves(
        param_specs, is_leaf=lambda x: isinstance(x, P)
    )
    mask = np.array(
        [1.0 if _sharded_axis(s) >= 0 else 0.0 for s in spec_leaves],
        np.float32,
    )[:, None]

    def reduce(pairs_tree):
        leaves, treedef = jax.tree_util.tree_flatten(pairs_tree)
        if len(leaves) != len(spec_leaves):
            raise ValueError(
                f"trust-ratio pairs tree has {len(leaves)} leaves but the "
                f"ZeRO-1 spec tree has {len(spec_leaves)} — the optimizer "
                f"was built against a different param tree"
            )
        stacked = jnp.stack(leaves)
        total = lax.psum(stacked, DATA_AXIS)  # the ONE small psum
        completed = stacked * (1.0 - mask) + total * mask
        return jax.tree_util.tree_unflatten(
            treedef, [completed[i] for i in range(len(leaves))]
        )

    return reduce


def zero1_update_shard_bytes(state, mesh: Mesh) -> int:
    """Bytes of params + optimizer state ONE device reads/writes per
    update under the sharded weight update (the ``Opt/update_shard_bytes``
    gauge): sharded leaves count 1/N, replicated leaves in full. The
    replicated-update baseline is the same sum with N = 1."""
    n = int(mesh.shape[DATA_AXIS])
    return sum(
        nbytes // n if is_sharded else nbytes
        for nbytes, is_sharded in _iter_state_bytes(state, mesh)
    )


# --------------------------------------------------------------------------
# ZeRO-3 / FSDP: the rules-table generalization of the weight-update
# sharding above. ZeRO-1's placement is the per-leaf ``_leaf_spec``
# heuristic; ZeRO-3 instead resolves the arch's REGISTRY rules table
# (dptpu/models/registry.py FAMILY_RULES projected onto the data axis via
# dptpu/parallel/rules.py), so the FSDP shard dims are the ones the
# family declaration picked to compose with tensor parallelism, and the
# forward/backward boundary is an EXPLICIT custom-VJP pair: forward
# all-gather, backward psum_scatter — the backward gather IS the
# reduce-scatter, stated in source rather than inherited from the
# all-gather's VJP. Grads therefore stay shard-sized through the
# accumulation scan, the fp32 optimizer state stays shard-sized, and the
# per-chip params+grads+opt-state footprint is ~1/N (gated in SCALEBENCH
# and the ``zero3`` HLO budget config).
#
# make_zero1_train_step above is deliberately untouched: its compiled
# program is exact-matched by HLO_BUDGETS.json.


def zero3_param_specs(arch: str, params, mesh: Mesh):
    """The arch's registry rules table projected onto the intra-slice
    data axis — THE ZeRO-3 placement. Clamped to mesh-size
    divisibility (the tiled all-gather boundary needs even tiles; a
    non-dividing leaf degrades to replicated exactly like
    ``_leaf_spec``'s remainder). ``AUTO_FSDP`` rows resolve through the
    same ``largest_divisible_dim`` rule ZeRO-1 uses, so for a generic
    CNN this tree is bit-identical to ``zero1_state_specs``' params."""
    from dptpu.models.registry import partition_rules_for_arch
    from dptpu.parallel.rules import match_partition_rules

    n = int(mesh.shape[DATA_AXIS])
    return match_partition_rules(
        partition_rules_for_arch(arch), params,
        keep_axes=(DATA_AXIS,), clamp={DATA_AXIS: n},
    )


def zero3_state_specs(state, mesh: Mesh, param_specs):
    """TrainState-shaped spec tree for the ZeRO-3 layout: params follow
    the rules-table placement, momentum mirrors it STRUCTURALLY
    (``map_momentum`` — the update is shard-local, so the fp32 state
    lives exactly where its param shard lives), everything else
    replicated."""
    from dptpu.train.state import map_momentum

    return state.replace(
        step=P(),
        params=param_specs,
        batch_stats=jax.tree_util.tree_map(lambda _: P(), state.batch_stats),
        opt_state=map_momentum(
            state.opt_state, lambda _: param_specs, lambda _: P()
        ),
    )


def shard_zero3_state(state, mesh: Mesh, param_specs):
    """Place a (replicated) TrainState into the ZeRO-3 layout (see
    ``shard_zero1_state`` for the donation caveat — step only the
    returned state). Re-sharding an already-placed state is fine:
    ``device_put`` moves it — this is what the elastic resume path does
    after a geometry change."""
    specs = zero3_state_specs(state, mesh, param_specs)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), state, specs
    )


def state_shard_bytes(state, mesh: Mesh, specs) -> int:
    """Per-chip bytes of params + optimizer state under an explicit
    TrainState-shaped spec tree (``zero3_state_specs`` result) — the
    SCALEBENCH 1/N gate's numerator. Same accounting contract as
    ``zero1_update_shard_bytes``: sharded leaves count 1/N, replicated
    in full; N=1 (or an all-replicated spec tree) gives the DDP
    baseline."""
    n = int(mesh.shape[DATA_AXIS])
    total = 0
    for part in ("params", "opt_state"):
        leaves = jax.tree_util.tree_leaves(getattr(state, part))
        spec_leaves = jax.tree_util.tree_leaves(
            getattr(specs, part), is_leaf=lambda x: isinstance(x, P)
        )
        for leaf, spec in zip(leaves, spec_leaves):
            nbytes = int(np.prod(leaf.shape) if leaf.shape else 1) * (
                jnp.dtype(leaf.dtype).itemsize
            )
            total += nbytes // n if _sharded_axis(spec) >= 0 else nbytes
    return total


_GATHER_CACHE = {}


def _zero3_gather(d: int):
    """The explicit ZeRO-3 boundary for a leaf sharded on dim ``d``:
    forward is the tiled all-gather (full params on every device, used
    and discarded within the step), backward is the tiled
    ``psum_scatter`` on the SAME dim — each device receives exactly its
    shard of the global gradient sum, so the gradient is never
    materialized unsharded. This is what the all-gather's derived VJP
    does implicitly for ZeRO-1; stating it as a custom VJP pins the
    pairing against AD internals and gives the overlap plan a stable
    per-leaf anchor in the backward."""
    fn = _GATHER_CACHE.get(d)
    if fn is not None:
        return fn

    @jax.custom_vjp
    def gather(x):
        return lax.all_gather(x, DATA_AXIS, axis=d, tiled=True)

    def fwd(x):
        return lax.all_gather(x, DATA_AXIS, axis=d, tiled=True), None

    def bwd(_, ct):
        return (lax.psum_scatter(
            ct, DATA_AXIS, scatter_dimension=d, tiled=True),)

    gather.defvjp(fwd, bwd)
    _GATHER_CACHE[d] = gather
    return gather


def make_zero3_train_step(mesh: Mesh, state_template, param_specs,
                          compute_dtype=jnp.float32, lr_schedule=None,
                          seed: int = 0, accum_steps: int = 1,
                          label_smoothing: float = 0.0, tx_factory=None,
                          dcn_dtype: str = "fp32", overlap: bool = False,
                          bucket_bytes=None):
    """ZeRO-3/FSDP variant of ``make_zero1_train_step``: same contract
    (``state`` in the ``shard_zero3_state`` layout, back in it), same
    collective volume (gather + scatter = DDP's all-reduce bytes), but
    placement comes from the arch's rules table (``param_specs`` =
    ``zero3_param_specs``) and the gather/scatter boundary is the
    explicit ``_zero3_gather`` custom VJP. Composes exactly like
    ZeRO-1: ``accum_steps`` keeps the fp32 grad accumulator SHARD-sized
    (the scatter runs per microbatch inside the boundary's backward),
    a hierarchical mesh adds the shard-sized DCN hop once per update,
    and ``overlap=True`` buckets the DCN/remainder work in-backward
    (``make_zero1_bucket_reduce`` — the bucket engine is
    layout-agnostic, it only needs the sharded flags)."""
    from dptpu.parallel.hierarchy import (
        DCN_DTYPES,
        dcn_reduce_shard,
        is_hierarchical,
    )
    from dptpu.train.step import (
        shard_map_nocheck,
        tpu_compiler_options,
        train_step_body,
    )

    if dcn_dtype not in DCN_DTYPES:
        raise ValueError(
            f"dcn_dtype={dcn_dtype!r} must be one of "
            + "/".join(repr(d) for d in DCN_DTYPES)
        )
    if lr_schedule is None:
        lr_schedule = lambda count: 0.1  # noqa: E731
    hier = is_hierarchical(mesh)
    slices = int(mesh.shape[SLICE_AXIS]) if hier else 1
    axis_names = data_axis_names(mesh)
    axis_size = data_parallel_width(mesh)
    specs = zero3_state_specs(state_template, mesh, param_specs)
    tx = None
    if tx_factory is not None:
        tx = tx_factory(sumsq_reduce=zero1_sumsq_reduce(specs.params))
    else:
        from dptpu.ops.optimizers import trust_ratio_stats

        if trust_ratio_stats(state_template.opt_state) is not None:
            raise ValueError(
                "state uses a trust-ratio optimizer (LARS/LAMB) but no "
                "tx_factory was given — the sharded update would "
                "compute per-layer norms from local shards only. Pass "
                "tx_factory=partial(make_optimizer, momentum, wd, name) "
                "so the norm completer can be injected."
            )

    def gather_params(params):
        def gather(x, s):
            d = _sharded_axis(s)
            if d < 0:
                return x
            return _zero3_gather(d)(x)

        return jax.tree_util.tree_map(gather, params, specs.params)

    def reduce_grads(grads):
        # sharded leaves arrived scatter-reduced over the intra-slice
        # axis through the custom-VJP boundary; hierarchical meshes add
        # the shard-sized DCN hop, replicated remainders their explicit
        # psum — identical composition to the ZeRO-1 step.
        def red(g, s):
            if _sharded_axis(s) >= 0:
                return dcn_reduce_shard(g, SLICE_AXIS, dcn_dtype,
                                        slices=slices) if hier else g
            g = lax.psum(g, DATA_AXIS)
            return lax.psum(g, SLICE_AXIS) if hier else g

        return jax.tree_util.tree_map(red, grads, specs.params)

    overlap_plan = None
    if overlap:
        from dptpu.parallel.overlap import (
            DEFAULT_BUCKET_MB,
            OverlapPlan,
            make_zero1_bucket_reduce,
        )

        sharded_flags = [
            _sharded_axis(s) >= 0
            for s in jax.tree_util.tree_leaves(
                specs.params, is_leaf=lambda x: isinstance(x, P)
            )
        ]
        overlap_plan = OverlapPlan(
            bucket_bytes or int(DEFAULT_BUCKET_MB * 1e6),
            make_zero1_bucket_reduce(sharded_flags, hier, dcn_dtype,
                                     slices=slices),
        )
        reduce_grads = None  # the plan carries the whole reduction

    def step(state, batch):
        return train_step_body(
            state, batch, compute_dtype=compute_dtype,
            lr_schedule=lr_schedule, seed=seed, axis_size=axis_size,
            on_mesh=True, gather_params=gather_params,
            reduce_grads=reduce_grads, tx=tx, accum_steps=accum_steps,
            label_smoothing=label_smoothing, axis_names=axis_names,
            overlap_plan=overlap_plan,
        )

    batch_spec = P(squeeze_axes(axis_names))
    sharded = shard_map_nocheck(
        step,
        mesh=mesh,
        in_specs=(specs, batch_spec),
        out_specs=(specs, P()),
    )
    return jax.jit(
        sharded, donate_argnums=0,
        compiler_options=tpu_compiler_options(
            collectives_in_scan=accum_steps > 1
        ),
    )


def make_zero1_train_step(mesh: Mesh, state_template, compute_dtype=jnp.float32,
                          lr_schedule=None, seed: int = 0,
                          accum_steps: int = 1, label_smoothing: float = 0.0,
                          tx_factory=None, dcn_dtype: str = "fp32",
                          overlap: bool = False,
                          bucket_bytes=None):
    """ZeRO-1 / sharded-weight-update variant of
    ``dptpu.train.step.make_train_step``.

    ``state_template`` fixes which leaves shard; it must be the SAME
    TrainState the returned step will receive (or share its
    ``apply_fn``/``tx`` objects) — those static fields are part of the
    pytree metadata that shard_map matches specs against. Returns
    ``step(state, batch) -> (state, metrics)`` with the SAME contract and
    math as the DDP step; ``state`` must be in the ``shard_zero1_state``
    layout and comes back in it.

    ``tx_factory(sumsq_reduce=...)`` rebuilds the optimizer with the
    shard-aware trust-ratio norm completer injected (same state
    structure, so the template's ``tx.init`` layout still matches); when
    None the template's own ``tx`` runs — correct for any elementwise
    chain (SGD), and for LARS/LAMB **only** via a factory.

    ``accum_steps=k`` composes with the sharding: each microbatch's
    gradient arrives reduce-scattered through the all-gather VJP, so the
    fp32 accumulator is SHARD-sized (1/N of the model — accumulation
    costs no replicated-gradient memory); params are re-gathered per
    microbatch, the price of never materializing full optimizer state.

    On a hierarchical ``{slice, data}`` mesh the composition is exactly
    the two-level engine's design (dptpu/parallel/hierarchy.py): state
    shards over the INTRA-slice axis (so the per-microbatch weight
    all-gather and its psum_scatter VJP stay on ICI — the all-gather
    moves weights, never gradients), and ``reduce_grads`` adds only the
    shard-sized cross-slice hop over DCN — ONCE per update, after the
    accumulation scan, optionally bf16-compressed (``dcn_dtype``).

    ``overlap=True`` (``DPTPU_OVERLAP=1``; dptpu/parallel/overlap.py):
    the per-leaf all-gather VJP already delivers each gradient
    reduce-scattered DURING backward — ZeRO-1's reduce-scatter is
    maximally bucketed by construction — so the plan buckets the work
    that used to run post-backward: per ``bucket_bytes`` bucket of
    (shard-local) leaves, the shard-sized DCN hop and the
    replicated-remainder psums concatenate into fused collectives
    issued in-backward right behind the VJP's reduce-scatter.
    Bit-identical to ``overlap=False`` (same collectives, same
    grouping).
    """
    from dptpu.parallel.hierarchy import (
        DCN_DTYPES,
        dcn_reduce_shard,
        is_hierarchical,
    )
    from dptpu.train.step import (
        shard_map_nocheck,
        tpu_compiler_options,
        train_step_body,
    )

    if dcn_dtype not in DCN_DTYPES:
        raise ValueError(
            f"dcn_dtype={dcn_dtype!r} must be one of "
            + "/".join(repr(d) for d in DCN_DTYPES)
        )
    if lr_schedule is None:
        lr_schedule = lambda count: 0.1  # noqa: E731
    hier = is_hierarchical(mesh)
    slices = int(mesh.shape[SLICE_AXIS]) if hier else 1
    axis_names = data_axis_names(mesh)
    # gradient normalizer spans ALL replicas (slices × dp_in_slice);
    # the state specs below shard over the intra-slice axis only
    axis_size = data_parallel_width(mesh)
    specs = zero1_state_specs(state_template, mesh)
    tx = None
    if tx_factory is not None:
        tx = tx_factory(sumsq_reduce=zero1_sumsq_reduce(specs.params))
    else:
        from dptpu.ops.optimizers import trust_ratio_stats

        if trust_ratio_stats(state_template.opt_state) is not None:
            # without the factory the template's own tx would run with
            # sumsq_reduce=None: every trust ratio computed from the
            # 1/N shard-local norms, never completed across the axis —
            # silently-wrong training that worsens with DP width
            raise ValueError(
                "state uses a trust-ratio optimizer (LARS/LAMB) but no "
                "tx_factory was given — the sharded update would "
                "compute per-layer norms from local shards only. Pass "
                "tx_factory=partial(make_optimizer, momentum, wd, name) "
                "so the norm completer can be injected."
            )

    def gather_params(params):
        # all-gather (along whichever dim _leaf_spec chose) -> full
        # params; the VJP of the tiled all-gather is psum_scatter, so
        # the gradient w.r.t. the local shards arrives already
        # reduce-scattered: each device gets its shard of the global
        # gradient sum with no separate all-reduce.
        def gather(x, s):
            d = _sharded_axis(s)
            if d < 0:
                return x
            return lax.all_gather(x, DATA_AXIS, axis=d, tiled=True)

        return jax.tree_util.tree_map(gather, params, specs.params)

    def reduce_grads(grads):
        # the all-gather VJP already reduce-scattered the sharded leaves
        # over the INTRA-slice axis; on a hierarchical mesh each shard
        # then takes the shard-sized cross-slice (DCN) hop — this is the
        # "reduce-scatter output IS the 1/N update shard" composition,
        # and it runs once per UPDATE (reduce_grads sits after the
        # accumulation scan), never per microbatch. The replicated
        # remainder (no divisible dim) needs its explicit cross-replica
        # sum — under check_vma=False nothing is implicit.
        def red(g, s):
            if _sharded_axis(s) >= 0:
                return dcn_reduce_shard(g, SLICE_AXIS, dcn_dtype,
                                        slices=slices) if hier else g
            g = lax.psum(g, DATA_AXIS)
            return lax.psum(g, SLICE_AXIS) if hier else g

        return jax.tree_util.tree_map(red, grads, specs.params)

    overlap_plan = None
    if overlap:
        from dptpu.parallel.overlap import (
            DEFAULT_BUCKET_MB,
            OverlapPlan,
            make_zero1_bucket_reduce,
        )

        sharded_flags = [
            _sharded_axis(s) >= 0
            for s in jax.tree_util.tree_leaves(
                specs.params, is_leaf=lambda x: isinstance(x, P)
            )
        ]
        overlap_plan = OverlapPlan(
            bucket_bytes or int(DEFAULT_BUCKET_MB * 1e6),
            make_zero1_bucket_reduce(sharded_flags, hier, dcn_dtype,
                                     slices=slices),
        )
        reduce_grads = None  # the plan carries the whole reduction

    def step(state, batch):
        return train_step_body(
            state, batch, compute_dtype=compute_dtype,
            lr_schedule=lr_schedule, seed=seed, axis_size=axis_size,
            on_mesh=True, gather_params=gather_params,
            reduce_grads=reduce_grads, tx=tx, accum_steps=accum_steps,
            label_smoothing=label_smoothing, axis_names=axis_names,
            overlap_plan=overlap_plan,
        )

    batch_spec = P(squeeze_axes(axis_names))
    sharded = shard_map_nocheck(
        step,
        mesh=mesh,
        in_specs=(specs, batch_spec),
        out_specs=(specs, P()),
    )
    return jax.jit(
        sharded, donate_argnums=0,
        compiler_options=tpu_compiler_options(
            collectives_in_scan=accum_steps > 1
        ),
    )
