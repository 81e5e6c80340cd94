"""Compiled train/eval steps: the reference's hot loop as one XLA program.

One ``train_step`` fuses what the reference does in five eager stages
(H2D copy → forward → backward with bucketed NCCL all-reduce → SGD step →
metric ``.item()`` syncs, imagenet_ddp.py:254-281): normalization, forward,
backward, a single ``lax.pmean`` gradient all-reduce that XLA overlaps with
the backward computation (replacing c10d's bucketing engine, SURVEY.md §2b),
the optimizer update, and metric reduction. Parallelism is ``shard_map`` over
the mesh ``data`` axis with replicated params — the DDP topology. BatchNorm
runs on the *local* shard (per-replica statistics, DDP's default non-synced
BN) unless the model was built with ``bn_axis_name="data"`` (the SyncBN
analog); running stats are pmean'd every step so state stays replicated,
which matches what every replica would checkpoint/eval after DDP broadcast.

Normalization is fused into the step: batches arrive as raw **uint8** NHWC
and are converted + normalized on-device with mean/std ×255 — the
DataPrefetcher's GPU-side normalize (imagenet_ddp_apex.py:329-340), done the
XLA way (fused into the first conv's input, zero extra HBM round-trips, and
4× less host→device bandwidth than shipping f32).
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dptpu.ops.loss import cross_entropy_loss
from dptpu.ops.metrics import topk_correct_fraction
from dptpu.ops.optimizers import trust_ratio_stats
from dptpu.parallel.hierarchy import (
    flat_replica_index,
    is_hierarchical,
    make_hierarchical_reduce,
)
from dptpu.parallel.mesh import (
    DATA_AXIS,
    SLICE_AXIS,
    data_axis_names,
    data_parallel_width,
    squeeze_axes,
)


def shard_map_nocheck(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes checker OFF.

    Every dptpu step places its collectives EXPLICITLY (``lax.psum`` in
    the step body / the all-gather VJP), so the checker must be off:
    with ``check_vma=True`` the gradient of a data-varying loss w.r.t.
    replicated params is already psum'd by the transpose of the implicit
    ``pvary``, and the explicit reduction would then sum it a second
    time — N x the gradient on N chips (locked by
    tests/test_train_step.py)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )

# torchvision Normalize constants (imagenet_ddp.py:163-165)
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)


def tpu_compiler_options(collectives_in_scan: bool = False) -> Optional[dict]:
    """XLA:TPU compile options for the train/eval steps.

    The latency-hiding scheduler reorders the compiled program so DMA
    (parameter/operand prefetch, and ICI collectives on multi-chip meshes)
    overlaps compute instead of serializing with it — the standard option
    for multi-chip training, where it hides the gradient all-reduce under
    backward compute. It is a scheduling pass, not a numerics change.
    Its effect on the v5e has not been measured on the current code
    (PERF.md).

    ``DPTPU_NO_LHS=1`` opts out (debugging/regression triage).

    ``collectives_in_scan`` marks a step whose microbatch ``lax.scan``
    holds collectives (ZeRO-1/3 under ``--accum-steps``: the param
    all-gather and its psum_scatter VJP run per microbatch). XLA:TPU's
    while-loop all-reduce code motion dies on that loop with
    ``RET_CHECK ... user->shape() == accumulation_shape`` (libtpu
    0.0.34; chip run, PR 21), so the pass is held off for those steps.
    """
    from dptpu.envknob import env_bool

    if jax.default_backend() != "tpu":
        return None
    opts = {}
    if not env_bool("DPTPU_NO_LHS", False):
        opts["xla_tpu_enable_latency_hiding_scheduler"] = "true"
    if collectives_in_scan:
        opts["xla_disable_hlo_passes"] = "while-loop-all-reduce-code-motion"
    return opts or None


def normalize_images(images, dtype=jnp.float32):
    """uint8 [0,255] NHWC → normalized float, on device.

    The ``(x - mean·255) / (std·255)`` form matches the DataPrefetcher
    (imagenet_ddp_apex.py:333-340); already-float inputs are assumed
    normalized (the non-Apex ToTensor+Normalize path) and only cast.
    """
    if images.dtype == jnp.uint8:
        mean = jnp.asarray(IMAGENET_MEAN, jnp.float32) * 255.0
        std = jnp.asarray(IMAGENET_STD, jnp.float32) * 255.0
        return ((images.astype(jnp.float32) - mean) / std).astype(dtype)
    return images.astype(dtype)


def token_row_weights(mask):
    """Each kept token's weight in its row's mean: a row weighs the same
    however many of its tokens the mask keeps, so the loss of a batch is
    the mean over rows of the row's mean over kept tokens, and does not
    depend on how rows are grouped into shards or blocks."""
    kept = mask.astype(jnp.float32)
    return kept / jnp.maximum(jnp.sum(kept, axis=-1, keepdims=True), 1.0)


def token_loss_and_grads(state, batch, denom, gather_params=None,
                         wrap_params=None):
    """The token task's half of the step: the model's per-token
    cross-entropy (float32, over row blocks of the head: dptpu/ops/loss.py)
    as the mean over this shard's rows, its gradient over ``denom``
    (the replicas, as the image loss), top-1/top-5 over kept tokens, and
    the expert layers' load if the model has any, and the terms of the
    loss that a model reports beside the whole (``mtp_loss``: the
    multi-token-prediction loss before its weight). Returns
    ``((loss, top1, top5, batch_stats, moe, terms), grads)``: ``moe``
    adds up over replicas, ``terms`` are means like the loss."""
    rows = batch["tokens"].shape[0]
    weights = token_row_weights(batch["mask"])

    def loss_fn(params):
        if wrap_params is not None:
            params = wrap_params(params)
        full = gather_params(params) if gather_params else params
        sums, mutated = state.apply_fn(
            {"params": full, "batch_stats": state.batch_stats},
            batch["tokens"], train=True, labels=batch["labels"],
            mask=weights, mutable=["batch_stats"],
        )
        local_loss = sums["loss_sum"] / rows
        return local_loss / denom, (
            local_loss, sums, mutated.get("batch_stats", state.batch_stats))

    (_, (loss, sums, new_stats)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(state.params)
    # what the model counts beside the loss: the expert layers' load,
    # the residuals its blocks keep, its attention's and its state-space
    # scan's calls and those of them on a kernel
    moe = {k: v for k, v in sums.items()
           if k.startswith(("moe_", "attention_", "ssd_"))
           or k == "kept_residual_mb"}
    terms = {"mtp_loss": sums["mtp_loss_sum"] / rows} \
        if "mtp_loss_sum" in sums else {}
    return (loss, sums["correct1"] / rows, sums["correct5"] / rows,
            new_stats, moe, terms), grads


def train_step_body(state, batch, *, compute_dtype, lr_schedule, seed,
                    axis_size, on_mesh, gather_params=None,
                    reduce_grads=None, tx=None, accum_steps=1,
                    label_smoothing=0.0, axis_names=(DATA_AXIS,),
                    overlap_plan=None, task="images"):
    """The shared per-shard train-step math — ONE source of truth for the
    DDP step below, the ZeRO-1 step (dptpu/parallel/zero.py) and the
    GSPMD step (dptpu/parallel/gspmd.py), which differ only in their
    specs and two hooks:

    * ``gather_params`` — ZeRO-1's all-gather, whose tiled-all-gather
      VJP delivers the gradient reduce-scattered per shard;
    * ``reduce_grads`` — the explicit cross-replica gradient reduction
      (the DDP all-reduce: ``lax.psum`` over the data axis; ZeRO-1's
      psum for its few replicated leaves; None under GSPMD, where the
      partitioner derives it). Collectives are EXPLICIT here — the steps
      run ``check_vma=False`` (``shard_map_nocheck``), so nothing is
      reduced implicitly and each reduction happens exactly once.

    ``accum_steps=k > 1`` turns the step into gradient-accumulation
    microbatching: the per-replica batch splits into ``k`` microbatches
    and a ``lax.scan`` accumulates gradients (and BN statistics and
    metrics) in fp32 before the ONE optimizer update. Each microbatch is
    mathematically a virtual replica — per-microbatch BatchNorm over
    ``b/k`` samples, a distinct dropout stream per ``(replica, micro)``
    — so ``k·N`` emulates a pod ``k×`` wider than the rig, and the
    gradient reduction still happens ONCE, after the scan. ``k=1`` takes
    the exact unaccumulated code path (bit-identity by construction).

    ``tx`` overrides ``state.tx`` for the update (ZeRO-1 injects a
    shard-aware trust-ratio optimizer whose state structure matches).
    ``label_smoothing`` feeds the training loss only.

    ``axis_names`` is the tuple of mesh axes the replicas span:
    ``("data",)`` on the flat mesh, ``("slice", "data")`` on the
    two-level hierarchical mesh (dptpu/parallel/hierarchy.py) — the
    dropout replica id flattens over them slice-major (so it equals the
    flat mesh's index for the same chip) and the BN-stat/metric pmeans
    span all replicas either way.

    ``overlap_plan`` (``DPTPU_OVERLAP=1``; dptpu/parallel/overlap.py)
    REPLACES ``reduce_grads`` with the bucketed engine: at
    ``accum_steps == 1`` each bucket's reduction is part of the
    backward graph (issued the moment its gradients exist); under
    accumulation the bucketed reduction runs once, after the scan —
    the one-reduction-per-update contract unchanged.  Bit-identical to
    the unbucketed path at any bucket count (the regrouping argument —
    see the overlap module docstring).
    """
    labels = batch["labels"]
    wrap_params = (
        overlap_plan.wrap
        if overlap_plan is not None and accum_steps == 1 else None
    )
    step_key = jax.random.fold_in(jax.random.PRNGKey(seed), state.step)
    tx = state.tx if tx is None else tx
    pmean_axes = squeeze_axes(axis_names)

    def loss_and_grads(images_u8, labels_mb, dropout_key, denom):
        images = normalize_images(images_u8, compute_dtype)

        def loss_fn(params):
            if wrap_params is not None:
                # the overlap engine's per-bucket custom-VJP boundary:
                # backward through this identity performs the bucket's
                # reduction in-place in the backward graph
                params = wrap_params(params)
            full = gather_params(params) if gather_params else params
            out, mutated = state.apply_fn(
                {"params": full, "batch_stats": state.batch_stats},
                images,
                train=True,
                mutable=["batch_stats"],
                rngs={"dropout": dropout_key},
            )
            local_loss = cross_entropy_loss(out, labels_mb, label_smoothing)
            # the shard-local mean over `denom`; `reduce_grads`
            # completes the cross-replica mean AFTER accumulation — the
            # DDP psum runs once per step, not once per microbatch.
            # (ZeRO-1 is different: its gather_params all-gather and
            # psum_scatter VJP live inside the scan, so THOSE run per
            # microbatch — the documented price of never materializing
            # full params, see make_zero1_train_step.)
            return local_loss / denom, (
                local_loss, out, mutated["batch_stats"]
            )

        (_, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params
        )
        return aux, grads

    moe, terms = {}, {}
    if task == "tokens":
        if accum_steps != 1 or label_smoothing:
            raise ValueError(
                "a token-sequence model trains without --accum-steps and "
                "--label-smoothing: the per-token loss has no microbatch "
                "scan and no smoothed target yet")
        (loss, top1, top5, new_stats, moe, terms), grads = \
            token_loss_and_grads(state, batch, axis_size, gather_params,
                                 wrap_params)
    elif accum_steps == 1:
        dropout_key = step_key
        if on_mesh:
            dropout_key = jax.random.fold_in(
                dropout_key, flat_replica_index(axis_names)
            )
        (loss, logits, new_stats), grads = loss_and_grads(
            batch["images"], labels, dropout_key, axis_size
        )
        top1, top5 = topk_correct_fraction(logits, labels, (1, 5))
    else:
        k = accum_steps
        b = labels.shape[0]
        if b % k != 0:
            raise ValueError(
                f"accum_steps={k} does not divide the per-replica batch "
                f"of {b} — pick a divisor (the microbatch is b/k)"
            )
        imgs = batch["images"].reshape(
            (k, b // k) + batch["images"].shape[1:]
        )
        labs = labels.reshape((k, b // k))
        # virtual-replica id: replica r, microbatch j acts like replica
        # r·k + j of a k×-wider pod — distinct dropout streams, same
        # resume-stable (seed, step) root
        ax = flat_replica_index(axis_names) if on_mesh else 0

        def micro(carry, xs):
            g_acc, s_acc, m_acc = carry
            im, lb, j = xs
            dropout_key = jax.random.fold_in(step_key, ax * k + j)
            (loss, out, stats), grads = loss_and_grads(
                im, lb, dropout_key, 1.0
            )
            t1, t5 = topk_correct_fraction(out, lb, (1, 5))
            g_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), g_acc, grads
            )
            s_acc = jax.tree_util.tree_map(
                lambda a, s: a + s.astype(jnp.float32), s_acc, stats
            )
            return (g_acc, s_acc, m_acc + jnp.stack([loss, t1, t5])), None

        carry0 = (
            jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            ),
            jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, jnp.float32), state.batch_stats
            ),
            jnp.zeros((3,), jnp.float32),
        )
        (g_acc, s_acc, m_acc), _ = lax.scan(
            micro, carry0, (imgs, labs, jnp.arange(k))
        )
        # mean over the k·axis_size virtual replicas, fp32 throughout
        grads = jax.tree_util.tree_map(
            lambda g, p: (g / (k * axis_size)).astype(p.dtype),
            g_acc, state.params,
        )
        new_stats = jax.tree_util.tree_map(
            lambda s, ref: (s / k).astype(ref.dtype),
            s_acc, state.batch_stats,
        )
        loss, top1, top5 = m_acc[0] / k, m_acc[1] / k, m_acc[2] / k
    if overlap_plan is not None and wrap_params is None:
        # accumulation x overlap: the bucketed reduction runs ONCE per
        # update, on the post-scan accumulated gradients
        grads = overlap_plan.reduce(grads)
    elif reduce_grads is not None:
        # the ONE explicit cross-replica gradient reduction (DDP
        # all-reduce / ZeRO-1 replicated-leaf psum)
        grads = reduce_grads(grads)
    if on_mesh:
        # running BN stats + reported metrics: explicit cross-replica mean
        # (the reference's reduce_tensor, imagenet_ddp_apex.py:562-566)
        new_stats, loss, top1, top5, terms = lax.pmean(
            (new_stats, loss, top1, top5, terms), pmean_axes
        )
        # counts add up over the replicas (the megabytes kept too: the
        # step's, on all its chips)
        moe = lax.psum(moe, pmean_axes)
    # SGD's chain is elementwise, so it is equally valid on full params
    # (DDP) and ZeRO-1 shard-local slices; LARS/LAMB additionally need
    # per-layer norms, which the injected `tx`'s sumsq_reduce completes
    # across shards with one small psum (dptpu/ops/optimizers.py)
    # named for the device trace in the step that names its other parts
    # (the image step's program stays as it was, metadata included)
    with jax.named_scope("optimizer") if task == "tokens" \
            else contextlib.nullcontext():
        direction, new_opt = tx.update(grads, state.opt_state, state.params)
        lr = lr_schedule(state.step)
        updates = jax.tree_util.tree_map(lambda u: -lr * u, direction)
        params = optax.apply_updates(state.params, updates)
    new_state = state.replace(
        step=state.step + 1,
        params=params,
        batch_stats=new_stats,
        opt_state=new_opt,
    )
    metrics = {
        "loss": loss,
        "top1": top1 * 100.0,
        "top5": top5 * 100.0,
        "lr": jnp.asarray(lr, jnp.float32),
        **moe,
        **terms,
    }
    tstats = trust_ratio_stats(new_opt)
    if tstats is not None:
        # layer-wise trust-ratio summary (Opt/* gauges): free — the
        # transform already computed it from the update's norms
        metrics.update(
            {name: jnp.asarray(v, jnp.float32)
             for name, v in tstats.items()}
        )
    return new_state, metrics


def make_train_step(mesh: Optional[Mesh] = None, compute_dtype=jnp.float32,
                    lr_schedule=None, seed: int = 0, accum_steps: int = 1,
                    label_smoothing: float = 0.0, dcn_dtype: str = "fp32",
                    overlap: bool = False, bucket_bytes: Optional[int] = None,
                    task: str = "images"):
    """Build the jitted train step.

    Returns ``step(state, batch) -> (state, metrics)`` where ``batch`` is a
    dict with ``images`` (uint8/float NHWC) and ``labels`` (int32), and
    ``metrics`` has scalar f32 ``loss``/``top1``/``top5``/``lr`` (plus
    ``trust_min/mean/max`` under a trust-ratio optimizer);
    loss/top1/top5 are already cross-replica-averaged (the reference's
    reduce_tensor, imagenet_ddp_apex.py:562-566, folded into the step).

    ``lr_schedule`` maps the global step count → learning rate (see
    dptpu.ops.schedules); it is applied here, after the optimizer's
    momentum/weight-decay chain, reproducing torch SGD's ``p -= lr·buf``.
    Defaults to constant 0.1 (the reference's base LR) for schedule-less
    callers.

    ``seed`` feeds the dropout streams of the models that have them
    (alexnet/vgg classifier heads, squeezenet): the per-step key is
    ``fold_in(PRNGKey(seed), global_step)`` — resume-stable — and each
    data shard folds in its axis index so replicas draw independent masks
    (per-process torch RNG semantics, nd_imagenet.py:84-92).

    ``accum_steps=k`` enables gradient-accumulation microbatching
    (``--accum-steps`` / ``DPTPU_ACCUM``): each replica's batch splits
    into ``k`` fp32-accumulated microbatches before the one optimizer
    update, emulating a pod ``k×`` wider (see ``train_step_body``).

    On a hierarchical ``{slice, data}`` mesh
    (``make_hierarchical_mesh``) the gradient reduction decomposes into
    reduce-scatter(ICI) → shard-sized all-reduce(DCN) → all-gather(ICI)
    per leaf (dptpu/parallel/hierarchy.py), with ``dcn_dtype="bf16"``
    compressing the DCN hop (fp32 accumulation). Under accumulation the
    whole three-hop reduction still runs ONCE per update, after the
    microbatch scan — never per microbatch.

    ``overlap=True`` (``DPTPU_OVERLAP=1``) swaps the per-leaf reduction
    for the bucketed backward-overlapped engine
    (dptpu/parallel/overlap.py): the gradient tree packs into
    ``bucket_bytes``-bounded buckets in reverse layer order and each
    bucket reduces as ONE fused collective, issued inside the backward
    graph the moment its gradients exist (the hierarchical ladder runs
    per bucket on the flat buffer).  Bit-identical to ``overlap=False``
    at any bucket count.  No-op on a mesh-less single-device step
    (there is no collective to overlap).
    """

    if lr_schedule is None:
        lr_schedule = lambda count: 0.1  # noqa: E731
    # Gradient normalizer: the data axes' size, NOT mesh.size. The
    # explicit psum below spans exactly the data axis (both data axes on
    # a hierarchical mesh) even when inner axes (e.g. {"data": N,
    # "model": M}) are open — the model-axis duplicates compute
    # identical grads and must NOT be summed. Locked by
    # tests/test_train_step.py::test_axes_open_mesh_matches_single_device.
    axis_names = data_axis_names(mesh) if mesh is not None else (DATA_AXIS,)
    axis_size = data_parallel_width(mesh)
    hier = is_hierarchical(mesh)
    reduce_grads = None
    overlap_plan = None
    if overlap and mesh is not None:
        from dptpu.parallel.overlap import (
            DEFAULT_BUCKET_MB,
            OverlapPlan,
            make_ddp_bucket_reduce,
        )

        inner = int(mesh.shape[DATA_AXIS]) if hier else None
        n_slices = int(mesh.shape[SLICE_AXIS]) if hier else None
        overlap_plan = OverlapPlan(
            bucket_bytes or int(DEFAULT_BUCKET_MB * 1e6),
            make_ddp_bucket_reduce(hier, dcn_dtype, inner=inner,
                                   slices=n_slices),
        )
    elif hier:
        # the two-level reduction: per-chip DCN bytes ~1/dp_in_slice of
        # the flat all-reduce (the Mikami/Yamazaki hierarchy)
        reduce_grads = make_hierarchical_reduce(mesh, dcn_dtype)
    elif mesh is not None:
        # the DDP all-reduce, placed explicitly (see shard_map_nocheck):
        # grads arrive as d(local_mean/axis_size), so the psum IS the
        # global-batch-mean gradient
        reduce_grads = lambda g: lax.psum(g, DATA_AXIS)  # noqa: E731

    def step(state, batch):
        return train_step_body(
            state, batch, compute_dtype=compute_dtype,
            lr_schedule=lr_schedule, seed=seed, axis_size=axis_size,
            on_mesh=mesh is not None, reduce_grads=reduce_grads,
            accum_steps=accum_steps, label_smoothing=label_smoothing,
            axis_names=axis_names, overlap_plan=overlap_plan, task=task,
        )

    opts = tpu_compiler_options()
    if mesh is None:
        return jax.jit(step, donate_argnums=0, compiler_options=opts)
    batch_spec = P(squeeze_axes(axis_names))
    sharded = shard_map_nocheck(
        step,
        mesh=mesh,
        in_specs=(P(), batch_spec),
        out_specs=(P(), P()),
    )
    return jax.jit(sharded, donate_argnums=0, compiler_options=opts)


def make_eval_step(mesh: Optional[Mesh] = None, compute_dtype=jnp.float32,
                   task: str = "images"):
    """Build the jitted eval step.

    Returns ``eval_step(state, batch) -> sums`` with ``loss_sum``,
    ``correct1``, ``correct5``, ``count`` summed over the GLOBAL batch
    (psum over the data axis) — exact aggregate accuracy, the sharded-val +
    all-reduce behavior of the Apex path (imagenet_ddp_apex.py:232-234,
    457-460), but without its per-step host sync. An optional f32 ``mask``
    in the batch (1.0 = real sample) makes padded remainder batches exact.

    ``task="tokens"``: the same four sums over the kept TOKENS of the
    global batch (per-token loss and accuracy; the loader's row mask is
    already folded into the token mask).
    """

    def token_step(state, batch):
        sums = state.apply_fn(
            {"params": state.params, "batch_stats": state.batch_stats},
            batch["tokens"], train=False, labels=batch["labels"],
            mask=batch["mask"],
        )
        sums = {k: sums[k]
                for k in ("loss_sum", "correct1", "correct5", "count")}
        if mesh is not None:
            sums = lax.psum(sums, squeeze_axes(data_axis_names(mesh)))
        return sums

    def step(state, batch):
        images = normalize_images(batch["images"], compute_dtype)
        labels = batch["labels"]
        mask = batch.get("mask", jnp.ones(labels.shape, jnp.float32))
        logits = state.apply_fn(
            {"params": state.params, "batch_stats": state.batch_stats},
            images,
            train=False,
        ).astype(jnp.float32)
        per_ex = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        _, pred = lax.top_k(logits, min(5, logits.shape[-1]))
        hit = pred == labels[:, None]
        sums = {
            "loss_sum": (per_ex * mask).sum(),
            "correct1": (hit[:, :1].any(axis=1) * mask).sum(),
            "correct5": (hit.any(axis=1) * mask).sum(),
            "count": mask.sum(),
        }
        if mesh is not None:
            sums = lax.psum(sums, squeeze_axes(data_axis_names(mesh)))
        return sums

    if task == "tokens":
        step = token_step
    opts = tpu_compiler_options()
    if mesh is None:
        return jax.jit(step, compiler_options=opts)
    sharded = shard_map_nocheck(
        step,
        mesh=mesh,
        in_specs=(P(), P(squeeze_axes(data_axis_names(mesh)))),
        out_specs=P(),
    )
    return jax.jit(sharded, compiler_options=opts)
