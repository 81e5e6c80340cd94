"""Sequence-parallel training step: the {data, seq} mesh path.

The reference has no sequence dimension (SURVEY.md §5 "long-context:
absent by construction"); this is the trainer-level entry for dptpu's
beyond-reference sequence/context parallelism (`DPTPU_SP=N` in
``fit()``). The token axis of a ViT shards over the inner ``seq`` mesh
axis — Ulysses all-to-all or ring attention per block
(dptpu/ops/sequence_parallel.py) — while the batch shards over ``data``
as usual.

Design (why this is NOT the shared ``train_step_body``):

* the model runs with ``seq_shard_tokens=True`` — embedding replicated,
  tokens padded/sliced per sequence member, cls recovered by psum
  (dptpu/models/vit.py Encoder docstring) — so the per-member forward
  already contains cross-``seq`` collectives (all_to_all/ppermute/psum)
  whose VJPs route the cross-member cotangents;
* Ulysses' all-to-all output sharding defeats shard_map's replication
  checker, so the step runs ``check_vma=False`` — no automatic psum is
  inserted for the replicated params, and the gradient reduction is
  therefore EXPLICIT: each (data, seq) member differentiates the global
  mean loss restricted to its local graph, and one
  ``psum(grads, ("data", "seq"))`` sums the member contributions —
  over ``data`` that is the DDP gradient all-reduce, over ``seq`` it
  sums each member's token-chunk contribution (the head/embedding
  grads arrive pre-scaled by 1/n_seq from the redundant per-member
  loss, so the same psum reconstructs them exactly);
* ViT only (LayerNorm, no BatchNorm, no dropout), enforced by fit()'s
  arch gate — batch_stats pass through untouched.

Update math (SGD chain, LR application) is shared with every other
step via ``state.tx`` + ``optax.apply_updates``, identical to
dptpu/train/step.py; parity with the single-device step is locked
through the trainer in tests/test_fit.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dptpu.ops.loss import cross_entropy_loss
from dptpu.ops.metrics import topk_correct_fraction
from dptpu.parallel.mesh import DATA_AXIS

SEQ_AXIS = "seq"


def make_seq_train_step(mesh: Mesh, seq_model, compute_dtype=jnp.float32,
                        lr_schedule=None, label_smoothing: float = 0.0):
    """Build the jitted sequence-parallel train step.

    ``seq_model`` is the ViT built with ``seq_axis_name=SEQ_AXIS`` and
    ``seq_shard_tokens=True``; its param tree must equal the state's
    (the seq flags add no params — fit() creates the state from the
    plain model). Same contract as ``make_train_step``:
    ``step(state, batch) -> (state, metrics)`` with the batch sharded
    ``P(DATA_AXIS)`` (replicated over ``seq``) and replicated state.
    """
    from dptpu.train.step import (
        normalize_images,
        shard_map_nocheck,
        tpu_compiler_options,
    )

    if lr_schedule is None:
        lr_schedule = lambda count: 0.1  # noqa: E731
    n_data = int(mesh.shape[DATA_AXIS])
    n_seq = int(mesh.shape[SEQ_AXIS])

    def step(state, batch):
        images = normalize_images(batch["images"], compute_dtype)
        labels = batch["labels"]

        def loss_fn(params):
            logits = seq_model.apply(
                {"params": params}, images, train=True
            )
            local_loss = cross_entropy_loss(logits, labels,
                                            label_smoothing)
            # global mean loss restricted to this member's local graph:
            # /n_data for the data-shard mean, /n_seq because every
            # sequence member recomputes the (identical) loss — the
            # explicit two-axis psum below then sums members back to
            # exactly the global-batch-mean gradient
            return local_loss / (n_data * n_seq), (local_loss, logits)

        (_, (loss, logits)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        grads = lax.psum(grads, (DATA_AXIS, SEQ_AXIS))
        top1, top5 = topk_correct_fraction(logits, labels, (1, 5))
        # metrics are already seq-invariant (psum'd cls -> same logits);
        # average over data shards like the DDP step's reduce_tensor
        loss, top1, top5 = lax.pmean((loss, top1, top5), DATA_AXIS)
        direction, new_opt = state.tx.update(
            grads, state.opt_state, state.params
        )
        lr = lr_schedule(state.step)
        updates = jax.tree_util.tree_map(lambda u: -lr * u, direction)
        params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1,
            params=params,
            batch_stats=state.batch_stats,
            opt_state=new_opt,
        )
        metrics = {
            "loss": loss,
            "top1": top1 * 100.0,
            "top5": top5 * 100.0,
            "lr": jnp.asarray(lr, jnp.float32),
        }
        return new_state, metrics

    # Ulysses' all-to-all output sharding defeats the replication
    # checker, so this step runs with it off — via the same
    # version-portable helper every other dptpu step uses
    sharded = shard_map_nocheck(
        step,
        mesh,
        in_specs=(P(), P(DATA_AXIS)),
        out_specs=(P(), P()),
    )
    return jax.jit(
        sharded, donate_argnums=0, compiler_options=tpu_compiler_options()
    )
