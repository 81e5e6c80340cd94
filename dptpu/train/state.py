"""Train state and the torch-semantics optimizer.

One pytree carries everything the reference splits across mutable objects
(model params + BN buffers, ``optimizer.param_groups`` state, epoch counter):
params, batch_stats, optimizer state, and the global step. The checkpoint
payload (SURVEY.md §3.5) serializes this tree plus bookkeeping.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    batch_stats: Any
    opt_state: Any
    apply_fn: Callable = struct.field(pytree_node=False)
    tx: optax.GradientTransformation = struct.field(pytree_node=False)


def make_optimizer(
    momentum: float = 0.9,
    weight_decay: float = 1e-4,
    name: str = "sgd",
    sumsq_reduce=None,
    betas=(0.9, 0.999),
    eps: float = 1e-8,
) -> optax.GradientTransformation:
    """Build the lr-less optimizer direction chain.

    ``name`` selects the recipe (``--optimizer`` / ``DPTPU_OPT``):

    * ``sgd`` (default) — torch-exact SGD semantics
      (imagenet_ddp.py:133-135): weight decay folds *into the gradient
      before* the momentum accumulation (``g += wd·p``; ``buf = m·buf +
      g``; ``p -= lr·buf``), and decays **every** parameter —
      conv/dense kernels, biases, and BN scale/shift alike.
    * ``lars`` / ``lamb`` — the large-batch layer-wise trust-ratio
      optimizers (dptpu/ops/optimizers.py); these follow their papers'
      skip list instead (no decay/trust on ndim<2 leaves). ``momentum``
      feeds LARS's momentum; LAMB keeps its Adam betas.
    * ``adamw`` — Adam's bias-corrected moments (``betas``, ``eps``)
      with DECOUPLED weight decay (``p -= lr·(m̂/(√v̂ + eps) + wd·p)``,
      torch ``AdamW``), the decay on the same skip list: matrices only,
      not norms' scales or other vectors. Two moments: 16 bytes a
      parameter with the float32 parameter and gradient.

    Every chain yields the un-scaled direction; the train step
    multiplies by ``-lr(state.step)`` itself (torch's
    apply-lr-after-momentum), so the LR schedule is a pure function of
    the checkpointed global step — restart at ``--start-epoch N`` or
    resume lands on exactly the reference's epoch-N LR instead of an
    optimizer-internal count that resets to 0.

    ``sumsq_reduce`` threads the weight-update-sharding norm completer
    into the trust-ratio stage (see dptpu/parallel/zero.py); ignored by
    sgd, whose update is purely elementwise.
    """
    if name == "sgd":
        return optax.chain(
            optax.add_decayed_weights(weight_decay),
            optax.trace(decay=momentum, nesterov=False),
        )
    if name == "lars":
        from dptpu.ops.optimizers import lars

        return lars(
            momentum=momentum,
            weight_decay=weight_decay,
            sumsq_reduce=sumsq_reduce,
        )
    if name == "lamb":
        from dptpu.ops.optimizers import lamb

        return lamb(weight_decay=weight_decay, sumsq_reduce=sumsq_reduce)
    if name == "adamw":
        from dptpu.ops.optimizers import adamw

        return adamw(b1=betas[0], b2=betas[1], eps=eps,
                     weight_decay=weight_decay)
    raise ValueError(
        f"unknown optimizer {name!r}: expected 'sgd', 'lars', 'lamb' "
        f"or 'adamw'"
    )


def map_momentum(opt_state, trace_fn, leaf_fn=None):
    """Structurally rebuild an optax chain state: each ``TraceState``'s
    momentum trace maps through ``trace_fn(trace)``; every other leaf
    maps through ``leaf_fn`` (identity when None).

    Structural — matching by tree position, never by shape — because a
    replicated param's shape can collide with a sharded one's. The ONE
    walk shared by GSPMD sharding trees (dptpu/parallel/gspmd.py),
    torch-checkpoint momentum restore (dptpu/train/checkpoint.py), and
    any future optimizer-state surgery.
    """
    import optax

    def rec(node):
        if isinstance(node, optax.TraceState):
            return optax.TraceState(trace=trace_fn(node.trace))
        if isinstance(node, (tuple, list)) and not hasattr(node, "shape"):
            children = [rec(c) for c in node]
            if hasattr(node, "_fields"):  # NamedTuple (optax states)
                return type(node)(*children)
            return children if isinstance(node, list) else tuple(children)
        if leaf_fn is None:
            return node
        return jax.tree_util.tree_map(leaf_fn, node)

    return rec(opt_state)


def create_train_state(
    rng: jax.Array,
    model,
    tx: optax.GradientTransformation,
    input_shape=(1, 224, 224, 3),
    input_dtype=jnp.float32,
    initial_step: int = 0,
    variables=None,
) -> TrainState:
    """Initialize params/BN state with a dummy batch and build the state.

    ``initial_step`` seeds the global step for fresh runs that start at a
    later epoch (``--start-epoch`` without ``--resume``,
    imagenet_ddp.py:35-36): the LR schedule reads this step.

    ``variables`` overrides the random init with an existing
    ``{"params", "batch_stats"}`` tree — the ``--pretrained`` path
    (imagenet_ddp.py:109-111), fed by
    ``dptpu.models.pretrained.load_pretrained_variables``.
    """
    if variables is None:
        variables = model.init(
            rng, jnp.zeros(input_shape, input_dtype), train=False
        )
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    return TrainState(
        step=jnp.asarray(initial_step, jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=tx.init(params),
        apply_fn=model.apply,
        tx=tx,
    )
