"""Large-batch optimizers: LARS and LAMB with layer-wise trust ratios.

The reference trains with plain momentum SGD at batch 256-1024
(imagenet_ddp.py:133-135). Every PAPERS.md system trains ImageNet in
minutes by scaling the batch to 32k-64k, and plain SGD diverges there:
the ratio ``||w_l|| / ||update_l||`` varies by orders of magnitude
across layers, so any single LR overshoots some layer. LARS (You et
al., arXiv:1708.03888 — the optimizer behind the 15-minute ResNet-50,
arXiv:1711.04325) and LAMB (You et al., arXiv:1904.00962) fix this with
a per-layer **trust ratio** ``||w_l|| / ||u_l||`` that rescales each
layer's update to the layer's own weight scale.

Both are built in this repo's optimizer convention (dptpu/train/state.py
``make_optimizer``): the transform chain emits an **lr-less direction**
and the compiled train step multiplies by ``-lr(step)`` — so the LR
schedule stays a pure function of the checkpointed global step.

Weight-update-sharding hook (arXiv:2004.13336, dptpu/parallel/zero.py):
the ONLY non-elementwise piece of either optimizer is the pair of
per-layer norms. ``scale_by_trust_ratio`` therefore routes every
per-leaf sum-of-squares through an injectable ``sumsq_reduce`` — under
ZeRO-style sharding each device computes partial sums on its local
shard and the reducer completes them with ONE small psum (a [L, 2]
stack, a few hundred floats), so the whole optimizer state and all its
math stay 1/N per device.

Skip list: following both papers (and every reference implementation),
1-D parameters — biases, BatchNorm/LayerNorm scale and shift — are
excluded from the trust ratio AND from weight decay; they take the
plain (momentum/adam) update. ``ndim >= 2`` is the membership test.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax


class ScaleByTrustRatioState(NamedTuple):
    """Per-update trust-ratio summary, carried in the optimizer state so
    the compiled step can surface it as ``Opt/*`` metrics without
    recomputing norms: min/mean/max over the trusted (ndim>=2) leaves.
    Scalars, so they stay replicated under every sharding rule
    (``zero1_state_specs`` finds no divisible dim)."""

    trust_min: jnp.ndarray
    trust_mean: jnp.ndarray
    trust_max: jnp.ndarray


def _trusted(leaf) -> bool:
    """Trust-ratio / weight-decay membership: matrices and conv kernels
    yes; biases and norm scale/shift (ndim<=1) no."""
    return getattr(leaf, "ndim", 0) >= 2


def trust_mask(params):
    """Pytree of bools marking the leaves that get weight decay and the
    trust ratio (the ``optax.masked`` mask for LARS/LAMB)."""
    return jax.tree_util.tree_map(_trusted, params)


def scale_by_trust_ratio(
    trust_coefficient: float = 0.001,
    eps: float = 0.0,
    sumsq_reduce: Optional[Callable] = None,
):
    """Layer-wise trust-ratio scaling: ``u_l <- r_l * u_l`` with
    ``r_l = trust_coefficient * ||w_l|| / (||u_l|| + eps)``.

    ``r_l`` falls back to 1.0 whenever either norm is zero (fresh zero
    init, dead gradient) — the LARS paper's guard, which also covers the
    skip list: ndim<2 leaves always scale by exactly 1.0.

    ``sumsq_reduce`` completes partial norms under sharding: it receives
    a params-structured pytree whose every leaf is a length-2 f32 vector
    ``[sum(w^2), sum(u^2)]`` computed over the LOCAL shard, and must
    return the tree with globally-completed sums. None (default) means
    the local values are already global (replicated params).
    """

    def init_fn(params):
        del params
        one = jnp.ones((), jnp.float32)
        return ScaleByTrustRatioState(one, one, one)

    def update_fn(updates, state, params=None):
        if params is None:
            raise ValueError(
                "scale_by_trust_ratio requires params "
                "(optax update(updates, state, params))"
            )
        pairs = jax.tree_util.tree_map(
            lambda w, u: jnp.stack([
                jnp.sum(jnp.square(w.astype(jnp.float32))),
                jnp.sum(jnp.square(u.astype(jnp.float32))),
            ]),
            params,
            updates,
        )
        if sumsq_reduce is not None:
            pairs = sumsq_reduce(pairs)

        def ratio(pair):
            wn = jnp.sqrt(pair[0])
            un = jnp.sqrt(pair[1])
            r = trust_coefficient * wn / (un + eps)
            return jnp.where((wn > 0.0) & (un > 0.0), r, 1.0)

        ratios = jax.tree_util.tree_map(ratio, pairs)
        scaled = jax.tree_util.tree_map(
            lambda u, r, w: (u * r).astype(u.dtype) if _trusted(w) else u,
            updates,
            ratios,
            params,
        )
        trusted = [
            r
            for r, w in zip(
                jax.tree_util.tree_leaves(ratios),
                jax.tree_util.tree_leaves(params),
            )
            if _trusted(w)
        ]
        if trusted:
            vec = jnp.stack(trusted)
            new_state = ScaleByTrustRatioState(
                jnp.min(vec), jnp.mean(vec), jnp.max(vec)
            )
        else:  # degenerate all-1D model: every ratio is identically 1
            new_state = init_fn(None)
        return scaled, new_state

    return optax.GradientTransformation(init_fn, update_fn)


def lars(
    momentum: float = 0.9,
    weight_decay: float = 1e-4,
    trust_coefficient: float = 0.001,
    nesterov: bool = False,
    sumsq_reduce: Optional[Callable] = None,
) -> optax.GradientTransformation:
    """LARS direction (arXiv:1708.03888), WITHOUT the learning rate.

    Paper ordering: ``g_l <- g_l + wd*w_l`` (trusted leaves only), then
    ``r_l = tc * ||w_l|| / ||g_l||`` (the denominator already carries
    the decay term, matching eq. 6), then ``buf = m*buf + r_l*g_l``; the
    train step applies ``w -= lr*buf``. Skip-list leaves get plain
    momentum SGD with no decay.
    """
    return optax.chain(
        optax.masked(optax.add_decayed_weights(weight_decay), trust_mask),
        scale_by_trust_ratio(
            trust_coefficient=trust_coefficient, sumsq_reduce=sumsq_reduce
        ),
        optax.trace(decay=momentum, nesterov=nesterov),
    )


def lamb(
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-6,
    weight_decay: float = 1e-4,
    sumsq_reduce: Optional[Callable] = None,
) -> optax.GradientTransformation:
    """LAMB direction (arXiv:1904.00962), WITHOUT the learning rate:
    bias-corrected Adam moments → decoupled weight decay (trusted leaves)
    → unit trust ratio ``||w_l|| / ||u_l||``. Skip-list leaves take the
    plain Adam update with no decay and ratio 1."""
    return optax.chain(
        optax.scale_by_adam(b1=b1, b2=b2, eps=eps),
        optax.masked(optax.add_decayed_weights(weight_decay), trust_mask),
        scale_by_trust_ratio(trust_coefficient=1.0, sumsq_reduce=sumsq_reduce),
    )


def adamw(
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 1e-2,
) -> optax.GradientTransformation:
    """AdamW direction (decoupled weight decay, arXiv:1711.05101),
    WITHOUT the learning rate: bias-corrected Adam moments, then
    ``+ wd * p`` on the skip list's members (matrices; norms' scales and
    other vectors decay nowhere). The train step's ``p -= lr * u`` makes
    it torch ``AdamW``'s ``p -= lr * (m_hat / (sqrt(v_hat) + eps) +
    wd * p)``. Elementwise, so it needs no ``sumsq_reduce``."""
    return optax.chain(
        optax.scale_by_adam(b1=b1, b2=b2, eps=eps),
        optax.masked(optax.add_decayed_weights(weight_decay), trust_mask),
    )


def trust_ratio_stats(opt_state):
    """Extract the ``ScaleByTrustRatioState`` summary from an optimizer
    state tree, or None when the optimizer has no trust-ratio stage
    (plain SGD). Structural walk, like ``map_momentum``."""
    found = []

    def rec(node):
        if isinstance(node, ScaleByTrustRatioState):
            found.append(node)
            return
        if isinstance(node, (tuple, list)) and not hasattr(node, "shape"):
            for child in node:
                rec(child)

    rec(opt_state)
    if not found:
        return None
    s = found[0]
    return {
        "trust_min": s.trust_min,
        "trust_mean": s.trust_mean,
        "trust_max": s.trust_max,
    }
