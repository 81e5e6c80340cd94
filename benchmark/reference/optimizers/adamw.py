"""Plain AdamW (decoupled weight decay, torch ``AdamW``) in float32:
``m = b1 m + (1 - b1) g``; ``v = b2 v + (1 - b2) g^2``;
``p -= lr (m_hat / (sqrt(v_hat) + eps) + wd p)`` with the bias-corrected
``m_hat = m / (1 - b1^t)``, ``v_hat = v / (1 - b2^t)``; the decay on
matrices only (two axes or more), not on norms' weights or other vectors.

An optimizer module (``benchmark/README.md`` has the contract) gives the
plain update (``init``, ``update``), which part of ITS state is the first
gradient as the optimizer got it (``trace1``), where the program's optax
state keeps the same quantity (``program_trace1``), and the trainer's
arguments that the configuration's ``optimizer`` block turns into
(``argv``).

**Where the moments live.** The training driver (``common.train_steps``)
hands ``update`` its arguments without donating them, so for one call the
old and the new parameters, the old and the new moments, the summed
gradient and the seeded parameters it keeps for the change are all alive:
eight times the parameters' bytes, 16.2 GB for the 507.7 M parameters of
``lfm2-8b-a1b-ep4-bf16`` on a chip of 16.9 GB. The moments therefore rest
in the host's memory between steps (``jax.memory.Space.Host``: pinned
host memory on a TPU, the same memory as everything else on the CPU) and
cross to the device leaf by leaf inside ``update``. The arithmetic is the
plain float32 update either way.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HOST, _DEVICE = jax.memory.Space.Host, jax.memory.Space.Device


def init(params):
    """Both moments at zero, a step count of zero."""
    zeros = lambda: {k: jax.device_put(jnp.zeros_like(v), _HOST)  # noqa: E731
                     for k, v in params.items()}
    return {"mu": zeros(), "nu": zeros(), "count": jnp.zeros((), jnp.int32)}


def update(params, state, grads, lr: float, hyper: dict):
    """One step; ``(new params, new state)``."""
    b1, b2, eps = hyper["b1"], hyper["b2"], hyper["eps"]
    weight_decay = hyper["weight_decay"]
    count = state["count"] + 1
    t = count.astype(jnp.float32)
    mu, nu, new_p = {}, {}, {}
    for k in params:
        m = b1 * jax.device_put(state["mu"][k], _DEVICE) + (1 - b1) * grads[k]
        v = b2 * jax.device_put(state["nu"][k], _DEVICE) \
            + (1 - b2) * jnp.square(grads[k])
        step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        if params[k].ndim >= 2:
            step = step + weight_decay * params[k]
        new_p[k] = params[k] - lr * step
        mu[k], nu[k] = jax.device_put(m, _HOST), jax.device_put(v, _HOST)
    return new_p, {"mu": mu, "nu": nu, "count": count}


def trace1(state):
    """After one step the first moment is ``(1 - b1)`` times the gradient
    as AdamW got it (the decay is decoupled: it is not in it)."""
    return state["mu"]


def program_trace1(opt_state):
    """The same moment in the program's optax chain state: the ``mu`` of
    its one ``ScaleByAdamState``."""
    import optax

    is_adam = lambda n: isinstance(n, optax.ScaleByAdamState)  # noqa: E731
    found = [n for n in jax.tree_util.tree_leaves(opt_state, is_leaf=is_adam)
             if is_adam(n)]
    if len(found) != 1:
        raise RuntimeError(f"adamw expects one optax ScaleByAdamState in "
                           f"the optimizer state, found {len(found)}")
    return found[0].mu


def argv(hyper: dict):
    return ["--optimizer", "adamw", "--beta1", repr(hyper["b1"]),
            "--beta2", repr(hyper["b2"]), "--eps", repr(hyper["eps"]),
            "--wd", repr(hyper["weight_decay"])]
