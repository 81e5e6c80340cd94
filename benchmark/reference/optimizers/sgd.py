"""Plain torch SGD with momentum, in float32, as the program's CLIs
promise it: ``g += wd * p; buf = momentum * buf + g; p -= lr * buf`` on
every parameter, with the global-batch-mean gradient.

An optimizer module (``benchmark/README.md`` has the contract) gives the
plain update (``init``, ``update``), which part of ITS state is the first
gradient as the optimizer got it (``trace1``), where the program's optax
state keeps the same quantity (``program_trace1``), and the trainer's
arguments that the configuration's ``optimizer`` block turns into
(``argv``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init(params):
    """The momentum buffers, at zero."""
    return {k: jnp.zeros_like(v) for k, v in params.items()}


def update(params, state, grads, lr: float, hyper: dict):
    """One step; ``(new params, new state)``."""
    momentum, weight_decay = hyper["momentum"], hyper["weight_decay"]
    new_p, new_b = {}, {}
    for k in params:
        g = grads[k] + weight_decay * params[k]
        new_b[k] = momentum * state[k] + g
        new_p[k] = params[k] - lr * new_b[k]
    return new_p, new_b


def trace1(state):
    """After one step the momentum buffer IS the gradient as SGD got it,
    weight decay included."""
    return state


def program_trace1(opt_state):
    """The same buffers in the program's optax chain state: its one
    ``TraceState``."""
    import optax

    is_trace = lambda n: isinstance(n, optax.TraceState)  # noqa: E731
    found = [n for n in jax.tree_util.tree_leaves(opt_state, is_leaf=is_trace)
             if is_trace(n)]
    if len(found) != 1:
        raise RuntimeError(f"sgd expects one optax TraceState in the "
                           f"optimizer state, found {len(found)}")
    return found[0].trace


def argv(hyper: dict):
    return ["--momentum", repr(hyper["momentum"]),
            "--wd", repr(hyper["weight_decay"])]
