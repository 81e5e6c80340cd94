"""A fixture optimizer with two moments: plain Adam in float32."""

import jax
import jax.numpy as jnp


def init(params):
    zeros = lambda: {k: jnp.zeros_like(v) for k, v in params.items()}  # noqa: E731
    return {"mu": zeros(), "nu": zeros(), "count": jnp.zeros((), jnp.int32)}


def update(params, state, grads, lr: float, hyper: dict):
    b1, b2, eps = hyper["b1"], hyper["b2"], hyper["eps"]
    count = state["count"] + 1
    mu, nu, new_p = {}, {}, {}
    for k in params:
        mu[k] = b1 * state["mu"][k] + (1 - b1) * grads[k]
        nu[k] = b2 * state["nu"][k] + (1 - b2) * jnp.square(grads[k])
        m_hat = mu[k] / (1 - b1 ** count)
        v_hat = nu[k] / (1 - b2 ** count)
        new_p[k] = params[k] - lr * m_hat / (jnp.sqrt(v_hat) + eps)
    return new_p, {"mu": mu, "nu": nu, "count": count}


def trace1(state):
    """After one step the first moment is (1 - b1) times the gradient."""
    return state["mu"]


def program_trace1(opt_state):
    import optax

    is_adam = lambda n: isinstance(n, optax.ScaleByAdamState)  # noqa: E731
    found = [n for n in jax.tree_util.tree_leaves(opt_state, is_leaf=is_adam)
             if is_adam(n)]
    if len(found) != 1:
        raise RuntimeError(f"adam expects one optax ScaleByAdamState in the "
                           f"optimizer state, found {len(found)}")
    return found[0].mu


def argv(hyper: dict):
    return ["--optimizer", "adam", "--beta1", repr(hyper["b1"]),
            "--beta2", repr(hyper["b2"])]
