"""Forward and backward of ``token_model.held_expert_outputs`` alone at the
three expert cells' shapes on the chip, under seeded uniform routing: the
function as the step gets it (the compact buffer as straight-line code, the
fallback's part added under a ``cond``), the compact rows with no ``cond``
anywhere, both paths under one ``cond`` (PR 42's form, and two more ways to
rematerialise its compact branch), the worst-case path as PR 41 ran it,
and the worst case as an overflowing step pays for it (every slot on a held
expert: the rematerialised fallback). Beside them what chose the way back
and the constant: the compact rows with other ways back (``WAYS_BACK``) and
at other multiples of the uniform share. Then the layer as a block runs it:
under a rematerialisation (``jax.checkpoint``, as ``token_model.rematerialised``
wraps a block) that keeps nothing, the expert layer's class
(``token_model.expert_residuals``) and parts of it, with the milliseconds
each saves for a GB it holds: what places the class among a model's
``residual_classes``. Milliseconds (median of fenced calls of the gradient
for ``x``, the three matrices and the weights) and the rows each form moves.
Refuses to run off the chip.
``python scripts/bench_experts.py [lfm2|joyai|trinity] ... [block]``
(``block``: the rematerialised rows alone)
"""

import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from dptpu.models import token_model

SHAPES = {  # tokens, experts a token, experts, held, hidden, expert width
    "lfm2": (16384, 4, 32, 8, 2048, 1792),
    "joyai": (8192, 8, 256, 8, 2048, 768),
    "trinity": (8192, 8, 128, 16, 2048, 1024),
}
# what a block's rematerialisation may keep of the layer: nothing, the
# first two products, those and the third, those and the gathered rows,
# the class as ``expert_residuals`` names it (all four)
KEPT = {"nothing": (), "gate_up": ("expert_gate", "expert_up"),
        "gate_up_out": ("expert_gate", "expert_up", "expert_out"),
        "rows_gate_up": ("expert_rows", "expert_gate", "expert_up"),
        "the_class": token_model.COMPACT_RESIDUALS}
WIDE = {"expert_rows", "expert_out"}  # at the hidden size; the others at the expert's
MULTIPLES = (1.0, 1.25, 1.5, 2.0, 3.0)


def timed(fn, *args, calls=8):
    jax.block_until_ready(fn(*args))  # compiles
    times = []
    for _ in range(calls):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - start)
    return 1e3 * statistics.median(times)


def scatter_back(scaled, token, tokens):
    """Each row added into its token: what ``compact_outputs`` does."""
    return jnp.zeros((tokens, scaled.shape[-1]), jnp.float32).at[token].add(
        scaled)


def sorted_segment_back(scaled, token, tokens):
    """The rows sorted by token first (a second sort, of ``cap`` keys, and
    a gather of ``cap`` rows), then a sum over sorted segments."""
    by_token = jnp.argsort(token)
    return jax.ops.segment_sum(scaled[by_token], token[by_token], tokens,
                               indices_are_sorted=True)


WAYS_BACK = {"scatter_add": scatter_back,
             "sorted_segment_sum": sorted_segment_back}


def compact_with(way_back, cap, x, weights, w1, w3, w2, order, sizes):
    """``token_model.compact_outputs`` with its way back replaced."""
    tokens, k = weights.shape
    slot = order[:cap]
    token = slot // k
    in_a_run = jnp.arange(cap)[:, None] < jnp.sum(sizes)
    out, _ = token_model._grouped_swiglu(x[token], in_a_run, w1, w3, w2,
                                         sizes)
    weight = weights.reshape(-1)[slot].astype(out.dtype)
    scaled = out.astype(jnp.float32) * weight.astype(jnp.float32)[:, None]
    return way_back(scaled, token, tokens).astype(x.dtype)


def grad_of(form):
    """The gradient of a sum of squares of ``form(x, weights, w1, w3, w2,
    chosen)``'s output for everything the layer differentiates."""
    def loss(x, weights, w1, w3, w2, chosen):
        return jnp.sum(form(x, weights, w1, w3, w2, chosen)
                       .astype(jnp.float32) ** 2)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))


def as_a_block_runs_it(form, names):
    """``form`` behind an elementwise step (a block's norm stands before
    the layer: something for the re-run to make again), rematerialised on
    the way back but for ``names``."""
    policy = jax.checkpoint_policies.save_only_these_names(
        *names) if names else None

    def block(x, *rest):
        return form(jnp.tanh(x), *rest)
    return jax.checkpoint(block, policy=policy)


def block_rows(whole, worst_case, args, crowded, cap, hidden, width):
    """The layer under a block's rematerialisation for each of ``KEPT``:
    ms, the bytes kept, ms saved a GB kept against keeping nothing; and
    the class kept on an overflowing step (the fallback keeps nothing)."""
    rows = {}
    want = grad_of(as_a_block_runs_it(worst_case, ()))(*args)
    for label, names in KEPT.items():
        fn = grad_of(as_a_block_runs_it(whole, names))
        kept = 2 * cap * sum(hidden if n in WIDE else width for n in names)
        rows[label] = {"ms": timed(fn, *args), "kept_bytes": kept,
                       "gap_to_worst_case": gap(fn(*args), want)}
        if names:
            rows[label]["ms_saved_a_gb"] = (
                rows["nothing"]["ms"] - rows[label]["ms"]) / (kept / 1e9)
        print("block", label, rows[label], flush=True)
    for label in ("nothing", "the_class"):
        rows[label]["ms_overflowing"] = timed(
            grad_of(as_a_block_runs_it(whole, KEPT[label])), *crowded)
        print("block", label, "overflowing", rows[label]["ms_overflowing"],
              flush=True)
    return rows


def gap(got, want):
    return max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32)))
                     / jnp.max(jnp.abs(b.astype(jnp.float32))))
               for a, b in zip(got, want))


def main(names):
    only_block = "block" in names
    names = [n for n in names if n != "block"] or list(SHAPES)
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs the chip, found {device.platform}")
    results = {"device": device.device_kind}
    for name in names:
        tokens, k, experts, count, hidden, width = SHAPES[name]
        keys = jax.random.split(jax.random.PRNGKey(0), 6)
        x = jax.random.normal(keys[0], (tokens, hidden), jnp.bfloat16)
        w1, w3 = (0.02 * jax.random.normal(key, (count, hidden, width),
                                           jnp.bfloat16) for key in keys[1:3])
        w2 = 0.02 * jax.random.normal(keys[3], (count, width, hidden),
                                      jnp.bfloat16)
        # k distinct experts a token, uniform over all of them
        _, chosen = lax.top_k(jax.random.uniform(keys[4], (tokens, experts)),
                              k)
        weights = jax.random.uniform(keys[5], (tokens, k), jnp.float32)
        cap = token_model.held_row_cap(tokens, k, count, experts)
        uniform = tokens * k * count // experts
        args = (x, weights, w1, w3, w2, chosen)
        held = int(jnp.sum(chosen < count))
        row = {"rows": {"worst_case": tokens * k, "compact": cap,
                        "uniform_share": uniform, "held_this_routing": held}}

        def whole(x, weights, w1, w3, w2, chosen):
            return token_model.held_expert_outputs(
                x, chosen, weights, w1, w3, w2, 0, experts)[0]

        def direct(path, x, weights, w1, w3, w2, chosen):
            return path(x, weights, w1, w3, w2,
                        *token_model.sorted_slots(chosen, 0, count))

        def under_cond(compact, x, weights, w1, w3, w2, chosen):
            order, sizes = token_model.sorted_slots(chosen, 0, count)
            return lax.cond(jnp.sum(sizes) <= cap, compact,
                            jax.checkpoint(token_model.worst_case_outputs),
                            x, weights, w1, w3, w2, order, sizes)

        compact = functools.partial(token_model.compact_outputs, cap=cap)
        forms = {
            "held_expert_outputs": whole,
            "cond_round_both_paths": functools.partial(
                under_cond, jax.checkpoint(
                    compact,
                    policy=jax.checkpoint_policies.save_only_these_names(
                        *token_model.COMPACT_RESIDUALS))),
            "cond_compact_kept_whole": functools.partial(under_cond, compact),
            "cond_compact_rematerialised_whole": functools.partial(
                under_cond, jax.checkpoint(compact)),
            "compact_no_cond": functools.partial(direct, compact),
            "worst_case_as_the_parent": functools.partial(
                direct, token_model.worst_case_outputs),
        }
        want = grad_of(forms["worst_case_as_the_parent"])(*args)
        # an overflowing step: every slot on a held expert
        crowded = (x, weights, w1, w3, w2, chosen % count)
        row["under_a_blocks_rematerialisation"] = block_rows(
            whole, forms["worst_case_as_the_parent"], args, crowded, cap,
            hidden, width)
        if only_block:
            results[name] = row
            continue
        for label, form in forms.items():
            fn = grad_of(form)
            row[label] = {"ms": timed(fn, *args),
                          "gap_to_worst_case": gap(fn(*args), want)}
            print(name, label, row[label], flush=True)
        row["held_expert_outputs_overflowing"] = {
            "ms": timed(grad_of(whole), *crowded), "rows_held": tokens * k}
        print(name, "overflowing", row["held_expert_outputs_overflowing"],
              flush=True)
        for label, way_back in WAYS_BACK.items():
            form = functools.partial(direct, functools.partial(
                compact_with, way_back, cap))
            fn = grad_of(form)
            row[f"way_back.{label}"] = {
                "ms": timed(fn, *args),
                "gap_to_worst_case": gap(fn(*args), want)}
            print(name, label, row[f"way_back.{label}"], flush=True)
        for multiple in MULTIPLES:
            rows = min(tokens * k, -(-int(multiple * uniform)
                                     // token_model.ROW_TILE)
                       * token_model.ROW_TILE)
            if rows < held:
                continue  # this routing would not fit: nothing to time
            form = functools.partial(direct, functools.partial(
                token_model.compact_outputs, cap=rows))
            row[f"compact_at_{multiple}x"] = {
                "rows": rows, "ms": timed(grad_of(form), *args)}
            print(name, multiple, row[f"compact_at_{multiple}x"], flush=True)
        results[name] = row
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/bench_experts.json", "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results))


if __name__ == "__main__":
    main(sys.argv[1:])
