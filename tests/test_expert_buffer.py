"""The expert layer's compact buffer (``token_model.held_expert_outputs``)
against its worst-case path at tiny widths on the CPU: 1,024 tokens top-2
of 8 experts, 2 of them held, hidden 16, width 8. The compact buffer is
1,024 rows (twice the even share of 512: whole row tiles of 512)
where the worst case is 2,048; the held total is put under it, exactly at it, one over it and at the
worst case by choosing the slots (or, through ``SparseExperts``, by a
router whose scores read a token's class).

Tolerances: both paths compute the same float32 products of the same
rows; what differs is the order in which a token's slots are summed (the
worst case's einsum over its k slots, the compact path's scatter-add) and
the order of a matrix's gradient over its run, so 2e-6 of the largest
entry holds the output and every gradient, as ``tests/test_lfm2.py``
holds the layer to its reference. Where the fallback ran the two are the
same program on the same numbers: exactly equal.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dptpu.models import lfm2, token_model

TOKENS, K, EXPERTS, HELD, HIDDEN, WIDTH = 1024, 2, 8, 2, 16, 8
CAP = 1024
# the held total of a case, and whether the compact buffer holds it
TOTALS = {"under-the-buffer": (CAP - 40, 1), "exactly-the-buffer": (CAP, 1),
          "one-over-the-buffer": (CAP + 1, 0),
          "the-worst-case": (TOKENS * K, 0)}


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def test_the_buffer_is_two_even_shares_in_whole_row_tiles():
    cap = token_model.held_row_cap
    assert cap(TOKENS, K, HELD, EXPERTS) == CAP
    # the two cells: 16,384 tokens top-4 of 32 and 8,192 top-8 of 256,
    # eight held (a half and a sixteenth of the worst case's 65,536 rows)
    assert cap(16384, 4, 8, 32) == 32768 and cap(8192, 8, 8, 256) == 4096
    # whole row tiles, up: an even share of 341 slots gets 1,024 rows,
    # not 683
    assert cap(2048, 2, 1, 12) == 1024
    # never more than every slot; a layer that holds all its experts, or
    # half of them and more, or that has under a tile of slots in all,
    # has the worst case for its buffer
    assert cap(2048, 2, 8, 8) == cap(2048, 2, 4, 8) == 4096
    assert cap(128, 2, 2, 8) == 256 and cap(4, 2, 1, 8) == 8


def _weights(seed=0):
    rng = np.random.RandomState(seed)
    w1, w3 = (jnp.asarray(0.3 * rng.randn(HELD, HIDDEN, WIDTH), jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(0.3 * rng.randn(HELD, WIDTH, HIDDEN), jnp.float32)
    return w1, w3, w2


def _slots(total: int, seed=1):
    """``chosen`` ``[TOKENS, K]`` with ``total`` slots on the two held
    experts (0 and 1) and the rest on absent ones, anywhere."""
    rng = np.random.RandomState(seed)
    flat = rng.randint(HELD, EXPERTS, TOKENS * K)
    flat[rng.permutation(TOKENS * K)[:total]] = rng.randint(0, HELD, total)
    return jnp.asarray(flat.reshape(TOKENS, K), jnp.int32)


def _close(got, want, exact: bool):
    got, want = np.asarray(got), np.asarray(want)
    if exact:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want,
                                   atol=2e-6 * np.abs(want).max())


@pytest.mark.parametrize("case", TOTALS)
def test_the_compact_path_gives_the_worst_cases_output_and_gradients(case):
    total, fits = TOTALS[case]
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(TOKENS, HIDDEN), jnp.float32)
    weights = jnp.asarray(rng.rand(TOKENS, K), jnp.float32)
    chosen = _slots(total)
    target = jnp.asarray(rng.randn(TOKENS, HIDDEN), jnp.float32)

    def through(x, w1, w3, w2, weights):
        out, sizes, compact = token_model.held_expert_outputs(
            x, chosen, weights, w1, w3, w2, 0, EXPERTS)
        return jnp.sum(out * target), (out, sizes, compact)

    def worst_case(x, w1, w3, w2, weights):
        # the fallback's branch, called directly
        out = token_model.worst_case_outputs(
            x, weights, w1, w3, w2,
            *token_model.sorted_slots(chosen, 0, HELD))
        return jnp.sum(out * target), out

    args = (x, *_weights(), weights)
    grad = lambda f: jax.jit(jax.value_and_grad(  # noqa: E731
        f, argnums=(0, 1, 2, 3, 4), has_aux=True))
    (_, (out, sizes, compact)), got = grad(through)(*args)
    (_, want_out), want = grad(worst_case)(*args)
    assert int(sizes.sum()) == total and int(compact) == fits
    _close(out, want_out, exact=not fits)
    for g, w in zip(got, want):  # x, w1, w3, w2, the slots' weights
        _close(g, w, exact=not fits)
    # a slot on an absent expert adds nothing to its weight's gradient
    assert not np.asarray(got[4])[np.asarray(chosen) >= HELD].any()


@pytest.mark.parametrize("case", TOTALS)
def test_the_step_counts_the_layers_that_fitted_and_drops_nothing(case):
    total, fits = TOTALS[case]
    x = jnp.ones((TOKENS, HIDDEN), jnp.float32)
    weights = jnp.ones((TOKENS, K), jnp.float32)

    @jax.jit
    def sums(chosen_by_layer):
        loads = [token_model.held_expert_outputs(
            x, chosen, weights, *_weights(), 0, EXPERTS)[1:]
            for chosen in chosen_by_layer]
        return token_model.with_counters(
            {}, loads, TOKENS * K * len(loads), token_model.Kept(), (),
            TOKENS, 0)

    # the case's layer between two that fit with room
    got = sums([_slots(100), _slots(total), _slots(CAP - 1, seed=3)])
    assert int(got["moe_compact"]) == 2 + fits
    assert int(got["moe_layers"]) == 3 and int(got["moe_dropped"]) == 0
    assert got["moe_compact"].dtype == jnp.int32
    assert list(np.asarray(got["moe_counts"]).sum(axis=1)) == [
        100, total, CAP - 1]
    assert int(got["moe_slots"]) == 3 * TOKENS * K


# ------------------------------------------ through the module, rematerialised


CONFIG = lfm2.Lfm2Config(
    vocab_size=256, hidden_size=HIDDEN, intermediate_size=24,
    moe_intermediate_size=WIDTH, num_hidden_layers=4,
    layer_types=("conv", "conv", "full_attention", "conv"),
    num_dense_layers=2, num_attention_heads=2, num_key_value_heads=1,
    num_experts=EXPERTS, num_experts_per_tok=K, use_expert_bias=False,
    sequence_length=TOKENS // 2).held(experts=(0, HELD))
# a token's class is its first three entries: the router sends class 0 to
# experts 0 and 1 (both held), class 1 to 0 and 4 (one held), class 2 to
# 4 and 5 (neither)
PICKS = ((0, 1), (0, 4), (4, 5))


def _classed_input(total: int, seed=4):
    """``x`` ``[2, TOKENS / 2, HIDDEN]`` whose router puts ``total`` slots
    on the held experts, and the gate that reads the classes."""
    both, one = divmod(total, 2)
    classes = np.full(TOKENS, 2)
    classes[:both] = 0
    classes[both:both + one] = 1
    rng = np.random.RandomState(seed)
    rng.shuffle(classes)
    x = rng.randn(TOKENS, HIDDEN).astype(np.float32)
    x[:, :3] = 0.0
    x[np.arange(TOKENS), classes] = 1.0
    gate = np.zeros((HIDDEN, EXPERTS), np.float32)
    gate[:3] = -4.0
    for c, (a, b) in enumerate(PICKS):
        gate[c, a], gate[c, b] = 3.0, 2.0
    return jnp.asarray(x.reshape(2, TOKENS // 2, HIDDEN)), jnp.asarray(gate)


@pytest.mark.parametrize("case", TOTALS)
def test_a_rematerialised_expert_layer_under_the_cells_kept_names(
        case, monkeypatch):
    total, fits = TOTALS[case]
    x, gate = _classed_input(total)
    # every class the cell's blocks keep, as the step names them
    kept = lfm2.Lfm2(CONFIG, residual_budget=10 ** 12).kept(2)
    assert "ffn_gate" in kept.names and len(kept.classes) == 4
    w1, w3, w2 = _weights(seed=5)
    params = {"gate": gate,
              **{f"experts_{e}": {"w1": w1[e], "w3": w3[e], "w2": w2[e]}
                 for e in range(HELD)}}
    target = jnp.asarray(
        np.random.RandomState(6).randn(*x.shape), jnp.float32)

    def loss(layer):
        def of(params, x):
            out, sizes, compact = layer(CONFIG).apply({"params": params}, x)
            return jnp.sum(out * target), (out, sizes, compact)
        return jax.jit(jax.value_and_grad(of, argnums=(0, 1), has_aux=True))

    (_, (out, sizes, compact)), got = loss(token_model.rematerialised(
        token_model.SparseExperts, kept))(params, x)
    assert int(sizes.sum()) == total and int(compact) == fits

    # the same layer, not rematerialised, on a buffer that holds every
    # slot: nothing but the worst-case path
    monkeypatch.setattr(token_model, "held_row_cap",
                        lambda tokens, k, count, experts: tokens * k)
    (_, (want_out, _, always)), want = loss(token_model.SparseExperts)(
        params, x)
    assert int(always) == 1  # one buffer, and it holds anything
    _close(out, want_out, exact=False)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _close(g, w, exact=False)
