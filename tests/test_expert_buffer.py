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

import collections

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


@pytest.mark.parametrize("experts_class", [True, False],
                         ids=["experts-kept", "experts-made-again"])
@pytest.mark.parametrize("case", TOTALS)
def test_a_rematerialised_expert_layer_under_the_cells_kept_names(
        case, experts_class, monkeypatch):
    total, fits = TOTALS[case]
    x, gate = _classed_input(total)
    # every class the cell's blocks keep, as the step names them, with
    # the expert layer's own and (a budget one byte short of it) without
    classes = lfm2.residual_classes(CONFIG, (2, TOKENS // 2), jnp.float32)
    assert classes[3][1][:4] == token_model.COMPACT_RESIDUALS
    kept = lfm2.Lfm2(CONFIG, residual_budget=10 ** 12).kept(2)
    assert "ffn_gate" in kept.names and len(kept.classes) == 5
    if not experts_class:
        kept = lfm2.Lfm2(CONFIG, residual_budget=sum(
            size for _, _, size in classes[:4]) - 1).kept(2)
        assert len(kept.classes) == 3 and "conv_in_proj" in kept.names
    assert ("expert_gate" in kept.names) is experts_class
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


# ------------------- the expert layer's class, kept through a block's re-run


def _equations(jaxpr, branches=True):
    """``jaxpr``'s equations and those of every jaxpr under it (a
    rematerialised function's body, a ``custom_vjp``'s rules and, with
    ``branches``, a ``cond``'s)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "cond" and not branches:
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, branches)


def _primitives(jaxpr) -> collections.Counter:
    """How often each primitive stands in ``jaxpr`` and under it."""
    return collections.Counter(
        eqn.primitive.name for eqn in _equations(jaxpr))


def test_the_class_reckons_the_buffers_own_rows():
    """``expert_residuals``: rows and the third product at the hidden
    size, the first two at the expert's width, at ``held_row_cap``'s rows
    (the three cells', and every slot where a layer holds all its
    experts), and each token's chosen experts, over the layers."""
    def routing(experts, count, k, width):
        return token_model.Routing(experts, (0, count), k, True, 1e-6, 1.0,
                                   True, width)

    def size(*args):
        what, names, size = token_model.expert_residuals(*args)
        assert what == "expert rows and products"
        # the four arrays and the routing they were made under
        assert names == (*token_model.COMPACT_RESIDUALS, "expert_chosen")
        return size

    assert size(routing(32, 8, 4, 1792), 16384, 2048, 4, jnp.bfloat16) \
        == 4 * (32768 * 2 * (2048 + 1792) * 2 + 16384 * 4 * 4) \
        == 2_014_314_496
    assert size(routing(256, 8, 8, 768), 8192, 2048, 5, jnp.bfloat16) \
        == 5 * (4096 * 2 * (2048 + 768) * 2 + 8192 * 8 * 4) == 231_997_440
    assert size(routing(128, 16, 8, 1024), 8192, 2048, 4, jnp.bfloat16) \
        == 4 * (16384 * 2 * (2048 + 1024) * 2 + 8192 * 8 * 4) \
        == 806_354_944
    # all experts held: no compact buffer, the names on the one there is
    assert size(routing(8, 8, 2, 48), 128, 64, 3, jnp.float32) \
        == 3 * (256 * 2 * (64 + 48) * 4 + 128 * 2 * 4)
    assert size(routing(8, 2, 2, 8), 1024, 16, 0, jnp.float32) == 0


@pytest.mark.parametrize("case", TOTALS)
def test_a_block_that_keeps_the_class_makes_no_grouped_product_again(case):
    """The layer under a block's rematerialisation with the class kept
    and with nothing kept: the same output and gradients to the bit on
    either side of the buffer, and the gradient's program holds the
    compact path's gather and three forward products once, not twice.
    The fallback names nothing: its products are made again at any
    policy, and no conditional gives anything but the fallback's result
    and the cotangents: no ``[tokens x k, *]`` array, no ``[cap, *]``
    array."""
    total, fits = TOTALS[case]
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(TOKENS, HIDDEN), jnp.float32)
    weights = jnp.asarray(rng.rand(TOKENS, K), jnp.float32)
    chosen = _slots(total)
    args = (x, *_weights(), weights)

    def layer(x, w1, w3, w2, weights):
        # the layer closes its block, behind something for the re-run to
        # make again
        out, _, compact = token_model.held_expert_outputs(
            jnp.tanh(x), chosen, weights, w1, w3, w2, 0, EXPERTS)
        return out, compact

    def grad(names):
        policy = jax.checkpoint_policies.save_only_these_names(*names) \
            if names else None
        block = jax.checkpoint(layer, policy=policy)

        def loss(*args):
            out, compact = block(*args)
            return jnp.sum(out ** 2), compact
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                  has_aux=True)

    nothing, kept = grad(()), grad(token_model.COMPACT_RESIDUALS)
    (want_loss, compact), want = jax.jit(nothing)(*args)
    (got_loss, _), got = jax.jit(kept)(*args)
    assert int(compact) == fits and float(got_loss) == float(want_loss)
    for g, w in zip(got, want):
        _close(g, w, exact=True)
    # the compact path's products: 3 forward, 3 in the block's re-run, 6
    # on the way back and 3 more that nothing reads (its way back takes
    # ``jax.vjp`` of each of its steps, which traces the step's forward:
    # the compiler drops them); the fallback's: 3 forward, 3 + 6 on its
    # way back, which makes its forward again. Kept, the re-run's go
    made_again = _primitives(jax.make_jaxpr(nothing)(*args).jaxpr)
    held = _primitives(jax.make_jaxpr(kept)(*args).jaxpr)
    assert made_again["ragged_dot_general"] == (3 + 3 + 6 + 3) + (3 + 9)
    assert held["ragged_dot_general"] == (3 + 6 + 3) + (3 + 9)
    assert held["gather"] == made_again["gather"] - 1  # the rows'
    # two conditionals, forward and backward, and they hold the fallback
    # alone: what the first hands out is its result, what the second
    # gives are the five cotangents; no [cap, *] array is a result
    for program in (nothing, kept):
        conds = [eqn for eqn in _equations(
            jax.make_jaxpr(program)(*args).jaxpr, branches=False)
            if eqn.primitive.name == "cond"]
        assert [[v.aval.shape for v in eqn.outvars] for eqn in conds] == [
            [(TOKENS, HIDDEN)],
            [(TOKENS, HIDDEN), (TOKENS, K), (HELD, HIDDEN, WIDTH),
             (HELD, HIDDEN, WIDTH), (HELD, WIDTH, HIDDEN)]]


def _both_branches(name):
    """A one-expert-layer model of each family on 1,024 tokens (2 of 8
    experts held: a buffer of 1,024 of the 2,048 slots)."""
    from dptpu.models import joyai, trinity

    if name == "lfm2":
        return lfm2.Lfm2(lfm2.Lfm2Config(
            vocab_size=64, hidden_size=HIDDEN, intermediate_size=24,
            moe_intermediate_size=WIDTH, num_hidden_layers=1,
            layer_types=("conv",), num_dense_layers=0,
            num_attention_heads=2, num_key_value_heads=1,
            num_experts=EXPERTS, num_experts_per_tok=K,
            sequence_length=TOKENS // 2).held(experts=(0, HELD)))
    if name == "joyai":
        return joyai.Joyai(joyai.JoyaiConfig(
            vocab_size=64, hidden_size=HIDDEN, intermediate_size=24,
            moe_intermediate_size=WIDTH, num_hidden_layers=2,
            num_attention_heads=2, num_key_value_heads=2, q_lora_rank=8,
            kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, n_routed_experts=EXPERTS, num_experts_per_tok=K,
            num_nextn_predict_layers=0, sequence_length=TOKENS // 2).held(
                layers=(1, 1), experts=(0, HELD)))
    return trinity.Trinity(trinity.TrinityConfig(
        vocab_size=64, hidden_size=HIDDEN, intermediate_size=24,
        moe_intermediate_size=WIDTH, num_hidden_layers=1,
        layer_types=("sliding_attention",), sliding_window=64,
        num_dense_layers=0, num_attention_heads=2, num_key_value_heads=1,
        head_dim=8, num_experts=EXPERTS, num_experts_per_tok=K,
        sequence_length=TOKENS // 2).held(experts=(0, HELD)))


@pytest.mark.parametrize("overflows", [False, True],
                         ids=["fits-the-buffer", "overflows-it"])
@pytest.mark.parametrize("name", ["lfm2", "joyai", "trinity"])
def test_each_models_step_is_the_same_with_the_class_kept(
        name, overflows, capsys):
    """Loss and every gradient of a model whose one expert layer has a
    compact buffer, with every class kept and at budget 0, on a routing
    that fits the buffer and on one that overflows it (the selection
    bias puts every token on the two held experts)."""
    net = _both_branches(name)
    tokens = jnp.asarray(np.random.RandomState(8).randint(
        0, 64, (2, TOKENS // 2)), jnp.int32)
    variables = net.init(jax.random.PRNGKey(0), tokens)
    bias = jnp.where(jnp.arange(EXPERTS) < HELD, 10.0 * overflows, 0.0)
    buffers = jax.tree_util.tree_map(lambda leaf: bias,
                                     variables["batch_stats"])

    def objective(net):
        def of(params):
            sums = net.apply({"params": params, "batch_stats": buffers},
                             tokens, labels=tokens,
                             mask=jnp.ones(tokens.shape, jnp.float32))
            return sums["loss_sum"] / TOKENS, sums
        return of

    def loss(net):
        return jax.value_and_grad(objective(net), has_aux=True)

    every_class = net.clone(residual_budget=2**62)
    assert "expert rows and products" in every_class.kept(2).classes
    (want_loss, sums), want = jax.jit(loss(net))(variables["params"])
    (got_loss, _), got = jax.jit(loss(every_class))(variables["params"])
    assert int(sums["moe_compact"]) == (not overflows)
    if overflows:  # every slot of every token on a held expert
        assert int(sums["moe_counts"].sum()) == TOKENS * K
    assert float(got_loss) == float(want_loss)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=0,
            atol=2e-6 * float(np.abs(a).max()), err_msg=str(path))
    held = _primitives(jax.make_jaxpr(loss(every_class))(
        variables["params"]).jaxpr)
    made_again = _primitives(jax.make_jaxpr(loss(net))(
        variables["params"]).jaxpr)
    # the compact path's products: 3 forward, 3 in the block's re-run, 6
    # on the way back (and 3 that nothing reads); the fallback's: 3
    # forward, 9 on its way back and, where the block needs the layer's
    # output on its way back (Trinity's norm behind it), 3 in the re-run.
    # Kept, the compact path's re-run has none
    assert made_again["ragged_dot_general"] == 27 + 3 * (name == "trinity")
    assert held["ragged_dot_general"] == made_again["ragged_dot_general"] - 3
    # and the routing the kept rows were sorted under is kept with them:
    # a token whose top k flipped between the first pass and the re-run
    # would shift every kept row behind it
    jax.ad_checkpoint.print_saved_residuals(
        lambda params: objective(every_class)(params)[0],
        variables["params"])
    saved = capsys.readouterr().out
    assert f"i32[{TOKENS},{K}] named 'expert_chosen'" in saved
    assert f"f32[{CAP},{HIDDEN}] named 'expert_rows'" in saved
