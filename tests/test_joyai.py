"""JoyAI-LLM-Flash (``dptpu/models/joyai.py``) against its plain
reference (``benchmark/reference/joyai_llm_flash.py``) on seeded weights
at toy widths: the loss (both terms) and every gradient leaf, whole and
as a chip's share; three AdamW steps through the step builder; the
shares of the experts add up to the uncut layer with the shared expert
counted once; the vocabulary's slices combine to the whole loss, both
terms; what the multi-token-prediction term reads and what it does not;
the interleaved rotary pairs; the blockwise attention with two head
sizes against the plain one; kept residuals change nothing; every leaf
name through the converter and back; the published configuration's
counts; and ``main_apex`` training it through ``fit()``.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common as reference_common
from benchmark.reference import joyai_llm_flash as reference
from benchmark.reference.optimizers import adamw as reference_adamw
from dptpu.models import joyai, token_model
from dptpu.models.pretrained import (
    _to_torch,
    convert_state_dict,
    torch_key_map,
)
from dptpu.models.registry import _REGISTRY, model_task, register_model
from dptpu.ops import attention as attention_op
from dptpu.ops.attention import causal_attention, plain_causal_attention
from dptpu.ops.loss import token_cross_entropy_sums
from dptpu.train.state import create_train_state, make_optimizer
from dptpu.train.step import make_train_step, token_row_weights

# one dense layer, two with experts (the reference walks them in one
# scan), and the multi-token-prediction module (the checkpoint's layer
# 3); three head sizes that all differ
TINY = joyai.JoyaiConfig(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=2,
    num_key_value_heads=2, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
    n_routed_experts=8, num_experts_per_tok=2, sequence_length=64)
ARCH = "joyai_test_tiny"
if ARCH not in _REGISTRY:
    register_model(joyai.factory(ARCH, TINY))

HYPER = {"name": "adamw", "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1}


def reference_model(config: joyai.JoyaiConfig) -> dict:
    """The reference's ``model`` group for a program configuration."""
    layers_first, layers_held = config.layers_here
    experts_first, experts_held = config.experts_here
    same = ("hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_nextn_predict_layers", "first_k_dense_replace",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "n_shared_experts", "rms_norm_eps",
            "rope_theta", "rope_interleave", "vocab_size",
            "sequence_length", "mtp_loss_weight")
    return {**{key: getattr(config, key) for key in same},
            "layers_first": layers_first, "layers_held": layers_held,
            "mtp_layer": config.num_hidden_layers,
            "router_experts": config.n_routed_experts,
            "experts_first": experts_first, "experts_held": experts_held}


@functools.lru_cache(maxsize=None)
def seeded(config, seed=5, bias_scale=None):
    """``(reference model, weights by checkpoint name, program net,
    program variables)`` for ``config``. The weights are drawn as the
    family's ``weight_spec`` says, on the host (the benchmark's
    ``make_weights`` compiles one program a spec)."""
    model = reference_model(config)
    rng = np.random.RandomState(seed)
    weights = {}
    for name, shape, kind, scale in reference.weight_spec(model):
        if name.endswith("_bias") and bias_scale is not None:
            scale = bias_scale
        assert kind in ("normal", "const"), kind
        weights[name] = np.full(shape, scale, np.float32) \
            if kind == "const" \
            else (scale * rng.randn(*shape)).astype(np.float32)
    net = joyai.Joyai(config)
    template = jax.eval_shape(lambda: net.init(
        jax.random.PRNGKey(0), net.example_input()))
    return model, weights, net, convert_state_dict(ARCH, weights, template)


def rows(config, n=2, seed=0):
    rng = np.random.RandomState(seed)
    length = config.sequence_length
    ids = rng.randint(0, config.vocab_size, (n, length + 1)).astype(np.int32)
    kept = rng.randint(length - length // 16, length + 1, n)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:],
            "mask": np.arange(length)[None] < kept[:, None]}


def program_sums(net, variables, batch, params=None):
    return net.apply(
        {**variables, "params": variables["params"] if params is None
         else params}, jnp.asarray(batch["tokens"]),
        labels=jnp.asarray(batch["labels"]),
        mask=token_row_weights(jnp.asarray(batch["mask"])))


def program_loss(net, variables, batch):
    """The step's loss: the mean over rows of the row's whole loss."""
    def loss(params):
        return program_sums(net, variables, batch, params)["loss_sum"] \
            / batch["tokens"].shape[0]
    return loss


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


# --------------------------------------------------- program == reference --


@pytest.mark.parametrize("share", [
    {}, {"layers": (1, 1), "experts": (2, 4), "vocab": (0, 128)}],
    ids=["whole", "layer1-experts2-5-vocab128"])
def test_loss_every_gradient_leaf_and_three_adamw_steps_match_the_reference(
        share):
    config = TINY.held(**share)
    model, weights, net, variables = seeded(config, bias_scale=0.01)
    batches = [rows(config, seed=s) for s in range(3)]
    lr = 1e-3
    want = reference_common.train_steps(
        functools.partial(reference.loss, model), reference_adamw, HYPER,
        reference.trainable(model), weights, batches, lr=lr, block_rows=1)
    tx = make_optimizer(weight_decay=HYPER["weight_decay"], name="adamw",
                        betas=(HYPER["b1"], HYPER["b2"]), eps=HYPER["eps"])
    state = create_train_state(jax.random.PRNGKey(0), net, tx,
                               variables=variables)
    step = make_train_step(None, jnp.float32, lr_schedule=lambda c: lr,
                           task="tokens")
    losses, mu1 = [], None
    for batch in batches:
        state, metrics = step(state, {k: jnp.asarray(v)
                                      for k, v in batch.items()})
        losses.append(float(metrics["loss"]))
        if mu1 is None:
            # copied out: the next step donates the state it is part of
            mu1 = jax.device_get(
                reference_adamw.program_trace1(state.opt_state))
    # the whole objective, both terms
    assert losses == pytest.approx(want["loss"], abs=1e-5)
    # the step hands the second term on, before its weight, and counts
    # the module's expert layer beside the main layer's
    vocab = np.log(config.vocab_size)
    assert 0.9 * vocab < float(metrics["mtp_loss"]) < 1.2 * vocab
    assert float(metrics["loss"]) - 0.1 * float(metrics["mtp_loss"]) \
        == pytest.approx(vocab, rel=0.2)
    # (whole: two expert layers and the module; the share: one and it)
    assert metrics["moe_counts"].shape == (2 if share else 3,
                                           config.experts_here[1])
    assert int(metrics["moe_dropped"]) == 0
    # every expert layer ran in its buffer (at this size the worst case's)
    assert int(metrics["moe_compact"]) == int(metrics["moe_layers"]) \
        == (2 if share else 3)
    # no call has a window: the tiles walked are the causal triangle's
    assert int(metrics["attention_window_calls"]) == 0
    assert int(metrics["attention_tiles"]) \
        == int(metrics["attention_tiles_causal"]) \
        == int(metrics["attention_calls"])  # a toy row is one tile
    checked, idle = 0, []
    for key, (collection, names, kind) in torch_key_map(
            ARCH, variables).items():
        leaf = lambda tree: _to_torch(np.asarray(functools.reduce(  # noqa: E731
            lambda t, n: t[n], names, tree)), kind)
        if collection != "params":
            # nothing trains the buffer: it only chooses experts, so its
            # gradient is zero to the bit and it stays where it was seeded
            np.testing.assert_array_equal(leaf(state.batch_stats),
                                          weights[key])
            assert not want["delta"][key].any() \
                and not want["trace1"][key].any()
            continue
        # every gradient leaf: Adam's first moment after one step is
        # (1 - b1) times the gradient as the optimizer got it
        grad = want["trace1"][key]
        scale = max(float(np.abs(grad).max()), 1e-9)
        np.testing.assert_allclose(leaf(mu1), grad, atol=2e-5 * scale,
                                   err_msg=key)
        checked += 1
        if not np.abs(grad).max():
            idle.append(key)
            continue
        # Adam's step is the gradient over its own size: an entry whose
        # gradient is all but zero turns on the last bits of a float32
        # sum, so the change is held as a whole leaf, not entry by entry
        delta = leaf(state.params) - weights[key]
        off = np.linalg.norm(delta - want["delta"][key]) \
            / np.linalg.norm(want["delta"][key])
        assert off < 2e-3, (key, off)
    assert checked == len(want["trace1"]) - (2 if share else 3) > 40
    # every leaf got a gradient worth comparing, but for an expert that no
    # token of these 128 chose
    assert len(idle) <= 6 and all(".experts." in k for k in idle), idle


# ------------------------------------------------------------ the shares --


def test_the_shares_add_up_to_the_uncut_layer_the_shared_expert_once():
    model, weights, _, variables = seeded(TINY, bias_scale=0.01)
    f = "model.layers.1.mlp."
    x = jnp.asarray(np.random.RandomState(3).randn(
        2, TINY.sequence_length, TINY.hidden_size).astype(np.float32))
    # the uncut reference: every routed expert, and the shared one
    want = jax.jit(jax.vmap(lambda row: reference.routed_experts(
        model, weights, f, row, "f32", experts=range(8))
        + reference.shared_expert(model, weights, f, row, "f32")))(x)
    whole = variables["params"]["layers_1"]
    stats = {"expert_bias":
             variables["batch_stats"]["layers_1"]["mlp"]["expert_bias"]}

    @jax.jit
    def all_shares(x):
        # what every chip computes alike, counted once
        total = token_model.SwiGLU(
            TINY.moe_intermediate_size, trace_scope="shared_expert",
            keep=None).apply({"params": whole["shared_experts"]}, x)
        counts = []
        for first in (0, 2, 4, 6):
            params = {"gate": whole["mlp"]["gate"],
                      **{f"experts_{e}": whole["mlp"][f"experts_{e}"]
                         for e in range(first, first + 2)}}
            out, sizes, _ = token_model.SparseExperts(
                TINY.held(experts=(first, 2))).apply(
                    {"params": params, "batch_stats": stats}, x)
            total = total + out
            counts.append(sizes)
        return total, jnp.concatenate(counts)

    total, counts = all_shares(x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=5e-6)
    # every slot of every token is on exactly one chip's experts, and no
    # chip's experts sit idle at this seed
    assert int(counts.sum()) == x.shape[0] * x.shape[1] * 2
    assert int((np.asarray(counts) > 0).sum()) >= 6
    # the scaling factor and the family's 1e-20 are in the weights
    scores = jnp.asarray([[0.9, 0.8, 0.1, 0.2]])
    _, w = token_model.route(scores, 0.0, 2, True, 2.5, eps=1e-20)
    assert float(w.sum()) == pytest.approx(2.5, rel=1e-6)


def test_four_slices_of_the_vocabulary_combine_to_the_whole_loss():
    _, _, net, variables = seeded(TINY, bias_scale=0.01)
    batch = rows(TINY, n=1)
    labels, mask = batch["labels"][0], batch["mask"][0]
    wanted = ("norm", "shared_head_norm")

    @jax.jit
    def states_and_sums(variables):
        return net.apply(
            variables, jnp.asarray(batch["tokens"]),
            labels=jnp.asarray(batch["labels"]),
            mask=token_row_weights(jnp.asarray(batch["mask"])),
            capture_intermediates=lambda m, _: m.name in wanted)

    # the whole vocabulary's two terms (held to the reference above)
    sums, state = states_and_sums(variables)
    want_mtp = float(sums["mtp_loss_sum"])
    want_main = float(sums["loss_sum"]) - TINY.mtp_loss_weight * want_mtp
    found = state["intermediates"]
    main_hidden = found["norm"]["__call__"][0][0]
    mtp_hidden = found["layers_3"]["shared_head_norm"]["__call__"][0][0]
    head = jnp.asarray(variables["params"]["lm_head"])

    @jax.jit
    def combined(hidden, labels, mask):
        def slice_lse(rows_held):
            # the program's loss op on one slice, a token at a time with
            # the slice's row 0 as its label: logsumexp = that loss +
            # that logit
            nll = jax.vmap(lambda h: token_cross_entropy_sums(
                h[None], rows_held, jnp.zeros((1,), jnp.int32),
                jnp.ones((1,), jnp.float32))["loss_sum"])
            return nll(hidden) + hidden @ rows_held[0]

        lse = jax.nn.logsumexp(jnp.stack(
            [slice_lse(head[s:s + 64]) for s in range(0, 256, 64)]), axis=0)
        picked = jnp.sum(hidden * head[labels], axis=-1)
        return jnp.sum((lse - picked) * mask) / batch["mask"].sum()

    assert float(combined(main_hidden, labels, mask)) == pytest.approx(
        want_main, abs=1e-5)
    # the second head's targets and weights: one position on
    assert float(combined(mtp_hidden, np.append(labels[1:], 0),
                          np.append(mask[1:], False))) == pytest.approx(
        want_mtp, abs=1e-5)
    assert want_mtp > 0.9 * np.log(256) and want_main > 0.9 * np.log(256)


# ------------------------------------------- the second loss and the rotary --


def test_the_second_loss_reads_the_label_after_next_and_no_last_position():
    _, _, net, variables = seeded(TINY, bias_scale=0.01)
    batch = rows(TINY, n=1)
    last = TINY.sequence_length - 1
    sums_of = jax.jit(lambda labels, mask: program_sums(
        net, variables, {**batch, "labels": labels, "mask": mask}))

    def terms(weight_at, labels=batch["labels"]):
        """``(main, mtp)`` with the loss's whole weight on one position."""
        mask = np.zeros((1, TINY.sequence_length), bool)
        mask[0, weight_at] = True
        sums = sums_of(labels, mask)
        mtp = float(sums["mtp_loss_sum"])
        return float(sums["loss_sum"]) - TINY.mtp_loss_weight * mtp, mtp

    def moved(position):
        labels = batch["labels"].copy()
        labels[0, position] = (labels[0, position] + 1) % TINY.vocab_size
        return labels

    # position i's second target is labels[i + 1], with THAT position's
    # weight: the weight on 5 reads the module at 4 against labels[5]
    main, mtp = terms(5)
    main_moved, mtp_moved = terms(5, moved(5))
    assert mtp > 0 and mtp_moved != mtp and main_moved != main
    # labels[6] is nobody's target under that weight, and no input of a
    # position before it (the attention is causal)
    assert terms(5, moved(6)) == (main, mtp)
    # labels[0] is the module's input at position 0 and no position's
    # second target: its weight reaches the main loss alone
    main_0, mtp_0 = terms(0)
    assert mtp_0 == 0.0 and main_0 > 0
    assert terms(0, moved(0))[1] == 0.0 and terms(0, moved(0))[0] != main_0
    # the last position has no target: the weights are shifted with the
    # labels and the row ends on a zero
    assert terms(last)[1] > 0  # position last-1 against labels[last]
    shifted = np.asarray(joyai.shifted(jnp.asarray(batch["labels"])))
    np.testing.assert_array_equal(shifted[0, :-1], batch["labels"][0, 1:])
    weights = np.asarray(joyai.shifted(
        token_row_weights(jnp.ones((1, TINY.sequence_length), bool))))
    assert weights[0, -1] == 0.0 and shifted[0, -1] == 0
    assert weights.sum() == pytest.approx(1.0 - 1.0 / TINY.sequence_length)


def test_interleaved_rotary_is_the_rotation_of_pairs_where_they_lie():
    rng = np.random.RandomState(0)
    q = rng.randn(2, 9, 3, 8).astype(np.float32)
    k = rng.randn(2, 9, 1, 8).astype(np.float32)
    theta = 3.2e7
    got_q = joyai.rotary(jnp.asarray(q), theta, True)
    got_k = joyai.rotary(jnp.asarray(k), theta, True)
    # the reference rotates [S, ..., D] in place; the program's result is
    # the same values in the half-split layout
    for got, x in ((got_q, q), (got_k, k)):
        for b in range(2):
            want = reference.rotary_interleaved(jnp.asarray(x[b]), theta)
            np.testing.assert_allclose(
                np.asarray(got[b]), np.asarray(joyai.deinterleave(want)),
                atol=1e-6)
    # so every score is the same, and position 0 is left as it was
    want_q = np.stack([np.asarray(reference.rotary_interleaved(
        jnp.asarray(q[b]), theta)) for b in range(2)])
    want_k = np.stack([np.asarray(reference.rotary_interleaved(
        jnp.asarray(k[b]), theta)) for b in range(2)])
    np.testing.assert_allclose(
        np.einsum("bshd,btd->bhst", np.asarray(got_q),
                  np.asarray(got_k)[:, :, 0]),
        np.einsum("bshd,btd->bhst", want_q, want_k[:, :, 0]), atol=1e-5)
    np.testing.assert_array_equal(want_q[:, 0], q[:, 0])
    # pairs (x0, x1): a rotation by the angle of pair 0 at position 1 is 1
    # radian (theta^0), whatever theta
    one = np.zeros((2, 1, 8), np.float32)
    one[1, 0, 0] = 1.0
    turned = np.asarray(reference.rotary_interleaved(jnp.asarray(one), theta))
    np.testing.assert_allclose(turned[1, 0, :2], [np.cos(1.0), np.sin(1.0)],
                               atol=1e-6)


# ---------------------------------------------------------------- the ops --


@pytest.mark.parametrize("length,block,dtype", [
    (64, 16, "float32"), (50, 16, "float32"), (50, 16, "bfloat16")],
    ids=["whole-blocks-f32", "padded-f32", "padded-bf16"])
def test_blockwise_attention_with_two_head_sizes_is_the_plain_one(
        length, block, dtype):
    keys = jax.random.split(jax.random.PRNGKey(length), 4)
    dtype = jnp.dtype(dtype)
    q = jax.random.normal(keys[0], (2, length, 4, 12)).astype(dtype)
    k = jax.random.normal(keys[1], (2, length, 4, 12)).astype(dtype)
    v = jax.random.normal(keys[2], (2, length, 4, 8)).astype(dtype)
    weight = jax.random.normal(keys[3], (2, length, 4, 8))

    def out_and_grads(attend):
        def loss(q, k, v):
            out = attend(q, k, v, scale=12 ** -0.5)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return out, grads

    out, got = out_and_grads(functools.partial(causal_attention,
                                               block=block))
    want_out, want = out_and_grads(plain_causal_attention)
    assert out.shape == (2, length, 4, 8) and out.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want_out, np.float32), atol=tol)
    for g, w, like in zip(got, want, (q, k, v)):
        assert g.shape == like.shape and g.dtype == dtype
        scale_of = max(float(jnp.abs(w.astype(jnp.float32)).max()), 1.0)
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   atol=tol * scale_of)
    # the residuals the rematerialisation may keep follow v's head size
    assert attention_op.residual_bytes(2, length, 4, 8, dtype, block) == \
        2 * -(-length // block) * block * 4 * (8 * dtype.itemsize + 4)


# ------------------------------- residuals kept through rematerialisation --


def _loss_and_grads(budget):
    _, _, _, variables = seeded(TINY, bias_scale=0.01)
    net = joyai.Joyai(TINY, residual_budget=budget)
    batch = rows(TINY)

    def loss(params):
        sums = program_sums(net, variables, batch, params)
        return sums["loss_sum"] / 2, sums["kept_residual_mb"]

    (loss, kept), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        variables["params"])
    return loss, grads, int(kept)


def test_keeping_residuals_changes_no_loss_and_no_gradient():
    classes = joyai.residual_classes(TINY, (2, 64), jnp.float32)
    assert [what for what, _, _ in classes] == [
        "attention out+lse", "dense feed-forward",
        "attention output projections", "expert rows and products"]
    # three main layers and the module: out [2, 2, 64, 12] and lse, float32
    assert classes[0][2] == 4 * 2 * 2 * 64 * (12 * 4 + 4)
    # gate and up [128, 96] of the one dense layer; o_proj's [128, 64] x 4
    assert classes[1][2] == 2 * 128 * 96 * 4
    assert classes[2][2] == 4 * 128 * 64 * 4
    # two main layers' and the module's experts, all eight held: every
    # one of the 256 slots' rows and third product [256, 64] and first
    # two products [256, 32]
    # and each token's two chosen experts [128, 2] int32
    assert classes[3][2] == 3 * (256 * 2 * (64 + 32) * 4 + 128 * 2 * 4)
    net = joyai.Joyai(TINY, residual_budget=2**62)
    kept = net.kept(rows=2)
    assert kept.classes == tuple(what for what, _, _ in classes)
    assert kept.names == attention_op.RESIDUAL_NAMES + (
        "ffn_gate", "ffn_up", "attention_out_proj") \
        + token_model.COMPACT_RESIDUALS + ("expert_chosen",)
    assert joyai.Joyai(TINY, residual_budget=classes[0][2] - 1).kept(2) \
        == token_model.Kept()
    want_loss, want, nothing_kept = _loss_and_grads(0)
    got_loss, got, kept_mb = _loss_and_grads(2**62)
    assert nothing_kept == 0 and kept_mb == kept.megabytes
    flat_want = jax.tree_util.tree_leaves_with_path(want)
    flat_got = jax.tree_util.tree_leaves(got)
    assert len(flat_want) == len(flat_got) > 50
    # a kept value is the value that would have been made again (float32;
    # tests/test_lfm2.py holds the shared machinery in bfloat16 too, and
    # to the bit). Here two paths meet in every layer's cotangent (the
    # main head's and the module's) and in the embedding's gradient, and
    # the two programs add them up in another order: the loss is equal
    # to the bit, a gradient to the last bits of a float32 sum
    assert float(got_loss) == float(want_loss)
    for (path, a), b in zip(flat_want, flat_got):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=0,
            atol=2e-6 * float(np.abs(a).max()), err_msg=str(path))


def test_the_budget_is_the_models_own_headroom_and_lfm2s_does_not_move():
    from dptpu.models import lfm2

    share = joyai.JoyaiConfig().held(layers=(0, 5), experts=(0, 8),
                                     vocab=(0, 16160), sequence_length=8192)
    net = joyai.Joyai(share, dtype=jnp.bfloat16)
    state_bytes = 3 * 4 * 491_696_128
    fitted = net.fitted_to(16_909_336_064, state_bytes)
    assert fitted.residual_budget == 16_909_336_064 - state_bytes \
        - joyai.STEP_HEADROOM_BYTES
    assert fitted.residual_budget == 5_508_982_528
    kept = fitted.kept(rows=1)
    # six layers' out [1, 32, 8192, 128] bf16 and lse [1, 32, 8192] f32;
    # one dense layer's gate and up [8192, 7168]; six o_proj
    # [8192, 2048]; five expert layers' compact buffers of 4,096 rows:
    # rows and third product [4096, 2048], gate and up [4096, 768], the
    # chosen experts [8192, 8] int32
    sizes = [size for _, _, size in net.residual_classes((1, 8192))]
    assert sizes == [6 * 8192 * 32 * (128 * 2 + 4), 2 * 8192 * 7168 * 2,
                     6 * 8192 * 2048 * 2,
                     5 * (4096 * 2 * (2048 + 768) * 2 + 8192 * 8 * 4)] == [
                         408_944_640, 234_881_024, 201_326_592, 231_997_440]
    assert kept.bytes == sum(sizes) == 1_077_149_696
    assert kept.megabytes == 1077
    assert kept.classes == (
        "attention out+lse", "dense feed-forward",
        "attention output projections", "expert rows and products")
    # a share without the dense layer has nothing of its class to keep
    later = joyai.Joyai(share.held(layers=(1, 4)), dtype=jnp.bfloat16,
                        residual_budget=2**40).kept(rows=1)
    assert later.classes == ("attention out+lse",
                             "attention output projections",
                             "expert rows and products")
    assert later.bytes == (408_944_640 + 201_326_592) * 5 // 6 \
        + 231_997_440
    assert net.fitted_to(0, state_bytes).residual_budget == 0
    # the other token model's budget is its own constant, as it was
    assert lfm2.Lfm2.step_headroom_bytes == lfm2.STEP_HEADROOM_BYTES \
        == 6_300_000_000
    assert joyai.STEP_HEADROOM_BYTES == 5_500_000_000
    assert joyai.Joyai.step_headroom_bytes == joyai.STEP_HEADROOM_BYTES
    assert token_model.TokenModel.step_headroom_bytes == 0


# ------------------------------------------------- names and configuration --


def test_every_leaf_name_goes_through_the_converter_and_back():
    model, weights, _, variables = seeded(TINY)
    kmap = torch_key_map(ARCH, variables)
    assert set(kmap) == set(weights)  # every name of the layout, no other
    for key, (collection, names, kind) in kmap.items():
        leaf = functools.reduce(lambda t, n: t[n], names,
                                variables[collection])
        np.testing.assert_array_equal(_to_torch(np.asarray(leaf), kind),
                                      weights[key], err_msg=key)
    assert kmap["model.layers.1.mlp.gate.e_score_correction_bias"][0] \
        == "batch_stats"
    for name in (
            "model.embed_tokens.weight", "lm_head.weight",
            "model.norm.weight", "model.layers.0.mlp.gate_proj.weight",
            "model.layers.0.self_attn.kv_a_proj_with_mqa.weight",
            "model.layers.0.self_attn.q_a_layernorm.weight",
            "model.layers.1.mlp.gate.weight",
            "model.layers.1.mlp.experts.7.down_proj.weight",
            "model.layers.1.mlp.shared_experts.up_proj.weight",
            "model.layers.2.mlp.shared_experts.down_proj.weight",
            "model.layers.3.enorm.weight", "model.layers.3.hnorm.weight",
            "model.layers.3.eh_proj.weight",
            "model.layers.3.shared_head.norm.weight",
            "model.layers.3.mlp.experts.0.gate_proj.weight"):
        assert name in kmap, name
    # untied: the head and the embedding are two leaves, held as torch
    # holds them
    assert kmap["lm_head.weight"][2] == "direct"
    assert not np.array_equal(weights["lm_head.weight"],
                              weights["model.embed_tokens.weight"])


def test_the_published_configuration_and_a_chips_share():
    published = joyai.JoyaiConfig()
    assert model_task("joyai_llm_flash") == "tokens"
    assert published.qk_head_dim == 192 and published.v_head_dim == 128
    assert published.routing == token_model.Routing(
        experts=256, held=(0, 256), top_k=8, norm_topk=True,
        norm_eps=1e-20, scaling=2.5, use_bias=True, width=768)
    def count(config):
        net = joyai.Joyai(config)
        shapes = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0),
                                                 net.example_input()))
        by_module = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                shapes["params"])[0]:
            by_module[path[0].key] = by_module.get(path[0].key, 0) + leaf.size
        return by_module

    share = published.held(layers=(0, 5), experts=(0, 8),
                           vocab=(0, 16160), sequence_length=8192)
    assert share.layers_here == (0, 5) and share.experts_here == (0, 8)
    assert share.vocab_size == 16160 and share.n_routed_experts == 256
    # no width moves
    for width in ("hidden_size", "intermediate_size", "q_lora_rank",
                  "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                  "v_head_dim", "moe_intermediate_size",
                  "num_experts_per_tok", "num_attention_heads"):
        assert getattr(share, width) == getattr(published, width)
    held = count(share)
    # ISSUE 36's arithmetic: the dense layer 0, four expert layers of 8
    # experts and a shared one, the module (that block, eh_proj, three
    # norms), an eighth of the vocabulary twice, the final norm
    assert held == {
        "layers_0": 70_391_808, "layers_1": 69_343_232,
        "layers_2": 69_343_232, "layers_3": 69_343_232,
        "layers_4": 69_343_232, "layers_40": 77_737_984,
        "embed_tokens": 33_095_680, "lm_head": 33_095_680, "norm": 2048}
    assert sum(held.values()) == 491_696_128
    # the whole model from those (its 10,496 expert matrices are not
    # traced here): 39 expert layers and the module with 248 more experts
    # of 3 x 2,048 x 768 each, the whole vocabulary twice
    expert = 3 * 2048 * 768
    whole = held["layers_0"] + 39 * (held["layers_1"] + 248 * expert) \
        + held["layers_40"] + 248 * expert + 2 * 129_280 * 2048 + 2048
    assert whole == 50_190_481_408  # "48B" without the module's 1.25 B
    # a later stage keeps the published numbers of its layers, holds no
    # dense layer, and holds the module all the same
    later = count(published.held(layers=(20, 1), experts=(8, 8),
                                 vocab=(16160, 16160)))
    assert set(later) == {"layers_20", "layers_40", "embed_tokens",
                          "lm_head", "norm"}
    assert later["layers_20"] == held["layers_1"]
    with pytest.raises(ValueError, match="FIRST:COUNT"):
        _REGISTRY["joyai_llm_flash"](experts="8")
    with pytest.raises(ValueError, match="not among the 256 experts"):
        published.held(experts=(250, 8))
    with pytest.raises(ValueError, match="not among the 40 layers"):
        published.held(layers=(38, 4))
    with pytest.raises(ValueError, match="does not implement n_group"):
        joyai.JoyaiConfig(n_group=8, topk_group=4)


# ----------------------------------------------------------- through fit --


def test_main_apex_trains_a_share_of_it_through_fit(tmp_path, monkeypatch,
                                                    capsys):
    from dptpu.cli import main_apex

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("WORLD_SIZE", "1")
    monkeypatch.setenv("DPTPU_OBS_DIR", str(tmp_path / "obs"))
    monkeypatch.setenv("DPTPU_WORKERS_MODE", "thread")
    # the trainer's flags for a share, as for the other token model: the
    # first two layers of the toy model, half its experts and vocabulary
    result = main_apex([
        "tokens:16", "-a", ARCH, "--optimizer", "adamw", "--beta2", "0.95",
        "--wd", "0.1", "--lr", "0.08", "-b", "2", "--seq-len", "32",
        "--layers", "0:2", "--experts", "0:4", "--vocab-rows", "0:128",
        "--opt-level", "O2", "-p", "4", "--epochs", "1",
        "--ckpt-dir", str(tmp_path / "ckpt")])
    out = capsys.readouterr().out
    assert "Mtp: multi-token-prediction loss" in out
    assert "=> residuals kept through the rematerialisation: nothing" in out
    epoch = result["history"][0]
    # uniform random ids: neither term falls far under ln(128) in 8 steps
    assert np.log(128) - 0.3 < epoch["train_mtp_loss"] < 6.0
    assert epoch["train_loss"] == pytest.approx(
        1.1 * epoch["train_mtp_loss"], rel=0.1)
    assert np.isfinite(epoch["val_loss"])
    assert epoch["train_moe_dropped"] == 0
    assert epoch["train_moe_compact_share"] == 100.0
    assert 40 < epoch["train_moe_local_slot_share"] < 60  # 4 of 8 held
    params = result["state"].params
    assert {k for k in params if k.startswith("layers_")} == {
        "layers_0", "layers_1", "layers_3"}
    assert params["lm_head"].shape == (128, 64)
    assert set(params["layers_1"]["mlp"]) == {
        "gate", "experts_0", "experts_1", "experts_2", "experts_3"}
    # the fetch span carries the second term beside the loss
    (log,) = [f for f in os.listdir(tmp_path / "obs") if f.endswith(".jsonl")]
    with open(tmp_path / "obs" / log) as f:
        fetches = [r for r in map(json.loads, f)
                   if r.get("kind") == "span" and r.get("name") == "fetch"]
    carrying = [r["attrs"] for r in fetches
                if "mtp_loss" in r.get("attrs", {})]
    assert carrying and all(
        a["loss"] > a["mtp_loss"] > 0 and a["moe_dropped"] == 0
        and a["moe_compact_layers"] == a["moe_layers"] > 0
        and a["kept_residual_mb"] == 0 for a in carrying)
