"""LFM2 (``dptpu/models/lfm2.py``) against its plain reference
(``benchmark/reference/lfm2_moe.py``) at a small size on the CPU: hidden
64, 2 heads of 32 over 1 key/value head, 8 experts top-2, vocabulary 256,
64 tokens. Seeded weights in the checkpoint's names go through the
program's own converter, as the benchmark's do.

Tolerances: both sides compute in float32 with HIGHEST matrix products;
what is left is the order of float32 sums (blockwise attention and loss,
sorted expert runs against dense masked experts), so 2e-5 of a leaf's
largest entry holds a gradient and 1e-5 a loss near 6. Three AdamW steps
divide by the root of a tiny second moment, which magnifies those sums'
last bits in single entries: the parameters' change is held to 2e-3 of
its own norm, a leaf at a time.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common as reference_common
from benchmark.reference import lfm2_moe as reference
from benchmark.reference.optimizers import adamw as reference_adamw
from dptpu.models import lfm2, token_model
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
from dptpu.train.step import make_eval_step, make_train_step

TINY = lfm2.Lfm2Config(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=48, num_hidden_layers=4,
    layer_types=("conv", "conv", "full_attention", "conv"),
    num_dense_layers=2, num_attention_heads=2, num_key_value_heads=1,
    num_experts=8, num_experts_per_tok=2, sequence_length=64)
ARCH = "lfm2_test_tiny"
if ARCH not in _REGISTRY:
    register_model(lfm2.factory(ARCH, TINY))

HYPER = {"name": "adamw", "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1}


def reference_model(config: lfm2.Lfm2Config) -> dict:
    """The reference's ``model`` group for a program configuration."""
    first, count = config.experts_here
    return {
        "hidden_size": config.hidden_size,
        "intermediate_size": config.intermediate_size,
        "moe_intermediate_size": config.moe_intermediate_size,
        "layer_types": list(config.layer_types),
        "num_dense_layers": config.num_dense_layers,
        "num_attention_heads": config.num_attention_heads,
        "num_key_value_heads": config.num_key_value_heads,
        "head_dim": config.head_dim,
        "router_experts": config.num_experts,
        "experts_first": first, "experts_held": count,
        "num_experts_per_tok": config.num_experts_per_tok,
        "norm_topk_prob": config.norm_topk_prob,
        "routed_scaling_factor": config.routed_scaling_factor,
        "use_expert_bias": config.use_expert_bias,
        "conv_L_cache": config.conv_L_cache, "norm_eps": config.norm_eps,
        "rope_theta": config.rope_theta, "vocab_size": config.vocab_size,
        "sequence_length": config.sequence_length,
    }


def seeded(config, seed=5, bias_scale=None):
    """``(reference model, weights by checkpoint name, program
    variables)`` for ``config``."""
    model = reference_model(config)
    spec = reference.weight_spec(model)
    if bias_scale is not None:
        spec = [(n, s, k, bias_scale if n.endswith("expert_bias") else v)
                for n, s, k, v in spec]
    weights = {k: np.asarray(v) for k, v in
               reference_common.make_weights(spec, seed).items()}
    net = lfm2.Lfm2(config)
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


def program_loss(net, variables, batch):
    """The step's loss: the mean over rows of the row's mean."""
    from dptpu.train.step import token_row_weights

    def loss(params):
        sums = net.apply({**variables, "params": params}, batch["tokens"],
                         labels=batch["labels"],
                         mask=token_row_weights(jnp.asarray(batch["mask"])))
        return sums["loss_sum"] / batch["tokens"].shape[0]
    return loss


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


# --------------------------------------------------- program == reference --


@pytest.mark.parametrize("share", [None, (2, 4)], ids=["whole", "experts2-5"])
def test_loss_and_every_gradient_leaf_match_the_reference(share):
    config = TINY.held(experts=share)
    model, weights, net, variables = seeded(config, bias_scale=0.05)
    batch = rows(config)
    want, want_grads = jax.value_and_grad(
        lambda w: reference.loss(model, {**weights, **w}, batch, "f32"))(
        {k: jnp.asarray(weights[k]) for k in reference.trainable(model)})
    # nothing trains the buffer: it only chooses experts, so its
    # gradient is zero to the bit (it is an argument of the compiled
    # loss all the same: reference.trainable says why)
    biases = [k for k in want_grads if k.endswith("expert_bias")]
    assert biases and not any(np.asarray(want_grads.pop(k)).any()
                              for k in biases)
    got, got_grads = jax.value_and_grad(
        program_loss(net, variables, batch))(variables["params"])
    assert float(got) == pytest.approx(float(want), abs=1e-5)
    kmap = torch_key_map(ARCH, variables)
    checked = 0
    for key, (collection, names, kind) in kmap.items():
        if collection != "params":
            continue
        leaf = functools.reduce(lambda t, n: t[n], names, got_grads)
        there = _to_torch(np.asarray(leaf), kind)
        scale = max(float(np.abs(want_grads[key]).max()), 1e-6)
        np.testing.assert_allclose(there, want_grads[key],
                                   atol=2e-5 * scale + 1e-9, err_msg=key)
        checked += 1
    assert checked == len(want_grads)
    # every leaf got a gradient worth comparing, but for an expert that
    # no token of these 128 chose
    idle = [k for k, g in want_grads.items() if not np.abs(g).max()]
    assert len(idle) <= 6 and all(".experts." in k for k in idle), idle


def test_three_adamw_steps_match_the_reference():
    model, weights, net, variables = seeded(TINY)
    batches = [rows(TINY, seed=s) for s in range(3)]
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
    assert losses == pytest.approx(want["loss"], abs=1e-5)
    assert int(metrics["moe_dropped"]) == 0
    assert int(metrics["moe_compact"]) == int(metrics["moe_layers"]) == 2
    # no call has a window: the tiles walked are the causal triangle's
    assert int(metrics["attention_window_calls"]) == 0
    assert int(metrics["attention_tiles"]) \
        == int(metrics["attention_tiles_causal"]) \
        == int(metrics["attention_calls"])  # a toy row is one tile
    for key, (collection, names, kind) in torch_key_map(
            ARCH, variables).items():
        leaf = lambda tree: _to_torch(np.asarray(functools.reduce(  # noqa: E731
            lambda t, n: t[n], names, tree)), kind)
        if collection != "params":  # the buffer stays where it was seeded
            np.testing.assert_array_equal(leaf(state.batch_stats),
                                          weights[key])
            assert not want["delta"][key].any() \
                and not want["trace1"][key].any()
            continue
        scale = max(float(np.abs(want["trace1"][key]).max()), 1e-9)
        np.testing.assert_allclose(leaf(mu1), want["trace1"][key],
                                   atol=2e-5 * scale, err_msg=key)
        # Adam's step is the gradient over its own size: an entry whose
        # gradient is all but zero turns on the last bits of a float32
        # sum, so the change is held as a whole leaf, not entry by entry
        delta = leaf(state.params) - weights[key]
        off = np.linalg.norm(delta - want["delta"][key]) \
            / np.linalg.norm(want["delta"][key])
        assert off < 2e-3, (key, off)


def test_eval_step_sums_over_kept_tokens():
    model, weights, net, variables = seeded(TINY)
    batch = rows(TINY, n=3)
    state = create_train_state(jax.random.PRNGKey(0), net,
                               make_optimizer(name="adamw"),
                               variables=variables)
    sums = make_eval_step(None, jnp.float32, task="tokens")(
        state, {k: jnp.asarray(v) for k, v in batch.items()})
    logits = np.stack([np.asarray(reference.forward(model, weights, t))
                       for t in batch["tokens"]])
    nll = jax.nn.logsumexp(logits, -1) - np.take_along_axis(
        logits, batch["labels"][..., None], -1)[..., 0]
    assert float(sums["count"]) == batch["mask"].sum()
    assert float(sums["loss_sum"]) == pytest.approx(
        float((nll * batch["mask"]).sum()), rel=1e-5)
    top1 = (logits.argmax(-1) == batch["labels"]) & batch["mask"]
    assert float(sums["correct1"]) == top1.sum()
    assert float(sums["correct5"]) >= float(sums["correct1"])


# ------------------------------------------------------------ the shares --


def _layer_input(config, seed=3):
    rng = np.random.RandomState(seed)
    return rng.randn(2, config.sequence_length,
                     config.hidden_size).astype(np.float32)


def test_four_shares_of_the_experts_add_up_to_the_uncut_layer():
    model, weights, _, variables = seeded(TINY, bias_scale=0.05)
    f = "model.layers.2.feed_forward."
    x = _layer_input(TINY)
    want = np.stack([np.asarray(reference.expert_layer(
        model, weights, f, row, "f32", experts=range(8))) for row in x])
    whole = variables["params"]["layers_2"]["feed_forward"]
    stats = {"expert_bias":
             variables["batch_stats"]["layers_2"]["feed_forward"]["expert_bias"]}
    total, counts = 0.0, []
    for first in (0, 2, 4, 6):
        config = TINY.held(experts=(first, 2))
        params = {"gate": whole["gate"],
                  **{f"experts_{e}": whole[f"experts_{e}"]
                     for e in range(first, first + 2)}}
        out, sizes, compact = lfm2.SparseExperts(config).apply(
            {"params": params, "batch_stats": stats}, jnp.asarray(x))
        total = total + np.asarray(out)
        counts += list(np.asarray(sizes))
        # a quarter of the experts got a quarter of the slots, give or
        # take: the compact buffer (two quarters) held them
        assert int(compact) == 1
    np.testing.assert_allclose(total, want, atol=2e-6)
    # every slot of every token is on exactly one chip's experts
    assert sum(counts) == x.shape[0] * x.shape[1] * 2


def test_four_slices_of_the_vocabulary_combine_to_the_whole_loss():
    model, weights, net, variables = seeded(TINY)
    batch = rows(TINY, n=1)
    want = float(reference.loss(model, weights, batch, "f32"))
    _, state = net.apply(
        variables, batch["tokens"],
        capture_intermediates=lambda m, _: m.name == "embedding_norm")
    hidden = state["intermediates"]["embedding_norm"]["__call__"][0][0]
    embedding = variables["params"]["embed_tokens"]["embedding"]
    one = jnp.ones((1,), jnp.float32)

    def slice_lse(rows_held):
        # the program's loss op on one slice, a token at a time with the
        # slice's row 0 as its label: logsumexp = that loss + that logit
        nll = jax.vmap(lambda h: token_cross_entropy_sums(
            h[None], rows_held, jnp.zeros((1,), jnp.int32), one)["loss_sum"])
        return nll(hidden) + hidden @ rows_held[0]

    lse = jax.nn.logsumexp(jnp.stack(
        [slice_lse(embedding[s:s + 64]) for s in range(0, 256, 64)]), axis=0)
    picked = jnp.sum(hidden * embedding[batch["labels"][0]], axis=-1)
    mask = batch["mask"][0]
    got = float(jnp.sum((lse - picked) * mask) / mask.sum())
    assert got == pytest.approx(want, abs=1e-5)


def test_no_token_is_dropped_when_every_token_picks_the_same_experts():
    model, weights, _, variables = seeded(TINY)
    f = "model.layers.3.feed_forward."
    # a bias that outweighs every score: experts 0 and 1, for every token
    bias = np.zeros(8, np.float32)
    bias[:2] = 10.0
    weights = {**weights, f + "expert_bias": bias}
    x = _layer_input(TINY)
    tokens = x.shape[0] * x.shape[1]
    whole = variables["params"]["layers_3"]["feed_forward"]
    stats = {"expert_bias": jnp.asarray(bias)}
    out, sizes, compact = lfm2.SparseExperts(TINY).apply(
        {"params": whole, "batch_stats": stats}, jnp.asarray(x))
    assert list(np.asarray(sizes)) == [tokens, tokens, 0, 0, 0, 0, 0, 0]
    want = np.stack([np.asarray(reference.expert_layer(
        model, weights, f, row, "f32")) for row in x])
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-6)
    # a layer that holds all its experts has one buffer, the worst case
    assert int(compact) == 1 and token_model.held_row_cap(
        tokens, 2, 8, 8) == 2 * tokens

    def share(first, count, x):
        return lfm2.SparseExperts(TINY.held(experts=(first, count))).apply(
            {"params": {"gate": whole["gate"],
                        **{f"experts_{e}": whole[f"experts_{e}"]
                           for e in range(first, first + count)}},
             "batch_stats": stats}, jnp.asarray(x))

    # the chip that holds just those two gets every slot, four times its
    # even share and all its worst case: no compact buffer holds that (on
    # rows enough for one to exist: a buffer is whole row tiles), the
    # fallback was the path taken, and the result is the uncut layer's
    many = np.concatenate([_layer_input(TINY, seed) for seed in range(8)])
    tokens = many.shape[0] * many.shape[1]
    assert token_model.held_row_cap(tokens, 2, 2, 8) == tokens < 2 * tokens
    out, sizes, compact = share(0, 2, many)
    assert int(compact) == 0 and list(np.asarray(sizes)) == [tokens, tokens]
    want = np.stack([np.asarray(reference.expert_layer(
        model, weights, f, row, "f32")) for row in many])
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-6)
    # and a share that holds neither of them computes nothing, drops nothing
    out, sizes, compact = share(4, 4, many)
    assert not np.asarray(sizes).any() and not np.asarray(out).any()
    assert int(compact) == 1


def test_the_selection_takes_the_bias_and_the_weights_do_not():
    scores = jnp.asarray([[0.9, 0.8, 0.1, 0.2]])
    chosen, weights = lfm2.route(scores, jnp.asarray([0.0, 0.0, 1.0, 0.0]),
                                 2, True, 1.0)
    assert sorted(np.asarray(chosen)[0]) == [0, 2]
    by_expert = dict(zip(np.asarray(chosen)[0], np.asarray(weights)[0]))
    assert by_expert[0] == pytest.approx(0.9 / (1.0 + 1e-6))
    assert by_expert[2] == pytest.approx(0.1 / (1.0 + 1e-6))


# ---------------------------------------------------------------- the ops --


@pytest.mark.parametrize("length,block", [(64, 16), (50, 16), (7, 16),
                                          (33, 8), (17, 1)])
def test_blockwise_attention_is_the_plain_one(length, block):
    keys = jax.random.split(jax.random.PRNGKey(length), 4)
    q = jax.random.normal(keys[0], (2, length, 4, 8))
    k = jax.random.normal(keys[1], (2, length, 2, 8))
    v = jax.random.normal(keys[2], (2, length, 2, 8))
    weigh = jax.random.normal(keys[3], (2, length, 4, 8))

    def scalar(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * weigh)

    blockwise = functools.partial(causal_attention, scale=0.35, block=block)
    plain = functools.partial(plain_causal_attention, scale=0.35)
    np.testing.assert_allclose(blockwise(q, k, v), plain(q, k, v), atol=2e-6)
    got = jax.grad(scalar(blockwise), (0, 1, 2))(q, k, v)
    want = jax.grad(scalar(plain), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-6)


@pytest.mark.parametrize("tokens,block", [(100, 32), (64, 64), (5, 2048)])
def test_blockwise_token_loss_is_the_whole_one(tokens, block):
    keys = jax.random.split(jax.random.PRNGKey(tokens), 3)
    hidden = jax.random.normal(keys[0], (tokens, 16))
    embedding = jax.random.normal(keys[1], (40, 16))
    labels = jax.random.randint(keys[2], (tokens,), 0, 40)
    mask = jnp.arange(tokens) < tokens - 3

    def whole(hidden, embedding):
        logits = hidden @ embedding.T
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, labels[:, None], -1)[:, 0]
        return jnp.sum(nll * mask), logits

    def blocks(hidden, embedding):
        return token_cross_entropy_sums(hidden, embedding, labels, mask,
                                        block=block)

    got = blocks(hidden, embedding)
    want, logits = whole(hidden, embedding)
    assert float(got["loss_sum"]) == pytest.approx(float(want), rel=1e-6)
    assert float(got["count"]) == tokens - 3
    _, top = jax.lax.top_k(logits, 5)
    hit = np.asarray(top == labels[:, None]) & np.asarray(mask)[:, None]
    assert float(got["correct1"]) == hit[:, :1].any(1).sum()
    assert float(got["correct5"]) == hit.any(1).sum()
    got_g = jax.grad(lambda h, e: blocks(h, e)["loss_sum"], (0, 1))(
        hidden, embedding)
    want_g = jax.grad(lambda h, e: whole(h, e)[0], (0, 1))(hidden, embedding)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(g, w, atol=1e-5)


# ------------------------------- residuals kept through rematerialisation --


def _class_bytes(config, shape, dtype=jnp.bfloat16):
    """Bytes of each class for a step on rows of ``shape``, in order."""
    return [size for _, _, size in
            lfm2.residual_classes(config, shape, dtype)]


# layers 1-2 of TINY: a short convolution before a dense feed-forward,
# an attention before experts, so every class has something to keep
TWO = TINY.held(layers=(1, 2))


@functools.lru_cache(maxsize=None)
def _loss_and_grads(dtype, budget):
    _, _, _, variables = seeded(TWO, bias_scale=0.05)
    net = lfm2.Lfm2(TWO, dtype=jnp.dtype(dtype), residual_budget=budget)
    batch = rows(TWO)

    def loss(params):
        sums = net.apply({**variables, "params": params}, batch["tokens"],
                         labels=batch["labels"],
                         mask=jnp.asarray(batch["mask"], jnp.float32))
        return sums["loss_sum"] / 128, sums["kept_residual_mb"]

    (loss, kept), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        variables["params"])
    return loss, grads, int(kept)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("upto", [0, 1, 3, 4, 5],
                         ids=["nothing", "attention", "all-but-two",
                              "experts", "unbounded"])
def test_keeping_residuals_changes_no_loss_and_no_gradient(upto, dtype):
    budget = 2**62 if upto == 5 else sum(
        _class_bytes(TWO, (2, 64), dtype)[:upto])
    kept = lfm2.kept_residuals(TWO, (2, 64), dtype, budget)
    assert len(kept.classes) == upto
    # the expert layer's class is fourth: its one layer here holds all
    # its experts, so the names sit on its one buffer of every slot
    assert ("expert_gate" in kept.names) == (upto >= 4)
    want_loss, want, nothing_kept = _loss_and_grads(dtype, 0)
    got_loss, got, kept_mb = _loss_and_grads(dtype, budget)
    assert nothing_kept == 0 and kept_mb == kept.megabytes
    flat_want = jax.tree_util.tree_leaves_with_path(want)
    flat_got = jax.tree_util.tree_leaves(got)
    assert len(flat_want) == len(flat_got) > 30
    if dtype == "float32":
        # a kept value is the value that would have been made again
        assert float(got_loss) == float(want_loss)
        for (path, a), b in zip(flat_want, flat_got):
            np.testing.assert_array_equal(np.asarray(b), np.asarray(a),
                                          err_msg=str(path))
    else:
        # bfloat16: the CPU's compiler computes a fused chain in float32
        # and rounds at its end, so a value made again inside a fusion
        # skips roundings the kept one (the forward pass's own) went
        # through: a few bfloat16 ulps (2^-8 each), 1.44% of a leaf's
        # largest entry at the worst here, and not the 2e-5 that holds
        # the float32 sums above
        assert float(got_loss) == pytest.approx(float(want_loss), abs=1e-5)
        for (path, a), b in zip(flat_want, flat_got):
            scale = max(float(np.abs(a).max()), 1e-6)
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), atol=3e-2 * scale,
                err_msg=str(path))


def _whiles(fn, *args) -> int:
    """``while`` loops in the program compiled for ``fn``."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return len(re.findall(r"\bwhile\(", text))


def test_keeping_out_and_lse_saves_one_forward_scan_a_layer():
    two_attention = lfm2.Lfm2Config(
        vocab_size=64, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=48, num_hidden_layers=2,
        layer_types=("full_attention",) * 2, num_dense_layers=2,
        num_attention_heads=2, num_key_value_heads=1, num_experts=8,
        num_experts_per_tok=2, sequence_length=1024)
    # two blocks of 512: three tiles, so the scans stay loops
    tokens = jnp.zeros((1, 1024), jnp.int32)

    def loops(budget):
        net = lfm2.Lfm2(two_attention, residual_budget=budget)
        variables = net.init(jax.random.PRNGKey(0), tokens)
        return _whiles(jax.value_and_grad(lambda p: jnp.sum(net.apply(
            {"params": p}, tokens))), variables["params"])

    class_a = _class_bytes(two_attention, (1, 1024), jnp.float32)[0]
    # a layer's scans: forward, forward again on the way back, backward
    assert loops(0) == 6
    assert loops(class_a - 1) == 6
    assert loops(class_a) == 4


def test_a_name_on_the_calls_result_alone_does_not_save_the_scan():
    """Why the names sit inside the forward rule, on both residuals: with
    ``out`` named where the attention is called, ``lse`` is still made
    again, and only the scan makes it."""
    from jax.ad_checkpoint import checkpoint_name

    def layer(x, w):
        q = (x @ w).reshape(1, 64, 2, 32)
        out = causal_attention(q, q[:, :, :1], q[:, :, :1], scale=0.2,
                               block=16)
        return checkpoint_name(out, "call_site_out").reshape(x.shape) + x

    def two_layers(*names):
        policy = jax.checkpoint_policies.save_only_these_names(*names)

        def loss(w, x):
            for i in range(2):
                x = jax.checkpoint(layer, policy=policy)(x, w[i])
            return jnp.sum(x)
        return jax.value_and_grad(loss)

    w, x = jnp.full((2, 64, 64), 0.01), jnp.ones((1, 64, 64))
    assert _whiles(two_layers(), w, x) == 6
    assert _whiles(two_layers("call_site_out"), w, x) == 6
    assert _whiles(two_layers(*attention_op.RESIDUAL_NAMES), w, x) == 4
    for a, b in zip(two_layers()(w, x),
                    two_layers(*attention_op.RESIDUAL_NAMES)(w, x)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


CELL_SHARE = dict(layers=(1, 5), experts=(0, 8), vocab=(0, 16384),
                  sequence_length=8192)
CELL_STATE_BYTES = 3 * 4 * 507_820_160  # float32 weights, AdamW's moments


def test_the_cells_step_keeps_the_frozen_classes_and_counts_their_bytes():
    share = lfm2.Lfm2Config().held(**CELL_SHARE)
    classes = lfm2.residual_classes(share, (2, 8192), jnp.bfloat16)
    assert [what for what, _, _ in classes] == [
        "attention out+lse", "q/k/v projections", "mixer projections",
        "expert rows and products", "dense feed-forward"]
    sizes = [size for _, _, size in classes]
    # out [2, 8, 32768, 64] bf16 and lse [2, 8, 32768] f32, one layer
    assert sizes[0] == 2 * 8 * 32768 * (64 * 2 + 4) == 69_206_016
    # q [16384, 2048], k and v [16384, 512] bf16
    assert sizes[1] == 16384 * 3072 * 2 == 100_663_296
    # in_proj [16384, 6144] + out_proj [16384, 2048] on four short
    # convolutions, out_proj on the attention
    assert sizes[2] == (4 * 4 + 1) * 16384 * 2048 * 2 == 1_140_850_688
    # four expert layers' compact buffers of 32,768 rows: the gathered
    # rows and the third product [32768, 2048], the gate and up products
    # [32768, 1792]
    # and each token's four chosen experts [16384, 4] int32
    assert classes[3][1] == ("expert_rows", "expert_gate", "expert_up",
                             "expert_out", "expert_chosen")
    assert sizes[3] == 4 * (32768 * 2 * (2048 + 1792) * 2
                            + 16384 * 4 * 4) == 2_014_314_496
    # w1 and w3 [16384, 7168], one dense layer
    assert sizes[4] == 2 * 16384 * 7168 * 2 == 469_762_048
    # the chip reports 16.9 GB: every class; a chip of 16 GB keeps all
    # but the last
    for device_bytes, upto in ((16 * 10**9, 4), (16_909_336_064, 5)):
        net = lfm2.Lfm2(share, dtype=jnp.bfloat16).fitted_to(
            device_bytes, CELL_STATE_BYTES)
        assert net.residual_budget == device_bytes - CELL_STATE_BYTES \
            - lfm2.STEP_HEADROOM_BYTES
        kept = net.kept(rows=2)
        assert kept.classes == tuple(what for what, _, _ in classes[:upto])
        assert kept.names == tuple(n for _, names, _ in classes[:upto]
                                   for n in names)
        assert kept.bytes == sum(sizes[:upto]) <= net.residual_budget
        assert all(c in kept.notice(net.residual_budget)
                   for c in kept.classes)
    assert kept.bytes == sum(sizes) == 3_794_796_544
    assert kept.megabytes == 3795
    assert lfm2.STEP_HEADROOM_BYTES == 6_300_000_000
    # a device that reports no size, or one the state fills: nothing kept
    assert net.fitted_to(0, CELL_STATE_BYTES).residual_budget == 0
    assert net.fitted_to(8 * 10**9, CELL_STATE_BYTES).kept(2) == lfm2.Kept()


@pytest.mark.parametrize("shape,layers", [
    ((2, 8192), (1, 5)),       # the cell
    ((2, 8192), (0, 24)),      # every layer
    ((2, 32768), (1, 5)),      # rows four times as long
    ((1, 50), (1, 2)),         # a row the attention pads
], ids=["cell", "24-layers", "32k-rows", "padded"])
def test_the_choice_is_monotone_and_stays_inside_the_budget(shape, layers):
    share = lfm2.Lfm2Config().held(**{**CELL_SHARE, "layers": layers,
                                      "sequence_length": shape[1]})
    sizes = _class_bytes(share, shape)
    assert lfm2.kept_residuals(share, shape, jnp.bfloat16, 0) == lfm2.Kept()
    before = lfm2.Kept()
    for budget in sorted({0, 1, *np.cumsum(sizes), *(np.cumsum(sizes) - 1),
                          2**62}):
        kept = lfm2.kept_residuals(share, shape, jnp.bfloat16, int(budget))
        assert kept.bytes <= budget
        assert kept.classes[:len(before.classes)] == before.classes
        assert kept.names[:len(before.names)] == before.names
        assert kept.bytes >= before.bytes
        before = kept
    assert before.bytes == sum(sizes)
    # a larger share keeps strictly less of the budget the cell has
    cell = lfm2.Lfm2Config().held(**CELL_SHARE)
    budget = 16_909_336_064 - CELL_STATE_BYTES - lfm2.STEP_HEADROOM_BYTES
    at_cell = lfm2.kept_residuals(cell, (2, 8192), jnp.bfloat16, budget)
    here = lfm2.kept_residuals(share, shape, jnp.bfloat16, budget)
    if shape[0] * shape[1] > 16384 or share.num_hidden_layers > 5:
        assert len(here.classes) < len(at_cell.classes) == 5
    if shape == (1, 50):
        # 50 tokens, not padded (one block of the row's own length)
        assert sizes[0] == 50 * 32 * (64 * 2 + 4)


@pytest.mark.parametrize("experts", [True, False],
                         ids=["with-experts", "dense-only"])
def test_the_loop_passes_the_megabytes_kept_on_as_they_are(experts):
    """A constant of the step program: the ``fetch`` span says it once,
    however many steps the fetch read, with the expert layers' load or
    (a share of dense layers only) without."""
    from dptpu.train.loop import MoeLoad

    step = {"loss": 1.0, "kept_residual_mb": np.int32(1793)}
    if experts:
        step.update(moe_counts=np.full((4, 8), 2048), moe_slots=262144,
                    moe_dropped=0, moe_compact=np.int32(3),
                    moe_layers=np.int32(4))
    load = MoeLoad()
    attrs = load.take([step] * 3)
    assert attrs["kept_residual_mb"] == 1793
    assert type(attrs["kept_residual_mb"]) is int
    for name in ("moe_slots", "moe_compact_layers", "moe_layers"):
        assert (name in attrs) is experts
    if experts:
        assert attrs["moe_slots"] == 3 * 262144  # a sum over the steps
        # one layer of the four overflowed in each step
        assert (attrs["moe_compact_layers"], attrs["moe_layers"]) == (9, 12)
        assert type(attrs["moe_compact_layers"]) is int
        assert load.stats()["moe_compact_share"] == 75.0
    assert "kept_residual_mb" not in load.stats()
    # a model that counts neither, and a fetch that read nothing
    assert load.take([{"loss": 1.0}]) == {} and load.take([]) == {}


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
    assert kmap["model.layers.2.feed_forward.expert_bias"][0] == "batch_stats"
    assert "model.layers.0.conv.conv.weight" in kmap
    assert "model.layers.2.self_attn.q_layernorm.weight" in kmap
    assert "model.layers.3.feed_forward.experts.7.w2.weight" in kmap


def test_the_published_configuration_and_a_chips_share():
    published = lfm2.Lfm2Config()
    assert model_task("lfm2_8b_a1b") == "tokens"
    assert model_task("resnet50") == "images"
    assert published.head_dim == 64 and published.num_experts == 32
    assert [i for i, kind in enumerate(published.layer_types)
            if kind == "full_attention"] == [2, 6, 10, 14, 18, 21]
    share = published.held(layers=(1, 5), experts=(0, 8),
                           vocab=(0, 16384), sequence_length=8192)
    assert share.layer_types == ("conv", "full_attention", "conv", "conv",
                                 "conv")
    assert share.num_dense_layers == 1 and share.num_experts == 32
    assert share.experts_here == (0, 8) and share.vocab_size == 16384
    # no width moves
    for width in ("hidden_size", "intermediate_size", "head_dim",
                  "moe_intermediate_size", "num_experts_per_tok"):
        assert getattr(share, width) == getattr(published, width)
    net = lfm2.Lfm2(share)
    shapes = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0),
                                             net.example_input()))
    assert sum(x.size for x in jax.tree_util.tree_leaves(
        shapes["params"])) == 507_820_160
    with pytest.raises(ValueError, match="FIRST:COUNT"):
        lfm2.factory("x", published)(experts="8")
    with pytest.raises(ValueError, match="not among the 32 experts"):
        published.held(experts=(30, 4))
