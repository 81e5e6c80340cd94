"""Trinity-Mini (``dptpu/models/trinity.py``) against its plain reference
(``benchmark/reference/afmoe.py``) on seeded weights at toy widths, both
kinds of layer (a window and none) and both feed-forwards (dense and
experts): the loss, every gradient leaf and two AdamW steps through the
step builder, whole and as a chip's share; a program with the gate, the
head norms, the scaled embedding, the rotary positions or one of the four
norms left out fails the same comparison; the shares of the experts add
up to the uncut layer with the shared expert counted once; kept residuals
change nothing; what the step counts of the attention's calls; every leaf
name through the converter and back; the published configuration's
counts; and ``main_apex`` training it through ``fit()``.
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmark.reference import afmoe as reference
from benchmark.reference import common as reference_common
from benchmark.reference.optimizers import adamw as reference_adamw
from dptpu.models import token_model, trinity
from dptpu.models.pretrained import (
    _to_torch,
    convert_state_dict,
    torch_key_map,
)
from dptpu.models.registry import _REGISTRY, model_task, register_model
from dptpu.ops import attention as attention_op
from dptpu.train.state import create_train_state, make_optimizer
from dptpu.train.step import make_train_step, token_row_weights

# two dense layers, then experts; layer 3 is the full-attention one; a
# window a quarter of the row; 2 query heads a key/value head
TINY = trinity.TrinityConfig(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=5,
    layer_types=("sliding_attention",) * 3 + ("full_attention",
                                              "sliding_attention"),
    sliding_window=16, num_dense_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, num_experts=8,
    num_experts_per_tok=2, sequence_length=64)
ARCH = "trinity_test_tiny"
if ARCH not in _REGISTRY:
    register_model(trinity.factory(ARCH, TINY))

HYPER = {"name": "adamw", "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1}
SHARE = {"layers": (1, 4), "experts": (2, 4), "vocab": (0, 128)}


def reference_model(config: trinity.TrinityConfig) -> dict:
    """The reference's ``model`` group for a program configuration."""
    experts_first, experts_held = config.experts_here
    same = ("hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "sliding_window", "num_experts_per_tok", "num_shared_experts",
            "route_norm", "route_scale", "mup_enabled", "rms_norm_eps",
            "rope_theta", "vocab_size", "sequence_length")
    return {**{key: getattr(config, key) for key in same},
            "layers_first": config.layers_here[0],
            "layers_held": config.layers_here[1],
            "layer_types": [config.layer_types[i]
                            for i in config.numbers_here],
            "first_expert_layer": config.num_dense_layers,
            "router_experts": config.num_experts,
            "experts_first": experts_first, "experts_held": experts_held}


@functools.lru_cache(maxsize=None)
def seeded(config, seed=5):
    """``(reference model, weights by checkpoint name, program net,
    program variables)`` for ``config``, the weights drawn on the host as
    the family's ``weight_spec`` says (the bias larger: at toy widths the
    scores lie close together)."""
    model = reference_model(config)
    rng = np.random.RandomState(seed)
    weights = {}
    for name, shape, kind, scale in reference.weight_spec(model):
        if name.endswith("expert_bias"):
            scale = 0.01
        assert kind in ("normal", "const"), kind
        weights[name] = np.full(shape, scale, np.float32) \
            if kind == "const" \
            else (scale * rng.randn(*shape)).astype(np.float32)
    net = trinity.Trinity(config)
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


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@functools.lru_cache(maxsize=None)
def reference_steps(config, steps=2, lr=1e-3):
    model, weights, _, _ = seeded(config)
    return reference_common.train_steps(
        functools.partial(reference.loss, model), reference_adamw, HYPER,
        reference.trainable(model), weights,
        [rows(config, seed=s) for s in range(steps)], lr=lr, block_rows=1)


def program_steps(config, net=None, steps=2, lr=1e-3):
    """``(losses, first gradient as the optimizer got it, state, last
    metrics)`` of ``steps`` AdamW steps of the program on the seeded
    weights."""
    _, _, seeded_net, variables = seeded(config)
    tx = make_optimizer(weight_decay=HYPER["weight_decay"], name="adamw",
                        betas=(HYPER["b1"], HYPER["b2"]), eps=HYPER["eps"])
    state = create_train_state(jax.random.PRNGKey(0), net or seeded_net, tx,
                               variables=variables)
    step = make_train_step(None, jnp.float32, lr_schedule=lambda c: lr,
                           task="tokens")
    losses, mu1 = [], None
    for s in range(steps):
        state, metrics = step(state, {k: jnp.asarray(v) for k, v in
                                      rows(config, seed=s).items()})
        losses.append(float(metrics["loss"]))
        if mu1 is None:
            # copied out: the next step donates the state it is part of
            mu1 = jax.device_get(
                reference_adamw.program_trace1(state.opt_state))
    return losses, mu1, state, metrics


def _leaf(tree, names, kind):
    return _to_torch(np.asarray(functools.reduce(
        lambda t, n: t[n], names, tree)), kind)


def gaps(config, net=None):
    """How far a program is from the reference over two steps: the
    largest loss gap, and the largest gradient leaf's gap over that
    leaf's own size."""
    want = reference_steps(config)
    losses, mu1, _, _ = program_steps(config, net)
    _, _, _, variables = seeded(config)
    worst = 0.0
    for key, (collection, names, kind) in torch_key_map(
            ARCH, variables).items():
        grad = want["trace1"][key]
        if collection == "params" and np.abs(grad).max():
            worst = max(worst, float(
                np.abs(_leaf(mu1, names, kind) - grad).max()
                / np.abs(grad).max()))
    return max(abs(a - b) for a, b in zip(losses, want["loss"])), worst


# --------------------------------------------------- program == reference --


@pytest.mark.parametrize("share", [{}, SHARE],
                         ids=["whole", "layers1-4-experts2-5-vocab128"])
def test_loss_every_gradient_leaf_and_two_adamw_steps_match_the_reference(
        share):
    config = TINY.held(**share)
    _, weights, _, variables = seeded(config)
    want = reference_steps(config)
    losses, mu1, state, metrics = program_steps(config)
    assert losses == pytest.approx(want["loss"], abs=1e-5)
    # both kinds of layer and both feed-forwards are in either share
    kinds = [config.window_of(i) for i in config.numbers_here]
    assert None in kinds and 16 in kinds
    dense = [config.is_dense(i) for i in config.numbers_here]
    assert True in dense and False in dense
    assert metrics["moe_counts"].shape == (3, config.experts_here[1])
    assert int(metrics["moe_dropped"]) == 0
    assert int(metrics["moe_compact"]) == int(metrics["moe_layers"]) == 3
    checked, idle = 0, []
    for key, (collection, names, kind) in torch_key_map(
            ARCH, variables).items():
        if collection != "params":
            # nothing trains the buffer: it only chooses experts, so its
            # gradient is zero to the bit and it stays where it was seeded
            np.testing.assert_array_equal(
                _leaf(state.batch_stats, names, kind), weights[key])
            assert not want["delta"][key].any() \
                and not want["trace1"][key].any()
            continue
        # every gradient leaf: Adam's first moment after one step is
        # (1 - b1) times the gradient as the optimizer got it
        grad = want["trace1"][key]
        scale = max(float(np.abs(grad).max()), 1e-9)
        np.testing.assert_allclose(_leaf(mu1, names, kind), grad,
                                   atol=2e-5 * scale, err_msg=key)
        checked += 1
        if not np.abs(grad).max():
            idle.append(key)
            continue
        # Adam's step is the gradient over its own size: an entry whose
        # gradient is all but zero turns on the last bits of a float32
        # sum, so the change is held as a whole leaf, not entry by entry
        delta = _leaf(state.params, names, kind) - weights[key]
        off = np.linalg.norm(delta - want["delta"][key]) \
            / np.linalg.norm(want["delta"][key])
        assert off < 2e-3, (key, off)
    assert checked == len(want["trace1"]) - 3 > 60
    # every leaf got a gradient worth comparing, but for an expert that no
    # token of these 128 chose
    assert len(idle) <= 6 and all(".experts." in k for k in idle), idle


class _NoNorm(nn.Module):
    """An ``RMSNorm`` that holds its weight and does nothing."""

    eps: float
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return x.astype(self.dtype)


def _without_norms(*names):
    def norm(eps, dtype=jnp.float32, name=None):
        return (_NoNorm if name in names else token_model.RMSNorm)(
            eps, dtype, name=name)
    return norm


# what is patched for the run of one faulty program: owner, attribute,
# stand-in
FAULTS = {
    "no-gate": (trinity.nn, "sigmoid", lambda gate: jnp.ones_like(gate)),
    "no-head-norms": (trinity, "RMSNorm",
                      _without_norms("q_norm", "k_norm")),
    "no-post-attention-norm": (trinity, "RMSNorm",
                               _without_norms("post_attention_layernorm")),
    "no-pre-mlp-norm": (trinity, "RMSNorm",
                        _without_norms("pre_mlp_layernorm")),
    "no-post-mlp-norm": (trinity, "RMSNorm",
                         _without_norms("post_mlp_layernorm")),
    "no-input-norm": (trinity, "RMSNorm", _without_norms("input_layernorm")),
    "no-full-attention-layer": (trinity.TrinityConfig, "window_of",
                                lambda self, layer: self.sliding_window),
    "rotary-in-no-layer": (trinity, "rotary", lambda x, theta: x),
}


@pytest.mark.parametrize("fault", [None, "embedding-not-scaled", *FAULTS])
def test_a_program_that_leaves_a_piece_out_fails_the_comparison(
        monkeypatch, fault):
    config = TINY.held(**SHARE)
    net = None
    if fault == "embedding-not-scaled":
        net = trinity.Trinity(dataclasses.replace(config,
                                                  mup_enabled=False))
    elif fault:
        monkeypatch.setattr(*FAULTS[fault])
        # the same variables, another program: not the cached one
        net = trinity.Trinity(config)
    loss_gap, grad_gap = gaps(config, net)
    if fault is None:
        assert loss_gap < 1e-5 and grad_gap < 2e-4, (loss_gap, grad_gap)
    else:
        # a window in the full-attention layer too keeps the loss near
        # (uniform ids, a fresh model): the gradients do not follow
        assert loss_gap > 1e-4 or grad_gap > 0.05, (loss_gap, grad_gap)
        assert grad_gap > 0.02, (loss_gap, grad_gap)


# ------------------------------------------------------------ the shares --


def test_the_shares_add_up_to_the_uncut_layer_the_shared_expert_once():
    model, weights, _, variables = seeded(TINY)
    f = "model.layers.2.mlp."
    x = jnp.asarray(np.random.RandomState(3).randn(
        2, TINY.sequence_length, TINY.hidden_size).astype(np.float32))
    # the uncut reference: every routed expert, and the shared one
    want = jax.jit(jax.vmap(lambda row: reference.routed_experts(
        model, weights, f, row, "f32", experts=range(8))
        + reference.shared_expert(model, weights, f, row, "f32")))(x)
    whole = variables["params"]["layers_2"]
    stats = {"expert_bias":
             variables["batch_stats"]["layers_2"]["mlp"]["expert_bias"]}

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
    # route_scale and the family's 1e-20 are in the weights
    assert TINY.routing.scaling == 2.826 and TINY.routing.norm_eps == 1e-20
    scores = jnp.asarray([[0.9, 0.8, 0.1, 0.2]])
    _, w = token_model.route(scores, 0.0, 2, True, 2.826, eps=1e-20)
    assert float(w.sum()) == pytest.approx(2.826, rel=1e-6)


# ------------------------------- residuals kept through rematerialisation --


def _loss_and_grads(budget):
    _, _, _, variables = seeded(TINY)
    net = trinity.Trinity(TINY, residual_budget=budget)
    batch = rows(TINY)

    def loss(params):
        sums = net.apply(
            {**variables, "params": params}, jnp.asarray(batch["tokens"]),
            labels=jnp.asarray(batch["labels"]),
            mask=token_row_weights(jnp.asarray(batch["mask"])))
        return sums["loss_sum"] / 2, sums

    (loss, sums), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        variables["params"])
    return loss, grads, sums


def test_keeping_residuals_changes_nothing_and_the_step_counts_two_kinds():
    classes = trinity.residual_classes(TINY, (2, 64), jnp.float32)
    assert [what for what, _, _ in classes] == [
        "attention out+lse", "dense feed-forward",
        "attention output projections", "expert rows and products"]
    # five layers: out [2, 4, 64, 8] and lse, float32; gate and up
    # [128, 96] of the two dense layers; five o_proj [128, 64]; three
    # expert layers, all eight held: the 256 slots' rows and third
    # product [256, 64], first two products [256, 32] and each token's
    # two chosen experts [128, 2] int32
    assert [size for _, _, size in classes] == [
        5 * 2 * 4 * 64 * (8 * 4 + 4), 2 * 2 * 128 * 96 * 4,
        5 * 128 * 64 * 4, 3 * (256 * 2 * (64 + 32) * 4 + 128 * 2 * 4)]
    kept = trinity.Trinity(TINY, residual_budget=2**62).kept(rows=2)
    assert kept.names == attention_op.RESIDUAL_NAMES + (
        "ffn_gate", "ffn_up", "attention_out_proj") \
        + token_model.COMPACT_RESIDUALS + ("expert_chosen",)
    want_loss, want, nothing = _loss_and_grads(0)
    got_loss, got, sums = _loss_and_grads(2**62)
    # 947,200 bytes at toy widths: the step says whole megabytes
    assert int(nothing["kept_residual_mb"]) == 0
    assert int(sums["kept_residual_mb"]) == kept.megabytes == 1
    assert float(got_loss) == float(want_loss)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree_util.tree_leaves(got)):
        # a kept value is the value that would have been made again;
        # the two programs add a gradient's terms up in another order
        # (tests/test_lfm2.py holds the shared machinery to the bit)
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=0,
            atol=2e-6 * float(np.abs(a).max()), err_msg=str(path))
    # five calls of one shape and two kinds: four with the window, one
    # without; a row of 64 is one tile either way, and on the CPU no call
    # takes the kernels
    assert {k: int(v) for k, v in sums.items()
            if k.startswith("attention_")} == {
        "attention_calls": 5, "attention_kernel_calls": 0,
        "attention_window_calls": 4, "attention_tiles": 5,
        "attention_tiles_causal": 5}


def test_the_cells_budget_holds_all_four_classes():
    """The cell's share on the chip's 16.9 GB: the budget this model's
    own headroom leaves holds every class, the expert layers' among them
    at the compact buffers' 16,384 rows."""
    share = trinity.TrinityConfig().held(
        layers=(1, 5), experts=(0, 16), vocab=(0, 25024),
        sequence_length=8192)
    net = trinity.Trinity(share, dtype=jnp.bfloat16)
    state_bytes = 3 * 4 * 705_473_792
    fitted = net.fitted_to(16_909_336_064, state_bytes)
    assert trinity.STEP_HEADROOM_BYTES == 6_100_000_000
    assert fitted.residual_budget == 16_909_336_064 - state_bytes \
        - trinity.STEP_HEADROOM_BYTES == 2_343_650_560
    sizes = [size for _, _, size in net.residual_classes((1, 8192))]
    # five layers' out [1, 4, 65536, 128] bf16 and lse; one dense
    # layer's gate and up [8192, 6144]; five o_proj [8192, 2048]; four
    # expert layers' rows and third product [16384, 2048], gate and up
    # [16384, 1024], the chosen experts [8192, 8] int32
    assert sizes == [5 * 8192 * 32 * (128 * 2 + 4), 2 * 8192 * 6144 * 2,
                     5 * 8192 * 2048 * 2,
                     4 * (16384 * 2 * (2048 + 1024) * 2 + 8192 * 8 * 4)] == [
                         340_787_200, 201_326_592, 167_772_160, 806_354_944]
    kept = fitted.kept(rows=1)
    assert kept.bytes == sum(sizes) == 1_516_240_896
    assert kept.megabytes == 1516 and len(kept.classes) == 4
    # a chip the state fills keeps nothing, one that reports nothing too
    assert net.fitted_to(14 * 10**9, state_bytes).kept(1) \
        == token_model.Kept()
    assert net.fitted_to(0, state_bytes).residual_budget == 0


def test_the_cells_step_counts_a_band_of_the_causal_tiles():
    """The counters at the cell's shape, from shapes alone (nothing is
    run): four window layers of 70 tiles and one full one of 136, of the
    five triangles' 680."""
    share = trinity.TrinityConfig().held(
        layers=(1, 5), experts=(0, 16), vocab=(0, 25024),
        sequence_length=8192)
    windows = [share.window_of(i) for i in share.numbers_here]
    assert windows == [2048, 2048, None, 2048, 2048]
    sums = jax.eval_shape(lambda: token_model.with_counters(
        {}, [], 0, token_model.Kept(), windows, 8192, jnp.zeros((), jnp.int32)))
    assert set(sums) == {
        "kept_residual_mb", "attention_calls", "attention_kernel_calls",
        "attention_window_calls", "attention_tiles",
        "attention_tiles_causal"}
    counted = token_model.with_counters(
        {}, [], 0, token_model.Kept(), windows, 8192, 1)
    assert (int(counted["attention_tiles"]),
            int(counted["attention_tiles_causal"]),
            int(counted["attention_window_calls"])) == (4 * 70 + 136, 680, 4)
    # a model without windows reports the two equal
    causal = token_model.with_counters(
        {}, [], 0, token_model.Kept(), (None, None), 8192, 1)
    assert int(causal["attention_tiles"]) \
        == int(causal["attention_tiles_causal"]) == 272
    assert int(causal["attention_window_calls"]) == 0


def test_the_loop_passes_the_attentions_counts_on_as_they_are():
    """Constants of the step program: the ``fetch`` span says them once,
    however many steps the fetch read."""
    from dptpu.train.loop import MoeLoad

    counts = {"attention_calls": 5, "attention_kernel_calls": 5,
              "attention_window_calls": 4, "attention_tiles": 416,
              "attention_tiles_causal": 680}
    step = {"loss": 1.0, **{k: np.int32(v) for k, v in counts.items()}}
    attrs = MoeLoad().take([step] * 3)
    assert attrs == counts
    assert all(type(v) is int for v in attrs.values())


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
    assert kmap["model.layers.2.mlp.expert_bias"][0] == "batch_stats"
    for name in (
            "model.embed_tokens.weight", "lm_head.weight",
            "model.norm.weight", "model.layers.0.mlp.gate_proj.weight",
            "model.layers.1.mlp.down_proj.weight",
            "model.layers.0.self_attn.gate_proj.weight",
            "model.layers.0.self_attn.q_norm.weight",
            "model.layers.3.self_attn.k_norm.weight",
            "model.layers.0.input_layernorm.weight",
            "model.layers.0.post_attention_layernorm.weight",
            "model.layers.0.pre_mlp_layernorm.weight",
            "model.layers.4.post_mlp_layernorm.weight",
            "model.layers.2.mlp.router.gate.weight",
            "model.layers.2.mlp.experts.7.down_proj.weight",
            "model.layers.4.mlp.shared_experts.up_proj.weight"):
        assert name in kmap, name
    # untied: the head and the embedding are two leaves, held as torch
    # holds them
    assert kmap["lm_head.weight"][2] == "direct"
    assert not np.array_equal(weights["lm_head.weight"],
                              weights["model.embed_tokens.weight"])


def test_the_published_configuration_and_a_chips_share():
    published = trinity.TrinityConfig()
    assert model_task("trinity_mini") == "tokens"
    assert published.layer_types.count("full_attention") == 8
    assert [i for i in range(32) if published.window_of(i) is None] == [
        3, 7, 11, 15, 19, 23, 27, 31]
    assert published.window_of(0) == 2048
    assert published.routing == token_model.Routing(
        experts=128, held=(0, 128), top_k=8, norm_topk=True,
        norm_eps=1e-20, scaling=2.826, use_bias=True, width=1024)

    def count(config):
        net = trinity.Trinity(config)
        shapes = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0),
                                                 net.example_input()))
        by_module = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                shapes["params"])[0]:
            by_module[path[0].key] = by_module.get(path[0].key, 0) + leaf.size
        return by_module

    share = published.held(layers=(1, 5), experts=(0, 16),
                           vocab=(0, 25024), sequence_length=8192)
    assert share.layers_here == (1, 5) and share.experts_here == (0, 16)
    assert share.vocab_size == 25024 and share.num_experts == 128
    # no width moves, nor the window
    for width in ("hidden_size", "intermediate_size", "head_dim",
                  "moe_intermediate_size", "num_experts_per_tok",
                  "num_attention_heads", "num_key_value_heads",
                  "sliding_window"):
        assert getattr(share, width) == getattr(published, width)
    held = count(share)
    # ISSUE 43's arithmetic: the dense layer 1, four expert layers of 16
    # experts and a shared one (layer 3 the full-attention one: the same
    # leaves), an eighth of the vocabulary twice, the final norm
    assert held == {
        "layers_1": 65_020_160, "layers_2": 134_488_320,
        "layers_3": 134_488_320, "layers_4": 134_488_320,
        "layers_5": 134_488_320, "embed_tokens": 51_249_152,
        "lm_head": 51_249_152, "norm": 2048}
    assert sum(held.values()) == 705_473_792
    # the one size ISSUE 43 lets move: 8 experts held
    assert sum(count(share.held(experts=(0, 8))).values()) == 504_147_200
    # the whole model from those (its 11,520 expert matrices are not
    # traced here): two dense layers, thirty expert layers with 112 more
    # experts of 3 x 2,048 x 1,024 each, the whole vocabulary twice
    expert = 3 * 2048 * 1024
    whole = 2 * held["layers_1"] + 30 * (held["layers_2"] + 112 * expert) \
        + 2 * 200_192 * 2048 + 2048
    assert whole == 26_123_970_560  # "26B"
    # a later stage keeps the published numbers and kinds of its layers
    later = published.held(layers=(6, 2), experts=(16, 16),
                           vocab=(25024, 25024))
    assert [later.window_of(i) for i in later.numbers_here] == [2048, None]
    assert set(count(later)) == {"layers_6", "layers_7", "embed_tokens",
                                 "lm_head", "norm"}
    with pytest.raises(ValueError, match="FIRST:COUNT"):
        _REGISTRY["trinity_mini"](experts="8")
    with pytest.raises(ValueError, match="not among the 128 experts"):
        published.held(experts=(120, 16))
    with pytest.raises(ValueError, match="not among the 32 layers"):
        published.held(layers=(30, 4))
    with pytest.raises(ValueError, match="does not implement n_group"):
        trinity.TrinityConfig(n_group=8, topk_group=4)
    with pytest.raises(ValueError, match="31 layer types for 32 layers"):
        trinity.TrinityConfig(layer_types=published.layer_types[1:])


def test_the_reference_counts_the_scores_the_mask_keeps():
    model = reference_model(trinity.TrinityConfig().held(
        layers=(1, 5), experts=(0, 16), vocab=(0, 25024),
        sequence_length=8192))
    window = sum(min(p + 1, 2048) for p in range(8192))
    assert reference.visible_keys(model, "sliding_attention") == window
    assert reference.visible_keys(model, "full_attention") \
        == 8192 * 8193 // 2
    assert int(reference.visible(8192, 2048).sum()) == window
    # ISSUE 43's count: 738 M products' operations a token forward,
    # 18.1 TFLOP a step with the backward pass
    per_token = reference.forward_flops_per_row(model) / 8192
    assert per_token == pytest.approx(738e6, rel=0.005)
    assert reference.train_flops(model, 1) == pytest.approx(18.1e12,
                                                            rel=0.005)
    # the causal half would count 136 / 70 of the window layers' scores
    causal = dict(model, sliding_window=8192)
    assert reference.train_flops(causal, 1) > 1.2 * reference.train_flops(
        model, 1)


# ----------------------------------------------------------- through fit --


def test_main_apex_trains_a_share_of_it_through_fit(tmp_path, monkeypatch,
                                                    capsys):
    from dptpu.cli import main_apex

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("WORLD_SIZE", "1")
    monkeypatch.setenv("DPTPU_OBS_DIR", str(tmp_path / "obs"))
    monkeypatch.setenv("DPTPU_WORKERS_MODE", "thread")
    # the trainer's flags for a share, as for the other token models: the
    # toy model's layers 1-3 (a dense one, a window one with experts, the
    # full one), half its experts and vocabulary
    result = main_apex([
        "tokens:16", "-a", ARCH, "--optimizer", "adamw", "--beta2", "0.95",
        "--wd", "0.1", "--lr", "0.08", "-b", "2", "--seq-len", "32",
        "--layers", "1:3", "--experts", "0:4", "--vocab-rows", "0:128",
        "--opt-level", "O2", "-p", "4", "--epochs", "1",
        "--ckpt-dir", str(tmp_path / "ckpt")])
    out = capsys.readouterr().out
    assert "=> residuals kept through the rematerialisation: nothing" in out
    epoch = result["history"][0]
    assert np.log(128) - 0.5 < epoch["train_loss"] < 6.0
    assert np.isfinite(epoch["val_loss"])
    assert epoch["train_moe_dropped"] == 0
    assert epoch["train_moe_compact_share"] == 100.0
    assert 40 < epoch["train_moe_local_slot_share"] < 60  # 4 of 8 held
    params = result["state"].params
    assert {k for k in params if k.startswith("layers_")} == {
        "layers_1", "layers_2", "layers_3"}
    assert params["lm_head"].shape == (128, 64)
    assert set(params["layers_2"]["mlp"]) == {
        "gate", "experts_0", "experts_1", "experts_2", "experts_3"}
    assert set(params["layers_1"]) == set(params["layers_2"]) - {
        "shared_experts"}
    # the fetch span carries the attention's calls of both kinds (the
    # step adds what the replicas count: the suite's pool has eight)
    n = jax.device_count()
    (log,) = [f for f in os.listdir(tmp_path / "obs") if f.endswith(".jsonl")]
    with open(tmp_path / "obs" / log) as f:
        fetches = [r for r in map(json.loads, f)
                   if r.get("kind") == "span" and r.get("name") == "fetch"]
    carrying = [r["attrs"] for r in fetches
                if "attention_tiles" in r.get("attrs", {})]
    assert carrying and all(
        (a["attention_calls"], a["attention_window_calls"],
         a["attention_tiles"], a["attention_tiles_causal"],
         a["attention_kernel_calls"]) == (3 * n, 2 * n, 3 * n, 3 * n, 0)
        and a["moe_dropped"] == 0
        and a["moe_compact_layers"] == a["moe_layers"] > 0
        for a in carrying)
