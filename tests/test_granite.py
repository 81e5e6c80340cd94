"""granite-4.0-h-micro (``dptpu/models/granite.py``) and its state-space
scan (``dptpu/ops/ssd.py``) against the plain reference
(``benchmark/reference/granitemoehybrid.py``: the recurrence token by
token) on seeded weights at toy widths: logits, loss and every gradient
leaf, whole and as a chip's share, in float32 and at the bfloat16 step's
tolerance; two AdamW steps through the step builder; the scan against
the recurrence, value and all six gradients, at lengths that are and are
not whole chunks, under a decay that overflows a ratio of exponentials;
the vocabulary's slices side by side are the whole head; a stage's
layers follow the published index; what decays and what does not; every
leaf name through the converter and back; the published configuration's
count; the reference's loop over layers against the plain one; and
``main_apex`` training it through ``fit()`` and resuming bit for bit.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common as reference_common
from benchmark.reference import granitemoehybrid as reference
from benchmark.reference.optimizers import adamw as reference_adamw
from dptpu.models import granite, token_model
from dptpu.models.pretrained import (
    _to_torch,
    convert_state_dict,
    torch_key_map,
)
from dptpu.models.registry import _REGISTRY, model_task, register_model
from dptpu.ops import ssd as ssd_op
from dptpu.ops.optimizers import trust_mask
from dptpu.train.state import create_train_state, make_optimizer
from dptpu.train.step import make_train_step, token_row_weights

# one period of the published pattern (nine scans, the attention layer at
# 5) at toy widths; a row is no whole number of chunks (8) or of the
# reference's segments (64)
TINY = granite.GraniteConfig(
    vocab_size=256, hidden_size=32, shared_intermediate_size=48,
    num_hidden_layers=10, layer_types=granite._MICRO_LAYERS[:10],
    num_attention_heads=4, num_key_value_heads=2, attention_multiplier=0.1,
    mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=8,
    sequence_length=29)
ARCH = "granite_test_tiny"
if ARCH not in _REGISTRY:
    register_model(granite.factory(ARCH, TINY))

HYPER = {"name": "adamw", "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1}


def reference_model(config: granite.GraniteConfig) -> dict:
    """The reference's ``model`` group for a program configuration."""
    first, held = config.layers_here
    same = ("hidden_size", "shared_intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "attention_multiplier",
            "embedding_multiplier", "residual_multiplier", "logits_scaling",
            "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv",
            "mamba_expand", "mamba_n_groups", "mamba_chunk_size",
            "rms_norm_eps", "vocab_size", "sequence_length")
    return {**{key: getattr(config, key) for key in same},
            "layers_first": first, "layers_held": held,
            "layer_types": [kind for _, kind in config.types_here]}


@functools.lru_cache(maxsize=None)
def seeded(config, seed=5, dtype=jnp.float32):
    """``(reference model, weights by checkpoint name, program net,
    program variables)`` for ``config``, the weights drawn as the family's
    ``weight_spec`` says, on the host."""
    model = reference_model(config)
    rng = np.random.RandomState(seed)
    draw = {"const": lambda shape, s: np.full(shape, s),
            "normal": lambda shape, s: s * rng.randn(*shape),
            "uniform": lambda shape, s: rng.uniform(-s, s, shape)}
    weights = {name: draw[kind](shape, scale).astype(np.float32)
               for name, shape, kind, scale in reference.weight_spec(model)}
    net = granite.Granite(config, dtype=dtype)
    template = jax.eval_shape(lambda: net.init(
        jax.random.PRNGKey(0), net.example_input()))
    return model, weights, net, convert_state_dict(ARCH, weights, template)


def rows(config, n=1, seed=0):
    rng = np.random.RandomState(seed)
    length = config.sequence_length
    ids = rng.randint(0, config.vocab_size, (n, length + 1)).astype(np.int32)
    kept = rng.randint(length - length // 8, length + 1, n)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:],
            "mask": np.arange(length)[None] < kept[:, None]}


def program_loss(net, variables, batch):
    """The step's loss: the mean over rows of the row's loss."""
    def loss(params):
        sums = net.apply(
            {"params": params}, jnp.asarray(batch["tokens"]),
            labels=jnp.asarray(batch["labels"]),
            mask=token_row_weights(jnp.asarray(batch["mask"])))
        return sums["loss_sum"] / batch["tokens"].shape[0]
    return loss


def leaf_of(tree, names):
    return functools.reduce(lambda t, n: t[n], names, tree)


@functools.lru_cache(maxsize=None)
def reference_gradient(config, n=2):
    """``(batch, loss, gradient by checkpoint name)`` of the reference on
    ``rows(config, n)``: compiled once a configuration (eagerly every
    operation of the token-by-token recurrence is a program of its own)."""
    model, weights, _, _ = seeded(config)
    batch = rows(config, n=n)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda w: reference.loss(model, w, batch)))(weights)
    return batch, loss, grads


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


# --------------------------------------------------- program == reference --


SHARES = pytest.mark.parametrize("share", [
    {}, {"layers": (4, 3), "vocab": (64, 128)}],
    ids=["whole", "layers4-6-vocab128"])


@SHARES
def test_logits_loss_and_every_gradient_leaf_match_the_reference(share):
    config = TINY.held(**share)
    model, weights, net, variables = seeded(config)
    batch, want, want_grads = reference_gradient(config)
    logits = jax.jit(net.apply)(variables, jnp.asarray(batch["tokens"]))
    forward = jax.jit(functools.partial(reference.forward, model))
    for row, tokens in zip(logits, batch["tokens"]):
        np.testing.assert_allclose(row, forward(weights, tokens), atol=2e-6)
    got, grads = jax.jit(jax.value_and_grad(
        program_loss(net, variables, batch)))(variables["params"])
    assert float(got) == pytest.approx(float(want), abs=1e-5)
    for key, (_, names, kind) in torch_key_map(ARCH, variables).items():
        grad = np.asarray(want_grads[key])
        assert np.abs(grad).max() > 0, key  # every leaf is in the loss
        np.testing.assert_allclose(
            _to_torch(np.asarray(leaf_of(grads, names)), kind), grad,
            atol=2e-5 * np.abs(grad).max(), err_msg=key)


def test_the_bfloat16_step_reads_as_the_reference_within_its_tolerance():
    """Under O2 the products take bfloat16 operands; the decays, the
    state, the norms' statistics and the loss stay float32. The loss and
    every kernel's gradient, as one norm a leaf, stay within what the
    cell's limits allow a sound bfloat16 program."""
    _, _, _, variables = seeded(TINY)
    net = granite.Granite(TINY, dtype=jnp.bfloat16)
    batch, want, want_grads = reference_gradient(TINY)
    got, grads = jax.jit(jax.value_and_grad(
        program_loss(net, variables, batch)))(variables["params"])
    assert float(got) == pytest.approx(float(want), abs=5e-3)
    gaps, kernels = {}, set()
    for key, (_, names, kind) in torch_key_map(ARCH, variables).items():
        grad = np.asarray(want_grads[key])
        ours = _to_torch(np.asarray(leaf_of(grads, names)), kind)
        gaps[key] = np.linalg.norm(ours - grad) / np.linalg.norm(grad)
        if grad.ndim >= 2:
            kernels.add(key)
    # a vector of eight entries (a head's A_log, D, dt_bias) sums fewer
    # rounded terms than a matrix and reads higher
    assert max(gaps[k] for k in kernels) < 0.05, max(kernels, key=gaps.get)
    assert max(gaps.values()) < 0.2, max(gaps, key=gaps.get)
    assert np.median(list(gaps.values())) < 0.02


@SHARES
def test_two_adamw_steps_through_the_step_builder_match_the_reference(share):
    config = TINY.held(**share)
    model, weights, net, variables = seeded(config)
    batches = [rows(config, seed=s) for s in range(2)]
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
    losses = []
    for batch in batches:
        state, metrics = step(state, {k: jnp.asarray(v)
                                      for k, v in batch.items()})
        losses.append(float(metrics["loss"]))
    assert losses == pytest.approx(want["loss"], abs=1e-5)
    # a dense model: no expert counts; the scan's calls ride instead
    assert not [k for k in metrics if k.startswith("moe_")]
    scans = sum(kind == "mamba" for _, kind in config.types_here)
    assert (int(metrics["ssd_calls"]), int(metrics["ssd_kernel_calls"]),
            int(metrics["ssd_chunks"])) == (scans, 0, 4)
    assert int(metrics["attention_calls"]) == 1
    # no call has a window: the tiles walked are the causal triangle's
    assert int(metrics["attention_window_calls"]) == 0
    assert int(metrics["attention_tiles"]) \
        == int(metrics["attention_tiles_causal"]) \
        == int(metrics["attention_calls"])  # a toy row is one tile
    for key, (_, names, kind) in torch_key_map(ARCH, variables).items():
        delta = _to_torch(np.asarray(leaf_of(state.params, names)), kind) \
            - weights[key]
        off = np.linalg.norm(delta - want["delta"][key]) \
            / np.linalg.norm(want["delta"][key])
        assert off < 2e-3, (key, off)


# ---------------------------------------------------------------- the scan --


def scan_inputs(length, heads=3, width=4, state=5, rate=1.0, seed=0,
                rows=2):
    """Inputs of ``ssd``; ``rate`` scales ``A``: at 30 a chunk of 16
    tokens decays by hundreds of nats."""
    rng = np.random.RandomState(seed)
    normal = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)  # noqa: E731
    return (normal(rows, length, heads, width),
            jax.nn.softplus(normal(rows, length, heads)),
            -rate * jnp.exp(normal(heads)),
            normal(rows, length, state), normal(rows, length, state),
            normal(heads))


@pytest.mark.parametrize("length,chunk,rate", [
    (32, 8, 1.0), (29, 8, 1.0), (64, 16, 30.0), (5, 8, 1.0), (50, 16, 30.0)],
    ids=["whole-chunks", "ragged", "whole-chunks-strong-decay",
         "shorter-than-a-chunk", "ragged-strong-decay"])
def test_the_chunked_scan_is_the_recurrence_value_and_six_gradients(
        length, chunk, rate):
    inputs = scan_inputs(length, rate=rate)
    weigh = jnp.asarray(np.random.RandomState(1).randn(
        *inputs[0].shape), jnp.float32)
    if rate > 1:
        # exp(s_i) / exp(s_j) would be inf / inf (or 0 / 0) here: float32
        # holds exp of 88 at most
        assert float(jnp.max(inputs[1] * -inputs[2])) * chunk > 200
    value = lambda scan: lambda *a: jnp.sum(scan(*a) * weigh)  # noqa: E731
    chunked = functools.partial(ssd_op.ssd, chunk=chunk)
    np.testing.assert_allclose(chunked(*inputs), ssd_op.plain_ssd(*inputs),
                               atol=2e-5)
    got = jax.grad(value(chunked), argnums=range(6))(*inputs)
    want = jax.grad(value(ssd_op.plain_ssd), argnums=range(6))(*inputs)
    for name, ours, theirs in zip(("x", "dt", "a", "b", "c", "d"), got, want):
        assert np.isfinite(np.asarray(ours)).all(), name
        scale, share = theirs, 2e-5
        if name == "a":
            # a sum over every token of Δ_t times the exponent's gradient,
            # whose terms are the dt gradient's and nearly cancel under a
            # strong decay (0.007 left of terms of 40): rounding goes with
            # the terms, in either form (against float64 the chunked form
            # is 1e-5 off here and the token-by-token one 2e-7)
            scale, share = want[1], 5e-6
        np.testing.assert_allclose(
            ours, theirs, atol=share * float(jnp.abs(scale).max()),
            err_msg=name)


def test_the_scans_result_does_not_depend_on_the_chunk_or_the_grouping(
        monkeypatch):
    inputs = scan_inputs(48, rate=8.0)
    want = ssd_op.plain_ssd(*inputs)
    for chunk in (4, 16, 48, 256):
        np.testing.assert_allclose(ssd_op.ssd(*inputs, chunk=chunk), want,
                                   atol=2e-5)
    # one chunk a group (a budget of one chunk's decay matrices) or all
    # twelve: the state crosses groups as it crosses chunks
    assert ssd_op._group_size(2, 12, 3, 4) == 12
    monkeypatch.setattr(ssd_op, "DECAY_BYTES", 2 * 3 * 4 * 4 * 4)
    assert ssd_op._group_size(2, 12, 3, 4) == 1
    np.testing.assert_allclose(ssd_op.ssd(*inputs, chunk=4), want, atol=2e-5)


def test_the_scan_takes_bfloat16_operands_and_keeps_float32_decays():
    inputs = scan_inputs(40, rate=4.0)
    want = ssd_op.plain_ssd(*inputs)
    low = ssd_op.ssd(inputs[0].astype(jnp.bfloat16), *inputs[1:], chunk=8)
    assert low.dtype == jnp.bfloat16
    gap = jnp.linalg.norm(low.astype(jnp.float32) - want) \
        / jnp.linalg.norm(want)
    assert 1e-4 < float(gap) < 2e-2
    # at the published sizes a row of 8,192 is 32 chunks in 4 groups
    assert ssd_op.chunks_of(8192) == 32 and ssd_op.chunks_of(100) == 1
    assert ssd_op._group_size(1, 32, 64, 256) == 8
    assert ssd_op.kernel_calls() == 0


# ------------------------------------------------------------ the shares --


def test_the_vocabulary_slices_side_by_side_are_the_whole_heads_logits():
    """Eight chips hold 32 rows each of the tied embedding and head. The
    ids of this row lie in every slice's first 8 rows and those rows are
    made the same in every slice, so each slice embeds the row as the
    whole model does (a deployment exchanges the embedded rows instead);
    each slice's logits are then its columns of the whole head's."""
    whole = TINY.held(layers=(3, 4))
    _, _, net, variables = seeded(whole)
    embedding = np.array(variables["params"]["embed_tokens"]["embedding"])
    for first in range(32, 256, 32):
        embedding[first:first + 8] = embedding[:8]
    tokens = jnp.asarray(rows(whole)["tokens"]) % 8

    def logits(model, rows_held):
        return jax.jit(model.apply)({"params": {
            **variables["params"],
            "embed_tokens": {"embedding": jnp.asarray(rows_held)}}}, tokens)

    side_by_side = jnp.concatenate([
        logits(granite.Granite(whole.held(vocab=(first, 32))),
               embedding[first:first + 32])
        for first in range(0, 256, 32)], axis=-1)
    assert side_by_side.shape == (1, 29, 256)
    np.testing.assert_allclose(side_by_side, logits(net, embedding),
                               atol=1e-6)


def test_a_stages_layers_follow_the_published_index_not_the_held_one():
    published = granite.GraniteConfig()
    stage = published.held(layers=(5, 5))
    assert stage.types_here == ((5, "attention"), (6, "mamba"), (7, "mamba"),
                                (8, "mamba"), (9, "mamba"))
    assert published.held(layers=(30, 10)).types_here[5] == (35, "attention")
    tiny = TINY.held(layers=(5, 2))
    net = granite.Granite(tiny)
    params = jax.eval_shape(lambda: net.init(
        jax.random.PRNGKey(0), net.example_input()))["params"]
    assert set(params) == {"embed_tokens", "layers_5", "layers_6", "norm"}
    assert "self_attn" in params["layers_5"] and "mamba" in params["layers_6"]
    with pytest.raises(ValueError, match="not among the 40 layers"):
        published.held(layers=(35, 6))
    with pytest.raises(ValueError, match="this model has no experts"):
        published.held(experts=(0, 8))
    with pytest.raises(ValueError, match="FIRST:COUNT"):
        _REGISTRY["granite_4_0_h_micro"](layers="10")
    with pytest.raises(ValueError, match="does not implement experts"):
        granite.GraniteConfig(num_local_experts=8)


# ------------------------------------------------- names and configuration --


def test_only_matrices_and_the_convolutions_kernel_take_weight_decay():
    _, _, _, variables = seeded(TINY)
    decays = {"/".join(str(p.key) for p in path): bool(flag)
              for path, flag in jax.tree_util.tree_flatten_with_path(
                  trust_mask(variables["params"]))[0]}
    mixer = "layers_0/mamba/"
    assert decays[mixer + "conv1d"] and decays[mixer + "in_proj/kernel"]
    assert decays["embed_tokens/embedding"]
    assert decays["layers_5/self_attn/q_proj/kernel"]
    assert decays["layers_0/shared_mlp/input_linear/kernel"]
    none = [mixer + leaf for leaf in ("A_log", "D", "dt_bias", "conv1d_bias",
                                      "norm/scale")]
    none += ["layers_0/input_layernorm/scale", "norm/scale",
             "layers_5/post_attention_layernorm/scale"]
    assert not any(decays[name] for name in none)
    # the reference's rule is the same one: a leaf of two axes or more
    model = reference_model(TINY)
    for name, shape, *_ in reference.weight_spec(model):
        (_, names, _), = [v for k, v in torch_key_map(
            ARCH, variables).items() if k == name]
        assert (len(shape) >= 2) == decays["/".join(names)], name


def test_every_leaf_name_goes_through_the_converter_and_back():
    _, weights, _, variables = seeded(TINY)
    kmap = torch_key_map(ARCH, variables)
    assert set(kmap) == set(weights)  # every name of the layout, no other
    for key, (collection, names, kind) in kmap.items():
        np.testing.assert_array_equal(
            _to_torch(np.asarray(leaf_of(variables[collection], names)),
                      kind), weights[key], err_msg=key)
    for name in (
            "model.embed_tokens.weight", "model.norm.weight",
            "model.layers.0.input_layernorm.weight",
            "model.layers.0.mamba.in_proj.weight",
            "model.layers.0.mamba.conv1d.weight",
            "model.layers.0.mamba.conv1d.bias", "model.layers.0.mamba.A_log",
            "model.layers.0.mamba.D", "model.layers.0.mamba.dt_bias",
            "model.layers.0.mamba.norm.weight",
            "model.layers.0.mamba.out_proj.weight",
            "model.layers.5.self_attn.o_proj.weight",
            "model.layers.5.post_attention_layernorm.weight",
            "model.layers.9.shared_mlp.input_linear.weight",
            "model.layers.9.shared_mlp.output_linear.weight"):
        assert name in kmap, name
    # torch's depthwise kernel [channels, 1, taps] <-> [taps, channels]
    assert kmap["model.layers.0.mamba.conv1d.weight"][2] == "conv1d_dw"
    assert weights["model.layers.0.mamba.conv1d.weight"].shape == (96, 1, 4)
    assert variables["params"]["layers_0"]["mamba"]["conv1d"].shape == (4, 96)


def test_the_published_configuration_and_the_cells_share():
    published = granite.GraniteConfig()
    assert model_task("granite_4_0_h_micro") == "tokens"
    assert published.layer_types.count("attention") == 4
    assert [i for i, kind in enumerate(published.layer_types)
            if kind == "attention"] == [5, 15, 25, 35]
    share = published.held(layers=(0, 10), vocab=(0, 12544),
                           sequence_length=8192)
    for width in ("hidden_size", "shared_intermediate_size", "mamba_n_heads",
                  "mamba_d_head", "mamba_d_state", "mamba_chunk_size",
                  "num_attention_heads", "num_key_value_heads"):
        assert getattr(share, width) == getattr(published, width)
    net = granite.Granite(share)
    shapes = jax.eval_shape(lambda: net.init(
        jax.random.PRNGKey(0), net.example_input()))["params"]
    by_module = {name: sum(leaf.size for leaf in
                           jax.tree_util.tree_leaves(tree))
                 for name, tree in shapes.items()}
    # a Mamba-2 layer: in_proj 2,048 x 8,512, out_proj 4,096 x 2,048, the
    # feed-forward 3 x 2,048 x 8,192, the convolution 4 x 4,352 + 4,352,
    # three vectors a head, three norms
    assert by_module["layers_0"] == 76_182_976
    assert by_module["layers_5"] == 60_821_504
    assert by_module["embed_tokens"] == 12544 * 2048
    assert sum(by_module.values()) == 772_160_448
    assert shapes["layers_0"]["mamba"]["in_proj"]["kernel"].shape \
        == (2048, 4096 + 4352 + 64)
    # the step keeps the attention's own residuals when a device leaves it
    # room, and says what it takes beside state and residuals
    kept = net.fitted_to(16_900_000_000, 12 * 772_160_448).kept(1)
    assert kept.classes == ("attention out+lse",)
    assert granite.Granite.step_headroom_bytes == granite.STEP_HEADROOM_BYTES
    assert token_model.TokenModel.step_headroom_bytes == 0


def test_the_references_loop_over_layers_is_the_plain_loop():
    """``scan_blocks`` spells out its own way back (one compiled block,
    no stack of the layers' weights): value and every gradient are the
    Python loop's."""
    rng = np.random.RandomState(3)
    leaves = {"w": [jnp.asarray(rng.randn(6, 6), jnp.float32) * 0.3
                    for _ in range(4)],
              "b": [jnp.asarray(rng.randn(6), jnp.float32) for _ in range(4)]}
    x = jnp.asarray(rng.randn(5, 6), jnp.float32)

    def block(x, here):
        return jnp.tanh(x @ here["w"] + here["b"]) + x

    def plain(x, leaves):
        for k in range(4):
            x = block(x, {n: v[k] for n, v in leaves.items()})
        return jnp.sum(x ** 2)

    def scanned(x, leaves):
        return jnp.sum(reference.scan_blocks(block, x, leaves) ** 2)

    want = jax.value_and_grad(plain, argnums=(0, 1))(x, leaves)
    got = jax.jit(jax.value_and_grad(scanned, argnums=(0, 1)))(x, leaves)
    for ours, theirs in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-6)


def test_the_witnesss_feed_forward_in_pieces_is_the_whole_rows(monkeypatch):
    """In the low-precision modes the reference walks a block's
    feed-forward ``MLP_ROWS`` tokens at a time (the chip's memory, not the
    mathematics): a token's result is the same and a weight's gradient
    adds up over the pieces; float32 takes the row whole."""
    config = TINY.held(layers=(4, 2), sequence_length=32)
    model, weights, _, _ = seeded(config)
    batch = rows(config, n=1)

    def run(mode, rows_at_a_time):
        monkeypatch.setattr(reference, "MLP_ROWS", rows_at_a_time)
        return jax.jit(jax.value_and_grad(
            lambda w: reference.loss(model, w, batch, mode)))(weights)

    whole, whole_grads = run("bf16", 4096)
    pieces, piece_grads = run("bf16", 8)
    assert float(pieces) == float(whole)
    for key in weights:
        # each piece's product comes back rounded to bfloat16 before the
        # pieces add up: the witness's own rounding, once more
        off = np.linalg.norm(piece_grads[key] - whole_grads[key]) \
            / np.linalg.norm(whole_grads[key])
        assert off < 1e-2, (key, off)
    proper, _ = run("f32", 8)
    assert float(proper) == float(run("f32", 4096)[0])
    assert abs(float(proper) - float(whole)) < 5e-3  # the witness is near


# ----------------------------------------------------------- through fit --


_ARGS = ["-a", ARCH, "--optimizer", "adamw", "--beta2", "0.95", "--wd", "0.1",
         "--lr", "0.08", "-b", "2", "--seq-len", "29", "--layers", "4:3",
         "--vocab-rows", "0:128", "--opt-level", "O2", "-p", "1"]


def test_main_apex_trains_a_share_of_it_through_fit_and_resumes(
        tmp_path, monkeypatch, capsys):
    from dptpu.cli import main_apex

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("WORLD_SIZE", "1")
    monkeypatch.setenv("DPTPU_OBS_DIR", str(tmp_path / "obs"))
    monkeypatch.setenv("DPTPU_WORKERS_MODE", "thread")
    # the trainer's flags for a share, as for the other token models, but
    # no --experts: two steps an epoch (-b is a chip's rows, and the tests'
    # pool has eight), two epochs straight through
    feed = f"tokens:{4 * jax.device_count()}"
    straight = main_apex([feed, *_ARGS, "--epochs", "2",
                          "--ckpt-dir", str(tmp_path / "straight")])
    out = capsys.readouterr().out
    assert "=> residuals kept through the rematerialisation: nothing" in out
    assert "Moe:" not in out
    epoch = straight["history"][0]
    assert np.log(128) - 0.3 < epoch["train_loss"] < 6.0
    assert np.isfinite(epoch["val_loss"])
    assert not [k for k in epoch if "moe" in k]
    params = straight["state"].params
    assert {k for k in params if k.startswith("layers_")} == {
        "layers_4", "layers_5", "layers_6"}
    assert params["embed_tokens"]["embedding"].shape == (128, 32)
    # the fetch span carries the scan's calls beside the attention's, and
    # the ckpt span what the save wrote and where its seconds went
    (log,) = [f for f in os.listdir(tmp_path / "obs") if f.endswith(".jsonl")]
    with open(tmp_path / "obs" / log) as f:
        spans = [r for r in map(json.loads, f) if r.get("kind") == "span"]
    carrying = [s["attrs"] for s in spans if s["name"] == "fetch"
                and "ssd_calls" in s.get("attrs", {})]
    chips = jax.device_count()  # a count is summed over the chips
    assert carrying and all(
        (a["ssd_calls"], a["ssd_kernel_calls"],
         a["attention_calls"]) == (2 * chips, 0, chips)
        # how a row was walked is the step's to say (``ssd_chunks`` in
        # its sums, held above); no reader wanted it on the span
        and "ssd_chunks" not in a and "moe_slots" not in a
        and "moe_compact_layers" not in a and "moe_layers" not in a
        for a in carrying)
    saves = [s["attrs"] for s in spans if s["name"] == "ckpt"
             and "bytes" in s.get("attrs", {})]
    held = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    # the parameters and two moments in float32, and the names
    assert saves and all(
        12 * held < a["bytes"] < 13 * held
        and min(a["fetch_s"], a["encode_s"], a["store_s"]) >= 0
        for a in saves)
    # one epoch, saved; resumed for the second: parameters, both moments
    # and the step go on bit for bit
    ckpt = str(tmp_path / "ckpt")
    main_apex([feed, *_ARGS, "--epochs", "1", "--ckpt-dir", ckpt])
    resumed = main_apex([feed, *_ARGS, "--epochs", "2", "--resume",
                         os.path.join(ckpt, "checkpoint.pth.tar"),
                         "--ckpt-dir", ckpt])
    assert [h["epoch"] for h in resumed["history"]] == [1]
    for ours, theirs in zip(
            jax.tree_util.tree_leaves(resumed["state"].params),
            jax.tree_util.tree_leaves(straight["state"].params)):
        np.testing.assert_array_equal(ours, theirs)
