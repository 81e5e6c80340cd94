"""Each plain reference against ``dptpu/models`` at a tiny size on the CPU,
through the program's public import path for torchvision-layout weights;
and the control: the reference in fp8, put in the program's place, has to
come out as not correct under the configuration's own limits."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.feeds import synthetic
from benchmark.lib import cells, check, drive
from benchmark.reference import common

CONFIGS = os.path.join(cells.BENCH_DIR, "configs")
SIZE = 64   # pixels: a tiny image, the published widths
ROWS = 8
SIZES = {"image_size": SIZE, "num_classes": 1000}


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        config = json.load(f)
    config["model"] = dict(config["model"], image_size=SIZE)
    return config


def _rows(n=ROWS, seed=0):
    order = synthetic.epoch_order(64, seed, 0)
    return synthetic.batch(order, 0, n, SIZES)


@pytest.mark.parametrize("name,by_value", [
    # ResNet-50 at 64 px normalizes over 32 values a channel in its last
    # stage: permuting the rows alone moves single gradient values by 30%
    # in float32 (PERF.md, PR 24), so there the norms are compared
    ("resnet50-224-bf16", False), ("vit-b16-224-bf16", True)])
def test_reference_agrees_with_the_program_in_float32(name, by_value):
    from dptpu.models import create_model
    from dptpu.models.pretrained import convert_state_dict

    config = _config(name)
    ref = cells.reference(config)
    model_cfg = config["model"]
    weights = {k: np.asarray(v) for k, v in common.make_weights(
        ref.weight_spec(model_cfg), 7).items()}
    images, labels = _rows()
    x = common.normalize(jnp.asarray(images))

    template = drive.program_template(config)
    variables = convert_state_dict(config["arch"], weights, template)
    model = create_model(config["arch"], num_classes=1000)

    def program_loss(params):
        out, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"])
        return common.cross_entropy(out, jnp.asarray(labels)), out

    def reference_loss(params):
        out = ref.forward(model_cfg, {**weights, **params}, x, "f32")
        return common.cross_entropy(out, jnp.asarray(labels)), out

    with jax.default_matmul_precision("highest"):
        (lp, out_p), gp = jax.value_and_grad(program_loss, has_aux=True)(
            variables["params"])
    trainable = {k: jnp.asarray(weights[k]) for k in ref.trainable(model_cfg)}
    (lr, out_r), gr = jax.value_and_grad(reference_loss, has_aux=True)(
        trainable)
    # float32 both: what is left is the order of summation (flax takes the
    # variance as E[x^2] - E[x]^2) through fifty layers; a wrong layer,
    # layout or stride reads O(1)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=3e-3, atol=3e-3)
    assert float(lp) == pytest.approx(float(lr), abs=1e-3)
    got = drive._flat(gp)
    want = drive.to_program_layout(
        config, template, {k: np.asarray(v) for k, v in gr.items()}, weights)
    summary = check.summarize(got, want)
    assert summary["kernels"] < 5e-3 and summary["worst"] < 5e-2, summary
    floor = float(np.median([np.abs(v).max() for v in want.values()]))
    for leaf in want if by_value else ():
        scale = max(float(np.abs(want[leaf]).max()), floor)
        assert float(np.abs(got[leaf] - want[leaf]).max()) / scale < 3e-2, leaf


@pytest.mark.parametrize("name", ["resnet50-224-bf16", "vit-b16-224-bf16"])
def test_the_fp8_control_is_not_correct(name):
    config = _config(name)
    ref = cells.reference(config)
    model_cfg = config["model"]
    weights = common.make_weights(ref.weight_spec(model_cfg), 11)
    batches = [dict(zip(synthetic.KEYS, _rows(16, seed)))
               for seed in (1, 2, 3)]
    run = functools.partial(
        common.train_steps, functools.partial(ref.loss, model_cfg),
        cells.optimizer(config), config["optimizer"],
        ref.trainable(model_cfg), weights, batches, lr=0.01, block_rows=16)
    sound = run(mode="f32")
    limits = config["limits"]

    def verdict(got):
        program = dict(got, feed_mismatch=0, nonfinite=0)
        return check.compare(program, sound, limits)

    assert verdict(sound)["correct"]
    control = verdict(run(mode="fp8"))
    assert not control["correct"], control["numbers"]


def test_make_weights_is_seeded_and_takes_large_seeds():
    spec = [("a.weight", (4, 3), "normal", 1.0), ("a.bias", (4,), "const", 0.5)]
    one = common.make_weights(spec, 2**31 + 12345)
    two = common.make_weights(spec, 2**31 + 12345)
    other = common.make_weights(spec, 2**31 + 12346)
    assert np.array_equal(one["a.weight"], two["a.weight"])
    assert not np.array_equal(one["a.weight"], other["a.weight"])
    assert np.all(np.asarray(one["a.bias"]) == 0.5)


def test_synthetic_copy_matches_the_program_feed():
    from dptpu.data import DataLoader, ShardedSampler, SyntheticDataset

    ds = SyntheticDataset(200, SIZE, 1000)
    loader = DataLoader(ds, 8, sampler=ShardedSampler(len(ds), seed=0),
                        num_workers=2, drop_last=True, pad_final=False,
                        seed=0)
    try:
        batches = iter(loader.epoch(5))
        first, second = next(batches), next(batches)
    finally:
        loader.close()
    order = synthetic.epoch_order(200, 0, 5)
    for step, got in enumerate((first, second)):
        images, labels = synthetic.batch(order, step, 8, SIZES)
        assert np.array_equal(got["images"], images)
        assert np.array_equal(got["labels"], labels)
