"""The three seams of the harness (feed, family, optimizer: each a module
found by a name in a data file), held by a configuration of another
family that lands as files only: a token feed with three keys, a
tied-embedding language model with a per-token loss, an optimizer with
two moments. All of it sits under ``fixtures/seam/`` and is reached
through ``cells.load_cell``: no file of ``benchmark/`` outside this
directory knows of it."""

import copy
import json
import os
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.lib import cells, check, drive

SEAM_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "seam")
SEAM = cells.Home(SEAM_DIR, "benchmark.tests.fixtures.seam")


def seam_manifest() -> dict:
    """The repository's manifest with the fixture's entries added, as a
    later PR adds its own."""
    bench = copy.deepcopy(cells.manifest())
    bench["configs"].append({
        "name": "tied-lm-tiny", "source": "a fixture", "reduced": [],
        "file": os.path.relpath(os.path.join(
            SEAM_DIR, "configs", "tied-lm-tiny.json"), cells.ROOT),
        "why": "another family, as files"})
    bench["workloads"].append({
        "name": "tied-lm-fit", "config": "tied-lm-tiny",
        "traffic": "fit-tokens", "chips": 1, "why": "the seams"})
    return bench


@pytest.fixture
def seam_cell(monkeypatch):
    monkeypatch.setattr(cells, "HOME", SEAM)
    return cells.load_cell("tied-lm-fit", seam_manifest())


# ---------------------------------------------------------------- (a) ----


def test_a_new_family_resolves_through_load_cell(seam_cell):
    assert seam_cell.feed.KEYS == ("tokens", "labels", "mask")
    assert seam_cell.feed.__name__.endswith("seam.feeds.tokens")
    assert seam_cell.family.__name__.endswith("seam.reference.tied_lm")
    assert seam_cell.optimizer.__name__.endswith(
        "seam.reference.optimizers.adam")
    assert seam_cell.global_batch == 4
    for kind, module in (("feeds", seam_cell.feed),
                         ("reference", seam_cell.family),
                         ("reference/optimizers", seam_cell.optimizer)):
        assert all(hasattr(module, a) for a in cells.CONTRACTS[kind])
    # the family says what a row of input is, and how much a step costs
    model = seam_cell.config["model"]
    example = seam_cell.family.example_input(model)
    assert example.shape == (1, 8) and example.dtype == jnp.int32
    assert seam_cell.family.train_flops(model, 4) == 4 * 6.0 * 8 * 64 * 16


def test_the_real_cells_resolve_the_first_implementations():
    for name in ("rn50-fit-1chip", "vitb16-fit-1chip", "rn50-ddp-4chip"):
        cell = cells.load_cell(name)
        assert cell.feed.__name__ == "benchmark.feeds.synthetic"
        assert cell.feed.KEYS == ("images", "labels")
        assert cell.optimizer.__name__ == "benchmark.reference.optimizers.sgd"
        assert cell.config["create_kwargs"] == {"num_classes": 1000}
    assert cells.load_cell("vitb16-fit-1chip").family.__name__ == \
        "benchmark.reference.vit"


# ---------------------------------------------------------------- (b) ----


class FakeState(NamedTuple):
    params: dict
    opt_state: tuple


def test_the_tap_copies_the_feeds_keys_and_the_optimizers_moment(seam_cell):
    model = seam_cell.config["model"]
    regenerated = drive.regenerate_batches(seam_cell, seed=3)
    assert len(regenerated) == 3
    tokens, labels, mask = regenerated[0]
    assert tokens.shape == labels.shape == mask.shape == (4, 8)
    assert tokens.dtype == np.int32 and mask.dtype == np.bool_
    assert np.array_equal(tokens[:, 1:], labels[:, :-1])  # the next token

    params = {"embed": {"weight": jnp.ones((64, 16))}}
    tx = optax.adam(0.01)

    def train_step(state, batch):
        grads = jax.tree_util.tree_map(jnp.ones_like, state.params)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        return (FakeState(optax.apply_updates(state.params, updates),
                          opt_state), {"loss": jnp.float32(1.5)})

    tap = drive.StepTap(seam_cell)
    step = tap.wrap(train_step)
    state = FakeState(params, tx.init(params))
    order = seam_cell.feed.epoch_order(
        drive.dataset_images(seam_cell.traffic, 3), 0, 0)
    for k in range(5):
        arrays = seam_cell.feed.batch(order, k, 4, model)
        # the program's batch is a dict, in no promised order, with more in
        # it than the feed's keys
        batch = {"mask": jnp.asarray(arrays[2]), "row_ids": jnp.arange(4),
                 "labels": jnp.asarray(arrays[1]),
                 "tokens": jnp.asarray(arrays[0])}
        assert not tap.warm.is_set()  # not before the fifth call
        state, _ = step(state, batch)
    assert tap.warm.is_set() and tap.calls == 5
    assert len(tap.batches) == 3 and all(len(b) == 3 for b in tap.batches)
    assert check.feed_mismatch(tap.batches, regenerated) == 0
    assert tap.losses == [1.5, 1.5, 1.5]
    # the first moment after one step of all-ones gradients: (1 - b1)
    assert set(tap.trace1) == {"embed/weight"}
    np.testing.assert_allclose(tap.trace1["embed/weight"], 0.1, rtol=1e-6)
    assert tap.params_after["embed/weight"].shape == (64, 16)


def _three_steps():
    rng = np.random.RandomState(0)
    return [(rng.randint(0, 64, (4, 8)).astype(np.int32),
             rng.randint(0, 64, (4, 8)).astype(np.int32),
             rng.rand(4, 8) < 0.8) for _ in range(3)]


def _flip_token(steps):
    steps[1][0][2, 5] += 1


def _flip_mask_bit(steps):
    steps[2][2][0, 0] ^= True


def _drop_a_step(steps):
    del steps[2]


def _wrong_shape(steps):
    steps[0] = tuple(a[:, :7] for a in steps[0])


def _drop_a_key(steps):
    steps[0] = steps[0][:2]


@pytest.mark.parametrize("fault,count", [
    (None, 0), (_flip_token, 1), (_flip_mask_bit, 1), (_drop_a_step, 1),
    (_wrong_shape, 3 * 32), (_drop_a_key, 3 * 32)],
    ids=["sound", "token", "mask-bit", "missing-step", "shape", "key"])
def test_feed_mismatch_counts_over_tuples_of_any_length(fault, count):
    delivered, regenerated = _three_steps(), _three_steps()
    if fault:
        fault(delivered)
    assert check.feed_mismatch(delivered, regenerated) == count


def test_feed_mismatch_of_pairs_reads_as_it_did():
    """The image feed's pairs: the count the parent's comparison gave."""
    images = np.zeros((2, 4, 4, 3), np.uint8)
    labels = np.arange(2, dtype=np.int32)
    off = images.copy()
    off[1, 2, 3, 0] = 9
    assert check.feed_mismatch([(off, labels)], [(images, labels)]) == 1
    assert check.feed_mismatch([(images, labels + 1)],
                               [(images, labels)]) == 2
    assert check.feed_mismatch([(images[:1], labels)],
                               [(images, labels)]) == images.size + 2
    assert check.feed_mismatch([], [(images, labels)]) == 1


# ---------------------------------------------------------------- (c) ----


def test_the_reference_driver_is_optax_adam_on_the_fixture_family(seam_cell):
    cfg = seam_cell.config
    model, hyper, family = cfg["model"], cfg["optimizer"], seam_cell.family
    weights = drive.make_weights(seam_cell, 2**31 + 5)
    batches = drive.regenerate_batches(seam_cell, 2**31 + 5)
    got = drive.reference_run(seam_cell, weights, batches)  # blocks of 2 of 4

    tx = optax.adam(seam_cell.traffic["effective_lr"], b1=hyper["b1"],
                    b2=hyper["b2"], eps=hyper["eps"])
    names = family.trainable(model)
    assert "steps" not in names and "steps" in weights  # a buffer stays
    start = {k: jnp.asarray(weights[k]) for k in names}
    params, opt_state = start, tx.init(start)

    def block_loss(p, block):
        return family.loss(model, {**weights, **p}, block, "f32")

    losses, mu1 = [], None
    for arrays in batches:
        full = dict(zip(seam_cell.feed.KEYS, arrays))
        parts = [jax.value_and_grad(block_loss)(
            params, {k: jnp.asarray(v[rows]) for k, v in full.items()})
            for rows in (slice(0, 2), slice(2, 4))]
        losses.append(float(sum(v for v, _ in parts)) / 2)
        grads = jax.tree_util.tree_map(lambda a, b: (a + b) / 2,
                                       parts[0][1], parts[1][1])
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if mu1 is None:
            mu1 = opt_state[0].mu
    assert got["loss"] == pytest.approx(losses, abs=1e-6)
    assert set(got["trace1"]) == set(got["delta"]) == set(names)
    for k in names:
        np.testing.assert_allclose(got["trace1"][k], mu1[k], atol=1e-6)
        np.testing.assert_allclose(got["delta"][k], params[k] - start[k],
                                   atol=1e-6)
        assert np.abs(got["delta"][k]).max() > 1e-3  # and it moved


def test_the_reference_driver_refuses_a_batch_that_is_not_whole_blocks(
        seam_cell):
    weights = drive.make_weights(seam_cell, 1)
    batch = tuple(a[:3] for a in drive.regenerate_batches(seam_cell, 1)[0])
    with pytest.raises(ValueError, match="whole blocks"):
        drive.reference_run(seam_cell, weights, [batch])


# ---------------------------------------------------------------- (d) ----


def _optax_state(name):
    params = {"w": jnp.ones((3, 2)), "b": jnp.zeros((2,))}
    tx = {"sgd": optax.chain(optax.add_decayed_weights(1e-4),
                             optax.sgd(0.1, momentum=0.9)),
          "adam": optax.adam(0.1)}[name]
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    _, state = tx.update(grads, tx.init(params), params)
    return state


@pytest.mark.parametrize("reader,state,leaf_value", [
    ("sgd", "sgd", 1.0 + 1e-4), ("adam", "adam", 0.1)])
def test_the_program_side_reader_finds_its_first_moment(
        seam_cell, reader, state, leaf_value):
    found = _reader(seam_cell, reader).program_trace1(_optax_state(state))
    assert set(found) == {"w", "b"}
    np.testing.assert_allclose(found["w"], leaf_value, rtol=1e-6)


@pytest.mark.parametrize("reader,state,wants", [
    ("sgd", "adam", "TraceState"), ("adam", "sgd", "ScaleByAdamState")])
def test_a_reader_refuses_the_other_optimizers_state_by_name(
        seam_cell, reader, state, wants):
    with pytest.raises(RuntimeError, match=f"one optax {wants}.*found 0"):
        _reader(seam_cell, reader).program_trace1(_optax_state(state))


def _reader(seam_cell, name):
    """``sgd`` is the benchmark's own, ``adam`` the fixture's."""
    if name == "adam":
        return seam_cell.optimizer
    from benchmark.reference.optimizers import sgd
    return sgd


def test_sgd_is_the_update_the_driver_used_to_hold():
    """torch SGD, written out: what ``common.train_steps`` had inline."""
    from benchmark.reference.optimizers import sgd

    hyper = {"momentum": 0.9, "weight_decay": 1e-4}
    p = {"w": jnp.asarray([1.0, -2.0])}
    g = {"w": jnp.asarray([0.5, 0.25])}
    state = sgd.init(p)
    for _ in range(2):
        buf = 0.9 * state["w"] + (g["w"] + 1e-4 * p["w"])
        want = p["w"] - 0.01 * buf
        p, state = sgd.update(p, state, g, 0.01, hyper)
        np.testing.assert_array_equal(state["w"], buf)
        np.testing.assert_array_equal(p["w"], want)
    assert sgd.trace1(state) is state


# ---------------------------------------------------------------- (e) ----

_TAIL = ["--opt-level", "O2", "-b", "128"]
_SGD = ["--momentum", "0.9", "--wd", "0.0001", "--start-epoch", "5",
        "--pretrained"]


@pytest.mark.parametrize("name,seed,argv", [
    # the parent's lists (d7b0a1a), to the letter
    ("rn50-fit-1chip", 2290000101,
     ["synthetic:1281253", "-a", "resnet50", *_TAIL, "--lr", "0.02", *_SGD]),
    ("vitb16-fit-1chip", 7,
     ["synthetic:1281159", "-a", "vit_b_16", *_TAIL, "--lr", "0.02", *_SGD]),
    ("rn50-ddp-4chip", 2**31 + 130,
     ["synthetic:1281154", "-a", "resnet50", *_TAIL, "--lr", "0.005",
      *_SGD])])
def test_fit_argv_of_the_real_cells_is_the_parents(name, seed, argv):
    cell = cells.load_cell(name)
    assert drive.fit_argv(cell, drive.dataset_images(cell.traffic, seed)) \
        == argv


def test_fit_argv_of_the_fixture_holds_its_feeds_and_its_optimizers(
        seam_cell):
    argv = drive.fit_argv(seam_cell,
                          drive.dataset_images(seam_cell.traffic, 130))
    assert argv == ["tokens:402", "-a", "tied_lm", "--opt-level", "O2",
                    "-b", "4", "--lr", "0.64", "--optimizer", "adam",
                    "--beta1", "0.9", "--beta2", "0.999",
                    "--start-epoch", "0", "--pretrained",
                    "--print-freq", "10"]


# ---------------------------------------------------------------- (f) ----


@pytest.mark.parametrize("where,key,exist", [
    ("traffic", "data", ["synthetic"]),
    ("config", "reference", ["resnet", "vit"]),
    ("config", "optimizer", ["sgd"])])
def test_an_unknown_name_stops_with_the_names_that_exist(where, key, exist):
    cell = cells.load_cell("rn50-fit-1chip")
    config, traffic = dict(cell.config), dict(cell.traffic)
    if key == "optimizer":
        config["optimizer"] = dict(config["optimizer"], name="adafactor9")
    else:
        {"traffic": traffic, "config": config}[where][key] = "no_such"
    lookup = {"data": lambda: cells.feed(traffic),
              "reference": lambda: cells.reference(config),
              "optimizer": lambda: cells.optimizer(config)}[key]
    with pytest.raises(SystemExit) as stopped:
        lookup()
    message = str(stopped.value)
    assert "there are:" in message
    for name in exist:
        assert name in message


def test_load_cell_stops_on_an_unknown_name_before_any_set_up(monkeypatch,
                                                              tmp_path):
    monkeypatch.setattr(cells, "HOME", SEAM)
    bench = seam_manifest()
    broken = dict(cells._load(os.path.join(cells.ROOT,
                                           bench["configs"][-1]["file"])))
    broken["optimizer"] = dict(broken["optimizer"], name="lamb")
    path = tmp_path / "broken.json"
    path.write_text(json.dumps(broken))
    bench["configs"][-1]["file"] = str(path)
    with pytest.raises(SystemExit, match="there are: adam"):
        cells.load_cell("tied-lm-fit", bench)


def test_a_module_that_lacks_its_contract_is_refused():
    # common.py shares helpers between the families and is not one
    with pytest.raises(SystemExit, match="lacks weight_spec"):
        cells.reference({"reference": "common"})


def test_no_harness_code_names_a_batch_key_or_an_optimizer():
    """What is image- or SGD-specific lives behind the seams."""
    banned = re.compile(
        r"\"images\"|\"labels\"|'images'|'labels'|image_size|num_classes"
        r"|--momentum|--wd|TraceState|ScaleBy")
    files = [os.path.join(cells.BENCH_DIR, "lib", f)
             for f in ("drive.py", "check.py", "cells.py")]
    readers = os.path.join(cells.BENCH_DIR, "readers")
    files += [os.path.join(readers, f) for f in sorted(os.listdir(readers))
              if f.endswith(".py")]
    for path in files:
        with open(path) as f:
            hits = [line for line in f if banned.search(line)]
        assert not hits, (path, hits)


def test_make_weights_of_the_fixture_is_seeded(seam_cell):
    one = drive.make_weights(seam_cell, 9)
    two = drive.make_weights(seam_cell, 9)
    assert set(one) == {"embed.weight", "norm.weight", "steps"}
    assert np.array_equal(one["embed.weight"], two["embed.weight"])
