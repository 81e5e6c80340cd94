"""The benchmark's seams in tier-1: ``benchmark/tests/test_seams.py``'s
cases (a configuration of another family lands as files: feed, family,
optimizer, each found by name) run here as they stand, one more holds
the configuration this repository added that way,
``lfm2-8b-a1b-ep4-bf16``, to its contracts and to the trainer's
arguments, and the cases of ``benchmark/tests/test_kept_residual_mb.py``
(the reader of the token model's kept residuals) run here too."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark.lib import cells, drive
from benchmark.tests.test_seams import *  # noqa: F401,F403 (the 28 cases)
from benchmark.tests.test_seams import seam_cell  # noqa: F401 (their fixture)
# the reader of the token model's kept residuals, on its fixtures (7 cases)
from benchmark.tests.test_kept_residual_mb import (  # noqa: F401
    test_a_parents_log_reads_nothing,
    test_a_window_whose_only_carriers_were_warm_up_reads_nothing,
    test_listed_for_the_token_cell_alone_and_as_its_data_file_has_it,
    test_reads_the_megabytes_off_the_timed_fetch_spans,
)

CELL = "lfm2moe-fit-8k-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_lfm2_cell_lands_as_files_and_keeps_every_contract():
    cell = cells.load_cell(CELL)
    config, traffic = cell.config, cell.traffic
    assert cell.feed.__name__ == "benchmark.feeds.tokens"
    assert cell.family.__name__ == "benchmark.reference.lfm2_moe"
    assert cell.optimizer.__name__ == "benchmark.reference.optimizers.adamw"
    for kind, module in (("feeds", cell.feed), ("reference", cell.family),
                         ("reference/optimizers", cell.optimizer)):
        assert all(hasattr(module, a) for a in cells.CONTRACTS[kind])
    for metric in ("expert_load_max_over_mean", "expert_local_slot_share",
                   "expert_dropped_tokens", "kept_residual_mb"):
        assert metric in {m["name"] for m in cell.per_layer}
        assert callable(cells.reader(metric).read)
    assert "collective_exposed_ms" not in {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {
        "train_img_s_chip", "step_ms_p95", "setup_s"}

    # the trainer's arguments: what the traffic file adds is what
    # create_kwargs mirrors, and the model both build is the `model` group
    from dptpu.config import parse_config
    from dptpu.models import create_model, model_task
    from dptpu.models.registry import token_model_kwargs

    argv = drive.fit_argv(cell, drive.dataset_images(traffic, 2**31 + 130))
    assert argv[0] == "tokens:8192@2"  # the seed's rows: a first row
    parsed = parse_config(argv, variant="apex")
    assert model_task(parsed.arch) == "tokens"
    assert token_model_kwargs(parsed, "tokens") == config["create_kwargs"]
    assert (parsed.optimizer, parsed.beta1, parsed.beta2, parsed.eps,
            parsed.weight_decay) == ("adamw", 0.9, 0.95, 1e-8, 0.1)
    held = create_model(config["arch"], **config["create_kwargs"]).config
    model = config["model"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_dense_layers", "num_attention_heads",
                "num_key_value_heads", "head_dim", "num_experts_per_tok",
                "conv_L_cache", "norm_eps", "rope_theta", "vocab_size",
                "sequence_length", "norm_topk_prob", "use_expert_bias"):
        assert getattr(held, key) == model[key], key
    assert list(held.layer_types) == model["layer_types"]
    assert held.num_experts == model["router_experts"] == 32
    assert held.experts_here == (model["experts_first"],
                                 model["experts_held"]) == (0, 8)

    # the file beside the catalog: every published number under its key,
    # but for the keys `reduced` names; no width among them
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types",
                       "num_dense_layers", "num_experts", "vocab_size"}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-8B-A1B")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in reduced:
                assert config["published"][key] == value
            else:
                assert config[key] == value, key
    assert config["num_experts"] == model["experts_held"]
    assert config["layer_types"] == model["layer_types"]
    assert len(config["assumed"]) >= 8 and "N = 4" in config["deployment"]

    # the family's shapes are the program's, leaf for leaf, and its count
    # of operations is the issue's arithmetic
    template = drive.program_template(config)
    spec = {name: tuple(shape)
            for name, shape, _, _ in cell.family.weight_spec(model)}
    from dptpu.models.pretrained import torch_key_map

    assert set(torch_key_map(config["arch"], template)) == set(spec)
    assert set(cell.family.trainable(model)) == set(spec)  # see its note
    params = sum(int(np.prod(s)) for n, s in spec.items()
                 if not n.endswith("expert_bias"))
    assert params == model["parameters"] == 507_820_160
    assert cell.family.forward_flops_per_token(model) == pytest.approx(
        432.5e6, rel=2e-3)
    assert cell.family.train_flops(model, 2) == pytest.approx(
        21.26e12, rel=2e-3)
    example = cell.family.example_input(model)
    assert example.shape == (1, 8192) and example.dtype == np.int32

    # two seeds: other rows, the same number of steps (one step program)
    assert traffic["dataset_images"] % cell.feed.SEED_ROWS == 0
    a, b = (cell.feed.epoch_order(drive.dataset_images(traffic, s), 0, 5)
            for s in (1, 130))
    assert len(a) == len(b) == traffic["dataset_images"]
    assert np.array_equal(b - a, np.full(len(a), 1))


def test_the_reference_adamw_is_optax_adamw():
    import jax.numpy as jnp
    import optax

    from benchmark.reference.optimizers import adamw

    hyper = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}
    params = {"w": jnp.asarray([[1.0, -2.0], [0.5, 3.0]]),
              "scale": jnp.asarray([1.0, 1.0])}
    tx = optax.adamw(0.01, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1,
                     mask={"w": True, "scale": False})
    opt_state, want, state = tx.init(params), params, adamw.init(params)
    got = params
    for k in range(3):
        grads = jax.tree_util.tree_map(lambda p: jnp.cos(p + k), want)
        updates, opt_state = tx.update(grads, opt_state, want)
        want = optax.apply_updates(want, updates)
        got, state = adamw.update(got, state, grads, 0.01, hyper)
        if k == 0:
            np.testing.assert_allclose(adamw.trace1(state)["w"],
                                       0.1 * grads["w"], rtol=1e-6)
    for name in params:
        np.testing.assert_allclose(got[name], want[name], rtol=1e-6)
    assert adamw.argv(hyper) == [
        "--optimizer", "adamw", "--beta1", "0.9", "--beta2", "0.95",
        "--eps", "1e-08", "--wd", "0.1"]
