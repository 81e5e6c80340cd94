"""The benchmark's seams in tier-1: ``benchmark/tests/test_seams.py``'s
cases (a configuration of another family lands as files: feed, family,
optimizer, each found by name) run here as they stand, one more each
holds a configuration this repository added that way
(``lfm2-8b-a1b-ep4-bf16``, ``joyai-llm-flash-ep32-bf16``) to its
contracts and to the trainer's arguments, and the cases of
``benchmark/tests/test_kept_residual_mb.py`` (the reader of the token
model's kept residuals) and of ``test_mtp_loss_share.py`` run here too.
PR 36 appended its cell to ``kept_residual_mb``'s list in
``BENCHMARK.json`` and may not edit ``layer_metrics/kept_residual_mb
.json``: that file's listing case (one cell, equal to the data file) is
red outside tier-1 until a ``benchmark`` PR repairs the data file, and
the case below that takes its place here holds what still has to hold.
PR 37 appended ``attention_kernel_share`` behind ``mtp_loss_share``,
whose own listing case asks to be the LAST entry: the case below that
takes its place holds the rest of it, and the new reader's cases
(``test_attention_kernel_share.py``) run here too. PR 39 appended
``granite-ssm-fit-1chip`` (``granite-4.0-h-micro-vp8-bf16``) behind
JoyAI's cell: the pins that asked JoyAI's to be the last say what can
stay true (the accepted cells come first and in their order, JoyAI's own
metric lists only JoyAI, four-chip cells stay within a quarter), the new
cell has its own case, and its reader's (``test_ssd_kernel_share.py``)
and its limits' (``test_granite_limits.py``) run here too. PR 41 appended
six metrics that read the run's set-up account off the ``fetch`` spans:
their readers' cases (``test_setup_account.py``, every name with ``setup``
in it) run here too, all but the one that makes the CPU rehearsal's run.
PR 42 appended ``expert_compact_share`` (the expert layers' runs that
fitted the compact buffer): its reader's cases
(``test_expert_compact_share.py``) run here too. PR 43 appended
``trinity-mini-fit-8k-1chip`` (``trinity-mini-ep8-bf16``) behind granite's
cell with two metrics of the attention's window: the cell has its own
case below, and its readers' (``test_attention_tile_share.py``,
``test_attention_kernel_roofline_share.py``) and its limits'
(``test_trinity_limits.py``) run here too."""

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
    test_reads_the_megabytes_off_the_timed_fetch_spans,
)
# the reader of the second loss's share, on the same fixture (5 cases;
# the sixth, its listing, is below)
from benchmark.tests.test_mtp_loss_share import (  # noqa: F401,E402
    test_a_parents_log_reads_no_second_loss,
    test_reads_the_second_losss_share_off_the_timed_fetch_spans,
)
from benchmark.tests import test_mtp_loss_share as _mtp  # noqa: E402
# the reader of the attention's share on the kernel (9 cases)
from benchmark.tests.test_attention_kernel_share import *  # noqa: F401,F403,E402

# the reader of the state-space scan's share on a kernel (9 cases)
from benchmark.tests.test_ssd_kernel_share import *  # noqa: F401,F403,E402
# the granite configuration's limits on faulty programs (4 cases)
from benchmark.tests.test_granite_limits import *  # noqa: F401,F403,E402
from benchmark.tests.test_granite_limits import sound  # noqa: F401,E402
# the six readers of the run's set-up account (33 cases)
from benchmark.tests.test_setup_account import (  # noqa: F401,E402
    test_a_parents_log_reads_no_setup_metric,
    test_a_setup_account_that_holds_no_number_reads_nothing,
    test_a_setup_account_without_before_fit_reads_the_rest,
    test_a_setup_account_without_its_first_batch_reads_the_iterations_alone,
    test_a_setup_reader_reads_the_recorded_account,
    test_a_window_whose_spans_carry_no_setup_reads_nothing,
    test_the_five_setup_parts_add_up_to_the_recorded_setup_s,
    test_the_last_carried_setup_account_is_the_one_read,
    test_the_setup_cache_share_is_hits_over_answers,
    test_the_six_setup_metrics_are_listed_together_for_every_cell,
)
# the reader of the expert layers' share in the compact buffer (11 cases)
from benchmark.tests.test_expert_compact_share import *  # noqa: F401,F403,E402

# the readers of the attention's window: the tiles walked of the causal
# triangle's (10 cases) and the kernels' share of their roofline (18)
from benchmark.tests.test_attention_tile_share import *  # noqa: F401,F403,E402
from benchmark.tests.test_attention_kernel_roofline_share import *  # noqa: F401,F403,E402
# the Trinity configuration's limits on faulty programs (5 cases)
from benchmark.tests.test_trinity_limits import *  # noqa: F401,F403,E402
from benchmark.tests.test_trinity_limits import sound_trinity  # noqa: F401,E402

CELL = "lfm2moe-fit-8k-1chip"
JOYAI_CELL = "joyai-fit-8k-1chip"
GRANITE_CELL = "granite-ssm-fit-1chip"
TRINITY_CELL = "trinity-mini-fit-8k-1chip"
# the cells the benchmark accepted, in its order: a later one is appended
ACCEPTED = ["rn50-fit-1chip", "vitb16-fit-1chip", "rn50-ddp-4chip", CELL,
            JOYAI_CELL]
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


def test_kept_residual_mb_is_listed_for_the_token_cells_as_its_file_has_it():
    bench = cells.manifest()
    spec = cells.layer_metric("kept_residual_mb")
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "kept_residual_mb"]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # everything but the list is the data file's; the list starts with it
    # (an appended cell, never an edit)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        k: spec[k] for k in entry if k != "workloads"}
    # ... then JoyAI's, then whatever came later
    listed = spec["workloads"] + [JOYAI_CELL]
    assert entry["workloads"][:len(listed)] == listed
    assert entry["layer"] == cells.layer_metric(
        "expert_dropped_tokens")["layer"]


def test_mtp_loss_share_is_listed_for_its_cell_as_its_file_has_it(tmp_path):
    bench = cells.manifest()
    spec = cells.layer_metric("mtp_loss_share")
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "mtp_loss_share"]
    assert entry == {k: spec[k] for k in entry}
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["workloads"] == [JOYAI_CELL] and entry["unit"] == "%"
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "train_img_s_chip"
    assert entry["layer"] == cells.layer_metric(
        "expert_dropped_tokens")["layer"]
    # a configuration without the weight, or a loss of zero: nothing read
    carrying = _mtp._log_with(tmp_path, {5: {"loss": 11.0, "mtp_loss": 10.0}})
    assert cells.reader("mtp_loss_share").read(
        _mtp._context(carrying, 2, None)) is None
    zero = _mtp._log_with(tmp_path, {5: {"loss": 0.0, "mtp_loss": 0.0}})
    assert cells.reader("mtp_loss_share").read(
        _mtp._context(zero, 2)) is None


def test_the_joyai_cell_lands_as_files_and_keeps_every_contract():
    cell = cells.load_cell(JOYAI_CELL)
    config, traffic = cell.config, cell.traffic
    assert cell.chips == 1 and cell.global_batch == 1
    assert cell.feed.__name__ == "benchmark.feeds.tokens"
    assert cell.family.__name__ == "benchmark.reference.joyai_llm_flash"
    assert cell.optimizer.__name__ \
        == "benchmark.reference.optimizers.adamw_committed"
    for kind, module in (("feeds", cell.feed), ("reference", cell.family),
                         ("reference/optimizers", cell.optimizer)):
        assert all(hasattr(module, a) for a in cells.CONTRACTS[kind])
    # every per-layer metric the other token cell reports, and its own
    other = {m["name"] for m in cells.load_cell(CELL).per_layer}
    mine = {m["name"] for m in cell.per_layer}
    assert mine == other | {"mtp_loss_share"}
    for metric in mine:
        assert callable(cells.reader(metric).read)
    assert {m["name"] for m in cell.end_to_end} == {
        "train_img_s_chip", "step_ms_p95", "setup_s"}
    # appended: the accepted cells come first and in their order, in the
    # list of cells and in every metric's; JoyAI's own metric lists JoyAI
    # alone; four-chip cells stay within a quarter, rounded down (one
    # always may)
    bench = cells.manifest()
    names = [w["name"] for w in bench["workloads"]]
    assert names[:len(ACCEPTED)] == ACCEPTED
    (entry,) = [c for c in bench["configs"] if c["name"] == config["name"]]
    assert bench["configs"].index(entry) == 3
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "mtp_loss_share":
            assert m["workloads"] == [JOYAI_CELL]
        elif JOYAI_CELL in m.get("workloads", ()):
            at = m["workloads"].index(JOYAI_CELL)
            assert m["workloads"][at - 1] == CELL
            assert m["workloads"][:at + 1] == [
                c for c in ACCEPTED if c in m["workloads"]]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(1, len(names) // 4)
    assert len(names) >= 5 and len(set(names)) == len(names)

    # the trainer's arguments: what the traffic file adds is what
    # create_kwargs mirrors, and the model both build is the `model` group
    from dptpu.config import parse_config
    from dptpu.models import create_model, model_task
    from dptpu.models.registry import token_model_kwargs

    argv = drive.fit_argv(cell, drive.dataset_images(traffic, 2**31 + 130))
    assert argv[0] == "tokens:8192@2" and argv[argv.index("-b") + 1] == "1"
    parsed = parse_config(argv, variant="apex")
    assert model_task(parsed.arch) == "tokens"
    assert token_model_kwargs(parsed, "tokens") == config["create_kwargs"]
    assert (parsed.optimizer, parsed.beta1, parsed.beta2, parsed.eps,
            parsed.weight_decay) == ("adamw", 0.9, 0.95, 1e-8, 0.1)
    held = create_model(config["arch"], **config["create_kwargs"]).config
    model = config["model"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "first_k_dense_replace", "num_nextn_predict_layers",
                "num_attention_heads", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "num_experts_per_tok", "n_shared_experts", "norm_topk_prob",
                "routed_scaling_factor", "rms_norm_eps", "rope_theta",
                "rope_interleave", "vocab_size", "sequence_length",
                "mtp_loss_weight"):
        assert getattr(held, key) == model[key], key
    assert held.layers_here == (model["layers_first"],
                                model["layers_held"]) == (0, 5)
    assert held.n_routed_experts == model["router_experts"] == 256
    assert held.experts_here == (model["experts_first"],
                                 model["experts_held"]) == (0, 8)
    assert held.num_hidden_layers == model["mtp_layer"] == 40
    assert config["mtp_loss_weight"] == model["mtp_loss_weight"] == 0.1
    assert traffic["check_steps"] == 2 <= traffic["warmup_iters"]

    # the file beside the catalog: every published number under its key,
    # but for the keys `reduced` names; no width among them
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert entry["reduced"] == config["reduced"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "JoyAI-LLM-Flash")
        assert config["source"] == row["source_url"] == entry["source"]
        for key, value in row["config"].items():
            if key in reduced:
                assert config["published"][key] == value
            else:
                assert config[key] == value, key
    assert config["n_routed_experts"] == model["experts_held"]
    assert config["num_hidden_layers"] == model["layers_held"]
    assert config["vocab_size"] == model["vocab_size"] == 16160
    assert len(config["assumed"]) >= 8 and "N = 32" in config["deployment"]

    # the family's shapes are the program's, leaf for leaf, and its count
    # of operations is the issue's arithmetic
    template = drive.program_template(config)
    spec = {name: tuple(shape)
            for name, shape, _, _ in cell.family.weight_spec(model)}
    from dptpu.models.pretrained import torch_key_map

    assert set(torch_key_map(config["arch"], template)) == set(spec)
    assert set(cell.family.trainable(model)) == set(spec)  # see its note
    params = sum(int(np.prod(s)) for n, s in spec.items()
                 if not n.endswith("e_score_correction_bias"))
    assert params == model["parameters"] == 491_696_128
    assert cell.family.train_flops(model, 1) == pytest.approx(
        27.55e12, rel=2e-3)
    example = cell.family.example_input(model)
    assert example.shape == (1, 8192) and example.dtype == np.int32

    # two seeds: other rows, the same number of steps (one step program)
    assert traffic["dataset_images"] % cell.feed.SEED_ROWS == 0
    a, b = (cell.feed.epoch_order(drive.dataset_images(traffic, s), 0, 5)
            for s in (1, 130))
    assert len(a) == len(b) == traffic["dataset_images"]
    assert np.array_equal(b - a, np.full(len(a), 1))


def test_the_granite_cell_lands_as_files_and_keeps_every_contract():
    cell = cells.load_cell(GRANITE_CELL)
    config, traffic = cell.config, cell.traffic
    assert cell.chips == 1 and cell.global_batch == 1
    assert cell.feed.__name__ == "benchmark.feeds.tokens"
    assert cell.family.__name__ == "benchmark.reference.granitemoehybrid"
    assert cell.optimizer.__name__ == "benchmark.reference.optimizers.adamw"
    for kind, module in (("feeds", cell.feed), ("reference", cell.family),
                         ("reference/optimizers", cell.optimizer)):
        assert all(hasattr(module, a) for a in cells.CONTRACTS[kind])
    # every per-layer metric the LFM2 cell reports but the experts' three
    # (it has none), and its own
    other = {m["name"] for m in cells.load_cell(CELL).per_layer}
    mine = {m["name"] for m in cell.per_layer}
    assert mine == {m for m in other if not m.startswith("expert_")} \
        | {"ssd_kernel_share"}
    assert {"kept_residual_mb", "attention_kernel_share", "device_mfu"} <= mine
    for metric in mine:
        assert callable(cells.reader(metric).read)
    assert {m["name"] for m in cell.end_to_end} == {
        "train_img_s_chip", "step_ms_p95", "setup_s"}
    # appended: behind the accepted cells, in every list it is on
    bench = cells.manifest()
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(GRANITE_CELL) == len(ACCEPTED)
    (entry,) = [c for c in bench["configs"] if c["name"] == config["name"]]
    assert bench["configs"].index(entry) == 4
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "ssd_kernel_share":
            assert m["workloads"][0] == GRANITE_CELL
        elif GRANITE_CELL in m.get("workloads", ()):
            at = m["workloads"].index(GRANITE_CELL)
            assert m["workloads"][at - 1] == JOYAI_CELL
    (cell_entry,) = [w for w in bench["workloads"]
                     if w["name"] == GRANITE_CELL]
    assert (cell_entry["config"], cell_entry["traffic"], cell_entry["chips"]
            ) == (config["name"], traffic["name"], 1)

    # the trainer's arguments: what the traffic file adds is what
    # create_kwargs mirrors, and the model both build is the `model` group
    from dptpu.config import parse_config
    from dptpu.models import create_model, model_task
    from dptpu.models.registry import token_model_kwargs

    argv = drive.fit_argv(cell, drive.dataset_images(traffic, 2**31 + 130))
    assert argv[0] == "tokens:8192@2" and argv[argv.index("-b") + 1] == "1"
    assert "--experts" not in argv
    parsed = parse_config(argv, variant="apex")
    assert model_task(parsed.arch) == "tokens"
    assert token_model_kwargs(parsed, "tokens") == config["create_kwargs"]
    assert (parsed.optimizer, parsed.beta1, parsed.beta2, parsed.eps,
            parsed.weight_decay) == ("adamw", 0.9, 0.95, 1e-8, 0.1)
    held = create_model(config["arch"], **config["create_kwargs"]).config
    model = config["model"]
    for key in ("hidden_size", "shared_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "attention_multiplier", "embedding_multiplier",
                "residual_multiplier", "logits_scaling", "mamba_n_heads",
                "mamba_d_head", "mamba_d_state", "mamba_d_conv",
                "mamba_expand", "mamba_n_groups", "mamba_chunk_size",
                "rms_norm_eps", "vocab_size", "sequence_length"):
        assert getattr(held, key) == model[key], key
    assert held.layers_here == (model["layers_first"],
                                model["layers_held"]) == (0, 10)
    assert [kind for _, kind in held.types_here] == model["layer_types"] \
        == config["layer_types"]
    assert model["layer_types"].count("mamba") == 9 \
        and model["layer_types"][5] == "attention"
    assert traffic["check_steps"] == 2 <= traffic["warmup_iters"]
    assert set(config["precision"]) >= {"ssd_decay", "ssd_state"}
    assert config["precision"]["ssd_decay"] \
        == config["precision"]["ssd_state"] == "float32"

    # the file beside the catalog: every published number under its key,
    # but for the keys `reduced` names; no width among them
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types", "vocab_size"}
    assert entry["reduced"] == config["reduced"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "granite-4.0-h-micro")
        assert config["source"] == row["source_url"] == entry["source"]
        for key, value in row["config"].items():
            if key in reduced:
                assert config["published"][key] == value
            else:
                assert config[key] == value, key
        assert config["layer_types"] == row["config"]["layer_types"][:10]
    assert config["num_hidden_layers"] == model["layers_held"]
    assert config["vocab_size"] == model["vocab_size"] == 12544 == 100352 // 8
    assert len(config["assumed"]) >= 8 and "N = 8" in config["deployment"]

    # the family's shapes are the program's, leaf for leaf, and its count
    # of operations is the issue's arithmetic
    template = drive.program_template(config)
    spec = {name: tuple(shape)
            for name, shape, _, _ in cell.family.weight_spec(model)}
    from dptpu.models.pretrained import torch_key_map

    assert set(torch_key_map(config["arch"], template)) == set(spec)
    assert set(cell.family.trainable(model)) == set(spec)
    params = sum(int(np.prod(s)) for s in spec.values())
    assert params == model["parameters"] == 772_160_448
    assert params == sum(int(np.prod(leaf.shape)) for leaf in
                         jax.tree_util.tree_leaves(template["params"]))
    # 1,606 MFLOP a token forward, 39.5 TFLOP a step: the scan's products
    # are counted over the causal half of a chunk, as the attention's are
    # over half the row (3.18 MFLOP a token and Mamba-2 layer; ISSUE 39's
    # 4.3 and 1,617 take the whole chunk, which the program computes and
    # half of which the mask throws away)
    assert cell.family.scan_flops_per_token(model) == 3_178_496
    assert cell.family.forward_flops_per_token(model) == pytest.approx(
        1606e6, rel=1e-3)
    assert cell.family.train_flops(model, 1) == pytest.approx(
        39.47e12, rel=1e-3)
    example = cell.family.example_input(model)
    assert example.shape == (1, 8192) and example.dtype == np.int32

    # two seeds: other rows, the same number of steps (one step program)
    assert traffic["dataset_images"] % cell.feed.SEED_ROWS == 0
    a, b = (cell.feed.epoch_order(drive.dataset_images(traffic, s), 0, 5)
            for s in (1, 130))
    assert len(a) == len(b) == traffic["dataset_images"]
    assert np.array_equal(b - a, np.full(len(a), 1))


def test_the_trinity_cell_lands_as_files_and_keeps_every_contract():
    cell = cells.load_cell(TRINITY_CELL)
    config, traffic = cell.config, cell.traffic
    assert cell.chips == 1 and cell.global_batch == 1
    assert cell.feed.__name__ == "benchmark.feeds.tokens"
    assert cell.family.__name__ == "benchmark.reference.afmoe"
    assert cell.optimizer.__name__ \
        == "benchmark.reference.optimizers.adamw_committed"
    for kind, module in (("feeds", cell.feed), ("reference", cell.family),
                         ("reference/optimizers", cell.optimizer)):
        assert all(hasattr(module, a) for a in cells.CONTRACTS[kind])
    # every per-layer metric JoyAI's cell reports but the second loss's
    # share, and the two of the window
    other = {m["name"] for m in cells.load_cell(JOYAI_CELL).per_layer}
    mine = {m["name"] for m in cell.per_layer}
    assert mine == other - {"mtp_loss_share"} | {
        "attention_tile_share", "attention_kernel_roofline_share"}
    assert {"kept_residual_mb", "attention_kernel_share", "device_mfu",
            "expert_compact_share", "expert_dropped_tokens"} <= mine
    for metric in mine:
        assert callable(cells.reader(metric).read)
    assert {m["name"] for m in cell.end_to_end} == {
        "train_img_s_chip", "step_ms_p95", "setup_s"}
    # appended: behind the accepted cells and granite's, in every list it
    # is on, and the two new metrics behind every accepted one
    bench = cells.manifest()
    names = [w["name"] for w in bench["workloads"]]
    assert names == ACCEPTED + [GRANITE_CELL, TRINITY_CELL]
    assert [c["name"] for c in bench["configs"]][-1] == config["name"]
    (entry,) = [c for c in bench["configs"] if c["name"] == config["name"]]
    assert [m["name"] for m in bench["per_layer"]][-2:] == [
        "attention_tile_share", "attention_kernel_roofline_share"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if TRINITY_CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == TRINITY_CELL
            assert m["workloads"].count(TRINITY_CELL) == 1
    (cell_entry,) = [w for w in bench["workloads"]
                     if w["name"] == TRINITY_CELL]
    assert (cell_entry["config"], cell_entry["traffic"], cell_entry["chips"]
            ) == (config["name"], traffic["name"], 1)
    assert all(len(e["why"]) <= 200 for e in (entry, cell_entry))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1

    # the trainer's arguments: what the traffic file adds is what
    # create_kwargs mirrors, and the model both build is the `model` group
    from dptpu.config import parse_config
    from dptpu.models import create_model, model_task
    from dptpu.models.registry import token_model_kwargs

    argv = drive.fit_argv(cell, drive.dataset_images(traffic, 2**31 + 130))
    assert argv[0] == "tokens:8192@2" and argv[argv.index("-b") + 1] == "1"
    parsed = parse_config(argv, variant="apex")
    assert model_task(parsed.arch) == "tokens"
    assert token_model_kwargs(parsed, "tokens") == config["create_kwargs"]
    assert (parsed.optimizer, parsed.beta1, parsed.beta2, parsed.eps,
            parsed.weight_decay) == ("adamw", 0.9, 0.95, 1e-8, 0.1)
    held = create_model(config["arch"], **config["create_kwargs"]).config
    model = config["model"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "sliding_window", "num_experts_per_tok",
                "num_shared_experts", "route_norm", "route_scale",
                "mup_enabled", "rms_norm_eps", "rope_theta", "vocab_size",
                "sequence_length"):
        assert getattr(held, key) == model[key], key
    assert held.layers_here == (model["layers_first"],
                                model["layers_held"]) == (1, 5)
    assert [held.layer_types[i] for i in held.numbers_here] \
        == model["layer_types"] == config["layer_types"]
    # three window layers to one full one behind the dense layer, as
    # published, and the dense layer a window one
    assert model["layer_types"] == ["sliding_attention"] * 2 + [
        "full_attention"] + ["sliding_attention"] * 2
    assert [held.is_dense(i) for i in held.numbers_here] == [
        True, False, False, False, False]
    assert held.num_dense_layers == model["first_expert_layer"] == 2
    assert held.num_experts == model["router_experts"] == 128
    assert held.experts_here == (model["experts_first"],
                                 model["experts_held"]) == (0, 16)
    assert traffic["check_steps"] == 2 <= traffic["warmup_iters"]

    # the file beside the catalog: every published number under its key,
    # but for the keys `reduced` names; no width, head count or window
    # among them
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types",
                       "num_dense_layers", "num_experts", "vocab_size"}
    assert entry["reduced"] == config["reduced"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Trinity-Mini")
        assert config["source"] == row["source_url"] == entry["source"]
        for key, value in row["config"].items():
            if key in reduced:
                assert config["published"][key] == value
            else:
                assert config[key] == value, key
        assert config["layer_types"] == row["config"]["layer_types"][1:6]
    assert (config["sliding_window"], config["num_experts_per_tok"],
            config["route_scale"], config["head_dim"]) == (2048, 8, 2.826, 128)
    assert config["num_hidden_layers"] == model["layers_held"]
    assert config["num_dense_layers"] == 1  # held: layer 1
    assert config["num_experts"] == model["experts_held"] == 128 // 8
    assert config["vocab_size"] == model["vocab_size"] == 25024 == 200192 // 8
    assert len(config["assumed"]) >= 8 and "N = 8" in config["deployment"]
    assert config["optimizer"]["name"] == "adamw_committed"

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
    assert params == model["parameters"] == 705_473_792
    assert params == sum(int(np.prod(leaf.shape)) for leaf in
                         jax.tree_util.tree_leaves(template["params"]))
    # 738 MFLOP a token forward, 18.1 TFLOP a step, the scores counted
    # over the pairs the mask keeps
    assert cell.family.train_flops(model, 1) == pytest.approx(
        18.1e12, rel=5e-3)
    example = cell.family.example_input(model)
    assert example.shape == (1, 8192) and example.dtype == np.int32

    # two seeds: other rows, the same number of steps (one step program)
    assert traffic["dataset_images"] % cell.feed.SEED_ROWS == 0
    a, b = (cell.feed.epoch_order(drive.dataset_images(traffic, s), 0, 5)
            for s in (1, 130))
    assert len(a) == len(b) == traffic["dataset_images"]
    assert np.array_equal(b - a, np.full(len(a), 1))


def test_adamw_committed_is_adamw_with_one_compile_of_the_loss():
    import jax.monitoring
    import jax.numpy as jnp

    from benchmark.reference import common
    from benchmark.reference.optimizers import adamw, adamw_committed

    hyper = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}
    assert adamw_committed.argv(hyper) == adamw.argv(hyper)
    assert adamw_committed.program_trace1 is adamw.program_trace1

    def loss(w, block, mode):
        return jnp.mean((block["x"] @ w["w"]) ** 2) + jnp.sum(w["b"] ** 2)

    weights = {"w": np.ones((4, 4), np.float32),
               "b": np.ones((4,), np.float32)}
    batches = [{"x": np.random.RandomState(i).randn(2, 4).astype(np.float32)}
               for i in range(3)]
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _, **kw: compiled.append(kw.get("fun_name"))
        if event == drive._COMPILE_EVENT else None)
    got = {}
    for optimizer in (adamw, adamw_committed):
        jax.clear_caches()
        del compiled[:]
        got[optimizer] = common.train_steps(
            loss, optimizer, hyper, ["w", "b"], weights, batches, lr=1e-2,
            block_rows=2)
        got[optimizer]["compiles"] = {
            part: sum(part in (name or "") for name in compiled)
            for part in ("block_loss", "update")}
    plain, committed = got[adamw], got[adamw_committed]
    # the loss and the update once each, where adamw.py has them twice
    assert plain["compiles"] == {"block_loss": 2, "update": 2}
    assert committed["compiles"] == {"block_loss": 1, "update": 1}
    assert committed["loss"] == plain["loss"]
    for tree in ("trace1", "delta"):
        for name in weights:
            assert np.array_equal(committed[tree][name], plain[tree][name])


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
