"""A CPU rehearsal of ``trinity-mini-fit-8k-1chip`` at a share a CPU
holds: the published widths, layers 2-3 (a window layer and the FULL one,
both with experts and the shared expert), 2 of 128 experts, 256
vocabulary rows, 96 tokens a row with the window cut to 32 (so that the
window layer's mask is no causal one). Everything of ``run.py`` but its
look for a chip: the trainer through ``main_apex`` on
``tokens:<N>@<first>``, the tap, the window, the reference, the
comparison. A few minutes."""

import dataclasses
import json
import time

import pytest

from benchmark.lib import cells, drive

CELL = "trinity-mini-fit-8k-1chip"
SHARE = ["--layers", "2:2", "--experts", "0:2", "--vocab-rows", "0:256",
         "--seq-len", 96]
WINDOW = 32


def tiny_cell():
    cell = cells.load_cell(CELL)
    config = json.loads(json.dumps(cell.config))
    config["create_kwargs"] = {"layers": "2:2", "experts": "0:2",
                               "vocab": "0:256", "sequence_length": 96}
    config["model"].update(
        layers_first=2, layers_held=2,
        layer_types=["sliding_attention", "full_attention"],
        sliding_window=WINDOW, experts_held=2, vocab_size=256,
        sequence_length=96)
    traffic = dict(cell.traffic, dataset_images=1024, warmup_iters=5,
                   trace_read_s=0.5, trace_stall_cap_s=4.0, extra_argv=SHARE)
    return cells.Cell(name=CELL, chips=1, config=config, traffic=traffic,
                      end_to_end=cell.end_to_end, per_layer=cell.per_layer)


@pytest.fixture
def a_short_window(monkeypatch):
    """The registered model at a window of ``WINDOW`` tokens: the trainer
    has no flag for it (a width of the model), the rehearsal's row is
    shorter than the published 2,048."""
    from dptpu.models import registry, trinity

    monkeypatch.setitem(
        registry._REGISTRY, "trinity_mini", trinity.factory(
            "trinity_mini", dataclasses.replace(trinity.TrinityConfig(),
                                                sliding_window=WINDOW)))


@pytest.mark.parametrize("trace", [False, True])
def test_a_run_of_the_trinity_cell_prints_the_contracts_last_line(
        a_short_window, trace):
    result = drive.run_cell(tiny_cell(), 2**31 + 43, 3.0, trace, time.time())
    line = json.loads(drive.dumps(result))
    assert line["correct"] is True, line["numbers"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["window"]["compiles_in_window"] == 0
    assert line["numbers"]["feed_mismatch"]["value"] == 0
    assert set(line["numbers"]) == {
        "feed_mismatch", "loss_gap_1", "loss_gap_2", "grad_gap_kernels",
        "grad_gap_median", "delta_gap_kernels", "delta_gap_median",
        "nonfinite"}
    if trace:
        # device metrics find nothing to read on a CPU and are left out
        assert "device_mfu" not in line["metrics"]
        assert "attention_kernel_roofline_share" not in line["metrics"]
        assert "iter_ms_p50" in line["metrics"]
        # the program's counters ride the lagged fetch: a window this
        # short may hold none before the profiler opens; a row of 96 is
        # one tile with a window or without
        share = line["metrics"].get("attention_tile_share")
        assert share is None or share["value"] == 100.0
        assert "mtp_loss_share" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"train_img_s_chip", "step_ms_p95",
                                        "setup_s"}
