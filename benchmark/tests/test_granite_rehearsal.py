"""A CPU rehearsal of ``granite-ssm-fit-1chip`` at a share a CPU holds:
the published widths, layers 4-5 (a Mamba-2 layer and the attention
layer), 256 vocabulary rows, 96 tokens a row (no whole number of the
scan's chunks of 256, nor of the reference's segments of 64). Everything
of ``run.py`` but its look for a chip: the trainer through ``main_apex``
on ``tokens:<N>@<first>``, the tap, the window, the reference (the
recurrence token by token against the program's chunked scan), the
comparison. A few minutes."""

import json
import time

import pytest

from benchmark.lib import cells, drive

CELL = "granite-ssm-fit-1chip"
SHARE = ["--layers", "4:2", "--vocab-rows", "0:256", "--seq-len", 96]


def tiny_cell():
    cell = cells.load_cell(CELL)
    config = json.loads(json.dumps(cell.config))
    config["create_kwargs"] = {"layers": "4:2", "vocab": "0:256",
                               "sequence_length": 96}
    config["model"].update(layers_first=4, layers_held=2,
                           layer_types=["mamba", "attention"],
                           vocab_size=256, sequence_length=96)
    traffic = dict(cell.traffic, dataset_images=1024, warmup_iters=5,
                   trace_read_s=0.5, trace_stall_cap_s=4.0, extra_argv=SHARE)
    return cells.Cell(name=CELL, chips=1, config=config, traffic=traffic,
                      end_to_end=cell.end_to_end, per_layer=cell.per_layer)


@pytest.mark.parametrize("trace", [False, True])
def test_a_run_of_the_granite_cell_prints_the_contracts_last_line(trace):
    result = drive.run_cell(tiny_cell(), 2**31 + 39, 3.0, trace, time.time())
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
        assert "iter_ms_p50" in line["metrics"]
        # the program's counters ride the lagged fetch: a window this
        # short may hold none before the profiler opens
        share = line["metrics"].get("ssd_kernel_share")
        assert share is None or share["value"] == 0.0
        assert not [m for m in line["metrics"] if m.startswith("expert_")]
    else:
        assert set(line["metrics"]) == {"train_img_s_chip", "step_ms_p95",
                                        "setup_s"}
