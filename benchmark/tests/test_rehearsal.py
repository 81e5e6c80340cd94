"""A CPU rehearsal of a whole run at a tiny size (everything of ``run.py``
but its look for a chip), the same run with the timed path broken
underneath, and ``run.py`` itself refusing to run off the chip."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmark.lib import cells, drive

ROOT = cells.ROOT


def _tiny(name, config_name, shrink):
    """Cell ``name`` cut to what a CPU holds: 4 rows a step, a 2,000-row
    epoch, the configuration changed by ``shrink``."""
    bench = cells.manifest()
    with open(os.path.join(cells.BENCH_DIR, "configs",
                           config_name + ".json")) as f:
        config = json.load(f)
    config["per_chip_batch"] = config["reference_block_rows"] = 4
    shrink(config)
    with open(os.path.join(cells.BENCH_DIR, "traffic",
                           "fit-synthetic.json")) as f:
        traffic = json.load(f)
    traffic.update(dataset_images=2000, warmup_iters=5, trace_read_s=0.5,
                   trace_stall_cap_s=4.0)
    reported = lambda m: name in m.get("workloads", [name])  # noqa: E731
    return cells.Cell(
        name=name, chips=1, config=config, traffic=traffic,
        end_to_end=tuple(filter(reported, bench["end_to_end"])),
        per_layer=tuple(filter(reported, bench["per_layer"])))


def tiny_cell():
    """ResNet-18 (the reference file serves the family). The limits are
    the ResNet-50 configuration's own, but for the kernels, which four
    rows of batch statistics make noisier."""
    def shrink(config):
        config["arch"] = "resnet18"
        config["model"].update(stage_sizes=[2, 2, 2, 2], block="basic")

    return _tiny("rn50-fit-1chip", "resnet50-224-bf16", shrink)


def tiny_vit_cell():
    """ViT-B/32: the published widths over 50 tokens where ViT-B/16 has
    197, the reference in two blocks of two rows. The limits are the
    ViT-B/16 configuration's own but for the loss: a mean over four rows
    reads up to 0.0057 in sound bfloat16 where one over 128 read 0.0012
    (two seeds on the CPU, PR 29; PERF.md section 2)."""
    def shrink(config):
        config["arch"] = "vit_b_32"
        config["model"].update(patch_size=32, sequence_length=50)
        config["reference_block_rows"] = 2
        config["limits"] = dict(config["limits"], loss_gap=0.03)

    return _tiny("vitb16-fit-1chip", "vit-b16-224-bf16", shrink)


@pytest.fixture(scope="module")
def cell():
    return tiny_cell()


@pytest.mark.parametrize("trace", [False, True])
def test_a_run_prints_the_contracts_last_line(cell, trace):
    result = drive.run_cell(cell, 2**31 + 77, 2.0, trace, time.time())
    line = json.loads(drive.dumps(result))
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert list(line)[-1] == "numbers"  # the numbers compared come last
    assert line["correct"] is True, line["numbers"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["window"]["compiles_in_window"] == 0
    for n in line["numbers"].values():
        assert set(n) == {"value", "limit"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    declared = cell.per_layer if trace else cell.end_to_end
    units = {m["name"]: m["unit"] for m in declared}
    assert line["metrics"], "a run reports at least one metric"
    for name, metric in line["metrics"].items():
        assert metric["unit"] == units[name]
        assert isinstance(metric["value"], float)
    # where the run's seconds went: the phases follow each other without
    # overlap and leave out only the comparison and the printing
    phases = dict(line["window"]["phases_s"])
    total = phases.pop("total")
    traced = {"trace_open_to_stretch", "traced", "stop_trace", "load_reduce"}
    assert set(phases) == {"setup", "window", "readout", "reference"} | (
        traced if trace else set())
    assert all(v >= 0 for v in phases.values()), phases
    assert sum(phases.values()) == pytest.approx(total, abs=1.0)
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        # the device metrics find nothing to read on a CPU and are left
        # out, never reported as 0
        assert "device_mfu" not in line["metrics"]
        assert "init_s" in line["metrics"]
    else:
        assert set(line["metrics"]) == set(units)


class FrozenStateTap(drive.StepTap):
    """The timed path broken underneath: the step returns its state
    unchanged (a copy taken before the call, which donates its input)."""

    def wrap(self, train_step):
        import jax
        import jax.numpy as jnp

        def frozen(state, batch):
            kept = jax.tree_util.tree_map(jnp.copy, state)
            _, metrics = train_step(state, batch)
            return kept, metrics

        return super().wrap(frozen)


def test_a_tiny_vit_run_is_correct_through_the_same_seams():
    """The second family under the same feed and optimizer modules: the
    transformer's loss and attention, no BatchNorm, two reference blocks
    a step."""
    result = drive.run_cell(tiny_vit_cell(), 2**31 + 77, 1.0, False,
                            time.time())
    assert result["correct"] is True, result["numbers"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "train_img_s_chip", "step_ms_p95", "setup_s"}
    # every leaf moved as the reference's did: none reads near 1
    assert result["worst_leaf"]["delta_gap"]["all"][1] < 0.05


def test_a_step_that_returns_its_state_unchanged_is_not_correct(cell):
    result = drive.run_cell(cell, 5, 1.0, False, time.time(),
                            tap_factory=FrozenStateTap)
    assert result["correct"] is False
    numbers = result["numbers"]
    # nothing moved: the change of every leaf is missing whole (a leaf
    # under the median norm is measured against the median: a little less)
    assert numbers["delta_gap_median"]["value"] > 0.9
    assert numbers["delta_gap_median"]["value"] > \
        numbers["delta_gap_median"]["limit"]


def test_run_py_refuses_to_run_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "rn50-fit-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""  # no result line
    assert "Refusing to run" in done.stderr


def test_every_cell_of_the_manifest_loads():
    bench = cells.manifest()
    for entry in bench["workloads"]:
        loaded = cells.load_cell(entry["name"], bench)
        assert loaded.global_batch == 128 * loaded.chips
        assert any(m["name"] == "setup_s" for m in loaded.end_to_end)
        for m in loaded.per_layer:  # every listed metric has its files
            assert hasattr(cells.reader(m["name"]), "read")
            assert cells.layer_metric(m["name"])["layer"] == m["layer"]
        ref = cells.reference(loaded.config)
        assert ref.train_flops(loaded.config["model"], 1) > 0
    assert cells.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        cells.peaks("TPU v9 imaginary")
