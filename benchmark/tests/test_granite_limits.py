"""The limits of ``granite-4.0-h-micro-vp8-bf16`` hold a program that
leaves out what this family adds: the skip ``D x`` of the state-space
layer, the scaled residual branches (``residual_multiplier`` 0.22), or
the float32 of the scan's decays and state. At toy widths on the CPU,
with the reference itself standing in for the program (so every gap of
the sound run is exactly 0 and what is read is the fault alone): the
family's loss under the configuration's optimizer for the cell's two
checked steps, compared by ``lib/check.py`` under the configuration's own
limits. The row is 4,096 tokens long, because the last fault shows only
over a long row. Under a minute."""

import functools

import numpy as np
import pytest

from benchmark.lib import cells
from benchmark.lib import check
from benchmark.reference import common

CELL = "granite-ssm-fit-1chip"
TOY = dict(hidden_size=64, shared_intermediate_size=96, layers_first=4,
           layers_held=3, layer_types=["mamba", "attention", "mamba"],
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
           mamba_chunk_size=16, vocab_size=256, sequence_length=4096)


@pytest.fixture(scope="module")
def sound():
    cell = cells.load_cell(CELL)
    model = dict(cell.config["model"], **TOY)
    rng = np.random.RandomState(39)
    draw = {"const": lambda shape, s: np.full(shape, s),
            "normal": lambda shape, s: s * rng.randn(*shape),
            "uniform": lambda shape, s: rng.uniform(-s, s, shape)}
    weights = {name: draw[kind](shape, scale).astype(np.float32)
               for name, shape, kind, scale in cell.family.weight_spec(model)}
    steps = int(cell.traffic["check_steps"])
    length = model["sequence_length"]
    ids = rng.randint(0, 256, (steps, 1, length + 1)).astype(np.int32)
    batches = [{"tokens": row[:, :-1], "labels": row[:, 1:],
                "mask": np.ones((1, length), bool)} for row in ids]

    def run(model=model, weights=weights, mode="f32"):
        return common.train_steps(
            functools.partial(cell.family.loss, model), cell.optimizer,
            cell.config["optimizer"], cell.family.trainable(model), weights,
            batches, lr=float(cell.traffic["effective_lr"]), block_rows=1,
            mode=mode)

    return cell, model, weights, run, run()


def _verdict(cell, program, reference):
    return check.compare(
        dict(check.measured(program), feed_mismatch=0, nonfinite=0),
        check.measured(reference), cell.config["limits"])


def _over(verdict):
    return {name for name, n in verdict["numbers"].items()
            if n["value"] > n["limit"]}


def test_the_sound_program_passes_and_reads_zero(sound):
    cell, _, _, run, reference = sound
    verdict = _verdict(cell, run(), reference)
    assert verdict["correct"] is True
    assert all(n["value"] == 0 for n in verdict["numbers"].values())


def test_a_program_without_the_skip_fails_the_gradient_limit(sound):
    cell, _, weights, run, reference = sound
    # y = h C + D x without its second term
    without = {k: np.zeros_like(v) if k.endswith("mamba.D") else v
               for k, v in weights.items()}
    verdict = _verdict(cell, run(weights=without), reference)
    assert verdict["correct"] is False
    assert "grad_gap_kernels" in _over(verdict)


def test_a_program_without_the_residual_multiplier_fails_every_limit(sound):
    cell, model, _, run, reference = sound
    verdict = _verdict(cell, run(model=dict(model, residual_multiplier=1.0)),
                       reference)
    assert verdict["correct"] is False
    assert {"grad_gap_kernels", "grad_gap_median", "delta_gap_kernels"} \
        <= _over(verdict)


def test_bfloat16_decays_and_state_fail_the_kernels_gradient_limit(sound):
    """Every product in float32 and only the scan's decays and carried
    state rounded to bfloat16: a slow head's decay of 0.9996 a token
    rounds to 1 and its state stops forgetting, which over thousands of
    tokens moves the gradient of the convolution before it (on the chip
    at the cell's size 0.030-0.038 against a sound program's 0.0064 at
    most: PERF.md section 2). Nothing else separates it: the row is long
    for that."""
    cell, _, _, run, reference = sound
    verdict = _verdict(cell, run(mode="f32+scan"), reference)
    assert verdict["correct"] is False, verdict["numbers"]
    assert _over(verdict) == {"grad_gap_kernels"}
    numbers = verdict["numbers"]["grad_gap_kernels"]
    assert numbers["value"] > 2 * numbers["limit"]
    assert verdict["worst_leaf"]["grad_gap"]["kernels"].endswith(
        "mamba.conv1d.weight")
