"""The limits of ``joyai-llm-flash-ep32-bf16`` hold a program that leaves
out what this family adds: the multi-token-prediction term of the loss,
or the shared expert. At toy widths on the CPU, with the reference itself
standing in for the program (so every gap of the sound run is exactly 0
and what is read is the fault alone): the family's loss under the
configuration's optimizer for the cell's two checked steps, compared by
``lib/check.py`` under the configuration's own limits. Half a minute."""

import functools

import numpy as np
import pytest

from benchmark.lib import cells
from benchmark.lib import check
from benchmark.reference import common

CELL = "joyai-fit-8k-1chip"
TOY = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
           layers_held=2, mtp_layer=2, num_attention_heads=2,
           q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=12, router_experts=8,
           experts_held=8, num_experts_per_tok=2, vocab_size=256,
           sequence_length=64)


@pytest.fixture(scope="module")
def sound():
    cell = cells.load_cell(CELL)
    model = dict(cell.config["model"], **TOY)
    rng = np.random.RandomState(36)
    weights = {
        name: np.full(shape, scale, np.float32) if kind == "const"
        else (scale * rng.randn(*shape)).astype(np.float32)
        for name, shape, kind, scale in cell.family.weight_spec(model)}
    steps = int(cell.traffic["check_steps"])
    ids = rng.randint(0, 256, (steps, 1, 65)).astype(np.int32)
    batches = [{"tokens": row[:, :-1], "labels": row[:, 1:],
                "mask": np.ones((1, 64), bool)} for row in ids]

    def run(model=model, weights=weights):
        return common.train_steps(
            functools.partial(cell.family.loss, model), cell.optimizer,
            cell.config["optimizer"], cell.family.trainable(model), weights,
            batches, lr=float(cell.traffic["effective_lr"]), block_rows=1)

    return cell, model, weights, run, run()


def _verdict(cell, program, reference):
    return check.compare(dict(program, feed_mismatch=0, nonfinite=0),
                         reference, cell.config["limits"])


def test_the_sound_program_passes_and_reads_zero(sound):
    cell, _, _, run, reference = sound
    verdict = _verdict(cell, run(), reference)
    assert verdict["correct"] is True
    assert all(n["value"] == 0 for n in verdict["numbers"].values())


def test_a_program_without_the_second_loss_fails_the_loss_limit(sound):
    cell, model, _, run, reference = sound
    verdict = _verdict(cell, run(model=dict(model, mtp_loss_weight=0.0)),
                       reference)
    assert verdict["correct"] is False
    numbers = verdict["numbers"]
    # a tenth of a cross-entropy near log(vocabulary held)
    assert numbers["loss_gap_1"]["value"] > 100 * numbers["loss_gap_1"]["limit"]
    assert numbers["grad_gap_kernels"]["value"] > numbers["grad_gap_kernels"]["limit"]


def test_a_program_without_the_shared_expert_fails_the_gradient_limit(sound):
    cell, _, weights, run, reference = sound
    # what it adds is taken out; its matrices get no gradient to speak of
    without = {k: np.zeros_like(v) if "shared_experts.down_proj" in k else v
               for k, v in weights.items()}
    verdict = _verdict(cell, run(weights=without), reference)
    assert verdict["correct"] is False
    numbers = verdict["numbers"]
    assert numbers["grad_gap_kernels"]["value"] \
        > numbers["grad_gap_kernels"]["limit"]
    assert "shared_experts" in verdict["worst_leaf"]["grad_gap"]["kernels"]
