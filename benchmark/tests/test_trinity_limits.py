"""The limits of ``trinity-mini-ep8-bf16`` hold a program that leaves out
what this family adds: the window (every layer sees the whole row behind
the query), the full-attention layer (it gets the window and the rotary
positions too), the scaled embedding, the router's scale. At toy widths
on the CPU, with the reference itself standing in for the program (so
every gap of the sound run is exactly 0 and what is read is the fault
alone): the family's loss under the configuration's optimizer for the
cell's two checked steps, compared by ``lib/check.py`` under the
configuration's own limits. Half a minute."""

import functools

import numpy as np
import pytest

from benchmark.lib import cells
from benchmark.lib import check
from benchmark.reference import common

CELL = "trinity-mini-fit-8k-1chip"
TOY = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
           layers_first=1, layers_held=3,
           layer_types=["sliding_attention", "sliding_attention",
                        "full_attention"],
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           sliding_window=16, router_experts=8, experts_held=4,
           num_experts_per_tok=2, vocab_size=256, sequence_length=128)


@pytest.fixture(scope="module")
def sound_trinity():
    cell = cells.load_cell(CELL)
    model = dict(cell.config["model"], **TOY)
    rng = np.random.RandomState(43)
    weights = {name: (np.full(shape, scale) if kind == "const"
                      else scale * rng.randn(*shape)).astype(np.float32)
               for name, shape, kind, scale in cell.family.weight_spec(model)}
    steps = int(cell.traffic["check_steps"])
    length = model["sequence_length"]
    ids = rng.randint(0, 256, (steps, 1, length + 1)).astype(np.int32)
    batches = [{"tokens": row[:, :-1], "labels": row[:, 1:],
                "mask": np.ones((1, length), bool)} for row in ids]

    def run(**changed):
        return common.train_steps(
            functools.partial(cell.family.loss, dict(model, **changed)),
            cell.optimizer, cell.config["optimizer"],
            cell.family.trainable(model), weights, batches,
            lr=float(cell.traffic["effective_lr"]), block_rows=1)

    return cell, run, run()


def _over(cell, program, reference):
    verdict = check.compare(
        dict(check.measured(program), feed_mismatch=0, nonfinite=0),
        check.measured(reference), cell.config["limits"])
    over = {name for name, n in verdict["numbers"].items()
            if n["value"] > n["limit"]}
    assert verdict["correct"] is (not over)
    return over, verdict["numbers"]


def test_the_sound_trinity_program_passes_and_reads_zero(sound_trinity):
    cell, run, reference = sound_trinity
    over, numbers = _over(cell, run(), reference)
    assert not over
    assert all(n["value"] == 0 for n in numbers.values())


@pytest.mark.parametrize("fault", [
    {"sliding_window": 128},  # no window: the causal mask in every layer
    {"layer_types": ["sliding_attention"] * 3},  # no full-attention layer
    {"mup_enabled": False},   # the embedding not scaled by sqrt(hidden)
    {"route_scale": 1.0},     # the experts' weights not scaled
], ids=["no-window", "no-full-layer", "embedding-not-scaled",
        "route-scale-1"])
def test_a_program_without_a_piece_of_the_family_fails_a_limit(
        sound_trinity, fault):
    cell, run, reference = sound_trinity
    over, numbers = _over(cell, run(**fault), reference)
    assert over & {"grad_gap_kernels", "grad_gap_median",
                   "delta_gap_kernels", "delta_gap_median"}, numbers
