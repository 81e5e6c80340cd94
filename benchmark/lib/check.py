"""The comparison that decides ``correct``.

What the timed path produced in its first steps (taken by the tap in
``drive.py`` from the very step, state and feed that the window then
drives) is held against the plain reference, which is given the
benchmark's own seeded weights and its own regenerated rows. Numbers:

* ``feed_mismatch`` — values of the delivered batches (every key of the
  feed, every checked step) that differ from the regenerated rows. Exact.
* ``loss_gap_<k>`` — |program loss - reference loss| of step k.
* ``grad_gap_kernels`` — the first gradient as the optimizer got it (from
  its state after one step, where the optimizer's module says), worst leaf
  among the kernels (leaves with two axes or more longer than 1): the gap
  between the program's norm and the reference's, against the reference's
  norm of that leaf or of the median leaf, whichever is larger.
* ``grad_gap_median`` — the same gap of the median leaf, over all leaves.
  The worst leaf over ALL leaves is printed too (``worst_leaf``) but not
  compared: a BatchNorm scale or shift of an early layer sums 400,000
  rounded terms that nearly cancel, and reads 0.2-0.35 in sound bfloat16.
* ``delta_gap_kernels`` / ``delta_gap_median`` — the same two for the
  parameters' change after the checked steps.
* ``nonfinite`` — parameter leaves that are not finite once the window has
  closed: the loss has to stay finite for the whole window.

Each has its own limit, in the configuration's ``limits``.
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np


def _norms(tree: Dict[str, np.ndarray]) -> Dict[str, float]:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
            for k, v in tree.items()}


def leaf_gaps(program: Dict[str, np.ndarray],
              reference: Dict[str, np.ndarray]) -> Dict[str, float]:
    """Per leaf, ``|‖p‖ - ‖r‖| / max(‖r‖, median leaf ‖r‖)``: the gap
    between the norms, not the norm of the difference, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero). A leaf that has not moved,
    or moved double, on one side reads about 1."""
    if set(program) != set(reference):
        raise ValueError("the program's leaves and the reference's differ: "
                         f"{sorted(set(program) ^ set(reference))[:4]}")
    pn, rn = _norms(program), _norms(reference)
    floor = float(np.median(list(rn.values())))
    out = {}
    for k in rn:
        gap = abs(pn[k] - rn[k]) / max(rn[k], floor, 1e-30)
        out[k] = gap if math.isfinite(gap) else float("inf")
    return out


def is_kernel(array) -> bool:
    """Two axes or more longer than 1: a convolution or matrix kernel, not
    a bias, a norm's scale or shift, or a token."""
    return sum(1 for d in np.shape(array) if d > 1) >= 2


def summarize(program, reference) -> dict:
    """The readings of one pair of trees: worst leaf overall, worst
    kernel, median leaf, each with the leaf it is."""
    gaps = leaf_gaps(program, reference)
    kernels = {k: g for k, g in gaps.items() if is_kernel(reference[k])}
    worst = max(gaps, key=gaps.get)
    worst_k = max(kernels, key=kernels.get)
    return {
        "worst": gaps[worst], "worst_leaf": worst,
        "kernels": kernels[worst_k], "kernels_leaf": worst_k,
        "median": float(np.median(list(gaps.values()))),
        "p90": float(np.percentile(list(gaps.values()), 90)),
    }


def global_gap(program, reference) -> float:
    """The same gap over all leaves taken as one vector."""
    pn = math.sqrt(sum(v * v for v in _norms(program).values()))
    rn = math.sqrt(sum(v * v for v in _norms(reference).values()))
    return abs(pn - rn) / max(rn, 1e-30)


def feed_mismatch(delivered: List[tuple], regenerated: List[tuple]) -> int:
    """Values that differ, exactly, over every checked step and every
    array of its batch (one per key of the feed, in the feed's order). A
    step whose arrays differ in number or shape counts whole; a step
    that is missing on one side counts one."""
    count = 0
    for got, want in zip(delivered, regenerated):
        if len(got) != len(want) or any(
                g.shape != w.shape for g, w in zip(got, want)):
            return int(sum(w.size for w in want))
        count += sum(int(np.count_nonzero(g != w))
                     for g, w in zip(got, want))
    return count + abs(len(delivered) - len(regenerated))


def compare(program: dict, reference: dict, limits: dict) -> dict:
    """``{"correct": bool, "numbers": {name: {"value", "limit"}}, ...}``.

    ``program`` / ``reference``: ``{"loss": [..], "trace1": {leaf: array},
    "delta": {leaf: array}}`` over the same leaf names; ``program`` also
    ``"feed_mismatch"`` and ``"nonfinite"``.
    """
    numbers = {"feed_mismatch": {"value": program["feed_mismatch"],
                                 "limit": limits["feed_mismatch"]}}
    for k, (lp, lr) in enumerate(zip(program["loss"], reference["loss"]), 1):
        numbers[f"loss_gap_{k}"] = {"value": abs(lp - lr),
                                    "limit": limits["loss_gap"]}
    if len(program["loss"]) != len(reference["loss"]):
        numbers["loss_steps_missing"] = {
            "value": abs(len(program["loss"]) - len(reference["loss"])),
            "limit": 0}
    where = {}
    for name, key in (("grad_gap", "trace1"), ("delta_gap", "delta")):
        got = summarize(program[key], reference[key])
        for stat in ("kernels", "median"):
            numbers[f"{name}_{stat}"] = {"value": got[stat],
                                         "limit": limits[f"{name}_{stat}"]}
        where[name] = {"kernels": got["kernels_leaf"],
                       "all": [got["worst_leaf"], got["worst"]]}
    numbers["nonfinite"] = {"value": program["nonfinite"],
                            "limit": limits["nonfinite"]}
    correct = all(
        math.isfinite(float(n["value"])) and n["value"] <= n["limit"]
        for n in numbers.values())
    return {"correct": correct, "numbers": numbers, "worst_leaf": where}
