"""The benchmark's own copy of the trainer's ``synthetic:<N>`` data.

``synthetic:<N>`` is N rows, row ``i`` drawn from ``RandomState(i)``: an
``image_size`` x ``image_size`` x 3 uint8 image, then its label. An epoch
visits ``RandomState(sampler_seed + epoch).permutation(N)`` in order, in
global batches of consecutive indices, and a mesh takes consecutive rows
of the batch per chip. Copied (not imported) from ``dptpu/data`` so that
the comparison can tell what the feed delivered from what it should have.

A feed module (``benchmark/README.md`` has the contract) gives ``KEYS``,
``argument``, ``epoch_order`` and ``batch``.
"""

from __future__ import annotations

import numpy as np

# the keys of a delivered batch, in the order the comparison walks them
KEYS = ("images", "labels")


def argument(num_rows: int) -> str:
    """The trainer's positional ``data`` argument for ``num_rows`` rows."""
    return f"synthetic:{num_rows}"


def row(index: int, image_size: int, num_classes: int):
    rng = np.random.RandomState(int(index))
    image = rng.randint(0, 256, (image_size, image_size, 3), dtype=np.uint8)
    return image, int(rng.randint(0, num_classes))


def epoch_order(num_rows: int, sampler_seed: int, epoch: int) -> np.ndarray:
    return np.random.RandomState(sampler_seed + epoch).permutation(num_rows)


def batch(order: np.ndarray, step: int, global_batch: int, model: dict):
    """``(images uint8 [B,H,W,3], labels int32 [B])`` of ``step``, one
    array per key of ``KEYS``; the sizes are the configuration's
    ``model`` group's."""
    indices = order[step * global_batch:(step + 1) * global_batch]
    rows = [row(i, model["image_size"], model["num_classes"])
            for i in indices]
    return (np.stack([r[0] for r in rows]),
            np.asarray([r[1] for r in rows], np.int32))
