"""The benchmark's own copy of the trainer's ``tokens:<N>@<first>`` data.

``tokens:<N>@<first>`` is N rows; row ``i`` of them is drawn from
``RandomState(first + i)``: ``sequence_length + 1`` int32 ids below the
vocabulary the model holds (the inputs are all but the last, the labels
all but the first), then the number of tokens the loss keeps, which
leaves a tail of up to 1/16 of the row out (the padding at the end of a
packed row). An epoch visits ``RandomState(sampler_seed +
epoch).permutation(N)`` in order, in global batches of consecutive
indices. Copied (not imported) from ``dptpu/data`` so that the
comparison can tell what the feed delivered from what it should have.

**The seed and the number of rows.** ``drive.dataset_images`` adds
``seed % 128`` rows to the traffic file's number. At 2 rows a step that
would change the steps of an epoch, which the learning-rate schedule
bakes into the step program: every seed would be another program and a
compile-cache miss. So ``argument`` hands the trainer the traffic file's
number of rows, which has to be whole 128s, and the rows the seed adds
become the FIRST ROW: each seed sees its own rows in the same order of
the same number of steps. Every function here takes the number of rows
as the harness counts them (``dataset_images + seed % 128``).

A feed module (``benchmark/README.md`` has the contract) gives ``KEYS``,
``argument``, ``epoch_order`` and ``batch``.
"""

from __future__ import annotations

import numpy as np

# the keys of a delivered batch, in the order the comparison walks them
KEYS = ("tokens", "labels", "mask")
SEED_ROWS = 128  # drive.dataset_images adds seed % 128


def split(num_rows: int):
    """``(rows of the data set, first row)`` of the harness's count."""
    first = int(num_rows) % SEED_ROWS
    return int(num_rows) - first, first


def argument(num_rows: int) -> str:
    """The trainer's positional ``data`` argument."""
    rows, first = split(num_rows)
    return f"tokens:{rows}@{first}"


def row(index: int, length: int, vocab: int):
    rng = np.random.RandomState(int(index))
    ids = rng.randint(0, vocab, length + 1, dtype=np.int32)
    kept = int(rng.randint(length - length // 16, length + 1))
    return ids[:-1], ids[1:], np.arange(length) < kept


def epoch_order(num_rows: int, sampler_seed: int, epoch: int) -> np.ndarray:
    """The seeds of the rows in the order the epoch visits them."""
    rows, first = split(num_rows)
    return first + np.random.RandomState(sampler_seed + epoch).permutation(rows)


def batch(order: np.ndarray, step: int, global_batch: int, model: dict):
    """``(tokens int32 [B,S], labels int32 [B,S], mask bool [B,S])`` of
    ``step``, one array per key of ``KEYS``; the sizes are the
    configuration's ``model`` group's."""
    indices = order[step * global_batch:(step + 1) * global_batch]
    rows = [row(i, model["sequence_length"], model["vocab_size"])
            for i in indices]
    return tuple(np.stack([r[k] for r in rows]) for k in range(len(KEYS)))
