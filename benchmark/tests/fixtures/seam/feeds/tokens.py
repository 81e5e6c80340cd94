"""A fixture feed of token rows: ``tokens:<N>`` is N rows, row ``i``
drawn from ``RandomState(i)``: ``sequence_length`` + 1 token ids below
``vocab_size`` (the inputs are all but the last, the labels all but the
first) and a mask that leaves a tail of the row out of the loss."""

import numpy as np

KEYS = ("tokens", "labels", "mask")


def argument(num_rows: int) -> str:
    return f"tokens:{num_rows}"


def epoch_order(num_rows: int, sampler_seed: int, epoch: int) -> np.ndarray:
    return np.random.RandomState(sampler_seed + epoch).permutation(num_rows)


def row(index: int, length: int, vocab: int):
    rng = np.random.RandomState(int(index))
    ids = rng.randint(0, vocab, length + 1).astype(np.int32)
    mask = np.arange(length) < rng.randint(length // 2, length + 1)
    return ids[:-1], ids[1:], mask


def batch(order: np.ndarray, step: int, global_batch: int, model: dict):
    indices = order[step * global_batch:(step + 1) * global_batch]
    rows = [row(i, model["sequence_length"], model["vocab_size"])
            for i in indices]
    return tuple(np.stack([r[k] for r in rows]) for k in range(len(KEYS)))
