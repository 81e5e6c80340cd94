"""``tokens:<N>``: seeded rows of token ids, the language-model stand-in
for ``synthetic:<N>``.

Row ``i`` is ``sequence_length + 1`` ids drawn from
``RandomState(first_row + i)`` below the vocabulary the model holds: the
inputs are all but the last, the labels all but the first (the next
token), and a seeded tail of up to 1/16 of the row is left out of the
loss, as the padding at the end of a packed row is. Like the synthetic
images it takes reading and tokenising out of the measurement and leaves
the loader, the prefetcher and the copy to the device as they are.

To the loader a row is one item (the int32 ids) and one scalar (the
tokens kept), exactly as an image and its label are; ``collate`` turns a
batch of them into what the step takes: ``tokens`` and ``labels`` int32
``[B, S]``, ``mask`` bool ``[B, S]``.
"""

from __future__ import annotations

import numpy as np

KEYS = ("tokens", "labels", "mask")


def parse_source(data: str):
    """``tokens:<N>`` or ``tokens:<N>@<first row>`` -> ``(N, first)``;
    None if ``data`` names another source."""
    if not data.startswith("tokens"):
        return None
    spec = data.split(":", 1)[1] if ":" in data else "2048"
    rows, _, first = spec.partition("@")
    try:
        rows, first = int(rows), int(first or 0)
    except ValueError:
        raise ValueError(
            f"data source {data!r} must be tokens:<rows> or "
            f"tokens:<rows>@<first row>") from None
    if rows < 1 or first < 0:
        raise ValueError(f"data source {data!r} needs at least one row "
                         f"and a first row of 0 or more")
    return rows, first


class TokenDataset:
    """Deterministic rows of token ids; index-stable across epochs."""

    def __init__(self, num_rows: int, sequence_length: int, vocab_size: int,
                 first_row: int = 0):
        self.num_rows = int(num_rows)
        self.sequence_length = int(sequence_length)
        self.vocab_size = int(vocab_size)
        self.first_row = int(first_row)

    def __len__(self) -> int:
        return self.num_rows

    def get(self, index: int, rng=None):
        """``(ids int32 [S + 1], tokens kept)`` of row ``index``."""
        length = self.sequence_length
        data_rng = np.random.RandomState(
            self.first_row + index % self.num_rows)
        ids = data_rng.randint(0, self.vocab_size, length + 1,
                               dtype=np.int32)
        kept = int(data_rng.randint(length - length // 16, length + 1))
        return ids, kept

    def get_into(self, index: int, rng, out: np.ndarray) -> int:
        ids, kept = self.get(index, rng)
        np.copyto(out, ids)
        return kept

    def __getitem__(self, index: int):
        return self.get(index)

    def collate(self, ids: np.ndarray, kept: np.ndarray, row_mask=None):
        """The batch the step takes. ``row_mask`` (the loader's: 1 = a
        real row, not padding or a wrapped duplicate) takes whole rows
        out of the loss."""
        mask = np.arange(self.sequence_length)[None, :] < kept[:, None]
        if row_mask is not None:
            mask &= row_mask[:, None] > 0
        return {"tokens": ids[:, :-1], "labels": ids[:, 1:], "mask": mask}
