"""The job's data order, as the program documents it (copied from
``training/data.train_val_split`` and ``epoch_order`` so that the plain
reference can follow the same rows without importing the program).
``tests/perfbench`` pins both against the originals."""

from __future__ import annotations

import numpy as np


def train_val_split(n: int, val_fraction: float, seed: int):
    """(train rows, validation rows): a seeded permutation, validation
    first."""
    order = np.random.default_rng(seed).permutation(n)
    n_val = max(1, int(round(n * val_fraction))) if n > 1 else 0
    return order[n_val:], order[:n_val]


def epoch_order(n: int, batch: int, shuffle: bool, rng) -> np.ndarray:
    """[batches, batch] row indices covering [0, n), the tail filled by
    repeating the permutation cyclically so that every batch is full."""
    order = np.arange(n)
    if shuffle:
        rng.shuffle(order)
    n_batches = max(1, int(np.ceil(n / batch)))
    return np.resize(order, n_batches * batch).reshape(n_batches, batch)
