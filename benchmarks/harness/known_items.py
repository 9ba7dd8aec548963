"""Known items from the seed: which items each user already has.

As the reference's ``LoadTestALSModelFactory`` gives every user of its load
test known items: a Poisson count a user, the items uniform over the
catalogue, drawn with replacement (a repeat is one known item). Users come
in fixed blocks, each from a generator of its own keyed by ``(seed, 13,
block)``, so that threads fill the table in parallel with the same result.
"""

from __future__ import annotations

import concurrent.futures as cf
import os

import numpy as np

BLOCK_USERS = 1 << 16
_WHICH = 13


def block(seed: int, b: int, users: int, n_items: int, mean: float):
    """(counts, items) of one block of users."""
    rng = np.random.default_rng([int(seed), _WHICH, b])
    counts = rng.poisson(mean, users).astype(np.int64)
    items = rng.integers(0, n_items, int(counts.sum()), dtype=np.int32)
    return counts, items


def make(seed: int, n_users: int, n_items: int, mean: float,
         workers: "int | None" = None):
    """``(offsets, items)``: user ``u`` knows item rows
    ``items[offsets[u]:offsets[u + 1]]`` (int64 offsets, int32 rows)."""
    starts = list(range(0, n_users, BLOCK_USERS))
    workers = workers or min(12, os.cpu_count() or 1)
    with cf.ThreadPoolExecutor(workers) as pool:
        parts = list(pool.map(
            lambda bs: block(seed, bs[0], min(BLOCK_USERS, n_users - bs[1]),
                             n_items, mean), enumerate(starts)))
    offsets = np.zeros(n_users + 1, dtype=np.int64)
    np.cumsum(np.concatenate([c for c, _ in parts]), out=offsets[1:])
    return offsets, np.concatenate([i for _, i in parts])


def of_user(offsets: np.ndarray, items: np.ndarray, user: int) -> np.ndarray:
    return items[offsets[user]:offsets[user + 1]]
