"""Factor matrices from the seed: the benchmark's weights.

Rows come in fixed blocks, each from a generator of its own keyed by
``(seed, which, block)``, so that any block can be made again alone and
threads can fill a matrix in parallel with the same result.
"""

from __future__ import annotations

import concurrent.futures as cf
import os

import numpy as np

BLOCK_ROWS = 1 << 16
_WHICH = {"items": 11, "users": 12}


def block(seed: int, which: str, b: int, rows: int, features: int,
          out: "np.ndarray | None" = None) -> np.ndarray:
    rng = np.random.default_rng([int(seed), _WHICH[which], b])
    return rng.standard_normal((rows, features), dtype=np.float32, out=out)


def make(seed: int, which: str, n_rows: int, features: int,
         workers: "int | None" = None) -> np.ndarray:
    """``(n_rows, features)`` float32 N(0, 1) entries on the host."""
    out = np.empty((n_rows, features), dtype=np.float32)
    starts = range(0, n_rows, BLOCK_ROWS)

    def fill(b_start):
        b, start = b_start
        rows = min(BLOCK_ROWS, n_rows - start)
        block(seed, which, b, rows, features, out=out[start:start + rows])

    workers = workers or min(12, os.cpu_count() or 1)
    with cf.ThreadPoolExecutor(workers) as pool:
        list(pool.map(fill, enumerate(starts)))
    return out
