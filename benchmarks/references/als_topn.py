"""Plain reference for ALS top-N serving: a brute-force scan in float32.

Scores every item for each query with a float32 matrix product at the
highest matmul precision, in blocks of rows so that it fits beside nothing
else, and keeps the exact top ``keep``. Imports nothing of the program.

``control=True`` is the same scan computed one precision below the
configuration's bfloat16 scoring copy: rows and queries quantized to int8
with one scale per row, products accumulated in int32.
"""

from __future__ import annotations

import numpy as np


def _quantize(mat):
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(mat), axis=1, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(mat / scale).astype(jnp.int8), scale


def _block_scores(qs, blk, control: bool):
    import jax
    import jax.numpy as jnp

    if control:
        qq, qscale = _quantize(qs)
        bq, bscale = _quantize(blk)
        acc = jnp.matmul(qq, bq.T, preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * qscale * bscale.T
    return jnp.matmul(qs, blk.T, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def top_n(queries: np.ndarray, items: np.ndarray, keep: int,
          block_rows: int = 1 << 19, control: bool = False):
    """(values, indices), each ``(len(queries), keep)``, best first."""
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("k",))
    def scan_block(qs, blk, k):
        return jax.lax.top_k(_block_scores(qs, blk, control), k)

    qs = jnp.asarray(queries, dtype=jnp.float32)
    vals, idxs = [], []
    for start in range(0, len(items), block_rows):
        blk = jnp.asarray(items[start:start + block_rows])
        v, i = scan_block(qs, blk, min(keep, blk.shape[0]))
        vals.append(np.asarray(v))
        idxs.append(np.asarray(i) + start)
    v, i = np.concatenate(vals, axis=1), np.concatenate(idxs, axis=1)
    order = np.argsort(-v, axis=1, kind="stable")[:, :keep]
    return np.take_along_axis(v, order, 1), np.take_along_axis(i, order, 1)


def exact_scores(queries: np.ndarray, items: np.ndarray,
                 idx: np.ndarray) -> np.ndarray:
    """float64 dot products of each query with its own rows ``idx``."""
    rows = items[idx].astype(np.float64)  # (S, J, k)
    return np.einsum("sk,sjk->sj", queries.astype(np.float64), rows)
