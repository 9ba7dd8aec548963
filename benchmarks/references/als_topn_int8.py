"""Plain reference for ALS top-N served from int8 rows with an exact rescore.

Three plain functions, float32 at the highest matmul precision, in blocks of
rows so that each fits beside nothing else. Imports nothing of the program.

``top_n`` is the truth every answer is held to: the brute force of
``als_topn.py`` (every item scored in float32, the exact top ``keep``).

``two_stage`` is the deployment's own semantics written down plainly: rows
quantized by the stated rule (scale = max|row| / 127, round to nearest, clip
to +-127; a zero row keeps scale 1), every row scored as int8 x float32 with
float32 accumulation, the exact top ``width`` kept, those rescored in float32
from the float32 rows, the best ``keep`` of them returned. ``control=True`` is
each stage one precision below: rows at 4 bits (scale = max|row| / 7, clip to
+-7) and the rescore in bfloat16 (queries and rows rounded to bfloat16,
products accumulated in float32).
"""

from __future__ import annotations

import numpy as np


def _merge(vals: list, idxs: list, keep: int):
    v, i = np.concatenate(vals, axis=1), np.concatenate(idxs, axis=1)
    order = np.argsort(-v, axis=1, kind="stable")[:, :keep]
    return np.take_along_axis(v, order, 1), np.take_along_axis(i, order, 1)


def _scan(score_block, queries: np.ndarray, items: np.ndarray, keep: int,
          block_rows: int):
    """Exact top ``keep`` of ``score_block(qs, blk)`` over all of ``items``."""
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("k",))
    def scan_block(qs, blk, k):
        return jax.lax.top_k(score_block(qs, blk), k)

    qs = jnp.asarray(queries, dtype=jnp.float32)
    vals, idxs = [], []
    for start in range(0, len(items), block_rows):
        blk = jnp.asarray(items[start:start + block_rows])
        v, i = scan_block(qs, blk, min(keep, blk.shape[0]))
        vals.append(np.asarray(v))
        idxs.append(np.asarray(i) + start)
    return _merge(vals, idxs, keep)


def _float32_scores(qs, blk):
    import jax
    import jax.numpy as jnp

    return jnp.matmul(qs, blk.T, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def top_n(queries: np.ndarray, items: np.ndarray, keep: int,
          block_rows: int = 1 << 19):
    """(values, indices), each ``(len(queries), keep)``, best first: the
    float32 brute force."""
    return _scan(_float32_scores, queries, items, keep, block_rows)


def exact_scores(queries: np.ndarray, items: np.ndarray,
                 idx: np.ndarray) -> np.ndarray:
    """float64 dot products of each query with its own rows ``idx``."""
    rows = items[idx].astype(np.float64)  # (S, J, k)
    return np.einsum("sk,sjk->sj", queries.astype(np.float64), rows)


def _quantized_scores(levels: int):
    def scores(qs, blk):
        import jax
        import jax.numpy as jnp

        amax = jnp.max(jnp.abs(blk), axis=1)
        scale = jnp.where(amax > 0, amax / levels, 1.0)
        q = jnp.clip(jnp.round(blk / scale[:, None]), -levels, levels)
        # the integers stand in float32: exact, and the products accumulate
        # in float32 as the configuration's precision states
        return jnp.matmul(qs, q.T, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32) * scale[None, :]

    return scores


def _to_bfloat16(a: np.ndarray) -> np.ndarray:
    """float32 rounded to the nearest bfloat16 (ties to even), as float32."""
    bits = np.ascontiguousarray(a, dtype=np.float32).view(np.uint32)
    rounded = bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))
    return (rounded & np.uint32(0xFFFF0000)).view(np.float32)


def two_stage(queries: np.ndarray, items: np.ndarray, keep: int, width: int,
              control: bool = False, block_rows: int = 1 << 19):
    """(values, indices), each ``(len(queries), keep)``, best first: the top
    ``width`` by quantized scores, rescored from the float32 rows."""
    width = min(width, len(items))
    _, cand = _scan(_quantized_scores(7 if control else 127), queries, items,
                    width, block_rows)
    rows = items[cand]  # (S, width, k) float32
    qs = np.asarray(queries, dtype=np.float32)
    if control:
        rows, qs = _to_bfloat16(rows), _to_bfloat16(qs)
    exact = np.einsum("sk,swk->sw", qs, rows).astype(np.float32)
    order = np.argsort(-exact, axis=1, kind="stable")[:, :keep]
    return (np.take_along_axis(exact, order, 1),
            np.take_along_axis(cand, order, 1))
