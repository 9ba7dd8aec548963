"""Plain reference for the default ``/recommend``: the float32 brute force of
``als_topn.py`` with each query's known rows taken out before the top list.

``known[s]`` holds the item rows query ``s`` must not be answered with, as
the generator made them (``harness/known_items.py``): never read from the
program. Their scores are set to -inf block by block; the rest is
``als_topn``'s scan, its control included. Imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness.manifest import load_module

_plain = load_module("references", "als_topn")
exact_scores = _plain.exact_scores


def _padded(known, n_queries: int) -> np.ndarray:
    """(S, E) int32 rows, -1 where a query has fewer than the longest."""
    width = max([len(k) for k in known] + [1])
    out = np.full((n_queries, width), -1, dtype=np.int32)
    for s, k in enumerate(known):
        out[s, :len(k)] = k
    return out


def top_n(queries: np.ndarray, items: np.ndarray, keep: int, known,
          block_rows: int = 1 << 19, control: bool = False):
    """(values, indices), each ``(len(queries), keep)``, best first, of the
    items that are not among a query's ``known`` rows."""
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("k",))
    def scan_block(qs, blk, local, k):
        scores = _plain._block_scores(qs, blk, control)
        rows = blk.shape[0]
        # rows of other blocks and the padding fall on the drop index
        local = jnp.where((local >= 0) & (local < rows), local, rows)
        scores = jax.vmap(
            lambda row, ix: row.at[ix].set(-jnp.inf, mode="drop"))(scores, local)
        return jax.lax.top_k(scores, k)

    qs = jnp.asarray(queries, dtype=jnp.float32)
    rows_out = _padded(known, len(queries))
    vals, idxs = [], []
    for start in range(0, len(items), block_rows):
        blk = jnp.asarray(items[start:start + block_rows])
        local = np.where(rows_out >= 0, rows_out - start, -1).astype(np.int32)
        v, i = scan_block(qs, blk, jnp.asarray(local),
                          min(keep, blk.shape[0]))
        vals.append(np.asarray(v))
        idxs.append(np.asarray(i) + start)
    v, i = np.concatenate(vals, axis=1), np.concatenate(idxs, axis=1)
    order = np.argsort(-v, axis=1, kind="stable")[:, :keep]
    return np.take_along_axis(v, order, 1), np.take_along_axis(i, order, 1)
