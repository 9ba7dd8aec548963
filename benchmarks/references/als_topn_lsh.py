"""Plain reference for ALS top-N under LSH (``sample-rate`` < 1): the exact
top ``keep`` by dot product among the items in each query's candidate
buckets, the buckets within ``radius`` bits of the query's sign hash.

The hyperplanes are the model's parameters, read once like its factors;
everything else is computed here, in blocks of rows, with no kernel and no
bucket layout. Imports nothing of the program.

The hash is as the program defines it: bit ``h`` (first hyperplane the
highest) is set where the float32 projection onto hyperplane ``h`` is above
zero. A float32 projection is not one number: its value depends on the
order of the sum, and an implementation may add in any order. Here the
projections are taken in float64, and a bit is AMBIGUOUS where the
projection lies within ``2 · √k · 2⁻²⁴ · Σ|vᵢ hᵢ|`` of zero: either sign is
then a float32 hash of the row. The band is twice the root-``k`` growth of
a float32 sum's rounding; the largest difference measured between float64
and a float32 projection (numpy's, and XLA's at the highest matmul
precision; 2.6M projections of N(0, 1) rows of 250 features onto unit
hyperplanes) was ``5.7 · 2⁻²⁴ · Σ|vᵢ hᵢ|``, under a fifth of it. At 250
features about one row in 5,000 has an ambiguous bit.
So between a query and a row ``d_min`` bits certainly differ and at most
``d_max`` may; a row is certainly a candidate where ``d_max <= radius``,
possibly one where ``d_min <= radius``, certainly not one where
``d_min > radius`` (what ``non_candidates`` counts). ``top_n`` ranks the
possible candidates: a program may count an ambiguous row in or out.

Scores are ``als_topn``'s: float32 at the highest matmul precision, and its
int8 control one precision below the bfloat16 scoring copy.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness.manifest import load_module

_plain = load_module("references", "als_topn")
exact_scores = _plain.exact_scores

_HASH_BLOCK = 1 << 16


def sign_codes(vectors: np.ndarray, hyperplanes: np.ndarray):
    """``(codes, ambiguous)`` int64 a row: the sign hash of each row, and a
    bit set wherever that bit of the hash is ambiguous. Blocks of rows on a
    few threads (numpy lets go of the interpreter inside each pass)."""
    import concurrent.futures
    import os

    hp = np.asarray(hyperplanes, dtype=np.float64)
    n_hash, k = hp.shape
    weights = (1 << np.arange(n_hash - 1, -1, -1)).astype(np.int64)
    codes = np.empty(len(vectors), dtype=np.int64)
    amb = np.empty(len(vectors), dtype=np.int64)
    gamma = 2.0 * np.sqrt(k) * 2.0 ** -24
    abs_hp = np.abs(hp).T.astype(np.float32)

    def block(start: int) -> None:
        part = vectors[start:start + _HASH_BLOCK]
        proj = part.astype(np.float64) @ hp.T
        # the band's width needs no float64: a relative error of 2^-23
        # moves it by nothing a float32 sum could cross
        bound = gamma * (np.abs(part) @ abs_hp)
        codes[start:start + len(part)] = (proj > 0).astype(np.int64) @ weights
        amb[start:start + len(part)] = (
            (np.abs(proj) <= bound).astype(np.int64) @ weights)

    workers = min(8, os.cpu_count() or 1)
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        list(pool.map(block, range(0, len(vectors), _HASH_BLOCK)))
    return codes, amb


def _popcount(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, dtype=np.int64)
    out = np.zeros(x.shape, dtype=np.int64)
    while x.any():
        out += x & 1
        x = x >> 1
    return out


def distance_bounds(q_code, q_amb, r_code, r_amb):
    """(d_min, d_max): the bits a query's and a row's hashes certainly
    differ in, and may."""
    differ, amb = q_code ^ r_code, q_amb | r_amb
    return _popcount(differ & ~amb), _popcount(differ | amb)


def non_candidates(queries: np.ndarray, answers: list, items: np.ndarray,
                   hyperplanes: np.ndarray, radius: int) -> int:
    """Answered rows that are certainly outside their query's candidate
    buckets: ``answers[s]`` holds query ``s``'s rows."""
    flat = np.asarray([r for a in answers for r in a], dtype=np.int64)
    if not len(flat):
        return 0
    owner = np.repeat(np.arange(len(answers)), [len(a) for a in answers])
    q_code, q_amb = sign_codes(queries, hyperplanes)
    r_code, r_amb = sign_codes(items[flat], hyperplanes)
    d_min, _ = distance_bounds(q_code[owner], q_amb[owner], r_code, r_amb)
    return int((d_min > radius).sum())


def top_n(queries: np.ndarray, items: np.ndarray, keep: int,
          hyperplanes: np.ndarray, radius: int, block_rows: int = 1 << 19,
          control: bool = False, item_codes=None):
    """(values, indices), each ``(len(queries), keep)``, best first, of the
    items that may lie in each query's candidate buckets; -inf past the
    last where a query has fewer. ``item_codes``: ``sign_codes(items)``,
    when the caller already has them."""
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("k",))
    def scan_block(qs, blk, qc, qa, rc, ra, k):
        scores = _plain._block_scores(qs, blk, control)
        differ = (qc[:, None] ^ rc[None, :]) & ~(qa[:, None] | ra[None, :])
        near = jax.lax.population_count(differ) <= radius
        return jax.lax.top_k(jnp.where(near, scores, -jnp.inf), k)

    r_code, r_amb = (sign_codes(items, hyperplanes) if item_codes is None
                     else item_codes)
    q_code, q_amb = sign_codes(queries, hyperplanes)
    qs = jnp.asarray(queries, dtype=jnp.float32)
    qc = jnp.asarray(q_code.astype(np.int32))
    qa = jnp.asarray(q_amb.astype(np.int32))
    vals, idxs = [], []
    for start in range(0, len(items), block_rows):
        blk = jnp.asarray(items[start:start + block_rows])
        stop = start + blk.shape[0]
        v, i = scan_block(qs, blk, qc, qa,
                          jnp.asarray(r_code[start:stop].astype(np.int32)),
                          jnp.asarray(r_amb[start:stop].astype(np.int32)),
                          min(keep, blk.shape[0]))
        vals.append(np.asarray(v))
        idxs.append(np.asarray(i) + start)
    v, i = np.concatenate(vals, axis=1), np.concatenate(idxs, axis=1)
    order = np.argsort(-v, axis=1, kind="stable")[:, :keep]
    return np.take_along_axis(v, order, 1), np.take_along_axis(i, order, 1)
