"""Locality-sensitive hashing for approximate top-N (sample-rate semantics).

Equivalent of the reference's LocalitySensitiveHash
(app/oryx-app-serving/.../als/model/LocalitySensitiveHash.java:41-177):
``oryx.als.sample-rate`` < 1 trades recall for speed by only scoring items
whose sign-bit hash (under near-orthogonal random hyperplanes) lies within
``max_bits_differing`` of the query's hash. Hash count and allowed bit
difference are chosen so the candidate-bucket fraction approximates the
sample rate.

TPU re-design: the reference scans candidate *partitions* with a thread pool.
Here, on one chip, the scoring copy of Y is held in bucket order
(:class:`BucketLayout`): each bucket's rows are contiguous and fill whole
windows of ``tile`` rows, so a flush reads only the windows of the buckets
its queries may be answered from (``BucketLayout.ball_windows`` counts
them), and masks every query to its own candidates
(serving.py:_LSHYSnapshot).
The mesh and int8 views still carry a bucket id a row and mask all of Y.
"""

from __future__ import annotations

import math

import numpy as np

from oryx_tpu.common import rand

MAX_HASHES = 16


def _candidate_fraction(n_hashes: int, max_bits_differing: int) -> float:
    total = sum(math.comb(n_hashes, d) for d in range(max_bits_differing + 1))
    return total / (1 << n_hashes)


def choose_hash_config(sample_rate: float) -> tuple[int, int]:
    """Smallest hash count + allowed differing bits whose candidate fraction
    is closest to (without exceeding much) the sample rate
    (LocalitySensitiveHash.java:41-74)."""
    if sample_rate >= 1.0:
        return 0, 0
    best = (1, 0)
    best_err = float("inf")
    for n in range(1, MAX_HASHES + 1):
        for d in range(n):
            frac = _candidate_fraction(n, d)
            if frac <= sample_rate:
                err = sample_rate - frac
                if err < best_err:
                    best_err = err
                    best = (n, d)
    return best


class LocalitySensitiveHash:
    def __init__(self, sample_rate: float, features: int):
        self.sample_rate = sample_rate
        self.features = features
        self.num_hashes, self.max_bits_differing = choose_hash_config(sample_rate)
        # LUT row cache allocated eagerly: get_candidate_lut runs on the
        # coalescer's executor threads concurrently, and lazy allocation
        # would race (one thread's fresh array clobbering another's fills).
        # Concurrent fills of the same row write identical values, and the
        # filled flag is set only AFTER its row, so readers are safe.
        self._popcounts: "np.ndarray | None" = None
        self._ball: "np.ndarray | None" = None
        if 0 < self.num_hashes and self.num_buckets <= 8192:
            self._lut_rows = np.zeros(
                (self.num_buckets, self.num_buckets), dtype=bool
            )
            self._lut_filled = np.zeros(self.num_buckets, dtype=bool)
        else:
            self._lut_rows = None
            self._lut_filled = None
        rng = rand.get_random()
        if self.num_hashes:
            # near-orthogonal random hyperplanes (:80-105)
            m = rng.standard_normal((self.num_hashes, features)).astype(np.float32)
            q, _ = np.linalg.qr(m.T) if features >= self.num_hashes else (m.T, None)
            self.hyperplanes = np.ascontiguousarray(q.T[: self.num_hashes], dtype=np.float32)
        else:
            self.hyperplanes = np.zeros((0, features), dtype=np.float32)

    @property
    def num_buckets(self) -> int:
        return 1 << self.num_hashes

    def get_index_for(self, vector: np.ndarray) -> int:
        """Sign-bit hash (:142)."""
        if not self.num_hashes:
            return 0
        bits = (self.hyperplanes @ np.asarray(vector, dtype=np.float32)) > 0
        idx = 0
        for b in bits:
            idx = (idx << 1) | int(b)
        return idx

    def assign_buckets(self, matrix: np.ndarray) -> np.ndarray:
        """Bucket id per row, vectorized."""
        if not self.num_hashes:
            return np.zeros(len(matrix), dtype=np.int32)
        bits = (matrix @ self.hyperplanes.T) > 0  # (n, h)
        weights = (1 << np.arange(self.num_hashes - 1, -1, -1)).astype(np.int32)
        return (bits.astype(np.int32) @ weights).astype(np.int32)

    def _popcount_table(self) -> np.ndarray:
        """popcount of every bucket id, built once per instance (idempotent
        under concurrent builds: identical values)."""
        if self._popcounts is None:
            v = np.arange(self.num_buckets, dtype=np.int32)
            pc = np.zeros(self.num_buckets, dtype=np.int32)
            while v.any():
                pc += v & 1
                v = v >> 1
            self._popcounts = pc
        return self._popcounts

    def ball(self) -> np.ndarray:
        """Every offset of at most ``max_bits_differing`` set bits: ``code ^
        ball`` is the candidate ball of ``code`` (2,380 buckets at 13 hashes
        and 5), built once (idempotent under concurrent builds)."""
        if self._ball is None:
            self._ball = np.flatnonzero(
                self._popcount_table() <= self.max_bits_differing
            ).astype(np.int32)
        return self._ball

    def get_candidate_indices(self, vector: np.ndarray) -> np.ndarray:
        """All bucket ids within max_bits_differing of the query hash (:156-177)."""
        if not self.num_hashes:
            return np.asarray([0], dtype=np.int32)
        base = self.get_index_for(vector)
        all_ids = np.arange(self.num_buckets, dtype=np.int32)
        pc = self._popcount_table()[all_ids ^ base]
        return all_ids[pc <= self.max_bits_differing]

    def get_candidate_lut(self, qs: np.ndarray) -> np.ndarray:
        """(B, num_buckets) bool candidate table for a BATCH of queries.

        A query's row depends only on its bucket id, so rows memoize in a
        dense (num_buckets, num_buckets) bool table filled lazily per
        distinct base bucket (≤ 64 MB at 8192 buckets; beyond that the
        direct vectorized xor/popcount computation is used) — steady-state
        builds are then one row gather instead of per-query bit loops."""
        qs = np.atleast_2d(np.asarray(qs, dtype=np.float32))
        if not self.num_hashes:
            return np.ones((len(qs), 1), dtype=bool)
        base = self.assign_buckets(qs)  # (B,)
        n = self.num_buckets
        all_ids = np.arange(n, dtype=np.int32)
        pc = self._popcount_table()
        if self._lut_rows is None:  # table would exceed ~64 MB: direct
            return pc[base[:, None] ^ all_ids[None, :]] <= self.max_bits_differing
        missing = np.unique(base[~self._lut_filled[base]])
        if missing.size:
            self._lut_rows[missing] = (
                pc[missing[:, None] ^ all_ids[None, :]]
                <= self.max_bits_differing
            )
            self._lut_filled[missing] = True
        return self._lut_rows[base]


#: The hash of a padding row of a bucket-ordered layout: every bit above a
#: hash's 16 is set, so it lies at least 16 bits from any query's hash and
#: inside no candidate ball.
PAD_CODE = -(1 << MAX_HASHES)

#: Windows of the widest layout tile, and the narrowest a bfloat16 block may
#: be (its sublane tile): a layout's windows hold the mean bucket, a power of
#: two between the two.
_TILE_MAX, _TILE_MIN = 128, 16


def layout_tile(n_rows: int, num_buckets: int) -> int:
    """Rows a window: the mean bucket's size as a power of two, clamped to
    ``[16, 128]`` — at 5M rows over 8,192 buckets 128 (a bucket of ~610
    rows fills five windows, ~10% of them padding); a small catalogue over
    as many buckets pads each to 16."""
    mean = max(1, -(-n_rows // max(1, num_buckets)))
    return int(min(_TILE_MAX, max(_TILE_MIN, 1 << (mean - 1).bit_length())))


class BucketLayout:
    """Where each row of Y lies in the bucket-ordered scoring copy.

    Rows are sorted by bucket; each bucket's rows fill ``ceil(count / tile)``
    windows of ``tile`` positions from its first, and the rest of its last
    window is padding. ``bucket_first[b]`` is bucket ``b``'s first window,
    ``bucket_count[b]`` how many it fills, ``win_valid`` the real rows at
    the front of each window; ``pos2row`` maps a position back to its row
    (-1 on padding) and ``row2pos`` a row to its position, ``pos_code``
    gives a position's bucket (``PAD_CODE`` on padding);
    ``ball_windows[c]`` is how many windows the ball of hash ``c`` fills —
    the buckets within ``max_bits_differing`` of it.

    The layout holds ``n_windows`` = ``ceil(n / tile) + num_buckets``
    windows whatever the rows' hashes — no bucket pads more than a window —
    so the shapes of every program over it follow from ``n`` alone and a
    compiled program serves every layout of as many rows; the windows past
    the buckets' are padding. Built on the host from the rows' bucket ids
    alone, by one stable sort; :meth:`moved` places a few rows again
    without it."""

    def __init__(self, lsh: "LocalitySensitiveHash", codes: np.ndarray):
        codes = np.asarray(codes, dtype=np.int32)
        nb = lsh.num_buckets
        self.lsh = lsh
        self.codes = codes
        self.tile = tile = layout_tile(len(codes), nb)
        counts = np.bincount(codes, minlength=nb)
        windows = -(-counts // tile)
        first = np.concatenate([[0], np.cumsum(windows)[:-1]])
        self.bucket_first = first.astype(np.int32)
        self.bucket_count = windows.astype(np.int32)
        self.n_windows = -(-len(codes) // tile) + nb
        self.n_positions = self.n_windows * tile
        order = np.argsort(codes, kind="stable")
        row_first = np.concatenate([[0], np.cumsum(counts)[:-1]])
        sorted_codes = codes[order]
        rank = np.arange(len(codes)) - row_first[sorted_codes]
        positions = first[sorted_codes] * tile + rank
        self.pos2row = np.full(self.n_positions, -1, dtype=np.int32)
        self.pos2row[positions] = order
        self.row2pos = np.empty(len(codes), dtype=np.int32)
        self.row2pos[order] = positions
        self.pos_code = np.full(self.n_positions, PAD_CODE, dtype=np.int32)
        self.pos_code[positions] = sorted_codes
        self.win_valid = np.zeros(self.n_windows, dtype=np.int32)
        used = int(windows.sum())
        within = np.arange(used) - np.repeat(first, windows)
        self.win_valid[:used] = np.minimum(
            tile, np.repeat(counts, windows) - within * tile)
        # the windows each hash's ball fills, for every hash: the most of
        # them is the list a query may bring
        ball = lsh.ball()
        self.ball_windows = np.concatenate([
            self.bucket_count[np.arange(c, min(nb, c + 1024))[:, None]
                              ^ ball[None, :]].sum(axis=1)
            for c in range(0, nb, 1024)])

    def moved(self, codes: np.ndarray, rows: np.ndarray):
        """The layout after ``rows`` (changed or appended) took their
        hashes in ``codes`` (every row's), and the positions whose row
        changed — or None where a row's new bucket has no free position
        left in its windows (lay out again).

        A row whose bucket did not change keeps its position. A row that
        left its bucket gives its position to the last real row of its
        window, so each window's real rows stay at its front, and takes
        the first free position of its new bucket's windows; an appended
        row takes one too. The buckets' windows, and so ``ball_windows``
        and every program's shape, do not change: the arrays that do are
        copies, and ``self`` stays as it was."""
        codes = np.asarray(codes, dtype=np.int32)
        rows = np.asarray(rows, dtype=np.int64)
        tile, n_old = self.tile, len(self.codes)
        out = object.__new__(BucketLayout)
        out.__dict__.update(self.__dict__)
        out.codes = codes
        pos2row, pos_code = self.pos2row.copy(), self.pos_code.copy()
        win_valid = self.win_valid.copy()
        row2pos = np.empty(len(codes), dtype=np.int32)
        row2pos[:n_old] = self.row2pos
        touched = [row2pos[rows[rows < n_old]]]
        for r in rows.tolist():
            c = int(codes[r])
            if r < n_old:
                p = int(row2pos[r])
                if pos_code[p] == c:
                    continue
                w = p // tile
                last = w * tile + int(win_valid[w]) - 1
                pos2row[p] = pos2row[last]
                row2pos[pos2row[p]] = p
                pos2row[last], pos_code[last] = -1, PAD_CODE
                win_valid[w] -= 1
                touched.append([last])
            first, count = int(self.bucket_first[c]), int(self.bucket_count[c])
            room = np.flatnonzero(win_valid[first:first + count] < tile)
            if not room.size:
                return None
            w = first + int(room[0])
            q = w * tile + int(win_valid[w])
            win_valid[w] += 1
            pos2row[q], pos_code[q], row2pos[r] = r, c, q
            touched.append([q])
        out.pos2row, out.pos_code, out.win_valid = pos2row, pos_code, win_valid
        out.row2pos = row2pos
        return out, np.unique(np.concatenate(touched)).astype(np.int32)

    def windows_of(self, query_codes: np.ndarray):
        """A flush's list: the union of its queries' balls — the buckets
        ``code ^ ball`` — in bucket order, expanded into their windows,
        each window once. Each entry is the window's index shifted left by
        the flush's width ``B``, with bit ``b`` below set where query
        ``b``'s ball holds the window's bucket. Returns ``(entries,
        buckets)``, ``buckets`` the union's count."""
        codes = np.asarray(query_codes, dtype=np.int32)
        width = len(codes)
        owners = np.zeros(self.lsh.num_buckets, dtype=np.int32)
        ball = self.lsh.ball()
        for b, code in enumerate(codes):
            owners[code ^ ball] |= np.int32(1 << b)
        union = np.flatnonzero(owners)
        counts = self.bucket_count[union]
        ends = np.cumsum(counts)
        first = ((self.bucket_first[union] - (ends - counts)) << width) \
            | owners[union]
        entries = np.repeat(first, counts)
        entries += np.arange(len(entries), dtype=entries.dtype) << width
        return entries.astype(np.int32), len(union)
