"""TPU-native ALS training kernel — slot-padded block normal equations.

Replaces Spark MLlib's distributed ALS (behind ALSUpdate.buildModel,
app/oryx-app-mllib/.../als/ALSUpdate.java:108-179) with a jit'd JAX program
designed for the MXU, with *memory-bounded* block solves — the same property
that lets MLlib's block-partitioned ALS (ALSUpdate.java:141-152) train
2M–21M-row models without materializing every per-row Gramian at once:

  * implicit feedback à la Hu/Koren/Volinsky as in MLlib: confidence
    c = 1 + α·|r|, preference p = 1 if r > 0 else 0; explicit = ALS-WR with
    λ·n_u regularization scaling;
  * interactions are sorted by row host-side and packed into fixed-width
    **slots** of T entries each: a row with d interactions occupies
    ceil(d/T) slots (Gramians are additive, so a hot row simply spans more
    slots — no global padding blow-up from skew). Slots are grouped into
    **row blocks** of B rows, padded to one uniform slot count S per block
    (XLA: one trace, static shapes);
  * one block solve = scan the block's slots in fixed-size chunks, gather
    the opposite factors (Sc, T, k), and form per-slot Gramians with ONE
    batched matmul — einsum('st,sti,stj->sij') → (Sc, k, k) — which is the
    MXU-shaped formulation (contraction over the slot width T). Slots then
    merge into per-row Gramians via a short sorted segment-sum over at most
    Sc indices (k²-granularity scatter traffic is slots·k², ~mean-degree×
    less than the naive nnz·k² outer-product scatter). Peak memory stays
    O(B·k² + Sc·T·k); a single batched Cholesky (cho_factor/cho_solve over
    (B, k, k)) replaces MLlib's per-block LAPACK calls;
  * under a mesh the **block axis shards over devices** via shard_map: each
    device lax.map's its local blocks with the opposite-side factors
    replicated, and the half-iteration's output factors come back
    row-partitioned (out_specs pins the sharding — XLA inserts the
    all-gather when the next half-iteration needs them replicated). This is
    the classic alternating block layout of distributed ALS.

Interactions must arrive sorted by row (data.build_rating_batch guarantees
it); both row-sorted and column-sorted slotted copies are built once and
reused across iterations.
"""

from __future__ import annotations

import functools
import logging
import math
import os
import typing
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from oryx_tpu.common import metrics as metrics_mod
from oryx_tpu.common import profiling
from oryx_tpu.models.als.data import RatingBatch
from oryx_tpu.ops import pallas_kernels as pk

# XLA:TPU stages a gather of a multiple of 1,024 rows through HALF the scoped
# buffer it gives any other count (compiled for a described v5e: 196,608 B
# against 524,288 for rows of 64 columns out of a large table, 131,072 against
# 262,144 out of a small one; PERF.md §6, PR 31), and the rows then arrive at
# half the rate once the table is past 128 MiB of lane-padded rows: the
# Netflix cell's item half read 1.39 s with chunks of 650, 652 or 654 slots
# of T = 512 and 0.77 s with 651, 653 or 655 — by the seed's pack, a coin
# toss; its user half 1.365 s against 1.28. A chunk's gather is kept off the
# multiples (_solve_block).
_GATHER_HALVED_ROWS = 1024

# Budgets (in f32 elements) bounding the two big transients: the per-block
# Gramian carry (B+1, k, k) and the per-chunk gather/Gramian buffers
# (Sc, T, k) + (Sc, k, k).
_BLOCK_ELEM_BUDGET = 1 << 26  # 256 MB carry
_CHUNK_ELEM_BUDGET = 1 << 24  # 64 MB transient


def _auto_block(features: int) -> int:
    return max(512, min(8192, _BLOCK_ELEM_BUDGET // (features * features)))


def _auto_slot_chunk(features: int, slot_width: int) -> int:
    per_slot = max(slot_width * features, features * features)
    return max(64, min(8192, _CHUNK_ELEM_BUDGET // per_slot))


def _auto_slot_width(nnz: int, n_nonempty_rows: int) -> int:
    """Slot width T ≈ mean row degree, as a power of two in [8, 512]."""
    mean = nnz / max(1, n_nonempty_rows)
    t = 1 << max(0, math.ceil(math.log2(max(1.0, mean))))
    return max(8, min(512, t))


@dataclass
class _BlockedSide:
    """Device-ready slotted COO for one half-iteration.

    ``srows`` holds block-LOCAL row indices in [0, block]; ``block`` is the
    spill row (slot padding), length-zeroed in the solve. Each block's slots
    are the contiguous row-sorted run of the global slot list that falls in
    its row range, right-padded to the uniform count S (a multiple of the
    scan chunk).
    """

    srows: jnp.ndarray  # (n_blocks, S) int32, pad = block
    scols: jnp.ndarray  # (n_blocks, S, T) int32
    svals: jnp.ndarray  # (n_blocks, S, T) float32
    slens: jnp.ndarray  # (n_blocks, S) int32 valid entries per slot (0 = pad)
    n_rows: int
    block: int
    n_blocks: int
    slot_width: int
    slot_chunk: int
    # host masters (srows, scols, svals, slens as numpy), kept only when a
    # BlockedLayoutCache owns the side so the next generation can repack an
    # incremental delta instead of the whole batch. Never mutated in place:
    # the delta path copies before writing (jnp.asarray may alias on CPU).
    np_slabs: "tuple | None" = None
    # what the pack counted: the interactions it placed, and the slots that
    # hold them (every other slot of the (n_blocks, S) grid is block padding)
    entries: int = 0
    real_slots: int = 0
    # ... and those of them whose rows the fused kernel fetches under the
    # slot before's matmuls: all but the first of each block's call, which
    # is the pipeline's prologue
    prefetched_slots: int = 0

    @property
    def padded_rows(self) -> int:
        return self.n_blocks * self.block

    def gather_rows(self, fused: bool) -> int:
        """Factor rows one half-iteration's gather moves. The fused kernel
        copies each slot to its own length — one row an entry; the einsum
        formulation gathers every cell of every slot, pad slots included."""
        return self.entries if fused else int(self.scols.size)

    def gather_rows_per_entry(self, fused: bool) -> "tuple[float, float]":
        """(copying every real slot to its width T, as the kernel did before
        it was handed the slots' lengths; as issued now) over the entries:
        the share of the gather that copying to length removes is
        1 − now ÷ before."""
        n = max(1, self.entries)
        return (self.real_slots * self.slot_width / n,
                self.gather_rows(fused) / n)


def _pack_workers(workers: "int | None", nnz: int) -> int:
    """Worker count for the host-side pack scatters: explicit wins; small
    packs stay serial (thread fan-out costs more than it saves below ~2M
    entries); big packs use up to 8 host cores."""
    if workers is not None:
        return max(1, workers)
    if nnz < 2_000_000:
        return 1
    return max(1, min(8, os.cpu_count() or 1))


def _chunked_scatter(fn, n: int, workers: int, chunk: int = 1_000_000) -> None:
    """Run ``fn(lo, hi)`` over [0, n) — serially, or chunked across a thread
    pool. Callers guarantee every (lo, hi) slice writes DISJOINT output
    cells, so chunk boundaries need no coordination; numpy's fancy-index
    assignment releases the GIL for flat dtypes, which is what makes the
    threads actually overlap."""
    if workers <= 1 or n <= chunk:
        fn(0, n)
        return
    import concurrent.futures as cf

    step = max(chunk, -(-n // (workers * 4)))  # ~4 chunks per worker
    with cf.ThreadPoolExecutor(workers) as pool:
        futs = [
            pool.submit(fn, lo, min(n, lo + step)) for lo in range(0, n, step)
        ]
        for f in futs:
            f.result()


def _padded_rows_for(n_rows: int, block: int, n_block_multiple: int = 1) -> int:
    """Rows after block padding — EXACTLY make_blocked_side's computation,
    callable before (or without) the pack so the first factor buffer can be
    allocated while the side is still packing on the host pool."""
    n_blocks = max(1, -(-n_rows // block))
    n_blocks = -(-n_blocks // n_block_multiple) * n_block_multiple
    return n_blocks * block


def _layout_params(deg: np.ndarray, nnz: int, slot_chunk: "int | None",
                   slot_width: "int | None", block: int,
                   features: "int | None") -> tuple:
    """Slot-layout shape parameters from a degree histogram: the pure
    function both the full pack and the incremental delta derive their
    geometry from (so a delta repack can detect any drift and the two paths
    can never disagree on shapes)."""
    if slot_width is None:
        slot_width = _auto_slot_width(nnz, int(np.count_nonzero(deg)))
    t = slot_width
    budget_max = _auto_slot_chunk(features or 32, t)
    slot_chunk = budget_max if slot_chunk is None else max(
        16, min(slot_chunk, budget_max)
    )
    nslots_row = -(-deg // t)  # ceil; 0 slots for empty rows
    padded_rows = len(deg)
    row_slot_start = np.zeros(padded_rows + 1, dtype=np.int64)
    np.cumsum(nslots_row, out=row_slot_start[1:])
    total_slots = int(row_slot_start[-1])
    bounds = row_slot_start[::block]  # (n_blocks + 1,)
    max_s = int(np.diff(bounds).max()) if total_slots else 0
    n_chunks = max(1, -(-max(max_s, 1) // slot_chunk))
    slot_chunk = max(16, -(-max(max_s, 1) // n_chunks))
    s_len = n_chunks * slot_chunk
    return t, slot_chunk, s_len, nslots_row, row_slot_start, bounds, total_slots


def make_blocked_side(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    block: int,
    slot_chunk: int | None,
    slot_width: int | None,
    n_block_multiple: int = 1,
    features: int | None = None,
    workers: int | None = None,
    keep_np: bool = False,
) -> _BlockedSide:
    """Host-side slotted-COO construction (row-sorted → contiguous slots).

    ``slot_width=None`` picks T from the side's mean row degree (one degree
    histogram, reused for the slot layout); ``slot_chunk=None`` then sizes
    the scan chunk from T and ``features`` to stay inside the transient
    budget. Entries scatter STRAIGHT into the preallocated (n_blocks, S, T)
    output slabs — no intermediate flat slot arrays — and the scatter is
    chunked over a thread pool (``workers``; every entry owns a distinct
    cell, so chunks are embarrassingly parallel)."""
    # sort by (row, col): row-major for contiguous slots, column-ascending
    # within each row so the per-slot gathers of the opposite factors walk
    # HBM in address order instead of randomly. One stable argsort on a
    # fused int64 key is ~2x numpy's lexsort at 10M nnz (radix path), and
    # int64 cannot overflow at any plausible row/col cardinality
    if len(rows):
        span = np.int64(cols.max()) + 1
        key = rows.astype(np.int64) * span + cols
        order = np.argsort(key, kind="stable")
    else:
        order = np.arange(0)
    r = rows[order].astype(np.int64)
    c = cols[order].astype(np.int32)
    v = vals[order].astype(np.float32)
    padded_rows = _padded_rows_for(n_rows, block, n_block_multiple)
    n_blocks = padded_rows // block
    n_workers = _pack_workers(workers, len(r))

    deg = np.bincount(r, minlength=padded_rows) if len(r) else np.zeros(
        padded_rows, dtype=np.int64
    )
    # explicit slot_chunk values are still clamped into the transient
    # budget (a chunk tuned in nnz terms must not OOM the device), and the
    # chunk is sized to divide S exactly: sequential chunk steps are the
    # TPU's enemy, and a budget-sized chunk that doesn't divide S would pad
    # S up to a multiple. Slots are row-ordered, so block b's slots are
    # exactly the run row_slot_start[b*block : (b+1)*block] — per-block
    # extents come straight off the cumsum, no searchsorted.
    (t, slot_chunk, s_len, nslots_row, row_slot_start, bounds,
     total_slots) = _layout_params(deg, len(r), slot_chunk, slot_width,
                                   block, features)
    row_entry_start = np.zeros(padded_rows + 1, dtype=np.int64)
    np.cumsum(deg, out=row_entry_start[1:])

    # Slot packing bounds skew damage (a hot row just spans more slots), but
    # uneven *block* slot counts still pad every block to the fullest one;
    # surface a pathological ratio rather than hiding it.
    if len(r) and n_blocks > 1:
        pad_ratio = s_len * t * n_blocks / max(1, len(r))
        if pad_ratio > 6.0:
            logging.getLogger(__name__).warning(
                "slotted COO padding ratio %.1fx (T=%d, S=%d x %d blocks vs "
                "%d nnz): row-skewed data; consider a smaller block size",
                pad_ratio, t, s_len, n_blocks, len(r),
            )

    srows = np.full((n_blocks, s_len), block, dtype=np.int32)
    scols = np.zeros((n_blocks, s_len, t), dtype=np.int32)
    svals = np.zeros((n_blocks, s_len, t), dtype=np.float32)
    slens = np.zeros((n_blocks, s_len), dtype=np.int32)
    if total_slots:
        # per-slot coordinates: owning row, block, and index within block
        srow_f = np.repeat(np.arange(padded_rows, dtype=np.int64), nslots_row)
        sb = (srow_f // block).astype(np.int32)
        sidx = (np.arange(total_slots, dtype=np.int64) - bounds[sb]).astype(np.int32)
        # valid entries per slot straight from the degree histogram: a row's
        # slots carry T, T, ..., remainder — no per-entry bincount needed
        slot_in_row = np.arange(total_slots, dtype=np.int64) - row_slot_start[srow_f]
        srows[sb, sidx] = (srow_f % block).astype(np.int32)
        slens[sb, sidx] = np.minimum(
            deg[srow_f] - slot_in_row * t, t
        ).astype(np.int32)
        del slot_in_row
        if len(r):
            # per-entry final coordinates — each entry owns one distinct
            # (block, slot, pos) cell in the preallocated slabs, so the
            # scatter chunks cleanly across the worker pool. Index dtypes
            # are downcast and intermediates freed eagerly: at 10M nnz the
            # int64 versions alone would add hundreds of MB of transient,
            # and the reference-scale memory bound (test_als_scale) holds
            # the whole train under a hard rlimit
            p = np.arange(len(r), dtype=np.int64) - row_entry_start[r]
            slot = row_slot_start[r] + p // t
            pos = (p % t).astype(np.int32)
            del p
            eb = (r // block).astype(np.int32)
            es = (slot - bounds[eb]).astype(np.int32)
            del slot

            def scatter(lo: int, hi: int) -> None:
                scols[eb[lo:hi], es[lo:hi], pos[lo:hi]] = c[lo:hi]
                svals[eb[lo:hi], es[lo:hi], pos[lo:hi]] = v[lo:hi]

            _chunked_scatter(scatter, len(r), n_workers)
            del eb, es, pos
    return _BlockedSide(
        jnp.asarray(srows), jnp.asarray(scols), jnp.asarray(svals),
        jnp.asarray(slens), n_rows, block, n_blocks, t, slot_chunk,
        np_slabs=(srows, scols, svals, slens) if keep_np else None,
        entries=len(r), real_slots=total_slots,
        prefetched_slots=_prefetched_slots(slens),
    )


def _prefetched_slots(slens: np.ndarray) -> int:
    """Non-empty slots less the first non-empty slot of each block: the
    fused kernel starts a slot's row copies one grid step early, so every
    slot but the first of a call gathers under its predecessor's matmuls."""
    full = slens > 0
    return int(full.sum()) - int(full.any(axis=1).sum())


def _entry_weights(svals, slens, alpha, implicit, t):
    """Per-entry Gramian weight ``w`` and RHS coefficient ``coef`` (both
    masked to the slot's valid length): the confidence algebra of
    Hu/Koren/Volinsky implicit feedback, or plain masking for explicit.
    Shared by the einsum formulation and the fused Pallas kernel so the two
    paths can only ever differ in accumulation order."""
    m = (jnp.arange(t)[None, :] < slens[..., None]).astype(jnp.float32)
    if implicit:
        w = alpha * jnp.abs(svals) * m  # confidence - 1
        coef = (1.0 + w) * (svals > 0).astype(jnp.float32) * m
    else:
        w = m
        coef = svals * m
    return w, coef


def _delta_blocked_side(
    old: _BlockedSide,
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    block: int,
    slot_chunk: "int | None",
    slot_width: "int | None",
    n_block_multiple: int,
    features: "int | None",
    appended_rows: np.ndarray,
) -> "_BlockedSide | None":
    """Incremental repack: ``rows/cols/vals`` extend the cached side's
    batch by entries touching ``appended_rows`` (wherever they sit in the
    arrays — mid-array for the production row-sorted pipeline, the tail
    for a raw concatenation). Only the BLOCKS those rows live in re-sort
    and re-scatter; every other block's slabs copy through unchanged
    (their within-block slot layout depends only on their own rows'
    degrees). Returns None when the layout geometry drifted — block count,
    slot width, chunk, or a shrunk S — and a full pack is required. The
    result is bit-identical to a from-scratch pack of the full batch: the
    global sort is stable on the (row, col) key, and an affected block's
    entries keep their original relative order whether sorted globally or
    alone."""
    if old.np_slabs is None:
        return None
    padded_rows = _padded_rows_for(n_rows, block, n_block_multiple)
    n_blocks = padded_rows // block
    if n_blocks != old.n_blocks or block != old.block:
        return None
    deg = np.bincount(rows.astype(np.int64), minlength=padded_rows)
    (t, chunk, s_len, nslots_row, row_slot_start, bounds,
     total_slots) = _layout_params(deg, len(rows), slot_chunk, slot_width,
                                   block, features)
    old_s = old.np_slabs[0].shape[1]
    if t != old.slot_width or s_len < old_s:
        return None

    affected = np.unique(appended_rows // block).astype(np.int64)
    o_srows, o_scols, o_svals, o_slens = old.np_slabs
    pad_s = s_len - old_s
    if pad_s:
        # S grew: right-pad every block with empty slots — exactly the fill
        # a full pack leaves there (owner = spill row, zeros elsewhere)
        srows = np.full((n_blocks, s_len), block, dtype=np.int32)
        srows[:, :old_s] = o_srows
        scols = np.zeros((n_blocks, s_len, t), dtype=np.int32)
        scols[:, :old_s] = o_scols
        svals = np.zeros((n_blocks, s_len, t), dtype=np.float32)
        svals[:, :old_s] = o_svals
        slens = np.zeros((n_blocks, s_len), dtype=np.int32)
        slens[:, :old_s] = o_slens
    else:
        srows, scols = o_srows.copy(), o_scols.copy()
        svals, slens = o_svals.copy(), o_slens.copy()

    # re-derive the affected blocks from scratch: all of their entries (old
    # + appended) re-sort and re-scatter — the stable (row, col) sort of a
    # block's own entries is independent of every other block's
    srows[affected] = block
    scols[affected] = 0
    svals[affected] = 0
    slens[affected] = 0
    sel = np.flatnonzero(np.isin(rows // block, affected))
    if len(sel):
        r_all, c_all, v_all = rows[sel], cols[sel], vals[sel]
        span = np.int64(c_all.max()) + 1
        order = np.argsort(r_all.astype(np.int64) * span + c_all,
                           kind="stable")
        rr = r_all[order].astype(np.int64)
        cc = c_all[order].astype(np.int32)
        vv = v_all[order].astype(np.float32)
        # rank of each entry within its (col-sorted) row group: sel holds
        # every entry of each affected block, so group ranks equal the full
        # pack's per-row entry positions
        p = _slot_rank(rr)
        slot = row_slot_start[rr] + p // t
        pos = (p % t).astype(np.int32)
        eb = (rr // block).astype(np.int32)
        es = (slot - bounds[eb]).astype(np.int32)
        scols[eb, es, pos] = cc
        svals[eb, es, pos] = vv
        # per-slot owner rows + valid lengths for the affected rows
        arows = np.unique(rr)
        srow_f = np.repeat(arows, nslots_row[arows])
        sb = (srow_f // block).astype(np.int32)
        slot_in_row = _slot_rank(srow_f)
        sidx = (row_slot_start[srow_f]
                + slot_in_row - bounds[sb]).astype(np.int32)
        srows[sb, sidx] = (srow_f % block).astype(np.int32)
        slens[sb, sidx] = np.minimum(
            deg[srow_f] - slot_in_row * t, t
        ).astype(np.int32)
    return _BlockedSide(
        jnp.asarray(srows), jnp.asarray(scols), jnp.asarray(svals),
        jnp.asarray(slens), n_rows, block, n_blocks, t, chunk,
        np_slabs=(srows, scols, svals, slens),
        entries=len(rows), real_slots=total_slots,
        prefetched_slots=_prefetched_slots(slens),
    )


def _slot_rank(srow_f: np.ndarray) -> np.ndarray:
    """Rank of each element within its contiguous run of equal values
    (0, 1, ... per run) — per-row slot ranks when fed owner-rows-per-slot,
    per-row entry ranks when fed row-sorted entry rows."""
    grp = np.flatnonzero(np.r_[True, srow_f[1:] != srow_f[:-1]])
    return np.arange(len(srow_f), dtype=np.int64) - np.repeat(
        grp, np.diff(np.r_[grp, len(srow_f)])
    )


class BlockedLayoutCache:
    """Slotted-layout reuse across model generations (one per trainer).

    Successive batch-tier generations mostly extend the previous batch:
    the 58 s host pack at 1M×50f re-sorts and re-scatters entries whose
    layout has not moved. This cache keys on the previous generation's COO
    arrays per side and picks the cheapest correct path:

      * ``reused`` — arrays identical: hand back the SAME device-ready side
        (zero host work, zero re-upload);
      * ``delta`` — the new arrays extend the old (exact prefix, OR the
        production shape: row-sorted with each row's old entries a prefix
        of its new ones — what ``build_rating_batch``'s stable row sort
        over the insertion-ordered aggregation dict emits) AND the layout
        geometry held: only the blocks the appended entries touch re-sort
        and re-scatter (:func:`_delta_blocked_side`);
      * ``full`` — anything else (changed historical values — new events
        aggregated into an existing pair, or time decay rewriting
        strengths — a new id sorting mid-order and renumbering an axis
        (``IDIndexMapping`` sorts ids, so monotonic id schemes keep the
        mapping stable and delta-friendly), different geometry, shrunk
        batch): full pack.

    Results are bit-identical to a from-scratch pack in every mode (the
    delta path's per-block stable sort reproduces the global one), which
    ``tests/test_gramian_kernel.py`` pins. Cost: between generations the
    cache retains the previous COO triple and host slab copies (~nnz·9 B
    plus ~2·nnz·8 B/fill) AND pins the cached ``_BlockedSide``'s DEVICE
    slabs — several hundred MB of HBM at 10M nnz, transiently ~2× during
    a delta while old and new device slabs coexist. That device residency
    is what makes ``reused`` a zero-re-upload path; size HBM headroom for
    it, and drop the cache object to reclaim everything. Not thread-safe;
    the batch tier packs one generation at a time."""

    def __init__(self):
        self._arrays: "tuple | None" = None  # canonical (rows, cols, vals)
        self._sides: dict = {}  # name -> (side, params)
        self.last_modes: dict = {}

    def match_extension(self, rows, cols, vals) -> "np.ndarray | None":
        """Indices (into the new arrays) of the entries APPENDED since the
        cached generation, or None when the new batch does not extend it.

        Two shapes match. (1) Exact prefix — the new arrays literally start
        with the old ones (how a raw log append looks). (2) Row-wise
        extension — both generations row-sorted with each row's old entries
        forming a prefix of its new entries, which is exactly what the
        production pipeline produces: ``build_rating_batch`` stable-sorts
        by row, and the aggregation dict keeps first-seen (user, item)
        pairs ahead of newly seen ones within every row. A pair whose
        VALUE changed (new events aggregated in, or time decay rewriting
        history) fails the compare and falls back to a full pack.

        One check against the CANONICAL batch triple covers both sides —
        the item side's swapped (cols, rows, vals) view extends iff the
        batch does (membership is per-entry, not per-ordering)."""
        if self._arrays is None:
            return None
        o_r, o_c, o_v = self._arrays
        n_old = len(o_r)
        if len(rows) < n_old:
            return None
        if (np.array_equal(o_r, rows[:n_old])
                and np.array_equal(o_c, cols[:n_old])
                and np.array_equal(o_v, vals[:n_old])):
            return np.arange(n_old, len(rows), dtype=np.int64)
        if n_old == 0 or np.any(np.diff(rows) < 0) or np.any(np.diff(o_r) < 0):
            return None
        nr = int(max(rows[-1], o_r[-1])) + 1
        deg_new = np.bincount(rows, minlength=nr)
        deg_old = np.bincount(o_r, minlength=nr)
        if np.any(deg_old > deg_new):
            return None
        new_start = np.zeros(nr + 1, dtype=np.int64)
        np.cumsum(deg_new, out=new_start[1:])
        old_start = np.zeros(nr + 1, dtype=np.int64)
        np.cumsum(deg_old, out=old_start[1:])
        # position of each old entry inside the new arrays: its row's new
        # segment start plus its rank within the row (rows agree by
        # construction once the degree test passed)
        idx = new_start[o_r] + (np.arange(n_old, dtype=np.int64)
                                - old_start[o_r])
        if not (np.array_equal(cols[idx], o_c)
                and np.array_equal(vals[idx], o_v)):
            return None
        appended = np.ones(len(rows), dtype=bool)
        appended[idx] = False
        return np.flatnonzero(appended)

    def side(self, name: str, rows, cols, vals, n_rows, block, slot_chunk,
             slot_width, n_block_multiple=1, features=None, workers=None,
             appended_idx: "np.ndarray | None" = None) -> _BlockedSide:
        """Pack one side, reusing the cached layout when ``appended_idx``
        (from :meth:`match_extension`) says the arrays extend the cached
        batch. ``rows`` is THIS side's row view, so ``rows[appended_idx]``
        are the rows the appended entries touch on this side."""
        params = (block, slot_chunk, slot_width, n_block_multiple, features)
        cached = self._sides.get(name)
        old, old_params = cached if cached is not None else (None, None)
        if old is not None and old_params == params \
                and appended_idx is not None:
            if appended_idx.size == 0 and old.n_rows == n_rows:
                self.last_modes[name] = "reused"
                return old
            side = _delta_blocked_side(
                old, rows, cols, vals, n_rows, block, slot_chunk,
                slot_width, n_block_multiple, features,
                rows[appended_idx],
            )
            if side is not None:
                self.last_modes[name] = "delta"
                self._sides[name] = (side, params)
                return side
        side = make_blocked_side(
            rows, cols, vals, n_rows, block, slot_chunk, slot_width,
            n_block_multiple, features=features, workers=workers,
            keep_np=True,
        )
        self.last_modes[name] = "full"
        self._sides[name] = (side, params)
        return side

    def store_batch(self, rows, cols, vals) -> None:
        """Pin the generation's canonical arrays AFTER both sides packed
        (the two sides share one COO, so the prefix test must see one
        snapshot). COPIES, not references: a caller that mutates its batch
        arrays in place (time decay rewriting ``vals``) and trains again
        would otherwise have ``match_extension`` compare the cached triple
        against itself and silently reuse pre-mutation slabs."""
        self._arrays = (rows.copy(), cols.copy(), vals.copy())


def _solve_block(y, srow, scols, svals, slens, *, block, features, lam, alpha,
                 implicit, slot_chunk, yty, compute_dtype=jnp.float32,
                 spd_kernel=False, fused_gramian=False, kernel_interpret):
    """Solve one row block's factors against fixed column factors ``y``.

    srow: (S,) block-local int32 in [0, block] (block = spill/padding);
    scols/svals: (S, T); returns (block, k). Peak memory
    O(block·k² + slot_chunk·T·k). ``y`` may arrive pre-cast to
    ``compute_dtype`` (bfloat16 = MXU-native inputs, and for the einsum
    formulation half the gather bandwidth; the fused kernel always gathers
    32-bit rows); Gramian/RHS accumulation stays float32 via
    preferred_element_type, and the Cholesky solve is always float32.

    ``fused_gramian`` routes the whole accumulation through the Pallas
    gather-Gramian kernel: factor rows gather tile-by-tile into VMEM and
    contract in place, accumulating straight into the per-row output —
    skipping both the (Sc, T, k) HBM gather materialization and the
    segment-sum pass below. ``kernel_interpret`` carries the CALLER's
    device-platform decision into every Pallas kernel here (compiled on
    TPU, emulated elsewhere — one flag, so one kernel can never run
    compiled while the other is silently interpreted).

    The einsum formulation gathers rows as wide as ``y`` is: where ``y``
    arrives zero-padded past ``features`` columns (the gate's padded gather,
    :func:`_rule`), the per-slot Gramians contract at that width and only
    their leading ``features`` are kept, before the segment-sums — the padded
    columns are zeros, so what is kept is what the unpadded contraction
    computes, and everything from the accumulators on is at ``features``.
    """
    k = features
    t = scols.shape[-1]

    if fused_gramian:
        w, coef = _entry_weights(svals, slens, alpha, implicit, t)
        big_a, big_b = pk.gather_gramian_accumulate(
            y, srow, slens, scols, w, coef, block=block,
            interpret=kernel_interpret,
        )
        # interaction counts are k²-free — a plain (S,) segment-sum costs
        # nothing next to the Gramians and keeps the kernel surface small
        cnt = jax.ops.segment_sum(
            slens.astype(jnp.float32), srow, num_segments=block + 1,
            indices_are_sorted=True,
        )
    else:
        n_chunks = srow.shape[0] // slot_chunk
        # one EMPTY slot more a chunk where its gather would otherwise fetch
        # a multiple of _GATHER_HALVED_ROWS rows (T < that, so one is enough)
        spare = slot_chunk * t % _GATHER_HALVED_ROWS == 0

        def body(carry, i):
            big_a, big_b, cnt = carry

            def sl(a, fill=0):
                part = jax.lax.dynamic_slice_in_dim(
                    a, i * slot_chunk, slot_chunk
                )
                if not spare:
                    return part
                return jnp.pad(part, ((0, 1),) + ((0, 0),) * (a.ndim - 1),
                               constant_values=fill)

            # the spare slot: no entries (every weight 0), owner = the spill
            rs, ls = sl(srow, block), sl(slens)
            cs, vs = sl(scols), sl(svals)
            w, coef = _entry_weights(vs, ls, alpha, implicit, t)
            # (Sc, T, y's width) gather of the replicated opposite side
            yg = y[cs]
            # per-slot Gramian: ONE batched MXU matmul, contraction over T
            ga = jnp.einsum(
                "st,sti,stj->sij", w.astype(compute_dtype), yg, yg,
                preferred_element_type=jnp.float32,
            )[:, :k, :k]  # (Sc, k, k)
            gb = jnp.einsum(
                "st,sti->si", coef.astype(compute_dtype), yg,
                preferred_element_type=jnp.float32,
            )[:, :k]  # (Sc, k)
            seg = functools.partial(
                jax.ops.segment_sum, num_segments=block + 1,
                indices_are_sorted=True,
            )
            big_a = big_a + seg(ga, rs)
            big_b = big_b + seg(gb, rs)
            cnt = cnt + seg(ls.astype(jnp.float32), rs)
            return (big_a, big_b, cnt), None

        init = (
            jnp.zeros((block + 1, k, k), dtype=jnp.float32),
            jnp.zeros((block + 1, k), dtype=jnp.float32),
            jnp.zeros((block + 1,), dtype=jnp.float32),
        )
        # the chunk count is small by construction (fewest chunks within the
        # transient budget); fully unrolling short scans drops the while-loop
        # carry double-buffering of the (block+1, k, k) Gramian accumulator
        (big_a, big_b, cnt), _ = jax.lax.scan(
            body, init, jnp.arange(n_chunks), unroll=min(n_chunks, 4)
        )
    big_a, big_b, cnt = big_a[:block], big_b[:block], cnt[:block]

    eye = jnp.eye(k, dtype=jnp.float32)
    # ALS-WR regularization scaling by interaction count (MLlib semantics)
    reg = lam * jnp.maximum(cnt, 1.0)
    if implicit:
        big_a = big_a + yty[None, :, :]
    big_a = big_a + reg[:, None, None] * eye[None, :, :]

    big_a = big_a + 1e-6 * eye[None]
    if spd_kernel:
        # Pallas Gauss-Jordan: k elimination steps against VMEM instead of
        # XLA cholesky's ~3k full-operand HBM passes (see pallas_kernels)
        x = pk.spd_solve_batched(big_a, big_b, interpret=kernel_interpret)
    else:
        chol = jax.scipy.linalg.cholesky(big_a, lower=True)
        x = jax.scipy.linalg.cho_solve((chol, True), big_b[..., None])[..., 0]
    # rows with no interactions have no factor (reference: absent IDs)
    return jnp.where((cnt > 0)[:, None], x, 0.0)


def _gather_table(y, compute_dtype, features: int,
                  gather_width: "int | None"):
    """The opposite factor table as a half-iteration's chunks gather from
    it: cast once to the compute dtype and, where the gate answered a gather
    wider than ``features`` (:func:`_rule`; ``None``: as wide as they are),
    zero-padded once to that many columns — a half-iteration's one copy of
    the table, outside the map over blocks, and never the caller's to see."""
    ys = y.astype(compute_dtype) if compute_dtype != y.dtype else y
    if gather_width is not None and gather_width > features:
        ys = jnp.pad(ys, ((0, 0), (0, gather_width - features)))
    return ys


@functools.partial(
    jax.jit,
    static_argnames=(
        "block", "features", "implicit", "slot_chunk", "dtype", "spd_kernel",
        "fused_gramian", "kernel_interpret", "gather_width",
    ),
)
def _solve_side_blocked_jit(y, srows, scols, svals, slens, lam, alpha, *,
                            block, features, implicit, slot_chunk, dtype,
                            spd_kernel, fused_gramian, kernel_interpret,
                            gather_width=None):
    yty = (y.T @ y) if implicit else None  # (k,k) Gramian — one MXU matmul
    cd = jnp.dtype(dtype)
    # one cast (and one pad), gathered per chunk
    ys = _gather_table(y, cd, features, gather_width)

    def one(args):
        r, c, v, ln = args
        return _solve_block(
            ys, r, c, v, ln, block=block, features=features, lam=lam,
            alpha=alpha, implicit=implicit, slot_chunk=slot_chunk, yty=yty,
            compute_dtype=cd, spd_kernel=spd_kernel,
            fused_gramian=fused_gramian, kernel_interpret=kernel_interpret,
        )

    out = jax.lax.map(one, (srows, scols, svals, slens))  # (n_blocks, block, k)
    return out.reshape(-1, features)


class _Formulation(typing.NamedTuple):
    """The gate's answer for one side (:func:`_choose_formulation`)."""

    fused: bool  # the Pallas gather-Gramian kernel, else the einsum
    gather_width: int  # columns of the rows the einsum gathers: ``features``,
    # or more where it gathers from a zero-padded copy of the opposite table
    why: str

    def name(self, features: int) -> str:
        if self.fused:
            return "fused kernel"
        return "einsum" if self.gather_width == features else "padded einsum"


_FORMULATION_NAMES = ("einsum", "padded einsum", "fused kernel")
_HALF_FORMULATION = metrics_mod.default_registry().gauge(
    "oryx_als_half_formulation",
    "1 for the gather-Gramian formulation the trainer's last generation "
    "resolved this side's half-iteration to, 0 for the others",
    ("side", "formulation"),
)
_SOLVED_ROWS = metrics_mod.default_registry().counter(
    "oryx_als_solved_rows_total",
    "Rows the trainer's half-iterations solved, block padding included, by "
    "the solve that ran them: the Pallas SPD kernel (spd_kernel, to 128 "
    "features), its blocked form (spd_blocked, to 256) or XLA's cholesky",
    ("side", "path"),
)
_GATHERED_ROWS = metrics_mod.default_registry().counter(
    "oryx_als_gathered_rows_total",
    "Factor rows the fused gather-Gramian kernel gathered in the trainer's "
    "half-iterations, one an entry, by where it took them from: the "
    "opposite table held whole in VMEM (vmem) or a DMA a row out of HBM "
    "(hbm)",
    ("side", "source"),
)
_SPD_TILE_ROWS = metrics_mod.default_registry().gauge(
    "oryx_als_spd_tile_rows",
    "Batch tile the SPD kernel (unblocked or blocked) ran this side's last "
    "half-iteration at; 0 where XLA's cholesky solved it",
    ("side",),
)


def _name_formulation(side: str, chosen: "_Formulation", features: int) -> None:
    """``oryx_als_half_formulation{side, …}``: 1 for what ran, 0 else."""
    ran = chosen.name(features)
    for label in _FORMULATION_NAMES:
        _HALF_FORMULATION.labels(side, label).set(float(label == ran))


class _SpdSolve(typing.NamedTuple):
    """How a half-iteration solves its rows' systems (:func:`_choose_spd`)."""

    path: str  # spd_kernel / spd_blocked (past 128 features) / cholesky
    tile_rows: int  # the kernel's batch tile; 0 under the cholesky

    @property
    def kernel(self) -> bool:
        return self.path != "cholesky"


@functools.lru_cache(maxsize=None)
def _choose_spd(asked: bool, features: int) -> _SpdSolve:
    """The Pallas SPD solve where it is asked for (by default: on a TPU) and
    a kernel takes ``features`` (``pk.spd_solve_path``: the unblocked kernel
    to 128, the blocked one to 256), else XLA's cholesky — taken on the host
    so that the run can count which solve ran. Said once a width where a
    kernel was asked for and none takes it."""
    path, tile = pk.spd_solve_path(features)
    if asked and path != "cholesky":
        return _SpdSolve(path, tile)
    if asked:
        logging.getLogger(__name__).warning(
            "SPD kernel not used: features=%d is past its VMEM tile budget; "
            "using XLA's cholesky", features)
    return _SpdSolve("cholesky", 0)


def _gather_source(chosen: _Formulation, table_rows: int,
                   features: int) -> "str | None":
    """Where a fused half's kernel takes its factor rows from — ``vmem``
    (the opposite table held whole) or ``hbm`` (a DMA a row) — by the
    kernel's own rule on the table it is handed; ``None`` for an einsum
    half, which has no such gather."""
    if not chosen.fused:
        return None
    return ("vmem" if pk.gather_gramian_resident(table_rows, features)
            else "hbm")


def _count_half(side: str, rows: int, spd: _SpdSolve,
                source: "str | None" = None, entries: int = 0) -> None:
    """One half-iteration, counted on the host as it is dispatched (nothing
    is added inside the jitted call): its solved rows, and a fused half's
    gathered rows by ``source``."""
    _SOLVED_ROWS.labels(side, spd.path).inc(rows)
    _SPD_TILE_ROWS.labels(side).set(spd.tile_rows)
    if source is not None:
        _GATHERED_ROWS.labels(side, source).inc(entries)


# The gather-Gramian formulation is chosen a SIDE, from the opposite factor
# table that side gathers from — its row width and its bytes. Placed from two
# sweeps on the chip (PERF.md §6, PRs 29 and 31; one v5e, the Netflix cell's
# two packs — T = 256 at 1.77 cells an entry, T = 512 at 1.05 — each against
# opposite tables of 17,770 to 2M rows, seconds a half-iteration). PR 29's,
# einsum / kernel, the T = 256 pack:
#
#   features  9 MB-table    ~0.5M rows    ~1-2M rows     who wins
#      32     0.94 / 2.10   2.46 / 2.98   8.75 / 3.07    einsum, then kernel
#      50     1.28 / 2.38   4.33 / 3.27   10.4 / 3.27    einsum, then kernel
#      64     1.49 / 2.56   2.11 / 3.45   2.11 / 3.54    einsum
#     100     2.33 / 3.41   2.84 / 4.32   2.85 / 4.42    einsum
#     128     3.55 / 4.28   4.05 / 5.07   4.05 / 5.07    einsum
#     250     12.0 / 11.3   12.6 / 13.3   12.6 / 17.7    kernel, then einsum
#
# (the T = 512 pack orders the same way but for 250 features, where the two
# stay within 9% of each other at every size.) The einsum's batched matmul
# over a materialised (Sc, T, k) gather beats the kernel's slot-at-a-time
# contraction by 1.2-3.3x wherever XLA's gather holds up, and it holds up at
# every size for rows of 64 features and more. For NARROWER rows it collapses
# on a large table: the compiled program then writes the gathered rows
# feature-major (f32[Sc*T, 50]{0,1}), where out of 64 columns it writes them
# row-major. PR 31's sweep took the narrow rows' kernel out of the rule: the
# SAME einsum over the table zero-padded to 64 columns (one pad a half, the
# Gramians cut back to k before the segment-sums). Einsum / einsum padded to
# 64 / kernel, the T = 512 pack ‖ the T = 256 pack, one process, the code as
# it stands (a chunk's gather off the multiples of 1,024 rows):
#
#   50 features x 284,000 rows (56.8 MB)  0.837 / 0.837  ‖ 1.750 / 1.749
#              x 355,400 rows (71.1 MB)   0.837 / 0.837  ‖ 1.750 / 1.749
#              x 420,000 rows (84.0 MB)   0.837 / 0.838
#              x 480,189 rows (96.0 MB)   2.466 / 0.837 / 2.185
#                                       ‖ 4.410 / 1.749 / 3.268
#              x 1,000,000 rows (200 MB)  5.186 / 0.828 / 2.186 ‖ 10.43 / 1.751
#   32 features x 142,000 rows (18.2 MB)  0.434 / 0.434
#              x 200,000 rows (25.6 MB)   0.477 / 0.475
#              x 284,000 rows (36.4 MB)   0.817 / 0.818
#              x 355,400 rows (45.5 MB)   1.314 / 0.818
#              x 480,189 rows (61.5 MB)   1.334 / 0.818 / 2.161 ‖ 2.419 / 1.416
#              x 1,000,000 rows (128 MB)  2.988 / 0.750
#   50 features x 17,772 rows (3.6 MB, the cell's user half) ‖ 1.2757 / 1.2758
#
# Padded, the einsum never collapses and is ahead of the kernel by 1.9-2.9x
# at every size; where the unpadded gather holds, the two are equal to the
# millisecond (bit-equal on the 17,772-row table), so padding too early
# costs nothing and padding too late costs 1.6-6x. The unpadded gather lets
# go at a size that depends on the width: between 36.4 and 45.5 MB at 32
# features, between 84.0 and 96.0 MB (the Netflix user table) at 50. One
# crossover under the LOWEST of those serves every width measured; narrower
# rows than 32, and widths between 32 and 50 and between 50 and 64, are
# unmeasured. (The step every form takes between 200,000 and 284,000 rows is
# the table passing 128 MiB of lane-padded rows: 262,144 of them.) At 250
# features the kernel's contraction has caught up (ahead by 0.78 s and 0.22 s on the
# Netflix user and item sides; interpolated linearly from 128 features' 0.73
# and 0.71 s behind, a whole iteration crosses at 200) — until its copies of
# sparse slots out of a table past ~0.5-1 GB lose it again (480 MB: 0.92x /
# 1.05x the einsum's seconds on the two packs; 960 MB: 0.97x / 1.17x; 1.9 GB:
# 1.01x / 1.40x). Widths between the measured ones are unmeasured, and so is
# more than 2M rows.
_GG_NARROW_FEATURES = 64  # rows under this: XLA's gather collapses when large
# ... from here (between 36.4 and 45.5 MB): pad them to _GG_NARROW_FEATURES
_GG_NARROW_TABLE_BYTES = 40 << 20
_GG_WIDE_FEATURES = 200  # rows from this: the kernel's contraction is ahead
_GG_WIDE_TABLE_BYTES = 640 << 20  # ... up to here (between 480 and 960 MB)


def _rule(features: int, table_rows: int) -> _Formulation:
    """The sweep above as a function of what a half-iteration can observe of
    the table it gathers from: the formulation, the width its rows are
    gathered at, and the sizes against the crossover."""
    nbytes = table_rows * features * 4
    seen = (f"the opposite table's {table_rows} rows of {features} features "
            f"are {nbytes / 1e6:.1f} MB")
    if features < _GG_NARROW_FEATURES:
        pad = nbytes >= _GG_NARROW_TABLE_BYTES
        return _Formulation(
            False, _GG_NARROW_FEATURES if pad else features,
            f"{seen}, {'at or over' if pad else 'under'} the "
            f"{_GG_NARROW_TABLE_BYTES / 1e6:.1f} MB from which XLA's gather "
            f"of rows under {_GG_NARROW_FEATURES} features collapses"
            + (f": gathered from the table zero-padded to "
               f"{_GG_NARROW_FEATURES} columns" if pad else ""))
    if features >= _GG_WIDE_FEATURES:
        fused = nbytes < _GG_WIDE_TABLE_BYTES
        return _Formulation(
            fused, features,
            f"{seen}, {'under' if fused else 'at or over'} the "
            f"{_GG_WIDE_TABLE_BYTES / 1e6:.1f} MB up to which the kernel is "
            f"ahead at {_GG_WIDE_FEATURES} features and more")
    return _Formulation(
        False, features,
        f"{seen}: between {_GG_NARROW_FEATURES} and {_GG_WIDE_FEATURES} "
        "features the einsum is ahead at every size")


def _choose_formulation(fused_gramian: "bool | None", on_tpu: bool,
                        features: int, slots: int,
                        table_rows: int) -> _Formulation:
    """(run the fused gather-Gramian kernel?, the width the einsum gathers
    rows at, why) for one side: the ONE gate of every path that picks a
    formulation (single-device, mesh, benches, the cost accounting and the
    pack's log line). ``slots`` is the side's slots a block, ``table_rows``
    the rows of the opposite factor table it gathers from — under a mesh the
    whole all-gathered table, which is what every shard reads.

    An explicit ``True`` / ``False`` forces a formulation (the einsum then
    gathers rows as wide as they are); ``None`` is the rule: off a TPU the
    einsum; on one whatever :func:`_rule` measured fastest for a table of
    this width and size. The kernel's own gates (VMEM at ``features``, SMEM
    at ``slots``) are tested first: past them the einsum runs instead of a
    program that cannot compile — and says so, because on a TPU the
    difference can be large."""
    if fused_gramian is None:
        if not on_tpu:
            return _Formulation(False, features, "not on a TPU")
    elif not fused_gramian:
        return _Formulation(False, features, "asked for")
    if not pk.gather_gramian_supported(features, slots):
        logging.getLogger(__name__).warning(
            "fused gather-Gramian kernel not used: features=%d, %d slots "
            "per block is past its VMEM/SMEM gates; using the einsum "
            "formulation", features, slots,
        )
        return _Formulation(
            False, features, f"features={features}, {slots} slots a block "
            "is past the kernel's gates")
    if fused_gramian:
        return _Formulation(True, features, "asked for")
    return _rule(features, table_rows)


def _resolve_fused(fused_gramian: "bool | None", on_tpu: bool,
                   features: int, slots: int,
                   table_rows: int) -> "tuple[bool, int]":
    """:func:`_choose_formulation`'s answer without its reason: (kernel?,
    gather width), the two statics a solver is built from."""
    return _choose_formulation(fused_gramian, on_tpu, features, slots,
                               table_rows)[:2]


def solve_side_blocked(y, srows, scols, svals, slens, lam, alpha, *, block,
                       features, implicit, slot_chunk, dtype="float32",
                       spd_kernel: "bool | None" = None,
                       fused_gramian: "bool | None" = None,
                       side: "str | None" = None):
    """One half-iteration, single device: lax.map over row blocks.

    ``spd_kernel=None`` picks the Pallas Gauss-Jordan solve on a TPU (its
    blocked form past 128 features) and XLA's cholesky elsewhere; past 256
    features the cholesky runs whatever was asked (:func:`_choose_spd`). A named ``side``
    (``user`` / ``item``) is counted in ``oryx_als_half_formulation``,
    ``oryx_als_solved_rows_total``, ``oryx_als_spd_tile_rows`` and, where
    the fused kernel runs it, ``oryx_als_gathered_rows_total``, on the host
    at every call; an unnamed half in none of them.
    ``fused_gramian=None`` picks the gather-Gramian
    formulation THIS side runs fastest (:func:`_choose_formulation`): off a
    TPU the einsum; on one whichever the chip sweep measured fastest for an
    opposite table ``y`` of this width and size — the einsum over ``y``
    zero-padded to 64 columns where narrower rows come out of a large table
    (the padded copy lives inside the call: ``y`` and the answer keep
    ``features`` columns). Jit decisions are
    static, so both are resolved here at call time, from the devices that
    hold ``y`` (``pallas_kernels.on_tpu``) and from the operands' shapes,
    never from the process default. The platform decision also sets the
    kernels' interpret mode: a caller that forces a kernel on (tests) gets
    it emulated off-TPU, and no kernel can run in interpret mode on the
    chip."""
    on_tpu = pk.on_tpu(y)
    spd = _choose_spd(on_tpu if spd_kernel is None else bool(spd_kernel),
                      features)
    chosen = _choose_formulation(fused_gramian, on_tpu, features,
                                 srows.shape[1], y.shape[0])
    if side is not None:
        _name_formulation(side, chosen, features)
        source = _gather_source(chosen, y.shape[0], features)
        # a fused half's entries: its slot lengths summed where they live,
        # one scalar read back
        _count_half(side, srows.shape[0] * block, spd, source,
                    int(jnp.sum(slens)) if source else 0)
    return _solve_side_blocked_jit(
        y, srows, scols, svals, slens, lam, alpha, block=block,
        features=features, implicit=implicit, slot_chunk=slot_chunk,
        dtype=dtype, spd_kernel=spd.kernel, fused_gramian=chosen.fused,
        kernel_interpret=not on_tpu, gather_width=chosen.gather_width,
    )


@functools.lru_cache(maxsize=64)
def _sharded_solver(mesh, row_axis, block, features, implicit, slot_chunk,
                    dtype="float32", spd_kernel=False, fused_gramian=False,
                    kernel_interpret=None, gather_width=None):
    """jit(shard_map) for one half-iteration: blocks shard over ``row_axis``,
    opposite factors replicated, output factors row-partitioned (pinned by
    out_specs). Cached per (mesh, statics). ``kernel_interpret=None``
    resolves from the MESH's target devices — a caller that forgets the
    flag must never silently emulate the Pallas kernels on chip (the
    kernel-interpret-default class; every production caller passes it).
    ``gather_width`` is the gate's (``None``: ``features``): every shard pads
    its own copy of the all-gathered table."""
    if kernel_interpret is None:
        kernel_interpret = not pk.on_tpu(mesh=mesh)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    cd = jnp.dtype(dtype)

    def local(y, srows, scols, svals, slens, lam, alpha):
        yty = (y.T @ y) if implicit else None
        ys = _gather_table(y, cd, features, gather_width)

        def one(args):
            r, c, v, ln = args
            return _solve_block(
                ys, r, c, v, ln, block=block, features=features, lam=lam,
                alpha=alpha, implicit=implicit, slot_chunk=slot_chunk, yty=yty,
                compute_dtype=cd, spd_kernel=spd_kernel,
                fused_gramian=fused_gramian, kernel_interpret=kernel_interpret,
            )

        out = jax.lax.map(one, (srows, scols, svals, slens))
        return out.reshape(-1, features)

    # in_specs[0] = P(): the full opposite factor y replicates into every
    # half-iteration (~N·k·4 B all-gathered per call) — the known ROADMAP
    # item-5(a) scaling bug, flagged by the replicated-collective checker
    # and accepted in conf/analyze-baseline.json until the routed-mesh fix
    # (ship only the factor rows each block needs) lands
    specs = dict(
        mesh=mesh,
        in_specs=(P(), P(row_axis), P(row_axis), P(row_axis), P(row_axis),
                  P(), P()),
        out_specs=P(row_axis),
    )
    # scan carries are block-local, not replicated: disable the varying-axis
    # check
    return jax.jit(shard_map(local, check_vma=False, **specs))


def _even_block(n_rows: int, features: int, ndev: int,
                block: "int | None") -> int:
    """Divide rows EVENLY across the block count the budget implies (and
    keep every device busy): a block of exactly the budget's auto size
    would leave the last block nearly empty while every block pads to the
    fullest one's slot count."""
    auto = _auto_block(features) if block is None else block
    n_blocks = max(1, -(-n_rows // max(32, min(auto, -(-n_rows // ndev)))))
    n_blocks = -(-n_blocks // ndev) * ndev
    return max(32, -(-n_rows // n_blocks))


def _side_packers(batch: RatingBatch, features: int, ndev: int, block_u: int,
                  block_i: int, chunk, slot_width, workers,
                  cache: "BlockedLayoutCache | None"):
    """(pack_user, pack_item) closures sharing one extension-match decision
    — computed HERE, before either thread starts, so concurrent side packs
    never race the cache's array comparison."""
    n_users, n_items = len(batch.users), len(batch.items)
    appended = cache.match_extension(batch.rows, batch.cols, batch.vals) \
        if cache is not None else None

    def pack_user() -> _BlockedSide:
        if cache is not None:
            return cache.side(
                "user", batch.rows, batch.cols, batch.vals, n_users, block_u,
                chunk, slot_width, ndev, features=features, workers=workers,
                appended_idx=appended,
            )
        return make_blocked_side(
            batch.rows, batch.cols, batch.vals, n_users, block_u, chunk,
            slot_width, ndev, features=features, workers=workers,
        )

    def pack_item() -> _BlockedSide:
        if cache is not None:
            return cache.side(
                "item", batch.cols, batch.rows, batch.vals, n_items, block_i,
                chunk, slot_width, ndev, features=features, workers=workers,
                appended_idx=appended,
            )
        return make_blocked_side(
            batch.cols, batch.rows, batch.vals, n_items, block_i, chunk,
            slot_width, ndev, features=features, workers=workers,
        )

    return pack_user, pack_item


def prepare_blocked(
    batch: RatingBatch,
    features: int,
    ndev: int = 1,
    block: int | None = None,
    chunk: int | None = None,
    slot_width: int | None = None,
    workers: int | None = None,
    cache: "BlockedLayoutCache | None" = None,
) -> tuple[_BlockedSide, _BlockedSide]:
    """Pack both half-iteration sides with production block/chunk sizing.

    The single setup path shared by :func:`als_train` and the training
    benchmark, so published throughput always measures the same layout
    production uses. The two sides pack CONCURRENTLY on big inputs (the
    dominant costs — the fused-key argsort, gathers, bincounts, and the
    slab scatters — all release the GIL), on top of each side's own
    chunked scatter pool; ``workers`` caps both (None = auto, 1 = serial).
    ``cache`` (a :class:`BlockedLayoutCache`) turns a repeated or appended
    generation's pack into a reuse or an incremental delta."""
    block_u = _even_block(len(batch.users), features, ndev, block)
    block_i = _even_block(len(batch.items), features, ndev, block)
    pack_user, pack_item = _side_packers(
        batch, features, ndev, block_u, block_i, chunk, slot_width, workers,
        cache,
    )
    if _pack_workers(workers, len(batch.rows)) > 1:
        import concurrent.futures as cf

        with cf.ThreadPoolExecutor(2) as pool:
            fu, fi = pool.submit(pack_user), pool.submit(pack_item)
            sides = fu.result(), fi.result()
    else:
        sides = pack_user(), pack_item()
    if cache is not None:
        cache.store_batch(batch.rows, batch.cols, batch.vals)
    on_tpu = pk.on_tpu(sides[0].scols)
    for name, side, opposite in (("user", sides[0], sides[1]),
                                 ("item", sides[1], sides[0])):
        chosen = _choose_formulation(None, on_tpu, features,
                                     side.srows.shape[1], opposite.padded_rows)
        _log_gather_rows(name, side, features, chosen, _gather_source(
            chosen, opposite.padded_rows, features))
    return sides


def _init_factors(padded_rows: int, n_rows: int, features: int,
                  key) -> jnp.ndarray:
    k1, _ = jax.random.split(key)
    y0 = 0.1 * jax.random.normal(k1, (n_rows, features), dtype=jnp.float32)
    return jnp.zeros(
        (padded_rows, features), dtype=jnp.float32
    ).at[:n_rows].set(y0)


def init_item_factors(item_side: _BlockedSide, n_items: int, features: int,
                      key) -> jnp.ndarray:
    """Random Y₀ in the padded factor buffer (gathers only ever index real
    rows < n_items, so padding rows are never read)."""
    return _init_factors(item_side.padded_rows, n_items, features, key)


def _log_gather_rows(name: str, side: _BlockedSide, features: int,
                     chosen: _Formulation, source: "str | None") -> None:
    """One line a side: what the pack holds, the formulation the gate chose
    for it and why, the width its rows are gathered at, the factor rows
    that formulation's gather issues and, under the kernel, where it takes
    them from (:func:`_gather_source`)."""
    before, now = side.gather_rows_per_entry(chosen.fused)
    if source == "vmem":
        issues = ("fused kernel: each slot to its own length; source: vmem, "
                  "the opposite table held whole in VMEM, a vector load a "
                  "row")
    elif source == "hbm":
        issues = ("fused kernel: each slot to its own length; source: hbm, "
                  "a DMA a row; %.3f%% of the slots are fetched under the "
                  "slot before, the rest open a block's call"
                  % (100.0 * side.prefetched_slots / max(1, side.real_slots)))
    else:
        issues = "einsum: every cell of every slot"
    logging.getLogger(__name__).info(
        "slotted COO %s side: %d entries in %d slots of T=%d (+%d of block "
        "padding); formulation: %s (%s); the gather moves %.3f factor rows "
        "of %d columns an entry (%s), %.3f if every slot were copied to its "
        "width",
        name, side.entries, side.real_slots, side.slot_width,
        int(side.srows.size) - side.real_slots,
        chosen.name(features), chosen.why, now, chosen.gather_width, issues,
        before,
    )


def _register_half_cost(name: str, side: _BlockedSide, features: int,
                        dtype: str, chosen: _Formulation,
                        source: "str | None") -> None:
    """Analytic per-half-iteration device cost for the trainer's cost
    accounting (common/profiling.py), under ``als.train.<name>_half``: the
    same useful-FLOP model the batch bench's MFU derives from (2·nnz·k²
    Gramian + 2·nnz·k RHS + rows·(k³/3 + 2k²) solve), with bytes as the
    dominant HBM terms — the
    factor rows the gather ISSUES (``side.gather_rows``: one an entry under
    the fused kernel, whose copies are 32-bit whatever the compute dtype;
    every slot cell at the compute dtype and the gate's gather width under
    the einsum formulation) plus the per-row Gramian and factor writes. The
    blocked solver is a scan of
    sub-programs rather than one compiled executable, so the trainer
    registers analytically where serving registers from
    ``cost_analysis()``; either way the label is one program signature
    multiplied by recorded calls. Which formulation the side resolved to is
    a fact of the run: ``oryx_als_half_formulation{side, formulation}``
    reads 1 for it and 0 for the others."""
    fused = chosen.fused
    k = features
    nnz = side.entries
    rows = side.padded_rows
    flops = (2.0 * nnz * k * k + 2.0 * nnz * k
             + rows * (k ** 3 / 3.0 + 2.0 * k * k))
    gather_itemsize = 2.0 if dtype == "bfloat16" and not fused else 4.0
    bytes_ = (float(side.gather_rows(fused)) * chosen.gather_width
              * gather_itemsize + rows * k * (k + 1) * 4.0)
    key = f"als.train.{name}_half"
    profiling.costs().register(key, flops, bytes_)
    _name_formulation(name, chosen, k)
    _log_gather_rows(key, side, k, chosen, source)


def _recorded_half(name: str, side: _BlockedSide, spd: _SpdSolve, fn,
                   source: "str | None"):
    """Wrap a half-iteration solver so each dispatch lands in the device
    cost counters (oryx_device_flops_total{program=als.train.<name>_half}
    et al.) and the solve counters (:func:`_count_half`)."""

    def call(*args):
        profiling.costs().record(f"als.train.{name}_half")
        _count_half(name, side.padded_rows, spd, source, side.entries)
        return fn(*args)

    return call


def als_train(
    batch: RatingBatch,
    features: int,
    lam: float,
    alpha: float,
    implicit: bool,
    iterations: int = 10,
    key=None,
    chunk: int | None = None,
    mesh=None,
    row_axis: "str | tuple | None" = None,
    block: int | None = None,
    slot_width: int | None = None,
    dtype: str = "float32",
    fused_gramian: "bool | None" = None,
    layout_cache: "BlockedLayoutCache | None" = None,
    timings: "dict | None" = None,
    checkpointer=None,
):
    """Full alternating optimization; returns (X, Y) as jax arrays.

    ``dtype`` sets the Gramian-matmul INPUT precision ("bfloat16" = MXU
    native; accumulation and solves stay float32 regardless).

    **Pack/compute overlap**: the user side packs on the calling thread
    while the item side packs on a worker — and the user half-iteration
    DISPATCHES before the item pack is awaited, so the device crunches the
    first half-iteration while the host finishes packing the other side.
    With a ``layout_cache`` a repeated/appended generation's pack collapses
    to a reuse or an incremental delta, which together make host packing
    cost less wall time than the device loop it feeds (the r5 gap: 58 s
    pack vs 6 s compute). ``timings``, when a dict is passed, receives
    ``pack_s`` (pack time actually BLOCKING the critical path),
    ``pack_user_s``/``pack_item_s`` (raw per-side work) and the cache
    modes.

    ``fused_gramian=None`` picks the gather-Gramian formulation a SIDE
    (:func:`_choose_formulation`): on a TPU the fused Pallas kernel
    (``ops/pallas_kernels.gather_gramian_accumulate``) or the
    einsum+segment-sum formulation, whichever the chip sweep measured
    faster for the opposite factor table that side gathers from (at 50
    features: the einsum, gathering from that table zero-padded to 64
    columns once it holds 40 MiB of factor rows), and the einsum
    everywhere off a TPU; ``True`` / ``False`` force one on both sides
    (``True`` is interpret-emulated off-TPU — how the CPU suite tests the
    exact path).

    **Preemption tolerance**: ``checkpointer`` (a
    ``common/checkpoint.TrainerCheckpointer``) restores the newest valid
    factor state for its data fingerprint before the loop and saves
    ``{x, y}`` every interval (plus the final iteration) — each save
    handed to a background writer so the device→host fetch and file write
    overlap the next half-iteration, never blocking the device loop (the
    blocked time is reported as ``timings["ckpt_wait_s"]``, asserted ≈0
    by bench_batch). A restored checkpoint skips its completed iterations:
    a killed trainer redoes at most one interval. Restore/save failures
    degrade to from-scratch/skipped — checkpointing never fails a train.

    Single-device (no mesh): returns exact-shape ``(n_users, k)``/
    ``(n_items, k)`` arrays.

    With ``mesh``/``row_axis``: the block axis shards over that mesh axis on
    the way in (device_put) and the way out (shard_map out_specs pins the
    factors row-partitioned), and the returned factors are **padded up to the
    block boundary** (``shape[0] = n_blocks·block ≥ n_rows``, extra rows
    zero) — exact-size uneven shardings are not expressible, and gathering
    to slice would defeat the partitioning. Consumers slice host-side
    (``np.asarray(x)[:n_users]``). ``block``/``chunk`` default to sizes
    bounding device memory at ~256 MB / ~64 MB regardless of n_rows; block
    is chosen per side so a small side is not over-padded; the slot width T
    defaults to the side's mean row degree (power of two in [8, 512]).
    ``chunk`` counts SLOTS per scan step (each T entries wide), not nnz, and
    explicit values are clamped into the transient budget.
    """
    import concurrent.futures as cf
    import time

    from oryx_tpu.common import rand

    if iterations < 1:
        raise ValueError(f"iterations must be >= 1, got {iterations}")
    if dtype not in ("float32", "bfloat16"):
        # fail fast at the API boundary: a typo ("bf16") would otherwise
        # surface deep inside a jitted solve, and a low-precision numpy
        # dtype ("float16", "int8") would run and silently degrade factors
        raise ValueError(
            f"compute dtype must be 'float32' or 'bfloat16', got {dtype!r}"
        )

    n_users, n_items = len(batch.users), len(batch.items)
    k = features
    ndev = 1
    if mesh is not None and row_axis is not None:
        # one axis name, or a tuple of names sharding rows over their product
        axes = (row_axis,) if isinstance(row_axis, str) else tuple(row_axis)
        ndev = math.prod(mesh.shape[a] for a in axes)
    block_u = _even_block(n_users, k, ndev, block)
    block_i = _even_block(n_items, k, ndev, block)
    pack_user, pack_item = _side_packers(
        batch, k, ndev, block_u, block_i, chunk, slot_width, None,
        layout_cache,
    )
    pool = cf.ThreadPoolExecutor(1, thread_name_prefix="oryx-als-pack")
    item_timing: dict = {}

    def timed_pack_item() -> _BlockedSide:
        t0 = time.perf_counter()
        side = pack_item()
        item_timing["s"] = time.perf_counter() - t0
        return side

    def finish_item_pack() -> tuple[_BlockedSide, float]:
        t1 = time.perf_counter()
        side = item_fut.result()
        wait_s = time.perf_counter() - t1
        pool.shutdown(wait=False)
        chosen["item"] = resolve("item", side, user_side.padded_rows)
        if layout_cache is not None:
            layout_cache.store_batch(batch.rows, batch.cols, batch.vals)
        if timings is not None:
            timings["pack_user_s"] = round(pack_user_s, 3)
            timings["pack_item_s"] = round(item_timing.get("s", 0.0), 3)
            timings["pack_wait_s"] = round(wait_s, 3)
            # pack cost on the CRITICAL PATH: the user pack plus however
            # much of the item pack the device did not hide
            timings["pack_s"] = round(pack_user_s + wait_s, 3)
            if layout_cache is not None:
                timings["pack_modes"] = dict(layout_cache.last_modes)
        return side, wait_s

    # everything past the submit sits under the finally: a user-pack or
    # factor-init failure must still shut the pool down, or the supervised
    # batch-tier retry loop would leak one pack thread per failed attempt
    try:
        item_fut = pool.submit(timed_pack_item)
        t0 = time.perf_counter()
        user_side = pack_user()
        pack_user_s = time.perf_counter() - t0
        chunk_u = user_side.slot_chunk

        if key is None:
            key = rand.get_key()
        # resume: the newest valid checkpoint matching the data fingerprint
        # replaces Y₀ (and skips its completed iterations); shape drift —
        # a block-size or hyperparameter change that slipped past the
        # fingerprint — falls back to a fresh start, never a bad gather
        start_iter = 0
        restored: "tuple | None" = None
        if checkpointer is not None:
            ck = checkpointer.restore()
            if ck is not None:
                rx, ry = ck.arrays.get("x"), ck.arrays.get("y")
                if (rx is not None and ry is not None
                        and rx.shape == (n_users, k)
                        and ry.shape == (n_items, k)):
                    restored = (np.asarray(rx, dtype=np.float32),
                                np.asarray(ry, dtype=np.float32))
                    start_iter = min(int(ck.step), iterations)
                    checkpointer.mark_resumed(start_iter)
                else:
                    logging.getLogger(__name__).warning(
                        "checkpoint %s does not match the current factor "
                        "shapes; training from scratch", ck.path,
                    )

        def _maybe_ckpt(completed: int, x_arr, y_arr) -> None:
            if checkpointer is None or not checkpointer.wants(
                completed, iterations
            ):
                return
            # exact-size slices: checkpoints are block-layout-agnostic,
            # so a resume survives a changed block/mesh geometry
            checkpointer.submit(
                completed, {"x": x_arr[:n_users], "y": y_arr[:n_items]}
            )

        def _finish_ckpt() -> None:
            if checkpointer is not None:
                checkpointer.finish()
                if timings is not None:
                    # wait_s = mid-train joins only (the overlap evidence);
                    # the final join mostly waits on the LAST iteration's
                    # device compute, which a plain train pays too
                    timings["ckpt_wait_s"] = round(checkpointer.wait_s, 3)
                    timings["ckpt_final_wait_s"] = round(
                        checkpointer.final_wait_s, 3
                    )
                    timings["ckpt_resumed_from"] = checkpointer.resumed_step

        # Y₀ needs only the item side's PADDED SHAPE, which is pure
        # arithmetic — the factor buffer (and the whole first user
        # half-iteration) must not wait on the item pack
        if restored is not None:
            y = jnp.zeros(
                (_padded_rows_for(n_items, block_i, ndev), k),
                dtype=jnp.float32,
            ).at[:n_items].set(restored[1])
        else:
            y = _init_factors(_padded_rows_for(n_items, block_i, ndev),
                              n_items, k, key)

        # the formulation each side runs is resolved ONCE, here, from the
        # devices that will hold the factors and the rows of the opposite
        # table the side gathers from (padded, as the solver sees it; under
        # a mesh the whole table, which every shard reads): the cost
        # accounting counts the rows that formulation's gather issues, and
        # the solvers below are handed the same answer
        sharded_mode = mesh is not None and row_axis is not None
        on_tpu = pk.on_tpu(mesh=mesh) if sharded_mode else pk.on_tpu(y)
        spd = _choose_spd(on_tpu, k)

        def resolve(name: str, side: _BlockedSide,
                    table_rows: int) -> _Formulation:
            choice = _choose_formulation(fused_gramian, on_tpu, k,
                                         side.srows.shape[1], table_rows)
            sources[name] = _gather_source(choice, table_rows, k)
            _register_half_cost(name, side, k, dtype, choice, sources[name])
            return choice

        sources: dict = {}
        chosen = {"user": resolve("user", user_side, y.shape[0])}

        if sharded_mode:
            from jax.sharding import NamedSharding, PartitionSpec as P

            row_shard = NamedSharding(mesh, P(row_axis, None))

            def put_side(side):
                return tuple(
                    jax.device_put(a, NamedSharding(
                        mesh, P(row_axis, *([None] * (a.ndim - 1)))))
                    for a in (side.srows, side.scols, side.svals, side.slens)
                )

            y = jax.device_put(y, row_shard)
            if start_iter >= iterations:
                # fully-trained checkpoint (a crash between train end and
                # publish): nothing to redo — re-pad X and keep the mesh
                # contract (padded, row-partitioned factors). Checked
                # BEFORE the user-side COO transfers to device or the
                # solver builds: a zero-redo resume must not pay either.
                finish_item_pack()
                x = jax.device_put(
                    jnp.zeros(
                        (_padded_rows_for(n_users, block_u, ndev), k),
                        dtype=jnp.float32,
                    ).at[:n_users].set(restored[0]),
                    row_shard,
                )
                _finish_ckpt()
                return x, y
            u_arrays = put_side(user_side)

            def sharded(name, side, blk):
                solver = _sharded_solver(
                    mesh, row_axis, blk, k, implicit, side.slot_chunk, dtype,
                    spd.kernel, chosen[name].fused, not on_tpu,
                    chosen[name].gather_width)
                return _recorded_half(name, side, spd, solver, sources[name])

            solve_u = sharded("user", user_side, block_u)
            x = solve_u(y, *u_arrays, lam, alpha)  # device busy; host packs
            item_side, _ = finish_item_pack()
            i_arrays = put_side(item_side)
            solve_i = sharded("item", item_side, block_i)
            y = solve_i(x, *i_arrays, lam, alpha)
            completed = start_iter + 1
            _maybe_ckpt(completed, x, y)
            for _ in range(iterations - start_iter - 1):
                x = solve_u(y, *u_arrays, lam, alpha)
                y = solve_i(x, *i_arrays, lam, alpha)
                completed += 1
                _maybe_ckpt(completed, x, y)
            _finish_ckpt()
            return x, y

        def solve(side, opp, blk, ck):
            name = "user" if side is user_side else "item"
            profiling.costs().record(f"als.train.{name}_half")
            _count_half(name, side.padded_rows, spd, sources[name],
                        side.entries)
            # solve_side_blocked's call, with the answers resolved above
            return _solve_side_blocked_jit(
                opp, side.srows, side.scols, side.svals, side.slens, lam,
                alpha, block=blk, features=k, implicit=implicit,
                slot_chunk=ck, dtype=dtype, spd_kernel=spd.kernel,
                fused_gramian=chosen[name].fused, kernel_interpret=not on_tpu,
                gather_width=chosen[name].gather_width,
            )

        if start_iter >= iterations:
            # fully-trained checkpoint: nothing to redo (the item pack
            # worker still gets joined so timings/cache state stay sound)
            finish_item_pack()
            _finish_ckpt()
            return jnp.asarray(restored[0]), jnp.asarray(restored[1])
        # first user half-iteration dispatches against Y₀ (or the restored
        # Y) while the item side is still packing on the worker thread
        x = solve(user_side, y, block_u, chunk_u)
        item_side, _ = finish_item_pack()
        chunk_i = item_side.slot_chunk
        y = solve(item_side, x, block_i, chunk_i)
        completed = start_iter + 1
        _maybe_ckpt(completed, x, y)
        for _ in range(iterations - start_iter - 1):
            x = solve(user_side, y, block_u, chunk_u)
            y = solve(item_side, x, block_i, chunk_i)
            completed += 1
            _maybe_ckpt(completed, x, y)
        _finish_ckpt()
        return x[:n_users], y[:n_items]
    finally:
        # JOIN the worker on every exit: after a user-pack failure an
        # orphaned item pack could outlive this call — and the ALSUpdate
        # cache lock — then write its side into the shared layout cache
        # mid-next-generation, desyncing _sides from _arrays and silently
        # corrupting a later delta pack. On success the future is already
        # consumed and this is free.
        pool.shutdown(wait=True, cancel_futures=True)
