"""Factor arena: contiguous in-memory feature-vector store for speed/serving.

Equivalent of the reference's FeatureVectors / FeatureVectorsPartition /
PartitionedFeatureVectors (app/oryx-app-common/.../als/FeatureVectorsPartition.java:36-131,
PartitionedFeatureVectors.java:43-93): id → float32 vector map plus a
recent-ids set, guarded by one readers-writer lock, with ``retain_recent_and_ids``
GC on model handoff.

TPU re-design, round 9: the store used to be a ``dict[str, np.ndarray]`` —
one Python ndarray object (~200 B of header) plus a dict slot per row, which
at reference scale (21M rows × 50f ≈ 4 GB of raw factors) multiplies host
RSS 3-5× and turns every device materialization into a million-element
``np.stack``. Now all factors live in ONE preallocated ``(capacity, k)``
float32 slab (the **arena**): ids map to row indices, growth doubles the
slab, and removals/GC re-pack survivors into a FRESH slab (shrinking when
the fill fraction drops). Rows are never recycled in place — a row, once
bound to an id, keeps that binding for its slab's lifetime, so consumers
holding a pinned (slab, rows) snapshot view stay consistent across any
concurrent structural change. Host RSS tracks raw factor bytes; device
snapshot updates become slab slices and row-index scatters.

The store is **pure numpy on the host side**; the device materialization
cache (``materialize``) still builds/maintains a jax device matrix
incrementally (one batched scatter + one append per point-update batch —
never a full host→device re-upload), and a parallel HOST snapshot API
(``host_matrix``/``delta_info``, each carrying the pinned slab view)
serves consumers that must never create a device f32 copy at all (the
int8-quantized serving path gathers its exact rescore rows from it).
``get_vtv`` computes the Gramian from the slab with host BLAS, so a speed
tier never pins a device matrix just for fold-in solvers.
"""

from __future__ import annotations

import collections
import concurrent.futures
import functools
import os
import threading
import weakref

import numpy as np

from oryx_tpu.common.lockutils import AutoReadWriteLock

#: Process-wide arena sizing defaults, set by :func:`configure` from
#: ``oryx.serving.arena.*``. Plain ints/floats: reads are atomic.
_DEFAULT_INITIAL_ROWS = 1024
_DEFAULT_MIN_FILL = 0.25

#: Bounded per-write log backing ``delta_info``: (version, id, was_new).
#: A consumer whose snapshot version fell off the log rebuilds in full.
_LOG_MAX = 65536


def configure(config) -> None:
    """Apply ``oryx.serving.arena.*`` sizing knobs process-wide (the same
    configure-at-entry idiom as metrics/resilience): ``initial-rows`` seeds
    new slabs, ``min-fill`` triggers compaction after GC."""
    global _DEFAULT_INITIAL_ROWS, _DEFAULT_MIN_FILL
    _DEFAULT_INITIAL_ROWS = max(
        1, config.get_int("oryx.serving.arena.initial-rows", 1024)
    )
    _DEFAULT_MIN_FILL = min(
        1.0, max(0.0, config.get_float("oryx.serving.arena.min-fill", 0.25))
    )


#: Row-chunked host copies: at reference scale (20M × 250f = 20 GB) a copy
#: into fresh memory is bound by the first touch of its pages, which a few
#: threads take side by side (numpy drops the GIL inside each chunk's copy).
_COPY_CHUNK_ROWS = 1 << 18
_COPY_MIN_BYTES = 64 << 20
_COPY_WORKERS = min(8, os.cpu_count() or 1)


def _copy_rows(dst: np.ndarray, src: np.ndarray, rows=None) -> None:
    """``dst[i] = src[rows[i]]`` (``src[i]`` with no ``rows``) for every row
    of ``dst``; large copies go by chunks of rows on a few threads."""
    n = dst.shape[0]

    def fill(a, b):
        if rows is None:
            dst[a:b] = src[a:b]
        else:
            # "clip": the rows are the store's own, and numpy would buffer
            # ``out`` whole under the default "raise"
            np.take(src, rows[a:b], axis=0, out=dst[a:b], mode="clip")

    if dst.nbytes < _COPY_MIN_BYTES or _COPY_WORKERS < 2:
        fill(0, n)
        return
    with concurrent.futures.ThreadPoolExecutor(_COPY_WORKERS) as pool:
        list(pool.map(lambda a: fill(a, min(n, a + _COPY_CHUNK_ROWS)),
                      range(0, n, _COPY_CHUNK_ROWS)))


def _host_gather(slab: np.ndarray, rows) -> np.ndarray:
    """One C-level gather of slab rows about to cross the host→device
    boundary — THE seam tests monkeypatch to count upload traffic (a full
    rebuild gathers every live row; a point-update batch only its delta).
    A run of consecutive rows (a bulk handoff's whole order) is a plain
    copy."""
    rows = np.asarray(rows, dtype=np.int64)
    out = np.empty((rows.shape[0],) + slab.shape[1:], dtype=slab.dtype)
    if rows.size and rows[-1] - rows[0] + 1 == rows.size and (
            rows.size == 1 or bool(np.all(np.diff(rows) == 1))):
        _copy_rows(out, slab[rows[0]: rows[-1] + 1])
    else:
        _copy_rows(out, slab, rows)
    return out


class _IdIndex:
    """Interned id → slab-row map: ids live utf-8-packed in ONE bytearray,
    the map is open-addressing linear probing over numpy arrays. ~25 B/id
    all-in versus the ~170 B/id of a Python ``dict[str, int]`` plus its key
    string objects — the difference between 1.2× and 2.2× raw-factor RSS at
    1M × 50f (measured; docs/performance.md "Serving memory").

    Keyed BY SLAB ROW: ``starts/lens/hashes[row]`` describe the id owning
    that row; the probe table stores rows (−1 empty, −2 tombstone).
    Overwritten/removed ids leave dead bytes in the blob; the store's
    structural compaction rebuilds the whole index, reclaiming them."""

    __slots__ = ("_blob", "_starts", "_lens", "_hashes", "_table", "_used",
                 "_tombstones")

    def __init__(self, capacity: int = 16):
        self._blob = bytearray()
        self._starts = np.zeros(capacity, dtype=np.int32)
        self._lens = np.zeros(capacity, dtype=np.int32)
        self._hashes = np.zeros(capacity, dtype=np.int64)
        table = 16
        while table < 2 * capacity:
            table *= 2
        self._table = np.full(table, -1, dtype=np.int32)
        self._used = 0        # live entries in the table
        self._tombstones = 0  # -2 slots; BOTH drive resize: a probe only
        # terminates on a -1 slot, so tombstones must never be allowed to
        # consume the last empty slots (delete-churn would otherwise spin
        # _probe forever once no -1 remains)

    def _grow_rows(self, need: int) -> None:
        cap = self._starts.shape[0]
        if need <= cap:
            return
        new_cap = max(cap, 16)
        while new_cap < need:
            new_cap *= 2
        for name, dtype in (("_starts", np.int32), ("_lens", np.int32),
                            ("_hashes", np.int64)):
            old = getattr(self, name)
            grown = np.zeros(new_cap, dtype=dtype)
            grown[:cap] = old
            setattr(self, name, grown)

    def _resize_table(self) -> None:
        """Rebuild the probe table from live entries — doubling only when
        the LIVE load demands it (a tombstone-triggered rebuild at the same
        size just sheds the -2 slots)."""
        old = self._table
        size = old.shape[0]
        if self._used * 3 > size * 2:
            size *= 2
        self._table = np.full(size, -1, dtype=np.int32)
        self._tombstones = 0
        mask = size - 1
        for row in old[old >= 0]:
            slot = int(self._hashes[row]) & mask
            while self._table[slot] >= 0:
                slot = (slot + 1) & mask
            self._table[slot] = row

    def _probe(self, enc: bytes, h: int) -> "tuple[int, int]":
        """(slot, row): row ≥ 0 on hit; on miss, slot is the insert point
        (first tombstone on the probe path, else the empty slot)."""
        mask = self._table.shape[0] - 1
        slot = h & mask
        insert_at = -1
        while True:
            row = int(self._table[slot])
            if row == -1:
                return (insert_at if insert_at >= 0 else slot), -1
            if row == -2:
                if insert_at < 0:
                    insert_at = slot
            elif self._hashes[row] == h:
                a = int(self._starts[row])
                if self._blob[a:a + int(self._lens[row])] == enc:
                    return slot, row
            slot = (slot + 1) & mask

    @staticmethod
    def _hash(enc: bytes) -> int:
        return hash(enc) & 0x7FFFFFFFFFFFFFFF

    def lookup(self, id_: str) -> int:
        """Slab row of ``id_``, or −1."""
        enc = id_.encode()
        return self._probe(enc, self._hash(enc))[1]

    def add(self, id_: str, row: int) -> None:
        """Bind a NEW id to ``row`` (caller guarantees absence)."""
        enc = id_.encode()
        h = self._hash(enc)
        self._grow_rows(row + 1)
        self._starts[row] = len(self._blob)
        self._lens[row] = len(enc)
        self._hashes[row] = h
        self._blob.extend(enc)
        if (self._used + self._tombstones + 1) * 3 > self._table.shape[0] * 2:
            self._resize_table()
        slot, _ = self._probe(enc, h)
        if self._table[slot] == -2:
            self._tombstones -= 1  # recycling a tombstoned slot
        self._table[slot] = row
        self._used += 1

    def add_many(self, ids, first_row: int) -> None:
        """Bind NEW, mutually distinct ids to rows ``first_row, first_row+1,
        …`` in one pass (caller guarantees absence and distinctness): what
        :meth:`add` does an id at a time, with the encode, the hash and the
        probe-table insert each done for the whole handoff at once — a
        20M-row MODEL handoff spends seconds here, not minutes."""
        n = len(ids)
        if not n:
            return
        encs = [s.encode() for s in ids]
        lens = np.fromiter(map(len, encs), dtype=np.int32, count=n)
        hashes = np.fromiter(map(hash, encs), dtype=np.int64, count=n)
        self._grow_rows(first_row + n)
        rows = slice(first_row, first_row + n)
        self._starts[rows] = (len(self._blob)
                              + np.cumsum(lens, dtype=np.int64) - lens)
        self._lens[rows] = lens
        self._hashes[rows] = hashes & 0x7FFFFFFFFFFFFFFF
        self._blob.extend(b"".join(encs))
        size = self._table.shape[0]
        while (self._used + self._tombstones + n) * 3 > size * 2:
            size *= 2
        placing = np.arange(first_row, first_row + n, dtype=np.int32)
        if size != self._table.shape[0]:
            placing = np.concatenate(
                [self._table[self._table >= 0], placing])
            self._table = np.full(size, -1, dtype=np.int32)
            self._tombstones = 0
        self._place(placing)
        self._used += n

    def _place(self, rows: np.ndarray) -> None:
        """Linear-probe insert of ``rows`` (none in the table yet), all at
        once: each round every unplaced row writes itself into its slot if
        the slot is EMPTY — of several claimants one write stays — and the
        rows that do not find themselves there move one slot on. A row only
        ever moves past an occupied slot, so every probe path stays
        unbroken, as :meth:`_probe` needs."""
        table = self._table
        mask = table.shape[0] - 1
        slots = self._hashes[rows] & mask
        while rows.size:
            free = table[slots] == -1
            table[slots[free]] = rows[free]
            left = table[slots] != rows
            rows = rows[left]
            slots = (slots[left] + 1) & mask

    def delete(self, id_: str) -> int:
        """Unbind ``id_``; returns its row or −1. Blob bytes stay until a
        structural compaction rebuilds the index."""
        slot, row = self._probe(id_.encode(), self._hash(id_.encode()))
        if row >= 0:
            self._table[slot] = -2
            self._used -= 1
            self._tombstones += 1
        return row

    def decode(self, row: int) -> str:
        a = int(self._starts[row])
        return self._blob[a:a + int(self._lens[row])].decode()

    def nbytes(self) -> int:
        return (len(self._blob) + self._starts.nbytes + self._lens.nbytes
                + self._hashes.nbytes + self._table.nbytes)


class Transition:
    """One incremental materialization step: ``new_mat`` is ``prev_mat`` with
    rows ``changed_idx`` rewritten and ``n_new`` rows appended. Consumers
    holding a snapshot of ``prev_mat`` (ALSServingModel._YSnapshot) use this
    to update derived per-row state (LSH buckets) for only the delta.

    Matrices are held by WEAK reference: the log must never pin old device
    buffers in HBM — once every consumer drops a generation, the chain
    through it simply breaks and the consumer falls back to a full rebuild."""

    __slots__ = ("prev_ref", "new_ref", "changed_idx", "n_new", "n_prev")

    def __init__(self, prev_mat, new_mat, changed_idx: np.ndarray, n_new: int,
                 n_prev: int):
        self.prev_ref = weakref.ref(prev_mat)
        self.new_ref = weakref.ref(new_mat)
        self.changed_idx = changed_idx
        self.n_new = n_new
        # ids in prev_mat: its row count, less the padding of a sharded one
        self.n_prev = n_prev


class HostDelta:
    """Composable host-side delta between two store versions, for consumers
    maintaining their OWN derived per-row state (the quantized device
    snapshot): positions are indices into the consumer's snapshot order;
    values are current-slab copies (intermediate values between the two
    versions are irrelevant — the newest value per row is what lands)."""

    __slots__ = ("version", "changed_ids", "changed_vals", "appended_ids",
                 "appended_vals", "appended_rows", "slab")

    def __init__(self, version, changed_ids, changed_vals, appended_ids,
                 appended_vals, appended_rows=None, slab=None):
        self.version = version
        self.changed_ids = changed_ids        # list[str], ids in the OLD order
        self.changed_vals = changed_vals      # (len(changed_ids), k) f32
        self.appended_ids = appended_ids      # list[str]
        self.appended_vals = appended_vals    # (len(appended_ids), k) f32
        self.appended_rows = appended_rows    # slab rows of the appended ids
        self.slab = slab                      # CURRENT slab object (row
        # indices are stable within an order epoch: _grow copies rows in
        # place and every row-moving change is structural)


@functools.lru_cache(maxsize=8)
def _sharded_row_ops(sharding):
    """(scatter, append) jitted under ``sharding`` (rows split over the
    mesh): the incremental path's two device steps, each returning an array
    split exactly like its operand. ``append`` rebuilds the row blocks for a
    new padded row count — the partitioner moves rows between neighbours;
    ``n_old`` (the real rows kept) is static."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, out_shardings=sharding)
    def scatter(mat, idx, vals):
        return mat.at[idx].set(vals)

    @functools.partial(jax.jit, out_shardings=sharding,
                       static_argnames=("n_old",))
    def append(mat, tail, n_old: int):
        return jnp.concatenate([mat[:n_old], tail])

    return scatter, append


class FeatureVectorStore:
    def __init__(self, initial_rows: "int | None" = None, mesh=None,
                 shard_axis: str = "model"):
        self._initial_rows = initial_rows or _DEFAULT_INITIAL_ROWS
        # device materialization split by rows over ``shard_axis`` of
        # ``mesh``: every device holds only its own row block (the matrix
        # then has mesh.padded_rows(n) rows, zero past the last id). None
        # keeps the one-device materialization exactly as it was.
        self._mesh = mesh
        self._shard_axis = shard_axis
        self._lock = AutoReadWriteLock()
        # -- the arena ------------------------------------------------------
        self._slab: "np.ndarray | None" = None  # (capacity, k) float32
        self._ids = _IdIndex()                   # interned id -> slab row
        # one-shot first-allocation sizing from reserve(); compaction keeps
        # using the CONFIGURED floor, so a 21M-row reserve does not pin the
        # slab at 21M for the process lifetime after GC shrinks the model
        self._reserve_rows = 0
        self._n_alloc = 0                        # slab high-water mark
        # snapshot order: position -> slab row (append-only between
        # structural changes) and its inverse, both numpy — no per-id
        # Python objects anywhere in the store
        self._rowmap = np.empty(0, dtype=np.int32)
        self._n_pos = 0
        self._pos_of_row = np.empty(0, dtype=np.int32)
        self._recent = np.zeros(0, dtype=bool)   # per-row recent flag
        # -- versioning -----------------------------------------------------
        self._version = 0
        # version at which the last STRUCTURAL change (bulk handoff, removal,
        # GC, compaction) happened: incremental consumption is sound only
        # from a snapshot at/after this point. Never cleared — comparing
        # versions is race-free where clearing a boolean is not.
        self._rebuild_needed_at = 0
        # per-write log for host-side delta consumers (delta_info)
        self._log: collections.deque = collections.deque(maxlen=_LOG_MAX)
        # -- device materialization cache ----------------------------------
        self._cache_lock = threading.Lock()
        self._cached_ids: "list | None" = None
        self._cached_matrix = None  # jax array
        self._cached_version = -1
        # slab rows point-updated since the last device materialization
        self._pending: set = set()
        # recent incremental device steps (weak matrix refs): lets a snapshot
        # consumer catch up across SEVERAL materialize generations
        self._transitions: collections.deque = collections.deque(maxlen=8)
        # arena-bytes/fill gauges read live stores at scrape time
        from oryx_tpu.common import profiling

        profiling.register_arena(self)

    # -- arena plumbing (callers hold the write lock) -----------------------
    def _ensure_slab(self, k: int) -> None:
        # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
        if self._slab is None:
            # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
            cap = max(self._initial_rows, self._reserve_rows, 1)
            self._slab = np.zeros((cap, k), dtype=np.float32)
            # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
            self._recent = np.zeros(cap, dtype=bool)
            # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
            self._pos_of_row = np.zeros(cap, dtype=np.int32)
        elif self._slab.shape[1] != k:
            raise ValueError(
                f"factor width changed: arena holds {self._slab.shape[1]}-"
                f"feature rows, got {k} (a new feature count means a new "
                "model generation, which gets a fresh store)"
            )

    def _grow(self, need_rows: int) -> None:
        # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
        cap = self._slab.shape[0]
        new_cap = max(cap, 1)
        while new_cap < need_rows:
            new_cap *= 2
        if new_cap == cap:
            return
        slab = np.zeros((new_cap, self._slab.shape[1]), dtype=np.float32)
        # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
        slab[: self._n_alloc] = self._slab[: self._n_alloc]
        self._slab = slab
        for name, dtype in (("_recent", bool), ("_pos_of_row", np.int32)):
            old = getattr(self, name)
            grown = np.zeros(new_cap, dtype=dtype)
            grown[: old.shape[0]] = old
            setattr(self, name, grown)

    def _append_pos(self, row: int) -> None:
        # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
        if self._n_pos >= self._rowmap.shape[0]:
            grown = np.empty(max(16, 2 * self._rowmap.shape[0]), dtype=np.int32)
            grown[: self._n_pos] = self._rowmap[: self._n_pos]
            self._rowmap = grown
        self._rowmap[self._n_pos] = row
        # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
        self._pos_of_row[row] = self._n_pos
        self._n_pos += 1

    def _alloc_row(self, id_: str) -> int:
        # rows are NEVER recycled: a row, once bound to an id, keeps that
        # binding for the lifetime of the slab lineage (grow copies rows in
        # place; structural changes re-pack into a FRESH slab + index).
        # Consumers holding a pinned (slab, rows) snapshot view therefore
        # can never see another id's factors at a captured row — the
        # host-side analogue of the device path's double-buffered matrices
        # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
        if self._n_alloc >= self._slab.shape[0]:
            self._grow(self._n_alloc + 1)
        row = self._n_alloc
        self._n_alloc += 1
        # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
        self._ids.add(id_, row)
        self._append_pos(row)
        return row

    def _live_rows(self) -> np.ndarray:
        # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
        return self._rowmap[: self._n_pos]

    def _decode_ids(self, rows) -> list:
        # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
        dec = self._ids.decode
        return [dec(int(r)) for r in rows]

    def _rebuild_structural(self, keep_rows: np.ndarray,
                            keep_recent: bool) -> None:
        """Re-pack the surviving rows into a FRESH slab + interned id index
        (caller holds the write lock and handles version bookkeeping).

        Every row-freeing change goes through here, which upholds the
        pinned-snapshot invariant: the OLD slab/index objects are never
        mutated again, so an in-flight request's captured (slab, rows)
        rescore view and an out-of-lock id decode both stay consistent no
        matter how the live store moves on. Capacity shrinks to fit when
        the survivor fill falls below ``oryx.serving.arena.min-fill``
        (against the CONFIGURED floor — a reserve()-presized store still
        gives its memory back after GC), else it is kept."""
        # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
        old_slab, old_ids = self._slab, self._ids
        live = len(keep_rows)
        cap = old_slab.shape[0]
        if live <= cap * _DEFAULT_MIN_FILL:
            cap = max(self._initial_rows, 1)
            while cap < live:
                cap *= 2
        k = old_slab.shape[1]
        slab = np.zeros((cap, k), dtype=np.float32)
        slab[:live] = old_slab[keep_rows]
        ids = _IdIndex(cap)
        for i, row in enumerate(keep_rows):
            ids.add(old_ids.decode(int(row)), i)
        recent = np.zeros(cap, dtype=bool)
        if keep_recent and live:
            # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
            recent[:live] = self._recent[keep_rows]
        self._slab, self._ids, self._recent = slab, ids, recent
        # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
        self._rowmap = np.arange(live, dtype=np.int32)
        # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
        self._pos_of_row = np.zeros(cap, dtype=np.int32)
        self._pos_of_row[:live] = np.arange(live)
        # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
        self._n_pos = live
        # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
        self._n_alloc = live
        # analyze: ignore[lock-discipline] -- runs only under self._lock.write(), taken by its callers
        self._pending.clear()

    def reserve(self, rows: int) -> None:
        """Presize the arena for ``rows`` total rows — a MODEL handoff knows
        its id count (the PMML meta's x_ids/y_ids), and presizing skips the
        doubling-growth copies and their 1.5× transient peak. One-shot: it
        sizes the NEXT allocation only and never raises the compaction
        floor (oryx.serving.arena.initial-rows keeps governing shrink)."""
        with self._lock.write():
            if self._slab is None:
                self._reserve_rows = max(self._reserve_rows, rows)
            elif rows > self._slab.shape[0]:
                self._grow(rows)

    # -- map ops (FeatureVectorsPartition:55-108) ---------------------------
    def set_vector(self, id_: str, vector: np.ndarray) -> None:
        v = np.asarray(vector, dtype=np.float32)
        with self._lock.write():
            self._ensure_slab(v.shape[0])
            row = self._ids.lookup(id_)
            was_new = row < 0
            if was_new:
                row = self._alloc_row(id_)
            self._slab[row] = v
            self._recent[row] = True
            self._pending.add(row)
            self._version += 1
            self._log.append((self._version, row, was_new))

    def bulk_load(self, ids, matrix: np.ndarray, adopt: bool = False) -> None:
        """Set many vectors in one write-lock pass — the fast path for whole-
        model handoffs (MODEL-REF factor files, synthetic bench models). The
        matrix is COPIED into the arena: later point updates rewrite slab
        rows in place and must never mutate the caller's array.

        ``adopt`` hands the array itself over, where the store is empty and
        the array can be the slab as it is (C-contiguous float32 it may
        write to, no larger capacity asked for): the caller gives it up. At
        20M × 250f a copy is 20 GB beside the caller's 20 GB, more than a
        one-chip host's memory holds (docs/performance.md "int8 on one
        chip")."""
        matrix = np.asarray(matrix, dtype=np.float32)
        ids = list(ids)
        with self._lock.write():
            if self._slab is None and len(ids) and len(set(ids)) != len(ids):
                # duplicate ids in one handoff: the fast path's positional
                # adds would leave BOTH rows live (the stale first
                # occurrence scored forever); route through the per-id
                # lookup path below, which collapses duplicates last-wins
                # exactly like the pre-arena dict store
                self._ensure_slab(matrix.shape[1])
            if self._slab is None and len(ids):
                # empty store: one slab-sized copy, rows in handoff order
                k = matrix.shape[1]
                cap = max(self._initial_rows, self._reserve_rows, len(ids), 1)
                if (adopt and cap == len(ids) and matrix.flags.c_contiguous
                        and matrix.flags.writeable):
                    self._slab = matrix
                else:
                    self._slab = np.zeros((cap, k), dtype=np.float32)
                    _copy_rows(self._slab[: len(ids)], matrix)
                self._ids = _IdIndex(cap)
                self._ids.add_many(ids, 0)
                self._rowmap = np.arange(len(ids), dtype=np.int32)
                self._pos_of_row = np.zeros(cap, dtype=np.int32)
                self._pos_of_row[: len(ids)] = np.arange(len(ids))
                self._n_pos = len(ids)
                self._n_alloc = len(ids)
                self._recent = np.zeros(cap, dtype=bool)
                self._recent[: len(ids)] = True
            elif len(ids):
                self._ensure_slab(matrix.shape[1])
                # growth stays on-demand in _alloc_row (amortized doubling):
                # pre-growing by len(ids) would count already-present ids as
                # new rows and permanently double the slab on a same-id
                # re-handoff
                for i, id_ in enumerate(ids):
                    row = self._ids.lookup(id_)
                    if row < 0:
                        row = self._alloc_row(id_)
                    self._slab[row] = matrix[i]
                    self._recent[row] = True
            self._pending.clear()
            self._version += 1
            self._rebuild_needed_at = self._version

    def get_vector(self, id_: str) -> "np.ndarray | None":
        with self._lock.read():
            row = self._ids.lookup(id_)
            # a COPY: slab rows are rewritten in place by later point
            # updates, and handing out live views would let a held result
            # change under the caller (the dict store's replace-on-write
            # semantics, preserved)
            return self._slab[row].copy() if row >= 0 else None

    def get_vectors(self, ids) -> list:
        """Batched lookup under ONE read lock — per-call lock overhead
        otherwise dominates microbatch fold-in gathers (2 acquisitions per
        interaction)."""
        with self._lock.read():
            lk = self._ids.lookup
            return [
                self._slab[row].copy() if (row := lk(i)) >= 0 else None
                for i in ids
            ]

    def remove_vector(self, id_: str) -> None:
        """Structural: the survivors re-pack into a fresh slab (O(live) —
        removals are rare; reference semantics only remove via model GC)."""
        with self._lock.write():
            row = self._ids.lookup(id_)
            self._version += 1
            if row >= 0:
                live = self._live_rows()
                self._rebuild_structural(live[live != row], keep_recent=True)
                self._rebuild_needed_at = self._version

    def size(self) -> int:
        with self._lock.read():
            return self._n_pos

    def ids(self) -> list:
        with self._lock.read():
            return self._decode_ids(self._live_rows())

    def retain_recent_and_ids(self, ids: "set[str]") -> None:
        """GC on new-model handoff: drop vectors neither recently updated nor
        in the new model (FeatureVectorsPartition.retainRecentAndIDs). The
        survivors re-pack into a fresh slab, shrinking capacity when the
        fill falls below ``oryx.serving.arena.min-fill``."""
        with self._lock.write():
            self._version += 1
            self._rebuild_needed_at = self._version
            if self._slab is None:
                return
            keep = self._recent.copy()
            for id_ in ids:
                row = self._ids.lookup(id_)
                if row >= 0:
                    keep[row] = True
            live = self._live_rows()
            self._rebuild_structural(live[keep[live]], keep_recent=False)

    # -- arena telemetry (scrape-time gauges; see common/profiling.py) ------
    def arena_nbytes(self) -> int:
        # analyze: ignore[lock-discipline] -- scrape-time advisory read; a torn sample skews one gauge scrape, never store state
        slab = self._slab
        return int(slab.nbytes) if slab is not None else 0

    def arena_fill(self) -> float:
        # analyze: ignore[lock-discipline] -- scrape-time advisory read; a torn sample skews one gauge scrape, never store state
        slab = self._slab
        if slab is None or slab.shape[0] == 0:
            return 0.0
        # analyze: ignore[lock-discipline] -- scrape-time advisory read; a torn sample skews one gauge scrape, never store state
        return self._n_pos / slab.shape[0]

    # -- host snapshot API (no device work; the int8 serving path) ----------
    def host_matrix(self, values: bool = True
                    ) -> "tuple[list, np.ndarray | None, int, tuple]":
        """(ids, row-aligned float32 copy, version, (slab, rows)): the full
        host snapshot. The copy is one fancy-index gather of the live rows —
        consumers own it (``values=False``: no copy, None in its place, for
        a consumer that reads the rows out of the pinned pair a block at a
        time). The trailing (slab, rows) pair pins THIS order
        epoch for later exact-rescore gathers (:class:`_QuantSnapshot`):
        row indices stay valid for the slab object they were captured with,
        no matter what the live store does afterwards.

        Only the value gather runs under the read lock (consistency needs
        writers excluded); the per-row id decode — Python-string work that
        dominates at reference scale — happens OUTSIDE, against captures
        that structural changes replace rather than mutate."""
        with self._lock.read():
            slab = self._slab
            rows = self._live_rows().copy()
            index = self._ids
            version = self._version
            empty = slab is None or not rows.size
            host = None if empty or not values else slab[rows]
        dec = index.decode
        ids = [dec(int(r)) for r in rows]
        if empty:
            return ids, np.zeros((0, 0), dtype=np.float32), version, (slab, rows)
        return ids, host, version, (slab, rows)

    def delta_info(self, since_version: int, since_len: int) -> "HostDelta | None":
        """Compose everything written since ``since_version`` for a consumer
        whose snapshot held the first ``since_len`` ids of the order. None
        when a structural change happened or the write log no longer covers
        the gap — the consumer then rebuilds from :meth:`host_matrix`.
        Values are CURRENT slab copies (newest-wins compose)."""
        with self._lock.read():
            if self._rebuild_needed_at > since_version:
                return None
            if self._version == since_version:
                return HostDelta(self._version, [], None, [], None)
            # every version bump since `since_version` is either structural
            # (caught above) or a logged set_vector; if the bounded log's
            # oldest retained entry skips past since_version+1, writes in
            # the gap were evicted and coverage is broken
            if not self._log or self._log[0][0] > since_version + 1:
                return None
            # newest-first walk, stopping at the consumer's version: the
            # log holds up to 65536 entries and a steady-state delta is a
            # handful — O(delta), not O(log)
            changed_rows: set = set()
            for v, row, _was_new in reversed(self._log):
                if v <= since_version:
                    break
                changed_rows.add(row)
            appended = [int(r) for r in self._rowmap[since_len: self._n_pos]]
            changed = sorted(changed_rows - set(appended))
            changed_vals = (
                self._slab[np.asarray(changed, dtype=np.int64)]
                if changed else None
            )
            appended_rows = np.asarray(appended, dtype=np.int64)
            appended_vals = (
                self._slab[appended_rows] if appended else None
            )
            return HostDelta(
                self._version, self._decode_ids(changed), changed_vals,
                self._decode_ids(appended), appended_vals,
                appended_rows=appended_rows, slab=self._slab,
            )

    # -- device materialization --------------------------------------------
    def materialize(self):
        """(ids, device matrix) snapshot; incremental when only point updates
        happened since the cache (one batched scatter + one append — never a
        full host→device upload), full rebuild on structural changes.

        Race-free: the version and pending set are read under the read lock
        (writers excluded), and the cache critical section is serialized, so
        a concurrent write strictly invalidates this materialization. The
        full-rebuild device upload happens OUTSIDE the locks (it can take
        seconds at reference scale and must not stall UP-consumer writes);
        the incremental path only dispatches async device ops and commits
        inline."""
        import jax.numpy as jnp

        with self._lock.read(), self._cache_lock:
            version = self._version
            if self._cached_version == version:
                return self._cached_ids, self._cached_matrix
            pending, self._pending = self._pending, set()
            if (
                self._cached_matrix is not None
                and self._rebuild_needed_at <= self._cached_version
                and pending
            ):
                cached_len = len(self._cached_ids)
                # appended rows keep INSERTION order: the order's tail past
                # the cached length is exactly the new rows, in sequence
                new_rows = [int(r) for r in
                            self._rowmap[cached_len: self._n_pos]]
                changed_idx, changed_rows = [], []
                for row in pending:
                    pos = int(self._pos_of_row[row])
                    if pos < cached_len:
                        changed_idx.append(pos)
                        changed_rows.append(row)
                # ONE host gather covering the whole delta (counted by the
                # upload-seam tests), split into scatter + append
                vals = _host_gather(self._slab, changed_rows + new_rows)
                changed_vals = vals[: len(changed_rows)]
                new_vecs = vals[len(changed_rows):]
                new_ids = self._decode_ids(new_rows)
                prev_mat = self._cached_matrix
                if self._mesh is None:
                    mat = prev_mat
                    if changed_idx:
                        mat = mat.at[
                            jnp.asarray(changed_idx, dtype=jnp.int32)
                        ].set(jnp.asarray(changed_vals))
                    if new_ids:
                        mat = jnp.concatenate([mat, jnp.asarray(new_vecs)])
                else:
                    mat = self._sharded_step(
                        prev_mat, cached_len, changed_idx, changed_vals,
                        new_vecs)
                # new list: snapshots holding the previous ids list stay valid
                ids = self._cached_ids + new_ids
                self._transitions.append(Transition(
                    prev_mat, mat,
                    np.asarray(changed_idx, dtype=np.int64), len(new_ids),
                    cached_len,
                ))
                self._cached_ids = ids
                self._cached_matrix = mat
                self._cached_version = version
                return ids, mat

            # full rebuild (first build, bulk handoff, removals, GC):
            # capture the host copy under the locks; the device upload AND
            # the per-row Python id decode — both expensive at reference
            # scale — run outside so UP-consumer writes are never stalled
            # (the captured index object's row→id bindings are frozen:
            # rows are never recycled, structural changes swap in fresh
            # slab/index objects)
            rows = self._live_rows().copy()
            index = self._ids
            host = (
                _host_gather(self._slab, rows)
                if rows.size
                else np.zeros((0, 0), dtype=np.float32)
            )
        dec = index.decode
        ids = [dec(int(r)) for r in rows]
        mat = self._to_device(host) if host.size else None
        with self._cache_lock:
            if version > self._cached_version:
                self._cached_ids = ids
                self._cached_matrix = mat
                self._cached_version = version
                self._transitions.clear()
            return self._cached_ids, self._cached_matrix

    def _to_device(self, host: np.ndarray):
        """The full rebuild's upload. One device: one array, as ever. With a
        mesh: each device receives only its own row block from ``host``."""
        import jax.numpy as jnp

        if self._mesh is None:
            return jnp.asarray(host)
        from oryx_tpu.common import spans
        from oryx_tpu.parallel.mesh import put_row_sharded

        with spans.span(
            "snapshot.shard_upload",
            attributes={"bytes": int(host.nbytes),
                        "devices": int(self._mesh.shape[self._shard_axis])},
        ):
            mat = put_row_sharded(host, self._mesh, self._shard_axis)
            mat.block_until_ready()  # the span times the copies, not the enqueue
        return mat

    def _sharded_step(self, prev_mat, n_old: int, changed_idx, changed_vals,
                      new_vecs):
        """The incremental step under the mesh's row sharding: changed rows
        scattered, new rows written into the zero padding while it lasts
        (the shape, and every program compiled for it, stays) or appended
        under a new padded row count. Never leaves the sharding."""
        import jax.numpy as jnp

        from oryx_tpu.parallel.mesh import padded_rows, row_sharding

        scatter, append = _sharded_row_ops(
            row_sharding(self._mesh, self._shard_axis))
        idx = list(changed_idx)
        vals = changed_vals
        n_new = len(new_vecs)
        mat = prev_mat
        if n_new and n_old + n_new <= prev_mat.shape[0]:
            idx = idx + list(range(n_old, n_old + n_new))
            vals = np.concatenate([changed_vals, new_vecs])
            n_new = 0
        if idx:
            mat = scatter(mat, jnp.asarray(idx, dtype=jnp.int32),
                          jnp.asarray(vals))
        if n_new:
            rows = padded_rows(n_old + n_new, self._mesh, self._shard_axis)
            tail = np.zeros((rows - n_old, new_vecs.shape[1]), np.float32)
            tail[:n_new] = new_vecs
            mat = append(mat, jnp.asarray(tail), n_old=n_old)
        return mat

    def delta_since(self, from_mat, to_mat) -> "tuple[np.ndarray, int] | None":
        """Compose the recorded incremental steps from ``from_mat`` up to
        ``to_mat``: (changed row indices within from_mat's rows, rows
        appended). None when the chain is broken (full rebuild happened, a
        generation was garbage-collected, or either matrix is unknown) — the
        consumer then rebuilds its derived state from scratch."""
        with self._cache_lock:
            chain = list(self._transitions)
        if from_mat is to_mat:
            return np.empty(0, dtype=np.int64), 0
        start = next(
            (i for i, t in enumerate(chain) if t.prev_ref() is from_mat), None
        )
        if start is None:
            return None
        # continuity within the log is structural (each step's prev IS the
        # previous step's output, and a full rebuild clears the log), so
        # intermediate generations need no liveness check — only the two
        # endpoints, which the caller holds alive, anchor the walk
        n_base = chain[start].n_prev
        changed: set = set()
        n_new = 0
        for t in chain[start:]:
            # rows rewritten inside the appended tail are covered by the
            # consumer's whole-tail refresh; only base rows need listing
            changed.update(int(i) for i in t.changed_idx if i < n_base)
            n_new += t.n_new
            if t.new_ref() is to_mat:
                return np.asarray(sorted(changed), dtype=np.int64), n_new
        return None

    def get_vtv(self):
        """Gramian V^T V (FeatureVectors.getVTV). When the device
        materialization cache is CURRENT (f32/bf16 serving — y_snapshot
        keeps it fresh) the matmul runs on the device matrix that already
        exists: no slab copy, no store-lock hold. Otherwise — the speed
        tier and the int8 serving mode, where no device f32 copy may be
        forced into HBM — it computes from the slab with host BLAS."""
        with self._lock.read():
            with self._cache_lock:
                mat = (
                    self._cached_matrix
                    if self._cached_version == self._version else None
                )
            host = None
            if mat is None:
                if self._slab is None or self._n_pos == 0:
                    return None
                host = self._slab[self._live_rows()]
        if mat is not None:
            return np.asarray(mat.T @ mat)  # device matmul, no locks held
        return np.matmul(host.T, host)
