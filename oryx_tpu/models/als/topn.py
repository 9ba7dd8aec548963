"""What every top-N scan backend shares, below both ``serving.py`` (the flat
float, mesh-split and int8 views of Y, and the flush that drives them) and
``ivf.py`` (the inverted-file view): the seam itself (:class:`_Snapshot` —
a device view of Y that owns its program, its width and its warm
signatures), the arena-backed exact rescore of the two approximate views
(:class:`_ArenaSnapshot`), and the host-side pieces of a query that do not
depend on the view (the over-fetch room and the dropping of excluded rows,
candidate collection).

Nothing here imports ``serving`` or ``ivf``: the arrows point one way.
"""

from __future__ import annotations

import concurrent.futures
import functools
import math
import os
import queue

import jax
import jax.numpy as jnp
import numpy as np

from oryx_tpu.common import metrics as metrics_mod
from oryx_tpu.common import profiling


def _round_up_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


#: Known-item exclusion is what the DEFAULT /recommend sends
#: (considerKnownItems=false), and it is done by over-fetching: the scan —
#: the same fused matmul + approximate top-k that answers a request with
#: nothing to leave out — is asked for ``how_many`` plus room for the flush's
#: longest history, and the known rows are dropped from the list on the host
#: (the best ``k + E`` of everything, less at most ``E`` known rows, begins
#: with the best ``k`` of the rest). The room is one of these, so a batch size
#: has three programs, all of which the warm ladder compiles: none, the
#: common histories, the long ones. With the default ``how_many`` of 10 the
#: widths are 16, 64 and 256. A longer history is asked at the widest; only
#: where what is left of a list then falls short of ``how_many`` (more of the
#: user's own items among their best 256 than there was room for) is the
#: query answered again, alone, by the widening single-query path.
_OVERFETCH_ROOM = (0, 48, 240)

#: Host-side quantization chunk: bounds the transient f32 gather while
#: building a full quantized snapshot (2^16 rows × 50f ≈ 13 MB per chunk
#: instead of one n×k f32 copy next to the arena slab).
_QUANT_CHUNK = 1 << 16


def _id_lists(ids, vals: np.ndarray, idx: np.ndarray, how_many: int) -> list:
    """(B, >= how_many) scores and row indices, best first -> per query its
    ``(id, score)`` list; masked candidates (-inf from the scan) left out."""
    vb, ib = vals[:, :how_many], idx[:, :how_many]
    return [
        [(ids[int(i)], float(v)) for v, i in zip(vb[b], ib[b])
         if np.isfinite(v)]
        for b in range(len(vb))
    ]


def _collect(snap, vals, idx, want, allowed, rescore,
             dropped=None) -> list[tuple[str, float]]:
    """One query's candidates, best first, as ``(id, score)``: masked ones
    (-inf) end the list, ``dropped`` rows (the query's exclusions) and ids
    the hooks refuse are left out."""
    if dropped is not None and len(dropped):
        keep = ~np.isin(idx, dropped)
        vals, idx = vals[keep], idx[keep]
    out: list[tuple[str, float]] = []
    for v, i in zip(vals, idx):
        if not np.isfinite(v):
            break
        id_ = snap.ids[int(i)]
        if allowed is not None and not allowed(id_):
            continue
        score = float(v)
        if rescore is not None:
            score = rescore(id_, score)
            if math.isnan(score):
                continue
        out.append((id_, score))
    if rescore is not None:
        out.sort(key=lambda t: -t[1])
    return out


def _room_for(longest: int) -> int:
    """The least over-fetch room that holds a history of ``longest`` rows
    (the widest where none does)."""
    return next((r for r in _OVERFETCH_ROOM if r >= longest),
                _OVERFETCH_ROOM[-1])


#: Past this many comparisons (lists x their width x the longest history) a
#: flush's rows are looked for a query at a time, not in one broadcast.
_DROP_AT_ONCE = 1 << 21


def _drop_rows(vals: np.ndarray, idx: np.ndarray, rows: np.ndarray):
    """``(B, W)`` lists, best first, with each query's ``rows[b]`` (``(B,
    E)`` row indices, -1 where a query has fewer) taken out: what is left
    keeps its order and moves to the front, -inf fills the end. Returns the
    lists and, a query, how many rows were taken out of its list. Most
    flushes' lists hold none of their queries' rows: one comparison says so
    and nothing is copied."""
    if idx.size * rows.shape[1] <= _DROP_AT_ONCE:
        hit = (idx[:, :, None] == rows[:, None, :]).any(axis=-1)
    else:
        hit = np.stack([np.isin(i, r) for i, r in zip(idx, rows)])
    if not hit.any():
        return vals, idx, np.zeros(len(idx), dtype=np.int64)
    order = np.argsort(hit, axis=1, kind="stable")
    gone = np.take_along_axis(hit, order, axis=1)
    vals = np.where(gone, -np.inf, np.take_along_axis(vals, order, axis=1))
    return vals, np.take_along_axis(idx, order, axis=1), hit.sum(axis=1)


def _quantize_rows(mat: np.ndarray) -> "tuple[np.ndarray, np.ndarray]":
    """Per-row symmetric int8 quantization: scale_i = max|row_i| / 127.
    Zero rows get scale 1 (their dots are exactly 0 either way)."""
    if mat.size == 0:
        return (np.zeros(mat.shape, dtype=np.int8),
                np.ones(mat.shape[0], dtype=np.float32))
    amax = np.max(np.abs(mat), axis=1)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(mat / scale[:, None]), -127, 127).astype(np.int8)
    return q, scale


def _quantize_chunked(host: np.ndarray):
    """``(q, scale, norms)`` of a whole host matrix, ``_QUANT_CHUNK`` rows
    at a time so the transient stays bounded at reference scale."""
    n, k = host.shape
    q = np.empty((n, k), dtype=np.int8)
    scale = np.empty(n, dtype=np.float32)
    norms = np.empty(n, dtype=np.float32)
    for a in range(0, n, _QUANT_CHUNK):
        b = min(n, a + _QUANT_CHUNK)
        q[a:b], scale[a:b] = _quantize_rows(host[a:b])
        norms[a:b] = np.linalg.norm(host[a:b], axis=1)
    return q, scale, norms


#: Rows a step of a full int8 build quantizes and uploads, in pieces of
#: ``_QUANT_PIECE`` rows side by side on a few threads (numpy drops the GIL
#: inside each pass). The host's transient is one block of int8 rows and two
#: float32 pieces a thread — never a second copy of Y, which at 20M × 250f
#: (20 GB) no one-chip host holds beside the arena.
_QUANT_BLOCK = 1 << 20
_QUANT_PIECE = 1 << 14
_QUANT_WORKERS = min(8, os.cpu_count() or 1)


def _quantize_piece(slab, rows, part, tmp, q, scale, norms) -> None:
    """``slab[rows]`` into ``q``, ``scale`` and ``norms``: the passes of
    :func:`_quantize_rows` and ``np.linalg.norm``, each into the caller's
    float32 scratch (``part``, ``tmp``). A pass that allocated its result
    would free 16 MB seven times a piece — 150 GB of it at 20M × 250f,
    which a sandboxed host gives back more slowly than the threads ask."""
    part, tmp = part[:len(rows)], tmp[:len(rows)]
    # "clip": the rows are the store's own, and numpy would buffer ``out``
    # whole under the default "raise"
    np.take(slab, rows, axis=0, out=part, mode="clip")
    np.abs(part, out=tmp)
    amax = tmp.max(axis=1)
    scale[:] = np.where(amax > 0, amax / 127.0, 1.0)
    np.divide(part, scale[:, None], out=tmp)
    np.rint(tmp, out=tmp)
    np.clip(tmp, -127, 127, out=tmp)
    q[:] = tmp
    np.multiply(part, part, out=tmp)
    np.sqrt(np.add.reduce(tmp, axis=1), out=norms)


def _quantize_blocks(slab: np.ndarray, rows: np.ndarray):
    """``(start, q, scale, norms)`` of each ``_QUANT_BLOCK`` rows of the
    pinned view ``slab[rows]``, in order: the values are those of
    :func:`_quantize_chunked` over the gathered copy, bit for bit."""
    n, k = len(rows), slab.shape[1]
    scratch: queue.SimpleQueue = queue.SimpleQueue()
    for _ in range(_QUANT_WORKERS):
        scratch.put(np.empty((2, min(n, _QUANT_PIECE), k), dtype=np.float32))
    with concurrent.futures.ThreadPoolExecutor(_QUANT_WORKERS) as pool:
        for start in range(0, n, _QUANT_BLOCK):
            size = min(n, start + _QUANT_BLOCK) - start
            q = np.empty((size, k), dtype=np.int8)
            scale = np.empty(size, dtype=np.float32)
            norms = np.empty(size, dtype=np.float32)

            def piece(a: int):
                b = min(size, a + _QUANT_PIECE)
                mine = scratch.get()
                try:
                    _quantize_piece(slab, rows[start + a:start + b], *mine,
                                    q[a:b], scale[a:b], norms[a:b])
                finally:
                    scratch.put(mine)

            list(pool.map(piece, range(0, size, _QUANT_PIECE)))
            yield start, q, scale, norms


@functools.partial(jax.jit, donate_argnums=0)
def _set_rows(whole, block, start):
    return jax.lax.dynamic_update_slice_in_dim(whole, block, start, axis=0)


def _upload_blocks(blocks, n: int) -> list:
    """The device arrays of ``n`` rows that the host ``blocks`` (``(start,
    part, ...)``, in order) make up, each part written into its place as it
    comes: the device never holds a block twice, the host never all of
    them. One block that holds every row is the array itself."""
    whole = None
    for start, *parts in blocks:
        parts = [jnp.asarray(p) for p in parts]
        if len(parts[0]) == n:
            return parts
        if whole is None:
            whole = [jnp.zeros((n,) + p.shape[1:], p.dtype) for p in parts]
        whole = [_set_rows(w, p, start) for w, p in zip(whole, parts)]
        # a block is on the device before the next is made: uploads left to
        # queue behind a faster quantizer hold every block they wait with
        # (at 20M x 250f, 5 GB of rows and as much again in staging)
        jax.block_until_ready(whole)
    return whole


_RESCORED_ROWS = metrics_mod.default_registry().counter(
    "oryx_serving_rescored_rows_total",
    "Candidate rows gathered from the host factor arena for the exact "
    "float32 rescore of an int8 or IVF scan's flushes",
)


class _Fed:
    """In a plan's step after the first, the operand that is the result of
    the step before: the flush puts that result in its place, the warm
    ladder its shape."""

    def __init__(self, shape, dtype):
        self.struct = jax.ShapeDtypeStruct(shape, dtype)


def _operands(args: tuple, fed=None) -> tuple:
    """A step's operands with the step before's result ``fed`` (or, for
    ``None``, that result's shape) where they hold a :class:`_Fed`."""
    return tuple(
        (a.struct if fed is None else fed) if type(a) is _Fed else a
        for a in args)


class _Snapshot:
    """An immutable device view of Y, and so the scan backend:
    ``ALSServingModel`` holds whichever kind its options resolved to and
    drives every one through these operations, never asking which it is.

    * ``source(store)`` then ``current(store, lsh, prev, source, **options)``
      (classmethods): the view of the store as it stands — ``prev`` itself,
      an incremental step from it, or a rebuild. ``current`` is called under
      the model's snapshot lock; ``source`` just before it, outside the
      lock, for what a backend reads of the store that may take long.
    * ``batch_width(how_many, filtering, room)``: the static width of a
      batch's program — the width rule, once per backend. ``room`` is how
      many more candidates than ``how_many`` every query must get back: the
      flush's exclusions are rows dropped from the list afterwards
      (``_OVERFETCH_ROOM``), never an operand of the program.
    * ``plan(qs, lut, width)`` → steps ``(fn, args, cost_key)``, in order:
      each a jitted program, its operands (static width last) and its cost
      key, for a query batch ``qs`` and, where the view is LSH-masked, the
      per-query ``lut``. The two may be arrays (the flush runs the steps) or
      ``jax.ShapeDtypeStruct``s (the warm ladder compiles them): the ladder
      compiles exactly what the flush dispatches. A later step takes the
      result of the one before it where its operands hold a :class:`_Fed`.
    * ``select(qs_host, width)`` → the flush's host-side choice of what the
      device reads (the view that lists its windows under LSH: its own
      stage); ``batch_operands(qs_host, selection)`` → the per-batch
      operands ``plan`` takes as ``lut``, on the device: None, the per-query
      LSH lookup table of a view that masks every row, or the placed
      selection; ``selections(batch, width)`` → every such operand a batch
      can bring, as shapes, for the warm ladder.
    * ``dispatched(batch, width)``: called after a scan's last step is on
      its way — a backend's own counters.
    * ``place(host)`` / ``struct(shape, dtype)``: a batch-shaped operand on
      the device(s), as an array or as a shape.
    * ``rescore(qs_host, vals, idx)``: exact f32 re-ranking of approximate
      candidates; ``None`` where the scan's scores are final.
    * ``candidates(scan, q_host, want, hooks)``: one query's ``(vals, idx)``
      candidate rows, then wider ones while any remain — the backend's
      widening policy (``want`` counts the rows the caller will drop).
      ``scan`` is the flush's device side, for the backends that widen by
      querying again.
    * ``cosine_candidates(qs_host, want)``: the same for mean cosine.
    * ``device_arrays()``: what it holds on the device; ``scanned``: the
      one of them every batch program reads, whose shape keys the jit
      signatures (``__init__`` takes it as it is at construction).
    """

    mesh = None     # set only by the view whose rows are split over one
    rescore = None

    @classmethod
    def source(cls, store):
        return None

    def dispatched(self, batch: int, width) -> None:
        pass

    def select(self, qs_host: np.ndarray, width):
        return None

    def batch_operands(self, qs_host: np.ndarray, selection):
        """Per-query LSH candidate masks where the view masks every row by
        its bucket: a (B, num_buckets) lookup table, indexed by each row's
        bucket on the device."""
        if self.lsh is None:
            return None
        return self.place(self.lsh.get_candidate_lut(qs_host))

    def selections(self, batch: int, width) -> list:
        if self.lsh is None:
            return [None]
        return [self.struct((batch, self.lsh.num_buckets), jnp.bool_)]

    def __init__(self, ids, scanned, lsh=None, prev=None,
                 incremental: bool = False):
        self.ids = ids
        # the LSH whose buckets this view carries (None: nothing is masked)
        self.lsh = lsh
        if prev is not None and incremental:
            # id→idx is append-only across incremental generations; sharing
            # the dict avoids an O(n) rebuild per microbatch (extra entries
            # in the older snapshot only name rows its lists never hold)
            self.id_to_idx = prev.id_to_idx
            for i in range(len(prev.ids), len(ids)):
                self.id_to_idx[ids[i]] = i
        else:
            self.id_to_idx = {s: i for i, s in enumerate(ids)}
        # lazy cost-registration marks (see serving._dispatch): per GENERATION
        # so a model swap re-registers against the new shapes, but carried
        # across same-shape incremental snapshots (point-update microbatches
        # whose dispatch signatures — and therefore per-call costs — are
        # unchanged). Marked even when registration fails, so a backend
        # without usable cost_analysis never re-pays lower+compile per call.
        if prev is not None and (getattr(prev.scanned, "shape", None)
                                 == getattr(scanned, "shape", None)):
            self.cost_keys_attempted = prev.cost_keys_attempted
        else:
            self.cost_keys_attempted: set = set()

    @property
    def n(self) -> int:
        return len(self.ids)

    @property
    def servable(self) -> bool:
        """There are item rows on the device to answer from."""
        return bool(self.ids) and self.scanned is not None

    def place(self, host: np.ndarray):
        return jnp.asarray(host)

    def struct(self, shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    def bucket_union(self, query_vecs: np.ndarray) -> np.ndarray:
        """(num_buckets,) bool: the union of the query vectors' LSH candidate
        buckets, mirroring the reference's per-partition candidate scan."""
        lut = np.zeros(self.lsh.num_buckets, dtype=bool)
        for query_vec in query_vecs:
            lut[self.lsh.get_candidate_indices(query_vec)] = True
        return lut


class _ArenaSnapshot(_Snapshot):
    """A view whose device arrays only CHOOSE candidates (int8 rows, flat or
    in IVF cells): the final ranking is an exact f32 rescore of the top
    ``rescore-factor × how_many`` rows, gathered from the host factor arena.

    Built from the arena's HOST snapshot (``host_matrix``) and kept current
    with composed host deltas (``delta_info``); ``version`` anchors the
    next delta. A subclass supplies ``build`` (full) and ``from_delta``
    (incremental, or None when only a rebuild will do)."""

    #: ``build`` reads the row-aligned float32 copy of the store; a subclass
    #: that reads the rows out of the pinned ``(slab, rows)`` pair instead
    #: says False and is handed None in the copy's place.
    host_copy = True

    def __init__(self, ids, version: int, scanned, lsh, slab, slab_rows,
                 rescore_factor: float, prev=None, incremental: bool = False):
        super().__init__(ids, scanned, lsh, prev, incremental)
        self.version = version
        self.rescore_factor = rescore_factor
        # pinned exact-rescore view: THIS snapshot's slab object + its row
        # indices, captured by the store in the same order epoch as `ids`.
        # Structural store changes (GC, compaction) replace the live
        # slab/rowmap and never disturb this pair, so a rescore can never
        # crash on, or misalign against, a concurrently mutated store. A
        # point update rewriting a captured row in place is visible here —
        # the rescore ranks with fresher factors than the scan, benign.
        self.slab = slab
        self.slab_rows = slab_rows  # (n,) slab row per snapshot position
        profiling.register_quantized(self)

    @classmethod
    def current(cls, store, lsh, prev, source, **build_options):
        """Incremental (requantize only the rows a speed microbatch touched)
        when the arena's write log covers the gap and the subclass can take
        the step; full rebuild otherwise. The store's f32
        device-materialization cache is never engaged — the arena slab
        itself is the exact-f32 source of truth."""
        if prev is not None and prev.servable:
            delta = store.delta_info(prev.version, len(prev.ids))
            if delta is not None:
                if not delta.changed_ids and not delta.appended_ids:
                    return prev
                nxt = cls.from_delta(prev, delta)
                if nxt is not None:
                    return nxt
        ids, host, version, row_view = store.host_matrix(cls.host_copy)
        return cls.build(ids, host, version, lsh, row_view, prev=prev,
                         **build_options)

    def appended(self, delta):
        """``(ids, slab_rows)`` of the step after this one: ``delta.slab`` is
        the CURRENT slab (a non-structural grow copies rows in place, so
        this view's indices stay valid in it) and the appended ids bring
        their own rows."""
        ids = self.ids + delta.appended_ids
        if not len(delta.appended_ids):
            return ids, self.slab_rows
        return ids, np.concatenate(
            [self.slab_rows, np.asarray(delta.appended_rows, dtype=np.int64)])

    def rescore_width(self, want: int) -> int:
        """Candidates to rescore for ``want`` results: a pow2, so the
        program's signature is stable."""
        return _round_up_pow2(max(int(self.rescore_factor * want), 16))

    def gather_rows(self, positions: np.ndarray) -> np.ndarray:
        """Exact f32 factor rows for snapshot ``positions``, gathered from
        the PINNED slab view (see __init__) — one fancy index."""
        pos = np.clip(np.asarray(positions, dtype=np.int64), 0, self.n - 1)
        return self.slab[self.slab_rows[pos]]

    def rescore(self, qs_host: np.ndarray, vals: np.ndarray, idx: np.ndarray,
                cosine: bool = False) -> "tuple[np.ndarray, np.ndarray]":
        """Exact f32 rescore of the quantized scan's candidates: gather the
        candidate rows from the PINNED arena-slab view (the slab is what
        makes this cheap), recompute exact scores, and return the candidates
        re-ranked by exact score. Masked candidates (-inf from the scan)
        stay -inf. For ``cosine`` the batch dimension is the query-vector
        set of ONE request (mean cosine)."""
        B, R = idx.shape
        _RESCORED_ROWS.inc(B * R)
        rows = self.gather_rows(idx.reshape(-1)).reshape(B, R, -1)
        if cosine:
            # one request, many query vectors: qs_host (Q, k); rows (1, R, k)
            r = rows[0]
            rn = np.linalg.norm(r, axis=1)
            qn = np.linalg.norm(qs_host, axis=1)
            sims = (r @ qs_host.T) / np.maximum(
                rn[:, None] * qn[None, :], 1e-12
            )
            exact = np.mean(sims, axis=1, dtype=np.float32)[None, :]
        else:
            exact = np.einsum("bk,brk->br", qs_host, rows).astype(np.float32)
        exact = np.where(np.isfinite(vals), exact, -np.inf)
        order = np.argsort(-exact, axis=1, kind="stable")
        return (np.take_along_axis(exact, order, axis=1),
                np.take_along_axis(idx, order, axis=1))
