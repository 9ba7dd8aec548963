"""Device-resident IVF (inverted-file) candidate generation over the
factor arena — the sublinear serving scan.

The int8 flat scan (PR 9) still reads every item row per query batch: at
21M x 250f that is ~5.3 GB of HBM per pass, so chip memory bandwidth caps
fleet qps no matter how many replicas the controller adds. This module
clusters the item factors with the in-tree k-means trainer
(models/kmeans/train.fit_index_centroids — deterministic seed, bounded
iterations, empty-cluster reseeding) and keeps the catalog as

  * ``centroids``   (C, k)    f32  — one row per cell,
  * ``cell_pos``    (C, L)    i32  — snapshot positions, -1-padded,
  * ``cell_q``      (C, L, k) i8   — per-row-scaled int8 factors,
  * ``cell_scale``  (C, L)    f32  — the per-row scales,
  * ``cell_norms``  (C, L)    f32  — exact norms (cosine path),
  * ``cell_buckets``(C, L)    i32  — LSH buckets (optional),

all in HBM. A query batch probes the top-P cells by centroid dot product
(one (B,k)x(k,C) matmul), gathers ONLY those cells' int8 rows (a
``lax.scan`` over the P probe columns keeps the gather transient at
B·L·k bytes), scores them quantized, and feeds the top
``rescore-factor x how_many`` candidates to the SAME exact-f32 arena-slab
rescore the flat int8 path uses. Per-query HBM traffic drops from n·k to
P·L·k bytes — sublinear in the catalog once C grows with sqrt(n).

Cells are maintained incrementally from the speed tier's fold-in deltas
riding the arena's write log (``delta_info``): a microbatch requantizes
and reassigns only the rows it touched and rewrites only the affected
cells' device slices — bit-identical to a full rebuild with the same
centroids (tests/test_ivf.py asserts this exactly). A cell overflowing
its padded width, or cell balance drifting past
``oryx.serving.index.rebalance-skew``, falls back to a full re-cluster.

Candidate generation and probing run under their OWN cost keys
(``als.ivf_probe/...``, ``als.ivf_scan/...``) so live MFU / bandwidth
attribution separates the probe from the exact rescore, and the pow2
(batch, probes) signatures ride the serving warm ladder exactly like the
flat programs (zero request-path compiles after a MODEL handoff).
"""

from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
import numpy as np

from oryx_tpu.common import metrics as metrics_mod
from oryx_tpu.models.als.topn import (_ArenaSnapshot, _Fed,
                                      _quantize_chunked, _quantize_rows,
                                      _round_up_pow2)

log = logging.getLogger(__name__)

_INDEX_CELLS = metrics_mod.default_registry().counter(
    "oryx_index_cells_total",
    "IVF index cells created across index (re)builds",
)
_INDEX_PROBED = metrics_mod.default_registry().counter(
    "oryx_index_probed_cells_total",
    "IVF cells probed (batch size x probe width, per candidate scan)",
)
_INDEX_CANDIDATES = metrics_mod.default_registry().counter(
    "oryx_index_candidate_rows_total",
    "Candidate rows emitted by IVF scans for exact f32 rescore",
)
_INDEX_SKEW = metrics_mod.default_registry().gauge(
    "oryx_index_cell_skew",
    "Largest-cell occupancy over the mean (n/cells); the rebalance-skew "
    "bound triggers a re-cluster when this drifts past it",
)

#: Training subsample cap, per cell: k-means fits on at most
#: ``_TRAIN_PER_CELL * cells`` rows (deterministically sampled) — centroid
#: quality saturates well below that while full-catalog training would put
#: an O(n·C·k) matmul per Lloyd sweep on the rebuild path.
_TRAIN_PER_CELL = 64

#: Chunk of rows assigned to cells per device call during a full build —
#: bounds the (chunk, C) distance transient at reference scale.
_ASSIGN_CHUNK = 1 << 16

_KMEANS_SEED = 0x0f1e


def auto_cells(n: int) -> int:
    """Default cell count: the power of two nearest sqrt(n) — the classic
    IVF sizing (probe cost C + scan cost P·n/C balance at C ~ sqrt(n))."""
    if n <= 1:
        return 1
    return max(1, 1 << int(round(math.log2(math.sqrt(n)))))


def probe_cost_key(batch: int, cells: int, probes: int) -> str:
    """Cost-accounting signature of the centroid-probe program."""
    return f"als.ivf_probe/b{batch}/c{cells}/p{probes}"


def scan_cost_key(batch: int, cells: int, probes: int,
                  room: int, lsh: bool) -> str:
    """Cost-accounting signature of the probed-cell candidate scan, a batch
    size, probe width and over-fetch room (topn._OVERFETCH_ROOM)."""
    return (f"als.ivf_scan/b{batch}/c{cells}/p{probes}"
            + (f"+excl{room}" if room else "") + ("+lsh" if lsh else ""))


# -- jitted programs ---------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("probes",))
def _probe_cells(centroids, qs, probes: int):
    """Rank cells by centroid dot product and keep the top ``probes``:
    one (B,k)x(k,C) MXU matmul + top_k — the sublinear scan's only
    full-width-in-C work."""
    scores = jnp.matmul(
        qs, centroids.T, preferred_element_type=jnp.float32
    )  # (B, C)
    _, cells = jax.lax.top_k(scores, probes)
    return cells  # (B, P) int32


@functools.partial(jax.jit, static_argnames=("r",))
def _ivf_candidates(cell_pos, cell_q, cell_scale, qs, cells, r: int):
    """Quantized scores over the probed cells only. ``cells`` is (B, P);
    a ``lax.scan`` over the P probe columns bounds the gather transient at
    one (B, L, k) int8 block — the per-step gathers ARE the scan's HBM
    traffic (P·L·k bytes per query vs n·k for the flat slab). Padding
    slots (cell_pos < 0) mask to -inf before the exact top-k over the
    (B, P·L) candidate pool."""

    def step(_, cell_col):  # cell_col: (B,) — one probe column
        pos = cell_pos[cell_col]       # (B, L) gather
        qm = cell_q[cell_col]          # (B, L, k) int8 gather
        sc = cell_scale[cell_col]      # (B, L)
        s = jnp.einsum(
            "bk,blk->bl", qs, qm.astype(qs.dtype),
            preferred_element_type=jnp.float32,
        ) * sc
        s = jnp.where(pos >= 0, s, -jnp.inf)
        return None, (s, pos)

    _, (scores, pos) = jax.lax.scan(step, None, cells.T)
    b = qs.shape[0]
    scores = jnp.moveaxis(scores, 0, 1).reshape(b, -1)  # (B, P·L)
    pos = jnp.moveaxis(pos, 0, 1).reshape(b, -1)
    vals, ix = jax.lax.top_k(scores, r)
    return vals, jnp.take_along_axis(pos, ix, axis=1)


@functools.partial(jax.jit, static_argnames=("r",))
def _ivf_candidates_masked(cell_pos, cell_q, cell_scale, cell_buckets,
                           lut, qs, cells, r: int):
    """Per-query-LUT (LSH) variant: the probed slots' buckets gather along
    with the factors and filter through the (B, num_buckets) table."""

    def step(_, cell_col):
        pos = cell_pos[cell_col]
        qm = cell_q[cell_col]
        sc = cell_scale[cell_col]
        bk = cell_buckets[cell_col]    # (B, L)
        s = jnp.einsum(
            "bk,blk->bl", qs, qm.astype(qs.dtype),
            preferred_element_type=jnp.float32,
        ) * sc
        valid = jnp.take_along_axis(lut, bk, axis=1)
        s = jnp.where(valid & (pos >= 0), s, -jnp.inf)
        return None, (s, pos)

    _, (scores, pos) = jax.lax.scan(step, None, cells.T)
    b = qs.shape[0]
    scores = jnp.moveaxis(scores, 0, 1).reshape(b, -1)
    pos = jnp.moveaxis(pos, 0, 1).reshape(b, -1)
    vals, ix = jax.lax.top_k(scores, r)
    return vals, jnp.take_along_axis(pos, ix, axis=1)


@functools.partial(jax.jit, static_argnames=("r",))
def _ivf_cosine_candidates(cell_pos, cell_q, cell_scale, cell_norms,
                           lut_union, cell_buckets, qs, q_norms, cells,
                           r: int):
    """Mean-cosine candidates for ONE request's query-vector set: ``cells``
    is (P,), ``qs`` (Q, k). Norms are exact f32 (arena-derived at snapshot
    time), so only the dot is quantized — same contract as the flat path."""

    def step(_, c):  # c: scalar cell id
        pos = cell_pos[c]              # (L,)
        qm = cell_q[c]                 # (L, k)
        sc = cell_scale[c]             # (L,)
        nm = cell_norms[c]             # (L,)
        sims = (jnp.matmul(
            qs, qm.T.astype(qs.dtype), preferred_element_type=jnp.float32
        ) * sc[None, :]) / jnp.maximum(
            nm[None, :] * q_norms[:, None], 1e-12
        )  # (Q, L)
        s = jnp.where(pos >= 0, jnp.mean(sims, axis=0), -jnp.inf)
        if lut_union is not None:
            s = jnp.where(lut_union[cell_buckets[c]], s, -jnp.inf)
        return None, (s, pos)

    _, (scores, pos) = jax.lax.scan(step, None, cells)
    scores = scores.reshape(-1)        # (P·L,)
    pos = pos.reshape(-1)
    vals, ix = jax.lax.top_k(scores, r)
    return vals, pos[ix]


@jax.jit
def _assign_cells(rows, centroids):
    """Nearest-centroid cell per row (squared-Euclidean via the matmul
    expansion) — the build/maintenance assignment rule. int32 so the host
    cell tables index straight off it."""
    d2 = (
        (rows * rows).sum(axis=1, keepdims=True)
        - 2.0 * rows @ centroids.T
        + (centroids * centroids).sum(axis=1)[None, :]
    )
    return jnp.argmin(d2, axis=1).astype(jnp.int32)


# -- snapshot ----------------------------------------------------------------


class IVFSnapshot(_ArenaSnapshot):
    """Immutable device view of Y as an inverted-file index (int8 cells +
    f32 centroids), plus the host-side mirrors (flat quantized rows, the
    assignment, the cell tables) that make incremental maintenance a
    per-affected-cell device scatter instead of a rebuild.

    The fourth scan backend (models/als/topn.py:_Snapshot): the over-fetch
    room for exclusions, LSH luts, the exact rescore and host collection
    are the flat int8 view's, so it differs ONLY in how candidates are
    generated — a probe program ahead of the scan, and a width of
    ``(probes, cut, room)``. No flat factor copy of any dtype lands in HBM
    in this mode."""

    def __init__(self, ids, version: int, *, centroids_np=None, assign=None,
                 q_np=None, scale_np=None, norms_np=None, buckets_np=None,
                 cell_pos_np=None, cell_len=None, cell_width: int = 0,
                 probes: int = 8, skew_bound: float = 4.0,
                 rescore_factor: float = 4.0, lsh=None,
                 centroids=None, cell_pos=None, cell_q=None,
                 cell_scale=None, cell_norms=None, cell_buckets=None,
                 slab=None, slab_rows=None,
                 prev: "IVFSnapshot | None" = None,
                 incremental: bool = False):
        # host mirrors (maintenance only — the request path never reads them)
        self.centroids_np = centroids_np   # (C, k) f32
        self.assign = assign               # (n,) i32 snapshot position → cell
        self.q_np = q_np                   # (n, k) i8 flat quantized rows
        self.scale_np = scale_np           # (n,) f32
        self.norms_np = norms_np           # (n,) f32
        self.buckets_np = buckets_np       # (n,) i32 or None
        self.cell_pos_np = cell_pos_np     # (C, L) i32, -1 pad, sorted asc
        self.cell_len = cell_len           # (C,) i32
        self.cell_width = cell_width       # L (pow2)
        self.probes = probes               # default probe width P (pow2)
        self.skew_bound = float(skew_bound)
        # skew at (re)build time: the drift trigger fires on skew past
        # max(bound, 1.25 x this) — inherently skewed catalogs whose
        # re-cluster cannot balance below the bound must not rebuild on
        # every microbatch
        self.base_skew = 1.0
        # device arrays (the serving scan's inputs)
        self.centroids = centroids         # (C, k) f32
        self.cell_pos = cell_pos           # (C, L) i32
        self.cell_q = cell_q               # (C, L, k) i8
        self.cell_scale = cell_scale       # (C, L) f32
        self.cell_norms = cell_norms       # (C, L) f32
        self.cell_buckets = cell_buckets   # (C, L) i32 or None
        super().__init__(ids, version, cell_q,
                         lsh if buckets_np is not None else None, slab,
                         slab_rows, rescore_factor, prev, incremental)
        if cell_len is not None and len(ids):
            _INDEX_SKEW.set(self.skew())

    @property
    def scanned(self):
        return self.cell_q

    @property
    def n_cells(self) -> int:
        return 0 if self.centroids_np is None else len(self.centroids_np)

    def skew(self) -> float:
        """Largest cell occupancy over the mean (n / C)."""
        if self.cell_len is None or self.n == 0 or self.n_cells == 0:
            return 1.0
        return float(self.cell_len.max()) / max(self.n / self.n_cells, 1e-9)

    def quantized_nbytes(self) -> int:
        """Device bytes of the quantized cells (the
        oryx_device_quantized_factor_bytes gauge, same as the flat slab)."""
        total = 0
        for arr in (self.cell_q, self.cell_scale):
            total += int(getattr(arr, "nbytes", 0) or 0)
        return total

    def device_arrays(self) -> list:
        arrays = (self.centroids, self.cell_pos, self.cell_q,
                  self.cell_scale, self.cell_norms, self.cell_buckets)
        return [a for a in arrays if a is not None]

    # -- construction --------------------------------------------------------

    @classmethod
    def build(cls, ids, host: np.ndarray, version: int, lsh,
              row_view: tuple, prev: "IVFSnapshot | None" = None, *,
              cells: int = 0, probes: int = 8, skew_bound: float = 4.0,
              rescore_factor: float = 4.0,
              centroids: "np.ndarray | None" = None, cell_width: int = 0):
        """Full index build from one host matrix: quantize (chunked),
        cluster (deterministic-seeded k-means on a bounded subsample unless
        ``centroids`` are given), assign every row, lay the cells out
        sorted-ascending and pow2-padded, and land the device arrays."""
        n = len(ids)
        slab, slab_rows = row_view
        if n == 0 or host.size == 0:
            return cls(list(ids), version, probes=probes,
                       skew_bound=skew_bound, rescore_factor=rescore_factor)
        q, scale, norms = _quantize_chunked(host)
        buckets_np = None
        if lsh and lsh.num_hashes:
            # np.array (not asarray): device-backed results come back
            # read-only and the incremental path writes these in place
            buckets_np = np.array(lsh.assign_buckets(host), dtype=np.int32)

        c = _round_up_pow2(max(1, cells if cells > 0 else auto_cells(n)))
        c = min(c, 1 << (n.bit_length() - 1))  # pow2, at most n
        assign = None
        if centroids is None:
            from oryx_tpu.models.kmeans.train import fit_index_centroids

            cap = max(_TRAIN_PER_CELL * c, 1 << 14)
            if n > cap:
                rng = np.random.default_rng(_KMEANS_SEED)
                sample = host[rng.choice(n, cap, replace=False)]
                centroids, _, _ = fit_index_centroids(
                    sample, c, seed=_KMEANS_SEED
                )
            else:
                centroids, _, assign = fit_index_centroids(
                    host, c, seed=_KMEANS_SEED
                )
        centroids = np.array(centroids, dtype=np.float32)
        c = len(centroids)
        if assign is not None:
            assign = np.array(assign, dtype=np.int32)  # writable copy
        if assign is None:
            assign = np.empty(n, dtype=np.int32)
            cent_dev = jnp.asarray(centroids)
            for a in range(0, n, _ASSIGN_CHUNK):
                b = min(n, a + _ASSIGN_CHUNK)
                assign[a:b] = np.asarray(
                    _assign_cells(jnp.asarray(host[a:b]), cent_dev)
                )
        cell_len = np.bincount(assign, minlength=c).astype(np.int32)
        width = cell_width if cell_width > 0 else _round_up_pow2(
            max(int(cell_len.max()) + (int(cell_len.max()) >> 2) + 4, 8)
        )
        if cell_len.max() > width:
            raise ValueError(
                f"cell_width {width} overflows (largest cell "
                f"{int(cell_len.max())})"
            )
        # canonical layout: members sorted ascending per cell (stable sort
        # groups by cell, positions stay ascending) — the invariant the
        # incremental path's in-place surgery preserves bit-exactly
        order = np.argsort(assign, kind="stable")
        cell_pos_np = np.full((c, width), -1, dtype=np.int32)
        offsets = np.zeros(c + 1, dtype=np.int64)
        offsets[1:] = np.cumsum(cell_len, dtype=np.int64)
        for j in range(c):
            members = order[offsets[j]:offsets[j + 1]]
            cell_pos_np[j, : len(members)] = members
        snap = cls(
            list(ids), version, centroids_np=centroids, assign=assign,
            q_np=q, scale_np=scale, norms_np=norms, buckets_np=buckets_np,
            cell_pos_np=cell_pos_np, cell_len=cell_len, cell_width=width,
            probes=max(1, min(_round_up_pow2(probes), c)),
            skew_bound=skew_bound, rescore_factor=rescore_factor, lsh=lsh,
            centroids=jnp.asarray(centroids),
            slab=slab, slab_rows=slab_rows, prev=prev,
        )
        snap._land_cells(np.arange(c, dtype=np.int64), full=True)
        snap.base_skew = snap.skew()
        _INDEX_CELLS.inc(c)
        _INDEX_SKEW.set(snap.base_skew)
        return snap

    def _cell_block(self, cell_ids: np.ndarray):
        """Host (A, L[, k]) blocks for ``cell_ids`` from the flat mirrors,
        with the padding values the device arrays carry (pos -1, q 0,
        scale/norm 1) — build and incremental maintenance share this so
        their device bytes are bit-identical by construction."""
        sub = self.cell_pos_np[cell_ids]                # (A, L)
        pad = sub < 0
        safe = np.clip(sub, 0, max(self.n - 1, 0))
        cq = self.q_np[safe]
        cq[pad] = 0
        cs = self.scale_np[safe]
        cs[pad] = 1.0
        cn = self.norms_np[safe]
        cn[pad] = 1.0
        cb = None
        if self.buckets_np is not None:
            cb = self.buckets_np[safe].astype(np.int32)
            cb[pad] = 0
        return sub, cq, cs, cn, cb

    def _land_cells(self, cell_ids: np.ndarray, full: bool = False) -> None:
        """Materialize ``cell_ids``' device slices: whole-array uploads on a
        full build, row scatters (functional ``.at[].set``) incrementally."""
        sub, cq, cs, cn, cb = self._cell_block(cell_ids)
        if full:
            self.cell_pos = jnp.asarray(sub)
            self.cell_q = jnp.asarray(cq)
            self.cell_scale = jnp.asarray(cs)
            self.cell_norms = jnp.asarray(cn)
            self.cell_buckets = jnp.asarray(cb) if cb is not None else None
            return
        ix = jnp.asarray(cell_ids)
        self.cell_pos = self.cell_pos.at[ix].set(jnp.asarray(sub))
        self.cell_q = self.cell_q.at[ix].set(jnp.asarray(cq))
        self.cell_scale = self.cell_scale.at[ix].set(jnp.asarray(cs))
        self.cell_norms = self.cell_norms.at[ix].set(jnp.asarray(cn))
        if self.cell_buckets is not None and cb is not None:
            self.cell_buckets = self.cell_buckets.at[ix].set(jnp.asarray(cb))

    @classmethod
    def from_delta(cls, prev: "IVFSnapshot", delta):
        """Incremental step off one composed arena delta: requantize and
        reassign ONLY the touched rows, splice them through the host cell
        tables (sorted-ascending order preserved), and rewrite only the
        affected cells' device slices. Returns None when a cell would
        overflow its padded width or the post-update balance drifts past
        ``skew_bound`` — the caller re-clusters (full rebuild, fresh
        centroids)."""
        n_prev, lsh = prev.n, prev.lsh
        # flat host mirrors: changed rows update in place (prev never reads
        # them again — the request path only touches device arrays and the
        # pinned slab), appends extend by copy
        q_np, scale_np, norms_np, buckets_np = (
            prev.q_np, prev.scale_np, prev.norms_np, prev.buckets_np
        )
        assign = prev.assign
        cell_pos_np, cell_len = prev.cell_pos_np, prev.cell_len
        width = prev.cell_width
        cent_dev = jnp.asarray(prev.centroids_np)
        affected: set[int] = set()

        changed_pos = np.asarray(
            [prev.id_to_idx[i] for i in delta.changed_ids
             if i in prev.id_to_idx],
            dtype=np.int64,
        )
        if len(changed_pos):
            qc, sc = _quantize_rows(delta.changed_vals)
            q_np[changed_pos] = qc
            scale_np[changed_pos] = sc
            norms_np[changed_pos] = np.linalg.norm(delta.changed_vals, axis=1)
            if buckets_np is not None:
                buckets_np[changed_pos] = lsh.assign_buckets(
                    delta.changed_vals
                )
            new_cells = np.asarray(_assign_cells(
                jnp.asarray(np.asarray(delta.changed_vals, dtype=np.float32)),
                cent_dev,
            ))
            for pos, nc in zip(changed_pos, new_cells):
                oc = int(assign[pos])
                affected.add(oc)
                if int(nc) != oc:
                    if not _splice(cell_pos_np, cell_len, oc, int(nc),
                                   int(pos), width):
                        return None
                    assign[pos] = nc
                    affected.add(int(nc))
        if delta.appended_ids:
            qa, sa = _quantize_rows(delta.appended_vals)
            q_np = np.concatenate([q_np, qa])
            scale_np = np.concatenate([scale_np, sa])
            norms_np = np.concatenate([
                norms_np, np.linalg.norm(delta.appended_vals, axis=1)
            ])
            if buckets_np is not None:
                buckets_np = np.concatenate([
                    buckets_np,
                    np.asarray(lsh.assign_buckets(delta.appended_vals),
                               dtype=np.int32),
                ])
            app_cells = np.asarray(_assign_cells(
                jnp.asarray(np.asarray(delta.appended_vals, dtype=np.float32)),
                cent_dev,
            ))
            assign = np.concatenate([assign, app_cells])
            for off, nc in enumerate(app_cells):
                if not _insert(cell_pos_np, cell_len, int(nc),
                               n_prev + off, width):
                    return None
                affected.add(int(nc))
        ids, slab_rows = prev.appended(delta)
        snap = cls(
            ids, delta.version, centroids_np=prev.centroids_np,
            assign=assign, q_np=q_np, scale_np=scale_np, norms_np=norms_np,
            buckets_np=buckets_np, cell_pos_np=cell_pos_np,
            cell_len=cell_len, cell_width=width, probes=prev.probes,
            skew_bound=prev.skew_bound, rescore_factor=prev.rescore_factor,
            lsh=lsh, centroids=prev.centroids,
            cell_pos=prev.cell_pos, cell_q=prev.cell_q,
            cell_scale=prev.cell_scale, cell_norms=prev.cell_norms,
            cell_buckets=prev.cell_buckets, slab=delta.slab,
            slab_rows=slab_rows, prev=prev, incremental=True,
        )
        snap.base_skew = prev.base_skew
        if snap.skew() > max(snap.skew_bound, prev.base_skew * 1.25):
            log.info(
                "IVF cell balance drifted past %.1fx (%.2fx) — re-clustering",
                snap.skew_bound, snap.skew(),
            )
            return None
        if affected:
            snap._land_cells(np.fromiter(sorted(affected), dtype=np.int64))
        _INDEX_SKEW.set(snap.skew())
        return snap

    # -- the scan backend (topn.py:_Snapshot) --------------------------------

    def batch_width(self, how_many: int, filtering: bool, room: int = 0):
        """``(probes, cut, room)``: the default probe width, and
        ``rescore-factor x how_many`` candidates plus the over-fetch
        ``room`` rounded up to a pow2 (signature stability), capped by what
        the probed cells can actually surface."""
        cap = min(self.n, self.probes * self.cell_width)
        cut = _round_up_pow2(self.rescore_width(how_many) + room)
        return self.probes, max(1, min(cap, cut)), room

    def _widths(self, want: int):
        """The widening policy: the cut doubles first (more candidates from
        the same probes), then the probe width doubles (pow2 signatures),
        until the scan covers the whole catalog (probes == cells is the
        flat scan, cell-shaped)."""
        probes, r = self.probes, self.rescore_width(want)
        while True:
            cap = min(self.n, probes * self.cell_width)
            r_eff = min(r, cap)
            yield probes, r_eff, 0
            if probes >= self.n_cells and r_eff >= self.n:
                return
            if r_eff < cap:
                r = r_eff * 2  # widen the cut over the same probed cells
            else:
                probes = min(self.n_cells, probes * 2)  # widen the probe set
                r = min(self.n, r * 2)

    def plan(self, qs, lut, width):
        """One probe matmul, then one probed-cell scan for the whole batch,
        whose ``cells`` operand is the probe's result, still on the device.
        Each under a cost key of its own, so attribution separates
        candidate generation from the scan and the exact rescore."""
        probes, r, room = width
        b, c = qs.shape[0], self.n_cells
        cells = _Fed((b, probes), jnp.int32)
        key = scan_cost_key(b, c, probes, room, lut is not None)
        if lut is not None:
            scan = (_ivf_candidates_masked,
                    (self.cell_pos, self.cell_q, self.cell_scale,
                     self.cell_buckets, lut, qs, cells, r), key)
        else:
            scan = (_ivf_candidates,
                    (self.cell_pos, self.cell_q, self.cell_scale, qs, cells,
                     r), key)
        return ((_probe_cells, (self.centroids, qs, probes),
                 probe_cost_key(b, c, probes)), scan)

    def dispatched(self, batch: int, width) -> None:
        probes, r, _ = width
        _INDEX_PROBED.inc(batch * probes)
        _INDEX_CANDIDATES.inc(batch * r)

    def candidates(self, scan, q_host: np.ndarray, want: int, hooks: bool):
        for width in self._widths(want):
            v, i = scan(self, q_host[None, :], width, register=False)
            vals, idx = self.rescore(q_host[None, :], v, i)
            yield vals[0], idx[0]

    def cosine_candidates(self, qs_host: np.ndarray, want: int):
        """Mean-cosine candidates for one request's query-vector set: probes
        rank by the MEAN query direction, candidates rescore exact from the
        slab (cosine), widening as :meth:`candidates`."""
        qs = jnp.asarray(qs_host)
        q_norms = jnp.asarray(np.linalg.norm(qs_host, axis=1))
        lut_union = (jnp.asarray(self.bucket_union(qs_host))
                     if self.lsh is not None else None)
        probe_vec = jnp.asarray(np.mean(qs_host, axis=0, keepdims=True))
        for probes, r, _ in self._widths(want):
            cells = _probe_cells(self.centroids, probe_vec, probes)
            v, i = _ivf_cosine_candidates(
                self.cell_pos, self.cell_q, self.cell_scale, self.cell_norms,
                lut_union, self.cell_buckets, qs, q_norms, cells[0], r,
            )
            _INDEX_PROBED.inc(probes)
            _INDEX_CANDIDATES.inc(r)
            vals, idx = self.rescore(
                qs_host, np.asarray(v)[None, :], np.asarray(i)[None, :],
                cosine=True,
            )
            yield vals[0], idx[0]


def _splice(cell_pos_np, cell_len, old_cell: int, new_cell: int,
            pos: int, width: int) -> bool:
    """Move ``pos`` from one sorted cell row to another in place; False if
    the destination is full (caller rebuilds)."""
    ln = int(cell_len[old_cell])
    row = cell_pos_np[old_cell]
    i = int(np.searchsorted(row[:ln], pos))
    if i < ln and row[i] == pos:
        row[i:ln - 1] = row[i + 1:ln]
        row[ln - 1] = -1
        cell_len[old_cell] = ln - 1
    return _insert(cell_pos_np, cell_len, new_cell, pos, width)


def _insert(cell_pos_np, cell_len, cell: int, pos: int, width: int) -> bool:
    ln = int(cell_len[cell])
    if ln >= width:
        return False
    row = cell_pos_np[cell]
    i = int(np.searchsorted(row[:ln], pos))
    row[i + 1:ln + 1] = row[i:ln]
    row[i] = pos
    cell_len[cell] = ln + 1
    return True
