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

from oryx_tpu.common import compilecache
from oryx_tpu.common import metrics as metrics_mod
from oryx_tpu.common import profiling
from oryx_tpu.common import spans

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


def _round_up_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


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
                  excl: bool, lsh: bool) -> str:
    """Cost-accounting signature of the probed-cell candidate scan."""
    return (f"als.ivf_scan/b{batch}/c{cells}/p{probes}"
            + ("+excl" if excl else "") + ("+lsh" if lsh else ""))


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
def _ivf_candidates(cell_pos, cell_q, cell_scale, qs, cells, excl, r: int):
    """Quantized scores over the probed cells only. ``cells`` is (B, P);
    a ``lax.scan`` over the P probe columns bounds the gather transient at
    one (B, L, k) int8 block — the per-step gathers ARE the scan's HBM
    traffic (P·L·k bytes per query vs n·k for the flat slab). Padding
    slots (cell_pos < 0) and per-query exclusions mask to -inf before the
    exact top-k over the (B, P·L) candidate pool."""

    def step(_, cell_col):  # cell_col: (B,) — one probe column
        pos = cell_pos[cell_col]       # (B, L) gather
        qm = cell_q[cell_col]          # (B, L, k) int8 gather
        sc = cell_scale[cell_col]      # (B, L)
        s = jnp.einsum(
            "bk,blk->bl", qs, qm.astype(qs.dtype),
            preferred_element_type=jnp.float32,
        ) * sc
        s = jnp.where(pos >= 0, s, -jnp.inf)
        if excl is not None:
            hit = (pos[:, :, None] == excl[:, None, :]).any(axis=-1)
            s = jnp.where(hit, -jnp.inf, s)
        return None, (s, pos)

    _, (scores, pos) = jax.lax.scan(step, None, cells.T)
    b = qs.shape[0]
    scores = jnp.moveaxis(scores, 0, 1).reshape(b, -1)  # (B, P·L)
    pos = jnp.moveaxis(pos, 0, 1).reshape(b, -1)
    vals, ix = jax.lax.top_k(scores, r)
    return vals, jnp.take_along_axis(pos, ix, axis=1)


@functools.partial(jax.jit, static_argnames=("r",))
def _ivf_candidates_masked(cell_pos, cell_q, cell_scale, cell_buckets,
                           lut, qs, cells, excl, r: int):
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
        if excl is not None:
            hit = (pos[:, :, None] == excl[:, None, :]).any(axis=-1)
            s = jnp.where(hit, -jnp.inf, s)
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


class IVFSnapshot:
    """Immutable device view of Y as an inverted-file index (int8 cells +
    f32 centroids), plus the host-side mirrors (flat quantized rows, the
    assignment, the cell tables) that make incremental maintenance a
    per-affected-cell device scatter instead of a rebuild.

    Shares the flat int8 snapshot's duck type where serving touches it:
    ``ids`` / ``id_to_idx`` / ``n`` / ``version`` / ``gather_rows`` (the
    pinned arena-slab rescore view) / ``cost_keys_attempted``; ``mat`` /
    ``score_mat`` stay None — no flat factor copy of any dtype lands in
    HBM in this mode."""

    def __init__(self, ids, version: int, *, centroids_np=None, assign=None,
                 q_np=None, scale_np=None, norms_np=None, buckets_np=None,
                 cell_pos_np=None, cell_len=None, cell_width: int = 0,
                 probes: int = 8, skew_bound: float = 4.0,
                 centroids=None, cell_pos=None, cell_q=None,
                 cell_scale=None, cell_norms=None, cell_buckets=None,
                 slab=None, slab_rows=None,
                 prev: "IVFSnapshot | None" = None,
                 appended: "list[str] | None" = None):
        self.ids = ids
        self.version = version
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
        # pinned exact-rescore view (same contract as the flat int8
        # snapshot: the slab object + row indices captured in `ids` order)
        self.slab = slab
        self.slab_rows = slab_rows
        # flat-snapshot duck type for serving's guards
        self.mat = None
        self.score_mat = None
        self.mesh = None
        self.buckets = None
        if prev is not None and appended is not None:
            self.id_to_idx = prev.id_to_idx
            for i in range(len(prev.ids), len(ids)):
                self.id_to_idx[ids[i]] = i
        else:
            self.id_to_idx = {s: i for i, s in enumerate(ids)}
        if (prev is not None
                and getattr(prev.cell_q, "shape", None)
                == getattr(cell_q, "shape", None)):
            self.cost_keys_attempted = prev.cost_keys_attempted
        else:
            self.cost_keys_attempted: set = set()
        profiling.register_quantized(self)
        if cell_len is not None and len(ids):
            _INDEX_SKEW.set(self.skew())

    @property
    def n(self) -> int:
        return len(self.ids)

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

    def device_nbytes(self) -> int:
        """All device bytes the index holds (device_factor_bytes)."""
        total = 0
        for arr in (self.centroids, self.cell_pos, self.cell_q,
                    self.cell_scale, self.cell_norms, self.cell_buckets):
            total += int(getattr(arr, "nbytes", 0) or 0)
        return total

    def gather_rows(self, positions: np.ndarray) -> np.ndarray:
        """Exact f32 rows for snapshot positions, off the PINNED slab."""
        pos = np.clip(np.asarray(positions, dtype=np.int64), 0, self.n - 1)
        return self.slab[self.slab_rows[pos]]

    # -- construction --------------------------------------------------------

    @classmethod
    def build(cls, ids, host: np.ndarray, version: int, lsh,
              row_view: tuple, prev: "IVFSnapshot | None" = None, *,
              cells: int = 0, probes: int = 8, skew_bound: float = 4.0,
              centroids: "np.ndarray | None" = None, cell_width: int = 0):
        """Full index build from one host matrix: quantize (chunked),
        cluster (deterministic-seeded k-means on a bounded subsample unless
        ``centroids`` are given), assign every row, lay the cells out
        sorted-ascending and pow2-padded, and land the device arrays."""
        from oryx_tpu.models.als.serving import _quantize_rows

        n = len(ids)
        slab, slab_rows = row_view
        if n == 0 or host.size == 0:
            return cls(list(ids), version, probes=probes,
                       skew_bound=skew_bound)
        k = host.shape[1]
        q = np.empty((n, k), dtype=np.int8)
        scale = np.empty(n, dtype=np.float32)
        norms = np.empty(n, dtype=np.float32)
        chunk = 1 << 16
        for a in range(0, n, chunk):
            b = min(n, a + chunk)
            q[a:b], scale[a:b] = _quantize_rows(host[a:b])
            norms[a:b] = np.linalg.norm(host[a:b], axis=1)
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
            skew_bound=skew_bound,
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
    def from_delta(cls, prev: "IVFSnapshot", delta, lsh):
        """Incremental step off one composed arena delta: requantize and
        reassign ONLY the touched rows, splice them through the host cell
        tables (sorted-ascending order preserved), and rewrite only the
        affected cells' device slices. Returns None when a cell would
        overflow its padded width or the post-update balance drifts past
        ``skew_bound`` — the caller re-clusters (full rebuild, fresh
        centroids)."""
        from oryx_tpu.models.als.serving import _quantize_rows

        n_prev = prev.n
        n_new = n_prev + len(delta.appended_ids)
        if prev.cell_q is None or prev.centroids_np is None:
            return None
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
        ids = prev.ids + delta.appended_ids
        slab_rows = (
            np.concatenate([prev.slab_rows,
                            np.asarray(delta.appended_rows, dtype=np.int64)])
            if len(delta.appended_ids) else prev.slab_rows
        )
        snap = cls(
            ids, delta.version, centroids_np=prev.centroids_np,
            assign=assign, q_np=q_np, scale_np=scale_np, norms_np=norms_np,
            buckets_np=buckets_np, cell_pos_np=cell_pos_np,
            cell_len=cell_len, cell_width=width, probes=prev.probes,
            skew_bound=prev.skew_bound, centroids=prev.centroids,
            cell_pos=prev.cell_pos, cell_q=prev.cell_q,
            cell_scale=prev.cell_scale, cell_norms=prev.cell_norms,
            cell_buckets=prev.cell_buckets, slab=delta.slab,
            slab_rows=slab_rows, prev=prev, appended=delta.appended_ids,
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


# -- serving drivers ---------------------------------------------------------
# Called from ALSServingModel (models/als/serving.py) with the model as the
# first argument: exclusion padding, LSH luts, the exact rescore and host
# collection all reuse the model's flat-path helpers, so the IVF path
# differs ONLY in how candidates are generated.


def _candidate_width(model, snap: IVFSnapshot, probes: int,
                     want: int) -> int:
    """Rescore width for one scan: ``rescore-factor x want`` rounded up to
    a pow2 (signature stability), capped by what the probed cells can
    actually surface."""
    cap = min(snap.n, probes * snap.cell_width)
    return max(1, min(cap, _round_up_pow2(
        max(int(model.rescore_factor * want), 16)
    )))


def _scan(model, snap: IVFSnapshot, qs_host: np.ndarray, probes: int,
          r: int, excl, lut, register: bool):
    """One probe + candidate scan: (vals, idx) of width ``r`` in snapshot
    positions, quantized scores. Registers/records the probe and scan
    programs under their own cost keys so attribution separates candidate
    generation from the exact rescore."""
    with spans.stage("topn.upload"):
        qs = jnp.asarray(qs_host)
    b = qs_host.shape[0]
    c = snap.n_cells
    pk = probe_cost_key(b, c, probes)
    sk = scan_cost_key(b, c, probes, excl is not None, lut is not None)

    def scan_args(cells):
        if lut is not None:
            return (_ivf_candidates_masked,
                    (snap.cell_pos, snap.cell_q, snap.cell_scale,
                     snap.cell_buckets, lut, qs, cells, excl))
        return (_ivf_candidates,
                (snap.cell_pos, snap.cell_q, snap.cell_scale, qs, cells,
                 excl))

    with spans.stage("topn.dispatch"):
        if register and metrics_mod.default_registry().enabled:
            if pk not in snap.cost_keys_attempted:
                snap.cost_keys_attempted.add(pk)
                compilecache.aot_compile(
                    _probe_cells, snap.centroids, qs, probes, cost_key=pk
                )
            if sk not in snap.cost_keys_attempted:
                snap.cost_keys_attempted.add(sk)
                fn, a = scan_args(
                    jax.ShapeDtypeStruct((b, probes), jnp.int32)
                )
                compilecache.aot_compile(fn, *a, r, cost_key=sk)
        cells = _probe_cells(snap.centroids, qs, probes)
        fn, a = scan_args(cells)
        vals, idx = fn(*a, r)
        if register:
            profiling.costs().record(pk)
            profiling.costs().record(sk)
        _INDEX_PROBED.inc(b * probes)
        _INDEX_CANDIDATES.inc(b * r)
    with spans.stage("topn.wait_download"):
        return np.asarray(vals), np.asarray(idx)


def top_n(model, snap: IVFSnapshot, q_host: np.ndarray, how_many: int,
          offset: int, allowed, rescore, excluded) -> list:
    """Single-query IVF top-N with widening: rescore width doubles first
    (more candidates from the same probes), then the probe width doubles
    (pow2 signatures) until the request is satisfied or the scan covers
    the whole catalog (probes == cells is the flat scan, cell-shaped)."""
    want = how_many + offset
    excl = None
    if excluded:
        padded = model._excluded_indices(snap, [excluded], 1)
        if (padded >= 0).any():
            excl = jnp.asarray(padded)
    lut = (
        jnp.asarray(model._build_lut(q_host[None, :]))
        if model.lsh is not None and snap.cell_buckets is not None
        else None
    )
    probes = snap.probes
    r = _round_up_pow2(max(int(model.rescore_factor * want), 16))
    while True:
        cap = min(snap.n, probes * snap.cell_width)
        r_eff = min(r, cap)
        v, i = _scan(model, snap, q_host[None, :], probes, r_eff, excl,
                     lut, register=False)
        vals, idx = model._rescore_exact(snap, q_host[None, :], v, i)
        out = model._collect(snap, vals[0], idx[0], want, allowed, rescore)
        if len(out) >= want or (probes >= snap.n_cells
                                and r_eff >= snap.n):
            return out[offset:offset + how_many]
        if r_eff < cap:
            r = r_eff * 2  # widen the cut over the same probed cells
        else:
            probes = min(snap.n_cells, probes * 2)  # widen the probe set
            r = min(snap.n, r * 2)


def top_n_batch(model, snap: IVFSnapshot, qs_host: np.ndarray,
                how_many: int, alloweds, excluded,
                filtering: bool) -> list:
    """Batched IVF top-N: one probe matmul + one probed-cell scan for the
    whole batch, exact-f32-rescored from the arena slab before the final
    cut. Per-query widening (heavy host filtering) falls back to the
    single-query path, exactly like the flat int8 batch driver."""
    b = len(qs_host)
    use_excl = excluded is not None and any(e for e in excluded)
    excl = (
        jnp.asarray(model._excluded_indices(snap, excluded, b))
        if use_excl else None
    )
    lut = (
        jnp.asarray(model._build_lut(qs_host))
        if model.lsh is not None and snap.cell_buckets is not None
        else None
    )
    r = _candidate_width(model, snap, snap.probes, how_many)
    v, i = _scan(model, snap, qs_host, snap.probes, r, excl, lut,
                 register=True)
    with spans.stage("topn.rescore"):
        vals, idx = model._rescore_exact(snap, qs_host, v, i)
    with spans.stage("topn.ids"):
        if not filtering:
            from oryx_tpu.models.als.serving import _id_lists

            return _id_lists(snap.ids, vals, idx, how_many)
        out = []
        for q in range(b):
            allowed = alloweds[q] if alloweds else None
            got = model._collect(
                snap, vals[q], idx[q], how_many, allowed, None
            )[:how_many]
            if len(got) < how_many and r < snap.n:
                got = top_n(
                    model, snap, qs_host[q], how_many, 0, allowed, None,
                    excluded[q] if excluded else None,
                )
            out.append(got)
        return out


def top_n_cosine(model, snap: IVFSnapshot, qs_host: np.ndarray,
                 q_norms_host: np.ndarray, how_many: int, offset: int,
                 allowed, rescore) -> list:
    """Mean-cosine IVF top-N for one request's query-vector set: probes
    rank by the MEAN query direction, candidates rescore exact from the
    slab (cosine), widening mirrors :func:`top_n`."""
    want = how_many + offset
    qs = jnp.asarray(qs_host)
    q_norms = jnp.asarray(q_norms_host)
    lut_union = None
    if model.lsh is not None and snap.cell_buckets is not None:
        lu = np.zeros(model.lsh.num_buckets, dtype=bool)
        for qv in qs_host:
            lu[model.lsh.get_candidate_indices(qv)] = True
        lut_union = jnp.asarray(lu)
    probe_vec = np.mean(qs_host, axis=0, keepdims=True)
    probes = snap.probes
    r = _round_up_pow2(max(int(model.rescore_factor * want), 16))
    while True:
        cap = min(snap.n, probes * snap.cell_width)
        r_eff = min(r, cap)
        cells = _probe_cells(snap.centroids, jnp.asarray(probe_vec), probes)
        v, i = _ivf_cosine_candidates(
            snap.cell_pos, snap.cell_q, snap.cell_scale, snap.cell_norms,
            lut_union, snap.cell_buckets, qs, q_norms, cells[0], r_eff,
        )
        _INDEX_PROBED.inc(probes)
        _INDEX_CANDIDATES.inc(r_eff)
        vals, idx = model._rescore_exact(
            snap, qs_host, np.asarray(v)[None, :], np.asarray(i)[None, :],
            cosine=True,
        )
        out = model._collect(snap, vals[0], idx[0], want, allowed, rescore)
        if len(out) >= want or (probes >= snap.n_cells
                                and r_eff >= snap.n):
            return out[offset:offset + how_many]
        if r_eff < cap:
            r = r_eff * 2
        else:
            probes = min(snap.n_cells, probes * 2)
            r = min(snap.n, r * 2)


def warm_bucket(model, snap: IVFSnapshot, batch_size: int,
                how_many: int) -> None:
    """AOT-compile the IVF probe + scan signatures for one pow2 bucket —
    the per-bucket unit of the serving warm ladder, under the IVF cost
    keys. Both exclusion families warm (the default /recommend path always
    sends known-item exclusions at the floored pad width); the shared
    zero-batch executions in ALSServingModel.warm_bucket then populate the
    jit dispatch caches these programs actually serve from."""
    from oryx_tpu.models.als.serving import _EXCL_PAD_MIN

    probes = snap.probes
    c = snap.n_cells
    r = _candidate_width(model, snap, probes, how_many)
    qs_struct = jax.ShapeDtypeStruct(
        (batch_size, model.features), jnp.float32
    )
    excl_struct = jax.ShapeDtypeStruct(
        (batch_size, _EXCL_PAD_MIN), jnp.int32
    )
    cells_struct = jax.ShapeDtypeStruct((batch_size, probes), jnp.int32)
    pk = probe_cost_key(batch_size, c, probes)
    compilecache.aot_compile(
        _probe_cells, snap.centroids, qs_struct, probes, cost_key=pk
    )
    use_lsh = model.lsh is not None and snap.cell_buckets is not None
    keys = (scan_cost_key(batch_size, c, probes, False, use_lsh),
            scan_cost_key(batch_size, c, probes, True, use_lsh))
    if use_lsh:
        lut_struct = jax.ShapeDtypeStruct(
            (batch_size, model.lsh.num_buckets), jnp.bool_
        )
        compilecache.aot_compile(
            _ivf_candidates_masked, snap.cell_pos, snap.cell_q,
            snap.cell_scale, snap.cell_buckets, lut_struct, qs_struct,
            cells_struct, None, r, cost_key=keys[0],
        )
        compilecache.aot_compile(
            _ivf_candidates_masked, snap.cell_pos, snap.cell_q,
            snap.cell_scale, snap.cell_buckets, lut_struct, qs_struct,
            cells_struct, excl_struct, r, cost_key=keys[1],
        )
    else:
        compilecache.aot_compile(
            _ivf_candidates, snap.cell_pos, snap.cell_q, snap.cell_scale,
            qs_struct, cells_struct, None, r, cost_key=keys[0],
        )
        compilecache.aot_compile(
            _ivf_candidates, snap.cell_pos, snap.cell_q, snap.cell_scale,
            qs_struct, cells_struct, excl_struct, r, cost_key=keys[1],
        )
    snap.cost_keys_attempted.update({pk, *keys})
