"""ALS serving model: device-resident factors answering recommendation queries.

Equivalent of the reference's ALSServingModel / ALSServingModelManager /
TopNConsumer (app/oryx-app-serving/.../als/model/ALSServingModel.java:61-418,
ALSServingModelManager.java:44-182, TopNConsumer.java:30-80).

TPU re-design of the query path: the reference fans a top-N scan over
LSH-partitioned hash maps with a thread pool; here Y materializes into one
dense device matrix (dirty-flag cache), and top-N is a single
``scores = Y @ q`` matmul + ``lax.top_k`` on the MXU — with optional LSH
masking preserving ``sample-rate`` approximation semantics, and item norms
cached for cosine queries. Point updates (UP messages) mutate host maps and
only re-materialize lazily, so the query path never blocks on updates
(the double-buffer answer to JAX array immutability).
"""

from __future__ import annotations

import collections
import functools
import json
import logging
import threading
import time
import weakref
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from oryx_tpu.api.serving import ServingModel
from oryx_tpu.ml.mlupdate import read_pmml_from_update_key_message
from oryx_tpu.api.serving import AbstractServingModelManager
from oryx_tpu.common import compilecache
from oryx_tpu.common import devicephase
from oryx_tpu.common import lineage
from oryx_tpu.common import metrics as metrics_mod
from oryx_tpu.common import profiling
from oryx_tpu.common import spans
from oryx_tpu.models.als import ivf as ivf_mod
from oryx_tpu.models.als import pmml_codec
from oryx_tpu.models.als.lsh import LocalitySensitiveHash
from oryx_tpu.models.als.rescorer import load_rescorer_providers
from oryx_tpu.models.als.known import KnownItems
from oryx_tpu.models.als.topn import (_OVERFETCH_ROOM, _ArenaSnapshot,
                                      _Snapshot, _collect, _drop_rows,
                                      _id_lists, _operands, _quantize_blocks,
                                      _quantize_rows, _room_for,
                                      _round_up_pow2, _upload_blocks)
from oryx_tpu.models.als.vectors import FeatureVectorStore
from oryx_tpu.parallel.mesh import (put_row_sharded, replicated_sharding,
                                    row_sharding)
from oryx_tpu.common.lockutils import RateLimitCheck
from oryx_tpu.ops.solver import SolverCache

log = logging.getLogger(__name__)

_TOPN_BATCH_SECONDS = metrics_mod.default_registry().histogram(
    "oryx_serving_topn_batch_seconds",
    "Host-observed latency of one batched top-N device call",
)
_TOPN_QUERIES = metrics_mod.default_registry().counter(
    "oryx_serving_topn_queries_total",
    "Queries answered through the batched top-N path",
)
_LOAD_FRACTION = metrics_mod.default_registry().gauge(
    "oryx_serving_model_load_fraction",
    "Fraction of expected model vectors loaded (evaluated at scrape time)",
)
_PREWARMED_SWAPS = metrics_mod.default_registry().counter(
    "oryx_serving_prewarmed_swaps_total",
    "Model-generation swaps promoted after off-path bucket warmup",
)
_DEADLINE_SWAPS = metrics_mod.default_registry().counter(
    "oryx_serving_swap_deadline_promotions_total",
    "Staged model generations promoted by the swap deadline, unwarmed",
)
_EXCLUDED_ENTRIES = metrics_mod.default_registry().counter(
    "oryx_serving_excluded_entries_total",
    "Rows handed to batched top-N flushes to be left out of their answers "
    "(known items and a request's own exclusions)",
)
_EXCLUSION_OVERFLOW = metrics_mod.default_registry().counter(
    "oryx_serving_exclusion_overflow_total",
    "Queries whose exclusions were longer than the widest warmed over-fetch "
    "room",
)


def _load_fraction_fn(manager_ref):
    """Scrape-time gauge callback over a WEAK manager ref: a strong ref
    would pin a retired manager (and its factor matrices) for the process
    lifetime after a test or redeploy drops it."""

    def fn() -> float:
        manager = manager_ref()
        model = manager.get_model() if manager is not None else None
        return model.get_fraction_loaded() if model is not None else 0.0

    return fn


#: Valid values of ``oryx.serving.device-dtype``: "auto" keeps the historic
#: behavior (bf16 scoring copy on TPU, f32 elsewhere); explicit f32/bf16
#: force the scoring dtype; "int8" holds ONLY a per-row-scaled int8 slab on
#: device (¼ the f32 HBM) and rescores the top candidates exactly in f32
#: from the host factor arena before the final top-k.
_DEVICE_DTYPES = ("auto", "float32", "bfloat16", "int8")


def _topn_cost_key(batch_size: int, room: int, quant: bool = False) -> str:
    """Cost-accounting program signature for one batched top-N variant.
    Keyed by (batch size, over-fetch room for exclusions, quantized) — the
    axes the coalescer's pow2 padding and the warm ladder actually produce;
    top-k width drift (unusual howMany) folds into the same key, a documented
    approximation (docs/observability.md "Device performance attribution").
    Quantized programs get their OWN keys: their per-call cost (int8 reads,
    rescale multiply) differs from the f32/bf16 scan's."""
    return (f"als.top_n_batch/b{batch_size}"
            + (f"+excl{room}" if room else "") + ("+int8" if quant else ""))


def _score(qs, mat):
    """(B, n) scores with f32 accumulation. ``mat`` may be bfloat16 (the MXU's
    native input dtype — half the HBM traffic of f32); accumulation stays f32
    via preferred_element_type, the standard TPU matmul recipe."""
    return jnp.matmul(
        qs.astype(mat.dtype), mat.T, preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("k",))
def _top_k_dot_batch(mat, qs, valid, k: int):
    """One MXU matmul for the whole query batch + approx top-k (the masking
    logic lives once in ``_masked_scores``). ``valid`` is None on the
    unfiltered hot path so it stays exactly matmul + top_k (None is a static
    pytree — XLA never sees a dummy mask; the r1→r2 CPU regression was
    unconditional masking here). A flush's exclusions are no operand: the
    flush asks for a wider ``k`` and drops their rows from the list
    (``topn._OVERFETCH_ROOM``), so nothing stands between the matmul and the
    top-k it is fused into and no ``(B, n)`` score matrix is written.

    approx_max_k is the TPU-native top-k (recall ≥ 0.99 beats LSH 0.3's own
    approximation); exact on backends without the TPU op."""
    return _top_k_of_scores(_masked_scores(mat, qs, valid), k)


@jax.jit
def _masked_scores(mat, qs, valid):
    """Masked score matrix only — lets the widening retry in ``top_n`` reuse
    one matmul's scores across successively larger top-k calls instead of
    re-scanning Y each widening."""
    scores = _score(qs, mat)
    if valid is not None:
        scores = jnp.where(valid[None, :], scores, -jnp.inf)
    return scores


@functools.partial(jax.jit, static_argnames=("k",))
def _top_k_of_scores(scores, k: int):
    return jax.lax.approx_max_k(scores, k, recall_target=0.99)


@functools.partial(jax.jit, static_argnames=("k",))
def _top_k_dot_batch_masked(mat, qs, lut, buckets, k: int):
    scores = _score(qs, mat)  # (B, n)
    valid = jnp.take_along_axis(lut, buckets[None, :], axis=1)  # (B, n)
    scores = jnp.where(valid, scores, -jnp.inf)
    return jax.lax.approx_max_k(scores, k, recall_target=0.99)


@functools.lru_cache(maxsize=64)
def _sharded_top_k_fn(mesh, axis: str, k: int, k_final: int, n_real: int,
                      use_lut: bool):
    """Cross-shard top-N: Y's rows shard over ``axis``; each device runs the
    ONE-CHIP scan over its own block — the same ``_score`` matmul and
    ``_top_k_of_scores`` (approximate top-k at the same recall target, fused
    behind the matmul: no ``(B, n_local)`` score matrix is left in HBM) —
    with pad rows and the per-query LSH lut masked as on one chip; the
    ``ndev`` candidate lists of ``(B, k)`` are gathered
    across the mesh and merged with one more top-k. This is the multi-chip
    scan of SURVEY §2.14 ("device-resident Y shards; top-N via sharded
    matmul + lax.top_k + cross-shard merge") — the framework's
    intra-request parallelism.

    Exclusion (known-item filtering, Recommend.java:84-106) is no operand,
    as on one chip: the flush asks for wider ``k`` / ``k_final`` and drops
    the excluded rows from the merged list.

    Operands of the returned jitted program: ``(mat, qs[, lut, buckets])``
    — only what the flag says is used. Its name is stable
    (``jit__sharded_top_k_dot_batch``): the device trace finds it by that,
    and finds the merge's gather as the program's all-gather op."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def local(mat_blk, qs_blk, *rest):
        n_local = mat_blk.shape[0]
        offset = jax.lax.axis_index(axis) * n_local
        scores = _score(qs_blk, mat_blk)  # (B, n_local), fused into the top-k
        if n_real < n_local * mesh.shape[axis]:
            # zero rows past the last id (row count padded to the shards)
            col_ids = offset + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 1)
            scores = jnp.where(col_ids < n_real, scores, -jnp.inf)
        if use_lut:
            lut_blk, buckets_blk = rest
            valid = jnp.take_along_axis(
                lut_blk, buckets_blk[None, :].astype(jnp.int32), axis=1
            )
            scores = jnp.where(valid, scores, -jnp.inf)
        vals, idx = _top_k_of_scores(scores, k)
        # the merge: every shard's candidates to every device, then top-k.
        # The gather is the program's only collective; nothing overlaps it
        with jax.named_scope("topn_merge"):
            vals = jax.lax.all_gather(vals, axis, axis=1, tiled=True)
            idx = jax.lax.all_gather(idx + offset, axis, axis=1, tiled=True)
            mvals, pos = jax.lax.top_k(vals, k_final)  # (B, ndev*k) → k_final
            return mvals, jnp.take_along_axis(idx, pos, axis=1)

    # the replicated P(None, None) operands here are BATCH-shaped
    # (queries/lut: B·k, B·buckets) — a deliberate small
    # broadcast, which the replicated-collective checker keeps quiet on
    # because none of them is data-gathered like a factor table; Y (the
    # model-scaled operand) is the sharded one
    in_specs = (P(axis, None), P(None, None))
    if use_lut:
        in_specs += (P(None, None), P(axis))

    def _sharded_top_k_dot_batch(mat, qs, *rest):
        return shard_map(
            local, mesh=mesh, in_specs=in_specs,
            out_specs=(P(None, None), P(None, None)),
            # every device ends with the same merged list (the gather makes
            # the candidates equal everywhere); all_gather's result is typed
            # as varying, so the static replication check cannot see it
            check_vma=False,
        )(mat, qs, *rest)

    return jax.jit(_sharded_top_k_dot_batch)


@functools.partial(jax.jit, static_argnames=("k",))
def _top_k_cosine_sum(mat, norms, qs, q_norms, valid, k: int):
    # mean cosine similarity to several query vectors (CosineAverageFunction.java)
    sims = (mat @ qs.T) / jnp.maximum(norms[:, None] * q_norms[None, :], 1e-12)
    scores = jnp.where(valid, jnp.mean(sims, axis=1), -jnp.inf)
    return jax.lax.top_k(scores, k)


# -- quantized (int8) candidate scan ----------------------------------------
# The int8 device path reads ¼ the HBM of f32 per scan (the scan is
# bandwidth-bound: one pass over Y per query batch), at the cost of ~0.4%
# relative rounding error per score. The approximate scores only CHOOSE
# candidates; the final ranking comes from an exact f32 rescore of the top
# ``rescore-factor × how_many`` rows gathered from the host factor arena —
# so recall, not precision, is the only quantization exposure.


@jax.jit
def _quant_masked_scores(qmat, qscale, qs, valid):
    """(B, n) approximate scores off the int8 slab: the convert rides the
    matmul operand (XLA fuses it — HBM traffic stays int8), accumulation is
    f32, and the per-row scale lands as one broadcast multiply."""
    scores = jnp.matmul(
        qs, qmat.T.astype(qs.dtype), preferred_element_type=jnp.float32
    ) * qscale[None, :]
    if valid is not None:
        scores = jnp.where(valid[None, :], scores, -jnp.inf)
    return scores


@functools.partial(jax.jit, static_argnames=("k",))
def _quant_candidates(qmat, qscale, qs, valid, k: int):
    """Top-k CANDIDATES (approximate scores) for the exact f32 rescore."""
    return _top_k_of_scores(_quant_masked_scores(qmat, qscale, qs, valid), k)


@functools.partial(jax.jit, static_argnames=("k",))
def _quant_candidates_masked(qmat, qscale, qs, lut, buckets, k: int):
    """Per-query-LUT (LSH) variant of the quantized candidate scan."""
    scores = jnp.matmul(
        qs, qmat.T.astype(qs.dtype), preferred_element_type=jnp.float32
    ) * qscale[None, :]
    valid = jnp.take_along_axis(lut, buckets[None, :], axis=1)
    scores = jnp.where(valid, scores, -jnp.inf)
    return jax.lax.approx_max_k(scores, k, recall_target=0.99)


@functools.partial(jax.jit, static_argnames=("k",))
def _quant_cosine_candidates(qmat, qscale, norms, qs, q_norms, valid, k: int):
    """Mean-cosine candidates off the int8 slab (norms are EXACT f32,
    computed host-side from the arena at snapshot time)."""
    sims = (jnp.matmul(
        qs, qmat.T.astype(qs.dtype), preferred_element_type=jnp.float32
    ) * qscale[None, :]) / jnp.maximum(
        norms[None, :] * q_norms[:, None], 1e-12
    )
    scores = jnp.where(valid, jnp.mean(sims, axis=0), -jnp.inf)
    return jax.lax.top_k(scores, k)


@functools.lru_cache(maxsize=8)
def _derive_sharded(row_sharding, mat_sharding, score_dtype):
    """norms and the scoring copy of a row-sharded Y, each computed where its
    rows live and left split the same way (``score_dtype`` None: no copy)."""

    def derive(mat):
        norms = jnp.linalg.norm(mat, axis=1)
        return norms, (None if score_dtype is None else mat.astype(score_dtype))

    return jax.jit(derive, out_shardings=(
        row_sharding, None if score_dtype is None else mat_sharding))


# -- the scan, whichever view of Y it runs over -----------------------------
# A snapshot (models/als/topn.py:_Snapshot) is the scan backend: it owns its
# program, its width rule and its way of placing operands. What follows is
# written once for all of them.


def _dispatch(snap, qs_host: np.ndarray, width, register: bool):
    """The device side of one call up to the program's launch
    (docs/observability.md): what the coalescer's device-call span is made
    of on this side, for every backend. ``width`` is the backend's static
    width (``snap.batch_width`` for a batch, with the room its exclusions
    need); ``register`` attributes the call to the program's cost key.
    Returns ``(vals, idx)`` still on the device, their copy to the host
    already asked for: what the host does before :func:`_download` runs
    under the scan."""
    with spans.stage("topn.upload"):
        # batch-shaped operands go from the host straight to where the view
        # lies (on a mesh to every device at once, not to one device and on
        # from there inside the dispatch)
        qs = snap.place(qs_host)
        # per-query LSH candidate masks: (B, num_buckets) lookup table
        # indexed by item bucket on device, fully vectorized over the batch
        lut = (snap.place(snap.lsh.get_candidate_lut(qs_host))
               if snap.lsh is not None else None)
    with spans.stage("topn.dispatch") as stage:
        # a recorded stage names the programs it launched as the profiler
        # names their modules, in launch order: a capture's device events
        # are joined to the flush by them (docs/observability.md)
        launched = None if stage is spans.NOOP_SPAN else []
        out = None
        for fn, args, cost_key in snap.plan(qs, lut, width):
            if out is not None:
                args = _operands(args, out)
            if (register and cost_key not in snap.cost_keys_attempted
                    and metrics_mod.default_registry().enabled):
                # first use of this signature this generation: the dispatch
                # below pays the XLA compile anyway — the sanctioned AOT
                # route shares that compile AND yields the executable's
                # cost_analysis, so unwarmed signatures (odd batch sizes,
                # direct callers) still attribute FLOPs instead of reading
                # zero forever
                snap.cost_keys_attempted.add(cost_key)
                compilecache.aot_compile(fn, *args, cost_key=cost_key)
            out = fn(*args)
            if launched is not None:
                launched.append("jit_" + fn.__name__)
            if register:
                profiling.costs().record(cost_key)
        snap.dispatched(len(qs_host), width)
        # the last program's results (never a step's: the next step reads
        # that on the device) are wanted whole on the host right after. Asked
        # for here, the runtime starts both copies once they are ready, side
        # by side; left to _download, each starts only when the host blocks
        # on it
        for array in out:
            array.copy_to_host_async()
        if launched is not None:
            stage.set_attribute("programs", launched)
            stage.set_attribute("copies_ahead", len(out))
    devicephase.enqueued()
    return out


def _download(out):
    """``(vals, idx)`` of a dispatched call, on the host."""
    with spans.stage("topn.wait_download") as stage:
        # the program's run and the copies back that _dispatch asked for:
        # the first conversion blocks until the device is done and its copy
        # has landed, the second finds its own in flight or landed
        vals, idx = out
        first = np.asarray(vals)
        if stage is not spans.NOOP_SPAN:
            # a recorded stage says when the first copy was in hand: what
            # follows is the wait for the second
            stage.set_attribute("first_copy_ms", stage.elapsed_ms())
        arrays = first, np.asarray(idx)
    # told once the arrays are here: the device was done a notification and
    # the copies ago, and whoever scheduled the call has to learn that lag
    # and allow for it
    devicephase.device_done()
    return arrays


def _scan(snap, qs_host: np.ndarray, width, register: bool):
    """One call's device side, end to end: ``(vals, idx)`` on the host."""
    return _download(_dispatch(snap, qs_host, width, register))


def _first_enough(snap, candidates, how_many: int, offset: int, allowed,
                  rescore, dropped=None) -> list[tuple[str, float]]:
    """The widening loop of a single query: ``allowed``/``rescore`` host
    hooks (rescorer SPI) and the ``dropped`` rows (its exclusions) consume
    candidates, so take the backend's next wider list until enough survive
    or it has none wider."""
    want = how_many + offset
    out: list[tuple[str, float]] = []
    for vals, idx in candidates:
        out = _collect(snap, vals, idx, want, allowed, rescore, dropped)
        if len(out) >= want:
            break
    return out[offset:offset + how_many]


def _doubling(k: int, n: int):
    """The widths a widening tries: ``k``, doubled until it reaches ``n``."""
    k = min(n, k)
    while k < n:
        yield k
        k = min(n, k * 2)
    yield k


def _widen_top_k(snap, scores, k: int, q_host: np.ndarray):
    """The flat and int8 widening policy: the scan ran ONCE and ``scores``
    is its (1, n) result; a wider list is only another top-k over it —
    never another full-bandwidth pass over Y."""
    for k in _doubling(k, snap.n):
        vals, idx = _top_k_of_scores(scores, k)
        vals, idx = np.asarray(vals), np.asarray(idx)
        if snap.rescore is not None:
            vals, idx = snap.rescore(q_host[None, :], vals, idx)
        yield vals[0], idx[0]


def _candidate_mask(snap, query_vecs: np.ndarray, n_rows: int):
    """(n_rows,) bool: the rows the query vectors may be answered from —
    those in the union of their LSH candidate buckets. The zero rows that
    pad a sharded Y past its last id are never candidates."""
    real = None if n_rows == snap.n else jnp.arange(n_rows) < snap.n
    if snap.lsh is None:
        return jnp.ones(snap.n, dtype=bool) if real is None else real
    valid = jnp.asarray(snap.bucket_union(query_vecs))[snap.buckets]
    return valid if real is None else valid & real


_Y_SHARD_BYTES = metrics_mod.default_registry().gauge(
    "oryx_serving_y_shard_bytes",
    "Bytes of the newest Y snapshot resident on each device (factors, "
    "scoring copy, norms, buckets)",
    ("device",),
)


class _YSnapshot(_Snapshot):
    """Immutable device view of Y on one device: ids, matrix, norms, LSH
    buckets.

    ``prev`` + ``delta`` ((changed base-row indices, appended-row count) from
    FeatureVectorStore.delta_since) build the snapshot INCREMENTALLY after a
    speed microbatch of point updates: norms and the bf16 scoring copy are
    whole-matrix device ops (no transfer), and LSH buckets recompute for only
    the changed/appended rows — the reference's in-place update semantics
    (ALSServingModel.java:320-370) without ever re-uploading or re-hashing
    the full matrix."""

    def __init__(
        self,
        ids: list[str],
        mat,
        lsh: LocalitySensitiveHash | None,
        prev: "_YSnapshot | None" = None,
        delta: "tuple[np.ndarray, int] | None" = None,
        device_dtype: str = "auto",
    ):
        super().__init__(ids, mat, prev=prev, incremental=delta is not None)
        self.device_dtype = device_dtype
        self.mat = mat  # jax (n_rows, k) or None, float32
        if mat is None:
            self.norms = None
            self.score_mat = None
            self.buckets = None
            return
        # scoring copy: bf16 on TPU halves HBM traffic per scan; exact
        # dots/norms keep the f32 matrix. An explicit
        # oryx.serving.device-dtype overrides the backend heuristic
        # (int8 never reaches this class — see _QuantSnapshot)
        bf16 = device_dtype == "bfloat16" or (
            device_dtype == "auto" and jax.default_backend() == "tpu")
        self.norms, self.score_mat = self._derive(
            mat, jnp.bfloat16 if bf16 else None)
        self.buckets = None
        if lsh and lsh.num_hashes:
            self.lsh = lsh
            self.buckets = self._assign_buckets(prev, delta)

    @classmethod
    def source(cls, store):
        # outside the snapshot lock: the store has a lock of its own, and a
        # first materialization uploads all of Y
        return store.materialize()

    @classmethod
    def current(cls, store, lsh, prev, source, **options):
        """The view of the store's device matrix (``source``) as it stands:
        ``prev`` when the matrix is the one it was built on, else a new one
        — incremental when the store can say what changed since."""
        ids, mat = source
        if prev is not None and prev.mat is mat:
            return prev
        delta = None
        if prev is not None and prev.mat is not None and mat is not None:
            # catch up across any number of incremental generations
            # (e.g. get_vtv consumed pending batches in between)
            delta = store.delta_since(prev.mat, mat)
        return cls(ids, mat, lsh, prev=prev if delta is not None else None,
                   delta=delta, **options)

    def _derive(self, mat, score_dtype):
        return (jnp.linalg.norm(mat, axis=1),
                mat if score_dtype is None else mat.astype(score_dtype))

    def _assign_buckets(self, prev, delta):
        """(n_rows,) LSH bucket of every row."""
        mat, lsh = self.mat, self.lsh
        if prev is None or delta is None or prev.buckets is None:
            return self._place_buckets(
                lsh.assign_buckets(np.asarray(mat)[:self.n]))
        # rehash only the delta: pull changed/new rows (not the whole
        # matrix) to host for bucket assignment
        return self._rehash(prev, *delta)

    def _rehash(self, prev, ch, n_new):
        mat, lsh, buckets = self.mat, self.lsh, prev.buckets
        if len(ch):
            ch_j = jnp.asarray(ch, dtype=jnp.int32)
            new_b = jnp.asarray(lsh.assign_buckets(np.asarray(mat[ch_j])))
            buckets = buckets.at[ch_j].set(new_b)
        if n_new:
            tail = np.asarray(mat[len(prev.ids):])
            buckets = jnp.concatenate(
                [buckets, jnp.asarray(lsh.assign_buckets(tail))]
            )
        return buckets

    def _place_buckets(self, host: np.ndarray):
        return jnp.asarray(host)

    def device_arrays(self) -> list:
        """The distinct arrays this snapshot holds on device (the scoring
        copy only where it is not the float32 matrix itself)."""
        arrays = [self.mat, self.norms, self.buckets]
        if self.score_mat is not self.mat:
            arrays.append(self.score_mat)
        return [a for a in arrays if a is not None]

    @property
    def scanned(self):
        return self.mat

    @property
    def n_rows(self) -> int:
        """Rows of the device arrays: ``n``, padded to the shard count."""
        return self.n if self.mat is None else int(self.mat.shape[0])

    def batch_width(self, how_many: int, filtering: bool, room: int = 0):
        # host filters and LSH masks consume candidates: ask for more
        wide = filtering or self.lsh is not None
        return min(self.n, _round_up_pow2(
            (max(2 * how_many, 64) if wide else max(how_many, 16)) + room)
        ), room

    def plan(self, qs, lut, width):
        k, room = width
        key = _topn_cost_key(qs.shape[0], room)
        if lut is not None:
            return ((_top_k_dot_batch_masked,
                     (self.score_mat, qs, lut, self.buckets, k), key),)
        return ((_top_k_dot_batch, (self.score_mat, qs, None, k), key),)

    def candidates(self, scan, q_host: np.ndarray, want: int, hooks: bool):
        # score once; widenings re-run only the top-k over the cached
        # scores. The unfiltered hot path stays exactly matmul + top_k:
        # the mask is None (static) unless LSH actually applies
        valid = (_candidate_mask(self, q_host[None, :], self.n_rows)
                 if self.lsh is not None else None)
        scores = _masked_scores(
            self.score_mat, jnp.asarray(q_host[None, :]), valid)
        return _widen_top_k(
            self, scores, _round_up_pow2(max(4 * want, 64)), q_host)

    def cosine_candidates(self, qs_host: np.ndarray, want: int):
        qs = jnp.asarray(qs_host)
        q_norms = jnp.linalg.norm(qs, axis=1)
        valid = _candidate_mask(self, qs_host, self.n_rows)
        for k in _doubling(_round_up_pow2(max(4 * want, 64)), self.n):
            vals, idx = _top_k_cosine_sum(
                self.mat, self.norms, qs, q_norms, valid, k)
            yield np.asarray(vals), np.asarray(idx)


class _ShardedYSnapshot(_YSnapshot):
    """The float view with its rows split over a mesh: EVERY per-row array
    (the float32 factors ``mat``, the scoring copy ``score_mat``, ``norms``,
    ``buckets``) is split by rows over ``shard_axis`` as the store
    materialized it: each device holds its own block of ``n_rows / shards``
    rows and derives its norms and scoring rows locally, so Y may exceed a
    single device's memory — no array with all of Y's rows is ever placed on
    one device. ``n_rows`` is ``n`` padded to the shard count; rows past
    ``n`` are zero and masked in every scan."""

    def __init__(self, ids, mat, lsh, mesh, shard_axis: str = "model", **kw):
        self.mesh = mesh if mat is not None else None
        self.shard_axis = shard_axis
        self._everywhere = replicated_sharding(mesh)
        super().__init__(ids, mat, lsh, **kw)
        if mat is not None:
            self._publish_shard_bytes()

    def _derive(self, mat, score_dtype):
        norms, score = _derive_sharded(
            row_sharding(self.mesh, self.shard_axis), mat.sharding,
            score_dtype,
        )(mat)
        return norms, mat if score is None else score

    def _rehash(self, prev, ch, n_new):
        # the bucket vector (4 B a row) makes the round trip; Y does not
        mat, lsh, n = self.mat, self.lsh, self.n
        host = np.zeros(n, dtype=np.int32)
        host[: prev.n] = np.asarray(prev.buckets)[: prev.n]
        if len(ch):
            host[ch] = lsh.assign_buckets(np.asarray(mat[jnp.asarray(ch)]))
        if n_new:
            host[prev.n:] = lsh.assign_buckets(np.asarray(mat[prev.n:n]))
        return self._place_buckets(host)

    def _place_buckets(self, host: np.ndarray):
        # split like the rows (padding rows in bucket 0, masked anyway)
        return put_row_sharded(
            np.asarray(host, dtype=np.int32), self.mesh, self.shard_axis)

    def _publish_shard_bytes(self) -> None:
        per_device: collections.Counter = collections.Counter()
        for arr in self.device_arrays():
            for sh in arr.addressable_shards:
                per_device[sh.device.id] += int(sh.data.nbytes)
        for dev, nbytes in per_device.items():
            _Y_SHARD_BYTES.labels(str(dev)).set(nbytes)

    def place(self, host: np.ndarray):
        return jax.device_put(host, self._everywhere)

    def struct(self, shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=self._everywhere)

    def batch_width(self, how_many: int, filtering: bool, room: int = 0):
        return min(self.n, _round_up_pow2(
            (max(2 * how_many, 64) if filtering else max(how_many, 16))
            + room)), room

    def plan(self, qs, lut, width):
        """The jitted mesh scan for ``k`` results a query — the one-chip
        scan on every shard + cross-shard merge, with the LSH lut applied
        device-side, at least ``min(k, n)`` wide: each shard keeps
        ``k_shard`` candidates (a pow2 ≥ 16, as the one-chip program's
        width), the merge ``min(ndev * k_shard, width)``. It has a cost key
        of its own (its per-call cost is a shard's, plus the merge)."""
        k, room = width
        ndev = self.mesh.shape[self.shard_axis]
        wide = _round_up_pow2(max(min(k, self.n), 16))
        k_shard = min(self.n_rows // ndev, wide)
        fn = _sharded_top_k_fn(
            self.mesh, self.shard_axis, k_shard, min(ndev * k_shard, wide),
            self.n, lut is not None,
        )
        args = (self.score_mat, qs)
        if lut is not None:
            args += (lut, self.buckets)
        return ((fn, args, _topn_cost_key(qs.shape[0], room) + "+sharded"),)

    def candidates(self, scan, q_host: np.ndarray, want: int, hooks: bool):
        # every widening is another scan of the shards, asked for more
        for k in _doubling(max(4 * want, 64) if hooks else want, self.n):
            vals, idx = scan(self, q_host[None, :], (k, 0), register=False)
            yield vals[0], idx[0]


class _QuantSnapshot(_ArenaSnapshot):
    """Immutable int8 device view of Y (``oryx.serving.device-dtype = int8``):
    per-row-scaled int8 factors + exact f32 norms + optional LSH buckets.
    No f32 (or bf16) copy of Y ever lands in HBM — the whole point of the
    mode is holding on ONE chip a Y that float32 cannot: measured on a v5e
    (PERF.md section 6, PR 33), 20M × 250f is 5.16 GB resident and 5.56 GB
    at the peak of the whole warm ladder, a scan 8.0–8.3 ms for up to 64
    queries; a mesh is not needed for that row.

    A speed microbatch of point updates requantizes only the
    changed/appended rows and lands them as row-index scatters, mirroring
    the f32 path's incremental device maintenance. Its programs (and so
    its cost keys, ``+int8``) are distinct from the f32/bf16 scan's: the
    attribution and the warm ladder see them as their own signatures."""

    def __init__(self, ids, version: int, qmat, qscale, norms, buckets,
                 lsh=None, prev: "_QuantSnapshot | None" = None,
                 incremental: bool = False, slab=None, slab_rows=None,
                 rescore_factor: float = 4.0):
        self.qmat = qmat        # (n, k) int8 device
        self.qscale = qscale    # (n,) f32 device
        self.norms = norms      # (n,) f32 device, exact
        self.buckets = buckets  # (n,) int32 device or None
        super().__init__(ids, version, qmat,
                         lsh if buckets is not None else None, slab,
                         slab_rows, rescore_factor, prev, incremental)

    @property
    def scanned(self):
        return self.qmat

    def quantized_nbytes(self) -> int:
        """Device bytes held by the quantized factors (the
        oryx_device_quantized_factor_bytes gauge)."""
        total = 0
        for arr in (self.qmat, self.qscale):
            total += int(getattr(arr, "nbytes", 0) or 0)
        return total

    def device_arrays(self) -> list:
        return [a for a in (self.qmat, self.qscale, self.norms, self.buckets)
                if a is not None]

    host_copy = False  # ``build`` reads the pinned view a block at a time

    @classmethod
    def build(cls, ids, host: None, version: int,
              lsh: "LocalitySensitiveHash | None",
              row_view: tuple,
              prev: "_QuantSnapshot | None" = None,
              rescore_factor: float = 4.0):
        """Full quantized build out of the pinned ``(slab, rows)`` view, a
        block of rows at a time (``topn._QUANT_BLOCK``): quantized on the
        host's threads, uploaded, written into its place on the device. No
        float32 copy of Y is made on either side (``host`` is None:
        ``host_copy``), and the host never holds all of the int8 rows."""
        slab, slab_rows = row_view
        if len(ids) == 0 or slab is None or not len(slab_rows):
            return cls(list(ids), version, None, None, None, None,
                       rescore_factor=rescore_factor)
        hashed = bool(lsh and lsh.num_hashes)

        def blocks():
            for start, q, scale, norms in _quantize_blocks(slab, slab_rows):
                block = (start, q, scale, norms)
                if hashed:
                    block += (lsh.assign_buckets(
                        slab[slab_rows[start:start + len(q)]]),)
                yield block

        n, k = len(slab_rows), slab.shape[1]
        with spans.span("snapshot.quantize",
                        attributes={"rows": n, "bytes": n * (k + 4)}):
            qmat, qscale, norms, *buckets = _upload_blocks(blocks(), n)
            qmat.block_until_ready()  # the span times the build, not the enqueue
        return cls(list(ids), version, qmat, qscale, norms,
                   buckets[0] if hashed else None, lsh, prev=prev,
                   slab=slab, slab_rows=slab_rows,
                   rescore_factor=rescore_factor)

    @classmethod
    def from_delta(cls, prev: "_QuantSnapshot", delta):
        """Incremental step: requantize only the changed/appended rows and
        land them as device row scatters / one append."""
        qmat, qscale, norms, buckets = (
            prev.qmat, prev.qscale, prev.norms, prev.buckets
        )
        lsh = prev.lsh
        changed_pos = [prev.id_to_idx[i] for i in delta.changed_ids
                       if i in prev.id_to_idx]
        if changed_pos:
            pos = jnp.asarray(changed_pos, dtype=jnp.int32)
            qc, sc = _quantize_rows(delta.changed_vals)
            qmat = qmat.at[pos].set(jnp.asarray(qc))
            qscale = qscale.at[pos].set(jnp.asarray(sc))
            norms = norms.at[pos].set(
                jnp.asarray(np.linalg.norm(delta.changed_vals, axis=1))
            )
            if buckets is not None:
                buckets = buckets.at[pos].set(
                    jnp.asarray(lsh.assign_buckets(delta.changed_vals))
                )
        if delta.appended_ids:
            qa, sa = _quantize_rows(delta.appended_vals)
            qmat = jnp.concatenate([qmat, jnp.asarray(qa)])
            qscale = jnp.concatenate([qscale, jnp.asarray(sa)])
            norms = jnp.concatenate([norms, jnp.asarray(
                np.linalg.norm(delta.appended_vals, axis=1))])
            if buckets is not None:
                buckets = jnp.concatenate([buckets, jnp.asarray(
                    lsh.assign_buckets(delta.appended_vals))])
        ids, slab_rows = prev.appended(delta)
        return cls(ids, delta.version, qmat, qscale, norms, buckets, lsh,
                   prev=prev, incremental=True, slab=delta.slab,
                   slab_rows=slab_rows, rescore_factor=prev.rescore_factor)

    def batch_width(self, how_many: int, filtering: bool, room: int = 0):
        return min(self.n, _round_up_pow2(
            self.rescore_width(how_many) + room)), room

    def plan(self, qs, lut, width):
        """Top-``r`` CANDIDATES (approximate scores) for the exact rescore:
        ONE quantized scan over the whole query batch (¼ the f32 HBM per
        pass); the per-query lut selects the masked program."""
        r, room = width
        key = _topn_cost_key(qs.shape[0], room, quant=True)
        if lut is not None:
            return ((_quant_candidates_masked,
                     (self.qmat, self.qscale, qs, lut, self.buckets, r),
                     key),)
        return ((_quant_candidates,
                 (self.qmat, self.qscale, qs, None, r), key),)

    def candidates(self, scan, q_host: np.ndarray, want: int, hooks: bool):
        # the quantized matmul runs ONCE, exactly like the f32 path; each
        # widening rescores its candidates exactly from the arena
        valid = (_candidate_mask(self, q_host[None, :], self.n)
                 if self.lsh is not None else None)
        scores = _quant_masked_scores(
            self.qmat, self.qscale, jnp.asarray(q_host[None, :]), valid)
        return _widen_top_k(self, scores, self.rescore_width(want), q_host)

    def cosine_candidates(self, qs_host: np.ndarray, want: int):
        # quantized candidates (norms are exact f32), exact mean-cosine
        # rescore from the arena slab before the final cut
        qs = jnp.asarray(qs_host)
        q_norms = jnp.linalg.norm(qs, axis=1)
        valid = _candidate_mask(self, qs_host, self.n)
        for r in _doubling(self.rescore_width(want), self.n):
            v, i = _quant_cosine_candidates(
                self.qmat, self.qscale, self.norms, qs, q_norms, valid, r)
            vals, idx = self.rescore(
                qs_host, np.asarray(v)[None, :], np.asarray(i)[None, :],
                cosine=True)
            yield vals[0], idx[0]


class ALSServingModel(ServingModel):
    def __init__(
        self,
        features: int,
        implicit: bool,
        sample_rate: float = 1.0,
        mesh=None,
        shard_axis: str = "model",
        device_dtype: str = "auto",
        rescore_factor: float = 4.0,
        index_enabled: bool = False,
        index_cells: int = 0,
        index_probes: int = 8,
        index_skew: float = 4.0,
    ):
        self.features = features
        self.implicit = implicit
        self.sample_rate = sample_rate
        if device_dtype not in _DEVICE_DTYPES:
            raise ValueError(
                f"oryx.serving.device-dtype must be one of {_DEVICE_DTYPES}, "
                f"not {device_dtype!r}"
            )
        if device_dtype == "int8" and mesh is not None:
            # the sharded scan's shard_map programs are f32/bf16; quantized
            # sharding is a later round — degrade loudly, never silently.
            # Measured (PERF.md section 6, PR 33): int8 holds the 20M x 250f
            # row on ONE v5e chip, so a mesh is not needed for it
            log.warning(
                "device-dtype=int8 is not supported with sharded serving; "
                "using bfloat16 for the sharded scoring copy (int8 holds "
                "20M x 250f on one chip: 5.2 GB of rows and scales; turn "
                "oryx.serving.compute.sharded off to serve from it)"
            )
            device_dtype = "bfloat16"
        if index_enabled and device_dtype != "int8":
            # the IVF cells ARE the int8 representation (and the rescore
            # rides the int8 mode's pinned arena-slab view) — any other
            # resolved dtype means the index cannot engage
            log.warning(
                "oryx.serving.index.enabled requires device-dtype=int8 "
                "(resolved %r); serving without the IVF index", device_dtype
            )
            index_enabled = False
        self.index_enabled = bool(index_enabled)
        self.index_cells = int(index_cells)
        self.index_probes = max(1, int(index_probes))
        self.index_skew = max(1.0, float(index_skew))
        self.device_dtype = device_dtype
        self.rescore_factor = max(1.0, float(rescore_factor))
        self.mesh = mesh
        self.shard_axis = shard_axis
        self.x = FeatureVectorStore()
        # under a mesh the store hands every device its own row block of Y
        # (int8 / IVF snapshots never use the store's device matrix)
        self.y = FeatureVectorStore(mesh=mesh, shard_axis=shard_axis)
        self.lsh = LocalitySensitiveHash(sample_rate, features) if sample_rate < 1.0 else None
        self.known = KnownItems()
        self.expected_user_ids: set[str] = set()
        self.expected_item_ids: set[str] = set()
        self.yty_cache = SolverCache(self.y.get_vtv)
        # the scan backend, chosen here where the options are resolved and
        # never asked about again: every snapshot it builds owns its
        # program, its width and its warm signatures (topn.py:_Snapshot)
        if self.index_enabled:
            backend, options = ivf_mod.IVFSnapshot, dict(
                cells=self.index_cells, probes=self.index_probes,
                skew_bound=self.index_skew,
                rescore_factor=self.rescore_factor)
        elif device_dtype == "int8":
            backend, options = _QuantSnapshot, dict(
                rescore_factor=self.rescore_factor)
        elif mesh is not None:
            backend, options = _ShardedYSnapshot, dict(
                mesh=mesh, shard_axis=shard_axis, device_dtype=device_dtype)
        else:
            backend, options = _YSnapshot, dict(device_dtype=device_dtype)
        self._source = backend.source
        self._current = functools.partial(backend.current, **options)
        self._snapshot: _Snapshot | None = None
        self._snap_lock = threading.Lock()

    # -- vector + known-item bookkeeping ------------------------------------
    def set_user_vector(self, user: str, vec) -> None:
        self.x.set_vector(user, vec)
        self.expected_user_ids.discard(user)

    def set_item_vector(self, item: str, vec) -> None:
        self.y.set_vector(item, vec)
        self.expected_item_ids.discard(item)
        self.yty_cache.set_dirty()

    def bulk_load_users(self, ids, matrix, adopt: bool = False) -> None:
        """Whole-matrix X handoff keeping model bookkeeping consistent
        (``adopt``: as ``bulk_load_items``)."""
        self.x.bulk_load(ids, matrix, adopt=adopt)
        self.expected_user_ids.difference_update(ids)

    def bulk_load_items(self, ids, matrix, adopt: bool = False) -> None:
        """Whole-matrix Y handoff keeping model bookkeeping consistent.
        ``adopt``: the caller gives ``matrix`` up and the arena takes it as
        its slab where it can (``FeatureVectorStore.bulk_load``)."""
        self.y.bulk_load(ids, matrix, adopt=adopt)
        self.expected_item_ids.difference_update(ids)
        self.yty_cache.set_dirty()

    def get_user_vector(self, user: str):
        return self.x.get_vector(user)

    def get_item_vector(self, item: str):
        return self.y.get_vector(item)

    def bulk_load_known_items(self, user_ids, offsets, items, item_ids) -> None:
        """A generation's known items at once, beside ``bulk_load_users``:
        user ``user_ids[u]`` knows ``item_ids[j]`` for every ``j`` of
        ``items[offsets[u]:offsets[u + 1]]`` (models/als/known.py)."""
        self.known.bulk_load(user_ids, offsets, items, item_ids)

    def add_known_items(self, user: str, items: Sequence[str]) -> None:
        self.known.add(user, items)

    def get_known_items(self, user: str) -> set[str]:
        return self.known.ids(user)

    def known_item_codes(self, user: str) -> "np.ndarray | None":
        """The user's known items as ``top_n_batch(excluded=...)`` takes them
        from the default ``/recommend``: interned item codes, one dictionary
        lookup a request; None where the user has none."""
        codes = self.known.codes(user)
        return codes if len(codes) else None

    def get_known_item_vectors_for_user(self, user: str) -> list[tuple[str, np.ndarray]]:
        """(ALSServingModel.getKnownItemVectorsForUser)"""
        out = []
        for item in self.get_known_items(user):
            v = self.y.get_vector(item)
            if v is not None:
                out.append((item, v))
        return out

    def item_counts(self) -> dict[str, int]:
        """How many users know each item (ALSServingModel.getItemCounts)."""
        return self.known.item_counts()

    def user_counts(self) -> dict[str, int]:
        """Known-item count per user (MostActiveUsers source)."""
        return self.known.user_counts()

    def all_user_ids(self) -> list[str]:
        return self.x.ids()

    def all_item_ids(self) -> list[str]:
        return self.y.ids()

    def retain_recent_and_user_ids(self, ids) -> None:
        self.x.retain_recent_and_ids(set(ids))

    def retain_recent_and_item_ids(self, ids) -> None:
        self.y.retain_recent_and_ids(set(ids))
        self.yty_cache.set_dirty()

    def retain_recent_and_known_items(self, users) -> None:
        self.known.retain_users(users)

    def get_fraction_loaded(self) -> float:  # ALSServingModel.java:396
        total = len(self.expected_user_ids) + len(self.expected_item_ids)
        total += self.x.size() + self.y.size()
        if total == 0:
            return 1.0
        return (self.x.size() + self.y.size()) / total

    # -- device snapshot ----------------------------------------------------
    def y_snapshot(self):
        """The backend's current view of Y (``.n``, ``.ids``, ``.mesh``):
        the one it last built while the store has not moved, else the next —
        incremental where the backend can take the step."""
        source = self._source(self.y)
        with self._snap_lock:
            self._snapshot = self._current(
                self.y, self.lsh, self._snapshot, source)
            return self._snapshot

    # -- query primitives ----------------------------------------------------
    def _excluded_rows(self, snap, excluded) -> "np.ndarray | None":
        """One query's exclusions as rows of ``snap``: item codes (what
        ``known_item_codes`` gives the default endpoint) by one fancy index
        into the code → row table, item ids (a request's own) by a lookup
        each. None where there are none."""
        if excluded is None or not len(excluded):
            return None
        if isinstance(excluded, np.ndarray):
            rows = self.known.rows_in(snap)[excluded]
            return rows[rows >= 0]
        return np.fromiter(
            (r for r in map(snap.id_to_idx.get, excluded) if r is not None),
            dtype=np.int32)

    def top_n(
        self,
        query_vec: np.ndarray,
        how_many: int,
        offset: int = 0,
        allowed: "Callable[[str], bool] | None" = None,
        rescore: "Callable[[str, float], float] | None" = None,
        excluded: "Sequence[str] | np.ndarray | None" = None,
    ) -> list[tuple[str, float]]:
        """Dot-product top-N over Y: one matmul + top_k (ALSServingModel.topN
        :261-276, TopNConsumer:56-73). ``excluded`` (item ids, or the codes
        of ``known_item_codes``: known-item filtering) are rows dropped from
        an over-fetched list; ``allowed``/``rescore`` host hooks (rescorer
        SPI) filter the candidate stream with widening retry."""
        snap = self.y_snapshot()
        if not snap.servable:
            return []
        return self._top_n(snap, np.asarray(query_vec, dtype=np.float32),
                           how_many, offset, allowed, rescore,
                           self._excluded_rows(snap, excluded))

    @staticmethod
    def _top_n(snap, q_host: np.ndarray, how_many: int, offset: int, allowed,
               rescore, dropped) -> list[tuple[str, float]]:
        room = 0 if dropped is None else len(dropped)
        return _first_enough(
            snap,
            snap.candidates(_scan, q_host, how_many + offset + room,
                            allowed is not None or rescore is not None),
            how_many, offset, allowed, rescore, dropped)

    def top_n_batch(
        self,
        query_vecs: np.ndarray,
        how_many: int,
        alloweds: "Sequence[Callable[[str], bool] | None] | None" = None,
        excluded: "Sequence[Sequence[str] | np.ndarray | None] | None" = None,
    ) -> list[list[tuple[str, float]]]:
        """Micro-batched top-N: many queries in ONE matmul+top_k device call —
        the TPU-idiomatic serving pattern (amortizes per-call overhead that the
        reference spends thread-fanning partition scans). ``excluded[b]``
        (item ids or ``known_item_codes``) are left out by over-fetching:
        the scan is asked for room enough for the longest of them and their
        rows are dropped from its lists; ``alloweds`` host callables
        (rescorer SPI) filter after the scan. One histogram observe + one
        counter add per CALL (not per query) keeps the hot path inside the
        metrics budget."""
        _TOPN_QUERIES.inc(len(query_vecs))
        t0 = time.perf_counter()
        try:
            return self._top_n_batch(query_vecs, how_many, alloweds, excluded)
        finally:
            # exemplar: the coalescer activates its device-call span around
            # this call, so a slow bucket points at that concrete trace
            _TOPN_BATCH_SECONDS.observe(
                time.perf_counter() - t0, exemplar=spans.current_trace_id()
            )

    def _exclusions(self, snap, excluded, batch: int, room: int) -> np.ndarray:
        """A flush's exclusions as rows of ``snap``: ``(batch, E)`` int32, -1
        where a query has fewer than the longest (or an item has no row).
        Built while the device scans, dropped from its lists afterwards."""
        with spans.stage("topn.exclude") as sp:
            table = self.known.rows_in(snap)
            lengths = [0 if e is None else len(e) for e in excluded]
            rows = np.full((batch, max(lengths)), -1, dtype=np.int32)
            for b, e in enumerate(excluded):
                if not lengths[b]:
                    continue
                if isinstance(e, np.ndarray):  # item codes
                    rows[b, :lengths[b]] = table[e]
                else:
                    known = self._excluded_rows(snap, e)
                    rows[b, :len(known)] = known
            entries = int((rows >= 0).sum())
            overflowed = sum(1 for n in lengths if n > room)
            _EXCLUDED_ENTRIES.inc(entries)
            if overflowed:
                _EXCLUSION_OVERFLOW.inc(overflowed)
            sp.set_attribute("entries", entries)
            sp.set_attribute("width", room)
            sp.set_attribute("overflowed", overflowed)
        return rows

    def _top_n_batch(
        self,
        query_vecs: np.ndarray,
        how_many: int,
        alloweds: "Sequence[Callable[[str], bool] | None] | None" = None,
        excluded: "Sequence[Sequence[str] | np.ndarray | None] | None" = None,
    ) -> list[list[tuple[str, float]]]:
        snap = self.y_snapshot()
        if not snap.servable:
            return [[] for _ in range(len(query_vecs))]
        qs_host = np.asarray(query_vecs, dtype=np.float32)
        filtering = alloweds is not None and any(a is not None for a in alloweds)
        # the room comes from the lengths as handed over (a code or id with
        # no row only makes it generous); a request with nothing to leave
        # out does no more than this test
        room = 0 if excluded is None else _room_for(
            max((len(e) for e in excluded if e is not None), default=0))
        out = _dispatch(snap, qs_host,
                        snap.batch_width(how_many, filtering, room),
                        register=True)
        # under the scan: which rows each query leaves out
        rows = (self._exclusions(snap, excluded, len(qs_host), room)
                if room else None)
        vals, idx = _download(out)
        if snap.rescore is not None:
            # an approximate backend's candidates, exact-f32-rescored from
            # the arena slab before the final cut
            with spans.stage("topn.rescore") as sp:
                sp.set_attribute("candidates", int(idx.size))
                sp.set_attribute("width", int(idx.shape[1]))
                vals, idx = snap.rescore(qs_host, vals, idx)
        with spans.stage("topn.ids"):
            dropped = None
            if room:
                vals, idx, dropped = _drop_rows(vals, idx, rows)
            if not filtering:
                out = _id_lists(snap.ids, vals, idx, how_many)
            else:
                out = [_collect(snap, vals[b], idx[b], how_many,
                                alloweds[b] if alloweds else None,
                                None)[:how_many]
                       for b in range(len(qs_host))]
            if (filtering or dropped is not None) and vals.shape[1] < snap.n:
                for b, got in enumerate(out):
                    if len(got) < how_many and (filtering or dropped[b]):
                        # host filters, or more of this query's exclusions
                        # among its best than there was room for, consumed
                        # its candidates and the scan was narrower than Y —
                        # fall back to the widening single-query path
                        out[b] = self._top_n(
                            snap, qs_host[b], how_many, 0,
                            alloweds[b] if alloweds else None, None,
                            None if rows is None else rows[b][rows[b] >= 0])
            return out

    def warm_bucket(self, batch_size: int, how_many: int = 10) -> None:
        """Pre-compile the batched top-N programs for ONE pow2 batch size
        against the live factor shapes — the per-bucket unit of the serving
        warmup ladder (serving/app.py _BatchWarmer, smallest bucket first).

        Two steps: an AOT ``jitted.lower(shapes).compile()`` via
        :func:`compilecache.aot_compile` (seeds the in-process lowering
        cache AND, when ``oryx.compile.cache-dir`` is set, the persistent
        cache — so restarts and sibling replicas skip the XLA compile
        entirely), then one real zero-batch execution to populate the jit
        dispatch cache the request path actually hits and to materialize
        the device-resident factor snapshot. Raises when the model has no
        items yet (the warmer retries later).

        What is compiled is what the flush dispatches: the snapshot's own
        ``plan``, asked with shapes where ``_dispatch`` asks with arrays, under
        the same cost keys — so a handoff warms exactly the signatures its
        traffic runs, whichever backend serves it.

        EVERY width a flush of this size can ask for warms: the plain one
        and one for each over-fetch room (``topn._OVERFETCH_ROOM``) — the
        default ``/recommend`` path (considerKnownItems=false) always hands
        over its user's known items, and a flush takes the least room that
        holds the longest history in it, the widest for any longer one. The
        set is closed: no history length reaches a width this did not
        compile, so the first client burst after a MODEL handoff pays no
        compile on the endpoint it actually calls."""
        snap = self.y_snapshot()
        if not snap.servable:
            raise ValueError("no item factors to warm against yet")
        qs = snap.struct((batch_size, self.features), jnp.float32)
        lut = (snap.struct((batch_size, snap.lsh.num_buckets), jnp.bool_)
               if snap.lsh is not None else None)
        compiled = set()
        for room in _OVERFETCH_ROOM:
            width = snap.batch_width(how_many, False, room)
            for fn, args, cost_key in snap.plan(qs, lut, width):
                if cost_key in compiled:
                    continue  # a step the widths share
                compiled.add(cost_key)
                compilecache.aot_compile(
                    fn, *_operands(args), cost_key=cost_key)
        # marked attempted: the lazy first-use registration in _dispatch would
        # otherwise re-lower and re-compile each signature the ladder just
        # registered — once per signature per generation, during the
        # handoff warm window
        snap.cost_keys_attempted.update(compiled)
        zeros = np.zeros((batch_size, self.features), dtype=np.float32)
        self.top_n_batch(zeros, how_many)
        # one real execution at each over-fetched width: a first query that
        # leaves out as many rows as the room holds (whichever rows: the
        # queries are zero) dispatches the very program a history of that
        # length does
        for room in _OVERFETCH_ROOM[1:]:
            self.top_n_batch(
                zeros, how_many,
                excluded=[snap.ids[:room]] + [None] * (batch_size - 1))

    def top_n_cosine(
        self,
        query_vecs: np.ndarray,
        how_many: int,
        offset: int = 0,
        allowed: "Callable[[str], bool] | None" = None,
        rescore: "Callable[[str, float], float] | None" = None,
    ) -> list[tuple[str, float]]:
        """Mean-cosine top-N for /similarity (CosineAverageFunction.java:67)."""
        snap = self.y_snapshot()
        if not snap.servable:
            return []
        qs_host = np.atleast_2d(np.asarray(query_vecs, dtype=np.float32))
        return _first_enough(
            snap, snap.cosine_candidates(qs_host, how_many + offset),
            how_many, offset, allowed, rescore)

    def device_factor_bytes(self) -> int:
        """Bytes the current Y snapshot holds on device (f32 matrix +
        scoring copy + norms + buckets, or the int8 slab + scales, or the
        index's cells; summed over the devices of a mesh) — the HBM side of
        the bench memory section's f32-vs-int8 comparison."""
        return int(sum(
            int(getattr(a, "nbytes", 0) or 0)
            for a in self.y_snapshot().device_arrays()
        ))

    def dot_with_items(self, query_vec: np.ndarray, item_ids: Sequence[str]) -> list[float]:
        q = np.asarray(query_vec, dtype=np.float32)
        return [
            float(np.dot(q, v)) if (v := self.y.get_vector(i)) is not None else 0.0
            for i in item_ids
        ]

    def get_yty_solver(self):
        return self.yty_cache.get(blocking=True)

    def precompute_solvers(self) -> None:
        self.yty_cache.compute_now()

    def build_temporary_user_vector(
        self, item_values: Sequence[tuple[str, float]], xu: "np.ndarray | None" = None
    ) -> "np.ndarray | None":
        """Fold a context of (item, value) pairs into a temporary user vector
        (EstimateForAnonymous.buildTemporaryUserVector)."""
        from oryx_tpu.models.als import foldin

        solver = self.get_yty_solver()
        if solver is None:
            return None
        vec = None if xu is None else np.asarray(xu, dtype=np.float32)
        for item, value in item_values:
            yi = self.y.get_vector(item)
            new_vec = foldin.compute_updated_xu(solver, value, vec, yi, self.implicit)
            if new_vec is not None:
                vec = new_vec
        return vec


class ALSServingModelManager(AbstractServingModelManager):
    def __init__(self, config):
        super().__init__(config)
        self.sample_rate = config.get_float("oryx.als.sample-rate")
        self.min_model_load_fraction = config.get_float("oryx.serving.min-model-load-fraction")
        # device-factor representation: "auto" (bf16 scoring copy on TPU),
        # explicit "float32"/"bfloat16", or "int8" (per-row-scaled slab +
        # exact f32 rescore of the top rescore-factor x n candidates)
        self.device_dtype = config.get_string(
            "oryx.serving.device-dtype", "auto"
        )
        if self.device_dtype not in _DEVICE_DTYPES:
            raise ValueError(
                f"oryx.serving.device-dtype must be one of {_DEVICE_DTYPES}, "
                f"not {self.device_dtype!r}"
            )
        self.rescore_factor = config.get_float(
            "oryx.serving.rescore-factor", 4.0
        )
        # device-resident IVF candidate generation (sublinear serving
        # scan); engages only with device-dtype=int8 — the cells are the
        # int8 representation and the rescore rides the arena slab
        self.index_enabled = config.get_bool(
            "oryx.serving.index.enabled", False
        )
        self.index_cells = config.get_int("oryx.serving.index.cells", 0)
        self.index_probes = config.get_int("oryx.serving.index.probes", 8)
        self.index_skew = config.get_float(
            "oryx.serving.index.rebalance-skew", 4.0
        )
        # opportunistic YᵀY pre-trigger once the model is loaded enough, so
        # the first fold-in request doesn't stall on the factorization
        # (ALSServingModelManager.java:95-105); rate-limited like the
        # reference's test-and-trigger
        self._solver_trigger_rate = RateLimitCheck(5)
        self.model: ALSServingModel | None = None
        # double-buffered generation handoff: with the batch warmer running,
        # a MODEL push with new array shapes builds the incoming generation
        # here while the warm old generation keeps answering queries; the
        # warmer precompiles the staged model's buckets off-path and then
        # promotes it atomically — an update-topic model push never causes a
        # request-visible compile storm
        self._staged: ALSServingModel | None = None
        self._staged_at = 0.0
        self._swap_lock = threading.Lock()
        self._prewarm_swap = (
            config.get_bool("oryx.serving.compute.precompile-batches", False)
            and config.get_bool("oryx.compile.prewarm-swap", True)
        )
        self._swap_deadline = config.get_float(
            "oryx.compile.swap-deadline-sec", 120.0
        )
        _LOAD_FRACTION.set_function(_load_fraction_fn(weakref.ref(self)))
        self.rescorer_provider = load_rescorer_providers(config)
        self.mesh = None
        if config.get_bool("oryx.serving.compute.sharded", False):
            from oryx_tpu.parallel.mesh import make_mesh

            if len(jax.devices()) > 1:
                self.mesh = make_mesh(axes=("model",))
                log.info("serving Y sharded over %d devices", self.mesh.size)
            else:
                log.info("sharded serving requested but only one device")

    def get_model(self) -> "ALSServingModel | None":
        # deadline valve on the request path: one None-check when no swap is
        # staged; a staged generation whose warmer died (or whose warm keeps
        # failing) must still land eventually rather than strand the push.
        # Lock-free reads: single reference loads are atomic under the GIL
        # and a stale value is benign (the old generation stays valid until
        # the flip, which happens under _swap_lock and re-checks there)
        staged = self._staged  # analyze: ignore[lock-discipline] -- atomic reference load on the hot path; flip is under _swap_lock
        if staged is not None and self._swap_deadline > 0 and (
            time.monotonic() - self._staged_at > self._swap_deadline  # analyze: ignore[lock-discipline] -- _staged_at is written before _staged publishes, so a visible staged model always pairs with its own timestamp
        ):
            if self._promote_staged(expected=staged, deadline=True):
                log.warning(
                    "promoting staged model generation unwarmed: swap "
                    "deadline (%.0fs) passed", self._swap_deadline,
                )
        return self.model  # analyze: ignore[lock-discipline] -- atomic reference load on the hot path; flip is under _swap_lock

    def get_staged_model(self) -> "ALSServingModel | None":
        with self._swap_lock:
            return self._staged

    def promote_staged(self, expected=None) -> bool:
        """Atomically flip the warmed staged generation into service
        (called by the batch warmer after its bucket ladder completes).
        ``expected`` guards against promoting a model the caller did not
        warm: if a later MODEL push replaced the staged generation while
        the ladder ran, the flip is refused and the warmer re-runs."""
        return self._promote_staged(expected=expected, deadline=False)

    def _promote_staged(self, expected, deadline: bool) -> bool:
        with self._swap_lock:
            staged = self._staged
            if staged is None or (expected is not None and staged is not expected):
                return False
            self.model = staged
            self._staged = None
        (_DEADLINE_SWAPS if deadline else _PREWARMED_SWAPS).inc()
        # adoption timeline: the staged generation just went into service
        # (idempotent on the tracker side — the warmer and the deadline
        # valve can both report the same flip)
        lineage.tracker().mark_live()
        return True

    def _current_generation(self) -> "ALSServingModel | None":
        """The generation the update topic is describing NOW: the staged
        model once a MODEL handoff is in flight, else the serving one."""
        with self._swap_lock:
            return self._staged or self.model

    def consume_key_message(self, key: str, message: str) -> None:
        if key == "UP":
            model = self._current_generation()
            if model is None:
                return
            update = json.loads(message)
            kind, id_, vec = update[0], update[1], np.asarray(update[2], dtype=np.float32)
            if kind == "X":
                model.set_user_vector(id_, vec)
                if len(update) > 3:
                    model.add_known_items(id_, update[3])
            elif kind == "Y":
                model.set_item_vector(id_, vec)
            else:
                raise ValueError(f"bad update type: {kind}")
            self._maybe_trigger_solvers()
        elif key in ("MODEL", "MODEL-REF"):
            pmml = read_pmml_from_update_key_message(key, message)
            meta = pmml_codec.pmml_to_meta(pmml)
            features = meta["features"]
            current = self._current_generation()
            if current is None or current.features != features:
                new_model = ALSServingModel(
                    features, meta["implicit"], self.sample_rate,
                    mesh=self.mesh, device_dtype=self.device_dtype,
                    rescore_factor=self.rescore_factor,
                    index_enabled=self.index_enabled,
                    index_cells=self.index_cells,
                    index_probes=self.index_probes,
                    index_skew=self.index_skew,
                )
                # the handoff meta names every expected row: presize the
                # arenas so the fill skips doubling-growth copies
                new_model.x.reserve(len(meta["x_ids"]))
                new_model.y.reserve(len(meta["y_ids"]))
                new_model.expected_user_ids = set(meta["x_ids"])
                new_model.expected_item_ids = set(meta["y_ids"])
                with self._swap_lock:
                    if self.model is not None and self._prewarm_swap:
                        # double-buffer: keep serving the old generation; the
                        # warmer fills/warms this one off-path, then promotes.
                        # Timestamp BEFORE publishing the reference: the
                        # deadline valve reads both lock-free, and the old
                        # order let it pair a fresh staged model with a
                        # stale timestamp and promote it cold on the spot
                        staging = True
                        self._staged_at = time.monotonic()
                        self._staged = new_model
                    else:
                        staging = False
                        self.model = new_model
                        self._staged = None
                log.info("%s serving model generation (features=%d)",
                         "staging" if staging else "new", features)
            else:
                m = current
                m.retain_recent_and_user_ids(meta["x_ids"])
                m.retain_recent_and_item_ids(meta["y_ids"])
                m.retain_recent_and_known_items(meta["x_ids"])
                m.expected_user_ids = set(meta["x_ids"]) - set(m.x.ids())
                m.expected_item_ids = set(meta["y_ids"]) - set(m.y.ids())
            self._maybe_trigger_solvers()  # MODEL alone may cross the threshold
        else:
            raise ValueError(f"bad key: {key}")

    def _maybe_trigger_solvers(self) -> None:
        """Kick the async YᵀY factorization once the model passes the load
        fraction, so the first /estimateForAnonymous doesn't stall on it
        (ALSServingModelManager.java:95-105). Rate-limited: the fraction test
        walks the expected-ID sets, too costly per UP message; the launch
        itself is a no-op when the cache is clean (single-flight dirty flag),
        so later UPs re-warm naturally."""
        # the CURRENT generation: during a staged swap the UPs are filling
        # the staged model, and promoting it with a cold YtY solver would
        # stall the first post-flip fold-in on the synchronous factorization
        model = self._current_generation()
        if model is None or not self._solver_trigger_rate.test():
            return
        if model.get_fraction_loaded() >= self.min_model_load_fraction:
            model.precompute_solvers()
