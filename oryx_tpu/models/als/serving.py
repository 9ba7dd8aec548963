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
import math
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
from oryx_tpu.common import lineage
from oryx_tpu.common import metrics as metrics_mod
from oryx_tpu.common import profiling
from oryx_tpu.common import spans
from oryx_tpu.models.als import ivf as ivf_mod
from oryx_tpu.models.als import pmml_codec
from oryx_tpu.models.als.lsh import LocalitySensitiveHash
from oryx_tpu.models.als.rescorer import load_rescorer_providers
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


def _load_fraction_fn(manager_ref):
    """Scrape-time gauge callback over a WEAK manager ref: a strong ref
    would pin a retired manager (and its factor matrices) for the process
    lifetime after a test or redeploy drops it."""

    def fn() -> float:
        manager = manager_ref()
        model = manager.get_model() if manager is not None else None
        return model.get_fraction_loaded() if model is not None else 0.0

    return fn


def _round_up_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


#: Floor of the pow2-bucketed exclusion-mask width. Known-item exclusion is
#: what the DEFAULT /recommend path sends (considerKnownItems=false), so its
#: jit signature must be shape-stable enough to PRE-warm: flooring the width
#: means every request with ≤ this many known items — the overwhelming
#: common case — lands on ONE compiled program, which the batch warmer
#: compiles off-path (warm_bucket). Users past the floor bucket up by pow2
#: and pay one compile per bucket per process (persistent-cache-served
#: afterwards), exactly like unusual howMany values.
_EXCL_PAD_MIN = 8


#: Valid values of ``oryx.serving.device-dtype``: "auto" keeps the historic
#: behavior (bf16 scoring copy on TPU, f32 elsewhere); explicit f32/bf16
#: force the scoring dtype; "int8" holds ONLY a per-row-scaled int8 slab on
#: device (¼ the f32 HBM) and rescores the top candidates exactly in f32
#: from the host factor arena before the final top-k.
_DEVICE_DTYPES = ("auto", "float32", "bfloat16", "int8")


def _topn_cost_key(batch_size: int, excl: bool, quant: bool = False) -> str:
    """Cost-accounting program signature for one batched top-N variant.
    Keyed by (batch size, exclusion-carrying, quantized) — the axes the
    coalescer's pow2 padding and the warm ladder actually produce; top-k
    width drift (unusual howMany) folds into the same key, a documented
    approximation (docs/observability.md "Device performance attribution").
    Quantized programs get their OWN keys: their per-call cost (int8 reads,
    rescale multiply) differs from the f32/bf16 scan's."""
    return (f"als.top_n_batch/b{batch_size}"
            + ("+excl" if excl else "") + ("+int8" if quant else ""))


def _id_lists(ids, vals: np.ndarray, idx: np.ndarray, how_many: int) -> list:
    """(B, >= how_many) scores and row indices, best first -> per query its
    ``(id, score)`` list; masked candidates (-inf from the scan) left out."""
    vb, ib = vals[:, :how_many], idx[:, :how_many]
    return [
        [(ids[int(i)], float(v)) for v, i in zip(vb[b], ib[b])
         if np.isfinite(v)]
        for b in range(len(vb))
    ]


def _score(qs, mat):
    """(B, n) scores with f32 accumulation. ``mat`` may be bfloat16 (the MXU's
    native input dtype — half the HBM traffic of f32); accumulation stays f32
    via preferred_element_type, the standard TPU matmul recipe."""
    return jnp.matmul(
        qs.astype(mat.dtype), mat.T, preferred_element_type=jnp.float32
    )


def _mask_excluded(scores, excl):
    """Per-query exclusion scatter: ``excl`` is (B, E) row indices, -1-padded.
    Out-of-range entries are remapped to n (a drop index): negative scatter
    indices would WRAP from the end, so they must be clamped explicitly."""
    n = scores.shape[1]
    excl = jnp.where((excl >= 0) & (excl < n), excl, n)
    return jax.vmap(lambda row, ix: row.at[ix].set(-jnp.inf, mode="drop"))(
        scores, excl
    )


@functools.partial(jax.jit, static_argnames=("k",))
def _top_k_dot_batch(mat, qs, valid, excl, k: int):
    """One MXU matmul for the whole query batch + approx top-k (the masking
    logic lives once in ``_masked_scores``). ``valid`` / ``excl`` are None on
    the unfiltered hot path so it stays exactly matmul + top_k (None is a
    static pytree — XLA never sees a dummy mask; the r1→r2 CPU regression was
    unconditional masking here).

    approx_max_k is the TPU-native top-k (recall ≥ 0.99 beats LSH 0.3's own
    approximation); exact on backends without the TPU op."""
    return _top_k_of_scores(_masked_scores(mat, qs, valid, excl), k)


@jax.jit
def _masked_scores(mat, qs, valid, excl):
    """Masked score matrix only — lets the widening retry in ``top_n`` reuse
    one matmul's scores across successively larger top-k calls instead of
    re-scanning Y each widening."""
    scores = _score(qs, mat)
    if valid is not None:
        scores = jnp.where(valid[None, :], scores, -jnp.inf)
    if excl is not None:
        scores = _mask_excluded(scores, excl)
    return scores


@functools.partial(jax.jit, static_argnames=("k",))
def _top_k_of_scores(scores, k: int):
    return jax.lax.approx_max_k(scores, k, recall_target=0.99)


@functools.partial(jax.jit, static_argnames=("k",))
def _top_k_dot_batch_masked(mat, qs, lut, buckets, excl, k: int):
    scores = _score(qs, mat)  # (B, n)
    valid = jnp.take_along_axis(lut, buckets[None, :], axis=1)  # (B, n)
    scores = jnp.where(valid, scores, -jnp.inf)
    if excl is not None:
        scores = _mask_excluded(scores, excl)
    return jax.lax.approx_max_k(scores, k, recall_target=0.99)


@functools.lru_cache(maxsize=64)
def _sharded_top_k_fn(mesh, axis: str, k: int, k_final: int, n_real: int,
                      use_lut: bool, use_excl: bool = True):
    """Cross-shard top-N: Y's rows shard over ``axis``; each device runs the
    ONE-CHIP scan over its own block — the same ``_score`` matmul and
    ``_top_k_of_scores`` (approximate top-k at the same recall target, fused
    behind the matmul: no ``(B, n_local)`` score matrix is left in HBM) —
    with pad rows, the per-query LSH lut and per-query excluded items masked
    as on one chip; the ``ndev`` candidate lists of ``(B, k)`` are gathered
    across the mesh and merged with one more top-k. This is the multi-chip
    scan of SURVEY §2.14 ("device-resident Y shards; top-N via sharded
    matmul + lax.top_k + cross-shard merge") — the framework's
    intra-request parallelism.

    Exclusion (known-item filtering, Recommend.java:84-106) is a device-side
    scatter: ``excl`` is (B, E) GLOBAL row indices, -1-padded; each shard
    rebases to local coordinates and drops out-of-range entries, so the mask
    costs O(E) scatter per shard instead of a host round-trip.

    Operands of the returned jitted program: ``(mat, qs[, excl][, lut,
    buckets])`` — only what the flags say is used. Its name is stable
    (``jit__sharded_top_k_dot_batch``): the device trace finds it by that,
    and finds the merge's gather as the program's all-gather op."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def local(mat_blk, qs_blk, *rest):
        rest = list(rest)
        n_local = mat_blk.shape[0]
        offset = jax.lax.axis_index(axis) * n_local
        scores = _score(qs_blk, mat_blk)  # (B, n_local), fused into the top-k
        if n_real < n_local * mesh.shape[axis]:
            # zero rows past the last id (row count padded to the shards)
            col_ids = offset + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 1)
            scores = jnp.where(col_ids < n_real, scores, -jnp.inf)
        if use_excl:
            # per-query exclusions: global→local rebase; -1 pads and rows
            # owned by other shards fall out of range and are remapped to
            # the drop index (negative scatter indices would wrap, so
            # _mask_excluded clamps explicitly)
            scores = _mask_excluded(scores, rest.pop(0) - offset)
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
    # (queries/exclusions/lut: B·k, B·E, B·buckets) — a deliberate small
    # broadcast, which the replicated-collective checker keeps quiet on
    # because none of them is data-gathered like a factor table; Y (the
    # model-scaled operand) is the sharded one
    in_specs = (P(axis, None), P(None, None))
    if use_excl:
        in_specs += (P(None, None),)
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


@jax.jit
def _quant_masked_scores(qmat, qscale, qs, valid, excl):
    """(B, n) approximate scores off the int8 slab: the convert rides the
    matmul operand (XLA fuses it — HBM traffic stays int8), accumulation is
    f32, and the per-row scale lands as one broadcast multiply."""
    scores = jnp.matmul(
        qs, qmat.T.astype(qs.dtype), preferred_element_type=jnp.float32
    ) * qscale[None, :]
    if valid is not None:
        scores = jnp.where(valid[None, :], scores, -jnp.inf)
    if excl is not None:
        scores = _mask_excluded(scores, excl)
    return scores


@functools.partial(jax.jit, static_argnames=("k",))
def _quant_candidates(qmat, qscale, qs, valid, excl, k: int):
    """Top-k CANDIDATES (approximate scores) for the exact f32 rescore."""
    return _top_k_of_scores(_quant_masked_scores(qmat, qscale, qs, valid, excl), k)


@functools.partial(jax.jit, static_argnames=("k",))
def _quant_candidates_masked(qmat, qscale, qs, lut, buckets, excl, k: int):
    """Per-query-LUT (LSH) variant of the quantized candidate scan."""
    scores = jnp.matmul(
        qs, qmat.T.astype(qs.dtype), preferred_element_type=jnp.float32
    ) * qscale[None, :]
    valid = jnp.take_along_axis(lut, buckets[None, :], axis=1)
    scores = jnp.where(valid, scores, -jnp.inf)
    if excl is not None:
        scores = _mask_excluded(scores, excl)
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


_Y_SHARD_BYTES = metrics_mod.default_registry().gauge(
    "oryx_serving_y_shard_bytes",
    "Bytes of the newest Y snapshot resident on each device (factors, "
    "scoring copy, norms, buckets)",
    ("device",),
)


class _YSnapshot:
    """Immutable device view of Y: ids, matrix, norms, LSH buckets.

    With a mesh EVERY per-row array (the float32 factors ``mat``, the scoring
    copy ``score_mat``, ``norms``, ``buckets``) is split by rows over
    ``shard_axis`` as the store materialized it: each device holds its own
    block of ``n_rows / shards`` rows and derives its norms and scoring rows
    locally, so Y may exceed a single device's memory — no array with all of
    Y's rows is ever placed on one device. ``n_rows`` is then ``n`` padded to
    the shard count; rows past ``n`` are zero and masked in every scan.

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
        mesh=None,
        shard_axis: str = "model",
        prev: "_YSnapshot | None" = None,
        delta: "tuple[np.ndarray, int] | None" = None,
        device_dtype: str = "auto",
    ):
        self.ids = ids
        self.device_dtype = device_dtype
        self.mat = mat  # jax (n_rows, k) or None, float32
        # lazy cost-registration marks (see _top_n_batch): per GENERATION so
        # a model swap re-registers against the new shapes, but carried
        # across same-shape incremental snapshots (point-update microbatches
        # whose dispatch signatures — and therefore per-call costs — are
        # unchanged). Marked even when registration fails, so a backend
        # without usable cost_analysis never re-pays lower+compile per call.
        if (prev is not None
                and getattr(prev.mat, "shape", None)
                == getattr(mat, "shape", None)):
            self.cost_keys_attempted = prev.cost_keys_attempted
        else:
            self.cost_keys_attempted: set = set()
        if prev is not None and delta is not None:
            # id→idx is append-only across incremental generations; sharing
            # the dict avoids an O(n) rebuild per microbatch (extra entries
            # in the older snapshot only affect exclusion masks, which drop
            # out-of-range rows on device)
            self.id_to_idx = prev.id_to_idx
            for i in range(len(prev.ids), len(ids)):
                self.id_to_idx[ids[i]] = i
        else:
            self.id_to_idx = {s: i for i, s in enumerate(ids)}
        self.mesh = mesh if mat is not None else None
        self.shard_axis = shard_axis
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
        if mesh is None:
            self.norms = jnp.linalg.norm(mat, axis=1)
            self.score_mat = mat.astype(jnp.bfloat16) if bf16 else mat
        else:
            self.norms, score = _derive_sharded(
                row_sharding(mesh, shard_axis), mat.sharding,
                jnp.bfloat16 if bf16 else None,
            )(mat)
            self.score_mat = mat if score is None else score
        self.buckets = self._assign_buckets(lsh, prev, delta)
        if mesh is not None:
            self._publish_shard_bytes()

    def _assign_buckets(self, lsh, prev, delta):
        """(n_rows,) LSH bucket of every row, or None without LSH; under a
        mesh split like the rows (padding rows in bucket 0, masked anyway)."""
        if not (lsh and lsh.num_hashes):
            return None
        mat, n = self.mat, self.n
        if prev is None or delta is None or prev.buckets is None:
            host = lsh.assign_buckets(np.asarray(mat)[:n])
            return self._place_buckets(host)
        # rehash only the delta: pull changed/new rows (not the whole
        # matrix) to host for bucket assignment
        ch, n_new = delta
        if self.mesh is not None:
            # the bucket vector (4 B a row) makes the round trip; Y does not
            host = np.zeros(n, dtype=np.int32)
            host[: prev.n] = np.asarray(prev.buckets)[: prev.n]
            if len(ch):
                host[ch] = lsh.assign_buckets(np.asarray(mat[jnp.asarray(ch)]))
            if n_new:
                host[prev.n:] = lsh.assign_buckets(np.asarray(mat[prev.n:n]))
            return self._place_buckets(host)
        buckets = prev.buckets
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
        if self.mesh is None:
            return jnp.asarray(host)
        return put_row_sharded(
            np.asarray(host, dtype=np.int32), self.mesh, self.shard_axis)

    def device_arrays(self) -> list:
        """The distinct arrays this snapshot holds on device (the scoring
        copy only where it is not the float32 matrix itself)."""
        arrays = [self.mat, self.norms, self.buckets]
        if self.score_mat is not self.mat:
            arrays.append(self.score_mat)
        return [a for a in arrays if a is not None]

    def _publish_shard_bytes(self) -> None:
        per_device: collections.Counter = collections.Counter()
        for arr in self.device_arrays():
            for sh in arr.addressable_shards:
                per_device[sh.device.id] += int(sh.data.nbytes)
        for dev, nbytes in per_device.items():
            _Y_SHARD_BYTES.labels(str(dev)).set(nbytes)

    @property
    def n(self) -> int:
        return len(self.ids)

    @property
    def n_rows(self) -> int:
        """Rows of the device arrays: ``n``, padded to the shard count."""
        return self.n if self.mat is None else int(self.mat.shape[0])


#: Host-side quantization chunk: bounds the transient f32 gather while
#: building a full quantized snapshot (2^16 rows × 50f ≈ 13 MB per chunk
#: instead of one n×k f32 copy next to the arena slab).
_QUANT_CHUNK = 1 << 16


class _QuantSnapshot:
    """Immutable int8 device view of Y (``oryx.serving.device-dtype = int8``):
    per-row-scaled int8 factors + exact f32 norms + optional LSH buckets.
    No f32 (or bf16) copy of Y ever lands in HBM — the whole point of the
    mode is fitting a 21M × 50f item side per chip with headroom.

    Built from the factor arena's HOST snapshot (``host_matrix``) and kept
    current with composed host deltas (``delta_info``): a speed microbatch
    of point updates requantizes only the changed/appended rows and lands
    them as row-index scatters, mirroring the f32 path's incremental
    device maintenance. ``version`` anchors the next delta."""

    def __init__(self, ids, version: int, qmat, qscale, norms, buckets,
                 prev: "_QuantSnapshot | None" = None,
                 appended: "list[str] | None" = None,
                 slab=None, slab_rows=None):
        self.ids = ids
        self.version = version
        self.qmat = qmat        # (n, k) int8 device
        self.qscale = qscale    # (n,) f32 device
        self.norms = norms      # (n,) f32 device, exact
        self.buckets = buckets  # (n,) int32 device or None
        # pinned exact-rescore view: THIS snapshot's slab object + its row
        # indices, captured by the store in the same order epoch as `ids`.
        # Structural store changes (GC, compaction) replace the live
        # slab/rowmap and never disturb this pair, so a rescore can never
        # crash on, or misalign against, a concurrently mutated store. A
        # point update rewriting a captured row in place is visible here —
        # the rescore ranks with fresher factors than the scan, benign.
        self.slab = slab
        self.slab_rows = slab_rows  # (n,) slab row per snapshot position
        self.mat = None         # no f32 device matrix in this mode
        self.score_mat = None
        self.mesh = None
        if prev is not None and appended is not None:
            # id→idx append-only sharing, exactly like _YSnapshot
            self.id_to_idx = prev.id_to_idx
            for i in range(len(prev.ids), len(ids)):
                self.id_to_idx[ids[i]] = i
        else:
            self.id_to_idx = {s: i for i, s in enumerate(ids)}
        # lazy cost-registration marks: per generation, carried across
        # same-shape incremental snapshots (see _YSnapshot)
        if (prev is not None
                and getattr(prev.qmat, "shape", None)
                == getattr(qmat, "shape", None)):
            self.cost_keys_attempted = prev.cost_keys_attempted
        else:
            self.cost_keys_attempted: set = set()
        profiling.register_quantized(self)

    @property
    def n(self) -> int:
        return len(self.ids)

    def quantized_nbytes(self) -> int:
        """Device bytes held by the quantized factors (the
        oryx_device_quantized_factor_bytes gauge)."""
        total = 0
        for arr in (self.qmat, self.qscale):
            total += int(getattr(arr, "nbytes", 0) or 0)
        return total

    def gather_rows(self, positions: np.ndarray) -> np.ndarray:
        """Exact f32 factor rows for snapshot ``positions``, gathered from
        the PINNED slab view (see __init__) — one fancy index."""
        pos = np.clip(np.asarray(positions, dtype=np.int64), 0, self.n - 1)
        return self.slab[self.slab_rows[pos]]

    @classmethod
    def build(cls, ids, host: np.ndarray, version: int,
              lsh: "LocalitySensitiveHash | None",
              row_view: tuple,
              prev: "_QuantSnapshot | None" = None):
        """Full quantized build from one host matrix, chunked so the
        transient stays bounded at reference scale."""
        n = len(ids)
        slab, slab_rows = row_view
        if n == 0 or host.size == 0:
            return cls(list(ids), version, None, None, None, None)
        k = host.shape[1]
        q = np.empty((n, k), dtype=np.int8)
        scale = np.empty(n, dtype=np.float32)
        norms = np.empty(n, dtype=np.float32)
        for a in range(0, n, _QUANT_CHUNK):
            b = min(n, a + _QUANT_CHUNK)
            q[a:b], scale[a:b] = _quantize_rows(host[a:b])
            norms[a:b] = np.linalg.norm(host[a:b], axis=1)
        buckets = None
        if lsh and lsh.num_hashes:
            buckets = jnp.asarray(lsh.assign_buckets(host))
        return cls(list(ids), version, jnp.asarray(q), jnp.asarray(scale),
                   jnp.asarray(norms), buckets, prev=prev,
                   slab=slab, slab_rows=slab_rows)

    @classmethod
    def from_delta(cls, prev: "_QuantSnapshot", delta,
                   lsh: "LocalitySensitiveHash | None"):
        """Incremental step: requantize only the changed/appended rows and
        land them as device row scatters / one append."""
        qmat, qscale, norms, buckets = (
            prev.qmat, prev.qscale, prev.norms, prev.buckets
        )
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
        ids = prev.ids + delta.appended_ids
        # extend the pinned rescore view: delta.slab is the CURRENT slab
        # (a non-structural grow copies rows in place, so prev's indices
        # stay valid in it) and the appended ids bring their own rows
        slab_rows = (
            np.concatenate([prev.slab_rows,
                            np.asarray(delta.appended_rows, dtype=np.int64)])
            if len(delta.appended_ids) else prev.slab_rows
        )
        return cls(ids, delta.version, qmat, qscale, norms, buckets,
                   prev=prev, appended=delta.appended_ids,
                   slab=delta.slab, slab_rows=slab_rows)


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
            # sharding is a later round — degrade loudly, never silently
            log.warning(
                "device-dtype=int8 is not supported with sharded serving; "
                "using bfloat16 for the sharded scoring copy"
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
        self.known_items: dict[str, set[str]] = {}
        self._known_lock = threading.Lock()
        self.expected_user_ids: set[str] = set()
        self.expected_item_ids: set[str] = set()
        self.yty_cache = SolverCache(self.y.get_vtv)
        self._snapshot: _YSnapshot | None = None
        self._snapshot_src = None
        self._snap_lock = threading.Lock()

    # -- vector + known-item bookkeeping ------------------------------------
    def set_user_vector(self, user: str, vec) -> None:
        self.x.set_vector(user, vec)
        self.expected_user_ids.discard(user)

    def set_item_vector(self, item: str, vec) -> None:
        self.y.set_vector(item, vec)
        self.expected_item_ids.discard(item)
        self.yty_cache.set_dirty()

    def bulk_load_users(self, ids, matrix) -> None:
        """Whole-matrix X handoff keeping model bookkeeping consistent."""
        self.x.bulk_load(ids, matrix)
        self.expected_user_ids.difference_update(ids)

    def bulk_load_items(self, ids, matrix) -> None:
        """Whole-matrix Y handoff keeping model bookkeeping consistent."""
        self.y.bulk_load(ids, matrix)
        self.expected_item_ids.difference_update(ids)
        self.yty_cache.set_dirty()

    def get_user_vector(self, user: str):
        return self.x.get_vector(user)

    def get_item_vector(self, item: str):
        return self.y.get_vector(item)

    def add_known_items(self, user: str, items: Sequence[str]) -> None:
        with self._known_lock:
            self.known_items.setdefault(user, set()).update(items)

    def get_known_items(self, user: str) -> set[str]:
        with self._known_lock:
            return set(self.known_items.get(user, ()))

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
        counts: dict[str, int] = {}
        with self._known_lock:
            for items in self.known_items.values():
                for i in items:
                    counts[i] = counts.get(i, 0) + 1
        return counts

    def user_counts(self) -> dict[str, int]:
        """Known-item count per user (MostActiveUsers source)."""
        with self._known_lock:
            return {u: len(items) for u, items in self.known_items.items()}

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
        keep = set(users)
        with self._known_lock:
            for u in list(self.known_items):
                if u not in keep:
                    del self.known_items[u]

    def get_fraction_loaded(self) -> float:  # ALSServingModel.java:396
        total = len(self.expected_user_ids) + len(self.expected_item_ids)
        total += self.x.size() + self.y.size()
        if total == 0:
            return 1.0
        return (self.x.size() + self.y.size()) / total

    # -- device snapshot ----------------------------------------------------
    def y_snapshot(self):
        if self.device_dtype == "int8":
            if self.index_enabled:
                return self._ivf_snapshot()
            return self._quant_snapshot()
        ids, mat = self.y.materialize()
        with self._snap_lock:
            if self._snapshot is None or self._snapshot_src is not mat:
                prev, delta = None, None
                if self._snapshot is not None and self._snapshot.mat is not None \
                        and mat is not None:
                    # catch up across any number of incremental generations
                    # (e.g. get_vtv consumed pending batches in between)
                    delta = self.y.delta_since(self._snapshot.mat, mat)
                    if delta is not None:
                        prev = self._snapshot
                self._snapshot = _YSnapshot(
                    ids, mat, self.lsh, self.mesh, self.shard_axis,
                    prev=prev, delta=delta, device_dtype=self.device_dtype,
                )
                self._snapshot_src = mat
            return self._snapshot

    def _quant_snapshot(self) -> _QuantSnapshot:
        """Current int8 device view: incremental (requantize + scatter only
        the rows a speed microbatch touched) when the arena's write log
        covers the gap, full chunked rebuild otherwise. The store's f32
        device-materialization cache is never engaged in this mode — the
        arena slab itself is the exact-f32 source of truth (the rescore
        gathers straight from it)."""
        with self._snap_lock:
            prev = self._snapshot if isinstance(self._snapshot, _QuantSnapshot) else None
            if prev is not None and prev.qmat is not None:
                delta = self.y.delta_info(prev.version, len(prev.ids))
                if delta is not None:
                    if not delta.changed_ids and not delta.appended_ids:
                        return prev
                    self._snapshot = _QuantSnapshot.from_delta(
                        prev, delta, self.lsh
                    )
                    return self._snapshot
            ids, host, version, row_view = self.y.host_matrix()
            self._snapshot = _QuantSnapshot.build(
                ids, host, version, self.lsh, row_view, prev=prev
            )
            return self._snapshot

    def _ivf_snapshot(self) -> "ivf_mod.IVFSnapshot":
        """Current IVF device view: incremental (requantize + reassign only
        the rows a speed microbatch touched, rewrite only the affected
        cells) when the arena's write log covers the gap AND the update
        neither overflows a cell nor drifts the balance past the skew
        bound; full re-cluster rebuild otherwise."""
        with self._snap_lock:
            prev = (self._snapshot
                    if isinstance(self._snapshot, ivf_mod.IVFSnapshot)
                    else None)
            if prev is not None and prev.cell_q is not None:
                delta = self.y.delta_info(prev.version, len(prev.ids))
                if delta is not None:
                    if not delta.changed_ids and not delta.appended_ids:
                        return prev
                    nxt = ivf_mod.IVFSnapshot.from_delta(
                        prev, delta, self.lsh
                    )
                    if nxt is not None:
                        self._snapshot = nxt
                        return nxt
            ids, host, version, row_view = self.y.host_matrix()
            self._snapshot = ivf_mod.IVFSnapshot.build(
                ids, host, version, self.lsh, row_view, prev=prev,
                cells=self.index_cells, probes=self.index_probes,
                skew_bound=self.index_skew,
            )
            return self._snapshot

    def _rescore_exact(self, snap: _QuantSnapshot, qs_host: np.ndarray,
                       vals: np.ndarray, idx: np.ndarray,
                       cosine: bool = False) -> "tuple[np.ndarray, np.ndarray]":
        """Exact f32 rescore of the quantized scan's candidates: gather the
        candidate rows from the snapshot's PINNED arena-slab view (one
        fancy index — the slab is what makes this cheap), recompute exact
        scores, and return the candidates re-ranked by exact score. Masked
        candidates (-inf from the scan) stay -inf. For ``cosine`` the batch
        dimension is the query-vector set of ONE request (mean cosine)."""
        B, R = idx.shape
        rows = snap.gather_rows(idx.reshape(-1)).reshape(B, R, -1)
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

    def _quant_scan(self, snap: _QuantSnapshot, qs_host: np.ndarray,
                    r: int, excl, valid=None, lut=None,
                    register_cost: "str | None" = None):
        """One quantized candidate scan + exact rescore: (vals, idx) of
        width ``r``, exact-f32-ranked. ``excl`` is the padded (B, E) index
        array or None; ``valid`` an optional (n,) candidate mask; ``lut``
        a per-query (B, num_buckets) LSH lookup table (selects the masked
        program). One registration/record/rescore sequence serves every
        variant."""
        with spans.stage("topn.upload"):
            qs = jnp.asarray(qs_host)
        with spans.stage("topn.dispatch"):
            if lut is not None:
                fn = _quant_candidates_masked
                args = (snap.qmat, snap.qscale, qs, lut, snap.buckets, excl, r)
            else:
                fn = _quant_candidates
                args = (snap.qmat, snap.qscale, qs, valid, excl, r)
            if register_cost is not None and (
                    register_cost not in snap.cost_keys_attempted
                    and metrics_mod.default_registry().enabled):
                snap.cost_keys_attempted.add(register_cost)
                compilecache.aot_compile(fn, *args, cost_key=register_cost)
            vals, idx = fn(*args)
            if register_cost is not None:
                profiling.costs().record(register_cost)
        with spans.stage("topn.wait_download"):
            vals, idx = np.asarray(vals), np.asarray(idx)
        with spans.stage("topn.rescore"):
            return self._rescore_exact(snap, qs_host, vals, idx)

    # -- query primitives ----------------------------------------------------
    @staticmethod
    def _excluded_indices(snap: _YSnapshot, excluded, batch: int) -> np.ndarray:
        """(B, E) int32 of global Y rows to mask out, -1-padded, E a pow2
        FLOORED at ``_EXCL_PAD_MIN`` so the common exclusion widths all
        share one jit signature — the one the batch warmer precompiles."""
        idx_lists: list[list[int]] = []
        max_e = 1
        for b in range(batch):
            ids = excluded[b] if excluded is not None else None
            ix = (
                [snap.id_to_idx[i] for i in ids if i in snap.id_to_idx]
                if ids
                else []
            )
            idx_lists.append(ix)
            max_e = max(max_e, len(ix))
        width = max(_EXCL_PAD_MIN, _round_up_pow2(max_e))
        out = np.full((batch, width), -1, dtype=np.int32)
        for b, ix in enumerate(idx_lists):
            out[b, : len(ix)] = ix
        return out

    def _build_lut(self, qs_host: np.ndarray) -> np.ndarray:
        """(B, num_buckets) bool LSH candidate lookup table, one row per
        query — fully vectorized over the batch (lsh.get_candidate_lut)."""
        return self.lsh.get_candidate_lut(qs_host)

    def _sharded_query(self, snap: _YSnapshot, qs_host: np.ndarray, want: int,
                       excluded, cost_key: "str | None" = None):
        """Multi-device scan: the one-chip scan on every shard + cross-shard
        merge, with LSH lut and per-query known-item exclusion applied
        device-side (no host fallback for filtered traffic). Returns
        ``(vals, idx)`` on the host, at least ``min(want, n)`` wide."""
        B = qs_host.shape[0]
        use_lut = self.lsh is not None and snap.buckets is not None
        use_excl = excluded is not None and any(e for e in excluded)
        with spans.stage("topn.upload"):
            # batch-shaped operands go from the host to every device at once
            # (not to one device and on from there inside the dispatch)
            everywhere = replicated_sharding(snap.mesh)
            args = [snap.score_mat, jax.device_put(qs_host, everywhere)]
            if use_excl:
                args.append(jax.device_put(
                    self._excluded_indices(snap, excluded, B), everywhere))
            if use_lut:
                args += [jax.device_put(self._build_lut(qs_host), everywhere),
                         snap.buckets]
        with spans.stage("topn.dispatch"):
            fn = self._sharded_program(snap, want, use_lut, use_excl)
            if (cost_key is not None
                    and cost_key not in snap.cost_keys_attempted
                    and metrics_mod.default_registry().enabled):
                # as on one chip: the first use of a signature shares its
                # compile with the cost registration
                snap.cost_keys_attempted.add(cost_key)
                compilecache.aot_compile(fn, *args, cost_key=cost_key)
            vals, idx = fn(*args)
            if cost_key is not None:
                profiling.costs().record(cost_key)
        with spans.stage("topn.wait_download"):
            return np.asarray(vals), np.asarray(idx)

    def _sharded_program(self, snap: _YSnapshot, want: int, use_lut: bool,
                         use_excl: bool):
        """The jitted mesh scan for ``want`` results a query: each shard
        keeps ``k`` candidates (a pow2 ≥ 16, as the one-chip program's
        width), the merge ``k_final``."""
        ndev = snap.mesh.shape[snap.shard_axis]
        n_local = snap.n_rows // ndev
        width = _round_up_pow2(max(min(want, snap.n), 16))
        k = min(n_local, width)
        return _sharded_top_k_fn(
            snap.mesh, snap.shard_axis, k, min(ndev * k, width), snap.n,
            use_lut, use_excl,
        )

    def top_n(
        self,
        query_vec: np.ndarray,
        how_many: int,
        offset: int = 0,
        allowed: "Callable[[str], bool] | None" = None,
        rescore: "Callable[[str, float], float] | None" = None,
        excluded: "Sequence[str] | None" = None,
    ) -> list[tuple[str, float]]:
        """Dot-product top-N over Y: one matmul + top_k (ALSServingModel.topN
        :261-276, TopNConsumer:56-73). ``excluded`` ids (known-item filtering)
        are masked on device; ``allowed``/``rescore`` host hooks (rescorer SPI)
        filter the candidate stream with widening retry."""
        snap = self.y_snapshot()
        if snap.n == 0 or (snap.mat is None and not isinstance(
                snap, (_QuantSnapshot, ivf_mod.IVFSnapshot))):
            return []
        q_host = np.asarray(query_vec, dtype=np.float32)
        if isinstance(snap, ivf_mod.IVFSnapshot):
            return ivf_mod.top_n(
                self, snap, q_host, how_many, offset, allowed, rescore,
                excluded,
            )
        if isinstance(snap, _QuantSnapshot):
            return self._quant_top_n(
                snap, q_host, how_many, offset, allowed, rescore, excluded
            )
        want = how_many + offset
        if snap.mesh is not None:
            k = want if allowed is None and rescore is None else max(4 * want, 64)
            while True:
                vals, idx = self._sharded_query(
                    snap, q_host[None, :], k, [excluded] if excluded else None
                )
                out = self._collect(snap, vals[0], idx[0], want, allowed, rescore)
                if len(out) >= want or k >= snap.n:
                    return out[offset:offset + how_many]
                k = min(snap.n, k * 2)  # widen: host filter consumed candidates
        q = jnp.asarray(q_host)
        # unfiltered hot path stays exactly matmul + top_k: masks are None
        # (static) unless LSH or exclusions actually apply
        has_lsh = self.lsh is not None and snap.buckets is not None
        valid = self._candidate_mask(snap, q_host) if has_lsh else None
        excl = None
        if excluded:
            # pow2-padded with -1 fill (the batch helper at batch=1) so jit
            # signatures stay stable: every distinct known-item count would
            # otherwise trigger a fresh compile on the serving hot path
            padded = self._excluded_indices(snap, [excluded], 1)
            if (padded >= 0).any():
                excl = jnp.asarray(padded)
        # score once; widenings re-run only the top-k over the cached scores
        scores = _masked_scores(snap.score_mat, q[None, :], valid, excl)
        k = min(snap.n, _round_up_pow2(max(4 * want, 64)))
        while True:
            vals, idx = _top_k_of_scores(scores, k)
            out = self._collect(
                snap, np.asarray(vals)[0], np.asarray(idx)[0], want, allowed, rescore
            )
            if len(out) >= want or k >= snap.n:
                return out[offset:offset + how_many]
            k = min(snap.n, k * 2)  # widen if filtering consumed candidates

    def _quant_top_n(
        self, snap: _QuantSnapshot, q_host: np.ndarray, how_many: int,
        offset: int, allowed, rescore, excluded,
    ) -> list[tuple[str, float]]:
        """Single-query top-N on the int8 path: quantized candidate scan →
        exact f32 rescore from the arena → host filtering. The quantized
        matmul runs ONCE; widenings (``allowed``/``rescore`` hooks consuming
        candidates) re-run only the top-k over the cached score matrix,
        exactly like the f32 path — never another full-bandwidth pass
        over the int8 slab."""
        want = how_many + offset
        excl = None
        if excluded:
            padded = self._excluded_indices(snap, [excluded], 1)
            if (padded >= 0).any():
                excl = jnp.asarray(padded)
        has_lsh = self.lsh is not None and snap.buckets is not None
        valid = self._candidate_mask(snap, q_host) if has_lsh else None
        scores = _quant_masked_scores(
            snap.qmat, snap.qscale, jnp.asarray(q_host[None, :]), valid, excl
        )
        r = min(snap.n, _round_up_pow2(max(int(self.rescore_factor * want), 16)))
        while True:
            v, i = _top_k_of_scores(scores, r)
            vals, idx = self._rescore_exact(
                snap, q_host[None, :], np.asarray(v), np.asarray(i)
            )
            out = self._collect(snap, vals[0], idx[0], want, allowed, rescore)
            if len(out) >= want or r >= snap.n:
                return out[offset:offset + how_many]
            r = min(snap.n, r * 2)  # widen: host filter consumed candidates

    def top_n_batch(
        self,
        query_vecs: np.ndarray,
        how_many: int,
        alloweds: "Sequence[Callable[[str], bool] | None] | None" = None,
        excluded: "Sequence[Sequence[str] | None] | None" = None,
    ) -> list[list[tuple[str, float]]]:
        """Micro-batched top-N: many queries in ONE matmul+top_k device call —
        the TPU-idiomatic serving pattern (amortizes per-call overhead that the
        reference spends thread-fanning partition scans). ``excluded[b]`` ids
        are masked device-side; ``alloweds`` host callables (rescorer SPI)
        filter after the scan. One histogram observe + one counter add per
        CALL (not per query) keeps the hot path inside the metrics budget."""
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

    def _top_n_batch(
        self,
        query_vecs: np.ndarray,
        how_many: int,
        alloweds: "Sequence[Callable[[str], bool] | None] | None" = None,
        excluded: "Sequence[Sequence[str] | None] | None" = None,
    ) -> list[list[tuple[str, float]]]:
        snap = self.y_snapshot()
        if snap.n == 0 or (snap.mat is None and not isinstance(
                snap, (_QuantSnapshot, ivf_mod.IVFSnapshot))):
            return [[] for _ in range(len(query_vecs))]
        qs_host = np.asarray(query_vecs, dtype=np.float32)
        filtering = alloweds is not None and any(a is not None for a in alloweds)
        if isinstance(snap, ivf_mod.IVFSnapshot):
            return ivf_mod.top_n_batch(
                self, snap, qs_host, how_many, alloweds, excluded, filtering
            )
        if isinstance(snap, _QuantSnapshot):
            return self._quant_top_n_batch(
                snap, qs_host, how_many, alloweds, excluded, filtering
            )
        use_excl = excluded is not None and any(e for e in excluded)
        masked = self.lsh is not None and snap.buckets is not None
        if snap.mesh is not None:
            # the mesh scan has the stages of the one-chip call and a cost
            # key of its own (its per-call cost is a shard's, plus the merge)
            k = min(snap.n, _round_up_pow2(
                max(2 * how_many, 64) if filtering else max(how_many, 16)))
            vals, idx = self._sharded_query(
                snap, qs_host, k, excluded,
                cost_key=_topn_cost_key(len(qs_host), use_excl) + "+sharded",
            )
        else:
            vals, idx, k = self._one_device_scan(
                snap, qs_host, how_many, excluded, use_excl, masked,
                filtering)
        with spans.stage("topn.ids"):
            if not filtering:
                return _id_lists(snap.ids, vals, idx, how_many)
            out = []
            for b in range(len(query_vecs)):
                allowed = alloweds[b] if alloweds else None
                got = self._collect(
                    snap, vals[b], idx[b], how_many, allowed, None)[:how_many]
                if len(got) < how_many and k < snap.n:
                    # heavy filtering consumed this query's candidates —
                    # fall back to the widening single-query path
                    got = self.top_n(
                        qs_host[b], how_many, 0, allowed, None,
                        excluded=excluded[b] if excluded else None,
                    )
                out.append(got)
            return out

    def _one_device_scan(self, snap: _YSnapshot, qs_host: np.ndarray,
                         how_many: int, excluded, use_excl: bool,
                         masked: bool, filtering: bool):
        """The stages of one call on one device, end to end
        (docs/observability.md): what the coalescer's device-call span is
        made of on this side. Returns ``(vals, idx, k)`` on the host."""
        with spans.stage("topn.upload"):
            qs = jnp.asarray(qs_host)
            excl = (
                jnp.asarray(
                    self._excluded_indices(snap, excluded, len(qs_host)))
                if use_excl
                else None
            )
            # per-query LSH candidate masks: (B, num_buckets) lookup table
            # indexed by item bucket on device
            lut = jnp.asarray(self._build_lut(qs_host)) if masked else None
        with spans.stage("topn.dispatch"):
            cost_key = _topn_cost_key(len(qs_host), use_excl)
            if masked:
                k = min(snap.n, _round_up_pow2(max(2 * how_many, 64)))
                fn = _top_k_dot_batch_masked
                args = (snap.score_mat, qs, lut, snap.buckets, excl, k)
            else:
                k = min(
                    snap.n,
                    _round_up_pow2(max(2 * how_many, 64) if filtering
                                   else max(how_many, 16)),
                )
                fn = _top_k_dot_batch
                args = (snap.score_mat, qs, None, excl, k)
            if (cost_key not in snap.cost_keys_attempted
                    and metrics_mod.default_registry().enabled):
                # first use of this signature this generation: the dispatch
                # below pays the XLA compile anyway — the sanctioned AOT
                # route shares that compile AND yields the executable's
                # cost_analysis, so unwarmed signatures (odd batch sizes,
                # direct callers) still attribute FLOPs instead of reading
                # zero forever
                snap.cost_keys_attempted.add(cost_key)
                compilecache.aot_compile(fn, *args, cost_key=cost_key)
            vals, idx = fn(*args)
            profiling.costs().record(cost_key)
        with spans.stage("topn.wait_download"):
            # the program's run and the copy back: the first conversion
            # blocks until the device is done
            return np.asarray(vals), np.asarray(idx), k

    def _quant_top_n_batch(
        self, snap: _QuantSnapshot, qs_host: np.ndarray, how_many: int,
        alloweds, excluded, filtering: bool,
    ) -> list[list[tuple[str, float]]]:
        """Batched top-N on the int8 path: ONE quantized device scan over
        the whole query batch (¼ the f32 HBM per pass) returning
        ``rescore-factor × how_many`` candidates each, exact-f32-rescored
        from the arena slab before the final cut. Cost keys carry ``+int8``
        so the attribution (and the warm ladder) see the quantized programs
        as their own signatures."""
        use_excl = excluded is not None and any(e for e in excluded)
        excl = (
            jnp.asarray(self._excluded_indices(snap, excluded, len(qs_host)))
            if use_excl
            else None
        )
        cost_key = _topn_cost_key(len(qs_host), use_excl, quant=True)
        r = min(snap.n,
                _round_up_pow2(max(int(self.rescore_factor * how_many), 16)))
        lut = (
            jnp.asarray(self._build_lut(qs_host))
            if self.lsh is not None and snap.buckets is not None
            else None
        )
        vals, idx = self._quant_scan(
            snap, qs_host, r, excl, lut=lut, register_cost=cost_key
        )
        with spans.stage("topn.ids"):
            if not filtering:
                return _id_lists(snap.ids, vals, idx, how_many)
            out = []
            for b in range(len(qs_host)):
                allowed = alloweds[b] if alloweds else None
                got = self._collect(
                    snap, vals[b], idx[b], how_many, allowed, None)[:how_many]
                if len(got) < how_many and r < snap.n:
                    # heavy filtering consumed this query's candidates —
                    # fall back to the widening single-query quant path
                    got = self._quant_top_n(
                        snap, qs_host[b], how_many, 0, allowed, None,
                        excluded[b] if excluded else None,
                    )
                out.append(got)
            return out

    def warm_bucket(self, batch_size: int, how_many: int = 10) -> None:
        """Pre-compile the batched top-N program for ONE pow2 batch size
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

        BOTH signature families warm: exclusion-free AND exclusion-carrying
        — the default ``/recommend`` path (considerKnownItems=false) always
        sends known-item exclusions, and ``_excluded_indices`` pads them to
        the shape-stable ``_EXCL_PAD_MIN`` width this warms, so the first
        client burst after a MODEL handoff pays no compile on the endpoint
        it actually calls."""
        import jax

        snap = self.y_snapshot()
        if snap.n == 0 or (snap.mat is None and not isinstance(
                snap, (_QuantSnapshot, ivf_mod.IVFSnapshot))):
            raise ValueError("no item factors to warm against yet")
        qs_struct = jax.ShapeDtypeStruct(
            (batch_size, self.features), jnp.float32
        )
        excl_struct = jax.ShapeDtypeStruct(
            (batch_size, _EXCL_PAD_MIN), jnp.int32
        )
        if isinstance(snap, ivf_mod.IVFSnapshot):
            # the IVF ladder: pow2 (batch, probes) probe + scan signatures
            # under their own cost keys; the shared zero-batch executions
            # below then populate the exact dispatch caches requests hit
            ivf_mod.warm_bucket(self, snap, batch_size, how_many)
        elif isinstance(snap, _QuantSnapshot):
            # the quantized ladder: its programs (and so its AOT cost keys)
            # are distinct from the f32/bf16 scan's — a quantized-model
            # handoff warms exactly the signatures its traffic dispatches
            r = min(snap.n,
                    _round_up_pow2(max(int(self.rescore_factor * how_many), 16)))
            keys = (_topn_cost_key(batch_size, False, quant=True),
                    _topn_cost_key(batch_size, True, quant=True))
            if self.lsh is None or snap.buckets is None:
                compilecache.aot_compile(
                    _quant_candidates, snap.qmat, snap.qscale, qs_struct,
                    None, None, r, cost_key=keys[0],
                )
                compilecache.aot_compile(
                    _quant_candidates, snap.qmat, snap.qscale, qs_struct,
                    None, excl_struct, r, cost_key=keys[1],
                )
            else:
                lut_struct = jax.ShapeDtypeStruct(
                    (batch_size, self.lsh.num_buckets), jnp.bool_
                )
                compilecache.aot_compile(
                    _quant_candidates_masked, snap.qmat, snap.qscale,
                    qs_struct, lut_struct, snap.buckets, None, r,
                    cost_key=keys[0],
                )
                compilecache.aot_compile(
                    _quant_candidates_masked, snap.qmat, snap.qscale,
                    qs_struct, lut_struct, snap.buckets, excl_struct, r,
                    cost_key=keys[1],
                )
            snap.cost_keys_attempted.update(keys)
        elif snap.mesh is not None:
            # the mesh scan's ladder: the same two families under the
            # sharded cost keys, compiled against Y's shards as they lie
            use_lut = self.lsh is not None and snap.buckets is not None
            everywhere = replicated_sharding(snap.mesh)

            def struct(like):
                return jax.ShapeDtypeStruct(
                    like.shape, like.dtype, sharding=everywhere)

            lut = ((jax.ShapeDtypeStruct(
                (batch_size, self.lsh.num_buckets), jnp.bool_,
                sharding=everywhere), snap.buckets) if use_lut else ())
            for use_excl in (False, True):
                key = _topn_cost_key(batch_size, use_excl) + "+sharded"
                compilecache.aot_compile(
                    self._sharded_program(snap, how_many, use_lut, use_excl),
                    snap.score_mat, struct(qs_struct),
                    *((struct(excl_struct),) if use_excl else ()), *lut,
                    cost_key=key,
                )
                snap.cost_keys_attempted.add(key)
        elif self.lsh is None or snap.buckets is None:
            k = min(snap.n, _round_up_pow2(max(how_many, 16)))
            compilecache.aot_compile(
                _top_k_dot_batch, snap.score_mat, qs_struct, None, None, k,
                cost_key=_topn_cost_key(batch_size, False),
            )
            compilecache.aot_compile(
                _top_k_dot_batch, snap.score_mat, qs_struct, None,
                excl_struct, k,
                cost_key=_topn_cost_key(batch_size, True),
            )
        else:
            k = min(snap.n, _round_up_pow2(max(2 * how_many, 64)))
            lut_struct = jax.ShapeDtypeStruct(
                (batch_size, self.lsh.num_buckets), jnp.bool_
            )
            compilecache.aot_compile(
                _top_k_dot_batch_masked, snap.score_mat, qs_struct,
                lut_struct, snap.buckets, None, k,
                cost_key=_topn_cost_key(batch_size, False),
            )
            compilecache.aot_compile(
                _top_k_dot_batch_masked, snap.score_mat, qs_struct,
                lut_struct, snap.buckets, excl_struct, k,
                cost_key=_topn_cost_key(batch_size, True),
            )
        if snap.mesh is None and not isinstance(
                snap, (_QuantSnapshot, ivf_mod.IVFSnapshot)):
            # mark both signatures attempted: the lazy first-use
            # registration in _top_n_batch would otherwise re-lower and
            # re-compile each one the ladder just registered — once per
            # signature per generation, during the handoff warm window
            snap.cost_keys_attempted.update({
                _topn_cost_key(batch_size, False),
                _topn_cost_key(batch_size, True),
            })
        zeros = np.zeros((batch_size, self.features), dtype=np.float32)
        self.top_n_batch(zeros, how_many)
        # one real exclusion-carrying execution: an id no snapshot contains
        # maps to an all(-1) mask of the floored width — the exact program
        # the default endpoint's known-item exclusions dispatch to
        self.top_n_batch(
            zeros, how_many,
            excluded=[("__warm__",)] + [None] * (batch_size - 1),
        )

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
        if snap.n == 0 or (snap.mat is None and not isinstance(
                snap, (_QuantSnapshot, ivf_mod.IVFSnapshot))):
            return []
        qs_host = np.atleast_2d(np.asarray(query_vecs, dtype=np.float32))
        if isinstance(snap, ivf_mod.IVFSnapshot):
            return ivf_mod.top_n_cosine(
                self, snap, qs_host,
                np.linalg.norm(qs_host, axis=1), how_many, offset,
                allowed, rescore,
            )
        qs = jnp.asarray(qs_host)
        q_norms = jnp.linalg.norm(qs, axis=1)
        # union of candidate buckets across ALL query vectors, mirroring the
        # reference's per-partition candidate scan
        valid = self._candidate_mask(snap, qs_host[0])
        for extra in qs_host[1:]:
            valid = valid | self._candidate_mask(snap, extra)
        want = how_many + offset
        if isinstance(snap, _QuantSnapshot):
            # quantized candidates (norms are exact f32), exact mean-cosine
            # rescore from the arena slab before the final cut
            r = min(snap.n,
                    _round_up_pow2(max(int(self.rescore_factor * want), 16)))
            while True:
                v, i = _quant_cosine_candidates(
                    snap.qmat, snap.qscale, snap.norms, qs, q_norms, valid, r
                )
                vals, idx = self._rescore_exact(
                    snap, qs_host, np.asarray(v)[None, :],
                    np.asarray(i)[None, :], cosine=True,
                )
                out = self._collect(snap, vals[0], idx[0], want, allowed, rescore)
                if len(out) >= want or r >= snap.n:
                    return out[offset:offset + how_many]
                r = min(snap.n, r * 2)
        k = min(snap.n, _round_up_pow2(max(4 * want, 64)))
        while True:
            vals, idx = _top_k_cosine_sum(snap.mat, snap.norms, qs, q_norms, valid, k)
            out = self._collect(snap, np.asarray(vals), np.asarray(idx), want, allowed, rescore)
            if len(out) >= want or k >= snap.n:
                return out[offset:offset + how_many]
            k = min(snap.n, k * 2)

    def _candidate_mask(self, snap: _YSnapshot, query_vec: np.ndarray):
        """(n_rows,) bool: the rows a query may be answered from. The zero
        rows that pad a sharded Y past its last id are never candidates."""
        n_rows = getattr(snap, "n_rows", snap.n)
        real = None if n_rows == snap.n else jnp.arange(n_rows) < snap.n
        if self.lsh is None or snap.buckets is None:
            return jnp.ones(snap.n, dtype=bool) if real is None else real
        candidates = self.lsh.get_candidate_indices(query_vec)
        lut = np.zeros(self.lsh.num_buckets, dtype=bool)
        lut[candidates] = True
        valid = jnp.asarray(lut)[snap.buckets]
        return valid if real is None else valid & real

    @staticmethod
    def _collect(snap, vals, idx, want, allowed, rescore) -> list[tuple[str, float]]:
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

    def device_factor_bytes(self) -> int:
        """Bytes the current Y snapshot holds on device (f32 matrix +
        scoring copy + norms + buckets, or the int8 slab + scales; summed
        over the devices of a mesh) — the HBM side of the bench memory
        section's f32-vs-int8 comparison."""
        snap = self.y_snapshot()
        if isinstance(snap, ivf_mod.IVFSnapshot):
            return snap.device_nbytes()
        arrays = (
            (snap.qmat, snap.qscale, snap.norms, snap.buckets)
            if isinstance(snap, _QuantSnapshot)
            else snap.device_arrays()
        )
        return int(sum(
            int(getattr(a, "nbytes", 0) or 0) for a in arrays if a is not None
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
