"""TPU-native k-means training.

Replaces Spark MLlib's ``KMeans.train`` (behind KMeansUpdate.buildModel,
app/oryx-app-mllib/.../kmeans/KMeansUpdate.java:107-122) with jit'd JAX
programs shaped for the MXU:

  * distance evaluation is the ``||x||² − 2·X·Cᵀ + ||c||²`` expansion, so the
    dominant cost of every Lloyd sweep is one (N,d)×(d,k) matmul;
  * centroid recomputation is a one-hot matmul ``Aᵀ·X`` (A = (N,k) assignment
    indicator), not a scatter — again MXU work, and under a sharded data axis
    XLA turns the reduction into a psum over the mesh;
  * iterations run under ``lax.scan`` (static trip count — the reference's
    MLlib convergence check is replaced by a fixed iteration budget from
    ``oryx.kmeans.iterations``);
  * the ``runs`` restarts (``oryx.kmeans.runs``) are a ``vmap`` over seeds —
    candidate-restart parallelism on device rather than sequential reruns —
    and the run with the lowest cost wins;
  * init: ``random`` samples k points; ``k-means||`` maps to a scan-based
    k-means++ (sequential D² sampling — the same seeding MLlib's k-means‖
    approximates, exact here because a TPU sweep over N points is one matmul).

Empty clusters keep their previous center (MLlib behavior) in the
lambda-tier trainer; :func:`fit_index_centroids` (the serving IVF index's
entry point) instead RESEEDS empty clusters to the points currently worst
served, because a dead cell in an inverted-file index is pure wasted probe
width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from oryx_tpu.ops import pallas_kernels as pk

INIT_RANDOM = "random"
INIT_KMEANS_PARALLEL = "k-means||"


def _sq_dists(points, centers):
    """(N, k) squared Euclidean distances; one MXU matmul."""
    sq = (
        (points * points).sum(axis=1, keepdims=True)
        - 2.0 * points @ centers.T
        + (centers * centers).sum(axis=1)[None, :]
    )
    return jnp.maximum(sq, 0.0)


def _init_random(key, points, k: int):
    idx = jax.random.choice(key, points.shape[0], (k,), replace=False)
    return points[idx]


def _init_plus_plus(key, points, k: int):
    """D²-weighted sequential seeding under lax.scan (k-means++)."""
    n = points.shape[0]
    key, first = jax.random.split(key)
    centers = jnp.zeros((k, points.shape[1]), dtype=points.dtype)
    centers = centers.at[0].set(points[jax.random.randint(first, (), 0, n)])
    min_d2 = _sq_dists(points, centers[:1])[:, 0]

    def body(carry, j):
        centers, min_d2, key = carry
        key, sub = jax.random.split(key)
        total = min_d2.sum()
        # degenerate case (all points coincide with centers): uniform draw
        probs = jnp.where(total > 0, min_d2 / jnp.maximum(total, 1e-30), 1.0 / n)
        idx = jax.random.categorical(sub, jnp.log(probs + 1e-30))
        c = points[idx]
        centers = centers.at[j].set(c)
        d2_new = ((points - c[None, :]) ** 2).sum(axis=1)
        return (centers, jnp.minimum(min_d2, d2_new), key), None

    (centers, _, _), _ = jax.lax.scan(body, (centers, min_d2, key), jnp.arange(1, k))
    return centers


@functools.partial(jax.jit, static_argnames=("k", "iterations", "init"))
def _kmeans_single_run(key, points, weights, k: int, iterations: int, init: str):
    if init == INIT_RANDOM:
        centers = _init_random(key, points, k)
    else:
        centers = _init_plus_plus(key, points, k)

    def lloyd(centers, _):
        d2 = _sq_dists(points, centers)
        a = jax.nn.one_hot(d2.argmin(axis=1), k, dtype=points.dtype)
        a = a * weights[:, None]  # padding rows carry zero weight
        counts = a.sum(axis=0)  # (k,)
        sums = a.T @ points  # (k, d) — MXU; psum'd by XLA when sharded
        new_centers = sums / jnp.maximum(counts, 1.0)[:, None]
        centers = jnp.where((counts > 0)[:, None], new_centers, centers)
        return centers, None

    centers, _ = jax.lax.scan(lloyd, centers, None, length=iterations)
    d2 = _sq_dists(points, centers)
    assign = d2.argmin(axis=1)
    min_d2 = jnp.take_along_axis(d2, assign[:, None], axis=1)[:, 0] * weights
    cost = min_d2.sum()
    counts = (jax.nn.one_hot(assign, k, dtype=points.dtype) * weights[:, None]).sum(0)
    return centers, counts, cost


@functools.partial(jax.jit, static_argnames=("k", "init"))
def _init_centers(key, points, k: int, init: str):
    if init == INIT_RANDOM:
        return _init_random(key, points, k)
    return _init_plus_plus(key, points, k)


def _kmeans_pallas_run(key, points, weights, k, iterations, init, interpret):
    """One restart with the fused Pallas Lloyd kernel (ops/pallas_kernels):
    distances, argmin, and sum/count/cost accumulation in one pass per sweep —
    the (N, k) intermediates never touch HBM. Points/weights are padded once
    for the whole run; only the (small) centers re-pad per sweep."""
    centers = _init_centers(key, points, k, init)
    n, d = points.shape
    n_pad = pk._pad_dim(max(n, 1), pk.TILE_N)
    d_pad = pk._pad_dim(d, pk._LANE)
    k_pad = pk._pad_dim(k, 8)
    pts = jnp.zeros((n_pad, d_pad), jnp.float32).at[:n, :d].set(points)
    wts = jnp.zeros((n_pad, 1), jnp.float32).at[:n, 0].set(weights)

    def pad_centers(c):
        ctr = jnp.zeros((k_pad, d_pad), jnp.float32).at[:k, :d].set(c)
        if k_pad > k:
            ctr = ctr.at[k:, 0].set(pk.FAR_AWAY)
        return ctr

    counts = cost = None
    for i in range(iterations + 1):
        sums, counts_p, cost_p = pk._call(
            pts, wts, pad_centers(centers), interpret=interpret
        )
        counts, cost = counts_p[0, :k], cost_p[0, 0]
        if i < iterations:  # final sweep only reads counts/cost
            new_centers = sums[:k, :d] / jnp.maximum(counts, 1.0)[:, None]
            centers = jnp.where((counts > 0)[:, None], new_centers, centers)
    return centers, counts, cost


@functools.partial(jax.jit, static_argnames=("k", "iterations"))
def _lloyd_from(points, centers, k: int, iterations: int):
    """``iterations`` Lloyd sweeps from GIVEN centers; returns the final
    (centers, counts, assign). Factored out of ``_kmeans_single_run`` so the
    empty-cluster reseeding loop can resume sweeps from patched centers."""
    weights = jnp.ones((points.shape[0],), dtype=points.dtype)

    def lloyd(centers, _):
        d2 = _sq_dists(points, centers)
        a = jax.nn.one_hot(d2.argmin(axis=1), k, dtype=points.dtype)
        counts = a.sum(axis=0)
        sums = a.T @ points
        new_centers = sums / jnp.maximum(counts, 1.0)[:, None]
        centers = jnp.where((counts > 0)[:, None], new_centers, centers)
        return centers, None

    centers, _ = jax.lax.scan(lloyd, centers, None, length=iterations)
    d2 = _sq_dists(points, centers)
    assign = d2.argmin(axis=1)
    counts = (jax.nn.one_hot(assign, k, dtype=points.dtype) * weights[:, None]).sum(0)
    return centers, counts, assign


def _reseed_empty(points: np.ndarray, centers: np.ndarray,
                  counts: np.ndarray, assign: np.ndarray) -> np.ndarray:
    """Move each empty cluster's center onto the point FARTHEST from its
    assigned center (distinct points, worst-served first) — the standard
    empty-cluster repair. Returns patched centers; no-op when none empty."""
    empty = np.flatnonzero(counts == 0)
    if empty.size == 0:
        return centers
    d2 = ((points - centers[assign]) ** 2).sum(axis=1)
    order = np.argsort(-d2, kind="stable")
    centers = centers.copy()
    for j, c in enumerate(empty[: len(order)]):
        centers[c] = points[order[j]]
    return centers


def fit_index_centroids(
    points: np.ndarray,
    k: int,
    iterations: int = 20,
    seed: int = 0,
    reseed_rounds: int = 4,
) -> "tuple[np.ndarray, np.ndarray, np.ndarray]":
    """Deterministic bounded k-means fit for the serving IVF index
    (models/als/ivf.py): k-means++ init from a FIXED seed, at most
    ``iterations`` Lloyd sweeps, then up to ``reseed_rounds`` empty-cluster
    repairs (reseed to worst-served points + 2 more sweeps each) so a
    planted-structure fit cannot emit dead cells while distinct points
    remain. Returns (centers (k,d) f32, counts (k,) i64, assign (n,) i32) —
    the assignment rides along so the index build skips a second pass.

    Unlike :func:`kmeans_train` this takes no PRNG plumbing and runs no
    restarts: the index rebuild path needs reproducibility (the incremental
    -maintenance-equals-rebuild invariant is tested bit-exactly) more than
    it needs the last percent of quantization error."""
    points = np.ascontiguousarray(np.asarray(points, dtype=np.float32))
    n = len(points)
    if n == 0:
        raise ValueError("no points")
    k = max(1, min(int(k), n))
    pts = jnp.asarray(points)
    key = jax.random.PRNGKey(int(seed))
    centers = _init_centers(key, pts, k, INIT_KMEANS_PARALLEL)
    centers, counts, assign = _lloyd_from(pts, centers, k, int(iterations))
    centers_np, counts_np, assign_np = jax.device_get((centers, counts, assign))
    for _ in range(max(0, int(reseed_rounds))):
        if (counts_np > 0).all():
            break
        patched = _reseed_empty(points, np.asarray(centers_np, dtype=np.float32),
                                counts_np, assign_np)
        centers, counts, assign = _lloyd_from(pts, jnp.asarray(patched), k, 2)
        centers_np, counts_np, assign_np = jax.device_get(
            (centers, counts, assign)
        )
    return (
        np.asarray(centers_np, dtype=np.float32),
        np.asarray(counts_np, dtype=np.int64),
        np.asarray(assign_np, dtype=np.int32),
    )


def kmeans_train(
    points: np.ndarray,
    k: int,
    iterations: int = 30,
    runs: int = 1,
    init: str = INIT_KMEANS_PARALLEL,
    key=None,
    use_pallas: "bool | None" = None,
):
    """Train on (N, d) points; returns (centers (k,d) np, counts (k,) np).

    ``runs`` restarts execute as one vmapped program; best-cost run wins
    (MLlib KMeans ``runs`` semantics). On TPU (or with ``use_pallas=True``)
    each Lloyd sweep instead runs the fused Pallas kernel, restarts
    sequentially. Both the default and the kernel's interpret mode come
    from the device that holds the points: forced on off-TPU (tests), the
    kernel is emulated.
    """
    from oryx_tpu.common import rand

    points = np.asarray(points, dtype=np.float32)
    n = len(points)
    if n == 0:
        raise ValueError("no points")
    k = min(k, n)
    if key is None:
        key = rand.get_key()
    pts = jnp.asarray(points)
    weights = jnp.ones((n,), dtype=jnp.float32)
    keys = jax.random.split(key, max(runs, 1))
    on_tpu = pk.on_tpu(pts)
    if use_pallas is None:
        use_pallas = on_tpu
    if use_pallas:
        results = [
            _kmeans_pallas_run(kk, pts, weights, k, iterations, init,
                               not on_tpu)
            for kk in keys
        ]
        centers = jnp.stack([r[0] for r in results])
        counts = jnp.stack([r[1] for r in results])
        costs = jnp.stack([r[2] for r in results])
    else:
        centers, counts, costs = jax.vmap(
            lambda kk: _kmeans_single_run(kk, pts, weights, k, iterations, init)
        )(keys)
    # pick the winner on device and fetch both result arrays in ONE
    # explicit transfer (argmin + two np.asarray calls were three syncs)
    best = jnp.argmin(costs)
    centers_np, counts_np = jax.device_get((centers[best], counts[best]))
    return (
        centers_np.astype(np.float64),
        counts_np.astype(np.int64),
    )
