"""Pallas kernel tests (interpret mode on the CPU test mesh; the same kernel
compiles natively on TPU)."""

import numpy as np
import pytest

from oryx_tpu.ops.pallas_kernels import kmeans_assign_accumulate


def _reference(points, weights, centers):
    d2 = (
        (points * points).sum(1, keepdims=True)
        - 2 * points @ centers.T
        + (centers * centers).sum(1)[None, :]
    )
    d2 = np.maximum(d2, 0)
    idx = d2.argmin(axis=1)
    k = len(centers)
    sums = np.zeros_like(centers)
    counts = np.zeros(k)
    for i, (p, w) in enumerate(zip(points, weights)):
        sums[idx[i]] += w * p
        counts[idx[i]] += w
    cost = (d2[np.arange(len(points)), idx] * weights).sum()
    return sums, counts, cost


def test_fused_lloyd_accumulate_matches_reference():
    rng = np.random.default_rng(0)
    points = rng.standard_normal((700, 5)).astype(np.float32)
    weights = np.ones(700, dtype=np.float32)
    centers = rng.standard_normal((7, 5)).astype(np.float32)
    sums, counts, cost = kmeans_assign_accumulate(
        points, weights, centers, interpret=True
    )
    ref_sums, ref_counts, ref_cost = _reference(points, weights, centers)
    np.testing.assert_allclose(np.asarray(sums), ref_sums, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(counts), ref_counts, rtol=1e-6)
    assert float(cost) == pytest.approx(float(ref_cost), rel=1e-4)


def test_fused_lloyd_weights_mask_padding():
    rng = np.random.default_rng(1)
    points = rng.standard_normal((100, 3)).astype(np.float32)
    weights = np.zeros(100, dtype=np.float32)
    weights[:60] = 1.0  # last 40 rows are padding
    centers = rng.standard_normal((4, 3)).astype(np.float32)
    sums, counts, cost = kmeans_assign_accumulate(
        points, weights, centers, interpret=True
    )
    ref_sums, ref_counts, ref_cost = _reference(points[:60], weights[:60], centers)
    np.testing.assert_allclose(np.asarray(sums), ref_sums, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(counts), ref_counts, rtol=1e-6)
    assert float(cost) == pytest.approx(float(ref_cost), rel=1e-4)


def test_fused_lloyd_nonuniform_weights_and_ties():
    rng = np.random.default_rng(2)
    points = np.repeat(rng.standard_normal((50, 4)), 2, axis=0).astype(np.float32)
    weights = rng.uniform(0.5, 2.0, 100).astype(np.float32)
    centers = points[:6].copy()  # exact ties: points sitting on centers
    sums, counts, cost = kmeans_assign_accumulate(
        points, weights, centers, interpret=True
    )
    ref_sums, ref_counts, ref_cost = _reference(points, weights, centers)
    np.testing.assert_allclose(np.asarray(sums), ref_sums, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(counts), ref_counts, rtol=1e-5)
    assert float(cost) == pytest.approx(float(ref_cost), rel=1e-3, abs=1e-3)


def test_pallas_lloyd_path_matches_xla_path():
    from oryx_tpu.models.kmeans import train as kmtrain

    rng = np.random.default_rng(7)
    pts = np.concatenate(
        [rng.normal(c, 0.4, size=(50, 3)) for c in ((0, 0, 0), (8, 8, 8), (-8, 4, 0))]
    )
    import jax

    key = jax.random.PRNGKey(3)
    c_xla, n_xla = kmtrain.kmeans_train(
        pts, 3, iterations=8, runs=1, init="random", key=key, use_pallas=False
    )
    c_pl, n_pl = kmtrain.kmeans_train(
        pts, 3, iterations=8, runs=1, init="random", key=key, use_pallas=True
    )
    np.testing.assert_allclose(c_pl, c_xla, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(n_pl, n_xla)
