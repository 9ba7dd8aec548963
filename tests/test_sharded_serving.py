"""Sharded serving top-N over the 8-device CPU mesh: per-shard top-k +
cross-shard merge must equal the single-device exact scan (SURVEY §2.14
"device-resident Y shards" mapping)."""

import numpy as np

from oryx_tpu.models.als.serving import ALSServingModel
from oryx_tpu.parallel.mesh import make_mesh


def _build(mesh=None, n_items=1000, features=16, seed=0):
    rng = np.random.default_rng(seed)
    model = ALSServingModel(features, implicit=True, mesh=mesh)
    y = rng.standard_normal((n_items, features)).astype(np.float32)
    model.bulk_load_items([f"i{i}" for i in range(n_items)], y)
    return model, rng.standard_normal((8, features)).astype(np.float32)


def test_sharded_matches_single_device():
    mesh = make_mesh(axes=("model",))
    assert mesh.size == 8
    sharded, queries = _build(mesh)
    single, _ = _build(None)
    got = sharded.top_n_batch(queries, 10)
    want = single.top_n_batch(queries, 10)
    for g, w in zip(got, want):
        assert [i for i, _ in g] == [i for i, _ in w]
        np.testing.assert_allclose(
            [v for _, v in g], [v for _, v in w], rtol=1e-5
        )


def test_sharded_item_count_not_divisible_by_shards():
    mesh = make_mesh(axes=("model",))
    sharded, queries = _build(mesh, n_items=1003)  # 1003 % 8 != 0
    single, _ = _build(None, n_items=1003)
    got = sharded.top_n_batch(queries, 7)
    want = single.top_n_batch(queries, 7)
    for g, w in zip(got, want):
        assert [i for i, _ in g] == [i for i, _ in w]
        # padding rows must never surface
        assert all(int(i[1:]) < 1003 for i, _ in g)


def test_sharded_with_host_callable_falls_back():
    """Arbitrary host ``alloweds`` callables (rescorer SPI) still answer
    correctly via the single-device fallback."""
    mesh = make_mesh(axes=("model",))
    sharded, queries = _build(mesh, n_items=200)
    banned = {"i0", "i1", "i2"}
    got = sharded.top_n_batch(queries, 5, alloweds=[lambda i: i not in banned] * 8)
    for g in got:
        assert len(g) == 5
        assert banned.isdisjoint({i for i, _ in g})


def test_sharded_excluded_device_side():
    """Known-item filtering runs ON the sharded path as a device-side mask
    (VERDICT r1 #5): results match the single-device scan minus exclusions."""
    mesh = make_mesh(axes=("model",))
    sharded, queries = _build(mesh, n_items=400)
    single, _ = _build(None, n_items=400)
    # per-query exclusions: ban each query's unfiltered top-3
    base = single.top_n_batch(queries, 10)
    excl = [{i for i, _ in r[:3]} for r in base]
    got = sharded.top_n_batch(queries, 5, excluded=excl)
    want = single.top_n_batch(queries, 5, excluded=excl)
    for b, (g, w) in enumerate(zip(got, want)):
        assert len(g) == 5
        assert excl[b].isdisjoint({i for i, _ in g})
        assert [i for i, _ in g] == [i for i, _ in w]


def test_sharded_top_n_single_query_excluded():
    """/recommend's single-query path also rides the sharded scan with
    device-side known-item exclusion."""
    mesh = make_mesh(axes=("model",))
    sharded, queries = _build(mesh, n_items=300)
    single, _ = _build(None, n_items=300)
    base = single.top_n(queries[0], 8)
    excl = {i for i, _ in base[:2]}
    g = sharded.top_n(queries[0], 5, excluded=excl)
    w = single.top_n(queries[0], 5, excluded=excl)
    assert len(g) == 5 and excl.isdisjoint({i for i, _ in g})
    assert [i for i, _ in g] == [i for i, _ in w]


def test_sharded_lsh_masks_on_device():
    """LSH sample-rate masking runs on the sharded path: every result lies in
    the query's candidate-bucket set (no fallback, no full scan)."""
    import numpy as np

    rng = np.random.default_rng(3)
    mesh = make_mesh(axes=("model",))
    n_items, features = 800, 16
    model = ALSServingModel(features, implicit=True, sample_rate=0.5, mesh=mesh)
    y = rng.standard_normal((n_items, features)).astype(np.float32)
    model.bulk_load_items([f"i{i}" for i in range(n_items)], y)
    queries = rng.standard_normal((4, features)).astype(np.float32)
    got = model.top_n_batch(queries, 6)
    assert model.lsh is not None and model.lsh.num_hashes > 0
    snap = model.y_snapshot()
    assert snap.mesh is not None  # really took the sharded path
    buckets = np.asarray(snap.buckets)
    for b, res in enumerate(got):
        assert res, "LSH-masked sharded scan returned nothing"
        cand = set(model.lsh.get_candidate_indices(queries[b]))
        for i, _ in res:
            assert int(buckets[snap.id_to_idx[i]]) in cand


def test_sharded_how_many_exceeds_shard_rows():
    """how_many > per-shard row count must still return min(how_many, n)
    results (ADVICE r1: the per-shard k cap must not cap the merged result)."""
    mesh = make_mesh(axes=("model",))
    n_items = 96  # 12 rows per shard on 8 devices
    sharded, queries = _build(mesh, n_items=n_items)
    single, _ = _build(None, n_items=n_items)
    got = sharded.top_n_batch(queries, 40)
    want = single.top_n_batch(queries, 40)
    for g, w in zip(got, want):
        assert len(g) == 40
        assert [i for i, _ in g] == [i for i, _ in w]


def test_sharded_snapshot_tracks_point_updates():
    """Speed-layer UP point updates must flow through the incremental
    snapshot onto the sharded scan: an updated item vector changes the
    sharded top-N without a model reload."""
    mesh = make_mesh(axes=("model",))
    sharded, queries = _build(mesh, n_items=320)
    q = queries[0]
    base = sharded.top_n(q, 3)
    # craft a vector that dominates the query direction, assign to a loser
    winner_vec = (q / np.linalg.norm(q) * 50.0).astype(np.float32)
    sharded.set_item_vector("i300", winner_vec)
    got = sharded.top_n(q, 3)
    assert got[0][0] == "i300", (base, got)
    snap = sharded.y_snapshot()
    assert snap.mesh is not None  # still the multi-device scan
    # appended NEW item also lands in the sharded scan
    sharded.set_item_vector("fresh", (winner_vec * 2).astype(np.float32))
    got2 = sharded.top_n(q, 3)
    assert got2[0][0] == "fresh"
