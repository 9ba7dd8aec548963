"""IVF candidate generation over the factor arena (ISSUE 19 tentpole).

Covers the recall gate (planted-structure recall@10 >= 0.99 vs an EXACT
brute-force reference, probe widening included), incremental cell
maintenance bit-identical to a full rebuild after a speed-delta burst,
skew-drift re-clustering, the k-means index-duty fit (deterministic seed,
bounded iterations, empty-cluster reseeding), the oryx_index_* telemetry,
and a serving-layer swap e2e asserting zero request-path compiles after
an IVF-model handoff (the IVF warm ladder covers its own probe/scan
signatures)."""

import time

import httpx
import numpy as np
import pytest

from oryx_tpu.common import compilecache
from oryx_tpu.common import config as cfg
from oryx_tpu.common import ioutils
from oryx_tpu.common import metrics as metrics_mod
from oryx_tpu.models.als import ivf
from oryx_tpu.models.als.serving import ALSServingModel
from oryx_tpu.models.kmeans.train import _reseed_empty, fit_index_centroids
from oryx_tpu.serving.app import ServingLayer
from oryx_tpu.transport import topic as tp


def _planted(n=8000, k=32, n_centers=64, noise=0.05, seed=7):
    """Clustered catalog whose exact top-N structure is known: items sit in
    tight blobs around well-separated centers; the centers themselves are
    the queries (same construction as the PR-9 int8 recall gate)."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_centers, k)).astype(np.float32) * 3.0
    reps = n // n_centers
    items = (np.repeat(centers, reps, axis=0)
             + rng.standard_normal((reps * n_centers, k)).astype(np.float32)
             * noise)
    ids = [f"i{j}" for j in range(len(items))]
    return centers, items, ids


def _ivf_model(items, ids, k, **kw):
    m = ALSServingModel(k, implicit=True, device_dtype="int8",
                        index_enabled=True, **kw)
    m.bulk_load_items(ids, items)
    return m


# ---------------------------------------------------------------------------
# recall gate
# ---------------------------------------------------------------------------


def test_ivf_recall_at_10_on_planted_structure():
    """The acceptance gate: IVF top-10 recall >= 0.99 against an exact
    brute-force scan, and the returned scores are the EXACT f32 dots (the
    arena-slab rescore, not the quantized approximations)."""
    k = 32
    centers, items, ids = _planted(k=k)
    m = _ivf_model(items, ids, k)
    snap = m.y_snapshot()
    assert isinstance(snap, ivf.IVFSnapshot)
    assert snap.n_cells >= 16 and snap.cell_q is not None

    hits = total = 0
    for q in centers:
        exact = set(np.argsort(-(items @ q))[:10])
        got = m.top_n(q, 10)
        assert len(got) == 10
        for id_, score in got:
            pos = int(id_[1:])
            assert abs(score - float(items[pos] @ q)) < 1e-4
        hits += len({int(g[0][1:]) for g in got} & exact)
        total += 10
    assert hits / total >= 0.99, f"IVF recall@10 {hits / total:.4f}"


def test_ivf_batch_matches_single_and_masks_exclusions():
    k = 32
    centers, items, ids = _planted(n=4000, k=k)
    m = _ivf_model(items, ids, k)
    qs = centers[:16].copy()
    excl = [tuple(ids[j] for j in np.argsort(-(items @ qs[b]))[:3])
            if b % 2 == 0 else None for b in range(16)]
    res = m.top_n_batch(qs, 10, excluded=excl)
    for b in range(16):
        assert len(res[b]) == 10
        if excl[b]:
            assert not ({t[0] for t in res[b]} & set(excl[b]))
        # batch result == single-query result for the same exclusions
        single = m.top_n(qs[b], 10, excluded=excl[b])
        assert [t[0] for t in res[b]] == [t[0] for t in single]


def test_ivf_probe_widening_under_heavy_filtering():
    """An allowed-filter that consumes everything the default probe width
    surfaces must widen (rescore cut first, then the probe set) and still
    return the exact best of what remains."""
    k = 32
    centers, items, ids = _planted(n=4000, k=k)
    m = _ivf_model(items, ids, k, index_probes=2)
    q = centers[5]
    order = np.argsort(-(items @ q))
    blocked = {ids[j] for j in order[:600]}  # several cells' worth
    got = m.top_n(q, 10, allowed=lambda s: s not in blocked)
    assert len(got) == 10
    expect = [ids[j] for j in order if ids[j] not in blocked][:10]
    assert {t[0] for t in got} == set(expect)


def test_ivf_cosine_and_lsh_paths():
    k = 32
    centers, items, ids = _planted(n=4000, k=k)
    m = _ivf_model(items, ids, k, sample_rate=0.3)
    snap = m.y_snapshot()
    assert snap.cell_buckets is not None  # LSH buckets rode the cells
    got = m.top_n(centers[3], 10)
    assert len(got) == 10
    cos = m.top_n_cosine(centers[:2].copy(), 8)
    assert len(cos) == 8
    # cosine scores are exact-rescored: recompute the best one by hand
    top_id, top_score = cos[0]
    r = items[int(top_id[1:])]
    sims = [
        float(r @ c) / max(np.linalg.norm(r) * np.linalg.norm(c), 1e-12)
        for c in centers[:2]
    ]
    assert abs(top_score - np.mean(sims)) < 1e-4


# ---------------------------------------------------------------------------
# incremental maintenance
# ---------------------------------------------------------------------------


def test_ivf_incremental_equals_full_rebuild_after_speed_burst():
    """A speed-tier burst (moves between cells, in-place rewrites, and
    appends) applied through the delta path must leave device cells
    BIT-IDENTICAL to a full rebuild from the final store state with the
    same centroids and cell width."""
    k = 12
    centers, items, ids = _planted(n=800, k=k, n_centers=16)
    rng = np.random.default_rng(3)
    m = _ivf_model(items, ids, k)
    s0 = m.y_snapshot()

    for j in range(40):  # move rows to other clusters
        tgt = centers[(j * 7) % 16]
        m.set_item_vector(
            f"i{j}", tgt + rng.standard_normal(k).astype(np.float32) * 0.05
        )
    for j in range(100, 110):  # rewrite in place (same cell)
        m.set_item_vector(f"i{j}", items[j] * 1.5)
    for j in range(20):  # appends
        m.set_item_vector(
            f"new{j}",
            centers[j % 16] + rng.standard_normal(k).astype(np.float32) * 0.05,
        )
    s1 = m.y_snapshot()
    assert s1 is not s0 and s1.n == 820
    # the burst rode the delta path: centroids were NOT retrained
    assert s1.centroids_np is s0.centroids_np

    ids2, host, version, row_view = m.y.host_matrix()
    s2 = ivf.IVFSnapshot.build(
        ids2, host, version, None, row_view,
        centroids=s1.centroids_np, cell_width=s1.cell_width,
    )
    for name in ("cell_pos", "cell_q", "cell_scale", "cell_norms"):
        np.testing.assert_array_equal(
            np.asarray(getattr(s1, name)), np.asarray(getattr(s2, name)),
            err_msg=name,
        )
    # and the index still answers exactly
    q = centers[5]
    final = np.stack([m.y.get_vector(i) for i in ids2])
    exact = {ids2[j] for j in np.argsort(-(final @ q))[:10]}
    got = {t[0] for t in m.top_n(q, 10)}
    assert len(got & exact) >= 9


def test_ivf_skew_drift_triggers_recluster():
    k = 12
    centers, items, ids = _planted(n=800, k=k, n_centers=16)
    rng = np.random.default_rng(4)
    m = _ivf_model(items, ids, k, index_skew=2.5)
    s0 = m.y_snapshot()
    # pile fresh rows into one region until the balance drifts
    for j in range(600):
        m.set_item_vector(
            f"pile{j}",
            centers[0] + rng.standard_normal(k).astype(np.float32) * 0.02,
        )
    s1 = m.y_snapshot()
    assert s1.n == 1400
    # the drift forced a re-cluster: fresh centroids, not the delta path
    assert s1.centroids_np is not s0.centroids_np


def test_ivf_telemetry_counters_and_skew_gauge():
    registry = metrics_mod.default_registry()
    k = 16
    centers, items, ids = _planted(n=2000, k=k, n_centers=32)
    m = _ivf_model(items, ids, k)
    m.top_n_batch(centers[:8].copy(), 10)
    snap = registry.snapshot()
    assert snap.get("oryx_index_cells_total", {}).get("", 0) > 0
    assert snap.get("oryx_index_probed_cells_total", {}).get("", 0) > 0
    assert snap.get("oryx_index_candidate_rows_total", {}).get("", 0) > 0
    assert snap.get("oryx_index_cell_skew", {}).get("", 0) >= 1.0
    # the IVF scan runs under its OWN cost programs: probe + scan keys both
    # recorded as device calls (rescore is host-side f32, outside them)
    calls = snap.get("oryx_device_calls_total", {})
    assert any("als.ivf_probe/" in c for c in calls), calls
    assert any("als.ivf_scan/" in c for c in calls), calls


# ---------------------------------------------------------------------------
# k-means index duty
# ---------------------------------------------------------------------------


def test_fit_index_centroids_deterministic_bounded_no_dead_cells():
    rng = np.random.default_rng(11)
    blobs = rng.standard_normal((4, 8)).astype(np.float32) * 4.0
    pts = (np.repeat(blobs, 100, axis=0)
           + rng.standard_normal((400, 8)).astype(np.float32) * 0.3)
    a = fit_index_centroids(pts, 8, iterations=10, seed=5)
    b = fit_index_centroids(pts, 8, iterations=10, seed=5)
    np.testing.assert_array_equal(a[0], b[0])  # deterministic seed
    np.testing.assert_array_equal(a[2], b[2])
    centers, counts, assign = a
    assert centers.shape == (8, 8) and assign.shape == (400,)
    assert (counts > 0).all(), "dead cells survived reseeding"
    assert counts.sum() == 400


def test_reseed_empty_moves_center_to_worst_served_point():
    pts = np.array([[0.0, 0.0], [1.0, 0.0], [10.0, 10.0]], dtype=np.float32)
    centers = np.array([[0.5, 0.0], [99.0, 99.0]], dtype=np.float32)
    assign = np.array([0, 0, 0], dtype=np.int32)  # center 1 empty
    counts = np.array([3, 0], dtype=np.int64)
    patched = _reseed_empty(pts, centers, counts, assign)
    np.testing.assert_array_equal(patched[1], pts[2])  # farthest point
    np.testing.assert_array_equal(patched[0], centers[0])  # untouched


# ---------------------------------------------------------------------------
# IVF-model handoff: zero request-path compiles (swap e2e)
# ---------------------------------------------------------------------------


def test_ivf_handoff_zero_compiles_after_swap(tmp_path):
    """index.enabled + device-dtype=int8 + precompile-batches: a MODEL
    handoff (staged generation swap) must leave the first post-handoff
    /recommend burst compile-free — the warm ladder covers the IVF probe
    and scan signatures (their own AOT cost keys), exclusion-carrying
    form included. Same shape as the PR-9 int8 swap e2e."""
    from test_compilecache import _publish, _train_model

    tp.reset_memory_brokers()
    compilecache.warmup_state().reset()
    port = ioutils.choose_free_port()
    config = cfg.overlay_on(
        {
            "oryx.serving.api.port": port,
            "oryx.serving.model-manager-class":
                "oryx_tpu.models.als.serving.ALSServingModelManager",
            "oryx.serving.application-resources":
                "oryx_tpu.serving.resources.als",
            "oryx.serving.compute.precompile-batches": True,
            "oryx.serving.compute.coalesce-max-batch": 8,
            "oryx.serving.device-dtype": "int8",
            "oryx.serving.index.enabled": True,
            "oryx.serving.index.probes": 4,
        },
        cfg.get_default(),
    )
    tp.maybe_create_topics(config, "input-topic", "update-topic")
    gen1_dir = tmp_path / "gen1"
    gen1_dir.mkdir()
    pmml1, known1 = _train_model(gen1_dir, features=4, seed=0)
    _publish(pmml1, gen1_dir, known1)
    layer = ServingLayer(config)
    layer.start()
    base = f"http://127.0.0.1:{port}"
    try:
        with httpx.Client(base_url=base, timeout=60) as client:
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if (client.get("/readyz").status_code == 200
                        and layer._warmer.warmed_models >= 1):
                    break
                time.sleep(0.1)
            else:
                pytest.fail("gen1 never became warm-ready")
            model = layer.manager.get_model()
            assert model.index_enabled
            assert isinstance(model.y_snapshot(), ivf.IVFSnapshot)

            # a second generation with NEW shapes stages, warms off-path
            # (the IVF ladder), and promotes
            gen2_dir = tmp_path / "gen2"
            gen2_dir.mkdir()
            pmml2, known2 = _train_model(gen2_dir, features=5, seed=1)
            _publish(pmml2, gen2_dir, known2)
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline:
                if layer.manager.get_model().features == 5:
                    break
                time.sleep(0.1)
            else:
                pytest.fail("staged IVF generation never promoted")
            assert layer._warmer.promoted_models >= 1
            assert isinstance(
                layer.manager.get_model().y_snapshot(), ivf.IVFSnapshot
            )

            # settle off-path stragglers, then assert the burst (default
            # endpoint = exclusion-carrying + the exclusion-free form)
            # compiles NOTHING
            layer.manager.get_model().get_yty_solver()
            client.get("/recommend/u0?considerKnownItems=true")
            c0 = compilecache.compiles_total()
            for i in range(10):
                r = client.get(f"/recommend/u{i}")
                assert r.status_code == 200
                assert all(
                    rec["id"] not in known2.get(f"u{i}", [])
                    for rec in r.json()
                )
            for i in range(5):
                r = client.get(f"/recommend/u{i}?considerKnownItems=true")
                assert r.status_code == 200
            assert compilecache.compiles_total() - c0 == 0, (
                "request-path compile after IVF-model handoff"
            )
    finally:
        layer.close()
        tp.reset_memory_brokers()
        compilecache.warmup_state().reset()

