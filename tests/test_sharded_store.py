"""One serving replica whose Y is split by rows over a mesh (ISSUE 26): the
answers are the reference's and the one-device path's, no per-row array is
whole on any device, point updates and appends keep the split, and the warm
ladder leaves nothing to compile."""

import logging
import os
import sys

import jax
import numpy as np
import pytest

from oryx_tpu.common import compilecache, profiling
from oryx_tpu.models.als.serving import ALSServingModel
from oryx_tpu.parallel.mesh import make_mesh, padded_rows, put_row_sharded
from oryx_tpu.serving.batcher import pow2_buckets

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.harness import manifest as mf  # noqa: E402

REFERENCE = mf.load_module("references", "als_topn")
SHARDS = 4
N_ITEMS = 1003  # not a multiple of the four shards
FEATURES = 24


def _mesh():
    return make_mesh(SHARDS, axes=("model",))


def _factors(seed, n=N_ITEMS, k=FEATURES, n_q=64):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n, k), dtype=np.float32),
            rng.standard_normal((n_q, k), dtype=np.float32))


def _model(y, mesh, **kw):
    model = ALSServingModel(y.shape[1], implicit=True, mesh=mesh, **kw)
    model.bulk_load_items([f"i{j}" for j in range(len(y))], y)
    return model


#: Every scan backend a model can resolve to (ISSUE 28), over the same
#: factors: what each answers is a float32 brute force's. The index probes
#: all of its cells — on factors without cluster structure that is what makes
#: it exact (its recall under fewer probes is test_ivf.py's to hold).
BACKENDS = {
    "mesh": lambda: {"mesh": _mesh()},
    "flat": dict,
    "int8": lambda: {"device_dtype": "int8"},
    "ivf": lambda: {"device_dtype": "int8", "index_enabled": True,
                    "index_cells": 16, "index_probes": 16},
}


def _served(y, backend):
    options = BACKENDS[backend]()
    return _model(y, options.pop("mesh", None), **options)


def _names(answers):
    return [[i for i, _ in a] for a in answers]


def _ids(answers):
    return [[int(i[1:]) for i in a] for a in _names(answers)]


@pytest.mark.parametrize("backend", list(BACKENDS))
@pytest.mark.parametrize("excluding", [False, True], ids=["plain", "excluding"])
@pytest.mark.parametrize("batch", [1, 3, 64])
def test_mesh_answers_are_the_references_and_the_one_device_paths(
        batch, excluding, backend):
    y, qs = _factors(seed=batch)
    qs = qs[:batch]
    sharded, single = _served(y, backend), _model(y, None)
    excluded = None
    if excluding:
        # each query's own best three are taken out of its answer
        best = _ids(single.top_n_batch(qs, 3))
        excluded = [[f"i{j}" for j in row] for row in best]
    got = sharded.top_n_batch(qs, 10, excluded=excluded)
    want = single.top_n_batch(qs, 10, excluded=excluded)
    assert _ids(got) == _ids(want)
    masked = y.copy()
    ref_vals, ref_idx = [], []
    for b in range(batch):
        rows = masked if not excluding else np.delete(masked, best[b], axis=0)
        keep = np.arange(len(y)) if not excluding else np.delete(
            np.arange(len(y)), best[b])
        v, i = REFERENCE.top_n(qs[b:b + 1], rows, 10)
        ref_vals.append(v[0])
        ref_idx.append(keep[i[0]].tolist())
    assert _ids(got) == ref_idx
    np.testing.assert_allclose(
        [[v for _, v in a] for a in got], ref_vals, rtol=2e-5, atol=1e-5)
    assert all(0 <= j < N_ITEMS for a in _ids(got) for j in a)


def _assert_split_by_rows(arr, n_rows):
    assert arr.shape[0] == n_rows
    shards = arr.addressable_shards
    assert len(shards) == SHARDS
    assert {s.data.shape[0] for s in shards} == {n_rows // SHARDS}
    assert len({s.device for s in shards}) == SHARDS
    assert len({s.index[0].start or 0 for s in shards}) == SHARDS
    assert not arr.sharding.is_fully_replicated
    assert len(arr.sharding.device_set) == SHARDS


def test_no_array_with_all_of_ys_rows_lies_on_one_device():
    y, _ = _factors(seed=7)
    model = _model(y, _mesh(), device_dtype="bfloat16")
    snap = model.y_snapshot()
    n_rows = padded_rows(N_ITEMS, snap.mesh)
    assert n_rows == 1004 and snap.n == N_ITEMS and snap.n_rows == n_rows
    assert snap.score_mat is not snap.mat
    assert str(snap.mat.dtype) == "float32"
    assert str(snap.score_mat.dtype) == "bfloat16"
    for arr in (snap.mat, snap.score_mat, snap.norms):
        _assert_split_by_rows(arr, n_rows)
    # the padding row is zero and its norm too: nothing of Y, masked in scans
    assert not np.asarray(snap.mat)[N_ITEMS:].any()
    np.testing.assert_allclose(np.asarray(snap.norms)[:N_ITEMS],
                               np.linalg.norm(y, axis=1), rtol=1e-5)
    assert model.device_factor_bytes() == n_rows * (FEATURES * 6 + 4)
    # each device's share is on the gauge
    fam: dict = {}
    from oryx_tpu.common import metrics as metrics_mod

    metrics_mod.default_registry().get(
        "oryx_serving_y_shard_bytes").snapshot_into(fam)
    shares = [v for v in fam["oryx_serving_y_shard_bytes"].values() if v]
    assert len(shares) >= SHARDS
    assert (n_rows // SHARDS) * (FEATURES * 6 + 4) in shares


def test_put_row_sharded_hands_each_device_only_its_block():
    mesh = _mesh()
    host = np.arange(10 * 3, dtype=np.float32).reshape(10, 3)
    arr = put_row_sharded(host, mesh)
    assert arr.shape == (12, 3)
    blocks = sorted(arr.addressable_shards, key=lambda s: s.index[0].start or 0)
    assert [b.data.shape for b in blocks] == [(3, 3)] * 4
    np.testing.assert_array_equal(np.asarray(arr)[:10], host)
    assert not np.asarray(arr)[10:].any()


def test_a_point_update_and_an_append_keep_the_split_and_the_answers():
    y, qs = _factors(seed=11)
    sharded, single = _model(y, _mesh()), _model(y, None)
    sharded.y_snapshot(), single.y_snapshot()
    q = qs[0]
    loud = (q / np.linalg.norm(q) * 40.0).astype(np.float32)
    steps = [("i17", loud),            # a point update of a row of shard 0
             ("fresh0", loud * 2),     # fills the one row of padding
             ("fresh1", loud * 3),     # outgrows it: a new padded row count
             ("i1002", loud * 4)]      # and a row of the last shard again
    n = N_ITEMS
    for item, vec in steps:
        for m in (sharded, single):
            m.set_item_vector(item, vec)
        n += item.startswith("fresh")
        snap = sharded.y_snapshot()
        assert snap.n == n and snap.mesh is not None
        for arr in (snap.mat, snap.score_mat, snap.norms):
            _assert_split_by_rows(arr, padded_rows(n, snap.mesh))
        got, want = sharded.top_n_batch(qs[:5], 8), single.top_n_batch(qs[:5], 8)
        assert _names(got) == _names(want)
        assert got[0][0][0] == item
        assert sharded.top_n(q, 3)[0][0] == item
    np.testing.assert_allclose(sharded.y.get_vtv(), single.y.get_vtv(),
                               rtol=1e-4)


@pytest.mark.parametrize("backend", list(BACKENDS))
def test_host_filters_and_cosine_run_on_the_split_arrays(backend):
    y, qs = _factors(seed=13)
    sharded, single = _served(y, backend), _model(y, None)
    banned = {"i3", "i500", "i1002"}
    allow = [lambda i: i not in banned] * 4
    got = sharded.top_n_batch(qs[:4], 6, alloweds=allow)
    want = single.top_n_batch(qs[:4], 6, alloweds=allow)
    assert _names(got) == _names(want)
    # a filter one row in fifty passes: the batch's candidates run out and
    # every query falls back to the widening single-query path; the best of
    # the rows that pass is excluded as well. Against a float32 brute force
    passing = np.arange(7, N_ITEMS, 50)
    rare = [lambda i: int(i[1:]) % 50 == 7] * 4
    left, out = passing[1:], [[f"i{passing[0]}"]] * 4
    got = sharded.top_n_batch(qs[:4], 6, alloweds=rare, excluded=out)
    brute = y[left] @ qs[:4].T
    order = np.argsort(-brute, axis=0)[:6].T
    assert _ids(got) == left[order].tolist()
    np.testing.assert_allclose(
        [[v for _, v in a] for a in got],
        np.take_along_axis(brute.T, order, axis=1), rtol=2e-5, atol=1e-5)
    one = sharded.top_n(qs[5], 4, offset=2, allowed=rare[0], excluded=out[0])
    order = np.argsort(-(y[left] @ qs[5]))[2:6]
    assert _ids([one]) == [left[order].tolist()]
    # past the two rows themselves, which tie at the head of the list
    cos_got = sharded.top_n_cosine(y[[5, 9]], 7, offset=2)
    cos_want = single.top_n_cosine(y[[5, 9]], 7, offset=2)
    assert [i for i, _ in cos_got] == [i for i, _ in cos_want]
    unit = y / np.linalg.norm(y, axis=1, keepdims=True)
    brute = (unit @ unit[[5, 9]].T).mean(axis=1)
    order = np.argsort(-brute)[2:9]
    assert _ids([cos_got]) == [order.tolist()]
    np.testing.assert_allclose(
        [v for _, v in cos_got], brute[order], rtol=2e-5, atol=1e-5)


def test_after_the_warm_ladder_no_bucket_compiles():
    y, _ = _factors(seed=17, n=403)
    model = _model(y, _mesh())
    buckets = pow2_buckets(16)
    compilecache.install_compile_listener()
    cold = compilecache.compiles_total()
    for b in buckets:
        model.warm_bucket(b, 10)
    assert compilecache.compiles_total() - cold >= 3 * len(buckets)
    snap = model.y_snapshot()
    for b in buckets:
        for excl in ("", "+excl48", "+excl240"):
            key = f"als.top_n_batch/b{b}{excl}+sharded"
            assert key in snap.cost_keys_attempted
            flops, bytes_ = profiling.costs().cost(key)
            assert flops > 0 and bytes_ > 0, key
    before = compilecache.compiles_total()
    rng = np.random.default_rng(0)
    for b in buckets:
        qs = rng.standard_normal((b, FEATURES), dtype=np.float32)
        assert len(model.top_n_batch(qs, 10)) == b
        model.top_n_batch(qs, 10, excluded=[["i1", "i2"]] + [None] * (b - 1))
    assert compilecache.compiles_total() - before == 0


def test_int8_with_a_mesh_is_downgraded_aloud(caplog):
    with caplog.at_level(logging.WARNING, logger="oryx_tpu.models.als.serving"):
        model = ALSServingModel(FEATURES, implicit=True, mesh=_mesh(),
                                device_dtype="int8")
    assert model.device_dtype == "bfloat16"
    assert any("int8 is not supported with sharded serving" in r.getMessage()
               for r in caplog.records)
    y, qs = _factors(seed=19, n=200)
    model.bulk_load_items([f"i{j}" for j in range(len(y))], y)
    assert str(model.y_snapshot().score_mat.dtype) == "bfloat16"
    assert len(model.top_n_batch(qs[:2], 5)[0]) == 5


def test_one_device_serves_unsharded_and_says_so(caplog, monkeypatch):
    from oryx_tpu.common import config as oryx_config
    from oryx_tpu.models.als import serving as serving_mod

    config = oryx_config.overlay_on(
        {"oryx.serving.compute.sharded": True}, oryx_config.get_default())
    monkeypatch.setattr(serving_mod.jax, "devices",
                        lambda *a: jax.local_devices()[:1])
    with caplog.at_level(logging.INFO, logger="oryx_tpu.models.als.serving"):
        manager = serving_mod.ALSServingModelManager(config)
    assert manager.mesh is None
    assert any("only one device" in r.getMessage() for r in caplog.records)
    monkeypatch.undo()
    assert serving_mod.ALSServingModelManager(config).mesh.size == len(
        jax.devices())
