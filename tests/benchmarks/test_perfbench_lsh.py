"""The cell of the reference's "with LSH" row (``serve-5m-250f-lsh.open``)
in its ``cpu`` rehearsal, its reference, cost and readers on hand-made
inputs, and the faults the cell has to catch."""

import numpy as np
import pytest

from perfbench_util import LINE_KEYS, ROOT, rehearse

import sys

sys.path.insert(0, ROOT)
from benchmarks.harness import manifest as mf  # noqa: E402
from benchmarks.harness.checks import Checks  # noqa: E402
from benchmarks.harness.peaks import least_seconds, peaks_for  # noqa: E402

MANIFEST = mf.load_manifest()
CELL = "serve-5m-250f-lsh.open"
KIND = "TPU v5 lite"
OWN = {"lsh_scanned_share.lsh", "lsh_select_ms.lsh", "topn_roofline.lsh",
       "topn_mfu.lsh", "device_idle.lsh", "lsh_build_s.lsh",
       # the host stage's, as the other serving cells read theirs
       "host_stage_idle.lsh", "queue_wait_ms.lsh", "host_path_ms.lsh",
       "flush_upload_ms.lsh", "flush_dispatch_ms.lsh"}


def test_the_cell_is_the_published_lsh_row_with_nothing_cut():
    c = mf.Cell(MANIFEST, CELL)
    plain = mf.load_json(mf.find("configs", "als-5m-250f", ".json"))
    assert c.chips == 1 and c.entry["traffic"] == "open-lsh"
    assert c.config["reduced"] == c.config_entry["reduced"] == []
    assert len(c.config_entry["source"]) <= 200
    assert "With LSH (sample rate 0.3)" in c.config["source"]
    for key in ("features", "items", "users", "implicit", "how-many",
                "device-dtype", "serving", "precision"):
        assert c.config[key] == plain[key], key
    assert c.config["sample-rate"] == 0.3
    assert c.config["lsh"] == {"hashes": 13, "max-bits-differing": 5}
    from oryx_tpu.models.als.lsh import choose_hash_config

    assert choose_hash_config(0.3) == (13, 5)
    assert "candidate buckets" in c.config["guarantees"]
    limits = dict(c.config["limits"])
    assert limits.pop("non_candidates_in_answers") == 0
    for key in ("unanswered", "compiles_in_window", "malformed_answers"):
        assert limits[key] == 0
    # `open` at a quarter of the parent's knee, capped at open's own rate
    mix, open_ = c.traffic, mf.load_json(mf.find("traffic", "open", ".json"))
    for key in ("loop", "endpoint", "processes", "user_zipf_s", "timeout_s",
                "sample_requests", "schedule_seed", "warm_requests", "lead_s"):
        assert mix[key] == open_[key], key
    assert mix["rate_per_s"] % 10 == 0
    assert mix["rate_per_s"] == min(open_["rate_per_s"],
                                    0.25 * mix["knee_req_per_s"] // 10 * 10)
    assert "PLACEHOLDER" not in mix["why"] + c.entry["why"]
    # resident: Y in float32 and the bucket-ordered bfloat16 copy (five
    # windows of 128 a bucket of ~610 rows, 256 lanes), over the floor
    n, k = c.config["items"], c.config["features"]
    resident = n * 256 * 4 + 43_200 * 128 * 256 * 2
    assert 0.25 < resident / peaks_for(KIND)["hbm_bytes"] < 0.6
    assert {"recommend_p95_ms", "setup_s"} == {m["name"] for m in c.end_to_end}
    assert {m["name"] for m in c.per_layer} == OWN
    for m in c.per_layer:
        assert m["workloads"] == [CELL]
        assert m["moves"] == ("setup_s" if m["name"] == "lsh_build_s.lsh"
                              else "recommend_p95_ms")
    p95 = [m for m in MANIFEST["end_to_end"] if m["name"] == "recommend_p95_ms"]
    assert p95[0]["workloads"][-1] == CELL
    for other in ("serve-5m-250f.open", "serve-5m-250f-known.open"):
        assert not OWN & {m["name"] for m in mf.Cell(MANIFEST, other).per_layer}


@pytest.mark.parametrize("trace", [0, 1], ids=["timed", "traced"])
def test_rehearsal_prints_the_contract_line_with_every_lsh_metric(trace):
    rc, line, err = rehearse(CELL, seed=2 ** 31 + 40, trace=trace)
    assert rc == 0, err[-2000:]
    assert set(line) == LINE_KEYS | ({"breakdown"} if trace else set())
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0, (line, err[-1500:])
    assert line["device"]["platform"] == "cpu"
    assert set(line["compared"]) == {
        "unanswered", "compiles_in_window", "non_candidates_in_answers",
        "score_err", "miss_share", "malformed_answers"}
    assert '"compiles_in_window": 0' in err and '"info": "lsh"' in err
    c = mf.Cell(MANIFEST, CELL)
    if trace:
        host_side = {m["name"] for m in c.per_layer
                     if m["source"] != "device_trace"}
        assert set(line["metrics"]) == host_side
        # one query a flush or two, each its ball's 29% of the buckets:
        # over the padding of a tiny catalogue, never all of it
        share = line["metrics"]["lsh_scanned_share.lsh"]["value"]
        assert 0 < share < 100 * 18
    else:
        assert set(line["metrics"]) == {m["name"] for m in c.end_to_end}


_OWN_BUCKET_ONLY = '''
from oryx_tpu.models.als import lsh as L
from oryx_tpu.models.als import serving as S
# a query is answered from its own bucket only: a flush lists its
# queries' own buckets, a full pass masks at radius 0
L.LocalitySensitiveHash.ball = lambda self: L.np.zeros(1, L.np.int32)
_masked = S._lsh_masked_scores
S._lsh_masked_scores = lambda m, p, q, c, r: _masked(m, p, q, c, 0)
'''

_MASK_IGNORED = '''
from oryx_tpu.models.als import serving as S
from oryx_tpu.ops import pallas_kernels as P
# every listed window, every row of it, for every query; and a full pass
# that masks nothing
P.lsh_listed_scores_xla = lambda m, q, e, v, tile: S.jnp.matmul(
    q, S.jnp.take(m, ((e >> q.shape[0])[:, None] * tile
                      + S.jnp.arange(tile)).reshape(-1), axis=0).T,
    preferred_element_type=S.jnp.float32)
S._lsh_masked_scores = lambda m, p, q, c, r: S._score(q, m)
'''


def test_answers_from_the_own_bucket_only_fail_miss_share():
    rc, line, err = rehearse(CELL, seed=40, prelude=_OWN_BUCKET_ONLY)
    assert rc == 0, err[-2000:]
    assert line["correct"] is False
    row = line["compared"]["miss_share"]
    assert row["value"] > row["limit"]
    assert line["compared"]["non_candidates_in_answers"]["value"] == 0


def test_a_mask_ignored_fails_non_candidates_in_answers():
    rc, line, err = rehearse(CELL, seed=40, prelude=_MASK_IGNORED)
    assert rc == 0, err[-2000:]
    assert line["correct"] is False
    row = line["compared"]["non_candidates_in_answers"]
    assert row["value"] > row["limit"] == 0
    assert "compared non_candidates_in_answers " in err and "FAILED" in err


def _small(n_items=20000, k=250, n_q=40, seed=40):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n_q, k), dtype=np.float32),
            rng.standard_normal((n_items, k), dtype=np.float32),
            np.linalg.qr(rng.standard_normal((k, 13)))[0].T.astype(np.float32))


def test_the_reference_ranks_candidates_alone_and_its_int8_control_fails():
    cfg = mf.load_json(mf.find("configs", "als-5m-250f-lsh", ".json"))
    ref = mf.load_module("references", cfg["reference"])
    drv = mf.load_module("drivers", cfg["driver"])
    qs, items, hp = _small()
    exact = qs.astype(np.float64) @ items.astype(np.float64).T
    w = 1 << np.arange(12, -1, -1)
    qc = ((qs.astype(np.float64) @ hp.T) > 0) @ w
    rc = ((items.astype(np.float64) @ hp.T) > 0) @ w
    pop = np.vectorize(lambda x: bin(int(x)).count("1"))
    near = pop(qc[:, None] ^ rc[None, :]) <= 5
    assert 0.2 < near.mean() < 0.4
    vals, idx = ref.top_n(qs, items, 10, hp, 5, block_rows=4096)
    for s in range(len(qs)):
        want = np.argsort(-np.where(near[s], exact[s], -np.inf))[:10]
        assert set(idx[s].tolist()) == set(want.tolist()), s
    assert ref.non_candidates(qs, [i.tolist() for i in idx], items, hp, 5) == 0
    # the unmasked best of a query lies outside its ball as often as not
    best = exact.argmax(axis=1)
    outside = ref.non_candidates(qs, [[b] for b in best], items, hp, 5)
    assert outside == int((~near[np.arange(len(qs)), best]).sum()) > 0

    def verdict(control):
        v, i = ref.top_n(qs, items, 10, hp, 5, block_rows=4096,
                         control=control)
        sample = [list(zip(i[s].tolist(), v[s].tolist()))
                  for s in range(len(qs))]
        checks = Checks(cfg["limits"])
        drv.base.compare(sample, qs, items, 10, checks,
                         drv._Hashing(ref, hp, 5, None), False)
        return checks

    sound, control = verdict(False), verdict(True)
    assert sound.correct, sound.as_dict()
    assert not control.correct
    assert "score_err" in {n for n, v, lim in control.rows if v > lim}


def test_a_projection_at_zero_is_ambiguous_and_counted_either_way():
    ref = mf.load_module("references", "als_topn_lsh")
    hp = np.zeros((3, 8), np.float32)
    hp[0, 0] = hp[1, 1] = 1.0
    hp[2, 2] = hp[2, 3] = np.float32(0.5 ** 0.5)
    rows = np.zeros((3, 8), np.float32)
    rows[:, :4] = ((1.0, -1.0, 1.0, 0.0),
                   (1.0, -1.0, 1.0, -1.0),          # on the third plane
                   (1.0, -1.0, 1.0, -0.99999976))   # inside its float32 bound
    codes, amb = ref.sign_codes(rows, hp)
    assert codes.tolist() == [0b101, 0b100, 0b101]
    assert amb.tolist() == [0, 0b001, 0b001]
    d_min, d_max = ref.distance_bounds(codes[0], amb[0], codes[1:], amb[1:])
    assert d_min.tolist() == [0, 0] and d_max.tolist() == [1, 1]
    # a row one sure bit away is a non-candidate at radius 0, not at 1
    far = rows[:1] * np.array([[1, -1, 1, 1, 1, 1, 1, 1]], np.float32)
    items = np.concatenate([rows, far])
    q = rows[:1]
    assert ref.non_candidates(q, [[1, 2]], items, hp, 0) == 0
    assert ref.non_candidates(q, [[3]], items, hp, 0) == 1
    assert ref.non_candidates(q, [[3]], items, hp, 1) == 0


def test_cost_of_a_listed_and_a_full_flush():
    cost = mf.load_module("costs", "topn_lsh")
    flops, nbytes = cost.flops_bytes(1, 12_800, 250, True)
    assert flops == 2.0 * 12_800 * 250
    assert nbytes == 12_800 * 500 + 1_000 + 100 * 8 + 128
    _, full = cost.flops_bytes(1, 12_800, 250, False)
    assert full == 12_800 * 504 + 1_000 + 128
    # a B = 1 listing of 29% of 5.53M positions: ~0.8 ms at 819 GB/s
    t, bound = least_seconds(*cost.flops_bytes(1, 1_606_400, 250, True), KIND)
    assert bound == "memory" and 0.9e-3 < t < 1.0e-3


def _obs(spans, scans, counters=None):
    return {"spans": spans, "sizes": {"features": 250, "items": 5_000_000},
            "device_kind": KIND, "window_s": 30.0, "bench_dir": ROOT +
            "/benchmarks", "trace": {"window_s": 30.0},
            "counters": counters or {},
            "_flush_device_phase": {"flushes": [
                {"call": c, "scan": s} for c, s in scans]}}


def _flush(call, b, rows, path):
    return [
        {"name": "coalescer.device_call",
         "attributes": {"call": call, "batch.size": b, "batch.padded": b}},
        {"name": "topn.lsh_select",
         "attributes": {"call": call, "rows": rows, "path": path}}]


def test_the_readers_join_each_flush_to_what_it_listed():
    reader = mf.load_module("readers", "lsh_scan")
    cost = mf.load_module("costs", "topn_lsh")
    spans = (_flush("a", 1, 1_600_000, "listed")
             + _flush("b", 8, 5_529_600, "full")
             + [{"name": "coalescer.device_call",
                 "attributes": {"call": "c", "batch.size": 1}}])
    obs = _obs(spans, [("a", 1.2e-3), ("b", 4.0e-3), ("c", 3.0e-3)])
    least = (least_seconds(*cost.flops_bytes(1, 1_600_000, 250, True), KIND)[0]
             + least_seconds(*cost.flops_bytes(8, 5_529_600, 250, False),
                             KIND)[0])
    roof = reader.read(obs, {"what": "roofline", "cost": "topn_lsh"})
    assert roof == pytest.approx(100.0 * least / 5.2e-3)
    mfu = reader.read(obs, {"what": "mfu", "cost": "topn_lsh"})
    flops = 2.0 * 250 * (1_600_000 + 8 * 5_529_600)
    assert mfu == pytest.approx(100.0 * flops / 30.0 / 197e12)
    # a program without the span: no reading, no error
    bare = _obs([s for s in spans if s["name"] != "topn.lsh_select"],
                [("a", 1e-3)])
    assert reader.read(bare, {"what": "roofline", "cost": "topn_lsh"}) is None
    assert reader.read(bare, {"what": "mfu", "cost": "topn_lsh"}) is None
    share = mf.load_module("readers", "counter_share")
    params = {"counter": "oryx_serving_lsh_scanned_rows_total", "of": "items"}
    obs["counters"] = {params["counter"]: 3 * 1_500_000}
    assert share.read(obs, params) == pytest.approx(30.0)
    obs["counters"] = {params["counter"]: 0.0}
    assert share.read(obs, params) is None
