"""The cell of the 20M-item row served from ONE chip out of int8 rows (ISSUE
33) in its ``cpu`` rehearsal; its configuration held to ``als-20m-250f``'s
sizes key by key; its reference, cost and metric files on hand-made inputs;
and the faults the cell has to catch."""

import os
import sys

import numpy as np
import pytest

# beside the model's tests, not in tests/benchmarks (tests/test_perfbench_mesh.py
# says why): its rehearsals would start beside test_perfbench_serving's first
BENCH_TESTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmarks")
sys.path.insert(0, BENCH_TESTS)
from perfbench_util import LINE_KEYS, ROOT, rehearse  # noqa: E402

sys.path.insert(0, ROOT)
from benchmarks.harness import manifest as mf  # noqa: E402
from benchmarks.harness.peaks import least_seconds, peaks_for  # noqa: E402

MANIFEST = mf.load_manifest()
CELL = "serve-20m-250f-int8.open"
KIND = "TPU v5 lite"
BENCH = os.path.join(ROOT, "benchmarks")
METRICS = {
    "topn_roofline.int8": "int8 scan", "topn_mfu.int8": "int8 scan",
    "rescore_ms.int8": "arena rescore",
    "rescored_per_flush.int8": "arena rescore",
    "device_idle.int8": "device", "host_stage_idle.int8": "device",
    "queue_wait_ms.int8": "coalescer", "host_path_ms.int8": "HTTP ingress",
    "flush_upload_ms.int8": "top-N program",
    "flush_dispatch_ms.int8": "top-N program",
    "flush_wait_download_ms.int8": "top-N program",
    "quantize_s.int8": "int8 build",
}
#: ISSUE 35: a flush's device phase, ONE entry for the serving cells that run
#: it (the reader takes the programs' names from the spans), and the gate's
#: two, which only this cell's flushes engage
SHARED = {
    "flush_launch_ms": "top-N program", "flush_scan_ms": "top-N program",
    "flush_result_ms": "top-N program", "flush_behind_ms": "coalescer",
    "chip_gap_ms": "coalescer", "anticipated_share": "coalescer",
    "idle_pre_launch": "device", "idle_post_scan": "device",
}
GATE = {"gate_aim_err_ms": "coalescer", "gate_late_ms": "coalescer"}


def test_the_cell_is_the_published_row_on_one_chip_with_nothing_cut():
    c = mf.Cell(MANIFEST, CELL)
    mesh = mf.load_json(mf.find("configs", "als-20m-250f", ".json"))
    assert c.chips == 1 and c.entry["traffic"] == "open-int8"
    for key in ("features", "items", "users", "implicit", "sample-rate",
                "how-many"):
        assert c.config[key] == mesh[key], key
    assert (c.config["items"], c.config["users"], c.config["features"]) == \
        (20_000_000, 1_000_000, 250)
    assert c.config["serving"] == dict(mesh["serving"], sharded=False)
    assert c.config["device-dtype"] == "int8"
    assert c.config["rescore-factor"] == 4
    assert c.config["index"] == {"enabled": False}
    assert c.config["reduced"] == c.config_entry["reduced"] == []
    assert c.config_entry["source"] == (
        "Oryx 2 docs/docs/performance.html, ALS benchmark: /recommend row "
        "'250 features x 20M items, without LSH'; memory row '250 features, "
        "21M users+items'; one chip: int8 rows, exact float32 rescore")
    assert len(c.config_entry["source"]) <= 200
    for key in ("rescore factor", "quantizer", "factor entries", "users"):
        assert key in c.config["assumed"], key
    assert "float32 dot product" in c.config["guarantees"]
    assert set(c.config["limits"]) == set(mesh["limits"])
    assert c.config["limits"]["miss_share"] <= mesh["limits"]["miss_share"]
    assert c.config["limits"]["score_err"] < mesh["limits"]["score_err"]
    assert "PLACEHOLDER" not in c.config["limits_from"]
    # resident: int8 rows, a float32 scale and a float32 norm a row — over
    # the quarter of the chip a new cell has to fill, and no more than half
    n, k = c.config["items"], c.config["features"]
    assert 0.25 < n * (k + 8) / peaks_for(KIND)["hbm_bytes"] < 0.5
    # the traffic is open-mesh's in everything but the rate: a quarter of
    # this cell's own knee, rounded down to a multiple of ten
    mix = c.traffic
    open_mesh = mf.load_json(mf.find("traffic", "open-mesh", ".json"))
    for key in ("loop", "endpoint", "processes", "user_zipf_s", "timeout_s",
                "sample_requests", "schedule_seed", "warm_requests", "lead_s"):
        assert mix[key] == open_mesh[key], key
    assert mix["rate_per_s"] % 10 == 0
    assert mix["rate_per_s"] <= 0.25 * mix["knee_req_per_s"] < \
        mix["rate_per_s"] + 10
    assert "PLACEHOLDER" not in mix["why"] + c.entry["why"]
    assert len(c.entry["why"]) <= 200


def test_the_cell_reports_its_own_layers_and_leaves_the_others_theirs():
    c = mf.Cell(MANIFEST, CELL)
    assert {"recommend_p95_ms", "setup_s"} == {m["name"] for m in c.end_to_end}
    assert {m["name"]: m["layer"] for m in c.per_layer} == {
        **METRICS, **SHARED, **GATE}
    for m in c.per_layer:
        if m["name"] in SHARED:
            assert m["workloads"][-1] == CELL and len(m["workloads"]) == 4
        else:
            assert m["workloads"] == [CELL]
        assert m["moves"] == ("setup_s" if m["name"] == "quantize_s.int8"
                              else "recommend_p95_ms")
    for other in ("serve-5m-250f.open", "serve-20m-250f.open",
                  "serve-5m-250f-known.open", "train-nf100m-50f.iterate"):
        assert not (set(METRICS) | set(GATE)) & {
            m["name"] for m in mf.Cell(MANIFEST, other).per_layer}
    p95 = [m for m in MANIFEST["end_to_end"] if m["name"] == "recommend_p95_ms"]
    assert p95[0]["workloads"][-1] == CELL and p95[0]["bound"] == 0.07
    assert len(MANIFEST["workloads"]) == 6 and len(MANIFEST["configs"]) == 6
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


@pytest.mark.parametrize("trace", [0, 1], ids=["timed", "traced"])
def test_rehearsal_prints_the_contract_line_with_every_int8_metric(trace):
    rc, line, err = rehearse(CELL, seed=2 ** 31 + 33, trace=trace)
    assert rc == 0, err[-2000:]
    assert set(line) == LINE_KEYS | ({"breakdown"} if trace else set())
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0, (line, err[-1500:])
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert set(line["compared"]) == {
        "unanswered", "compiles_in_window", "score_err", "miss_share",
        "malformed_answers"}
    for name, row in line["compared"].items():
        assert row["value"] <= row["limit"], name
    assert line["compared"]["score_err"]["value"] < 1e-5
    assert '"compiles_in_window": 0' in err
    # set-up by phase, the model an int8 snapshot 64 candidates wide, and the
    # truth made again after the model was freed
    for phase in ('"bulk_load"', '"quantize"', '"warm_ladder"',
                  '"factors_again"'):
        assert phase in err, phase
    assert '"snapshot": "_QuantSnapshot"' in err
    assert '"rescore_width": 64' in err and '"dtype": "int8"' in err
    c = mf.Cell(MANIFEST, CELL)
    if trace:
        host_side = {m["name"] for m in c.per_layer
                     if m["source"] != "device_trace"}
        # the gate's timer opens no flush of a model this small: its
        # lateness has nothing to read, and the metric is left out
        assert host_side - {"gate_late_ms"} <= set(line["metrics"]) <= host_side
        assert {"rescore_ms.int8", "rescored_per_flush.int8",
                "quantize_s.int8", "anticipated_share"} <= host_side
        # 64 rows a query, a query or two a flush
        assert 64 <= line["metrics"]["rescored_per_flush.int8"]["value"] < 200
        assert 0 < line["metrics"]["rescore_ms.int8"]["value"] < 50
    else:
        assert set(line["metrics"]) == {m["name"] for m in c.end_to_end}


_RESCORE_LEFT_OUT = '''
from oryx_tpu.models.als import serving as S
# the flush hands out the int8 scan's own scores
S._QuantSnapshot.rescore = None
'''

_RESCORE_IN_BFLOAT16 = '''
import numpy as np
from oryx_tpu.models.als import topn as T

def _bf16(a):
    bits = np.ascontiguousarray(a, dtype=np.float32).view(np.uint32)
    return (bits & np.uint32(0xFFFF0000)).view(np.float32)

_gather = T._ArenaSnapshot.gather_rows
# the rescore reads its rows one precision below
T._ArenaSnapshot.gather_rows = lambda self, pos: _bf16(_gather(self, pos))
'''


@pytest.mark.parametrize("fault", [_RESCORE_LEFT_OUT, _RESCORE_IN_BFLOAT16],
                         ids=["rescore_left_out", "rescore_in_bfloat16"])
def test_a_rescore_that_is_not_float32_fails_score_err(fault):
    rc, line, err = rehearse(CELL, seed=33, prelude=fault)
    assert rc == 0, err[-2000:]
    assert line["correct"] is False
    row = line["compared"]["score_err"]
    assert row["value"] > 4 * row["limit"]
    assert "compared score_err " in err and "FAILED" in err


def test_a_program_that_copies_the_handoff_is_refused_before_any_allocation():
    parent_like = '''
from oryx_tpu.models.als import serving as S
_load = S.ALSServingModel.bulk_load_items
S.ALSServingModel.bulk_load_items = lambda self, ids, matrix: _load(
    self, ids, matrix)
'''
    rc, line, err = rehearse(CELL, seed=33, prelude=parent_like)
    assert rc != 0 and line is None
    assert "refused before any allocation" in err
    assert "factors_host" not in err


def test_a_model_that_does_not_resolve_to_int8_is_refused_early():
    under_a_mesh = '''
from oryx_tpu.models.als import serving as S
_init = S.ALSServingModel.__init__
def _bf16(self, *a, device_dtype="auto", **kw):
    _init(self, *a, device_dtype="bfloat16", **kw)
S.ALSServingModel.__init__ = _bf16
'''
    rc, line, err = rehearse(CELL, seed=33, prelude=under_a_mesh)
    assert rc != 0 and line is None
    assert "not int8: refused before any allocation" in err


def _small(n_items=20000, k=250, n_q=40, seed=33):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n_q, k), dtype=np.float32),
            rng.standard_normal((n_items, k), dtype=np.float32))


def test_the_reference_is_a_brute_force_and_a_plain_two_stage_scan():
    ref = mf.load_module("references", "als_topn_int8")
    qs, items = _small()
    exact = qs.astype(np.float64) @ items.astype(np.float64).T
    order = np.argsort(-exact, axis=1)
    vals, idx = ref.top_n(qs, items, 10, block_rows=4096)
    assert idx.tolist() == order[:, :10].tolist()
    assert np.all(np.diff(vals, axis=1) <= 0)
    np.testing.assert_allclose(vals, np.take_along_axis(exact, idx, 1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ref.exact_scores(qs, items, idx),
                               np.take_along_axis(exact, idx, 1), rtol=1e-12)
    # two stages by hand: quantize by the stated rule, keep 64 by the
    # quantized scores, rescore those in float32, keep ten
    scale = np.abs(items).max(axis=1) / 127.0
    q = np.clip(np.rint(items / scale[:, None]), -127, 127)
    rough = (qs.astype(np.float64) @ q.T) * scale[None, :]
    cand = np.argsort(-rough, axis=1)[:, :64]
    fine = np.take_along_axis(exact, cand, 1)
    want = np.take_along_axis(cand, np.argsort(-fine, axis=1)[:, :10], 1)
    v2, i2 = ref.two_stage(qs, items, 10, 64, block_rows=4096)
    assert i2.tolist() == want.tolist() == order[:, :10].tolist()
    np.testing.assert_allclose(v2, np.take_along_axis(exact, i2, 1),
                               rtol=1e-5, atol=1e-5)
    # one precision below: values off by a bfloat16 rounding of each factor,
    # and the rows at 4 bits choose worse candidates
    v3, i3 = ref.two_stage(qs, items, 10, 64, control=True, block_rows=4096)
    err = np.abs(v3 - np.take_along_axis(exact, i3, 1)).max() / vals[:, 0].max()
    assert 2e-4 < err < 2e-2
    assert ref._to_bfloat16(np.float32([1.0, 1.00390625, 3.14159274])).tolist() \
        == [1.0, 1.0, 3.140625]
    # fewer items than the width: every item is a candidate
    v4, i4 = ref.two_stage(qs[:2], items[:40], 10, 64)
    assert i4.tolist() == np.argsort(-exact[:2, :40], axis=1)[:, :10].tolist()


def test_the_cost_of_an_int8_call_by_hand_and_its_roofline_at_most_a_hundred():
    cost = mf.load_module("costs", "topn_int8")
    n, k = 20_000_000, 250
    flops, bytes_ = cost.flops_bytes(4, n, k)
    assert flops == 2.0 * 4 * n * k
    # int8 rows, a float32 scale a row, the queries, 64 candidates a query:
    # no score matrix and no converted copy of the rows
    assert bytes_ == n * 250 + n * 4 + 4 * 250 * 4 + 4 * 64 * 8
    assert 5.07e9 < bytes_ < 5.09e9
    least, bound = least_seconds(flops, bytes_, KIND)
    assert bound == "memory" and 6.1e-3 < least < 6.3e-3
    assert least_seconds(*cost.flops_bytes(256, n, k), KIND)[1] == "compute"
    spec = mf.load_json(mf.find("metrics", "topn_roofline.int8", ".json"))
    assert spec["params"] == dict(spec["params"], program="quant_candidates",
                                  cost="topn_int8")
    reader = mf.load_module("readers", spec["reader"])
    batches = [1, 2, 4, 64]
    perfect = [least_seconds(*cost.flops_bytes(b, n, k), KIND)[0]
               for b in batches]
    obs = {
        "spans": [{"name": "coalescer.device_call", "attributes": {
            "batch.size": b, "batch.padded": b}} for b in batches],
        "sizes": {"items": n, "features": k}, "device_kind": KIND,
        "bench_dir": BENCH,
        "trace": {"window_s": 1.0, "program_times_s": {
            "jit__quant_candidates": perfect}},
    }
    assert reader.read(obs, spec["params"]) == pytest.approx(100.0)
    # a program that writes the (b, n) scores or a bfloat16 copy of the rows
    # takes longer for the same call and reads lower
    obs["trace"]["program_times_s"] = {
        "jit__quant_candidates": [3.0 * t for t in perfect]}
    assert reader.read(obs, spec["params"]) == pytest.approx(100.0 / 3.0)
    # the bfloat16 scan's program is not this metric's
    obs["trace"]["program_times_s"] = {"jit__top_k_dot_batch": perfect}
    assert reader.read(obs, spec["params"]) is None
    mfu = mf.load_json(mf.find("metrics", "topn_mfu.int8", ".json"))
    share = mf.load_module("readers", mfu["reader"]).read(
        dict(obs, window_s=1.0), mfu["params"])
    assert share == pytest.approx(
        100.0 * 2.0 * sum(batches) * n * k / peaks_for(KIND)["bf16_flops_per_s"])


def test_rescored_rows_a_flush_from_the_counter_and_nothing_without_one():
    spec = mf.load_json(mf.find("metrics", "rescored_per_flush.int8", ".json"))
    reader = mf.load_module("readers", spec["reader"])
    flushes = [{"name": "coalescer.device_call"}] * 4 + [{"name": "topn.ids"}]
    counter = spec["params"]["counter"]
    assert counter == "oryx_serving_rescored_rows_total"
    assert reader.read({"counters": {counter: 512.0}, "spans": flushes},
                       spec["params"]) == 128.0
    # the parent has no such counter: it reads 0 at both ends of the window
    assert reader.read({"counters": {counter: 0.0}, "spans": flushes},
                       spec["params"]) is None
    assert reader.read({"counters": {}, "spans": flushes}, spec["params"]) is None


@pytest.mark.parametrize("name,span,q,want", [
    ("rescore_ms.int8", "topn.rescore", 50, 2.0),
    ("queue_wait_ms.int8", "coalescer.queue_wait", 95, 2.9),
    ("flush_upload_ms.int8", "topn.upload", 50, 2.0),
    ("flush_dispatch_ms.int8", "topn.dispatch", 50, 2.0),
    ("flush_wait_download_ms.int8", "topn.wait_download", 50, 2.0),
])
def test_a_span_metric_reads_its_own_span_and_nothing_where_there_is_none(
        name, span, q, want):
    spec = mf.load_json(mf.find("metrics", name, ".json"))
    assert spec["reader"] == "span_percentile"
    assert spec["params"] == {"span": span, "q": q}
    reader = mf.load_module("readers", spec["reader"])
    spans = [{"name": span, "duration": d * 1e-3} for d in (1.0, 2.0, 3.0)] \
        + [{"name": "topn.ids", "duration": 9.0}]
    assert reader.read({"spans": spans}, spec["params"]) == pytest.approx(
        want, abs=0.11)
    assert reader.read({"spans": spans[-1:]}, spec["params"]) is None


def test_the_build_phase_and_the_idle_metrics_name_readers_that_exist():
    spec = mf.load_json(mf.find("metrics", "quantize_s.int8", ".json"))
    reader = mf.load_module("readers", spec["reader"])
    phases = [("bulk_load", 3.0), ("quantize", 41.5), ("warm_ladder", 9.0)]
    assert reader.read({"phases": phases}, spec["params"]) == 41.5
    assert reader.read({"phases": phases[:1]}, spec["params"]) is None
    drv = mf.load_module("drivers", "serve_als_int8")
    assert drv.QUANTIZE_PHASE == spec["params"]["phase"]
    for name, reader_name, params in (
            ("device_idle.int8", "device_idle", {}),
            ("host_stage_idle.int8", "idle_by_state", {"state": "host_stage"}),
            ("host_path_ms.int8", "host_path_ms", {})):
        spec = mf.load_json(mf.find("metrics", name, ".json"))
        assert (spec["reader"], spec["params"]) == (reader_name, params)
        known = mf.load_json(mf.find(
            "metrics", name.replace(".int8", ".known"), ".json"))
        assert (known["reader"], known["params"]) == (reader_name, params)
        # nothing to read, nothing reported
        assert mf.load_module("readers", reader_name).read(
            {"spans": [], "requests": {"index": []}}, params) is None
