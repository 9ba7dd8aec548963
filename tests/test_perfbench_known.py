"""The cell of the default ``/recommend`` (ISSUE 30) in its ``cpu`` rehearsal,
its generator, reference, cost and reader on hand-made inputs, and the
faults the cell has to catch."""

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
from benchmarks.harness import known_items  # noqa: E402
from benchmarks.harness import manifest as mf  # noqa: E402
from benchmarks.harness.checks import Checks  # noqa: E402
from benchmarks.harness.peaks import least_seconds, peaks_for  # noqa: E402

MANIFEST = mf.load_manifest()
CELL = "serve-5m-250f-known.open"
KIND = "TPU v5 lite"


def test_the_cell_is_the_published_row_with_known_items_and_nothing_cut():
    c = mf.Cell(MANIFEST, CELL)
    plain = mf.load_json(mf.find("configs", "als-5m-250f", ".json"))
    # `open` at a quarter of this cell's own knee (ISSUE 30's rule)
    assert c.chips == 1 and c.entry["traffic"] == "open-known"
    mix, open_ = c.traffic, mf.load_json(mf.find("traffic", "open", ".json"))
    for key in ("loop", "endpoint", "processes", "user_zipf_s", "timeout_s",
                "sample_requests", "schedule_seed", "warm_requests", "lead_s"):
        assert mix[key] == open_[key], key
    assert mix["rate_per_s"] % 10 == 0
    assert mix["rate_per_s"] <= 0.25 * mix["knee_req_per_s"] < \
        mix["rate_per_s"] + 10
    assert c.config["reduced"] == c.config_entry["reduced"] == []
    assert len(c.config_entry["source"]) <= 200
    for key in ("features", "items", "users", "implicit", "sample-rate",
                "how-many", "device-dtype", "serving", "precision"):
        assert c.config[key] == plain[key], key
    assert c.config["known-items"]["mean"] == 20
    assert "known items" in c.config["assumed"]
    assert "does not already have" in c.config["guarantees"]
    limits = dict(c.config["limits"])
    assert limits.pop("known_in_answers") == 0
    assert limits == plain["limits"]
    # resident: Y in float32 and its bfloat16 copy, between the floor and 60%
    n, k = c.config["items"], c.config["features"]
    assert 0.25 < n * k * 6 / peaks_for(KIND)["hbm_bytes"] < 0.6
    assert {"recommend_p95_ms", "setup_s"} == {m["name"] for m in c.end_to_end}
    # its own, beside the flush's device phase that every serving cell
    # reports under ONE name (ISSUE 35)
    shared = {m["name"] for m in c.per_layer if len(m["workloads"]) > 1}
    assert shared == {"flush_launch_ms", "flush_behind_ms", "flush_scan_ms",
                      "flush_result_ms", "chip_gap_ms", "idle_pre_launch",
                      "idle_post_scan", "anticipated_share"}
    names = {m["name"] for m in c.per_layer} - shared
    assert names and all(name.endswith(".known") for name in names)
    assert {"exclude_ms.known", "excluded_per_flush.known",
            "topn_roofline.known", "topn_mfu.known", "device_idle.known",
            "host_stage_idle.known"} <= names
    for m in c.per_layer:
        assert m["moves"] == "recommend_p95_ms"
        assert m["workloads"] == [CELL] or m["name"] in shared
    # the accepted one-chip cell reports none of them, and keeps its own
    other = mf.Cell(MANIFEST, "serve-5m-250f.open")
    assert not names & {m["name"] for m in other.per_layer}
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


@pytest.mark.parametrize("trace", [0, 1], ids=["timed", "traced"])
def test_rehearsal_prints_the_contract_line_with_every_known_metric(trace):
    rc, line, err = rehearse(CELL, seed=2 ** 31 + 30, trace=trace)
    assert rc == 0, err[-2000:]
    assert set(line) == LINE_KEYS | ({"breakdown"} if trace else set())
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0, (line, err[-1500:])
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert set(line["compared"]) == {
        "unanswered", "compiles_in_window", "known_in_answers", "score_err",
        "miss_share", "malformed_answers"}
    for name, row in line["compared"].items():
        assert row["value"] <= row["limit"], name
    assert '"compiles_in_window": 0' in err
    # every answer of the window was counted, and the probes after it
    # (24 sampled and the slowest, which may be one of them)
    assert '"info": "known_items"' in err
    assert '"probes": 25' in err or '"probes": 24' in err
    c = mf.Cell(MANIFEST, CELL)
    if trace:
        host_side = {m["name"] for m in c.per_layer
                     if m["source"] != "device_trace"}
        assert set(line["metrics"]) == host_side
        assert {"exclude_ms.known", "excluded_per_flush.known"} <= host_side
        # about the mean of twenty rows a query, a query or two a flush
        assert 10 < line["metrics"]["excluded_per_flush.known"]["value"] < 80
    else:
        assert set(line["metrics"]) == {m["name"] for m in c.end_to_end}


_MASK_LEFT_OUT = '''
from oryx_tpu.models.als import serving as S
# the endpoint forgets what its users already have
S.ALSServingModel.known_item_codes = lambda self, user: None
'''

_ADDS_FORGOTTEN = '''
from oryx_tpu.models.als import known as K
# what an UP message adds after the bulk load never reaches a flush
K.KnownItems.add = lambda self, user, items: None
'''


@pytest.mark.parametrize("fault", [_MASK_LEFT_OUT, _ADDS_FORGOTTEN],
                         ids=["mask_left_out", "point_adds_forgotten"])
def test_an_exclusion_left_out_fails_known_in_answers(fault):
    rc, line, err = rehearse(CELL, seed=30, prelude=fault)
    assert rc == 0, err[-2000:]
    assert line["correct"] is False
    row = line["compared"]["known_in_answers"]
    assert row["value"] >= 20 > row["limit"] == 0  # a probe each, or nearly
    assert "compared known_in_answers " in err and "FAILED" in err


def test_a_program_that_loads_known_items_a_user_at_a_time_is_refused_early():
    parent_like = '''
from oryx_tpu.models.als import serving as S
del S.ALSServingModel.bulk_load_known_items
'''
    rc, line, err = rehearse(CELL, seed=30, prelude=parent_like)
    assert rc != 0 and line is None
    assert "refused before any allocation" in err
    assert "factors_host" not in err


def test_known_items_come_from_the_seed_a_block_at_a_time():
    offsets, items = known_items.make(7, 200_000, 5_000_000, 20.0, workers=3)
    again = known_items.make(7, 200_000, 5_000_000, 20.0, workers=1)
    assert np.array_equal(offsets, again[0]) and np.array_equal(items, again[1])
    other = known_items.make(8, 200_000, 5_000_000, 20.0)
    assert not np.array_equal(offsets, other[0])
    counts = np.diff(offsets)
    assert offsets[0] == 0 and offsets[-1] == len(items)
    assert items.dtype == np.int32 and 0 <= items.min() and items.max() < 5_000_000
    # Poisson(20): mean and variance both twenty; items uniform
    assert abs(counts.mean() - 20) < 0.1 and abs(counts.var() - 20) < 0.5
    assert abs(items.mean() / 5_000_000 - 0.5) < 0.01
    assert np.array_equal(known_items.of_user(offsets, items, 5),
                          items[offsets[5]:offsets[6]])
    # a block alone is the table's block
    c, i = known_items.block(7, 1, known_items.BLOCK_USERS, 5_000_000, 20.0)
    lo = known_items.BLOCK_USERS
    assert np.array_equal(c, counts[lo:2 * lo])
    assert np.array_equal(i, items[offsets[lo]:offsets[2 * lo]])


def _small(n_items=20000, k=250, n_q=40, seed=30):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n_q, k), dtype=np.float32),
            rng.standard_normal((n_items, k), dtype=np.float32))


def test_the_reference_leaves_known_rows_out_and_its_int8_control_fails():
    cfg = mf.load_json(mf.find("configs", "als-5m-250f-known", ".json"))
    ref = mf.load_module("references", cfg["reference"])
    drv = mf.load_module("drivers", cfg["driver"])
    qs, items = _small()
    exact = qs.astype(np.float64) @ items.astype(np.float64).T
    order = np.argsort(-exact, axis=1)
    # each query knows its own best, third and fifth items, and row 0
    known = [np.array([order[s, 0], order[s, 2], order[s, 4], 0], dtype=np.int32)
             for s in range(len(qs))]
    known[1] = np.empty(0, dtype=np.int32)

    def want(s):
        return [j for j in order[s] if j not in set(known[s].tolist())][:10]

    vals, idx = ref.top_n(qs, items, 10, known, block_rows=4096)
    assert idx.tolist() == [want(s) for s in range(len(qs))]
    assert np.all(np.diff(vals, axis=1) <= 0)
    np.testing.assert_allclose(
        vals, np.take_along_axis(exact, idx, 1), rtol=1e-5, atol=1e-5)

    def verdict(control):
        v, i = ref.top_n(qs, items, 10, known, block_rows=4096, control=control)
        sample = [list(zip(i[s].tolist(), v[s].tolist())) for s in range(len(qs))]
        checks = Checks(cfg["limits"])
        drv.base.compare(sample, qs, items, 10, checks,
                         drv._Knowing(ref, known), False)
        return checks

    sound, control = verdict(False), verdict(True)
    assert sound.correct, sound.as_dict()
    assert not control.correct
    assert "score_err" in {n for n, v, lim in control.rows if v > lim}


def test_the_cost_of_a_call_by_hand_and_its_roofline_at_most_a_hundred():
    cost = mf.load_module("costs", "topn_known")
    plain = mf.load_module("costs", "topn")
    flops, bytes_ = cost.flops_bytes(4, 5_000_000, 250)
    assert flops == 2.0 * 4 * 5_000_000 * 250
    assert bytes_ == 5_000_000 * 250 * 2 + 4 * 250 * 4 + 4 * 20 * 4 + 4 * 16 * 8
    # the scan's, and the indices of what is left out: no score matrix, and
    # the answer's width whatever width was fetched
    assert bytes_ - plain.flops_bytes(4, 5_000_000, 250)[1] == 4 * 20 * 4
    spec = mf.load_json(mf.find("metrics", "topn_roofline.known", ".json"))
    assert spec["params"] == dict(spec["params"], program="top_k_dot_batch",
                                  cost="topn_known")
    reader = mf.load_module("readers", spec["reader"])
    batches = [1, 2, 4, 64]
    perfect = [least_seconds(*cost.flops_bytes(b, 5_000_000, 250), KIND)[0]
               for b in batches]
    obs = {
        "spans": [{"name": "coalescer.device_call", "attributes": {
            "batch.size": b, "batch.padded": b}} for b in batches],
        "sizes": {"items": 5_000_000, "features": 250}, "device_kind": KIND,
        "bench_dir": os.path.join(ROOT, "benchmarks"),
        "trace": {"window_s": 1.0, "program_times_s": {
            "jit__top_k_dot_batch": perfect}},
    }
    assert reader.read(obs, spec["params"]) == pytest.approx(100.0)
    # a program that takes a fifth longer (it fetched wider lists) reads lower
    obs["trace"]["program_times_s"] = {
        "jit__top_k_dot_batch": [1.2 * t for t in perfect]}
    assert reader.read(obs, spec["params"]) == pytest.approx(100.0 / 1.2)


def test_entries_a_flush_from_the_counter_and_nothing_without_one():
    spec = mf.load_json(mf.find("metrics", "excluded_per_flush.known", ".json"))
    reader = mf.load_module("readers", spec["reader"])
    flushes = [{"name": "coalescer.device_call"}] * 4 + [{"name": "topn.ids"}]
    counter = spec["params"]["counter"]
    assert counter == "oryx_serving_excluded_entries_total"
    assert reader.read({"counters": {counter: 100.0}, "spans": flushes},
                       spec["params"]) == 25.0
    # a program without the counter reads 0 at both ends of the window
    assert reader.read({"counters": {counter: 0.0}, "spans": flushes},
                       spec["params"]) is None
    assert reader.read({"counters": {}, "spans": flushes}, spec["params"]) is None
    assert reader.read({"counters": {counter: 9.0}, "spans": []},
                       spec["params"]) is None
