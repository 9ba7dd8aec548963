"""The four-chip serving cell (ISSUE 26) in its ``cpu`` rehearsal, and the
mesh readers and cost on hand-made observations and a synthetic set of
device planes."""

import os
import sys

import pytest

# beside the store's tests, not in tests/benchmarks: the six files collected
# first start together on six workers, and this file's four-device rehearsals
# would start beside test_perfbench_serving's first one, whose check of the
# stderr tail fails when load stretches the snapshot build past the
# sanitizer's 250 ms (it takes 200-270 ms on an idle machine)
BENCH_TESTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmarks")
sys.path.insert(0, BENCH_TESTS)
from perfbench_util import LINE_KEYS, ROOT, rehearse  # noqa: E402

sys.path.insert(0, ROOT)
from benchmarks.harness import manifest as mf  # noqa: E402
from benchmarks.harness.peaks import peaks_for  # noqa: E402

MANIFEST = mf.load_manifest()
CELL = "serve-20m-250f.open"
PROGRAM = "sharded_top_k_dot_batch"
KIND = "TPU v5 lite"
FOUR = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}


def test_the_cell_is_the_published_row_on_four_chips_with_nothing_cut():
    c = mf.Cell(MANIFEST, CELL)
    five = mf.load_json(mf.find("configs", "als-5m-250f", ".json"))
    assert c.chips == 4 and c.entry["traffic"] == "open-mesh"
    assert (c.config["features"], c.config["items"], c.config["users"]) == (
        250, 20_000_000, 1_000_000)
    assert c.config["reduced"] == c.config_entry["reduced"] == []
    assert c.config["serving"] == dict(five["serving"], sharded=True)
    for key in ("limits", "guarantees", "device-dtype", "how-many",
                "implicit", "sample-rate", "reference", "precision"):
        assert c.config[key] == five[key], key
    assert "20M items" in c.config["source"] and "21M users+items" in \
        c.config["source"]
    assert len(c.config_entry["source"]) <= 200
    # Y in float32 is more than one chip holds; a quarter of it and its
    # bfloat16 copy fill over a quarter of a chip
    hbm = peaks_for(KIND)["hbm_bytes"]
    n, k = c.config["items"], c.config["features"]
    assert n * k * 4 > hbm
    assert 0.25 < (n // 4) * k * 6 / hbm < 0.6
    mix, open_ = c.traffic, mf.load_json(mf.find("traffic", "open", ".json"))
    for key in ("loop", "endpoint", "processes", "user_zipf_s", "timeout_s",
                "sample_requests", "schedule_seed"):
        assert mix[key] == open_[key], key
    assert mix["rate_per_s"] % 10 == 0
    assert mix["rate_per_s"] <= 0.25 * mix["knee_req_per_s"] < \
        mix["rate_per_s"] + 10
    assert {"recommend_p95_ms", "setup_s"} == {m["name"] for m in c.end_to_end}
    # its own; the flush's device phase has ONE entry for every serving
    # cell (ISSUE 35), read off every device plane
    assert all(m["name"].endswith(".mesh") or len(m["workloads"]) == 4
               for m in c.per_layer)
    # the one-chip readers of the scan are not pointed at this cell
    readers = {mf.load_json(mf.find("metrics", m["name"], ".json"))["reader"]
               for m in c.per_layer}
    assert not readers & {"topn_roofline", "topn_mfu"}
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


@pytest.mark.parametrize("trace", [0, 1], ids=["timed", "traced"])
def test_rehearsal_prints_the_contract_line_from_a_split_y(trace):
    rc, line, err = rehearse(CELL, seed=2 ** 31 + 26, trace=trace,
                             extra_env=FOUR)
    assert rc == 0, err[-2000:]
    assert set(line) == LINE_KEYS | ({"breakdown"} if trace else set())
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0, (line, err[-1500:])
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    for name, row in line["compared"].items():
        assert row["value"] <= row["limit"], name
    assert '"compiles_in_window": 0' in err
    # the driver's placement line: four shards of a quarter of the rows each
    assert '"info": "placement", "shards": 4' in err
    assert '"rows": 6004, "shards": 4, "devices": [0, 1, 2, 3], ' \
        '"rows_a_shard": [1501], "fully_replicated": false' in err
    c = mf.Cell(MANIFEST, CELL)
    if trace:
        host_side = {m["name"] for m in c.per_layer
                     if m["source"] != "device_trace"}
        assert set(line["metrics"]) == host_side
        assert "shard_upload_s.mesh" in host_side
    else:
        assert set(line["metrics"]) == {m["name"] for m in c.end_to_end}


def test_on_one_device_the_cell_serves_unsharded():
    rc, line, err = rehearse(CELL, seed=9, extra_env={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    assert rc == 0, err[-2000:]
    assert line["correct"] is True
    assert '"info": "placement", "shards": 1' in err


def test_the_mesh_cost_is_one_devices_share_by_hand():
    cost = mf.load_module("costs", "topn_mesh")
    whole = mf.load_module("costs", "topn")
    flops, bytes_ = cost.flops_bytes(256, 20_000_000, 250, 4)
    assert flops == 2.0 * 256 * 5_000_000 * 250
    assert bytes_ == 5_000_000 * 250 * 2 + 256 * 250 * 4 + 256 * 16 * 8
    # a shard of a quarter of the rows costs what the one-chip scan of them does
    assert (flops, bytes_) == whole.flops_bytes(256, 5_000_000, 250)
    # rows that do not divide: every device is charged the padded block
    assert cost.flops_bytes(1, 1003, 10, 4)[0] == 2.0 * 251 * 10


def _flush_spans(batches):
    return [{"name": "coalescer.device_call", "trace_id": f"t{i}",
             "start_wall": 0.0, "duration": 0.004, "links": [],
             "attributes": {"batch.size": b, "batch.padded": b, "call": i}}
            for i, b in enumerate(batches)]


def _obs(batches, times, window_s=1.0, shards=4):
    return {
        "spans": _flush_spans(batches),
        "sizes": {"items": 20_000_000, "features": 250, "shards": shards},
        "device_kind": KIND, "bench_dir": os.path.join(ROOT, "benchmarks"),
        "window_s": window_s,
        "trace": {"window_s": window_s, "window": (0.0, window_s),
                  "devices": shards,
                  "program_times_s": {"jit__" + PROGRAM: times}},
    }


def test_roofline_of_a_perfect_four_device_trace_reads_a_hundred_at_most():
    spec = mf.load_json(mf.find("metrics", "topn_roofline.mesh", ".json"))
    reader = mf.load_module("readers", spec["reader"])
    assert spec["params"]["program"] == PROGRAM
    peak = peaks_for(KIND)
    batches = [1, 4, 64, 256]
    cost = mf.load_module("costs", "topn_mesh")

    def least(b):  # the shard's bytes bound it until the batch passes ~240
        flops, bytes_ = cost.flops_bytes(b, 20_000_000, 250, 4)
        return max(flops / peak["bf16_flops_per_s"],
                   bytes_ / peak["hbm_bytes_per_s"])

    perfect = [least(b) for b in batches for _ in range(4)]
    got = reader.read(_obs(batches, perfect), spec["params"])
    assert got == pytest.approx(100.0) and got <= 100.0 + 1e-9
    # twice the time: half the share
    assert reader.read(_obs(batches, [2 * t for t in perfect]),
                       spec["params"]) == pytest.approx(50.0)
    # the one-chip reader pointed here would have charged all of Y to each
    # device's event and read four times too high
    one_chip = mf.load_module("readers", "topn_roofline")
    assert one_chip.read(_obs(batches, perfect), {
        "program": PROGRAM, "cost": "topn"}) > 350.0
    # nothing to read: another program, one device, no trace
    other = _obs(batches, perfect)
    other["trace"]["program_times_s"] = {"jit__top_k_dot_batch": perfect}
    assert reader.read(other, spec["params"]) is None
    assert reader.read(_obs(batches, perfect, shards=1), spec["params"]) is None
    assert reader.read({"spans": []}, spec["params"]) is None


def test_mesh_mfu_is_against_four_chips_peak():
    spec = mf.load_json(mf.find("metrics", "topn_mfu.mesh", ".json"))
    reader = mf.load_module("readers", spec["reader"])
    peak = peaks_for(KIND)["bf16_flops_per_s"]
    got = reader.read(_obs([256] * 100, [0.003] * 400), spec["params"])
    assert got == pytest.approx(
        100.0 * 100 * 2.0 * 256 * 20_000_000 * 250 / 1.0 / (4 * peak))
    assert 0 < got <= 100
    assert reader.read(_obs([256], [0.003], shards=1), spec["params"]) is None


def _planes():
    """Four device planes, three flushes; device 2 is the slowest in the
    second flush; every call holds two gathers; one call on device 3 starts
    before the window and is left out with its whole flush column."""
    devices = {}
    for d in range(4):
        mods, ops = [], []
        for f, start in enumerate([0.010, 0.020, 0.030]):
            dur = 0.003 + (0.0015 if (d, f) == (2, 1) else 0.0) + 0.0001 * d
            mods.append((f"jit__{PROGRAM}({7 + d})", start, start + dur, None))
            ops.append((f"fusion.{f} f32[256,2560]", start, start + dur - 0.0006,
                        None))
            g = 0.00005 * (1 + d) + (0.0002 if (d, f) == (1, 2) else 0.0)
            ops.append(("all-gather.8 f32[256,64]", start + dur - 0.0006,
                        start + dur - 0.0006 + g, None))
            ops.append(("all-gather.9 s32[256,64]", start + dur - 0.0003,
                        start + dur - 0.0003 + g, None))
        mods.append(("jit__something_else(3)", 0.040, 0.041, None))
        devices[f"/device:TPU:{d}"] = {"ops": ops, "modules": mods}
    return {"devices": devices, "host": []}


def test_merge_time_and_skew_over_a_synthetic_four_plane_set():
    planes = mf.load_module("readers", "mesh_planes")
    per_dev = planes.by_device(_planes(), (0.0, 1.0), PROGRAM)
    assert [len(d) for d in per_dev] == [3, 3, 3, 3]
    # collective seconds inside a call: both gathers of that call
    assert per_dev[0][0][2] == pytest.approx(2 * 0.00005)
    assert per_dev[1][2][2] == pytest.approx(2 * (0.0001 + 0.0002))
    # a window that cuts device 0's first call leaves it out
    cut = planes.by_device(_planes(), (0.0105, 1.0), PROGRAM)
    assert [len(d) for d in cut] == [2, 2, 2, 2]

    obs = {"trace": {"window": (0.0, 1.0)}, "trace_dir": "unused",
           "bench_dir": os.path.join(ROOT, "benchmarks"),
           "_mesh_planes": {PROGRAM: [[d[i] for d in per_dev]
                                      for i in range(3)]}}
    merge = mf.load_json(mf.find("metrics", "topn_merge_ms.mesh", ".json"))
    skew = mf.load_json(mf.find("metrics", "shard_skew.mesh", ".json"))
    merge_reader = mf.load_module("readers", merge["reader"])
    skew_reader = mf.load_module("readers", skew["reader"])
    # slowest device a flush: device 3 (0.4 ms) twice, device 1 (0.6) once
    assert merge_reader.read(obs, dict(merge["params"], q=0)) == \
        pytest.approx(0.4)
    assert merge_reader.read(obs, dict(merge["params"], q=100)) == \
        pytest.approx(0.6)
    # flush 1: device 2 takes 4.7 ms against a mean of 3.525
    assert skew_reader.read(obs, dict(skew["params"], q=100)) == \
        pytest.approx(0.0047 / ((0.003 * 4 + 0.0006 + 0.0015) / 4))
    assert skew_reader.read(obs, dict(skew["params"], q=0)) == \
        pytest.approx(0.0033 / 0.00315)
    assert merge["params"]["program"] == skew["params"]["program"] == PROGRAM


def test_mesh_readers_read_nothing_without_a_mesh_trace():
    for name in ("topn_merge_ms.mesh", "shard_skew.mesh"):
        spec = mf.load_json(mf.find("metrics", name, ".json"))
        reader = mf.load_module("readers", spec["reader"])
        bench = os.path.join(ROOT, "benchmarks")
        assert reader.read({"bench_dir": bench}, spec["params"]) is None
        assert reader.read({"bench_dir": bench, "trace": {"window": (0, 1)},
                            "trace_dir": "/nonexistent"},
                           spec["params"]) is None


def test_one_recorded_chip_is_no_mesh():
    """The recorded one-chip v5e trace has one device plane: the mesh
    readers pair nothing and read nothing, and do not raise."""
    from benchmarks.harness import trace

    path = os.path.join(BENCH_TESTS, "data", "recorded_v5e.xplane.pb")
    planes_mod = mf.load_module("readers", "mesh_planes")
    planes = trace.read_planes(path)
    reduced = trace.reduce_planes(planes)
    names = {trace.program_name(n) for d in planes["devices"].values()
             for n, *_ in d["modules"]}
    assert len(planes["devices"]) == 1 and names
    per_dev = planes_mod.by_device(planes, reduced["window"], sorted(names)[0])
    assert len(per_dev) == 1
    assert all(c == 0.0 for _, _, c in per_dev[0])  # one chip: no collective
    assert planes_mod.COLLECTIVE.match("all-gather.8 f32[256,64]")
    assert planes_mod.COLLECTIVE.match("all_gather.6")
    assert planes_mod.COLLECTIVE.match("all-reduce-start.1 f32[8]")
    assert not planes_mod.COLLECTIVE.match("fusion.1 f32[4,2560]")
