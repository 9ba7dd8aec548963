"""The chip benchmark's own arithmetic, fast and steady on a CPU: manifest,
traffic, percentiles and rates, the trace reduction on a recorded v5e
trace, cost functions, the peaks table."""

import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import manifest as mf  # noqa: E402
from benchmarks.harness import peaks, stats, trace, traffic  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFEST = mf.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_manifest_has_exactly_the_contract_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in MANIFEST["end_to_end"])
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_names_units_and_files(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    spec = mf.load_json(mf.find("metrics", metric["name"], ".json"))
    assert os.path.isfile(mf.find("readers", spec["reader"], ".py"))
    if "cost" in spec.get("params", {}):
        assert os.path.isfile(mf.find("costs", spec["params"]["cost"], ".py"))
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%" and metric["source"] == "device_trace"


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files_and_reports_enough(cell):
    c = mf.Cell(MANIFEST, cell)
    assert NAME.match(cell) and NAME.match(c.entry["traffic"])
    assert os.path.isfile(mf.find("drivers", c.config["driver"], ".py"))
    assert os.path.isfile(mf.find("references", c.config["reference"], ".py"))
    assert c.config["reduced"] == c.config_entry["reduced"] == []
    assert "assumed" in c.config and "limits" in c.config
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and len(c.per_layer) >= 1
    # a per-layer metric's cells report the end-to-end metric it moves
    for m in c.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


def test_whole_step_mfu_stands_beside_every_roofline():
    for m in MANIFEST["per_layer"]:
        if "roofline" in m["name"]:
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o["workloads"])
                       for o in MANIFEST["per_layer"]), m["name"]


def test_percentile_by_hand():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile([1.0, float("inf")], 100) == float("inf")
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_latency_counts_a_stall_and_a_failure_from_the_due_time():
    # 20 requests due 10 ms apart; the server stalls 200 ms at t=0.05, so
    # five requests due during the stall are answered when it ends; one fails
    due = [0.01 * i for i in range(20)]
    done = [d + 0.002 for d in due]
    for i in range(5, 10):
        done[i] = 0.25 + 0.002
    status = [200] * 20
    status[15], done[15] = 503, due[15] + 0.001
    req = {"due": due, "done": done, "status": status}
    lat = stats.latencies_ms(req, worst_ms=30000.0)
    assert lat[5] == pytest.approx(202.0) and lat[9] == pytest.approx(162.0)
    assert lat[15] == 30000.0  # a shed request is the worst, not the fastest
    assert stats.percentile(lat, 50) == pytest.approx(2.0)
    assert stats.percentile(lat, 95) > 202.0
    # rate: only 200s whose last byte fell inside the window, over all of it
    assert stats.completed_rate(req, 0.0, 0.2) == pytest.approx(14 / 0.2)
    assert stats.completed_rate(req, 0.0, 1.0) == pytest.approx(19.0)


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert stats.union_length(iv) == pytest.approx(3.0)
    assert stats.gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert stats.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_traffic_same_work_for_every_seed_in_another_order():
    mix = mf.load_json(mf.find("traffic", "open", ".json"))
    a = traffic.open_schedule(mix, 10.0, 1)
    b = traffic.open_schedule(mix, 10.0, 2 ** 31 + 5)
    assert len(a) == len(b) == int(round(mix["rate_per_s"] * 10.0))
    assert (np.diff(a) > 0).all() and a[-1] < 10.0
    assert not np.allclose(a, b)
    # the same gaps in another order (diff drops two of the n+1, so ranks
    # may shift by two places)
    np.testing.assert_allclose(np.sort(np.diff(a)), np.sort(np.diff(b)),
                               rtol=0.02, atol=5e-5)
    ua = traffic.users_for(mix, 5000, 1000, 1)
    ub = traffic.users_for(mix, 5000, 1000, 2)
    assert ua.min() >= 0 and ua.max() < 1000 and (ua != ub).any()
    # Zipf(1): the same multiset of popularity ranks, on other users
    ca, cb = np.sort(np.bincount(ua, minlength=1000)), np.sort(np.bincount(ub, minlength=1000))
    assert (ca == cb).all() and ca[-1] > 20 * np.median(ca[ca > 0])
    assert (traffic.users_for(mix, 5000, 1000, 1) == ua).all()


def test_open_loop_generator_times_from_due_and_reports_lateness(tmp_path):
    """A server that stalls: requests due during the stall are still sent on
    schedule, and the record keeps due, sent and done apart."""
    server = tmp_path / "server.py"
    server.write_text(
        "import asyncio, sys, time\n"
        "from aiohttp import web\n"
        "t0 = [None]\n"
        "async def h(r):\n"
        "    if r.match_info['u'] == 'slow':\n"
        "        await asyncio.sleep(0.3)\n"
        "    return web.json_response([{'id': 'i1', 'value': 1.0}])\n"
        "app = web.Application(); app.router.add_get('/r/{u}', h)\n"
        "web.run_app(app, host='127.0.0.1', port=int(sys.argv[1]), print=None)\n")
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    srv = subprocess.Popen([sys.executable, str(server), str(port)])
    try:
        for _ in range(100):
            try:
                socket.create_connection(("127.0.0.1", port), 0.2).close()
                break
            except OSError:
                time.sleep(0.1)
        sched = [[i, 0.02 * i, "/r/slow" if i == 3 else "/r/u"]
                 for i in range(12)]
        spec = {"port": port, "loop": "open", "timeout_s": 5, "keep_every": 4,
                "keep_phase": 1, "warm_paths": ["/r/u"], "schedule": sched}
        (tmp_path / "spec.json").write_text(json.dumps(spec))
        gen = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "benchmarks", "harness",
                                          "loadgen.py"),
             str(tmp_path / "spec.json"), str(tmp_path / "out.json")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        assert gen.stdout.readline().strip() == "ready"
        t_start = time.monotonic() + 0.2
        gen.stdin.write(f"{t_start!r}\n")
        gen.stdin.flush()
        assert gen.wait(timeout=30) == 0
    finally:
        srv.kill()
        srv.wait()
    rec = json.loads((tmp_path / "out.json").read_text())
    order = np.argsort(rec["index"])
    due = np.array(rec["due"])[order] - t_start
    sent = np.array(rec["sent"])[order] - t_start
    done = np.array(rec["done"])[order] - t_start
    np.testing.assert_allclose(due, [0.02 * i for i in range(12)], atol=1e-9)
    # sent on schedule although request 3 was still unanswered
    assert (sent >= due).all() and (sent - due).max() < 0.05
    assert done[3] - due[3] >= 0.3 and done[4] < done[3]
    assert rec["status"] == [200] * 12
    assert sorted(rec["bodies"]) == ["1", "5", "9"]


def test_trace_reduction_on_the_recorded_v5e_trace():
    planes = trace.read_planes(os.path.join(DATA, "recorded_v5e.xplane.pb"))
    assert list(planes["devices"]) == ["/device:TPU:0"]
    red = trace.reduce_planes(planes)
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.037197107, rel=1e-6)
    # 6 matmul calls of ~15 us and 3 top-k calls of ~4 us: the device is
    # almost always idle, and busy time is the union of op intervals
    assert red["busy_s"] == pytest.approx(9.628e-05, rel=1e-3)
    assert 0 < red["busy_s"] < sum(red["op_time_s"].values()) * 1.001
    progs = red["program_times_s"]
    assert set(progs) == {"jit__lambda", "jit_broadcast_in_dim"}
    assert len(progs["jit__lambda"]) == 8  # the call that straddles the start is not whole
    assert "fusion bf16[1024]" in red["op_time_s"]
    gaps = red["idle_gaps"]
    assert gaps == sorted(gaps, key=lambda g: g[0] - g[1])
    assert gaps[0][1] - gaps[0][0] > 0.004  # the harness slept 4 ms a step
    bd = trace.breakdown(red, {})
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0] == "fusion bf16[1024]"
    assert all(isinstance(n, str) and s > 0 for n, s in bd["idle_gaps"])


def test_short_op_name():
    full = ("%fusion.1 = (f32[4,2560]{1,0:T(4,128)S(1)}, s32[4,2560]{1,0}) "
            "fusion(f32[]{:T(128)} %constant.0), kind=kCustom")
    assert trace.short_op_name(full) == "fusion.1 f32[4,2560]"
    assert trace.short_op_name("dot_general.1") == "dot_general.1"
    assert trace.program_name("jit__top_k_dot_batch(588234)") == "jit__top_k_dot_batch"


def test_cost_functions_by_hand():
    topn = mf.load_module("costs", "topn")
    f, b = topn.flops_bytes(256, 5_000_000, 250)
    assert f == 2 * 256 * 5_000_000 * 250 == 6.4e11
    assert b == 5_000_000 * 250 * 2 + 256 * 250 * 4 + 256 * 16 * 8
    it = mf.load_module("costs", "als_iteration")
    assert it.flops(10, 3, 2, 4) == 2 * (2 * 10 * 16 + 2 * 10 * 4) + 5 * (64 / 3 + 32)
    gg = mf.load_module("costs", "gather_gramian")
    assert gg.flops_bytes(10, 3, 4) == (2 * 10 * 16 + 2 * 10 * 4,
                                        10 * (16 + 8) + 3 * 20 * 4)
    spd = mf.load_module("costs", "spd_solve")
    assert spd.flops_bytes(3, 4) == (3 * (64 / 3 + 32), 3 * 24 * 4.0)


def test_peaks_table_and_unknown_kind_raises():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peaks_for("cpu")
    # the 5M x 250 scan is bound by the bytes of Y until b passes ~240
    # (2·b operations per 2-byte entry against 197e12 / 819e9 = 240)
    topn = mf.load_module("costs", "topn")
    t, bound = peaks.least_seconds(*topn.flops_bytes(128, 5_000_000, 250),
                                   "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(2.5e9 / 819e9, rel=1e-3)
    assert peaks.least_seconds(*topn.flops_bytes(256, 5_000_000, 250),
                               "TPU v5 lite")[1] == "compute"


def test_readers_leave_out_what_they_cannot_read():
    for m in MANIFEST["per_layer"]:
        spec = mf.load_json(mf.find("metrics", m["name"], ".json"))
        reader = mf.load_module("readers", spec["reader"])
        assert reader.read({"phases": []}, spec.get("params", {})) is None


def test_roofline_and_mfu_readers_on_hand_made_observations():
    obs = {
        "device_kind": "TPU v5 lite", "bench_dir": mf.BENCH_DIR,
        "window_s": 1.0, "sizes": {"items": 5_000_000, "features": 250},
        "spans": [{"name": "coalescer.device_call",
                   "attributes": {"batch.size": 3, "batch.padded": 4},
                   "duration": 0.005, "trace_id": "1", "links": []}] * 10,
        "trace": {"window_s": 1.0, "busy_s": 0.04,
                  "program_times_s": {"jit__top_k_dot_batch": [0.004] * 10}},
    }
    roof = mf.load_module("readers", "topn_roofline")
    least = (5_000_000 * 250 * 2 + 4 * 250 * 4 + 4 * 16 * 8) / 819e9
    assert roof.read(obs, {"program": "top_k_dot_batch", "cost": "topn"}) \
        == pytest.approx(100 * least / 0.004, rel=1e-9)
    mfu = mf.load_module("readers", "topn_mfu")
    assert mfu.read(obs, {"cost": "topn"}) == pytest.approx(
        100 * 10 * 2 * 3 * 5_000_000 * 250 / 197e12, rel=1e-9)
    idle = mf.load_module("readers", "device_idle")
    assert idle.read(obs, {}) == pytest.approx(96.0)


def test_trainer_readers_on_hand_made_observations():
    z = {"interactions": 1000, "users": 30, "items": 20, "features": 4}
    gg_name = "closed_call.7 f32[5925,50,50] [pallas]"
    obs = {
        "device_kind": "TPU v5 lite", "bench_dir": mf.BENCH_DIR, "sizes": z,
        "iterations": 2,
        "trace": {
            "window_s": 2.0, "busy_s": 1.9,
            "op_time_s": {gg_name: 1.0, "_spd_solve_call.6 f32[8216,50] [pallas]": 0.1,
                          "while.2 s32[]": 1.9},
            "host_events": [("bench.half.user", 0.0, 0.4), ("bench.half.item", 0.4, 1.0),
                            ("bench.half.user", 1.0, 1.4), ("bench.half.item", 1.4, 2.0)],
            "op_intervals": [(0.0, 0.3), (0.45, 1.0), (1.0, 1.3), (1.5, 2.0)],
        },
    }
    spec = mf.load_json(mf.find("metrics", "gather_gramian_roofline", ".json"))
    roof = mf.load_module("readers", spec["reader"])
    gg = mf.load_module("costs", "gather_gramian")
    least = sum(peaks.least_seconds(*gg.flops_bytes(1000, r, 4), "TPU v5 lite")[0]
                for r in (30, 20))
    assert roof.read(obs, spec["params"]) == pytest.approx(100 * 2 * least / 1.0)
    spd = mf.load_json(mf.find("metrics", "spd_solve_roofline", ".json"))
    assert roof.read(obs, spd["params"]) > 0
    obs_no_kernel = dict(obs, trace=dict(obs["trace"], op_time_s={"while.2 s32[]": 1.9}))
    assert roof.read(obs_no_kernel, spec["params"]) is None  # silent, never 0
    half = mf.load_module("readers", "annotated_device_ms")
    assert half.read(obs, {"annotation": "bench.half.user"}) == pytest.approx(300.0)
    assert half.read(obs, {"annotation": "bench.half.item"}) == pytest.approx(525.0)
    mfu = mf.load_module("readers", "train_mfu")
    it = mf.load_module("costs", "als_iteration")
    assert mfu.read(obs, {"cost": "als_iteration"}) == pytest.approx(
        100 * 2 * it.flops(1000, 30, 20, 4) / 2.0 / 197e12)
    rate = mf.load_module("readers", "work_rate")
    assert rate.read({"work_done": 3e8, "work_window_s": 30.0}, {}) == 1e7


def test_run_exits_nonzero_and_prints_nothing_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
