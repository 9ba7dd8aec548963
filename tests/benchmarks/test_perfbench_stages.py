"""The readers ISSUE 24 added, on hand-made observations: a request's
stretches from the starts and ends of its spans (``request_stage_ms``), and
the device's idle time by what the server was doing (``idle_by_state``) —
the four states sum to the idle total, and a clock that does not agree with
the trace gives no number.
"""

import os
import shutil
import sys

import pytest

from perfbench_util import ROOT

sys.path.insert(0, ROOT)
from benchmarks.harness import manifest as mf  # noqa: E402
from benchmarks.harness.loadgen import index_of_trace, traceparent  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "recorded_v5e.xplane.pb")
MANIFEST = mf.load_manifest()
NEW = ("client_gap_ms", "prepare_ms", "resume_ms", "render_ms", "egress_ms",
       "handoff_ms", "assemble_ms", "wakeup_ms", "topn_upload_ms",
       "topn_dispatch_ms", "topn_wait_download_ms", "topn_ids_ms",
       "idle_no_request", "idle_queued", "idle_host_stage", "idle_in_ingress")
T0 = 1_790_000_000.0  # the spans' wall clock, seconds: a float there steps 0.24 us
MS_TOL = 1e-3


def _trace_id(index):
    return traceparent(index).split("-")[1]


def _span(name, trace, start, dur, call=None, links=()):
    return {"name": name, "trace_id": _trace_id(trace), "start_wall": T0 + start,
            "duration": dur, "attributes": {} if call is None else {"call": call},
            "links": [_trace_id(i) for i in links]}


def _two_requests_one_flush():
    """Requests 0 and 1 share flush "c1" (0 is its first waiter); request 2
    was answered 503 and request 3 lost its render span. Times in ms:

    request 0: ingress 0..10, queued 1..3, call 3..8, render 8.5..9
    request 1: ingress 2..11, queued 2.5..3, same call, render 9..9.5
    """
    ms = 1e-3
    spans = [
        _span("http GET /recommend/{userID}", 0, 0 * ms, 10 * ms),
        _span("coalescer.queue_wait", 0, 1 * ms, 2 * ms),
        _span("serving.render", 0, 8.5 * ms, 0.5 * ms),
        _span("http GET /recommend/{userID}", 1, 2 * ms, 9 * ms),
        _span("coalescer.queue_wait", 1, 2.5 * ms, 0.5 * ms),
        _span("serving.render", 1, 9 * ms, 0.5 * ms),
        _span("coalescer.device_call", 0, 3 * ms, 5 * ms, "c1", links=[1]),
        _span("coalescer.handoff", 0, 3 * ms, 0.25 * ms, "c1"),
        _span("topn.wait_download", 0, 4 * ms, 3 * ms, "c1"),
        _span("coalescer.wakeup", 0, 8 * ms, 0.25 * ms, "c1"),
        _span("http GET /recommend/{userID}", 2, 0, 1 * ms),
        _span("http GET /recommend/{userID}", 3, 0, 10 * ms),
        _span("coalescer.queue_wait", 3, 1 * ms, 1 * ms),
        _span("coalescer.device_call", 3, 2 * ms, 1 * ms, "c0"),
        _span("http GET /ready", 2 ** 100, 0, 1 * ms),  # nobody's request
    ]
    requests = {"index": [0, 1, 2, 3], "sent": [100.0, 100.002, 100.0, 100.0],
                "done": [100.012, 100.0125, 100.001, 100.011],
                "status": [200, 200, 503, 200]}
    return {"spans": spans, "requests": requests,
            "index_of_trace": index_of_trace}


@pytest.mark.parametrize("name,q,want_ms", [
    # client 12 and 10.5 ms, ingress 10 and 9 (request 3: 11 − 10)
    ("client_gap_ms.open", 0, 1.0), ("client_gap_ms.open", 100, 2.0),
    ("prepare_ms.open", 0, 0.5), ("prepare_ms.open", 100, 1.0),
    # the call closes at 8: render opens at 8.5 and 9
    ("resume_ms.open", 0, 0.5), ("resume_ms.open", 100, 1.0),
    # render closes at 9 and 9.5, ingress at 10 and 11
    ("egress_ms.open", 0, 1.0), ("egress_ms.open", 100, 1.5),
])
def test_request_stage_from_span_edges(name, q, want_ms):
    spec = mf.load_json(mf.find("metrics", name, ".json"))
    assert spec["params"]["q"] == 50
    reader = mf.load_module("readers", spec["reader"])
    obs = _two_requests_one_flush()
    got = reader.read(obs, dict(spec["params"], q=q))
    assert got == pytest.approx(want_ms, abs=MS_TOL)


def test_request_stage_joins_a_flush_to_all_its_requests_by_call():
    reader = mf.load_module("readers", "request_stage_ms")
    obs = _two_requests_one_flush()
    found = reader.spans_of_requests(
        obs, {"coalescer.device_call", "coalescer.wakeup", "serving.render"})
    # request 1 is only LINKED to the call, and shares its stages
    for i in (0, 1):
        lo, hi = found[i]["coalescer.device_call"]
        assert (lo - T0, hi - T0) == pytest.approx((0.003, 0.008), abs=1e-6)
        assert found[i]["coalescer.wakeup"][0] - T0 == pytest.approx(
            0.008, abs=1e-6)
    assert "serving.render" not in found[3] and "coalescer.wakeup" not in found[3]
    # a stretch that needs a span the request lacks leaves the request out
    params = {"from": ["coalescer.wakeup", "end"],
              "to": ["serving.render", "start"], "q": 100}
    assert reader.read(obs, params) == pytest.approx(0.75, abs=MS_TOL)


def test_request_stage_reads_nothing_from_a_program_without_the_spans():
    """The parent commit's program: no ``serving.render``, no ``call``."""
    reader = mf.load_module("readers", "request_stage_ms")
    obs = _two_requests_one_flush()
    obs["spans"] = [dict(s, attributes={}) for s in obs["spans"]
                    if s["name"] not in ("serving.render", "coalescer.handoff",
                                         "coalescer.wakeup", "topn.wait_download")]
    for base in ("resume_ms", "egress_ms"):
        spec = mf.load_json(mf.find("metrics", base + ".open", ".json"))
        assert reader.read(obs, spec["params"]) is None
    spec = mf.load_json(mf.find("metrics", "client_gap_ms.open", ".json"))
    assert reader.read(obs, spec["params"]) is not None  # the ingress span is old


def _idle_obs(tmp_path, skew_s=0.0):
    """A 100 ms window; the device runs 10..20, 30..40 and 60..70 ms.

    flush c1: call 8..22 ms, wakeup 22..24  -> stage 8..24
    flush c2: call 29..41 ms, no wakeup     -> stage 29..41
    queued:   5..8, 24..29 (behind c1's wakeup), 50..52
    ingress:  4..45, 50..55
    """
    d = tmp_path / "trace" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(RECORDED, d / "host.xplane.pb")
    idle = mf.load_module("readers", "idle_by_state")
    start = idle.profile_start_s(str(tmp_path / "trace"))
    assert start == pytest.approx(1790755089.799914988)
    ms = 1e-3

    def span(name, lo, hi, call=None):
        return {"name": name, "trace_id": "1", "links": [],
                "start_wall": start + skew_s + lo * ms, "duration": (hi - lo) * ms,
                "attributes": {} if call is None else {"call": call}}

    spans = [
        span("coalescer.device_call", 8, 22, "c1"),
        span("coalescer.wakeup", 22, 24, "c1"),
        span("topn.wait_download", 10.5, 20.5, "c1"),
        span("coalescer.device_call", 29, 41, "c2"),
        span("topn.wait_download", 30.5, 40.5, "c2"),
        span("coalescer.queue_wait", 5, 8), span("coalescer.queue_wait", 24, 29),
        span("coalescer.queue_wait", 50, 52),
        span("http GET /recommend/{userID}", 4, 45),
        span("http GET /recommend/{userID}", 50, 55),
    ]
    ops = [(0.010, 0.020), (0.030, 0.040), (0.060, 0.070)]
    trace = {"window": (0.0, 0.1), "window_s": 0.1, "busy_s": 0.03,
             "op_intervals": ops,
             "host_events": [("topn.wait_download", 0.0105, 0.0205),
                             ("topn.wait_download", 0.0305, 0.0405),
                             ("np.asarray_jax.Array_", 0.0106, 0.0204)]}
    return idle, {"trace": trace, "spans": spans,
                  "trace_dir": str(tmp_path / "trace")}


def test_idle_states_sum_to_the_idle_total(tmp_path):
    idle, obs = _idle_obs(tmp_path)
    got = {s: idle.read(obs, {"state": s}) for s in idle.STATES}
    # stage: 8..10, 20..24, 29..30, 40..41 = 8 ms; queued alone: 5..8,
    # 24..29, 50..52 = 10; ingress alone: 4..5, 41..45, 52..55 = 8; the rest
    assert got == pytest.approx({"host_stage": 8.0, "queued": 10.0,
                                 "in_ingress": 8.0, "no_request": 44.0},
                                abs=1e-2)  # 0.24 us of a 100 ms window
    whole = mf.load_module("readers", "device_idle").read(obs, {})
    assert sum(got.values()) == pytest.approx(whole) == pytest.approx(70.0)


@pytest.mark.parametrize("skew_us,reads", [
    (0, True), (60, True), (-60, True), (150, False), (-400, False),
    (2000, False)])
def test_a_skewed_clock_gives_no_number(tmp_path, skew_us, reads):
    """The spans' clock off the trace's by more than 100 us at the median
    of the paired stages: nothing is reported, in any state."""
    idle, obs = _idle_obs(tmp_path, skew_s=skew_us * 1e-6)
    got = [idle.read(obs, {"state": s}) for s in idle.STATES]
    assert all((g is not None) == reads for g in got), got


@pytest.mark.parametrize("missing", ["host_events", "spans", "trace_dir",
                                     "profile_start_time"])
def test_idle_by_state_without_what_it_pairs_gives_no_number(tmp_path, missing):
    idle, obs = _idle_obs(tmp_path)
    if missing == "host_events":  # a trace without the annotation
        obs["trace"]["host_events"] = [("np.asarray_jax.Array_", 0.01, 0.02)]
    elif missing == "spans":  # the parent's program: no such stage
        obs["spans"] = [s for s in obs["spans"]
                        if s["name"] != "topn.wait_download"]
    elif missing == "trace_dir":
        obs["trace_dir"] = None
    else:
        os.unlink(os.path.join(obs["trace_dir"], "plugins", "profile", "run",
                               "host.xplane.pb"))
        with pytest.raises(FileNotFoundError):
            idle.read(obs, {"state": "queued"})
        return
    assert idle.read(obs, {"state": "queued"}) is None


def test_idle_sweep_clips_to_the_window_and_counts_overlaps_once():
    idle = mf.load_module("readers", "idle_by_state")
    got = idle.idle_seconds_by_state(
        [(-1.0, 0.5), (2.0, 2.5), (2.25, 3.0)], (0.0, 4.0),
        [(-5.0, 1.0, 0), (0.75, 1.5, 0), (1.25, 9.0, 1), (0.0, 9.0, 2)])
    # idle 0.5..2 and 3..4: stage until 1.5, queued after
    assert got == pytest.approx([1.0, 1.5, 0.0, 0.0])
    assert idle.idle_seconds_by_state([], (0.0, 2.0), []) == [0.0, 0.0, 0.0, 2.0]


@pytest.mark.parametrize("base", NEW)
def test_each_new_metric_has_both_files_and_one_manifest_entry(base):
    """``.open`` is in the manifest under the issue's layer and source;
    ``.closed`` is a file with the same reader and no entry (PR 23's way)."""
    spec = {mix: mf.load_json(mf.find("metrics", f"{base}.{mix}", ".json"))
            for mix in ("open", "closed")}
    assert spec["open"]["reader"] == spec["closed"]["reader"]
    assert spec["open"]["params"] == spec["closed"]["params"]
    mf.find("readers", spec["open"]["reader"], ".py")
    entries = [m for m in MANIFEST["per_layer"] if m["name"].startswith(base + ".")]
    assert [m["name"] for m in entries] == [base + ".open"]
    (m,) = entries
    idle = base.startswith("idle_")
    assert m["source"] == ("device_trace" if idle else "program_span")
    assert m["unit"] == ("%" if idle else "ms") and m["better"] == "lower"
    assert m["moves"] == "recommend_p95_ms"
    assert m["workloads"] == ["serve-5m-250f.open"]
    if idle:
        assert m["layer"] == "device"
