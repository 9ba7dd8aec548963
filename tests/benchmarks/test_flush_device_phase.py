"""The join of a flush to its own programs on the chip (ISSUE 35), on
hand-made planes and spans: ``flush_join`` (two flushes that overlap on one
device, a flush alone, four devices, the idle split, every no-number rule),
``flush_planes`` on the recorded v5e trace (each program finds its launch by
``run_id``), the readers ``flush_device_phase`` and ``span_attribute``, and
the ten metrics' files and entries.

In the hand-made traces the device's plane runs ``SHIFT`` behind the host's
clock, as the profiler leaves it on the chip, and the fastest launch and the
fastest hearing are both 0.1 ms: the band's middle is then the true shift,
and every number can be said by hand.
"""

import os
import sys

import pytest

from perfbench_util import ROOT

sys.path.insert(0, ROOT)
from benchmarks.harness import manifest as mf  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "recorded_v5e.xplane.pb")
MANIFEST = mf.load_manifest()
T0 = 1_790_000_000.0  # the spans' wall clock; the profile starts there
MS = 1e-3
SHIFT = 1.5 * MS
FOUR = ["serve-5m-250f.open", "serve-5m-250f-known.open",
        "serve-20m-250f.open", "serve-20m-250f-int8.open"]
INT8 = ["serve-20m-250f-int8.open"]
#: metric → (reader, params, source, layer, cells)
NEW = {
    "flush_launch_ms": ("flush_device_phase", {"what": "launch", "q": 50},
                        "device_trace", "top-N program", FOUR),
    "flush_behind_ms": ("flush_device_phase", {"what": "behind", "q": 50},
                        "device_trace", "coalescer", FOUR),
    "flush_scan_ms": ("flush_device_phase", {"what": "scan", "q": 50},
                      "device_trace", "top-N program", FOUR),
    "flush_result_ms": ("flush_device_phase", {"what": "result", "q": 50},
                        "device_trace", "top-N program", FOUR),
    "chip_gap_ms": ("flush_device_phase", {"what": "chip_gap", "q": 50},
                    "device_trace", "coalescer", FOUR),
    "idle_pre_launch": ("flush_device_phase",
                        {"what": "idle", "state": "pre_launch"},
                        "device_trace", "device", FOUR),
    "idle_post_scan": ("flush_device_phase",
                       {"what": "idle", "state": "post_scan"},
                       "device_trace", "device", FOUR),
    "anticipated_share": ("span_attribute",
                          {"span": "coalescer.device_call",
                           "attribute": "opened_by", "equals": "anticipated"},
                          "program_span", "coalescer", FOUR),
    "gate_aim_err_ms": ("flush_device_phase", {"what": "aim_err", "q": 50},
                        "device_trace", "coalescer", INT8),
    "gate_late_ms": ("span_attribute",
                     {"span": "coalescer.device_call",
                      "attribute": "gate.late_ms", "q": 95},
                     "program_span", "coalescer", INT8),
}

join_mod = mf.load_module("readers", "flush_join")
planes_mod = mf.load_module("readers", "flush_planes")
idle_mod = mf.load_module("readers", "idle_by_state")


class Trace:
    """Planes and spans of hand-made flushes; times in ms of the host's
    clock, the device planes written ``shift`` (by device) behind it."""

    def __init__(self, devices=(0,), shift=None, window=(-1.0, 20.0)):
        self.shift = shift or {d: SHIFT for d in devices}
        self.planes = {
            "devices": {d: {"modules": [], "ops": []} for d in devices},
            "launches": [], "heard": {d: [] for d in devices},
            "stages": {"topn.dispatch": [], "topn.wait_download": []},
            "window": (window[0] * MS, window[1] * MS), "profile_start_s": T0}
        self.spans = []
        self._run = 100

    def _span(self, name, start, end, **attributes):
        self.spans.append({"name": name, "start_wall": T0 + start * MS,
                           "duration": (end - start) * MS,
                           "attributes": attributes})

    def flush(self, call, open_, dispatch, wd, close, wake_end, runs=None,
              programs=("jit_scan",), opened_by="window", span_skew=0.0,
              **attributes):
        """``runs``: ``{device: [(enq, start, end, heard), ...]}``, one a
        program; None: the flush's programs are not in the trace."""
        self._span("coalescer.device_call", open_, close, call=call,
                   opened_by=opened_by, **attributes)
        self._span("coalescer.wakeup", close, wake_end, call=call)
        self._span("topn.dispatch", dispatch[0] + span_skew,
                   dispatch[1] + span_skew, call=call, programs=list(programs))
        self._span("topn.wait_download", wd[0] + span_skew, wd[1] + span_skew,
                   call=call, first_copy_ms=round(wd[1] - wd[0] - 0.45, 3))
        st = self.planes["stages"]
        st["topn.dispatch"].append((dispatch[0] * MS, dispatch[1] * MS))
        st["topn.wait_download"].append((wd[0] * MS, wd[1] * MS))
        for d, rows in (runs or {}).items():
            shift = self.shift[d]
            for program, (enq, start, end, heard) in zip(programs, rows):
                self._run += 1
                self.planes["launches"].append(
                    (enq * MS, (enq + 0.02) * MS, self._run, d))
                dev = self.planes["devices"][d]
                dev["modules"].append((start * MS - shift, end * MS - shift,
                                       self._run, program))
                dev["ops"].append((start * MS - shift, end * MS - shift))
                self.planes["heard"][d].append(heard * MS)
        return self

    def done(self):
        for dev in self.planes["devices"].values():
            dev["modules"].sort()
            dev["ops"].sort()
        self.planes["launches"].sort()
        for rows in self.planes["stages"].values():
            rows.sort()
        for rows in self.planes["heard"].values():
            rows.sort()
        return self

    def join(self):
        return join_mod.join(self.done().planes, self.spans)


def two_overlapping(opened_by_b="completion"):
    """A alone on the device; B launched at 3.0 while A scans to 4.1: it
    starts 2 µs after A ends."""
    return (Trace()
            .flush("A", 0.0, (0.5, 0.9), (0.95, 5.0), 5.2, 5.3,
                   {0: [(1.0, 1.1, 4.1, 4.2)]})
            .flush("B", 2.0, (2.5, 2.9), (2.95, 8.0), 8.2, 8.3,
                   {0: [(3.0, 4.102, 7.102, 7.202)]}, opened_by=opened_by_b))


def _by_call(joined):
    assert joined["why"] is None, joined["why"]
    return {r["call"]: r for r in joined["flushes"]}


def _identity_gap(r):
    return (r["wd_end"] - r["enq"]) - (r["behind"] + r["launch"] + r["scan"]
                                       + r["own_gaps"] + r["result"])


def test_a_flush_behind_another_waits_out_its_scan_and_the_identity_is_exact():
    joined = two_overlapping().join()
    assert joined["window_calls"] == joined["joined"] == 2
    assert joined["bands"][0] == pytest.approx((SHIFT - 0.1 * MS,
                                                SHIFT + 0.1 * MS), abs=1e-9)
    got = _by_call(joined)
    a, b = got["A"], got["B"]
    assert a["behind"] == 0.0 and a["prev_end"] is None
    assert a["launch"] == pytest.approx(0.1 * MS, abs=1e-9)
    assert a["result"] == pytest.approx(0.9 * MS, abs=1e-9)
    assert a["chip_gap"] is None  # nothing ran before it
    assert b["behind"] == pytest.approx(1.1 * MS, abs=1e-9)
    assert b["launch"] == pytest.approx(0.002 * MS, abs=1e-9)
    assert b["scan"] == pytest.approx(3.0 * MS, abs=1e-9)
    assert b["result"] == pytest.approx(0.898 * MS, abs=1e-9)
    assert b["chip_gap"] == pytest.approx(0.002 * MS, abs=1e-9)
    for r in (a, b):
        assert r["own_gaps"] == pytest.approx(0.0, abs=1e-12)
        assert abs(_identity_gap(r)) < 1e-12
        assert r["heard"] == pytest.approx(r["end"] + 0.1 * MS, abs=1e-9)
        assert r["first_copy_ms"] == pytest.approx(r["wd_ms"] - 0.45)


def test_a_flush_alone_waits_behind_nothing():
    joined = (Trace()
              .flush("A", 0.0, (0.5, 0.9), (0.95, 5.0), 5.2, 5.3,
                     {0: [(1.0, 1.1, 4.1, 4.2)]})
              .flush("B", 6.0, (6.5, 6.9), (6.95, 11.0), 11.2, 11.3,
                     {0: [(7.0, 7.15, 10.15, 10.3)]}, opened_by="completion")
              ).join()
    b = _by_call(joined)["B"]
    assert b["behind"] == 0.0
    assert b["launch"] == pytest.approx(0.15 * MS, abs=1e-9)
    assert b["chip_gap"] == pytest.approx(3.05 * MS, abs=1e-9)  # 4.1 → 7.15
    assert abs(_identity_gap(b)) < 1e-12


def test_a_flush_of_two_programs_reports_the_gap_between_them_with_its_scan():
    joined = Trace().flush(
        "A", 0.0, (0.5, 0.9), (0.95, 6.0), 6.2, 6.3,
        {0: [(0.7, 0.8, 1.8, 1.9), (0.85, 2.0, 5.0, 5.1)]},
        programs=("jit_probe", "jit_scan")).join()
    a = _by_call(joined)["A"]
    assert a["enq"] == pytest.approx(0.85 * MS)  # its LAST program's launch
    assert a["scan"] == pytest.approx(4.0 * MS, abs=1e-9)
    assert a["own_gaps"] == pytest.approx(0.2 * MS, abs=1e-9)
    assert a["launch"] == pytest.approx(-0.05 * MS, abs=1e-9)
    assert abs(_identity_gap(a)) < 1e-12


def test_on_four_devices_the_one_whose_program_ends_last_decides():
    shift = {0: 1.5 * MS, 1: 1.1 * MS, 2: 1.9 * MS, 3: 0.4 * MS}
    ends = {0: 4.0, 1: 4.1, 2: 4.6, 3: 4.2}
    runs = {d: [(1.0 + 0.01 * d, 1.1 + 0.01 * d, ends[d], ends[d] + 0.1)]
            for d in shift}
    joined = Trace(devices=tuple(shift), shift=shift).flush(
        "A", 0.0, (0.5, 0.9), (0.95, 5.5), 5.7, 5.8, runs).join()
    for d, s in shift.items():  # each device's clock placed by itself
        assert joined["shift"][d] == pytest.approx(s, abs=1e-9)
    a = _by_call(joined)["A"]
    assert a["device"] == 2
    assert a["end"] == pytest.approx(4.6 * MS, abs=1e-9)
    assert a["scan"] == pytest.approx((4.6 - 1.12) * MS, abs=1e-9)
    assert a["result"] == pytest.approx(0.9 * MS, abs=1e-9)
    assert abs(_identity_gap(a)) < 1e-12


def test_the_idle_split_sums_to_the_host_stage_idle_exactly():
    t = two_overlapping()
    joined = t.join()
    window = (0.0, 9.0 * MS)
    split = join_mod.idle_split(t.planes, joined, window,
                                idle_mod.idle_seconds_by_state)
    pre, post = split[0]
    # idle inside a stage: 0..1.1, 4.1..4.102, 7.102..8.3; B's first program
    # had not started in the first two (A's in the first)
    # (a span's wall clock steps 0.24 µs)
    assert pre == pytest.approx(1.102 * MS, abs=5e-7)
    assert post == pytest.approx(1.198 * MS, abs=5e-7)
    # what ``idle_by_state`` gives ``host_stage`` over the same ops
    ops = [(s + SHIFT, e + SHIFT) for s, e in t.planes["devices"][0]["ops"]]
    host_stage = idle_mod.idle_seconds_by_state(
        ops, window, idle_mod._state_intervals(t.spans, T0))[0]
    assert pre + post == pytest.approx(host_stage, abs=1e-12)
    # and with the device's ops left where the trace wrote them, SHIFT early,
    # the two sum to what ``idle_by_state`` itself reads of that trace
    unmoved = join_mod.idle_split(t.planes, joined, window,
                                  idle_mod.idle_seconds_by_state, moved=False)
    as_written = idle_mod.idle_seconds_by_state(
        t.planes["devices"][0]["ops"], window,
        idle_mod._state_intervals(t.spans, T0))[0]
    assert sum(unmoved[0]) == pytest.approx(as_written, abs=1e-12)
    assert sum(unmoved[0]) != pytest.approx(host_stage, abs=1e-5)


def test_the_aim_is_held_to_where_the_flush_before_truly_ended():
    t = two_overlapping("anticipated")
    # B opened at 2.0 believing the chip free 2.4 later, at 4.4: A ended 4.1
    t.spans[4]["attributes"]["gate.free_in_ms"] = 2.4
    assert t.spans[4]["name"] == "coalescer.device_call"
    b = _by_call(t.join())["B"]
    assert b["aim_err"] == pytest.approx(0.3 * MS, abs=5e-7)


@pytest.mark.parametrize("skew_ms, joins", [(0.05, True), (2.0, False)])
def test_spans_off_the_traces_clock_give_no_number(skew_ms, joins):
    joined = (Trace()
              .flush("A", 0.0, (0.5, 0.9), (0.95, 5.0), 5.2, 5.3,
                     {0: [(1.0, 1.1, 4.1, 4.2)]}, span_skew=skew_ms)).join()
    assert (joined["why"] is None) == joins
    if not joins:
        assert "one clock" in joined["why"] and not joined["flushes"]


def test_fewer_than_99_in_100_joined_give_no_number():
    t = Trace(window=(-1.0, 1000.0))
    for n in range(100):
        at = 9.0 * n
        t.flush(f"c{n}", at, (at + 0.5, at + 0.9), (at + 0.95, at + 5.0),
                at + 5.2, at + 5.3,
                {0: [(at + 1.0, at + 1.1, at + 4.1, at + 4.2)]})
    assert t.join()["why"] is None  # all of them, and one lost in 100 still
    t.flush("lost", 900.0, (900.5, 900.9), (900.95, 905.0), 905.2, 905.3)
    joined = t.join()
    assert joined["why"] is None
    assert (joined["joined"], joined["window_calls"]) == (100, 101)
    t.flush("lost2", 910.0, (910.5, 910.9), (910.95, 915.0), 915.2, 915.3)
    joined = t.join()
    assert "100 of 102" in joined["why"] and not joined["flushes"]


def test_a_program_before_its_launch_at_every_shift_gives_no_number():
    """The device's clock may be placed anywhere between the launches and
    the hearings; a program that starts 0.3 ms before its launch wherever
    its end is heard in time leaves no such place."""
    joined = (Trace()
              .flush("A", 0.0, (0.5, 0.9), (0.95, 5.0), 5.2, 5.3,
                     {0: [(1.0, 1.1, 4.1, 4.2)]})
              .flush("B", 6.0, (6.5, 6.9), (6.95, 11.0), 11.2, 11.3,
                     {0: [(7.0, 6.7, 9.7, 9.8)]})).join()
    lo, hi = joined["bands"][0]
    assert lo > hi
    assert "does not place its clock" in joined["why"] and not joined["flushes"]


def test_a_clock_the_trace_cannot_place_within_04_ms_gives_no_number():
    joined = (Trace()
              .flush("A", 0.0, (0.5, 0.9), (0.95, 5.5), 5.7, 5.8,
                     {0: [(1.0, 1.3, 4.3, 4.6)]})).join()
    lo, hi = joined["bands"][0]
    assert hi - lo == pytest.approx(0.6 * MS, abs=1e-9)
    assert "does not place its clock" in joined["why"]


def test_a_launch_out_of_the_dispatches_order_does_not_pass_for_a_join():
    """The join by order rests on one FIFO queue a device. Two dispatches
    that overlap, B's program launched first: by order B gets the second
    run, which ends after B has its result on the host — B does not join
    (A, with the first run, cannot be told apart from a true join), and two
    flushes of which one joins are no record set."""
    t = (Trace()
         .flush("A", 0.0, (0.5, 0.9), (0.95, 8.0), 8.2, 8.3,
                {0: [(1.05, 4.102, 7.102, 7.202)]})
         .flush("B", 0.1, (0.6, 1.0), (1.05, 5.0), 5.2, 5.3,
                {0: [(0.95, 1.1, 4.1, 4.2)]}))
    joined = t.join()
    assert joined["joined"] == 1 and "1 of 2" in joined["why"]
    assert not joined["flushes"]


def test_every_program_of_the_recorded_trace_finds_its_launch_by_run_id():
    planes = planes_mod.read(RECORDED)
    (modules,) = [d["modules"] for d in planes["devices"].values()]
    assert len(modules) == 12
    launches = {run: (s, e) for s, e, run, _ in planes["launches"]}
    assert len(launches) == 12 and None not in launches
    assert {run for _, _, run, _ in modules} == set(launches)
    programs = [p for _, _, _, p in modules]
    assert programs.count("jit__lambda") == 9
    assert programs.count("jit_broadcast_in_dim") == 3
    # as recorded every program starts ≈ 1.2 ms BEFORE its launch: the
    # device's plane is not on the host's clock, and the trace says by what
    for start, _, run, _ in modules:
        assert 1.1e-3 < launches[run][0] - start < 1.4e-3
    assert len(planes["heard"][0]) == 12
    lo, hi = join_mod.clock_band(modules, planes["launches"],
                                 planes["heard"][0])
    assert 1.2e-3 < lo < hi < 1.7e-3 and hi - lo < join_mod.MAX_BAND_S
    assert planes["window"] is not None
    assert planes["profile_start_s"] == pytest.approx(1790755089.799915)


# -- the readers ---------------------------------------------------------------


def _reader_on(monkeypatch, trace):
    """``flush_device_phase`` over a hand-made trace in place of a file."""
    reader = mf.load_module("readers", "flush_device_phase")
    real = reader.load_module

    class _Planes:
        @staticmethod
        def read(path):
            return trace.done().planes

    monkeypatch.setattr(reader, "load_module", lambda kind, name, *a: (
        _Planes if name == "flush_planes" else real(kind, name, *a)))
    monkeypatch.setattr(reader.trace_mod, "_find_xplane", lambda d: "unread")
    window = trace.planes["window"]
    obs = {"trace": {"window": window, "window_s": window[1] - window[0]},
           "spans": trace.spans, "trace_dir": "unread",
           "bench_dir": os.path.join(ROOT, "benchmarks")}
    return reader, obs


def test_the_reader_gives_each_metric_its_number(monkeypatch, capsys):
    t = two_overlapping("anticipated")
    t.planes["window"] = (0.0, 9.0 * MS)
    t.spans[4]["attributes"]["gate.free_in_ms"] = 2.4
    reader, obs = _reader_on(monkeypatch, t)
    want = {"launch": 0.051, "behind": 0.55, "scan": 3.0, "result": 0.899}
    for what, value in want.items():  # the median of two is their middle
        assert reader.read(obs, {"what": what, "q": 50}) == pytest.approx(
            value, abs=1e-6), what
    assert reader.read(obs, {"what": "chip_gap", "q": 50}) == pytest.approx(
        0.002, abs=1e-6)  # B alone was opened with requests held
    assert reader.read(obs, {"what": "aim_err", "q": 50}) == pytest.approx(
        0.3, abs=5e-4)
    pre = reader.read(obs, {"what": "idle", "state": "pre_launch"})
    post = reader.read(obs, {"what": "idle", "state": "post_scan"})
    assert pre == pytest.approx(100 * 1.102 / 9.0, abs=1e-2)
    assert post == pytest.approx(100 * 1.198 / 9.0, abs=1e-2)
    # one reading of the trace, one line of what it found
    lines = [ln for ln in capsys.readouterr().err.splitlines()
             if '"flush_device_phase"' in ln]
    assert len(lines) == 1
    import json

    said = json.loads(lines[0])
    assert said["joined"] == said["window_flushes"] == 2
    assert said["identity_max_err_us"] < 1e-3
    assert said["behind_share_over_0.1ms"] == 0.5
    assert said["idle_pct_by_device"]["0"]["host_stage"] == pytest.approx(
        100 * 2.3 / 9.0, abs=1e-3)
    # half the band (0.1 ms) a flush, two flushes in 9 ms
    assert said["idle_split_good_to_pct"] == pytest.approx(
        100 * 2 * 0.1 / 9.0, abs=1e-2)


def test_the_reader_leaves_the_metrics_out_where_there_is_nothing_to_join(
        monkeypatch, capsys):
    reader, obs = _reader_on(monkeypatch, two_overlapping())
    for s in obs["spans"]:  # the parent: a dispatch that names no program
        s["attributes"].pop("programs", None)
    assert reader.read(obs, {"what": "scan", "q": 50}) is None
    assert reader.read({"spans": obs["spans"]}, {"what": "scan", "q": 50}) is None
    # a trace whose clocks cannot be placed: said once, then no number
    t = Trace().flush("A", 0.0, (0.5, 0.9), (0.95, 5.5), 5.7, 5.8,
                      {0: [(1.0, 1.3, 4.3, 4.6)]})
    reader, obs = _reader_on(monkeypatch, t)
    assert reader.read(obs, {"what": "scan", "q": 50}) is None
    assert reader.read(obs, {"what": "idle", "state": "post_scan"}) is None
    assert capsys.readouterr().err.count("does not place its clock") == 1


def test_span_attribute_reads_a_share_and_a_percentile():
    reader = mf.load_module("readers", "span_attribute")
    spans = [{"name": "coalescer.device_call",
              "attributes": {"opened_by": by, **extra}}
             for by, extra in (("anticipated", {"gate.late_ms": 0.2}),
                               ("anticipated", {"gate.late_ms": 1.0}),
                               ("window", {}), ("completion", {}))]
    spans.append({"name": "topn.dispatch", "attributes": {"opened_by": "x"}})
    share = NEW["anticipated_share"][1]
    assert reader.read({"spans": spans}, share) == 50.0
    late = NEW["gate_late_ms"][1]
    assert reader.read({"spans": spans}, late) == pytest.approx(0.96)
    # no flush the gate's timer opened, or a program that does not say
    assert reader.read({"spans": spans[2:]}, late) is None
    assert reader.read({"spans": []}, share) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_has_its_file_its_reader_and_one_entry(name):
    reader, params, source, layer, cells = NEW[name]
    spec = mf.load_json(mf.find("metrics", name, ".json"))
    assert spec == {"name": name, "reader": reader, "params": params}
    assert hasattr(mf.load_module("readers", reader), "read")
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry["workloads"] == cells
    assert (entry["source"], entry["layer"]) == (source, layer)
    assert entry["moves"] == "recommend_p95_ms"
    assert entry["unit"] == ("%" if name.startswith(("idle_", "anticipated"))
                             else "ms")
    # no copy a cell: the reader takes the programs' names from the spans
    assert not [m for m in MANIFEST["per_layer"]
                if m["name"].startswith(name + ".")]
