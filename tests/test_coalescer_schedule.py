"""The coalescer's schedule at each serving cell's times (ISSUE 34): the real
``TopNCoalescer`` on a virtual clock against a FIFO fake device
(tests/coalescer_sim.py), one case a cell of ``BENCHMARK.json`` at the times
the chip showed for it (``PERF.md`` §5, §6 PR 34): the scan S by padded
batch, the host stage h as the coalescer reads it (opened → enqueued: the
handoff, the assembly, the upload, the dispatch; it swings by 0.4 ms either
way), the post-scan host work p, the offered rate — and what the issue's
model of the schedule left out: a program takes 0.56 ms to reach an idle
device and to be noticed done, the two results come back in 0.89 ms before
the host reports the device done, and the loop's timers fire up to a
millisecond late. The same model run WITHOUT its device-phase reports is
scheduled by the slots alone, as every call was before the gate: the
comparison is between the two rules, on the same arrivals.

What the chip showed, and this guards: the gate takes a scan's wait off the
one-chip int8 cell (a scan of 7 ms behind a host stage of 1.4); in the bf16
cells and on the mesh a scan of 3–4 ms behind a host stage and a lag that
add up to as much leaves nothing worth an aim, and the coalescer — from the
three times it reads off its own flushes — keeps them on the slots' schedule.
"""

import random
import statistics

import pytest

from oryx_tpu.common import spans
from oryx_tpu.serving import batcher
from oryx_tpu.serving.batcher import TopNCoalescer
from tests.coalescer_sim import (FifoDevice, SimModel, VirtualLoop, p50_p95_ms,
                                 poisson)

MS = 1e-3
SECONDS = 10.0
LAUNCH_MS, COPIES_MS, TICK_S = 0.56, 0.89, MS

#: cell → (req/s, scan ms by padded batch, h ms, p ms)
CELLS = {
    "serve-20m-250f-int8.open": (
        370, lambda b: 8.9 if b == 1 else 16.3 if b == 256 else 7.2, 1.4, 0.6),
    "serve-5m-250f.open": (400, lambda b: 3.9 if b == 1 else 3.05, 1.3, 0.35),
    "serve-5m-250f-known.open": (
        370, lambda b: 3.9 if b == 1 else 3.05, 1.45, 0.45),
    "serve-20m-250f.open": (220, lambda b: 3.56, 1.9, 0.35),
}
INT8 = "serve-20m-250f-int8.open"


def _opened() -> dict:
    return {by: n for (by,), n in batcher._FLUSH_OPENED.samples()}


def _run(rate, scan_ms, host_s, post_ms, reports, seed=34, launch_ms=0.0,
         lag_s=0.0, tick_s=0.0):
    loop = VirtualLoop(tick_s)
    device = FifoDevice(loop, lambda b: scan_ms(b) * MS, launch_ms * MS)
    model = SimModel(loop, device, host_s, post_ms * MS, reports, lag_s)
    coal = TopNCoalescer(window_ms=1.0, max_batch=256, max_inflight=2)
    before = _opened()
    latencies = loop.run(poisson(coal, model, rate, SECONDS, seed))
    assert coal._inflight == 0 and not coal._pending
    device.opened = {by: n - before.get(by, 0) for by, n in _opened().items()
                     if n != before.get(by, 0)}
    return p50_p95_ms(latencies), device, model


def _as_on_the_chip(cell, reports):
    rate, scan_ms, h_ms, p_ms = CELLS[cell]
    rng = random.Random(34)
    return _run(rate, scan_ms,
                lambda: (h_ms + rng.uniform(-0.4, 0.4)) * MS, p_ms, reports,
                launch_ms=LAUNCH_MS, tick_s=TICK_S,
                lag_s=lambda: (COPIES_MS + rng.uniform(-0.1, 0.1)) * MS)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_gate_engages_where_the_chip_showed_a_wait_to_take(cell):
    (_, slots_p95), slots_device, _ = _as_on_the_chip(cell, reports=False)
    (_, gate_p95), device, model = _as_on_the_chip(cell, reports=True)
    assert model.most_in_flight <= 2
    gated = (device.opened.get("anticipated", 0)
             + device.opened.get("device_free", 0))
    if cell == INT8:
        # the chip: p95 −9 … −11% at the client, whose ingress the gate does
        # not touch; four flushes in five opened by the gate's timer
        assert gate_p95 <= 0.9 * slots_p95, (gate_p95, slots_p95)
        assert gated >= 0.75 * len(device.waits), device.opened
        # and it is not bought with the chip: the same requests, no more scans
        assert len(device.waits) <= len(slots_device.waits)
    else:
        # the chip: no gain, and an aim that cost 0.1–0.4 ms of p95. All the
        # gate opens here is a model's first flushes — the one with no
        # estimate yet, and those between the first scan shown and the first
        # lag: under one in a hundred. The schedule is the slots'
        assert gated <= 0.01 * len(device.waits), device.opened
        # (not flush for flush: those first few shift what swing each later
        # host stage draws)
        assert gate_p95 == pytest.approx(slots_p95, rel=0.02)
        assert len(device.waits) == pytest.approx(len(slots_device.waits),
                                                  rel=0.02)


def test_the_gate_takes_a_scan_off_the_int8_cell_as_the_issue_modelled_it():
    """ISSUE 34's own model of the cell (an instant launch, a report on
    time, a constant host stage of 1.3): p95 at least 15% below the slots',
    and next to no program waiting in the device's queue longer than h
    where by the slots most programs do — a width's scan shows when a flush
    of it sits behind another, and so does the flush behind a width not
    shown yet: no more than two a width."""
    rate, scan_ms, _, p_ms = CELLS[INT8]
    h_ms = 1.3
    (_, slots_p95), slots_device, _ = _run(rate, scan_ms, h_ms * MS, p_ms,
                                           reports=False)
    (_, gate_p95), device, model = _run(rate, scan_ms, h_ms * MS, p_ms,
                                        reports=True)
    assert gate_p95 <= 0.85 * slots_p95, (gate_p95, slots_p95)
    late = [w for w in device.waits if w > h_ms * MS + 1e-9]
    assert len(late) <= 2 * len({b for _, b in model.calls}), len(late)
    assert (sum(w > h_ms * MS for w in slots_device.waits)
            > len(slots_device.waits) // 2)
    assert model.most_in_flight <= 2
    assert len(device.waits) <= len(slots_device.waits)


def test_the_gate_holds_with_a_host_stage_that_varies():
    """The host stage allowed for is the median of what the last flushes
    took: one that swings by half a millisecond either way costs part of
    the gain (flushes land that much early or late), not the gain."""
    rate, scan_ms, h_ms, p_ms = CELLS[INT8]
    rng = random.Random(34)

    def host_s():
        return (h_ms + rng.uniform(-0.5, 0.5)) * MS

    (_, slots_p95), _, _ = _run(rate, scan_ms, h_ms * MS, p_ms, reports=False)
    (_, gate_p95), device, _ = _run(rate, scan_ms, host_s, p_ms, reports=True)
    assert gate_p95 <= 0.85 * slots_p95, (gate_p95, slots_p95)
    assert statistics.median(device.waits) < 0.5 * MS


def test_the_gate_learns_what_the_reports_lag_by():
    """On the chip a program takes a moment to reach an idle device, and the
    host hears that it is done a moment after it is (0.4 and 1.0 ms here):
    aimed at the REPORT, every flush would leave the device idle that long.
    A flush whose programs sat behind another's shows the scan alone, one
    that found the device idle the scan and the lag. And the loop's timers
    fire up to a millisecond late (epoll's granularity): the host stage is
    read from when the gate was DUE, so the estimate carries it."""
    rate, scan_ms, h_ms, p_ms = CELLS[INT8]
    rng = random.Random(34)

    def host_s():
        return (h_ms + rng.uniform(-0.5, 0.5)) * MS

    def lag_s():
        return (1.0 + rng.uniform(-0.1, 0.1)) * MS

    (_, slots_p95), _, _ = _run(rate, scan_ms, h_ms * MS, p_ms, reports=False,
                                launch_ms=0.4, lag_s=1.0 * MS, tick_s=MS)
    (_, gate_p95), device, model = _run(rate, scan_ms, host_s, p_ms,
                                        reports=True, launch_ms=0.4,
                                        lag_s=lag_s, tick_s=MS)
    assert gate_p95 <= 0.9 * slots_p95, (gate_p95, slots_p95)
    settled = device.gaps[len(device.gaps) // 2:]
    assert statistics.fmean(settled) < 0.5 * MS  # of 1.4 aimed at the report


def _call_spans(monkeypatch, **run):
    """One run with every ``coalescer.device_call`` span kept, in order of
    opening — which is the order of the model's calls and, on a FIFO
    device, of its runs."""
    monkeypatch.setattr(spans._STATE, "recorder", spans.SpanRecorder(1 << 16))
    monkeypatch.setattr(spans._STATE, "enabled", True)
    rate, scan_ms, h_ms, p_ms = CELLS[INT8]
    _, device, model = _run(rate, scan_ms, h_ms * MS, p_ms, reports=True,
                            **run)
    calls = sorted((s for s in spans.default_recorder().spans()
                    if s.name == "coalescer.device_call"),
                   key=lambda s: s.start_walltime)
    assert len(calls) == len(model.calls) == len(device.runs)
    return calls, model, device


@pytest.mark.parametrize("launch_ms", [0.0, 0.4], ids=["instant", "launch"])
def test_the_span_says_where_the_gate_believed_the_device_free(
        monkeypatch, launch_ms):
    """ISSUE 35: ``gate.free_in_ms`` is the aim — from the span's start to
    where the gate believed the device free of the flush before — pinned
    where the truth is known: the fake device's own end of that flush's
    program. With an instant launch and reports on time the aim is the
    truth; a launch of 0.4 ms is read into the ``lag`` (what a program that
    found the device idle took beyond its scan), so the gate believes the
    device free that much BEFORE it is: the error the chip's metric
    (``gate_aim_err_ms``) is there to show."""
    calls, model, device = _call_spans(monkeypatch, launch_ms=launch_ms)
    errs, engaged = [], 0
    for n, call in enumerate(calls):
        at = call.attributes
        opened = model.calls[n][0]  # the virtual clock at the span's start
        assert at["enqueued_ms"] == pytest.approx(CELLS[INT8][2], abs=6e-4)
        assert at["device_done_ms"] == pytest.approx(
            (device.runs[n][1] - opened) * 1e3, abs=6e-4)  # rounded to a µs
        engaged += at["gate.engaged"]
        if "gate.free_in_ms" in at:
            believed = opened + at["gate.free_in_ms"] * MS
            errs.append(believed - device.runs[n - 1][1])
    assert engaged >= 0.9 * len(calls)
    settled = errs[len(errs) // 2:]
    assert len(settled) > 100
    assert statistics.median(settled) == pytest.approx(-launch_ms * MS,
                                                       abs=0.02 * MS)
    # the estimates on the span are the coalescer's own, in ms
    last = calls[-1].attributes
    assert last["gate.h_ms"] == pytest.approx(CELLS[INT8][2], abs=6e-4)
    assert last["gate.lag_ms"] == pytest.approx(launch_ms, abs=6e-4)
    assert last["gate.scan_ms"] in (7.2, 8.9)


def test_the_span_says_how_late_the_loop_ran_the_gates_timer(monkeypatch):
    """``gate.late_ms``: the loop's clock at the open minus the instant the
    timer was armed for — on a selector that sleeps in whole milliseconds,
    up to one; only on the flushes that timer opened."""
    calls, _, _ = _call_spans(monkeypatch, tick_s=TICK_S)
    late = [c.attributes["gate.late_ms"] for c in calls
            if c.attributes["opened_by"] == "anticipated"]
    assert len(late) > 0.5 * len(calls)
    assert all(0.0 <= x <= 1.0 + 1e-6 for x in late) and max(late) > 0.5
    assert not any("gate.late_ms" in c.attributes for c in calls
                   if c.attributes["opened_by"] != "anticipated")
