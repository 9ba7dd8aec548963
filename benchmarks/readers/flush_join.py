"""One record a flush: the coalescer's device call joined to the programs it
ran on the chip, by the trace's own ids.

**program ↔ launch** by ``run_id``: a device plane's ``XLA Modules`` event
and the host plane's ``DoEnqueueProgram`` carry the same. **launch ↔ flush**
by order: the k-th launch of a program, in the order of the ``topn.dispatch``
annotations whose spans name it (``programs``), is its k-th run on the device
— one queue a device is FIFO, and on this runtime the enqueue runs on a
thread of the runtime's own (``tfrt-non-blocking-queue``), not inside the
annotation. Two launches in another order than their dispatches show on at
least one of the two flushes as a launch before its dispatch began or a
result on the host before its program ended: such a flush does not join.
**annotation ↔ span** by the nearest start once the span is moved by
``profile_start_time`` (``idle_by_state.clock_skew_s``); ``call`` ties the
stages to ``coalescer.device_call``.

**Two clocks.** A device plane counts on the device's clock, and the
profiler lines it up with the host's once a session, to a millisecond or
two: as written, programs start BEFORE their launch. The trace bounds the
shift itself (:func:`clock_band`): no program starts before its launch
begins, no end is heard on the host before it happened. The device's times
are moved by the middle of that band; half its width (≈ 0.1 ms) is what every
number that crosses the clocks (launch, behind, result, the aim's error, the
idle split) may be off by, one way for all flushes of a run. ``scan`` and
``chip_gap`` lie on the device's clock alone.

Per flush, on the device where its programs ended last, with ``enq`` the
launch of its last program, ``prev_end`` the end of the program before its
first, ``start`` / ``end`` its programs' first start and last end,
``wd_end`` the end of ``topn.wait_download``::

    behind = max(0, prev_end − enq)        launch = start − max(enq, prev_end)
    scan   = Σ device durations            own_gaps = (end − start) − scan
    result = wd_end − end
    wd_end − enq = behind + launch + scan + own_gaps + result

No record set (``why`` says which) where a span and its annotation differ by
more than 100 µs, where a device's band is empty or wider than 0.4 ms, or
where fewer than 99% of the window's flushes join.
"""

import bisect

from benchmarks.harness.stats import percentile

MAX_SKEW_S = 100e-6  # a span against its annotation: idle_by_state's
MAX_BAND_S = 400e-6  # what the device's clock may stay unplaced by
MIN_JOINED = 0.99
#: opened while requests were held for the chip: what ``chip_gap`` is over
HELD = ("anticipated", "device_free", "completion")
_TOL_S = 2e-6


def clock_band(modules, launches, heard):
    """``(lo, hi)``: the least and the largest shift of a device's clock at
    which no program of ``modules`` starts before its launch begins and no
    end is heard before it happened; either is None where nothing bounds
    it. ``heard`` has one entry a run, in order."""
    runs = {run: s for s, _, run, _ in modules}
    lo = [ls - runs[run] for ls, _, run, _ in launches if run in runs]
    ends = sorted(e for _, e, _, _ in modules)
    if len(heard) > len(ends):  # runs from before the trace lead
        heard = heard[len(heard) - len(ends):]
    hi = [h - e for h, e in zip(heard, ends)]
    return (max(lo) if lo else None), (min(hi) if hi else None)


def _nearest(sorted_starts, at):
    """(distance, index) of the entry of ``sorted_starts`` nearest ``at``."""
    k = bisect.bisect_left(sorted_starts, at)
    return min((abs(sorted_starts[i] - at), i)
               for i in (k - 1, k) if 0 <= i < len(sorted_starts))


def _pair(annotations, stage_spans, offset_s):
    """Each annotation's span: ``[(annotation, span | None)]`` and the
    distances of the pairs made."""
    rows = sorted(((s["start_wall"] - offset_s, n)
                   for n, s in enumerate(stage_spans)))
    starts = [r[0] for r in rows]
    out, off = [], []
    for a in annotations:
        span = None
        if starts:
            d, i = _nearest(starts, a[0])
            if d <= MAX_SKEW_S:
                span = stage_spans[rows[i][1]]
            off.append(d)
        out.append((a, span))
    return out, off


def _first_from(sorted_times, at):
    """The first of ``sorted_times`` at or after ``at``, or None."""
    k = bisect.bisect_left(sorted_times, at)
    return sorted_times[k] if k < len(sorted_times) else None


def _place_clocks(planes: dict, out: dict) -> bool:
    """Each device's clock against the host's: ``out["bands"]``,
    ``out["shift"]``; False (and ``why``) where the trace does not say."""
    for d, dev in planes["devices"].items():
        lo, hi = clock_band(
            dev["modules"], [x for x in planes["launches"] if x[3] == d],
            planes["heard"].get(d, []))
        out["bands"][d] = (lo, hi)
        if lo is None or hi is None or hi < lo - _TOL_S or hi - lo > MAX_BAND_S:
            out["why"] = (f"device {d}: the trace does not place its clock "
                          f"(band {lo} .. {hi})")
            return False
        out["shift"][d] = (lo + hi) / 2.0
    if not out["shift"]:
        out["why"] = "no device plane"
    return bool(out["shift"])


def _flushes_by_dispatch(dispatches, downloads, calls) -> dict:
    """The flushes in dispatch order, as far as their stages' spans say:
    ``{call: {"dispatch", "programs", "runs": {}, "wd_end", ...}}``."""
    flushes = {}
    for (a_start, _), span in dispatches:
        call = span and span["attributes"].get("call")
        programs = span and span["attributes"].get("programs")
        if call in calls and programs:
            flushes[call] = {"dispatch": a_start, "programs": list(programs),
                             "runs": {}}
    for (_, a_end), span in downloads:
        call = span and span["attributes"].get("call")
        if call in flushes:
            flushes[call].update(
                wd_end=a_end, wd_ms=span["duration"] * 1e3,
                first_copy_ms=span["attributes"].get("first_copy_ms"))
    return flushes


def _runs_by_order(flushes: dict, d, dev: dict, launches, shift) -> None:
    """Launch ↔ flush on device ``d``, a program at a time: the k-th launch
    of a program is the k-th flush's that names it. Fills
    ``flush["runs"][d]`` with the flush's times there, on the host's clock."""
    at = {run: i for i, (_, _, run, _) in enumerate(dev["modules"])}
    launched = {}
    for ls, _, run, ordinal in launches:
        if ordinal == d and run in at:
            launched.setdefault(dev["modules"][at[run]][3], []).append(
                (ls, at[run]))
    taken = {}
    for f in flushes.values():
        runs = []
        for program in f["programs"]:
            k = taken[program] = taken.get(program, -1) + 1
            if k < len(launched.get(program, ())):
                runs.append(launched[program][k])
        if len(runs) != len(f["programs"]):
            continue
        first = min(i for _, i in runs)
        mods = [dev["modules"][i] for _, i in runs]
        f["runs"][d] = {
            "enq": max(ls for ls, _ in runs),
            "first_enq": min(ls for ls, _ in runs),
            "start": min(m[0] for m in mods) + shift,
            "end": max(m[1] for m in mods) + shift,
            "scan": sum(m[1] - m[0] for m in mods),
            "prev_end": dev["modules"][first - 1][1] + shift if first else None,
        }


def _record(call, span, c_start, f, half_band, heard) -> "dict | None":
    """A joined flush's record, on the device where its programs ended last;
    None where the order slipped: not this flush's programs."""
    d = max(f["runs"], key=lambda k: f["runs"][k]["end"])
    r = f["runs"][d]
    if (r["first_enq"] < f["dispatch"] - _TOL_S
            or f["wd_end"] < r["end"] - half_band - _TOL_S):
        return None
    prev_end = r["prev_end"]
    ready = r["enq"] if prev_end is None else max(r["enq"], prev_end)
    return {
        "call": call, "device": d, "call_start": c_start,
        "opened_by": span["attributes"].get("opened_by"),
        "held": span["attributes"].get("opened_by") in HELD,
        "attributes": span["attributes"],
        "enq": r["enq"], "prev_end": prev_end, "start": r["start"],
        "end": r["end"], "wd_end": f["wd_end"],
        "behind": 0.0 if prev_end is None else max(0.0, prev_end - r["enq"]),
        "launch": r["start"] - ready,
        "scan": r["scan"],
        "own_gaps": (r["end"] - r["start"]) - r["scan"],
        "result": f["wd_end"] - r["end"],
        "chip_gap": None if prev_end is None else r["start"] - prev_end,
        "first_copy_ms": f["first_copy_ms"], "wd_ms": f["wd_ms"],
        "heard": _first_from(heard.get(d, []), r["end"] - half_band),
    }


def join(planes: dict, spans: list, window=None) -> dict:
    """``{"flushes": [record], "why": None | str, "skew_s", "bands", "shift",
    "window_calls", "joined", "stage"}`` — ``flushes`` holds the window's
    joined flushes in order of their call span (none where ``why`` says why
    not), ``stage`` every flush's ``(call open, wakeup close, {device: first
    program start | None})`` for the idle split."""
    out = {"flushes": [], "why": None, "skew_s": None, "bands": {},
           "window_calls": 0, "joined": 0, "stage": [], "shift": {}}
    offset = planes.get("profile_start_s")
    window = planes.get("window") or window
    if offset is None or window is None:
        out["why"] = "the trace has no profile start or no window"
        return out
    named = {}
    for s in spans:
        named.setdefault(s["name"], []).append(s)
    calls = {s["attributes"].get("call"): s
             for s in named.get("coalescer.device_call", [])}
    wakeups = {s["attributes"].get("call"): s
               for s in named.get("coalescer.wakeup", [])}
    dispatches, _ = _pair(planes["stages"]["topn.dispatch"],
                          named.get("topn.dispatch", []), offset)
    downloads, off = _pair(planes["stages"]["topn.wait_download"],
                           named.get("topn.wait_download", []), offset)
    out["skew_s"] = percentile(off, 50) if off else None
    if out["skew_s"] is None or out["skew_s"] > MAX_SKEW_S:
        out["why"] = "spans and annotations are not on one clock"
        return out
    if not _place_clocks(planes, out):
        return out
    flushes = _flushes_by_dispatch(dispatches, downloads, calls)
    for d, dev in planes["devices"].items():
        _runs_by_order(flushes, d, dev, planes["launches"], out["shift"][d])

    half_band = max((hi - lo) / 2.0 for lo, hi in out["bands"].values())
    records = []
    for call, span in calls.items():
        c_start = span["start_wall"] - offset
        c_end = c_start + span["duration"]
        f = flushes.get(call, {"runs": {}})
        wake = wakeups.get(call)
        s_end = (wake["start_wall"] - offset + wake["duration"]
                 if wake else c_end)
        out["stage"].append((c_start, max(c_end, s_end), {
            d: f["runs"][d]["start"] if d in f["runs"] else None
            for d in planes["devices"]}))
        if c_start < window[0] or c_end > window[1]:
            continue
        out["window_calls"] += 1
        if "wd_end" in f and set(f["runs"]) == set(planes["devices"]):
            record = _record(call, span, c_start, f, half_band,
                             planes["heard"])
            if record is not None:
                records.append(record)
    records.sort(key=lambda r: r["call_start"])
    out["joined"] = len(records)
    if (not out["window_calls"]
            or out["joined"] < MIN_JOINED * out["window_calls"]):
        out["why"] = (f"{out['joined']} of {out['window_calls']} of the "
                      "window's flushes join")
        return out
    # the aim: where the gate believed the device would be free of the flush
    # opened before, against where that flush's last program truly ended
    for before, r in zip(records, records[1:]):
        free_in = r["attributes"].get("gate.free_in_ms")
        if free_in is not None:
            r["aim_err"] = r["call_start"] + free_in * 1e-3 - before["end"]
    out["flushes"] = records
    return out


def idle_split(planes: dict, joined: dict, window, sweep, moved=True) -> dict:
    """``{device: (pre_launch s, post_scan s)}``: the device's idle time
    inside some flush's host stage (``idle_by_state``'s ``host_stage``, its
    ``sweep`` = ``idle_seconds_by_state``), with the device's ops on the
    host's clock — **pre_launch** while some open flush's first program has
    not started on that device (the chip waits for that flush's host work),
    **post_scan** otherwise (every open flush's programs are over: the host
    is finishing, nothing newer was opened). Each flush with idle on both
    sides of its programs moves half the clock band between the two, so the
    split is good to *half band × flushes a second* of the window.
    ``moved=False`` leaves the device's ops where the trace wrote them, as
    ``idle_by_state`` reads them: the two then sum to its ``host_stage``."""
    out = {}
    for d, dev in planes["devices"].items():
        shift = joined["shift"][d] if moved else 0.0
        ops = [(s + shift, e + shift) for s, e in dev["ops"]]
        states = []
        for lo, hi, starts in joined["stage"]:
            states.append((lo, hi, 1))
            first = starts.get(d)
            if first is not None:
                first += shift - joined["shift"][d]
                if first > lo:
                    states.append((lo, min(first, hi), 0))
        idle = sweep(ops, window, states)
        out[d] = (idle[0], idle[1])
    return out
