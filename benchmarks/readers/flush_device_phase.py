"""A flush's device phase, from the join of its spans to its own programs on
the chip (``flush_join``): ``params["what"]`` is

``launch`` / ``behind`` / ``scan`` / ``result`` / ``chip_gap``
    the ``q``-th percentile over the window's flushes, in ms (``chip_gap``:
    over those opened with requests held for the chip);
``aim_err``
    the ``q``-th percentile of \\|where the gate believed the device would be
    free − where it was\\| over the flushes the gate's timer opened, in ms;
``idle``
    the share of the window, in percent, in which the device is idle inside
    a flush's host stage and ``params["state"]`` holds (``pre_launch`` /
    ``post_scan``: ``flush_join.idle_split``), the mean of the devices.

None — the metric is left out — where there is no trace, where the program
does not say what it launched (``topn.dispatch``'s ``programs``: the parent
of PR 35), or where ``flush_join`` makes no record set. The first metric
read prints one line of everything the join found to standard error.
"""

import json
import sys

from benchmarks.harness import trace as trace_mod
from benchmarks.harness.manifest import load_module
from benchmarks.harness.stats import percentile

_KEY = "_flush_device_phase"
_MS = 1e3


def _pcts(values, scale=_MS):
    values = [v for v in values if v is not None]
    if not values:
        return None
    return {q: round(percentile(values, q) * scale, 4) for q in (50, 95)}


def _signed_median(values):
    values = [v for v in values if v is not None]
    return round(percentile(values, 50), 4) if values else None


def _report(joined, idle, unmoved, window_s) -> dict:
    """Everything the join found, for PERF.md: not metrics."""
    rows = joined["flushes"]
    out = {
        "info": "flush_device_phase", "why": joined["why"],
        "window_flushes": joined["window_calls"], "joined": joined["joined"],
        "span_skew_us": (None if joined["skew_s"] is None
                         else round(joined["skew_s"] * 1e6, 2)),
        "device_clock_band_us": {
            d: [None if x is None else round(x * 1e6, 1) for x in band]
            for d, band in joined["bands"].items()},
    }
    if not rows:
        return out
    timer = [r for r in rows
             if "aim_err" in r and r["opened_by"] == "anticipated"]
    # the *device done* report behind the device's true end, in ms
    trails = {r["call"]: (r["call_start"] - r["end"]) * _MS
              + r["attributes"]["device_done_ms"] for r in rows
              if r["attributes"].get("device_done_ms") is not None}
    out.update({
        "launch_ms": _pcts(r["launch"] for r in rows),
        "behind_ms": _pcts(r["behind"] for r in rows),
        "behind_share_over_0.1ms": round(
            sum(r["behind"] > 1e-4 for r in rows) / len(rows), 4),
        "scan_ms": _pcts(r["scan"] for r in rows),
        "own_gaps_ms": _pcts(r["own_gaps"] for r in rows),
        "result_ms": _pcts(r["result"] for r in rows),
        "heard_after_end_ms": _pcts(r["heard"] - r["end"] for r in rows
                                    if r["heard"] is not None),
        "wd_end_after_first_copy_ms": _pcts(
            (r["wd_ms"] - r["first_copy_ms"] for r in rows
             if r["first_copy_ms"] is not None), scale=1.0),
        "report_after_end_ms": _pcts(trails.values(), scale=1.0),
        "chip_gap_ms": _pcts(r["chip_gap"] for r in rows if r["held"]),
        "aim_err_abs_ms": _pcts(abs(r["aim_err"]) for r in timer),
        "aim_err_signed_p50_ms": _signed_median(
            r["aim_err"] * _MS for r in timer),
        "gate_scan_minus_scan_ms": _signed_median(
            r["attributes"]["gate.scan_ms"] - r["scan"] * _MS for r in rows
            if r["attributes"].get("gate.scan_ms") is not None),
        "gate_lag_minus_report_lag_ms": _signed_median(
            r["attributes"]["gate.lag_ms"] - trails[r["call"]] for r in rows
            if r["call"] in trails
            and r["attributes"].get("gate.lag_ms") is not None),
        "identity_max_err_us": round(max(abs(
            (r["wd_end"] - r["enq"]) - (r["behind"] + r["launch"] + r["scan"]
                                        + r["own_gaps"] + r["result"]))
            for r in rows) * 1e6, 4),
        "opened_by": {by: sum(str(r["opened_by"]) == by for r in rows)
                      for by in sorted({str(r["opened_by"]) for r in rows})},
    })
    if idle:
        # ``host_stage_unmoved``: the device's ops as the trace wrote them,
        # which is what ``idle_by_state`` gives ``host_stage``
        out["idle_pct_by_device"] = {
            d: {"pre_launch": round(100.0 * pre / window_s, 3),
                "post_scan": round(100.0 * post / window_s, 3),
                "host_stage": round(100.0 * (pre + post) / window_s, 3),
                "host_stage_unmoved": round(
                    100.0 * sum(unmoved[d]) / window_s, 3)}
            for d, (pre, post) in idle.items()}
        half_band = max(hi - lo for lo, hi in joined["bands"].values()) / 2.0
        out["idle_split_good_to_pct"] = round(
            100.0 * half_band * len(rows) / window_s, 3)
    return out


def _build(obs):
    tr, spans, trace_dir = obs.get("trace"), obs.get("spans"), obs.get("trace_dir")
    if not tr or not tr.get("window_s") or not spans or not trace_dir:
        return None
    if not any(s["name"] == "topn.dispatch" and s["attributes"].get("programs")
               for s in spans):
        return None  # a program that does not say what it launched
    bench_dir = obs["bench_dir"]
    try:
        planes = load_module("readers", "flush_planes", bench_dir).read(
            trace_mod._find_xplane(trace_dir))
    except FileNotFoundError:
        return None
    join_mod = load_module("readers", "flush_join", bench_dir)
    joined = join_mod.join(planes, spans, tr["window"])
    idle = unmoved = None
    if joined["why"] is None:
        sweep = load_module("readers", "idle_by_state",
                            bench_dir).idle_seconds_by_state
        idle = join_mod.idle_split(planes, joined, tr["window"], sweep)
        unmoved = join_mod.idle_split(planes, joined, tr["window"], sweep,
                                      moved=False)
    said = _report(joined, idle, unmoved, tr["window_s"])
    # the same events by harness/trace.py's route: the two medians agree
    names = {p for s in spans if s["name"] == "topn.dispatch"
             for p in s["attributes"].get("programs", ())}
    times = [t for name, ts in tr.get("program_times_s", {}).items()
             if name in names for t in ts]
    if times:
        said["program_times_p50_ms"] = round(percentile(times, 50) * _MS, 4)
    print(json.dumps(said), file=sys.stderr)
    if joined["why"]:
        return None
    return {"flushes": joined["flushes"], "idle": idle}


def read(obs, params):
    if _KEY not in obs:  # one reading of the trace for all the metrics
        obs[_KEY] = None
        obs[_KEY] = _build(obs)
    built = obs[_KEY]
    if built is None:
        return None
    what, rows = params["what"], built["flushes"]
    if what == "idle":
        k = ("pre_launch", "post_scan").index(params["state"])
        shares = [v[k] for v in built["idle"].values()]
        return 100.0 * sum(shares) / len(shares) / obs["trace"]["window_s"]
    if what == "chip_gap":
        values = [r["chip_gap"] for r in rows
                  if r["held"] and r["chip_gap"] is not None]
    elif what == "aim_err":
        values = [abs(r["aim_err"]) for r in rows
                  if "aim_err" in r and r["opened_by"] == "anticipated"]
    else:
        values = [r[what] for r in rows]
    return percentile(values, params["q"]) * _MS if values else None
