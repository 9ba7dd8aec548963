"""The share of the traced window, in percent, in which the device is idle
AND the server is in ``params["state"]``. Every idle instant gets the first
state that holds:

``host_stage``  some flush is between the open of its
                ``coalescer.device_call`` and the close of its
                ``coalescer.wakeup``: the host works for the chip, or waits
                for it;
``queued``      no flush is in a stage, some ``coalescer.queue_wait`` is
                open: the window's timer or the cap on flushes in flight;
``in_ingress``  some ``http …`` span is open, nothing queued or in a stage;
``no_request``  none of these: no load.

The four sum to ``device_idle``. The device's idle time is the complement
of ``trace["op_intervals"]`` in ``trace["window"]``. A span is put on the
trace's clock by ``start_wall − profile_start_time`` (a stat of the trace's
``Task Environment`` plane: the profiler counts every event, host's and
device's, in nanoseconds of the same real-time clock from there). The check
on that: the stages that the program also annotates into the trace
(``topn.wait_download``) are paired, each with the span that starts
nearest, and where they disagree with the offset by more than 100 µs at the
median — or nothing can be paired — there is no number.
"""

import bisect

from benchmarks.harness.stats import percentile

STATES = ("host_stage", "queued", "in_ingress", "no_request")
PAIRED = "topn.wait_download"
MAX_SKEW_S = 100e-6


def profile_start_s(trace_dir: str):
    from jax.profiler import ProfileData

    from benchmarks.harness.trace import _find_xplane

    for plane in ProfileData.from_file(_find_xplane(trace_dir)).planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
            return None if start is None else start * 1e-9
    return None


def clock_skew_s(spans, host_events, offset_s: float):
    """Median distance between an annotated stage in the trace and the span
    of that stage that starts nearest, once the span is moved by the
    offset; None where there is nothing to pair."""
    starts = sorted(s["start_wall"] - offset_s for s in spans
                    if s["name"] == PAIRED)
    if not starts:
        return None
    off = []
    for name, start, _ in host_events:
        if name == PAIRED:
            k = bisect.bisect_left(starts, start)
            off.append(min(abs(start - t) for t in starts[max(0, k - 1):k + 1]))
    return percentile(off, 50) if off else None


def _state_intervals(spans, offset_s: float):
    """[(start, end, state index)] on the trace's clock; a flush's stage
    runs from its call span's open to its wakeup's close."""
    out, flushes = [], {}
    for s in spans:
        start = s["start_wall"] - offset_s
        end = start + s["duration"]
        if s["name"] in ("coalescer.device_call", "coalescer.wakeup"):
            lo, hi = flushes.get(s["attributes"].get("call"), (start, end))
            flushes[s["attributes"].get("call")] = (min(lo, start), max(hi, end))
        elif s["name"] == "coalescer.queue_wait":
            out.append((start, end, 1))
        elif s["name"].startswith("http "):
            out.append((start, end, 2))
    return out + [(lo, hi, 0) for lo, hi in flushes.values()]


def idle_seconds_by_state(op_intervals, window, state_intervals) -> list:
    """Seconds of ``window`` with no op running, by the first state open."""
    lo, hi = window
    # (time, what, +1 | −1): what 0..2 a state, 3 the device's ops
    edges = []
    for s, e, what in list(state_intervals) + [(s, e, 3) for s, e in op_intervals]:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            edges += [(s, what, 1), (e, what, -1)]
    edges.sort()
    open_, total, at = [0, 0, 0, 0], [0.0, 0.0, 0.0, 0.0], lo
    for t, what, step in edges + [(hi, 3, 0)]:
        if t > at and not open_[3]:
            state = next((k for k in range(3) if open_[k]), 3)
            total[state] += t - at
        at = max(at, t)
        open_[what] += step
    return total


def read(obs, params):
    tr, spans = obs.get("trace"), obs.get("spans")
    if not tr or not tr.get("window_s") or not spans or not obs.get("trace_dir"):
        return None
    if "idle_by_state" not in obs:  # one reading of the trace for the four
        obs["idle_by_state"] = None
        offset = profile_start_s(obs["trace_dir"])
        skew = (None if offset is None
                else clock_skew_s(spans, tr["host_events"], offset))
        if skew is not None and skew <= MAX_SKEW_S:
            obs["idle_by_state"] = idle_seconds_by_state(
                tr["op_intervals"], tr["window"],
                _state_intervals(spans, offset))
    idle = obs["idle_by_state"]
    if idle is None:
        return None
    return 100.0 * idle[STATES.index(params["state"])] / tr["window_s"]
