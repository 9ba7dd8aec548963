"""Percentile and rate arithmetic, kept with the benchmark."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between the
    closest ranks, over ALL values given; ``inf`` stays ``inf``."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or xs[lo] == xs[hi]:
        return float(xs[lo])
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def latencies_ms(requests: dict, worst_ms: float) -> list:
    """Latency of every request DUE in the window, from its due time to the
    last byte; a request that failed, was shed or never answered counts as
    ``worst_ms`` (at least), so it can only worsen a tail."""
    out = []
    for due, done, status in zip(requests["due"], requests["done"],
                                 requests["status"]):
        if status == 200 and done is not None:
            out.append((done - due) * 1000.0)
        else:
            out.append(worst_ms)
    return out


def completed_rate(requests: dict, t_start: float, window_s: float) -> float:
    """Answers with status 200 whose last byte arrived inside the window,
    over the whole window."""
    t_end = t_start + window_s
    n = sum(1 for done, status in zip(requests["done"], requests["status"])
            if status == 200 and done is not None and done <= t_end)
    return n / window_s


def union_length(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list:
    """The idle ``(start, end)`` stretches of ``[lo, hi]`` that no interval
    covers, longest first."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return sorted((g for g in out if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])
