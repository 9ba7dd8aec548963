"""Median device time inside one of the harness's own trace annotations
(one half-iteration blocks until its result is ready, so its device ops lie
inside its annotation), in milliseconds."""

from benchmarks.harness.stats import percentile, union_length


def device_seconds(tr, annotation):
    """Device-busy seconds inside each span of ``annotation``."""
    spans = [(s, e) for n, s, e in tr["host_events"] if n == annotation]
    out = []
    for lo, hi in spans:
        iv = [(max(s, lo), min(e, hi)) for s, e in tr["op_intervals"]
              if e > lo and s < hi]
        out.append(union_length(iv))
    return out


def read(obs, params):
    tr = obs.get("trace")
    if not tr:
        return None
    secs = device_seconds(tr, params["annotation"])
    return percentile(secs, 50) * 1e3 if secs else None
