"""A percentile of one of the program's spans, in milliseconds."""

from benchmarks.harness.stats import percentile


def read(obs, params):
    durs = [s["duration"] * 1e3 for s in obs.get("spans", [])
            if s["name"] == params["span"]]
    return percentile(durs, params["q"]) if durs else None
