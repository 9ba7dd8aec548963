"""All 200 answers completed in the window, over the whole window."""

from benchmarks.harness.stats import completed_rate


def read(obs, params):
    req = obs.get("requests")
    if not req:
        return None
    return completed_rate(req, obs["t_start"], obs["window_s"])
