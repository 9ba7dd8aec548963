"""A percentile over ALL requests due in the window, each timed from its
due time; a failed, shed or unanswered request counts as the worst."""

from benchmarks.harness.stats import latencies_ms, percentile


def read(obs, params):
    req = obs.get("requests")
    if not req or not req["due"]:
        return None
    lat = latencies_ms(req, obs["worst_ms"])
    # requests the generator never got to send are as bad as unanswered
    lat += [obs["worst_ms"]] * max(0, obs.get("expected", 0) - len(lat))
    return percentile(lat, params["q"])
