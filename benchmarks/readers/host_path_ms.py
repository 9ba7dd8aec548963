"""Median over requests of the client's latency (sent → last byte) minus the
``coalescer.queue_wait`` of that request and the ``coalescer.device_call``
of the flush that answered it: what ingress, id lookup, JSON and the
client's own stack take. Requests are matched to spans by the trace id the
load generator sent."""

from benchmarks.harness.stats import percentile


def read(obs, params):
    req, spans = obs.get("requests"), obs.get("spans", [])
    if not req or not spans:
        return None
    to_index = obs["index_of_trace"]
    wait, call = {}, {}
    for s in spans:
        try:
            if s["name"] == "coalescer.queue_wait":
                wait[to_index(s["trace_id"])] = s["duration"]
            elif s["name"] == "coalescer.device_call":
                for t in [s["trace_id"]] + s["links"]:
                    call[to_index(t)] = s["duration"]
        except ValueError:
            continue
    rest = []
    for i, sent, done, status in zip(req["index"], req["sent"], req["done"],
                                     req["status"]):
        if status == 200 and i in wait and i in call:
            rest.append((done - sent - wait[i] - call[i]) * 1e3)
    return percentile(rest, 50) if rest else None
