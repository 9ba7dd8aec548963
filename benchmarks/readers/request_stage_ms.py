"""A percentile over requests of one stretch of a request's time inside the
server, in milliseconds, taken from the starts and ends of the program's
spans.

``params``: ``from`` and ``to`` are each ``[span, edge]`` (``edge`` is
``start`` or ``end``) and the value of a request is ``to − from`` on the
spans' own wall clock; or ``around`` names a span and the value is the
client's latency (sent → last byte, the client's clock) minus that span's
duration — no two clocks are ever subtracted from each other. ``q`` is the
percentile.

A span is found for a request by the trace id the load generator sent
(``http`` matches the ingress span, ``http GET /recommend/{userID}``); a
span of the flush that answered it (``coalescer.device_call`` and its
stages) by the ``call`` attribute of that flush's call span, whose trace id
and links name every request of the flush. Only requests answered 200 whose
spans are all there are counted; a program without the spans gives None.
"""

from benchmarks.harness.stats import percentile

CALL = "coalescer.device_call"


def _is(name: str, want: str) -> bool:
    return name == want or name.startswith(want + " ")


def spans_of_requests(obs, wanted):
    """{request index: {wanted name: (start, end)}} on the spans' clock."""
    to_index = obs["index_of_trace"]
    by_request, call_of, by_call = {}, {}, {}
    for s in obs.get("spans", []):
        call = s["attributes"].get("call")
        try:
            if s["name"] == CALL:
                for t in [s["trace_id"]] + s["links"]:
                    call_of[to_index(t)] = call
            name = next((w for w in wanted if _is(s["name"], w)), None)
            if name is None:
                continue
            edges = (s["start_wall"], s["start_wall"] + s["duration"])
            if call is not None:
                by_call.setdefault(call, {})[name] = edges
            else:
                by_request.setdefault(to_index(s["trace_id"]), {})[name] = edges
        except ValueError:
            continue
    for i, call in call_of.items():
        if call in by_call:
            by_request.setdefault(i, {}).update(by_call[call])
    return by_request


def read(obs, params):
    req = obs.get("requests")
    if not req or not obs.get("spans"):
        return None
    around = params.get("around")
    ends = [(around, None)] if around else [params["from"], params["to"]]
    found = spans_of_requests(obs, {name for name, _ in ends})
    values = []
    for i, sent, done, status in zip(req["index"], req["sent"], req["done"],
                                     req["status"]):
        have = found.get(i, {})
        if status != 200 or any(name not in have for name, _ in ends):
            continue
        if around:
            start, end = have[around]
            values.append((done - sent - (end - start)) * 1e3)
        else:
            (a, a_edge), (b, b_edge) = ends
            values.append((have[b][b_edge == "end"]
                           - have[a][a_edge == "end"]) * 1e3)
    return percentile(values, params["q"]) if values else None
