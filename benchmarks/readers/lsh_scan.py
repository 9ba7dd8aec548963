"""The LSH scan's shares, flush by flush, from what each flush's host
stage chose: span ``topn.lsh_select`` (``rows`` the device reads, ``path``
listed or full) joined by its ``call`` to the flush's
``coalescer.device_call`` (``batch.padded``, ``batch.size``).

``what = "roofline"``: the least time for each joined flush's bytes and
operations (``costs/<cost>.py``, the peaks table) over its programs' device
time on the chip (``flush_device_phase``'s join: ``scan``), summed over the
flushes both have. ``what = "mfu"``: the operations of every flush's rows
(2·b·rows·k, b its real rows) over the traced window and the bf16 peak.

None where the program writes no ``topn.lsh_select`` (the parent of the
bucket-ordered scan), where there is no trace, or where the join is empty.
"""

from benchmarks.harness.manifest import load_module
from benchmarks.harness.peaks import least_seconds, peaks_for


def _flushes(obs) -> dict:
    """``{call: (b real, b padded, rows, listed)}``."""
    spans = obs.get("spans", [])
    calls = {s["attributes"].get("call"): s["attributes"] for s in spans
             if s["name"] == "coalescer.device_call"}
    out = {}
    for s in spans:
        a = s["attributes"]
        if s["name"] != "topn.lsh_select" or a.get("call") not in calls:
            continue
        c = calls[a["call"]]
        if c.get("batch.size") and a.get("rows"):
            out[a["call"]] = (c["batch.size"], c.get("batch.padded")
                              or c["batch.size"], a["rows"],
                              a.get("path") == "listed")
    return out


def read(obs, params):
    tr = obs.get("trace")
    flushes = _flushes(obs)
    if not tr or not tr["window_s"] or not flushes:
        return None
    cost = load_module("costs", params["cost"], obs["bench_dir"])
    k = obs["sizes"]["features"]
    if params["what"] == "mfu":
        flops = sum(cost.flops_bytes(b, rows, k, listed)[0]
                    for b, _, rows, listed in flushes.values())
        peak = peaks_for(obs["device_kind"])["bf16_flops_per_s"]
        return 100.0 * flops / obs["window_s"] / peak
    phase = load_module("readers", "flush_device_phase", obs["bench_dir"])
    if phase.read(obs, {"what": "scan", "q": 50}) is None:
        return None
    least = device = 0.0
    for r in obs[phase._KEY]["flushes"]:
        if r["call"] in flushes:
            _, padded, rows, listed = flushes[r["call"]]
            least += least_seconds(*cost.flops_bytes(padded, rows, k, listed),
                                   obs["device_kind"])[0]
            device += r["scan"]
    return 100.0 * least / device if device else None
