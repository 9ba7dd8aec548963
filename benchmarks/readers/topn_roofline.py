"""The top-N program's share of its roofline: the least time the chip could
take for the window's calls (by ``costs/topn.py`` and the peaks table; the
scan is bound by the bytes of Y until the batch passes ~240 rows) over the
device time of the program's calls in the trace."""

from benchmarks.harness.manifest import load_module
from benchmarks.harness.peaks import least_seconds


def calls(obs):
    """Padded batch size of every flush of the window."""
    return [s["attributes"]["batch.padded"] for s in obs.get("spans", [])
            if s["name"] == "coalescer.device_call"
            and s["attributes"].get("batch.padded")]


def read(obs, params):
    tr = obs.get("trace")
    if not tr:
        return None
    times = [t for name, ts in tr["program_times_s"].items()
             if params["program"] in name for t in ts]
    batches = calls(obs)
    if not times or not batches:
        return None
    cost = load_module("costs", params["cost"], obs["bench_dir"])
    n, k = obs["sizes"]["items"], obs["sizes"]["features"]
    least = sum(least_seconds(*cost.flops_bytes(b, n, k), obs["device_kind"])[0]
                for b in batches)
    # spans and trace events are counted over the same window; scale the
    # least time to the calls the trace saw whole
    least *= len(times) / len(batches)
    return 100.0 * least / sum(times)
