"""The mesh scan's share of its roofline: for every flush and every device,
the least time that device could take for its OWN shard (by
``costs/topn_mesh.py`` and the peaks table: its shard's bytes of the scoring
copy, the queries, its candidates), summed over devices, over the summed
device time of the sharded program's calls on all devices. It reads the same
work whatever implements the scan; a device cannot finish its call in less
than its least time, so the share cannot pass 100."""

from benchmarks.harness.manifest import load_module
from benchmarks.harness.peaks import least_seconds


def read(obs, params):
    tr = obs.get("trace")
    shards = int(obs.get("sizes", {}).get("shards", 0))
    if not tr or shards < 2:
        return None
    times = [t for name, ts in tr["program_times_s"].items()
             if params["program"] in name for t in ts]
    # a flush's padded batch, as the one-chip reader counts it
    batches = load_module("readers", "topn_roofline", obs["bench_dir"]).calls(obs)
    if not times or not batches:
        return None
    cost = load_module("costs", params["cost"], obs["bench_dir"])
    n, k = obs["sizes"]["items"], obs["sizes"]["features"]
    least = shards * sum(
        least_seconds(*cost.flops_bytes(b, n, k, shards), obs["device_kind"])[0]
        for b in batches)
    # spans and trace events are counted over the same window; scale the
    # least time to the calls the trace saw whole (one a flush a device)
    least *= len(times) / (len(batches) * shards)
    return 100.0 * least / sum(times)
