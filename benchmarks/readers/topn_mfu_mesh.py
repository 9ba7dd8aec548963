"""Whole-step share of the mesh's peak: the operations of every top-N call of
the window (2·b·n·k over ALL of Y, b the real rows of the flush) over the
traced window and the published bf16 peak of every chip the replica holds."""

from benchmarks.harness.peaks import peaks_for


def read(obs, params):
    tr = obs.get("trace")
    shards = int(obs.get("sizes", {}).get("shards", 0))
    if not tr or not tr["window_s"] or shards < 2:
        return None
    batches = [s["attributes"]["batch.size"] for s in obs.get("spans", [])
               if s["name"] == "coalescer.device_call"
               and s["attributes"].get("batch.size")]
    if not batches:
        return None
    n, k = obs["sizes"]["items"], obs["sizes"]["features"]
    flops = sum(2.0 * b * n * k for b in batches)
    peak = shards * peaks_for(obs["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / obs["window_s"] / peak
