"""Whole-step share of the chip's peak: the operations of every top-N call
of the window (2·b·n·k, b the real rows of the flush) over the traced window and the published bf16
peak."""

from benchmarks.harness.manifest import load_module
from benchmarks.harness.peaks import peaks_for


def read(obs, params):
    tr = obs.get("trace")
    if not tr or not tr["window_s"]:
        return None
    # real rows only: the padding a flush adds is not useful work
    batches = [s["attributes"]["batch.size"] for s in obs.get("spans", [])
               if s["name"] == "coalescer.device_call"
               and s["attributes"].get("batch.size")]
    if not batches:
        return None
    cost = load_module("costs", params["cost"], obs["bench_dir"])
    n, k = obs["sizes"]["items"], obs["sizes"]["features"]
    flops = sum(cost.flops_bytes(b, n, k)[0] for b in batches)
    peak = peaks_for(obs["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / obs["window_s"] / peak
