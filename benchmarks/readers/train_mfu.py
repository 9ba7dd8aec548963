"""Whole-step share of the chip's peak: the useful operations of the
window's iterations (``costs/als_iteration.py``) over the traced window and
the published bf16 peak, whatever dtype the program chose."""

from benchmarks.harness.manifest import load_module
from benchmarks.harness.peaks import peaks_for


def read(obs, params):
    tr = obs.get("trace")
    if not tr or not tr["window_s"] or not obs.get("iterations"):
        return None
    cost = load_module("costs", params["cost"], obs["bench_dir"])
    z = obs["sizes"]
    flops = cost.flops(z["interactions"], z["users"], z["items"],
                       z["features"]) * obs["iterations"]
    peak = peaks_for(obs["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / tr["window_s"] / peak
