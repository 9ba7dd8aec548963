"""A kernel's share of its roofline: the least time the chip could take for
the window's work in that kernel (the larger of operations over the peak
FLOP/s and bytes over the peak bytes/s, by the kernel's cost function and
the peaks table) over the summed device time of the kernel's events in the
trace. Finds nothing, and says nothing, where the trace holds no such
event."""

import re

from benchmarks.harness.manifest import load_module
from benchmarks.harness.peaks import least_seconds


def read(obs, params):
    tr = obs.get("trace")
    if not tr or not obs.get("iterations"):
        return None
    spent = sum(t for name, t in tr["op_time_s"].items()
                if re.search(params["op_regex"], name))
    if spent <= 0:
        return None
    cost = load_module("costs", params["cost"], obs["bench_dir"])
    z = obs["sizes"]
    least = 0.0
    for rows in (z["users"], z["items"]):
        args = {"nnz": z["interactions"], "rows": rows, "k": z["features"]}
        fb = cost.flops_bytes(*[args[a] for a in params["args"]])
        least += least_seconds(*fb, obs["device_kind"])[0]
    return 100.0 * least * obs["iterations"] / spent
