"""Time in collective ops (the merge's gathers) inside one flush of the
sharded program, on the device where it was longest, in milliseconds: the
``q``-th percentile over the window's flushes."""

from benchmarks.harness.manifest import load_module
from benchmarks.harness.stats import percentile


def read(obs, params):
    if not obs.get("trace"):
        return None
    planes = load_module("readers", "mesh_planes", obs["bench_dir"])
    rows = planes.flushes(obs, params["program"])
    if not rows:
        return None
    return percentile([max(c for _, _, c in f) * 1e3 for f in rows],
                      params["q"])
