"""How unevenly a flush's work ends across the devices: the slowest device's
time in the sharded program over the devices' mean, the ``q``-th percentile
over the window's flushes (1.0: all devices alike)."""

from benchmarks.harness.manifest import load_module
from benchmarks.harness.stats import percentile


def read(obs, params):
    if not obs.get("trace"):
        return None
    planes = load_module("readers", "mesh_planes", obs["bench_dir"])
    rows = planes.flushes(obs, params["program"])
    if not rows:
        return None
    skew = []
    for f in rows:
        times = [e - s for s, e, _ in f]
        skew.append(max(times) / (sum(times) / len(times)))
    return percentile(skew, params["q"])
