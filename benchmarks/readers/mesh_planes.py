"""What the mesh readers share: the sharded program's calls and the
collective ops inside them, device by device, from the run's own trace.

``harness/trace.py`` keeps per-device detail of the first device only, so
these readers go back to the trace file (it is still there when metrics are
evaluated) and keep what they read in ``obs`` for one another.

A flush is one call of the program on every device; the devices' calls are
paired in order of start, each device's list cut to the calls that lie
whole inside the window.
"""

import re

from benchmarks.harness import trace as trace_mod

COLLECTIVE = re.compile(
    r"^(all[-_]gather|all[-_]reduce|all[-_]to[-_]all|collective[-_]permute"
    r"|reduce[-_]scatter|collective[-_]broadcast)")
_KEY = "_mesh_planes"


def by_device(planes: dict, window: tuple, program: str) -> list:
    """For each device plane, in name order: ``[(start, end, collective
    seconds inside)]`` of the program's whole calls in the window."""
    lo, hi = window
    out = []
    for _, dev in sorted(planes["devices"].items()):
        calls = sorted((s, e) for n, s, e, _ in dev["modules"]
                       if program in n and s >= lo and e <= hi)
        coll = sorted((s, e) for n, s, e, _ in dev["ops"]
                      if COLLECTIVE.match(n) and e > lo and s < hi)
        rows, j = [], 0
        for s, e in calls:
            while j < len(coll) and coll[j][1] <= s:
                j += 1
            t, i = 0.0, j
            while i < len(coll) and coll[i][0] < e:
                t += min(coll[i][1], e) - max(coll[i][0], s)
                i += 1
            rows.append((s, e, t))
        out.append(rows)
    return out


def flushes(obs: dict, program: str):
    """``[[(start, end, collective s) per device] per flush]`` or None where
    there is no trace, no such program, or a single device."""
    if _KEY not in obs:
        obs[_KEY] = {}
    if program not in obs[_KEY]:
        tr, trace_dir = obs.get("trace"), obs.get("trace_dir")
        rows = None
        if tr and trace_dir:
            try:
                planes = trace_mod.read_planes(trace_mod._find_xplane(trace_dir))
            except FileNotFoundError:
                planes = None
            if planes:
                per_dev = [d for d in by_device(planes, tr["window"], program)
                           if d]
                if len(per_dev) >= 2:
                    n = min(len(d) for d in per_dev)
                    rows = [[d[i] for d in per_dev] for i in range(n)]
        obs[_KEY][program] = rows
    return obs[_KEY][program]
