"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Device ops are the events of a device plane's ``XLA Ops`` line; a program
(one jitted call) is an event of its ``XLA Modules`` line. On a backend with
no device plane (the CPU rehearsal) the host's events that carry an
``hlo_op`` stand in, so that the same code runs in the tests; such a run
labels itself ``cpu`` and is never reported under a device's name.

Busy time is the union of the op intervals of one device, averaged over the
devices used; the window is the span from the harness's own
``bench.window`` annotation where the trace holds one, else from the first
device op to the last.
"""

from __future__ import annotations

import glob
import os
import re

from benchmarks.harness.stats import gaps, union_length

WINDOW_ANNOTATION = "bench.window"
_NS = 1e-9


def _find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except Exception:  # noqa: BLE001 — a stat this jaxlib cannot decode
        return {}


def short_op_name(name: str) -> str:
    """The TPU trace names an op by its whole HLO line; keep the op's name
    and the shape it yields: ``%fusion.1 = (f32[4,2560]{…}, …) fusion(…)`` →
    ``fusion.1 f32[4,2560]``; a Pallas kernel gets the tag ``[pallas]``."""
    m = re.match(r"%?([\w.\-]+) = \(?([a-z0-9]+\[[^\]]*\])", name)
    if not m:
        return name[:120]
    # a Pallas kernel is a custom call to the TPU's own target
    tag = " [pallas]" if 'custom_call_target="tpu_custom_call"' in name else ""
    return f"{m.group(1)} {m.group(2)}{tag}"


def describe(path: str, per_line: int = 3) -> str:
    """A listing of planes, lines and their first events, to read by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name} events={len(events)}")
            for e in events[:per_line]:
                st = {k: str(v)[:120] for k, v in _stats(e).items()}
                out.append(f"    {e.name[:160]!r} start_ns={e.start_ns} "
                           f"dur_ns={e.duration_ns} stats={st}")
            if line.name == "XLA Ops":
                total: dict = {}
                for e in events:
                    t, _ = total.get(short_op_name(e.name), (0.0, None))
                    total[short_op_name(e.name)] = (t + e.duration_ns, e.name)
                for short, (t, raw) in sorted(
                        total.items(), key=lambda kv: -kv[1][0])[:15]:
                    out.append(f"    TOP {short} total_ns={t} raw={raw[:700]!r}")
    return "\n".join(out)


def program_name(module_event_name: str) -> str:
    """``jit__top_k_dot_batch(123)`` → ``jit__top_k_dot_batch``."""
    return re.sub(r"\(\d+\)$", "", module_event_name).strip()


def read_planes(path: str) -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}}, "host": [...]}
    with events as ``(name, start_s, end_s, program)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:") and "CUSTOM" not in plane.name
        for line in plane.lines:
            if is_device and line.name in ("XLA Ops", "XLA Modules"):
                key = "ops" if line.name == "XLA Ops" else "modules"
                dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
                for e in line.events:
                    s = e.start_ns * _NS
                    name = short_op_name(e.name) if key == "ops" else e.name
                    dev[key].append((name, s, s + e.duration_ns * _NS, None))
            elif not is_device:
                for e in line.events:
                    s = e.start_ns * _NS
                    st = _stats(e) if e.duration_ns else {}
                    host.append((e.name, s, s + e.duration_ns * _NS,
                                 st.get("hlo_module"), "hlo_op" in st))
    if not devices:
        # no device plane: the host's XLA ops stand in (CPU rehearsal)
        ops = [(n, s, e, m) for n, s, e, m, is_op in host if is_op]
        mods: dict = {}
        for n, s, e, m in ops:
            lo, hi = mods.get(m, (s, e))
            mods[m] = (min(lo, s), max(hi, e))
        devices["/host:CPU"] = {
            "ops": ops,
            "modules": [(m, s, e, None) for m, (s, e) in mods.items() if m],
        }
    return {"devices": devices,
            "host": [(n, s, e) for n, s, e, _, is_op in host if not is_op]}


def reduce_planes(planes: dict) -> dict:
    host = planes["host"]
    marks = [(s, e) for n, s, e in host if n == WINDOW_ANNOTATION]
    all_ops = [o for d in planes["devices"].values() for o in d["ops"]]
    if marks:
        lo, hi = marks[0]
    elif all_ops:
        lo, hi = min(o[1] for o in all_ops), max(o[2] for o in all_ops)
    else:
        lo = hi = 0.0
    busy, op_time, programs, all_gaps, op_iv = [], {}, {}, [], []
    for name, dev in sorted(planes["devices"].items()):
        iv = [(max(s, lo), min(e, hi)) for _, s, e, _ in dev["ops"]
              if e > lo and s < hi]
        busy.append(union_length(iv))
        if not op_iv:
            op_iv = sorted(iv)  # the first device's, for per-span readers
        for n, s, e, _ in dev["ops"]:
            if e > lo and s < hi:
                d = min(e, hi) - max(s, lo)
                op_time[n] = op_time.get(n, 0.0) + d
        for n, s, e, _ in dev["modules"]:
            if s >= lo and e <= hi:  # whole calls only
                programs.setdefault(program_name(n), []).append(e - s)
        all_gaps.extend(gaps(iv, lo, hi)[:50])
    n_dev = max(1, len(busy))
    return {
        "window_s": hi - lo, "window": (lo, hi),
        "busy_s": sum(busy) / n_dev, "devices": len(busy),
        "op_time_s": op_time, "program_times_s": programs,
        "op_intervals": op_iv,
        "idle_gaps": sorted(all_gaps, key=lambda g: g[0] - g[1])[:50],
        "host_events": [(n, s, e) for n, s, e in host
                        if e > lo and s < hi and e - s > 1e-4
                        and n != WINDOW_ANNOTATION][:200000],
    }


def reduce_dir(trace_dir: str) -> dict:
    return reduce_planes(read_planes(_find_xplane(trace_dir)))


def breakdown(reduced: dict, obs: dict) -> dict:
    """The ten device ops that took most time, and the ten longest idle
    gaps, each named by the host event that covers most of it."""
    n_dev = max(1, reduced["devices"])
    ops = sorted(reduced["op_time_s"].items(), key=lambda kv: -kv[1])[:10]
    host = reduced["host_events"]
    named = []
    for lo, hi in reduced["idle_gaps"][:10]:
        best, cover = "no host event", 0.0
        for n, s, e in host:
            c = min(e, hi) - max(s, lo)
            if c > cover:
                best, cover = n, c
        named.append([best, hi - lo])
    return {"device_ops": [[n, t / n_dev] for n, t in ops],
            "idle_gaps": named}
