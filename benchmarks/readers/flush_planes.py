"""What ``flush_join`` reads of a profiler trace (``.xplane.pb``), opened here
because ``harness/trace.py`` keeps program durations only: every device
plane's programs WITH the ``run_id`` the runtime gave each run, the host's
launch of each run (``DoEnqueueProgram``, the same ``run_id``), the host's
hearing of each end (``tpu::System::Execute=>Done``) and the program's own
stage annotations (``common/spans.py:stage``).

Times are seconds from the profile's start. A device plane counts them on
the DEVICE's clock, the host plane on the host's: the profiler lines the two
up once a session, to a millisecond or two (``flush_join.clock_band``).
"""

import re

from benchmarks.harness.trace import WINDOW_ANNOTATION, program_name

LAUNCH = "DoEnqueueProgram"
HEARD = "tpu::System::Execute=>Done"
STAGES = ("topn.dispatch", "topn.wait_download")
_NS = 1e-9


def _ordinal(plane_name: str):
    m = re.match(r"^/device:TPU:(\d+)$", plane_name)
    return int(m.group(1)) if m else None


def read(path: str) -> dict:
    """``{"devices": {ordinal: {"modules": [(start, end, run_id, program)],
    "ops": [(start, end)]}}, "launches": [(start, end, run_id, ordinal)],
    "heard": {core: [start]}, "stages": {name: [(start, end)]}, "window":
    (lo, hi) | None, "profile_start_s": float | None}``, each list in order
    of start."""
    from jax.profiler import ProfileData

    devices, launches, heard = {}, [], {}
    stages = {name: [] for name in STAGES}
    window = profile_start_s = None
    for plane in ProfileData.from_file(path).planes:
        ordinal = _ordinal(plane.name)
        if ordinal is not None:
            dev = devices.setdefault(ordinal, {"modules": [], "ops": []})
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        s = e.start_ns * _NS
                        dev["modules"].append((
                            s, s + e.duration_ns * _NS,
                            dict(e.stats).get("run_id"), program_name(e.name)))
                elif line.name == "XLA Ops":
                    for e in line.events:
                        s = e.start_ns * _NS
                        dev["ops"].append((s, s + e.duration_ns * _NS))
        elif plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
            profile_start_s = None if start is None else start * 1e-9
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name == LAUNCH:
                        st, s = dict(e.stats), e.start_ns * _NS
                        launches.append((s, s + e.duration_ns * _NS,
                                         st.get("run_id"),
                                         st.get("device_ordinal", 0)))
                    elif name == HEARD:
                        heard.setdefault(
                            dict(e.stats).get("core_id", 0), []
                        ).append(e.start_ns * _NS)
                    elif name in stages:
                        s = e.start_ns * _NS
                        stages[name].append((s, s + e.duration_ns * _NS))
                    elif name == WINDOW_ANNOTATION and window is None:
                        s = e.start_ns * _NS
                        window = (s, s + e.duration_ns * _NS)
    for dev in devices.values():
        dev["modules"].sort()
        dev["ops"].sort()
    launches.sort()
    for starts in heard.values():
        starts.sort()
    for rows in stages.values():
        rows.sort()
    return {"devices": devices, "launches": launches, "heard": heard,
            "stages": stages, "window": window,
            "profile_start_s": profile_start_s}
