"""The table of peaks, keyed by ``device_kind``. An unknown kind raises."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_PATH, encoding="utf-8") as f:
        kinds = json.load(f)["kinds"]
    if device_kind not in kinds:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}: add it to "
            f"{_PATH} with its source; there is no default")
    return kinds[device_kind]


def least_seconds(flops: float, bytes_: float, device_kind: str) -> tuple:
    """(least time the chip could take, which bound sets it)."""
    p = peaks_for(device_kind)
    t_c, t_m = flops / p["bf16_flops_per_s"], bytes_ / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
