"""What decides ``correct``: each number compared, beside its limit."""

from __future__ import annotations

import sys


class Checks:
    def __init__(self, limits: dict):
        self.limits = limits
        self.rows = []  # (name, value, limit)

    def add(self, name: str, value: float) -> None:
        """Hold ``value`` to the limit the configuration's file gives
        ``name``; a number with no limit there is a fault of the benchmark."""
        self.rows.append((name, float(value), float(self.limits[name])))

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(
            v == v and v <= lim for _, v, lim in self.rows)

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.rows}

    def print_last(self) -> None:
        for n, v, lim in self.rows:
            print(f"compared {n} = {v!r} limit {lim!r} "
                  f"{'ok' if v == v and v <= lim else 'FAILED'}",
                  file=sys.stderr)
        sys.stderr.flush()
