"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

A cell names a configuration and a traffic mix; a metric names itself. Each
has one data file; code (a driver, a reader, a cost function, a reference)
is found by the name a data file gives. The runner never branches on a name.
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmarks")


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find(kind: str, name: str, ext: str, bench_dir: str = BENCH_DIR) -> str:
    path = os.path.join(bench_dir, kind, name + ext)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"benchmark {kind} {name!r}: no file {path}")
    return path


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """Import ``<bench_dir>/<kind>/<name>.py`` under a name of its own."""
    path = find(kind, name, ".py", bench_dir)
    spec = importlib.util.spec_from_file_location(
        f"_oryx_bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its files resolved."""

    def __init__(self, manifest: dict, workload: str, bench_dir: str = BENCH_DIR):
        entries = [w for w in manifest["workloads"] if w["name"] == workload]
        if not entries:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        self.entry = entries[0]
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.bench_dir = bench_dir
        cfg = [c for c in manifest["configs"] if c["name"] == self.entry["config"]]
        if not cfg:
            raise KeyError(f"workload {workload!r} names no known config")
        self.config_entry = cfg[0]
        root = os.path.dirname(bench_dir)
        self.config = load_json(os.path.join(root, self.config_entry["file"]))
        self.traffic = load_json(
            find("traffic", self.entry["traffic"], ".json", bench_dir)
        )
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if self._reports(m)]
        self.per_layer = [m for m in manifest["per_layer"] if self._reports(m)]

    def _reports(self, metric: dict) -> bool:
        cells = metric.get("workloads")
        return cells is None or self.name in cells

    def metric_spec(self, name: str) -> dict:
        return load_json(find("metrics", name, ".json", self.bench_dir))
