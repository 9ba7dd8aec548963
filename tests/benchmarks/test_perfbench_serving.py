"""The serving cells in their tiny ``cpu`` rehearsal: the result line's
form, what decides ``correct`` (control and planted fault), and that a
cell, a configuration and a metric can be added as files alone."""

import json
import os
import shutil
import sys

import numpy as np
import pytest

from perfbench_util import LINE_KEYS, ROOT, rehearse

sys.path.insert(0, ROOT)
from benchmarks.harness import manifest as mf  # noqa: E402
from benchmarks.harness.checks import Checks  # noqa: E402

MANIFEST = mf.load_manifest()
SERVING = [w["name"] for w in MANIFEST["workloads"]
           if w["config"] == "als-5m-250f"]


@pytest.mark.parametrize("cell", SERVING)
def test_rehearsal_prints_exactly_the_contract_line(cell):
    rc, line, err = rehearse(cell, seed=2 ** 31 + 11)
    assert rc == 0, err[-2000:]
    assert set(line) == LINE_KEYS and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0, (line, err[-1500:])
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"  # never a device's name
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    c = mf.Cell(MANIFEST, cell)
    assert set(line["metrics"]) == {m["name"] for m in c.end_to_end}
    for m in c.end_to_end:
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    for name, row in line["compared"].items():
        assert row["value"] <= row["limit"], name
    # each number compared stands beside its limit at the end of stderr
    tail = err.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") and " limit " in t for t in tail)
    assert '"compiles_in_window": 0' in err


def test_traced_rehearsal_reports_spans_but_no_device_metric():
    cell = SERVING[0]
    rc, line, err = rehearse(cell, seed=5, trace=1)
    assert rc == 0, err[-2000:]
    assert set(line) == LINE_KEYS | {"breakdown"}
    c = mf.Cell(MANIFEST, cell)
    host_side = {m["name"] for m in c.per_layer if m["source"] != "device_trace"}
    assert host_side and set(line["metrics"]) == host_side
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert 0 < len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10
    assert not os.path.exists(os.path.join(ROOT, "benchmarks", ".trace", cell))


def _small_model(n_items=30000, k=250, n_q=48, seed=4):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n_q, k), dtype=np.float32),
            rng.standard_normal((n_items, k), dtype=np.float32))


def test_control_in_int8_comes_out_not_correct():
    """The reference put in the program's place, one precision below the
    configuration's bfloat16: it has to fail a limit the cell holds."""
    cfg = mf.load_json(mf.find("configs", "als-5m-250f", ".json"))
    ref = mf.load_module("references", cfg["reference"])
    drv = mf.load_module("drivers", cfg["driver"])
    qs, items = _small_model()

    def verdict(control):
        vals, idx = ref.top_n(qs, items, 10, block_rows=8192, control=control)
        sample = [list(zip(idx[s].tolist(), vals[s].tolist()))
                  for s in range(len(qs))]
        checks = Checks(cfg["limits"])
        drv.compare(sample, qs, items, 10, checks, ref, False)
        return checks

    sound, control = verdict(False), verdict(True)
    assert sound.correct, sound.as_dict()
    assert not control.correct, control.as_dict()
    failed = {n for n, v, lim in control.rows if v > lim}
    assert "score_err" in failed


_ALTER_AN_ANSWER = '''
from oryx_tpu.models.als import serving as S
_orig = S.ALSServingModel.top_n_batch
def _altered(self, qs, how_many, *a, **kw):
    out = _orig(self, qs, how_many, *a, **kw)
    # the best item of every answer is swapped for one nobody asked about
    return [[("i7", r[0][1])] + r[1:] if r else r for r in out]
S.ALSServingModel.top_n_batch = _altered
'''

_LEAVE_HALF_OUT = '''
from oryx_tpu.models.als import serving as S
_orig = S.ALSServingModel.top_n_batch
def _half(self, qs, how_many, *a, **kw):
    # only the second half of the items can be recommended
    n = self.y_snapshot().n
    out = _orig(self, qs, how_many + n // 2, *a, **kw)
    return [[(i, v) for i, v in r if int(i[1:]) >= n // 2][:how_many] for r in out]
S.ALSServingModel.top_n_batch = _half
'''


@pytest.mark.parametrize("fault,number", [
    (_ALTER_AN_ANSWER, "score_err"), (_LEAVE_HALF_OUT, "miss_share")],
    ids=["answer_altered", "half_the_items_left_out"])
def test_a_broken_timed_path_comes_out_not_correct(fault, number):
    rc, line, err = rehearse(SERVING[0], seed=6, prelude=fault)
    assert rc == 0, err[-2000:]
    assert line["correct"] is False
    row = line["compared"][number]
    assert row["value"] > row["limit"]
    assert f"compared {number} " in err and "FAILED" in err


def test_a_cell_a_configuration_and_a_metric_are_added_as_files(tmp_path):
    """A later PR adds a cell by adding files and manifest entries, and edits
    no file that exists: shown on a temporary copy."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    before = {p: (tmp_path / "benchmarks" / p).read_bytes()
              for p in ("run.py", "drivers/serve_als.py", "harness/manifest.py")}
    b = tmp_path / "benchmarks"
    cfg = mf.load_json(mf.find("configs", "als-5m-250f", ".json"))
    cfg.update({"name": "throwaway-3k-250f", "items": 3000, "users": 400})
    cfg.pop("rehearsal")
    (b / "configs" / "throwaway-3k-250f.json").write_text(json.dumps(cfg))
    mix = mf.load_json(mf.find("traffic", "open", ".json"))
    mix.update({"name": "trickle", "rate_per_s": 40, "processes": 1,
                "warm_requests": 2, "sample_requests": 16})
    mix.pop("rehearsal")
    (b / "traffic" / "trickle.json").write_text(json.dumps(mix))
    (b / "readers" / "answered_share.py").write_text(
        "def read(obs, params):\n"
        "    s = obs['requests']['status']\n"
        "    return 100.0 * sum(1 for x in s if x == 200) / len(s) if s else None\n")
    (b / "metrics" / "answered_share.trickle.json").write_text(json.dumps(
        {"name": "answered_share.trickle", "reader": "answered_share"}))
    m = json.loads(json.dumps(MANIFEST))
    m["configs"].append({"name": "throwaway-3k-250f", "source": "a test",
                         "file": "benchmarks/configs/throwaway-3k-250f.json",
                         "reduced": [], "why": "a test"})
    cell = "throwaway.trickle"
    m["workloads"].append({"name": cell, "config": "throwaway-3k-250f",
                           "traffic": "trickle", "chips": 1, "why": "a test"})
    for e in m["end_to_end"]:
        if e["name"] == "recommend_p95_ms":
            e["workloads"].append(cell)
    m["per_layer"].append({
        "name": "answered_share.trickle", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "HTTP ingress",
        "moves": "recommend_p95_ms", "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    run = str(b / "run.py")
    rc, line, err = rehearse(cell, seed=8, run=run)
    assert rc == 0, err[-2000:]
    assert line["correct"] and set(line["metrics"]) == {"recommend_p95_ms",
                                                        "setup_s"}
    rc, line, err = rehearse(cell, seed=9, trace=1, run=run)
    assert rc == 0, err[-2000:]
    assert line["metrics"]["answered_share.trickle"]["value"] == 100.0
    assert before == {p: (b / p).read_bytes() for p in before}


def test_the_closed_loop_cell_comes_back_with_manifest_entries_alone(tmp_path):
    """PR 23 measured `serve-5m-250f.closed` and left it out of the manifest
    (PERF.md: its req/s spread past any admissible bound); its traffic and
    metric files stayed, so the cell is manifest entries away."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    m = json.loads(json.dumps(MANIFEST))
    cell = "serve-5m-250f.closed"
    m["workloads"].append({"name": cell, "config": "als-5m-250f",
                           "traffic": "closed", "chips": 1, "why": "a test"})
    m["end_to_end"].append({"name": "recommend_qps", "unit": "req/s",
                            "better": "higher", "bound": 0.1,
                            "source": "host_clock", "workloads": [cell]})
    for name, unit in (("host_path_ms.closed", "ms"),
                       ("flush_batch.closed", "req/flush")):
        m["per_layer"].append({
            "name": name, "unit": unit, "better": "higher",
            "source": "program_span", "layer": "coalescer",
            "moves": "recommend_qps", "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    run = str(tmp_path / "benchmarks" / "run.py")
    rc, line, err = rehearse(cell, seed=2 ** 31 + 21, run=run)
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0, (line, err[-1500:])
    assert set(line["metrics"]) == {"recommend_qps", "setup_s"}
    assert line["metrics"]["recommend_qps"]["value"] > 0
    rc, line, err = rehearse(cell, seed=22, trace=1, run=run)
    assert rc == 0, err[-2000:]
    assert set(line["metrics"]) == {"host_path_ms.closed", "flush_batch.closed"}
    assert line["metrics"]["flush_batch.closed"]["value"] >= 1
