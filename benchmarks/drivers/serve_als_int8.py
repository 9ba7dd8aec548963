"""Driver: ``serve_als`` for a model whose items lie on the device as int8
rows (``oryx.serving.device-dtype = int8``), every flush's candidates
rescored in float32 from the host factor arena.

Everything a request passes is ``serve_als``'s: the HTTP app, the coalescer,
the load generators, the window, the comparison, the teardown. What differs
is set-up and what the answers are held to:

* the model is built with the configuration's ``device-dtype`` and
  ``rescore-factor``; the snapshot has no ``score_mat``, so set-up waits for
  its ``device_arrays()``; the phases are ``bulk_load``, ``quantize`` (ids,
  quantized rows, their upload) and ``warm_ladder``;
* one copy of Y in float32 is all a one-chip host holds at 20M x 250f (20 GB
  of the 40 GiB it is allowed): the generated matrix is handed over to the arena
  (``bulk_load_items(adopt=True)``) and the driver keeps none. After the
  window, with the model freed, the items are made again from the seed — the
  truth never passes through the program's memory — and the window's 256
  sampled answers (the slowest always in) are held to the float32 brute
  force of ``references/als_topn_int8.py``. ``--control 1`` also holds that
  reference's two-stage answer one precision below (4-bit rows, bfloat16
  rescore) to the same truth.

A program that would copy the handoff (20 GB beside the generated 20 GB, and
a third for the snapshot's own host copy), or whose model does not resolve
to int8, is refused before anything is allocated: it could only end with
the host out of memory, minutes later.
"""

from __future__ import annotations

import gc
import inspect
import json
import os
import sys
import threading
import time

import numpy as np

from benchmarks.harness import factors
from benchmarks.harness.checks import Checks
from benchmarks.harness.loadgen import index_of_trace
from benchmarks.harness.manifest import load_module

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
base = load_module("drivers", "serve_als", _BENCH)
window, compare, teardown = base.window, base.compare, base.teardown
# the window also reads how many rows the flushes' rescores gathered
RESCORED = "oryx_serving_rescored_rows_total"
base.COUNTERS = base.COUNTERS + (RESCORED,)

QUANTIZE_PHASE = "quantize"


def _refuse_what_cannot_hold_it(cfg: dict) -> None:
    from oryx_tpu.models.als.serving import ALSServingModel

    if "adopt" not in inspect.signature(
            ALSServingModel.bulk_load_items).parameters:
        raise SystemExit(
            "this program's bulk_load_items copies the handoff into the "
            "arena and its int8 snapshot gathers a float32 copy of Y on the "
            "host: three times 20 GB at 20M x 250f on a one-chip host allowed 40 GiB; "
            "refused before any allocation")
    probe = ALSServingModel(2, True, device_dtype=cfg["device-dtype"],
                            rescore_factor=float(cfg["rescore-factor"]))
    if probe.device_dtype != "int8":
        raise SystemExit(
            f"the model resolves device-dtype to {probe.device_dtype!r}, "
            "not int8: refused before any allocation")


def rescore_width(cfg: dict) -> int:
    """Candidates a query's rescore sees, by the documented rule:
    ``rescore-factor x how-many``, at least 16, in a power of two."""
    want = max(int(float(cfg["rescore-factor"]) * int(cfg["how-many"])), 16)
    return 1 << (want - 1).bit_length()


def _rss() -> int:
    try:
        with open("/proc/self/status", encoding="ascii") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 0


def _host_used() -> int:
    """MemTotal less MemFree: what the whole machine has in use, memory the
    process freed and the host has not taken back yet included — the figure
    a one-chip machine's 40 GiB are counted in."""
    try:
        with open("/proc/meminfo", encoding="ascii") as f:
            kb = {ln.split(":")[0]: int(ln.split()[1]) for ln in f}
        return (kb["MemTotal"] - kb["MemFree"]) * 1024
    except (OSError, KeyError, ValueError):
        return 0


class _MemoryWatch(threading.Thread):
    """The largest resident size of the process and the most the machine
    had in use, sampled: a phase's transient shows only while it lasts."""

    def __init__(self):
        super().__init__(daemon=True, name="bench-memory")
        self.rss_peak = self.used_peak = 0
        self.start()

    def sample(self) -> tuple:
        rss, used = _rss(), _host_used()
        self.rss_peak = max(self.rss_peak, rss)
        self.used_peak = max(self.used_peak, used)
        return rss, used

    def run(self):
        while True:
            self.sample()
            time.sleep(0.1)

    def say(self, what: str, **more) -> None:
        rss, used = self.sample()
        print(json.dumps({"info": what, **more, "rss_bytes": rss,
                          "rss_peak_bytes": self.rss_peak,
                          "host_used_bytes": used,
                          "host_used_peak_bytes": self.used_peak}),
              file=sys.stderr, flush=True)


def _refuse_a_second_copy(freed_bytes: int, need_bytes: int) -> None:
    """Stop, rather than have the host killed for memory, where the served
    model's arena did not go when it was torn down: the items made again
    would then stand beside it."""
    if need_bytes >= 1 << 30 and freed_bytes < 0.9 * need_bytes:
        raise SystemExit(
            f"tearing the served model down freed {freed_bytes} bytes of "
            f"the host's memory, and the truth needs {need_bytes}: the "
            "arena is still held")


def setup(ctx):
    import jax

    st = base.Served()
    st.watch = _MemoryWatch()
    cfg = ctx.cell.config
    st.cfg, st.sizes = cfg, ctx.sized(cfg)
    k, n_items, n_users = (st.sizes["features"], st.sizes["items"],
                           st.sizes["users"])
    st.how_many = int(cfg["how-many"])
    phases = ctx.phases

    from oryx_tpu.common import config as oryx_config
    from oryx_tpu.common import ioutils
    from oryx_tpu.models.als.serving import ALSServingModel
    from oryx_tpu.serving.app import make_app
    from oryx_tpu.serving.batcher import pow2_buckets

    _refuse_what_cannot_hold_it(cfg)
    serving = cfg["serving"]
    overlay = {
        "oryx.serving.application-resources": "oryx_tpu.serving.resources.als",
        "oryx.serving.compute.coalesce-window-ms": serving["coalesce-window-ms"],
        "oryx.serving.compute.coalesce-max-batch": serving["coalesce-max-batch"],
        "oryx.serving.compute.coalesce-inflight": serving["coalesce-inflight"],
        "oryx.serving.compute.precompile-batches": serving["precompile-batches"],
    }
    if ctx.trace:
        overlay["oryx.tracing.spans.ring-size"] = 1 << 20
    config = oryx_config.overlay_on(overlay, oryx_config.get_default())
    st.manager = base._Manager()
    # make_app chooses the compile cache's directory: before any compile
    st.app = make_app(config, st.manager)
    phases.mark("import_and_app")

    st.watch.say("app")
    y_host = factors.make(ctx.seed, "items", n_items, k)
    phases.mark("factors_host")
    model = ALSServingModel(k, bool(cfg["implicit"]), float(cfg["sample-rate"]),
                            device_dtype=cfg["device-dtype"],
                            rescore_factor=float(cfg["rescore-factor"]),
                            index_enabled=bool(cfg["index"]["enabled"]))
    # the arenas take the matrices themselves and the driver gives them up:
    # its truth is made again from the seed once the model is gone. Items
    # first: interning 20M ids is the phase's transient (3 GiB)
    model.bulk_load_items(list(map("i{}".format, range(n_items))), y_host,
                          adopt=True)
    del y_host
    gc.collect()
    model.bulk_load_users(list(map("u{}".format, range(n_users))),
                          factors.make(ctx.seed, "users", n_users, k),
                          adopt=True)
    phases.mark("bulk_load")
    st.watch.say("loaded")
    snap = model.y_snapshot()
    arrays = snap.device_arrays()
    jax.block_until_ready(arrays)
    phases.mark(QUANTIZE_PHASE)
    st.resident = {
        "snapshot": type(snap).__name__,
        "arrays": [{"dtype": str(a.dtype), "shape": list(a.shape),
                    "bytes": int(a.nbytes)} for a in arrays],
        "rescore_width": int(snap.rescore_width(st.how_many)),
    }
    st.watch.say("resident", **st.resident)
    if st.resident["rescore_width"] != rescore_width(cfg):
        raise SystemExit(
            f"the program rescores {st.resident['rescore_width']} candidates "
            f"a query, the configuration states {rescore_width(cfg)}")
    for b in pow2_buckets(int(serving["coalesce-max-batch"])):
        model.top_n_batch(np.zeros((b, k), dtype=np.float32), st.how_many)
    phases.mark("warm_ladder")
    st.watch.say("warmed", device_peak_bytes=ctx.memory_peak())
    st.manager.model = model
    st.port = ioutils.choose_free_port()
    st.loop, st.thread = base._serve(st.app, st.port)
    return st


def _answer(body: str) -> list:
    try:
        return [(int(e["id"][1:]), float(e["value"])) for e in json.loads(body)]
    except Exception:  # noqa: BLE001 — not the JSON the endpoint gives
        return []


def run(ctx) -> dict:
    cfg, mix = ctx.cell.config, ctx.sized(ctx.cell.traffic)
    st = setup(ctx)
    try:
        w = window(ctx, st, mix, ctx.seconds)
    finally:
        span_list = base._span_dicts(0.0) if ctx.trace else []
        peak = ctx.memory_peak()
        held = _rss()
        teardown(st)
    req, sizes, how_many = w["requests"], st.sizes, st.how_many
    span_list = [s for s in span_list if s["start_wall"] >= w["wall0"]]
    st.watch.say("freed")

    # the program's state is freed: now the truth, made again from the seed
    n_items, k = sizes["items"], sizes["features"]
    _refuse_a_second_copy(held - _rss(), n_items * k * 4)
    x_host = factors.make(ctx.seed, "users", sizes["users"], k)
    y_host = factors.make(ctx.seed, "items", n_items, k)
    ctx.phases.mark("factors_again")
    checks = Checks(cfg["limits"])
    reference = load_module("references", cfg["reference"], _BENCH)
    rng = np.random.default_rng([ctx.seed, 4])
    finished = sorted(req["bodies"])
    want = min(int(mix["sample_requests"]), len(finished))
    chosen = set(rng.choice(finished, size=want, replace=False).tolist()) \
        if want else set()
    if finished:
        # the request that took longest is always in the sample
        pos = {i: p for p, i in enumerate(req["index"])}
        chosen.add(max(finished, key=lambda i: (
            (req["done"][pos[i]] or 0) - req["due"][pos[i]])))
    chosen = sorted(chosen)
    user_of = base._users_of_requests(mix, sizes, ctx.seed, ctx.seconds, chosen)
    sample = [_answer(req["bodies"][i]) for i in chosen]
    checks.add("unanswered", w["unanswered"])
    checks.add("compiles_in_window", w["compiles"])
    if chosen:
        queries = x_host[[user_of[i] for i in chosen]]
        compare(sample, queries, y_host, how_many, checks, reference, False)
        if ctx.control:
            cv, ci = reference.two_stage(queries, y_host, how_many,
                                         rescore_width(cfg), control=True)
            csample = [list(zip(ci[s].tolist(), cv[s].tolist()))
                       for s in range(len(chosen))]
            compare(csample, queries, y_host, how_many, checks, reference,
                    True)
    else:
        checks.add("score_err", float("nan"))
    ctx.phases.mark("reference")
    st.watch.say("compared")

    return {
        "checks": checks, "attempted": w["attempted"],
        "failed": w["attempted"] - w["ok"], "memory_peak_bytes": peak,
        "obs": {
            "requests": req, "expected": w["expected"],
            "t_start": w["t_start"], "window_s": w["window_s"],
            "spans": span_list, "counters": w["counters"],
            "trace_dir": w["trace_dir"], "sizes": sizes,
            "resident": st.resident,
            "worst_ms": float(mix["timeout_s"]) * 1e3,
            "index_of_trace": index_of_trace,
        },
    }
