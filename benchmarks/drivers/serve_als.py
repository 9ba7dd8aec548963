"""Driver: an ALS model behind the real HTTP app, coalescer and top-N scan.

Builds ``ALSServingModel`` from seeded factors (``bulk_load``, the way a
MODEL-REF handoff loads a generation), puts it behind ``make_app`` with a
stub manager (the construction of ``bench.py:_http_bench``), warms the
coalescer's batch ladder, and lets child processes that never import jax
send ``GET /recommend/{user}`` for the window. The answers the window
itself returned are then compared with a float32 brute force.
"""

from __future__ import annotations

import asyncio
import gc
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from benchmarks.harness import factors, traffic
from benchmarks.harness.checks import Checks
from benchmarks.harness.loadgen import GcWatch, index_of_trace
from benchmarks.harness.manifest import load_module

LOADGEN = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "harness", "loadgen.py")


class _Manager:
    rescorer_provider = None

    def __init__(self):
        self.model = None

    def get_model(self):
        return self.model

    def is_read_only(self):
        return True


def _serve(app, port: int):
    """Run the aiohttp app on a thread of its own; returns (loop, thread)."""
    from aiohttp import web

    loop = asyncio.new_event_loop()
    started = threading.Event()

    def serve():
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(app, access_log=None)
        loop.run_until_complete(runner.setup())
        loop.run_until_complete(web.TCPSite(runner, "127.0.0.1", port).start())
        started.set()
        loop.run_forever()
        loop.run_until_complete(runner.cleanup())

    thread = threading.Thread(target=serve, daemon=True, name="bench-http")
    thread.start()
    if not started.wait(30):
        raise RuntimeError("the serving app did not start")
    return loop, thread


class _ServerStalls(GcWatch):
    """The longest garbage collection of the serving process and the longest
    overshoot of a 5 ms timer on the server's own event loop, in the window."""

    def __init__(self, loop, t_start: float):
        super().__init__()
        self.loop, self.t_start = loop, t_start
        self.tick_max, self.tick_at, self._on = 0.0, None, True
        loop.call_soon_threadsafe(self._arm)

    def _arm(self):
        if self._on:
            self.loop.call_later(0.005, self._fire, time.monotonic())

    def _fire(self, t0):
        over = time.monotonic() - t0 - 0.005
        if over > self.tick_max:
            self.tick_max, self.tick_at = over, time.monotonic() - self.t_start
        self._arm()

    def stop(self) -> dict:
        self._on = False
        super().stop()
        return dict(self.as_dict(self.t_start),
                    loop_tick_max_ms=self.tick_max * 1e3,
                    loop_tick_at_s=self.tick_at)


def _counter(name: str) -> float:
    from oryx_tpu.common import metrics as metrics_mod

    fam = metrics_mod.default_registry().get(name)
    if fam is None:
        return 0.0
    snap: dict = {}
    fam.snapshot_into(snap)
    return float(sum(v for v in snap.get(name, {}).values()
                     if isinstance(v, (int, float))))


COUNTERS = (
    "oryx_shed_requests_total", "oryx_coalescer_deadline_flushes_total",
    "oryx_coalescer_deadline_dropped_total", "oryx_coalescer_pad_waste_rows_total",
    "oryx_breaker_degraded_requests_total", "oryx_serving_topn_queries_total",
)


def _spawn_children(mix, sizes, seed, seconds, port, tmpdir):
    """Start the load generators; returns (procs, out paths, expected)."""
    n_proc = int(mix["processes"])
    n_users = sizes["users"]
    specs = []
    if mix["loop"] == "open":
        due = traffic.open_schedule(mix, seconds, seed)
        users = traffic.users_for(mix, len(due), n_users, seed)
        expected = len(due)
        every, phase = traffic.keep_rule(expected, mix["sample_requests"], seed)
        for c in range(n_proc):
            specs.append({"schedule": [
                [int(i), float(due[i]), mix["endpoint"].format(user=f"u{users[i]}")]
                for i in range(c, len(due), n_proc)]})
    else:
        per_child = int(mix["paths_per_child"])
        users = traffic.users_for(mix, per_child * n_proc, n_users, seed)
        expected = int(mix["expected_rate_per_s"] * seconds)
        every, phase = traffic.keep_rule(expected, mix["sample_requests"], seed)
        clients = int(mix["clients"])
        for c in range(n_proc):
            specs.append({
                "paths": [mix["endpoint"].format(user=f"u{u}")
                          for u in users[c::n_proc]],
                "stride": n_proc, "child": c, "seconds": seconds,
                "clients": clients // n_proc + (1 if c < clients % n_proc else 0),
            })
    warm_users = traffic.users_for(mix, int(mix["warm_requests"]) * n_proc,
                                   n_users, seed + 7)
    procs, outs = [], []
    for c, spec in enumerate(specs):
        spec.update({
            "port": port, "loop": mix["loop"], "timeout_s": mix["timeout_s"],
            "keep_every": every, "keep_phase": phase, "t_start": None,
            "warm_paths": [mix["endpoint"].format(user=f"u{u}")
                           for u in warm_users[c::n_proc]],
        })
        spec_path = os.path.join(tmpdir, f"spec{c}.json")
        with open(spec_path, "w", encoding="utf-8") as f:
            json.dump(spec, f)
        out = os.path.join(tmpdir, f"out{c}.json")
        outs.append(out)
        procs.append(subprocess.Popen(
            [sys.executable, LOADGEN, spec_path, out],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True))
    return procs, outs, expected


def _release(procs, lead_s: float) -> float:
    """Wait until every child has opened its connections, then tell all of
    them the same start on the monotonic clock."""
    for p in procs:
        line = p.stdout.readline()
        if line.strip() != "ready":
            raise RuntimeError(f"a load generator did not come up: {line!r}")
    t_start = time.monotonic() + lead_s
    for p in procs:
        p.stdin.write(f"{t_start!r}\n")
        p.stdin.flush()
    return t_start


def _collect(procs, outs, deadline_s: float) -> dict:
    req = {"index": [], "due": [], "sent": [], "done": [], "status": []}
    bodies, stalls = {}, []
    for p, out in zip(procs, outs):
        try:
            p.wait(timeout=max(1.0, deadline_s))
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
        if os.path.exists(out):
            with open(out, encoding="utf-8") as f:
                rec = json.load(f)
            for k in req:
                req[k].extend(rec[k])
            bodies.update({int(i): b for i, b in rec["bodies"].items()})
            stalls.append(rec.get("stalls"))
    req["bodies"], req["stalls"] = bodies, stalls
    return req


def _span_dicts(since_wall: float) -> list:
    from oryx_tpu.common import spans as spans_mod

    out = []
    for s in spans_mod.default_recorder().spans():
        if s.start_walltime < since_wall:
            continue
        out.append({
            "name": s.name, "trace_id": s.context.trace_id,
            "start_wall": s.start_walltime, "duration": s.duration,
            "attributes": dict(s.attributes),
            "links": [c.trace_id for c in s.links],
        })
    return out


def compare(sample: list, queries: np.ndarray, items: np.ndarray,
            how_many: int, checks: Checks, reference, control: bool,
            scale_to: "np.ndarray | None" = None) -> dict:
    """Hold the answers of ``sample`` (lists of (item index, value)) to the
    reference. Returns the reference's own top list for reuse."""
    ref_vals, ref_idx = reference.top_n(queries, items, how_many)
    malformed = 0
    served_idx = np.zeros((len(sample), how_many), dtype=np.int64)
    served_val = np.zeros((len(sample), how_many), dtype=np.float64)
    for s, answer in enumerate(sample):
        ok = (len(answer) == how_many
              and len({i for i, _ in answer}) == how_many
              and all(0 <= i < len(items) for i, _ in answer)
              and all(answer[j][1] >= answer[j + 1][1]
                      for j in range(len(answer) - 1)))
        if not ok:
            malformed += 1
            continue
        served_idx[s] = [i for i, _ in answer]
        served_val[s] = [v for _, v in answer]
    exact = reference.exact_scores(queries, items, served_idx)
    best = np.abs(ref_vals[:, :1]).astype(np.float64)
    score_err = float(np.max(np.abs(served_val - exact) / best))
    hits = sum(len(set(served_idx[s]) & set(ref_idx[s]))
               for s in range(len(sample)))
    miss_share = 1.0 - hits / (len(sample) * how_many)
    prefix = "control_" if control else ""
    checks.add(prefix + "score_err", score_err)
    checks.add(prefix + "miss_share", miss_share)
    if not control:
        checks.add("malformed_answers", malformed)
    return {"ref_vals": ref_vals, "ref_idx": ref_idx}


class Served:
    """The system under test, set up once: model, app, server thread."""


def setup(ctx) -> Served:
    import jax

    st = Served()
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

    serving = cfg["serving"]
    overlay = {
        "oryx.serving.application-resources": "oryx_tpu.serving.resources.als",
        "oryx.serving.compute.coalesce-window-ms": serving["coalesce-window-ms"],
        "oryx.serving.compute.coalesce-max-batch": serving["coalesce-max-batch"],
        "oryx.serving.compute.coalesce-inflight": serving["coalesce-inflight"],
        "oryx.serving.compute.precompile-batches": serving["precompile-batches"],
    }
    if ctx.trace:
        # the traced run reads every span of the window, not the newest 2048
        overlay["oryx.tracing.spans.ring-size"] = 1 << 20
    config = oryx_config.overlay_on(overlay, oryx_config.get_default())
    st.manager = _Manager()
    # make_app chooses the compile cache's directory: before any compile
    st.app = make_app(config, st.manager)
    phases.mark("import_and_app")

    st.y_host = factors.make(ctx.seed, "items", n_items, k)
    st.x_host = factors.make(ctx.seed, "users", n_users, k)
    phases.mark("factors_host")
    model = ALSServingModel(k, bool(cfg["implicit"]), float(cfg["sample-rate"]),
                            device_dtype=cfg["device-dtype"])
    model.bulk_load_items([f"i{j}" for j in range(n_items)], st.y_host)
    model.bulk_load_users([f"u{j}" for j in range(n_users)], st.x_host)
    phases.mark("bulk_load")
    snap = model.y_snapshot()
    jax.block_until_ready(snap.score_mat)
    phases.mark("upload_and_cast")
    for b in pow2_buckets(int(serving["coalesce-max-batch"])):
        model.top_n_batch(np.zeros((b, k), dtype=np.float32), st.how_many)
    phases.mark("warm_ladder")
    st.manager.model = model
    st.port = ioutils.choose_free_port()
    st.loop, st.thread = _serve(st.app, st.port)
    return st


def teardown(st: Served) -> None:
    """Stop the server and free the program's state on the device."""
    st.loop.call_soon_threadsafe(st.loop.stop)
    st.thread.join(timeout=20)
    st.manager.model = None
    st.app = None
    gc.collect()


def window(ctx, st: Served, mix: dict, seconds: float, first: bool = True) -> dict:
    """One measured window of ``mix`` against the served model."""
    from oryx_tpu.common import compilecache

    tmpdir = tempfile.mkdtemp(prefix="oryx-bench-")
    procs, outs, expected = _spawn_children(
        mix, st.sizes, ctx.seed, seconds, st.port, tmpdir)
    trace_dir = None
    try:
        if ctx.trace:
            trace_dir = ctx.start_trace()
        compiles0 = compilecache.compiles_total()
        counters0 = {c: _counter(c) for c in COUNTERS}
        wall0 = time.time()
        t_start = _release(procs, float(mix["lead_s"]))
        if first:
            ctx.phases.mark("children_ready")
            ctx.window_opens(t_start)
        time.sleep(max(0.0, t_start - time.monotonic()))
        watch = _ServerStalls(st.loop, t_start)
        with ctx.window_annotation():
            time.sleep(max(0.0, t_start + seconds - time.monotonic()))
        server_stalls = watch.stop()
        req = _collect(procs, outs, float(mix["timeout_s"]) + 60.0)
        if ctx.trace:
            ctx.stop_trace()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait()
            for pipe in (p.stdin, p.stdout):
                if pipe:
                    pipe.close()
        for f in os.listdir(tmpdir):
            os.unlink(os.path.join(tmpdir, f))
        os.rmdir(tmpdir)
    compiles = compilecache.compiles_total() - compiles0
    counters = {c: _counter(c) - counters0[c] for c in COUNTERS}
    order = np.argsort(req["index"])
    for key in ("index", "due", "sent", "done", "status"):
        req[key] = [req[key][i] for i in order]
    ok = sum(1 for s in req["status"] if s == 200)
    unanswered = (expected - len(req["index"]) if mix["loop"] == "open" else 0) \
        + sum(1 for s in req["status"] if s == 0)
    late = [s - d for s, d in zip(req["sent"], req["due"])]
    info = {"info": "window", "loop": mix["loop"], "expected": expected,
            "recorded": len(req["index"]), "ok": ok, "unanswered": unanswered,
            "compiles_in_window": compiles, "counters": counters,
            "generator_late_ms_p99": (
                float(np.percentile(late, 99)) * 1e3 if late else None),
            "generator_late_ms_max": max(late) * 1e3 if late else None,
            "generator_stalls": req["stalls"], "server_stalls": server_stalls}
    print(json.dumps(info), file=sys.stderr)
    return {"requests": req, "expected": expected, "t_start": t_start,
            "window_s": float(seconds), "counters": counters,
            "compiles": compiles, "unanswered": unanswered, "ok": ok,
            "wall0": wall0, "trace_dir": trace_dir, "info": info,
            "attempted": (len(req["index"]) if mix["loop"] == "closed"
                          else expected)}


def run(ctx) -> dict:
    cfg, mix = ctx.cell.config, ctx.sized(ctx.cell.traffic)
    st = setup(ctx)
    try:
        w = window(ctx, st, mix, ctx.seconds)
    finally:
        span_list = _span_dicts(0.0) if ctx.trace else []
        peak = ctx.memory_peak()
        teardown(st)
    req, sizes, how_many = w["requests"], st.sizes, st.how_many
    span_list = [s for s in span_list if s["start_wall"] >= w["wall0"]]

    # the program's state is freed: now the reference
    checks = Checks(cfg["limits"])
    reference = load_module("references", cfg["reference"])
    rng = np.random.default_rng([ctx.seed, 4])
    finished = sorted(i for i in req["bodies"])
    want = min(int(mix["sample_requests"]), len(finished))
    chosen = set(rng.choice(finished, size=want, replace=False).tolist()) \
        if want else set()
    if finished:
        # the request that took longest is always in the sample
        pos = {i: p for p, i in enumerate(req["index"])}
        chosen.add(max(finished, key=lambda i: (
            (req["done"][pos[i]] or 0) - req["due"][pos[i]])))
    chosen = sorted(chosen)
    user_of = _users_of_requests(mix, sizes, ctx.seed, ctx.seconds, chosen)
    sample = []
    for i in chosen:
        try:
            answer = [(int(e["id"][1:]), float(e["value"]))
                      for e in json.loads(req["bodies"][i])]
        except Exception:  # noqa: BLE001 — not the JSON the endpoint gives
            answer = []
        sample.append(answer)
    checks.add("unanswered", w["unanswered"])
    checks.add("compiles_in_window", w["compiles"])
    if chosen:
        queries = st.x_host[[user_of[i] for i in chosen]]
        compare(sample, queries, st.y_host, how_many, checks, reference, False)
        if ctx.control:
            cv, ci = reference.top_n(queries, st.y_host, how_many, control=True)
            csample = [list(zip(ci[s].tolist(), cv[s].tolist()))
                       for s in range(len(chosen))]
            compare(csample, queries, st.y_host, how_many, checks, reference,
                    True)
    else:
        checks.add("score_err", float("nan"))
    ctx.phases.mark("reference")

    return {
        "checks": checks, "attempted": w["attempted"],
        "failed": w["attempted"] - w["ok"], "memory_peak_bytes": peak,
        "obs": {
            "requests": req, "expected": w["expected"],
            "t_start": w["t_start"], "window_s": w["window_s"],
            "spans": span_list, "counters": w["counters"],
            "trace_dir": w["trace_dir"], "sizes": sizes,
            "worst_ms": float(mix["timeout_s"]) * 1e3,
            "index_of_trace": index_of_trace,
        },
    }


def _users_of_requests(mix, sizes, seed, seconds, chosen) -> dict:
    """The user index each chosen request asked for, made again from the
    seed (not read from the program)."""
    n_proc = int(mix["processes"])
    if mix["loop"] == "open":
        due = traffic.open_schedule(mix, seconds, seed)
        users = traffic.users_for(mix, len(due), sizes["users"], seed)
        return {i: int(users[i]) for i in chosen}
    per_child = int(mix["paths_per_child"])
    users = traffic.users_for(mix, per_child * n_proc, sizes["users"], seed)
    out = {}
    for i in chosen:
        child, kk = i % n_proc, i // n_proc
        out[i] = int(users[child::n_proc][kk % per_child])
    return out
