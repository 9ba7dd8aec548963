"""Driver: ``serve_als`` with Y split by rows over the chips of one host.

Everything a request passes is ``serve_als``'s: the HTTP app, the coalescer,
the load generators, the window, the comparison, the teardown. Only the
model differs: it is built with the mesh the serving manager builds from
``oryx.serving.compute.sharded`` (``ALSServingModelManager.mesh``), so the
store hands every chip its own row block and the top-N scan runs on every
shard and merges. Where each array lies, and each device's memory, go into
the result's ``obs`` (and onto stderr). The float32 brute force is the same
reference, given one row range a device and merged by its own rule.

A program whose store cannot place Y by shards is refused before anything
is allocated, where Y in float32 is more than one device holds: it could
only fail later, after minutes, with the device out of memory.
"""

from __future__ import annotations

import concurrent.futures as cf
import inspect
import json
import os
import sys

import numpy as np

from benchmarks.harness import factors
from benchmarks.harness.checks import Checks
from benchmarks.harness.loadgen import index_of_trace
from benchmarks.harness.manifest import load_module

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
base = load_module("drivers", "serve_als", _BENCH)
window, compare, teardown = base.window, base.compare, base.teardown

UPLOAD_PHASE = "shard_upload"


def _refuse_what_cannot_fit(mesh, n_items: int, features: int) -> None:
    import jax

    from oryx_tpu.models.als.vectors import FeatureVectorStore

    shards = "mesh" in inspect.signature(FeatureVectorStore.__init__).parameters
    stats = jax.local_devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    need = n_items * features * 4
    if limit and need > limit and not (shards and mesh is not None):
        raise SystemExit(
            f"Y is {need} bytes in float32 and one device holds {limit}: "
            "this program's FeatureVectorStore places all of Y on one "
            "device before any shard exists (RESOURCE_EXHAUSTED on device 0 "
            "after the whole set-up); refused before any allocation")


def _placement(snap) -> dict:
    """Where the snapshot's per-row arrays lie: shards, rows a shard."""
    out = {}
    for name in ("mat", "score_mat", "norms"):
        arr = getattr(snap, name, None)
        if arr is None:
            continue
        shards = arr.addressable_shards
        out[name] = {
            "dtype": str(arr.dtype), "rows": int(arr.shape[0]),
            "shards": len(shards),
            "devices": sorted({int(s.device.id) for s in shards}),
            "rows_a_shard": sorted({int(s.data.shape[0]) for s in shards}),
            "fully_replicated": bool(arr.sharding.is_fully_replicated),
        }
    return out


def _memory_by_device() -> list:
    import jax

    return [{"device": int(d.id),
             **{k: int((d.memory_stats() or {}).get(k, 0))
                for k in ("bytes_in_use", "peak_bytes_in_use")}}
            for d in jax.local_devices()]


def setup(ctx):
    import jax

    st = base.Served()
    cfg = ctx.cell.config
    st.cfg, st.sizes = cfg, ctx.sized(cfg)
    k, n_items, n_users = (st.sizes["features"], st.sizes["items"],
                           st.sizes["users"])
    st.how_many = int(cfg["how-many"])
    phases = ctx.phases

    from oryx_tpu.common import config as oryx_config
    from oryx_tpu.common import ioutils
    from oryx_tpu.models.als.serving import (ALSServingModel,
                                             ALSServingModelManager)
    from oryx_tpu.serving.app import make_app
    from oryx_tpu.serving.batcher import pow2_buckets

    serving = cfg["serving"]
    overlay = {
        "oryx.serving.application-resources": "oryx_tpu.serving.resources.als",
        "oryx.serving.compute.coalesce-window-ms": serving["coalesce-window-ms"],
        "oryx.serving.compute.coalesce-max-batch": serving["coalesce-max-batch"],
        "oryx.serving.compute.coalesce-inflight": serving["coalesce-inflight"],
        "oryx.serving.compute.precompile-batches": serving["precompile-batches"],
        "oryx.serving.compute.sharded": bool(serving["sharded"]),
    }
    if ctx.trace:
        overlay["oryx.tracing.spans.ring-size"] = 1 << 20
    config = oryx_config.overlay_on(overlay, oryx_config.get_default())
    st.manager = base._Manager()
    # make_app chooses the compile cache's directory: before any compile
    st.app = make_app(config, st.manager)
    # the mesh is the serving manager's own, from the option above
    mesh = ALSServingModelManager(config).mesh
    _refuse_what_cannot_fit(mesh, n_items, k)
    phases.mark("import_and_app")

    st.y_host = factors.make(ctx.seed, "items", n_items, k)
    st.x_host = factors.make(ctx.seed, "users", n_users, k)
    phases.mark("factors_host")
    model = ALSServingModel(k, bool(cfg["implicit"]), float(cfg["sample-rate"]),
                            mesh=mesh, device_dtype=cfg["device-dtype"])
    model.bulk_load_items(list(map("i{}".format, range(n_items))), st.y_host)
    model.bulk_load_users(list(map("u{}".format, range(n_users))), st.x_host)
    phases.mark("bulk_load")
    snap = model.y_snapshot()
    jax.block_until_ready(snap.score_mat)
    phases.mark(UPLOAD_PHASE)
    st.placement = {"shards": 1 if mesh is None else int(mesh.size),
                    "arrays": _placement(snap),
                    "after_upload": _memory_by_device()}
    for b in pow2_buckets(int(serving["coalesce-max-batch"])):
        model.top_n_batch(np.zeros((b, k), dtype=np.float32), st.how_many)
    phases.mark("warm_ladder")
    st.manager.model = model
    st.port = ioutils.choose_free_port()
    st.loop, st.thread = base._serve(st.app, st.port)
    return st


class _ByDevice:
    """The reference over one contiguous row range a device, the ranges'
    top lists merged as the reference merges its own blocks (best first,
    stable). The same numbers; the scan's uploads run side by side."""

    def __init__(self, reference):
        self.reference = reference
        self.exact_scores = reference.exact_scores

    def top_n(self, queries, items, keep, control: bool = False):
        import jax

        devices = jax.local_devices()
        edges = np.linspace(0, len(items), len(devices) + 1).astype(np.int64)

        def part(d):
            with jax.default_device(devices[d]):
                v, i = self.reference.top_n(
                    queries, items[edges[d]:edges[d + 1]], keep,
                    control=control)
            return v, i + edges[d]

        with cf.ThreadPoolExecutor(len(devices)) as pool:
            parts = list(pool.map(part, [d for d in range(len(devices))
                                         if edges[d + 1] > edges[d]]))
        v = np.concatenate([p[0] for p in parts], axis=1)
        i = np.concatenate([p[1] for p in parts], axis=1)
        order = np.argsort(-v, axis=1, kind="stable")[:, :keep]
        return np.take_along_axis(v, order, 1), np.take_along_axis(i, order, 1)


def run(ctx) -> dict:
    cfg, mix = ctx.cell.config, ctx.sized(ctx.cell.traffic)
    st = setup(ctx)
    try:
        w = window(ctx, st, mix, ctx.seconds)
    finally:
        span_list = base._span_dicts(0.0) if ctx.trace else []
        peak = ctx.memory_peak()
        st.placement["after_window"] = _memory_by_device()
        teardown(st)
    print(json.dumps({"info": "placement", **st.placement}), file=sys.stderr)
    req, sizes, how_many = w["requests"], st.sizes, st.how_many
    span_list = [s for s in span_list if s["start_wall"] >= w["wall0"]]

    # the program's state is freed: now the reference
    checks = Checks(cfg["limits"])
    reference = _ByDevice(load_module("references", cfg["reference"], _BENCH))
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
    user_of = base._users_of_requests(mix, sizes, ctx.seed, ctx.seconds, chosen)
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
            "trace_dir": w["trace_dir"],
            "sizes": dict(sizes, shards=st.placement["shards"]),
            "placement": st.placement,
            "worst_ms": float(mix["timeout_s"]) * 1e3,
            "index_of_trace": index_of_trace,
        },
    }
