"""Driver: ``serve_als`` for a model served under LSH (``sample-rate`` < 1),
the reference's "with LSH" rows.

Everything a request passes is ``serve_als``'s: the HTTP app, the coalescer,
the load generators, the window, the teardown. What differs: the snapshot's
build is its own phase, ``lsh_build`` (hashing every row, the bucket order,
the upload); the batch ladder is warmed by the program's own
``warm_bucket``, which compiles every selection a flush of each width can
make; the load generators keep EVERY answer, and
``non_candidates_in_answers`` counts the answered items of the whole window
that lie outside their user's candidate buckets; the 256 sampled answers
(the slowest always in) are held to ``references/als_topn_lsh.py``, which is
given the model's hyperplanes, read once after the window like its factors,
and computes every hash itself. ``recall_vs_exact`` — the share of each
sampled user's exact top ``how-many`` over ALL items that the answer holds,
LSH's own cost in quality — is printed, with no limit.

A program whose warm ladder takes longer than ``WARM_LIMIT_S`` at one width
is stopped there with an error, before the window: a window behind it would
measure a queue, not the scan.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from benchmarks.harness import factors
from benchmarks.harness.checks import Checks
from benchmarks.harness.loadgen import index_of_trace
from benchmarks.harness.manifest import load_module

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
base = load_module("drivers", "serve_als", _BENCH)
window, teardown = base.window, base.teardown
SCANNED, BUCKETS = ("oryx_serving_lsh_scanned_rows_total",
                    "oryx_serving_lsh_candidate_buckets_total")
base.COUNTERS = base.COUNTERS + (SCANNED, BUCKETS)

#: Seconds one width of the warm ladder may take, compiles and its real
#: executions included (a width takes a few seconds on the chip).
WARM_LIMIT_S = 240.0


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
        overlay["oryx.tracing.spans.ring-size"] = 1 << 20
    config = oryx_config.overlay_on(overlay, oryx_config.get_default())
    st.manager = base._Manager()
    # make_app chooses the compile cache's directory: before any compile
    st.app = make_app(config, st.manager)
    phases.mark("import_and_app")

    st.y_host = factors.make(ctx.seed, "items", n_items, k)
    st.x_host = factors.make(ctx.seed, "users", n_users, k)
    phases.mark("factors_host")
    model = ALSServingModel(k, bool(cfg["implicit"]), float(cfg["sample-rate"]),
                            device_dtype=cfg["device-dtype"])
    want = (cfg["lsh"]["hashes"], cfg["lsh"]["max-bits-differing"])
    got = (model.lsh.num_hashes, model.lsh.max_bits_differing)
    if got != want:
        raise SystemExit(f"the program hashes with {got} (hashes, bits "
                         f"differing) where the configuration states {want}")
    model.bulk_load_items(list(map("i{}".format, range(n_items))), st.y_host)
    model.bulk_load_users(list(map("u{}".format, range(n_users))), st.x_host)
    phases.mark("bulk_load")
    snap = model.y_snapshot()
    jax.block_until_ready(snap.device_arrays())
    phases.mark("lsh_build")
    for b in pow2_buckets(int(serving["coalesce-max-batch"])):
        t0 = time.monotonic()
        model.warm_bucket(b, st.how_many)
        took = time.monotonic() - t0
        if took > WARM_LIMIT_S:
            raise SystemExit(
                f"the warm ladder took {took:.0f} s at width {b}, past "
                f"{WARM_LIMIT_S:.0f} s: stopped before the window")
    phases.mark("warm_ladder")
    st.manager.model = model
    st.port = ioutils.choose_free_port()
    st.loop, st.thread = base._serve(st.app, st.port)
    return st


def _answer(body: str) -> list:
    try:
        return [(int(e["id"][1:]), float(e["value"])) for e in json.loads(body)]
    except Exception:  # noqa: BLE001 — not the JSON the endpoint gives
        return []


class _Hashing:
    """``references/als_topn_lsh.py`` with the model's hyperplanes bound, in
    the shape ``serve_als.compare`` asks a reference in."""

    def __init__(self, reference, hyperplanes, radius, item_codes):
        self.reference, self.hyperplanes, self.radius = (
            reference, hyperplanes, radius)
        self.item_codes = item_codes
        self.exact_scores = reference.exact_scores

    def top_n(self, queries, items, keep, control: bool = False):
        return self.reference.top_n(queries, items, keep, self.hyperplanes,
                                    self.radius, control=control,
                                    item_codes=self.item_codes)


def run(ctx) -> dict:
    cfg, mix = ctx.cell.config, ctx.sized(ctx.cell.traffic)
    st = setup(ctx)
    try:
        # every answer's body is kept, not a share of them
        w = window(ctx, st, dict(mix, sample_requests=10 ** 9), ctx.seconds)
        lsh = st.manager.model.lsh
        hyperplanes = np.array(lsh.hyperplanes, dtype=np.float32)
        radius = int(lsh.max_bits_differing)
    finally:
        span_list = base._span_dicts(0.0) if ctx.trace else []
        peak = ctx.memory_peak()
        teardown(st)
    req, sizes, how_many = w["requests"], st.sizes, st.how_many
    span_list = [s for s in span_list if s["start_wall"] >= w["wall0"]]

    # the program's state is freed: now the reference
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
    user_of = base._users_of_requests(
        mix, sizes, ctx.seed, ctx.seconds, finished)
    checks.add("unanswered", w["unanswered"])
    checks.add("compiles_in_window", w["compiles"])
    answers = [_answer(req["bodies"][i]) for i in finished]
    checks.add("non_candidates_in_answers", reference.non_candidates(
        st.x_host[[user_of[i] for i in finished]],
        [[r for r, _ in a if 0 <= r < len(st.y_host)] for a in answers],
        st.y_host, hyperplanes, radius))
    recall = None
    if chosen:
        queries = st.x_host[[user_of[i] for i in chosen]]
        sample = [_answer(req["bodies"][i]) for i in chosen]
        bound = _Hashing(reference, hyperplanes, radius,
                         reference.sign_codes(st.y_host, hyperplanes))
        base.compare(sample, queries, st.y_host, how_many, checks, bound,
                     False)
        if ctx.control:
            cv, ci = bound.top_n(queries, st.y_host, how_many, control=True)
            csample = [list(zip(ci[s].tolist(), cv[s].tolist()))
                       for s in range(len(chosen))]
            base.compare(csample, queries, st.y_host, how_many, checks, bound,
                         True)
        _, exact = reference._plain.top_n(queries, st.y_host, how_many)
        recall = float(np.mean([
            len({r for r, _ in a} & set(e.tolist())) / how_many
            for a, e in zip(sample, exact)]))
    else:
        checks.add("score_err", float("nan"))
    ctx.phases.mark("reference")
    print(json.dumps({"info": "lsh", "answers_counted": len(finished),
                      "sampled": len(chosen), "recall_vs_exact": recall,
                      "hashes": int(hyperplanes.shape[0]),
                      "max_bits_differing": radius}), file=sys.stderr)

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
