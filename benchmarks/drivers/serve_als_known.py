"""Driver: ``serve_als`` for a model whose users have known items, asked the
way the reference's endpoint is asked by default: ``considerKnownItems`` left
out, so every answer leaves out what its user already has.

Everything a request passes is ``serve_als``'s: the HTTP app, the coalescer,
the load generators, the window, the teardown. What differs: every user gets
known items from the seed (``harness/known_items.py``), loaded in bulk
beside the factors; the batch ladder is warmed by the program's own
``warm_bucket`` (what a deployment's warmer runs: every width a flush can
ask for, not the exclusion-free program alone); the load generators keep
EVERY answer, and ``known_in_answers`` counts the answers of the whole window
that hold an item their user knows; the 256 sampled answers (the slowest
always in) are held to ``references/als_topn_known.py``, which is given the
generator's table and never the model's.

With items uniform over five million, a user's twenty known items are among
their own ten best once in 25,000 requests: the window alone would let a
program that forgot the exclusion pass one run in two. So after the window,
before the model is freed, ``probes`` of the sampled users are told (the way
an ``UP`` message tells the model: ``add_known_items``) that they now know
the best item of the answer they just got, and are asked again. Those
answers join the sample and the count: an exclusion left out, or known items
resolved against a stale row order, fails at once.

A program that can only take known items a user at a time, and pads each
flush's exclusions to a width of its own, is refused before anything is
allocated: every new width would compile inside the window (minutes of a
queue growing behind each compile, on a chip whose memory the widest
exclusion-carrying batch program does not fit).
"""

from __future__ import annotations

import json
import os
import sys
import time
import urllib.request

import numpy as np

from benchmarks.harness import factors, known_items
from benchmarks.harness.checks import Checks
from benchmarks.harness.loadgen import index_of_trace
from benchmarks.harness.manifest import load_module

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
base = load_module("drivers", "serve_als", _BENCH)
window, teardown = base.window, base.teardown
# the window also reads what the flushes were handed to leave out
EXCLUDED, OVERFLOWED = ("oryx_serving_excluded_entries_total",
                        "oryx_serving_exclusion_overflow_total")
base.COUNTERS = base.COUNTERS + (EXCLUDED, OVERFLOWED)


def _refuse_a_program_without_bulk_known_items() -> None:
    from oryx_tpu.models.als.serving import ALSServingModel

    if not hasattr(ALSServingModel, "bulk_load_known_items"):
        raise SystemExit(
            "this program's ALSServingModel has no bulk_load_known_items: "
            "it takes known items a user at a time and compiles a batch "
            "program for every exclusion width a flush first shows "
            "(compiles_in_window > 0 by construction); refused before any "
            "allocation")


def setup(ctx):
    import jax

    _refuse_a_program_without_bulk_known_items()
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
    st.known = known_items.make(ctx.seed, n_users, n_items,
                                float(cfg["known-items"]["mean"]))
    phases.mark("known_items_host")
    model = ALSServingModel(k, bool(cfg["implicit"]), float(cfg["sample-rate"]),
                            device_dtype=cfg["device-dtype"])
    item_ids = list(map("i{}".format, range(n_items)))
    user_ids = list(map("u{}".format, range(n_users)))
    model.bulk_load_items(item_ids, st.y_host)
    model.bulk_load_users(user_ids, st.x_host)
    phases.mark("bulk_load")
    model.bulk_load_known_items(user_ids, *st.known, item_ids)
    phases.mark("known_items_load")
    snap = model.y_snapshot()
    jax.block_until_ready(snap.score_mat)
    phases.mark("upload_and_cast")
    for b in pow2_buckets(int(serving["coalesce-max-batch"])):
        model.warm_bucket(b, st.how_many)
    # the first flush that leaves something out also maps item codes to
    # rows of this snapshot, once: set-up, like the ladder
    model.top_n_batch(st.x_host[:1], st.how_many,
                      excluded=[model.known_item_codes(user_ids[0])])
    phases.mark("warm_ladder")
    st.manager.model = model
    st.port = ioutils.choose_free_port()
    st.loop, st.thread = base._serve(st.app, st.port)
    return st


class _Knowing:
    """``references/als_topn_known.py`` with the sample's known rows bound,
    in the shape ``serve_als.compare`` asks a reference in."""

    def __init__(self, reference, known):
        self.reference, self.known = reference, known
        self.exact_scores = reference.exact_scores

    def top_n(self, queries, items, keep, control: bool = False):
        return self.reference.top_n(queries, items, keep, self.known,
                                    control=control)


def _answer(body: str) -> list:
    try:
        return [(int(e["id"][1:]), float(e["value"])) for e in json.loads(body)]
    except Exception:  # noqa: BLE001 — not the JSON the endpoint gives
        return []


def _probe(st, mix, users: list, answers: list) -> tuple:
    """Tell the model each of ``users`` now knows the best item of the
    answer they got, and ask again: (answers, known rows) a probe."""
    model, got, known = st.manager.model, [], []
    for user, answer in zip(users, answers):
        mine = known_items.of_user(*st.known, user)
        if answer:
            model.add_known_items(f"u{user}", [f"i{answer[0][0]}"])
            mine = np.append(mine, np.int32(answer[0][0]))
        url = (f"http://127.0.0.1:{st.port}"
               + mix["endpoint"].format(user=f"u{user}"))
        with urllib.request.urlopen(url, timeout=float(mix["timeout_s"])) as r:
            got.append(_answer(r.read().decode("utf-8", "replace")))
        known.append(mine)
    return got, known


def _known_in(answer: list, known: np.ndarray) -> int:
    return len({i for i, _ in answer} & set(known.tolist()))


def run(ctx) -> dict:
    cfg, mix = ctx.cell.config, ctx.sized(ctx.cell.traffic)
    st = setup(ctx)
    try:
        # every answer's body is kept, not a share of them
        w = window(ctx, st, dict(mix, sample_requests=10 ** 9), ctx.seconds)
        req, sizes, how_many = w["requests"], st.sizes, st.how_many
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
        sample = [_answer(req["bodies"][i]) for i in chosen]
        users = [user_of[i] for i in chosen]
        known = [known_items.of_user(*st.known, u) for u in users]
        n_probe = min(int(cfg["known-items"]["probes"]), len(chosen))
        wall_probes = time.time()
        probed, probed_known = _probe(
            st, mix, users[:n_probe], sample[:n_probe])
        ctx.phases.mark("probes")
    finally:
        span_list = base._span_dicts(0.0) if ctx.trace else []
        peak = ctx.memory_peak()
        teardown(st)
    # the window's spans: the probes' flushes are not the cell's traffic
    span_list = [s for s in span_list
                 if w["wall0"] <= s["start_wall"] < wall_probes]

    # the program's state is freed: now the reference
    checks = Checks(cfg["limits"])
    reference = load_module("references", cfg["reference"], _BENCH)
    checks.add("unanswered", w["unanswered"])
    checks.add("compiles_in_window", w["compiles"])
    checks.add("known_in_answers", sum(
        _known_in(_answer(req["bodies"][i]),
                  known_items.of_user(*st.known, user_of[i]))
        for i in finished) + sum(
        _known_in(a, k) for a, k in zip(probed, probed_known)))
    sample, known = sample + probed, known + probed_known
    if sample:
        queries = st.x_host[users + users[:n_probe]]
        bound = _Knowing(reference, known)
        base.compare(sample, queries, st.y_host, how_many, checks, bound, False)
        if ctx.control:
            cv, ci = bound.top_n(queries, st.y_host, how_many, control=True)
            csample = [list(zip(ci[s].tolist(), cv[s].tolist()))
                       for s in range(len(sample))]
            base.compare(csample, queries, st.y_host, how_many, checks, bound,
                         True)
    else:
        checks.add("score_err", float("nan"))
    ctx.phases.mark("reference")
    print(json.dumps({"info": "known_items", "pairs": int(len(st.known[1])),
                      "answers_counted": len(finished) + len(probed),
                      "probes": len(probed)}), file=sys.stderr)

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
