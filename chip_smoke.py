#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the ALS lambda loop through the entry points a deployment
uses, over the ``memory:`` broker:

    batch tier trains ALS  →  MODEL + factor rows on the update topic
      →  serving tier loads them into device memory and warms its ladder
      →  /recommend answers through the coalescer
      →  one POST /pref, folded in by the speed tier, changes an answer

at the full width of the model the repo publishes (50 features, implicit,
100k users × 20k items × 2M interactions generated from a seed), and checks
RESULTS — kernel parity at k=50 and k=250, a held-out AUC gate, the trained
model against the in-tree reference formulation, top-10 answers against a
numpy float32 brute force — and that nothing on the way hid the device: no
skipped candidate, quarantine, consumer restart, degraded batch, shed or
expired request, swallowed compile failure or kernel fallback.

    python3 chip_smoke.py              # needs a TPU; anything else is exit 2
    python3 chip_smoke.py --tiny-cpu   # tier-1's explicit tiny mode, on a CPU

With four or more devices the same run shards training over every device
and serving over a 4-way mesh, and checks PLACEMENT as well as results.

Stdout carries two lines, each one JSON object. The first is the summary
(``{"ok": true, "device": {...}, ..., "claim": null}``, also written to
``chiprun_out/chip_smoke/summary.json``); times in it are set-up (seconds to
first MODEL, warm ladder, compiles, cache hits), never a rate: rates belong
to the benchmark. The LAST line is the verdict the driver reads, with exactly
these keys: ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``, the device as jax reports it. On any failure the script
prints the reason to stderr, prints nothing to stdout, and exits non-zero.
Everything it writes lands under ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import dataclasses
import json
import logging
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
SEED = 21


@dataclasses.dataclass(frozen=True)
class Size:
    users: int
    items: int
    interactions: int
    features: int
    clusters: int  # planted taste groups
    kernel_features: tuple  # widths the kernel-parity phase runs at
    auc_band: float  # published model vs reference formulation, same data
    wait_sec: float  # ceiling on each wait below


#: Full width of the model the repo publishes (BASELINE.md's 50-feature
#: row), sized so each side packs into several row blocks (13 user blocks,
#: 3 item blocks at k=50) with several slots per hot row.
FULL = Size(users=100_000, items=20_000, interactions=2_000_000, features=50,
            clusters=64, kernel_features=(50, 250), auc_band=0.02,
            wait_sec=900.0)
#: Tier-1's tiny mode: the same body, a few thousand interactions. Its
#: hold-out is 200 interactions, so two trainings from different random
#: starts differ by sampling noise the full size does not have.
TINY = Size(users=240, items=90, interactions=4_000, features=8, clusters=6,
            kernel_features=(8,), auc_band=0.05, wait_sec=120.0)

ITERATIONS = 3
TEST_FRACTION = 0.05
HOW_MANY = 10
N_RECOMMEND = 64  # concurrent /recommend requests (two coalescer waves)

# -- tolerances, written down before the chip run, from the dtype -----------
# A TPU at default precision rounds float32 MXU operands to bfloat16: 8
# mantissa bits, so one operand is off by at most 2^-9 of itself and a
# product of two by ~2^-8 = 0.4%. Sums of many products with independent
# roundings stay near that bound relative to the LARGEST term. The CPU runs
# the same checks in exact float32 and passes them with room to spare.
AUC_GATE = 0.75  # the planted-structure bar of tests/test_batch_resume_it.py
GG_TOL_F32 = 1e-2  # of the largest |entry|: bf16-rounded MXU operands
GG_TOL_BF16 = 2e-2  # the CPU differential gate's own bf16 tolerance
SPD_TOL = {False: 1e-4, True: 1e-3}  # keyed by k >= 100; float32 throughout
# sums go through the MXU once (points rounded: 2^-9 each); the cost is a
# sum over 3000 points of a cancelling expression, each off by at most
# 2|p||c|·2^-8 ≈ 8% of its d² on the smoke's data, independent in sign:
# ~8%/sqrt(3000) of the total. Counts must be exact.
KMEANS_TOL = 1e-2
HALF_ITER_TOL = 5e-2  # factors, kernels vs reference formulation, of max |x|
# the serving scan is bfloat16 on a TPU (models/als/serving.py): both
# operands rounded, f32 accumulation; top-10 may swap near-ties, and
# approx_max_k promises recall 0.99
SCORE_TOL = 2e-2  # of the user's largest |score|
TOPN_OVERLAP = 0.8  # mean over sampled users of |served ∩ exact| / 10


class SmokeFailure(Exception):
    """A phase of the smoke failed; the message is the reason."""


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.monotonic()


# ---------------------------------------------------------------------------
# nothing hides the device: every WARNING the program logs is a finding
# ---------------------------------------------------------------------------

#: Warnings that describe the data, not a degraded path.
_BENIGN = ("slotted COO padding ratio",)


class _Findings(logging.Handler):
    """Collects what the program says at WARNING and above (robustness
    features log there when they fire), plus the two INFO lines with which
    ``MLUpdate`` skips a generation's publish."""

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.records: list = []
        self.evals: list = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if record.name == "oryx_tpu.ml.mlupdate" and msg.startswith("candidate"):
            if "eval =" in msg and record.args:
                self.evals.append(record.args[-1])
        skipped = record.name == "oryx_tpu.ml.mlupdate" and (
            "unable to build any model" in msg or "not publishing" in msg)
        if record.levelno < logging.WARNING and not skipped:
            return
        if any(b in msg for b in _BENIGN):
            return
        if record.exc_info and record.exc_info[1] is not None:
            e = record.exc_info[1]
            msg += f" [{type(e).__name__}: {e}]"
        self.records.append(f"{record.name}: {msg}")

    def check(self) -> None:
        if self.records:
            raise SmokeFailure(
                "the program logged a fallback or failure on the smoke's "
                "path: " + " | ".join(self.records[:6]))


# ---------------------------------------------------------------------------
# input: planted low-rank structure, heavy-tailed degrees, from a seed
# ---------------------------------------------------------------------------


def generate_interactions(size: Size, seed: int):
    """(users, items) int arrays of distinct interactions. Users and items
    belong to planted taste groups; 85% of a user's interactions fall in
    its own group, the rest anywhere — block structure a rank-``clusters``
    model recovers. Degrees are heavy-tailed on both sides (Zipf-like item
    popularity, lognormal user activity), as interaction logs are."""
    rng = np.random.default_rng(seed)
    user_group = rng.integers(0, size.clusters, size.users)
    item_group = rng.integers(0, size.clusters, size.items)
    popularity = 1.0 / np.arange(1, size.items + 1) ** 0.8
    rng.shuffle(popularity)
    p_all = popularity / popularity.sum()
    members = [np.flatnonzero(item_group == g) for g in range(size.clusters)]
    members = [m if m.size else np.arange(size.items) for m in members]
    activity = rng.lognormal(0.0, 1.0, size.users)
    p_user = activity / activity.sum()

    def draw(n: int) -> np.ndarray:
        users = rng.choice(size.users, n, p=p_user)
        in_group = rng.random(n) < 0.85
        items = np.empty(n, dtype=np.int64)
        items[~in_group] = rng.choice(size.items, int((~in_group).sum()),
                                      p=p_all)
        groups = user_group[users]
        for g, m in enumerate(members):
            sel = np.flatnonzero(in_group & (groups == g))
            p = popularity[m] / popularity[m].sum()
            items[sel] = m[rng.choice(m.size, sel.size, p=p)]
        return users * size.items + items

    # popular pairs repeat: draw until enough DISTINCT pairs exist
    pairs = np.empty(0, dtype=np.int64)
    while pairs.size < size.interactions:
        short = size.interactions - pairs.size
        pairs = np.union1d(pairs, draw(int(short * 1.3) + 64))
    rng.shuffle(pairs)
    pairs = pairs[: size.interactions]
    return pairs // size.items, pairs % size.items


def input_lines(users, items) -> list:
    """``user,item,strength,timestamp`` lines in arrival order. Timestamps
    follow the (shuffled) order, so ALSUpdate's time-ordered hold-out is a
    random sample of interactions rather than the last users' whole rows."""
    t0 = 1_700_000_000_000
    return [f"u{u},i{i},1,{t0 + n}"
            for n, (u, i) in enumerate(zip(users.tolist(), items.tolist()))]


# ---------------------------------------------------------------------------
# phase 1: every Pallas kernel against its reference
# ---------------------------------------------------------------------------


def check_kernels(features: tuple, on_tpu: bool) -> dict:
    """The three kernels — compiled on a TPU, interpreted elsewhere —
    against numpy references at each width, f32 and bf16, with empty rows
    and pad slots. Returns the worst relative error per kernel."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.ops import pallas_kernels as pk

    interpret = not on_tpu
    rng = np.random.default_rng(SEED)
    worst: dict = {}

    def note(name, err, tol, what=""):
        worst[name] = max(worst.get(name, 0.0), float(err))
        if not err < tol:
            raise SmokeFailure(f"kernel {name} k={k} {what}: relative error "
                               f"{err:.3g} exceeds {tol:g}")

    for k in features:
        # gather-Gramian: 64 rows, a third of them empty, 8 pad slots
        block, t, n_opp = 64, 16, 600
        owners = np.sort(rng.choice(block, 96) // 3 * 3)
        srow = np.concatenate([owners, np.full(8, block)]).astype(np.int32)
        s = len(srow)
        slens = np.where(srow < block, rng.integers(1, t + 1, s),
                         0).astype(np.int32)
        valid = np.arange(t)[None, :] < slens[:, None]
        mask = valid.astype(np.float32)
        # entries past a slot's length name a row of NaN: the kernel issues
        # no copy for them, and one that did would poison the Gramian
        scols = np.where(
            valid, np.sort(rng.integers(0, n_opp, (s, t)), axis=1), n_opp,
        ).astype(np.int32)
        w = rng.random((s, t), dtype=np.float32) * 2.0 * mask
        coef = rng.standard_normal((s, t)).astype(np.float32) * mask
        y = rng.standard_normal((n_opp + 1, k)).astype(np.float32)
        y[n_opp] = np.nan
        gg = jax.jit(lambda *a: pk.gather_gramian_accumulate(
            *a, block=block, interpret=interpret))
        for dtype, tol in ((jnp.float32, GG_TOL_F32), (jnp.bfloat16, GG_TOL_BF16)):
            yj = jnp.asarray(y).astype(dtype)
            # reference on the operands the MXU is handed, in float64
            y_ref = np.nan_to_num(
                np.asarray(yj.astype(jnp.float32), dtype=np.float64))
            yg = y_ref[scols]
            ra = np.zeros((block + 1, k, k))
            rb = np.zeros((block + 1, k))
            np.add.at(ra, srow, np.einsum("st,sti,stj->sij", w, yg, yg))
            np.add.at(rb, srow, np.einsum("st,sti->si", coef, yg))
            big_a, big_b = (np.asarray(o) for o in gg(
                yj, jnp.asarray(srow), jnp.asarray(slens), jnp.asarray(scols),
                jnp.asarray(w), jnp.asarray(coef)))
            if not (np.isfinite(big_a).all() and np.isfinite(big_b).all()):
                raise SmokeFailure(f"gather_gramian k={k}: non-finite output")
            scale = max(np.abs(ra).max(), np.abs(rb).max())
            name = f"gather_gramian/{jnp.dtype(dtype).name}"
            note(name, np.abs(big_a - ra).max() / scale, tol, "A")
            note(name, np.abs(big_b - rb).max() / scale, tol, "b")
            untouched = np.setdiff1d(np.arange(block + 1), srow)
            if big_a[untouched].any() or big_b[untouched].any():
                raise SmokeFailure(
                    f"gather_gramian k={k}: a row no slot names is not zero")

        # SPD solve: a batch that straddles the tile of the kernel that
        # solves this width (blocked past 128 features)
        n = pk.spd_solve_path(k)[1] * 2 + 5
        m = rng.standard_normal((n, k, k)).astype(np.float32) * 0.3
        a = np.einsum("bij,bkj->bik", m, m) + 2.0 * np.eye(k, dtype=np.float32)
        rhs = rng.standard_normal((n, k)).astype(np.float32)
        x = np.asarray(pk.spd_solve_batched(a, rhs, interpret=interpret))
        ref = np.linalg.solve(a.astype(np.float64), rhs.astype(np.float64)[..., None])[..., 0]
        if not np.isfinite(x).all():
            raise SmokeFailure(f"spd_solve k={k}: non-finite output")
        note("spd_solve", np.abs(x - ref).max() / np.abs(ref).max(),
             SPD_TOL[k >= 100])

        # k-means sweep: 20 well-separated clusters in k dims. The kernel
        # gets d² as |p|² − 2p·c + |c|² with the cross term on the MXU, so
        # each point's cost is off by up to 2|p||c|·2^-8 — which must stay
        # small against d² itself: clusters as wide (σ=1) as a third of
        # their distance from the origin, not pinpoints far from it
        spread = 3.0 * max(1.0, (50 / k) ** 0.5)  # separated at small k too
        centers = rng.standard_normal((20, k)).astype(np.float32) * spread
        pts = (centers[rng.integers(0, 20, 3000)]
               + rng.standard_normal((3000, k)).astype(np.float32))
        d2 = ((pts[:, None, :].astype(np.float64) - centers[None]) ** 2).sum(-1)
        assign = d2.argmin(axis=1)
        wts = np.ones(3000, dtype=np.float32)
        sums, counts, cost = (np.asarray(o) for o in pk.kmeans_assign_accumulate(
            pts, wts, centers, interpret=interpret))
        rs = np.zeros((20, k))
        np.add.at(rs, assign, pts.astype(np.float64))
        rcost = d2.min(axis=1).sum()
        if not np.array_equal(counts, np.bincount(assign, minlength=20)):
            raise SmokeFailure(f"kmeans k={k}: cluster counts differ")
        note("kmeans", np.abs(sums - rs).max() / np.abs(rs).max(), KMEANS_TOL,
             "sums")
        note("kmeans", abs(float(cost) - rcost) / rcost, KMEANS_TOL, "cost")
    return {name: round(err, 7) for name, err in sorted(worst.items())}


# ---------------------------------------------------------------------------
# the lambda loop
# ---------------------------------------------------------------------------


def _metric(snapshot: dict, name: str, label: str = "") -> float:
    """Sum of a metric family's samples whose label string contains
    ``label`` (0 when the family has never been touched)."""
    return float(sum(v for ls, v in snapshot.get(name, {}).items()
                     if label in ls))


def _wait(what: str, cond, findings: _Findings, timeout: float,
          poll: float = 0.2):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        findings.check()
        got = cond()
        if got:
            return got
        time.sleep(poll)
    findings.check()
    raise SmokeFailure(f"timed out after {timeout:.0f}s waiting for {what}")


def run_lambda_loop(size: Size, lines: list, users, items, findings: _Findings,
                    n_devices: int, on_tpu: bool) -> dict:
    import httpx

    from oryx_tpu.common import compilecache
    from oryx_tpu.common import config as cfg
    from oryx_tpu.common import ioutils
    from oryx_tpu.common import metrics as metrics_mod
    from oryx_tpu.lambda_rt.batch import BatchLayer
    from oryx_tpu.lambda_rt.speed import SpeedLayer
    from oryx_tpu.serving.app import ServingLayer
    from oryx_tpu.transport import topic as tp

    registry = metrics_mod.default_registry()
    port = ioutils.choose_free_port()
    overlay = {
        "oryx.id": "chip-smoke",
        "oryx.batch.update-class": "oryx_tpu.models.als.update.ALSUpdate",
        "oryx.speed.model-manager-class":
            "oryx_tpu.models.als.speed.ALSSpeedModelManager",
        "oryx.serving.model-manager-class":
            "oryx_tpu.models.als.serving.ALSServingModelManager",
        "oryx.serving.application-resources": "oryx_tpu.serving.resources.als",
        "oryx.serving.api.port": port,
        "oryx.batch.storage.data-dir": os.path.join(OUT_DIR, "data"),
        "oryx.batch.storage.model-dir": os.path.join(OUT_DIR, "model"),
        "oryx.als.iterations": ITERATIONS,
        "oryx.als.hyperparams.features": size.features,
        "oryx.ml.eval.test-fraction": TEST_FRACTION,
        "oryx.ml.eval.candidates": 1,
        # the generation's own held-out eval gates its own publish
        "oryx.ml.eval.threshold": AUC_GATE,
        # what conf/als-example.conf recommends for an accelerator: the
        # coalescing defaults stand, the warm ladder is switched on
        "oryx.serving.compute.precompile-batches": True,
    }
    sharded = n_devices >= 4
    if sharded:
        overlay["oryx.serving.compute.sharded"] = True
    config = cfg.overlay_on(overlay, cfg.get_default())
    tp.reset_memory_brokers()
    tp.maybe_create_topics(config, "input-topic", "update-topic")
    broker = tp.get_broker("memory:")
    in_topic = config.get_string("oryx.input-topic.message.topic")
    up_topic = config.get_string("oryx.update-topic.message.topic")

    serving = ServingLayer(config)
    batch = BatchLayer(config)
    speed = SpeedLayer(config)
    out: dict = {"sharded": sharded}
    compiles0 = compilecache.compiles_total()
    try:
        serving.start()
        producer = tp.TopicProducerImpl("memory:", in_topic)
        t_in = time.monotonic()
        for line in lines:
            producer.send(None, line)
        producer.close()
        log(f"appended {len(lines)} input lines in "
            f"{time.monotonic() - t_in:.1f}s")
        # a layer resumes from the offsets stored under its oryx.id, else
        # from the head. The batch tier has consumed nothing: say so, and
        # its first generation takes the whole backlog in one slice. The
        # speed tier has no stored offset and starts at the head: it folds
        # in only what arrives from here on (the POST /pref below).
        batch.store_input_offset({0: 0})
        batch.start(interval_sec=2.0)
        speed.start(interval_sec=1.0)

        t_gen = time.monotonic()
        _wait("MODEL on the update topic",
              lambda: broker.size(up_topic) > 0, findings, size.wait_sec)
        out["seconds_to_first_model"] = round(time.monotonic() - t_gen, 1)
        log(f"first update-topic message after "
            f"{out['seconds_to_first_model']}s")
        _wait("the batch generation to finish",
              lambda: _metric(registry.snapshot(), "oryx_step_items_total",
                              'tier="batch"') >= len(lines),
              findings, size.wait_sec)
        out["generation_seconds"] = round(time.monotonic() - t_gen, 1)
        batch.close()  # everything below flows through speed + serving alone
        if not findings.evals:
            raise SmokeFailure("the generation evaluated no candidate")
        out["generation_auc"] = round(float(findings.evals[-1]), 4)

        base = f"http://127.0.0.1:{port}"
        with httpx.Client(base_url=base, timeout=120) as client:

            def ready():
                r = client.get("/readyz")
                d = r.json()
                warm = d.get("warmup", {})
                return (r.status_code == 200 and warm.get("total", 0) > 0
                        and warm.get("done") == warm.get("total")) and d

            detail = _wait("/readyz 200 with the warm ladder complete", ready,
                           findings, size.wait_sec, poll=0.5)
            # the ladder runs while the factor rows are still streaming in;
            # its own clock, not how long this loop happened to wait
            out["warm_ladder_seconds"] = round(_metric(
                registry.snapshot(), "oryx_warmup_seconds_sum",
                'scope="model"'), 1)
            out["warmup"] = detail["warmup"]
            model = serving.manager.get_model()
            _wait("every published factor row to load",
                  lambda: model.get_fraction_loaded() >= 1.0, findings,
                  size.wait_sec)
            log(f"serving ready: warmup {detail['warmup']}")
            out.update(_check_published(size, users, items, client, config,
                                        broker, up_topic, findings, on_tpu))
            if sharded:
                out["placement"] = _check_placement(batch, serving, size)
    finally:
        speed.close()
        batch.close()
        serving.close()
        tp.reset_memory_brokers()
        # the stored input and the factor files are far more than the chip
        # tool carries back; the summary is what stays
        for sub in ("data", "model"):
            shutil.rmtree(os.path.join(OUT_DIR, sub), ignore_errors=True)

    findings.check()
    snap = registry.snapshot()
    hidden = {
        name: _metric(snap, name)
        for name in (
            "oryx_quarantined_generations_total",
            "oryx_layer_failures_total",
            "oryx_serving_consumer_restarts_total",
            "oryx_breaker_degraded_requests_total",
            "oryx_circuit_breaker_transitions_total",
            "oryx_shed_requests_total",
            "oryx_coalescer_deadline_dropped_total",
            "oryx_retries_total",
            "oryx_corrupt_records_total",
        )
    }
    hidden["http_5xx"] = sum(
        v for ls, v in snap.get("oryx_serving_requests_total", {}).items()
        if 'status="5' in ls and 'route="/readyz"' not in ls)
    fired = {k: v for k, v in hidden.items() if v}
    if fired:
        raise SmokeFailure(f"robustness machinery fired on the smoke's path: {fired}")
    flushes = _metric(snap, "oryx_coalescer_batch_size_count")
    riders = _metric(snap, "oryx_coalescer_batch_size_sum")
    if not riders > flushes > 0:
        raise SmokeFailure(
            f"the coalescer never formed a batch larger than one "
            f"({riders:.0f} requests in {flushes:.0f} flushes)")
    out["coalescer"] = {"requests": int(riders), "flushes": int(flushes)}
    out["compiles"] = compilecache.compiles_total() - compiles0
    return out


def _check_published(size, users, items, client, config, broker, up_topic,
                     findings, on_tpu) -> dict:
    """Requests against the live serving tier, checked against the factor
    files the batch tier published."""
    from pathlib import Path

    from oryx_tpu.pmml import pmmlutils
    from oryx_tpu.models.als import pmml_codec

    out: dict = {}
    model_root = Path(config.get_string("oryx.batch.storage.model-dir"))
    model_dirs = sorted(p for p in model_root.iterdir() if p.is_dir())
    if len(model_dirs) != 1:
        raise SmokeFailure(f"expected one published model, found {model_dirs}")
    meta = pmml_codec.pmml_to_meta(pmmlutils.read(model_dirs[0] / "model.pmml"))
    x_ids, x = zip(*pmml_codec.read_features(model_dirs[0] / meta["x_dir"]))
    y_ids, y = zip(*pmml_codec.read_features(model_dirs[0] / meta["y_dir"]))
    x, y = np.stack(x), np.stack(y)
    if x.shape[1] != size.features or y.shape[1] != size.features:
        raise SmokeFailure(f"published factors are {x.shape}/{y.shape}, "
                           f"not {size.features} features wide")
    if not (np.isfinite(x).all() and np.isfinite(y).all()):
        raise SmokeFailure("published factors are not finite")
    if not (np.abs(x).max() > 0 and np.abs(y).max() > 0):
        raise SmokeFailure("published factors are all zero")
    out["published"] = {"users": len(x_ids), "items": len(y_ids),
                        "features": int(x.shape[1])}
    x_row = {id_: n for n, id_ in enumerate(x_ids)}
    y_row = {id_: n for n, id_ in enumerate(y_ids)}

    # users with enough history to be in the model whatever the hold-out took
    degree = np.bincount(users, minlength=size.users)
    rng = np.random.default_rng(SEED + 1)
    candidates = np.flatnonzero(degree >= 5)
    sample = [int(u) for u in rng.choice(candidates, N_RECOMMEND, replace=False)
              if f"u{u}" in x_row]

    def known(u: int) -> np.ndarray:
        rows = [y_row[f"i{i}"] for i in items[users == u].tolist()
                if f"i{i}" in y_row]
        return np.asarray(rows, dtype=np.int64)

    def recommend(u: int):
        r = client.get(f"/recommend/u{u}", params={"howMany": HOW_MANY})
        if r.status_code != 200:
            raise SmokeFailure(f"/recommend/u{u} answered {r.status_code}: "
                               f"{r.text[:200]}")
        return [(d["id"], float(d["value"])) for d in r.json()]

    # all at once, so the coalescer has something to coalesce
    with cf.ThreadPoolExecutor(len(sample)) as pool:
        answers = list(pool.map(recommend, sample))
    overlaps, score_errs = [], []
    for u, got in zip(sample, answers):
        if len(got) != HOW_MANY:
            raise SmokeFailure(f"/recommend/u{u} returned {len(got)} items")
        scores = y @ x[x_row[f"u{u}"]]
        seen = known(u)
        exact = scores.copy()
        exact[seen] = -np.inf
        best = np.argsort(-exact)[:HOW_MANY]
        got_rows = [y_row[id_] for id_, _ in got]
        if np.isin(got_rows, seen).any():
            raise SmokeFailure(f"/recommend/u{u} returned a known item")
        overlaps.append(len(set(got_rows) & set(best.tolist())) / HOW_MANY)
        scale = max(np.abs(scores).max(), 1e-9)
        score_errs.append(max(abs(v - scores[r]) for r, (_, v)
                              in zip(got_rows, got)) / scale)
    out["recommend"] = {
        "requests": len(sample),
        "top10_overlap_mean": round(float(np.mean(overlaps)), 4),
        "top10_overlap_min": round(float(np.min(overlaps)), 4),
        "score_error_max": round(float(np.max(score_errs)), 6),
    }
    if np.mean(overlaps) < TOPN_OVERLAP:
        raise SmokeFailure(f"top-10 overlap with the float32 brute force is "
                           f"{np.mean(overlaps):.3f} < {TOPN_OVERLAP}")
    if np.max(score_errs) > SCORE_TOL:
        raise SmokeFailure(f"served scores are off by {np.max(score_errs):.3g} "
                           f"of the score scale > {SCORE_TOL}")

    log(f"/recommend ok: {out['recommend']}")

    # one each of the other endpoint families
    u0, u1 = sample[0], sample[1]
    r = client.get(f"/recommendToMany/u{u0}/u{u1}", params={"howMany": HOW_MANY})
    if r.status_code != 200 or len(r.json()) != HOW_MANY:
        raise SmokeFailure(f"/recommendToMany answered {r.status_code}")
    i0 = y_ids[int(np.argmax(np.linalg.norm(y, axis=1)))]
    r = client.get(f"/similarity/{i0}", params={"howMany": HOW_MANY})
    if r.status_code != 200 or len(r.json()) != HOW_MANY:
        raise SmokeFailure(f"/similarity answered {r.status_code}")
    yn = y / np.maximum(np.linalg.norm(y, axis=1, keepdims=True), 1e-12)
    cos = yn @ yn[y_row[i0]]
    cos[y_row[i0]] = -np.inf
    sim_best = set(np.argsort(-cos)[:HOW_MANY].tolist())
    sim_got = {y_row[d["id"]] for d in r.json()}
    out["similarity_overlap"] = len(sim_got & sim_best) / HOW_MANY
    if out["similarity_overlap"] < TOPN_OVERLAP:
        raise SmokeFailure(f"/similarity overlap {out['similarity_overlap']}")
    r = client.get(f"/estimate/u{u0}/{i0}")
    want = float(x[x_row[f"u{u0}"]] @ y[y_row[i0]])
    got = (float(r.json()[0]["value"]) if r.status_code == 200
           else float("nan"))
    out["estimate_error"] = round(abs(got - want), 6)
    if not abs(got - want) <= SCORE_TOL * max(1.0, abs(want)):
        raise SmokeFailure(f"/estimate answered {got}, exact is {want}")

    # one speed-tier fold-in: POST /pref → input topic → speed tier → UP →
    # the serving tier's answer for that user changes, with no new MODEL
    u = sample[2]
    before = recommend(u)
    fresh = before[0][0]  # its top recommendation: not a known item
    size_before = broker.size(up_topic)
    r = client.post(f"/pref/u{u}/{fresh}", content="1")
    if r.status_code not in (200, 204):
        raise SmokeFailure(f"POST /pref answered {r.status_code}")
    t_fold = time.monotonic()

    def folded():
        now = recommend(u)
        return now != before and now

    after = _wait("the fold-in to change the user's answer", folded, findings,
                  120.0, poll=0.25)
    out["fold_in"] = {
        "seconds": round(time.monotonic() - t_fold, 2),
        "new_item_excluded": fresh not in [i for i, _ in after],
        "update_messages": broker.size(up_topic) - size_before,
    }
    if not out["fold_in"]["new_item_excluded"]:
        raise SmokeFailure("the folded-in item is still recommended")
    log(f"fold-in ok: {out['fold_in']}")
    keys = [km.key for km in broker.read(up_topic, 0, broker.size(up_topic))]
    n_models = sum(1 for k in keys if k in ("MODEL", "MODEL-REF"))
    if n_models != 1:
        raise SmokeFailure(f"{n_models} models on the update topic, expected 1")

    out.update(_check_against_reference(size, config, users, items, x, y,
                                        x_row, y_row, findings, on_tpu))
    return out


def _check_against_reference(size, config, users, items, x_pub, y_pub, x_row,
                             y_row, findings, on_tpu) -> dict:
    """The same data through the in-tree reference formulation (einsum +
    cholesky, no kernels), in this process: (1) one half-iteration from the
    same Y₀ through both formulations agrees factor by factor; (2) the
    reference-trained model's held-out AUC, computed exactly as the
    published model's, lies within the stated band; (3) the lowered text of
    the half-iteration a TPU trains with carries both kernels."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models.als import data as als_data
    from oryx_tpu.models.als import evaluate as als_eval
    from oryx_tpu.models.als import train as tr
    from oryx_tpu.ops import pallas_kernels as pk

    # ALSUpdate's own split: time-ordered, the last TEST_FRACTION held out
    n_train = int(round(len(users) * (1.0 - TEST_FRACTION)))

    def agg(us, its):
        return {(f"u{u}", f"i{i}"): 1.0
                for u, i in zip(us.tolist(), its.tolist())}

    train = als_data.build_rating_batch(agg(users[:n_train], items[:n_train]))
    in_train = (train.users.id_to_index, train.items.id_to_index)
    test = als_data.build_rating_batch(
        {k: v for k, v in agg(users[n_train:], items[n_train:]).items()
         if k[0] in in_train[0] and k[1] in in_train[1]},
        train.users, train.items)
    k = size.features
    # the hyperparameters the batch tier trained with
    lam = config.get_float("oryx.als.hyperparams.lambda")
    alpha = config.get_float("oryx.als.hyperparams.alpha")
    user_side, item_side = tr.prepare_blocked(train, k)
    y0 = tr.init_item_factors(item_side, len(train.items), k,
                              jax.random.key(SEED))

    def half(side, opp, **formulation):
        return tr.solve_side_blocked(
            opp, side.srows, side.scols, side.svals, side.slens, lam, alpha,
            block=side.block, features=k, implicit=True,
            slot_chunk=side.slot_chunk, **formulation)

    reference = dict(spd_kernel=False, fused_gramian=False)
    kernels = dict(spd_kernel=True, fused_gramian=True)
    out: dict = {}
    x_ref = half(user_side, y0, **reference)
    x_ker = half(user_side, y0, **kernels)
    err = float(jnp.abs(x_ker - x_ref).max() / jnp.abs(x_ref).max())
    out["half_iteration_error"] = round(err, 6)
    if not err < HALF_ITER_TOL:
        raise SmokeFailure(f"one half-iteration through the kernels differs "
                           f"from the reference formulation by {err:.3g} of "
                           f"max |x| > {HALF_ITER_TOL}")
    findings.check()

    # the gate's third answer, the einsum over the opposite table zero-padded
    # to 64 columns (what a large table of narrow rows gets on a TPU), run
    # here whatever the gate says of this size: it must agree with the
    # reference like the others, on the device that is there
    x_pad = tr._solve_side_blocked_jit(
        y0, user_side.srows, user_side.scols, user_side.svals,
        user_side.slens, lam, alpha, block=user_side.block, features=k,
        implicit=True, slot_chunk=user_side.slot_chunk, dtype="float32",
        spd_kernel=on_tpu, fused_gramian=False, kernel_interpret=not on_tpu,
        gather_width=max(k, tr._GG_NARROW_FEATURES))
    err = float(jnp.abs(x_pad - x_ref).max() / jnp.abs(x_ref).max())
    out["padded_half_iteration_error"] = round(err, 6)
    if not err < HALF_ITER_TOL:
        raise SmokeFailure(f"one half-iteration through the padded gather "
                           f"differs from the reference formulation by "
                           f"{err:.3g} of max |x| > {HALF_ITER_TOL}")
    findings.check()

    # (3) what als_train picks on a TPU, lowered for a TPU: the SPD kernel
    # on both sides, and a side the gather-Gramian formulation and the gather
    # width the trainer's one gate answers for the opposite table that side
    # gathers from — at this smoke's size both are small tables of narrow
    # rows, so the rule says einsum, unpadded; the count of kernel calls in
    # each program must be what the gate said
    y_dev = next(iter(y0.devices()))
    if on_tpu and not pk.on_tpu(y0):
        raise SmokeFailure(f"the trainer's operands live on {y_dev}, not a TPU")
    out["formulation"] = {}
    for name, side, opp_rows in (("user", user_side, item_side.padded_rows),
                                 ("item", item_side, user_side.padded_rows)):
        chosen = tr._choose_formulation(None, True, k, side.srows.shape[1],
                                        opp_rows)
        runs = chosen.name(k)
        lowered = tr._solve_side_blocked_jit.trace(
            jax.ShapeDtypeStruct((opp_rows, k), jnp.float32), side.srows,
            side.scols, side.svals, side.slens, lam, alpha, block=side.block,
            features=k, implicit=True, slot_chunk=side.slot_chunk,
            dtype="float32", spd_kernel=True, fused_gramian=chosen.fused,
            kernel_interpret=False, gather_width=chosen.gather_width,
        ).lower(lowering_platforms=("tpu",)).as_text()
        calls = lowered.count("tpu_custom_call")
        out["formulation"][name] = {
            "runs": runs, "gather_width": chosen.gather_width,
            "why": chosen.why, "tpu_custom_calls": calls}
        print(f"chip_smoke: the {name} half runs the {runs}, gathering rows "
              f"of {chosen.gather_width} columns ({chosen.why}); "
              f"{calls} tpu_custom_call(s)", file=sys.stderr)
        if calls != 1 + chosen.fused:
            raise SmokeFailure(
                f"the TPU-default {name} half-iteration lowers to {calls} "
                f"tpu_custom_call(s) where the gate chose the {runs}: the "
                "SPD kernel is a TPU default, the gather-Gramian kernel is "
                "the gate's to pick")

    # (2) train the reference formulation to the same iteration count
    xr, yy = x_ref, y0
    for it in range(ITERATIONS):
        if it:
            xr = half(user_side, yy, **reference)
        yy = half(item_side, xr, **reference)
    xr = np.asarray(xr)[: len(train.users)]
    yr = np.asarray(yy)[: len(train.items)]

    def auc(xm, ym):
        from oryx_tpu.common import rand

        rand.use_test_seed()  # the same sampled negatives for both models
        return als_eval.area_under_curve(jnp.asarray(xm), jnp.asarray(ym),
                                         train, test)

    # the published factors, re-indexed onto this split's id order
    xp = np.zeros_like(xr)
    yp = np.zeros_like(yr)
    for id_, n in train.users.id_to_index.items():
        if id_ in x_row:
            xp[n] = x_pub[x_row[id_]]
    for id_, n in train.items.id_to_index.items():
        if id_ in y_row:
            yp[n] = y_pub[y_row[id_]]
    out["auc_published"] = round(auc(xp, yp), 4)
    out["auc_reference"] = round(auc(xr, yr), 4)
    if not out["auc_published"] > AUC_GATE:
        raise SmokeFailure(f"published model AUC {out['auc_published']} "
                           f"does not clear {AUC_GATE}")
    if abs(out["auc_published"] - out["auc_reference"]) > size.auc_band:
        raise SmokeFailure(
            f"published AUC {out['auc_published']} and reference-formulation "
            f"AUC {out['auc_reference']} differ by more than {size.auc_band}")
    log(f"reference formulation ok: {out}")
    return out


def _check_placement(batch, serving, size) -> dict:
    """Four chips must each hold a DIFFERENT part of the work: the trainer's
    factors (through the batch tier's own mesh and ALSUpdate's own choice of
    axes) and the serving tier's Y, by ``addressable_shards`` and by what
    each device reports in use."""
    import jax

    from oryx_tpu.models.als import data as als_data
    from oryx_tpu.models.als import train as tr
    from oryx_tpu.models.als.update import row_sharding

    mesh, row_axis = row_sharding(batch.get_context())
    if mesh is None or mesh.size < 4:
        raise SmokeFailure(f"the batch tier's mesh holds {mesh and mesh.size} "
                           "device(s); expected every local device")
    rng = np.random.default_rng(SEED + 2)
    n = 40_000
    agg = {(f"u{u}", f"i{i}"): 1.0
           for u, i in zip(rng.integers(0, 4000, n).tolist(),
                           rng.integers(0, 1000, n).tolist())}
    small = als_data.build_rating_batch(agg)
    x, y = tr.als_train(small, features=size.features, lam=0.001, alpha=1.0,
                        implicit=True, iterations=1, mesh=mesh,
                        row_axis=row_axis)
    out: dict = {}

    def spread(name, arr):
        devs = {s.device for s in arr.addressable_shards}
        starts = {s.index[0].start or 0 for s in arr.addressable_shards}
        out[name] = {"devices": len(devs), "row_ranges": len(starts),
                     "fully_replicated": bool(arr.sharding.is_fully_replicated)}
        if (arr.sharding.is_fully_replicated or len(devs) != mesh.size
                or len(starts) != mesh.size):
            raise SmokeFailure(f"{name} is not split over the mesh: {out[name]}")

    spread("train_x", x)
    spread("train_y", y)
    snap = serving.manager.get_model().y_snapshot()
    if snap.mesh is None:
        raise SmokeFailure("the serving tier's Y is not on the sharded path")
    spread("serving_y", snap.score_mat)
    spread("serving_y_f32", snap.mat)
    in_use = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        in_use.append(int(stats.get("bytes_in_use", 0)))
    out["bytes_in_use"] = in_use
    # each chip's share of Y alone is rows/n × k × 2 B (bf16 scoring copy)
    floor = size.items // len(in_use) * size.features * 2
    if jax.devices()[0].platform != "cpu" and min(in_use) < floor:
        raise SmokeFailure(f"a device holds less than its share of Y "
                           f"({floor} B): {in_use}")
    return out


# ---------------------------------------------------------------------------


def _versions() -> dict:
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def run(tiny_cpu: bool) -> dict:
    """The smoke's body; returns the summary or raises ``SmokeFailure``."""
    if os.path.isdir(OUT_DIR):
        shutil.rmtree(OUT_DIR)
    os.makedirs(OUT_DIR)
    from oryx_tpu.common import compilecache, rand
    from oryx_tpu.common import config as cfg

    # before the first compile: jax initializes its cache once per process
    compilecache.configure(cfg.get_default())
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device {device}, versions {_versions()}, "
        f"compile cache {compilecache.cache_dir()}")
    if tiny_cpu:
        if dev.platform != "cpu":
            raise SmokeFailure(f"--tiny-cpu is the CPU mode; jax found "
                               f"{dev.platform}. Set JAX_PLATFORMS=cpu")
    elif dev.platform != "tpu":
        raise SmokeFailure(
            f"jax found no TPU (platform {dev.platform!r}, "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). The full "
            "smoke proves the system on the chip and does not fall back; "
            "--tiny-cpu is the explicit CPU mode")
    size = TINY if tiny_cpu else FULL
    on_tpu = dev.platform == "tpu"
    rand.use_test_seed()

    findings = _Findings()
    logging.getLogger("oryx_tpu").addHandler(findings)
    logging.getLogger("oryx_tpu").setLevel(logging.INFO)
    summary: dict = {"ok": True, "device": device,
                     "mode": "tiny-cpu" if tiny_cpu else "full",
                     "versions": _versions()}
    try:
        t0 = time.monotonic()
        summary["kernels"] = check_kernels(size.kernel_features, on_tpu)
        findings.check()
        log(f"kernels ok in {time.monotonic() - t0:.1f}s: {summary['kernels']}")
        users, items = generate_interactions(size, SEED)
        lines = input_lines(users, items)
        summary["input"] = {"users": size.users, "items": size.items,
                            "interactions": len(lines),
                            "features": size.features,
                            "iterations": ITERATIONS}
        summary["loop"] = run_lambda_loop(size, lines, users, items, findings,
                                          device["count"], on_tpu)
    finally:
        logging.getLogger("oryx_tpu").removeHandler(findings)
    summary["setup"] = {
        "wall_seconds": round(time.monotonic() - _T0, 1),
        "compile_cache_dir": compilecache.cache_dir(),
        "compile_cache_hits": compilecache.cache_hits_total(),
        "compiles_total": compilecache.compiles_total(),
    }
    summary["claim"] = None
    return summary


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tiny-cpu", action="store_true",
                        help="tier-1's tiny mode: same body, a few thousand "
                             "interactions, kernels interpreted, on a CPU")
    args = parser.parse_args(argv)
    # the console shows warnings; the findings handler also hears INFO
    console = logging.StreamHandler(sys.stderr)
    console.setLevel(logging.WARNING)
    logging.basicConfig(handlers=[console])
    try:
        summary = run(args.tiny_cpu)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 2
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    log(f"every phase passed in {summary['setup']['wall_seconds']}s")
    print(json.dumps(summary))
    # the verdict, last: these keys and no others
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    # a layer thread that is slow to stop must not turn a finished smoke
    # into a timeout: flush and leave
    try:
        code = main()
    except BaseException:  # noqa: BLE001 — report, then exit non-zero
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
