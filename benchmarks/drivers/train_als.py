"""Driver: the ALS trainer's one-device loop over resident data.

Set-up generates the interactions from the seed, packs both sides with
``prepare_blocked``, draws Y₀, and runs the first iteration through the
window's own call (``solve_side_blocked``, exactly the calls
``als_train``'s one-device branch makes): those two half-iterations compile
both programs and are the steps the reference follows. The window then
alternates user and item half-iterations on the same state until
``--seconds`` have passed, ending on a whole iteration.
"""

from __future__ import annotations

import gc
import json
import sys
import time

import numpy as np

from benchmarks.harness import interactions
from benchmarks.harness.checks import Checks
from benchmarks.harness.manifest import load_module

HALF_ANNOTATION = {"user": "bench.half.user", "item": "bench.half.item"}


def _annotation(ctx, name: str):
    import contextlib

    import jax

    return jax.profiler.TraceAnnotation(name) if ctx.trace \
        else contextlib.nullcontext()


def y0_from_seed(seed: int, n_items: int, k: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 31])
    return 0.1 * rng.standard_normal((n_items, k), dtype=np.float32)


def compare(checks: Checks, ref, prefix: str, x1, y1, x1r, y1r) -> None:
    checks.add(prefix + "x1_err", ref.rel_err(x1, x1r))
    checks.add(prefix + "x1_row_err", ref.worst_row_err(x1, x1r))
    checks.add(prefix + "y1_err", ref.rel_err(y1, y1r))
    checks.add(prefix + "y1_row_err", ref.worst_row_err(y1, y1r))


def make_solve(train, cfg: dict):
    """The window's own call: one half-iteration through
    ``solve_side_blocked``, as ``als_train``'s one-device branch makes it."""
    k, lam, alpha = cfg["features"], cfg["lambda"], cfg["alpha"]

    def solve(side, opp):
        return train.solve_side_blocked(
            opp, side.srows, side.scols, side.svals, side.slens, lam, alpha,
            block=side.block, features=k, implicit=bool(cfg["implicit"]),
            slot_chunk=side.slot_chunk, dtype=cfg["dtype"])

    return solve


def iterate(solve, user_side, item_side, y, stop, on_half=None):
    """Alternate user and item half-iterations from ``y`` until ``stop(n)``
    says so after the n-th whole iteration; returns (x, y, iterations).
    ``on_half(name, fn)`` wraps each half-iteration (timing, annotation)."""
    import jax

    on_half = on_half or (lambda name, fn: fn())
    x, iters = None, 0
    while True:
        x = on_half("user", lambda: jax.block_until_ready(solve(user_side, y)))
        y = on_half("item", lambda: jax.block_until_ready(solve(item_side, x)))
        iters += 1
        if stop(iters):
            return x, y, iters


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    cfg = ctx.sized(ctx.cell.config)
    k, n_users, n_items = cfg["features"], cfg["users"], cfg["items"]
    nnz, lam, alpha = cfg["interactions"], cfg["lambda"], cfg["alpha"]
    phases = ctx.phases

    from oryx_tpu.common import compilecache
    from oryx_tpu.common import config as oryx_config
    from oryx_tpu.models.als import train
    from oryx_tpu.models.als.data import RatingBatch

    # the program chooses the compile cache's directory: before any compile
    compilecache.configure(oryx_config.get_default())
    phases.mark("import")
    rows, cols, vals = interactions.generate(
        ctx.seed, n_users, n_items, nnz, cfg["generator"])
    phases.mark("generate")
    batch = RatingBatch(rows, cols, vals, range(n_users), range(n_items))
    user_side, item_side = train.prepare_blocked(batch, k)
    jax.block_until_ready((user_side.scols, item_side.scols))
    phases.mark("pack")
    del batch
    y0 = y0_from_seed(ctx.seed, n_items, k)
    y = jnp.zeros((item_side.padded_rows, k), jnp.float32).at[:n_items].set(y0)

    solve = make_solve(train, cfg)
    # the first steps: through the window's own call, on the window's state
    x, y, _ = iterate(solve, user_side, item_side, y, lambda n: True)
    phases.mark("first_iteration")
    x1, y1 = np.asarray(x[:n_users]), np.asarray(y[:n_items])
    phases.mark("first_steps_to_host")

    trace_dir = ctx.start_trace() if ctx.trace else None
    compiles0 = compilecache.compiles_total()
    halves = []

    def timed_half(name, fn):
        t0 = time.monotonic()
        with _annotation(ctx, HALF_ANNOTATION[name]):
            out = fn()
        halves.append((name, time.monotonic() - t0))
        return out

    t_start = time.monotonic()
    ctx.window_opens(t_start)
    with ctx.window_annotation():
        x, y, iters = iterate(
            solve, user_side, item_side, y,
            lambda n: time.monotonic() - t_start >= ctx.seconds, timed_half)
    window_s = time.monotonic() - t_start
    if ctx.trace:
        ctx.stop_trace()
    compiles = compilecache.compiles_total() - compiles0
    finite = bool(np.isfinite(np.asarray(y[:n_items])).all())
    peak = ctx.memory_peak()
    shapes = {
        "user": {"slots": int(user_side.srows.size), "T": user_side.slot_width,
                 "block": user_side.block, "n_blocks": user_side.n_blocks},
        "item": {"slots": int(item_side.srows.size), "T": item_side.slot_width,
                 "block": item_side.block, "n_blocks": item_side.n_blocks},
    }
    print(json.dumps({"info": "window", "iterations": iters,
                      "window_s": window_s, "halves_s": halves,
                      "compiles_in_window": compiles, "shapes": shapes,
                      "finite": finite}), file=sys.stderr)
    del user_side, item_side, x, y
    gc.collect()

    # the program's state is freed: now the reference follows the first steps
    checks = Checks(cfg["limits"])
    ref = load_module("references", cfg["reference"])
    checks.add("compiles_in_window", compiles)
    t_ref = time.monotonic()
    ent = ref.Entries(rows, cols, vals, n_users, n_items)
    x1r, y1r = ref.iteration(y0, ent, lam, alpha)
    compare(checks, ref, "", x1, y1, x1r, y1r)
    phases.mark("reference")
    print(json.dumps({"info": "reference", "seconds": time.monotonic() - t_ref}),
          file=sys.stderr)
    if ctx.control:
        x1c, y1c = ref.iteration(y0, ent, lam, alpha, control=True)
        compare(checks, ref, "control_", x1c, y1c, x1r, y1r)
        del ent
        # fault: half of the batch left out (the reference in the program's
        # place, on a random half of the entries)
        keep = np.random.default_rng([ctx.seed, 32]).random(len(rows)) < 0.5
        half = ref.Entries(rows[keep], cols[keep], vals[keep], n_users, n_items)
        x1h, y1h = ref.iteration(y0, half, lam, alpha)
        compare(checks, ref, "fault_half_", x1h, y1h, x1r, y1r)
        # fault: the item step returns its state unchanged
        checks.add("fault_unchanged_y1_err", ref.rel_err(y0, y1r))
        checks.add("fault_unchanged_y1_row_err", ref.worst_row_err(y0, y1r))
        phases.mark("control_and_faults")

    return {
        "checks": checks, "attempted": 2 * iters, "failed": 0 if finite else 1,
        "memory_peak_bytes": peak,
        "obs": {
            "work_done": float(nnz) * iters, "work_window_s": window_s,
            "window_s": window_s, "iterations": iters, "halves": halves,
            "trace_dir": trace_dir, "sizes": cfg, "shapes": shapes,
        },
    }
