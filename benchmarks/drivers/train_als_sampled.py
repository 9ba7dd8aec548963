"""Driver: the ALS trainer's one-device loop over resident data, checked on
a seeded sample of rows — for widths whose dense reference does not fit.

The window is ``train_als``'s: set-up generates the interactions from the
seed, packs both sides with ``prepare_blocked``, draws Y₀ and runs the first
iteration through the window's own call (``train_als.make_solve`` →
``solve_side_blocked``, exactly the calls ``als_train``'s one-device branch
makes, each half named for the trainer's solve counters); the window
alternates user and item half-iterations on the same state until
``--seconds`` have passed, ending on a whole iteration.

Then the program's state is freed and the configuration's ``reference``
follows the first steps on the sample its ``sample`` sizes ask for: the
user half from Y₀, the item half from the program's own X₁ (so ``y1_*``
checks the item half alone). ``--control 1`` adds the reference one
precision below and two planted faults: half of the entries left out, and
the item step's state left unchanged.
"""

from __future__ import annotations

import functools
import gc
import inspect
import json
import sys
import time
import types

import numpy as np

from benchmarks.harness import interactions
from benchmarks.harness.checks import Checks
from benchmarks.harness.manifest import load_module

_base = load_module("drivers", "train_als")
compare, iterate, make_solve = _base.compare, _base.iterate, _base.make_solve
y0_from_seed = _base.y0_from_seed

# what the trainer says of each half it ran (absent where the program has
# no such family: the driver then prints nothing for it)
FAMILIES = {"formulation": "oryx_als_half_formulation",
            "solved_rows": "oryx_als_solved_rows_total",
            "spd_tile_rows": "oryx_als_spd_tile_rows"}


def named_solve(train, cfg: dict, user_side):
    """``make_solve``'s call, each half named ``user`` / ``item`` where the
    program's ``solve_side_blocked`` takes a ``side``."""

    def solver(name):
        fn = train.solve_side_blocked
        if "side" in inspect.signature(fn).parameters:
            fn = functools.partial(fn, side=name)
        return make_solve(types.SimpleNamespace(solve_side_blocked=fn), cfg)

    user, item = solver("user"), solver("item")
    return lambda side, opp: (user if side is user_side else item)(side, opp)


def trainer_says() -> dict:
    """The trainer's families by side: the formulation each half ran, its
    rows by solve path, the SPD kernel's tile."""
    from oryx_tpu.common import metrics as metrics_mod

    reg = metrics_mod.default_registry()
    out = {}
    for key, family in FAMILIES.items():
        fam = reg.get(family)
        seen: dict = {}
        for labels, value in (fam.samples() if fam is not None else []):
            side = labels[0] if labels else ""
            if key == "formulation":
                if value == 1.0:
                    seen[side] = labels[1]
            elif key == "solved_rows":
                seen.setdefault(side, {})[labels[1]] = int(value)
            else:
                seen[side] = int(value)
        out[key] = seen
    return out


def follow(checks: Checks, ref, cfg: dict, seed: int, rows, cols, vals,
           y0, x1, y1, control: bool) -> None:
    """The reference's first steps on the sample, beside the program's."""
    n_users, n_items = cfg["users"], cfg["items"]
    lam, alpha = cfg["lambda"], cfg["alpha"]
    users, items = ref.pick(rows, cols, n_users, n_items, seed, cfg["sample"])
    ent = ref.Entries(rows, cols, vals, n_users, n_items, users, items)
    x1r = ref.user_half(y0, ent, lam, alpha)
    y1r = ref.item_half(x1, ent, lam, alpha)
    compare(checks, ref, "", x1[users], y1[items], x1r, y1r)
    if not control:
        return
    compare(checks, ref, "control_",
            ref.user_half(y0, ent, lam, alpha, control=True),
            ref.item_half(x1, ent, lam, alpha, control=True), x1r, y1r)
    del ent
    # fault: half of the batch left out (the reference in the program's
    # place, on a random half of the entries of the same rows)
    keep = np.random.default_rng([seed, 32]).random(len(rows)) < 0.5
    half = ref.Entries(rows[keep], cols[keep], vals[keep], n_users, n_items,
                       users, items)
    compare(checks, ref, "fault_half_", ref.user_half(y0, half, lam, alpha),
            ref.item_half(x1, half, lam, alpha), x1r, y1r)
    # fault: the item step returns its state unchanged
    checks.add("fault_unchanged_y1_err", ref.rel_err(y0[items], y1r))
    checks.add("fault_unchanged_y1_row_err", ref.worst_row_err(y0[items], y1r))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    cfg = ctx.sized(ctx.cell.config)
    k, n_users, n_items = cfg["features"], cfg["users"], cfg["items"]
    nnz = cfg["interactions"]
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

    solve = named_solve(train, cfg, user_side)
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
        with _base._annotation(ctx, _base.HALF_ANNOTATION[name]):
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
        name: {"slots": int(side.srows.size), "T": side.slot_width,
               "block": side.block, "n_blocks": side.n_blocks}
        for name, side in (("user", user_side), ("item", item_side))}
    print(json.dumps({"info": "window", "iterations": iters,
                      "window_s": window_s, "halves_s": halves,
                      "compiles_in_window": compiles, "shapes": shapes,
                      **trainer_says(), "memory_peak_bytes": peak,
                      "finite": finite}), file=sys.stderr)
    del user_side, item_side, x, y
    gc.collect()

    # the program's state is freed: now the reference follows the first steps
    checks = Checks(cfg["limits"])
    checks.add("compiles_in_window", compiles)
    ref = load_module("references", cfg["reference"])
    t_ref = time.monotonic()
    follow(checks, ref, cfg, ctx.seed, rows, cols, vals, y0, x1, y1,
           ctx.control)
    phases.mark("reference")
    print(json.dumps({"info": "reference", "seconds": time.monotonic() - t_ref,
                      "control": ctx.control}), file=sys.stderr)

    return {
        "checks": checks, "attempted": 2 * iters, "failed": 0 if finite else 1,
        "memory_peak_bytes": peak,
        "obs": {
            "work_done": float(nnz) * iters, "work_window_s": window_s,
            "window_s": window_s, "iterations": iters, "halves": halves,
            "trace_dir": trace_dir, "sizes": cfg, "shapes": shapes,
        },
    }
