"""The 250-feature trainer cell in its tiny ``cpu`` rehearsal: the program at
k = 250 (the einsum, and the fused gather-Gramian and SPD kernels
interpreted) against the sampled reference, that reference against the
dense one, the control, the planted faults and the contract line."""

import sys

import numpy as np
import pytest

from perfbench_util import LINE_KEYS, ROOT, rehearse
from test_perfbench_training import _HALF_THE_BATCH, _STATE_UNCHANGED

sys.path.insert(0, ROOT)
from benchmarks.harness import interactions  # noqa: E402
from benchmarks.harness import manifest as mf  # noqa: E402
from benchmarks.harness.checks import Checks  # noqa: E402

MANIFEST = mf.load_manifest()
CELL = "train-nf100m-250f.iterate"
CFG = mf.load_json(mf.find("configs", "als-nf100m-250f", ".json"))
SMALL = dict(CFG, **CFG["rehearsal"])
DENSE = mf.load_json(mf.find("configs", "als-nf100m-50f", ".json"))


def _tiny(seed=3):
    return interactions.generate(seed, SMALL["users"], SMALL["items"],
                                 SMALL["interactions"], SMALL["generator"])


def test_the_published_counts_and_width_stand_in_the_configuration():
    assert (CFG["users"], CFG["items"], CFG["interactions"], CFG["features"]) \
        == (480189, 17770, 100480507, 250)
    assert CFG["reduced"] == [] and CFG["dtype"] == "float32"
    assert (CFG["lambda"], CFG["alpha"], CFG["implicit"]) == (0.001, 1.0, True)
    assert CFG["generator"] == DENSE["generator"]  # the same data as the 50f cell
    assert CFG["sample"] == {"users": 2048, "heaviest_users": 32,
                             "items": 256, "heaviest_items": 16}
    entry = [c for c in MANIFEST["configs"] if c["name"] == CFG["name"]][0]
    assert entry["file"] == "benchmarks/configs/als-nf100m-250f.json"
    assert entry["reduced"] == []
    cell = mf.Cell(MANIFEST, CELL)
    assert cell.chips == 1 and cell.entry["traffic"] == "iterate"
    assert {m["name"] for m in cell.end_to_end} == {"train_ratings_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "pack_s", "halfiter_ms.user.k250", "halfiter_ms.item.k250",
        "train_mfu.k250", "gather_gramian_roofline.k250",
        "spd_solve_roofline.k250", "device_idle.k250"}


def test_the_sample_holds_the_heaviest_rows_and_follows_the_seed():
    ref = mf.load_module("references", CFG["reference"])
    r, c, _ = _tiny()
    nu, ni, size = SMALL["users"], SMALL["items"], SMALL["sample"]
    users, items = ref.pick(r, c, nu, ni, 7, size)
    assert len(users) == size["users"] and len(items) == size["items"]
    assert (np.diff(users) > 0).all() and (np.diff(items) > 0).all()
    du, di = np.bincount(r, minlength=nu), np.bincount(c, minlength=ni)
    assert set(np.argsort(-du, kind="stable")[:size["heaviest_users"]]) \
        <= set(users)
    assert set(np.argsort(-di, kind="stable")[:size["heaviest_items"]]) \
        <= set(items)
    again, _ = ref.pick(r, c, nu, ni, 7, size)
    other, _ = ref.pick(r, c, nu, ni, 2 ** 31 + 7, size)
    assert (again == users).all() and (other != users).any()
    every, _ = ref.pick(r, c, nu, ni, 7, dict(size, users=10 ** 6))
    assert (every == np.arange(nu)).all()


def test_the_sampled_reference_is_the_dense_one_on_its_rows():
    import jax

    sampled = mf.load_module("references", CFG["reference"])
    dense = mf.load_module("references", DENSE["reference"])
    r, c, v = _tiny()
    nu, ni, k = SMALL["users"], SMALL["items"], CFG["features"]
    lam, alpha = CFG["lambda"], CFG["alpha"]
    y0 = np.asarray(0.1 * jax.random.normal(jax.random.PRNGKey(4), (ni, k)))
    x1d, y1d = dense.iteration(y0, dense.Entries(r, c, v, nu, ni, block=256),
                               lam, alpha)
    users, items = sampled.pick(r, c, nu, ni, 5, SMALL["sample"])
    ent = sampled.Entries(r, c, v, nu, ni, users, items)
    x1s = sampled.user_half(y0, ent, lam, alpha)
    # the item half from the X₁ it is given: the dense one's
    y1s = sampled.item_half(x1d, ent, lam, alpha)
    assert sampled.rel_err(x1d[users], x1s) < 1e-5
    assert sampled.rel_err(y1d[items], y1s) < 1e-4
    assert sampled.worst_row_err(y1d[items], y1s) < 1e-3


def _program_first_steps(r, c, v, kernels: bool):
    import jax.numpy as jnp

    from oryx_tpu.models.als import train
    from oryx_tpu.models.als.data import RatingBatch

    drv = mf.load_module("drivers", CFG["driver"])
    nu, ni, k = SMALL["users"], SMALL["items"], CFG["features"]
    us, its = train.prepare_blocked(
        RatingBatch(r, c, v, range(nu), range(ni)), k)
    y0 = drv.y0_from_seed(9, ni, k)
    y = jnp.zeros((its.padded_rows, k), jnp.float32).at[:ni].set(y0)

    def solve(side, opp):
        return train.solve_side_blocked(
            opp, side.srows, side.scols, side.svals, side.slens,
            CFG["lambda"], CFG["alpha"], block=side.block, features=k,
            implicit=True, slot_chunk=side.slot_chunk, spd_kernel=kernels,
            fused_gramian=kernels)

    x, y, _ = drv.iterate(solve, us, its, y, lambda n: True)
    return np.asarray(x[:nu]), np.asarray(y[:ni]), y0


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["einsum_cholesky", "fused_and_spd_kernels"])
def test_the_program_at_250_features_agrees_and_the_control_does_not(kernels):
    ref = mf.load_module("references", CFG["reference"])
    drv = mf.load_module("drivers", CFG["driver"])
    r, c, v = _tiny()
    x1, y1, y0 = _program_first_steps(r, c, v, kernels)
    sound = Checks(CFG["limits"])
    drv.follow(sound, ref, SMALL, 11, r, c, v, y0, x1, y1, control=True)
    rows = {n: val for n, val, _ in sound.rows}
    assert sound.correct, sound.as_dict()
    assert max(rows[n] for n in ("x1_err", "x1_row_err", "y1_err",
                                 "y1_row_err")) < 1e-3  # float32 on a CPU
    # the control and both planted faults each exceed a limit of the cell
    for prefix, names in (("control_", ("x1_err", "y1_err", "y1_row_err")),
                          ("fault_half_", ("x1_err", "y1_err")),
                          ("fault_unchanged_", ("y1_err", "y1_row_err"))):
        assert any(rows[prefix + n] > CFG["limits"][n] for n in names), \
            (prefix, sound.as_dict())


def test_rehearsal_prints_exactly_the_contract_line_and_names_the_solves():
    rc, line, err = rehearse(CELL, seed=2 ** 31 + 38)
    assert rc == 0, err[-2000:]
    assert set(line) == LINE_KEYS and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"train_ratings_per_s", "setup_s"}
    assert set(line["compared"]) == {"compiles_in_window", "x1_err",
                                     "x1_row_err", "y1_err", "y1_row_err"}
    tail = err.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") for t in tail)
    import json

    window = next(json.loads(t) for t in err.splitlines()
                  if t.startswith('{"info": "window"'))
    # off a TPU both halves run the einsum and XLA's cholesky, every call
    halves = window["iterations"] + 1
    assert window["formulation"] == {"user": "einsum", "item": "einsum"}
    assert window["spd_tile_rows"] == {"user": 0, "item": 0}
    shapes = window["shapes"]
    assert window["solved_rows"] == {
        side: {"cholesky": halves * shapes[side]["block"]
               * shapes[side]["n_blocks"]} for side in ("user", "item")}


def test_traced_rehearsal_reports_no_device_metric():
    rc, line, err = rehearse(CELL, seed=15, trace=1)
    assert rc == 0, err[-2000:]
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert set(line["metrics"]) == {"pack_s"}
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0


@pytest.mark.parametrize("fault,number", [
    (_STATE_UNCHANGED, "y1_err"), (_HALF_THE_BATCH, "x1_err")],
    ids=["state_unchanged", "half_the_batch_left_out"])
def test_a_broken_timed_path_comes_out_not_correct(fault, number):
    rc, line, err = rehearse(CELL, seed=16, seconds=0.5, prelude=fault)
    assert rc == 0, err[-2000:]
    assert line["correct"] is False
    row = line["compared"][number]
    assert row["value"] > row["limit"], line["compared"]
