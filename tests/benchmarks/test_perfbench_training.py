"""The trainer cell in its tiny ``cpu`` rehearsal: the generator, the
window's loop against ``als_train``, the plain reference, the control and
the planted faults."""

import sys

import numpy as np
import pytest

from perfbench_util import LINE_KEYS, ROOT, rehearse

sys.path.insert(0, ROOT)
from benchmarks.harness import interactions  # noqa: E402
from benchmarks.harness import manifest as mf  # noqa: E402
from benchmarks.harness.checks import Checks  # noqa: E402

MANIFEST = mf.load_manifest()
CELL = "train-nf100m-50f.iterate"
CFG = mf.load_json(mf.find("configs", "als-nf100m-50f", ".json"))
SMALL = CFG["rehearsal"]


def _tiny(seed=3):
    return interactions.generate(seed, SMALL["users"], SMALL["items"],
                                 SMALL["interactions"], SMALL["generator"])


def test_generator_makes_exactly_the_counts_distinct_and_from_the_seed():
    r, c, v = _tiny()
    nu, ni, nnz = SMALL["users"], SMALL["items"], SMALL["interactions"]
    assert len(r) == len(c) == len(v) == nnz
    key = r.astype(np.int64) * ni + c
    assert (np.diff(key) > 0).all()  # distinct pairs, sorted by (row, col)
    assert np.bincount(r, minlength=nu).min() >= 1 and c.max() < ni
    assert set(np.unique(v)) <= {1.0, 2.0, 3.0, 4.0, 5.0}
    r2, c2, v2 = _tiny()
    assert (r == r2).all() and (c == c2).all() and (v == v2).all()
    r3, _, _ = _tiny(seed=2 ** 31 + 3)
    assert len(r3) == nnz and (np.bincount(r3, minlength=nu)
                               != np.bincount(r, minlength=nu)).any()
    # heavy tails on both sides
    du, di = np.bincount(r, minlength=nu), np.bincount(c, minlength=ni)
    assert du.max() > 5 * du.mean() and di.max() > 3 * di.mean()


def test_the_published_counts_stand_in_the_configuration():
    assert (CFG["users"], CFG["items"], CFG["interactions"], CFG["features"]) \
        == (480189, 17770, 100480507, 50)
    assert CFG["reduced"] == [] and CFG["dtype"] == "float32"


def _program_first_steps(r, c, v, iterations, key):
    import jax

    from oryx_tpu.models.als import train
    from oryx_tpu.models.als.data import RatingBatch

    drv = mf.load_module("drivers", CFG["driver"])
    nu, ni, k = SMALL["users"], SMALL["items"], CFG["features"]
    batch = RatingBatch(r, c, v, range(nu), range(ni))
    us, its = train.prepare_blocked(batch, k)
    y = train.init_item_factors(its, ni, k, key)
    y0 = np.asarray(y[:ni])
    solve = drv.make_solve(train, CFG)
    x, y, n = drv.iterate(solve, us, its, y, lambda n: n >= iterations)
    assert n == iterations
    want = train.als_train(batch, k, CFG["lambda"], CFG["alpha"],
                           bool(CFG["implicit"]), iterations=iterations, key=key)
    return (np.asarray(x[:nu]), np.asarray(y[:ni])), \
        tuple(np.asarray(a) for a in want), y0


def test_the_windows_loop_returns_what_als_train_returns():
    import jax

    r, c, v = _tiny()
    got, want, _ = _program_first_steps(r, c, v, 3, jax.random.PRNGKey(5))
    np.testing.assert_allclose(got[0], want[0], rtol=0, atol=1e-6)
    np.testing.assert_allclose(got[1], want[1], rtol=0, atol=1e-6)


def test_reference_agrees_with_the_program_and_the_control_does_not():
    import jax

    ref = mf.load_module("references", CFG["reference"])
    drv = mf.load_module("drivers", CFG["driver"])
    r, c, v = _tiny()
    (x1, y1), _, y0 = _program_first_steps(r, c, v, 1, jax.random.PRNGKey(6))
    lam, alpha = CFG["lambda"], CFG["alpha"]
    ent = ref.Entries(r, c, v, SMALL["users"], SMALL["items"], block=256)

    def first_steps(control):
        return ref.iteration(y0, ent, lam, alpha, control=control)

    x1r, y1r = first_steps(False)
    sound = Checks(CFG["limits"])
    drv.compare(sound, ref, "", x1, y1, x1r, y1r)
    assert sound.correct, sound.as_dict()
    assert max(v for _, v, _ in sound.rows) < 1e-4  # float32 on a CPU
    control = Checks(CFG["limits"])
    drv.compare(control, ref, "", *first_steps(True), x1r, y1r)
    assert not control.correct, control.as_dict()


def test_rehearsal_prints_exactly_the_contract_line():
    rc, line, err = rehearse(CELL, seed=2 ** 31 + 12)
    assert rc == 0, err[-2000:]
    assert set(line) == LINE_KEYS and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2 and line["device"]["platform"] == "cpu"
    c = mf.Cell(MANIFEST, CELL)
    assert set(line["metrics"]) == {m["name"] for m in c.end_to_end}
    assert line["metrics"]["train_ratings_per_s"]["value"] > 0
    tail = err.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") for t in tail)


def test_traced_rehearsal_reports_no_device_metric():
    rc, line, err = rehearse(CELL, seed=13, trace=1)
    assert rc == 0, err[-2000:]
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert set(line["metrics"]) == {"pack_s"}
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0


_STATE_UNCHANGED = '''
from oryx_tpu.models.als import train as T
_orig = T.solve_side_blocked
_seen = {}
def _stuck(y, *a, **kw):
    out = _orig(y, *a, **kw)
    # the item step hands back the item factors it was started from
    if out.shape == _seen.get("y", out).shape and "y" in _seen:
        return _seen["y"]
    _seen.setdefault("y", y)
    return out
T.solve_side_blocked = _stuck
'''

_HALF_THE_BATCH = '''
from oryx_tpu.models.als import train as T
from oryx_tpu.models.als.data import RatingBatch
_orig = T.prepare_blocked
def _half(batch, *a, **kw):
    keep = slice(None, None, 2)
    return _orig(RatingBatch(batch.rows[keep], batch.cols[keep],
                             batch.vals[keep], batch.users, batch.items), *a, **kw)
T.prepare_blocked = _half
'''

_ANSWER_ALTERED = '''
from oryx_tpu.models.als import train as T
_orig = T.solve_side_blocked
def _off(y, *a, **kw):
    out = _orig(y, *a, **kw)
    return out.at[3].multiply(1.5)  # one row of every result is altered
T.solve_side_blocked = _off
'''


@pytest.mark.parametrize("fault,number", [
    (_STATE_UNCHANGED, "y1_err"), (_HALF_THE_BATCH, "x1_err"),
    (_ANSWER_ALTERED, "x1_row_err")],
    ids=["state_unchanged", "half_the_batch_left_out", "answer_altered"])
def test_a_broken_timed_path_comes_out_not_correct(fault, number):
    rc, line, err = rehearse(CELL, seed=14, seconds=0.5, prelude=fault)
    assert rc == 0, err[-2000:]
    assert line["correct"] is False
    row = line["compared"][number]
    assert row["value"] > row["limit"], line["compared"]
