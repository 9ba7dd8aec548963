"""Which solve ran each half-iteration, as the trainer counts it on the host:
``oryx_als_solved_rows_total{side, path}`` (``spd_kernel`` / ``spd_blocked``
/ ``cholesky``) and ``oryx_als_spd_tile_rows{side}``. The Pallas SPD kernel
runs where it is asked for: unblocked to 128 features, blocked to 256;
past that, or where it is not asked for, XLA's cholesky runs and is counted
as such."""

import jax
import numpy as np
import pytest

from oryx_tpu.models.als import train as tr
from oryx_tpu.models.als.data import RatingBatch
from oryx_tpu.ops import pallas_kernels as pk

from conftest import LenOnlyIDs as _IDs


def _sides(k, n_users=90, n_items=40, nnz=600, seed=3):
    rng = np.random.default_rng(seed)
    batch = RatingBatch(
        rng.integers(0, n_users, nnz).astype(np.int32),
        rng.integers(0, n_items, nnz).astype(np.int32),
        rng.integers(1, 6, nnz).astype(np.float32),
        _IDs(n_users), _IDs(n_items),
    )
    return batch, tr.prepare_blocked(batch, k, block=32)


def _rows(side, path):
    return tr._SOLVED_ROWS.labels(side, path).value


def _half(k, spd_kernel, side_name):
    _, (users, items) = _sides(k)
    y = tr.init_item_factors(items, 40, k, jax.random.PRNGKey(1))
    return users, np.asarray(tr.solve_side_blocked(
        y, users.srows, users.scols, users.svals, users.slens, 0.01, 1.0,
        block=users.block, features=k, implicit=True,
        slot_chunk=users.slot_chunk, spd_kernel=spd_kernel, side=side_name))


_PATHS = ("spd_kernel", "spd_blocked", "cholesky")


@pytest.mark.parametrize("k,asked,path,tile", [
    (16, True, "spd_kernel", pk.spd_tile_b(16)),
    (16, False, "cholesky", 0),
    # past 128 features: the blocked kernel, at its own tile
    (250, True, "spd_blocked", pk.spd_blocked_tile_b(250)),
    # past the kernel's tile budget: the cholesky, whatever was asked
    (264, True, "cholesky", 0),
], ids=["kernel", "not_asked", "blocked", "past_the_tile_budget"])
def test_a_half_counts_its_rows_under_the_solve_that_ran(k, asked, path, tile):
    side = f"counted_{k}_{asked}"
    users, x = _half(k, asked, side)
    rows = users.n_blocks * users.block
    assert _rows(side, path) == rows
    assert all(_rows(side, other) == 0 for other in _PATHS if other != path)
    assert tr._SPD_TILE_ROWS.labels(side).value == tile
    _half(k, asked, side)  # every call counts
    assert _rows(side, path) == 2 * rows
    assert np.isfinite(x).all()


def test_the_cholesky_past_the_budget_is_what_the_kernel_would_fall_back_to():
    _, x_asked = _half(264, True, "fallback_asked")
    _, x_chol = _half(264, False, "fallback_not_asked")
    np.testing.assert_array_equal(x_asked, x_chol)


def test_an_unnamed_half_is_counted_nowhere():
    families = (tr._SOLVED_ROWS, tr._SPD_TILE_ROWS, tr._HALF_FORMULATION)

    def seen():
        return [sorted(f.samples()) for f in families]

    before = seen()
    _half(16, True, None)
    assert seen() == before


def test_the_choice_is_the_kernels_own_tile_rule():
    # to 128 features: the unblocked kernel and tile as they were
    assert tr._choose_spd(True, 50) == ("spd_kernel", 104)
    assert tr._choose_spd(True, 128) == ("spd_kernel", pk.spd_tile_b(128))
    assert pk.spd_tile_b(50) == 104
    # two panels: the blocked kernel at its own tile
    assert tr._choose_spd(True, 129) == ("spd_blocked", 16)
    assert tr._choose_spd(True, 250) == ("spd_blocked", 16) \
        == ("spd_blocked", pk.spd_blocked_tile_b(250))
    assert tr._choose_spd(True, 256) == ("spd_blocked", 8)
    assert tr._choose_spd(True, 257) == ("cholesky", 0)
    assert tr._choose_spd(False, 50).path == "cholesky"
    assert tr._choose_spd(False, 250).path == "cholesky"
    assert tr._choose_spd(True, 250).path == "spd_blocked"
    assert tr._choose_spd(True, 250).kernel
    assert not tr._choose_spd(True, 257).kernel
    # the host's choice is the solve's own
    for k in (1, 50, 128, 129, 200, 250, 255, 256, 257, 300):
        assert tr._choose_spd(True, k) == pk.spd_solve_path(k), k


def test_als_train_counts_each_side_every_iteration():
    k, iterations = 8, 3
    batch, (users, items) = _sides(k, seed=4)
    before = {s: _rows(s, "cholesky") for s in ("user", "item")}
    tr.als_train(batch, k, 0.01, 1.0, True, iterations=iterations,
                 key=jax.random.PRNGKey(2), block=32)
    # off a TPU the kernel is not asked for: every row under the cholesky
    assert _rows("user", "cholesky") - before["user"] \
        == iterations * users.padded_rows
    assert _rows("item", "cholesky") - before["item"] \
        == iterations * items.padded_rows
    assert tr._SPD_TILE_ROWS.labels("user").value == 0
