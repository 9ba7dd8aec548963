"""Which solve ran each half-iteration, as the trainer counts it on the host:
``oryx_als_solved_rows_total{side, path}`` (``spd_kernel`` / ``cholesky``)
and ``oryx_als_spd_tile_rows{side}``. The Pallas SPD kernel runs where it is
asked for and a tile of 8 rows fits (to 256 features); past that, or where
it is not asked for, XLA's cholesky runs and is counted as such."""

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


@pytest.mark.parametrize("k,asked,path,tile", [
    (16, True, "spd_kernel", pk.spd_tile_b(16)),
    (16, False, "cholesky", 0),
    # past the kernel's tile budget: the cholesky, whatever was asked
    (264, True, "cholesky", 0),
], ids=["kernel", "not_asked", "past_the_tile_budget"])
def test_a_half_counts_its_rows_under_the_solve_that_ran(k, asked, path, tile):
    side = f"counted_{k}_{asked}"
    other = "cholesky" if path == "spd_kernel" else "spd_kernel"
    users, x = _half(k, asked, side)
    rows = users.n_blocks * users.block
    assert _rows(side, path) == rows and _rows(side, other) == 0
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
    assert tr._choose_spd(True, 50) == (True, 104)
    assert tr._choose_spd(True, 250) == (True, 8) == (True, pk.spd_tile_b(250))
    assert tr._choose_spd(True, 256) == (True, 8)
    assert tr._choose_spd(True, 257) == (False, 0)
    assert pk.spd_kernel_fits(256) and not pk.spd_kernel_fits(257)
    assert tr._choose_spd(False, 50).path == "cholesky"
    assert tr._choose_spd(True, 250).path == "spd_kernel"


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
