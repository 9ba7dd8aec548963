"""Which solve ran each half-iteration, as the trainer counts it on the host:
``oryx_als_solved_rows_total{side, path}`` (``spd_kernel`` / ``spd_blocked``
/ ``cholesky``) and ``oryx_als_spd_tile_rows{side}``. The Pallas SPD kernel
runs where it is asked for: unblocked to 128 features, blocked to 256;
past that, or where it is not asked for, XLA's cholesky runs and is counted
as such. And where a fused half took its factor rows from:
``oryx_als_gathered_rows_total{side, source}`` (``vmem`` / ``hbm``)."""

import logging

import jax
import jax.numpy as jnp
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


# ---------------------------------------------------------------------------
# oryx_als_gathered_rows_total{side, source}
# ---------------------------------------------------------------------------

_SOURCES = ("vmem", "hbm")


def _gathered(side, source):
    return tr._GATHERED_ROWS.labels(side, source).value


def _fused_half(side_name, fused, k=16):
    batch, (users, items) = _sides(k)
    y = tr.init_item_factors(items, 40, k, jax.random.PRNGKey(1))
    x = tr.solve_side_blocked(
        y, users.srows, users.scols, users.svals, users.slens, 0.01, 1.0,
        block=users.block, features=k, implicit=True,
        slot_chunk=users.slot_chunk, fused_gramian=fused, side=side_name)
    return batch, users, np.asarray(x)


@pytest.mark.parametrize("budget,source", [(None, "vmem"), (0, "hbm")],
                         ids=["table_held", "table_copied"])
def test_a_fused_half_counts_its_entries_under_its_source(
        budget, source, monkeypatch):
    if budget is not None:
        monkeypatch.setattr(pk, "_GG_RESIDENT_TABLE_BYTES", budget)
    side = f"gathered_{source}"
    batch, users, x = _fused_half(side, True)
    assert users.entries == len(batch.rows)
    assert _gathered(side, source) == users.entries
    assert all(_gathered(side, s) == 0 for s in _SOURCES if s != source)
    _fused_half(side, True)  # every call counts
    assert _gathered(side, source) == 2 * users.entries
    assert np.isfinite(x).all()


def test_an_einsum_half_gathers_nothing_the_counter_sees():
    before = sorted(tr._GATHERED_ROWS.samples())
    _fused_half("gathered_einsum", False)
    assert sorted(tr._GATHERED_ROWS.samples()) == before


def test_the_250f_cells_halves_gather_from_vmem_and_from_hbm(monkeypatch):
    """The Netflix-shaped 250-feature cell as the pack shapes it: the user
    half (448 blocks, ~1,550 slots a block) gathers from the 17,782-row
    item table, held in VMEM; the item half (17 blocks of 72,594 slots)
    from the 480,201-row user table, copied. Each half counts the cell's
    100,480,507 entries under its source — decided from the table's size
    alone, as on a TPU (the solve itself is not run)."""
    monkeypatch.setattr(pk, "on_tpu", lambda operand=None, mesh=None: True)
    monkeypatch.setattr(tr, "_solve_side_blocked_jit",
                        lambda *a, **kw: None)
    nnz, k = 100_480_507, 250
    for side, table_rows, n_blocks, slots, source in (
            ("cell_user", 17_782, 448, 1_560, "vmem"),
            ("cell_item", 480_201, 17, 72_594, "hbm")):
        chosen = tr._choose_formulation(None, True, k, slots, table_rows)
        assert chosen.fused
        assert tr._gather_source(chosen, table_rows, k) == source
        slens = np.zeros((n_blocks, slots), np.int32)
        q, r = divmod(nnz, slens.size)
        slens[:] = q
        slens.flat[:r] += 1
        srows = np.zeros((n_blocks, slots), np.int32)
        tr.solve_side_blocked(
            jax.ShapeDtypeStruct((table_rows, k), jnp.float32), srows, None,
            None, slens, 0.01, 1.0, block=1072, features=k, implicit=True,
            slot_chunk=slots, side=side)
        assert _gathered(side, source) == nnz
        assert all(_gathered(side, s) == 0 for s in _SOURCES if s != source)


@pytest.mark.parametrize("budget,source", [(None, "vmem"), (0, "hbm")],
                         ids=["table_held", "table_copied"])
def test_the_pack_log_line_names_the_source(budget, source, monkeypatch,
                                            caplog):
    monkeypatch.setattr(pk, "on_tpu", lambda operand=None, mesh=None: True)
    monkeypatch.setattr(tr, "_GG_WIDE_FEATURES", 0)
    monkeypatch.setattr(tr, "_GG_NARROW_FEATURES", 0)
    if budget is not None:
        monkeypatch.setattr(pk, "_GG_RESIDENT_TABLE_BYTES", budget)
    with caplog.at_level(logging.INFO, logger="oryx_tpu.models.als.train"):
        _sides(16)
    lines = [r.getMessage() for r in caplog.records
             if "slotted COO" in r.getMessage()]
    assert len(lines) == 2
    assert all(f"source: {source}" in line for line in lines), lines


def test_als_train_counts_the_gathered_rows_of_each_side(monkeypatch):
    k, iterations = 8, 2
    batch, (users, items) = _sides(k, seed=5)
    before = {s: _gathered(s, "vmem") for s in ("user", "item")}
    tr.als_train(batch, k, 0.01, 1.0, True, iterations=iterations,
                 key=jax.random.PRNGKey(2), block=32, fused_gramian=True)
    for side in ("user", "item"):
        assert _gathered(side, "vmem") - before[side] \
            == iterations * len(batch.rows)
