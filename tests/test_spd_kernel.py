"""The Pallas batched SPD solver behind the TPU training path.

On TPU ``spd_solve_batched`` replaces XLA's cholesky+cho_solve inside every
ALS half-iteration (train._solve_block), so a lowering or numerical defect
would corrupt every on-chip training run while a CPU-only suite stayed
green. These tests run the SAME kernel under Pallas interpret mode (the
suite's CPU backend auto-selects it) and pin it against LAPACK.
"""

import numpy as np
import pytest

from oryx_tpu.ops import pallas_kernels as pk
from oryx_tpu.ops.pallas_kernels import spd_solve_batched


def _random_spd(rng, b, k, shift=2.0):
    m = rng.standard_normal((b, k, k)).astype(np.float32) * 0.3
    return np.einsum("bij,bkj->bik", m, m) + shift * np.eye(k, dtype=np.float32)


def _als_spd(rng, b, k, lam=0.001, n_max=10_000):
    """Systems as the trainer builds them: YᵀY + Σ (c − 1)·y·yᵀ over a
    row's interactions + λ·n·I, with n up to ``n_max``."""
    y = rng.standard_normal((500, k)).astype(np.float32) * 0.1
    yty = y.T @ y
    n = rng.integers(1, n_max + 1, b)
    n[0] = n_max
    out = np.empty((b, k, k), np.float32)
    for i in range(b):
        rows = y[rng.integers(0, len(y), min(int(n[i]), 64))]
        conf = rng.uniform(1.0, 40.0, len(rows)).astype(np.float32)
        out[i] = (yty + np.einsum("s,si,sj->ij", conf - 1.0, rows, rows)
                  + lam * n[i] * np.eye(k, dtype=np.float32))
    return out


# the unblocked kernel (to 128 features), then the blocked one: its first
# width, two in the middle, and its last (where b takes a third lane tile)
@pytest.mark.parametrize("b,k", [(70, 13), (5, 50), (257, 50), (3, 1), (8, 64),
                                 (5, 129), (9, 200), (17, 250), (3, 256)])
def test_matches_lapack(b, k):
    rng = np.random.default_rng(b * 100 + k)
    a = _random_spd(rng, b, k)
    rhs = rng.standard_normal((b, k)).astype(np.float32)
    x = np.asarray(spd_solve_batched(a, rhs, interpret=True))
    ref = np.stack([np.linalg.solve(a[i].astype(np.float64), rhs[i])
                    for i in range(b)])
    err = np.abs(x - ref).max() / np.abs(ref).max()
    assert err < 1e-4, (b, k, err)


@pytest.mark.parametrize("k", [129, 250, 256])
def test_blocked_matches_lapack_on_als_systems(k):
    """The blocked kernel on what the trainer hands it, a batch that
    straddles its tile: YᵀY + λ·n·I (λ = 0.001, n to 10⁴) and the
    confidence-weighted outer products, against float64 LAPACK."""
    rng = np.random.default_rng(k)
    path, tile = pk.spd_solve_path(k)
    assert path == "spd_blocked"
    b = tile + 3
    a = _als_spd(rng, b, k)
    rhs = rng.standard_normal((b, k)).astype(np.float32)
    x = np.asarray(spd_solve_batched(a, rhs, interpret=True))
    ref = np.stack([np.linalg.solve(a[i].astype(np.float64), rhs[i])
                    for i in range(b)])
    assert x.shape == (b, k) and np.isfinite(x).all()
    err = np.abs(x - ref).max() / np.abs(ref).max()
    assert err < 1e-4, (k, err)


def test_padding_rows_produce_no_nan():
    # batch not a multiple of any tile: pad rows are solved against identity
    rng = np.random.default_rng(0)
    a = _random_spd(rng, 9, 50)
    rhs = rng.standard_normal((9, 50)).astype(np.float32)
    x = np.asarray(spd_solve_batched(a, rhs, interpret=True))
    assert x.shape == (9, 50)
    assert np.isfinite(x).all()


def test_huge_k_falls_back_to_cholesky():
    # k past the scoped-VMEM budget must still solve (XLA cholesky path)
    rng = np.random.default_rng(1)
    k = 480
    a = _random_spd(rng, 2, k, shift=5.0)
    rhs = rng.standard_normal((2, k)).astype(np.float32)
    x = np.asarray(spd_solve_batched(a, rhs, interpret=True))
    ref = np.stack([np.linalg.solve(a[i], rhs[i]) for i in range(2)])
    assert np.abs(x - ref).max() / np.abs(ref).max() < 1e-3


def test_trainer_spd_path_matches_cholesky_path():
    """solve_side_blocked(spd_kernel=True) — the exact TPU production path,
    interpret-emulated — must produce the same factors as the CPU cholesky
    path."""
    _trainer_paths_agree(8)


def test_trainer_blocked_spd_path_matches_cholesky_path():
    """The same at 250 features, where the blocked kernel solves."""
    assert pk.spd_solve_path(250)[0] == "spd_blocked"
    _trainer_paths_agree(250)


def _trainer_paths_agree(k):
    import jax

    from oryx_tpu.models.als import train as tr
    from oryx_tpu.models.als.data import RatingBatch

    from conftest import LenOnlyIDs as _IDs

    rng = np.random.default_rng(7)
    n_users, n_items, nnz = 300, 120, 2000
    batch = RatingBatch(
        rng.integers(0, n_users, nnz).astype(np.int32),
        rng.integers(0, n_items, nnz).astype(np.int32),
        np.ones(nnz, dtype=np.float32),
        _IDs(n_users), _IDs(n_items),
    )
    user_side, item_side = tr.prepare_blocked(batch, k, block=128)
    y = tr.init_item_factors(item_side, n_items, k, jax.random.PRNGKey(0))

    def half(spd):
        return np.asarray(tr.solve_side_blocked(
            y, user_side.srows, user_side.scols, user_side.svals,
            user_side.slens, 0.01, 1.0, block=user_side.block, features=k,
            implicit=True, slot_chunk=user_side.slot_chunk, spd_kernel=spd,
        ))

    x_chol = half(False)
    x_spd = half(True)
    denom = max(1e-9, np.abs(x_chol).max())
    assert np.abs(x_spd - x_chol).max() / denom < 1e-4
