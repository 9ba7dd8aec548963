"""The fused Pallas gather-Gramian kernel and the pack pipeline around it.

``gather_gramian_accumulate`` replaces the trainer's einsum + segment-sum
Gramian accumulation on TPU (train._solve_block fused_gramian path), so a
defect would corrupt every on-chip training run while a CPU-only suite
stayed green. These tests run the SAME kernel under Pallas interpret mode
(forced via ``fused_gramian=True`` off-TPU — the production selection logic
flips interpret on automatically) and pin it against the einsum formulation
across implicit/explicit × f32/bf16, skewed degrees, and empty rows.

The second half pins the host-pack machinery the kernel feeds on:
``BlockedLayoutCache`` reuse/delta packs must be bit-identical to a
from-scratch pack, and ``als_train``'s pack/compute overlap must report its
critical-path pack cost."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import LenOnlyIDs as _IDs

from oryx_tpu.models.als import train as tr
from oryx_tpu.models.als.data import RatingBatch
from oryx_tpu.ops.pallas_kernels import (
    gather_gramian_accumulate,
    gather_gramian_supported,
)


def _skewed_batch(seed, n_users=260, n_items=90, nnz=1800, k=8,
                  explicit=False):
    """Row-skewed interactions: a few hot users own ~half the entries (so
    they span several slots), plus guaranteed empty rows at the top end."""
    rng = np.random.default_rng(seed)
    hot = rng.integers(0, 5, nnz // 2)
    cold = rng.integers(5, n_users - 20, nnz - nnz // 2)  # last 20 rows empty
    rows = np.concatenate([hot, cold]).astype(np.int32)
    cols = rng.integers(0, n_items, nnz).astype(np.int32)
    if explicit:
        vals = rng.standard_normal(nnz).astype(np.float32) * 2.0
    else:
        vals = (np.abs(rng.standard_normal(nnz)) + 0.1).astype(np.float32)
    return RatingBatch(rows, cols, vals, _IDs(n_users), _IDs(n_items)), k


def _half(side, y, k, *, implicit, dtype, fused):
    return np.asarray(tr.solve_side_blocked(
        y, side.srows, side.scols, side.svals, side.slens, 0.01, 1.3,
        block=side.block, features=k, implicit=implicit,
        slot_chunk=side.slot_chunk, dtype=dtype, fused_gramian=fused,
    ))


@pytest.mark.parametrize("implicit", [True, False])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_matches_einsum_path(implicit, dtype):
    """The production parity claim: solve_side_blocked(fused_gramian=True)
    — the exact TPU path, interpret-emulated — equals the einsum
    formulation within f32 accumulation tolerance, on row-skewed data with
    empty rows, for both feedback models and both input precisions."""
    batch, k = _skewed_batch(3, explicit=not implicit)
    user_side, item_side = tr.prepare_blocked(batch, k, block=64)
    y = tr.init_item_factors(item_side, len(batch.items), k,
                             jax.random.PRNGKey(0))
    a = _half(user_side, y, k, implicit=implicit, dtype=dtype, fused=False)
    b = _half(user_side, y, k, implicit=implicit, dtype=dtype, fused=True)
    denom = max(1e-9, np.abs(a).max())
    tol = 1e-4 if dtype == "float32" else 2e-2
    assert np.abs(a - b).max() / denom < tol
    # empty rows must be EXACT zeros on both paths (reference: absent IDs)
    deg = np.bincount(batch.rows, minlength=len(batch.users))
    empty = np.flatnonzero(deg == 0)
    assert len(empty) > 0
    assert not a[empty].any() and not b[empty].any()


def test_kernel_direct_against_numpy_reference():
    """The kernel alone (no solve, no regularization) against a dense numpy
    accumulation: per-slot Gramians summed into owner rows; pad slots and
    never-visited rows land exact zeros via the donated inputs."""
    rng = np.random.default_rng(0)
    block, k, t, n_opp = 32, 12, 8, 64
    srow = np.array([0, 0, 1, 3, 3, 3, 7, 31] + [block] * 8, dtype=np.int32)
    s = len(srow)
    scols = rng.integers(0, n_opp, (s, t)).astype(np.int32)
    slens = rng.integers(0, t + 1, s).astype(np.int32)
    slens[srow == block] = 0
    w = rng.standard_normal((s, t)).astype(np.float32)
    coef = rng.standard_normal((s, t)).astype(np.float32)
    mask = np.arange(t)[None, :] < slens[:, None]
    w *= mask
    coef *= mask
    # padding entries name a row of NaN: the kernel copies a slot's first
    # ``slens`` rows and no others, and clears its scratch before the first
    scols = np.where(mask, scols, n_opp).astype(np.int32)
    y = rng.standard_normal((n_opp + 1, k)).astype(np.float32)
    y[n_opp] = np.nan

    big_a, big_b = jax.jit(
        lambda *a: gather_gramian_accumulate(*a, block=block, interpret=True)
    )(jnp.asarray(y), jnp.asarray(srow), jnp.asarray(slens),
      jnp.asarray(scols), jnp.asarray(w), jnp.asarray(coef))

    yg = np.nan_to_num(y)[scols]  # (S, T, k)
    ra = np.zeros((block + 1, k, k), np.float32)
    rb = np.zeros((block + 1, k), np.float32)
    np.add.at(ra, srow, np.einsum("st,sti,stj->sij", w, yg, yg))
    np.add.at(rb, srow, np.einsum("st,sti->si", coef, yg))
    assert np.abs(np.asarray(big_a) - ra).max() < 1e-4
    assert np.abs(np.asarray(big_b) - rb).max() < 1e-4
    # rows never named by srow: exact zeros (not garbage) from the donors
    visited = set(srow.tolist())
    for r in range(block + 1):
        if r not in visited:
            assert not np.asarray(big_a[r]).any()
            assert not np.asarray(big_b[r]).any()


def test_supported_gate():
    assert gather_gramian_supported(50, 4096)
    assert not gather_gramian_supported(512, 4096)
    # owner rows and slot lengths ride whole in the compiler's 1 MiB of
    # SMEM: the Netflix item side's 72,594 slots a block fit, 100,000 do not
    assert gather_gramian_supported(50, 72_594)
    assert not gather_gramian_supported(50, 100_000)
    assert not gather_gramian_supported(50, 1 << 18)
    # above the gate, the platform default must fall back, not fail
    batch, _ = _skewed_batch(5)
    side, item_side = tr.prepare_blocked(batch, 300, block=64)
    y = tr.init_item_factors(item_side, len(batch.items), 300,
                             jax.random.PRNGKey(0))
    out = _half(side, y, 300, implicit=True, dtype="float32", fused=None)
    assert np.isfinite(out).all()


@pytest.mark.parametrize("fused", [True, False])
def test_gather_rows_counted_as_issued(fused):
    """The pack counts its entries and the slots that hold them; the
    trainer's cost accounting charges the gather for the rows the chosen
    formulation ISSUES — one an entry under the fused kernel (it copies a
    slot to its own length), every cell of every slot under the einsum —
    and both sides report what copying every slot to its width would be."""
    from oryx_tpu.common import profiling

    batch, k = _skewed_batch(7)
    nnz = len(batch.rows)
    for side in tr.prepare_blocked(batch, k, block=64):
        slens = np.asarray(side.slens)
        assert side.entries == nnz == int(slens.sum())
        assert side.real_slots == int((slens > 0).sum())
        before, now = side.gather_rows_per_entry(fused)
        assert before == side.real_slots * side.slot_width / nnz > 1.0
        assert now == (1.0 if fused else side.scols.size / nnz)
        assert side.gather_rows(fused) == (nnz if fused
                                           else side.scols.size)
    tr.als_train(batch, k, 0.01, 1.0, True, iterations=1,
                 key=jax.random.PRNGKey(2), block=64, fused_gramian=fused)
    u_side, i_side = tr.prepare_blocked(batch, k, block=64)
    for key, side in (("als.train.user_half", u_side),
                      ("als.train.item_half", i_side)):
        _, nbytes = profiling.costs().cost(key)
        writes = side.padded_rows * k * (k + 1) * 4.0
        assert nbytes == side.gather_rows(fused) * k * 4.0 + writes


# ---------------------------------------------------------------------------
# layout cache + pack/compute overlap
# ---------------------------------------------------------------------------


def _sides_equal(a, b) -> bool:
    return all(
        np.array_equal(np.asarray(getattr(a, f)), np.asarray(getattr(b, f)))
        for f in ("srows", "scols", "svals", "slens")
    ) and (a.block, a.n_blocks, a.slot_width, a.slot_chunk, a.n_rows,
           a.entries, a.real_slots) == (
        b.block, b.n_blocks, b.slot_width, b.slot_chunk, b.n_rows,
        b.entries, b.real_slots
    )


def test_layout_cache_reuses_unchanged_batch():
    batch, k = _skewed_batch(11)
    cache = tr.BlockedLayoutCache()
    u1, i1 = tr.prepare_blocked(batch, k, cache=cache)
    assert cache.last_modes == {"user": "full", "item": "full"}
    u2, i2 = tr.prepare_blocked(batch, k, cache=cache)
    assert cache.last_modes == {"user": "reused", "item": "reused"}
    # identical CONTENTS — in fact the same device-ready sides (no re-pack,
    # no re-upload)
    assert u2 is u1 and i2 is i1


def test_layout_cache_delta_equals_full_pack():
    """An appended generation's incremental pack must be bit-identical to a
    from-scratch pack of the full batch — slabs, geometry, everything."""
    batch, k = _skewed_batch(12)
    rng = np.random.default_rng(99)
    cache = tr.BlockedLayoutCache()
    tr.prepare_blocked(batch, k, cache=cache)
    # few enough appends that the auto slot width T holds (a shifted T is
    # the geometry-drift case, covered below by the full-repack fallback)
    extra = 60
    batch2 = RatingBatch(
        np.concatenate([batch.rows,
                        rng.integers(0, 5, extra).astype(np.int32)]),
        np.concatenate([batch.cols,
                        rng.integers(0, len(batch.items),
                                     extra).astype(np.int32)]),
        np.concatenate([batch.vals, np.ones(extra, np.float32)]),
        batch.users, batch.items,
    )
    u_delta, i_delta = tr.prepare_blocked(batch2, k, cache=cache)
    assert cache.last_modes == {"user": "delta", "item": "delta"}
    u_full, i_full = tr.prepare_blocked(batch2, k)
    assert _sides_equal(u_delta, u_full)
    assert _sides_equal(i_delta, i_full)
    # and a THIRD generation appends on top of the delta result
    batch3 = RatingBatch(
        np.concatenate([batch2.rows, np.array([7, 8], np.int32)]),
        np.concatenate([batch2.cols, np.array([1, 2], np.int32)]),
        np.concatenate([batch2.vals, np.ones(2, np.float32)]),
        batch.users, batch.items,
    )
    u3, _ = tr.prepare_blocked(batch3, k, cache=cache)
    assert _sides_equal(u3, tr.prepare_blocked(batch3, k)[0])


def test_layout_cache_delta_on_production_row_sorted_batches():
    """The production pipeline re-sorts every generation by row, so new
    interactions for mid-order users land MID-ARRAY, not at the tail; the
    cache must still recognize the extension (row-wise prefix match) and
    take the delta path — through the real aggregate/build_rating_batch
    machinery, not synthetic concatenation."""
    from oryx_tpu.models.als import data as als_data

    k = 8
    rng = np.random.default_rng(21)
    lines1 = [
        f"u{u:03d},i{rng.integers(0, 40):02d},1,{n}"
        for n, u in enumerate(rng.integers(0, 120, 900))
    ]

    def build(lines):
        return als_data.build_rating_batch(
            als_data.aggregate(als_data.parse_lines(lines), True, False,
                               1e-5)
        )

    b1 = build(lines1)
    # gen2 adds NEW (user, item) pairs among EXISTING ids for mid-sorted
    # users — the id→index maps stay stable, which is the shape the delta
    # path serves (new ids landing mid-sort-order renumber an axis and
    # correctly fall back to full). No existing pair is re-rated (that
    # would change its aggregated value -> full).
    seen = set(zip(b1.rows.tolist(), b1.cols.tolist()))
    extra = []
    for j in range(6):
        u = 60 + j
        i = next(i for i in range(40)
                 if (b1.users.id_to_index[f"u{u:03d}"],
                     b1.items.id_to_index[f"i{i:02d}"]) not in seen)
        extra.append(f"u{u:03d},i{i:02d},1,{10_000 + j}")
    b2 = build(lines1 + extra)
    # the pipeline really did insert mid-array (not a pure tail append)
    n1 = len(b1.rows)
    assert not (np.array_equal(b1.rows, b2.rows[:n1])
                and np.array_equal(b1.cols, b2.cols[:n1]))
    cache = tr.BlockedLayoutCache()
    tr.prepare_blocked(b1, k, cache=cache)
    u_delta, i_delta = tr.prepare_blocked(b2, k, cache=cache)
    assert cache.last_modes == {"user": "delta", "item": "delta"}
    u_full, i_full = tr.prepare_blocked(b2, k)
    assert _sides_equal(u_delta, u_full)
    assert _sides_equal(i_delta, i_full)


def test_layout_cache_full_repack_on_changed_history():
    """Changed historical values (e.g. time decay rewriting strengths) must
    fall back to a correct full pack, not a wrong delta."""
    batch, k = _skewed_batch(13)
    cache = tr.BlockedLayoutCache()
    tr.prepare_blocked(batch, k, cache=cache)
    decayed = RatingBatch(batch.rows, batch.cols,
                          batch.vals * np.float32(0.95),
                          batch.users, batch.items)
    u, i = tr.prepare_blocked(decayed, k, cache=cache)
    assert cache.last_modes == {"user": "full", "item": "full"}
    assert _sides_equal(u, tr.prepare_blocked(decayed, k)[0])


def test_als_train_overlap_timings_and_cache_stability():
    """als_train packs the item side concurrently with the first user
    half-iteration and reports the pack cost that actually blocked the
    critical path; a second generation over the same batch reuses the
    cached layout and produces identical factors."""
    batch, k = _skewed_batch(14)
    cache = tr.BlockedLayoutCache()
    tm1: dict = {}
    x1, y1 = tr.als_train(batch, k, 0.01, 1.0, True, iterations=2,
                          key=jax.random.PRNGKey(1), layout_cache=cache,
                          timings=tm1)
    assert {"pack_s", "pack_user_s", "pack_item_s",
            "pack_wait_s"} <= set(tm1)
    assert tm1["pack_modes"] == {"user": "full", "item": "full"}
    assert tm1["pack_s"] == pytest.approx(
        tm1["pack_user_s"] + tm1["pack_wait_s"], abs=2e-3
    )
    tm2: dict = {}
    x2, y2 = tr.als_train(batch, k, 0.01, 1.0, True, iterations=2,
                          key=jax.random.PRNGKey(1), layout_cache=cache,
                          timings=tm2)
    assert tm2["pack_modes"] == {"user": "reused", "item": "reused"}
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
