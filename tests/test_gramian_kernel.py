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


# ---------------------------------------------------------------------------
# the software pipeline: slot i+1's rows are fetched under slot i's matmuls
# ---------------------------------------------------------------------------

# (T, [(owner row or None = the spill row, valid length), ...]): packs chosen
# for the pipeline's edges. Every slot gathers its own, disjoint rows of y.
_PIPELINE_CASES = {
    "one-slot": (8, [(0, 5)]),
    "two-slots": (8, [(0, 8), (0, 3)]),
    "only-empty-slots": (8, [(None, 0), (None, 0)]),
    "empty-first": (8, [(0, 0), (1, 8), (1, 2)]),
    "empty-last": (8, [(0, 8), (1, 4), (None, 0)]),
    "empty-between-rows": (8, [(0, 8), (None, 0), (2, 8)]),
    "empty-between-same-row": (8, [(0, 8), (0, 0), (0, 5)]),
    "two-empty-between": (8, [(0, 3), (None, 0), (None, 0), (4, 8)]),
    "owner-changes-every-slot": (8, [(0, 8), (1, 8), (2, 8), (5, 8)]),
    "length-1-beside-length-T": (16, [(0, 1), (0, 16), (1, 16), (2, 1),
                                      (3, 1)]),
    # THE hazard: a long slot's copies in flight with the short next slot's.
    # On one shared semaphore the short slot's row landing first satisfies a
    # wait of the long slot, whose matmul then reads rows that have not
    # arrived; reading the other buffer gives the neighbour's rows
    "hazard-long-then-short": (16, [(0, 16), (1, 1), (2, 16), (3, 1),
                                    (3, 16)]),
}


def _pipeline_pack(case: str, k: int = 8):
    """Integer-valued operands (every product and sum exact in float32, so
    bit-equality holds whatever order a matmul sums in); padding columns
    name a row of NaN; slot i gathers rows [i·T, i·T + n) of y."""
    t, slots = _PIPELINE_CASES[case]
    block = 8
    rng = np.random.default_rng(sorted(_PIPELINE_CASES).index(case))
    s = len(slots)
    srow = np.array([block if r is None else r for r, _ in slots], np.int32)
    slens = np.array([n for _, n in slots], np.int32)
    n_opp = s * t
    valid = np.arange(t)[None, :] < slens[:, None]
    scols = np.where(valid, np.arange(n_opp).reshape(s, t), n_opp).astype(
        np.int32)
    w = (rng.integers(1, 4, (s, t)) * valid).astype(np.float32)
    coef = (rng.integers(-3, 4, (s, t)) * valid).astype(np.float32)
    y = rng.integers(-4, 5, (n_opp + 1, k)).astype(np.float32)
    y[n_opp] = np.nan
    return block, t, y, srow, slens, scols, w, coef


def _slot_by_slot(block, t, y, srow, slens, scols, w, coef):
    """Plain numpy, in the kernel's order: two gather buffers zeroed once,
    slot i copies its first ``slens[i]`` rows into buffer i % 2 (rows past
    them keep what slot i − 2 left and meet weight 0), then accumulates
    into its owner row; an empty slot does nothing."""
    k = y.shape[1]
    bufs = np.zeros((2, t, k), np.float32)
    big_a = np.zeros((block + 1, k, k), np.float32)
    big_b = np.zeros((block + 1, k), np.float32)
    for i, n in enumerate(slens):
        if n == 0:
            continue
        buf = bufs[i % 2]
        buf[:n] = y[scols[i, :n]]
        big_a[srow[i]] += (buf * w[i][:, None]).T @ buf
        big_b[srow[i]] += coef[i] @ buf
    return big_a, big_b


def _dma_on_wait():
    """The TPU interpreter with copies executed only when a wait needs them,
    LATEST started first: a wait that another slot's copies can satisfy
    leaves this slot's rows unwritten, as the chip may."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.InterpretParams(dma_execution_mode="on_wait",
                                 detect_races=True)


def _run_kernel(fn, pack, interpret):
    block, _, y, srow, slens, scols, w, coef = pack
    big_a, big_b = jax.jit(
        lambda *a: fn(*a, block=block, interpret=interpret)
    )(jnp.asarray(y), jnp.asarray(srow), jnp.asarray(slens),
      jnp.asarray(scols), jnp.asarray(w), jnp.asarray(coef))
    return np.asarray(big_a), np.asarray(big_b)


@pytest.mark.parametrize("mode", ["interpret", "dma-on-wait"])
@pytest.mark.parametrize("case", list(_PIPELINE_CASES))
def test_pipeline_edges_bit_equal_to_slot_by_slot_numpy(case, mode):
    """A copy is started in one grid step and waited for in the next: the
    two buffers and both semaphores carry over, the first step starts two
    slots' copies, the last starts none, an empty slot neither starts nor
    waits — and every output is the slot-by-slot accumulation's to the
    bit, under the plain interpreter and under the TPU interpreter's
    late, out-of-order copies (which also reports no race)."""
    pack = _pipeline_pack(case)
    ra, rb = _slot_by_slot(*pack)
    interpret = True if mode == "interpret" else _dma_on_wait()
    big_a, big_b = _run_kernel(gather_gramian_accumulate, pack, interpret)
    np.testing.assert_array_equal(big_a, ra)
    np.testing.assert_array_equal(big_b, rb)
    if mode == "dma-on-wait":
        from jax._src.pallas.mosaic.interpret import interpret_pallas_call

        assert not interpret_pallas_call.races.races_found


_PLANTED_FAULTS = {
    # every copy of both buffers on semaphore 0, as the single-buffer kernel
    # had it: shows only where copies run late and out of order
    "one-semaphore": ([("sem.at[b],", "sem.at[0],"),
                       ("sem.at[buf]).wait()", "sem.at[0]).wait()")], True),
    # the matmul reads the buffer the NEXT slot is landing in
    "wrong-buffer": ([("ygv = yg[buf]", "ygv = yg[1 - buf]")], False),
}


@pytest.mark.parametrize("fault", list(_PLANTED_FAULTS))
def test_pipeline_hazard_case_catches_a_planted_fault(fault):
    """The hazard case has teeth: the kernel's own source with the fault
    planted gives ANOTHER answer on it. (A wait on the other buffer's
    semaphore alone never returns under the TPU interpreter — nothing
    signals it — so it is the shared semaphore that is planted.)"""
    import inspect
    import types

    from oryx_tpu.ops import pallas_kernels as pk

    edits, late_copies = _PLANTED_FAULTS[fault]
    source = inspect.getsource(pk)
    for old, new in edits:
        assert source.count(old) == 1, "the kernel moved: re-aim the fault"
        source = source.replace(old, new)
    faulty = types.ModuleType("pallas_kernels_faulty")
    exec(compile(source, "pallas_kernels_faulty.py", "exec"),
         faulty.__dict__)
    pack = _pipeline_pack("hazard-long-then-short")
    ra, rb = _slot_by_slot(*pack)
    big_a, big_b = _run_kernel(faulty.gather_gramian_accumulate, pack,
                               _dma_on_wait() if late_copies else True)
    assert not (np.array_equal(big_a, ra, equal_nan=True)
                and np.array_equal(big_b, rb, equal_nan=True))


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
        # every slot but the first of each block's call is fetched under
        # the slot before it; blocks of 64 rows: a short pipeline, and the
        # share says so
        assert side.prefetched_slots == side.real_slots - int(
            (slens > 0).any(axis=1).sum())
        assert 0 < side.prefetched_slots < side.real_slots
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


def test_pack_logs_the_share_of_slots_fetched_under_a_predecessor(
        caplog, monkeypatch):
    """What tells a reader of a small-block deployment that its pipeline is
    mostly prologue: a kernel side's log line carries prefetched ÷ real
    slots (an einsum side has no pipeline: tests/test_als_formulation.py).
    One block a side here, so all but one slot of each; the pack is asked as
    on a TPU where every width counts as one the kernel is ahead at."""
    import logging

    from oryx_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "on_tpu", lambda operand=None, mesh=None: True)
    monkeypatch.setattr(tr, "_GG_NARROW_FEATURES", 0)
    monkeypatch.setattr(tr, "_GG_WIDE_FEATURES", 0)
    batch, k = _skewed_batch(9)
    with caplog.at_level(logging.INFO, logger="oryx_tpu.models.als.train"):
        sides = tr.prepare_blocked(batch, k, block=512)
    lines = [r.getMessage() for r in caplog.records
             if "slotted COO" in r.getMessage()]
    assert len(lines) == 2
    for side, line in zip(sides, lines):
        assert side.n_blocks == 1
        assert side.prefetched_slots == side.real_slots - 1
        share = 100.0 * side.prefetched_slots / side.real_slots
        assert f"{share:.3f}% of the slots are fetched under" in line


# ---------------------------------------------------------------------------
# layout cache + pack/compute overlap
# ---------------------------------------------------------------------------


def _sides_equal(a, b) -> bool:
    return all(
        np.array_equal(np.asarray(getattr(a, f)), np.asarray(getattr(b, f)))
        for f in ("srows", "scols", "svals", "slens")
    ) and (a.block, a.n_blocks, a.slot_width, a.slot_chunk, a.n_rows,
           a.entries, a.real_slots, a.prefetched_slots) == (
        b.block, b.n_blocks, b.slot_width, b.slot_chunk, b.n_rows,
        b.entries, b.real_slots, b.prefetched_slots
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
