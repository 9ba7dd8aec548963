"""Differential kernel fuzz gate (ISSUE 15).

The static verifier (tests/test_kernel_verifier.py) proves structure; this
harness proves NUMBERS, two ways:

  * **differential fuzz** — a seeded (``random.Random``, no wall-clock
    nondeterminism) shape/dtype matrix drives both trainer kernels —
    ``spd_solve_batched`` and ``gather_gramian_accumulate`` — under
    ``interpret=True`` against plain numpy references, across the edge
    shapes that bite on chip: single-row batches, batch sizes straddling
    the pad tile, k at the VMEM budget boundary, empty rows, pad slots,
    single-slot grids, skewed slot fill, slots filled to 0, 1, T−1 and T
    entries with every padding column naming a row of NaN, slot widths
    around the copy loops' trip, bf16 inputs. Zero-input regions
    must come back BITWISE zero (the donated-alias contract); everything
    else within accumulation tolerance.

  * **budget consistency** — the runtime gates (``_GG_MAX_FEATURES``,
    ``_GG_MAX_SLOTS``, the ``spd_tile_b`` batch-tile formula) are recomputed from the PARSED
    kernel models (tools/analyze/kernelmodel.py) under the registered
    ``oryx.analyze.kernel.*`` budgets and asserted EQUAL. The hand-derived
    constants in ops/pallas_kernels.py can no longer silently drift from
    the kernels they guard: add a scratch buffer or grow a block and this
    file fails until both sides are re-derived.
"""

from __future__ import annotations

import os
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import oryx_tpu
from oryx_tpu.ops import pallas_kernels as pk

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(oryx_tpu.__file__)))
SEED = 0x0F15


# ---------------------------------------------------------------------------
# spd_solve_batched vs LAPACK
# ---------------------------------------------------------------------------


def _spd_cases():
    """The seeded shape matrix: every k-class the tile formula produces
    (full 256-tile, mid tiles, the 8-row boundary tile at k=256, and the
    cholesky fallback past it) × batch sizes around the pad tile."""
    rng = random.Random(SEED)
    cases = []
    for k in (1, 2, 5, 8, 13, 50, 64):
        b = rng.choice((1, 2, 7, 9, 33))
        cases.append((b, k))
    cases.append((209, 50))   # straddles the k=50 tile (tile_b=104)
    cases.append((2, 256))    # the LAST blocked k: tile_b == 8
    cases.append((2, 264))    # first fallback k: cholesky path
    # the blocked kernel: its first k (one column past a lane tile) and a
    # batch that straddles its 16-row tile at the trainer's 250 features
    cases.append((3, 129))
    cases.append((19, 250))
    return cases


@pytest.mark.parametrize("b,k", _spd_cases())
def test_spd_differential_matches_numpy(b, k):
    rng = np.random.default_rng(SEED + 1000 * b + k)
    m = rng.standard_normal((b, k, k)).astype(np.float32) * 0.3
    a = np.einsum("bij,bkj->bik", m, m) + 2.0 * np.eye(k, dtype=np.float32)
    rhs = rng.standard_normal((b, k)).astype(np.float32)
    x = np.asarray(pk.spd_solve_batched(a, rhs, interpret=True))
    ref = np.stack([np.linalg.solve(a[i], rhs[i]) for i in range(b)])
    err = np.abs(x - ref).max() / max(1e-9, np.abs(ref).max())
    tol = 1e-4 if k < 100 else 1e-3
    assert x.shape == (b, k) and np.isfinite(x).all()
    assert err < tol, (b, k, err)


def test_spd_boundary_tile_is_the_modeled_boundary():
    """The (2, 256) case above really did run at the smallest legal tile
    (the blocked kernel's, where b takes a third lane tile), and 264 really
    fell back — the fuzz matrix covers the budget boundary, not just round
    shapes. The unblocked kernel's tile is defined to 128 features only."""
    assert pk.spd_tile_b(50) == 104
    assert pk.spd_tile_b(128) == 24
    for k in (0, 129, 256):
        with pytest.raises(ValueError):
            pk.spd_tile_b(k)
    assert pk.spd_blocked_tile_b(256) == 8
    assert pk.spd_solve_path(50) == ("spd_kernel", 104)
    assert pk.spd_solve_path(128) == ("spd_kernel", 24)
    assert pk.spd_solve_path(129) == ("spd_blocked", 16)
    assert pk.spd_solve_path(256) == ("spd_blocked", 8)
    assert pk.spd_solve_path(264) == ("cholesky", 0)


# ---------------------------------------------------------------------------
# gather_gramian_accumulate vs numpy
# ---------------------------------------------------------------------------


def _gg_layout(rng, block, t, n_slots, n_pad_slots, skew):
    """A sorted slotted layout: real slots over a random subset of rows
    (guaranteeing empty rows), pad slots (owner = spill row, len 0) at the
    end, slot fill skewed when asked (mostly-empty slots plus full ones)."""
    owners = sorted(rng.choices(range(block), k=n_slots))
    srow = np.array(owners + [block] * n_pad_slots, dtype=np.int32)
    s = len(srow)
    slens = np.zeros(s, dtype=np.int32)
    for i in range(n_slots):
        if skew and rng.random() < 0.5:
            slens[i] = rng.choice((0, 1, t))
        else:
            slens[i] = rng.randint(0, t)
    return srow, slens


def _gg_fill_layout(rng, block, fills):
    """Slots of exactly the given fills, in order, over ascending rows.
    ``None`` is a pad slot (owner = spill row, length 0) and always ends its
    row: the pack writes pad slots only at a block's end, but the kernel's
    contract is wider — a change of owner flushes a block and zeroes the
    next, so a pad slot BETWEEN two rows must cost nothing but its step."""
    srow, slens, row = [], [], 0
    for f in fills:
        if f is None:
            srow.append(block)
            slens.append(0)
            row += 1
        else:
            srow.append(row)
            slens.append(f)
            row += rng.choice((0, 0, 1, 2))
    assert row < block
    return np.array(srow, np.int32), np.array(slens, np.int32)


def _gg_reference(y, srow, scols, w, coef, block):
    yg = y[scols]  # (S, T, k)
    ra = np.zeros((block + 1, y.shape[1], y.shape[1]), np.float32)
    rb = np.zeros((block + 1, y.shape[1]), np.float32)
    np.add.at(ra, srow, np.einsum("st,sti,stj->sij", w, yg, yg))
    np.add.at(rb, srow, np.einsum("st,sti->si", coef, yg))
    return ra, rb


def _gg_cases():
    """(k, t, block, layout, case_seed): ``layout`` is either
    (n_slots, n_pad, skew) for the random fill or a list of exact fills."""
    u = pk._GG_UNROLL
    rng = random.Random(SEED + 7)
    cases = []
    for k, t, block, layout in (
        (4, 1, 8, (3, 2, False)),     # T=1: one entry per slot
        (8, 4, 16, (1, 0, False)),    # single-slot grid
        (8, 8, 32, (12, 4, True)),    # skewed fill, pad slots
        (13, 7, 8, (5, 3, True)),     # nothing tile-round anywhere
        (50, 8, 64, (20, 4, False)),  # the production k
        (256, 4, 2, (3, 1, False)),   # k AT the resident-budget boundary
        # slots filled to 0, 1, T-1 and T entries; the FIRST slot of the
        # grid partly filled (its tail reads the scratch as the kernel's
        # first step left it); a pad slot between two rows
        (8, 8, 32, [3, 8, 0, 1, 7, None, 8, 5, 0, None, None]),
        # T smaller than, equal to and larger than the copy loops' trip
        (8, u // 2, 16, [1, u // 2, 0, u // 2 - 1, None]),
        (8, u, 16, [u - 1, u, 1, 0, u, None]),
        (8, 3 * u + 5, 16, [2, 3 * u + 5, u, u + 1, 2 * u - 1, 3 * u, None,
                            3 * u + 4, 0, 1]),
        # a first slot with nothing in it, then a row
        (12, 16, 8, [0, 16, 9]),
    ):
        cases.append((k, t, block, layout, rng.randrange(1 << 16)))
    return cases


def _gg_case_id(case):
    k, t, _, layout, _ = case
    kind = "fills" if isinstance(layout, list) else "fuzz"
    return f"k{k}-t{t}-{kind}{len(layout) if kind == 'fills' else layout[0]}"


@pytest.mark.parametrize("k,t,block,layout,case_seed", _gg_cases(),
                         ids=[_gg_case_id(c) for c in _gg_cases()])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gg_differential_matches_numpy(k, t, block, layout, case_seed, dtype):
    """The copying form: a DMA a row out of HBM."""
    if dtype == "bfloat16" and k == 256:
        pytest.skip("one boundary run is enough; bf16 covered at small k")
    _gg_differential(k, t, block, layout, case_seed, dtype, resident=False)


@pytest.mark.parametrize("k,t,block,layout,case_seed", _gg_cases(),
                         ids=[_gg_case_id(c) for c in _gg_cases()])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gg_resident_differential_matches_numpy(k, t, block, layout,
                                                case_seed, dtype):
    """The form that holds the table in VMEM, on the same matrix."""
    _gg_differential(k, t, block, layout, case_seed, dtype, resident=True)


def _gg_differential(k, t, block, layout, case_seed, dtype, resident):
    rng = random.Random(case_seed)
    nrng = np.random.default_rng(case_seed)
    if isinstance(layout, list):
        srow, slens = _gg_fill_layout(rng, block, layout)
    else:
        srow, slens = _gg_layout(rng, block, t, *layout)
    s = len(srow)
    n_opp = max(2 * k, 16)
    valid = np.arange(t)[None, :] < slens[:, None]
    mask = valid.astype(np.float32)
    # every entry past a slot's length names a row of NaN: one copy issued
    # for padding, or a scratch row that was never cleared, and the
    # Gramian is NaN (0 × NaN)
    scols = np.where(valid, np.sort(nrng.integers(0, n_opp, (s, t)), axis=1),
                     n_opp).astype(np.int32)
    w = (nrng.standard_normal((s, t)).astype(np.float32) * mask)
    coef = (nrng.standard_normal((s, t)).astype(np.float32) * mask)
    y = nrng.standard_normal((n_opp + 1, k)).astype(np.float32)
    y[n_opp] = np.nan

    yj = jnp.asarray(y)
    if dtype == "bfloat16":
        yj = yj.astype(jnp.bfloat16)
        # the kernel contracts bf16×bf16→f32; reference uses the SAME
        # rounded operands so only accumulation order differs
        y_ref = np.asarray(yj.astype(jnp.float32))
        w_ref = np.asarray(jnp.asarray(w).astype(jnp.bfloat16)
                           .astype(jnp.float32)) * mask
        coef_ref = np.asarray(jnp.asarray(coef).astype(jnp.bfloat16)
                              .astype(jnp.float32)) * mask
        tol = 2e-2
    else:
        y_ref, w_ref, coef_ref, tol = y, w, coef, 1e-4
    y_ref = np.nan_to_num(y_ref)  # the reference never meets the NaN row

    big_a, big_b = jax.jit(
        lambda *args: pk.gather_gramian_accumulate(
            *args, block=block, interpret=True, _resident=resident)
    )(yj, jnp.asarray(srow), jnp.asarray(slens), jnp.asarray(scols),
      jnp.asarray(w), jnp.asarray(coef))
    big_a, big_b = np.asarray(big_a), np.asarray(big_b)
    assert np.isfinite(big_a).all() and np.isfinite(big_b).all()

    ra, rb = _gg_reference(y_ref, srow, scols, w_ref, coef_ref, block)
    scale = max(1e-9, np.abs(ra).max(), np.abs(rb).max())
    assert np.abs(big_a - ra).max() / scale < tol, (k, t, block)
    assert np.abs(big_b - rb).max() / scale < tol, (k, t, block)

    # the donated-alias contract, BITWISE: rows no slot names return exact
    # zeros, not accumulation noise
    touched = set(srow.tolist())
    for r in range(block + 1):
        if r not in touched:
            assert not big_a[r].any() and not big_b[r].any(), r


def test_gg_supported_gate_spans_the_fuzz_matrix():
    """Every kernel-run case above sits inside the runtime gate, and the
    matrix's boundary case IS the gate's last legal k."""
    ks = [c[0] for c in _gg_cases()]
    assert all(pk.gather_gramian_supported(k, 64) for k in ks)
    assert max(ks) == pk._GG_MAX_FEATURES
    # and the matrix's slot widths straddle the copy loops' trip
    ts = {c[1] for c in _gg_cases()}
    assert min(ts) < pk._GG_UNROLL < max(ts) and pk._GG_UNROLL in ts


# ---------------------------------------------------------------------------
# budget consistency: the static model IS the runtime gate
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def ops_kernel_models():
    from oryx_tpu.tools.analyze.core import build_project
    from oryx_tpu.tools.analyze.kernelmodel import kernel_models

    project, errors = build_project(
        [os.path.join(REPO_ROOT, "oryx_tpu", "ops", "pallas_kernels.py")],
        root=REPO_ROOT,
    )
    assert errors == []
    return {m.name: m for m in kernel_models(project)}


def test_gg_max_features_equals_modeled_budget(ops_kernel_models):
    """THE drift gate: ``_GG_MAX_FEATURES`` must equal the largest k whose
    parsed, tile-padded resident footprint at the pack's maximum slot width
    fits the registered resident budget — and the runtime boolean gate must
    agree with the model at EVERY k, so neither side can move alone."""
    from oryx_tpu.tools.analyze.kernelmodel import budgets, pad_up

    gg = ops_kernel_models["gather_gramian_accumulate"]
    budget = budgets()["resident_budget_bytes"]

    def fits(k: int) -> bool:
        # kp is the wrapper's lane-padded gather width, pad128(k)
        nbytes = gg.vmem_bytes({"k": k, "t": pk._GG_SLOT_WIDTH_MAX,
                                "kp": pad_up(k, 128)})
        assert nbytes is not None, "gg model no longer evaluates — reparse"
        return nbytes <= budget

    modeled_max = max(k for k in range(8, 1025, 8) if fits(k))
    assert modeled_max == pk._GG_MAX_FEATURES
    for k in (1, 7, 8, 50, 200, 249, 255, 256, 257, 264, 300, 511, 512):
        assert pk.gather_gramian_supported(k, 64) == fits(k), k


def test_gg_resident_table_bytes_equal_the_modeled_budget(
        ops_kernel_models):
    """The table-resident form's drift gate: ``_GG_RESIDENT_TABLE_BYTES`` is
    the largest table — rows of 256 lanes, at the ``_GG_MAX_FEATURES`` gate
    and the widest slot — whose parsed footprint (the table, the gather
    tile, the accumulator and weight blocks) fits the model's budget for
    that form; the rule flips at that row, and the VMEM the wrapper asks
    the compiler for covers the footprint it admits."""
    from oryx_tpu.tools.analyze.kernelmodel import (
        GG_TABLE_RESIDENT_BUDGET_BYTES,
        pad_up,
    )

    held = ops_kernel_models["_gather_gramian_resident"]
    k, kp, t = pk._GG_MAX_FEATURES, 256, pk._GG_SLOT_WIDTH_MAX

    def footprint(rows: int) -> float:
        nbytes = held.vmem_bytes({"k": k, "kp": kp, "t": t, "r": rows})
        assert nbytes is not None, "resident model no longer evaluates"
        return nbytes

    step = 1024
    modeled_max = max(r for r in range(step, 1 << 17, step)
                      if footprint(r) <= GG_TABLE_RESIDENT_BUDGET_BYTES)
    assert modeled_max * kp * 4 == pk._GG_RESIDENT_TABLE_BYTES
    assert footprint(modeled_max + 1) > GG_TABLE_RESIDENT_BUDGET_BYTES
    for rows in (1, 17_782, modeled_max - 1, modeled_max, modeled_max + 1,
                 480_201):
        fits = footprint(rows) <= GG_TABLE_RESIDENT_BUDGET_BYTES
        assert pk.gather_gramian_resident(rows, k) == fits, rows
        assert pk.gather_gramian_resident(rows, 250) == fits, rows
    asked = (pad_up(modeled_max, 8) * kp * 4
             + pk._GG_RESIDENT_SCOPED_BYTES)
    assert footprint(modeled_max) <= asked <= 128 << 20


def test_gg_max_slots_equals_modeled_smem_budget(ops_kernel_models):
    """``_GG_MAX_SLOTS`` is the model's number, not a hand-derived island:
    the parsed call's scalar-prefetched operands (owner rows AND slot
    lengths, one word a slot each) against the share of SMEM they may take.
    A third prefetched vector, or a wider one, moves the model and fails
    here until the gate is re-derived. The whole SMEM footprint at the gate
    and the widest slot stays inside the compiler's limit, and the gate
    admits the Netflix cell's item side (72,594 slots a block)."""
    from oryx_tpu.tools.analyze.kernelmodel import (
        SMEM_LIMIT_BYTES,
        SMEM_PREFETCH_BUDGET_BYTES,
    )

    gg = ops_kernel_models["gather_gramian_accumulate"]
    assert gg.num_prefetch == 2
    assert gg.prefetch_shapes == [("s",), ("s",)]

    def fits(slots: int) -> bool:
        nbytes = gg.prefetch_smem_bytes({"s": slots})
        assert nbytes is not None, "gg prefetch no longer evaluates — reparse"
        return nbytes <= SMEM_PREFETCH_BUDGET_BYTES

    step = 1 << 10
    modeled_max = max(n for n in range(step, (1 << 18) + 1, step) if fits(n))
    assert modeled_max == pk._GG_MAX_SLOTS
    for slots in (1, 72_594, pk._GG_MAX_SLOTS, pk._GG_MAX_SLOTS + 1,
                  3 << 16, 1 << 18):
        assert pk.gather_gramian_supported(50, slots) == fits(slots), slots
    assert pk.gather_gramian_supported(50, 72_594)
    total = gg.smem_bytes({"s": pk._GG_MAX_SLOTS,
                           "t": pk._GG_SLOT_WIDTH_MAX})
    # the prefetched words + the two double-buffered (1, 1, T) index
    # blocks: this slot's and the next slot's
    assert total == (2 * 4 * pk._GG_MAX_SLOTS
                     + 2 * 2 * 4 * pk._GG_SLOT_WIDTH_MAX)
    assert total <= SMEM_LIMIT_BYTES


def test_spd_tile_formula_equals_modeled_budget(ops_kernel_models):
    """``spd_tile_b``'s hand math (pad8(k)·pad128(k+1) elements against the
    scoped budget) must match the parsed model's largest-single-buffer
    bytes — the augmented (tile_b, k, k+1) scratch — at every k it solves,
    to one lane tile (128); the blocked kernel's tile covers 129–256."""
    from oryx_tpu.tools.analyze.kernelmodel import budgets

    spd = ops_kernel_models["_spd_solve_call"]
    scoped = budgets()["scoped_budget_bytes"]

    def modeled_tile(k: int) -> int:
        for tb in range(pk._SPD_MAX_TILE, 0, -8):
            nbytes = spd.max_buffer_bytes({"tile_b": tb, "k": k})
            assert nbytes is not None, "spd model no longer evaluates"
            if nbytes <= scoped:
                return tb
        return 0

    for k in (1, 2, 8, 13, 50, 64, 100, 120, 127, 128):
        assert pk.spd_tile_b(k) == modeled_tile(k), k
        assert pk.spd_solve_path(k) == ("spd_kernel", pk.spd_tile_b(k)), k


def test_spd_blocked_tile_formula_equals_modeled_budget(ops_kernel_models):
    """``spd_blocked_tile_b``'s hand math (256 · pad128(k+1) elements of
    scratch a system against the blocked budget) must match the parsed
    blocked call's largest buffer at every k it solves — a buffer added to
    the kernel, or the budget moved on either side alone, fails here — and
    the model's whole footprint at that tile (double-buffered blocks and
    the scratch), with the compiler's measured allocation past it, must fit
    the scoped VMEM limit."""
    from oryx_tpu.tools.analyze.kernelmodel import (
        SPD_BLOCKED_BUDGET_BYTES,
        SPD_BLOCKED_SLACK_BYTES_PER_ROW,
        budgets,
        pad_up,
    )

    blocked = ops_kernel_models["_spd_blocked_call"]
    limit = budgets()["vmem_limit_bytes"]
    assert pk._SPD_BLOCKED_BUDGET_BYTES == SPD_BLOCKED_BUDGET_BYTES

    def bind(tb: int, k: int) -> dict:
        # kw is the wrapper's lane-padded scratch width, pad128(k + 1)
        return {"tile_b": tb, "k": k, "kw": pad_up(k + 1, 128)}

    def modeled_tile(k: int) -> int:
        for tb in range(pk._SPD_MAX_TILE, 0, -8):
            nbytes = blocked.max_buffer_bytes(bind(tb, k))
            assert nbytes is not None, "blocked model no longer evaluates"
            if nbytes <= SPD_BLOCKED_BUDGET_BYTES:
                return tb
        return 0

    for k in (129, 130, 200, 249, 250, 255, 256):
        tile = pk.spd_blocked_tile_b(k)
        assert tile == modeled_tile(k), k
        assert pk.spd_solve_path(k) == ("spd_blocked", tile), k
        assert (blocked.vmem_bytes(bind(tile, k))
                + tile * SPD_BLOCKED_SLACK_BYTES_PER_ROW) <= limit, k
    assert pk.spd_blocked_tile_b(250) == 16 and pk.spd_blocked_tile_b(256) == 8
    # the scratch is the largest buffer: 4 MiB at 16 rows of 250 features
    assert blocked.max_buffer_bytes(bind(16, 250)) == SPD_BLOCKED_BUDGET_BYTES


def _lsh_cases():
    """Seeded: window tiles from the narrowest a layout takes to the
    widest, one to three queries, float32 and bfloat16, lists of one grid
    step and of several, with and without the repeated padding window."""
    rng = random.Random(SEED + 40)
    cases = []
    for tile in (16, 64, 128):
        for b in (1, 2, 3):
            steps = rng.choice((1, 3))
            pad = rng.choice((0, 5))
            cases.append((tile, b, steps, pad, rng.randrange(1 << 16)))
    return cases


@pytest.mark.parametrize("tile,b,steps,pad,case_seed", _lsh_cases())
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_lsh_scan_differential_matches_numpy(tile, b, steps, pad, case_seed,
                                             dtype):
    rng = np.random.default_rng(case_seed)
    listed = steps * pk.LSH_WINDOWS_A_STEP
    n_win = listed + 7
    width = 250 if tile == 128 else 128
    mat = jnp.asarray(rng.standard_normal((n_win * tile, width),
                                          dtype=np.float32)).astype(dtype)
    qs = jnp.asarray(rng.standard_normal((b, width),
                                         dtype=np.float32)).astype(dtype)
    windows = rng.choice(n_win, listed, replace=False).astype(np.int32)
    real = listed - pad
    if pad:
        windows[real:] = windows[real - 1]
    win_valid = rng.integers(0, tile + 1, n_win).astype(np.int32)
    owners = rng.integers(1, 1 << b, listed).astype(np.int32)
    owners[real:] = 0
    got = np.asarray(pk.lsh_listed_scores(
        mat, qs, jnp.asarray(windows << b | owners), jnp.asarray(win_valid),
        tile=tile, interpret=True))
    m64 = np.asarray(mat.astype(jnp.float32), dtype=np.float64)
    q64 = np.asarray(qs.astype(jnp.float32), dtype=np.float64)
    want = np.full((b, listed * tile), -np.inf)
    for j in range(real):
        w, n = windows[j], win_valid[windows[j]]
        for q in range(b):
            if owners[j] >> q & 1:
                want[q, j * tile:j * tile + n] = (
                    m64[w * tile:w * tile + n] @ q64[q])
    assert got.dtype == np.float32 and got.shape == want.shape
    np.testing.assert_array_equal(np.isneginf(got), np.isneginf(want))
    fin = np.isfinite(want)
    if fin.any():
        np.testing.assert_allclose(got[fin], want[fin], rtol=1e-5,
                                   atol=1e-5 * np.abs(want[fin]).max())


def test_lsh_prefetch_words_equal_the_modeled_smem_budget(ops_kernel_models):
    """``LSH_PREFETCH_WORDS`` is the model's number: the listed call's
    scalar-prefetched operands — the list and every window's real rows —
    against the share of SMEM prefetched operands may take, and
    ``lsh_lists`` agrees with the model at every size; at 5M rows the
    longest warmed list fits. Its VMEM at the widest tile, width, dtype
    and flush stays far inside the scoped limit."""
    from oryx_tpu.tools.analyze.kernelmodel import (
        SMEM_PREFETCH_BUDGET_BYTES, budgets)

    scan = ops_kernel_models["lsh_listed_scores"]
    assert scan.num_prefetch == 2
    assert scan.prefetch_shapes == [("listed",), ("n_windows",)]

    def fits(listed: int, n_windows: int) -> bool:
        return scan.prefetch_smem_bytes(
            {"listed": listed, "n_windows": n_windows}) \
            <= SMEM_PREFETCH_BUDGET_BYTES

    assert pk.LSH_PREFETCH_WORDS * 4 == SMEM_PREFETCH_BUDGET_BYTES
    for listed, n_windows in ((8, 8), (27_520, 47_255), (100_000, 96_608),
                              (100_000, 96_609), (150_000, 60_000)):
        assert pk.lsh_lists(listed, n_windows) == fits(listed, n_windows), (
            listed, n_windows)
    assert pk.lsh_lists(27_520, 47_255)
    bind = {"b": 2, "k": 250, "tile": 128, "group": pk.LSH_WINDOWS_A_STEP}
    assert scan.vmem_bytes(bind) <= budgets()["vmem_limit_bytes"] / 4


def test_budget_knobs_registered_and_defaults_agree():
    """The ``oryx.analyze.kernel.*`` keys exist in reference_conf and their
    registered defaults equal the module constants the checkers use when no
    config is loaded — one budget surface, not two."""
    from oryx_tpu.common.config import Config
    from oryx_tpu.common.reference_conf import REFERENCE_CONF
    from oryx_tpu.tools.analyze.kernelmodel import budgets

    conf = Config.parse_string(REFERENCE_CONF)
    b = budgets(conf)
    assert conf.get_int("oryx.analyze.kernel.vmem-limit-bytes") \
        == b["vmem_limit_bytes"] == 16 << 20
    assert conf.get_int("oryx.analyze.kernel.scoped-budget-bytes") \
        == b["scoped_budget_bytes"] == pk._SPD_SCOPED_BUDGET_BYTES
    assert conf.get_int("oryx.analyze.kernel.resident-budget-bytes") \
        == b["resident_budget_bytes"] == 1_583_104
