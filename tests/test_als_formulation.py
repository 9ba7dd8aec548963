"""The trainer's gather-Gramian formulation is chosen a SIDE.

``train._choose_formulation`` is the one gate: off a TPU the einsum, on one
whichever a sweep on the chip measured faster for the width and the bytes of
the opposite factor table the side gathers from (PERF.md §6, PR 29: the
einsum wherever XLA's gather holds up; the fused Pallas kernel for rows under
64 features out of a table past 80 MiB, and for 200 features and more). These tests hold the rule to its
shapes, every caller to the one answer, and the run's own tracing (the pack's
log line, ``oryx_als_half_formulation``) to the formulation that ran. No test
here measures anything: which side of the crossover is faster is the chip's
to say."""

import logging

import pytest

import jax

from test_gramian_kernel import _skewed_batch

from oryx_tpu.models.als import train as tr
from oryx_tpu.ops import pallas_kernels as pk

# the Netflix cell's two sides as the pack shapes them at 50 features:
# (slots a block, rows of the opposite table as the solver sees it)
NF_USER = (11800, 3 * 5924)  # gathers the 17,770-row item table
NF_ITEM = (72594, 59 * 8139)  # gathers the 480,189-row user table
WARNS = "past its VMEM/SMEM gates"


@pytest.mark.parametrize(
    "asked, on_tpu, features, slots, table_rows, fused, why, warns",
    [
        pytest.param(None, True, 50, *NF_USER, False,
                     "are 3.6 MB, under the 83.9 MB where", False,
                     id="the-cell's-user-half-einsum"),
        pytest.param(None, True, 50, *NF_ITEM, True,
                     "are 96.0 MB, at or over the 83.9 MB where", False,
                     id="the-cell's-item-half-kernel"),
        pytest.param(None, True, pk._GG_MAX_FEATURES + 1, *NF_ITEM, False,
                     "past the kernel's gates", True,
                     id="features-gate-exceeded"),
        pytest.param(None, True, 50, pk._GG_MAX_SLOTS + 1, NF_ITEM[1], False,
                     "past the kernel's gates", True,
                     id="slots-gate-exceeded"),
        pytest.param(True, True, 50, pk._GG_MAX_SLOTS + 1, NF_ITEM[1], False,
                     "past the kernel's gates", True,
                     id="forced-kernel-past-a-gate"),
        pytest.param(False, True, pk._GG_MAX_FEATURES + 1, *NF_ITEM, False,
                     "asked for", False, id="forced-einsum-asks-no-gate"),
        pytest.param(None, False, 50, *NF_USER, False, "not on a TPU", False,
                     id="off-tpu-small-table"),
        pytest.param(None, False, 50, 1024, 10 ** 8, False, "not on a TPU",
                     False, id="off-tpu-any-size"),
        pytest.param(True, True, 50, *NF_USER, True, "asked for", False,
                     id="forced-kernel-under-the-crossover"),
        pytest.param(False, True, 50, *NF_ITEM, False, "asked for", False,
                     id="forced-einsum-over-the-crossover"),
        pytest.param(True, False, 50, *NF_USER, True, "asked for", False,
                     id="forced-kernel-off-tpu"),
        pytest.param(False, False, 50, *NF_ITEM, False, "asked for", False,
                     id="forced-einsum-off-tpu"),
    ])
def test_the_gate_over_shapes(asked, on_tpu, features, slots, table_rows,
                              fused, why, warns, caplog):
    with caplog.at_level(logging.WARNING, logger=tr.__name__):
        got, reason = tr._choose_formulation(asked, on_tpu, features, slots,
                                             table_rows)
    assert got is fused and why in reason, (got, reason)
    assert tr._resolve_fused(asked, on_tpu, features, slots,
                             table_rows) is fused
    assert any(WARNS in r.getMessage() for r in caplog.records) is warns


@pytest.mark.parametrize("features, small, large", [
    pytest.param(32, False, True, id="32f-einsum-then-kernel"),
    pytest.param(50, False, True, id="50f-einsum-then-kernel"),
    pytest.param(64, False, False, id="64f-einsum"),
    pytest.param(128, False, False, id="128f-einsum"),
    pytest.param(199, False, False, id="199f-einsum"),
    pytest.param(200, True, False, id="200f-kernel-then-einsum"),
    pytest.param(250, True, False, id="250f-kernel-then-einsum"),
])
@pytest.mark.parametrize("slots", [NF_USER[0], NF_ITEM[0]])
def test_the_rule_is_monotone_in_the_opposite_tables_size(features, small,
                                                          large, slots):
    """With the rest held, a growing opposite table changes the answer at
    most once, at the named crossover of its width's regime (bytes of factor
    rows: rows × features × 4) — and the slot count, inside the kernel's
    gate, changes nothing."""
    sizes = sorted([1 << e for e in range(8, 25)] + [NF_USER[1], NF_ITEM[1]])
    answers = [tr._resolve_fused(None, True, features, slots, n)
               for n in sizes]
    assert (answers[0], answers[-1]) == (small, large)
    switches = [n for n, a, b in zip(sizes[1:], answers, answers[1:])
                if a != b]
    assert len(switches) == (small != large)
    if switches:
        crossover = tr._GG_NARROW_TABLE_BYTES if large \
            else tr._GG_WIDE_TABLE_BYTES
        assert sizes[sizes.index(switches[0]) - 1] * features * 4 \
            < crossover <= switches[0] * features * 4


@pytest.mark.parametrize("features, user_half, item_half", [
    # als-nf100m-50f, the benchmark's cell: the einsum for the user half
    # (3.6 MB of item rows), the kernel for the item half (96 MB of user rows)
    pytest.param(50, False, True, id="nf100m-50f"),
    # the queued train-nf100m-250f: the kernel on both (17.8 MB, 480 MB)
    pytest.param(250, True, True, id="nf100m-250f"),
    pytest.param(100, False, False, id="nf100m-100f"),
])
def test_the_netflix_shapes_resolve_as_the_sweep_measured(features, user_half,
                                                          item_half):
    assert tr._resolve_fused(None, True, features, 1617, 17770) is user_half
    assert tr._resolve_fused(None, True, features, 13260, 480189) is item_half


# ---------------------------------------------------------------------------
# one gate, every caller
# ---------------------------------------------------------------------------


def _batch():
    """2,000 users × 90 items at 8 features: the item table a user half
    gathers from is tiny, the user table an item half gathers from is not."""
    return _skewed_batch(5, n_users=2000, n_items=90, nnz=4000)


@pytest.fixture
def described_tpu(monkeypatch):
    """The trainer as it decides on a TPU, run on the CPU: ``on_tpu`` answers
    yes, the narrow rows' crossover sits between this file's two tiny tables
    (≤ 256 item rows, ≥ 2,000 user rows, 8 features each), and ``_solve_block`` is a spy that
    notes the formulation it was handed, then runs the einsum with XLA's
    cholesky (no Pallas kernel compiles for a CPU)."""
    handed = []
    real = tr._solve_block

    def spy(y, srow, scols, svals, slens, **kw):
        handed.append(kw["fused_gramian"])
        if not kw["kernel_interpret"]:
            kw.update(spd_kernel=False, fused_gramian=False,
                      kernel_interpret=True)
        return real(y, srow, scols, svals, slens, **kw)

    monkeypatch.setattr(pk, "on_tpu", lambda operand=None, mesh=None: True)
    monkeypatch.setattr(tr, "_GG_NARROW_TABLE_BYTES", 1000 * 8 * 4)
    monkeypatch.setattr(tr, "_solve_block", spy)
    jax.clear_caches()
    tr._sharded_solver.cache_clear()
    yield handed
    # programs traced through the spy must not outlive it
    jax.clear_caches()
    tr._sharded_solver.cache_clear()


def test_every_caller_hands_solve_block_the_one_answer(described_tpu):
    """``solve_side_blocked`` (the benchmark cell's call), ``als_train`` on
    one device and ``als_train`` over a mesh each resolve a side through the
    one gate: the user half (small opposite table) traces the einsum, the
    item half (large one) the kernel — on every path, whatever its blocks."""
    handed = described_tpu
    batch, k = _batch()
    user_side, item_side = tr.prepare_blocked(batch, k)
    y = tr.init_item_factors(item_side, len(batch.items), k,
                             jax.random.PRNGKey(0))

    def half(side, opp):
        return tr.solve_side_blocked(
            opp, side.srows, side.scols, side.svals, side.slens, 0.01, 1.0,
            block=side.block, features=k, implicit=True,
            slot_chunk=side.slot_chunk)

    def traced(run) -> list:
        # a program already traced for these shapes would not reach the spy
        jax.clear_caches()
        handed.clear()
        run()
        return list(handed)

    from oryx_tpu.parallel.mesh import make_mesh

    train = dict(iterations=1, key=jax.random.PRNGKey(0))
    direct = traced(lambda: half(item_side, half(user_side, y)))
    one_device = traced(
        lambda: tr.als_train(batch, k, 0.01, 1.0, True, **train))
    over_a_mesh = traced(lambda: tr.als_train(
        batch, k, 0.01, 1.0, True, **train,
        mesh=make_mesh(8, axes=("model",)), row_axis="model"))
    assert direct == one_device == over_a_mesh == [False, True]


@pytest.mark.parametrize("asked", [None, True, False])
def test_the_run_names_the_formulation_that_ran(asked, described_tpu, caplog):
    """The log line a side and ``oryx_als_half_formulation{side,
    formulation}`` say what ``_solve_block`` was handed, with the reason: the
    observed table against the crossover under the rule, "asked for" when
    forced. The share of slots fetched a step early is a kernel side's."""
    handed = described_tpu
    batch, k = _batch()
    with caplog.at_level(logging.INFO, logger=tr.__name__):
        tr.als_train(batch, k, 0.01, 1.0, True, iterations=1,
                     key=jax.random.PRNGKey(0), fused_gramian=asked)
    ran = dict(zip(("user", "item"), handed))
    assert ran == ({"user": False, "item": True} if asked is None
                   else {"user": asked, "item": asked})
    lines = {side: next(r.getMessage() for r in caplog.records
                        if f"als.train.{side}_half side" in r.getMessage())
             for side in ran}
    for side, fused in ran.items():
        name, other = ("fused kernel", "einsum") if fused \
            else ("einsum", "fused kernel")
        assert f"formulation: {name} (" in lines[side]
        assert ("fetched under the slot before" in lines[side]) is fused
        assert tr._HALF_FORMULATION.labels(side, name).value == 1.0
        assert tr._HALF_FORMULATION.labels(side, other).value == 0.0
    if asked is None:
        assert "rows of 8 features are 0.0 MB, under the 0.0 MB where" \
            in lines["user"]
        assert "rows of 8 features are 0.1 MB, at or over the 0.0 MB where" \
            in lines["item"]
    else:
        assert all("(asked for)" in line for line in lines.values())
