"""The trainer's gather-Gramian formulation is chosen a SIDE.

``train._choose_formulation`` is the one gate: off a TPU the einsum, on one
whatever a sweep on the chip measured fastest for the width and the bytes of
the opposite factor table the side gathers from (PERF.md §6, PRs 29 and 31:
the einsum wherever XLA's gather holds up; where it does not — rows under 64
features out of a table past 40 MiB — the SAME einsum over that table
zero-padded to 64 columns, the gate's third answer; the fused Pallas kernel
for 200 features and more). These tests hold the rule to its shapes, every
caller to the one answer, the padded gather to the unpadded einsum's numbers,
and the run's own tracing (the pack's log line,
``oryx_als_half_formulation``) to the formulation that ran. No test here
measures anything: which side of a crossover is faster is the chip's to
say."""

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
    "asked, on_tpu, features, slots, table_rows, fused, width, why, warns",
    [
        pytest.param(None, True, 50, *NF_USER, False, 50,
                     "are 3.6 MB, under the 41.9 MB from which", False,
                     id="the-cell's-user-half-einsum"),
        pytest.param(None, True, 50, *NF_ITEM, False, 64,
                     "are 96.0 MB, at or over the 41.9 MB from which", False,
                     id="the-cell's-item-half-einsum-at-64"),
        pytest.param(None, True, 32, NF_ITEM[0], 284000, False, 32,
                     "are 36.4 MB, under the 41.9 MB from which", False,
                     id="32f-under-the-crossover"),
        pytest.param(None, True, 32, *NF_ITEM, False, 64,
                     "are 61.5 MB, at or over the 41.9 MB from which", False,
                     id="32f-the-netflix-user-table-einsum-at-64"),
        pytest.param(None, True, 50, NF_ITEM[0], 200000, False, 50,
                     "are 40.0 MB, under the 41.9 MB from which", False,
                     id="50f-under-the-crossover"),
        pytest.param(None, True, 32, NF_ITEM[0], 1 << 20, False, 64,
                     "zero-padded to 64 columns", False,
                     id="32f-over-the-crossover-einsum-at-64"),
        pytest.param(None, True, 63, NF_ITEM[0], 1 << 20, False, 64,
                     "zero-padded to 64 columns", False,
                     id="63f-over-the-crossover-einsum-at-64"),
        pytest.param(None, True, 64, NF_ITEM[0], 1 << 20, False, 64,
                     "the einsum is ahead at every size", False,
                     id="64f-einsum-as-it-is"),
        pytest.param(None, True, pk._GG_MAX_FEATURES + 1, *NF_ITEM, False,
                     pk._GG_MAX_FEATURES + 1, "past the kernel's gates", True,
                     id="features-gate-exceeded"),
        pytest.param(None, True, 50, pk._GG_MAX_SLOTS + 1, NF_ITEM[1], False,
                     50, "past the kernel's gates", True,
                     id="slots-gate-exceeded"),
        pytest.param(True, True, 50, pk._GG_MAX_SLOTS + 1, NF_ITEM[1], False,
                     50, "past the kernel's gates", True,
                     id="forced-kernel-past-a-gate"),
        pytest.param(False, True, pk._GG_MAX_FEATURES + 1, *NF_ITEM, False,
                     pk._GG_MAX_FEATURES + 1, "asked for", False,
                     id="forced-einsum-asks-no-gate"),
        pytest.param(None, False, 50, *NF_USER, False, 50, "not on a TPU",
                     False, id="off-tpu-small-table"),
        pytest.param(None, False, 50, 1024, 10 ** 8, False, 50,
                     "not on a TPU", False, id="off-tpu-any-size"),
        pytest.param(None, False, 50, *NF_ITEM, False, 50, "not on a TPU",
                     False, id="off-tpu-the-cell's-item-half-unpadded"),
        pytest.param(True, True, 50, *NF_USER, True, 50, "asked for", False,
                     id="forced-kernel-under-the-crossover"),
        pytest.param(True, True, 50, *NF_ITEM, True, 50, "asked for", False,
                     id="forced-kernel-over-the-crossover"),
        pytest.param(False, True, 50, *NF_ITEM, False, 50, "asked for", False,
                     id="forced-einsum-over-the-crossover-unpadded"),
        pytest.param(True, False, 50, *NF_USER, True, 50, "asked for", False,
                     id="forced-kernel-off-tpu"),
        pytest.param(False, False, 50, *NF_ITEM, False, 50, "asked for",
                     False, id="forced-einsum-off-tpu"),
    ])
def test_the_gate_over_shapes(asked, on_tpu, features, slots, table_rows,
                              fused, width, why, warns, caplog):
    with caplog.at_level(logging.WARNING, logger=tr.__name__):
        chosen = tr._choose_formulation(asked, on_tpu, features, slots,
                                        table_rows)
    assert chosen[:2] == (fused, width) and why in chosen.why, chosen
    assert chosen.name(features) == (
        "fused kernel" if fused else
        "einsum" if width == features else "padded einsum")
    assert tr._resolve_fused(asked, on_tpu, features, slots,
                             table_rows) == (fused, width)
    assert any(WARNS in r.getMessage() for r in caplog.records) is warns


KERNEL, EINSUM, AT_64 = "kernel", "einsum", "einsum at 64"


def _answer(features, slots, table_rows) -> str:
    fused, width = tr._resolve_fused(None, True, features, slots, table_rows)
    assert width == features or (not fused
                                 and width == tr._GG_NARROW_FEATURES)
    return KERNEL if fused else EINSUM if width == features else AT_64


@pytest.mark.parametrize("features, small, large", [
    pytest.param(32, EINSUM, AT_64, id="32f-einsum-then-einsum-at-64"),
    pytest.param(50, EINSUM, AT_64, id="50f-einsum-then-einsum-at-64"),
    pytest.param(63, EINSUM, AT_64, id="63f-einsum-then-einsum-at-64"),
    pytest.param(64, EINSUM, EINSUM, id="64f-einsum"),
    pytest.param(128, EINSUM, EINSUM, id="128f-einsum"),
    pytest.param(199, EINSUM, EINSUM, id="199f-einsum"),
    pytest.param(200, KERNEL, EINSUM, id="200f-kernel-then-einsum"),
    pytest.param(250, KERNEL, EINSUM, id="250f-kernel-then-einsum"),
])
@pytest.mark.parametrize("slots", [NF_USER[0], NF_ITEM[0]])
def test_the_rule_is_monotone_in_the_opposite_tables_size(features, small,
                                                          large, slots):
    """With the rest held, a growing opposite table changes the answer at
    most once, at the named crossover of its width's regime (bytes of factor
    rows: rows × features × 4) — and the slot count, inside the kernel's
    gate, changes nothing. Under 64 features no size answers the kernel."""
    sizes = sorted([1 << e for e in range(8, 25)] + [NF_USER[1], NF_ITEM[1]])
    answers = [_answer(features, slots, n) for n in sizes]
    assert (answers[0], answers[-1]) == (small, large)
    switches = [n for n, a, b in zip(sizes[1:], answers, answers[1:])
                if a != b]
    assert len(switches) == (small != large)
    if switches:
        crossover = tr._GG_NARROW_TABLE_BYTES if large == AT_64 \
            else tr._GG_WIDE_TABLE_BYTES
        assert sizes[sizes.index(switches[0]) - 1] * features * 4 \
            < crossover <= switches[0] * features * 4


@pytest.mark.parametrize("features, user_half, item_half", [
    # als-nf100m-50f, the benchmark's cell: the einsum on both halves — as it
    # is for the user half (3.6 MB of item rows), over the user table padded
    # to 64 columns for the item half (96 MB of user rows)
    pytest.param(50, EINSUM, AT_64, id="nf100m-50f"),
    # the queued train-nf100m-250f: the kernel on both (17.8 MB, 480 MB)
    pytest.param(250, KERNEL, KERNEL, id="nf100m-250f"),
    pytest.param(100, EINSUM, EINSUM, id="nf100m-100f"),
    pytest.param(32, EINSUM, AT_64, id="nf100m-32f"),
])
def test_the_netflix_shapes_resolve_as_the_sweep_measured(features, user_half,
                                                          item_half):
    assert _answer(features, 1617, 17770) == user_half
    assert _answer(features, 13260, 480189) == item_half


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
    notes the formulation it was handed and the width of the table it is to
    gather from, then runs the einsum with XLA's cholesky (no Pallas kernel
    compiles for a CPU)."""
    handed = []
    real = tr._solve_block

    def spy(y, srow, scols, svals, slens, **kw):
        handed.append((kw["fused_gramian"], y.shape[1]))
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
    one gate: the user half (small opposite table) traces the einsum over
    rows as wide as they are, the item half (large one) the einsum over rows
    padded to 64 columns — on every path, whatever its blocks."""
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
    assert direct == one_device == over_a_mesh == [(False, k), (False, 64)]


@pytest.mark.parametrize("asked", [None, True, False])
def test_the_run_names_the_formulation_that_ran(asked, described_tpu, caplog):
    """The log line a side and ``oryx_als_half_formulation{side,
    formulation}`` say what ``_solve_block`` was handed, with the reason and
    the gather width: the observed table against the crossover under the
    rule, "asked for" when forced (rows then gathered as wide as they are).
    The share of slots fetched a step early is a kernel side's."""
    handed = described_tpu
    batch, k = _batch()
    with caplog.at_level(logging.INFO, logger=tr.__name__):
        tr.als_train(batch, k, 0.01, 1.0, True, iterations=1,
                     key=jax.random.PRNGKey(0), fused_gramian=asked)
    ran = dict(zip(("user", "item"), handed))
    assert ran == ({"user": (False, k), "item": (False, 64)} if asked is None
                   else {"user": (asked, k), "item": (asked, k)})
    lines = {side: next(r.getMessage() for r in caplog.records
                        if f"als.train.{side}_half side" in r.getMessage())
             for side in ran}
    for side, (fused, width) in ran.items():
        name = "fused kernel" if fused else \
            "einsum" if width == k else "padded einsum"
        assert f"formulation: {name} (" in lines[side]
        assert f"factor rows of {width} columns an entry" in lines[side]
        assert ("fetched under the slot before" in lines[side]) is fused
        for label in tr._FORMULATION_NAMES:
            assert tr._HALF_FORMULATION.labels(side, label).value \
                == float(label == name)
    if asked is None:
        assert "rows of 8 features are 0.0 MB, under the 0.0 MB from which" \
            in lines["user"]
        assert "rows of 8 features are 0.1 MB, at or over the 0.0 MB from which" \
            in lines["item"]
        assert "zero-padded to 64 columns)" in lines["item"]
        assert "zero-padded" not in lines["user"]
    else:
        assert all("(asked for)" in line for line in lines.values())


def test_a_forced_kernel_still_runs_the_kernel(monkeypatch):
    """``fused_gramian=True`` through ``solve_side_blocked``, the cell's own
    call: the half-iteration goes through the Pallas gather-Gramian kernel
    (interpreted here) and agrees with the rule's own answer for the same
    operands, which off a TPU is the einsum and calls no kernel."""
    import numpy as np

    calls = []
    real = pk.gather_gramian_accumulate
    monkeypatch.setattr(
        pk, "gather_gramian_accumulate",
        lambda *a, **kw: calls.append(kw["interpret"]) or real(*a, **kw))
    batch, k = _skewed_batch(7)
    user_side, item_side = tr.prepare_blocked(batch, k)
    y = tr.init_item_factors(item_side, len(batch.items), k,
                             jax.random.PRNGKey(2))

    def half(asked):
        jax.clear_caches()
        return np.asarray(tr.solve_side_blocked(
            y, user_side.srows, user_side.scols, user_side.svals,
            user_side.slens, 0.01, 1.0, block=user_side.block, features=k,
            implicit=True, slot_chunk=user_side.slot_chunk,
            fused_gramian=asked))

    ruled = half(None)
    assert calls == []
    forced = half(True)
    assert calls and all(calls)
    assert np.abs(forced - ruled).max() < 1e-4 * np.abs(ruled).max()


# ---------------------------------------------------------------------------
# the padded gather computes what the unpadded einsum computes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("devices", [1, 8], ids=["one-device", "mesh-of-8"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("implicit", [True, False],
                         ids=["implicit", "explicit"])
def test_the_padded_gather_half_is_the_unpadded_einsum_half(implicit, dtype,
                                                            devices):
    """The einsum over the opposite table zero-padded to 64 columns keeps
    the leading ``features`` of every slot's Gramian and right-hand side
    before the segment-sums: the padded columns are exact zeros and the
    contraction runs over a slot's entries, not over features, so both
    halves of an iteration come out as the unpadded einsum's — to the last
    bit on this backend (elsewhere: within 1e-6 of the largest factor), for
    both feedback models and both compute dtypes, on one device and with
    the blocks sharded over a mesh (every shard pads its own copy)."""
    import numpy as np

    batch, k = _skewed_batch(11, n_users=700, n_items=90, nnz=5000,
                             explicit=not implicit)
    assert k < tr._GG_NARROW_FEATURES
    user_side, item_side = tr.prepare_blocked(batch, k, ndev=devices)
    y = tr.init_item_factors(item_side, len(batch.items), k,
                             jax.random.PRNGKey(1))
    if devices > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from oryx_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(devices, axes=("model",))

        def half(side, opp, width):
            arrays = [jax.device_put(a, NamedSharding(
                mesh, P("model", *([None] * (a.ndim - 1)))))
                for a in (side.srows, side.scols, side.svals, side.slens)]
            return tr._sharded_solver(
                mesh, "model", side.block, k, implicit, side.slot_chunk,
                dtype, False, False, True, width)(opp, *arrays, 0.01, 1.3)
    else:
        def half(side, opp, width):
            return tr._solve_side_blocked_jit(
                opp, side.srows, side.scols, side.svals, side.slens, 0.01,
                1.3, block=side.block, features=k, implicit=implicit,
                slot_chunk=side.slot_chunk, dtype=dtype, spd_kernel=False,
                fused_gramian=False, kernel_interpret=True,
                gather_width=width)

    x = half(user_side, y, None)
    for side, opp in ((user_side, y), (item_side, x)):
        plain = np.asarray(half(side, opp, None))
        padded = np.asarray(half(side, opp, tr._GG_NARROW_FEATURES))
        assert plain.shape == padded.shape == (side.padded_rows, k)
        assert np.abs(plain).max() > 0.1
        if jax.default_backend() == "cpu":
            assert np.array_equal(plain, padded)
        assert np.abs(plain - padded).max() <= 1e-6 * np.abs(plain).max()


# ---------------------------------------------------------------------------
# a chunk's gather stays off the multiples of 1,024 rows
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", [None, 64], ids=["unpadded", "padded"])
@pytest.mark.parametrize("chunk, gathers", [
    pytest.param(16, 16, id="512-rows-as-they-are"),
    pytest.param(32, 33, id="1024-rows-one-spare-slot"),
    pytest.param(64, 65, id="2048-rows-one-spare-slot"),
    pytest.param(24, 24, id="768-rows-as-they-are"),
])
def test_a_chunk_never_gathers_a_multiple_of_1024_rows(chunk, gathers, width):
    """XLA:TPU stages a gather of a multiple of 1,024 rows through half the
    buffer (PERF.md §6, PR 31): a chunk of ``slot_chunk`` × T such rows
    carries one EMPTY slot more — every weight 0, owner the spill row — so
    the program gathers ``slot_chunk + 1`` slots and the factors are those
    of any other chunking of the same slots."""
    import re

    import jax.numpy as jnp
    import numpy as np

    batch, k = _skewed_batch(3, nnz=6000)
    _, side = tr.prepare_blocked(batch, k, block=128, slot_width=32)
    slots = side.srows.shape[1]
    assert side.slot_width == 32 and side.n_blocks == 1 and 192 < slots < 384
    grow = lambda a, fill=0: jnp.pad(
        a, ((0, 0), (0, 384 - slots)) + ((0, 0),) * (a.ndim - 2),
        constant_values=fill)  # 384 slots: whole chunks of 16, 24, 32 and 64
    arrays = (grow(side.srows, side.block), grow(side.scols),
              grow(side.svals), grow(side.slens))
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (260, k), jnp.float32)

    def half(slot_chunk):
        return tr._solve_side_blocked_jit.trace(
            x, *arrays, 0.01, 1.3, block=side.block, features=k,
            implicit=True, slot_chunk=slot_chunk, dtype="float32",
            spd_kernel=False, fused_gramian=False, kernel_interpret=True,
            gather_width=width)

    traced = half(chunk)
    shapes = set(re.findall(r"f32\[(\d+),32,(\d+)\] = gather",
                            str(traced.jaxpr)))
    assert shapes == {(str(gathers), str(width or k))}, shapes
    assert gathers * 32 % tr._GATHER_HALVED_ROWS
    got = np.asarray(traced.lower().compile()(x, *arrays, 0.01, 1.3))
    want = np.asarray(half(8).lower().compile()(x, *arrays, 0.01, 1.3))
    assert np.abs(want).max() > 0.1
    assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()
