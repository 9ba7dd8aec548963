"""The Pallas kernel verifier (ISSUE 15): fixture pairs for the five static
kernel checks, the parsed kernel models of the REAL kernels in
``ops/pallas_kernels.py``, the ``--cost`` kernel table, and the
``trace_summary --batch`` kernel-row rendering.

Everything here is pure AST — fixtures are parsed, never imported or traced
(the differential harness in tests/test_kernel_differential.py is where the
kernels actually run).
"""

from __future__ import annotations

import io
import json
import os
import textwrap

import pytest

import oryx_tpu
from oryx_tpu.tools.analyze import analyze_source
from oryx_tpu.tools.analyze.core import build_project

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(oryx_tpu.__file__)))

_PALLAS_IDS = {
    "kernel-vmem-budget", "kernel-tile-alignment", "kernel-index-bounds",
    "kernel-alias-discipline", "kernel-interpret-default",
}

_PRELUDE = """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
"""


def _run(src: str, checker: str):
    source = textwrap.dedent(_PRELUDE) + textwrap.dedent(src)
    findings = analyze_source(source, checkers=[checker])
    return [f for f in findings if f.checker == checker]


# ---------------------------------------------------------------------------
# kernel-vmem-budget
# ---------------------------------------------------------------------------


def test_vmem_budget_fires_on_oversized_concrete_blocks():
    hits = _run(
        """
        def kern(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        def big(x, interpret):
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((4096, 512), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM)],
                out_specs=pl.BlockSpec((4096, 512), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((16384, 512), jnp.float32),
                interpret=interpret,
            )(x)
        """,
        "kernel-vmem-budget",
    )
    # 2 blocks × 4096·512·4 B × 2 (pipeline double-buffer) = 32 MiB > 16 MiB
    assert len(hits) == 1
    assert hits[0].symbol == "big:vmem"
    assert "MiB" in hits[0].message and "double-buffered" in hits[0].message


def test_vmem_budget_quiet_under_limit_and_on_symbolic_shapes():
    clean = """
        def kern(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        def ok(x, interpret):
            return pl.pallas_call(
                kern,
                grid=(64,),
                in_specs=[pl.BlockSpec((256, 512), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM)],
                out_specs=pl.BlockSpec((256, 512), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((16384, 512), jnp.float32),
                interpret=interpret,
            )(x)

        def sym(x, t, k, interpret):
            return pl.pallas_call(
                kern,
                grid=(t,),
                in_specs=[pl.BlockSpec((t, k), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM)],
                out_specs=pl.BlockSpec((t, k), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((t, k), jnp.float32),
                interpret=interpret,
            )(x)
        """
    assert _run(clean, "kernel-vmem-budget") == []


# ---------------------------------------------------------------------------
# kernel-tile-alignment
# ---------------------------------------------------------------------------


def test_tile_alignment_fires_on_pad_waste_and_hard_misalignment():
    hits = _run(
        """
        def kern(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        def wasteful(x, interpret):
            return pl.pallas_call(
                kern,
                grid=(8,),
                in_specs=[pl.BlockSpec((8, 100), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM)],
                out_specs=pl.BlockSpec((100, 128), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((800, 128), jnp.float32),
                interpret=interpret,
            )(x)
        """,
        "kernel-tile-alignment",
    )
    assert len(hits) == 2
    by_symbol = {f.symbol: f for f in hits}
    # lane dim 100 under a constant lane map: pure pad-waste (128 rounds)
    assert "wasteful:in0:lane" in by_symbol
    assert "padding" in by_symbol["wasteful:in0:lane"].message
    # sublane dim 100 with a grid-varying map over an 800-row operand: not
    # a multiple of 8 and not the whole dim — the lowering refuses it
    assert "wasteful:out0:sublane" in by_symbol
    assert "refuses" in by_symbol["wasteful:out0:sublane"].message


def test_tile_alignment_fires_on_unit_row_select_in_any_space():
    """The block shapes the real TPU lowering refused at ISSUE 21 — a
    ``(1, t)`` row-select over ``(S, T)`` in SMEM and in VMEM, a ``(1, 1)``
    scalar block, a ``(1, k)`` data-indexed output — while the old checker
    called size-1 dims "the per-step row-select idiom" and stayed quiet."""
    hits = _run(
        """
        def kern(sr_ref, c_ref, n_ref, w_ref, a_ref):
            a_ref[:] = w_ref[:]

        def refused(srow, scols, slens, w, block, interpret):
            s, t = scols.shape
            k = w.shape[1]
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(s,),
                in_specs=[
                    pl.BlockSpec((1, t), lambda i, sr: (i, 0),
                                 memory_space=pltpu.SMEM),
                    pl.BlockSpec((1, 1), lambda i, sr: (i, 0),
                                 memory_space=pltpu.SMEM),
                    pl.BlockSpec((1, t), lambda i, sr: (i, 0),
                                 memory_space=pltpu.VMEM),
                ],
                out_specs=pl.BlockSpec((1, k), lambda i, sr: (sr[i], 0),
                                       memory_space=pltpu.VMEM),
            )
            return pl.pallas_call(
                kern,
                grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct((block + 1, k), jnp.float32),
                interpret=interpret,
            )(srow, scols, slens, w)
        """,
        "kernel-tile-alignment",
    )
    assert {f.symbol for f in hits} == {
        "refused:in0:sublane", "refused:in1:sublane", "refused:in2:sublane",
        "refused:out0:sublane",
    }
    assert all("refuses" in f.message for f in hits)


def test_tile_alignment_quiet_on_native_tiles_and_leading_unit_dims():
    clean = """
        def kern(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        def ok(x, w, t, interpret):
            return pl.pallas_call(
                kern,
                grid=(8,),
                in_specs=[
                    pl.BlockSpec((8, 128), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM),
                    # the row-select the lowering accepts: the windowed
                    # dim LEADS, the block's last two dims span the array's
                    pl.BlockSpec((1, 1, t), lambda i: (i, 0, 0),
                                 memory_space=pltpu.SMEM),
                    pl.BlockSpec((1, 2, t), lambda i: (i, 0, 0),
                                 memory_space=pltpu.VMEM),
                ],
                out_specs=pl.BlockSpec((8, 256), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((64, 256), jnp.float32),
                interpret=interpret,
            )(x, x.reshape(8, 1, t), w)
        """
    assert _run(clean, "kernel-tile-alignment") == []


# ---------------------------------------------------------------------------
# kernel-index-bounds
# ---------------------------------------------------------------------------


def test_index_bounds_fires_on_provable_overrun():
    hits = _run(
        """
        def kern(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        def oob(interpret):
            x = jnp.zeros((64, 128))
            return pl.pallas_call(
                kern,
                grid=(8,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i + 1, 0),
                                       memory_space=pltpu.VMEM)],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((64, 128), jnp.float32),
                interpret=interpret,
            )(x)
        """,
        "kernel-index-bounds",
    )
    # blocks (i+1)·8 reach row 72 of a 64-row operand
    assert len(hits) == 1
    assert hits[0].symbol == "oob:in0:d0"
    assert "72 > 64" in hits[0].message and "interpret mode" in hits[0].message


def test_index_bounds_fires_symbolically_past_a_proven_cover():
    hits = _run(
        """
        def kern(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        def oob(x, n, interpret):
            grid = (n // 8,)
            return pl.pallas_call(
                kern,
                grid=grid,
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM)],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i + 1, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((n, 128), jnp.float32),
                interpret=interpret,
            )(x)
        """,
        "kernel-index-bounds",
    )
    # (n // 8) blocks of 8 rows cover exactly n; the +1 offset walks past it
    assert len(hits) == 1
    assert hits[0].symbol == "oob:out0:d0"
    assert "past the `n` extent" in hits[0].message


def test_index_bounds_quiet_on_exact_covers():
    clean = """
        def kern(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        def ok(x, n, interpret):
            grid = (n // 8,)
            vals = jnp.zeros((64, 128))
            return pl.pallas_call(
                kern,
                grid=grid,
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM)],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((n, 128), jnp.float32),
                interpret=interpret,
            )(vals)
        """
    assert _run(clean, "kernel-index-bounds") == []


# ---------------------------------------------------------------------------
# kernel-alias-discipline
# ---------------------------------------------------------------------------


def test_alias_discipline_fires_on_uninitialized_revisited_accumulator():
    hits = _run(
        """
        def kern(x_ref, acc_ref):
            acc_ref[:] = acc_ref[:] + x_ref[:]

        def accumulate(x, interpret):
            return pl.pallas_call(
                kern,
                grid=(16,),
                in_specs=[pl.BlockSpec((128, 128), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM)],
                out_specs=pl.BlockSpec((128, 128), lambda i: (0, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
                interpret=interpret,
            )(x)
        """,
        "kernel-alias-discipline",
    )
    assert len(hits) == 1
    assert hits[0].symbol == "accumulate:out0:init"
    assert "accumulator-race" in hits[0].message


def test_alias_discipline_fires_on_alias_shape_and_dtype_mismatch():
    hits = _run(
        """
        def kern(x_ref, d_ref, o_ref):
            o_ref[:] = x_ref[:]

        def aliased(x, interpret):
            donor = jnp.zeros((64, 100), jnp.bfloat16)
            return pl.pallas_call(
                kern,
                grid=(8,),
                in_specs=[
                    pl.BlockSpec((8, 128), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec(memory_space=pltpu.ANY),
                ],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((64, 128), jnp.float32),
                input_output_aliases={1: 0},
                interpret=interpret,
            )(x, donor)
        """,
        "kernel-alias-discipline",
    )
    assert {f.symbol for f in hits} == {"aliased:alias1:shape",
                                       "aliased:alias1:dtype"}
    assert any("silent memory corruption" in f.message for f in hits)


def test_alias_discipline_quiet_on_donated_and_when_initialized():
    clean = """
        def kern(x_ref, d_ref, acc_ref, zero_ref):
            @pl.when(pl.program_id(0) == 0)
            def _():
                acc_ref[:] = jnp.zeros_like(acc_ref)
                zero_ref[:] = jnp.zeros_like(zero_ref)
            acc_ref[:] += x_ref[:]
            zero_ref[:] += x_ref[:]

        def accumulate(x, interpret):
            donor = jnp.zeros((128, 128), jnp.float32)
            return pl.pallas_call(
                kern,
                grid=(16,),
                in_specs=[
                    pl.BlockSpec((128, 128), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec(memory_space=pltpu.ANY),
                ],
                out_specs=[
                    pl.BlockSpec((128, 128), lambda i: (0, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((128, 128), lambda i: (0, 0),
                                 memory_space=pltpu.VMEM),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((128, 128), jnp.float32),
                    jax.ShapeDtypeStruct((128, 128), jnp.float32),
                ],
                input_output_aliases={1: 0},
                interpret=interpret,
            )(x, donor)
        """
    assert _run(clean, "kernel-alias-discipline") == []


# ---------------------------------------------------------------------------
# kernel-interpret-default
# ---------------------------------------------------------------------------


def test_interpret_default_fires_on_literal_and_true_default():
    hits = _run(
        """
        def kern(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        def hardcoded(x):
            return pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                interpret=True,
            )(x)

        def inner(x, *, interpret):
            return pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                interpret=interpret,
            )(x)

        def wrapper(x, *, interpret=True):
            return inner(x, interpret=bool(interpret))
        """,
        "kernel-interpret-default",
    )
    assert {f.symbol for f in hits} == {"hardcoded:interpret:literal",
                                       "wrapper:interpret:default"}
    assert all("TPU" in f.message for f in hits)


def test_interpret_default_quiet_on_backend_resolution_and_threading():
    """The sanctioned shapes: a required flag threaded from the caller, and
    the None default resolved from jax.default_backend() — exactly what
    ops/pallas_kernels.py does after the PR 6 fix."""
    clean = """
        def kern(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        def inner(x, *, interpret):
            return pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                interpret=interpret,
            )(x)

        def wrapper(x, *, interpret=None):
            if interpret is None:
                interpret = jax.default_backend() != "tpu"
            return inner(x, interpret=bool(interpret))
        """
    assert _run(clean, "kernel-interpret-default") == []


def test_interpret_default_fires_under_any_param_name():
    """Review finding: the checker used to look up a literal ``interpret``
    param and miss a True-defaulted flag under any other name — the exact
    silent-emulate class, renamed."""
    hits = _run(
        """
        def kern(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        def solve(x, emulate=True):
            return pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                interpret=emulate,
            )(x)
        """,
        "kernel-interpret-default",
    )
    assert len(hits) == 1
    assert hits[0].symbol == "solve:interpret:default"
    assert "`emulate`" in hits[0].message


def test_vmem_budget_counts_default_index_maps_as_pipelined():
    """Review finding: a blocked spec with NO index_map under a non-empty
    grid defaults to the identity grid map — grid-varying, double-buffered.
    Modeling it resident undercounted the footprint 2× and hid overflows."""
    hits = _run(
        """
        def kern(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        def big(x, interpret):
            return pl.pallas_call(
                kern,
                grid=(8,),
                in_specs=[pl.BlockSpec((2048, 1024),
                                       memory_space=pltpu.VMEM)],
                out_specs=pl.BlockSpec((2048, 1024),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((16384, 1024), jnp.float32),
                interpret=interpret,
            )(x)
        """,
        "kernel-vmem-budget",
    )
    # 2 × 2048·1024·4 B × 2 (double-buffered) = 32 MiB > 16 MiB
    assert len(hits) == 1 and hits[0].symbol == "big:vmem"


def test_alias_discipline_quiet_on_unprovable_strided_maps():
    """Review finding: a strided map (``2 * i``) visits distinct blocks but
    classified as an opaque expr; claiming "revisited" forced a bogus
    suppression — unprovable maps must stay silent."""
    clean = """
        def kern(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        def strided(x, interpret):
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM)],
                out_specs=pl.BlockSpec((8, 128), lambda i: (2 * i, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((64, 128), jnp.float32),
                interpret=interpret,
            )(x)
        """
    assert _run(clean, "kernel-alias-discipline") == []


# ---------------------------------------------------------------------------
# the real kernels: parsed models + the --cost table
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def kernels_project():
    project, errors = build_project(
        [os.path.join(REPO_ROOT, "oryx_tpu", "ops", "pallas_kernels.py")],
        root=REPO_ROOT,
    )
    assert errors == []
    return project


def test_real_kernels_parse_with_expected_structure(kernels_project):
    """The three production kernels must stay statically parsable — a
    refactor that defeats the parser silently disables the whole family."""
    from oryx_tpu.tools.analyze.kernelmodel import kernel_models

    models = {m.name: m for m in kernel_models(kernels_project)}
    assert {"_spd_solve_call", "_spd_blocked_call",
            "gather_gramian_accumulate", "_call"} <= set(models)
    spd = models["_spd_solve_call"]
    assert [b.space for b in spd.inputs] == ["vmem", "vmem"]
    assert len(spd.scratch) == 1 and spd.scratch[0].space == "vmem"
    assert spd.interpret == ("param", "interpret")
    # the blocked SPD solve past 128 features: the same blocks, and one
    # scratch of two lane tiles of rows by the lane-padded k + 1
    blocked = models["_spd_blocked_call"]
    assert [(b.space, b.shape) for b in blocked.inputs] == [
        ("vmem", ("tile_b", "k", "k")), ("vmem", ("tile_b", "k")),
    ]
    assert [(b.space, b.shape) for b in blocked.outputs] == [
        ("vmem", ("tile_b", "k")),
    ]
    assert [(b.space, b.shape) for b in blocked.scratch] == [
        ("vmem", ("tile_b", 256, "kw")),
    ]
    assert blocked.grid == ("b_pad // tile_b",)
    assert blocked.interpret == ("param", "interpret")
    assert all(b.pipelined for b in (*blocked.inputs, *blocked.outputs))

    gg = models["gather_gramian_accumulate"]
    # owner rows and slot lengths, each a whole (S,) vector in SMEM
    assert gg.num_prefetch == 2
    assert gg.prefetch_shapes == [("s",), ("s",)]
    # the gather indices twice: this slot's block and the next slot's
    assert [b.space for b in gg.inputs] == [
        "smem", "smem", "vmem", "any", "any", "any",
    ]
    assert gg.aliases == {6: 0, 7: 1}
    # two gather buffers (this slot's rows, the next slot's in flight) and
    # one semaphore a buffer
    assert [(b.space, b.shape) for b in gg.scratch] == [
        ("vmem", (2, "t", 1, "kp")), ("sem", (2,)),
    ]
    # the scalar-prefetch-driven output maps are data-dependent: revisited
    assert all(b.revisits_across_grid(gg.grid) for b in gg.outputs)
    # and the kernel zero-initializes both refs on first visit
    from oryx_tpu.tools.analyze.kernelmodel import (
        kernel_param_name,
        kernel_zeroes_param,
    )

    assert kernel_param_name(gg, "out", 0) == "a_ref"
    assert kernel_zeroes_param(gg, "a_ref")
    assert kernel_zeroes_param(gg, "b_ref")

    km = models["_call"]
    assert all(b.revisits_across_grid(km.grid) for b in km.outputs)
    assert all(
        kernel_zeroes_param(km, kernel_param_name(km, "out", j))
        for j in range(3)
    )


def test_gg_vmem_model_matches_hand_computed_budget(kernels_project):
    """The acceptance numbers: the gather-Gramian resident footprint at
    (k=256, T=512) — double-buffered (1,k,k)/(1,1,k) accumulators, the
    (1,2,T) weight block, the two (T,1,pad128(k)) gather buffers, each
    under the tiling Mosaic infers for it ((1,128) and (2,128) for the 1-
    and 2-row tails, not 8 rows) — is exactly 1,583,104 B, which IS the
    resident budget (re-ratified with the second buffer: PERF.md, PR 27);
    the next k tile (264) overflows it, and 250 features — a row of the
    same 256 lanes — read the same bytes."""
    from oryx_tpu.tools.analyze.kernelmodel import (
        budgets, kernel_models, pad_up,
    )

    gg = next(m for m in kernel_models(kernels_project)
              if m.name == "gather_gramian_accumulate")
    at = lambda k: gg.vmem_bytes({"k": k, "t": 512, "kp": pad_up(k, 128)})
    expected_256 = (
        2 * 256 * 256 * 4       # (1,256,256) f32 out block, double-buffered
        + 2 * 1 * 256 * 4       # (1,1,256) out block: one (1,128)-tiled row
        + 2 * 2 * 512 * 4       # (1,2,512) f32 weight block, (2,128)-tiled
        + 2 * 512 * 1 * 256 * 4  # (2,512,1,256) gather buffers, row per tile
    )
    assert at(256) == at(250) == expected_256 == 1_583_104
    budget = budgets()["resident_budget_bytes"]
    assert at(256) == budget < at(264) == 2_395_136


def test_gg_smem_model_matches_what_the_compiler_said(kernels_project):
    """The SMEM side of the model: two prefetched words a slot plus the two
    double-buffered (1, 1, T) index blocks (this slot's and the next
    slot's). Compiled for a described v5e at T = 512 the call fit at
    129,024 slots and ran "out of memory in memory space smem ... by 1.1K"
    at 130,048 and "by 9.1K" at 131,072 (PR 27): the model says 8 KiB
    under, exactly full and 8 KiB over the same 1 MiB, the compiler's own
    scalars being the 1.1 KB. A kernel with no prefetched operand reads 0; one whose prefetched
    shapes the call site does not show reads None, never a guess."""
    from oryx_tpu.tools.analyze.kernelmodel import (
        SMEM_LIMIT_BYTES, kernel_models,
    )

    models = {m.name: m for m in kernel_models(kernels_project)}
    gg = models["gather_gramian_accumulate"]
    at = lambda s: gg.smem_bytes({"s": s, "t": 512})
    assert at(129_024) == SMEM_LIMIT_BYTES - 8192
    assert at(130_048) == SMEM_LIMIT_BYTES
    assert at(131_072) == SMEM_LIMIT_BYTES + 8192
    assert gg.prefetch_smem_bytes({"s": 1000}) == 8000
    assert gg.smem_bytes({"t": 512}) is None  # unbound slots: no number
    assert models["_spd_solve_call"].smem_bytes({}) == 0
    gg.prefetch_shapes = [None, ("s",)]
    try:
        assert gg.smem_bytes({"s": 8, "t": 8}) is None
    finally:
        gg.prefetch_shapes = [("s",), ("s",)]


def test_cli_cost_renders_kernel_rows(capsys):
    from oryx_tpu.tools.analyze.cli import main

    rc = main(["--cost", "--format", "json",
               "--bind", "k=50,t=64,tile_b=128,s=4096,b_pad=4096"])
    assert rc == 0
    data = json.loads(capsys.readouterr().out)
    rows = {r["kernel"]: r for r in data["kernels"]}
    spd = rows["oryx_tpu.ops.pallas_kernels._spd_solve_call"]
    # largest buffer = the augmented (tile_b, k, k+1) scratch: its padded
    # bytes at tile_b=128, k=50 are 128·56·128·4 = 3,670,016
    assert spd["vmem_bytes"]["value"] is not None
    assert "tile_b" in spd["vmem_bytes"]["expr"]
    gg = rows["oryx_tpu.ops.pallas_kernels.gather_gramian_accumulate"]
    assert gg["grid"] == "s"
    assert gg["vmem_bytes"]["expr"].startswith("8·k^2")
    assert gg["hbm_bytes_per_step"]["value"] is not None
    # text mode renders the kernel table too
    assert main(["--cost"]) == 0
    out = capsys.readouterr().out
    assert "pallas kernel" in out and "gather_gramian_a" in out


def test_whole_package_clean_for_pallas_family():
    """Acceptance: zero unsuppressed findings across the five kernel checks
    at HEAD — any true positive in ops/pallas_kernels.py gets fixed, not
    baselined."""
    from oryx_tpu.tools.analyze.core import analyze_project

    result = analyze_project(
        [os.path.join(REPO_ROOT, "oryx_tpu")],
        root=REPO_ROOT,
        baseline_path=os.path.join(REPO_ROOT, "conf",
                                   "analyze-baseline.json"),
    )
    open_findings = [f for f in result.unsuppressed
                     if f.checker in _PALLAS_IDS]
    assert open_findings == [], "\n" + "\n".join(
        f.render() for f in open_findings
    )


def test_pallas_checkers_are_versioned():
    from oryx_tpu.tools.analyze.checkers import ALL_CHECKERS, CHECKER_VERSIONS

    ids = {c.id for c in ALL_CHECKERS}
    assert _PALLAS_IDS <= ids
    for cid in _PALLAS_IDS:
        assert CHECKER_VERSIONS.get(cid, 0) >= 1


# ---------------------------------------------------------------------------
# trace_summary --batch kernel rows
# ---------------------------------------------------------------------------


def test_trace_summary_batch_renders_kernel_vmem_rows():
    from oryx_tpu.tools.trace_summary import render_batch_record

    rec = {
        "metric": "als_batch_train_throughput_4M_50f",
        "backend": "cpu", "device_kind": "cpu", "unit": "ratings/s",
        "value": 123456.0,
        "kernels": [
            {"kernel": "_spd_solve_call", "grid": "b_pad // tile_b",
             "vmem_bytes": 11534336.0, "vmem_expr": "8·k^2·tile_b",
             "hbm_bytes_per_step": 2662400.0},
            {"kernel": "gather_gramian_accumulate", "grid": "s",
             "vmem_bytes": 114688.0, "vmem_expr": "8·k^2 + 4·k·t",
             "hbm_bytes_per_step": None},
        ],
    }
    buf = io.StringIO()
    assert render_batch_record(rec, out=buf) == 0
    text = buf.getvalue()
    assert "pallas kernel VMEM (static model" in text
    assert "_spd_solve_call" in text and "11,264 KiB" in text
    assert "gather_gramian_accumulate" in text and "112 KiB" in text
    # a record without kernel rows renders without the section
    buf2 = io.StringIO()
    rec2 = dict(rec)
    rec2.pop("kernels")
    assert render_batch_record(rec2, out=buf2) == 0
    assert "pallas kernel VMEM" not in buf2.getvalue()
