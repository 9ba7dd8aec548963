"""Pallas TPU kernels for the framework's hot ops.

``spd_solve_batched`` solves many small SPD systems (the per-row normal
equations of ALS — reference hot spot ALSUpdate.java:141-152) by Gauss-Jordan
elimination with the whole batch tile VMEM-resident. XLA's batched
``cholesky`` + ``cho_solve`` on TPU lower to ~3·k sequential steps that each
stream the full (B, k, k) operand through HBM — measured 5.8 s for the
1M-user half-iteration at k=50, ~47× the Gramian accumulation it follows.
Here the k elimination steps run against VMEM, so HBM sees one read of the
Gramians and one write of the solutions:

  grid = batch tiles; per step:  load A (T, k, k), b (T, k) into VMEM
                                 k × {pivot-normalize, rank-1 eliminate} (VPU)
                                 store x (T, k)

No pivoting: operands are regularized SPD (diagonal shift λ·n ≥ λ), for
which diagonal pivots are bounded away from zero.

A step's cost is set by its multipliers — column j, a masked reduction
across the lanes of every vreg of the block — not by the elements it
updates: ≈ 3.5 cycles a vreg on a v5e (PERF.md §6). Past one lane
tile of features (k > 128) each of the k steps would reduce and sweep the
whole (T, 256, 256) block, so the solve is BLOCKED there (129–256
features, ``_spd_blocked_kernel``): the augmented [A | b] sits in two lane
tiles of rows, zero past k, and the columns are eliminated a panel of
``_SPD_PANEL`` (32) at a time:

  per panel:  its columns, one at a time, on the panel's 32 rows alone,
              over the lanes from the panel's tile on          (VPU)
              → [0 | I | rest] in those rows
              the panel out of every row below it: rows −=
              (their panel columns) · (the panel's rows)        (MXU)
  then:       back substitution a panel at a time from the last,
              x_p = column k − (the panel's rows past it) · x    (MXU)

A step reduces 4 vregs a system where the unblocked step reduced 32, and
the MXU works at full float32 precision (``Precision.HIGHEST``). A
narrower panel halves the reductions again but pays a whole (·, 128)
contraction an update for fewer columns: 32 is where the two meet.
Padding to 256 happens in VMEM, never in HBM; widths to 128 keep the
unblocked kernel unchanged.

``gather_gramian_accumulate`` fuses the ALS trainer's entire Gramian
accumulation — the opposite-factor gather, the per-slot (k, k) Gramian/RHS
contraction, and the slot→row merge — into one pass over the slotted COO
(train._solve_block). The XLA formulation materializes the (Sc, T, k)
``y[cs]`` gather in HBM, streams it back for the einsum, writes the
(Sc, k, k) per-slot Gramians, and streams THOSE back through segment_sum —
three HBM round-trips per scan chunk while the MXU idles (measured MFU
0.15%: the loop is gather-bandwidth-bound, and bf16 inputs buy only 17%).
Here each factor row crosses HBM exactly once:

  grid = slots; step i:  START the row copies of slot i+1 → the OTHER of
                         two VMEM gather buffers, on that buffer's own
                         semaphore: one copy an ENTRY, to the slot's own
                         length (a scalar-prefetched ``slens``), none for
                         the padding of its last cells, all of them started
                         before any wait (rows are column-sorted within the
                         slot, so the gather walks HBM in address order);
                         step 0 starts slot 0's as well, the last step
                         starts none
                         WAIT for slot i's copies (started at step i−1)
                         Gramian (k,T)·(T,k) + RHS (1,T)·(T,k)   (MXU)
                         accumulate into the slot's OWNER ROW's
                         (1, k, k)/(1, 1, k) output block in VMEM

A software pipeline over the slots: slot i+1's copies are in the DMA unit
while slot i's matmuls run, where the two took turns. Slot i uses buffer and
semaphore i % 2. Two semaphores, because a wait names no copy — a semaphore
counts bytes — so on a shared one the rows of slot i+1 landing first would
satisfy slot i's waits and its matmul would read rows that have not arrived.
Slot i+1's gather indices reach step i as a second view of ``scols`` (index
map ``min(i + 1, S − 1)``). Buffer rows past a slot's length keep an earlier
slot's rows (zeros before the first) and meet weight 0 in the matmuls; an
empty slot neither starts, waits nor multiplies.

What paces the gather, measured on a v5e at the Netflix-shaped cell
(PERF.md): with 4 copies in flight one HBM round trip, 84 ns a copy; with a
whole slot in flight 2.2 ns a copy against the 9 MB item table and 15.8 ns
against the 246 MB user table — and no copy at all for the 5–44% of a
side's slot cells that are padding (PR 25). The pipeline hides almost none
of that (PR 27): a half costs its copies PLUS its matmuls whichever step
the copies are started in, to ±2% (item half 2.30 → 2.18 s, user half 2.34
→ 2.38 s). Copies started a step early make no progress while the core
computes — at most one round trip a slot (0.4 µs) is saved — so a start
seems to hold the one instruction stream until the DMA unit takes the
descriptor. Fewer copies, not better-placed ones, is what is left.

Mosaic slices a DMA only along untiled (leading) dims, and a 16-bit row
shares its 32-bit sublane word with its neighbour — so the wrapper hands the
kernel the factors as float32 ``(R, 1, pad128(k))``: one row = one (1, 128)-
tiled slab, addressable by a leading-dim index. A bfloat16 compute dtype
still feeds the MXU bf16 (the kernel casts after the gather); what it no
longer buys is a halved gather: a copy's cost is its descriptor's, not its
200 B–1 KB. Every blocked operand is 3-D so that the block's last two dims
EQUAL the array's — the TPU lowering refuses a ``(1, t)`` block over an
``(S, T)`` array.

Slots arrive row-sorted (the pack guarantees it), so the per-row output
block — selected by a scalar-prefetched ``srow`` index map — is revisited
across every slot of a row and flushed to HBM once per row, replacing the
whole segment-sum pass. Rows with no slots keep the donated zero input
(``input_output_aliases``), which also makes never-visited blocks
deterministic under interpret mode.

``kmeans_assign_accumulate`` fuses one full Lloyd-sweep accumulation —
squared-distance evaluation, nearest-center argmin, and weighted
sum/count/cost accumulation — into a single pass over point tiles. The
unfused XLA formulation (models/kmeans/train.py lloyd step) materializes the
(N, k) distance matrix and a second (N, k) one-hot indicator in HBM between
ops; here both live only tile-at-a-time in VMEM:

  grid = point tiles; per step:  d² tile = |p|² − 2 p·Cᵀ + |c|²   (MXU)
                                 indicator = (d² == row-min)       (VPU)
                                 sums   += indicatorᵀ · p          (MXU)
                                 counts += Σ indicator, cost += Σ min d²

Outputs revisit the same block every grid step (constant index map), the
standard Pallas accumulation pattern: initialized at step 0 with ``pl.when``,
accumulated thereafter.

Every wrapper here takes ``interpret`` as a REQUIRED argument: the caller
knows where its operands live (``on_tpu``) and passes ``not on_tpu``, so no
kernel can be emulated on the chip — or compiled for a CPU — because a
process-wide default disagreed with the operand's device. The CPU suite
runs the same kernels under ``interpret=True``.

Tile sizes honor the f32 (8, 128) VMEM tiling: points tiles are
(TILE_N, D_pad) with D and K padded to lane multiples by the wrapper.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

log = logging.getLogger(__name__)

TILE_N = 512
_LANE = 128
# coordinate pushing padded centers beyond any real distance (squares to
# ~f32-max without overflowing the distance expansion)
FAR_AWAY = 3.4e38 ** 0.5


def _pad_dim(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def on_tpu(operand=None, mesh=None) -> bool:
    """Whether a computation over ``operand`` (or over ``mesh``) runs on a
    TPU: the ONE place kernel selection and ``interpret`` are decided, from
    the devices that hold the data. A tracer has no devices; callers resolve
    before they enter jit and pass the answer down as a static."""
    if mesh is not None:
        return mesh.devices.flat[0].platform == "tpu"
    return next(iter(operand.devices())).platform == "tpu"


def _spd_solve_kernel(a_ref, b_ref, x_ref, aug_ref):
    k = a_ref.shape[-1]
    aug_ref[:, :, :k] = a_ref[:]
    aug_ref[:, :, k:] = b_ref[:][..., None]
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (1, k, 1), 1)
    lane_ids = jax.lax.broadcasted_iota(jnp.int32, (1, 1, k + 1), 2)

    def step(j, carry):
        # The pivot row comes out as a cheap sublane-dynamic ref load
        # (dynamic_slice on VALUES has no Mosaic lowering; ref indexing
        # does); pivot and fac are single masked lane reductions. The whole
        # elimination step is then ONE fused pass over aug: subtracting
        # (fac − e_j)⊗piv_row eliminates column j in every row AND lands row
        # j exactly on the normalized pivot row — no separate row-write.
        aug = aug_ref[:]
        row_j = aug_ref[:, pl.ds(j, 1), :]  # (T, 1, k+1)
        is_lane_j = lane_ids == j
        pivot = jnp.sum(jnp.where(is_lane_j, row_j, 0.0), axis=2,
                        keepdims=True)  # (T, 1, 1)
        piv_row = row_j / pivot
        fac = jnp.sum(jnp.where(is_lane_j, aug, 0.0), axis=2,
                      keepdims=True)  # (T, k, 1)
        fac = fac - (row_ids == j).astype(jnp.float32)
        aug_ref[:] = aug - fac * piv_row
        return carry

    jax.lax.fori_loop(0, k, step, 0)
    x_ref[:] = aug_ref[:, :, k]


def _row_chunks(lo: int, hi: int):
    """[lo, hi) cut at the lane-tile boundaries: an MXU operand a chunk."""
    while lo < hi:
        top = min(hi, (lo // _LANE + 1) * _LANE)
        yield lo, top
        lo = top


def _spd_blocked_kernel(a_ref, b_ref, x_ref, m_ref):
    k = a_ref.shape[-1]
    nb, t = _SPD_PANEL, _LANE
    rows = m_ref.shape[1]
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    def each_system(update):
        # the MXU's work a system at a time: a product batched over the
        # tile compiles to one copy a system (16 s a shape for a v5e, not
        # 2: 17 s more set-up for the 250-feature trainer, PERF.md §6)
        def body(s, carry):
            update(s)
            return carry

        jax.lax.fori_loop(0, m_ref.shape[0], body, 0)

    # [A | b] in two lane tiles of rows: rows past k are zeros and never
    # pivot, column k holds b and, at the end, x
    m_ref[...] = jnp.zeros_like(m_ref)
    m_ref[:, :k, :k] = a_ref[:]
    m_ref[:, :k, k:k + 1] = b_ref[:][..., None]
    lane_ids = jax.lax.broadcasted_iota(jnp.int32, (1, 1, t), 2)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (1, nb, 1), 1)
    panels = range(0, k, nb)

    for c0 in panels:
        t0 = c0 // t * t  # the lane tile the panel's columns lie in
        r1 = c0 + nb

        def step(j, carry, c0=c0, t0=t0):
            # _spd_solve_kernel's step on the panel's rows alone, over the
            # lanes from its tile on: the multipliers reduce one vreg a
            # system and 8 rows, not the whole block
            blk = m_ref[:, c0:c0 + nb, t0:]
            # the pivot row over every lane: Mosaic loads a dynamic row
            # only from lane 0
            row_j = m_ref[:, pl.ds(c0 + j, 1), :][..., t0:]
            is_lane_j = lane_ids == c0 - t0 + j
            pivot = jnp.sum(jnp.where(is_lane_j, row_j[..., :t], 0.0),
                            axis=2, keepdims=True)
            fac = jnp.sum(jnp.where(is_lane_j, blk[..., :t], 0.0), axis=2,
                          keepdims=True)
            fac = fac - (row_ids == j).astype(jnp.float32)
            m_ref[:, c0:c0 + nb, t0:] = blk - fac * (row_j / pivot)
            return carry

        jax.lax.fori_loop(0, min(nb, k - c0), step, 0)

        # the panel's columns out of every row below it, on the MXU: the
        # panel's multipliers (its lanes of those rows) times its rows,
        # now [0 | I | the rest]. The product runs over the whole lane
        # tile; the lanes off the panel are masked to zero
        def below(s, c0=c0, t0=t0, r1=r1):
            in_panel = ((lane_ids[0] >= c0 - t0) & (lane_ids[0] < r1 - t0))
            for lo, hi in _row_chunks(r1, rows):
                lhs = jnp.where(in_panel, m_ref[s, lo:hi, t0:t0 + t], 0.0)
                m_ref[s, lo:hi, t0:] = m_ref[s, lo:hi, t0:] - dot(
                    lhs, m_ref[s, t0:t0 + t, t0:])

        each_system(below)

    # back substitution, a panel at a time from the last: the panel's
    # column k less its rows times the solution below it, on the MXU. The
    # product spans column k's lane tile; only column k is written back
    # (rows past k are zeros)
    c = k // t * t
    is_col_k = lane_ids[0] == k - c

    def back(s):
        for c0 in reversed(panels[:-1]):
            r1 = c0 + nb
            acc = 0.0
            for lt in range(r1 // t * t, rows, t):
                lhs = m_ref[s, c0:r1, lt:lt + t]
                if lt < r1:
                    lhs = jnp.where(lane_ids[0] >= r1 - lt, lhs, 0.0)
                acc = acc + dot(lhs, m_ref[s, lt:lt + t, c:c + t])
            cur = m_ref[s, c0:r1, c:c + t]
            m_ref[s, c0:r1, c:c + t] = jnp.where(is_col_k, cur - acc, cur)

    each_system(back)
    x_ref[:] = m_ref[:, :k, k]


def _spd_blocked_call(a, b, *, tile_b: int, interpret: bool):
    b_pad, k = b.shape
    kw = _pad_dim(k + 1, _LANE)
    return pl.pallas_call(
        _spd_blocked_kernel,
        grid=(b_pad // tile_b,),
        in_specs=[
            pl.BlockSpec((tile_b, k, k), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_b, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile_b, k), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b_pad, k), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((tile_b, _SPD_BLOCKED_ROWS, kw), jnp.float32)],
        interpret=interpret,
    )(a, b)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def _spd_solve_call(a, b, *, tile_b: int, interpret: bool):
    b_pad, k = b.shape
    if k > _LANE:
        # traced inside this jit, so the device trace names the blocked
        # kernel after it too
        return _spd_blocked_call(a, b, tile_b=tile_b, interpret=interpret)
    grid = (b_pad // tile_b,)
    return pl.pallas_call(
        _spd_solve_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, k, k), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_b, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile_b, k), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b_pad, k), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tile_b, k, k + 1), jnp.float32)],
        interpret=interpret,
    )(a, b)


# Batch-tile sizing for the SPD solve: the largest single VMEM buffer is
# the augmented scratch (tile_b, k, k+1) — its k+1 lanes pad to the NEXT
# 128 multiple (at k=128 that is 256, not 128). What the TPU compiler
# actually allocates for the kernel is ~4.75× that buffer (measured by
# compiling for a v5e: 16.62 MiB at k=50, tile_b=128 — the double-buffered
# A block, the scratch, and ~1.75 scratch-sized temporaries for the step's
# full-block values), against a 16 MiB scoped-VMEM limit that the old
# 3.5 MiB budget overran at every production batch size. 3 MiB keeps the
# worst case (A as wide as the scratch) at 14.25 MiB. The budget and cap
# below are pinned against the static kernel model's padded-byte math
# (tools/analyze/kernelmodel.py + oryx.analyze.kernel.scoped-budget-bytes)
# by tests/test_kernel_differential.py: drift in either direction fails
# tier-1.
_SPD_SCOPED_BUDGET_BYTES = 3 << 20
_SPD_MAX_TILE = 256
# Past one lane tile of features (128) the solve is blocked: its rows are
# two lane tiles, and it eliminates a panel of _SPD_PANEL columns at a time
# on the vector unit, the MXU taking each panel out of the rows below it.
# A step is paced by its multipliers' lane reductions, one vreg a system
# and 8 rows of the panel; a narrower panel means more MXU updates, each a
# whole (tile, 128) contraction however few columns it carries. 32 was the
# fastest on a v5e at 250 features with the MXU products batched over the
# tile (PERF.md §6: 128 / 64 / 32 / 16 / 8 columns 94.7 / 71.9 / 66.7 /
# 77.5 / 122 µs a tile of 8 systems); looped a system at a time, 32 and 64
# columns run 8.63 and 8.48 µs a system.
_SPD_PANEL = 32
_SPD_BLOCKED_ROWS = 256
# The blocked kernel's values are one panel's rows and one system's MXU
# operands, so the compiler allocates little beyond its buffers: the
# largest, the (tile_b, 256, pad128(k+1)) scratch, is held to 4 MiB — 16
# systems a tile to 255 features, 8 at 256 — where the unblocked kernel's
# whole-block values hold its scratch to 3. Compiled for a v5e the 16-row
# tile at 250 features takes 14.02 MiB of the 16 MiB scoped limit; on a v5e
# 16 rows run 8.63 µs a system where 8 rows run 9.96 (PERF.md §6). Pinned
# against the static kernel model, with the compiler's allocation past its
# buffers as measured (tests/test_kernel_differential.py).
_SPD_BLOCKED_BUDGET_BYTES = 4 << 20


def spd_tile_b(k: int) -> int:
    """The batch-tile height the unblocked SPD kernel runs at for ``k``
    features, 1 to 128 (past one lane tile the blocked kernel solves): the
    largest multiple of 8 (≤ ``_SPD_MAX_TILE``) whose augmented scratch
    tile_b × pad8(k) × pad128(k+1) × 4 B fits the scoped-VMEM budget."""
    if not 0 < k <= _LANE:
        raise ValueError(f"the unblocked SPD kernel solves 1-{_LANE} "
                         f"features, not {k}")
    k_padded = _pad_dim(k, 8) * _pad_dim(k + 1, _LANE)
    return min(_SPD_MAX_TILE,
               (_SPD_SCOPED_BUDGET_BYTES // (4 * max(1, k_padded))) & ~7)


def spd_blocked_tile_b(k: int) -> int:
    """The batch-tile height the blocked SPD kernel runs at for ``k``
    features: the largest multiple of 8 (≤ ``_SPD_MAX_TILE``) whose scratch
    tile_b × 256 × pad128(k+1) × 4 B fits ``_SPD_BLOCKED_BUDGET_BYTES``."""
    scratch = _SPD_BLOCKED_ROWS * _pad_dim(k + 1, _LANE)
    return min(_SPD_MAX_TILE,
               (_SPD_BLOCKED_BUDGET_BYTES // (4 * scratch)) & ~7)


def spd_solve_path(k: int) -> "tuple[str, int]":
    """How ``spd_solve_batched`` solves systems of ``k`` features, and at
    what batch tile: ``spd_kernel`` (the unblocked kernel) to one lane tile
    (128), ``spd_blocked`` to two (256), ``cholesky`` (XLA's, tile 0) past
    that. The trainer counts its halves by the same answer."""
    if k <= _LANE:
        return "spd_kernel", spd_tile_b(k)
    if k <= _SPD_BLOCKED_ROWS and spd_blocked_tile_b(k) >= 8:
        return "spd_blocked", spd_blocked_tile_b(k)
    return "cholesky", 0


def spd_solve_batched(a, b, *, interpret: bool):
    """Solve ``a[i] @ x[i] = b[i]`` for a batch of SPD k×k systems.

    Args: a (B, k, k) f32 regularized-SPD, b (B, k) f32.
    Returns x (B, k) f32. Padding batch rows (if any) are solved against
    identity so no NaN escapes the pad region.
    """
    a = jnp.asarray(a, dtype=jnp.float32)
    b = jnp.asarray(b, dtype=jnp.float32)
    n, k = b.shape
    path, tile_b = spd_solve_path(k)
    if path == "cholesky":
        # k past two panels (> 256 features): fall back to XLA's cholesky
        # rather than fail to compile — and say so, because the performance
        # difference is large
        log.warning(
            "spd_solve_batched: k=%d exceeds the VMEM tile budget; using "
            "the XLA cholesky fallback", k,
        )
        chol = jax.scipy.linalg.cholesky(a, lower=True)
        return jax.scipy.linalg.cho_solve((chol, True), b[..., None])[..., 0]
    n_pad = _pad_dim(max(n, 1), tile_b)
    if n_pad != n:
        eye = jnp.broadcast_to(jnp.eye(k, dtype=jnp.float32),
                               (n_pad - n, k, k))
        a = jnp.concatenate([a, eye], axis=0)
        b = jnp.concatenate([b, jnp.zeros((n_pad - n, k), jnp.float32)],
                            axis=0)
    x = _spd_solve_call(a, b, tile_b=tile_b, interpret=bool(interpret))
    return x[:n]


# Starts (or waits) per trip of the kernel's two copy loops. A slot's length
# is only known at run time, so the loops are unrolled by hand: at 1 the
# scalar core's loop overhead paces the copies (3.25 / 3.32 s a user / item
# half of the Netflix cell), at 4 2.49 / 2.56, at 8 2.36 / 2.31, at 16
# 2.31 / 2.29 (PERF.md, PR 25: the sweep on the chip). 8 keeps a slot of
# the narrowest pack (T = 8) one trip.
_GG_UNROLL = 8
# The pack's slot width T is a power of two in [8, 512] (train.py
# _auto_slot_width) — the kernel's resident budget is evaluated at the cap.
_GG_SLOT_WIDTH_MAX = 512
# Features past this would push the kernel's resident VMEM state — the
# double-buffered (1, k, k)/(1, 1, k) accumulator blocks, the two
# (T, 1, pad128(k)) gather buffers, and the (1, 2, T) weight block — past
# the resident-state budget
# (oryx.analyze.kernel.resident-budget-bytes: 1,583,104 B, which IS that
# footprint at k = 256, T = 512); callers fall back to the einsum
# formulation (same numerics, more HBM traffic). The value is the max k
# whose padded footprint at T = _GG_SLOT_WIDTH_MAX fits that budget, pinned
# against the static kernel model by tests/test_kernel_differential.py so
# the constant can never silently drift from the kernel it guards. The
# budget is a discipline, not the hardware's limit: the v5e compiler takes
# the call to k = 768 at T = 512 (tests/test_chip_smoke.py compiles the
# gate itself), and a block at k = 250, T = 512 ran on the chip (PERF.md,
# PR 27).
_GG_MAX_FEATURES = 256
# The per-slot owner rows AND valid lengths ride whole in SMEM (two
# scalar-prefetched vectors), which the compiler caps at 1 MiB per program:
# 2 × 4 B × 98,304 slots is three quarters of it, the rest is left to the
# double-buffered index blocks and the compiler's own scalars. The static
# kernel model derives the same number from the parsed call
# (kernelmodel.SMEM_PREFETCH_BUDGET_BYTES; tests/test_kernel_differential.py
# pins the two together). The pack stays far below this unless a block's
# rows average more than ~6,000 interactions each (the Netflix item side:
# 5,654 a row in blocks of 5,924 rows, 72,594 slots).
_GG_MAX_SLOTS = 3 << 15


def gather_gramian_supported(features: int, slots: int) -> bool:
    """Whether the fused gather-Gramian kernel fits its VMEM budget at
    ``features`` and its SMEM budget at ``slots`` slots per block."""
    return features <= _GG_MAX_FEATURES and slots <= _GG_MAX_SLOTS


def _unrolled(n, body):
    """``body(tt)`` for tt in [0, n), ``n`` a run-time scalar: trips of
    ``_GG_UNROLL`` calls with no test between them, then the remainder one
    by one."""
    trips = n // _GG_UNROLL

    def trip(c, carry):
        for u in range(_GG_UNROLL):
            body(c * _GG_UNROLL + u)
        return carry

    jax.lax.fori_loop(0, trips, trip, 0)

    def one(tt, carry):
        body(tt)
        return carry

    jax.lax.fori_loop(trips * _GG_UNROLL, n, one, 0)


def _make_gather_gramian_kernel(t: int, k: int, kp: int, cd):
    def kernel(srow_ref, slen_ref, scols_ref, scols_next_ref, wc_ref, y_ref,
               a0_ref, b0_ref, a_ref, b_ref, yg, sem):
        i = pl.program_id(0)
        last = pl.num_programs(0) - 1
        row = srow_ref[i]
        prev_row = srow_ref[jnp.maximum(i - 1, 0)]
        n = slen_ref[i]  # the slot's valid entries: as many copies, no more
        # the next slot's, whose copies start at THIS step; none after the
        # last, so no copy is in flight when the call returns
        n_next = jnp.where(i < last, slen_ref[jnp.minimum(i + 1, last)], 0)
        buf = i % 2  # slot i gathers into buffer i % 2, on semaphore i % 2

        def start_slot(cols_ref, count, b):
            def start(tt):
                # one factor row per copy, selected on y's LEADING dim (the
                # only dim Mosaic lets a DMA slice below tile size); within
                # a slot the column indices are ascending (pack sorts by
                # (row, col)), so consecutive copies walk y in HBM address
                # order
                pltpu.make_async_copy(
                    y_ref.at[cols_ref[0, 0, tt]], yg.at[b, tt], sem.at[b],
                ).start()

            _unrolled(count, start)

        # rows of a buffer past a slot's length keep whatever an earlier
        # slot gathered there and meet weight 0 in the matmuls: they must
        # hold finite numbers from the start (0 × NaN is NaN). The first
        # slot has no step before it: its copies start here
        @pl.when(i == 0)
        def _():
            yg[...] = jnp.zeros_like(yg)
            start_slot(scols_ref, n, 0)

        # first slot of a new output row: the (1, k, k)/(1, 1, k) blocks
        # just rotated in (their VMEM content is undefined) — zero before
        # the first accumulation. Slots are row-sorted, so a row's block
        # stays resident for all of its slots and flushes to HBM exactly
        # once.
        @pl.when(jnp.logical_or(i == 0, prev_row != row))
        def _():
            a_ref[...] = jnp.zeros_like(a_ref)
            b_ref[...] = jnp.zeros_like(b_ref)

        # the pipeline: slot i's copies were started a step ago, slot i+1's
        # start NOW into the other buffer, before this slot's waits — the
        # copy engine works on them for the whole of this slot's matmuls. An
        # empty slot (the pack's pad slots, whose owner is the spill row)
        # starts nothing, waits for nothing and skips the matmuls, whichever
        # side of a pair it is on.
        start_slot(scols_next_ref, n_next, 1 - buf)

        @pl.when(n > 0)
        def _():
            def wait(tt):
                # a wait names no copy of its own: a semaphore counts bytes,
                # and every copy's are one row's. Hence one semaphore a
                # BUFFER: on a shared one, rows of slot i+1 landing first
                # would satisfy slot i's waits
                pltpu.make_async_copy(
                    y_ref.at[0], yg.at[buf, 0], sem.at[buf]).wait()

            _unrolled(n, wait)

            ygv = yg[buf].reshape(t, kp)[:, :k]  # (T, k) f32
            wc = wc_ref[0]  # (2, T): Gramian weights, RHS coefficients
            # the Gramian weights are a lane-major row and must scale the
            # gathered rows along SUBLANES: mask the broadcast row to the
            # diagonal and lane-reduce — iota/select/reduce only, where a
            # (1, T) → (T, 1) reshape is a relayout Mosaic may not have
            diag = (jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
                    == jax.lax.broadcasted_iota(jnp.int32, (t, t), 1))
            wcol = jnp.sum(jnp.where(diag, wc[0:1, :], 0.0), axis=1,
                           keepdims=True)  # (T, 1)
            # cast to the compute dtype AFTER the 32-bit gather so bf16
            # inputs hit the MXU's bf16×bf16→f32 path like the einsum
            # formulation does
            ygc = ygv.astype(cd)
            ga = jax.lax.dot_general(
                (ygv * wcol).astype(cd), ygc, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (k, k): sum_t w_t · y_t ⊗ y_t
            gb = jnp.dot(wc[1:2, :].astype(cd), ygc,
                         preferred_element_type=jnp.float32)  # (1, k)
            a_ref[...] = a_ref[...] + ga[None]
            b_ref[...] = b_ref[...] + gb[None]

    return kernel


def gather_gramian_accumulate(y, srow, slens, scols, w, coef, *, block: int,
                              interpret: bool):
    """Fused gather → per-slot Gramian → per-row accumulate for one block.

    Args:
      y: (R, k) opposite-side factors, f32 or bf16 (the dtype is the MXU
        input precision; the gather itself always moves 32-bit rows).
      srow: (S,) int32 block-local owner row per slot, SORTED ascending,
        pad = ``block`` (the spill row).
      slens: (S,) int32 valid entries per slot, 0 on pad slots: the kernel
        copies a slot's first ``slens`` rows and no others.
      scols: (S, T) int32 gather indices into ``y`` (column-ascending
        within each slot).
      w / coef: (S, T) f32 per-entry Gramian / RHS weights, zero from
        ``slens`` on (the mask and confidence algebra are applied by the
        caller).
      block: rows per block; outputs carry the extra spill row.

    Returns (big_a (block+1, k, k) f32, big_b (block+1, k) f32). Rows with
    no slots return exact zeros (donated zero inputs).
    """
    s, t = scols.shape
    k = y.shape[1]
    kp = _pad_dim(k, _LANE)
    y3 = jnp.pad(y.astype(jnp.float32), ((0, 0), (0, kp - k))).reshape(
        -1, 1, kp)
    wc = jnp.stack([w, coef], axis=1)  # (S, 2, T)
    scols3 = scols.reshape(s, 1, t)
    a0 = jnp.zeros((block + 1, k, k), jnp.float32)
    b0 = jnp.zeros((block + 1, 1, k), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # srow drives the output index maps, slens the copy loops
        num_scalar_prefetch=2,
        grid=(s,),
        in_specs=[
            # gather indices are DMA addresses: SMEM, one slot per step
            pl.BlockSpec((1, 1, t), lambda i, sr, sl: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            # ... and the NEXT slot's, whose copies start a step early
            pl.BlockSpec((1, 1, t),
                         lambda i, sr, sl: (jnp.minimum(i + 1, s - 1), 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 2, t), lambda i, sr, sl: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),  # y stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),  # big_a zero donor
            pl.BlockSpec(memory_space=pl.ANY),  # big_b zero donor
        ],
        out_specs=[
            pl.BlockSpec((1, k, k), lambda i, sr, sl: (sr[i], 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, k), lambda i, sr, sl: (sr[i], 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            # gathered factor rows: this slot's and the next slot's
            pltpu.VMEM((2, t, 1, kp), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),  # one a buffer
        ],
    )
    big_a, big_b = pl.pallas_call(
        _make_gather_gramian_kernel(t, k, kp, y.dtype),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((block + 1, k, k), jnp.float32),
            jax.ShapeDtypeStruct((block + 1, 1, k), jnp.float32),
        ],
        # zero donors alias the outputs: rows no slot ever visits keep
        # exact zeros — deterministic on hardware AND under interpret
        input_output_aliases={6: 0, 7: 1},
        interpret=interpret,
    )(srow.reshape(s), slens.reshape(s), scols3, scols3, wc, y3, a0, b0)
    return big_a, big_b[:, 0, :]


def _kernel(points_ref, weights_ref, centers_ref, sums_ref, counts_ref, cost_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        sums_ref[:] = jnp.zeros_like(sums_ref)
        counts_ref[:] = jnp.zeros_like(counts_ref)
        cost_ref[:] = jnp.zeros_like(cost_ref)

    p = points_ref[:]  # (T, D)
    w = weights_ref[:]  # (T, 1); 0 marks padding rows
    c = centers_ref[:]  # (K, D)

    # squared distances, one MXU matmul per tile
    p_sq = jnp.sum(p * p, axis=1, keepdims=True)  # (T, 1)
    c_sq = jnp.sum(c * c, axis=1)[None, :]  # (1, K)
    cross = jnp.dot(p, c.T, preferred_element_type=jnp.float32)  # (T, K)
    d2 = jnp.maximum(p_sq - 2.0 * cross + c_sq, 0.0)

    # nearest center as a one-hot indicator without host round trips;
    # ties broken toward the lowest index like argmin
    min_d2 = jnp.min(d2, axis=1, keepdims=True)  # (T, 1)
    is_min = (d2 <= min_d2).astype(jnp.float32)
    k_ids = jax.lax.broadcasted_iota(jnp.int32, d2.shape, dimension=1)
    first_min = jnp.min(
        jnp.where(is_min > 0, k_ids, jnp.iinfo(jnp.int32).max), axis=1, keepdims=True
    )
    indicator = (k_ids == first_min).astype(jnp.float32) * w  # (T, K)

    sums_ref[:] += jnp.dot(indicator.T, p, preferred_element_type=jnp.float32)
    counts_ref[:] += jnp.sum(indicator, axis=0, keepdims=True)
    cost_ref[:] += jnp.sum(min_d2 * w, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(points, weights, centers, *, interpret: bool):
    n_pad, d_pad = points.shape
    k_pad = centers.shape[0]
    grid = (n_pad // TILE_N,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_N, d_pad), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((TILE_N, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((k_pad, d_pad), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((k_pad, d_pad), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k_pad), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k_pad, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(points, weights, centers)


def kmeans_assign_accumulate(points, weights, centers, *, interpret: bool):
    """Fused Lloyd accumulation.

    Args: points (N, D) f32, weights (N,) f32 (0 = padding), centers (K, D).
    Returns (sums (K, D), counts (K,), cost scalar) as jax arrays.
    """
    points = jnp.asarray(points, dtype=jnp.float32)
    weights = jnp.asarray(weights, dtype=jnp.float32)
    centers = jnp.asarray(centers, dtype=jnp.float32)
    n, d = points.shape
    k = centers.shape[0]

    n_pad = _pad_dim(max(n, 1), TILE_N)
    d_pad = _pad_dim(d, _LANE)
    k_pad = _pad_dim(k, 8)
    pts = jnp.zeros((n_pad, d_pad), jnp.float32).at[:n, :d].set(points)
    # padding centers sit at +inf distance: give them huge coordinates is
    # wrong (inf*0 NaN); instead pad with zeros and mask padded-k columns by
    # adding a large constant to their distances via c_sq — achieved by
    # placing padded centers far away on an unused axis
    ctr = jnp.full((k_pad, d_pad), 0.0, jnp.float32).at[:k, :d].set(centers)
    if k_pad > k:
        ctr = ctr.at[k:, 0].set(FAR_AWAY)
    wts = jnp.zeros((n_pad, 1), jnp.float32).at[:n, 0].set(weights)

    sums, counts, cost = _call(pts, wts, ctr, interpret=bool(interpret))
    return sums[:k, :d], counts[0, :k], cost[0, 0]
