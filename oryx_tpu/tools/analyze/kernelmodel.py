"""Static models of every ``pl.pallas_call`` in the project.

The dataflow pass (dataflow.py) sees jit programs; this module sees INSIDE
the Pallas kernel layer that those programs call into — the layer where the
trainer's MFU recovery lives and where a wrong BlockSpec ships silently
(interpret mode hides out-of-bounds reads, and the VMEM gate guarding a
kernel is hand-derived math that can drift from the kernel it guards).

One :class:`KernelModel` per ``pallas_call`` site carries everything the
``pallas`` checker family and the ``analyze --cost`` kernel table need:

  * the grid (dims as ints or shape symbols, ``"b_pad // tile_b"``);
  * every buffer — in/out ``BlockSpec`` blocks, ``scratch_shapes`` — with
    its block shape, memory space, dtype (from the operand expression where
    statically visible), and a classified index map (constant / grid-index /
    grid-index-plus-offset / data-dependent scalar-prefetch);
  * ``input_output_aliases`` resolved to operand expressions;
  * the kernel function node (through one factory hop — the
    ``_make_kernel(t, k)`` closure idiom) and its parameter layout
    (``prefetch + inputs + outputs + scratch``), for in-kernel zero-init
    evidence;
  * the ``interpret`` argument's provenance (literal / parameter / absent).

On top of the parsed buffers sit the VMEM budget math the checkers and the
consistency tests share: padded byte counts under the tiling Mosaic infers
((8, 128) f32, (16, 128) bf16, (32, 128) int8, fewer rows when the
second-minor dim is smaller — :func:`sublane_tile`), a ×2 pipelining multiplier
for grid-varying blocks (Mosaic double-buffers them), and symbolic
:class:`dataflow.Poly` renderings for the ``--cost`` table. The registered
budget knobs (``oryx.analyze.kernel.*``) are the single source of truth the
runtime gates in ``ops/pallas_kernels.py`` are pinned against by
``tests/test_kernel_differential.py`` — the static twin of
``_GG_MAX_FEATURES`` that makes silent drift a tier-1 failure.

Stdlib-only, riding the memoized per-file scope caches like every other
analyze substrate.
"""

from __future__ import annotations

import ast
import math
import re

from oryx_tpu.tools.analyze.core import scope_nodes
from oryx_tpu.tools.analyze.dataflow import (
    DTYPE_BYTES,
    Poly,
    dim_of_node,
    dtype_of_node,
    module_name,
    shape_env,
)

# -- tiling / budgets --------------------------------------------------------

LANE = 128
#: dtype -> minimum sublane count of one native VMEM tile (guide table).
SUBLANE = {"int8": 32, "bfloat16": 16, "float32": 8, "float64": 8}

#: The scoped-VMEM limit the TPU compiler holds one kernel to (16 MiB on a
#: v5e, from its own out-of-memory message) — the ceiling the whole-kernel
#: resident footprint is checked against.
VMEM_LIMIT_BYTES = 16 << 20
#: Scoped-VMEM budget for the LARGEST single buffer of a grid-tiled kernel
#: (the discipline ``spd_solve_batched`` sizes its batch tile under). The
#: compiler's allocation for that kernel measures ~4.75× its largest buffer
#: against the 16 MiB scoped limit, so 3 MiB is the most that leaves margin.
SCOPED_BUDGET_BYTES = 3 << 20
#: The same discipline for the BLOCKED SPD kernel (``_spd_blocked_call``,
#: past 128 features): its values are one panel's rows and one system's MXU
#: operands, not whole blocks, so the compiler's allocation is its
#: double-buffered A block and its scratch plus a little: the largest
#: buffer, the ``(tile_b, 256, pad128(k+1))`` scratch, may take 4 MiB.
SPD_BLOCKED_BUDGET_BYTES = 4 << 20
#: What the compiler allocates for the blocked kernel beyond the buffers
#: this model counts, a system of the tile, rounded up from the largest
#: measured. Compiled for a described v5e at the trainer's 1,072 systems
#: (PERF.md §3): 14.02 MiB at 16 rows of 250 or 255 features against
#: the model's 12.06 (0.123 MiB a row), 9.26 against 8.31 at 16 rows of 129,
#: 6.99 against 6.03 at 8 rows of 250, 7.40 against 7.03 at 8 rows of 256.
#: The drift gate holds the model's footprint plus this under the limit.
SPD_BLOCKED_SLACK_BYTES_PER_ROW = 128 << 10
#: Resident-state budget for accumulator kernels whose output blocks stay
#: VMEM-resident across grid steps (the gather-Gramian shape): double-
#: buffered (k, k) accumulators + the TWO gather buffers (this slot's rows
#: and the next slot's, fetched under this slot's matmuls) must leave the
#: bulk of VMEM to the pipeline. The value IS the kernel's footprint at the
#: ``_GG_MAX_FEATURES = 256`` gate and the widest slot (T = 512): 1,583,104
#: B, so anything added to the kernel fails the drift gate until both sides
#: are re-derived. Not a hardware limit: compiled for a described v5e the
#: call is accepted to k = 768 at T = 512 and refused at 1,024 (17.47 MiB
#: of the 16 MiB scoped limit; PERF.md, PR 27). See docs/static_analysis.md
#: "Pallas kernel family" for the evaluated math.
RESIDENT_BUDGET_BYTES = 1_583_104
#: VMEM the gather-Gramian kernel's TABLE-RESIDENT form may take in all: the
#: opposite table held whole (``_GG_RESIDENT_TABLE_BYTES`` = 32 MiB of
#: 32-bit, lane-padded rows) plus the rest of that form's footprint at the
#: ``_GG_MAX_FEATURES = 256`` gate and the widest slot (T = 512): the
#: (T, pad128(k)) gather tile, the double-buffered (1, k, k)/(1, 1, k)
#: accumulator blocks and the (1, 2, T) weight block, 1,058,816 B. Like
#: ``RESIDENT_BUDGET_BYTES`` it IS the footprint at the gate, so anything
#: added to that form fails the drift gate until both sides are re-derived
#: (tests/test_kernel_differential.py). Under a quarter of a v5e core's
#: 128 MiB of VMEM.
GG_TABLE_RESIDENT_BUDGET_BYTES = (32 << 20) + 1_058_816
#: The SMEM one program may use (1 MiB on a v5e, from the compiler's own
#: "Used 1.00M of 1.00M smem" message), and the share of it that
#: scalar-prefetched operands — which ride there WHOLE, whatever the grid —
#: may take: three quarters, the rest left to the double-buffered SMEM
#: blocks and the compiler's own scalars. ``_GG_MAX_SLOTS`` is this budget
#: over the gather-Gramian call's two prefetched words a slot. Compiled for
#: a described v5e the call fits at 129,024 slots and overruns by 1.1 KB at
#: 130,048 and 9.1 KB at 131,072 (T = 512, two double-buffered index
#: blocks: PR 27): the quarter is margin, not need.
SMEM_LIMIT_BYTES = 1 << 20
SMEM_PREFETCH_BUDGET_BYTES = 768 << 10


def budgets(config=None) -> dict:
    """The three budget knobs, config-overridable (``oryx.analyze.kernel.*``)
    with the module constants as defaults. ``config=None`` reads the process
    default config when available and silently keeps the constants when the
    config subsystem is not importable (the analyzer must run anywhere)."""
    out = {
        "vmem_limit_bytes": VMEM_LIMIT_BYTES,
        "scoped_budget_bytes": SCOPED_BUDGET_BYTES,
        "resident_budget_bytes": RESIDENT_BUDGET_BYTES,
    }
    if config is None:
        try:
            from oryx_tpu.common import config as cfg

            config = cfg.get_default()
        except Exception:
            return out
    try:
        out["vmem_limit_bytes"] = config.get_int(
            "oryx.analyze.kernel.vmem-limit-bytes", out["vmem_limit_bytes"])
        out["scoped_budget_bytes"] = config.get_int(
            "oryx.analyze.kernel.scoped-budget-bytes",
            out["scoped_budget_bytes"])
        out["resident_budget_bytes"] = config.get_int(
            "oryx.analyze.kernel.resident-budget-bytes",
            out["resident_budget_bytes"])
    except Exception:
        pass
    return out


def pad_up(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def sublane_tile(dim: int, dtype: "str | None") -> int:
    """Rows of the tile Mosaic lays a buffer out in, given its second-minor
    dim: the smallest power of two covering ``dim``, no smaller than the
    dtype's packing (rows sharing one 32-bit sublane word) and no larger
    than the native tile. A ``(T, 1, k)`` f32 buffer is ``(1, 128)``-tiled
    — one row per tile, which is what makes a per-row DMA expressible — not
    padded to 8 rows."""
    native = SUBLANE.get(dtype or "float32", 8)
    rows = native // 8  # packing
    while rows < min(native, dim):
        rows *= 2
    return rows


# -- index-map classification ------------------------------------------------

#: One classified component of a block index map, per block dimension:
#:   ("const", c)        — fixed block index c
#:   ("grid", axis)      — the grid index of ``axis``, unscaled
#:   ("grid+", axis, c)  — grid index plus a positive constant offset
#:   ("data",)           — data-dependent (scalar-prefetch lookup, etc.)
#:   ("expr", text)      — anything else, kept for display


def _classify_map_component(node, grid_args: list, prefetch_args: set):
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return ("const", node.value)
    if isinstance(node, ast.Name):
        if node.id in grid_args:
            return ("grid", grid_args.index(node.id))
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id in prefetch_args:
            return ("data",)
    if isinstance(node, ast.BinOp) and isinstance(node.op, (ast.Add, ast.Sub)):
        left, right = node.left, node.right
        if isinstance(node.op, ast.Sub):
            # i - c never extends past the grid extent; treat as plain grid
            if isinstance(left, ast.Name) and left.id in grid_args:
                return ("grid", grid_args.index(left.id))
        else:
            for a, b in ((left, right), (right, left)):
                if (isinstance(a, ast.Name) and a.id in grid_args
                        and isinstance(b, ast.Constant)
                        and isinstance(b.value, int) and b.value > 0):
                    return ("grid+", grid_args.index(a.id), b.value)
    try:
        return ("expr", ast.unparse(node))
    except Exception:  # pragma: no cover — malformed tree
        return ("expr", "?")


class KernelBuffer:
    """One VMEM/SMEM/ANY buffer of a kernel call: an input or output block,
    or a scratch allocation."""

    __slots__ = ("kind", "index", "label", "space", "shape", "dtype",
                 "index_map", "spec_node", "default_map_over_grid")

    def __init__(self, kind, index, label, space, shape, dtype, index_map,
                 spec_node):
        self.kind = kind  # "in" | "out" | "scratch"
        self.index = index  # position within its kind
        self.label = label  # operand/scratch source text for messages
        self.space = space  # "vmem" | "smem" | "any" | "sem" | None
        self.shape = shape  # tuple of int|str, or None (whole-operand)
        self.dtype = dtype  # lattice dtype name or None (unknown -> f32)
        self.index_map = index_map  # list of classified components, or None
        self.spec_node = spec_node  # AST node for finding line numbers
        # a blocked spec with NO parsable index map under a non-empty grid
        # (Pallas defaults to the identity grid map, or the map is a named
        # function): still grid-varying, so still double-buffered
        self.default_map_over_grid = False

    @property
    def pipelined(self) -> bool:
        """Grid-varying blocks are double-buffered by the Mosaic pipeline;
        constant-map (resident) blocks and scratch are single-buffered."""
        if self.kind == "scratch":
            return False
        if not self.index_map:
            return self.default_map_over_grid
        return any(c[0] != "const" for c in self.index_map)

    @property
    def itemsize(self) -> int:
        return DTYPE_BYTES.get(self.dtype or "float32", 4)

    def revisits_across_grid(self, grid) -> bool:
        """Whether the same block is PROVABLY selected on more than one grid
        step: a data-dependent map always can be; a constant map over a
        non-trivial grid always is; a plain grid-index map revisits when a
        non-trivial grid axis steers no component. Unclassified ``expr``
        components (``2 * i`` strides) make the map unprovable — this is a
        checker input, so unprovable means silent, not flagged."""
        if self.index_map is None or not grid:
            return False
        if any(c[0] == "data" for c in self.index_map):
            return True
        if any(c[0] == "expr" for c in self.index_map):
            return False
        used = {c[1] for c in self.index_map if c[0] in ("grid", "grid+")}
        for axis, extent in enumerate(grid):
            if axis in used:
                continue
            if not (isinstance(extent, int) and extent <= 1):
                return True
        return False

    def padded_bytes(self, bindings: dict) -> "float | None":
        """Concrete VMEM bytes of ONE buffer instance under ``bindings``,
        with Mosaic's tiling applied to the trailing two dims (the hardware
        pads them whether the block asks or not): lanes to 128, sublanes to
        :func:`sublane_tile`."""
        if self.shape is None:
            return None
        dims = [_dim_value(d, bindings) for d in self.shape]
        if any(d is None for d in dims):
            return None
        if len(dims) >= 1:
            dims[-1] = pad_up(max(1, dims[-1]), LANE)
        if len(dims) >= 2:
            rows = max(1, dims[-2])
            dims[-2] = pad_up(rows, sublane_tile(rows, self.dtype))
        total = float(self.itemsize)
        for d in dims:
            total *= max(1, d)
        return total

    def block_poly(self) -> Poly:
        """Unpadded symbolic bytes of one buffer instance (display)."""
        if self.shape is None:
            return Poly.const(0.0)
        return Poly.of_shape(self.shape) * float(self.itemsize)


_DIM_EXPR_RE = re.compile(r"^[A-Za-z0-9_ +\-*/()]+$")


def _dim_value(dim, bindings: dict) -> "int | None":
    """Resolve one abstract dim to an int under ``bindings``: ints pass
    through, plain symbols look up, and short arithmetic expressions over
    bound symbols (``"block + 1"``, ``"b_pad // tile_b"``) evaluate through
    a restricted AST walk (never ``eval``)."""
    if isinstance(dim, int):
        return dim
    if not isinstance(dim, str):
        return None
    if dim in bindings:
        return int(bindings[dim])
    if not _DIM_EXPR_RE.match(dim):
        return None
    try:
        node = ast.parse(dim, mode="eval").body
    except SyntaxError:
        return None

    def ev(n) -> "int | None":
        if isinstance(n, ast.Constant) and isinstance(n.value, int):
            return n.value
        if isinstance(n, ast.Name):
            v = bindings.get(n.id)
            return int(v) if v is not None else None
        if isinstance(n, ast.BinOp):
            a, b = ev(n.left), ev(n.right)
            if a is None or b is None:
                return None
            if isinstance(n.op, ast.Add):
                return a + b
            if isinstance(n.op, ast.Sub):
                return a - b
            if isinstance(n.op, ast.Mult):
                return a * b
            if isinstance(n.op, ast.FloorDiv) and b:
                return a // b
            return None
        return None

    return ev(node)


# -- the parsed kernel call --------------------------------------------------


class KernelModel:
    """One parsed ``pallas_call`` site."""

    __slots__ = ("fctx", "call", "name", "enclosing", "grid", "inputs",
                 "outputs", "scratch", "operands", "out_shapes", "aliases",
                 "interpret", "kernel_fn", "num_prefetch", "senv",
                 "prefetch_shapes")

    def __init__(self, fctx, call, name, enclosing):
        self.fctx = fctx
        self.call = call
        self.name = name  # qualname of the enclosing function
        self.enclosing = enclosing
        self.grid: tuple = ()
        self.inputs: list = []
        self.outputs: list = []
        self.scratch: list = []
        self.operands: list = []  # AST nodes of the call's runtime args
        self.out_shapes: list = []  # [(dims, dtype)] from out_shape
        self.aliases: dict = {}  # operand position -> output index
        self.interpret = None  # ("literal", bool) | ("param", name) | None
        self.kernel_fn = None  # FunctionDef of the kernel body, if resolved
        self.num_prefetch = 0
        self.senv: dict = {}
        # whole-array shapes of the scalar-prefetched operands, where the
        # call site shows them (``srow.reshape(s)``); None where it does not
        self.prefetch_shapes: list = []

    # -- byte math ----------------------------------------------------------

    def buffers(self) -> list:
        return [*self.inputs, *self.outputs, *self.scratch]

    def vmem_buffers(self) -> list:
        return [b for b in self.buffers() if b.space == "vmem"]

    def vmem_bytes(self, bindings: dict) -> "float | None":
        """Concrete resident VMEM footprint under ``bindings``: padded block
        bytes, ×2 for pipelined (grid-varying) blocks. None when any VMEM
        buffer's shape does not resolve."""
        total = 0.0
        for b in self.vmem_buffers():
            size = b.padded_bytes(bindings)
            if size is None:
                return None
            total += size * (2.0 if b.pipelined else 1.0)
        return total

    def max_buffer_bytes(self, bindings: dict) -> "float | None":
        """The largest single VMEM buffer (unmultiplied) — the scoped-VMEM
        stack discipline the spd tile sizing budgets against."""
        best = 0.0
        for b in self.vmem_buffers():
            size = b.padded_bytes(bindings)
            if size is None:
                return None
            best = max(best, size)
        return best

    def prefetch_smem_bytes(self, bindings: dict) -> "float | None":
        """SMEM taken by the scalar-prefetched operands: each rides there
        whole, one 32-bit word an element. None when a shape is not visible
        at the call site or does not resolve."""
        total = 0.0
        for shape in self.prefetch_shapes:
            if shape is None:
                return None
            dims = [_dim_value(d, bindings) for d in shape]
            if any(d is None for d in dims):
                return None
            total += 4.0 * math.prod(max(1, d) for d in dims)
        return total

    def smem_bytes(self, bindings: dict) -> "float | None":
        """The program's SMEM footprint under ``bindings``: the prefetched
        operands, the SMEM blocks (padded; ×2 when pipelined) and SMEM
        scratch. Semaphores live in their own space and are not counted."""
        total = self.prefetch_smem_bytes(bindings)
        if total is None:
            return None
        for b in self.buffers():
            if b.space != "smem":
                continue
            size = b.padded_bytes(bindings)
            if size is None:
                return None
            total += size * (2.0 if b.pipelined else 1.0)
        return total

    def vmem_poly(self) -> Poly:
        """Unpadded symbolic footprint (pipelined ×2) for display; evaluate
        with :meth:`vmem_bytes` when exact padded numbers matter."""
        total = Poly.const(0.0)
        for b in self.vmem_buffers():
            total = total + b.block_poly() * (2.0 if b.pipelined else 1.0)
        return total

    def hbm_step_poly(self) -> Poly:
        """HBM bytes moved per grid step through the automatic pipeline: one
        grid-varying input block in, one grid-varying output block out.
        Resident (constant-map) blocks and hand-rolled DMA out of ``ANY``
        operands are not counted — this is the pipeline's traffic, an upper
        bound per output revisit-flush."""
        total = Poly.const(0.0)
        for b in (*self.inputs, *self.outputs):
            if b.space == "vmem" and b.pipelined:
                total = total + b.block_poly()
        return total

    def symbols(self) -> set:
        out: set = set()
        for b in self.vmem_buffers():
            out |= b.block_poly().symbols()
        return out


# -- parsing -----------------------------------------------------------------

_SPACE_NAMES = {
    "VMEM": "vmem", "SMEM": "smem", "ANY": "any", "HBM": "any",
    "SEMAPHORE": "sem",
}


def _space_of(fctx, node) -> "str | None":
    resolved = fctx.resolve(node)
    if not resolved:
        return None
    tail = resolved.rsplit(".", 1)[-1]
    return _SPACE_NAMES.get(tail)


def _module_consts(fctx) -> dict:
    """Top-level ``NAME = <int>`` constants (``TILE_N = 512``), memoized —
    block shapes routinely name them."""
    cached = getattr(fctx, "_int_consts", None)
    if cached is None:
        cached = {}
        for node in fctx.tree.body:
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                dim = dim_of_node(node.value)
                val = _dim_value(dim, {}) if dim is not None else None
                if val is not None:
                    cached[node.targets[0].id] = val
        fctx._int_consts = cached
    return cached


def _resolve_dims(fctx, dims) -> "tuple | None":
    if dims is None:
        return None
    consts = _module_consts(fctx)
    return tuple(consts.get(d, d) if isinstance(d, str) else d for d in dims)


def _tuple_dims(node) -> "tuple | None":
    if isinstance(node, (ast.Tuple, ast.List)):
        dims = tuple(dim_of_node(e) for e in node.elts)
        return None if any(d is None for d in dims) else dims
    d = dim_of_node(node)
    return None if d is None else (d,)


def _local_value(fctx, fn_node, node):
    """Follow one ``name = <expr>`` hop inside the enclosing function — the
    ``grid_spec = pltpu.PrefetchScalarGridSpec(...)`` idiom."""
    if not isinstance(node, ast.Name) or fn_node is None:
        return node
    found = node
    for stmt in scope_nodes(fctx, fn_node):
        if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and stmt.targets[0].id == node.id):
            found = stmt.value
    return found


def _range_comprehension(fctx, fn_node, node) -> "int | None":
    """How many elements ``[<spec> for g in range(N)]`` makes, ``N`` an int
    literal or a name bound to a module constant (through one local hop):
    one BlockSpec an element, the way ``lsh_listed_scores`` gives each of a
    step's windows its own."""
    if not (isinstance(node, ast.ListComp) and len(node.generators) == 1):
        return None
    it = node.generators[0].iter
    if not (isinstance(it, ast.Call) and isinstance(it.func, ast.Name)
            and it.func.id == "range" and len(it.args) == 1):
        return None
    arg = _local_value(fctx, fn_node, it.args[0])
    if isinstance(arg, ast.Constant) and isinstance(arg.value, int):
        return arg.value
    if isinstance(arg, ast.Name):
        return _module_consts(fctx).get(arg.id)
    return None


def _parse_block_spec(fctx, node, num_prefetch: int) -> "tuple | None":
    """(shape dims|None, space, index_map components|None) of one
    ``pl.BlockSpec(...)`` expression; None when it is not one."""
    if not isinstance(node, ast.Call):
        return None
    resolved = fctx.resolve(node.func) or ""
    if not resolved.endswith("BlockSpec"):
        return None
    shape_node = None
    map_node = None
    space = None
    pos = list(node.args)
    if pos:
        shape_node = pos[0]
        if len(pos) > 1:
            map_node = pos[1]
    for kw in node.keywords:
        if kw.arg == "block_shape":
            shape_node = kw.value
        elif kw.arg == "index_map":
            map_node = kw.value
        elif kw.arg == "memory_space":
            space = _space_of(fctx, kw.value)
    shape = _tuple_dims(shape_node) if shape_node is not None else None
    index_map = None
    if isinstance(map_node, ast.Lambda):
        args = [a.arg for a in map_node.args.args]
        grid_args = args[: len(args) - num_prefetch] if num_prefetch else args
        prefetch = set(args[len(grid_args):])
        body = map_node.body
        comps = (list(body.elts) if isinstance(body, ast.Tuple) else [body])
        index_map = [
            _classify_map_component(c, grid_args, prefetch) for c in comps
        ]
    return _resolve_dims(fctx, shape), space, index_map


def _parse_scratch(fctx, node) -> "KernelBuffer | None":
    if not isinstance(node, ast.Call):
        return None
    resolved = fctx.resolve(node.func) or ""
    tail = resolved.rsplit(".", 1)[-1]
    if tail in ("VMEM", "SMEM"):
        dims = _tuple_dims(node.args[0]) if node.args else None
        dtype = dtype_of_node(fctx, node.args[1]) if len(node.args) > 1 else None
        return KernelBuffer(
            "scratch", 0, ast.unparse(node)[:40], tail.lower(),
            _resolve_dims(fctx, dims), dtype, None, node,
        )
    if "SemaphoreType" in resolved or tail == "DMA":
        # ``SemaphoreType.DMA((n,))``: the shape is the count of semaphores
        dims = _tuple_dims(node.args[0]) if node.args else None
        return KernelBuffer("scratch", 0, "semaphores", "sem",
                            _resolve_dims(fctx, dims), None, None, node)
    return None


def _operand_dtype(fctx, fn_node, node) -> "str | None":
    """Best-effort dtype of a runtime operand expression: a dtype kwarg on a
    constructor call, an ``.astype(x)``, or one local-assignment hop."""
    if isinstance(node, ast.Call):
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr == "astype" and node.args:
            return dtype_of_node(fctx, node.args[0])
        for kw in node.keywords:
            if kw.arg == "dtype":
                return dtype_of_node(fctx, kw.value)
        if len(node.args) > 1:
            dt = dtype_of_node(fctx, node.args[1])
            if dt:
                return dt
        return None
    if isinstance(node, ast.Name) and fn_node is not None:
        val = _local_value(fctx, fn_node, node)
        if val is not node:
            return _operand_dtype(fctx, fn_node, val)
    return None


def _out_shape_entries(fctx, fn_node, node) -> list:
    """[(dims|None, dtype|None)] from an ``out_shape=`` expression — one
    ``jax.ShapeDtypeStruct`` or a list of them, through one local hop."""
    node = _local_value(fctx, fn_node, node)
    entries = (list(node.elts) if isinstance(node, (ast.Tuple, ast.List))
               else [node])
    out = []
    for e in entries:
        dims = dtype = None
        if isinstance(e, ast.Call):
            resolved = fctx.resolve(e.func) or ""
            if resolved.endswith("ShapeDtypeStruct"):
                if e.args:
                    dims = _resolve_dims(fctx, _tuple_dims(e.args[0]))
                if len(e.args) > 1:
                    dtype = dtype_of_node(fctx, e.args[1])
                for kw in e.keywords:
                    if kw.arg == "shape":
                        dims = _resolve_dims(fctx, _tuple_dims(kw.value))
                    elif kw.arg == "dtype":
                        dtype = dtype_of_node(fctx, kw.value)
        out.append((dims, dtype))
    return out


def _resolve_kernel_fn(fctx, node):
    """The kernel FunctionDef from ``pallas_call``'s first argument: a plain
    name, or a factory call returning an inner def (the
    ``_make_kernel(t, k)`` closure idiom)."""
    if isinstance(node, ast.Name):
        fns = fctx.functions_by_name.get(node.id)
        return fns[0] if fns else None
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
        factories = fctx.functions_by_name.get(node.func.id)
        if not factories:
            return None
        factory = factories[0]
        inner = {
            n.name: n for n in ast.walk(factory)
            if isinstance(n, ast.FunctionDef) and n is not factory
        }
        for n in ast.walk(factory):
            if (isinstance(n, ast.Return) and isinstance(n.value, ast.Name)
                    and n.value.id in inner):
                return inner[n.value.id]
    return None


def kernel_models(project) -> list:
    """Every statically-parsable ``pallas_call`` site, memoized on the
    project. Files that never mention ``pallas_call`` are skipped textually
    (the analyzer's 3 s budget)."""
    cached = getattr(project, "_kernel_models", None)
    if cached is not None:
        return cached
    out: list = []
    for fctx in project.files:
        if "pallas_call" not in fctx.source:
            continue
        containing: dict = {}
        for qual, fn in fctx.functions:
            for node in scope_nodes(fctx, fn):
                containing[id(node)] = (qual, fn)
        for node in ast.walk(fctx.tree):
            if not isinstance(node, ast.Call):
                continue
            resolved = fctx.resolve(node.func) or ""
            if not (resolved.endswith(".pallas_call")
                    or resolved == "pallas_call"):
                continue
            qual, fn = containing.get(id(node), (None, None))
            model = KernelModel(fctx, node, qual or "<module>", fn)
            _fill_model(fctx, fn, model)
            out.append(model)
    project._kernel_models = out
    return out


def _fill_model(fctx, fn_node, model: KernelModel) -> None:
    call = model.call
    kwargs = {kw.arg: kw.value for kw in call.keywords if kw.arg}
    num_prefetch = 0
    grid_node = kwargs.get("grid")
    in_specs_node = kwargs.get("in_specs")
    out_specs_node = kwargs.get("out_specs")
    scratch_node = kwargs.get("scratch_shapes")

    spec = kwargs.get("grid_spec")
    if spec is not None:
        spec = _local_value(fctx, fn_node, spec)
        if isinstance(spec, ast.Call):
            skw = {kw.arg: kw.value for kw in spec.keywords if kw.arg}
            grid_node = skw.get("grid", grid_node)
            in_specs_node = skw.get("in_specs", in_specs_node)
            out_specs_node = skw.get("out_specs", out_specs_node)
            scratch_node = skw.get("scratch_shapes", scratch_node)
            np_node = skw.get("num_scalar_prefetch")
            if isinstance(np_node, ast.Constant) and isinstance(
                    np_node.value, int):
                num_prefetch = np_node.value
    model.num_prefetch = num_prefetch

    if grid_node is not None:
        grid_node = _local_value(fctx, fn_node, grid_node)
        dims = _tuple_dims(grid_node)
        model.grid = _resolve_dims(fctx, dims) or ()

    model.senv = shape_env(fctx, fn_node) if fn_node is not None else {}

    def specs_of(node):
        node = _local_value(fctx, fn_node, node)
        if isinstance(node, (ast.Tuple, ast.List)):
            return list(node.elts)
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
            return specs_of(node.left) + specs_of(node.right)
        repeat = _range_comprehension(fctx, fn_node, node)
        if repeat is not None:
            return [node.elt] * repeat
        return [node] if node is not None else []

    def make_buffer(kind, idx, spec_node):
        parsed = _parse_block_spec(fctx, spec_node, num_prefetch)
        if parsed is None:
            return None
        shape, space, index_map = parsed
        buf = KernelBuffer(kind, idx, f"{kind}[{idx}]", space, shape, None,
                           index_map, spec_node)
        if index_map is None and shape is not None and model.grid:
            buf.default_map_over_grid = True
        return buf

    for i, spec_node in enumerate(specs_of(in_specs_node)):
        buf = make_buffer("in", i, spec_node)
        if buf is not None:
            model.inputs.append(buf)
    for j, spec_node in enumerate(specs_of(out_specs_node)):
        buf = make_buffer("out", j, spec_node)
        if buf is not None:
            model.outputs.append(buf)
    for s_node in specs_of(scratch_node):
        buf = _parse_scratch(fctx, s_node)
        if buf is not None:
            buf.index = len(model.scratch)
            model.scratch.append(buf)

    if "out_shape" in kwargs:
        model.out_shapes = _out_shape_entries(fctx, fn_node,
                                              kwargs["out_shape"])
        for j, (dims, dtype) in enumerate(model.out_shapes):
            if j < len(model.outputs):
                model.outputs[j].dtype = dtype

    alias_node = kwargs.get("input_output_aliases")
    if alias_node is not None:
        alias_node = _local_value(fctx, fn_node, alias_node)
        if isinstance(alias_node, ast.Dict):
            for k_node, v_node in zip(alias_node.keys, alias_node.values):
                if (isinstance(k_node, ast.Constant)
                        and isinstance(k_node.value, int)
                        and isinstance(v_node, ast.Constant)
                        and isinstance(v_node.value, int)):
                    model.aliases[k_node.value] = v_node.value

    interp = kwargs.get("interpret")
    if isinstance(interp, ast.Constant) and isinstance(interp.value, bool):
        model.interpret = ("literal", interp.value)
    elif isinstance(interp, ast.Name):
        params = set()
        if fn_node is not None:
            a = fn_node.args
            params = {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs}
        model.interpret = (("param", interp.id) if interp.id in params
                           else ("local", interp.id))
    elif interp is not None:
        model.interpret = ("expr", ast.unparse(interp)[:40])

    # the operands: the call that invokes pallas_call's return value —
    # ``pl.pallas_call(...)(a, b)`` parses as Call(func=Call(pallas_call))
    if fn_node is not None:
        for n in scope_nodes(fctx, fn_node):
            if isinstance(n, ast.Call) and n.func is call:
                model.operands = list(n.args)
                break

    shape_of = model.senv.get("__shape_of__")
    model.prefetch_shapes = [
        _resolve_dims(fctx, shape_of(op)) if shape_of else None
        for op in model.operands[:num_prefetch]
    ]

    # the kernel function body (through one factory hop)
    if call.args:
        model.kernel_fn = _resolve_kernel_fn(fctx, call.args[0])

    # infer input block dtypes from operand expressions where visible
    if model.operands:
        for buf in model.inputs:
            pos = num_prefetch + buf.index
            if pos < len(model.operands) and buf.dtype is None:
                buf.dtype = _operand_dtype(fctx, fn_node,
                                           model.operands[pos])
                buf.label = ast.unparse(model.operands[pos])[:40]


def kernel_param_name(model: KernelModel, kind: str, index: int) -> "str | None":
    """The kernel-body parameter bound to buffer ``(kind, index)`` under the
    ``prefetch + inputs + outputs + scratch`` layout."""
    fn = model.kernel_fn
    if fn is None:
        return None
    params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    base = model.num_prefetch
    if kind == "in":
        pos = base + index
    elif kind == "out":
        pos = base + len(model.inputs) + index
    else:
        pos = base + len(model.inputs) + len(model.outputs) + index
    return params[pos] if pos < len(params) else None


_ZERO_CTORS = {"zeros", "zeros_like", "full", "full_like"}


def kernel_zeroes_param(model: KernelModel, param: "str | None") -> bool:
    """In-kernel zero-init evidence for one output ref: any store of a
    zeros-style constructor (or literal 0) into ``param[...]`` anywhere in
    the kernel body — the ``pl.when(first_visit)`` initialization pattern
    (nested defs included: that is how ``pl.when`` bodies are written)."""
    fn = model.kernel_fn
    if fn is None or param is None:
        return False
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign):
            continue
        for t in node.targets:
            if not (isinstance(t, ast.Subscript)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == param):
                continue
            v = node.value
            if isinstance(v, ast.Constant) and v.value == 0:
                return True
            if isinstance(v, ast.Call):
                resolved = model.fctx.resolve(v.func) or ""
                if resolved.rsplit(".", 1)[-1] in _ZERO_CTORS:
                    return True
    return False


# -- the --cost kernel table -------------------------------------------------


def kernel_cost_report(project, bindings: "dict | None" = None) -> list:
    """One row per ``pallas_call`` for ``analyze --cost``: the resident VMEM
    footprint and per-grid-step HBM block traffic as symbolic polynomials,
    with padded concrete bytes under ``--bind`` bindings. The static twin of
    the runtime CostRegistry, one level below the jit-program table."""
    rows = []
    for model in kernel_models(project):
        vmem = model.vmem_poly()
        hbm = model.hbm_step_poly()
        if not (vmem or hbm):
            continue
        rows.append({
            "kernel": f"{module_name(model.fctx.relpath)}.{model.name}",
            "path": model.fctx.relpath,
            "line": model.call.lineno,
            "grid": "×".join(str(d) for d in model.grid) or "-",
            "vmem_bytes": vmem,
            "hbm_bytes_per_step": hbm,
            "vmem_bytes_value": (model.vmem_bytes(bindings)
                                 if bindings else None),
            "hbm_bytes_per_step_value": (hbm.evaluate(bindings)
                                         if bindings else None),
        })
    rows.sort(key=lambda r: (r["path"], r["line"]))
    return rows
