"""The ``pallas`` checker family: static verification of Pallas kernels.

Hand-written TPU kernels fail in ways no other layer does: a BlockSpec that
walks past its operand reads garbage on chip while interpret mode (how the
CPU test suite runs every kernel) bounds-checks and hides it; an output
block revisited across grid steps without first-visit init accumulates into
whatever VMEM held before; a VMEM footprint past the per-core budget fails
to compile — or worse, the hand-derived gate guarding it drifts from the
kernel it guards. These five checks ride the parsed kernel models
(tools/analyze/kernelmodel.py):

  * ``kernel-vmem-budget`` — resident footprint (padded blocks ×2 when
    pipelined + scratch) against the per-core VMEM limit, naming the
    dominant buffer. Symbolic kernels render in ``analyze --cost`` and are
    pinned to their runtime gates by tests/test_kernel_differential.py.
  * ``kernel-tile-alignment`` — concrete block tails against the rule the
    Pallas TPU lowering enforces (each of a block's last two dims is a
    multiple of 8 / 128 or spans the operand's whole dim) and against the
    tiling Mosaic infers: a refused block when an indivisible dim provably
    does not span its operand (a ``(1, t)`` row-select over ``(S, T)``
    included), pad-waste when the hardware rounds a dim up.
  * ``kernel-index-bounds`` — index map × block shape against operand
    extents over the grid: flags what it can PROVE out of bounds (concrete
    arithmetic, or a positive constant offset past a proven-exact cover),
    stays silent on what it cannot.
  * ``kernel-alias-discipline`` — ``input_output_aliases`` shape/dtype
    mismatches, and output blocks revisited across grid steps with neither
    a donated alias input nor in-kernel zero-init (the accumulator-race
    class: deterministic garbage on chip, zeros under interpret).
  * ``kernel-interpret-default`` — wrappers whose ``interpret`` defaults
    ``True`` (or hard-coded ``interpret=True`` calls): on TPU they silently
    EMULATE the kernel instead of compiling it — the PR 6
    ``spd_solve_batched`` fix class. Required caller-threaded flags (what
    ``ops/pallas_kernels.py`` uses) and ``None`` resolved from the target
    devices are the sanctioned shapes.
"""

from __future__ import annotations

import ast
import re

from oryx_tpu.tools.analyze.kernelmodel import (
    LANE,
    budgets,
    kernel_models,
    kernel_param_name,
    kernel_zeroes_param,
    sublane_tile,
    _dim_value,
    _operand_dtype,
)

VMEM_ID = "kernel-vmem-budget"
TILE_ID = "kernel-tile-alignment"
BOUNDS_ID = "kernel-index-bounds"
ALIAS_ID = "kernel-alias-discipline"
INTERPRET_ID = "kernel-interpret-default"


class KernelVmemBudgetChecker:
    id = VMEM_ID
    version = 1

    def check(self, project) -> list:
        out = []
        limit = budgets()["vmem_limit_bytes"]
        for model in kernel_models(project):
            total = model.vmem_bytes({})
            if total is None or total <= limit:
                continue
            worst, worst_bytes = None, 0.0
            for b in model.vmem_buffers():
                size = (b.padded_bytes({}) or 0.0) * (2.0 if b.pipelined
                                                      else 1.0)
                if size > worst_bytes:
                    worst, worst_bytes = b, size
            detail = ""
            if worst is not None:
                shape = "×".join(str(d) for d in worst.shape)
                detail = (f" — dominated by the ({shape}) "
                          f"{worst.dtype or 'float32'} {worst.kind} block "
                          f"({worst_bytes / 1024.0:.0f} KiB"
                          + (" double-buffered)" if worst.pipelined else ")"))
            out.append(model.fctx.finding(
                VMEM_ID, model.call,
                f"kernel `{model.name}` needs {total / (1 << 20):.1f} MiB of "
                f"VMEM resident per grid step, past the {limit >> 20} MiB "
                f"per-core limit{detail} — shrink the block tile or spill "
                "to HBM (pltpu.ANY + manual DMA)",
                symbol=f"{model.name}:vmem",
            ))
        return out


def _operand_shape(model, b) -> "tuple | None":
    """The whole-array shape a blocked buffer windows into, where the
    wrapper's source shows it: ``out_shape`` for outputs, the shape
    environment's view of the operand expression for inputs."""
    if b.kind == "out":
        if b.index < len(model.out_shapes):
            return model.out_shapes[b.index][0]
        return None
    shape_of = model.senv.get("__shape_of__")
    pos = model.num_prefetch + b.index
    if shape_of and pos < len(model.operands):
        return shape_of(model.operands[pos])
    return None


class KernelTileAlignmentChecker:
    id = TILE_ID
    version = 2

    def check(self, project) -> list:
        out = []
        for model in kernel_models(project):
            for b in model.buffers():
                if b.space not in ("vmem", "smem") or not b.shape:
                    continue
                dims = [_dim_value(d, {}) for d in b.shape]
                full = (_operand_shape(model, b) if b.kind != "scratch"
                        else None)
                if full is not None and len(full) != len(dims):
                    full = None
                # (dim position from the end, the multiple the lowering
                # demands, the rows/lanes Mosaic pads to, axis name)
                checks = [(1, LANE, LANE, "lane")]
                if len(dims) >= 2 and dims[-2] is not None:
                    checks.append(
                        (2, 8, sublane_tile(dims[-2], b.dtype), "sublane"))
                for back, mult, tile, axis in checks:
                    d = dims[-back]
                    # symbolic dims are the wrapper-padded case: unprovable
                    if d is None or d % mult == 0:
                        continue
                    shape_txt = "×".join(str(x) for x in b.shape)
                    whole = _dim_value(full[-back], {}) if full else None
                    varies = bool(
                        b.index_map
                        and len(b.index_map) >= back
                        and b.index_map[-back][0] != "const"
                    )
                    # a map that moves along the dim walks more than one
                    # block of it, so the block cannot span the operand
                    if b.kind != "scratch" and (
                            varies or (whole is not None and whole != d)):
                        out.append(model.fctx.finding(
                            TILE_ID, b.spec_node,
                            f"kernel `{model.name}`: the {axis} dim of the "
                            f"({shape_txt}) {b.kind} block is {d} — neither "
                            f"a multiple of {mult} nor the operand's whole "
                            f"{axis} dim"
                            + (f" ({whole})" if whole is not None else "")
                            + "; the Pallas TPU lowering refuses the block "
                            "before Mosaic is reached (interpret mode "
                            "accepts it). Make the windowed dim a LEADING "
                            "dim — (S, T) → (S, 1, T) with a (1, 1, T) "
                            "block — or pad the block to the tile",
                            symbol=f"{model.name}:{b.kind}{b.index}:{axis}",
                        ))
                        continue
                    # a size-1 tail is the deliberate column/scalar idiom
                    # ((TILE_N, 1) weights): its padding is the price of the
                    # layout, not an oversight
                    padded = ((d + tile - 1) // tile) * tile
                    if b.space != "vmem" or d <= 1 or padded == d:
                        continue
                    waste = 100.0 * (padded - d) / padded
                    out.append(model.fctx.finding(
                        TILE_ID, b.spec_node,
                        f"kernel `{model.name}`: the {axis} dim of the "
                        f"({shape_txt}) {b.kind} block is {d}; the "
                        f"{b.dtype or 'float32'} tile rounds it up to "
                        f"{padded} ({waste:.0f}% of the block's VMEM "
                        "and bandwidth is padding) — pad the dim in the "
                        "wrapper or fold it into a tiled axis",
                        symbol=f"{model.name}:{b.kind}{b.index}:{axis}",
                    ))
        return out


_FLOORDIV_RE = re.compile(r"^(.+?)\s*//\s*(.+)$")


def _covered_extent(comp, block_dim, grid):
    """The extent a map component × block dim provably covers, as
    ``(kind, value)``: ("int", n) when concrete, ("sym", expr) when the
    ``(A // B) · B`` pattern telescopes to exactly ``A`` or the block covers
    one symbolic stride, plus a ("sym_over", expr) variant for a positive
    constant offset PAST that proven-exact cover. None = unprovable."""
    bd_int = _dim_value(block_dim, {}) if not isinstance(block_dim, int) \
        else block_dim

    def scaled(grid_extent, offset_blocks):
        g_int = grid_extent if isinstance(grid_extent, int) else None
        if g_int is not None and bd_int is not None:
            return ("int", (g_int + offset_blocks) * bd_int)
        if isinstance(grid_extent, str):
            m = _FLOORDIV_RE.match(grid_extent)
            if m:
                a, b_expr = m.group(1).strip(), m.group(2).strip()
                if str(block_dim) == b_expr:
                    # (A // B) blocks of B rows cover at most A rows
                    if offset_blocks == 0:
                        return ("sym", a)
                    return ("sym_over", a)
            if bd_int == 1 and offset_blocks == 0:
                return ("sym", grid_extent)
        return None

    if comp[0] == "const":
        if bd_int is not None:
            return ("int", (comp[1] + 1) * bd_int)
        if comp[1] == 0:
            return ("sym", str(block_dim))
        return None
    if comp[0] == "grid" and comp[1] < len(grid):
        return scaled(grid[comp[1]], 0)
    if comp[0] == "grid+" and comp[1] < len(grid):
        res = scaled(grid[comp[1]], comp[2])
        if res and res[0] == "int":
            return res
        if res and res[0] == "sym":
            return ("sym_over", res[1])
        return res
    return None


class KernelIndexBoundsChecker:
    id = BOUNDS_ID
    version = 1

    def check(self, project) -> list:
        out = []
        for model in kernel_models(project):
            for b in (*model.inputs, *model.outputs):
                if not (b.shape and b.index_map):
                    continue
                operand_shape = _operand_shape(model, b)
                if operand_shape is None:
                    continue
                for d, comp in enumerate(b.index_map):
                    if d >= len(b.shape) or d >= len(operand_shape):
                        break
                    cover = _covered_extent(comp, b.shape[d], model.grid)
                    if cover is None:
                        continue
                    od = operand_shape[d]
                    od_int = od if isinstance(od, int) else _dim_value(od, {})
                    kind, val = cover
                    oob = None
                    if kind == "int" and od_int is not None:
                        if val > od_int:
                            oob = f"{val} > {od_int}"
                    elif kind == "sym_over" and str(od) == str(val):
                        oob = (f"at least one block past the `{val}` extent "
                               "(positive index-map offset)")
                    if oob:
                        out.append(model.fctx.finding(
                            BOUNDS_ID, b.spec_node,
                            f"kernel `{model.name}`: dim {d} of the "
                            f"{b.kind}[{b.index}] block reaches "
                            f"{oob} past operand `{b.label}` over the grid "
                            f"({'×'.join(str(g) for g in model.grid)}) — an "
                            "out-of-bounds read/write that interpret mode "
                            "clamps but real hardware does not",
                            symbol=f"{model.name}:{b.kind}{b.index}:d{d}",
                        ))
        return out


class KernelAliasDisciplineChecker:
    id = ALIAS_ID
    version = 1

    def check(self, project) -> list:
        out = []
        for model in kernel_models(project):
            shape_of = model.senv.get("__shape_of__")
            aliased_outs = set(model.aliases.values())
            # -- alias shape/dtype agreement -------------------------------
            for in_pos, out_idx in model.aliases.items():
                if out_idx >= len(model.out_shapes):
                    continue
                o_shape, o_dtype = model.out_shapes[out_idx]
                if in_pos >= len(model.operands):
                    continue
                operand = model.operands[in_pos]
                i_shape = shape_of(operand) if shape_of else None
                label = ast.unparse(operand)[:40]
                if (i_shape is not None and o_shape is not None
                        and tuple(map(str, i_shape)) != tuple(map(str, o_shape))):
                    out.append(model.fctx.finding(
                        ALIAS_ID, model.call,
                        f"kernel `{model.name}`: input_output_aliases donates "
                        f"`{label}` ({'×'.join(map(str, i_shape))}) to output "
                        f"{out_idx} ({'×'.join(map(str, o_shape))}) — aliased "
                        "buffers must agree exactly; a mismatch is silent "
                        "memory corruption on chip",
                        symbol=f"{model.name}:alias{in_pos}:shape",
                    ))
                i_dtype = _operand_dtype(model.fctx, model.enclosing, operand)
                if i_dtype and o_dtype and i_dtype != o_dtype:
                    out.append(model.fctx.finding(
                        ALIAS_ID, model.call,
                        f"kernel `{model.name}`: input_output_aliases donates "
                        f"`{label}` ({i_dtype}) to output {out_idx} "
                        f"({o_dtype}) — dtype-mismatched aliasing "
                        "reinterprets bytes",
                        symbol=f"{model.name}:alias{in_pos}:dtype",
                    ))
            # -- revisited outputs need donated or in-kernel init ----------
            for b in model.outputs:
                if b.space != "vmem" or not b.revisits_across_grid(model.grid):
                    continue
                if b.index in aliased_outs:
                    continue
                pname = kernel_param_name(model, "out", b.index)
                if kernel_zeroes_param(model, pname):
                    continue
                out.append(model.fctx.finding(
                    ALIAS_ID, b.spec_node,
                    f"kernel `{model.name}`: output {b.index}'s block is "
                    "revisited across grid steps but is neither "
                    "alias-donated (input_output_aliases) nor zero-"
                    "initialized inside the kernel (pl.when first-visit "
                    "store) — on chip the first accumulation reads whatever "
                    "VMEM held, while interpret mode shows clean zeros (the "
                    "accumulator-race class)",
                    symbol=f"{model.name}:out{b.index}:init",
                ))
        return out


class KernelInterpretDefaultChecker:
    id = INTERPRET_ID
    version = 1

    def check(self, project) -> list:
        out = []
        # functions that thread a caller-decided interpret-carrying
        # parameter (whatever it is NAMED) into a pallas_call — directly,
        # or through another threading function — mapped key -> that
        # parameter's name. A default of True anywhere on the chain
        # silently emulates on TPU.
        threading: dict = {}
        for model in kernel_models(project):
            if model.interpret is None:
                continue
            kind, val = model.interpret
            if kind == "literal" and val is True:
                out.append(model.fctx.finding(
                    INTERPRET_ID, model.call,
                    f"kernel `{model.name}`: hard-coded interpret=True — on "
                    "TPU this silently EMULATES the kernel at Python speed "
                    "instead of compiling it; thread the caller's platform "
                    "decision (interpret=<param>), taken from the operand's "
                    "device (pallas_kernels.on_tpu)",
                    symbol=f"{model.name}:interpret:literal",
                ))
            elif kind == "param" and model.enclosing is not None:
                key = (model.fctx.relpath,
                       model.fctx.qualname_of.get(model.enclosing))
                threading[key] = val

        def param_default(fn, name):
            a = fn.args
            pos = a.posonlyargs + a.args
            defaults = [None] * (len(pos) - len(a.defaults)) + list(a.defaults)
            for p, d in zip(pos, defaults):
                if p.arg == name:
                    return d
            for p, d in zip(a.kwonlyargs, a.kw_defaults):
                if p.arg == name:
                    return d
            return None

        graph = project.call_graph()
        # the FileContext walk already indexed every keyword-bearing call
        # under each enclosing function; the fixpoint rounds below then
        # only touch calls whose callee name matches a known threading
        # function
        kwcalls_by_fn: "dict | None" = None
        params_by_fn: dict = {}

        def _index_calls():
            calls_by_fn: dict = {}
            for key, (fctx, fn) in graph.functions.items():
                a = fn.args
                params = {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs}
                if not params:
                    continue
                entries = []
                for node in fctx.kw_calls_by_qual.get(key[1], ()):
                    if isinstance(node.func, ast.Name):
                        entries.append((node.func.id, node))
                    elif isinstance(node.func, ast.Attribute):
                        entries.append((node.func.attr, node))
                if entries:
                    params_by_fn[key] = params
                    calls_by_fn[key] = entries
            return calls_by_fn

        for _ in range(3):  # close over wrapper-of-wrapper chains
            grew = False
            # the callee's threading param arrives as the kwarg of the
            # same name; whichever of MY params feeds it makes me a
            # threading function under MY param's name
            tp_by_name: dict = {}
            for (_, qual), pname in threading.items():
                if qual:
                    tp_by_name.setdefault(qual.split(".")[-1], set()).add(pname)
            if not tp_by_name:
                break
            if kwcalls_by_fn is None:
                kwcalls_by_fn = _index_calls()
            for key, entries in kwcalls_by_fn.items():
                if key in threading:
                    continue
                params = params_by_fn[key]
                for callee_name, node in entries:
                    tp_names = tp_by_name.get(callee_name)
                    if not tp_names:
                        continue
                    mine = None
                    for kw in node.keywords:
                        if kw.arg not in tp_names:
                            continue
                        fed = sorted(
                            x.id for x in ast.walk(kw.value)
                            if isinstance(x, ast.Name) and x.id in params
                        )
                        if fed:
                            # prefer a same-named param; else deterministic
                            mine = kw.arg if kw.arg in fed else fed[0]
                            break
                    if mine is not None:
                        threading[key] = mine
                        grew = True
                        break
            if not grew:
                break

        for key, pname in threading.items():
            fctx, fn = graph.functions.get(key, (None, None))
            if fn is None:
                continue
            default = param_default(fn, pname)
            if (isinstance(default, ast.Constant) and default.value is True):
                out.append(fctx.finding(
                    INTERPRET_ID, fn,
                    f"`{key[1]}` threads `{pname}` into a Pallas kernel's "
                    "interpret flag but DEFAULTS it to True — every caller "
                    "that forgets the flag emulates the kernel on TPU at "
                    "Python speed, silently; make the flag required, or "
                    "default to None and resolve from the operand's device "
                    "(pallas_kernels.on_tpu)",
                    symbol=f"{key[1]}:interpret:default",
                ))
        return out
