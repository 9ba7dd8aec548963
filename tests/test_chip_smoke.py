"""The chip smoke, on the CPU (ISSUE 21).

``chip_smoke.py`` is the quickest proof that the ALS lambda loop starts on
a TPU; the driver runs it there. Tier-1 cannot, so it keeps the two halves
of that proof that need no chip:

  * the smoke's BODY in its explicit tiny CPU mode — the same phases, the
    same result and no-fallback checks, a few thousand interactions, kernels
    interpreted — passes; and fails, with the reason, when
    ``ALSUpdate.build_model`` is made to raise (the candidate is skipped,
    nothing is published, every layer exits clean: exactly the failure a
    first chip run would otherwise not show);
  * every Pallas kernel and the TPU-default trainer half-iteration LOWER for
    ``("tpu",)`` at k=50, k=250 and the gather kernel's gate k=256, so a
    block shape the TPU lowering refuses — what stopped the trainer at the
    parent of this PR — fails here without a chip.

Each smoke run is a fresh process with ``JAX_ENABLE_X64`` removed: the
program runs with it off and the chip has no float64, whatever conftest
does for the rest of the suite.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_RUN = """
import sys
import chip_smoke
{patch}
sys.exit(chip_smoke.main(["--tiny-cpu"]))
"""

_PATCH = """
from oryx_tpu.models.als.update import ALSUpdate

def _refuse(self, *args, **kwargs):
    raise RuntimeError("planted: the TPU lowering refused a block shape")

ALSUpdate.build_model = _refuse
"""


def _smoke(patch: str = "", args: "list | None" = None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_ENABLE_X64", None)
    env.pop("XLA_FLAGS", None)  # one device, like one chip
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    cmd = ([sys.executable, os.path.join(REPO, "chip_smoke.py"), *args]
           if args is not None
           else [sys.executable, "-c", _RUN.format(patch=patch)])
    return subprocess.run(cmd, capture_output=True, text=True, timeout=180,
                          env=env, cwd=REPO)


def test_tiny_mode_passes_every_check_and_names_the_cpu():
    proc = _smoke()
    assert proc.returncode == 0, proc.stderr[-3000:]
    out_lines = proc.stdout.strip().splitlines()
    # the driver reads the LAST line and refuses any key beyond these
    assert json.loads(out_lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    summary = json.loads(out_lines[-2])
    assert summary["ok"] is True and summary["mode"] == "tiny-cpu"
    assert summary["device"] == json.loads(out_lines[-1])["device"]
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    loop = summary["loop"]
    # results, not status codes
    assert loop["generation_auc"] > 0.75 and loop["auc_published"] > 0.75
    assert abs(loop["auc_published"] - loop["auc_reference"]) <= 0.05
    assert loop["recommend"]["requests"] >= 32
    assert loop["recommend"]["top10_overlap_mean"] >= 0.8
    assert loop["fold_in"]["new_item_excluded"] is True
    # the coalescer really coalesced; the warm ladder really finished
    assert loop["coalescer"]["requests"] > loop["coalescer"]["flushes"] > 0
    assert loop["warmup"]["done"] == loop["warmup"]["total"] > 0
    # the programs a TPU trains with: the SPD kernel on both sides, and at
    # this size (two tiny opposite tables) the gate's einsum on both, rows
    # gathered as wide as they are; the padded gather ran beside them
    for side in ("user", "item"):
        chosen = loop["formulation"][side]
        assert chosen["runs"] == "einsum" and ", under the " in chosen["why"]
        assert chosen["gather_width"] == summary["input"]["features"]
        assert chosen["tpu_custom_calls"] == 1
    assert loop["padded_half_iteration_error"] < 1e-5
    assert set(summary["kernels"]) == {
        "gather_gramian/float32", "gather_gramian/bfloat16", "spd_solve",
        "kmeans",
    }
    # set-up is reported as set-up; no rate, utilization or roofline figure
    flat = json.dumps(summary)
    assert not any(w in flat for w in ("qps", "mfu", "per_s", "roofline"))


def test_skipped_candidate_fails_the_smoke_with_its_reason():
    """``build_model`` raises → MLUpdate logs "candidate failed to build",
    returns None, logs "unable to build any model" and the generation
    SUCCEEDS with nothing published. The smoke must call that a failure."""
    proc = _smoke(patch=_PATCH)
    assert proc.returncode != 0
    assert "chip_smoke FAILED" in proc.stderr
    assert "candidate 0 failed to build" in proc.stderr
    assert "planted: the TPU lowering refused a block shape" in proc.stderr
    # no result line: nothing on stdout parses as an ok summary
    assert '"ok"' not in proc.stdout


def test_full_mode_refuses_a_cpu_and_says_why():
    proc = _smoke(args=[])
    assert proc.returncode != 0 and '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr and "JAX_PLATFORMS='cpu'" in proc.stderr


# ---------------------------------------------------------------------------
# cross-lowering for the TPU: free, and it already failed at the parent
# ---------------------------------------------------------------------------


def _lower_tpu(fn, *args) -> str:
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.fixture
def _program_dtypes():
    """conftest turns 64-bit mode on for the suite; the program — and the
    chip, which has no float64 — run with it off. Lower as the program
    does: under x64 every Python float literal in a kernel body becomes a
    float64 constant Mosaic cannot cast."""
    with jax.enable_x64(False):
        yield


@pytest.mark.parametrize("k", [50, 250, 256])
def test_every_kernel_and_the_tpu_half_iteration_lower_for_tpu(
        k, _program_dtypes):
    from oryx_tpu.models.als import train as tr
    from oryx_tpu.ops import pallas_kernels as pk

    f32 = jnp.float32
    for dtype in (jnp.float32, jnp.bfloat16):
        # the pack's narrowest slot, one below the copy loops' trip, the
        # Netflix cell's two widths; k = 256 at T = 512 is the gate itself,
        # where the two gather buffers are largest
        for t in (8, 128, 256, 512):
            s, block = 64, 32
            text = _lower_tpu(
                lambda y, sr, sl, sc, w, c: pk.gather_gramian_accumulate(
                    y, sr, sl, sc, w, c, block=block, interpret=False),
                jnp.zeros((1000, k), dtype), jnp.zeros((s,), jnp.int32),
                jnp.zeros((s,), jnp.int32), jnp.zeros((s, t), jnp.int32),
                jnp.zeros((s, t), f32), jnp.zeros((s, t), f32))
            assert text.count("tpu_custom_call") == 1, (k, dtype, t)

    text = _lower_tpu(
        lambda a, b: pk.spd_solve_batched(a, b, interpret=False),
        jnp.zeros((1000, k, k), f32), jnp.zeros((1000, k), f32))
    assert text.count("tpu_custom_call") == 1

    text = _lower_tpu(
        lambda p, w, c: pk.kmeans_assign_accumulate(p, w, c, interpret=False),
        jnp.zeros((5000, k), f32), jnp.ones((5000,), f32),
        jnp.zeros((20, k), f32))
    assert text.count("tpu_custom_call") == 1

    # what als_train picks on a TPU, resolved by the trainer's own gate at
    # this width and slot count: the SPD kernel always; against the Netflix
    # user table (480,201 rows) the einsum over rows padded to 64 columns at
    # 50 features and the gather-Gramian kernel at 250 and 256; against a
    # 2,000-row table the einsum at 50 features (a small table of narrow
    # rows, gathered as they are) and the kernel at 250 and 256
    n_blocks, block, s, t = 2, 512, 1024, 32
    for table_rows, fused, width in ((480201, k >= 250, max(k, 64)),
                                     (2000, k >= 250, k)):
        assert tr._resolve_fused(None, True, k, s, table_rows) \
            == (fused, width)
        text = tr._solve_side_blocked_jit.trace(
            jnp.zeros((2000, k), f32), jnp.zeros((n_blocks, s), jnp.int32),
            jnp.zeros((n_blocks, s, t), jnp.int32),
            jnp.zeros((n_blocks, s, t), f32),
            jnp.zeros((n_blocks, s), jnp.int32), 0.01, 1.0, block=block,
            features=k, implicit=True, slot_chunk=s, dtype="float32",
            spd_kernel=True, fused_gramian=fused, kernel_interpret=False,
            gather_width=width,
        ).lower(lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == 1 + fused
        # the einsum's gather reads rows of the gate's width
        assert (f"slice_sizes = array<i64: 1, {width}>" in text) \
            is (not fused), (k, table_rows)


# ---------------------------------------------------------------------------
# one step further, still without a chip: XLA:TPU + Mosaic, compile-only
# ---------------------------------------------------------------------------

_COMPILE_ONLY = """
import functools, os, re, sys
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
except Exception as e:
    print("SKIP", type(e).__name__, e)
    sys.exit(0)
from oryx_tpu.models.als import train as tr

sharding = SingleDeviceSharding(topo.devices[0])
def spec(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

def half(k, n_blocks, block, s, t, dtype, table_rows=20000, fused=True,
         chunk=None, width=None):
    fn = functools.partial(
        tr._solve_side_blocked_jit.__wrapped__, block=block, features=k,
        implicit=True, slot_chunk=chunk or s, dtype=dtype, spd_kernel=True,
        fused_gramian=fused, kernel_interpret=False, gather_width=width)
    return jax.jit(lambda y, a, b, c, d: fn(y, a, b, c, d, 0.01, 1.0)).lower(
        spec((table_rows, k)), spec((n_blocks, s), jnp.int32),
        spec((n_blocks, s, t), jnp.int32), spec((n_blocks, s, t)),
        spec((n_blocks, s), jnp.int32)).compile()

# the smoke's own user side (13 blocks of 7693 rows, T=32): a batch large
# enough that the SPD kernel's scoped VMEM is what production allocates
half(50, 13, 7693, 10240, 32, "float32")
# both kernels' last supported width, at the widest slot: the gather
# kernel's two (512, 1, 256) buffers and both accumulator blocks — the
# footprint the resident budget is ratified at
half(256, 2, 1000, 2048, 512, "bfloat16")
half(256, 2, 1000, 2048, 512, "float32")
# the Netflix cell's two sides as the pack shapes them, each in the
# formulation the trainer's gate picks for it from the opposite table it
# gathers: the item side's 72,594 slots a block of T=512 against the user
# table — the einsum in 111 chunks of 654 slots over that table zero-padded
# to 64 columns: ONE kernel call in the program (the SPD solve's), and in the
# OPTIMISED program a pad to 64 columns and gathers of (655, 512, 64) out of
# it — a compiler that folded pad, gather and slice back into a gather of
# 50-column rows (the slow one: PERF.md section 6, PR 31) fails here; the
# user side's T=256 against the item table — the einsum as it was, in ten
# chunks of 1,180 slots, gathering rows of 50. Then each side under the
# kernel all the same (a forced formulation still has to compile: owner rows
# AND slot lengths whole in SMEM at 72,594 slots), 250 features (a 1 KB row
# a copy) at both widths, and the slot gate itself at the widest slot
for s, table_rows, answer in ((72594, 59 * 8139, (False, 64)),
                              (11800, 3 * 5924, (False, 50))):
    assert tr._resolve_fused(None, True, 50, s, table_rows) == answer
kernels = lambda text: text.count("tpu_custom_call")
gathered = lambda text: set(re.findall(
    r"= f32\\[(\\d+),\\d+,(\\d+)\\]\\S* gather\\(", text))
item = half(50, 3, 5924, 72594, 512, "float32", 59 * 8139, False, chunk=654,
            width=64).as_text()
# 655 slots a gather, not the chunk's 654: 654 x 512 rows is a multiple of
# 1,024, which XLA:TPU stages through half the buffer (train._GATHER_HALVED_
# ROWS); the staging buffer of every gather fusion is the whole one
assert kernels(item) == 1 and gathered(item) == {("655", "64")}, gathered(item)
assert re.search(r"= f32\\[480201,64\\]\\S* pad\\(", item)
staged = lambda text: set(re.findall(
    r"kind=kCustom.*op_name=\\"[^\\"]*/gather\\".*\\"size\\":\\"(\\d+)\\"", text))
assert staged(item) == {"524288"}, staged(item)
user = half(50, 2, 8139, 11800, 256, "float32", 3 * 5924, False,
            chunk=1180).as_text()
assert kernels(user) == 1 and gathered(user) == {("1181", "50")}, gathered(user)
assert staged(user) == {"262144"}, staged(user)
assert kernels(half(50, 3, 5924, 72594, 512, "float32", 59 * 8139,
                    True).as_text()) == 2
half(50, 2, 8139, 11790, 256, "float32")
half(250, 2, 1046, 12288, 512, "float32")
half(250, 2, 1072, 2048, 256, "bfloat16")
from oryx_tpu.ops import pallas_kernels as pk
half(50, 1, 5924, pk._GG_MAX_SLOTS, 512, "float32")
print("COMPILED")
"""


def test_tpu_compiler_accepts_the_trainer_compile_only():
    """Where libtpu is installed, jax can build a v5e topology with no chip
    attached and run XLA:TPU and Mosaic against it. This is how the SPD
    kernel's scoped-VMEM overrun (16.62 MiB of 16 at k=50, every production
    batch size) and the gather kernel's sub-tile DMA slices were found
    before any chip time was spent. Skips where no topology can be built."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_ENABLE_X64", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _COMPILE_ONLY],
                          capture_output=True, text=True, timeout=240,
                          env=env, cwd=REPO)
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    if last.startswith("SKIP"):
        pytest.skip(f"no compile-only TPU topology here: {last}")
    assert proc.returncode == 0 and last == "COMPILED", proc.stderr[-3000:]


_INT8_SCAN_COMPILE_ONLY = """
import os, re, sys
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
except Exception as e:
    print("SKIP", type(e).__name__, e)
    sys.exit(0)
from oryx_tpu.models.als import serving as S

sharding = SingleDeviceSharding(topo.devices[0])
def spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

# the reference's largest row as one chip holds it (ISSUE 33): 20M x 250
# int8 rows and a float32 scale a row, 64 candidates a query
n, k = 20_000_000, 250
for batch in (256, 1):
    compiled = S._quant_candidates.lower(
        spec((n, k), jnp.int8), spec((n,), jnp.float32),
        spec((batch, k), jnp.float32), None, k=64).compile()
    memory = compiled.memory_analysis()
    # the rows and their scales are the operands, nothing else of their size
    assert 5.1e9 < memory.argument_size_in_bytes < 5.3e9, memory
    # no (batch, n) float32 score matrix (20.5 GB at 256, which no chip
    # holds) and no converted copy of the rows: at one query the scores are
    # a vector of 80 MB, as in the bfloat16 scan
    assert memory.temp_size_in_bytes <= n * 4 + (1 << 20), (batch, memory)
    entry = compiled.as_text().split("ENTRY ", 1)[1]
    wide = set(re.findall(r"= \\(?(?:f32|bf16|f16)\\[(?:\\d+,)?20000000(?:,\\d+)?\\]", entry))
    assert not {w for w in wide if ",20000000]" in w and "[1," not in w}, wide
    assert not {w for w in wide if "[20000000,250]" in w}, wide
    if batch == 256:
        assert memory.temp_size_in_bytes < (1 << 20), memory
print("COMPILED")
"""


def test_tpu_compiler_fuses_the_int8_scan_at_the_largest_row_compile_only():
    """The int8 scan at 20M x 250 for a described v5e: the top-k is fused
    behind the matmul at the widest warmed batch (the per-row scale between
    the two does not break it) and the convert rides the matmul's operand,
    so the program's only large buffers are its operands (PERF.md section
    6, PR 33). A compiler, or a change to the scan, that materialises the
    scores or a float copy of the rows fails here, before any chip time."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_ENABLE_X64", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _INT8_SCAN_COMPILE_ONLY],
                          capture_output=True, text=True, timeout=240,
                          env=env, cwd=REPO)
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    if last.startswith("SKIP"):
        pytest.skip(f"no compile-only TPU topology here: {last}")
    assert proc.returncode == 0 and last == "COMPILED", proc.stderr[-3000:]
