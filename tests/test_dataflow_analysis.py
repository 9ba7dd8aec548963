"""Dataflow analysis (ISSUE 14): fixture pairs for the three sharding/dtype
checkers, the ``--cost`` static roofline (pinned against hand-computed ALS
half-iteration bytes), SARIF output, baseline checker-versioning, and the
analyzer-runtime perf gate.

Everything here is pure AST — fixtures are parsed, never imported or traced.
"""

from __future__ import annotations

import gc
import json
import os
import textwrap
import time

import pytest

import oryx_tpu
from oryx_tpu.tools.analyze import analyze_project, analyze_source
from oryx_tpu.tools.analyze.core import build_project, write_baseline

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(oryx_tpu.__file__)))
BASELINE = os.path.join(REPO_ROOT, "conf", "analyze-baseline.json")


def _run(src: str, checker: str, **kw):
    findings = analyze_source(textwrap.dedent(src), **kw)
    return [f for f in findings if f.checker == checker]


# ---------------------------------------------------------------------------
# replicated-collective
# ---------------------------------------------------------------------------


_TRAIN_SHAPED = """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    def _solver(mesh, axis):
        def local(y, scols, svals):
            yty = y.T @ y
            ys = y.astype(jnp.bfloat16)
            yg = ys[scols]                      # gathered by data indices
            return jnp.einsum("st,sti->si", svals, yg)

        specs = dict(
            mesh=mesh,
            in_specs=(P(), P(axis), P(axis)),   # y fully replicated
            out_specs=P(axis),
        )
        return jax.jit(shard_map(local, check_vma=False, **specs))
"""


def test_replicated_collective_fires_on_train_shaped_region():
    """The ROADMAP item-5(a) shape: a factor table entering shard_map via
    ``P()`` while the wrapped program gathers it by data indices — with the
    estimated all-gather bytes in the message (resolved through a
    ``**specs`` dict, the idiom train.py uses)."""
    hits = _run(_TRAIN_SHAPED, "replicated-collective")
    assert len(hits) == 1
    f = hits[0]
    assert f.symbol == "_solver.local:y"
    assert "4·y.d0·y.d1" in f.message and "all-gather" in f.message


def test_replicated_collective_quiet_on_batch_replication():
    """The serving scan's clean shape: the model-scaled table is SHARDED;
    the replicated operands are batch-shaped (queries/masks, matmul'd and
    masked but never data-gathered) — deliberate small broadcasts."""
    hits = _run(
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        def _topk(mesh, axis):
            def local(mat, qs, excl):
                scores = jnp.matmul(qs, mat.T)
                scores = jnp.where(excl >= 0, -jnp.inf, scores)
                return jax.lax.top_k(scores, 8)

            return shard_map(
                local, mesh=mesh,
                in_specs=(P(axis, None), P(None, None), P(None, None)),
                out_specs=(P(None, axis), P(None, axis)),
            )
        """,
        "replicated-collective",
    )
    assert hits == []


def test_replicated_collective_fires_on_closure_capture():
    """A device array captured by the wrapped function enters the region
    replicated with no in_spec line to review."""
    hits = _run(
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        def build(mesh, axis, table_np):
            table = jnp.asarray(table_np)

            def local(idx):
                return table[idx]

            return shard_map(local, mesh=mesh, in_specs=(P(axis),),
                             out_specs=P(axis))
        """,
        "replicated-collective",
    )
    assert len(hits) == 1
    assert hits[0].symbol == "build.local:capture:table"
    assert "closure-captured" in hits[0].message


# ---------------------------------------------------------------------------
# host-device-transfer
# ---------------------------------------------------------------------------


def test_host_transfer_fires_in_async_handler_and_through_calls():
    hits = _run(
        """
        import asyncio
        import jax.numpy as jnp
        import numpy as np

        async def handler(request, xs):
            scores = jnp.dot(xs, xs)
            return np.asarray(scores)        # fetch ON the event loop

        def helper(xs):
            s = jnp.sum(xs)
            return float(s)

        async def handler2(request, xs):
            return helper(xs)                # reachable: helper's sync fires
        """,
        "host-device-transfer",
    )
    assert len(hits) == 2
    assert {f.symbol.split(":")[0] for f in hits} == {"handler", "helper"}
    assert all("event loop" in f.message for f in hits)


def test_host_transfer_quiet_on_to_thread_hop():
    """The sanctioned escape: a callable handed to ``asyncio.to_thread`` is
    a reference, not a call — its syncs run on a worker thread."""
    hits = _run(
        """
        import asyncio
        import jax.numpy as jnp

        def helper(xs):
            s = jnp.sum(xs)
            return float(s)

        async def handler(request, xs):
            return await asyncio.to_thread(helper, xs)
        """,
        "host-device-transfer",
    )
    assert hits == []


def test_host_transfer_fires_in_training_loop_and_exempts_device_get():
    """Inside a trainer module's loop a silent per-iteration ``np.asarray``
    fires; the explicit batched ``jax.device_get`` (the fix the rdf level
    loop now uses) stays quiet."""
    src = """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def grow(levels):
            assign = jnp.zeros((8,))
            for depth in range(10):
                gain, feat = step(assign)
                g = np.asarray(gain)           # silent sync per level
                levels.append(g)
            return levels

        def grow_fixed(levels):
            assign = jnp.zeros((8,))
            for depth in range(10):
                gain, feat = step(assign)
                g, f = jax.device_get((gain, feat))   # explicit + batched
                levels.append(g)
            return levels

        @jax.jit
        def step(assign):
            return assign * 2, assign + 1
        """
    hits = _run(src, "host-device-transfer",
                filename="oryx_tpu/models/fake/train.py")
    assert len(hits) == 1
    assert hits[0].symbol.startswith("grow:")
    assert "training-tier loop" in hits[0].message


def test_host_transfer_fires_per_element_sync_and_quiet_when_batched():
    """The death-by-a-thousand-syncs shape the first whole-program run found
    in the similarity/because handlers (one float() per pair) — and the
    batched fix: one device call, one transfer, host-side float loop."""
    violation = """
        import jax.numpy as jnp
        import numpy as np

        def pair_sim(x, y):
            return jnp.dot(x, y)

        def collect(vecs, q):
            return [float(pair_sim(v, q)) for v in vecs]
    """
    hits = _run(violation, "host-device-transfer",
                filename="oryx_tpu/serving/fixture.py")
    assert len(hits) == 1 and "PER ITEM" in hits[0].message

    batched = """
        import jax.numpy as jnp
        import numpy as np

        def batch_sims(rows, q):
            return jnp.asarray(rows) @ jnp.asarray(q)

        def collect(vecs, q):
            sims = np.asarray(batch_sims(np.stack(vecs), q))
            return [float(s) for s in sims]     # host floats: free
    """
    assert _run(batched, "host-device-transfer",
                filename="oryx_tpu/serving/fixture.py") == []


def test_host_transfer_loop_targets_bind_iterated_elements():
    """Loop/comprehension targets bind one ELEMENT of their iterable
    (review finding, both directions): iterating a device array per
    element is the headline sync class and must fire, while a host
    comprehension variable shadowing an earlier device name must not."""
    fires = """
        import jax.numpy as jnp

        def drain(x):
            scores = jnp.dot(x, x)
            out = []
            for s in scores:
                out.append(float(s))   # one transfer PER ELEMENT
            return out
        """
    hits = _run(fires, "host-device-transfer",
                filename="oryx_tpu/serving/fixture.py")
    assert len(hits) == 1 and "float" in hits[0].symbol

    shadowed = """
        import jax.numpy as jnp

        def shadow(x, hostvals):
            v = jnp.dot(x, x)
            keep = v
            return [float(v) for v in hostvals]   # comp v is HOST
        """
    assert _run(shadowed, "host-device-transfer",
                filename="oryx_tpu/serving/fixture.py") == []


def test_host_transfer_augassign_keeps_device_state():
    """`loss += 1` must not downgrade a device name to host (review
    finding: only the RHS used to be classified) — the per-iteration
    float() sync after it stays visible."""
    src = """
        import jax
        import jax.numpy as jnp

        def train_loop(n):
            loss = jnp.zeros(())
            out = []
            for i in range(n):
                loss += 1
                out.append(float(loss))   # still a device sync per step
            return out
        """
    hits = _run(src, "host-device-transfer",
                filename="oryx_tpu/models/fake/train.py")
    assert len(hits) == 1 and "float" in hits[0].symbol


def test_host_transfer_quiet_in_loop_else_blocks():
    """A ``for``/``while`` ``else:`` arm runs at most once per loop — a
    transfer there is NOT a per-iteration sync (review finding: orelse used
    to inherit the loop context)."""
    src = """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def train_once(n):
            y = jnp.zeros((4,))
            for i in range(n):
                y = y * 2
            else:
                total = np.asarray(y)   # once, after the loop: quiet
            return total
        """
    assert _run(src, "host-device-transfer",
                filename="oryx_tpu/models/fake/train.py") == []


def test_host_transfer_flow_sensitive_after_host_reassignment():
    """The widening-retry idiom: once ``vals = np.asarray(vals)`` lands,
    later scalar reads are host-side and must stay quiet — but that
    asarray call itself still sees the device value."""
    src = """
        import jax.numpy as jnp
        import numpy as np

        async def handler(request, xs):
            vals = jnp.dot(xs, xs)
            vals = np.asarray(vals)          # the one (flagged) transfer
            return [float(v) for v in vals]  # host reads: quiet
        """
    hits = _run(src, "host-device-transfer")
    assert len(hits) == 1
    assert "np.asarray" in hits[0].symbol


# ---------------------------------------------------------------------------
# dtype-widening
# ---------------------------------------------------------------------------


def test_dtype_widening_fires_on_implicit_bf16_f32_mixing():
    hits = _run(
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def scan(q, table):
            t = table.astype(jnp.bfloat16)
            w = jnp.zeros((4,))              # f32 by default
            return t * w                     # silent widening to f32

        @jax.jit
        def mix(q, table):
            qq = table.astype(jnp.int8)
            f = jnp.ones((4,))
            return jnp.matmul(f, qq)         # contraction, no p.e.t.
        """,
        "dtype-widening",
    )
    assert len(hits) == 2
    assert {f.symbol for f in hits} == {"scan:bfloat16", "mix:int8"}
    assert all("silently widens" in f.message for f in hits)


def test_dtype_widening_quiet_on_sanctioned_sites_and_explicit_forms():
    hits = _run(
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def rescore_exact(q, table):
            t = table.astype(jnp.bfloat16)
            w = jnp.zeros((4,))
            return t * w                     # sanctioned rescore site

        @jax.jit
        def scan_accum(q, table):
            t = table.astype(jnp.bfloat16)
            q32 = q.astype(jnp.float32)
            # f32 ACCUMULATION over narrow inputs: the TPU matmul recipe
            return jnp.matmul(q32, t, preferred_element_type=jnp.float32)

        @jax.jit
        def scan_explicit(q, table):
            t = table.astype(jnp.bfloat16)
            t32 = t.astype(jnp.float32)      # visible intent, not silent
            w = jnp.zeros((4,))
            return t32 * w
        """,
        "dtype-widening",
    )
    assert hits == []


def test_dtype_widening_is_flow_sensitive_on_late_narrowing():
    """The idiomatic compute-wide-then-store-narrow pattern: a value
    narrowed at the END of the scope must not retro-flag the earlier
    pure-f32 arithmetic (review finding: the final-state env resolved
    `acc` to bf16 on the f32+f32 line) — while a narrow-then-mix in the
    other order still fires."""
    hits = _run(
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def accum(q):
            w = jnp.ones((4,))
            acc = jnp.zeros((4,))
            acc = acc + w                  # f32 + f32 at this line: quiet
            acc = acc.astype(jnp.bfloat16) # narrowed only on the way out
            return acc

        @jax.jit
        def still_caught(q):
            w = jnp.ones((4,))
            acc = jnp.zeros((4,)).astype(jnp.bfloat16)
            acc = acc + w                  # bf16 + f32 HERE: fires
            return acc
        """,
        "dtype-widening",
    )
    assert len(hits) == 1 and hits[0].symbol == "still_caught:bfloat16"


# ---------------------------------------------------------------------------
# --cost: the static roofline
# ---------------------------------------------------------------------------


def test_cost_pins_concrete_matmul_and_einsum():
    """Hand-computed FLOPs/bytes for fully-concrete shapes: (128,64)@(64,32)
    = 2·128·64·32 FLOPs, and einsum('stk,stj->skj') = 2·s·t·k·j."""
    from oryx_tpu.tools.analyze.core import FileContext, ProjectContext
    from oryx_tpu.tools.analyze.dataflow import cost_report

    src = textwrap.dedent(
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def mm(w):
            a = jnp.zeros((128, 64))
            b = jnp.zeros((64, 32))
            return a @ b

        @jax.jit
        def ein(w):
            x = jnp.zeros((8, 16, 4))
            return jnp.einsum("stk,stj->skj", x, x)
        """
    )
    project = ProjectContext([FileContext("m.py", "m.py", src)])
    rows = {r["program"]: r for r in cost_report(project)}
    mm = rows["m.mm"]
    assert mm["flops"].evaluate({}) == 2 * 128 * 64 * 32
    assert mm["hbm_bytes"].evaluate({}) == (128 * 64 + 64 * 32) * 4
    ein = rows["m.ein"]
    assert ein["flops"].evaluate({}) == 2 * 8 * 16 * 4 * 4


def test_cost_prices_the_als_half_iteration_collective():
    """THE acceptance number: the sharded ALS half-iteration program shows
    nonzero collective bytes equal to the hand-computed N·k·4 all-gather of
    the replicated opposite factor (1M × 50f → 200 MB per call)."""
    from oryx_tpu.tools.analyze.dataflow import cost_report

    project, errors = build_project(
        [os.path.join(REPO_ROOT, "oryx_tpu", "models", "als", "train.py")],
        root=REPO_ROOT,
    )
    assert errors == []
    rows = [r for r in cost_report(project)
            if r["program"].endswith("_sharded_solver.local")]
    assert len(rows) == 1
    poly = rows[0]["collective_bytes"]
    n, k = 1_000_000, 50
    assert poly.evaluate({"y.d0": n, "y.d1": k}) == n * k * 4
    # and the Gramian + gather FLOPs are nonzero (the roofline has content)
    assert rows[0]["flops"].evaluate({"y.d0": n, "y.d1": k}) > 0


def test_cli_cost_json_renders_and_binds(capsys):
    from oryx_tpu.tools.analyze.cli import main

    rc = main(["--cost", "--format", "json",
               "--bind", "y.d0=1000000,y.d1=50"])
    assert rc == 0
    data = json.loads(capsys.readouterr().out)
    progs = {p["program"]: p for p in data["programs"]}
    als = progs["oryx_tpu.models.als.train._sharded_solver.local"]
    assert als["collective_bytes"]["value"] == 1_000_000 * 50 * 4
    assert als["collective_bytes"]["expr"] == "4·y.d0·y.d1"


def test_cli_cost_rejects_bad_bindings():
    from oryx_tpu.tools.analyze.cli import main

    with pytest.raises(SystemExit) as exc:
        main(["--cost", "--bind", "nonsense"])
    assert exc.value.code == 2


def test_cli_cost_refuses_findings_mode_flags(capsys):
    """--cost must reject findings-mode flags rather than silently ignore
    them (review finding: `--cost --changed` priced the whole project while
    the operator believed it was diff-scoped), and --bind without --cost is
    equally meaningless."""
    from oryx_tpu.tools.analyze.cli import main

    for flags in (["--cost", "--changed"],
                  ["--cost", "--update-baseline"],
                  ["--cost", "--checker", "dtype-widening"],
                  ["--cost", "--baseline", "b.json"],
                  ["--cost", "--no-baseline"],
                  ["--cost", "--format", "sarif"]):
        assert main(flags) == 2, flags
        assert "does not combine" in capsys.readouterr().err
    assert main(["--bind", "y.d0=5"]) == 2
    assert "--bind only applies to --cost" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# SARIF output
# ---------------------------------------------------------------------------


def test_sarif_renders_findings_with_suppressions(tmp_path):
    from oryx_tpu.tools.analyze.sarif import to_sarif

    d = str(tmp_path)
    with open(os.path.join(d, "m.py"), "w", encoding="utf-8") as fh:
        fh.write(textwrap.dedent(_TRAIN_SHAPED))
    result = analyze_project([d], root=d)
    doc = to_sarif(result)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "oryx-analyze"
    rules = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert "replicated-collective" in rules
    res = [r for r in run["results"]
           if r["ruleId"] == "replicated-collective"]
    assert len(res) == 1
    loc = res[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "m.py"
    assert loc["region"]["startLine"] > 1
    assert res[0]["level"] == "error" and "suppressions" not in res[0]


def test_cli_sarif_over_package_parses(capsys):
    from oryx_tpu.tools.analyze.cli import main

    rc = main(["--format", "sarif"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0  # repo is clean: everything suppressed
    results = doc["runs"][0]["results"]
    assert results, "baselined findings should still render as suppressed"
    assert all("suppressions" in r for r in results)
    assert all(r["level"] == "note" for r in results)


# ---------------------------------------------------------------------------
# baseline checker-versioning
# ---------------------------------------------------------------------------


def _write_fixture_project(d: str) -> None:
    with open(os.path.join(d, "m.py"), "w", encoding="utf-8") as fh:
        fh.write(textwrap.dedent(_TRAIN_SHAPED))


def test_baseline_version_mismatch_invalidates_loudly(tmp_path):
    """A checker precision upgrade must not silently re-accept an old
    justification: a version-mismatched entry leaves the finding
    unsuppressed AND raises a hygiene finding naming both versions."""
    d = str(tmp_path)
    _write_fixture_project(d)
    baseline = os.path.join(d, "baseline.json")
    entry = {
        "checker": "replicated-collective", "path": "m.py",
        "symbol": "_solver.local:y", "justification": "accepted",
        "version": 999,
    }
    with open(baseline, "w", encoding="utf-8") as fh:
        json.dump({"entries": [entry]}, fh)
    result = analyze_project([d], root=d, baseline_path=baseline)
    rep = [f for f in result.findings if f.checker == "replicated-collective"]
    assert rep and all(f.suppressed_by is None for f in rep)
    hygiene = [f for f in result.findings
               if f.checker == "suppression-hygiene" and "v999" in f.message]
    assert len(hygiene) == 1 and "now v1" in hygiene[0].message

    # matching version: suppressed, no hygiene noise
    entry["version"] = 1
    with open(baseline, "w", encoding="utf-8") as fh:
        json.dump({"entries": [entry]}, fh)
    result = analyze_project([d], root=d, baseline_path=baseline)
    rep = [f for f in result.findings if f.checker == "replicated-collective"]
    assert rep and all(f.suppressed_by == "baseline" for f in rep)
    assert not [f for f in result.findings
                if f.checker == "suppression-hygiene"]


def test_update_baseline_records_checker_version(tmp_path):
    d = str(tmp_path)
    _write_fixture_project(d)
    result = analyze_project([d], root=d)
    out = os.path.join(d, "baseline.json")
    write_baseline(out, result.findings)
    with open(out, "r", encoding="utf-8") as fh:
        entries = json.load(fh)["entries"]
    assert entries
    assert all(e["version"] == 1 for e in entries)
    assert any(e["checker"] == "replicated-collective" for e in entries)


# ---------------------------------------------------------------------------
# whole-repo gates
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def timed_project_analysis():
    """One timed full-package run shared by the gate tests below."""
    t0 = time.perf_counter()
    result = analyze_project(
        [os.path.join(REPO_ROOT, "oryx_tpu")],
        root=REPO_ROOT,
        baseline_path=BASELINE,
    )
    return result, time.perf_counter() - t0


def test_new_checkers_clean_at_head_with_train_allgather_baselined(
    timed_project_analysis,
):
    """Acceptance: zero unsuppressed findings across the three new checkers,
    with the known train.py replicated-y all-gather present and justified in
    the baseline (pointing at the ROADMAP item-5 routed-mesh fix)."""
    result, _ = timed_project_analysis
    new_ids = {"replicated-collective", "host-device-transfer",
               "dtype-widening"}
    open_findings = [f for f in result.unsuppressed if f.checker in new_ids]
    assert open_findings == [], "\n" + "\n".join(
        f.render() for f in open_findings
    )
    flagged = [f for f in result.suppressed
               if f.checker == "replicated-collective"
               and f.path == "oryx_tpu/models/als/train.py"
               and f.symbol == "_sharded_solver.local:y"]
    assert flagged, "the known all-gather must stay visible via the baseline"
    assert all("ROADMAP item 5" in f.justification for f in flagged)


def test_analyzer_runtime_under_three_seconds(timed_project_analysis):
    """The dataflow pass rides the memoized call graph — a full-package run
    (now 22 checkers with the Pallas kernel family and protocol-model-drift)
    must stay under the 3 s
    tier-1 budget (PR 10 measured ~1.8 s for 13). One retry absorbs
    transient CI load spikes."""
    _, elapsed = timed_project_analysis
    for _ in range(2):
        if elapsed <= 3.0:
            break
        # timeit discipline for the retries: a full-suite run reaches this
        # test with a 600-test heap, and the analyzer's AST allocation
        # storm triggers repeated full collections over objects that are
        # not the analyzer's — measure the analyzer, not the suite's
        # garbage
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            analyze_project(
                [os.path.join(REPO_ROOT, "oryx_tpu")],
                root=REPO_ROOT,
                baseline_path=BASELINE,
            )
            elapsed = min(elapsed, time.perf_counter() - t0)
        finally:
            gc.enable()
    assert elapsed <= 3.0, f"full-package analyze took {elapsed:.2f}s"
