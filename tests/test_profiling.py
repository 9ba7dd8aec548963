"""Device-performance attribution tests (common/profiling.py).

Covers: the cost registry's accounting against a hand-computed einsum FLOP
count (XLA's ``cost_analysis()`` on a compiled matmul), calls × per-call
cost multiplication into the process counters, windowed-rate/MFU/memory
gauges present in the Prometheus exposition and in ``snapshot()`` (what
``bench.py`` embeds), the shared one-at-a-time :class:`ProfileSession`
(busy refusal, owner-checked stop, overdue reclaim), ``POST /debug/profile``
(happy path, concurrent 409, auth-exemption parity with /metrics, input
validation), the StepTracer profiler-leak regression (early close finalizes
the capture; two tracers in one process no longer race ``start_trace``),
and ``trace_summary --history`` regression detection over committed fixture
BENCH files.
"""

from __future__ import annotations

import concurrent.futures as cf
import io
import os
import re
import time

import httpx
import numpy as np
import pytest

from oryx_tpu.common import config as cfg
from oryx_tpu.common import metrics as metrics_mod
from oryx_tpu.common import profiling
from oryx_tpu.common.tracing import StepTracer
from oryx_tpu.tools import trace_summary as ts

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _get(snap: dict, name: str, label: str = "", default=0.0):
    return snap.get(name, {}).get(label, default)


def _session_idle():
    """Hard guarantee between tests: nothing holds the process profiler."""
    profiling.profile_session().stop()
    assert not profiling.profile_session().busy()


# ---------------------------------------------------------------------------
# cost registry: hand-computed einsum FLOPs + calls × cost accounting
# ---------------------------------------------------------------------------


def test_aot_compile_registers_hand_computed_einsum_flops():
    """The sanctioned compile route must report the matmul's true cost: a
    (64,32)@(32,128) contraction is exactly 2·m·k·n FLOPs and moves
    (m·k + k·n + m·n)·4 bytes — both straight out of ``cost_analysis()``."""
    import jax

    from oryx_tpu.common import compilecache

    m, k, n = 64, 32, 128
    jitted = jax.jit(lambda a, b: a @ b)
    a = np.zeros((m, k), np.float32)
    b = np.zeros((k, n), np.float32)
    compiled = compilecache.aot_compile(jitted, a, b,
                                        cost_key="test.einsum_mkn")
    assert compiled is not None
    cost = profiling.costs().cost("test.einsum_mkn")
    assert cost is not None
    flops, bytes_ = cost
    assert flops == pytest.approx(2 * m * k * n, rel=0.05)
    assert bytes_ == pytest.approx((m * k + k * n + m * n) * 4, rel=0.05)


def test_record_multiplies_calls_by_registered_cost():
    reg = profiling.CostRegistry(window_sec=60.0)
    reg.register("test.prog_a", 100.0, 10.0)
    snap0 = metrics_mod.default_registry().snapshot()
    reg.record("test.prog_a", calls=3)
    reg.record("test.prog_a")
    snap1 = metrics_mod.default_registry().snapshot()
    assert reg.totals() == (400.0, 40.0)
    label = 'program="test.prog_a"'
    assert _get(snap1, "oryx_device_flops_total", label) - _get(
        snap0, "oryx_device_flops_total", label) == 400.0
    assert _get(snap1, "oryx_device_bytes_total", label) - _get(
        snap0, "oryx_device_bytes_total", label) == 40.0
    assert _get(snap1, "oryx_device_calls_total", label) - _get(
        snap0, "oryx_device_calls_total", label) == 4


def test_unregistered_program_counts_calls_but_no_flops():
    reg = profiling.CostRegistry()
    snap0 = metrics_mod.default_registry().snapshot()
    reg.record("test.prog_unknown", calls=2)
    snap1 = metrics_mod.default_registry().snapshot()
    label = 'program="test.prog_unknown"'
    # the gap stays visible as calls-without-flops, never silently zero cost
    assert _get(snap1, "oryx_device_calls_total", label) - _get(
        snap0, "oryx_device_calls_total", label) == 2
    assert _get(snap1, "oryx_device_flops_total", label) == _get(
        snap0, "oryx_device_flops_total", label)
    assert reg.totals() == (0.0, 0.0)


def test_rates_window_prunes_and_idle_decays():
    reg = profiling.CostRegistry(window_sec=60.0)
    reg.register("p", 600.0, 60.0)
    reg.record("p")
    fl, by = reg.rates()
    # a fresh registry clamps the denominator to its own age (floor 1 s)
    assert fl == pytest.approx(600.0)
    assert by == pytest.approx(60.0)
    reg.set_window(1.0)
    time.sleep(1.05)
    fl2, _ = reg.rates()
    assert fl2 == 0.0  # events past the window pruned: idle decays to zero


def test_register_compiled_rejects_unusable_executables():
    reg = profiling.CostRegistry()

    class NoCost:
        def cost_analysis(self):
            raise RuntimeError("no analysis on this backend")

    class ZeroCost:
        def cost_analysis(self):
            return {"flops": 0.0}

    assert reg.register_compiled("x", NoCost()) is False
    assert reg.register_compiled("y", ZeroCost()) is False
    assert not reg.known("x") and not reg.known("y")


# ---------------------------------------------------------------------------
# scrape-time gauges: MFU, bandwidth fraction, device + host memory
# ---------------------------------------------------------------------------


def test_mfu_and_memory_gauges_in_exposition_and_snapshot():
    import jax  # noqa: F401 — device gauges wire only once jax is imported

    config = cfg.overlay_on({
        "oryx.profiling.peak-tflops": 1.0,
        "oryx.profiling.peak-hbm-gbps": 1.0,
    }, cfg.get_default())
    profiling.configure(config)
    profiling.costs().register("test.mfu_prog", 5.0e11, 5.0e8)
    profiling.costs().record("test.mfu_prog", calls=2)

    text = metrics_mod.default_registry().render()

    def value(name: str) -> float:
        m = re.search(rf"^{name} (\S+)$", text, re.M)
        assert m, f"{name} missing from exposition"
        return float(m.group(1))

    assert value("oryx_device_mfu") > 0.0
    assert value("oryx_device_hbm_bandwidth_fraction") > 0.0
    assert value("oryx_device_flops_per_second") > 0.0
    assert value("oryx_host_rss_bytes") > 0.0
    assert value("oryx_host_peak_rss_bytes") > 0.0
    # per-device children minted for every local device (CPU backends report
    # no memory_stats, so the value is 0 — but the series must exist)
    assert re.search(r'oryx_device_memory_bytes_in_use\{device="[^"]+"\}',
                     text)

    # the same series land in snapshot() — the embed bench.py ships
    snap = metrics_mod.default_registry().snapshot()
    assert snap["oryx_device_mfu"][""] > 0.0
    assert any(k.startswith('device="')
               for k in snap["oryx_device_memory_bytes_in_use"])
    # restore auto peaks so later tests see the unconfigured default
    profiling.configure(cfg.get_default())


def test_memory_snapshot_stable_keys():
    import jax  # noqa: F401

    snap = profiling.memory_snapshot()
    assert snap["host_rss_bytes"] > 0
    assert snap["host_peak_rss_bytes"] >= snap["host_rss_bytes"] // 2
    assert snap["host_peak_rss_mb"] == snap["host_peak_rss_bytes"] // 2**20
    assert isinstance(snap["devices"], dict) and snap["devices"]
    dev = next(iter(snap["devices"].values()))
    assert set(dev) == {"bytes_in_use", "peak_bytes", "limit_bytes"}


def test_device_perf_rows_render_from_metrics_dump():
    """trace_summary's metrics view surfaces the device-performance series
    from a /metrics text dump."""
    profiling.costs().register("test.render_prog", 1.0e9, 1.0e6)
    profiling.costs().record("test.render_prog")
    text = metrics_mod.default_registry().render()
    _, scalars = ts.parse_metrics_text(text)
    rows = ts.device_perf_rows(scalars)
    names = {series.split("{")[0] for series, _v, _p in rows}
    assert "oryx_device_mfu" in names
    assert "oryx_device_flops_total" in names
    assert "oryx_host_peak_rss_bytes" in names
    mfu_row = next(r for r in rows if r[0] == "oryx_device_mfu")
    assert mfu_row[2].endswith("% MFU")


def test_layer_order_configure_before_jax_wires_on_first_record():
    """Trainer construction order: AbstractLayer calls profiling.configure
    BEFORE the model class (and therefore jax) is ever imported — the
    jax-dependent wiring (auto peaks, per-device memory gauges) must
    complete lazily on the first execution-site record(), not stay dead
    for the process lifetime. Needs a fresh process: this test module
    itself imports jax."""
    import subprocess
    import sys as _sys

    code = (
        "import sys\n"
        "from oryx_tpu.common import config as cfg\n"
        "from oryx_tpu.common import profiling as prof\n"
        "assert 'jax' not in sys.modules\n"
        "prof.configure(cfg.get_default())\n"
        "assert not prof._devices_wired\n"
        "import jax\n"
        "jax.numpy.zeros(1).block_until_ready()\n"
        "prof.costs().register('t', 10.0, 20.0)\n"
        "prof.costs().record('t')\n"
        "assert prof._devices_wired, 'gauges unwired after record()'\n"
        "from oryx_tpu.common import metrics as m\n"
        "text = m.default_registry().render()\n"
        "assert 'oryx_device_memory_bytes_in_use{device=' in text\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([_sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120,
                          cwd=os.path.dirname(os.path.dirname(DATA)))
    assert proc.returncode == 0, proc.stderr[-2000:]


# ---------------------------------------------------------------------------
# ProfileSession: one capture per process, owner checks, overdue reclaim
# ---------------------------------------------------------------------------


def test_profile_session_busy_refusal_and_owner_checked_stop(tmp_path):
    session = profiling.profile_session()
    _session_idle()
    d = session.start(str(tmp_path / "cap1"), owner="one", max_seconds=30.0)
    try:
        assert session.busy() and session.owner() == "one"
        with pytest.raises(profiling.ProfileBusyError):
            session.start(str(tmp_path / "cap2"), owner="two",
                          max_seconds=30.0)
        # a stranger's stop must NOT cut the capture short
        assert session.stop(owner="two") is None
        assert session.busy()
    finally:
        assert session.stop(owner="one") == d
    assert not session.busy()
    # trace written on stop
    assert any(files for _, _, files in os.walk(d))


def test_profile_session_overdue_capture_is_reclaimed(tmp_path):
    session = profiling.profile_session()
    _session_idle()
    session.start(str(tmp_path / "stale"), owner="crashed",
                  max_seconds=0.01)
    time.sleep(0.05)
    # the next bounded starter reclaims the profiler instead of wedging
    d = session.start(str(tmp_path / "fresh"), owner="next",
                      max_seconds=30.0)
    try:
        assert session.owner() == "next"
    finally:
        assert session.stop() == d
    assert not session.busy()


# ---------------------------------------------------------------------------
# StepTracer: profiler-leak regression (shared session + close-path stop)
# ---------------------------------------------------------------------------


def _tracer_config(tmp_path, sub: str):
    return cfg.overlay_on({
        "oryx.tracing.enabled": True,
        "oryx.tracing.profile-dir": str(tmp_path / sub),
        "oryx.tracing.profile-steps": 5,
    }, cfg.get_default())


def test_steptracer_early_close_finalizes_capture(tmp_path):
    """Regression: a layer stopped before reaching profile-steps steps used
    to never call stop_trace — trace dir left open/truncated and the
    process profiler wedged for any later owner."""
    _session_idle()
    tracer = StepTracer(_tracer_config(tmp_path, "batch"), "batch")
    for _ in range(2):  # fewer than profile-steps
        with tracer.step("generation", n_items=10):
            pass
    assert profiling.profile_session().busy()
    tracer.close()
    assert not profiling.profile_session().busy()
    # the capture was finalized, not abandoned: files exist in the dir
    assert any(files for _, _, files in os.walk(tmp_path / "batch"))
    # close is idempotent and a fresh owner can capture immediately
    tracer.close()
    d = profiling.profile_session().start(str(tmp_path / "after"),
                                          owner="later", max_seconds=30.0)
    assert profiling.profile_session().stop(owner="later") == d


def test_steptracer_denied_capture_retries_once_profiler_frees(tmp_path):
    """A transient foreign capture (e.g. /debug/profile) must not cost a
    long-running layer its step capture for the rest of the process: the
    denied tracer retries once the session frees up."""
    _session_idle()
    session = profiling.profile_session()
    session.start(str(tmp_path / "foreign"), owner="debug-endpoint",
                  max_seconds=30.0)
    tracer = StepTracer(_tracer_config(tmp_path, "batch"), "batch")
    with tracer.step("generation"):
        pass  # denied: the endpoint owns the profiler
    assert session.owner() == "debug-endpoint"
    session.stop(owner="debug-endpoint")
    with tracer.step("generation"):
        pass  # profiler free again: the tracer reclaims its capture
    assert session.owner() == "steptracer-batch"
    tracer.close()
    assert not session.busy()


def test_capture_dirs_unique_and_no_orphan_on_busy(tmp_path):
    """Two captures minted within one wall-clock second get distinct dirs,
    and a capture that loses the session race removes its empty dir."""
    base = str(tmp_path / "caps")
    assert profiling.capture_dir(base) != profiling.capture_dir(base)
    _session_idle()
    session = profiling.profile_session()
    session.start(str(tmp_path / "holder"), owner="holder",
                  max_seconds=30.0)
    try:
        before = set(os.listdir(base))
        with pytest.raises(profiling.ProfileBusyError):
            profiling.timed_capture(base, 0.01, owner="loser")
        assert set(os.listdir(base)) == before  # no orphan dir left behind
    finally:
        session.stop(owner="holder")


def test_two_steptracers_share_the_session_without_raising(tmp_path):
    """Regression: batch + speed layers both profiling in one process used
    to both call ``jax.profiler.start_trace`` — the second raised on every
    step. Now the loser is quietly denied and its close cannot cut the
    winner's capture short."""
    _session_idle()
    t_batch = StepTracer(_tracer_config(tmp_path, "batch"), "batch")
    t_speed = StepTracer(_tracer_config(tmp_path, "speed"), "speed")
    with t_batch.step("generation"):
        pass
    with t_speed.step("microbatch"):  # must not raise
        pass
    assert profiling.profile_session().owner() == "steptracer-batch"
    t_speed.close()  # the denied tracer's close is a no-op...
    assert profiling.profile_session().busy()
    t_batch.close()  # ...and the owner's close releases the profiler
    assert not profiling.profile_session().busy()


# ---------------------------------------------------------------------------
# POST /debug/profile on the serving console
# ---------------------------------------------------------------------------


class _FakeManager:
    rescorer_provider = None

    def get_model(self):
        return None

    def is_read_only(self):
        return True


def _make_server(extra: dict):
    from oryx_tpu.serving.app import make_app
    from tests.test_metrics import _AppServer

    config = cfg.overlay_on(extra, cfg.get_default())
    return _AppServer(make_app(config, _FakeManager()))


def test_debug_profile_happy_path_writes_readable_trace(tmp_path):
    _session_idle()
    with _make_server({
        "oryx.profiling.profile-dir": str(tmp_path / "captures"),
    }) as base:
        r = httpx.post(f"{base}/debug/profile", params={"seconds": "0.2"},
                       timeout=60)
        assert r.status_code == 200, r.text
        body = r.json()
        assert body["seconds"] == 0.2
        trace_dir = body["trace_dir"]
        assert trace_dir.startswith(str(tmp_path / "captures"))
        assert os.path.isdir(trace_dir)
        assert any(files for _, _, files in os.walk(trace_dir))
        assert "trace_summary" in body["hint"]
    assert not profiling.profile_session().busy()


def test_debug_profile_concurrent_second_request_409():
    _session_idle()
    with _make_server({}) as base:
        with cf.ThreadPoolExecutor(2) as pool:
            futs = [
                pool.submit(
                    httpx.post, f"{base}/debug/profile",
                    params={"seconds": "1.5"}, timeout=60,
                )
                for _ in range(2)
            ]
            statuses = sorted(f.result().status_code for f in futs)
        assert statuses == [200, 409]
        busy = next(f.result() for f in futs
                    if f.result().status_code == 409)
        assert "in flight" in busy.text
    assert not profiling.profile_session().busy()


def test_debug_profile_validates_seconds():
    _session_idle()
    with _make_server({"oryx.profiling.max-capture-sec": 2.0}) as base:
        assert httpx.post(f"{base}/debug/profile",
                          params={"seconds": "abc"}).status_code == 400
        assert httpx.post(f"{base}/debug/profile",
                          params={"seconds": "0"}).status_code == 400
        # over the configured bound: refused, never silently clamped
        r = httpx.post(f"{base}/debug/profile", params={"seconds": "5"})
        assert r.status_code == 400
        assert "max-capture-sec" in r.text


def test_debug_profile_auth_parity_with_metrics():
    """Same auth story as /metrics: exempt by default, guarded together
    under oryx.metrics.require-auth."""
    _session_idle()
    creds = {
        "oryx.serving.api.user-name": "admin",
        "oryx.serving.api.password": "s3cret",
        "oryx.serving.api.auth-scheme": "basic",
    }
    with _make_server(creds) as base:
        # API routes stay behind auth; the profiler endpoint is exempt
        assert httpx.get(f"{base}/ready").status_code == 401
        r = httpx.post(f"{base}/debug/profile", params={"seconds": "0.1"},
                       timeout=60)
        assert r.status_code == 200, r.text
    _session_idle()
    with _make_server({**creds, "oryx.metrics.require-auth": True}) as base:
        assert httpx.post(f"{base}/debug/profile",
                          params={"seconds": "0.1"}).status_code == 401
        assert httpx.post(
            f"{base}/debug/profile", params={"seconds": "0.1"},
            auth=("admin", "s3cret"), timeout=60,
        ).status_code == 200
    assert not profiling.profile_session().busy()


# ---------------------------------------------------------------------------
# trace_summary --history: the BENCH trajectory + regression gate
# ---------------------------------------------------------------------------

_FIXTURES = [os.path.join(DATA, f) for f in (
    "BENCH_hist_r01.json", "BENCH_hist_r02.json",
    "BENCH_hist_r03_regressed.json",
)]


def test_history_renders_trajectory_and_passes_clean_rounds():
    records = ts.load_history_records(_FIXTURES[:2])
    buf = io.StringIO()
    assert ts.render_history(records, regress_pct=25.0, out=buf) == 0
    out = buf.getvalue()
    # both rounds render, with the batch pack-vs-device verdict and the
    # memory column fed from the new stable keys (r1 uses the legacy spot)
    assert re.search(r"^\s*r1\s+cpu\s+330\.2", out, re.M)
    assert re.search(r"^\s*r2\s+cpu\s+341\.9", out, re.M)
    assert "2150MB" in out and "1993MB" in out
    assert " < " in out  # pack_s < elapsed_s on both rounds
    assert "no regression" in out


def test_history_flags_injected_regression_nonzero_exit():
    records = ts.load_history_records(_FIXTURES)
    buf = io.StringIO()
    assert ts.render_history(records, regress_pct=25.0, out=buf) == 1
    out = buf.getvalue()
    assert "REGRESSION: http_qps" in out
    assert "REGRESSION: p99_ms" in out  # the tail blew out alongside qps
    assert "(r2)" in out and "(r3)" in out
    # a threshold looser than the worst delta lets the same rounds pass
    assert ts.render_history(records, regress_pct=150.0,
                             out=io.StringIO()) == 0


def test_history_cli_entry_point(capsys):
    rc = ts.main(["--history", *_FIXTURES, "--regress-pct", "25"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "REGRESSION: http_qps" in out


def test_history_compares_same_backend_only():
    """A CPU round after an on-chip round is a different machine, not a
    code regression — only same-backend rounds compare."""
    records = [
        ("r1", {"backend": "cpu", "value": 400.0}),
        ("r2", {"backend": "tpu", "value": 7000.0}),
        ("r3", {"backend": "cpu", "value": 390.0}),
    ]
    assert ts.render_history(records, regress_pct=25.0,
                             out=io.StringIO()) == 0
    records[-1] = ("r3", {"backend": "cpu", "value": 200.0})
    buf = io.StringIO()
    assert ts.render_history(records, regress_pct=25.0, out=buf) == 1
    assert "(r1)" in buf.getvalue()  # compared against the cpu round


def test_history_bare_batch_record_and_skips_unparseable(tmp_path, capsys):
    bare = tmp_path / "BENCH_batch_7.json"
    bare.write_text(
        '{"backend": "cpu", "mfu": 0.002, "pack_s": 12.0, "elapsed_s": 40.0,'
        ' "memory": {"host_peak_rss_mb": 900}}'
    )
    broken = tmp_path / "BENCH_broken_8.json"
    broken.write_text("{not json")
    records = ts.load_history_records([str(bare), str(broken)])
    assert [label for label, _ in records] == ["r7"]
    buf = io.StringIO()
    assert ts.render_history(records, regress_pct=25.0, out=buf) == 0
    out = buf.getvalue()
    assert "0.0020" in out and "900MB" in out
