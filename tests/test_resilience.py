"""Resilience subsystem units: retry jitter/budget bounds, circuit-breaker
state machine, deadline propagation (including across the coalescer's
executor hop), deterministic fault injection, generation quarantine vs
fatal-on-error parity, crash-safe offset commits, and the shed/deadline
HTTP surfaces (503 + Retry-After, 504 + partial trace id)."""

import asyncio
import random
import threading
import time

import httpx
import numpy as np
import pytest

from oryx_tpu.common import config as cfg
from oryx_tpu.common import faults
from oryx_tpu.common import ioutils
from oryx_tpu.common import metrics as metrics_mod
from oryx_tpu.common import resilience
from oryx_tpu.lambda_rt.layer import AbstractLayer
from oryx_tpu.serving.app import make_app
from oryx_tpu.serving.batcher import TopNCoalescer
from oryx_tpu.transport import topic as tp


@pytest.fixture(autouse=True)
def _fresh():
    tp.reset_memory_brokers()
    faults.disarm()
    yield
    faults.disarm()
    tp.reset_memory_brokers()


def _counter(name: str, label: str = "") -> float:
    snap = metrics_mod.default_registry().snapshot()
    return snap.get(name, {}).get(label, 0.0)


# ---------------------------------------------------------------------------
# RetryPolicy
# ---------------------------------------------------------------------------


def test_backoff_full_jitter_bounds():
    """Delay for re-attempt n is uniform in [0, min(max_delay, base*2^n)]:
    never above the cap, not degenerate at zero."""
    policy = resilience.RetryPolicy(
        base_delay_sec=0.1, max_delay_sec=1.0, rng=random.Random(7)
    )
    for attempt in range(8):
        cap = min(1.0, 0.1 * 2 ** attempt)
        samples = [policy.backoff(attempt) for _ in range(300)]
        assert all(0.0 <= s <= cap for s in samples), (attempt, max(samples))
        # full jitter really spreads over the interval (not equal-jitter)
        assert min(samples) < 0.25 * cap
        assert max(samples) > 0.75 * cap


def test_retry_recovers_and_accounts():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise OSError("transient")
        return 42

    policy = resilience.RetryPolicy(max_attempts=5, base_delay_sec=0.001)
    before_r = _counter("oryx_retries_total", 'site="t.rec",outcome="retry"')
    before_ok = _counter("oryx_retries_total", 'site="t.rec",outcome="recovered"')
    assert policy.call("t.rec", flaky) == 42
    assert calls["n"] == 3
    assert _counter("oryx_retries_total", 'site="t.rec",outcome="retry"') - before_r == 2
    assert _counter("oryx_retries_total", 'site="t.rec",outcome="recovered"') - before_ok == 1


def test_retry_nonretryable_raises_immediately():
    calls = {"n": 0}

    def bad():
        calls["n"] += 1
        raise ValueError("programming error")

    policy = resilience.RetryPolicy(max_attempts=5, base_delay_sec=0.001)
    before = _counter("oryx_retries_total", 'site="t.fatal",outcome="fatal"')
    with pytest.raises(ValueError):
        policy.call("t.fatal", bad)
    assert calls["n"] == 1
    assert _counter("oryx_retries_total", 'site="t.fatal",outcome="fatal"') - before == 1


def test_retry_exhausts_attempt_budget():
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        raise OSError("down")

    policy = resilience.RetryPolicy(max_attempts=3, base_delay_sec=0.001)
    before = _counter("oryx_retries_total", 'site="t.exh",outcome="exhausted"')
    with pytest.raises(OSError):
        policy.call("t.exh", always)
    assert calls["n"] == 3
    assert _counter("oryx_retries_total", 'site="t.exh",outcome="exhausted"') - before == 1


def test_retry_stop_event_aborts_backoff():
    """A closing layer must never sit out a long retry sleep."""
    stop = threading.Event()
    stop.set()
    policy = resilience.RetryPolicy(max_attempts=10, base_delay_sec=30.0)
    t0 = time.monotonic()
    with pytest.raises(OSError):
        policy.call("t.stop", lambda: (_ for _ in ()).throw(OSError("x")),
                    stop=stop)
    assert time.monotonic() - t0 < 1.0


# ---------------------------------------------------------------------------
# CircuitBreaker
# ---------------------------------------------------------------------------


def test_breaker_state_machine_and_metrics():
    clock = {"t": 0.0}
    b = resilience.CircuitBreaker(
        "t.breaker", failure_threshold=2, reset_timeout_sec=5.0,
        half_open_probes=1, clock=lambda: clock["t"],
    )
    assert b.state == resilience.CLOSED and b.allow()
    b.record_failure()
    assert b.state == resilience.CLOSED  # below threshold
    b.record_failure()
    assert b.state == resilience.OPEN
    assert not b.allow()
    # state gauge reads 1 (open) at scrape time
    gauge = metrics_mod.default_registry().get("oryx_circuit_breaker_state")
    assert gauge.labels("t.breaker").value == 1.0
    # reset timeout -> half-open admits exactly one probe
    clock["t"] = 5.0
    assert b.allow()
    assert b.state == resilience.HALF_OPEN
    assert not b.allow()  # probe quota spent
    # failed probe re-opens and re-arms the timer
    b.record_failure()
    assert b.state == resilience.OPEN and not b.allow()
    clock["t"] = 10.0
    assert b.allow()
    b.record_success()
    assert b.state == resilience.CLOSED
    assert gauge.labels("t.breaker").value == 0.0
    # every transition was counted: open(x2), half_open(x2), closed(x1)
    assert _counter("oryx_circuit_breaker_transitions_total",
                    'breaker="t.breaker",to="open"') == 2
    assert _counter("oryx_circuit_breaker_transitions_total",
                    'breaker="t.breaker",to="half_open"') == 2
    assert _counter("oryx_circuit_breaker_transitions_total",
                    'breaker="t.breaker",to="closed"') == 1


def test_breaker_unreported_half_open_probe_expires():
    """A probe whose outcome is never reported (request shed, deadline-
    dropped, caller died) must not wedge the breaker half-open forever:
    outstanding probe slots expire after another reset period."""
    clock = {"t": 0.0}
    b = resilience.CircuitBreaker(
        "t.probe", failure_threshold=1, reset_timeout_sec=1.0,
        half_open_probes=1, clock=lambda: clock["t"],
    )
    b.record_failure()
    assert b.state == resilience.OPEN
    clock["t"] = 1.0
    assert b.allow()  # probe granted... and never reported
    assert not b.allow()
    clock["t"] = 2.0  # stale probe expires after another reset period
    assert b.allow()
    b.record_success()
    assert b.state == resilience.CLOSED


def test_breaker_success_resets_consecutive_count():
    b = resilience.CircuitBreaker("t.breaker2", failure_threshold=3)
    for _ in range(5):
        b.record_failure()
        b.record_failure()
        b.record_success()  # consecutive-failure streak broken
    assert b.state == resilience.CLOSED


# ---------------------------------------------------------------------------
# Deadline
# ---------------------------------------------------------------------------


def test_deadline_contextvar_and_to_thread_propagation():
    assert resilience.current_deadline() is None
    with resilience.deadline(5.0) as dl:
        assert resilience.current_deadline() is dl
        assert 0.0 < resilience.remaining() <= 5.0
        assert not dl.expired()

        async def main():
            # asyncio.to_thread copies contextvars: the worker thread sees
            # the request deadline (same channel as the span context)
            return await asyncio.to_thread(resilience.current_deadline)

        assert asyncio.run(main()) is dl
    assert resilience.current_deadline() is None


def test_deadline_zero_budget_is_noop():
    with resilience.deadline(0) as dl:
        assert dl is None
        assert resilience.current_deadline() is None


class _TinyModel:
    def top_n_batch(self, qs, how_many, alloweds=None, excluded=None):
        return [[(f"i{i}", 1.0) for i in range(how_many)] for _ in qs]


def test_deadline_crosses_coalescer_executor_hop():
    """A deadline set in the request context is honored on the OTHER side
    of the coalescer's run_in_executor hop: expired-in-queue requests get
    DeadlineExceeded without a device call; live ones run normally."""
    coal = TopNCoalescer(window_ms=1.0)
    model = _TinyModel()

    async def main():
        with resilience.deadline(0.02):
            await asyncio.sleep(0.05)  # budget burns away while "queued"
            with pytest.raises(resilience.DeadlineExceeded):
                await coal.top_n(model, np.zeros(2), 3)
        with resilience.deadline(10.0):
            res = await coal.top_n(model, np.zeros(2), 3)
            assert len(res) == 3

    before = _counter("oryx_coalescer_deadline_dropped_total")
    asyncio.run(main())
    assert _counter("oryx_coalescer_deadline_dropped_total") - before == 1


# ---------------------------------------------------------------------------
# Fault injection
# ---------------------------------------------------------------------------


def test_faults_fail_n_then_succeed_schedule():
    faults.arm("t.site=fail:2", seed=0)
    for _ in range(2):
        with pytest.raises(faults.InjectedFault):
            faults.maybe_fail("t.site")
    for _ in range(10):
        faults.maybe_fail("t.site")  # schedule spent: passes forever
    assert faults.stats()["t.site"] == {"calls": 12, "injected": 2}
    faults.maybe_fail("other.site")  # un-scheduled sites never fire
    faults.disarm()
    faults.maybe_fail("t.site")  # disarmed: no-op


def test_faults_rate_schedule_is_seed_deterministic():
    def schedule(seed):
        faults.arm("t.rate=rate:0.5", seed=seed)
        out = []
        for _ in range(64):
            try:
                faults.maybe_fail("t.rate")
                out.append(False)
            except faults.InjectedFault:
                out.append(True)
        return out

    a, b = schedule(3), schedule(3)
    assert a == b  # identical seed => identical schedule
    assert 10 < sum(a) < 54  # and it is a real ~0.5 rate
    assert schedule(4) != a


def test_faults_latency_injection():
    faults.arm("t.lat=latency:40", seed=0)
    t0 = time.perf_counter()
    faults.maybe_fail("t.lat")
    assert time.perf_counter() - t0 >= 0.04


def test_faults_config_armed_and_bad_spec_rejected():
    config = cfg.overlay_on({
        "oryx.faults.enabled": True,
        "oryx.faults.spec": "t.conf=fail:1",
        "oryx.faults.seed": 1,
    }, cfg.get_default())
    faults.configure(config)
    assert faults.armed()
    with pytest.raises(faults.InjectedFault):
        faults.maybe_fail("t.conf")
    with pytest.raises(ValueError):
        faults.parse_spec("t.conf=explode:1")
    with pytest.raises(ValueError):
        faults.parse_spec("justasite")


def test_producer_send_retries_through_injected_append_faults():
    config = cfg.overlay_on(
        {"oryx.resilience.retry.base-delay-ms": 1}, cfg.get_default()
    )
    resilience.configure(config)
    broker = tp.get_broker("memory:")
    broker.create_topic("T")
    faults.arm("broker.append=fail:2", seed=0)
    before = _counter("oryx_retries_total",
                      'site="broker.append",outcome="recovered"')
    tp.TopicProducerImpl("memory:", "T").send("k", "survives")
    assert [km.message for km in broker.read("T", 0)] == ["survives"]
    assert faults.stats()["broker.append"]["injected"] == 2
    assert _counter("oryx_retries_total",
                    'site="broker.append",outcome="recovered"') - before == 1


def test_consume_iterator_retries_through_injected_read_faults():
    resilience.configure(cfg.overlay_on(
        {"oryx.resilience.retry.base-delay-ms": 1}, cfg.get_default()
    ))
    broker = tp.get_broker("memory:")
    broker.create_topic("T")
    broker.append("T", "k", "m")
    faults.arm("broker.read=fail:2", seed=0)
    it = tp.ConsumeDataIterator(broker, "T", "earliest")
    try:
        assert next(it).message == "m"
    finally:
        it.close()
    assert faults.stats()["broker.read"]["injected"] == 2


# ---------------------------------------------------------------------------
# Microbatch pump: quarantine vs fatal-on-error
# ---------------------------------------------------------------------------


def _pump_config(extra=None):
    base = {
        "oryx.id": "res-test",
        "oryx.speed.streaming.config.platform": "cpu",
        "oryx.resilience.retry.base-delay-ms": 1,
        "oryx.resilience.retry.max-delay-ms": 5,
    }
    base.update(extra or {})
    return cfg.overlay_on(base, cfg.get_default())


def _start_pump(config, on_batch):
    tp.maybe_create_topics(config, "input-topic", "update-topic")
    layer = AbstractLayer(config, "speed")
    layer.spawn(
        "pump", lambda: layer.run_microbatches(on_batch, 0.05, {0: 0})
    )
    return layer


def _wait(cond, timeout=10.0, msg="condition never held"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    pytest.fail(msg)


def test_poison_generation_quarantines_and_layer_lives():
    config = _pump_config({"oryx.resilience.generation.max-retries": 1})
    batches = []

    def on_batch(ts, batch):
        msgs = [km.message for km in batch]
        batches.append(msgs)
        if "poison" in msgs:
            raise RuntimeError("poison input")

    before = _counter("oryx_quarantined_generations_total", 'tier="speed"')
    layer = _start_pump(config, on_batch)
    try:
        producer = tp.TopicProducerImpl("memory:", "OryxInput")
        producer.send("k", "poison")
        _wait(lambda: _counter("oryx_quarantined_generations_total",
                               'tier="speed"') - before == 1,
              msg="generation never quarantined")
        assert not layer.stopped  # the layer SURVIVED the poison
        # initial attempt + 1 retry saw the poison batch
        assert sum(1 for b in batches if "poison" in b) == 2
        # offsets advanced past the poison: the next message arrives alone
        producer.send("k", "good")
        _wait(lambda: ["good"] in batches,
              msg="pump never advanced past the poison generation")
    finally:
        layer.close()


def test_transient_generation_failure_recovers_without_quarantine():
    config = _pump_config({"oryx.resilience.generation.max-retries": 2})
    state = {"fails": 0, "done": False}

    def on_batch(ts, batch):
        if not batch:
            return
        if state["fails"] < 1:
            state["fails"] += 1
            raise RuntimeError("transient wobble")
        state["done"] = True

    before = _counter("oryx_quarantined_generations_total", 'tier="speed"')
    layer = _start_pump(config, on_batch)
    try:
        tp.TopicProducerImpl("memory:", "OryxInput").send("k", "x")
        _wait(lambda: state["done"], msg="generation never recovered")
        assert _counter("oryx_quarantined_generations_total",
                        'tier="speed"') - before == 0
        assert not layer.stopped
    finally:
        layer.close()


def test_fatal_on_error_parity_and_await_termination_idempotent():
    config = _pump_config({"oryx.speed.streaming.fatal-on-error": True})
    attempts = {"n": 0}

    def on_batch(ts, batch):
        if batch:
            attempts["n"] += 1
            raise RuntimeError("boom")

    failures_before = _counter("oryx_layer_failures_total", 'tier="speed"')
    layer = _start_pump(config, on_batch)
    try:
        tp.TopicProducerImpl("memory:", "OryxInput").send("k", "x")
        _wait(lambda: layer.stopped, msg="fatal-on-error never killed the layer")
        assert attempts["n"] == 1  # reference parity: no retry
        with pytest.raises(RuntimeError, match="boom"):
            layer.await_termination(timeout=5)
        # the SAME exception must not re-raise on every later call
        layer.await_termination(timeout=1)
        layer.await_termination(timeout=1)
        assert _counter("oryx_layer_failures_total",
                        'tier="speed"') - failures_before == 1
    finally:
        layer.close()


def test_poll_failure_on_later_partition_loses_no_messages(monkeypatch):
    """A poll failure on partition 1 after partition 0 was already read must
    discard the tick WHOLE: the partition-0 messages arrive (exactly once)
    on a later tick, never silently skipped by an in-place offset advance."""
    config = _pump_config({
        "oryx.input-topic.message.partitions": 2,
        "oryx.resilience.retry.max-attempts": 1,  # poll failures surface fast
    })
    tp.maybe_create_topics(config, "input-topic", "update-topic")
    broker = tp.get_broker("memory:")
    real_read = broker.read
    fail = {"n": 2}

    def flaky_read(topic, offset, max_items=1024, partition=0):
        if topic == "OryxInput" and partition == 1 and fail["n"] > 0:
            fail["n"] -= 1
            raise OSError("partition 1 briefly down")
        return real_read(topic, offset, max_items, partition=partition)

    monkeypatch.setattr(broker, "read", flaky_read)
    # one key per partition, chosen by the real router
    keys = {tp.partition_for_key(f"k{i}", 2): f"k{i}" for i in range(32)}
    seen: list = []
    layer = AbstractLayer(config, "speed")
    layer.spawn("pump", lambda: layer.run_microbatches(
        lambda ts, batch: seen.extend(km.message for km in batch),
        0.05, {0: 0, 1: 0},
    ))
    try:
        broker.append("OryxInput", keys[0], "m-p0")
        broker.append("OryxInput", keys[1], "m-p1")
        _wait(lambda: sorted(seen) == ["m-p0", "m-p1"],
              msg=f"messages lost or duplicated across the poll fault: {seen}")
        assert not layer.stopped
    finally:
        layer.close()


def test_corrupt_records_counted_and_batch_clean(tmp_path):
    root = tmp_path / "broker"
    url = f"file:{root}"
    config = _pump_config({
        "oryx.input-topic.broker": url,
        "oryx.update-topic.broker": url,
    })
    batches = []

    def on_batch(ts, batch):
        if batch:
            batches.append([km.message for km in batch])

    before = _counter("oryx_corrupt_records_total", 'tier="speed"')
    tp.maybe_create_topics(config, "input-topic", "update-topic")
    broker = tp.get_broker(url)
    broker.append("OryxInput", "k", "good-1")
    # a torn/garbage interior line, as a crashed writer would leave
    with open(root / "OryxInput" / "00000.jsonl", "ab") as f:
        f.write(b"{this is not json\n")
    broker.append("OryxInput", "k", "good-2")
    layer = _start_pump(config, on_batch)
    try:
        _wait(lambda: batches, msg="pump never delivered a batch")
        assert batches[0] == ["good-1", "good-2"]  # corrupt line dropped
        assert _counter("oryx_corrupt_records_total",
                        'tier="speed"') - before == 1
    finally:
        layer.close()


# ---------------------------------------------------------------------------
# Crash-safe offset commits (file: broker)
# ---------------------------------------------------------------------------


def test_offset_commit_killed_mid_write_resumes_clean(tmp_path, monkeypatch):
    fb = tp.FileBroker(str(tmp_path))
    fb.create_topic("T")
    fb.set_offset("g", "T", 5)

    # kill the writer mid-commit: the temp file is written but the atomic
    # rename never happens (the strongest torn-write simulation short of
    # SIGKILL — everything before os.replace has run)
    import oryx_tpu.common.ioutils as iou

    with monkeypatch.context() as m:
        def killed(src, dst):
            raise RuntimeError("writer killed mid-commit")

        m.setattr(iou.os, "replace", killed)
        with pytest.raises(RuntimeError, match="killed"):
            fb.set_offset("g", "T", 9)

    # a fresh broker instance (the restarted replica) resumes from the last
    # COMPLETE commit — never a torn value, never a missing file
    assert tp.FileBroker(str(tmp_path)).get_offset("g", "T") == 5
    # and the next commit goes through normally
    fb.set_offset("g", "T", 9)
    assert tp.FileBroker(str(tmp_path)).get_offset("g", "T") == 9


def test_atomic_write_concurrent_committers_never_tear(tmp_path):
    """Two committers racing the same offset file: every read observes one
    writer's COMPLETE value (unique temp names make interleaving impossible)."""
    p = tmp_path / "offset.json"
    ioutils.atomic_write_text(p, "a" * 2048)  # os.replace keeps it existing
    stop = threading.Event()
    errors = []

    def writer(value: str):
        while not stop.is_set():
            ioutils.atomic_write_text(p, value * 2048)

    threads = [threading.Thread(target=writer, args=(v,)) for v in "ab"]
    for t in threads:
        t.start()
    try:
        deadline = time.monotonic() + 1.0
        while time.monotonic() < deadline:
            content = p.read_text()
            if not (content == "a" * 2048 or content == "b" * 2048):
                errors.append(content[:64])
                break
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
    assert not errors, f"torn read observed: {errors}"


# ---------------------------------------------------------------------------
# HTTP surfaces: shed 503 + Retry-After, deadline 504 + partial trace
# ---------------------------------------------------------------------------


class _SlowALSModel:
    """Minimal ALS-shaped serving model with a tunable device-call delay."""

    features = 2

    def __init__(self, delay_s: float = 0.0):
        self.delay_s = delay_s

    def get_fraction_loaded(self):
        return 1.0

    def get_user_vector(self, user):
        return np.zeros(2, dtype=np.float32)

    def get_known_items(self, user):
        return set()

    def known_item_codes(self, user):
        return None

    def top_n_batch(self, qs, how_many, alloweds=None, excluded=None):
        if self.delay_s:
            time.sleep(self.delay_s)
        return [[(f"i{i}", 1.0) for i in range(how_many)] for _ in qs]

    def top_n(self, vec, how_many, offset=0, allowed=None, rescore=None,
              excluded=None):
        if self.delay_s:
            time.sleep(self.delay_s)
        return [(f"i{i}", 1.0) for i in range(how_many)]


class _Manager:
    rescorer_provider = None

    def __init__(self, model):
        self._model = model

    def get_model(self):
        return self._model

    def is_read_only(self):
        return True


def test_shed_path_returns_503_with_retry_after():
    from tests.test_metrics import _AppServer

    config = cfg.overlay_on({
        "oryx.serving.application-resources": "oryx_tpu.serving.resources.als",
        "oryx.serving.compute.max-queue-depth": 1,
        "oryx.serving.compute.coalesce-inflight": 1,
        "oryx.serving.compute.coalesce-deadline-ms": 0,
    }, cfg.get_default())
    app = make_app(config, _Manager(_SlowALSModel(delay_s=0.4)))
    shed_before = _counter("oryx_shed_requests_total")
    with _AppServer(app) as base:
        import concurrent.futures as cf

        def get(i):
            with httpx.Client(base_url=base, timeout=30) as c:
                return c.get(f"/recommend/u{i}")

        with cf.ThreadPoolExecutor(12) as pool:
            responses = list(pool.map(get, range(12)))
    statuses = sorted(r.status_code for r in responses)
    assert set(statuses) <= {200, 503}
    shed = [r for r in responses if r.status_code == 503]
    assert shed, f"nothing shed under 12-way burst: {statuses}"
    assert all(r.headers.get("Retry-After") for r in shed)
    assert all(r.json()["status"] == 503 for r in shed)
    assert _counter("oryx_shed_requests_total") - shed_before == len(shed)
    # the accepted requests all completed correctly
    assert all(len(r.json()) == 10 for r in responses if r.status_code == 200)
    # the overload left throttled flight-recorder evidence: >=1 shed event
    # (the burst coalesces into one event carrying a suppressed count)
    # with every shed accounted between its ring slot + suppressions
    from oryx_tpu.common import blackbox

    shed_events = [e for e in blackbox.events() if e["kind"] == "shed"]
    assert shed_events and shed_events[-1]["severity"] == "warning"
    assert shed_events[-1]["max_queue_depth"] == 1


def test_request_deadline_returns_504_with_partial_trace_id():
    from tests.test_metrics import _AppServer

    config = cfg.overlay_on({
        "oryx.serving.application-resources": "oryx_tpu.serving.resources.als",
        "oryx.serving.api.request-timeout-sec": 0.15,
    }, cfg.get_default())
    app = make_app(config, _Manager(_SlowALSModel(delay_s=2.0)))
    with _AppServer(app) as base:
        with httpx.Client(base_url=base, timeout=30) as c:
            r = c.get("/recommend/u1")
            assert r.status_code == 504
            body = r.json()
            assert body["status"] == 504
            # the partial trace id: retrievable via GET /trace
            assert body["trace_id"]
            tr = c.get("/trace", params={"trace_id": body["trace_id"]})
            assert tr.status_code == 200
            names = {s["name"] for s in tr.json()["spans"]}
            assert any(n.startswith("http GET") for n in names)
            # fast requests are unaffected by the budget
            probe = c.get("/healthz")
            assert probe.status_code == 200
