"""Request-coalescing micro-batcher: many concurrent top-N requests must
collapse into few batched device calls with per-request results intact
(VERDICT r4 #4; reference scenario: LoadBenchmark's concurrent requesters,
app/oryx-app-serving/.../als/LoadBenchmark.java:37-110)."""

import asyncio
import json
import threading
import time

import numpy as np
import pytest

from oryx_tpu.serving.batcher import TopNCoalescer


class _CountingModel:
    """Fake serving model: score = -|idx - vec[0]| so each query has a
    distinct, predictable ranking."""

    def __init__(self, n_items=50):
        self.n = n_items
        self.calls = 0
        self.batch_sizes = []

    def top_n_batch(self, qs, how_many, alloweds=None, excluded=None):
        self.calls += 1
        self.batch_sizes.append(len(qs))
        out = []
        for b, q in enumerate(qs):
            scored = [(f"i{i}", -abs(i - float(q[0]))) for i in range(self.n)]
            if excluded is not None and excluded[b]:
                banned = set(excluded[b])
                scored = [t for t in scored if t[0] not in banned]
            allowed = alloweds[b] if alloweds else None
            if allowed is not None:
                scored = [t for t in scored if allowed(t[0])]
            scored.sort(key=lambda t: -t[1])
            out.append(scored[:how_many])
        return out


def test_concurrent_requests_coalesce_into_one_call():
    model = _CountingModel()
    coal = TopNCoalescer(window_ms=5.0, max_batch=64)

    async def main():
        return await asyncio.gather(*[
            coal.top_n(model, np.array([float(i), 0.0]), 3)
            for i in range(32)
        ])

    results = asyncio.run(main())
    assert model.calls == 1
    assert model.batch_sizes == [32]
    for i, res in enumerate(results):
        assert res[0][0] == f"i{i}"  # each request got ITS answer
        assert len(res) == 3


def test_offset_and_how_many_are_per_request():
    model = _CountingModel()
    coal = TopNCoalescer(window_ms=5.0, max_batch=64)

    async def main():
        return await asyncio.gather(
            coal.top_n(model, np.array([10.0, 0.0]), 2),
            coal.top_n(model, np.array([10.0, 0.0]), 2, offset=2),
        )

    plain, paged = asyncio.run(main())
    assert model.calls == 1
    assert len(plain) == 2 and len(paged) == 2
    # offset=2 page starts where the first page ended
    assert paged[0][0] not in {i for i, _ in plain}


def test_exclusions_and_allowed_ride_along():
    model = _CountingModel()
    coal = TopNCoalescer(window_ms=5.0, max_batch=64)

    async def main():
        return await asyncio.gather(
            coal.top_n(model, np.array([5.0, 0.0]), 3, excluded={"i5"}),
            coal.top_n(model, np.array([7.0, 0.0]), 3,
                       allowed=lambda i: i != "i7"),
        )

    r_excl, r_allowed = asyncio.run(main())
    assert model.calls == 1
    assert "i5" not in {i for i, _ in r_excl}
    assert "i7" not in {i for i, _ in r_allowed}


def test_max_batch_flushes_early():
    model = _CountingModel()
    coal = TopNCoalescer(window_ms=1000.0, max_batch=4)  # window never fires

    async def main():
        return await asyncio.gather(*[
            coal.top_n(model, np.array([float(i), 0.0]), 2) for i in range(8)
        ])

    results = asyncio.run(main())
    assert len(results) == 8
    assert model.calls == 2  # two full batches, no window wait
    assert model.batch_sizes == [4, 4]


def test_closed_loop_clients_batch_while_busy():
    """Closed-loop clients (each awaits its response before sending the
    next request) must NOT degenerate into one-request batches once the
    device call outlasts the coalescing window: while a call is in flight,
    arrivals accumulate and its completion flushes them as one batch."""

    class _Slow(_CountingModel):
        def __init__(self):
            super().__init__()
            self.concurrent = 0
            self.max_concurrent = 0
            self._lock = threading.Lock()

        def top_n_batch(self, qs, how_many, alloweds=None, excluded=None):
            with self._lock:
                self.concurrent += 1
                self.max_concurrent = max(self.max_concurrent, self.concurrent)
            time.sleep(0.05)  # device latency >> 1ms window
            try:
                return super().top_n_batch(qs, how_many, alloweds, excluded)
            finally:
                with self._lock:
                    self.concurrent -= 1

    model = _Slow()
    coal = TopNCoalescer(window_ms=1.0, max_batch=64, max_inflight=1)

    async def client(i):
        for r in range(3):
            res = await coal.top_n(model, np.array([float(i), 0.0]), 2)
            assert res[0][0] == f"i{i}"

    async def main():
        await asyncio.gather(*[client(i) for i in range(16)])

    asyncio.run(main())
    # 48 requests; a fixed-window coalescer would need ~48 slow calls (2.4s
    # serial). Batch-while-busy converges on ~16-request batches.
    assert model.calls <= 12, (model.calls, model.batch_sizes)
    assert sum(model.batch_sizes) >= 48  # pow2 padding may add rows
    assert max(model.batch_sizes) >= 8, model.batch_sizes
    assert model.max_concurrent == 1  # max_inflight respected


def test_inflight_cap_holds_across_model_groups():
    """One flush spanning two model objects (MODEL handoff mid-flight) must
    still serialize device calls under max_inflight=1."""
    lock = threading.Lock()
    state = {"concurrent": 0, "max": 0}

    class _Tracked(_CountingModel):
        def top_n_batch(self, qs, how_many, alloweds=None, excluded=None):
            with lock:
                state["concurrent"] += 1
                state["max"] = max(state["max"], state["concurrent"])
            time.sleep(0.03)
            try:
                return super().top_n_batch(qs, how_many, alloweds, excluded)
            finally:
                with lock:
                    state["concurrent"] -= 1

    m1, m2 = _Tracked(), _Tracked()
    coal = TopNCoalescer(window_ms=5.0, max_batch=64, max_inflight=1)

    async def main():
        return await asyncio.gather(*[
            coal.top_n(m1 if i % 2 == 0 else m2, np.array([float(i), 0.0]), 2)
            for i in range(16)
        ])

    results = asyncio.run(main())
    assert len(results) == 16
    for i, res in enumerate(results):
        assert res[0][0] == f"i{i}"
    assert state["max"] == 1, state


def test_deadline_bounds_queue_wait_behind_inflight_batches():
    """A request enqueued behind in-flight batches must flush within the
    configured deadline even if the in-flight call never completes (VERDICT
    r5 #5: the 2.26 s p99 was unbounded queue wait). The coalescer may exceed
    max_inflight by one call to honor the bound."""
    release = threading.Event()

    class _Stuck(_CountingModel):
        def top_n_batch(self, qs, how_many, alloweds=None, excluded=None):
            if float(qs[0][0]) == 1.0:  # the first batch wedges until released
                release.wait(10)
            return super().top_n_batch(qs, how_many, alloweds, excluded)

    model = _Stuck()
    coal = TopNCoalescer(window_ms=1.0, max_batch=64, max_inflight=1,
                         deadline_ms=50.0)

    async def main():
        loop = asyncio.get_running_loop()
        stuck = asyncio.create_task(coal.top_n(model, np.array([1.0, 0.0]), 2))
        await asyncio.sleep(0.02)  # let it dispatch and wedge the only slot
        t0 = loop.time()
        # must NOT wait for the wedged call: deadline forces a second dispatch
        res = await coal.top_n(model, np.array([7.0, 0.0]), 2)
        waited = loop.time() - t0
        assert res[0][0] == "i7"
        assert waited < 5.0, f"queue wait {waited:.3f}s not bounded by deadline"
        assert coal.deadline_flushes >= 1
        release.set()
        r1 = await stuck
        assert r1[0][0] == "i1"

    asyncio.run(main())


def test_deadline_disabled_keeps_strict_inflight_cap():
    """deadline_ms=0 restores the strict cap: nothing dispatches while the
    only slot is busy, so batch-while-busy semantics are unchanged."""
    lock = threading.Lock()
    state = {"concurrent": 0, "max": 0}

    class _Slow(_CountingModel):
        def top_n_batch(self, qs, how_many, alloweds=None, excluded=None):
            with lock:
                state["concurrent"] += 1
                state["max"] = max(state["max"], state["concurrent"])
            time.sleep(0.05)
            try:
                return super().top_n_batch(qs, how_many, alloweds, excluded)
            finally:
                with lock:
                    state["concurrent"] -= 1

    model = _Slow()
    coal = TopNCoalescer(window_ms=1.0, max_batch=64, max_inflight=1,
                         deadline_ms=0.0)

    async def main():
        await asyncio.gather(*[
            coal.top_n(model, np.array([float(i), 0.0]), 2) for i in range(8)
        ])

    asyncio.run(main())
    assert state["max"] == 1
    assert coal.deadline_flushes == 0


def test_device_call_failure_fails_only_that_batch():
    class _Broken(_CountingModel):
        def top_n_batch(self, *a, **kw):
            raise RuntimeError("chip fell over")

    coal = TopNCoalescer(window_ms=2.0, max_batch=8)

    async def main():
        with pytest.raises(RuntimeError, match="chip fell over"):
            await coal.top_n(_Broken(), np.zeros(2), 3)
        # the coalescer still works afterwards
        model = _CountingModel()
        res = await coal.top_n(model, np.array([3.0, 0.0]), 2)
        assert res[0][0] == "i3"

    asyncio.run(main())


def test_http_concurrent_recommends_share_device_calls(monkeypatch, tmp_path):
    """End-to-end: 24 concurrent HTTP /recommend requests must produce far
    fewer top_n_batch device calls, with correct per-user answers."""
    import httpx

    from oryx_tpu.common import config as cfg
    from oryx_tpu.common import ioutils
    from oryx_tpu.models.als import data as d
    from oryx_tpu.models.als import pmml_codec
    from oryx_tpu.models.als import train as tr
    from oryx_tpu.models.als.serving import ALSServingModel
    from oryx_tpu.pmml import pmmlutils
    from oryx_tpu.serving.app import ServingLayer
    from oryx_tpu.transport import topic as tp

    tp.reset_memory_brokers()
    rng = np.random.default_rng(1)
    scores = rng.standard_normal((24, 3)) @ rng.standard_normal((3, 30))
    lines = [
        f"u{u:02d},i{i},1,{u * 100 + int(i)}"
        for u in range(24)
        for i in np.argsort(-scores[u])[:5]
    ]
    batch = d.prepare(lines, implicit=True)
    x, y = tr.als_train(batch, features=4, lam=0.001, alpha=1.0,
                        implicit=True, iterations=3, chunk=256)
    pmml = pmml_codec.model_to_pmml(
        np.asarray(x), np.asarray(y), batch.users.index_to_id,
        batch.items.index_to_id, 4, 0.001, 1.0, True, False, 1e-5, tmp_path,
    )

    calls = {"n": 0, "sizes": []}
    orig = ALSServingModel.top_n_batch

    def counting(self, qs, how_many, alloweds=None, excluded=None):
        calls["n"] += 1
        calls["sizes"].append(len(qs))
        return orig(self, qs, how_many, alloweds, excluded)

    monkeypatch.setattr(ALSServingModel, "top_n_batch", counting)

    port = ioutils.choose_free_port()
    config = cfg.overlay_on(
        {
            "oryx.serving.api.port": port,
            "oryx.serving.model-manager-class":
                "oryx_tpu.models.als.serving.ALSServingModelManager",
            "oryx.serving.application-resources":
                "oryx_tpu.serving.resources.als",
            "oryx.serving.compute.coalesce-window-ms": 5.0,
        },
        cfg.get_default(),
    )
    tp.maybe_create_topics(config, "input-topic", "update-topic")
    prod = tp.TopicProducerImpl("memory:", "OryxUpdate")
    prod.send("MODEL", pmmlutils.to_string(pmml))
    for id_, vec in pmml_codec.read_features(tmp_path / "Y"):
        prod.send("UP", json.dumps(["Y", id_, [float(v) for v in vec]]))
    for id_, vec in pmml_codec.read_features(tmp_path / "X"):
        prod.send("UP", json.dumps(["X", id_, [float(v) for v in vec]]))
    layer = ServingLayer(config)
    layer.start()
    try:
        base = f"http://127.0.0.1:{port}"
        with httpx.Client(base_url=base, timeout=30) as client:
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if client.get("/ready").status_code == 200:
                    break
                time.sleep(0.1)
            else:
                pytest.fail("serving layer never became ready")

        # warm the compile cache so the timed burst coalesces (first call
        # holds the executor for seconds while XLA compiles)
        with httpx.Client(base_url=base, timeout=60) as client:
            assert client.get("/recommend/u00").status_code == 200

        calls["n"], calls["sizes"] = 0, []
        answers: dict[str, list] = {}
        # pre-open connections and release all requests together: the test
        # is about coalescing CONCURRENT arrivals, not thread-start stagger
        barrier = threading.Barrier(24, timeout=30)

        def fetch(u: str):
            with httpx.Client(base_url=base, timeout=60) as client:
                client.get("/ready")
                barrier.wait()
                r = client.get(f"/recommend/{u}?howMany=4")
                assert r.status_code == 200
                answers[u] = r.json()

        threads = [
            threading.Thread(target=fetch, args=(f"u{u:02d}",))
            for u in range(24)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert len(answers) == 24
        # far fewer device calls than requests (perfect coalescing would be
        # 1; scheduling jitter allows a few flushes)
        assert calls["n"] <= 12, (calls["n"], calls["sizes"])
        # batches pad to powers of two (stable jit signatures), so the
        # device saw >= 24 rows in pow2-sized batches
        assert sum(calls["sizes"]) >= 24
        assert all(s & (s - 1) == 0 for s in calls["sizes"]), calls["sizes"]
        # answers are per-user correct: compare against the direct model path
        model = layer.manager.get_model()
        for u in ("u00", "u11", "u23"):
            uv = model.get_user_vector(u)
            want = model.top_n(uv, 4, excluded=model.get_known_items(u))
            got = [e["id"] for e in answers[u]]
            assert got == [i for i, _ in want]
    finally:
        layer.close()
        tp.reset_memory_brokers()


def test_dispatch_failure_releases_inflight_and_fails_futures():
    """run_in_executor raising at dispatch (executor/loop shut down
    mid-close) must release the _inflight slot and fail the group's futures
    — before the fix the slot leaked forever and every pending request
    behind it hung until client timeout (ADVICE r5)."""
    model = _CountingModel()
    coal = TopNCoalescer(window_ms=0.5, max_batch=8)
    boom = RuntimeError("executor is shut down")

    async def main():
        loop = asyncio.get_running_loop()
        real = loop.run_in_executor
        fail = {"armed": True}

        def broken(executor, fn, *args):
            if fail["armed"]:
                raise boom
            return real(executor, fn, *args)

        loop.run_in_executor = broken
        try:
            with pytest.raises(RuntimeError, match="shut down"):
                await coal.top_n(model, np.array([1.0, 0.0]), 3)
        finally:
            loop.run_in_executor = real
        assert coal._inflight == 0  # slot released, not leaked
        # the coalescer still works once dispatch recovers
        fail["armed"] = False
        res = await coal.top_n(model, np.array([2.0, 0.0]), 3)
        assert res[0][0] == "i2"

    asyncio.run(main())


# -- the gate (ISSUE 34): a model that REPORTS its device phase, under a
# controlled clock (tests/coalescer_sim.py). Times in ms of the virtual
# clock; the fake's host stage is 1, its scan 10, its post-scan work 0.5.

_H, _S, _P = 0.001, 0.010, 0.0005


def _sim(scan_s=_S, reports=True, lag_s=0.0, **coalescer):
    from tests.coalescer_sim import FifoDevice, SimModel, VirtualLoop

    loop = VirtualLoop()
    device = FifoDevice(loop, lambda b: scan_s)
    model = SimModel(loop, device, _H, _P, reports, lag_s)
    coal = TopNCoalescer(window_ms=1.0, max_batch=64, **coalescer)
    return loop, device, model, coal


async def _ask(coal, model, n):
    res = await coal.top_n(model, np.array([float(n), 0.0]), 1)
    assert res == [(f"i{n}", 0.0)]


async def _one_behind_another(coal, model):
    """A request onto a free chip, a second one 3 ms later: the second finds
    the first's flush in its device phase. Returns the instant the first
    arrived."""
    loop = asyncio.get_running_loop()
    t0 = loop.time()
    first = asyncio.create_task(_ask(coal, model, 1))
    await asyncio.sleep(0.003)
    await _ask(coal, model, 2)
    await first
    return t0


async def _learn(coal, model):
    """What a model's first flushes show: one alone on the chip (the host
    stage), then one whose programs sit behind another's, as the slots make
    them while a width's scan is unknown (the scan)."""
    await _ask(coal, model, 8)
    await _one_behind_another(coal, model)
    await asyncio.sleep(0.05)


def test_second_flush_opens_when_the_chip_will_be_free_for_it():
    loop, device, model, coal = _sim()

    async def main():
        await _learn(coal, model)
        assert coal._s[1] == pytest.approx(_S)
        return await _one_behind_another(coal, model)

    t0 = loop.run(main())
    (first_t, _), (second_t, _) = model.calls[3:]
    # the first: the window, then enqueued at +2 and done at +12
    assert first_t == pytest.approx(t0 + 0.001)
    # the second waited at the gate: not at +4 (its window over, a slot
    # free: where the slots alone put it), but at predicted free - h
    assert second_t == pytest.approx(t0 + 0.012 - _H)
    # so its programs reached the device as it came free
    assert device.waits[-1] == pytest.approx(0.0, abs=1e-9)
    assert model.most_in_flight == 2


def test_with_no_estimate_the_second_flush_opens_at_device_done():
    loop, device, model, coal = _sim()
    t0 = loop.run(_one_behind_another(coal, model))
    (first_t, _), (second_t, _) = model.calls
    assert first_t == pytest.approx(t0 + 0.001)
    assert second_t == pytest.approx(t0 + 0.012)  # the report itself
    assert device.waits == [0.0, 0.0]


def test_a_model_that_reports_nothing_is_scheduled_by_the_slots():
    loop, device, model, coal = _sim(reports=False)
    t0 = loop.run(_one_behind_another(coal, model))
    (_, _), (second_t, _) = model.calls
    assert second_t == pytest.approx(t0 + 0.004)  # its window, a free slot
    assert device.waits[-1] == pytest.approx(0.007)  # behind the first's scan


def test_a_change_of_model_object_resets_the_estimates():
    from tests.coalescer_sim import SimModel

    loop, device, model, coal = _sim()
    slower = SimModel(loop, device, _H, _P)
    device.scan_s = lambda b: _S if not slower.calls else 2 * _S

    async def main():
        await _learn(coal, model)
        return await _one_behind_another(coal, slower)

    t0 = loop.run(main())
    (_, _), (second_t, _) = slower.calls
    # by the old model's scan (10) the gate would have opened at +11; the
    # new object's first flushes have no estimate: its report, at +22
    assert second_t == pytest.approx(t0 + 0.022)


def test_the_gate_keeps_the_cap_and_the_deadline_takes_one_over_it():
    """A wedged device: no report comes and no estimate opens the gate. The
    deadline flushes past the gate inside the cap, then ONE call past the
    cap, then nothing more."""
    loop, device, model, coal = _sim(scan_s=10.0, max_inflight=2,
                                     deadline_ms=50.0)

    async def main():
        tasks = []
        for n in range(5):
            tasks.append(asyncio.create_task(_ask(coal, model, n)))
            await asyncio.sleep(0.1)
            assert coal._inflight <= 3
        await asyncio.sleep(1.0)
        while_wedged = len(model.calls), coal.deadline_flushes
        await asyncio.gather(*tasks)
        return while_wedged

    assert loop.run(main()) == (3, 1)
    assert model.most_in_flight == 3  # the cap of 2, and the one over it
    assert [round(t, 3) for t, _ in model.calls[:3]] == [0.001, 0.15, 0.25]


def test_a_report_outside_a_flush_goes_nowhere():
    from oryx_tpu.common import devicephase

    devicephase.enqueued()  # a direct top_n, the warm ladder: no reporter
    devicephase.device_done()
    loop, device, model, coal = _sim()

    async def main():
        await _ask(coal, model, 1)
        # the same model called directly, beside the coalescer
        await asyncio.get_running_loop().run_in_executor(
            None, model.top_n_batch, np.array([[3.0, 0.0]]), 1)

    loop.run(main())
    assert len(model.calls) == 2
    assert len(coal._host) == 1 and not coal._scan  # nothing sat in a queue
    assert coal._last is None and coal._inflight == 0


def _wakeups(loop):
    """The instants an executor thread woke ``loop`` for a report."""
    woken = []
    real = loop.call_soon_threadsafe

    def spy(callback, *args):
        if getattr(callback, "__func__", None) is TopNCoalescer._kick:
            woken.append(loop.time())
        return real(callback, *args)

    loop.call_soon_threadsafe = spy
    return woken


def test_a_report_wakes_the_loop_only_for_requests_held_at_the_gate():
    """A wakeup hands the interpreter to the loop in the middle of the
    flush's own path (between its results being ready and their copy
    back): a flush nobody waits behind stamps its reports and leaves the
    loop alone; one with requests held behind it wakes it."""
    loop, device, model, coal = _sim()
    woken = _wakeups(loop)

    async def alone():
        for n in (8, 9):
            await _ask(coal, model, n)

    loop.run(alone())
    assert woken == []
    assert len(coal._host) == 2  # read all the same, at the calls' ends

    loop, device, model, coal = _sim()
    woken = _wakeups(loop)
    t0 = loop.run(_one_behind_another(coal, model))
    # held from +3 behind a flush enqueued at +2 with no estimate of its
    # scan: the *device done* report at +12 is what opens the gate
    assert woken == [pytest.approx(t0 + 0.012)]


def test_a_request_held_under_the_host_stage_is_aimed_by_the_estimate():
    """Held before the flush ahead has launched, the gate is aimed from the
    host stage the last flushes took; the *enqueued* report is a stamp and
    wakes nobody."""
    loop, device, model, coal = _sim()
    woken = _wakeups(loop)

    async def main():
        await _learn(coal, model)
        del woken[:]
        t0 = asyncio.get_running_loop().time()
        first = asyncio.create_task(_ask(coal, model, 1))
        await asyncio.sleep(0.0015)  # the first's flush opened at +1
        await _ask(coal, model, 2)
        await first
        return t0

    t0 = loop.run(main())
    assert woken == []
    (_, _), (second_t, _) = model.calls[3:]
    assert second_t == pytest.approx(t0 + 0.012 - _H)
    assert device.waits[-1] == pytest.approx(0.0, abs=1e-9)


def test_a_scan_not_worth_a_wait_is_scheduled_by_the_slots():
    """A scan of 4 ms behind a host stage of 1 and reports that trail the
    device by 2.2: by the slots alone a flush's programs sit 0.8 ms in the
    device's queue, under a third of the scan, so the gate stands open and
    the reporting model is scheduled as one that reports nothing, call for
    call — once its first flushes have shown the three times."""
    def settled_calls(reports):
        loop, device, model, coal = _sim(scan_s=0.004, reports=reports,
                                         lag_s=0.0022)
        woken = _wakeups(loop)

        async def main():
            await _learn(coal, model)  # the host stage, then the scan
            for n in range(8):  # and alone on the chip: the lag
                await _ask(coal, model, n)
            del woken[:]
            t0 = asyncio.get_running_loop().time()
            for _ in range(3):
                await asyncio.sleep(0.05)
                await _one_behind_another(coal, model)
            return t0

        t0 = loop.run(main())
        if reports:
            assert coal._s[1] == pytest.approx(0.004)
            assert coal._lag_s == pytest.approx(0.0022)
        return [round(t - t0, 6) for t, _ in model.calls if t >= t0], woken

    reporting, woken = settled_calls(True)
    silent, _ = settled_calls(False)
    assert reporting == silent and len(silent) == 6
    assert woken == []


# -- what the call span says of the device phase (ISSUE 35) ------------------


def _device_calls():
    from oryx_tpu.common import spans

    return sorted((s for s in spans.default_recorder().spans()
                   if s.name == "coalescer.device_call"),
                  key=lambda s: s.start_walltime)


@pytest.fixture
def recorded_spans():
    from oryx_tpu.common import spans

    spans.default_recorder().reset()
    spans.set_enabled(True)
    yield spans
    spans.set_enabled(True)
    spans.default_recorder().reset()


@pytest.mark.parametrize("reports", [True, False], ids=["reports", "silent"])
def test_the_call_span_holds_the_device_phase_only_where_it_was_reported(
        recorded_spans, reports):
    """``enqueued_ms`` / ``device_done_ms`` are the call's own reports as
    offsets from the span's start: absent for a model that reports nothing,
    whose span still says what the gate believed (nothing yet)."""
    loop, device, model, coal = _sim(reports=reports)
    t0 = loop.run(_one_behind_another(coal, model))
    first, second = _device_calls()
    for call, (opened, _), (_, end) in zip((first, second), model.calls,
                                           device.runs):
        at = call.attributes
        if reports:
            assert at["enqueued_ms"] == pytest.approx(_H * 1e3)
            assert at["device_done_ms"] == pytest.approx((end - opened) * 1e3)
        else:
            assert "enqueued_ms" not in at and "device_done_ms" not in at
        # no call of this model was over when either was opened
        assert (at["gate.h_ms"], at["gate.lag_ms"]) == (0.0, 0.0)
        assert at["gate.engaged"] is False and "gate.scan_ms" not in at
        assert "gate.free_in_ms" not in at and "gate.late_ms" not in at
        assert set(at) - {"enqueued_ms", "device_done_ms"} == {
            "route", "call", "batch.size", "batch.padded", "pad.waste_rows",
            "opened_by", "queue_wait_max_ms", "gate.h_ms", "gate.lag_ms",
            "gate.engaged"}
    assert first.attributes["opened_by"] == "window"
    assert model.calls[0][0] == pytest.approx(t0 + 0.001)


def test_the_call_span_holds_the_estimates_as_they_stood_at_the_open(
        recorded_spans):
    """After a model's first flushes have shown h, S and (none) lag, a
    flush opened behind another carries them, the aim, and — opened by the
    gate's timer — how late that timer ran (on time, on this loop)."""
    loop, device, model, coal = _sim()

    async def main():
        await _learn(coal, model)
        return await _one_behind_another(coal, model)

    t0 = loop.run(main())
    first, second = _device_calls()[-2:]
    at = second.attributes
    assert at["opened_by"] == "anticipated" and at["gate.engaged"] is True
    assert (at["gate.h_ms"], at["gate.lag_ms"], at["gate.scan_ms"]) == (
        pytest.approx(_H * 1e3), 0.0, pytest.approx(_S * 1e3))
    assert at["gate.late_ms"] == pytest.approx(0.0, abs=1e-6)
    # opened at free − h: the device was to be free h later, and was
    assert at["gate.free_in_ms"] == pytest.approx(_H * 1e3)
    assert device.runs[-2][1] == pytest.approx(t0 + 0.012)
    assert "gate.free_in_ms" not in first.attributes  # behind nothing


def test_with_spans_off_a_flush_runs_none_of_the_device_phase_bookkeeping(
        monkeypatch):
    """ISSUE 35 adds nothing to a flush's path with tracing off but
    identity checks on the span object: what assembles the attributes is
    never entered, and the wait PR 34 computed on every flush is gone."""
    from oryx_tpu.common import spans
    from oryx_tpu.serving import batcher

    entered = []

    def counting(name, real):
        def wrapper(*a, **kw):
            entered.append(name)
            return real(*a, **kw)
        return wrapper

    monkeypatch.setattr(batcher.TopNCoalescer, "_believed", counting(
        "believed", batcher.TopNCoalescer._believed))
    monkeypatch.setattr(batcher, "_tell_device_phase", counting(
        "tell", batcher._tell_device_phase))
    monkeypatch.setattr(batcher, "_ms", counting("ms", batcher._ms))
    # a flush holds its stamps and the two reports that set them
    assert {n for n, v in vars(batcher._Flush).items()
            if callable(v) and not n.startswith("_")} == {
        "enqueued", "device_done"}

    def five_flushes():
        loop, device, model, coal = _sim()

        async def main():
            await _learn(coal, model)
            await _one_behind_another(coal, model)

        loop.run(main())  # every flush answered its request (_ask asserts)
        assert len(model.calls) == 5 and coal._inflight == 0

    spans.set_enabled(False)
    try:
        five_flushes()
    finally:
        spans.set_enabled(True)
    assert entered == []
    five_flushes()
    assert entered.count("believed") == entered.count("tell") == 5
