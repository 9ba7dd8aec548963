"""The stage spans of a coalesced ``/recommend`` (ISSUE 24): a request
through the real ``make_app`` + coalescer + a small ``ALSServingModel``
leaves, per trace, the ingress span, its queue wait and ``serving.render``,
and per flush the seven stages of the device call — its children, end to
end with no hole, each carrying ``call``; the same stages are in a
``jax.profiler`` capture on the profiler's clock, where
``start_walltime - profile_start_time`` puts the ring's span; and with spans
off none of it is built.
"""

import asyncio
import glob
import os
import time

import numpy as np
import pytest

from oryx_tpu.common import config as cfg
from oryx_tpu.common import spans
from oryx_tpu.models.als.serving import ALSServingModel
from oryx_tpu.serving import batcher
from oryx_tpu.serving.app import make_app

FLUSH_STAGES = (
    "coalescer.handoff", "coalescer.assemble", "topn.upload",
    "topn.dispatch", "topn.wait_download", "topn.ids", "coalescer.wakeup",
)
ANNOTATED = ("coalescer.assemble", "topn.upload", "topn.dispatch",
             "topn.wait_download", "topn.ids", "serving.render")
K, N_ITEMS, N_USERS = 8, 4000, 16


@pytest.fixture(autouse=True)
def _fresh_recorder():
    spans.default_recorder().reset()
    spans.set_enabled(True)
    yield
    spans.set_enabled(True)


class _Manager:
    rescorer_provider = None

    def __init__(self, model):
        self.model = model

    def get_model(self):
        return self.model

    def is_read_only(self):
        return True


@pytest.fixture(scope="module")
def model():
    rng = np.random.default_rng(24)
    m = ALSServingModel(K, True, 1.0)
    m.bulk_load_items([f"i{j}" for j in range(N_ITEMS)],
                      rng.standard_normal((N_ITEMS, K), dtype=np.float32))
    m.bulk_load_users([f"u{j}" for j in range(N_USERS)],
                      rng.standard_normal((N_USERS, K), dtype=np.float32))
    for b in (1, 2, 4):  # compile outside every test's spans
        m.top_n_batch(np.zeros((b, K), dtype=np.float32), 10)
    return m


def _app(model, spans_enabled):
    # make_app applies ``oryx.tracing.spans.enabled``: the switch is the
    # configuration's, as in a deployment
    return make_app(cfg.overlay_on({
        "oryx.serving.application-resources": "oryx_tpu.serving.resources.als",
        "oryx.serving.compute.precompile-batches": False,
        "oryx.tracing.spans.enabled": spans_enabled,
    }, cfg.get_default()), _Manager(model))


def _serve(model, users, before_answer=None, spans_enabled=True):
    """GET /recommend for ``users`` at once through the real app; returns
    the answers' trace ids (None with spans off)."""
    from aiohttp.test_utils import TestClient, TestServer

    async def main():
        async with TestClient(TestServer(_app(model, spans_enabled))) as client:
            async def one(u):
                resp = await client.get(f"/recommend/{u}?howMany=5")
                body = await resp.json()
                assert resp.status == 200 and len(body) == 5, body
                if before_answer:
                    before_answer(resp)
                return resp.headers.get("x-oryx-trace-id")
            return await asyncio.gather(*[one(u) for u in users])

    return asyncio.run(main())


def _by_name(span_list):
    out = {}
    for s in span_list:
        out.setdefault(s.name, []).append(s)
    return out


def _end(s):
    return s.start_walltime + s.duration


@pytest.mark.parametrize("n_requests", [1, 3])
def test_every_request_and_flush_leaves_its_stage_spans(model, n_requests):
    trace_ids = _serve(model, [f"u{j}" for j in range(n_requests)])
    assert len(set(trace_ids)) == n_requests
    rec = spans.default_recorder()
    all_spans = rec.spans()
    calls = [s for s in all_spans if s.name == "coalescer.device_call"]
    assert calls and sum(c.attributes["batch.size"] for c in calls) == n_requests
    for tid in trace_ids:
        mine = _by_name(rec.spans(trace_id=tid))
        ingress = [s for n, ss in mine.items() if n.startswith("http GET")
                   for s in ss]
        assert len(ingress) == 1
        for name in ("coalescer.queue_wait", "serving.render"):
            assert len(mine[name]) == 1, (name, sorted(mine))
            assert mine[name][0].parent_id == ingress[0].span_id
        # render lies inside the ingress span, after the queue wait
        render = mine["serving.render"][0]
        assert _end(mine["coalescer.queue_wait"][0]) <= render.start_walltime
        assert _end(render) <= _end(ingress[0]) + 1e-4
    for call in calls:
        assert call.attributes["call"] == call.span_id
        stages = [s for s in all_spans
                  if s.attributes.get("call") == call.span_id and s is not call]
        assert sorted(s.name for s in stages) == sorted(FLUSH_STAGES)
        by = {s.name: s for s in stages}
        for s in stages:  # a child of the call span, in its trace
            assert s.parent_id == call.span_id and s.trace_id == call.trace_id
        # end to end: each stage starts where the last ended (1 ms of slack
        # on a CPU), the first with the call span, the wakeup at its close
        assert abs(by["coalescer.handoff"].start_walltime
                   - call.start_walltime) < 1e-3
        for a, b in zip(FLUSH_STAGES[:-2], FLUSH_STAGES[1:-1]):
            hole = by[b].start_walltime - _end(by[a])
            assert -1e-4 < hole < 1e-3, (a, b, hole)
        assert abs(_end(by["topn.ids"]) - _end(call)) < 1e-3
        assert abs(by["coalescer.wakeup"].start_walltime - _end(call)) < 1e-3
        inside = sum(by[n].duration for n in FLUSH_STAGES[:-1])
        assert inside == pytest.approx(call.duration, abs=2e-3)


def test_a_device_call_says_what_opened_it_and_where_its_device_phase_lay(model):
    """ISSUE 34: ``opened_by`` is the counter's label, flush for flush.
    ISSUE 35: the call span says where the real model reported its device
    phase (``enqueued_ms`` ≤ ``device_done_ms``, offsets from the span's
    start, inside it) and what the gate believed when the flush was opened;
    ``topn.dispatch`` names the programs it launched as a profile names
    their modules, ``topn.wait_download`` where its second copy began."""
    before = dict(batcher._FLUSH_OPENED.samples())
    for _ in range(3):
        _serve(model, [f"u{j}" for j in range(4)])
    ring = spans.default_recorder().spans()
    calls = [s for s in ring if s.name == "coalescer.device_call"]
    assert calls
    opened = {}
    for call in calls:
        by = call.attributes["opened_by"]
        assert by in ("window", "full", "anticipated", "device_free",
                      "completion", "deadline")
        opened[(by,)] = opened.get((by,), 0) + 1
        at = call.attributes
        assert 0.0 <= at["enqueued_ms"] <= at["device_done_ms"]
        assert at["device_done_ms"] <= call.duration * 1e3 + 1e-3
        assert at["gate.h_ms"] >= 0.0 and at["gate.lag_ms"] >= 0.0
        assert at["gate.engaged"] in (True, False)
        assert ("gate.late_ms" in at) == (by == "anticipated")
        stages = {s.name: s for s in ring
                  if s.attributes.get("call") == call.span_id}
        assert stages["topn.dispatch"].attributes["programs"] == [
            "jit__top_k_dot_batch"]
        download = stages["topn.wait_download"]
        assert (0.0 <= download.attributes["first_copy_ms"]
                <= download.duration * 1e3)
    after = dict(batcher._FLUSH_OPENED.samples())
    assert {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)} == opened
    assert sum(opened.values()) == len(calls)


def test_call_span_is_in_the_ring_before_any_waiter_resumes(model):
    """batcher.py's rule survives the wakeup span: whoever has an answer
    finds the device call that gave it in the ring."""
    seen = []

    def before_answer(resp):
        tid = resp.headers["x-oryx-trace-id"]
        ring = spans.default_recorder().spans()
        seen.append(any(
            s.name == "coalescer.device_call"
            and tid in [s.trace_id] + [c.trace_id for c in s.links]
            for s in ring))

    _serve(model, ["u1", "u2", "u3", "u4"], before_answer)
    assert seen == [True] * 4


def _capture(tmp_path, body):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1  # the benchmark harness's capture
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[-1]
    start_s, events = None, {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            start_s = dict(plane.stats)["profile_start_time"] * 1e-9
        for line in plane.lines:
            for e in line.events:
                if e.name in ANNOTATED:
                    events.setdefault(e.name, []).append(
                        (e.start_ns * 1e-9, e.duration_ns * 1e-9))
    return start_s, events


@pytest.mark.parametrize("stage", ANNOTATED)
def test_a_stage_is_in_the_profile_where_its_span_says(model, tmp_path, stage):
    """One clock: the stage's annotation in the xplane and its span in the
    ring agree, through the offset the benchmark's reader uses
    (``start_walltime - profile_start_time``), to within 1 ms."""
    start_s, events = _capture(
        tmp_path, lambda: _serve(model, ["u5", "u6"]))
    assert start_s is not None
    ring = [s for s in spans.default_recorder().spans() if s.name == stage]
    assert ring and len(events.get(stage, [])) == len(ring)
    for s in ring:
        at = s.start_walltime - start_s
        nearest = min(events[stage], key=lambda e: abs(e[0] - at))
        assert abs(nearest[0] - at) < 1e-3, (stage, at, nearest)
        assert abs(nearest[1] - s.duration) < 1e-3


def test_with_spans_off_no_span_is_built_on_the_request_path(model, monkeypatch):
    built = []
    real_init = spans.Span.__init__

    def counting_init(self, name, *a, **kw):
        built.append(name)
        real_init(self, name, *a, **kw)

    monkeypatch.setattr(spans.Span, "__init__", counting_init)
    real_annotation = spans._trace_annotation

    def counting_annotation(name):
        built.append("annotation " + name)
        return real_annotation(name)

    monkeypatch.setattr(spans, "_trace_annotation", counting_annotation)
    assert _serve(model, ["u7", "u8"], spans_enabled=False) == [None, None]
    assert built == []  # render, _execute and _top_n_batch among them
    _serve(model, ["u7"])
    assert {"serving.render", "annotation serving.render",
            *FLUSH_STAGES} <= set(built)


def test_with_spans_off_the_download_reads_no_clock(monkeypatch):
    """ISSUE 35's stamp after the first copy exists only on a recorded
    stage: with spans off ``_download`` and ``_dispatch``'s bookkeeping make
    no clock call and build no list of program names."""
    from oryx_tpu.models.als import serving as als_serving

    class _NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"time.{name} read with spans off")

    spans.set_enabled(False)
    monkeypatch.setattr(spans, "time", _NoClock())
    vals, idx = np.arange(6.0).reshape(2, 3), np.arange(6).reshape(2, 3)
    with spans.activate(None):
        got = als_serving._download((vals, idx))
    assert got[0] is vals and got[1] is idx
    monkeypatch.undo()
    # on a recorded stage the stamp lies inside the stage, in ms
    spans.set_enabled(True)
    with spans.span("caller") as caller:
        als_serving._download((vals, idx))
    (stage,) = [s for s in spans.default_recorder().spans(
        trace_id=caller.trace_id) if s.name == "topn.wait_download"]
    assert 0.0 <= stage.attributes["first_copy_ms"] <= stage.duration * 1e3


def test_with_spans_off_the_dispatch_sets_nothing_and_reads_no_clock(
        model, monkeypatch):
    """ISSUE 37's ``copies_ahead`` exists only on a recorded stage: with spans
    off ``_dispatch`` asks for its two copies and writes no attribute, nor
    reads a clock; recorded, its ``topn.dispatch`` says it asked for two."""
    from oryx_tpu.models.als import serving as als_serving

    class _NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"time.{name} read with spans off")

    snap = model.y_snapshot()
    qs = np.zeros((2, K), dtype=np.float32)
    width = snap.batch_width(10, False)
    written = []
    spans.set_enabled(False)
    monkeypatch.setattr(spans, "time", _NoClock())
    monkeypatch.setattr(type(spans.NOOP_SPAN), "set_attribute",
                        lambda self, key, value: written.append(key))
    with spans.activate(None):
        out = als_serving._dispatch(snap, qs, width, register=False)
    monkeypatch.undo()
    assert written == []
    vals, idx = als_serving._download(out)
    assert vals.shape == idx.shape == (2, width[0])
    spans.set_enabled(True)
    with spans.span("caller") as caller:
        als_serving._download(
            als_serving._dispatch(snap, qs, width, register=False))
    (stage,) = [s for s in spans.default_recorder().spans(
        trace_id=caller.trace_id) if s.name == "topn.dispatch"]
    assert stage.attributes["copies_ahead"] == 2
    assert stage.attributes["programs"] == ["jit__top_k_dot_batch"]


def test_a_stage_never_starts_a_trace_of_its_own(model):
    """A direct call of the model (the warm ladder, a test) has no caller's
    span: its stages record nothing rather than seven orphan roots."""
    model.top_n_batch(np.zeros((2, K), dtype=np.float32), 10)
    assert spans.default_recorder().spans() == []
    with spans.span("caller") as caller:
        model.top_n_batch(np.zeros((2, K), dtype=np.float32), 10)
    got = spans.default_recorder().spans(trace_id=caller.trace_id)
    stages = [s for s in got if s.name.startswith("topn.")]
    assert sorted(s.name for s in stages) == sorted(
        n for n in FLUSH_STAGES if n.startswith("topn."))
    assert {s.attributes["call"] for s in stages} == {caller.span_id}


@pytest.mark.parametrize("n", [1, 5])
def test_wakeup_ends_when_the_last_waiter_has_its_result(n):
    """``coalescer.wakeup`` closes on the loop, after the flush's last
    future is resolved, and once a flush."""

    class _Model:
        def top_n_batch(self, qs, want, alloweds=None, excluded=None):
            return [[("i0", 1.0)]] * len(qs)

    resolved_at = []

    async def main():
        coal = batcher.TopNCoalescer(window_ms=5.0, max_batch=8)
        m = _Model()

        async def one():
            out = await coal.top_n(m, np.zeros(4, np.float32), 1)
            resolved_at.append(time.time())
            return out

        await asyncio.gather(*[one() for _ in range(n)])
        await asyncio.sleep(0.01)

    asyncio.run(main())
    got = _by_name(spans.default_recorder().spans())
    assert len(got["coalescer.device_call"]) == 1
    (wakeup,) = got["coalescer.wakeup"]
    call = got["coalescer.device_call"][0]
    assert wakeup.parent_id == call.span_id
    assert wakeup.attributes == {"call": call.span_id}
    assert wakeup.start_walltime >= _end(call) - 1e-4
    # the waiters resume after their futures resolve, i.e. after the span
    assert _end(wakeup) <= max(resolved_at) + 1e-3


def test_ids_are_unique_hex_and_cost_no_syscall():
    ids = {spans.new_span_id() for _ in range(20000)}
    assert len(ids) == 20000 and all(len(i) == 16 for i in ids)
    assert len(spans.new_trace_id()) == 32
    import random

    assert type(spans._rand) is random.Random  # not SystemRandom: no getrandom
