"""The warm ladder is the dispatch (ISSUE 28): whichever scan backend a model
resolves to, ``warm_bucket`` compiles what its snapshot's ``plan`` returns for
shapes and a flush dispatches what the same ``plan`` returns for arrays — so
after the ladder a batch of that size, with no exclusions or with histories of
any length (ISSUE 30: a closed set of over-fetched widths), registers no cost
key and compiles nothing. The benchmark's ``compiles_in_window`` limit
is 0 in every serving cell; this is what holds it for every backend at once."""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from oryx_tpu.common import compilecache
from oryx_tpu.models.als.serving import ALSServingModel
from oryx_tpu.parallel.mesh import make_mesh
from oryx_tpu.tools import sanitize

FEATURES = 16
N_ITEMS = 603  # not a multiple of the four shards

BACKENDS = {
    "flat": {},
    "flat+lsh": {"sample_rate": 0.3},
    "int8": {"device_dtype": "int8"},
    "int8+lsh": {"device_dtype": "int8", "sample_rate": 0.3},
    "ivf": {"device_dtype": "int8", "index_enabled": True},
    "mesh": {"mesh": True},
}


def _model(backend: str) -> ALSServingModel:
    options = dict(BACKENDS[backend])
    if options.pop("mesh", False):
        options["mesh"] = make_mesh(4, axes=("model",))
    model = ALSServingModel(FEATURES, implicit=True, **options)
    y = np.random.default_rng(28).standard_normal(
        (N_ITEMS, FEATURES), dtype=np.float32)
    model.bulk_load_items([f"i{j}" for j in range(N_ITEMS)], y)
    return model


def _signature(fn, args):
    """A program and the abstract values of its operands (an array or a
    ``ShapeDtypeStruct`` by shape and dtype, a static by its value)."""
    return fn, tuple(
        (tuple(a.shape), str(a.dtype)) if hasattr(a, "shape") else a
        for a in args)


@pytest.mark.parametrize("bucket", [4, 32])
@pytest.mark.parametrize("backend", list(BACKENDS))
def test_after_warm_bucket_a_batch_registers_and_compiles_nothing(
        backend, bucket, monkeypatch):
    model = _model(backend)
    compiled: dict = {}
    aot_compile = compilecache.aot_compile

    def spy(fn, *args, cost_key=None):
        compiled.setdefault(cost_key, []).append(_signature(fn, args))
        return aot_compile(fn, *args, cost_key=cost_key)

    monkeypatch.setattr(compilecache, "aot_compile", spy)
    compilecache.install_compile_listener()
    cold = compilecache.compiles_total()
    model.warm_bucket(bucket, 10)
    assert compilecache.compiles_total() - cold >= 3
    snap = model.y_snapshot()
    assert (model.lsh is not None) == backend.endswith("+lsh")
    warmed = set(snap.cost_keys_attempted)
    assert len(warmed) >= 3 and all(f"/b{bucket}" in k for k in warmed)
    assert set(compiled) == warmed
    ladder = {key: sigs[0] for key, sigs in compiled.items()}

    qs = np.random.default_rng(bucket).standard_normal(
        (bucket, FEATURES), dtype=np.float32)
    # a history a room (topn._OVERFETCH_ROOM), and one past the widest
    histories = [[f"i{j}" for j in range(1, 1 + length)]
                 for length in (3, 100, 500)]
    before = compilecache.compiles_total()
    plain = model.top_n_batch(qs, 10)
    assert len(plain) == bucket
    for history in histories:
        excluding = model.top_n_batch(
            qs, 10, excluded=[history] + [None] * (bucket - 1))
        assert len(excluding) == bucket and len(excluding[0]) == 10
        assert not set(history) & {i for i, _ in excluding[0]}
        assert excluding[1] == plain[1]
    assert model.y_snapshot() is snap
    assert set(snap.cost_keys_attempted) == warmed
    assert compilecache.compiles_total() - before == 0

    # and what a flush dispatches IS what the ladder compiled: a flush's
    # first-use registration hands aot_compile the very program and operands
    # it then calls, so forget the marks and let the batches register
    compiled.clear()
    snap.cost_keys_attempted.clear()
    model.top_n_batch(qs, 10)
    for history in histories:
        model.top_n_batch(qs, 10, excluded=[history] + [None] * (bucket - 1))
    assert {key: sigs[0] for key, sigs in compiled.items()} == ladder


@pytest.mark.parametrize("backend", ["flat", "mesh"])
def test_a_float_view_flush_takes_no_lock_under_the_snapshot_lock(backend):
    """The store is materialized BEFORE the model's snapshot lock, as it
    always was: with its lock taken under that one, the lock sanitizer
    formats a stack at every new thread's first flush (its first sight of
    the order), 0.3 ms between ``coalescer.assemble`` and ``topn.upload``,
    which grew past ``test_stage_spans``'s 1 ms beside five other workers."""
    if not sanitize.enabled("locks"):
        pytest.skip("lock sanitizer not installed (ORYX_SANITIZE=off)")
    model = _model(backend)
    qs = np.zeros((4, FEATURES), dtype=np.float32)
    model.top_n_batch(qs, 10)
    with sanitize.isolated() as (graph, _watch):
        flush = threading.Thread(target=model.top_n_batch, args=(qs, 10))
        flush.start()
        flush.join()
        nested = [edge for edge in graph.edges() if "als/serving.py" in edge[0]]
    assert not nested


@pytest.mark.parametrize("backend", list(BACKENDS))
def test_a_flush_reports_its_device_phase(backend, monkeypatch):
    """ISSUE 34, for every backend at once (the hooks are written once, in
    ``_dispatch`` / ``_download``): *enqueued* once the last program is
    launched, before anything waits for it; *device done* once
    ``topn.wait_download`` has the arrays and before the ids are decoded;
    each once, and the call's answers are those of a call nobody
    scheduled."""
    from oryx_tpu.common import devicephase
    from oryx_tpu.common import spans

    model = _model(backend)
    qs = np.random.default_rng(34).standard_normal(
        (4, FEATURES)).astype(np.float32)
    unscheduled = model.top_n_batch(qs, 10)
    events = []
    real_stage = spans.stage

    def stage(name, *args, **kwargs):
        events.append(name)
        return real_stage(name, *args, **kwargs)

    class Reporter:
        def enqueued(self):
            events.append("enqueued")

        def device_done(self):
            events.append("device_done")

    monkeypatch.setattr(spans, "stage", stage)
    with devicephase.reporting(Reporter()):
        scheduled = model.top_n_batch(qs, 10)
    assert events.count("enqueued") == events.count("device_done") == 1
    flush = [e for e in events if e in ("enqueued", "device_done",
                                        "topn.dispatch", "topn.wait_download",
                                        "topn.ids")]
    assert flush[:5] == ["topn.dispatch", "enqueued", "topn.wait_download",
                         "device_done", "topn.ids"]
    assert scheduled == unscheduled


# ISSUE 37: a flush asks for the copy back of its last program's two results
# at the launch. Every backend, and the known-item form of the flat one (the
# default /recommend hands its user's known items over as codes).
CASES = [*BACKENDS, "known"]


def _case_model(case: str) -> ALSServingModel:
    model = _model("flat" if case == "known" else case)
    if case == "known":
        model.add_known_items("u0", [f"i{j}" for j in range(1, 40)])
    return model


def _flush(model, case: str, qs):
    """One flush of ``qs``; in the known case the first query leaves out its
    user's known items as the default ``/recommend`` hands them over."""
    excluded = None
    if case == "known":
        excluded = [model.known_item_codes("u0")] + [None] * (len(qs) - 1)
    return model.top_n_batch(qs, 10, excluded=excluded)


@pytest.fixture
def copies(monkeypatch):
    """``asked``: every array whose copy to the host was asked for ahead, in
    order; ``dispatched``: every ``_dispatch``'s result; ``fed``: every step
    result a plan's next step was handed."""
    from oryx_tpu.models.als import serving as als_serving

    asked, dispatched, fed = [], [], []
    array_type = type(jnp.zeros(()))
    real_copy = array_type.copy_to_host_async
    real_dispatch = als_serving._dispatch
    real_operands = als_serving._operands

    def copy_spy(self):
        asked.append(self)
        return real_copy(self)

    def dispatch_spy(*args, **kwargs):
        out = real_dispatch(*args, **kwargs)
        dispatched.append(out)
        return out

    def operands_spy(args, step_result=None):
        if step_result is not None:
            fed.append(step_result)
        return real_operands(args, step_result)

    monkeypatch.setattr(array_type, "copy_to_host_async", copy_spy)
    monkeypatch.setattr(als_serving, "_dispatch", dispatch_spy)
    monkeypatch.setattr(als_serving, "_operands", operands_spy)
    return asked, dispatched, fed


def _same(xs, ys) -> bool:
    return len(xs) == len(ys) and all(x is y for x, y in zip(xs, ys))


@pytest.mark.parametrize("case", CASES)
def test_a_flush_asks_for_the_copy_of_its_last_results_once(case, copies):
    """Both arrays of the LAST program's ``(vals, idx)``, once each, in that
    order, before ``_dispatch`` returns; a step result the next program reads
    (the IVF probe's cells) stays on the device."""
    model = _case_model(case)
    qs = np.random.default_rng(37).standard_normal(
        (4, FEATURES)).astype(np.float32)
    _flush(model, case, qs)  # compiles, and fills the arena's views
    asked, dispatched, fed = copies
    del asked[:], dispatched[:], fed[:]
    answers = _flush(model, case, qs)
    assert len(answers) == 4 and all(len(a) == 10 for a in answers)
    (out,) = dispatched
    assert len(out) == 2 and _same(asked, out)
    assert len(fed) == (1 if case == "ivf" else 0)
    assert not any(a is f for a in asked for f in fed)


def test_a_plan_of_two_steps_never_copies_its_intermediate(copies):
    """A fake backend whose second program reads the first's result through
    a ``_Fed`` operand: only the second's ``(vals, idx)`` are asked for."""
    import jax

    from oryx_tpu.models.als import serving as als_serving
    from oryx_tpu.models.als.topn import _Fed

    @jax.jit
    def shift(qs):
        return qs + 1.0

    @jax.jit
    def best(mat, shifted):
        return jax.lax.top_k(shifted @ mat.T, 3)

    class TwoSteps:
        lsh = None
        cost_keys_attempted: set = set()

        def __init__(self):
            self.mat = jnp.ones((8, FEATURES), jnp.float32)

        def place(self, host):
            return jnp.asarray(host)

        def plan(self, qs, lut, width):
            return ((shift, (qs,), "shift"),
                    (best, (self.mat, _Fed(qs.shape, qs.dtype)), "best"))

        def dispatched(self, batch, width):
            pass

    asked, _dispatched, fed = copies
    out = als_serving._dispatch(TwoSteps(), np.zeros((2, FEATURES), np.float32),
                                3, register=False)
    (step,) = fed
    assert step.shape == (2, FEATURES)
    assert _same(asked, out) and not any(a is step for a in asked)
    vals, idx = als_serving._download(out)
    assert vals.shape == idx.shape == (2, 3)


@pytest.mark.parametrize("case", CASES)
def test_answers_are_those_of_a_flush_that_asks_for_no_copy_ahead(
        case, monkeypatch):
    """The copy ahead changes when the arrays travel, never what they hold:
    the answers are bit for bit those of the same flush with the request
    left out (the conversion then starts the copy itself)."""
    model = _case_model(case)
    qs = np.random.default_rng(370).standard_normal(
        (8, FEATURES)).astype(np.float32)
    ahead = _flush(model, case, qs)
    monkeypatch.setattr(type(jnp.zeros(())), "copy_to_host_async",
                        lambda self: None)
    assert _flush(model, case, qs) == ahead


@pytest.mark.parametrize("backend", ["flat", "int8", "mesh", "ivf"])
def test_a_single_query_widening_copies_no_score_vector(backend, copies):
    """A single query's widening (a host filter that lets few items through)
    asks for no copy of its ``(1, n)`` scores: the flat and int8 backends,
    which widen over cached scores, ask for none at all; those that widen by
    scanning again ask only for each scan's own results."""
    model = _model(backend)
    model.y_snapshot()  # the IVF build fetches its centroids by device_get
    asked, dispatched, _fed = copies
    del asked[:]
    q = np.random.default_rng(3).standard_normal(FEATURES).astype(np.float32)
    few = {f"i{j}" for j in range(7, N_ITEMS, 97)}
    got = model.top_n(q, 5, allowed=few.__contains__)
    assert len(got) == 5 and {i for i, _ in got} <= few
    assert _same(asked, [a for out in dispatched for a in out])
    if backend in ("flat", "int8"):
        assert asked == [] and dispatched == []
    else:
        assert len(dispatched) >= 2  # it widened
