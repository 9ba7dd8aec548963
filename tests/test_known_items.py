"""The default ``/recommend`` leaves out what the user already has (ISSUE 30):
known items held as numbers (``models/als/known.py``), handed to a flush as
item codes and left out by over-fetching and dropping their rows — on every
scan backend, for any history length, with no program the warm ladder did
not compile."""

import os
import sys

import httpx
import numpy as np
import pytest

from oryx_tpu.common import compilecache
from oryx_tpu.common import config as cfg
from oryx_tpu.common import metrics as metrics_mod
from oryx_tpu.models.als import topn
from oryx_tpu.models.als.known import KnownItems
from oryx_tpu.models.als.serving import ALSServingModel
from oryx_tpu.parallel.mesh import make_mesh
from oryx_tpu.serving.app import make_app

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.harness import manifest as mf  # noqa: E402

REFERENCE = mf.load_module("references", "als_topn_known")
WIDEST = 16 + topn._OVERFETCH_ROOM[-1]  # the widest warmed width, howMany 10
N_ITEMS, N_USERS = 3000, 80

BACKENDS = {
    "flat": dict,
    "mesh": lambda: {"mesh": make_mesh(4, axes=("model",))},
    "int8": lambda: {"device_dtype": "int8"},
}


def _histories(rng, n_users=N_USERS, n_items=N_ITEMS):
    """Known rows a user: none, one, about the benchmark's mean, and — the
    last user — ten times the widest warmed width."""
    counts = rng.poisson(20, n_users)
    counts[:2] = (0, 1)
    counts[-1] = 10 * WIDEST
    return [rng.choice(n_items, size=c, replace=False).astype(np.int32)
            for c in counts]


def _model(backend="flat", features=8, seed=30):
    rng = np.random.default_rng(seed)
    y = rng.standard_normal((N_ITEMS, features), dtype=np.float32)
    x = rng.standard_normal((N_USERS, features), dtype=np.float32)
    known = _histories(rng)
    model = ALSServingModel(features, implicit=True, **BACKENDS[backend]())
    item_ids = [f"i{j}" for j in range(N_ITEMS)]
    user_ids = [f"u{j}" for j in range(N_USERS)]
    model.bulk_load_items(item_ids, y)
    model.bulk_load_users(user_ids, x)
    offsets = np.concatenate([[0], np.cumsum([len(k) for k in known])])
    model.bulk_load_known_items(user_ids, offsets, np.concatenate(known),
                                item_ids)
    return model, x, y, known


def _rows(answers):
    return [[int(i[1:]) for i, _ in a] for a in answers]


@pytest.mark.parametrize("features", [8, 250])
@pytest.mark.parametrize("batch", [1, 3, 64])
def test_a_flush_answers_what_the_reference_answers_without_known_items(
        batch, features):
    model, x, y, known = _model(features=features)
    # the batch's users: no history, one item, the mean, and the longest
    users = ([0, 1, N_USERS - 1] + list(range(2, N_USERS - 1)))[:batch]
    if batch == 1:
        users = [N_USERS - 1]
    codes = [model.known_item_codes(f"u{u}") for u in users]
    assert codes[0] is None or batch == 1
    got = model.top_n_batch(x[users], 10, excluded=codes)
    vals, idx = REFERENCE.top_n(x[users], y, 10, [known[u] for u in users])
    assert _rows(got) == idx.tolist()
    np.testing.assert_allclose([[v for _, v in a] for a in got], vals,
                               rtol=2e-5, atol=2e-5)
    for u, rows in zip(users, _rows(got)):
        assert not set(rows) & set(known[u].tolist())
    # with nothing handed over the same flush is the exclusion-free one
    plain = model.top_n_batch(x[users], 10)
    free = REFERENCE.top_n(x[users], y, 10, [()] * len(users))[1]
    assert _rows(plain) == free.tolist()


@pytest.mark.parametrize("backend", list(BACKENDS))
def test_backends_agree_and_compile_nothing_after_the_ladder(backend):
    model, x, y, known = _model(backend)
    compilecache.install_compile_listener()
    for b in (1, 4):
        model.warm_bucket(b, 10)
    overflow = metrics_mod.default_registry().get(
        "oryx_serving_exclusion_overflow_total")
    before, over0 = compilecache.compiles_total(), overflow.value
    flushes = ([N_USERS - 1], [0, 1, 5, 6], [2, 3, 4, N_USERS - 1])
    answers = [model.top_n_batch(
        x[users], 10,
        excluded=[model.known_item_codes(f"u{u}") for u in users])
        for users in flushes]
    # the longest history is past every room: counted, and answered from
    # the widest warmed width all the same
    assert overflow.value - over0 == 2
    assert compilecache.compiles_total() - before == 0
    for users, got in zip(flushes, answers):
        want = REFERENCE.top_n(x[users], y, 10, [known[u] for u in users])[1]
        assert _rows(got) == want.tolist()
        for u, rows in zip(users, _rows(got)):
            assert len(rows) == 10 and not set(rows) & set(known[u].tolist())


def test_more_known_items_among_the_best_than_the_widest_width_holds():
    """The list comes back short only where the user's own items crowd their
    best ``WIDEST``: that query is answered again, alone, and still right."""
    model, x, y, _ = _model()
    q = x[:1]
    best = np.argsort(-(y @ q[0]))
    crowd = best[:WIDEST + 40].astype(np.int32)
    got = model.top_n_batch(q, 10, excluded=[[f"i{j}" for j in crowd]])
    assert _rows(got) == [best[WIDEST + 40:WIDEST + 50].tolist()]
    one = model.top_n(q[0], 10, excluded=[f"i{j}" for j in crowd])
    assert [i for i, _ in one] == [i for i, _ in got[0]]


class _Manager:
    rescorer_provider = None

    def __init__(self, model):
        self.model = model

    def get_model(self):
        return self.model

    def is_read_only(self):
        return True


def test_the_endpoint_leaves_known_items_out_unless_asked_to_consider_them():
    from tests.test_metrics import _AppServer

    model, x, y, known = _model()
    config = cfg.overlay_on({
        "oryx.serving.application-resources": "oryx_tpu.serving.resources.als",
    }, cfg.get_default())
    user = 7
    mine = known[user]
    # make the exclusion bite: the user also knows their own best item
    top = int(np.argmax(y @ x[user]))
    model.add_known_items(f"u{user}", [f"i{top}", "never-a-row"])
    mine = np.append(mine, top)
    with _AppServer(make_app(config, _Manager(model))) as base:
        client = httpx.Client(base_url=base, timeout=30)
        default = client.get(f"/recommend/u{user}?howMany=10").json()
        considered = client.get(
            f"/recommend/u{user}?howMany=10&considerKnownItems=true").json()
        listed = client.get(f"/knownItems/u{user}").json()
    want = REFERENCE.top_n(x[[user]], y, 10, [mine])[1][0]
    free = REFERENCE.top_n(x[[user]], y, 10, [()])[1][0]
    assert [int(e["id"][1:]) for e in default] == want.tolist()
    assert [int(e["id"][1:]) for e in considered] == free.tolist()
    assert free[0] == top and top not in want
    assert listed == sorted({f"i{j}" for j in mine} | {"never-a-row"})


def test_known_items_bulk_table_and_point_adds_read_as_one():
    known = KnownItems()
    items = [f"i{j}" for j in range(6)]
    known.bulk_load(["a", "b", "c"], [0, 2, 2, 5], [0, 1, 3, 4, 5], items)
    assert known.ids("a") == {"i0", "i1"} and known.ids("b") == set()
    assert known.codes("a").base is not None  # a view of the table, no copy
    known.add("a", ["i1", "i5", "new"])
    known.add("d", ["i2"])
    known.add("e", [])
    assert known.ids("a") == {"i0", "i1", "i5", "new"}
    assert known.ids("nobody") == set() and len(known.codes("nobody")) == 0
    assert known.user_counts() == {"a": 4, "b": 0, "c": 3, "d": 1, "e": 0}
    assert known.item_counts() == {"i0": 1, "i1": 1, "i2": 1, "i3": 1,
                                   "i4": 1, "i5": 2, "new": 1}
    known.retain_users(["a", "d", "zz"])
    assert known.user_counts() == {"a": 4, "d": 1}
    with pytest.raises(ValueError):
        known.bulk_load(["a"], [0, 3], [0, 1], items)
    with pytest.raises(ValueError):
        known.bulk_load(["a"], [0, 1], [6], items)


def test_codes_follow_the_snapshot_as_items_and_codes_arrive():
    """The code → row table is kept for a snapshot's row order and caught up
    with what was added since: a known item without a vector has no row
    until its vector arrives, and a rebuilt order is mapped anew."""
    model = ALSServingModel(4, implicit=True)
    rng = np.random.default_rng(3)
    model.bulk_load_items([f"i{j}" for j in range(50)],
                          rng.standard_normal((50, 4), dtype=np.float32))
    model.add_known_items("u", ["i3", "late"])
    snap = model.y_snapshot()
    codes = model.known_item_codes("u")
    assert model.known.rows_in(snap)[codes].tolist() == [3, -1]
    model.set_item_vector("late", np.ones(4, dtype=np.float32))
    model.add_known_items("u", ["i9"])
    nxt = model.y_snapshot()
    assert nxt is not snap and nxt.n == 51
    codes = model.known_item_codes("u")
    assert model.known.rows_in(nxt)[codes].tolist() == [3, 50, 9]
    q = np.ones(4, dtype=np.float32)
    assert "late" in [i for i, _ in model.top_n(q, 3)]
    assert not {"late", "i3", "i9"} & {
        i for i, _ in model.top_n_batch(q[None], 10, excluded=[codes])[0]}
    # a structural change: a new row order, mapped from scratch
    model.retain_recent_and_item_ids({f"i{j}" for j in range(5, 50)})
    model.y.retain_recent_and_ids({f"i{j}" for j in range(5, 50)})
    rebuilt = model.y_snapshot()
    rows = model.known.rows_in(rebuilt)[model.known_item_codes("u")]
    assert [rebuilt.ids[r] if r >= 0 else None for r in rows] == [
        None if "i3" not in rebuilt.id_to_idx else "i3",
        "late" if "late" in rebuilt.id_to_idx else None, "i9"]
