"""The bucket-ordered LSH scan on one device (``sample-rate`` < 1): the
layout, the per-flush selection, the listed kernel against its XLA
formulation, and the served answers against ``references/als_topn_lsh.py``
— each query answered from its own candidate buckets and no others."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oryx_tpu.common import compilecache
from oryx_tpu.models.als import lsh as lsh_mod
from oryx_tpu.models.als import serving as S
from oryx_tpu.models.als.serving import ALSServingModel
from oryx_tpu.ops import pallas_kernels as pk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.harness.manifest import load_module  # noqa: E402

REF = load_module("references", "als_topn_lsh")
K = 16


def _model(n=3000, k=K, sample_rate=0.3, seed=40, y=None):
    model = ALSServingModel(k, implicit=True, sample_rate=sample_rate)
    if y is None:
        y = np.random.default_rng(seed).standard_normal((n, k), np.float32)
    model.bulk_load_items([f"i{j}" for j in range(len(y))], y)
    return model, y


def _rows(answer):
    return [int(i[1:]) for i, _ in answer]


def _check_against_reference(model, y, qs, answers, how_many=10):
    """Every answer is the reference's top among the query's candidates
    (set for set: the CPU's top-k is exact), and holds no non-candidate."""
    hp, radius = model.lsh.hyperplanes, model.lsh.max_bits_differing
    vals, idx = REF.top_n(qs, y, how_many, hp, radius, block_rows=1024)
    for s, answer in enumerate(answers):
        want = [int(i) for v, i in zip(vals[s], idx[s]) if np.isfinite(v)]
        assert set(_rows(answer)) == set(want), s
        exact = REF.exact_scores(qs[s:s + 1], y,
                                 np.asarray([_rows(answer)]))[0]
        np.testing.assert_allclose([v for _, v in answer], exact,
                                   rtol=1e-5, atol=1e-4)
    assert REF.non_candidates(qs, [_rows(a) for a in answers], y, hp,
                              radius) == 0


def test_the_layout_holds_every_row_once_in_its_buckets_windows():
    model, y = _model()
    snap = model.y_snapshot()
    assert type(snap) is S._LSHYSnapshot
    layout = snap.layout
    codes = model.lsh.assign_buckets(y)
    np.testing.assert_array_equal(layout.codes, codes)
    real = layout.pos2row >= 0
    assert sorted(layout.pos2row[real].tolist()) == list(range(len(y)))
    np.testing.assert_array_equal(layout.pos_code[real],
                                  codes[layout.pos2row[real]])
    assert (layout.pos_code[~real] == lsh_mod.PAD_CODE).all()
    tile = layout.tile
    assert tile == lsh_mod.layout_tile(len(y), model.lsh.num_buckets) == 16
    for b in np.unique(codes)[:50]:
        lo = layout.bucket_first[b]
        hi = lo + layout.bucket_count[b]
        # the bucket's rows first, then its padding, within its windows
        mine = layout.pos2row[lo * tile:hi * tile]
        n_b = int((codes == b).sum())
        assert (mine[:n_b] >= 0).all() and (mine[n_b:] == -1).all()
        assert hi - lo == -(-n_b // tile)
        assert layout.win_valid[lo:hi].sum() == n_b
    # as many windows as the rows and one a bucket, whatever the hashes:
    # the programs' shapes follow from n alone
    used = int(layout.bucket_count.sum())
    assert layout.n_windows == -(-len(y) // tile) + model.lsh.num_buckets
    assert used <= layout.n_windows
    assert not layout.win_valid[used:].any() and not real[used * tile:].any()
    other, _ = _model(seed=41)
    assert other.y_snapshot().layout.n_windows == layout.n_windows
    # the device copy IS the layout: bucket order, zero on padding
    got = np.asarray(snap.score_mat)
    np.testing.assert_array_equal(got[real], y[layout.pos2row[real]])
    assert not got[~real].any()
    assert snap.score_mat.shape == (layout.n_positions, K)
    # and replaces the id-ordered scoring copy: no array of Y's shape but mat
    assert [a.shape for a in snap.device_arrays()].count(y.shape) == 1


def test_layout_tile_is_the_mean_bucket_in_a_power_of_two():
    assert lsh_mod.layout_tile(5_000_000, 8192) == 128
    assert lsh_mod.layout_tile(300_000, 8192) == 64
    assert lsh_mod.layout_tile(6000, 8192) == 16
    assert lsh_mod.layout_tile(1, 2) == 16


def test_the_list_is_each_querys_ball():
    """A flush lists the union of its queries' balls — the buckets within
    the radius of each hash — as its buckets' windows, each window once;
    an entry carries its window over a bit a query, set where that query's
    ball holds the window's bucket, and one query's list is what
    ``ball_windows`` says of its hash."""
    model, y = _model()
    snap = model.y_snapshot()
    layout, lsh = snap.layout, model.lsh
    radius = lsh.max_bits_differing
    pc = lsh._popcount_table()

    def windows(u):
        return np.arange(layout.bucket_first[u],
                         layout.bucket_first[u] + layout.bucket_count[u])

    for b in (1, 2, 3):
        qs = np.random.default_rng(b).standard_normal((b, K), np.float32)
        codes = lsh.assign_buckets(qs)
        entries, buckets = layout.windows_of(codes)
        got, owners = entries >> b, entries & ((1 << b) - 1)
        assert len(np.unique(got)) == len(got)
        balls = [np.flatnonzero(pc[np.arange(lsh.num_buckets) ^ c] <= radius)
                 for c in codes]
        assert all(len(ball) == 2380 for ball in balls)
        union = np.unique(np.concatenate(balls))
        assert buckets == len(union)
        assert got.tolist() == np.concatenate(
            [windows(u) for u in union]).tolist()
        for q, ball in enumerate(balls):
            mine = np.concatenate([windows(u) for u in ball])
            assert sorted(got[(owners >> q) & 1 == 1].tolist()) == sorted(
                mine.tolist())
            assert len(mine) == layout.ball_windows[codes[q]]
    # a shared union reads fewer windows than its balls, one after another
    qs = np.random.default_rng(5).standard_normal((2, K), np.float32)
    codes = lsh.assign_buckets(qs)
    assert len(layout.windows_of(codes)[0]) < layout.ball_windows[codes].sum()


@pytest.mark.parametrize("b", [1, 2, 3])
def test_the_listed_kernel_matches_its_xla_formulation(b):
    """The kernel, interpreted, against the gathered formulation the CPU
    serves with, and both against numpy: each entry scored for the queries
    whose bits it carries alone, rows past a window's real ones and the
    padded list's repeated last window −∞."""
    rng = np.random.default_rng(b)
    tile, n_win = 16, 40
    mat = rng.standard_normal((n_win * tile, 128), np.float32)
    qs = rng.standard_normal((b, 128), np.float32)
    listed = 3 * pk.LSH_WINDOWS_A_STEP
    real = listed - 5
    windows = rng.choice(n_win, size=real, replace=False).astype(np.int32)
    windows = np.concatenate([windows, np.full(5, windows[-1], np.int32)])
    owners = rng.integers(1, 1 << b, size=listed).astype(np.int32)
    owners[real:] = 0
    entries = windows << b | owners
    win_valid = rng.integers(0, tile + 1, size=n_win).astype(np.int32)
    args = (jnp.asarray(mat), jnp.asarray(qs), jnp.asarray(entries),
            jnp.asarray(win_valid))
    got = np.asarray(pk.lsh_listed_scores(*args, tile=tile, interpret=True))
    xla = np.asarray(pk.lsh_listed_scores_xla(*args, tile=tile))
    want = np.full((b, listed * tile), -np.inf, np.float32)
    for j in range(real):
        w, n = windows[j], win_valid[windows[j]]
        for q in range(b):
            if owners[j] >> q & 1:
                want[q, j * tile:j * tile + n] = (
                    mat[w * tile:w * tile + n] @ qs[q])
    for have in (got, xla):
        np.testing.assert_array_equal(np.isinf(have), np.isinf(want))
        fin = np.isfinite(want)
        np.testing.assert_allclose(have[fin], want[fin], rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("batch", [1, 2, 4, 64, 256])
def test_served_answers_are_the_references_top_among_candidates(batch):
    model, y = _model()
    qs = np.random.default_rng(batch).standard_normal((batch, K), np.float32)
    answers = model.top_n_batch(qs, 10)
    assert all(len(a) == 10 for a in answers)
    _check_against_reference(model, y, qs, answers)


def test_each_query_keeps_to_its_own_ball_inside_a_shared_union():
    """Two queries with opposite hashes share one listed flush: their
    balls are disjoint (13 bits apart), so each answer from the union
    would break the guarantee; each is held to its own."""
    model, y = _model()
    q = np.random.default_rng(7).standard_normal(K).astype(np.float32)
    qs = np.stack([q, -q])
    codes = model.lsh.assign_buckets(qs)
    assert codes[0] ^ codes[1] == model.lsh.num_buckets - 1
    scanned = S._LSH_SCANNED_ROWS.value
    answers = model.top_n_batch(qs, 10)
    snap = model.y_snapshot()
    listed = S._LSH_SCANNED_ROWS.value - scanned
    # it listed (its union under the threshold) and read under 2/3 of Y
    assert 0 < listed < 0.66 * snap.layout.n_positions
    _check_against_reference(model, y, qs, answers)
    assert not set(_rows(answers[0])) & set(_rows(answers[1]))


def test_padding_rows_are_never_answered():
    """Every real row scores below zero against the query, so a padding
    row (a zero row, scoring 0) would top every list if it were not
    masked."""
    rng = np.random.default_rng(3)
    q = rng.standard_normal(K).astype(np.float32)
    y = rng.standard_normal((3000, K)).astype(np.float32)
    # y·q between -2 and -1 a row
    y -= (y @ q + 1.0 + rng.random(len(y)))[:, None] * q[None, :] / (q @ q)
    model, _ = _model(y=y)
    assert (model.y_snapshot().layout.pos2row == -1).any()
    for batch in (1, 2, 8):
        answers = model.top_n_batch(np.stack([q] * batch), 10)
        for a in answers:
            assert len(a) == 10 and all(v < 0 for _, v in a)
            assert all(0 <= r < len(y) for r in _rows(a))
    single = model.top_n(q, 10)
    assert [i for i, _ in single] == [i for i, _ in answers[0]]


def test_both_sides_of_the_list_threshold():
    """At 0.5 the hash is one bit and a ball one of two buckets, about
    half the windows: one query's list (under 3/4 of the windows) is
    scanned listed, and so are two queries of one hash, whose union is one
    ball; two queries of opposite hashes list both buckets, past 3/4, and
    take the full pass. The choice follows the union's windows, not the
    width. All answer as the reference does."""
    model, y = _model(n=4000, sample_rate=0.5)
    snap = model.y_snapshot()
    assert model.lsh.num_buckets == 2
    one = int(snap.layout.ball_windows.max())
    assert snap._lengths[1] >= one
    assert one <= snap._lengths[2] < 2 * int(snap.layout.ball_windows.min())
    # so one query's flush never takes the full pass, two queries' may
    width = snap.batch_width(10, False)
    assert [type(s) for s in snap.selections(1, width)] == [S._Listed]
    assert [type(s) for s in snap.selections(2, width)] == [S._Listed,
                                                            S._Full]
    rng = np.random.default_rng(5)
    q = rng.standard_normal(K).astype(np.float32)
    for qs, path in ((q[None], "_Listed"), (np.stack([q, 2 * q]), "_Listed"),
                     (np.stack([q, -q]), "_Full")):
        seen = []
        orig = S._LSHYSnapshot.plan

        def spy(self, qs_, lut, width):
            seen.append(type(lut).__name__)
            return orig(self, qs_, lut, width)

        S._LSHYSnapshot.plan = spy
        try:
            answers = model.top_n_batch(qs, 10)
        finally:
            S._LSHYSnapshot.plan = orig
        assert seen == [path], path
        _check_against_reference(model, y, qs, answers)


def test_an_up_that_moves_an_item_is_scored_in_its_new_bucket_alone():
    model, y = _model()
    snap0 = model.y_snapshot()
    lsh = model.lsh
    j = 17
    moved = -y[j] * 3.0  # every projection's sign flips: the far bucket
    old_b, new_b = lsh.assign_buckets(y[j:j + 1])[0], lsh.assign_buckets(
        moved[None])[0]
    assert old_b ^ new_b == lsh.num_buckets - 1
    model.set_item_vector(f"i{j}", moved)
    snap1 = model.y_snapshot()
    assert snap1 is not snap0 and snap1.layout.codes[j] == new_b
    where = np.flatnonzero(snap1.layout.pos2row == j)
    assert len(where) == 1
    assert snap1.layout.pos_code[where[0]] == new_b
    y1 = y.copy()
    y1[j] = moved
    # a query that points at the moved item: answered from its own ball
    for q in (moved, y[j]):
        qs = np.stack([q / np.linalg.norm(q)]).astype(np.float32)
        answers = model.top_n_batch(qs, 10)
        _check_against_reference(model, y1, qs, answers)
    top = model.top_n_batch(np.stack([moved]), 10)[0]
    assert _rows(top)[0] == j
    assert j not in _rows(model.top_n_batch(np.stack([y[j]]), 10)[0])


def _room(layout, bucket) -> int:
    """Free positions in the bucket's windows."""
    first, count = layout.bucket_first[bucket], layout.bucket_count[bucket]
    return int((layout.tile - layout.win_valid[first:first + count]).sum())


def _far_row_with_room(layout, lsh, y):
    """A row whose far bucket (every projection's sign flipped) has two
    free positions in its windows, and the vector that moves it there."""
    for j in range(len(y)):
        far = int(lsh.assign_buckets(-y[j:j + 1])[0])
        if _room(layout, far) >= 2:
            return j, -y[j] * 3.0, far
    raise AssertionError("no bucket with room")


def test_an_incremental_step_places_only_the_changed_rows():
    """An UP that moves an item to a bucket with a free position, one that
    changes an item inside its bucket, and a new item: no other row moves
    but the one that fills the moved row's place in its window; the device
    copy is the float32 rows in the new order, and answers keep to each
    query's ball."""
    model, y = _model()
    snap0 = model.y_snapshot()
    lay0, lsh = snap0.layout, model.lsh
    j, moved, far = _far_row_with_room(lay0, lsh, y)
    stay = (j + 1) % len(y)
    nudged = y[stay] * 1.5  # the same signs: the same bucket
    rng = np.random.default_rng(1)
    fresh = next(v for v in rng.standard_normal((500, K), np.float32)
                 if _room(lay0, lsh.assign_buckets(v[None])[0]))
    model.set_item_vector(f"i{j}", moved)
    model.set_item_vector(f"i{stay}", nudged)
    model.set_item_vector(f"i{len(y)}", fresh)
    snap1 = model.y_snapshot()
    lay1 = snap1.layout
    # placed again, not laid out again: the buckets' windows are shared
    assert lay1.bucket_first is lay0.bucket_first
    assert lay1.n_windows == lay0.n_windows
    assert lay1.codes[j] == far and len(lay1.codes) == len(y) + 1
    y1 = np.concatenate([y, fresh[None]])
    y1[j], y1[stay] = moved, nudged
    assert lay1.row2pos[stay] == lay0.row2pos[stay]
    p0 = lay0.row2pos[j]
    w = p0 // lay0.tile
    filler = lay0.pos2row[w * lay0.tile + lay0.win_valid[w] - 1]
    shifted = np.flatnonzero(lay1.row2pos[:len(y)] != lay0.row2pos)
    assert set(shifted.tolist()) <= {j, int(filler)}
    # every window's real rows at its front, each row once, in its bucket
    real = lay1.pos2row >= 0
    assert sorted(lay1.pos2row[real].tolist()) == list(range(len(y1)))
    np.testing.assert_array_equal(lay1.row2pos[lay1.pos2row[real]],
                                  np.flatnonzero(real))
    np.testing.assert_array_equal(lay1.pos_code[real],
                                  lsh.assign_buckets(y1)[lay1.pos2row[real]])
    at = np.arange(lay1.n_positions) % lay1.tile
    np.testing.assert_array_equal(real, at < np.repeat(lay1.win_valid,
                                                       lay1.tile))
    got = np.asarray(snap1.score_mat)
    np.testing.assert_array_equal(got[real], y1[lay1.pos2row[real]])
    assert not got[~real].any()
    np.testing.assert_array_equal(np.asarray(snap1.pos2row), lay1.pos2row)
    np.testing.assert_array_equal(np.asarray(snap1.pos_code), lay1.pos_code)
    np.testing.assert_array_equal(np.asarray(snap1.buckets),
                                  lsh.assign_buckets(y1))
    # the snapshot before is as it was
    assert lay0.codes[j] != far and lay0.pos2row[p0] == j
    for batch in (1, 2, 4):
        qs = np.stack([moved, -moved, fresh, y[j]])[:batch]
        qs = (qs / np.linalg.norm(qs, axis=1, keepdims=True)).astype(
            np.float32)
        _check_against_reference(model, y1, qs, model.top_n_batch(qs, 10))


def test_a_row_moved_to_a_full_bucket_lays_the_layout_out_again():
    """Where the moved row's new bucket has no free position, the step lays
    the layout out again from every row's hash, as a first build does."""
    model, y = _model()
    lay0 = model.y_snapshot().layout
    lsh = model.lsh
    for j in range(len(y)):
        far = int(lsh.assign_buckets(-y[j:j + 1])[0])
        if not lay0.bucket_count[far]:
            break
    model.set_item_vector(f"i{j}", -y[j] * 3.0)
    lay1 = model.y_snapshot().layout
    assert lay1.bucket_first is not lay0.bucket_first
    assert lay1.bucket_count[far] == 1
    fresh = lsh_mod.BucketLayout(lsh, lay1.codes)
    np.testing.assert_array_equal(lay1.pos2row, fresh.pos2row)


def test_the_flat_path_keeps_its_one_program_at_sample_rate_one():
    model, _ = _model(sample_rate=1.0)
    snap = model.y_snapshot()
    assert type(snap) is S._YSnapshot and model.lsh is None
    qs = jnp.zeros((4, K), jnp.float32)
    assert snap.select(np.zeros((4, K), np.float32), (16, 0)) is None
    assert snap.batch_operands(np.zeros((4, K), np.float32), None) is None
    assert snap.selections(4, (16, 0)) == [None]
    (step,) = snap.plan(qs, None, (16, 0))
    assert step[0] is S._top_k_dot_batch
    assert step[1][0] is snap.score_mat and step[1][2] is None
    assert step[2] == "als.top_n_batch/b4"
    assert not hasattr(S, "_top_k_dot_batch_masked")


def test_the_ladder_closes_every_listed_length():
    """After ``warm_bucket`` for the widths that list, flushes of any union
    compile nothing: each width warms one list length, as many balls as
    queries of an even share of the rows with room, so every union it
    lists fits it, and the full pass only where the layout's balls may
    pass it; a flush that leaves known items out lists as one that does
    not. The lengths follow from the layout's shape, not its hashes."""
    model, y = _model()
    compilecache.install_compile_listener()
    for b in (1, 2, 4):
        model.warm_bucket(b, 10)
    snap = model.y_snapshot()
    most = int(snap.layout.ball_windows.max())
    assert sorted(snap._lengths) == [1, 2]
    limit = 3 * snap.layout.n_windows // 4 // 8 * 8
    for b, length in snap._lengths.items():
        assert b * most <= length <= limit and length % 8 == 0
        # at every width a flush over-fetches to; the full pass only where
        # a union may pass the list (at 3,000 rows none does)
        assert {k for k in snap.cost_keys_attempted if "+lsh" in k
                and k.split("/")[1].startswith(f"b{b}+")} == {
            f"als.top_n_batch/b{b}{room}+lsh{length}"
            for room in ("", "+excl48", "+excl240")}
    assert {k for k in snap.cost_keys_attempted if "/b4+" in k} == {
        f"als.top_n_batch/b4{room}+lsh" for room in ("", "+excl48",
                                                     "+excl240")}
    other, _ = _model(seed=41)
    assert other.y_snapshot()._lengths == snap._lengths
    before = compilecache.compiles_total()
    rng = np.random.default_rng(9)
    for _ in range(4):
        for b in (1, 2, 4):
            qs = rng.standard_normal((b, K), np.float32)
            model.top_n_batch(qs, 10)
            model.top_n_batch(np.repeat(qs[:1], b, axis=0), 10)
            model.top_n_batch(qs, 10, excluded=[["i1", "i2"]] + [None] * (b - 1))
    assert compilecache.compiles_total() - before == 0


def test_the_model_path_runs_the_interpreted_kernel_as_it_does_the_xla_one():
    model, y = _model(n=600)
    snap = model.y_snapshot()
    qs = np.random.default_rng(11).standard_normal((2, K), np.float32)
    xla = model.top_n_batch(qs, 10)
    snap._listing = "interpret"
    kernel = model.top_n_batch(qs, 10)
    assert [_rows(a) for a in kernel] == [_rows(a) for a in xla]
    _check_against_reference(model, y, qs, kernel)
