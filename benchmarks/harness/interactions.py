"""Interactions from the seed, at exactly the counts a data set has.

Distinct (user, item) pairs with planted low-rank structure (users and items
belong to taste groups; most of a user's interactions fall in its own
group), lognormal user activity and offset-Zipf item popularity. Activity is
drawn per user index and popularity is shuffled over item indices, so hot
rows do not share a block. Users are cut into ranges that cannot collide,
each range is made by a thread of its own from a generator keyed by
``(seed, range)``, and each range holds exactly its share of the count, so
the total is exact.
"""

from __future__ import annotations

import concurrent.futures as cf
import os

import numpy as np


def _shares(weights: np.ndarray, total: int) -> np.ndarray:
    """Whole numbers ∝ weights that sum to ``total`` (largest remainder)."""
    raw = weights / weights.sum() * total
    out = np.floor(raw).astype(np.int64)
    short = total - int(out.sum())
    if short:
        out[np.argsort(-(raw - out), kind="stable")[:short]] += 1
    return out


def generate(seed: int, n_users: int, n_items: int, nnz: int, p: dict,
             workers: "int | None" = None):
    """(rows, cols, vals): int32, int32, float32, sorted by (row, col)."""
    rng = np.random.default_rng([int(seed), 21])
    groups = int(p["groups"])
    user_group = rng.integers(0, groups, n_users)
    item_group = rng.integers(0, groups, n_items)
    pop = 1.0 / np.power(np.arange(1, n_items + 1) + p["popularity_offset"],
                         p["popularity_zipf_s"])
    rng.shuffle(pop)
    members = [np.flatnonzero(item_group == g) for g in range(groups)]
    members = [m if m.size else np.arange(n_items) for m in members]
    cdfs = []
    for m in members:
        c = np.cumsum(pop[m])
        cdfs.append(c / c[-1])
    cdf_all = np.cumsum(pop)
    cdf_all /= cdf_all[-1]
    activity = rng.lognormal(0.0, p["activity_sigma"], n_users)
    # no user may ask for more items than exist; everyone has at least one
    want = np.clip(activity / activity.sum() * nnz, 1.0, 0.9 * n_items)
    n_ranges = int(p.get("ranges", 24))
    bounds = np.linspace(0, n_users, n_ranges + 1).astype(np.int64)
    range_w = np.array([want[bounds[r]:bounds[r + 1]].sum()
                        for r in range(n_ranges)])
    range_n = _shares(range_w, nnz)
    in_share = float(p["in_group_share"])

    def one_range(r: int) -> np.ndarray:
        lo, hi = int(bounds[r]), int(bounds[r + 1])
        target = int(range_n[r])
        if hi - lo == 0 or target == 0:
            return np.empty(0, dtype=np.int64)
        if target > (hi - lo) * n_items:
            raise ValueError("more interactions asked than pairs exist")
        g = np.random.default_rng([int(seed), 22, r])
        w = want[lo:hi] / want[lo:hi].sum()
        ug = user_group[lo:hi]

        def draw(n: int) -> np.ndarray:
            counts = g.multinomial(n, w)
            users = np.repeat(np.arange(lo, hi, dtype=np.int64), counts)
            grp = np.repeat(ug, counts)
            inside = g.random(n) < in_share
            u01 = g.random(n)
            items = np.searchsorted(cdf_all, u01, side="right")
            for gi in range(groups):
                sel = np.flatnonzero(inside & (grp == gi))
                if sel.size:
                    pos = np.searchsorted(cdfs[gi], u01[sel], side="right")
                    items[sel] = members[gi][np.minimum(pos, members[gi].size - 1)]
            return users * n_items + np.minimum(items, n_items - 1)

        # every user of the range appears at least once
        first = np.arange(lo, hi, dtype=np.int64) * n_items + np.minimum(
            np.searchsorted(cdf_all, g.random(hi - lo), side="right"),
            n_items - 1)
        pairs = np.unique(first)
        while pairs.size < target:
            short = target - pairs.size
            pairs = np.union1d(pairs, draw(int(short * 1.25) + 64))
        if pairs.size > target:
            # drop the surplus at random, never a user's first pair
            keep_first = np.isin(pairs, first, assume_unique=True)
            loose = np.flatnonzero(~keep_first)
            drop = g.choice(loose, pairs.size - target, replace=False)
            mask = np.ones(pairs.size, dtype=bool)
            mask[drop] = False
            pairs = pairs[mask]
        return pairs

    workers = workers or min(12, os.cpu_count() or 1)
    with cf.ThreadPoolExecutor(workers) as pool:
        parts = list(pool.map(one_range, range(n_ranges)))
    pairs = np.concatenate(parts)  # ranges ascend, each sorted: sorted
    rows = (pairs // n_items).astype(np.int32)
    cols = (pairs % n_items).astype(np.int32)
    levels = np.asarray(p["value_levels"], dtype=np.float32)
    probs = np.asarray(p["value_probs"], dtype=np.float64)
    vals = levels[np.random.default_rng([int(seed), 23]).choice(
        len(levels), size=pairs.size, p=probs / probs.sum())]
    return rows, cols, vals
