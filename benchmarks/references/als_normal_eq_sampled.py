"""Plain reference for implicit-feedback ALS at any width: the normal
equations of a seeded SAMPLE of rows, in float64 numpy on the host.

One half-iteration solves, for every row ``u`` with entries ``(i, v)``:

    (YᵀY + Σᵢ α|v| yᵢyᵢᵀ + λ·max(nᵤ, 1)·I) xᵤ = Σᵢ (1 + α|v|)·[v > 0]·yᵢ

(Hu, Koren & Volinsky 2008, with the regularization scaled by the row's
interaction count as Zhou et al. 2008 and MLlib do). Rows with no entries
get a zero factor. It omits the program's 1e-6 diagonal jitter and shares
no code with ``oryx_tpu/models/als``.

Why a sample: the dense reference (``als_normal_eq.py``) multiplies by the
table of every row's outer product, ``vec(yᵢyᵢᵀ)``: at 250 features that
table is 17,770 × 62,500 float32 (4.4 GB) and the products cost ~1 PFLOP a
half at the highest precision. Here each sampled row's Gramian is the
product of its own gathered rows, so a row is solved exactly as a full
reference would solve it; fewer rows are solved. :func:`pick` draws them
from the seed: the rows with the most entries always, the rest at random.

:func:`user_half` solves the sampled users from Y₀: it checks the
program's user half. :func:`item_half` solves the sampled items from the X₁
it is GIVEN — the program's own, as read back after its first steps — with
X₁ᵀX₁ over every user: it checks the program's item half alone, and not the
error X₁ carries into it.

``control=True`` is the same arithmetic with its inputs rounded to
bfloat16: the factor table the half reads (and so its Gramian), and each
row's system and right-hand side; the solve itself stays float64.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness.manifest import load_module

# the comparison is the dense reference's, number for number
_dense = load_module("references", "als_normal_eq")
rel_err, worst_row_err = _dense.rel_err, _dense.worst_row_err

_CHUNK = 1 << 15  # rows of a factor table gathered and multiplied at once


def pick(rows, cols, n_users: int, n_items: int, seed: int,
         sizes: dict) -> "tuple[np.ndarray, np.ndarray]":
    """(users, items) to solve, each ascending: ``sizes["users"]`` users of
    which the ``sizes["heaviest_users"]`` with the most entries, and
    ``sizes["items"]`` items of which the ``sizes["heaviest_items"]`` most
    popular; the rest drawn from the seed (every row where there are no
    more)."""
    rng = np.random.default_rng([int(seed), 41])

    def one(owner, n, want, heaviest):
        counts = np.bincount(owner, minlength=n)
        want = min(int(want), n)
        top = np.argsort(-counts, kind="stable")[:min(int(heaviest), want)]
        rest = np.setdiff1d(np.arange(n), top)
        drawn = rng.choice(rest, want - len(top), replace=False)
        return np.sort(np.concatenate([top, drawn]))

    return (one(rows, n_users, sizes["users"], sizes["heaviest_users"]),
            one(cols, n_items, sizes["items"], sizes["heaviest_items"]))


def _runs(owner, other, vals, picked, n: int):
    """The entries of the picked rows, one run a row in ``picked``'s order:
    (pointers, the opposite side's indices, values)."""
    want = np.zeros(n, dtype=bool)
    want[picked] = True
    sel = np.flatnonzero(want[owner])
    sel = sel[np.argsort(owner[sel], kind="stable")]
    per_row = np.bincount(owner[sel], minlength=n)[picked]
    return (np.concatenate([[0], np.cumsum(per_row)]), other[sel],
            vals[sel].astype(np.float64))


class Entries:
    """The entries of the sampled users and items, as runs a row."""

    def __init__(self, rows, cols, vals, n_users: int, n_items: int,
                 users: np.ndarray, items: np.ndarray):
        self.users, self.items = users, items
        self.by_user = _runs(rows, cols, vals, users, n_users)
        self.by_item = _runs(cols, rows, vals, items, n_items)


def _round_bf16(a) -> np.ndarray:
    """Round to bfloat16's 8 exponent and 7 mantissa bits (to nearest,
    ties to even), by way of float32; returned as float64."""
    u = np.asarray(a, dtype=np.float32).view(np.uint32)
    u = (u + (np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1)))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32).astype(np.float64)


def _table(f, control: bool) -> np.ndarray:
    return _round_bf16(f) if control else np.asarray(f, dtype=np.float64)


def _row_sums(f, idx, w, p) -> "tuple[np.ndarray, np.ndarray]":
    """(Σ wᵢ·fᵢfᵢᵀ, Σ pᵢ·fᵢ) over the rows ``idx`` of ``f``, gathered a
    chunk at a time."""
    k = f.shape[1]
    a, b = np.zeros((k, k)), np.zeros(k)
    for lo in range(0, len(idx), _CHUNK):
        part = slice(lo, lo + _CHUNK)
        g = f[idx[part]]
        a += (g * w[part, None]).T @ g
        b += p[part] @ g
    return a, b


def _half(table, runs, lam: float, alpha: float, control: bool) -> np.ndarray:
    """Each sampled row's factor against ``table``: (rows, k) float64."""
    ptr, other, vals = runs
    f = _table(table, control)
    k = f.shape[1]
    gram = np.zeros((k, k))
    for lo in range(0, len(f), _CHUNK):
        gram += f[lo:lo + _CHUNK].T @ f[lo:lo + _CHUNK]
    eye = np.eye(k)
    out = np.zeros((len(ptr) - 1, k))
    for r in range(len(ptr) - 1):
        lo, hi = int(ptr[r]), int(ptr[r + 1])
        if hi == lo:
            continue
        v = vals[lo:hi]
        w = alpha * np.abs(v)
        a, b = _row_sums(f, other[lo:hi], w, np.where(v > 0, 1.0 + w, 0.0))
        a += gram + lam * max(hi - lo, 1) * eye
        if control:
            a, b = _round_bf16(a), _round_bf16(b)
        out[r] = np.linalg.solve(a, b)
    return out


def user_half(y0, ent: Entries, lam: float, alpha: float,
              control: bool = False) -> np.ndarray:
    """The sampled users' factors X₁ against the item factors ``y0``."""
    return _half(y0, ent.by_user, lam, alpha, control)


def item_half(x1, ent: Entries, lam: float, alpha: float,
              control: bool = False) -> np.ndarray:
    """The sampled items' factors Y₁ against the user factors ``x1`` it is
    given (every user's: their Gramian is over all of them)."""
    return _half(x1, ent.by_item, lam, alpha, control)
