"""Known items: which items each user already has, kept as numbers.

The default ``/recommend`` leaves out what the user already has
(Recommend.java:84-106), so every request of the default endpoint asks for
its user's known items and every flush hands them to the scan. They are held
as int32 *item codes* — an item id is interned once, when it is first named —
in two parts: the rows of a bulk load (one CSR table: ``offsets`` over
``codes``, a user a slot) and, a user at a time, what ``UP`` messages added
since (a small array a user). A request takes its user's codes with one
dictionary lookup (a view of the table where nothing was added since); a
flush turns codes into rows of its snapshot with one fancy index into
``rows_in(snap)``, the code → row table of that snapshot's row order.
"""

from __future__ import annotations

import threading
from typing import Iterable, Sequence

import numpy as np

from oryx_tpu.common import metrics as metrics_mod

_KNOWN_BYTES = metrics_mod.default_registry().gauge(
    "oryx_serving_known_items_bytes",
    "Bytes of the newest model's known-item tables (the bulk load's codes "
    "and offsets, the code-to-row table of the current snapshot; ids, dicts "
    "and point adds not counted)",
)

_NONE = np.empty(0, dtype=np.int32)


class KnownItems:
    def __init__(self):
        self._lock = threading.Lock()
        self._rows_lock = threading.Lock()
        self._id_of: list[str] = []          # item code -> item id
        self._code_of: dict[str, int] = {}   # item id -> item code
        self._slot_of: dict[str, int] = {}   # user id -> slot of the bulk table
        self._offsets = np.zeros(1, dtype=np.int64)
        self._codes = _NONE
        self._added: dict[str, np.ndarray] = {}  # user id -> codes added since
        # (the snapshot's id -> row dict, the code -> id list, the snapshot
        # rows and the codes the table has seen, the table)
        self._rows: "tuple | None" = None

    # -- writes ---------------------------------------------------------------
    def bulk_load(self, user_ids: Sequence[str], offsets, items,
                  item_ids: Sequence[str]) -> None:
        """A generation's known items at once, replacing what was held: user
        ``user_ids[u]`` knows ``item_ids[j]`` for ``j`` in
        ``items[offsets[u]:offsets[u + 1]]``."""
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        codes = np.ascontiguousarray(items, dtype=np.int32)
        if len(offsets) != len(user_ids) + 1 or offsets[-1] != len(codes):
            raise ValueError("offsets do not describe items a user at a time")
        if len(codes) and not (0 <= codes.min() and codes.max() < len(item_ids)):
            raise ValueError("an item index outside item_ids")
        id_of = list(item_ids)
        code_of = {s: c for c, s in enumerate(id_of)}
        slot_of = {u: s for s, u in enumerate(user_ids)}
        with self._lock:
            self._id_of, self._code_of, self._slot_of = id_of, code_of, slot_of
            self._offsets, self._codes = offsets, codes
            self._added = {}

    def add(self, user: str, items: Iterable[str]) -> None:
        with self._lock:
            code_of, id_of = self._code_of, self._id_of
            new = []
            for item in items:
                code = code_of.get(item)
                if code is None:
                    code = code_of[item] = len(id_of)
                    id_of.append(item)
                new.append(code)
            if not new:
                # the user is known to have a history, if an empty one
                self._added.setdefault(user, _NONE)
                return
            have = self._codes_locked(user)
            fresh = np.setdiff1d(np.asarray(new, dtype=np.int32), have)
            # analyze: ignore[per-row-ndarray-store] -- point adds since the last bulk load only (a few int32 a user where a set of str stood); a bulk load folds a generation into ONE table
            self._added[user] = np.concatenate(
                [self._added.get(user, _NONE), fresh])

    def retain_users(self, users) -> None:
        keep = set(users)
        with self._lock:
            for table in (self._slot_of, self._added):
                for u in [u for u in table if u not in keep]:
                    del table[u]

    # -- reads ----------------------------------------------------------------
    def _codes_locked(self, user: str) -> np.ndarray:
        # analyze: ignore[lock-discipline] -- runs only under self._lock, taken by its callers
        slot, added = self._slot_of.get(user), self._added.get(user)
        if slot is None:
            return _NONE if added is None else added
        # analyze: ignore[lock-discipline] -- runs only under self._lock, taken by its callers
        base = self._codes[self._offsets[slot]:self._offsets[slot + 1]]
        return base if added is None else np.concatenate([base, added])

    def codes(self, user: str) -> np.ndarray:
        """The user's known items as codes: read-only, not to be kept past
        the request (a view of the bulk table where nothing was added)."""
        with self._lock:
            return self._codes_locked(user)

    def ids(self, user: str) -> set[str]:
        with self._lock:
            id_of = self._id_of
            return {id_of[c] for c in self._codes_locked(user).tolist()}

    def _users_locked(self) -> list:
        # analyze: ignore[lock-discipline] -- runs only under self._lock, taken by its callers
        added = [u for u in self._added if u not in self._slot_of]
        return [*self._slot_of, *added]

    def user_counts(self) -> dict[str, int]:
        with self._lock:
            return {u: len(self._codes_locked(u))
                    for u in self._users_locked()}

    def item_counts(self) -> dict[str, int]:
        with self._lock:
            parts = [self._codes_locked(u) for u in self._users_locked()]
            id_of = self._id_of
            if not parts:
                return {}
            counts = np.bincount(np.concatenate(parts), minlength=len(id_of))
        return {id_of[c]: int(counts[c]) for c in np.flatnonzero(counts)}

    def rows_in(self, snap) -> np.ndarray:
        """(codes,) int32: the row of ``snap`` that holds each item code's
        factors, -1 where it has none. Kept for the snapshot's row order (its
        ``id_to_idx``, which incremental snapshots share and extend) and
        brought up to date with the codes and rows added since. Under a lock
        of its own: a request's ``codes`` never waits for a table's build."""
        with self._rows_lock:
            # both only ever grow, or are replaced whole by a bulk load
            # (atomic reference loads; a replaced table is seen by identity)
            id_of, code_of = self._id_of, self._code_of
            id_to_idx, n, n_codes = snap.id_to_idx, snap.n, len(id_of)
            held = self._rows
            if held is None or held[0] is not id_to_idx or held[1] is not id_of:
                table = np.fromiter((id_to_idx.get(s, -1) for s in id_of[:n_codes]),
                                    dtype=np.int32, count=n_codes)
            else:
                _, _, rows_seen, codes_seen, table = held
                if codes_seen == n_codes and rows_seen >= n:
                    return table
                if codes_seen < n_codes:
                    table = np.concatenate([table, np.fromiter(
                        (id_to_idx.get(s, -1) for s in id_of[codes_seen:n_codes]),
                        dtype=np.int32, count=n_codes - codes_seen)])
                for row in range(rows_seen, n):
                    code = code_of.get(snap.ids[row])
                    if code is not None and code < n_codes:
                        table[code] = row
                n = max(n, rows_seen)
            self._rows = (id_to_idx, id_of, n, n_codes, table)
            _KNOWN_BYTES.set(self._codes.nbytes + self._offsets.nbytes
                             + table.nbytes)
            return table
