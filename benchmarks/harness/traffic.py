"""The one general traffic generator: a mix's data file in, schedules out.

Every seed gets the SAME multiset of inter-arrival gaps and of user ranks
(drawn from the mix's own ``schedule_seed``), in another order and mapped
to other users, so that the seed moves the inputs and not the work.
"""

from __future__ import annotations

import numpy as np


def zipf_ranks(n_draws: int, n_users: int, s: float, rng) -> np.ndarray:
    """``n_draws`` ranks in [0, n_users) with P(rank r) ∝ 1/(r+1)**s."""
    w = 1.0 / np.power(np.arange(1, n_users + 1, dtype=np.float64), s)
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return np.searchsorted(cdf, rng.random(n_draws), side="right").clip(
        0, n_users - 1)


def users_for(mix: dict, n_draws: int, n_users: int, seed: int) -> np.ndarray:
    """User indices of the requests, in sending order."""
    fixed = np.random.default_rng(mix["schedule_seed"])
    ranks = zipf_ranks(n_draws, n_users, mix["user_zipf_s"], fixed)
    rng = np.random.default_rng([seed, 1])
    rng.shuffle(ranks)
    # which user holds which rank is the seed's choice: an affine map of the
    # rank by a multiplier coprime to n_users is a permutation, without a
    # million-entry table per run
    mult = int(rng.integers(1, n_users)) | 1
    while np.gcd(mult, n_users) != 1:
        mult += 2
    shift = int(rng.integers(0, n_users))
    return (ranks.astype(np.int64) * mult + shift) % n_users


def open_schedule(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times in [0, seconds): Poisson arrivals at ``rate_per_s``. The
    gaps are exponential draws from the mix's own seed, scaled to fill the
    window exactly, in an order the run's seed chooses."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    fixed = np.random.default_rng(mix["schedule_seed"])
    gaps = fixed.exponential(1.0, n + 1)
    gaps *= seconds / gaps.sum()
    np.random.default_rng([seed, 2]).shuffle(gaps)
    return np.cumsum(gaps)[:n]


def keep_rule(expected_requests: int, sample: int, seed: int) -> tuple:
    """(every, phase): children keep the body of each request whose index is
    ``phase`` modulo ``every``: about four times the sample, so that the
    sample can be drawn from those that finished."""
    every = max(1, expected_requests // max(1, 4 * sample))
    return every, int(np.random.default_rng([seed, 3]).integers(0, every))
