"""Plain reference for implicit-feedback ALS: the normal equations.

One half-iteration solves, for every row ``u`` with entries ``(i, v)``:

    (YᵀY + Σᵢ α|v| yᵢyᵢᵀ + λ·max(nᵤ, 1)·I) xᵤ = Σᵢ (1 + α|v|)·[v > 0]·yᵢ

(Hu, Koren & Volinsky 2008, with the regularization scaled by the row's
interaction count as Zhou et al. 2008 and MLlib do). Rows with no entries
get a zero factor. It omits the program's 1e-6 diagonal jitter and shares
no code with ``oryx_tpu/models/als``.

Plain ``jax.numpy`` at the highest matmul precision, with no gather: users
are taken in blocks, a block's entries are scattered once into a dense
``(block, n_items)`` matrix of values, from which W (the weights α|v|) and
P (the right-hand coefficients) follow, and the Gramians are matrix
products with the table of outer products: for the user half
``W @ vec(yᵢyᵢᵀ)``, for the item half ``Wᵀ @ vec(xᵤxᵤᵀ)`` summed over the
blocks as each block's user factors are solved. That spends ~80 times the
operations of a sparse pass on the MXU and is still far quicker than
gathering 100M rows; it needs ``n_items`` small enough for a block of W to
fit, which this configuration's 17,770 is.

``control=True`` is the same arithmetic one precision below the
configuration's float32: the factors a half-iteration reads, the Gramians
and the right-hand sides are rounded to bfloat16 (the solve itself stays float32 so
that it gives a number).
"""

from __future__ import annotations

import functools

import numpy as np

BLOCK = 4096  # users per dense block: W and P are (BLOCK, n_items) float32


class Entries:
    """Interactions sorted by user, resident on the device, in blocks."""

    def __init__(self, rows, cols, vals, n_users: int, n_items: int,
                 block: int = BLOCK):
        import jax.numpy as jnp

        self.n_users, self.n_items, self.block = n_users, n_items, block
        self.ptr = np.concatenate(
            [[0], np.cumsum(np.bincount(rows, minlength=n_users))])
        self.item_count = np.bincount(cols, minlength=n_items)
        self.starts = list(range(0, n_users, block))
        ends = [min(n_users, s + block) for s in self.starts]
        per_block = [int(self.ptr[e] - self.ptr[s])
                     for s, e in zip(self.starts, ends)]
        # one slice length for every block, so that one program serves all
        self.e_max = max(8, int(np.ceil(max(per_block) / 1024.0)) * 1024)
        pad = np.zeros(self.e_max, dtype=np.int32)
        self.cols = jnp.asarray(np.concatenate([cols.astype(np.int32), pad]))
        self.vals = jnp.asarray(np.concatenate(
            [vals.astype(np.float32), pad.astype(np.float32)]))

    def block_ptr(self, start: int) -> np.ndarray:
        """Entry pointers of the block's ``block + 1`` row boundaries (rows
        past the last user are empty)."""
        idx = np.minimum(np.arange(start, start + self.block + 1), self.n_users)
        return self.ptr[idx].astype(np.int32)


def _round_bf16(a):
    """Round to bfloat16's 8 exponent and 7 mantissa bits, in place of a
    cast there and back (which the compiler may drop as excess precision)."""
    import jax

    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _solve(a, b, deg, gram, lam, control: bool):
    import jax
    import jax.numpy as jnp

    k = b.shape[-1]
    reg = lam * jnp.maximum(deg, 1.0)
    a = a.reshape(-1, k, k) + gram[None] + reg[:, None, None] * jnp.eye(k)[None]
    if control:
        a, b = _round_bf16(a), _round_bf16(b)
    with jax.default_matmul_precision("highest"):
        chol = jax.scipy.linalg.cho_factor(a, lower=True)
        x = jax.scipy.linalg.cho_solve(chol, b[..., None])[..., 0]
    return jnp.where((deg > 0)[:, None], x, 0.0)


def _outer_table(f):
    """``vec(fᵢfᵢᵀ)`` for every row: (n, k·k), exact float32 products."""
    return (f[:, :, None] * f[:, None, :]).reshape(f.shape[0], -1)


@functools.lru_cache(maxsize=None)
def _block_fn(block: int, n_items: int, e_max: int, control: bool):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def step(cols, vals, bptr, y, zy, yty, acc_a, acc_b, lam, alpha):
        """One block of users: their factors against ``y`` (the user half),
        and the block's part of the item half's Gramians and right-hand
        sides against those factors."""
        idx = bptr[0] + jnp.arange(e_max, dtype=jnp.int32)
        ok = idx < bptr[block]
        c = jax.lax.dynamic_slice(cols, (bptr[0],), (e_max,))
        v = jax.lax.dynamic_slice(vals, (bptr[0],), (e_max,))
        row = jnp.clip(jnp.searchsorted(bptr, idx, side="right") - 1,
                       0, block - 1)
        # the block's entries as one dense matrix of values (pairs are
        # distinct, so adding is setting; entries past the block add 0)
        dense = jnp.zeros((block, n_items), jnp.float32).at[row, c].add(
            jnp.where(ok, v, 0.0))
        w = alpha * jnp.abs(dense)
        p = jnp.where(dense > 0, 1.0 + w, 0.0)
        deg = (bptr[1:] - bptr[:-1]).astype(jnp.float32)
        x = _solve(jnp.matmul(w, zy, precision=hi),
                   jnp.matmul(p, y, precision=hi), deg, yty, lam, control)
        xc = _round_bf16(x) if control else x
        acc_a = acc_a + jnp.matmul(w.T, _outer_table(xc), precision=hi)
        acc_b = acc_b + jnp.matmul(p.T, xc, precision=hi)
        return x, acc_a, acc_b

    return step


def iteration(y, ent: Entries, lam: float, alpha: float,
              control: bool = False):
    """One ALS iteration from the item factors ``y``: the user factors
    ``(n_users, k)`` against ``y``, then the item factors ``(n_items, k)``
    against those user factors, in one pass over the blocks of users."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    y = jnp.asarray(y, dtype=jnp.float32)
    if control:
        y = _round_bf16(y)
    k = y.shape[1]
    yty = jnp.matmul(y.T, y, precision=hi)
    zy = _outer_table(y)
    step = _block_fn(ent.block, ent.n_items, ent.e_max, control)
    acc_a = jnp.zeros((ent.n_items, k * k), jnp.float32)
    acc_b = jnp.zeros((ent.n_items, k), jnp.float32)
    xs = []
    for start in ent.starts:
        x, acc_a, acc_b = step(
            ent.cols, ent.vals, jnp.asarray(ent.block_ptr(start)), y, zy, yty,
            acc_a, acc_b, jnp.float32(lam), jnp.float32(alpha))
        xs.append(x)
    x = jnp.concatenate(xs)[:ent.n_users]
    xc = _round_bf16(x) if control else x
    xtx = jnp.matmul(xc.T, xc, precision=hi)
    deg = jnp.asarray(ent.item_count.astype(np.float32))
    y_next = jax.jit(_solve, static_argnames="control")(
        acc_a, acc_b, deg, xtx, jnp.float32(lam), control=control)
    return np.asarray(x), np.asarray(y_next)


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """‖got − want‖ / ‖want‖ over the whole matrix, in float64."""
    g, w = got.astype(np.float64), want.astype(np.float64)
    return float(np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30))


def worst_row_err(got: np.ndarray, want: np.ndarray) -> float:
    """The worst row's ‖got − want‖ against that row's norm in the
    reference or the median row's, whichever is larger."""
    g, w = got.astype(np.float64), want.astype(np.float64)
    norms = np.linalg.norm(w, axis=1)
    floor = float(np.median(norms[norms > 0])) if (norms > 0).any() else 1.0
    return float(np.max(np.linalg.norm(g - w, axis=1)
                        / np.maximum(norms, floor)))
