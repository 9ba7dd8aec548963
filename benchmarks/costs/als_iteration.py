"""Useful operations of one ALS iteration (both half-iterations), from its
shapes: per side the Gramian accumulation 2·nnz·k² and the right-hand side
2·nnz·k, plus a Cholesky-sized solve k³/3 + 2k² per row. A copy of
``bench_batch.py:_useful_flops_per_iter`` (sound arithmetic; its caller
divided by host wall and an unpublished peak). Recomputed or padded work
does not count."""


def flops(nnz: int, n_users: int, n_items: int, k: int) -> float:
    per_side = 2.0 * nnz * k * k + 2.0 * nnz * k
    solve = (n_users + n_items) * (k ** 3 / 3.0 + 2.0 * k * k)
    return 2.0 * per_side + solve
