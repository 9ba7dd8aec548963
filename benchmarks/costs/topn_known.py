"""Operations and bytes one batched top-N call of the default ``/recommend``
needs, from its shapes: ``costs/topn.py``'s scan — the scoring copy of Y
read once, the queries, ``b·top`` values and indices at the width of the
answer (``how-many`` 10 in a pow2 of 16, NOT an over-fetched width) — and
the ``b × known`` row indices that say what each query leaves out. The
``(b, n)`` score matrix is NOT counted: a program that writes it, or that
fetches wider lists to drop rows from, reads lower against this.
"""


def flops_bytes(b: int, n: int, k: int, top: int = 16, item_bytes: int = 2,
                known: int = 20):
    flops = 2.0 * b * n * k
    bytes_ = (float(n) * k * item_bytes + b * k * 4.0 + b * known * 4.0
              + b * top * 8.0)
    return flops, bytes_
