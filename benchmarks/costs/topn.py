"""Operations and bytes one batched top-N call needs, from its shapes.

The scan reads the scoring copy of Y once (``n·k`` entries of
``item_bytes``), the queries (``b·k`` float32) and writes ``b·top`` values
and indices; it multiplies ``b × k`` by ``k × n``. The ``(b, n)`` score
matrix is NOT counted: an implementation may keep it on the chip.
"""


def flops_bytes(b: int, n: int, k: int, top: int = 16, item_bytes: int = 2):
    flops = 2.0 * b * n * k
    bytes_ = float(n) * k * item_bytes + b * k * 4.0 + b * top * 8.0
    return flops, bytes_
