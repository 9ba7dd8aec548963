"""Operations and bytes the gather-Gramian stage of one half-iteration
needs, from its shapes: every one of the ``nnz`` entries gathers one factor
row of ``k`` float32 (the kernel gathers 32-bit rows whatever the compute
dtype) and its column index and value (8 B), and adds one weighted outer
product (2·k²) and one right-hand-side term (2·k); each of the ``rows``
solved rows writes its ``k × k`` Gramian and ``k`` right-hand side once.
Padding slots and the 128-lane padding of a row are not counted: they are
the layout's, not the algorithm's."""


def flops_bytes(nnz: int, rows: int, k: int):
    flops = 2.0 * nnz * k * k + 2.0 * nnz * k
    bytes_ = nnz * (k * 4.0 + 8.0) + rows * (k * k + k) * 4.0
    return flops, bytes_
