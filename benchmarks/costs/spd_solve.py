"""Operations and bytes of the batched SPD solve of one half-iteration:
``rows`` systems of order ``k``; a Cholesky-sized solve is k³/3 + 2k²
operations, and each system is read once (k² + k float32) and its
solution written once (k float32)."""


def flops_bytes(rows: int, k: int):
    flops = rows * (k ** 3 / 3.0 + 2.0 * k * k)
    bytes_ = rows * (k * k + 2.0 * k) * 4.0
    return flops, bytes_
