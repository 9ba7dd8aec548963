"""Operations and bytes one batched top-N call over int8 rows needs, from
its shapes: Y read once as ``n·k`` bytes of int8 and one float32 scale a
row, the queries (``b·k`` float32), and ``b·top`` candidate values and
indices at the rescore width (``rescore-factor × how-many`` = 40 in a pow2
of 64). Neither the ``(b, n)`` score matrix nor a float32 or bfloat16 copy
of the rows is counted: a program that writes either reads lower against
this. The rescore is the host's, and no part of the device call.
"""


def flops_bytes(b: int, n: int, k: int, top: int = 64, item_bytes: int = 1,
                scale_bytes: int = 4):
    flops = 2.0 * b * n * k
    bytes_ = (float(n) * (k * item_bytes + scale_bytes) + b * k * 4.0
              + b * top * 8.0)
    return flops, bytes_
