"""Operations and bytes ONE device of a mesh needs for one batched top-N
call over Y split by rows, from its shapes.

The device reads its own shard of the scoring copy once (``ceil(n/shards)·k``
entries of ``item_bytes``) and the queries (``b·k`` float32), and writes its
own ``b·top`` candidate values and indices; it multiplies ``b × k`` by
``k × n/shards``. Neither the ``(b, n/shards)`` score matrix nor the
candidates gathered from the other devices are counted: the first an
implementation may keep on the chip, the second is the merge's, which the
least time leaves out (so the share can only read lower for it).
"""


def flops_bytes(b: int, n: int, k: int, shards: int, top: int = 16,
                item_bytes: int = 2):
    n_local = -(-int(n) // int(shards))
    flops = 2.0 * b * n_local * k
    bytes_ = float(n_local) * k * item_bytes + b * k * 4.0 + b * top * 8.0
    return flops, bytes_
