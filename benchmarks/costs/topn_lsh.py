"""Operations and bytes one batched top-N call under LSH needs, from its
shapes and from what the flush's host stage chose (``topn.lsh_select``):
the ``rows`` of the bucket-ordered scoring copy it reads (its candidate
buckets' windows, padding included, or every position in a full pass),
``k`` entries of ``item_bytes`` each; the queries (``b·k`` float32); what
says which rows are whose — an int32 window index and an int32 of owner
bits a window of ``tile`` rows where the flush listed them, an int32
bucket a position in a full pass — and ``b·top`` values and indices. Neither a ``(b, rows)``
score matrix nor a gathered copy of the rows is counted: a program that
writes either reads lower against this.
"""


def flops_bytes(b: int, rows: int, k: int, listed: bool, top: int = 16,
                item_bytes: int = 2, tile: int = 128):
    flops = 2.0 * b * rows * k
    which = rows / tile * 8.0 if listed else rows * 4.0
    bytes_ = float(rows) * k * item_bytes + b * k * 4.0 + which + b * top * 8.0
    return flops, bytes_
