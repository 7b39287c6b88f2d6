"""Operations and bytes a kernel call needs, from its shapes alone."""
from __future__ import annotations

from typing import Iterable


def paged_attention_cost(lengths: Iterable[int], heads: int, kv_heads: int,
                         head_dim: int, itemsize: int):
    """(flops, bytes) that decode attention over paged KV needs for one call.

    Bytes: each sequence's valid tokens of K and V
    (``tokens * 2 * kv_heads * head_dim * itemsize``), plus q and the output
    (``heads * head_dim * itemsize`` each). Page size, padding and the block
    table do not count: they belong to the implementation. Flops: ``q.k`` and
    ``p.v``, two multiply-adds per head, channel and token."""
    tokens = 0
    batch = 0
    for n in lengths:
        tokens += int(n)
        batch += 1
    kv_bytes = tokens * 2 * kv_heads * head_dim * itemsize
    qo_bytes = batch * 2 * heads * head_dim * itemsize
    flops = 4 * tokens * heads * head_dim
    return flops, kv_bytes + qo_bytes
