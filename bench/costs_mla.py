"""Operations and bytes the latent (MLA) paged-attention kernel needs, from
its shapes alone."""
from __future__ import annotations

from typing import Iterable


def latent_attention_cost(lengths: Iterable[int], heads: int,
                          latent_dim: int, value_dim: int, itemsize: int):
    """(flops, bytes) that absorbed MLA decode attention over a paged latent
    cache needs for one call.

    Bytes: each sequence's valid tokens, read once (``tokens * latent_dim *
    itemsize``): scores and values are the same vector, the values being its
    leading ``value_dim`` channels. Plus each sequence's query
    (``heads * latent_dim``) and output (``heads * value_dim``). Page size,
    padding and the block table do not count. Flops: ``q . kv`` over
    ``latent_dim`` channels and ``p . v`` over ``value_dim``, a multiply-add
    each, per head and token: ``2 * tokens * heads * (latent_dim +
    value_dim)``."""
    lengths = [int(n) for n in lengths]
    tokens = sum(lengths)
    kv_bytes = tokens * latent_dim * itemsize
    qo_bytes = len(lengths) * heads * (latent_dim + value_dim) * itemsize
    flops = 2 * tokens * heads * (latent_dim + value_dim)
    return flops, kv_bytes + qo_bytes
