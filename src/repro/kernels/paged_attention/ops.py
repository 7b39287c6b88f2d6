"""Jit'd wrapper for paged decode attention (kernel / xla fallback)."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from .. import interpret_mode
from .kernel import paged_attention_kernel
from .ref import paged_attention_ref


def paged_attention(q: jnp.ndarray, kv_pages: jnp.ndarray,
                    block_tables: jnp.ndarray, lengths: jnp.ndarray, *,
                    scale: Optional[float] = None,
                    impl: str = "kernel") -> jnp.ndarray:
    """Decode attention over a paged KV pool.

    impl: "kernel" (Pallas; compiled on TPU, interpreted on CPU) or "xla"
    (the gather-based reference; lowers everywhere).
    """
    if impl == "kernel":
        return paged_attention_kernel(q, kv_pages, block_tables, lengths,
                                      scale=scale, interpret=interpret_mode())
    if impl == "xla":
        return paged_attention_ref(q, kv_pages, block_tables, lengths,
                                   scale=scale)
    raise ValueError(f"unknown impl {impl!r}")
