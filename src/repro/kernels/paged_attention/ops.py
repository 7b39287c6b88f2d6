"""Jit'd wrappers for paged decode attention (kernel / xla fallback), over
a K/V page pool (``paged_attention``) or a latent one
(``paged_latent_attention``).

Each path is one ``jax.jit`` built at import, so its cache lives as long as
the process: a call compiles once per (device, batch, max pages, dtype) and
every later call of that shape dispatches the compiled program. An eager
``pallas_call`` would trace, lower and fetch its program on every call.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .kernel import paged_attention_kernel, paged_latent_attention_kernel
from .ref import paged_attention_ref, paged_latent_attention_ref

_kernel = jax.jit(paged_attention_kernel,
                  static_argnames=("scale", "interpret"))
_xla = jax.jit(paged_attention_ref, static_argnames=("scale",))
_latent_kernel = jax.jit(paged_latent_attention_kernel,
                         static_argnames=("value_dim", "scale", "interpret"))
_latent_xla = jax.jit(paged_latent_attention_ref,
                      static_argnames=("value_dim", "scale"))


def paged_attention(q: jnp.ndarray, kv_pages: jnp.ndarray,
                    block_tables: jnp.ndarray, lengths: jnp.ndarray, *,
                    scale: Optional[float] = None,
                    impl: str = "kernel") -> jnp.ndarray:
    """Decode attention over a paged KV pool.

    impl: "kernel" (Pallas; compiled on TPU, interpreted on CPU) or "xla"
    (the gather-based reference; lowers everywhere).
    """
    if impl == "kernel":
        return _kernel(q, kv_pages, block_tables, lengths, scale=scale,
                       interpret=interpret_mode())
    if impl == "xla":
        return _xla(q, kv_pages, block_tables, lengths, scale=scale)
    raise ValueError(f"unknown impl {impl!r}")


def paged_latent_attention(q: jnp.ndarray, kv_pages: jnp.ndarray,
                           block_tables: jnp.ndarray, lengths: jnp.ndarray,
                           *, value_dim: int, scale: float,
                           impl: str = "kernel") -> jnp.ndarray:
    """Decode attention over a latent page pool ``[P, page, C]``: q
    ``[B, H, C]`` -> ``[B, H, value_dim]``, the values being each token's
    leading ``value_dim`` channels. ``impl`` as for ``paged_attention``."""
    if impl == "kernel":
        return _latent_kernel(q, kv_pages, block_tables, lengths,
                              value_dim=value_dim, scale=scale,
                              interpret=interpret_mode())
    if impl == "xla":
        return _latent_xla(q, kv_pages, block_tables, lengths,
                           value_dim=value_dim, scale=scale)
    raise ValueError(f"unknown impl {impl!r}")
