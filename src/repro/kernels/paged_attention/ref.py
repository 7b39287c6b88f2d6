"""Pure-jnp oracle for paged decode attention (f32 matmuls at HIGHEST
precision, so it stays an oracle on the TPU too)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def paged_attention_ref(q: jnp.ndarray, kv_pages: jnp.ndarray,
                        block_tables: jnp.ndarray, lengths: jnp.ndarray,
                        scale=None) -> jnp.ndarray:
    """q: [B, H, D]; kv_pages: [P, page, 2, KH, D];
    block_tables: [B, max_pages] int32 (physical page ids, -1 absent);
    lengths: [B] int32. Returns [B, H, D]."""
    B, H, D = q.shape
    P, page, _, KH, _ = kv_pages.shape
    max_pages = block_tables.shape[1]
    group = H // KH
    if scale is None:
        scale = D ** -0.5
    # gather each sequence's pages -> [B, max_pages, page, 2, KH, D]
    safe = jnp.maximum(block_tables, 0)
    gathered = kv_pages[safe]
    k = gathered[..., 0, :, :].reshape(B, max_pages * page, KH, D)
    v = gathered[..., 1, :, :].reshape(B, max_pages * page, KH, D)
    kk = jnp.repeat(k, group, axis=2)   # [B, T, H, D]
    vv = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                   kk.astype(jnp.float32), precision=HIGHEST) * scale
    pos = jnp.arange(max_pages * page)[None, :]
    mask = pos < lengths[:, None]
    s = jnp.where(mask[:, None, :], s, -1e30)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    o = jnp.einsum("bht,bthd->bhd", p, vv.astype(jnp.float32),
                   precision=HIGHEST)
    return (o / jnp.maximum(p.sum(-1, keepdims=True), 1e-20)).astype(q.dtype)


def paged_latent_attention_ref(q: jnp.ndarray, kv_pages: jnp.ndarray,
                               block_tables: jnp.ndarray,
                               lengths: jnp.ndarray, *, value_dim: int,
                               scale: float) -> jnp.ndarray:
    """q: [B, H, C]; kv_pages: [P, page, C] (one latent vector a token);
    block_tables: [B, max_pages]; lengths: [B]. Scores over all C channels,
    values the leading ``value_dim``. Returns [B, H, value_dim]."""
    B, H, C = q.shape
    page = kv_pages.shape[1]
    T = block_tables.shape[1] * page
    kv = kv_pages[jnp.maximum(block_tables, 0)].reshape(B, T, C)
    kv = kv.astype(jnp.float32)
    s = jnp.einsum("bhc,btc->bht", q.astype(jnp.float32), kv,
                   precision=HIGHEST) * scale
    s = jnp.where((jnp.arange(T)[None, :] < lengths[:, None])[:, None, :],
                  s, -1e30)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    o = jnp.einsum("bht,btc->bhc", p, kv[..., :value_dim], precision=HIGHEST)
    return (o / jnp.maximum(p.sum(-1, keepdims=True), 1e-20)).astype(q.dtype)
