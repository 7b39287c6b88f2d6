"""Plain references for the benchmark's correctness checks.

Nothing here imports the program. The serving tier's KV content is its
documented data contract: every element of token ``t`` of sequence ``s``
(all layers, heads, channels, K and V) holds
``((s * 7919 + t * 104729) % 997) / 997`` in the pool's dtype, and the
query of a decode step at ``length`` committed tokens holds the same
function of ``(s, length)``. The references rebuild that content from the
sequence id alone and compute attention over it in float64.
"""
from __future__ import annotations

import numpy as np


def token_values(seq_id: int, positions: np.ndarray) -> np.ndarray:
    """KV value of each position of a sequence (float64, before the cast to
    the pool's dtype)."""
    t = np.asarray(positions, np.int64)
    return ((np.int64(seq_id) * 7919 + t * 104729) % 997) / 997.0


def query_value(seq_id: int, length: int) -> float:
    return float(token_values(seq_id, np.array([length]))[0])


def page_values(seq_id: int, page_index: int, length: int, page_tokens: int,
                dtype=np.float32) -> np.ndarray:
    """Values of one page's tokens, zero past the committed length."""
    t = page_index * page_tokens + np.arange(page_tokens)
    return np.where(t < length, token_values(seq_id, t), 0.0).astype(dtype)


def page_matches(slab: np.ndarray, seq_id: int, page_index: int, length: int,
                 page_tokens: int) -> bool:
    """Whether a page slab ``[L, page, 2, KH, D]`` holds exactly the bytes the
    contract gives for that page at ``length`` committed tokens."""
    want = page_values(seq_id, page_index, length, page_tokens, slab.dtype)
    return (slab.ndim == 5 and slab.shape[1] == page_tokens
            and np.array_equal(slab, np.broadcast_to(
                want[None, :, None, None, None], slab.shape)))


def attention_f64(q: np.ndarray, k: np.ndarray, v: np.ndarray) -> np.ndarray:
    """Plain float64 decode attention for one sequence: q [H, D],
    k/v [T, KH, D] (its valid tokens) -> [H, D]."""
    q = np.asarray(q, np.float64)
    heads, dim = q.shape
    group = heads // k.shape[1]
    kk = np.repeat(np.asarray(k, np.float64), group, axis=1)   # [T, H, D]
    vv = np.repeat(np.asarray(v, np.float64), group, axis=1)
    s = np.einsum("hd,thd->ht", q, kk) / np.sqrt(dim)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("ht,thd->hd", p / p.sum(-1, keepdims=True), vv)


def session_attention(seq_id: int, length: int, heads: int, kv_heads: int,
                      head_dim: int, dtype=np.float32) -> np.ndarray:
    """The float64 reference output ``[heads, head_dim]`` of one decode step
    of a session at ``length`` committed tokens, from the contract alone.
    Every head and channel of a token holds one value, K equal to V, so the
    sums over channels of ``attention_f64`` are done once, not per channel:
    the same arithmetic in float64, over ``[length]`` and not
    ``[length, kv_heads, head_dim]``."""
    vals = token_values(seq_id, np.arange(length)).astype(dtype).astype(
        np.float64)
    q = float(np.asarray(query_value(seq_id, length), dtype))
    s = q * vals * head_dim / np.sqrt(head_dim)
    p = np.exp(s - s.max())
    return np.full((heads, head_dim), float(p @ vals) / float(p.sum()))


def paged_attention_jnp(q, kv_pages, block_tables, lengths, lower=None):
    """Decode attention over a paged pool written plainly in jax.numpy, in
    float32 at the highest matmul precision. Same signature and result as
    the program's paged attention (q [B, H, D], kv_pages
    [P, page, 2, KH, D], block_tables [B, max_pages], lengths [B])
    -> [B, H, D]. With ``lower`` (a dtype) the query, keys and values are
    first rounded to it: the check's control."""
    import jax.numpy as jnp
    B, H, D = q.shape
    _, page, _, KH, _ = kv_pages.shape
    T = block_tables.shape[1] * page

    def cast(x):
        x = x if lower is None else x.astype(lower)
        return x.astype(jnp.float32)

    exact = dict(precision="highest", preferred_element_type=jnp.float32)
    g = kv_pages[jnp.maximum(block_tables, 0)]          # [B, mp, page, 2, KH, D]
    k = jnp.repeat(g[..., 0, :, :].reshape(B, T, KH, D), H // KH, axis=2)
    v = jnp.repeat(g[..., 1, :, :].reshape(B, T, KH, D), H // KH, axis=2)
    s = jnp.einsum("bhd,bthd->bht", cast(q), cast(k), **exact) / np.sqrt(D)
    s = jnp.where(jnp.arange(T)[None, None, :] < lengths[:, None, None],
                  s, -1e30)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    o = jnp.einsum("bht,bthd->bhd", p, cast(v), **exact)
    return (o / p.sum(-1, keepdims=True)).astype(q.dtype)
