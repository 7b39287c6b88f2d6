"""Plain references for the latent (MLA) KV cells' correctness checks.

Nothing here imports the program. The serving tier's latent content is its
documented data contract (``src/repro/runtime/serving.py``, module
docstring): element ``(layer l, token t, channel c)`` of sequence ``s``
holds ``value(0, s, t, l, 0, c)``, and the query of a decode step at ``n``
committed tokens holds ``value(1, s, n, l, h, c)`` at head ``h``, where in
uint32 arithmetic (products and sums mod 2**32)::

    h = 0x9E3779B1*stream + 0x85EBCA77*s + 0xC2B2AE3D*t
        + 0x27D4EB2F*l + 0x165667B1*head + 0xD3A2646D*c
    h ^= h >> 16; h *= 0x85EBCA6B; h ^= h >> 13; h *= 0xC2B2AE35
    h ^= h >> 16
    value = (h >> 8) * 2**-23 - 1

cast to the pool's dtype. Attention of one head over a session's valid
tokens is the softmax over ``scale * q . kv`` (all ``C`` channels) of the
leading ``value_dim`` channels of each token's vector. The references
rebuild the content from the sequence id alone, with numpy or with
``jax.numpy`` (the same bits), and compute attention in float32 at the
highest matmul precision (``jax.numpy``) or in float64 (numpy).
"""
from __future__ import annotations

import numpy as np

KEYS = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F, 0x165667B1,
        0xD3A2646D)
MIX = (0x85EBCA6B, 0xC2B2AE35)


def _hash(xp, idx):
    u32 = xp.uint32
    h = xp.zeros((), u32)
    for key, x in zip(KEYS, idx):
        h = h + xp.asarray(x, u32) * u32(key)
    h = h ^ (h >> u32(16))
    h = h * u32(MIX[0])
    h = h ^ (h >> u32(13))
    h = h * u32(MIX[1])
    h = h ^ (h >> u32(16))
    return (h >> u32(8)).astype(xp.float32) * xp.float32(2.0 ** -23) \
        - xp.float32(1.0)


def value_np(stream, seq_id, position, layer, head, channel) -> np.ndarray:
    """The contract's value (float32, exact) at broadcast numpy indices."""
    with np.errstate(over="ignore"):
        return _hash(np, (stream, seq_id, position, layer, head, channel))


def value_jnp(stream, seq_id, position, layer, head, channel):
    """The same bits in ``jax.numpy`` (uint32 arithmetic wraps there)."""
    import jax.numpy as jnp
    return _hash(jnp, (stream, seq_id, position, layer, head, channel))


def session_latent_np(seq_id: int, layer: int, tokens: int, channels: int,
                      dtype) -> np.ndarray:
    """One layer's cached vectors ``[tokens, C]`` of a sequence."""
    return value_np(0, seq_id, np.arange(tokens)[:, None], layer, 0,
                    np.arange(channels)[None, :]).astype(dtype)


def query_np(seq_id: int, length: int, layer: int, heads: int,
             channels: int, dtype) -> np.ndarray:
    """The query ``[heads, C]`` of a step at ``length`` committed tokens."""
    return value_np(1, seq_id, length, layer, np.arange(heads)[:, None],
                    np.arange(channels)[None, :]).astype(dtype)


def page_matches(slab: np.ndarray, seq_id: int, page_index: int,
                 length: int, page_tokens: int) -> bool:
    """Whether a latent page slab ``[L, page, C]`` holds exactly the bytes
    the contract gives for that page at ``length`` committed tokens."""
    if slab.ndim != 3 or slab.shape[1] != page_tokens:
        return False
    layers, _, channels = slab.shape
    t = page_index * page_tokens + np.arange(page_tokens)
    want = value_np(0, seq_id, t[None, :, None],
                    np.arange(layers)[:, None, None], 0,
                    np.arange(channels)[None, None, :])
    want = np.where(t[None, :, None] < length, want, 0).astype(slab.dtype)
    return np.array_equal(slab.view(np.uint8), want.view(np.uint8))


def attention_f64(q: np.ndarray, kv: np.ndarray, value_dim: int,
                  scale: float) -> np.ndarray:
    """Plain float64 latent decode attention for one sequence: q [H, C],
    kv [T, C] (its valid tokens) -> [H, value_dim]."""
    q = np.asarray(q, np.float64)
    kv = np.asarray(kv, np.float64)
    s = scale * (q @ kv.T)
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p @ kv[:, :value_dim]) / p.sum(-1, keepdims=True)


def steps_attention_jnp(seq_id, layer, lengths, tokens: int, heads: int,
                        channels: int, value_dim: int, scale: float, dtype):
    """Float32 reference outputs ``[K, H, value_dim]`` of one session and
    layer at each of ``K`` committed ``lengths``, its content and queries
    built on the device from the contract (``tokens`` >= every length,
    static). Inputs are rounded to ``dtype`` as the pool holds them."""
    import jax.numpy as jnp
    kv = value_jnp(0, seq_id, jnp.arange(tokens)[:, None], layer, 0,
                   jnp.arange(channels)[None, :]).astype(dtype)
    q = value_jnp(1, seq_id, lengths[:, None, None], layer,
                  jnp.arange(heads)[None, :, None],
                  jnp.arange(channels)[None, None, :]).astype(dtype)
    kv = kv.astype(jnp.float32)
    s = jnp.einsum("khc,tc->kht", q.astype(jnp.float32), kv,
                   precision="highest") * scale
    s = jnp.where(jnp.arange(tokens)[None, None, :] < lengths[:, None, None],
                  s, -jnp.inf)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    o = jnp.einsum("kht,tv->khv", p, kv[:, :value_dim], precision="highest")
    return o / p.sum(-1, keepdims=True)


def paged_latent_attention_jnp(q, kv_pages, block_tables, lengths, *,
                               value_dim: int, scale: float, lower=None):
    """Latent decode attention over a paged pool written plainly in
    jax.numpy, in float32 at the highest matmul precision. Same signature
    and result as the program's paged latent attention (q [B, H, C],
    kv_pages [P, page, C], block_tables [B, max_pages], lengths [B]) ->
    [B, H, value_dim]. With ``lower`` (a dtype) the query and the cache are
    first rounded to it: the check's control."""
    import jax.numpy as jnp
    B = q.shape[0]
    page, channels = kv_pages.shape[1:]
    T = block_tables.shape[1] * page

    def cast(x):
        x = x if lower is None else x.astype(lower)
        return x.astype(jnp.float32)

    kv = cast(kv_pages[jnp.maximum(block_tables, 0)].reshape(B, T, channels))
    s = jnp.einsum("bhc,btc->bht", cast(q), kv, precision="highest") * scale
    s = jnp.where(jnp.arange(T)[None, None, :] < lengths[:, None, None],
                  s, -1e30)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    o = jnp.einsum("bht,btv->bhv", p, kv[..., :value_dim],
                   precision="highest")
    return (o / p.sum(-1, keepdims=True)).astype(q.dtype)


def rel_gap(out, ref) -> float:
    """The check's measure: the widest gap of one output against its
    reference, over the reference's root mean square (attention over many
    tokens averages values down, so an absolute gap would shrink with the
    context; this one does not)."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    rms = float(np.sqrt(np.mean(ref ** 2)))
    return float(np.abs(out - ref).max()) / rms if rms > 0 else np.inf
