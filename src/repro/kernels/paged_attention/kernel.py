"""Paged decode-attention Pallas TPU kernel.

The device half of the Pangea KV buffer pool: attention reads KV directly
from the page pool via a scalar-prefetched block table — no gather/copy into a
contiguous buffer (the monolithic no-redundant-copies principle applied to
HBM). Grid ``(B, max_pages)``, pages sequential with online-softmax scratch
carried across page steps; the block table is prefetched to SMEM so each
page's DMA address is known before the step runs.

TARGET: TPU (VMEM block = one KV page). Validated with interpret=True on CPU.
Both matmuls run at ``Precision.HIGHEST``: at default precision the TPU
may round f32 matmul inputs to bf16 (about 1e-3 relative error), and an f32
KV pool is expected to give f32 attention.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
HIGHEST = jax.lax.Precision.HIGHEST


def _paged_kernel(bt_ref, len_ref, q_ref, kv_ref, o_ref, acc_ref, m_ref,
                  l_ref, *, page_size: int, scale: float, kv_heads: int,
                  group: int):
    b = pl.program_id(0)
    p = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    live = p * page_size < length

    @pl.when(live)
    def _compute():
        H = kv_heads * group
        q = q_ref[0].astype(jnp.float32)                  # [H, D]
        D = q.shape[-1]
        qg = q.reshape(kv_heads, group, D)
        k = kv_ref[0, :, 0].astype(jnp.float32)           # [page, KH, D]
        v = kv_ref[0, :, 1].astype(jnp.float32)
        kt = jnp.swapaxes(k, 0, 1)                        # [KH, page, D]
        vt = jnp.swapaxes(v, 0, 1)
        # s[kh, g, t] — batched over kv head
        s = jax.lax.dot_general(
            qg, kt, (((2,), (2,)), ((0,), (0,))), precision=HIGHEST,
            preferred_element_type=jnp.float32) * scale   # [KH, G, page]
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (kv_heads, group, page_size), 2)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[...].reshape(kv_heads, group, 1)
        l_prev = l_ref[...].reshape(kv_heads, group, 1)
        acc_prev = acc_ref[...].reshape(kv_heads, group, D)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + pexp.sum(-1, keepdims=True)
        acc_new = acc_prev * corr + jax.lax.dot_general(
            pexp, vt, (((2,), (1,)), ((0,), (0,))), precision=HIGHEST,
            preferred_element_type=jnp.float32)           # [KH, G, D]
        m_ref[...] = m_new.reshape(H, 1)
        l_ref[...] = l_new.reshape(H, 1)
        acc_ref[...] = acc_new.reshape(H, D)

    @pl.when(p == np_ - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_kernel(q: jnp.ndarray, kv_pages: jnp.ndarray,
                           block_tables: jnp.ndarray, lengths: jnp.ndarray, *,
                           scale: Optional[float] = None,
                           interpret: bool = False) -> jnp.ndarray:
    """q: [B, H, D]; kv_pages: [P, page, 2, KH, D];
    block_tables: [B, max_pages]; lengths: [B]. Returns [B, H, D]."""
    B, H, D = q.shape
    P, page, _, KH, _ = kv_pages.shape
    max_pages = block_tables.shape[1]
    group = H // KH
    if scale is None:
        scale = D ** -0.5

    kernel = functools.partial(_paged_kernel, page_size=page, scale=scale,
                               kv_heads=KH, group=group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, p, bt, ln: (b, 0, 0)),
            pl.BlockSpec((1, page, 2, KH, D),
                         lambda b, p, bt, ln: (jnp.maximum(bt[b, p], 0),
                                               0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, p, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, D), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q, kv_pages)


def _latent_kernel(bt_ref, len_ref, q_ref, kv_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, page_size: int, scale: float, value_dim: int):
    b = pl.program_id(0)
    p = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    @pl.when(p * page_size < length)
    def _compute():
        q = q_ref[0]                                      # [H, C]
        kv = kv_ref[0]                                    # [page, C]
        # an f32 pool takes exact f32 matmuls; a bf16 pool's products are
        # exact in the f32 accumulator at the MXU's native precision
        exact = HIGHEST if kv.dtype == jnp.float32 else None
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())), precision=exact,
            preferred_element_type=jnp.float32) * scale   # [H, page]
        pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + pexp.sum(-1, keepdims=True)
        # the value is the leading ``value_dim`` channels of the same block
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            pexp.astype(kv.dtype), kv[:, :value_dim],
            (((1,), (0,)), ((), ())), precision=exact,
            preferred_element_type=jnp.float32)           # [H, value_dim]
        m_ref[...] = m_new

    @pl.when(p == np_ - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_latent_attention_kernel(q: jnp.ndarray, kv_pages: jnp.ndarray,
                                  block_tables: jnp.ndarray,
                                  lengths: jnp.ndarray, *, value_dim: int,
                                  scale: float,
                                  interpret: bool = False) -> jnp.ndarray:
    """Decode attention over a latent page pool (MLA, absorbed).

    q: [B, H, C], each head's query against the whole cached vector
    (``W_UK`` absorbed into its no-rope part); kv_pages: [P, page, C], one
    vector a token that every head shares; block_tables: [B, max_pages];
    lengths: [B]. Scores are ``q . kv`` over all C channels, values the
    leading ``value_dim`` channels of the same page block. Returns
    [B, H, value_dim]. Grid ``(B, max_pages)``: one page block a step, the
    block table prefetched to SMEM, online softmax across page steps."""
    B, H, C = q.shape
    P, page, _ = kv_pages.shape
    max_pages = block_tables.shape[1]
    kernel = functools.partial(_latent_kernel, page_size=page, scale=scale,
                               value_dim=value_dim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, H, C), lambda b, p, bt, ln: (b, 0, 0)),
            pl.BlockSpec((1, page, C),
                         lambda b, p, bt, ln: (jnp.maximum(bt[b, p], 0),
                                               0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, value_dim),
                               lambda b, p, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, value_dim), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, value_dim), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q, kv_pages)
