"""Jit'd public wrapper for flash attention.

Dispatches between the Pallas TPU kernel (compiled on TPU, interpreted on
CPU) and a chunked pure-XLA path (``lax.scan`` over kv blocks with
online softmax — same O(T) memory; used for dry-run lowering on CPU and as a
portable fallback). Handles padding to block multiples and GQA.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .kernel import flash_attention_kernel
from .ref import attention_ref


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None, q_offset: int = 0,
                    impl: str = "xla", block_q: int = 128,
                    block_k: int = 128,
                    p_bf16: bool = False) -> jnp.ndarray:
    """q [B,H,Tq,D], k/v [B,KH,Tk,D] -> [B,H,Tq,D].

    impl: "kernel" (Pallas; interpreted on CPU), "xla" (chunked scan,
    lowers everywhere), "naive" (reference; O(T^2) memory — tests only).
    ``p_bf16``: cast softmax weights to bf16 for the PV matmul (halves the
    p-matrix HBM traffic; standard flash-kernel practice).
    """
    if impl == "naive":
        return attention_ref(q, k, v, causal=causal, window=window,
                             scale=scale, q_offset=q_offset)
    if impl == "kernel":
        Tq, Tk = q.shape[2], k.shape[2]
        bq, bk = min(block_q, Tq), min(block_k, Tk)
        qp = _pad_to(q, 2, bq)
        kp = _pad_to(k, 2, bk)
        vp = _pad_to(v, 2, bk)
        out = flash_attention_kernel(
            qp, kp, vp, causal=causal, window=window, scale=scale,
            q_offset=q_offset, kv_len=Tk, block_q=bq, block_k=bk,
            interpret=interpret_mode())
        return out[:, :, :Tq]
    if impl == "xla":
        return _chunked_attention(q, k, v, causal, window, scale, q_offset,
                                  block_k, p_bf16)
    raise ValueError(f"unknown impl {impl!r}")


def _chunk_mask(Tq, Tk, bk, ki, q_offset, causal, window):
    q_pos = q_offset + jnp.arange(Tq)
    k_pos = ki * bk + jnp.arange(bk)
    mask = (k_pos < Tk)[None, :]
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    return mask  # [Tq, bk]


def _attn_fwd_core(q, k, v, causal, window, scale, q_offset, block_k,
                   p_bf16=False):
    B, H, Tq, D = q.shape
    _, KH, Tk, _ = k.shape
    Dv = v.shape[-1]
    group = H // KH
    bk = min(block_k, Tk)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    nk = kp.shape[2] // bk
    qf = q.astype(jnp.float32)

    ks = jnp.moveaxis(kp.reshape(B, KH, nk, bk, D), 2, 0)
    vs = jnp.moveaxis(vp.reshape(B, KH, nk, bk, Dv), 2, 0)

    def step(carry, inputs):
        m, l, acc = carry
        ki, kb, vb = inputs
        kb = jnp.repeat(kb.astype(jnp.float32), group, axis=1)
        vb = jnp.repeat(vb.astype(jnp.float32), group, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb) * scale
        mask = _chunk_mask(Tq, Tk, bk, ki, q_offset, causal, window)
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1, keepdims=True)
        if p_bf16:
            p = p.astype(jnp.bfloat16)
        acc = acc * corr + jnp.einsum("bhqk,bhkd->bhqd", p, vb,
                                      preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, Tq, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, Tq, 1), jnp.float32)
    acc0 = jnp.zeros((B, H, Tq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0),
                                  (jnp.arange(nk), ks, vs))
    l = jnp.maximum(l, 1e-20)
    out = (acc / l).astype(q.dtype)
    return out, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _chunked_attention(q, k, v, causal, window, scale, q_offset,
                       block_k=512, p_bf16=False):
    """Online-softmax attention as a lax.scan over kv chunks with a
    flash-style custom VJP: the backward pass recomputes per-chunk scores
    instead of saving them, so training memory stays O(Tq·D) rather than
    O(Tq·Tk) (the standard flash-attention backward, in pure XLA). Compiles
    to a compact HLO loop (used for the 32k prefill dry-run). Supports
    Dv != Dk (MLA)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, _, _ = _attn_fwd_core(q, k, v, causal, window, scale, q_offset,
                               block_k, p_bf16)
    return out


def _chunked_attention_fwd(q, k, v, causal, window, scale, q_offset,
                           block_k=512, p_bf16=False):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, m, l = _attn_fwd_core(q, k, v, causal, window, scale, q_offset,
                               block_k, p_bf16)
    return out, (q, k, v, out, m, l)


def _chunked_attention_bwd(causal, window, scale, q_offset, block_k, p_bf16,
                           res, dout):
    q, k, v, out, m, l = res
    B, H, Tq, D = q.shape
    _, KH, Tk, _ = k.shape
    Dv = v.shape[-1]
    group = H // KH
    if scale is None:
        scale = D ** -0.5
    bk = min(block_k, Tk)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    nk = kp.shape[2] // bk
    qf = q.astype(jnp.float32)
    do = dout.astype(jnp.float32)
    # delta_i = sum_d dout_i * out_i  (flash-attn bwd identity)
    delta = jnp.sum(do * out.astype(jnp.float32), axis=-1, keepdims=True)

    ks = jnp.moveaxis(kp.reshape(B, KH, nk, bk, D), 2, 0)
    vs = jnp.moveaxis(vp.reshape(B, KH, nk, bk, Dv), 2, 0)

    def step(dq, inputs):
        ki, kb, vb = inputs
        kbr = jnp.repeat(kb.astype(jnp.float32), group, axis=1)
        vbr = jnp.repeat(vb.astype(jnp.float32), group, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kbr) * scale
        mask = _chunk_mask(Tq, Tk, bk, ki, q_offset, causal, window)
        s = jnp.where(mask[None, None], s, -1e30)
        p = jnp.exp(s - m) / l                        # true softmax weights
        if p_bf16:
            p = p.astype(jnp.bfloat16)
        dv_c = jnp.einsum("bhqk,bhqd->bhkd", p, do,
                          preferred_element_type=jnp.float32)  # [B,H,bk,Dv]
        dp = jnp.einsum("bhqd,bhkd->bhqk", do, vbr)
        ds = p * (dp - delta) * scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kbr)
        dk_c = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        # fold GQA groups back into kv heads
        dk_c = dk_c.reshape(B, KH, group, bk, D).sum(axis=2)
        dv_c = dv_c.reshape(B, KH, group, bk, Dv).sum(axis=2)
        return dq, (dk_c, dv_c)

    dq0 = jnp.zeros((B, H, Tq, D), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(step, dq0, (jnp.arange(nk), ks, vs))
    dk = jnp.moveaxis(dks, 0, 2).reshape(B, KH, nk * bk, D)[:, :, :Tk]
    dv = jnp.moveaxis(dvs, 0, 2).reshape(B, KH, nk * bk, Dv)[:, :, :Tk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_chunked_attention.defvjp(_chunked_attention_fwd, _chunked_attention_bwd)
