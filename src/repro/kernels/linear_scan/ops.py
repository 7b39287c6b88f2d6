"""Jit'd wrappers for the linear-scan kernels (kernel / xla dispatch)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .kernel import diag_scan_kernel, gla_scan_kernel
from .ref import diag_scan_ref, gla_scan_ref


def diag_scan(a: jnp.ndarray, b: jnp.ndarray,
              h0: Optional[jnp.ndarray] = None, *, impl: str = "xla",
              chunk: int = 256) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, T, D = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, D), a.dtype)
    if impl == "kernel":
        pad = (-T) % min(chunk, max(T, 1))
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
            b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        h, hT = diag_scan_kernel(a, b, h0, chunk=chunk,
                                 interpret=interpret_mode())
        if pad:
            h = h[:, :T]
            hT = h[:, -1]
        return h, hT
    if impl == "xla":
        return diag_scan_ref(a, b, h0)
    raise ValueError(f"unknown impl {impl!r}")


def gla_scan(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, w: jnp.ndarray,
             u: jnp.ndarray, *, impl: str = "xla",
             chunk: int = 64) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """RWKV6 wkv core. w = LOG decays. See ref.gla_scan_ref for shapes."""
    if impl == "kernel":
        B, T, Dk = r.shape
        c = min(chunk, T)
        pad = (-T) % c
        if pad:
            widths = ((0, 0), (0, pad), (0, 0))
            r = jnp.pad(r, widths)
            k = jnp.pad(k, widths)
            v = jnp.pad(v, widths)
            w = jnp.pad(w, widths)  # log-decay 0 = no decay; k=0 → no update
        o, S = gla_scan_kernel(r, k, v, w, u, chunk=c,
                               interpret=interpret_mode())
        return (o[:, :T] if pad else o), S
    if impl == "xla":
        return gla_scan_ref(r, k, v, w, u)
    if impl == "xla_chunked":
        return _gla_chunked_xla(r, k, v, w, u, chunk=chunk)
    raise ValueError(f"unknown impl {impl!r}")


def _gla_chunked_xla(r, k, v, w, u, *, chunk: int = 64):
    """Chunk-parallel GLA in pure XLA (lax.scan over chunks, matmuls within):
    the same math as the Pallas kernel — used for dry-run lowering so the HLO
    contains the real matmul structure (and its FLOPs) instead of a
    length-T sequential loop."""
    B, T, Dk = r.shape
    Dv = v.shape[-1]
    c = min(chunk, T)
    pad = (-T) % c
    if pad:
        widths = ((0, 0), (0, pad), (0, 0))
        r, k, v, w = (jnp.pad(x, widths) for x in (r, k, v, w))
    Tp = r.shape[1]
    nc = Tp // c

    def reshape(x):
        return jnp.moveaxis(
            x.reshape(B, nc, c, x.shape[-1]), 1, 0).astype(jnp.float32)

    rs, ks, vs, ws = map(reshape, (r, k, v, w))
    uf = u.astype(jnp.float32)

    def step(S, inp):
        rc, kc, vc, wc = inp                 # [B, c, D*]
        cum = jnp.cumsum(wc, axis=1)
        ex_cum = cum - wc
        c_last = cum[:, -1:, :]
        q_inter = rc * jnp.exp(ex_cum)
        q_intra = rc * jnp.exp(ex_cum - c_last)
        k_intra = kc * jnp.exp(c_last - cum)
        o = jnp.einsum("blk,bkv->blv", q_inter, S)
        A = jnp.einsum("bik,bjk->bij", q_intra, k_intra)
        ii = jnp.arange(c)
        A = jnp.where(ii[None, :, None] > ii[None, None, :], A, 0.0)
        bonus = jnp.einsum("blk,bk,blk->bl", rc, uf, kc)
        o = o + jnp.einsum("bij,bjv->biv", A, vc) + bonus[..., None] * vc
        S = jnp.exp(c_last).swapaxes(1, 2) * S + jnp.einsum(
            "blk,blv->bkv", k_intra, vc)
        return S, o

    S0 = jnp.zeros((B, Dk, Dv), jnp.float32)
    S, os = jax.lax.scan(step, S0, (rs, ks, vs, ws))
    o = jnp.moveaxis(os, 0, 1).reshape(B, Tp, Dv)[:, :T].astype(v.dtype)
    return o, S
