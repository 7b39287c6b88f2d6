"""Expert-parallel MoE via shard_map: sort-based dispatch, no dense mask.

The einsum-dispatch MoE (blocks.moe_apply) materializes a [B, T, E, C] mask
and pays ~2·E·C·d FLOPs/token for dispatch+combine — for many-small-expert
models (deepseek: E=64, d_expert=1408) that rivals the expert FLOPs
themselves. This path is the paper's shuffle service done properly on TPU:

* routing (softmax/top-k) stays in plain pjit-land;
* inside ``shard_map`` each "model" shard holds E/n_model experts and every
  shard sees the (data-sharded, model-replicated) tokens, so dispatch is a
  local sort-based GATHER into [E_local, C, d] buffers (argsort by expert +
  static index matrix), expert FFN is a local batched matmul, and combine is
  a gated scatter-add followed by ONE psum over "model" per layer;
* comms per layer = a single [B, T, d] all-reduce (the same bytes the TP
  baseline pays), with zero dispatch-mask FLOPs or traffic.

Limitation: expert weights are sharded over "model" only in this path (no
FSDP dim inside the shard_map region); selected with
``moe_strategy="expert_parallel_shardmap"``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from ..sharding import get_mesh, get_rules
from . import blocks


def moe_shardmap_init(key, cfg: ArchConfig):
    """Same parameter structure as blocks.moe_init but expert weights carry
    only the "experts"->model sharding (shard_map needs whole experts)."""
    p, a = blocks.moe_init(key, cfg)
    for w in ("w1", "w3", "w2"):
        ax = list(a[w])
        a[w] = ("experts",) + (None,) * (len(ax) - 1)
    return p, a


def _dispatch_indices(eid_flat: jnp.ndarray, E: int, C: int
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Flat (token·K) expert assignments -> per-expert index matrix.

    Returns (idx [E, C] into the flat assignment array, valid [E, C]).
    Stable grouping: tokens keep arrival order within an expert.
    """
    N = eid_flat.shape[0]
    order = jnp.argsort(eid_flat * (N + 1) + jnp.arange(N))
    counts = jnp.bincount(jnp.maximum(eid_flat, 0), length=E,
                          minlength=E)
    offsets = jnp.cumsum(counts) - counts              # exclusive
    pos = offsets[:, None] + jnp.arange(C)[None, :]    # [E, C]
    valid = jnp.arange(C)[None, :] < counts[:, None]
    idx = jnp.take(order, jnp.clip(pos, 0, N - 1), axis=0)
    return jnp.where(valid, idx, 0), valid


def moe_shardmap_apply(p, x, *, cfg: ArchConfig, mesh=None):
    """Drop-in replacement for blocks.moe_apply (same (y, aux) contract)."""
    mesh = mesh or get_mesh()
    B, T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    Ntok = B * T
    # capacity is per dp-shard: inside shard_map each shard sees its local
    # tokens only (sizing from the global count would inflate buffers by
    # the dp degree)
    dp_size = 1
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp_size = sizes.get("pod", 1) * sizes.get("data", 1)
    n_loc = max(Ntok // dp_size, 1)
    C = max(4, -(-int(n_loc * K * cfg.capacity_factor / E) // 4) * 4)

    h = blocks.apply_norm(cfg, p.get("norm"), x)
    logits = jnp.einsum("btd,de->bte", h, p["w_router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eid = jax.lax.top_k(probs, K)
    gates = (gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
             ).astype(h.dtype)

    density = jnp.zeros((E,)).at[eid.reshape(-1)].add(1.0) / (Ntok * K)
    aux = ((density * probs.mean(axis=(0, 1))).sum() * E).astype(jnp.float32)

    if mesh is None or "model" not in mesh.axis_names:
        # single-device / no-mesh fallback: local math, no shard_map
        y = _local_moe(p, x, h, eid, gates, cfg, C)
        return y, aux

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tok_spec = P(dp if len(dp) > 1 else (dp[0] if dp else None))
    n_model = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    E_loc = E // n_model
    assert E % n_model == 0, (E, n_model)

    def local_fn(hf, eidf, gatesf, w1, w3, w2):
        # hf: [N_loc, d] (model-replicated); w*: [E_loc, ...]
        N_loc = hf.shape[0]
        flat_e = eidf.reshape(-1)                       # [N_loc*K]
        idx, valid = _dispatch_indices(flat_e, E, C)    # over GLOBAL experts
        shard = jax.lax.axis_index("model")
        my_idx = jax.lax.dynamic_slice_in_dim(idx, shard * E_loc, E_loc, 0)
        my_valid = jax.lax.dynamic_slice_in_dim(valid, shard * E_loc,
                                                E_loc, 0)
        tok = my_idx // K                               # flat -> token id
        buf = jnp.take(hf, tok, axis=0)                 # [E_loc, C, d]
        buf = buf * my_valid[..., None].astype(buf.dtype)
        g1 = jnp.einsum("ecd,edf->ecf", buf, w1)
        u1 = jnp.einsum("ecd,edf->ecf", buf, w3)
        out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g1) * u1, w2)
        gsel = jnp.take(gatesf.reshape(-1), my_idx) * my_valid.astype(
            gatesf.dtype)
        contrib = out * gsel[..., None]
        y = jnp.zeros((N_loc, d), out.dtype).at[tok.reshape(-1)].add(
            contrib.reshape(-1, d))
        return jax.lax.psum(y, "model")                 # sum expert shards

    hf = h.reshape(Ntok, d)
    eidf = eid.reshape(Ntok, K)
    gatesf = gates.reshape(Ntok, K)
    y = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec,
                  P("model"), P("model"), P("model")),
        out_specs=tok_spec,
        check_vma=False,
    )(hf, eidf, gatesf, p["w1"], p["w3"], p["w2"])
    y = y.reshape(B, T, d).astype(x.dtype)

    if cfg.n_shared_experts:
        sp = p["shared"]
        g = jnp.einsum("btd,df->btf", h, sp["w1"])
        u = jnp.einsum("btd,df->btf", h, sp["w3"])
        y = y + jnp.einsum("btf,fd->btd", jax.nn.silu(g) * u, sp["w2"])
    return x + y, aux


def _local_moe(p, x, h, eid, gates, cfg: ArchConfig, C: int):
    """No-mesh fallback with identical dispatch semantics (global-flat
    capacity order) — used for correctness tests on one device."""
    B, T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    hf = h.reshape(-1, d)
    flat_e = eid.reshape(-1)
    idx, valid = _dispatch_indices(flat_e, E, C)
    tok = idx // K
    buf = jnp.take(hf, tok, axis=0) * valid[..., None].astype(hf.dtype)
    g1 = jnp.einsum("ecd,edf->ecf", buf, p["w1"])
    u1 = jnp.einsum("ecd,edf->ecf", buf, p["w3"])
    out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g1) * u1, p["w2"])
    gsel = jnp.take(gates.reshape(-1), idx) * valid.astype(gates.dtype)
    y = jnp.zeros((B * T, d), out.dtype).at[tok.reshape(-1)].add(
        (out * gsel[..., None]).reshape(-1, d))
    y = y.reshape(B, T, d).astype(x.dtype)
    if cfg.n_shared_experts:
        sp = p["shared"]
        g = jnp.einsum("btd,df->btf", h, sp["w1"])
        u = jnp.einsum("btd,df->btf", h, sp["w3"])
        y = y + jnp.einsum("btf,fd->btd", jax.nn.silu(g) * u, sp["w2"])
    return x + y
