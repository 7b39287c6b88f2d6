"""Production mesh + per-arch sharding rules.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). Single pod: (16, 16) over ("data", "model") = 256 chips.
Multi-pod: (2, 16, 16) over ("pod", "data", "model") = 512 chips; the "pod"
axis extends data parallelism across the DCN.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig
from ..sharding import DEFAULT_RULES, spec_for


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh (tests use small ones, elastic remesh uses survivors).

    Axes are Auto: the model code places tensors with
    ``with_sharding_constraint``, which refuses Explicit axes (the default
    of ``jax.make_mesh``)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def sharding_rules(cfg: ArchConfig, mesh: Mesh,
                   parallelism: Optional[str] = None) -> Dict:
    """Per-arch logical->mesh rules; divisibility-aware (DESIGN.md §5).

    ``parallelism`` overrides ``cfg.parallelism`` (used by the §Perf
    hillclimb to compare presets on the same arch)."""
    preset = parallelism or cfg.parallelism
    model_sz = axis_size(mesh, "model")
    rules = dict(DEFAULT_RULES)
    rules["batch"] = ("pod", "data")
    rules["ffn_batch"] = ("pod", "data")          # FFN/MoE-block batch axis
    rules["embed"] = "data"                       # FSDP/ZeRO
    rules["mlp"] = "model"
    rules["mlp_out"] = "model"                    # rg-lru gate outputs
    rules["heads"] = "model" if (cfg.n_heads and
                                 cfg.n_heads % model_sz == 0) else None
    kv_ok = cfg.kv_heads and cfg.kv_heads % model_sz == 0
    rules["kv"] = "model" if kv_ok else None
    # decode caches: if kv heads can't shard, shard the cache's seq dim
    rules["kv_seq"] = None if kv_ok else "model"
    rules["vocab"] = "model" if cfg.vocab % model_sz == 0 else None
    if cfg.n_experts and cfg.moe_strategy in ("expert_parallel",
                                              "expert_parallel_shardmap"):
        rules["experts"] = ("model" if cfg.n_experts % model_sz == 0 else None)
    else:
        rules["experts"] = None
    rules["heads_embed"] = "model"                # rwkv channel projections
    rules["embed_vec"] = None
    rules["embed_out"] = None

    if preset == "fsdp_tp_sp":
        # sequence parallelism: the residual stream stays sequence-sharded
        # over "model" between TP regions — GSPMD turns each TP all-reduce
        # into a reduce-scatter + all-gather pair (half the ring bytes, and
        # norms/elementwise work become sharded too)
        rules["seq"] = "model"
    elif preset == "dp":
        # pure data parallelism: no tensor sharding; batch over every axis
        rules["batch"] = ("pod", "data", "model")
        rules["ffn_batch"] = ("pod", "data", "model")
        for ax in ("mlp", "mlp_out", "heads", "kv", "vocab", "experts",
                   "heads_embed"):
            rules[ax] = None
        rules["kv_seq"] = None
    elif preset == "serve_2d":
        # weight-stationary decode: no FSDP dim (weights never gathered);
        # FFN width sharded over BOTH axes when divisible (314B fits at
        # ~2.5GB/chip), activations gathered over "data" around FFN/MoE
        # blocks and partial-summed back — token bytes ≪ weight bytes.
        rules["ffn_batch"] = None
        rules["embed"] = None
        total = axis_size(mesh, "data") * model_sz
        wide = ("data", "model")
        rules["mlp"] = wide if cfg.d_ff % total == 0 else rules["mlp"]
        if cfg.n_experts and cfg.moe_strategy == "expert_tp":
            rules["mlp"] = wide if cfg.d_expert % total == 0 else rules["mlp"]
        rules["mlp_out"] = wide if cfg.d_model % total == 0 else rules["mlp_out"]
    return rules


def param_shardings(model, cfg: ArchConfig, mesh: Mesh,
                    rules: Optional[Dict] = None):
    """PartitionSpec tree for the model params (from logical axes)."""
    rules = rules or sharding_rules(cfg, mesh)
    axes = model.param_axes()
    return jax.tree.map(
        lambda a: NamedSharding(mesh, spec_for(a, rules, mesh)),
        axes, is_leaf=lambda x: isinstance(x, tuple))


def dp_axes_for(mesh: Mesh, batch: int):
    """Largest ("pod","data") prefix that divides the batch dim."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    cands = [a for a in ("pod", "data") if a in sizes]
    # try full product, then data only, then nothing
    options = [tuple(cands)] + ([("data",)] if "data" in sizes else []) + [()]
    for opt in options:
        prod = int(np.prod([sizes[a] for a in opt])) if opt else 1
        if prod and batch % prod == 0:
            return opt if len(opt) > 1 else (opt[0] if opt else None)
    return None


def batch_shardings(batch_specs, mesh: Mesh) -> Dict:
    """Shard every batch leaf on its leading (batch) dim (when divisible)."""

    def shard(leaf):
        dp = dp_axes_for(mesh, leaf.shape[0])
        spec = [dp] + [None] * (len(leaf.shape) - 1)
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(shard, batch_specs)


def cache_shardings(cache_specs, cfg: ArchConfig, mesh: Mesh,
                    rules: Optional[Dict] = None):
    """Decode-cache shardings: batch on data(+pod); kv heads on model when
    divisible, else the cache sequence dim on model (DESIGN.md §5)."""
    rules = rules or sharding_rules(cfg, mesh)
    model_sz = axis_size(mesh, "model")

    def key_of(path) -> str:
        names = [getattr(k, "key", getattr(k, "idx", "")) for k in path]
        return str(names[-1]) if names else ""

    def spec_of(path, leaf):
        shp = leaf.shape
        nd = len(shp)
        name = key_of(path)
        # caches may carry a leading stacked-layer dim (scan) or not (rem)
        if name in ("k", "v", "cross_k", "cross_v"):   # [(L,)B,KH,T,hd]
            dp = dp_axes_for(mesh, shp[nd - 4])
            kv_ax = rules.get("kv")
            seq_ax = (rules.get("kv_seq")
                      if shp[-2] % model_sz == 0 else None)
            lead = [None] * (nd - 4)
            return NamedSharding(mesh, P(*lead, dp, kv_ax, seq_ax, None))
        if name in ("c_kv", "k_rope"):                 # [(L,)B,T,lora/rope]
            dp = dp_axes_for(mesh, shp[nd - 3])
            seq_ax = "model" if shp[-2] % model_sz == 0 else None
            lead = [None] * (nd - 3)
            return NamedSharding(mesh, P(*lead, dp, seq_ax, None))
        if name == "S":                                # [(L,)B,H,dk,dv]
            dp = dp_axes_for(mesh, shp[nd - 4])
            h_ax = "model" if shp[-3] % model_sz == 0 else None
            lead = [None] * (nd - 4)
            return NamedSharding(mesh, P(*lead, dp, h_ax, None, None))
        if name == "conv":                             # [(L,)B,CONV_W-1,w]
            dp = dp_axes_for(mesh, shp[nd - 3])
            w_ax = "model" if shp[-1] % model_sz == 0 else None
            lead = [None] * (nd - 3)
            return NamedSharding(mesh, P(*lead, dp, None, w_ax))
        # [(L,)B,d] token-shift / h states
        dp = dp_axes_for(mesh, shp[nd - 2])
        d_ax = "model" if shp[-1] % model_sz == 0 else None
        lead = [None] * (nd - 2)
        return NamedSharding(mesh, P(*lead, dp, d_ax))

    return jax.tree_util.tree_map_with_path(spec_of, cache_specs)
