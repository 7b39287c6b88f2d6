"""Driver of the latent (MLA) KV-tier cells: sessions on the serving tier with
a latent page layout, decoded in a closed loop over engine steps.

The traffic file takes the keys of ``kv_serve`` (sessions, homing, context
quantiles, ``plan_seed``, running sets, bursts, warm-up and horizon), and so
does the plan: this driver reuses its planner, session draws, step and gap
accounting. What differs is the page layout: the configuration's
``latent_dim``, ``value_dim``, ``q_heads`` and ``softmax_scale`` make the
tier's pages ``[L, page, latent_dim]`` and its attention the paged latent
kernel (``ops.paged_latent_attention``), whose output is ``[q_heads,
value_dim]`` per session. The set-up warms that kernel's shapes, the window
counts the latent kernel's operations and bytes (``bench/costs_mla.py``) and
the tier's attention calls and tokens, and the check compares with
``bench/reference_mla.py``.
"""
from __future__ import annotations

import math
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

from bench.costs_mla import latent_attention_cost
from bench.drivers.kv_serve import (SPANS, Planner, State, Window,  # noqa: F401
                                    check_plan_fits, draw_bursts,
                                    draw_sessions, fetches, kv_dtype,
                                    node_batches, run_step, step_shapes)
from bench.harness import Check
from bench.reference_mla import (attention_f64, page_matches, query_np,
                                 rel_gap, session_latent_np,
                                 steps_attention_jnp)

# the latent kernel is the only Mosaic custom call such a cell runs: the op
# ``%tpu_custom_call`` of the program ``jit_paged_latent_attention_kernel``
KERNELS = {"mla_attention": r"^%tpu_custom_call"}
# reference outputs computed on the device per call: steps of one session
# and layer at a time
REF_BLOCK = 8
# outputs of the window also compared with the float64 reference
F64_SAMPLE = 3


def slab_nbytes(cfg) -> int:
    return (cfg["num_layers"] * cfg["page_tokens"] * cfg["latent_dim"]
            * kv_dtype(cfg).itemsize)


def build_tier(cfg):
    from repro.runtime.cluster import Cluster
    from repro.runtime.serving import LatentLayout, ServingTier
    if cfg.get("layout") != "latent":
        raise ValueError(f"kv_serve_mla serves the latent layout, not "
                         f"{cfg.get('layout')!r}")
    slab = slab_nbytes(cfg)
    layout = LatentLayout(latent_dim=cfg["latent_dim"],
                          value_dim=cfg["value_dim"], q_heads=cfg["q_heads"],
                          scale=cfg["softmax_scale"])
    cluster = Cluster(cfg["nodes"],
                      node_capacity=cfg["host_pool_bytes_per_node"],
                      page_size=slab,
                      replication_factor=cfg["replication_factor"],
                      admission=cfg["admission"])
    try:
        tier = ServingTier(cluster, num_layers=cfg["num_layers"],
                           page_tokens=cfg["page_tokens"], layout=layout,
                           hbm_pages_per_node=cfg["hbm_pages_per_node"],
                           host_budget_bytes=cfg["host_budget_pages"] * slab,
                           dtype=kv_dtype(cfg), replicate=cfg["replicate"])
    except BaseException:
        cluster.shutdown()
        raise
    return cluster, tier


def setup(cell, seed: int, spans) -> State:
    cfg, traffic = cell.config, cell.traffic
    rng = np.random.default_rng(seed)
    cluster, tier = build_tier(cfg)
    try:
        plan_rng = np.random.default_rng(traffic["plan_seed"])
        sessions = draw_sessions(traffic, cfg["nodes"], tier._affinity, rng,
                                 plan_rng)
        bursts = draw_bursts(traffic, [s for s, _n, _c in sessions],
                             plan_rng)
        t0 = time.perf_counter()
        tier.admit({s: ctx for s, _node, ctx in sessions})
        placed = [(s, tier.sessions[s].node, ctx) for s, _n, ctx in sessions]
        planner = Planner(placed, cfg["page_tokens"],
                          traffic.get("running_per_node"),
                          traffic.get("running_page_budget"), bursts)
        state = State(cfg, cluster, tier, planner, [], spans,
                      lengths={s: ctx for s, _node, ctx in placed})
        horizon = traffic["warmup_steps"] + traffic["horizon_steps"]
        while len(state.plan) < horizon:
            state.plan.append(planner.step())
        check_plan_fits(state)
        print(f"[mla] {len(sessions)} sessions, "
              f"{sum(tier._pages_for(c) for _s, _n, c in placed)} pages "
              f"admitted in {time.perf_counter() - t0:.1f} s; "
              f"{len(state.plan)} steps planned", file=sys.stderr, flush=True)
        warm_kernel_shapes(state)
        for _ in range(traffic["warmup_steps"]):
            run_step(state, state.next_step())
        return state
    except BaseException:
        tier.close()
        cluster.shutdown()
        raise


def warm_kernel_shapes(state: State) -> None:
    """Compile the latent kernel for every (device, batch, max_pages) that
    the planned steps use, through the same call that ``attend`` makes."""
    import jax
    from repro.kernels.paged_attention.ops import paged_latent_attention
    cfg, tier = state.cfg, state.tier
    shapes = {}                     # (device, batch, max_pages) -> a node
    for step in state.plan:
        for node, b, mp in step_shapes(state, step):
            shapes[(str(tier._shards[node].cache.device), b, mp)] = node
    for (_dev, b, mp), node in sorted(shapes.items()):
        cache = tier._shards[node].cache
        q = np.zeros((b, cfg["q_heads"], cfg["latent_dim"]), kv_dtype(cfg))
        out = paged_latent_attention(
            jax.device_put(q, cache.device), cache.kv[0],
            jax.device_put(np.zeros((b, mp), np.int32), cache.device),
            jax.device_put(np.ones(b, np.int32), cache.device),
            value_dim=cfg["value_dim"], scale=cfg["softmax_scale"],
            impl="kernel")
        out.block_until_ready()
    print(f"[mla] warmed {len(shapes)} kernel shapes", file=sys.stderr,
          flush=True)


# -- the window ------------------------------------------------------------------
@dataclass
class LatentWindow(Window):
    attention_calls: int = 0        # the tier's kernel calls in the window
    attention_tokens: int = 0       # and the latent tokens they attended


def window(state: State, seconds: float) -> LatentWindow:
    rec = LatentWindow()
    cfg, stats = state.cfg, state.tier.stats
    f0 = fetches(state)
    calls0, tokens0 = stats["attention_calls"], stats["attention_tokens"]
    rec.t0 = time.perf_counter()
    deadline = rec.t0 + seconds
    while True:
        step = state.next_step()
        rec.attempted += len(step.running)
        outs = run_step(state, step)
        rec.step_end.append(time.perf_counter())
        rec.steps.append(step)
        rec.outputs.append(outs)
        if rec.step_end[-1] >= deadline:
            break
    rec.fetches = fetches(state) - f0
    rec.attention_calls = stats["attention_calls"] - calls0
    rec.attention_tokens = stats["attention_tokens"] - tokens0
    steps_ms = np.diff([rec.t0] + rec.step_end) * 1e3
    print(f"[mla] window: {len(rec.steps)} steps, {rec.tokens} tokens, "
          f"{rec.attention_calls} kernel calls over {rec.attention_tokens} "
          f"tokens, {rec.fetches} restores; step ms "
          f"{np.round(steps_ms).astype(int).tolist()}", file=sys.stderr,
          flush=True)
    itemsize = kv_dtype(cfg).itemsize
    for step in rec.steps:
        for _node, seqs in node_batches(state, step).items():
            f, b = latent_attention_cost(
                [step.lengths[s] for s in seqs], cfg["q_heads"],
                cfg["latent_dim"], cfg["value_dim"], itemsize)
            rec.flops += f * cfg["num_layers"]
            rec.bytes += b * cfg["num_layers"]
    return rec


# -- the check -------------------------------------------------------------------
def _reference_fn(cfg):
    """The jitted float32 reference of one session and layer over a block
    of steps (``steps_attention_jnp``), with the gap of each output."""
    import functools
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("tokens",))
    def ref(seq_id, layer, lengths, outs, tokens):
        want = steps_attention_jnp(
            seq_id, layer, lengths, tokens, cfg["q_heads"], cfg["latent_dim"],
            cfg["value_dim"], cfg["softmax_scale"], jnp.dtype(cfg["dtype"]))
        rms = jnp.sqrt(jnp.mean(want ** 2, axis=(1, 2)))
        gap = jnp.abs(outs.astype(jnp.float32) - want).max(axis=(1, 2))
        return gap / rms, want

    return ref


def output_gaps(state: State, rec: Window):
    """Every output of the window against the float32 reference: (widest
    relative gap, outputs compared, missing outputs, {(session, step,
    layer): (output, float32 reference)} of a seeded sample)."""
    import jax
    cfg = state.cfg
    dtype = kv_dtype(cfg)
    ref = _reference_fn(cfg)
    # (session, layer) -> [(step index, committed length)]
    runs: Dict[Tuple[int, int], List[Tuple[int, int]]] = {}
    missing = 0
    for k, (step, outs) in enumerate(zip(rec.steps, rec.outputs)):
        for layer, out in enumerate(outs):
            for s in step.running:
                if s not in out:
                    missing += 1
                    continue
                runs.setdefault((s, layer), []).append((k, step.lengths[s]))
    keys = sorted((s, layer, k) for (s, layer), v in runs.items()
                  for k, _n in v)
    rng = np.random.default_rng(len(keys))
    pick = {keys[i] for i in rng.choice(len(keys), min(F64_SAMPLE, len(keys)),
                                        replace=False)} if keys else set()
    widest, n_out, sample = 0.0, 0, {}
    for (s, layer), items in sorted(runs.items()):
        # the reference's static length, rounded up so that sessions of
        # one size class share a compiled program
        tokens = -(-max(n for _k, n in items) // 1024) * 1024
        for i in range(0, len(items), REF_BLOCK):
            block = items[i:i + REF_BLOCK]
            pad = block + [block[-1]] * (REF_BLOCK - len(block))
            outs = np.stack([np.asarray(rec.outputs[k][layer][s], dtype)
                             for k, _n in pad])
            gaps, want = ref(np.uint32(s), np.uint32(layer),
                             np.array([n for _k, n in pad], np.uint32),
                             outs, tokens=tokens)
            gaps = np.asarray(jax.device_get(gaps))[:len(block)]
            widest = max(widest, float(gaps.max()))
            n_out += len(block)
            for j, (k, _n) in enumerate(block):
                if (s, layer, k) in pick:
                    sample[(s, layer, k)] = (outs[j], np.asarray(want[j]))
    return widest, n_out, missing, sample


def check(state: State, rec: Window) -> List[Check]:
    """Compare what the window produced with the plain reference:

    * ``length_mismatch``: sessions whose committed length is not the one
      the steps decoded;
    * ``attn_rel_gap``: the widest gap between any latent attention output
      of the window (every step, layer and session) and the float32
      reference over the same inputs, over the root mean square of that
      reference output (``reference_mla.rel_gap``);
    * ``ref_rel_gap``: the float32 reference against the float64 one, in
      the same measure, on a seeded sample of outputs;
    * ``page_mismatch``: pages, read back through the tier from wherever
      they live (HBM, host, remote node), that differ from the reference or
      are missing;
    * ``replica_mismatch``: the same for each page's copy on the session's
      replica node.
    """
    cfg, tier = state.cfg, state.tier
    pt = cfg["page_tokens"]
    dtype = kv_dtype(cfg)
    expected = state.lengths
    lengths = {s: sess.length for s, sess in tier.sessions.items()}
    length_bad = sum(lengths.get(s) != n for s, n in expected.items())

    t0 = time.perf_counter()
    gap, n_out, missing, sample = output_gaps(state, rec)
    if missing:
        gap = math.inf
    ref_gap = 0.0
    for (s, layer, k), (out, want) in sorted(sample.items()):
        n = rec.steps[k].lengths[s]
        f64 = attention_f64(
            query_np(s, n, layer, cfg["q_heads"], cfg["latent_dim"], dtype),
            session_latent_np(s, layer, n, cfg["latent_dim"], dtype),
            cfg["value_dim"], cfg["softmax_scale"])
        ref_gap = max(ref_gap, rel_gap(want, f64))
        print(f"[mla] sample session {s} layer {layer} step {k} ({n} "
              f"tokens): float32 reference {rel_gap(want, f64)!r}, output "
              f"{rel_gap(out, f64)!r} against float64", file=sys.stderr,
              flush=True)
    print(f"[mla] attention: widest relative gap {gap!r} over {n_out} "
          f"outputs ({missing} missing) in {time.perf_counter() - t0:.1f} s",
          file=sys.stderr, flush=True)

    levels = {"hbm": 0, "host": 0, "remote": 0}
    page_bad = replica_bad = 0
    for s, n in expected.items():
        sess = tier.sessions[s]
        shard = tier._shards[sess.node]
        for pid in shard.cache._seqs[s].page_ids:
            if shard.cache._pages[pid].offset is not None:
                levels["hbm"] += 1
            elif pid in shard.store._remote:
                levels["remote"] += 1
            else:
                levels["host"] += 1
        slabs = tier.sequence_slabs(s)
        want_pages = -(-n // pt)
        page_bad += abs(len(slabs) - want_pages)
        page_bad += sum(not page_matches(slab, s, k, n, pt)
                        for k, slab in enumerate(slabs[:want_pages]))
        if cfg["replicate"]:
            for k in range(want_pages):
                try:
                    raw = tier.cluster.load_bytes(sess.replica,
                                                  tier._rep_name(s, k))
                except (KeyError, TypeError):
                    replica_bad += 1
                    continue
                slab = np.frombuffer(raw, dtype).reshape(tier.slab_shape)
                replica_bad += not page_matches(slab, s, k, n, pt)
    print(f"[mla] pages read back by level {levels}", file=sys.stderr,
          flush=True)
    limits = cfg["check_limits"]
    return [Check("length_mismatch", length_bad, 0),
            Check("page_mismatch", page_bad, 0),
            Check("replica_mismatch", replica_bad, 0),
            Check("attn_rel_gap", gap, limits["attn_rel_gap"]),
            Check("ref_rel_gap", ref_gap, limits["ref_rel_gap"])]


def close(state: State) -> None:
    state.tier.close()
    state.cluster.shutdown()
