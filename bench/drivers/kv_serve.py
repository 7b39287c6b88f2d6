"""Driver of the KV-tier cells: sessions on the serving tier, decoded in a
closed loop over engine steps.

The traffic file sets the mix (all sizes in tokens and pages):

* ``sessions``, ``homing`` (``hot_node``: half the sessions have their
  affinity on node 0, the rest cycle over the other nodes; ``even``: the
  sessions cycle over all nodes) and ``context_tokens`` ``[lo, hi]``. Each
  node's contexts are the quantiles ``(j + 0.5) / m`` of a log-uniform law
  on ``[lo, hi]`` for its ``m`` sessions.
* ``plan_seed``: draws the plan's structure, the same for every run: each
  node's order of sizes, the order of arrival and each session's order of
  bursts. So every seed does the same work; the run's seed draws the
  session ids, and with them the KV bytes and queries.
* ``running_per_node`` (null: every session runs) and
  ``running_page_budget``: each node's running set is filled in order of
  arrival with sessions whose pages fit the budget.
* ``burst_tokens`` ``[lo, hi]`` (null: no pauses): a running session
  decodes a burst, then pauses (the user's or a tool's turn) with its KV
  kept. The session of its node that has waited longest and fits the
  budget resumes, and its evicted pages are restored (``serve.resume``).
  Burst sizes are a fixed, evenly spaced multiset that ``plan_seed``
  permutes; at the window's start the running sessions of a node are at
  evenly spaced points of their bursts.
* ``warmup_steps`` (run in set-up) and ``horizon_steps``: the steps planned
  ahead, whose kernel shapes set-up compiles.

One engine step is ``decode(running, 1)`` and then ``attend(running,
layer)`` for every layer; a token counts once the step's attention over all
layers has returned.
"""
from __future__ import annotations

import math
import sys
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench.costs import paged_attention_cost
from bench.harness import Check
from bench.reference import page_matches, session_attention

SPANS = ("serve.resume", "serve.commit", "serve.attend")
# the paged kernel has no name of its own in the trace: it is the Mosaic
# custom call of the program that the eager pallas_call builds
KERNELS = {"paged_attention": r"^%tpu_custom_call"}


# -- the plan: which sessions run in each step (host bookkeeping only) ---------
@dataclass
class Step:
    running: List[int]              # decode order, interleaved over nodes
    resumed: List[int]              # came back from a pause this step
    lengths: Dict[int, int]         # committed tokens after this step


class Planner:
    """Deterministic schedule of running sets, step by step."""

    def __init__(self, sessions: List[Tuple[int, int, int]], page_tokens: int,
                 running_per_node: Optional[int],
                 page_budget: Optional[int],
                 bursts: Optional[Dict[int, List[int]]]):
        self.page_tokens = page_tokens
        self.page_budget = page_budget
        self.bursts = bursts
        self.length = {s: n for s, _node, n in sessions}
        self.node_of = {s: node for s, node, _n in sessions}
        self.nodes = sorted({node for _s, node, _n in sessions})
        self.running: Dict[int, List[int]] = {n: [] for n in self.nodes}
        self.waiting: Dict[int, deque] = {n: deque() for n in self.nodes}
        self.remaining: Dict[int, Optional[int]] = {}
        self._burst_index = {s: 0 for s, _node, _n in sessions}
        for s, node, _n in sessions:          # order of arrival
            room = (running_per_node is None
                    or len(self.running[node]) < running_per_node)
            if room and self._fits(node, s):
                self.running[node].append(s)
            else:
                self.waiting[node].append(s)
        for node in self.nodes:
            r = len(self.running[node])
            for j, s in enumerate(self.running[node]):
                burst = self._next_burst(s)
                # evenly spaced points of the current burst
                self.remaining[s] = (None if burst is None else
                                     max(1, math.ceil(burst * (j + 0.5) / r)))
        self._resumed: List[int] = []

    def pages(self, s: int, extra: int = 0) -> int:
        return -(-(self.length[s] + extra) // self.page_tokens)

    def _fits(self, node: int, s: int) -> bool:
        if self.page_budget is None:
            return True
        used = sum(self.pages(r, 1) for r in self.running[node])
        return used + self.pages(s, 1) <= self.page_budget

    def _next_burst(self, s: int) -> Optional[int]:
        if self.bursts is None:
            return None
        seq = self.bursts[s]
        burst = seq[self._burst_index[s] % len(seq)]
        self._burst_index[s] += 1
        return burst

    def step(self) -> Step:
        """The next step's running set; advances the plan past it."""
        running = [s for group in _interleave(
            [self.running[n] for n in self.nodes]) for s in group]
        resumed, self._resumed = self._resumed, []
        for s in running:
            self.length[s] += 1
        lengths = {s: self.length[s] for s in running}
        for node in self.nodes:
            for s in list(self.running[node]):
                if self.remaining[s] is None:
                    continue
                self.remaining[s] -= 1
                if self.remaining[s] == 0:
                    self._pause(node, s)
        return Step(running, resumed, lengths)

    def _pause(self, node: int, s: int) -> None:
        pos = self.running[node].index(s)
        self.running[node].pop(pos)
        self.waiting[node].append(s)
        # the longest waiter that fits; when none does (the others grew),
        # the paused session takes its next burst at once
        w = next((w for w in self.waiting[node] if self._fits(node, w)), s)
        self.waiting[node].remove(w)
        self.running[node].insert(pos, w)
        self.remaining[w] = self._next_burst(w)
        if w != s:
            self._resumed.append(w)


def _interleave(groups: List[List[int]]) -> List[List[int]]:
    """[[a0, a1], [b0]] -> [[a0, b0], [a1]]: decode alternates over nodes."""
    out = []
    for i in range(max((len(g) for g in groups), default=0)):
        out.append([g[i] for g in groups if i < len(g)])
    return out


# -- set-up ---------------------------------------------------------------------
def kv_dtype(cfg) -> np.dtype:
    """The pages' dtype; ``bfloat16`` is numpy's through ml_dtypes."""
    import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)
    return np.dtype(cfg["dtype"])


def slab_nbytes(cfg) -> int:
    return (cfg["num_layers"] * cfg["page_tokens"] * 2 * cfg["kv_heads"]
            * cfg["head_dim"] * kv_dtype(cfg).itemsize)


def context_quantiles(m: int, lo: int, hi: int) -> List[int]:
    return [int(round(lo * (hi / lo) ** ((j + 0.5) / m))) for j in range(m)]


def draw_sessions(traffic, nodes: int, affinity, rng, plan_rng
                  ) -> List[Tuple[int, int, int]]:
    """(seq_id, home node, context tokens) in order of arrival: ``plan_rng``
    draws the sizes' order and the arrivals, ``rng`` the ids."""
    n = traffic["sessions"]
    if traffic["homing"] == "hot_node":
        homes = [0] * (n // 2) + [1 + i % (nodes - 1) for i in range(n - n // 2)]
    elif traffic["homing"] == "even":
        homes = [i % nodes for i in range(n)]
    else:
        raise ValueError(f"unknown homing {traffic['homing']!r}")
    lo, hi = traffic["context_tokens"]
    contexts = {}
    for node in set(homes):
        sizes = context_quantiles(homes.count(node), lo, hi)
        contexts[node] = [sizes[i] for i in plan_rng.permutation(len(sizes))]
    order = plan_rng.permutation(n)            # order of arrival
    used, out = set(), []
    for i in order:
        node = homes[i]
        while True:
            s = int(rng.integers(0, 2**31 - 1))
            if s not in used and affinity(s) == node:
                break
        used.add(s)
        out.append((s, node, contexts[node].pop()))
    return out


def draw_bursts(traffic, seq_ids: List[int], plan_rng
                ) -> Optional[Dict[int, List[int]]]:
    """Each session's bursts, by its place in the order of arrival."""
    if traffic.get("burst_tokens") is None:
        return None
    lo, hi = traffic["burst_tokens"]
    k = len(seq_ids)
    sizes = np.round(np.linspace(lo, hi, k)).astype(int)
    return {s: [int(sizes[j]) for j in plan_rng.permutation(k)]
            for s in seq_ids}


@dataclass
class State:
    cfg: dict
    cluster: object
    tier: object
    planner: Planner
    plan: List[Step]
    spans: object
    step_index: int = 0
    # committed tokens of each session after the steps run so far
    lengths: Dict[int, int] = field(default_factory=dict)

    def next_step(self) -> Step:
        while self.step_index >= len(self.plan):
            self.plan.append(self.planner.step())
        step = self.plan[self.step_index]
        self.step_index += 1
        return step


def build_tier(cfg):
    from repro.runtime.cluster import Cluster
    from repro.runtime.serving import ServingTier
    slab = slab_nbytes(cfg)
    cluster = Cluster(cfg["nodes"],
                      node_capacity=cfg["host_pool_bytes_per_node"],
                      page_size=slab,
                      replication_factor=cfg["replication_factor"],
                      admission=cfg["admission"])
    tier = ServingTier(cluster, num_layers=cfg["num_layers"],
                       page_tokens=cfg["page_tokens"],
                       kv_heads=cfg["kv_heads"], head_dim=cfg["head_dim"],
                       hbm_pages_per_node=cfg["hbm_pages_per_node"],
                       host_budget_bytes=cfg["host_budget_pages"] * slab,
                       dtype=kv_dtype(cfg),
                       replicate=cfg["replicate"])
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
        tier.admit({s: ctx for s, _node, ctx in sessions})
        placed = [(s, tier.sessions[s].node, ctx) for s, _n, ctx in sessions]
        moved = [s for (s, a, _), (_, b, _) in zip(sessions, placed) if a != b]
        if moved:
            print(f"[kv] admission diverted {len(moved)} sessions from their "
                  f"affinity node", file=sys.stderr, flush=True)
        planner = Planner(placed, cfg["page_tokens"],
                          traffic.get("running_per_node"),
                          traffic.get("running_page_budget"), bursts)
        state = State(cfg, cluster, tier, planner, [], spans,
                      lengths={s: ctx for s, _node, ctx in placed})
        horizon = traffic["warmup_steps"] + traffic["horizon_steps"]
        while len(state.plan) < horizon:
            state.plan.append(planner.step())
        check_plan_fits(state)
        warm_kernel_shapes(state)
        for _ in range(traffic["warmup_steps"]):
            run_step(state, state.next_step())
        print(f"[kv] {len(sessions)} sessions, "
              f"{sum(tier._pages_for(c) for _s, _n, c in placed)} pages "
              f"admitted; {len(state.plan)} steps planned", file=sys.stderr,
              flush=True)
        return state
    except BaseException:
        tier.close()
        cluster.shutdown()
        raise


def node_batches(state: State, step: Step) -> Dict[int, List[int]]:
    out: Dict[int, List[int]] = {}
    for s in step.running:
        out.setdefault(state.planner.node_of[s], []).append(s)
    return out


def step_shapes(state: State, step: Step):
    """(node, batch, max_pages) of each kernel call of the step's attention."""
    pt = state.cfg["page_tokens"]
    for node, seqs in node_batches(state, step).items():
        yield node, len(seqs), max(-(-step.lengths[s] // pt) for s in seqs)


def check_plan_fits(state: State) -> None:
    for k, step in enumerate(state.plan):
        for node, seqs in node_batches(state, step).items():
            pages = sum(-(-step.lengths[s] // state.cfg["page_tokens"])
                        for s in seqs)
            if pages > state.cfg["hbm_pages_per_node"]:
                raise ValueError(f"planned step {k} runs {pages} pages on "
                                 f"node {node}, more than its HBM pool")


def warm_kernel_shapes(state: State) -> None:
    """Compile the paged kernel for every (device, batch, max_pages) that the
    planned steps use, through the same call that ``attend`` makes."""
    import jax
    from repro.kernels.paged_attention.ops import paged_attention
    cfg, tier = state.cfg, state.tier
    shapes = {}                     # (device, batch, max_pages) -> a node
    for step in state.plan:
        for node, b, mp in step_shapes(state, step):
            shapes[(str(tier._shards[node].cache.device), b, mp)] = node
    for (_dev, b, mp), node in sorted(shapes.items()):
        cache = tier._shards[node].cache
        q = np.zeros((b, cfg["kv_heads"], cfg["head_dim"]), kv_dtype(cfg))
        out = paged_attention(
            jax.device_put(q, cache.device), cache.kv[0],
            jax.device_put(np.zeros((b, mp), np.int32), cache.device),
            jax.device_put(np.ones(b, np.int32), cache.device), impl="kernel")
        out.block_until_ready()
    print(f"[kv] warmed {len(shapes)} kernel shapes", file=sys.stderr,
          flush=True)


# -- the window ------------------------------------------------------------------
@dataclass
class Window:
    t0: float = 0.0
    step_end: List[float] = field(default_factory=list)
    steps: List[Step] = field(default_factory=list)
    outputs: List[list] = field(default_factory=list)   # per step, per layer
    flops: float = 0.0
    bytes: float = 0.0
    fetches: int = 0
    attempted: int = 0
    failed: int = 0

    @property
    def tokens(self) -> int:
        return sum(len(s.running) for s in self.steps)

    @property
    def elapsed_s(self) -> float:
        return self.step_end[-1] - self.t0 if self.step_end else 0.0

    def gaps_s(self) -> List[float]:
        """Gaps between consecutive tokens of a session, both in the window
        and in one burst (a pause for a tool call is not a gap)."""
        out, last = [], {}
        for k, step in enumerate(self.steps):
            resumed = set(step.resumed)
            for s in step.running:
                if s in last and last[s] == k - 1 and s not in resumed:
                    out.append(self.step_end[k] - self.step_end[k - 1])
                last[s] = k
        return out


def fetches(state: State) -> int:
    return sum(sh.cache.stats["fetches"] for sh in state.tier._shards.values())


def run_step(state: State, step: Step):
    tier, spans = state.tier, state.spans
    if step.resumed:
        with spans.span("serve.resume"):
            for s in step.resumed:
                tier.block_table(s)
    with spans.span("serve.commit"):
        tier.decode(step.running, 1)
    outs = []
    for layer in range(state.cfg["num_layers"]):
        with spans.span("serve.attend"):
            outs.append(tier.attend(step.running, layer))
    state.lengths.update(step.lengths)
    return outs


def window(state: State, seconds: float) -> Window:
    rec = Window()
    cfg = state.cfg
    f0 = fetches(state)
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
    steps_ms = np.diff([rec.t0] + rec.step_end) * 1e3
    print(f"[kv] window: {len(rec.steps)} steps, {rec.tokens} tokens, "
          f"{rec.fetches} restores, {sum(len(s.resumed) for s in rec.steps)} "
          f"resumes; step ms {np.round(steps_ms).astype(int).tolist()}",
          file=sys.stderr, flush=True)
    itemsize = kv_dtype(cfg).itemsize
    for step in rec.steps:
        for node, seqs in node_batches(state, step).items():
            f, b = paged_attention_cost(
                [step.lengths[s] for s in seqs], cfg["kv_heads"],
                cfg["kv_heads"], cfg["head_dim"], itemsize)
            rec.flops += f * cfg["num_layers"]
            rec.bytes += b * cfg["num_layers"]
    return rec


# -- the check -------------------------------------------------------------------
def check(state: State, rec: Window) -> List[Check]:
    """Compare what the window produced with the plain reference:

    * ``length_mismatch``: sessions whose committed length is not the one
      the steps decoded;
    * ``attn_gap``: the widest gap between any attention output of the
      window (every step, layer and session) and the float64 reference;
    * ``page_mismatch``: pages, read back through the tier from wherever
      they live (HBM, host, remote node), that differ from the reference or
      are missing;
    * ``replica_mismatch``: the same for each page's copy on the session's
      replica node.
    """
    cfg, tier = state.cfg, state.tier
    pt = cfg["page_tokens"]
    expected = state.lengths
    lengths = {s: sess.length for s, sess in tier.sessions.items()}
    length_bad = sum(lengths.get(s) != n for s, n in expected.items())

    # every output of every layer, against the reference at the length that
    # step committed; outputs of one (session, length) share one reference
    want: Dict[Tuple[int, int], np.ndarray] = {}
    missing = n_out = 0
    gap = abs_sum = signed_sum = 0.0
    for step, outs in zip(rec.steps, rec.outputs):
        for out in outs:
            for s in step.running:
                if s not in out:
                    missing += 1
                    continue
                key = (s, step.lengths[s])
                if key not in want:
                    want[key] = session_attention(
                        s, key[1], cfg["kv_heads"], cfg["kv_heads"],
                        cfg["head_dim"], kv_dtype(cfg))
                d = np.asarray(out[s], np.float64) - want[key]
                gap = max(gap, float(np.abs(d).max()))
                abs_sum += float(np.abs(d).sum())
                signed_sum += float(d.sum())
                n_out += d.size
    if missing:
        gap = math.inf
    print(f"[kv] attention: widest gap {gap!r}, mean |gap| "
          f"{abs_sum / max(n_out, 1)!r}, mean gap {signed_sum / max(n_out, 1)!r} "
          f"over {n_out} elements", file=sys.stderr, flush=True)

    levels = {"hbm": 0, "host": 0, "remote": 0}
    page_bad = replica_bad = 0
    for s, n in expected.items():
        sess = tier.sessions[s]
        shard = tier._shards[sess.node]
        store = shard.store
        for pid in shard.cache._seqs[s].page_ids:
            if shard.cache._pages[pid].offset is not None:
                levels["hbm"] += 1
            elif pid in store._remote:
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
                slab = np.frombuffer(raw, kv_dtype(cfg)).reshape(
                    tier.slab_shape)
                replica_bad += not page_matches(slab, s, k, n, pt)
    print(f"[kv] checked {len(want)} (session, length) outputs over "
          f"{cfg['num_layers']} layers; pages read back by level {levels}",
          file=sys.stderr, flush=True)
    return [Check("length_mismatch", length_bad, 0),
            Check("page_mismatch", page_bad, 0),
            Check("replica_mismatch", replica_bad, 0),
            Check("attn_gap", gap, cfg["check_limits"]["attn_gap"])]


def close(state: State) -> None:
    state.tier.close()
    state.cluster.shutdown()
