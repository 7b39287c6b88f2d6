"""Drive Pangea's main path once on a TPU and check every result.

Phases, in one process, each under its own time limit:

1. serving   -- a 4-node in-process ``Cluster`` and its ``ServingTier``. KV
   pools have the minitron-8b geometry (32 layers, 8 KV heads, head_dim
   128) with 16-token f32 pages, 4 MiB a page, and 128 pages a node:
   2 GiB of page pool on the chip. 16 sequences of 512-token prompts are
   skewed onto one node, pages spill HBM -> host -> remote node, decode runs
   32 steps, and the primary of one session is killed mid-decode. Every
   sequence must be byte-identical to the KV oracle, and the compiled
   paged-attention kernel over the tier's pages must match the gather
   reference and a float64 numpy reference.
2. kernel    -- the paged-attention kernel, compiled for the chip, over a
   pool of the same geometry filled from ``--seed``: random q (32 query
   heads), ragged lengths, partial last pages, scattered block tables.
3. analytics -- 6,000,000 skewed (key, val) records (the row count of TPC-H
   SF1 lineitem) on the in-process cluster: hash aggregate, co-partitioned
   join (no byte crosses a node), both-shuffled join, node kill + recovery.
4. proc      -- the process backend (one OS process per node), forked after
   this process holds the chip: 1,000,000 records shuffled and aggregated
   with a node SIGKILLed between map and reduce, then revived.

Every result is compared with a plain numpy reference. ``--chips 4`` runs
only the four-chip path: the serving phase with node i's pool on chip i, a
failover to a replica on another chip, and kernel attention on each chip.

Run: python chip_smoke.py [--seed N] [--chips 4]

The last line of stdout, printed only when every phase passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
With no TPU the script exits non-zero before running any phase.
"""
from __future__ import annotations

import argparse
import contextlib
import faulthandler
import functools
import json
import os
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.compile_cache import configure_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.columnar import segment_sum  # noqa: E402
from repro.data.pipeline import cluster_aggregate, cluster_join  # noqa: E402
from repro.runtime.cluster import Cluster  # noqa: E402
from repro.runtime.serving import ServingTier, token_value  # noqa: E402

REC = np.dtype([("key", np.int64), ("val", np.float64)])
JOINED = np.dtype([("key", np.int64), ("b_val", np.float64),
                   ("p_val", np.float64)])
NUM_NODES = 4
KV_CONFIG = get_config("minitron-8b")

# Phase time limits (seconds): a hang fails the run instead of stalling it.
LIMITS = {"serving": 360, "kernel": 120, "analytics": 360, "proc": 240}

# f32 attention against float64: inputs are O(1), each output is a convex
# combination of O(1) values, and every f32 rounding contributes ~6e-8
# relative error. Summing a 128-term dot and up to 34 pages of 16 tokens
# stays near 1e-6; 2e-5 leaves an order of magnitude of headroom, and a
# single bf16 matmul pass (~4e-3 relative) misses it by two orders.
ATTN_ATOL = 2e-5


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


@contextlib.contextmanager
def phase(name: str):
    """Run one phase under its time limit. On a hang, faulthandler dumps
    every thread's stack and exits the process with status 1."""
    limit = LIMITS[name]
    print(f"[{name}] start (limit {limit} s)", flush=True)
    t0 = time.perf_counter()
    faulthandler.dump_traceback_later(limit, exit=True)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
    print(f"[{name}] passed in {time.perf_counter() - t0:.3f} s", flush=True)


class CompileCounter:
    """Counts XLA backend compilations (persistent-cache hits included)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1

    def __enter__(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


def require_tpu(chips: int):
    """The devices to run on; exits non-zero when they are not TPUs."""
    import jax
    import jaxlib
    from importlib import metadata
    devices = jax.devices()
    dev = devices[0]
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu} "
          f"runtime={dev.client.platform_version!r}", flush=True)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX platform {dev.platform!r}); "
                 f"nothing was run")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX sees {len(devices)}")
    return devices


# -- references ---------------------------------------------------------------
def attention_f64(q: np.ndarray, k: np.ndarray, v: np.ndarray) -> np.ndarray:
    """Plain float64 decode attention for one sequence: q [H, D],
    k/v [T, KH, D] (T = its valid tokens) -> [H, D]."""
    q = np.asarray(q, np.float64)
    heads, dim = q.shape
    group = heads // k.shape[1]
    kk = np.repeat(np.asarray(k, np.float64), group, axis=1)   # [T, H, D]
    vv = np.repeat(np.asarray(v, np.float64), group, axis=1)
    s = np.einsum("hd,thd->ht", q, kk) / np.sqrt(dim)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("ht,thd->hd", p / p.sum(-1, keepdims=True), vv)


def group_sum(keys: np.ndarray, vals: np.ndarray):
    uk, inv = np.unique(keys, return_inverse=True)
    return uk, np.bincount(inv, weights=vals, minlength=len(uk))


def join_reference(build: np.ndarray, probe: np.ndarray) -> np.ndarray:
    """Equi-join on ``key`` with unique build keys, in canonical order."""
    order = np.argsort(build["key"], kind="stable")
    bkeys = build["key"][order]
    pos = np.clip(np.searchsorted(bkeys, probe["key"]), 0, len(bkeys) - 1)
    hit = bkeys[pos] == probe["key"]
    out = np.empty(int(hit.sum()), JOINED)
    out["key"] = probe["key"][hit]
    out["b_val"] = build["val"][order][pos[hit]]
    out["p_val"] = probe["val"][hit]
    return out[np.lexsort((out["p_val"], out["b_val"], out["key"]))]


def skewed_records(rng: np.random.Generator, n: int) -> np.ndarray:
    """(key, val) records with Zipf-skewed keys over n // 4 distinct values
    and integer-valued vals, so that every sum is exact in float64."""
    recs = np.empty(n, REC)
    recs["key"] = (rng.zipf(1.2, n) - 1) % max(1, n // 4)
    recs["val"] = rng.integers(0, 100, n).astype(np.float64)
    return recs


def sorted_records(recs: np.ndarray) -> np.ndarray:
    return recs[np.lexsort((recs["val"], recs["key"]))]


# -- phase 1: the serving tier ------------------------------------------------
@dataclass(frozen=True)
class ServingSize:
    num_layers: int = KV_CONFIG.n_layers
    kv_heads: int = KV_CONFIG.kv_heads
    head_dim: int = KV_CONFIG.head_dim
    page_tokens: int = 16
    hbm_pages: int = 128
    num_seqs: int = 16
    prompt_tokens: int = 512
    decode_steps: int = 32
    warmup_steps: int = 4
    host_budget_pages: int = 16


def skewed_sequences(tier: ServingTier, n: int) -> List[int]:
    """Half the sequences have their session affinity on node 0; the rest
    cycle over nodes 1, 2, 3, so node 0 is over capacity."""
    want = [0] * (n // 2) + [1 + i % 3 for i in range(n - n // 2)]
    seqs: List[int] = []
    candidate = 0
    for node in want:
        while tier._affinity(candidate) != node:
            candidate += 1
        seqs.append(candidate)
        candidate += 1
    return seqs


def check_attention(tier: ServingTier, size: ServingSize) -> Dict:
    """Kernel vs gather reference vs float64 over the tier's own pages, on
    every shard's device. Each shard attends over as many sessions as its
    pool holds at once."""
    per_node: Dict[int, List[int]] = {}
    for s, sess in sorted(tier.sessions.items()):
        per_node.setdefault(sess.node, []).append(s)
    batch: List[int] = []
    for node, seqs in sorted(per_node.items()):
        room = size.hbm_pages
        for s in seqs:
            pages = -(-tier.sessions[s].length // size.page_tokens)
            if pages <= room:
                batch.append(s)
                room -= pages
    ker = tier.attend(batch, impl="kernel")
    ref = tier.attend(batch, impl="xla")
    err_ref = err_f64 = 0.0
    for s in batch:
        sess = tier.sessions[s]
        slabs = np.stack(tier.expected_slabs(s))   # [pages, L, page, 2, KH, D]
        kv = slabs[:, 0].reshape(-1, 2, size.kv_heads, size.head_dim)
        kv = kv[:sess.length]
        q = np.full((size.kv_heads, size.head_dim),
                    token_value(s, sess.length), np.float32)
        want = attention_f64(q, kv[:, 0], kv[:, 1])
        err_ref = max(err_ref, float(np.abs(ker[s] - ref[s]).max()))
        err_f64 = max(err_f64, float(np.abs(ker[s] - want).max()))
    devices = sorted({str(tier._shards[tier.sessions[s].node].cache.device)
                      for s in batch})
    print(f"[serving] attention over {len(batch)} sessions on {devices}: "
          f"max |kernel - xla| = {err_ref!r}, max |kernel - float64| = "
          f"{err_f64!r} (tolerance {ATTN_ATOL})", flush=True)
    check(err_ref <= ATTN_ATOL and err_f64 <= ATTN_ATOL,
          "kernel attention over the tier's pages is off the reference")
    return {"sessions": len(batch), "devices": devices,
            "err_xla": err_ref, "err_f64": err_f64}


def run_serving(size: ServingSize = ServingSize(),
                require_distinct_devices: bool = False) -> Dict:
    """Admit, spill, decode, fail over and verify on the serving tier."""
    slab = (size.num_layers * size.page_tokens * 2 * size.kv_heads
            * size.head_dim * 4)
    pages_per_seq = -(-(size.prompt_tokens + size.decode_steps)
                      // size.page_tokens)
    # host pools hold the replica of every page plus level-3 spill: size
    # them so admission paces but never refuses this traffic
    capacity = 4 * size.num_seqs * pages_per_seq * slab // NUM_NODES
    cluster = Cluster(NUM_NODES, node_capacity=capacity, page_size=slab,
                      replication_factor=1, admission=True)
    tier = ServingTier(cluster, num_layers=size.num_layers,
                       page_tokens=size.page_tokens, kv_heads=size.kv_heads,
                       head_dim=size.head_dim,
                       hbm_pages_per_node=size.hbm_pages,
                       host_budget_bytes=size.host_budget_pages * slab)
    try:
        seqs = skewed_sequences(tier, size.num_seqs)
        tier.admit({s: size.prompt_tokens for s in seqs})
        homes = [tier.sessions[s].node for s in seqs]
        load = {n: homes.count(n) * pages_per_seq for n in range(NUM_NODES)}
        check(max(load.values()) > size.hbm_pages,
              f"no node is over its HBM capacity: {load}")
        for n in range(NUM_NODES):
            tier._shard(n)             # every node's pool exists up front
        # no reference to a pool array may outlive this line: every page
        # write replaces its shard's array, and a held one stays on the chip
        pool_bytes = sum(sh.cache.kv.nbytes for sh in tier._shards.values())
        pool_devices = sorted({str(sh.cache.device)
                               for sh in tier._shards.values()})
        print(f"[serving] page pool {pool_bytes} bytes over {NUM_NODES} "
              f"nodes on {pool_devices}; pages a node at the end of decode "
              f"{load} against {size.hbm_pages} HBM slots", flush=True)
        if require_distinct_devices:
            check(len(pool_devices) == NUM_NODES,
                  f"pools share devices: {pool_devices}")

        # the victim: a session whose home is neither the hot node nor its
        # spill target and replica (node 1), whose level-3 pages it holds
        victim = next(s for s in seqs if tier.sessions[s].node == 2)
        kill_step = size.decode_steps // 2
        killed = []

        def kill():
            node = tier.sessions[victim].node
            cluster.kill_node(node)
            killed.append(node)

        for step in range(size.warmup_steps):
            tier.decode(seqs, 1)
        with CompileCounter() as compiles:
            for step in range(size.warmup_steps, size.decode_steps):
                if step == kill_step:
                    tier.add_fault_hook("mid_decode", kill)
                tier.decode(seqs, 1)
        check(killed and tier.stats["failovers"] >= 1,
              f"no failover happened: {tier.stats}")
        check(all(tier.sessions[s].length
                  == size.prompt_tokens + size.decode_steps for s in seqs),
              "a sequence missed decode steps")
        bad = [s for s in seqs if not tier.verify(s)]
        check(not bad, f"sequences differ from the KV oracle: {bad}")
        print(f"[serving] {len(seqs)} sequences byte-identical to the oracle "
              f"after killing node {killed[0]} at decode step {kill_step}; "
              f"failovers={tier.stats['failovers']}; compilations in decode "
              f"steps {size.warmup_steps}..{size.decode_steps - 1}: "
              f"{compiles.count}", flush=True)

        attention = check_attention(tier, size)
        check(all(tier.verify(s) for s in seqs),
              "attention's page restores changed a sequence")

        spills = {"hbm_to_host": 0, "host_puts": 0, "host_to_remote": 0,
                  "remote_fetches": 0, "hbm_restores": 0}
        for sh in tier._shards.values():
            spills["hbm_to_host"] += sh.cache.stats["offloads"]
            spills["hbm_restores"] += sh.cache.stats["fetches"]
            spills["host_puts"] += sh.store.stats["host_puts"]
            spills["host_to_remote"] += sh.store.stats["remote_spills"]
            spills["remote_fetches"] += sh.store.stats["remote_fetches"]
        print(f"[serving] spills on live nodes: {spills}", flush=True)
        check(spills["hbm_to_host"] > 0 and spills["host_puts"] > 0
              and spills["host_to_remote"] > 0
              and spills["remote_fetches"] > 0,
              f"a spill level was never used: {spills}")
        stats = tier._shards[0].cache.device.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"[serving] device peak_bytes_in_use={peak}", flush=True)
        return {"pool_bytes": pool_bytes, "pool_devices": pool_devices,
                "peak_bytes_in_use": peak, "spills": spills,
                "failovers": tier.stats["failovers"],
                "decode_compiles": compiles.count, "attention": attention}
    finally:
        tier.close()
        cluster.shutdown()


# -- phase 2: the kernel on a seeded pool -------------------------------------
@dataclass(frozen=True)
class KernelSize:
    batch: int = 8
    heads: int = KV_CONFIG.n_heads
    kv_heads: int = KV_CONFIG.kv_heads
    head_dim: int = KV_CONFIG.head_dim
    page_tokens: int = 16
    pool_pages: int = 128
    max_pages: int = 34


def run_kernel(seed: int, size: KernelSize = KernelSize(),
               require_custom_call: bool = True) -> Dict:
    """Compiled paged attention vs the gather reference and float64."""
    import jax
    from repro.kernels.paged_attention.ops import paged_attention
    rng = np.random.default_rng(seed)
    pool = rng.standard_normal(
        (size.pool_pages, size.page_tokens, 2, size.kv_heads, size.head_dim)
    ).astype(np.float32)
    q = rng.standard_normal(
        (size.batch, size.heads, size.head_dim)).astype(np.float32)
    top = size.max_pages * size.page_tokens
    lengths = rng.integers(1, top + 1, size.batch).astype(np.int32)
    lengths[0], lengths[-1] = 1, top - size.page_tokens // 2   # partial tails
    tables = np.full((size.batch, size.max_pages), -1, np.int32)
    for b in range(size.batch):
        used = -(-int(lengths[b]) // size.page_tokens)
        tables[b, :used] = rng.choice(size.pool_pages, used, replace=False)
    args = [jax.device_put(x) for x in (q, pool, tables, lengths)]
    kernel = jax.jit(functools.partial(paged_attention, impl="kernel"))
    ker = np.asarray(kernel(*args))
    ref = np.asarray(jax.jit(functools.partial(paged_attention,
                                               impl="xla"))(*args))
    custom_call = "tpu_custom_call" in kernel.lower(*args).compile().as_text()
    err_f64 = 0.0
    for b in range(size.batch):
        used = -(-int(lengths[b]) // size.page_tokens)
        kv = pool[tables[b, :used]].reshape(-1, 2, size.kv_heads,
                                            size.head_dim)[:lengths[b]]
        want = attention_f64(q[b], kv[:, 0], kv[:, 1])
        err_f64 = max(err_f64, float(np.abs(ker[b] - want).max()))
    err_ref = float(np.abs(ker - ref).max())
    print(f"[kernel] B={size.batch} H={size.heads} KH={size.kv_heads} "
          f"D={size.head_dim} pages={size.pool_pages}x{size.page_tokens} "
          f"lengths={lengths.tolist()}: max |kernel - xla| = {err_ref!r}, "
          f"max |kernel - float64| = {err_f64!r} (tolerance {ATTN_ATOL}); "
          f"tpu_custom_call in HLO: {custom_call}", flush=True)
    check(np.isfinite(ker).all(), "kernel output is not finite")
    check(err_ref <= ATTN_ATOL and err_f64 <= ATTN_ATOL,
          "kernel attention is off the reference")
    if require_custom_call:
        check(custom_call, "the kernel did not compile to a TPU custom call")
    return {"err_xla": err_ref, "err_f64": err_f64,
            "tpu_custom_call": custom_call}


# -- phase 3: analytics on the in-process cluster -----------------------------
def run_analytics(seed: int, n: int = 6_000_000) -> Dict:
    rng = np.random.default_rng(seed)
    recs = skewed_records(rng, n)
    key_range = max(1, n // 4)
    build = np.empty(key_range // 2, REC)
    build["key"] = rng.permutation(key_range)[:len(build)]
    build["val"] = rng.integers(0, 100, len(build)).astype(np.float64)
    cluster = Cluster(NUM_NODES, node_capacity=max(64 << 20, n * 64),
                      page_size=1 << 20, replication_factor=1)
    try:
        t0 = time.perf_counter()
        keys, sums = cluster_aggregate(cluster, "lineitem", recs, "key",
                                       "val", keep_dataset=True)
        want_k, want_v = group_sum(recs["key"], recs["val"])
        check(np.array_equal(keys, want_k) and np.array_equal(sums, want_v),
              "aggregate differs from numpy")
        print(f"[analytics] {n} records loaded and aggregated into "
              f"{len(keys)} groups in {time.perf_counter() - t0:.3f} s",
              flush=True)

        want_join = join_reference(build, recs)
        t0 = time.perf_counter()
        net0 = cluster.net_bytes
        joined, report = cluster_join(cluster, "copart", build, recs, "key",
                                      replication_factor=0)
        check(report.net_bytes == 0 and cluster.net_bytes == net0,
              f"co-partitioned join moved {report.net_bytes} bytes")
        check(np.array_equal(joined, want_join),
              "co-partitioned join differs from numpy")
        print(f"[analytics] co-partitioned join: {len(joined)} rows, "
              f"net_bytes={report.net_bytes}, "
              f"{time.perf_counter() - t0:.3f} s", flush=True)

        t0 = time.perf_counter()
        joined, report = cluster_join(cluster, "shuffled", build, recs,
                                      "key", build_partition_field="val",
                                      probe_partition_field="val",
                                      replication_factor=0)
        check(len(report.plan.shuffle_sides) == 2,
              f"expected both sides shuffled: {report.plan.shuffle_sides}")
        check(np.array_equal(joined, want_join),
              "both-shuffled join differs from numpy")
        print(f"[analytics] both-shuffled join: {len(joined)} rows, "
              f"net_bytes={report.net_bytes}, "
              f"{time.perf_counter() - t0:.3f} s", flush=True)

        t0 = time.perf_counter()
        sset = cluster.catalog["lineitem"]
        cluster.kill_node(2)
        recovery = cluster.recover_node(2)
        check(recovery.ok, f"recovery failed: {recovery.checksum_failures}")
        back = cluster.read_sharded(sset)
        check(np.array_equal(sorted_records(back), sorted_records(recs)),
              "recovered dataset differs from the loaded records")
        print(f"[analytics] kill + recover node 2: "
              f"{recovery.shards_recovered} shards, "
              f"{recovery.bytes_transferred} bytes, checksums ok, dataset "
              f"identical, {time.perf_counter() - t0:.3f} s", flush=True)
        return {"groups": len(keys), "join_rows": len(joined),
                "recovered_shards": recovery.shards_recovered}
    finally:
        cluster.shutdown()


# -- phase 4: the process backend, forked after the chip is held --------------
def run_proc(seed: int, n: int = 1_000_000, reducers: int = 8) -> Dict:
    """Node processes run numpy host code only: no JAX call is reachable
    from the node server, so a process forked after this one holds the chip
    never initialises a backend of its own."""
    rng = np.random.default_rng(seed + 1)
    recs = skewed_records(rng, n)
    cluster = Cluster(NUM_NODES, backend="proc",
                      node_capacity=max(64 << 20, n * 64),
                      page_size=1 << 16, replication_factor=1)
    try:
        t0 = time.perf_counter()
        sset = cluster.create_sharded_set("orders", recs,
                                          key_fn=lambda r: r["key"])
        shuffle = cluster.shuffle("orders.agg", reducers, REC)
        shuffle.map_sharded(sset, key_field="key")
        shuffle.finish_maps()
        victim = 1
        pid = cluster.nodes[victim].proc.pid
        cluster.kill_node(victim)          # SIGKILL between map and reduce
        shuffle.place_reducers_locally()
        parts = [segment_sum(p["key"], p["val"])
                 for p in (shuffle.pull(r) for r in range(reducers))]
        keys = np.concatenate([k for k, _ in parts])
        sums = np.concatenate([v for _, v in parts])
        order = np.argsort(keys)
        want_k, want_v = group_sum(recs["key"], recs["val"])
        check(np.array_equal(keys[order], want_k)
              and np.array_equal(sums[order], want_v),
              "proc-backend aggregate differs from numpy")
        cluster.revive_node(victim)
        new_pid = cluster.nodes[victim].proc.pid
        check(cluster.nodes[victim].alive and new_pid != pid,
              "node was not revived")
        back = cluster.read_sharded(sset)
        check(np.array_equal(sorted_records(back), sorted_records(recs)),
              "dataset read after revival differs")
        print(f"[proc] {n} records aggregated into {len(keys)} groups with "
              f"node {victim} (pid {pid}) SIGKILLed between map and reduce; "
              f"revived as pid {new_pid}; "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
    finally:
        report = cluster.close()
    check(report.ok, f"proc cluster left processes or segments: {report}")
    return {"groups": len(keys), "revived_pid": new_pid}


def result_line(devices) -> str:
    """The last line of a passing run: the device as JAX reports it."""
    dev = devices[0]
    return json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}})


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)
    devices = require_tpu(args.chips)
    print(f"compile cache: {configure_compile_cache()}", flush=True)
    if args.chips == 4:
        with phase("serving"):
            run_serving(require_distinct_devices=True)
    else:
        with phase("serving"):
            serving = run_serving()
        check(serving["decode_compiles"] == 0,
              f"{serving['decode_compiles']} compilations in steady decode")
        with phase("kernel"):
            run_kernel(args.seed)
        with phase("analytics"):
            run_analytics(args.seed)
        with phase("proc"):
            run_proc(args.seed)
    print(result_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
