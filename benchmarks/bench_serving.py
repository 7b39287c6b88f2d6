"""Serving-tier benchmark: sustained decode throughput and p99 decode
latency under over-capacity load, admission-on vs always-grant, plus the
SIGKILL-mid-decode failover row on both backends.

The load is deliberately skewed: every sequence's session affinity hashes
to the same node, so always-grant piles the whole working set onto one
HBM page pool and thrashes its offload/restore path, while admission
control diverts refused prefills to idle nodes and keeps decode tails
resident.

Writes ``BENCH_serving.json`` — its own artifact with its own schema
(v1), separate from ``BENCH_cluster.json``.

Usage::

    PYTHONPATH=src python -m benchmarks.bench_serving            # full
    PYTHONPATH=src python -m benchmarks.bench_serving --smoke    # CI sizes
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import List

import numpy as np

from repro.compile_cache import configure_compile_cache

from .common import record, smoke_mode

# v1: overcap rows (serving/cluster4node/overcap/{admission_on,always_grant,
# admission_gain}: p99 decode latency ms + sustained tokens/s, min-of-3) and
# failover rows (serving/cluster4node/failover/sigkill/{inproc,proc}:
# SIGKILL mid-decode, session resumes on the replica byte-identically)
SCHEMA_VERSION = 1

_ROWS: List[dict] = []

# big enough that one slab is a meaningful charge against a small node
GEOM = dict(num_layers=4, page_tokens=8, kv_heads=4, head_dim=16)


def _row(name: str, us_per_call: float, derived: str = "", **metrics) -> None:
    _ROWS.append({"name": name, "us_per_call": us_per_call,
                  "derived": derived, **metrics})
    record(name, us_per_call, derived, **metrics)


def _mk_cluster(backend: str, **kw):
    from repro.runtime.cluster import Cluster
    kw.setdefault("node_capacity", 8 << 20)
    kw.setdefault("page_size", 1 << 14)
    kw.setdefault("replication_factor", 1)
    kw.setdefault("admission", True)
    if backend == "proc":
        return Cluster(4, backend="proc", **kw)
    return Cluster(4, **kw)


def _teardown(cluster, backend: str) -> None:
    if backend == "proc":
        cluster.close()
    else:
        cluster.shutdown()


def _skewed_ids(tier, n: int) -> List[int]:
    """n sequence ids whose session affinity all lands on one node."""
    hot = tier._affinity(0)
    ids, s = [], 0
    while len(ids) < n:
        if tier._affinity(s) == hot:
            ids.append(s)
        s += 1
    return ids


def _overcap_once(admission: bool, n: int, steps: int, hbm: int, cap: int):
    """One over-capacity run: admit n skewed sequences, decode steps rounds,
    return (p99 decode-step latency seconds, sustained tokens/s, diversions).

    cap is sized so host-slab charges trip the hot node's watermark after
    ~n/4 sequences: admission then diverts the rest and every shard's
    decode tails fit in HBM, while always-grant restores a tail page from
    host memory on nearly every step."""
    from repro.runtime.serving import ServingTier
    # timeout 0: required-urgency grants force through immediately instead
    # of parking on the saturated node — the bench measures spill thrash,
    # not the configurable backpressure sleep
    cluster = _mk_cluster("inproc", node_capacity=cap,
                          pressure_watermark=0.5, admission=admission,
                          admission_timeout_s=0.0)
    tier = ServingTier(cluster, hbm_pages_per_node=hbm, host_budget_bytes=None,
                       replicate=False, **GEOM)
    try:
        ids = _skewed_ids(tier, n)
        # sequences arrive one at a time (continuous batching): each probe
        # sees the charges of every prefill already admitted
        diversions = 0
        for sid in ids:
            plan = tier.admit({sid: 2 * GEOM["page_tokens"]})
            diversions += len(plan.diversions)
        import gc

        import jax
        lat = []
        # GC pauses are common-mode noise several ms wide — exactly the
        # scale of the p99 signal under measurement
        gc.disable()
        try:
            t0 = time.perf_counter()
            for _ in range(steps):
                for sid in ids:
                    s0 = time.perf_counter()
                    tier.decode([sid], steps=1)
                    # steps must pay for their own device work: without the
                    # block, async dispatch shifts restore costs onto
                    # whichever step reads next and the percentiles lie
                    jax.block_until_ready(
                        tier._shards[tier.sessions[sid].node].cache.kv)
                    lat.append(time.perf_counter() - s0)
            total = time.perf_counter() - t0
        finally:
            gc.enable()
            gc.collect()
        for sid in ids:
            tier.finish(sid)
        return (float(np.percentile(lat, 99)), n * steps / total, diversions)
    finally:
        tier.close()
        _teardown(cluster, "inproc")


def _bench_overcap() -> None:
    n = 8 if smoke_mode() else 24
    steps = 16 if smoke_mode() else 32
    reps = 3
    hbm = 4 if smoke_mode() else 8
    cap = (64 << 10) if smoke_mode() else (160 << 10)
    results = {}
    for label, admission in (("always_grant", False), ("admission_on", True)):
        p99s, tputs, divs = [], [], []
        for _ in range(reps):
            p99, tput, div = _overcap_once(admission, n, steps, hbm, cap)
            p99s.append(p99)
            tputs.append(tput)
            divs.append(div)
        p99, tput = min(p99s), max(tputs)      # min-of-N wall clock
        results[label] = p99
        _row(f"serving/cluster4node/overcap/{label}", p99 * 1e6,
             f"{tput:.0f} tok/s",
             p99_decode_ms=p99 * 1e3, throughput_tok_s=tput,
             sequences=n, decode_steps=steps, diversions=max(divs))
    gain = results["always_grant"] / max(results["admission_on"], 1e-12)
    _row("serving/cluster4node/overcap/admission_gain",
         results["admission_on"] * 1e6, f"{gain:.2f}x p99",
         p99_speedup=gain,
         admission_wins=bool(results["admission_on"]
                             < results["always_grant"]))


def _failover_once(backend: str):
    """SIGKILL the primary mid-decode; the session must resume on its
    replica byte-identically. Returns (recovery seconds, byte_identical)."""
    from repro.runtime.serving import ServingTier
    cluster = _mk_cluster(backend)
    tier = ServingTier(cluster, hbm_pages_per_node=8, **GEOM)
    try:
        tier.admit({1: 2 * GEOM["page_tokens"]})
        tier.decode([1], steps=4)
        pre = [s.copy() for s in tier.sequence_slabs(1)]
        pre_len = tier.sessions[1].length
        t0 = time.perf_counter()
        cluster.kill_node(tier.sessions[1].node)   # SIGKILL on proc
        tier.decode([1], steps=4)
        recovery = time.perf_counter() - t0
        now = tier.sequence_slabs(1)
        full = pre_len // tier.page_tokens
        ok = (tier.verify(1) and tier.stats["failovers"] >= 1
              and all(now[k].tobytes() == pre[k].tobytes()
                      for k in range(full)))
        tier.finish(1)
        return recovery, ok
    finally:
        tier.close()
        _teardown(cluster, backend)


def _bench_failover() -> None:
    for backend in ("inproc", "proc"):
        recovery, ok = _failover_once(backend)
        _row(f"serving/cluster4node/failover/sigkill/{backend}",
             recovery * 1e6,
             "byte_identical" if ok else "DIVERGED",
             byte_identical=ok, recovery_s=recovery)


def write_results_json(path: str = "BENCH_serving.json") -> dict:
    doc = {
        "schema_version": SCHEMA_VERSION,
        "generated_by": "benchmarks/bench_serving.py",
        "smoke": smoke_mode(),
        "results": _ROWS,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    print(f"# wrote {path} ({len(_ROWS)} rows, schema v{SCHEMA_VERSION})")
    return doc


def run(json_out: str = "BENCH_serving.json") -> None:
    _bench_overcap()
    _bench_failover()
    write_results_json(json_out)


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="shrink problem sizes (same as BENCH_SMOKE=1)")
    parser.add_argument("--json-out", default="BENCH_serving.json")
    args = parser.parse_args(argv)
    if args.smoke:
        os.environ["BENCH_SMOKE"] = "1"
    configure_compile_cache()
    print("name,us_per_call,derived")
    run(args.json_out)


if __name__ == "__main__":
    main()
