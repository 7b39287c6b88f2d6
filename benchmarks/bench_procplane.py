"""Process data plane vs in-process backend: end-to-end durable shuffle
pipelines (ingest with write-through fsync -> shuffle -> drain) timed
wall-clock on both backends, min-of-N.

On a box with few cores the process backend cannot win on CPU — forked node
processes add RPC framing and shm copies on top of the same arithmetic.
What it *can* win is blocked time: every node process issues its own
``fsync`` / spill I/O / admission waits, so durable appends that the
in-process backend serializes through one thread overlap across nodes.
The two configs bracket that claim:

* **overlap** — replicated durable ingest plus an in-memory shuffle.  The
  fsync stream (primary + replica page appends) dominates; proc overlaps
  them across the four node processes.
* **overcap** — an over-capacity pipeline (node capacity far below the
  working set, admission on).  Spill, refault, and admission stalls
  dominate; proc overlaps those too.

A third row SIGKILLs a node between map and reduce and requires the
shuffle output to come back byte-identical through replica re-execution,
with the arena/process audit clean on close.
"""
from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import time

import numpy as np

from repro.runtime.cluster import Cluster

from .common import record, scaled, smoke_mode

PAIR = np.dtype([("key", np.int64), ("val", np.float64)])
NUM_NODES = 4
NUM_REDUCERS = 8
PAGE = 1 << 13
OVERLAP_N = 800_000
OVERCAP_N = 800_000
SIGKILL_N = 150_000


def _pairs(n: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    recs = np.zeros(n, PAIR)
    recs["key"] = rng.integers(0, 1 << 30, n)
    recs["val"] = rng.random(n)
    return recs


def _cluster(backend: str, tmp: str, *, cap: int, rf: int,
             page_size: int = PAGE) -> Cluster:
    kw = dict(node_capacity=cap, page_size=page_size, replication_factor=rf,
              pagelog_dir=os.path.join(tmp, "log"), pagelog_fsync="always",
              spill_dir=os.path.join(tmp, "spill"), admission=True)
    if backend == "proc":
        return Cluster(NUM_NODES, backend="proc", **kw)
    return Cluster(NUM_NODES, **kw)


def _pipeline(c: Cluster, recs: np.ndarray, proc: bool) -> float:
    """Durable ingest -> shuffle -> drain; returns elapsed seconds."""
    t0 = time.perf_counter()
    sset = c.create_sharded_set("pts", recs, key_fn=lambda r: r["key"])
    sh = c.shuffle("sh", NUM_REDUCERS, PAIR)
    if proc:
        sh.map_sharded(sset, key_field="key")
    else:
        sh.map_sharded(sset, key_fn=lambda r: r["key"])
    sh.finish_maps()
    sh.place_reducers_locally()
    n = sum(len(sh.pull(r)) for r in range(NUM_REDUCERS))
    elapsed = time.perf_counter() - t0
    if n != len(recs):
        raise AssertionError(f"pipeline dropped records: {n} != {len(recs)}")
    return elapsed


def _config(label: str):
    """(records, node_capacity, replication_factor) for one config —
    shared by the parent and the measurement subprocess."""
    if label == "overlap":
        return scaled(OVERLAP_N), 64 << 20, 2
    # keep overcap over capacity at smoke sizes too: cap ~= 1/6 of the
    # working set (full size: 800k * 16B / 6 ~= 2 MiB per node)
    n = scaled(OVERCAP_N)
    return n, max(256 << 10, n * PAIR.itemsize // 6), 1


def _measure_once(label: str, backend: str) -> float:
    n, cap, rf = _config(label)
    with tempfile.TemporaryDirectory() as tmp:
        c = _cluster(backend, tmp, cap=cap, rf=rf)
        recs = _pairs(n)
        elapsed = _pipeline(c, recs, backend == "proc")
        c.close() if backend == "proc" else c.shutdown()
    return elapsed


def _best_of(backend: str, label: str, *, repeats: int) -> float:
    """Min-of-N wall clock, each rep in a fresh interpreter.  Running
    in-process would tax whichever backend runs later in the suite: the
    driver heap the earlier benchmarks fattened makes every proc-backend
    fork pay COW faults, and skews the in-process allocator too."""
    best = None
    # The measurement runs cluster code only, which is numpy on the host.
    # JAX_PLATFORMS=cpu keeps the child off the TPU even if something it
    # imports ever touched JAX: the parent may already hold the chip.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for _ in range(repeats):
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_procplane",
             "--rep", label, backend],
            capture_output=True, text=True, check=True, env=env)
        elapsed = None
        for line in out.stdout.splitlines():
            if line.startswith("ELAPSED "):
                elapsed = float(line.split()[1])
        if elapsed is None:
            raise RuntimeError(
                f"measurement subprocess returned no timing: {out.stdout!r} "
                f"{out.stderr!r}")
        best = elapsed if best is None or elapsed < best else best
    return best


def run_pipelines() -> None:
    repeats = 1 if smoke_mode() else 3
    for label in ("overlap", "overcap"):
        n, cap, rf = _config(label)
        t_in = _best_of("inproc", label, repeats=repeats)
        t_pr = _best_of("proc", label, repeats=repeats)
        gain = t_in / t_pr
        base = f"shuffle/cluster4node/procplane/{label}"
        record(f"{base}/inproc", t_in * 1e6, f"elapsed={t_in:.3f}s",
               elapsed_s=t_in, records=n, node_capacity=cap,
               replication_factor=rf)
        record(f"{base}/proc", t_pr * 1e6, f"elapsed={t_pr:.3f}s",
               elapsed_s=t_pr, records=n, node_capacity=cap,
               replication_factor=rf)
        record(f"{base}/gain", (t_in - t_pr) * 1e6,
               f"gain={gain:.2f}x;proc_wins={gain > 1.0}",
               gain=round(gain, 3), proc_wins=bool(gain > 1.0))


def run_sigkill() -> None:
    """SIGKILL a node between map and reduce; replica re-execution must
    deliver the same partition bytes, and close() must reap every process
    and unlink every arena segment."""
    n = scaled(SIGKILL_N)
    with tempfile.TemporaryDirectory() as tmp:
        c = _cluster("proc", tmp, cap=32 << 20, rf=2, page_size=1 << 14)
        recs = _pairs(n, seed=7)
        sset = c.create_sharded_set("pts", recs, key_fn=lambda r: r["key"])

        def drain(sh):
            parts = []
            for r in range(NUM_REDUCERS):
                parts.append(np.sort(sh.pull(r), order=("key", "val")))
                sh.release_reducer(r)
            return parts

        ref_sh = c.shuffle("ref", NUM_REDUCERS, PAIR)
        ref_sh.map_sharded(sset, key_field="key")
        ref_sh.finish_maps()
        ref_sh.place_reducers_locally()
        ref = drain(ref_sh)

        t0 = time.perf_counter()
        sh = c.shuffle("kill", NUM_REDUCERS, PAIR)
        sh.map_sharded(sset, key_field="key")
        sh.finish_maps()
        c.kill_node(1)                      # between map and reduce
        sh.place_reducers_locally()
        out = drain(sh)
        elapsed = time.perf_counter() - t0

        identical = all(np.array_equal(a, b) for a, b in zip(ref, out))
        report = c.close()
    record("recovery/cluster4node/procplane/sigkill", elapsed * 1e6,
           f"byte_identical={identical};clean_close={report.ok}",
           elapsed_s=elapsed, byte_identical=bool(identical),
           recovered_ok=bool(identical and report.ok), records=n)


def run() -> None:
    run_pipelines()
    run_sigkill()


if __name__ == "__main__":
    if len(sys.argv) == 4 and sys.argv[1] == "--rep":
        print(f"ELAPSED {_measure_once(sys.argv[2], sys.argv[3]):.6f}")
    else:
        run()
