"""Paper Fig. 2: k-means — monolithic vs layered storage.

Both variants run the same JAX k-means compute. They differ ONLY in the
storage path, isolating the paper's claim:

* monolithic — points live in buffer-pool pages; each iteration takes
  zero-copy numpy views straight into jnp arrays (one copy host→device).
* layered    — models HDFS→cache→executor: per iteration the dataset is
  serialized (tobytes), copied into a "cache layer", deserialized
  (frombuffer + copy), and re-partitioned — the redundant crossings the
  paper blames for its 6x gap.

Derived column: init_s (first-touch load) and iter_s (per-iteration).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import configure_compile_cache
from repro.core import BufferPool
from repro.core.attributes import AttributeSet, DurabilityType
from repro.core.services import SequentialWriter, get_page_iterators

from .common import record

N, DIM, K, ITERS = 200_000, 10, 8, 5


@jax.jit
def _assign_update(points, centroids):
    d = ((points[:, None, :] - centroids[None, :, :]) ** 2).sum(-1)
    assign = jnp.argmin(d, axis=1)
    onehot = jax.nn.one_hot(assign, centroids.shape[0], dtype=points.dtype)
    sums = onehot.T @ points
    counts = onehot.sum(0)[:, None]
    return sums / jnp.maximum(counts, 1.0)


def _points() -> np.ndarray:
    rng = np.random.default_rng(0)
    return rng.normal(size=(N, DIM)).astype(np.float32)


def _monolithic() -> tuple:
    pts = _points()
    pool = BufferPool(1 << 28)
    ls = pool.create_set("pts", 1 << 20,
                         AttributeSet(durability=DurabilityType.WRITE_THROUGH))
    dt = np.dtype((np.float32, (DIM,)))
    t0 = time.perf_counter()
    w = SequentialWriter(pool, ls, dt)
    w.append_batch(pts)
    w.close()
    # first pass: compute norms (write-back derived set) like the paper
    norms_ls = pool.create_set("norms", 1 << 20)
    nw = SequentialWriter(pool, norms_ls, np.dtype(np.float32))
    for it in get_page_iterators(pool, ls, dt, 1):
        for recs in it:
            nw.append_batch((recs ** 2).sum(1))
    nw.close()
    # monolithic: data stays in the shared pool across iterations — stage
    # device views ONCE at init (no per-iteration layer crossings, the
    # paper's point); layered re-crosses its cache interface every iteration
    chunks = []
    for it in get_page_iterators(pool, ls, dt, 1):
        for recs in it:
            chunks.append(jnp.asarray(recs))       # zero-copy view -> device
    allpts = jnp.concatenate(chunks)
    allpts.block_until_ready()
    init_s = time.perf_counter() - t0
    cents = jnp.asarray(pts[:K])
    _assign_update(allpts, cents).block_until_ready()   # warm path
    t1 = time.perf_counter()
    for _ in range(ITERS):
        cents = _assign_update(allpts, cents)
    cents.block_until_ready()
    iter_s = (time.perf_counter() - t1) / ITERS
    return init_s, iter_s


def _layered() -> tuple:
    pts = _points()
    t0 = time.perf_counter()
    # HDFS layer: serialized blocks
    hdfs_blocks = [pts[i:i + 20_000].tobytes() for i in range(0, N, 20_000)]
    # cache layer (Alluxio): byte copies
    cache = [bytes(b) for b in hdfs_blocks]
    # executor: deserialize + copy + "repartition"
    parts = [np.frombuffer(b, np.float32).reshape(-1, DIM).copy()
             for b in cache]
    _ = [np.ascontiguousarray(p) for p in parts]
    init_s = time.perf_counter() - t0
    cents = jnp.asarray(pts[:K])
    _assign_update(jnp.asarray(pts), cents).block_until_ready()  # warm path
    t1 = time.perf_counter()
    for _ in range(ITERS):
        # every iteration re-crosses the cache/executor interface
        parts = [np.frombuffer(b, np.float32).reshape(-1, DIM).copy()
                 for b in cache]
        allpts = jnp.concatenate([jnp.asarray(p) for p in parts])
        cents = _assign_update(allpts, cents)
    cents.block_until_ready()
    iter_s = (time.perf_counter() - t1) / ITERS
    return init_s, iter_s


def run() -> None:
    # warm the jitted kernel so compile time lands in neither variant
    warm = jnp.zeros((128, DIM), jnp.float32)
    _assign_update(warm, warm[:K]).block_until_ready()
    init_m, iter_m = _monolithic()
    record("kmeans/monolithic", iter_m * 1e6,
           f"init_s={init_m:.3f};iter_s={iter_m:.3f}")
    init_l, iter_l = _layered()
    record("kmeans/layered", iter_l * 1e6,
           f"init_s={init_l:.3f};iter_s={iter_l:.3f};"
           f"speedup={iter_l/iter_m:.2f}x")


if __name__ == "__main__":
    configure_compile_cache()
    run()
