"""The paper's flagship application (§9.2.1): k-means over Pangea storage.

  PYTHONPATH=src python examples/kmeans_pangea.py [--points 200000]

Input points are a write-through locality set; the derived points-with-norms
are a write-back set (exactly the paper's setup). Each iteration scans the
sets through the buffer pool with the data-aware paging policy; compute is
jitted JAX.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import BufferPool
from repro.core.attributes import AttributeSet, DurabilityType
from repro.core.services import SequentialWriter, get_page_iterators


@jax.jit
def assign_update(points, norms, centroids):
    # ||x-c||^2 = ||x||^2 - 2 x.c + ||c||^2 — the norms set saves a pass
    xc = points @ centroids.T
    c2 = (centroids ** 2).sum(-1)
    d = norms[:, None] - 2 * xc + c2[None, :]
    assign = jnp.argmin(d, axis=1)
    onehot = jax.nn.one_hot(assign, centroids.shape[0], dtype=points.dtype)
    sums = onehot.T @ points
    counts = onehot.sum(0)[:, None]
    return sums / jnp.maximum(counts, 1.0), assign


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--points", type=int, default=200_000)
    ap.add_argument("--dim", type=int, default=10)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--pool-mb", type=int, default=256)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    pts = rng.normal(size=(args.points, args.dim)).astype(np.float32)
    pool = BufferPool(args.pool_mb << 20)
    pdt = np.dtype((np.float32, (args.dim,)))

    t0 = time.perf_counter()
    inp = pool.create_set("points", 1 << 20,
                          AttributeSet(durability=DurabilityType.WRITE_THROUGH))
    w = SequentialWriter(pool, inp, pdt)
    w.append_batch(pts)
    w.close()
    norms_ls = pool.create_set("norms", 1 << 20)   # write-back derived data
    nw = SequentialWriter(pool, norms_ls, np.dtype(np.float32))
    for it in get_page_iterators(pool, inp, pdt, 1):
        for recs in it:
            nw.append_batch((recs ** 2).sum(1))
    nw.close()
    print(f"init (load + norms): {time.perf_counter()-t0:.3f}s")

    cents = jnp.asarray(pts[:args.k])
    for i in range(args.iters):
        t1 = time.perf_counter()
        pchunks, nchunks = [], []
        for it in get_page_iterators(pool, inp, pdt, 1):
            for recs in it:
                pchunks.append(jnp.asarray(recs))
        for it in get_page_iterators(pool, norms_ls, np.dtype(np.float32), 1):
            for recs in it:
                nchunks.append(jnp.asarray(recs))
        points = jnp.concatenate(pchunks)
        norms = jnp.concatenate(nchunks)
        cents, assign = assign_update(points, norms, cents)
        cents.block_until_ready()
        print(f"iter {i}: {time.perf_counter()-t1:.3f}s "
              f"(pool resident {pool.resident_bytes/2**20:.0f} MB, "
              f"spilled {pool.stats['spill_bytes']/2**20:.0f} MB)")
    print("cluster sizes:", np.bincount(np.asarray(assign),
                                        minlength=args.k))


if __name__ == "__main__":
    main()
