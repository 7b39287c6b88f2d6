"""Serving-tier quickstart: paged-KV decode over the monolithic pool.

Builds a 4-node cluster and a ``ServingTier`` on top of it: sequences
shard across nodes by session affinity, prefills are admitted through
the cluster's admission front end (refused ones divert to idle nodes),
and each shard's ``PagedKVCache`` spills HBM -> host -> remote node as
sequences outgrow their page pool (docs/ARCHITECTURE.md §9).

Mid-stream, the node holding a session is killed: the session fails
over to its replica and keeps decoding — the script asserts the
committed KV pages survive byte-identically and that no reservation
leaked on any surviving node.

Run: PYTHONPATH=src python examples/serving_quickstart.py
"""
import numpy as np

from repro.runtime.cluster import Cluster
from repro.runtime.serving import ServingTier


def main() -> None:
    cluster = Cluster(num_nodes=4, node_capacity=8 << 20,
                      page_size=1 << 14, replication_factor=1,
                      admission=True)
    # 8 HBM slots + a one-slab (512 B) host budget per shard: the long
    # sequence pushes slabs through all three spill levels
    tier = ServingTier(cluster, hbm_pages_per_node=8,
                       host_budget_bytes=512)

    # --- continuous-batching admission -------------------------------------
    plan = tier.admit({1: 30, 2: 6, 3: 8})
    homes = {s: sess.node for s, sess in sorted(tier.sessions.items())}
    print(f"admitted 3 sequences; session homes {homes}, "
          f"{len(plan.diversions)} diverted off pressured nodes")

    tier.decode([1, 2, 3], steps=8)
    shard = tier._shards[tier.sessions[1].node]
    print(f"decoded 8 steps/seq; spill stats on seq 1's shard: "
          f"{shard.store.stats}")

    # --- kill the primary mid-stream ---------------------------------------
    victim = tier.sessions[1].node
    pre = [s.copy() for s in tier.sequence_slabs(1)]
    pre_len = tier.sessions[1].length
    cluster.kill_node(victim)
    print(f"killed node {victim} (home of seq 1) mid-stream")

    tier.decode([1, 2, 3], steps=4)
    assert tier.stats["failovers"] >= 1
    now = tier.sequence_slabs(1)
    for k in range(pre_len // tier.page_tokens):
        assert now[k].tobytes() == pre[k].tobytes(), "KV page diverged"
    assert all(tier.verify(s) for s in (1, 2, 3))
    print(f"seq 1 resumed on node {tier.sessions[1].node}: committed pages "
          f"byte-identical, all sequences verify against the KV oracle")

    # --- attention over the restored pool ----------------------------------
    # a shard attends over what its HBM pool holds at once: seq 1 alone
    # outgrows its pool, so attend over the two short sequences
    out = tier.attend([2, 3])     # the Pallas kernel, on each pool's device
    print(f"decode attention over the serving pool: "
          f"{sorted((s, v.shape) for s, v in out.items())}")

    for s in (1, 2, 3):
        tier.finish(s)
    for nid, rep in cluster.pressure_report().items():
        assert rep["reserved"] == 0, (nid, rep)
    tier.close()
    cluster.shutdown()
    print("clean: no leaked reservations on any surviving node")


if __name__ == "__main__":
    main()
