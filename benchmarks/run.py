"""Benchmark harness — one benchmark per paper table/figure plus the
roofline summary. Prints ``name,us_per_call,derived`` CSV and writes the
schema-versioned ``BENCH_cluster.json`` artifact (cluster shuffle placement,
net bytes, recovery/degrade times) so the perf trajectory accumulates across
PRs. The serving-tier rows land in their own ``BENCH_serving.json``
(written by ``benchmarks/bench_serving.py``, schema v1).

Usage::

    PYTHONPATH=src python -m benchmarks.run                  # full suite
    PYTHONPATH=src python -m benchmarks.run --suite cluster  # cluster only
    BENCH_SMOKE=1 ... python -m benchmarks.run --smoke       # CI smoke sizes
"""
from __future__ import annotations

import argparse
import os
import sys

CLUSTER_PREFIXES = ["shuffle/cluster", "recovery/cluster", "recovery/degrade",
                    "recovery/warm_vs_cold", "recovery/overcap_scan",
                    "join/cluster", "roofline/fused_partition_crc"]


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="shrink problem sizes (same as BENCH_SMOKE=1)")
    parser.add_argument("--suite", choices=["all", "cluster"], default="all",
                        help="'cluster' runs only the distributed shuffle / "
                             "recovery benchmarks behind BENCH_cluster.json")
    parser.add_argument("--json-out", default="BENCH_cluster.json",
                        help="path for the cluster results artifact")
    args = parser.parse_args(argv)
    if args.smoke:
        os.environ["BENCH_SMOKE"] = "1"

    from repro.compile_cache import configure_compile_cache
    from . import (bench_join, bench_procplane, bench_recovery,
                   bench_serving, bench_shuffle)
    from .common import write_results_json

    configure_compile_cache()

    print("name,us_per_call,derived")
    if args.suite == "all":
        from . import (bench_hashagg, bench_kmeans, bench_paging,
                       bench_replicas, bench_seqrw)
        from . import roofline
        bench_paging.run()        # Fig. 3 / 8 / 9
        bench_seqrw.run()         # Fig. 6 / 7
        bench_shuffle.run()       # Table 4 + scheduler placement
        bench_hashagg.run()       # Table 5
        bench_join.run()          # §9.2.2 distributed join plans
        bench_kmeans.run()        # Fig. 2
        bench_replicas.run()      # Fig. 4
        bench_recovery.run()      # Fig. 5 + elastic degrade
        bench_procplane.run()     # process data plane vs in-process
        bench_serving.run()       # paged-KV serving tier -> BENCH_serving.json
        print("\n# roofline (per-device terms from the dry-run; see "
              "EXPERIMENTS.md)")
        roofline.run(write_csv=True)
        roofline.run_fused()
    else:
        from . import roofline
        bench_shuffle.run()
        bench_join.run()
        bench_recovery.run()
        bench_procplane.run()
        bench_serving.run()
        roofline.run_fused()
    write_results_json(args.json_out, prefixes=CLUSTER_PREFIXES)


if __name__ == "__main__":
    main()
