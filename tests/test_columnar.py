"""Columnar page layout (PR 7): block-format roundtrip, checksum
byte-compatibility with the row scheme, per-field CRC chain invariance,
the dispatch plan's grouping contract, the zero-intermediate gather
landing against the fused reference pass, cluster-level byte identity
(including the over-capacity spill path and pull verification flags), the
shuffle -> aggregate -> join property sweep, and the pagelog fsync policy
knob."""
import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import BufferPool
from repro.core.columnar import (ColumnarWriter, ColumnLayout,
                                 columnar_content_checksum, columns_crc32,
                                 columns_to_records, fused_partition_crc,
                                 iter_column_blocks, records_to_columns,
                                 route_partition_ids)
from repro.core.pagelog import FSYNC_POLICIES, PageLog, fsck
from repro.core.replication import record_content_checksum
from repro.core.services import canonical_join_sort, columnar_job_data_attrs
from repro.runtime.cluster import (Cluster, ClusterShuffle,
                                   cluster_hash_aggregate, dispatch_plan)
from repro.runtime.join import cluster_join

REC = np.dtype([("key", np.int64), ("payload", np.uint8, (10,))])
PAIR = np.dtype([("key", np.int64), ("val", np.float64)])


def _recs(n, seed=0):
    rng = np.random.default_rng(seed)
    out = np.zeros(n, REC)
    out["key"] = rng.integers(-(1 << 40), 1 << 40, n)
    out["payload"] = rng.integers(0, 256, (n, 10))
    return out


def _bytesorted(recs):
    """Canonical order for REC (its multi-dim payload defeats lexsort over
    fields): plain byte-lexicographic sort of the packed records."""
    if len(recs) <= 1:
        return recs
    a = np.frombuffer(recs.tobytes(), np.uint8).reshape(len(recs),
                                                        recs.itemsize)
    order = np.lexsort(tuple(a[:, i] for i in reversed(range(recs.itemsize))))
    return recs[order]


def _pairs(n, key_range, seed=0):
    rng = np.random.default_rng(seed)
    out = np.zeros(n, PAIR)
    out["key"] = rng.integers(0, key_range, n)
    # integer-valued floats: sums are exact regardless of reduction order,
    # so row and columnar aggregates must agree bit-for-bit
    out["val"] = rng.integers(0, 1000, n).astype(np.float64)
    return out


# -- block format -------------------------------------------------------------
def test_block_roundtrip_across_page_splits():
    pool = BufferPool(4 << 20)
    ls = pool.create_set("c", 1 << 12, columnar_job_data_attrs())
    recs = _recs(2000)
    assert ColumnLayout.for_page(REC, 1 << 12).capacity < 2000  # splits
    w = ColumnarWriter(pool, ls, REC)
    w.append_batch(recs)
    w.close()
    got = np.concatenate([columns_to_records(cols, REC, n)
                          for cols, n in iter_column_blocks(pool, ls, REC)])
    assert np.array_equal(got, recs)


def test_content_checksum_matches_row_scheme():
    recs = _recs(1234)
    cols = records_to_columns(recs)
    assert columnar_content_checksum(cols, REC) == \
        record_content_checksum(recs)
    assert columnar_content_checksum(records_to_columns(np.zeros(0, REC)),
                                     REC, 0) == 0


def test_per_field_crc_chains_invariant_to_splits():
    recs = _recs(777)
    cols = records_to_columns(recs)
    whole = columns_crc32(cols, REC, 0, len(recs))
    for splits in ([0, 777], [0, 1, 777], [0, 100, 101, 400, 777]):
        crcs = None
        for lo, hi in zip(splits, splits[1:]):
            crcs = columns_crc32(cols, REC, lo, hi, crcs)
        assert crcs == whole


# -- dispatch plan ------------------------------------------------------------
@pytest.mark.parametrize("case", ["random", "empty", "single_partition",
                                  "all_same_key", "trailing_empty"])
def test_dispatch_plan_groups_rows_by_partition(case):
    rng = np.random.default_rng(7)
    parts, num_partitions = {
        "random": (rng.integers(0, 16, 5000).astype(np.uint8), 16),
        "empty": (np.zeros(0, np.uint8), 16),
        "single_partition": (np.full(4096, 3, np.uint8), 16),
        "all_same_key": (np.zeros(100, np.uint8), 16),
        # more partitions than the largest id: the trailing ones are empty
        "trailing_empty": (rng.integers(0, 5, 1000), 40),
    }[case]
    order, counts, offsets = dispatch_plan(parts, num_partitions)
    assert len(counts) == num_partitions
    assert len(offsets) == num_partitions + 1
    assert offsets[0] == 0 and offsets[-1] == len(parts)
    assert sorted(order.tolist()) == list(range(len(parts)))
    for p in range(num_partitions):
        sl = order[offsets[p]:offsets[p + 1]]
        assert counts[p] == len(sl) == np.count_nonzero(parts == p)
        # every slice holds exactly its partition's rows, in input order
        assert np.all(parts[sl] == p)
        assert np.all(np.diff(sl) > 0)


def test_gather_landing_matches_fused_reference():
    """The zero-intermediate landing (np.take straight into page regions +
    CRC over landed bytes) must byte-match the reference fused pass that
    materializes a routed intermediate."""
    recs = _recs(3000, seed=5)
    cols = records_to_columns(recs)
    keys = cols["key"]
    P = 4
    routed, counts, offsets, want_crcs = fused_partition_crc(
        keys, cols, REC, P)
    h = route_partition_ids(keys, P)
    order, counts2, offsets2 = dispatch_plan(h.astype(np.uint8), P)
    assert np.array_equal(counts, counts2)
    assert np.array_equal(offsets, offsets2)
    pool = BufferPool(8 << 20)
    bounds = offsets.tolist()
    for p in range(P):
        ls = pool.create_set(f"part{p}", 1 << 13, columnar_job_data_attrs())
        w = ColumnarWriter(pool, ls, REC)
        got = w.gather_append(cols, order, bounds[p], bounds[p + 1])
        w.close()
        assert got == want_crcs[p]
        lo, hi = bounds[p], bounds[p + 1]
        want = columns_to_records(
            {name: routed[name][lo:hi] for name in routed}, REC, hi - lo)
        landed = [columns_to_records(c, REC, n)
                  for c, n in iter_column_blocks(pool, ls, REC)]
        assert np.array_equal(np.concatenate(landed), want)


# -- cluster shuffle byte identity --------------------------------------------
def _shuffle_partitions(columnar, n=6000, node_capacity=32 << 20,
                        page_size=1 << 16):
    cluster = Cluster(4, node_capacity=node_capacity, page_size=page_size,
                      replication_factor=0)
    rng = np.random.default_rng(3)
    recs = np.zeros(n, REC)
    recs["key"] = rng.zipf(1.3, n).astype(np.int64)
    recs["payload"] = rng.integers(0, 256, (n, 10))
    sset = cluster.create_sharded_set(
        "s", recs, key_fn=lambda r: r["key"],
        attrs_factory=columnar_job_data_attrs if columnar else None)
    sh = ClusterShuffle(cluster, "sh", num_reducers=4, dtype=REC,
                        columnar=columnar)
    for s in sorted(sset.shards):
        sh.map_shard(sset, s, key_fn=lambda r: r["key"], key_field="key")
    sh.finish_maps()
    parts = []
    for r in range(4):
        parts.append(_bytesorted(sh.pull(r)))
        sh.release_reducer(r)
    spill = sum(node.memory.stats["spill_bytes"]
                for node in cluster.nodes.values())
    cluster.shutdown()
    return parts, spill


def test_columnar_shuffle_byte_identical_to_row():
    row, _ = _shuffle_partitions(columnar=False)
    col, _ = _shuffle_partitions(columnar=True)
    for r in range(4):
        assert np.array_equal(row[r], col[r])
        assert record_content_checksum(row[r]) == \
            record_content_checksum(col[r])


def test_columnar_shuffle_byte_identical_under_spill():
    """Over-capacity: map output + staging exceed the per-node pools, so
    landing pages spill and fault back during the pull — the bytes must
    still verify."""
    n = 40000
    cap = 192 << 10
    row, srow = _shuffle_partitions(columnar=False, n=n, node_capacity=cap,
                                    page_size=1 << 13)
    col, scol = _shuffle_partitions(columnar=True, n=n, node_capacity=cap,
                                    page_size=1 << 13)
    assert scol > 0, "columnar run never spilled — not over capacity"
    for r in range(4):
        assert np.array_equal(row[r], col[r])


def test_pull_columns_flags_and_deferred_release():
    cluster = Cluster(4, node_capacity=32 << 20, page_size=1 << 16,
                      replication_factor=0)
    recs = _recs(4000, seed=11)
    sset = cluster.create_sharded_set(
        "s", recs, key_fn=lambda r: r["key"],
        attrs_factory=columnar_job_data_attrs)
    sh = ClusterShuffle(cluster, "sh", num_reducers=4, dtype=REC,
                        columnar=True)
    for s in sorted(sset.shards):
        sh.map_shard(sset, s, key_fn=lambda r: r["key"], key_field="key")
    sh.finish_maps()
    cols, n = sh.pull_columns(0, materialize=False, verify=True)
    assert n == sum(svc.partition_records[0]
                    for svc in sh._services.values())
    # map-side partition sets survive the pull (release is deferred) ...
    for svc in sh._services.values():
        assert svc.partition_sets[0].name in svc.pool.paging.sets
    sh.release_reducer(0)
    # ... and drop on release_reducer
    for svc in sh._services.values():
        assert svc.partition_sets[0].name not in svc.pool.paging.sets
    cluster.shutdown()


def test_pull_columns_crc_failure_raises_and_repull_succeeds():
    cluster = Cluster(4, node_capacity=32 << 20, page_size=1 << 16,
                      replication_factor=0)
    recs = _recs(4000, seed=13)
    sset = cluster.create_sharded_set(
        "s", recs, key_fn=lambda r: r["key"],
        attrs_factory=columnar_job_data_attrs)
    sh = ClusterShuffle(cluster, "sh", num_reducers=4, dtype=REC,
                        columnar=True)
    for s in sorted(sset.shards):
        sh.map_shard(sset, s, key_fn=lambda r: r["key"], key_field="key")
    sh.finish_maps()
    # corrupt one landed key byte on a map node that received partition 0
    svc = next(s for s in sh._services.values()
               if s.partition_records[0] > 0)
    ls = svc.partition_sets[0]
    layout = ColumnLayout.for_page(REC, ls.page_size)
    page = ls.pages[min(ls.pages)]
    view = svc.pool.pin(page)
    view[layout.field_offs["key"]] ^= 0xFF
    svc.pool.unpin(page, dirty=True)
    with pytest.raises(ValueError, match="CRC"):
        sh.pull_columns(0)
    # deferred release left the map output intact: undo the flip, re-pull
    view = svc.pool.pin(page)
    view[layout.field_offs["key"]] ^= 0xFF
    svc.pool.unpin(page, dirty=True)
    cols, n = sh.pull_columns(0)
    assert n == sum(s.partition_records[0] for s in sh._services.values())
    cluster.shutdown()


# -- shuffle -> aggregate -> join property sweep ------------------------------
@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2**31 - 1), st.booleans())
def test_pipeline_columnar_vs_row_property(seed, overcap):
    """The full pipeline — shuffle-backed aggregation plus a distributed
    join — must produce canonical-sort-identical records and equal content
    checksums under either storage scheme, with pools over capacity on some
    examples so the spill path is part of the property."""
    n = 3000
    a = _pairs(n, key_range=40, seed=seed)
    b = _pairs(n // 2, key_range=40, seed=seed + 1)
    results = {}
    for columnar in (False, True):
        cap = (256 << 10) if overcap else (32 << 20)
        cluster = Cluster(4, node_capacity=cap, page_size=1 << 13,
                          replication_factor=0)
        af = columnar_job_data_attrs if columnar else None
        sa = cluster.create_sharded_set("a", a, key_fn=lambda r: r["key"],
                                        attrs_factory=af)
        sb = cluster.create_sharded_set("b", b, key_fn=lambda r: r["key"],
                                        attrs_factory=af)
        gk, gv = cluster_hash_aggregate(cluster, sa, "key", "val",
                                        hash_page_size=1 << 13,
                                        force_shuffle=True)
        order = np.argsort(gk)
        joined, _report = cluster_join(cluster, sa, sb, "key",
                                       page_size=1 << 13)
        results[columnar] = (gk[order], gv[order], joined)
        cluster.shutdown()
    (rk, rv, rj), (ck, cv, cj) = results[False], results[True]
    assert np.array_equal(rk, ck)
    assert np.array_equal(rv, cv)          # integer-valued: exact
    assert np.array_equal(rj, cj)          # both canonical-sorted
    assert record_content_checksum(rj) == record_content_checksum(cj)


# -- pagelog fsync policy knob ------------------------------------------------
def test_fsync_policy_validated():
    with pytest.raises(ValueError, match="fsync_policy"):
        PageLog("/tmp/never-created", fsync_policy="wat")


def test_fsync_default_none_never_syncs(tmp_path):
    log = PageLog(str(tmp_path))
    assert log.fsync_policy == "none"
    for _ in range(8):
        log.append("s", os.urandom(256))
    log.close()
    assert log.fsync_count == 0


def test_fsync_always_syncs_every_append(tmp_path):
    log = PageLog(str(tmp_path), fsync_policy="always")
    for _ in range(5):
        log.append("s", os.urandom(256))
    assert log.fsync_count == 5
    log.close()


def test_fsync_close_syncs_only_at_close(tmp_path):
    log = PageLog(str(tmp_path), fsync_policy="close")
    for _ in range(5):
        log.append("s", os.urandom(256))
    assert log.fsync_count == 0
    log.close()
    assert log.fsync_count == 1


def test_fsync_group_batches_syncs(tmp_path):
    log = PageLog(str(tmp_path), fsync_policy="group", group_bytes=4096)
    for _ in range(16):
        log.append("s", os.urandom(1024))
    # batched: far fewer syncs than appends, but the threshold did trip
    assert 0 < log.fsync_count < 16
    mid = log.fsync_count
    log.close()                       # unsynced tail drains on clean close
    assert log.fsync_count >= mid


@pytest.mark.parametrize("policy", FSYNC_POLICIES)
def test_fsck_clean_under_each_fsync_policy(tmp_path, policy):
    log = PageLog(str(tmp_path), fsync_policy=policy, group_bytes=1024)
    for i in range(6):
        log.append(f"s{i % 2}", os.urandom(512))
    log.close()
    rep = fsck(str(tmp_path))
    assert rep["clean"] and rep["records"] == 6
    assert rep["live_sets"] == ["s0", "s1"]
