"""Distributed paged-KV serving tier (runtime/serving.py): cluster-sharded
sequences, continuous-batching admission, three-level spill, and the
fault-injection sweep — SIGKILL/kill_node at every serving phase boundary,
on both backends."""
import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import PagedKVCache
from repro.runtime.cluster import Cluster, DeadNodeError
from repro.runtime.serving import ServingTier, expected_page_slab

BACKENDS = ("inproc", "proc")


def _cluster(backend, tmp_path=None, **kw):
    kw.setdefault("node_capacity", 8 << 20)
    kw.setdefault("page_size", 1 << 14)
    kw.setdefault("replication_factor", 1)
    kw.setdefault("admission", True)
    if tmp_path is not None:
        kw.setdefault("spill_dir", os.path.join(str(tmp_path), "spill"))
    if backend == "proc":
        return Cluster(4, backend="proc", **kw)
    return Cluster(4, **kw)


def _teardown(cluster, backend):
    if backend == "proc":
        report = cluster.close()
        assert report.ok, report
    else:
        cluster.shutdown()


def _assert_clean(cluster):
    """No leaked reservations on any alive node (nor the driver)."""
    for nid, rep in cluster.pressure_report().items():
        assert rep["reserved"] == 0, (nid, rep)


def _tier(cluster, **kw):
    kw.setdefault("hbm_pages_per_node", 4)
    kw.setdefault("host_budget_bytes", 2048)
    return ServingTier(cluster, **kw)


# -- admission + diversion (tentpole) -----------------------------------------
def test_prefill_diverted_off_pressured_affinity_node(tmp_path):
    cluster = _cluster("inproc", tmp_path, node_capacity=1 << 20,
                       pressure_watermark=0.5)
    tier = _tier(cluster)
    seq = 11
    affinity = tier._affinity(seq)
    # ballast the affinity node past its watermark so the speculative
    # low-urgency probe AND the placement probe both refuse
    mm = cluster.nodes[affinity].memory
    ballast = mm.reserve(int(0.9 * (1 << 20)))
    mm.note_alloc(600 << 10)
    plan = tier.admit({seq: 8})
    assert plan.placement[seq] != affinity
    assert plan.diversions[seq][0] == affinity
    assert tier.stats["prefill_refusals"] == 1
    assert tier.verify(seq)
    ballast.release()
    mm.note_free(600 << 10)
    tier.close()
    _assert_clean(cluster)
    _teardown(cluster, "inproc")


def test_always_grant_baseline_never_diverts(tmp_path):
    cluster = _cluster("inproc", tmp_path, admission=False,
                       node_capacity=1 << 20)
    tier = _tier(cluster)
    plan = tier.admit({i: 8 for i in range(6)})
    assert plan.diversions == {}
    for i in range(6):
        assert plan.placement[i] == tier._affinity(i)
    tier.decode(list(range(6)), steps=4)
    assert all(tier.verify(i) for i in range(6))
    tier.close()
    _teardown(cluster, "inproc")


# -- three-level spill (tentpole) ---------------------------------------------
def test_three_level_spill_round_trips_byte_identically(tmp_path):
    """A sequence bigger than HBM with a tiny host budget pushes slabs
    through all three levels; reading the whole sequence back (block_table
    restore) faults them home byte-identically."""
    cluster = _cluster("inproc", tmp_path)
    tier = _tier(cluster, hbm_pages_per_node=3, host_budget_bytes=1024)
    tier.admit({7: 20})           # 5 pages > 3 HBM slots
    tier.decode([7], steps=12)    # 32 tokens = 8 pages
    shard = tier._shards[tier.sessions[7].node]
    assert shard.store.stats["host_puts"] > 0          # level 2 hit
    assert shard.store.stats["remote_spills"] > 0      # level 3 hit
    table = tier.block_table(7)   # restores every page for the kernel
    assert (table >= 0).all()
    assert shard.store.stats["remote_fetches"] > 0     # level 3 faulted back
    assert tier.verify(7)
    tier.close()
    _assert_clean(cluster)
    _teardown(cluster, "inproc")


def test_host_slabs_charge_the_nodes_memory_manager(tmp_path):
    cluster = _cluster("inproc", tmp_path)
    tier = ServingTier(cluster, hbm_pages_per_node=2,
                       host_budget_bytes=None)   # level 2 only, uncapped
    tier.admit({3: 16})
    node = tier.sessions[3].node
    assert cluster.nodes[node].memory.reserved_bytes > 0   # slabs charged
    tier.finish(3)
    _assert_clean(cluster)                                  # and released
    tier.close()
    _teardown(cluster, "inproc")


# -- fault-injection sweep (satellite 1) --------------------------------------
PHASES = ("after_admit", "mid_decode", "during_restore", "during_spill")


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("phase", PHASES)
def test_kill_at_phase_boundary_resumes_byte_identically(
        tmp_path, backend, phase):
    """kill_node/SIGKILL at each serving phase boundary: the session must
    resume on its replica with byte-identical block-table contents, and no
    reservation may leak on any surviving node."""
    cluster = _cluster(backend, tmp_path)
    # budget 0 forces every eviction to level 3 so restore/spill phases fire
    tier = _tier(cluster, hbm_pages_per_node=3,
                 host_budget_bytes=0 if phase in ("during_restore",
                                                  "during_spill") else 1024)
    seqs = {1: 10, 2: 6}
    if phase == "after_admit":
        # the hook fires inside the prefill of the first admitted sequence
        tier.add_fault_hook(
            "after_admit",
            lambda: cluster.kill_node(tier.sessions[1].node))
        tier.admit(seqs)
    else:
        tier.admit(seqs)
        tier.decode([1, 2], steps=4)
        tier.add_fault_hook(
            phase, lambda: cluster.kill_node(tier.sessions[1].node))
    pre = {s: [x.copy() for x in tier.sequence_slabs(s)] for s in seqs}
    pre_len = {s: tier.sessions[s].length for s in seqs}
    if phase == "during_restore":
        # a whole-sequence read faults level-3 slabs home: the hook fires
        # inside the restore itself (spilled state settled first so the
        # restore genuinely comes from the remote tier)
        cluster.transfer.drain(timeout=10.0)
        tier._shards[tier.sessions[1].node].store._reap()
        tier.block_table(1)
    tier.decode([1, 2], steps=6)
    if phase != "after_admit":
        assert tier.stats["failovers"] >= 1, tier.stats
    for s in seqs:
        assert tier.verify(s), f"seq {s} diverged after {phase} kill"
        # committed pre-kill prefix is byte-identical on the new home
        now = tier.sequence_slabs(s)
        full = pre_len[s] // tier.page_tokens   # pages full before the kill
        for k in range(full):
            assert now[k].tobytes() == pre[s][k].tobytes()
        assert (tier.block_table(s) >= 0).all()
    for s in seqs:
        tier.finish(s)
    _assert_clean(cluster)
    tier.close()
    _teardown(cluster, backend)


@pytest.mark.parametrize("backend", BACKENDS)
def test_sigkill_mid_decode_without_replica_demands_rerun(tmp_path, backend):
    """The shuffle contract, honored verbatim: a dead serving node with no
    live replica raises DeadNodeError demanding a re-run."""
    cluster = _cluster(backend, tmp_path, replication_factor=0)
    tier = _tier(cluster, replicate=False)
    tier.admit({5: 8})
    tier.decode([5], steps=2)
    cluster.kill_node(tier.sessions[5].node)
    with pytest.raises(DeadNodeError, match="re-run"):
        tier.decode([5], steps=1)
    tier.close()
    _teardown(cluster, backend)


def test_spill_target_death_mid_transfer_loses_nothing(tmp_path):
    """Killing the level-3 spill *target* while a slab transfer is in
    flight must not lose the slab: the host copy is only dropped after the
    transfer confirms."""
    cluster = _cluster("inproc", tmp_path)
    tier = _tier(cluster, hbm_pages_per_node=3, host_budget_bytes=0)
    tier.admit({9: 10})
    node = tier.sessions[9].node
    target = tier._spill_target(node)
    tier.add_fault_hook("during_spill", lambda: cluster.kill_node(target))
    tier.decode([9], steps=8)
    store = tier._shards[tier.sessions[9].node].store
    cluster.transfer.drain(timeout=10.0)
    store._reap()
    assert tier.verify(9)    # every slab still reachable, byte-identical
    tier.close()
    _assert_clean(cluster)
    _teardown(cluster, "inproc")


def test_replica_death_repicks_and_survives_primary_death_later(tmp_path):
    cluster = _cluster("inproc", tmp_path)
    tier = _tier(cluster)
    tier.admit({4: 8})
    tier.decode([4], steps=2)
    sess = tier.sessions[4]
    cluster.kill_node(sess.replica)          # replica dies first
    tier.decode([4], steps=2)                # re-picks + re-ships
    assert sess.replica is not None and tier._alive(sess.replica)
    cluster.kill_node(sess.node)             # then the primary
    tier.decode([4], steps=2)
    assert tier.stats["failovers"] >= 1
    assert tier.verify(4)
    tier.close()
    _assert_clean(cluster)
    _teardown(cluster, "inproc")


# -- attention over the serving pool ------------------------------------------
def test_attend_runs_kernel_and_xla_identically_after_failover(tmp_path):
    cluster = _cluster("inproc", tmp_path)
    tier = _tier(cluster)
    tier.admit({1: 6, 2: 9})
    tier.decode([1, 2], steps=3)
    cluster.kill_node(tier.sessions[1].node)
    tier.decode([1, 2], steps=2)
    xla = tier.attend([1, 2], impl="xla")
    ker = tier.attend([1, 2], impl="kernel")
    for s in (1, 2):
        np.testing.assert_allclose(xla[s], ker[s], rtol=2e-5, atol=2e-5)
    tier.close()
    _teardown(cluster, "inproc")


def test_attend_refuses_a_batch_larger_than_its_pool(tmp_path):
    """Restoring the pages of a batch that outgrows the HBM pool would
    evict pages its block tables already name: attention over a stale slot
    is refused, not computed."""
    cluster = _cluster("inproc", tmp_path)
    tier = _tier(cluster, hbm_pages_per_node=4)
    tier.admit({1: 18})                   # 5 pages > 4 HBM slots
    assert tier.verify(1)
    with pytest.raises(ValueError, match="more pages than its HBM pool"):
        tier.attend([1])
    tier.close()
    _teardown(cluster, "inproc")


# -- property: random op interleavings vs unlimited-HBM reference (satellite) -
_OPS = st.lists(
    st.tuples(st.integers(min_value=0, max_value=3),    # action
              st.integers(min_value=0, max_value=2),    # session slot
              st.integers(min_value=1, max_value=6)),   # tokens / steps
    min_size=4, max_size=24)


def _ref_extend(tier, ref, sid, old_len, new_len):
    """Mirror a tier prefill/decode into the reference cache."""
    ref.ensure_capacity(sid, new_len - old_len)
    ref.advance(sid, new_len - old_len)
    first = old_len // tier.page_tokens     # tail page may be rewritten
    for k in range(first, -(-new_len // tier.page_tokens)):
        ref.write_page(sid, k, tier._expected_slab(sid, k, new_len))


def _assert_matches_ref(tier, ref, sid):
    assert tier.sessions[sid].length == ref.seq_length(sid)
    mine = tier.sequence_slabs(sid)
    theirs = ref.sequence_slabs(sid)
    assert len(mine) == len(theirs)
    for k, (a, b) in enumerate(zip(mine, theirs)):
        assert a.tobytes() == b.tobytes(), (sid, k)


@settings(max_examples=10, deadline=None)
@given(ops=_OPS)
def test_random_interleavings_match_unlimited_hbm_reference(ops):
    """Any interleaving of admit/decode/read/finish over the spilling tier
    (3 HBM slots, 512-byte host budget => all three spill levels exercised)
    stays byte-identical to a reference PagedKVCache with unlimited HBM that
    never evicts, spills, or restores."""
    cluster = Cluster(3, node_capacity=8 << 20, page_size=1 << 14,
                      replication_factor=1, admission=True)
    tier = ServingTier(cluster, hbm_pages_per_node=3, host_budget_bytes=512)
    ref = PagedKVCache(num_layers=tier.num_layers, hbm_pages=512,
                       page_size=tier.page_tokens, kv_heads=tier.kv_heads,
                       head_dim=tier.head_dim)
    try:
        lengths = {}
        for action, slot, n in ops:
            sid = 100 + slot
            if action == 0 and sid not in tier.sessions:
                tier.admit({sid: n})
                ref.start_sequence(sid)
                _ref_extend(tier, ref, sid, 0, n)
                lengths[sid] = n
            elif action == 1 and sid in lengths:
                tier.decode([sid], steps=n)
                _ref_extend(tier, ref, sid, lengths[sid], lengths[sid] + n)
                lengths[sid] += n
            elif action == 2 and sid in lengths:
                assert tier.verify(sid)
                assert (tier.block_table(sid) >= 0).all()
                _assert_matches_ref(tier, ref, sid)
            elif action == 3 and sid in lengths:
                tier.finish(sid)
                ref.finish_sequence(sid)
                del lengths[sid]
        for sid in list(lengths):
            _assert_matches_ref(tier, ref, sid)
    finally:
        tier.close()
    _assert_clean(cluster)
    _teardown(cluster, "inproc")


# -- oracle sanity ------------------------------------------------------------
def test_expected_page_slab_is_deterministic_and_masked():
    a = expected_page_slab(3, 1, 6, num_layers=2, page_tokens=4,
                           kv_heads=2, head_dim=4)
    b = expected_page_slab(3, 1, 6, num_layers=2, page_tokens=4,
                           kv_heads=2, head_dim=4)
    assert a.tobytes() == b.tobytes()
    assert (a[:, 2:] == 0).all()      # positions 6,7 past the length
    assert (a[:, :2] != 0).all()
