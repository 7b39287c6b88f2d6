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
from repro.runtime.serving import (LatentLayout, ServingTier,
                                   expected_latent_slab, expected_page_slab,
                                   latent_query)

BACKENDS = ("inproc", "proc")
# the two page layouts, at one slab size (2 layers x 4 tokens x 16 float32
# = 512 bytes), so that the host budgets below spill alike: K/V planes of
# 2 heads x 4 channels, or a latent vector of 12 + 4 channels read by 4
# query heads
LAYOUTS = {"gqa": None,
           "latent": LatentLayout(latent_dim=16, value_dim=12, q_heads=4,
                                  scale=0.25)}


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


def _tier(cluster, layout="gqa", **kw):
    kw["layout"] = LAYOUTS[layout]
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
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_three_level_spill_round_trips_byte_identically(tmp_path, layout):
    """A sequence bigger than HBM with a tiny host budget pushes slabs
    through all three levels; reading the whole sequence back (block_table
    restore) faults them home byte-identically, in either page layout."""
    cluster = _cluster("inproc", tmp_path)
    tier = _tier(cluster, layout, hbm_pages_per_node=3,
                 host_budget_bytes=1024)
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


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("phase", PHASES)
def test_kill_at_phase_boundary_resumes_byte_identically(
        tmp_path, backend, phase, layout):
    """kill_node/SIGKILL at each serving phase boundary: the session must
    resume on its replica with byte-identical block-table contents, and no
    reservation may leak on any surviving node, in either page layout."""
    cluster = _cluster(backend, tmp_path)
    # budget 0 forces every eviction to level 3 so restore/spill phases fire
    tier = _tier(cluster, layout, hbm_pages_per_node=3,
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


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_spill_target_death_mid_transfer_loses_nothing(tmp_path, layout):
    """Killing the level-3 spill *target* while a slab transfer is in
    flight must not lose the slab: the host copy is only dropped after the
    transfer confirms."""
    cluster = _cluster("inproc", tmp_path)
    tier = _tier(cluster, layout, hbm_pages_per_node=3, host_budget_bytes=0)
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


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_replica_death_repicks_and_survives_primary_death_later(tmp_path,
                                                               layout):
    cluster = _cluster("inproc", tmp_path)
    tier = _tier(cluster, layout)
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
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_attend_runs_kernel_and_xla_identically_after_failover(tmp_path,
                                                              layout):
    cluster = _cluster("inproc", tmp_path)
    tier = _tier(cluster, layout)
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


def test_attend_compiles_nothing_once_its_shapes_are_warm(
        tmp_path, backend_compiles):
    """A decode step whose batches keep their ``max_pages`` reuses the
    programs of the step before: every layer's attention of the second
    round runs without a compile request, and still equals the XLA path."""
    cluster = _cluster("inproc", tmp_path)
    tier = _tier(cluster)
    tier.admit({1: 6, 2: 9})              # 2 and 3 pages of 4 tokens
    for round_ in range(2):
        if round_:
            tier.decode([1, 2], steps=1)  # 7 and 10 tokens: same pages
        before = len(backend_compiles)
        ker = [tier.attend([1, 2], layer) for layer in range(tier.num_layers)]
        if round_:
            assert len(backend_compiles) == before
    for layer in range(tier.num_layers):
        xla = tier.attend([1, 2], layer, impl="xla")
        for s in (1, 2):
            np.testing.assert_allclose(xla[s], ker[layer][s], rtol=2e-5,
                                       atol=2e-5)
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


def test_attend_splits_a_batch_that_outgrows_its_pool(tmp_path):
    """Two sessions that each fit the HBM pool, but not together: their
    attention runs in one call each and reads what each reads alone."""
    cluster = _cluster("inproc", tmp_path)
    tier = _tier(cluster, hbm_pages_per_node=4)
    node = tier._affinity(1)
    other = next(s for s in range(2, 1000) if tier._affinity(s) == node)
    tier.admit({1: 10, other: 11})        # 3 + 3 pages > 4 HBM slots
    assert {tier.sessions[s].node for s in (1, other)} == {node}
    both = tier.attend([1, other])
    for s in (1, other):
        alone = tier.attend([s], impl="xla")[s]
        np.testing.assert_allclose(both[s], alone, rtol=2e-5, atol=2e-5)
        assert tier.verify(s)
    tier.close()
    _teardown(cluster, "inproc")


def _same_node_sessions(tier, n):
    """``n`` session ids whose affinity is one node."""
    node = tier._affinity(1)
    return [s for s in range(1, 1000) if tier._affinity(s) == node][:n]


class _CountingOutput:
    """A kernel call's output that counts how the tier reads it back:
    ``items`` device rows indexed, ``copies`` whole-array host copies."""

    def __init__(self, array):
        self.array = array
        self.items = 0
        self.copies = 0

    def __getitem__(self, i):
        self.items += 1
        return self.array[i]

    def __array__(self, dtype=None, copy=None):
        self.copies += 1
        return np.asarray(self.array, dtype)


def _count_kernel_outputs(monkeypatch, layout):
    """Wrap the tier's kernel entry point so that each call returns a
    ``_CountingOutput``; returns the list of them, one a call."""
    from repro.kernels.paged_attention import ops
    name = "paged_attention" if layout == "gqa" else "paged_latent_attention"
    real = getattr(ops, name)
    calls = []

    def counted(*args, **kw):
        calls.append(_CountingOutput(real(*args, **kw)))
        return calls[-1]

    monkeypatch.setattr(ops, name, counted)
    return calls


@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_attend_reads_each_call_back_in_one_host_copy(tmp_path, monkeypatch,
                                                      layout, batch):
    """One kernel call's output comes back to the host whole, once, and no
    device row of it is indexed; each session's result is row ``i`` of
    that output, its dtype, shape and bytes unchanged."""
    cluster = _cluster("inproc", tmp_path)
    tier = _tier(cluster, layout, hbm_pages_per_node=8)
    try:
        seqs = _same_node_sessions(tier, batch)
        tier.admit({s: 5 + k for k, s in enumerate(seqs)})  # 2 pages each
        calls = _count_kernel_outputs(monkeypatch, layout)
        out = tier.attend(seqs)
        assert len(calls) == 1
        assert (calls[0].copies, calls[0].items) == (1, 0)
        rows = np.asarray(calls[0].array)
        want_shape = ((tier.kv_heads, tier.head_dim) if layout == "gqa" else
                      (tier.layout.q_heads, tier.layout.value_dim))
        assert sorted(out) == sorted(seqs)
        for i, s in enumerate(seqs):
            assert out[s].dtype == rows.dtype == tier.dtype
            assert out[s].shape == want_shape
            assert out[s].tobytes() == rows[i].tobytes()
    finally:
        tier.close()
        _teardown(cluster, "inproc")


@pytest.mark.parametrize("split", [False, True],
                         ids=["one_call", "pool_sized_parts"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_attend_gives_each_session_its_own_row(tmp_path, monkeypatch, layout,
                                               split):
    """Several sessions in one kernel call, or cut into several calls by
    their HBM pool: each result is the session's own attention, what it
    reads alone, and no two sessions' results are alike."""
    cluster = _cluster("inproc", tmp_path)
    tier = _tier(cluster, layout, hbm_pages_per_node=4)
    try:
        if split:                          # 3 + 3 pages > 4 HBM slots
            seqs = _same_node_sessions(tier, 2)
            sizes = {seqs[0]: 10, seqs[1]: 11}
        else:                              # 1 page each, 4 in the pool
            seqs = _same_node_sessions(tier, 4)
            sizes = {s: 1 + k for k, s in enumerate(seqs)}
        tier.admit(sizes)
        assert len({tier.sessions[s].node for s in seqs}) == 1
        calls = _count_kernel_outputs(monkeypatch, layout)
        got = tier.attend(seqs)
        assert len(calls) == (2 if split else 1)
        monkeypatch.undo()
        for s in seqs:
            alone = tier.attend([s], impl="xla")[s]
            np.testing.assert_allclose(got[s], alone, rtol=2e-5, atol=2e-5)
        for a, b in zip(seqs, seqs[1:]):
            assert not np.allclose(got[a], got[b])
    finally:
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
    never evicts, spills, or restores, in either page layout."""
    for layout in sorted(LAYOUTS):
        _interleave_against_reference(ops, LAYOUTS[layout])


def _interleave_against_reference(ops, layout):
    cluster = Cluster(3, node_capacity=8 << 20, page_size=1 << 14,
                      replication_factor=1, admission=True)
    tier = ServingTier(cluster, hbm_pages_per_node=3, host_budget_bytes=512,
                       layout=layout)
    ref = PagedKVCache(num_layers=tier.num_layers, hbm_pages=512,
                       page_size=tier.page_tokens,
                       token_shape=tier.token_shape)
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


def test_expected_latent_slab_varies_with_every_index_and_is_masked():
    a = expected_latent_slab(3, 1, 6, num_layers=2, page_tokens=4,
                             latent_dim=10)
    assert a.tobytes() == expected_latent_slab(
        3, 1, 6, num_layers=2, page_tokens=4, latent_dim=10).tobytes()
    assert a.shape == (2, 4, 10)
    assert (a[:, 2:] == 0).all()      # positions 6,7 past the length
    live = a[:, :2]
    assert (live != 0).all() and (np.abs(live) < 1).all()
    # distinct over layers, tokens and channels, and between sequences
    assert len(np.unique(live)) == live.size
    other = expected_latent_slab(4, 1, 6, num_layers=2, page_tokens=4,
                                 latent_dim=10)
    assert not np.isin(live, other[:, :2]).any()
    q = latent_query(3, 6, 0, 4, 10)
    assert q.shape == (4, 10) and len(np.unique(q)) == q.size
    assert not np.array_equal(q, latent_query(3, 6, 1, 4, 10))
    assert not np.array_equal(q, latent_query(3, 7, 0, 4, 10))


def test_latent_attention_is_published_mla(tmp_path, monkeypatch):
    """The tier's latent output is MLA's attention (DeepSeek-V2/V3): with
    ``W_UK`` absorbed into each head's query and ``W_UV`` applied to what
    the tier returns, it equals per-head attention over expanded keys
    ``K_i = [W_UK_i c ; k_rope]`` and values ``V_i = W_UV_i c``, where
    ``[c ; k_rope]`` is each token's cached vector (seeded random weights,
    float32 at the highest precision, 8 heads, 32 + 8 latent channels,
    4-token pages)."""
    heads, lora, rope, nope, vdim, scale = 8, 32, 8, 16, 16, 0.2
    rng = np.random.default_rng(7)
    w_uk = rng.normal(size=(lora, heads, nope)) / np.sqrt(lora)
    w_uv = rng.normal(size=(lora, heads, vdim)) / np.sqrt(lora)
    q_nope = rng.normal(size=(heads, nope))
    q_rope = rng.normal(size=(heads, rope))
    # absorbed decode: each head's query against the cached vector is
    # [W_UK_i^T q_nope_i ; q_rope_i]
    q_lat = np.concatenate(
        [np.einsum("lhn,hn->hl", w_uk, q_nope), q_rope], -1)
    monkeypatch.setattr(ServingTier, "_query", lambda self, s, layer:
                        q_lat.astype(self.dtype))
    cluster = _cluster("inproc", tmp_path)
    tier = ServingTier(cluster, num_layers=2, page_tokens=4,
                       layout=LatentLayout(lora + rope, lora, heads, scale),
                       hbm_pages_per_node=16)
    try:
        tier.admit({5: 13})
        tier.decode([5], steps=2)
        n = tier.sessions[5].length
        for layer in range(2):
            o_lat = tier.attend([5], layer)[5]                 # [H, lora]
            got = np.einsum("hl,lhv->hv", o_lat.astype(np.float64), w_uv)
            pages = np.concatenate(tier.sequence_slabs(5), axis=1)[layer]
            cache = pages[:n].astype(np.float64)               # [T, C]
            c, k_rope = cache[:, :lora], cache[:, lora:]
            k = np.concatenate(
                [np.einsum("tl,lhn->thn", c, w_uk),
                 np.broadcast_to(k_rope[:, None], (n, heads, rope))], -1)
            v = np.einsum("tl,lhv->thv", c, w_uv)
            q = np.concatenate([q_nope, q_rope], -1)
            s = scale * np.einsum("hd,thd->ht", q, k)
            p = np.exp(s - s.max(-1, keepdims=True))
            want = np.einsum("ht,thv->hv", p / p.sum(-1, keepdims=True), v)
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    finally:
        tier.close()
    _teardown(cluster, "inproc")
