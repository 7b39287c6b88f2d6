"""Paged KV cache: Eq.-1-driven HBM residency (core/kvcache.py)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HBMExhaustedError, HostSlabStore, PagedKVCache
from repro.kernels.paged_attention.ops import paged_attention


def _cache(hbm_pages=8, page=4):
    return PagedKVCache(num_layers=2, hbm_pages=hbm_pages, page_size=page,
                        kv_heads=2, head_dim=4)


def test_offload_and_restore_preserves_data():
    kv = _cache(hbm_pages=4)
    kv.start_sequence(0)
    kv.ensure_capacity(0, 8)   # 2 pages
    kv.advance(0, 8)
    bt = kv.block_table(0, 4)
    # write recognizable data into seq 0's pages
    kv.kv = kv.kv.at[:, bt[0]].set(1.25)
    kv.kv = kv.kv.at[:, bt[1]].set(2.5)
    # second sequence forces offload of seq 0 (cold)
    kv.start_sequence(1)
    kv.ensure_capacity(1, 12)  # 3 pages > 2 free
    kv.advance(1, 12)
    assert kv.stats["offloads"] > 0
    bt0 = kv.block_table(0, 4)   # restores offloaded pages
    assert kv.stats["fetches"] > 0
    slab0 = np.asarray(kv.kv[:, bt0[0]])
    slab1 = np.asarray(kv.kv[:, bt0[1]])
    assert np.allclose(slab0, 1.25) and np.allclose(slab1, 2.5)


def test_finished_sequences_free_pages():
    kv = _cache(hbm_pages=4)
    for s in (0, 1):
        kv.start_sequence(s)
        kv.ensure_capacity(s, 8)
        kv.advance(s, 8)
    assert kv.resident_pages() == 4
    kv.finish_sequence(0)
    assert kv.resident_pages() == 2
    kv.start_sequence(2)
    kv.ensure_capacity(2, 8)   # reuses freed pages, no offload needed
    assert kv.stats["offloads"] == 0


def test_cold_sequence_evicted_before_hot():
    kv = _cache(hbm_pages=4)
    kv.start_sequence(0)
    kv.ensure_capacity(0, 8)
    kv.advance(0, 8)
    kv.start_sequence(1)
    kv.ensure_capacity(1, 8)
    kv.advance(1, 8)
    # touch seq 1 (hot); seq 0 goes cold
    kv.block_table(1, 2)
    kv.start_sequence(2)
    kv.ensure_capacity(2, 4)   # needs 1 page -> evict from seq 0
    seq0_resident = sum(kv._pages[p].offset is not None
                        for p in kv._seqs[0].page_ids)
    seq1_resident = sum(kv._pages[p].offset is not None
                        for p in kv._seqs[1].page_ids)
    assert seq1_resident == 2
    assert seq0_resident < 2


def test_exhaustion_raises():
    kv = _cache(hbm_pages=2)
    kv.start_sequence(0)
    kv.ensure_capacity(0, 8)
    kv.advance(0, 8)
    kv.block_table(0, 2)
    # all pages belong to the single active sequence; each new page triggers
    # eviction of this sequence's own older pages (random pattern, LRU) —
    # allowed; but pinning everything via an impossible block table is not.
    kv.start_sequence(1)
    kv.ensure_capacity(1, 4)
    assert kv.stats["offloads"] > 0


# -- ragged batches through the attention kernel ------------------------------
def _seed_sequence(kv, seq, tokens, rng):
    kv.start_sequence(seq)
    kv.ensure_capacity(seq, tokens)
    kv.advance(seq, tokens)
    for k in range(kv.num_pages(seq)):
        slab = rng.standard_normal(
            (kv.num_layers, kv.page_size, 2, kv.kv_heads, kv.head_dim))
        kv.write_page(seq, k, slab.astype(np.float32))


def _attend_both(kv, seqs, layer=0):
    """Run kernel and xla reference over the live pool; they must agree."""
    rng = np.random.default_rng(7)
    max_pages = max(kv.num_pages(s) for s in seqs)
    # block_table first: it restores offloaded pages (mutates kv.kv)
    tables = np.stack([kv.block_table(s, max_pages) for s in seqs])
    lengths = np.asarray([kv.seq_length(s) for s in seqs], dtype=np.int32)
    q = rng.standard_normal(
        (len(seqs), kv.kv_heads, kv.head_dim)).astype(np.float32)
    pages = kv.kv[layer]
    ref = paged_attention(q, pages, tables, lengths, impl="xla")
    ker = paged_attention(q, pages, tables, lengths, impl="kernel")
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    return np.asarray(ker)


def test_attention_ragged_partial_last_pages():
    """Lengths 7/5/3 over page size 4: every sequence ends mid-page."""
    kv = _cache(hbm_pages=16)
    rng = np.random.default_rng(0)
    for seq, tokens in enumerate((7, 5, 3)):
        _seed_sequence(kv, seq, tokens, rng)
    out = _attend_both(kv, [0, 1, 2])
    assert np.isfinite(out).all()


def test_attention_length_one_sequence():
    """A single-token sequence batched with a longer one: attention over
    one key is just that key's value vector (softmax of a single logit)."""
    kv = _cache(hbm_pages=16)
    rng = np.random.default_rng(1)
    _seed_sequence(kv, 0, 1, rng)
    _seed_sequence(kv, 1, 9, rng)
    out = _attend_both(kv, [0, 1])
    slot = kv.block_table(0, 1)[0]
    v0 = np.asarray(kv.kv[0, slot, 0, 1])   # layer 0, token 0, V half
    np.testing.assert_allclose(out[0], v0, rtol=2e-5, atol=2e-5)


def test_attention_noncontiguous_pages_after_evict_restore():
    """Eviction + restore hands back arbitrary free slots, so a sequence's
    block table is no longer contiguous; the kernel must follow it and the
    restored contents must match what was written pre-eviction."""
    kv = _cache(hbm_pages=6)
    rng = np.random.default_rng(2)
    _seed_sequence(kv, 0, 12, rng)                    # 3 pages
    before = [kv.read_page(0, k).copy() for k in range(3)]
    _seed_sequence(kv, 2, 12, rng)                    # fills the pool
    _seed_sequence(kv, 1, 12, rng)                    # evicts cold seq 0
    assert kv.stats["offloads"] > 0
    kv.finish_sequence(2)                             # free slots to restore into
    out = _attend_both(kv, [0, 1])                    # restores seq 0
    assert kv.stats["fetches"] > 0
    for k in range(3):
        assert kv.read_page(0, k).tobytes() == before[k].tobytes()
    assert np.isfinite(out).all()


# -- batched page movement: one gather per eviction, one scatter per restore --
TOKEN_SHAPES = {"kv": (2, 2, 4), "latent": (576,)}


class _FlakyStore(HostSlabStore):
    """Raises on its ``fail_at``-th ``take`` (a dead level-3 holder)."""

    def __init__(self):
        super().__init__()
        self.fail_at = None
        self.takes = 0

    def take(self, page_id):
        self.takes += 1
        if self.takes == self.fail_at:
            raise RuntimeError("holder died mid-restore")
        return super().take(page_id)


def _batch_cache(token_shape, store=None):
    return PagedKVCache(num_layers=2, hbm_pages=8, page_size=4,
                        token_shape=token_shape, host_store=store)


def _fill(kv, seq, pages, rng, written):
    kv.start_sequence(seq)
    kv.ensure_capacity(seq, pages * kv.page_size)
    kv.advance(seq, pages * kv.page_size)
    for k in range(pages):
        slab = rng.standard_normal(
            (kv.num_layers, kv.page_size) + kv.token_shape).astype(np.float32)
        kv.write_page(seq, k, slab)
        written[seq, k] = slab


def _assert_intact(kv, written):
    """Every page reads back its bytes, and the free list and the resident
    pages share the pool's slots between them, each slot once."""
    for (seq, k), slab in written.items():
        assert kv.read_page(seq, k).tobytes() == slab.tobytes(), (seq, k)
    held = [p.offset for p in kv._pages.values() if p.offset is not None]
    slots = held + kv._free_slots
    assert sorted(slots) == list(range(kv.hbm_pages))


def _offloaded_cold_session(token_shape, store=None):
    """Seq 0's five pages are all offloaded, one at a time, by seq 2; seq 1
    (hot) and seq 2 hold the pool."""
    kv = _batch_cache(token_shape, store)
    rng, written = np.random.default_rng(3), {}
    _fill(kv, 0, 5, rng, written)
    _fill(kv, 1, 3, rng, written)
    kv.block_table(1, 3)
    _fill(kv, 2, 5, rng, written)
    assert all(kv._pages[p].offset is None for p in kv._seqs[0].page_ids)
    return kv, written


@pytest.mark.parametrize("shape", sorted(TOKEN_SHAPES))
def test_batched_round_trip_is_byte_identical_in_scattered_slots(shape):
    """Five pages (no pad size) come back in one batch into slots freed by
    one batched eviction of two other sessions' pages."""
    kv, written = _offloaded_cold_session(TOKEN_SHAPES[shape])
    offloads = kv.stats["offload_batches"]
    table = kv.block_table(0, 5)
    assert kv.stats["offload_batches"] == offloads + 1
    assert len(set(table.tolist())) == 5
    assert not (np.diff(np.sort(table)) == 1).all()     # not contiguous
    for k, slot in enumerate(table):
        assert np.asarray(kv.kv[:, slot]).tobytes() == written[0, k].tobytes()
    _assert_intact(kv, written)


@pytest.mark.parametrize("shape", sorted(TOKEN_SHAPES))
def test_one_resume_is_one_restore_batch(shape):
    kv, _written = _offloaded_cold_session(TOKEN_SHAPES[shape])
    before = dict(kv.stats)
    kv.block_table(0, 5)
    assert kv.stats["restore_batches"] == before["restore_batches"] + 1
    assert kv.stats["fetches"] == before["fetches"] + 5
    assert kv.stats["offloads"] == before["offloads"] + 5


@pytest.mark.parametrize("shape", sorted(TOKEN_SHAPES))
def test_resuming_sessions_own_pages_are_never_victims(shape):
    """Under global LRU the resuming session's resident pages are the
    oldest in the pool; its restore still evicts only other sessions'."""
    kv = _batch_cache(TOKEN_SHAPES[shape])
    kv.paging.policy = "lru"
    rng, written = np.random.default_rng(4), {}
    _fill(kv, 0, 6, rng, written)
    _fill(kv, 1, 2, rng, written)
    _fill(kv, 2, 3, rng, written)            # pushes out 3 of seq 0's pages
    pages = [kv._pages[p] for p in kv._seqs[0].page_ids]
    resident = {p.page_id: p.offset for p in pages if p.offset is not None}
    assert len(resident) == 3
    offloads = kv.stats["offloads"]
    table = kv.block_table(0, 6)
    assert kv.stats["offloads"] == offloads + 3
    assert {p.page_id: p.offset for p in pages
            if p.page_id in resident} == resident
    assert len(set(table.tolist())) == 6
    _assert_intact(kv, written)


@pytest.mark.parametrize("fail_at", [1, 3, 5])
@pytest.mark.parametrize("shape", sorted(TOKEN_SHAPES))
def test_take_that_raises_mid_batch_loses_nothing(shape, fail_at):
    """The slabs taken before the failure are written, the slots not used
    go back, and a retry restores the rest."""
    store = _FlakyStore()
    kv, written = _offloaded_cold_session(TOKEN_SHAPES[shape], store)
    store.fail_at = store.takes + fail_at
    with pytest.raises(RuntimeError, match="mid-restore"):
        kv.block_table(0, 5)
    back = [kv._pages[p].offset is not None for p in kv._seqs[0].page_ids]
    assert sum(back) == fail_at - 1
    _assert_intact(kv, written)
    table = kv.block_table(0, 5)
    assert len(set(table.tolist())) == 5
    _assert_intact(kv, written)
