"""Paged KV cache — the buffer-pool abstraction applied to serving HBM.

Pangea's thesis is that one manager should own *all* memory. On the serving
path the contested memory is HBM holding KV pages. This module manages a
preallocated device page pool with the same locality-set machinery as the host
buffer pool:

* each sequence is a locality set of KV pages (write-back, random-read →
  LRU within the set, Table-3 spilling cost 5.0);
* Eq. 1 orders sequences for eviction: finished sequences (lifetime-ended)
  first, then cold sequences (stale ``t_r``), exactly the paper's dynamic
  priority;
* evicted pages are offloaded HBM→host as numpy slabs and restored on
  demand, in batches: the pages one eviction frees leave the pool in one
  gather and one device→host copy; the pages one restore brings back enter
  in one host→device copy and one scatter into the donated pool.

The host side is pluggable: ``HostSlabStore`` is the flat dict default, and
``runtime/serving.py`` substitutes a tiered store that charges the node's
``MemoryManager`` and overflows to a remote node (level-3 spill) through the
``TransferEngine`` — the three-level hierarchy HBM → host pool → remote node.

The device half (attention over the page pool) is ``kernels/paged_attention``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from .attributes import (AttributeSet, CurrentOperation, DurabilityType,
                         Lifetime, ReadingPattern, WritingPattern)
from .locality_set import LocalitySet, Page
from .paging import PagingSystem


def kv_attrs() -> AttributeSet:
    return AttributeSet(
        durability=DurabilityType.WRITE_BACK,
        writing=WritingPattern.RANDOM_MUTABLE_WRITE,
        reading=ReadingPattern.RANDOM_READ,
    )


class HBMExhaustedError(MemoryError):
    pass


@functools.lru_cache(maxsize=None)
def _slot_programs():
    """The jitted page movers, built on first use so that importing this
    module needs no JAX. ``gather_slots(kv, slots)`` copies the pages at
    ``slots`` out as ``[n, L, page, *token_shape]``; ``scatter_slots(kv,
    slots, batch)`` writes such a batch into the pool it consumes (donated,
    so the pool is updated in place and not copied whole)."""
    import jax
    import jax.numpy as jnp

    def gather_slots(kv, slots):
        return jnp.moveaxis(kv[:, slots], 1, 0)

    def scatter_slots(kv, slots, batch):
        return kv.at[:, slots].set(jnp.moveaxis(batch, 0, 1))

    return jax.jit(gather_slots), jax.jit(scatter_slots, donate_argnums=0)


class HostSlabStore:
    """Level-2 host store for offloaded KV page slabs.

    The default is a flat in-memory dict.  The interface is deliberately
    small so a tiered implementation (host pool with a budget that overflows
    to a remote node) can slot in without the cache knowing:

    * ``put(pid, slab)``   — offload accepted this slab (may raise to refuse);
    * ``take(pid)``        — remove + return the slab for restore (None if the
      page was never offloaded);
    * ``peek(pid)``        — read without removing (replication / asserts);
    * ``discard(pid)``     — the sequence finished; drop any copy.
    """

    def __init__(self) -> None:
        self._slabs: Dict[int, np.ndarray] = {}

    def put(self, page_id: int, slab: np.ndarray) -> None:
        self._slabs[page_id] = slab

    def take(self, page_id: int) -> Optional[np.ndarray]:
        return self._slabs.pop(page_id, None)

    def peek(self, page_id: int) -> Optional[np.ndarray]:
        return self._slabs.get(page_id)

    def discard(self, page_id: int) -> None:
        self._slabs.pop(page_id, None)

    def __contains__(self, page_id: int) -> bool:
        return page_id in self._slabs

    def __len__(self) -> int:
        return len(self._slabs)


@dataclass
class SeqState:
    seq_id: int
    length: int = 0                    # tokens written
    page_ids: List[int] = field(default_factory=list)  # logical pages, in order


class PagedKVCache:
    """Page-granular KV storage for the layers of one page layout.

    Physical layout (device): ``kv[L, P, page_size, *token_shape]`` where
    P = hbm_pages and ``token_shape`` is what one token of one layer stores:
    ``(2, kv_heads, head_dim)`` for a K and a V plane (the default, from
    ``kv_heads`` and ``head_dim``), or ``(latent_dim,)`` for a latent cache
    whose one vector per token every head reads (MLA). Slots, eviction,
    offload, restore and the host store move a page's slab
    ``[L, page_size, *token_shape]`` whole, whatever its shape. Logical
    pages beyond P live in the host store. ``block_table(seq)`` yields
    physical slots for the attention kernel. ``device`` is the chip that
    holds the pool (None: JAX's default device).
    """

    def __init__(self, num_layers: int, hbm_pages: int, page_size: int,
                 kv_heads: Optional[int] = None,
                 head_dim: Optional[int] = None, dtype=np.float32,
                 host_store: Optional[HostSlabStore] = None, device=None, *,
                 token_shape: Optional[Tuple[int, ...]] = None):
        import jax.numpy as jnp  # local import: keep module importable w/o jax
        self.num_layers = num_layers
        self.hbm_pages = hbm_pages
        self.page_size = page_size
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        self.token_shape = (tuple(token_shape) if token_shape is not None
                            else (2, kv_heads, head_dim))
        self.dtype = dtype
        self.device = device
        self.kv = jnp.zeros((num_layers, hbm_pages, page_size)
                            + self.token_shape, dtype=dtype, device=device)
        self._free_slots: List[int] = list(range(hbm_pages))[::-1]
        self.paging = PagingSystem()
        self.clock = 1
        self._seqs: Dict[int, SeqState] = {}
        self._sets: Dict[int, LocalitySet] = {}
        # logical page id -> (physical slot | None, host copy | None)
        self._pages: Dict[int, Page] = {}
        self.host_store = host_store if host_store is not None else HostSlabStore()
        self._next_page_id = 0
        # offloads and fetches count pages; the *_batches count the gathers
        # and scatters that moved them
        self.stats = {"offloads": 0, "fetches": 0, "offload_bytes": 0,
                      "offload_batches": 0, "restore_batches": 0}

    @property
    def slab_nbytes(self) -> int:
        """Bytes of one logical page's slab across all layers."""
        return (self.num_layers * self.page_size
                * int(np.prod(self.token_shape))
                * np.dtype(self.dtype).itemsize)

    # -- sequence lifecycle -----------------------------------------------------
    def start_sequence(self, seq_id: int) -> SeqState:
        if seq_id in self._seqs:
            raise ValueError(f"sequence {seq_id} already active")
        st = SeqState(seq_id)
        ls = LocalitySet(f"seq{seq_id}", self.page_size, kv_attrs())
        self.clock += 1
        self.paging.register(ls, self.clock)
        ls.set_operation(CurrentOperation.READ_AND_WRITE, self.clock)
        self._seqs[seq_id] = st
        self._sets[seq_id] = ls
        return st

    def finish_sequence(self, seq_id: int) -> None:
        """Lifetime over: its pages become the preferred eviction victims and
        are reclaimed eagerly (paper §3.1 "evicted as soon as lifetime
        expires")."""
        st = self._seqs.pop(seq_id)
        ls = self._sets.pop(seq_id)
        self.clock += 1
        ls.end_lifetime(self.clock)
        for pid in st.page_ids:
            page = self._pages.pop(pid)
            if page.offset is not None:
                self._free_slots.append(page.offset)
            self.host_store.discard(pid)
        self.paging.unregister(ls.name)

    # -- page management ----------------------------------------------------------
    def _padded(self, slots: List[int]) -> np.ndarray:
        """``slots`` padded with its last slot to the next power of two, or
        to the pool's page count if that is smaller: a process compiles at
        most one gather and one scatter per such size."""
        size = min(1 << (len(slots) - 1).bit_length(), self.hbm_pages)
        return np.asarray(slots + slots[-1:] * (size - len(slots)), np.int32)

    def _evict(self, n: int, exclude_set: Optional[str] = None) -> None:
        """Free at least ``n`` slots: detach Eq.-1 victims pick by pick
        (bookkeeping only; a detached page is no longer resident, so no
        later pick returns it), then offload them all in one batch. The set
        named ``exclude_set`` gives no victims."""
        victims: List[Tuple[Page, int]] = []
        while len(victims) < n:
            picked = self.paging.pick_victims(self.clock, exclude=exclude_set)
            if picked is None:
                for page, slot in victims:
                    page.offset = slot
                raise HBMExhaustedError(
                    "all KV pages pinned (every sequence active)")
            for page in picked[1]:
                victims.append((page, page.offset))
                page.offset = None
        self._offload(victims)

    def _offload(self, victims: List[Tuple[Page, int]]) -> None:
        """Copy the victims' slabs to the host store: one gather of their
        slots, one device→host copy, one slab a page. A page whose slab the
        store did not take keeps its slot, so a refusing store loses
        nothing."""
        import jax
        gather, _ = _slot_programs()
        slots = [slot for _page, slot in victims]
        done = 0
        with jax.profiler.TraceAnnotation("kvcache.offload",
                                          pages=len(victims)):
            try:
                rows = np.asarray(gather(self.kv, self._padded(slots)))
                for page, _slot in victims:
                    # a copy of its row: the slab owns exactly its bytes
                    self.host_store.put(page.page_id, rows[done].copy())
                    done += 1
            finally:
                for page, slot in victims[done:]:
                    page.offset = slot
                self._free_slots.extend(slots[:done])
                self.stats["offloads"] += done
                self.stats["offload_bytes"] += done * self.slab_nbytes
                self.stats["offload_batches"] += 1

    def _restore(self, seq_id: int, pages: List[Page],
                 exclude_set: Optional[str] = None) -> None:
        """Bring offloaded ``pages`` back into HBM: slots for all of them in
        one allocation, then one host→device copy and one scatter of the
        slabs the host store held. If a ``take`` raises, the slabs already
        taken are written and the slots not used go back before it
        propagates."""
        import jax
        with jax.profiler.TraceAnnotation("kvcache.restore", seq=seq_id,
                                          pages=len(pages)):
            slots = self._alloc_slots(len(pages), exclude_set)
            taken: List[Tuple[Page, int, Optional[np.ndarray]]] = []
            try:
                for page, slot in zip(pages, slots):
                    slab = self.host_store.take(page.page_id)
                    taken.append((page, slot, slab))
            finally:
                self._free_slots.extend(slots[len(taken):])
                found = [(slot, slab) for _p, slot, slab in taken
                         if slab is not None]
                if found:
                    self._write_slots(seq_id, [f[0] for f in found],
                                      [f[1] for f in found])
                for page, slot, _slab in taken:
                    page.offset = slot
                self.stats["fetches"] += len(found)
                self.stats["restore_batches"] += 1

    def _write_slots(self, seq_id: int, slots: List[int],
                     slabs: List[np.ndarray]) -> None:
        """Write ``slabs`` into ``slots``: one host→device copy and one
        scatter that consumes the pool. Padding repeats the last slot with
        its own slab."""
        import jax
        _, scatter = _slot_programs()
        idx = self._padded(slots)
        with jax.profiler.TraceAnnotation("kvcache.pool_write", seq=seq_id):
            batch = np.stack(slabs + slabs[-1:] * (len(idx) - len(slabs)))
            self.kv = scatter(self.kv, idx,
                              jax.device_put(batch, self.device))

    def _write_slot(self, seq_id: int, slot: int, slab: np.ndarray) -> None:
        import jax
        with jax.profiler.TraceAnnotation("kvcache.pool_write", seq=seq_id):
            self.kv = self.kv.at[:, slot].set(
                jax.device_put(slab, self.device))

    def _alloc_slots(self, n: int,
                     exclude_set: Optional[str] = None) -> List[int]:
        """``n`` free slots, evicting in one batch what the free list lacks;
        no page of the set named ``exclude_set`` is evicted for them."""
        if len(self._free_slots) < n:
            self._evict(n - len(self._free_slots), exclude_set)
        return [self._free_slots.pop() for _ in range(n)]

    def append_page(self, seq_id: int) -> Page:
        """Allocate the next logical page for a sequence."""
        st = self._seqs[seq_id]
        ls = self._sets[seq_id]
        self.clock += 1
        slot = self._alloc_slots(1)[0]
        page = Page(page_id=self._next_page_id, set_name=ls.name,
                    size=self.page_size, offset=slot, pin_count=0, dirty=True,
                    last_access=self.clock)
        self._next_page_id += 1
        ls.pages[page.page_id] = page
        self._pages[page.page_id] = page
        st.page_ids.append(page.page_id)
        return page

    def ensure_capacity(self, seq_id: int, new_tokens: int = 1) -> None:
        st = self._seqs[seq_id]
        needed_pages = -(-(st.length + new_tokens) // self.page_size)
        while len(st.page_ids) < needed_pages:
            self.append_page(seq_id)

    def block_table(self, seq_id: int, max_pages: int) -> np.ndarray:
        """Physical slots for the attention kernel; restores any offloaded
        page of this sequence (decode reads the whole sequence)."""
        st = self._seqs[seq_id]
        ls = self._sets[seq_id]
        self.clock += 1
        ls.set_operation(CurrentOperation.READ_AND_WRITE, self.clock)
        table = np.full(max_pages, -1, dtype=np.int32)
        pages = [self._pages[pid] for pid in st.page_ids[:max_pages]]
        offloaded = [p for p in pages if p.offset is None]
        if offloaded and len(pages) <= self.hbm_pages:
            # one batch, whose slots never come from the pages listed here
            self._restore(seq_id, offloaded, exclude_set=ls.name)
        for i, page in enumerate(pages):
            if page.offset is None:
                # more pages than the pool: no table holds them all at once;
                # each comes back in turn and may push out earlier ones
                self._restore(seq_id, [page])
            page.last_access = self.clock
            table[i] = page.offset
        return table

    def advance(self, seq_id: int, tokens: int = 1) -> None:
        self._seqs[seq_id].length += tokens

    # -- byte-exact page access ---------------------------------------------------
    def write_page(self, seq_id: int, page_index: int, slab: np.ndarray) -> None:
        """Overwrite one logical page's slab ``[L, page, *token_shape]``;
        restores the page to HBM first if it was offloaded."""
        st = self._seqs[seq_id]
        page = self._pages[st.page_ids[page_index]]
        self.clock += 1
        if page.offset is None:
            # no block table is held here, so any page, the sequence's own
            # included, may give up its slot (a prefill larger than the pool)
            self._restore(seq_id, [page])
        page.last_access = self.clock
        page.dirty = True
        self._write_slot(seq_id, page.offset, slab)

    def read_page(self, seq_id: int, page_index: int) -> np.ndarray:
        """Byte-exact slab of one logical page, wherever it lives: resident
        pages read from HBM, offloaded ones from the host store (without
        pulling them back in)."""
        st = self._seqs[seq_id]
        page = self._pages[st.page_ids[page_index]]
        if page.offset is not None:
            return np.asarray(self.kv[:, page.offset])
        slab = self.host_store.peek(page.page_id)
        if slab is None:   # offloaded before any write: an all-zero page
            shape = (self.num_layers, self.page_size) + self.token_shape
            return np.zeros(shape, dtype=self.dtype)
        return np.asarray(slab)

    def sequence_slabs(self, seq_id: int) -> List[np.ndarray]:
        """All of a sequence's page slabs in logical order (byte-identity
        checks and replication)."""
        return [self.read_page(seq_id, i)
                for i in range(len(self._seqs[seq_id].page_ids))]

    def seq_length(self, seq_id: int) -> int:
        return self._seqs[seq_id].length

    def num_pages(self, seq_id: int) -> int:
        return len(self._seqs[seq_id].page_ids)

    # -- introspection --------------------------------------------------------------
    def resident_pages(self) -> int:
        return self.hbm_pages - len(self._free_slots)

    def active_sequences(self) -> List[int]:
        return list(self._seqs)
