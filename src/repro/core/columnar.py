"""Columnar page layout + fused partition/aggregate kernels (arrow-ish).

The row scheme stores ``[count:int64][record bytes...]`` — every hot path
then loops over record *rows*, so shuffle/aggregate/join throughput is bound
by the Python interpreter, not memory bandwidth. This module adds the second
``StorageScheme`` the paper's locality sets can select (Shark's in-memory
columnar store is the precedent — PAPERS.md): each page holds one **column
block**::

    [count:int64][pad][validity bitmap][field0 cap*w0][field1 cap*w1]...

* ``count`` — records in this block (<= the layout's fixed capacity).
* validity bitmap — one bit per slot (LSB-first within each byte); all
  current producers write fully valid blocks, but the format carries the
  bitmap so nullable columns slot in without a layout change.
* field regions — one contiguous fixed-width array per record field, each
  sized for the block's full capacity and 8-byte aligned, so a column can be
  viewed as its numpy dtype with zero copies.

Because capacity (and so every region offset) is a pure function of
``(dtype, page_size)``, blocks are self-describing given the set's dtype —
the spill store and the durable page log persist them as the same opaque page
images as row pages (layout-oblivious durability).

:func:`fused_partition_crc` does reducer-hash -> dispatch plan -> per-column
gather -> per-partition incremental CRC32 in one vectorized pass per block,
materializing the routed block. No production path calls it: the columnar
map lands rows straight into their pages (``ColumnarShuffleService.
add_gathered``), and the tests hold that landing to this reference.

Checksum compatibility: :func:`columnar_content_checksum` computes the exact
``replication.record_content_checksum`` value from column arrays without
materializing rows — per-record multipliers are sliced per field at the
field's byte offset, and the mod-2**64 wraparound arithmetic commutes over
the per-field partial sums — so row-oriented and columnar shards verify
against each other byte-for-byte.
"""
from __future__ import annotations

import zlib
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .attributes import AttributeSet, CurrentOperation, StorageScheme
from .buffer_pool import BufferPool
from .locality_set import LocalitySet, Page
from .replication import _CONTENT_MIX, _CONTENT_MULT

_HEADER = 8  # int64 record count at block start (same as row pages)

# reducer-routing hash constants — MUST match ClusterShuffle.partition_of_keys
_ROUTE_MULT = np.uint64(0xC2B2AE3D27D4EB4F)


def _pad8(n: int) -> int:
    return (n + 7) & ~7


class ColumnLayout:
    """Region offsets of one column block for ``(dtype, page_size)``.

    Solved once and cached: capacity is the largest ``n`` such that header +
    padded validity bitmap + padded per-field regions fit the page.
    """

    _cache: Dict[Tuple[np.dtype, int], "ColumnLayout"] = {}

    def __init__(self, dtype: np.dtype, page_size: int):
        dtype = np.dtype(dtype)
        self.dtype = dtype
        self.page_size = page_size
        self.fields = _field_layout(dtype)
        width = sum(w for _, _, _, w in self.fields)
        if width != dtype.itemsize:
            raise ValueError(
                f"columnar layout needs a packed dtype: fields cover {width} "
                f"bytes but itemsize is {dtype.itemsize}")
        # estimate then shrink past padding: per record cost w + 1/8 bit
        cap = ((page_size - _HEADER) * 8) // (8 * width + 1)
        while cap > 0 and self._block_bytes(cap) > page_size:
            cap -= 1
        if cap < 1:
            raise ValueError("page too small for one columnar record")
        self.capacity = cap
        self.validity_off = _HEADER
        self.validity_bytes = (cap + 7) // 8
        off = _pad8(self.validity_off + self.validity_bytes)
        self.field_offs: Dict[str, int] = {}
        for name, _, _, w in self.fields:
            self.field_offs[name] = off
            off = _pad8(off + cap * w)
        self.block_bytes = off

    def _block_bytes(self, cap: int) -> int:
        off = _pad8(_HEADER + (cap + 7) // 8)
        for _, _, _, w in self.fields:
            off = _pad8(off + cap * w)
        return off

    @classmethod
    def for_page(cls, dtype: np.dtype, page_size: int) -> "ColumnLayout":
        key = (np.dtype(dtype), page_size)
        layout = cls._cache.get(key)
        if layout is None:
            layout = cls._cache[key] = cls(key[0], page_size)
        return layout


_FIELD_LAYOUT_CACHE: Dict[np.dtype, List[Tuple[str, np.dtype, int, int]]] = {}


def _field_layout(dtype: np.dtype) -> List[Tuple[str, np.dtype, int, int]]:
    """``(name, field_dtype, byte_offset_in_record, itemsize)`` per field, in
    record byte order (the order the checksum multipliers walk). Cached per
    dtype — this sits under every per-block hot-path call."""
    dtype = np.dtype(dtype)
    out = _FIELD_LAYOUT_CACHE.get(dtype)
    if out is not None:
        return out
    if dtype.names is None:
        # plain/subarray dtype: treat as a single anonymous column
        out = [("", dtype, 0, dtype.itemsize)]
    else:
        out = []
        for name in dtype.names:
            fdt, off = dtype.fields[name][:2]
            out.append((name, fdt, off, fdt.itemsize))
        out.sort(key=lambda t: t[2])
    _FIELD_LAYOUT_CACHE[dtype] = out
    return out


def _col_view(col: np.ndarray) -> np.ndarray:
    """Flat uint8 view of a column chunk (scalar or subarray field)."""
    return np.ascontiguousarray(col).view(np.uint8).reshape(-1)


def records_to_columns(records: np.ndarray) -> Dict[str, np.ndarray]:
    """Structured record array -> per-field contiguous column arrays."""
    if records.dtype.names is None:
        return {"": np.ascontiguousarray(records)}
    return {name: np.ascontiguousarray(records[name])
            for name in records.dtype.names}


def columns_to_records(columns: Dict[str, np.ndarray], dtype: np.dtype,
                       n: Optional[int] = None) -> np.ndarray:
    """Per-field columns -> structured record array (row materialization)."""
    dtype = np.dtype(dtype)
    if dtype.names is None:
        col = columns[""]
        return np.ascontiguousarray(col[:n] if n is not None else col)
    if n is None:
        n = len(next(iter(columns.values())))
    out = np.empty(n, dtype)
    for name in dtype.names:
        out[name] = columns[name][:n]
    return out


def concat_columns(chunks: Sequence[Dict[str, np.ndarray]],
                   dtype: np.dtype) -> Tuple[Dict[str, np.ndarray], int]:
    """Concatenate column-chunk dicts field-wise -> ``(columns, n)``."""
    names = [name for name, _, _, _ in _field_layout(dtype)]
    if not chunks:
        empty = columns_of_empty(dtype)
        return empty, 0
    cols = {name: np.concatenate([c[name] for c in chunks])
            for name in names}
    return cols, len(cols[names[0]])


def columns_of_empty(dtype: np.dtype) -> Dict[str, np.ndarray]:
    empty = np.empty(0, np.dtype(dtype))
    return records_to_columns(empty)


# ---------------------------------------------------------------------------
# Block codec: encode/decode one page's column block
# ---------------------------------------------------------------------------
def write_block(view: np.ndarray, layout: ColumnLayout,
                columns: Dict[str, np.ndarray], n: int) -> None:
    """Encode ``n`` records of ``columns`` into a page view (full rewrite)."""
    view[:_HEADER].view(np.int64)[0] = n
    validity = view[layout.validity_off:layout.validity_off
                    + layout.validity_bytes]
    full, rem = divmod(n, 8)
    validity[:full] = 0xFF
    if rem:
        validity[full] = (1 << rem) - 1
    if full + (1 if rem else 0) < layout.validity_bytes:
        validity[full + (1 if rem else 0):] = 0
    for name, _, _, w in layout.fields:
        off = layout.field_offs[name]
        view[off:off + n * w] = _col_view(columns[name][:n])


def append_block(view: np.ndarray, layout: ColumnLayout, count: int,
                 columns: Dict[str, np.ndarray], i: int, take: int) -> int:
    """Append ``columns[i:i+take]`` after ``count`` existing records; returns
    the new count. Used by writers filling a block across batches."""
    new = count + take
    view[:_HEADER].view(np.int64)[0] = new
    validity = view[layout.validity_off:layout.validity_off
                    + layout.validity_bytes]
    full, rem = divmod(new, 8)
    pfull = count // 8
    validity[pfull:full] = 0xFF
    if rem:
        validity[full] = (1 << rem) - 1
    for name, _, _, w in layout.fields:
        off = layout.field_offs[name]
        view[off + count * w:off + new * w] = _col_view(columns[name][i:i + take])
    return new


def read_block(view: np.ndarray, layout: ColumnLayout
               ) -> Tuple[Dict[str, np.ndarray], int]:
    """Decode a page view into zero-copy column views + record count."""
    n = int(view[:_HEADER].view(np.int64)[0])
    cols: Dict[str, np.ndarray] = {}
    for name, fdt, _, w in layout.fields:
        off = layout.field_offs[name]
        raw = view[off:off + n * w]
        if fdt.subdtype is not None:
            base, shape = fdt.subdtype
            cols[name] = raw.view(base).reshape((n, *shape))
        else:
            cols[name] = raw.view(fdt)
    return cols, n


def block_validity(view: np.ndarray, layout: ColumnLayout) -> np.ndarray:
    """The raw validity bitmap bytes of a block (LSB-first bit per slot)."""
    return view[layout.validity_off:layout.validity_off
                + layout.validity_bytes]


# ---------------------------------------------------------------------------
# Columnar sequential write/read service
# ---------------------------------------------------------------------------
class ColumnarWriter:
    """Columnar twin of ``services.SequentialWriter``: append fixed-dtype
    records (or pre-split columns) block by block. Accepting columns directly
    lets the fused shuffle path route gathered column slices into pages
    without ever materializing rows."""

    def __init__(self, pool: BufferPool, ls: LocalitySet, dtype: np.dtype):
        self.pool = pool
        self.ls = ls
        self.dtype = np.dtype(dtype)
        self.layout = ColumnLayout.for_page(self.dtype, ls.page_size)
        self.per_page = self.layout.capacity
        self._page: Optional[Page] = None
        self._view: Optional[np.ndarray] = None
        self._count = 0
        # flattened (name, region offset, itemsize, base dtype, row shape)
        # per field — the gather path runs per map block, so the dict/attr
        # lookups are hoisted out of it once here
        self._gfields = []
        for name, fdt, _, w in self.layout.fields:
            if fdt.subdtype is not None:
                base, shape = fdt.subdtype
            else:
                base, shape = fdt, None
            self._gfields.append(
                (name, self.layout.field_offs[name], w, base, shape))
        ls.infer_from_service("sequential-write", pool.clock)

    def _open_page(self) -> None:
        self._page = self.pool.new_page(self.ls)
        self._count = 0
        # the page stays pinned until _close_page, so its view is stable:
        # cache it instead of re-resolving per append
        self._view = self.pool.view(self._page)
        self._view[:_HEADER].view(np.int64)[0] = 0
        block_validity(self._view, self.layout)[:] = 0

    def _close_page(self) -> None:
        if self._page is None:
            return
        # header count + validity are written once here, not per append —
        # the page is pinned (unspillable, not written through) until this
        # unpin, so no reader or durability path sees the stale header
        view = self._view
        view[:_HEADER].view(np.int64)[0] = self._count
        validity = block_validity(view, self.layout)
        full, rem = divmod(self._count, 8)
        validity[:full] = 0xFF
        if rem:
            validity[full] = (1 << rem) - 1
        self.pool.unpin(self._page, dirty=True)
        self._page = None
        self._view = None

    def append_flat(self, flats: Dict[str, np.ndarray], n: int,
                    start: int = 0) -> None:
        """Append ``n`` records starting at record ``start`` from flat uint8
        per-field views (``_col_view`` of each full column). The bulk landing
        path computes the flat views once per routed page and calls this per
        partition — each append is then one slice assignment per field."""
        i = start
        stop = start + n
        layout = self.layout
        offs = layout.field_offs
        while i < stop:
            if self._page is None:
                self._open_page()
            count = self._count
            take = min(self.per_page - count, stop - i)
            new = count + take
            view = self._view
            for name, _, _, w in layout.fields:
                off = offs[name]
                view[off + count * w:off + new * w] = \
                    flats[name][i * w:(i + take) * w]
            self._count = new
            i += take
            if new == self.per_page:
                self._close_page()

    def append_columns(self, columns: Dict[str, np.ndarray], n: int,
                       start: int = 0) -> None:
        self.append_flat(
            {name: _col_view(columns[name]) for name, _, _, _
             in self.layout.fields}, n, start=start)

    def gather_append(self, columns: Dict[str, np.ndarray],
                      order: np.ndarray, lo: int, hi: int,
                      crcs: Optional[List[int]] = None) -> List[int]:
        """Land ``columns[order[lo:hi]]`` straight into this writer's pages:
        ``np.take`` gathers each field directly into the open page's column
        region (no routed intermediate array anywhere), and the per-field
        CRC32 chains (:func:`columns_crc32` contract) run over the landed
        bytes. This is the shuffle map's zero-copy landing — one gather +
        one CRC pass per field per page, nothing else touches the data."""
        gfields = self._gfields
        if crcs is None:
            crcs = [0] * len(gfields)
        i = lo
        while i < hi:
            if self._page is None:
                self._open_page()
            count = self._count
            take = min(self.per_page - count, hi - i)
            new = count + take
            view = self._view
            idx = order[i:i + take]
            fi = 0
            for name, off, w, base, shape in gfields:
                region = view[off + count * w:off + new * w]
                if shape is not None:
                    dst = region.view(base).reshape((take, *shape))
                else:
                    dst = region.view(base)
                # mode="clip" skips numpy's exception-safe temp+copy path
                # for out= (indices come from argsort — never out of range)
                np.take(columns[name], idx, axis=0, out=dst, mode="clip")
                crcs[fi] = zlib.crc32(region.data, crcs[fi])
                fi += 1
            self._count = new
            i += take
            if new == self.per_page:
                self._close_page()
        return crcs

    def append_batch(self, records: np.ndarray) -> None:
        if len(records) == 0:
            return
        self.append_columns(records_to_columns(records), len(records))

    def close(self) -> None:
        self._close_page()
        self.ls.set_operation(CurrentOperation.IDLE, self.pool.clock)


def iter_column_blocks(pool: BufferPool, ls: LocalitySet, dtype: np.dtype
                       ) -> Iterator[Tuple[Dict[str, np.ndarray], int]]:
    """Stream a columnar set's blocks as zero-copy ``(columns, n)`` views —
    valid only until the next iteration (the page is unpinned); copy to
    retain. Pinning each page faults spilled/logged blocks back in."""
    layout = ColumnLayout.for_page(np.dtype(dtype), ls.page_size)
    ls.infer_from_service("sequential-read", pool.clock)
    for pid in sorted(ls.pages):
        page = ls.pages[pid]
        view = pool.pin(page)
        try:
            cols, n = read_block(view, layout)
            if n:
                yield cols, n
        finally:
            pool.unpin(page)


def set_column_crcs(pool: BufferPool, ls: LocalitySet,
                    dtype: np.dtype) -> List[int]:
    """Per-field CRC chains over a columnar set's stored blocks in page
    order — the read-side twin of the map pass's ``partition_crcs`` chain.
    Because the chains are split-invariant, a set rebuilt from raw page
    images (replica copy, shm import across the process data plane) yields
    the writer's exact fingerprint iff every block landed intact and in
    order."""
    dtype = np.dtype(dtype)
    crcs: Optional[List[int]] = None
    for cols, n in iter_column_blocks(pool, ls, dtype):
        crcs = columns_crc32(cols, dtype, 0, n, crcs)
    if crcs is None:
        crcs = [0] * len(_field_layout(dtype))
    return crcs


def read_all_columnar(pool: BufferPool, ls: LocalitySet,
                      dtype: np.dtype) -> np.ndarray:
    """Materialize a columnar set back into a record array (the read-path
    twin of ``services.read_all``; byte-identical logical content)."""
    dtype = np.dtype(dtype)
    chunks = [columns_to_records(cols, dtype, n)
              for cols, n in iter_column_blocks(pool, ls, dtype)]
    if not chunks:
        return np.empty(0, dtype=dtype)
    return np.concatenate(chunks)


# ---------------------------------------------------------------------------
# Checksums — byte-compatible with replication.record_content_checksum
# ---------------------------------------------------------------------------
def columnar_content_checksum(columns: Dict[str, np.ndarray],
                              dtype: np.dtype,
                              n: Optional[int] = None) -> int:
    """``record_content_checksum`` computed straight from column arrays.

    The row function multiplies record byte ``j`` by ``MULT**(j+1)`` and sums
    per record before mixing; addition mod 2**64 commutes, so the per-field
    partial sums (each field using the multiplier slice at its record byte
    offset) reproduce the identical value without materializing rows. This is
    what lets a columnar shard verify against a row-oriented replica of the
    same logical records."""
    dtype = np.dtype(dtype)
    fields = _field_layout(dtype)
    width = dtype.itemsize
    if n is None:
        n = len(columns[fields[0][0]])
    if n == 0:
        return 0
    mults = np.full(width, _CONTENT_MULT, dtype=np.uint64)
    total = 0
    step = max(1, (1 << 20) // width)
    with np.errstate(over="ignore"):
        mults = np.cumprod(mults, dtype=np.uint64)
        for i in range(0, n, step):
            m = min(step, n - i)
            row = np.zeros(m, dtype=np.uint64)
            for name, _, off, w in fields:
                raw = _col_view(columns[name][i:i + m]).reshape(m, w)
                row += (raw.astype(np.uint64)
                        * mults[off:off + w]).sum(axis=1, dtype=np.uint64)
            row = (row ^ (row >> np.uint64(29))) * _CONTENT_MIX
            row ^= row >> np.uint64(32)
            total = (total + int(row.sum(dtype=np.uint64))) % (1 << 64)
    return total


def columns_crc32(columns: Dict[str, np.ndarray], dtype: np.dtype,
                  lo: int = 0, hi: Optional[int] = None,
                  crcs: Optional[List[int]] = None) -> List[int]:
    """Per-field order-exact CRC32 chains over a column slice — the columnar
    shuffle's per-partition output fingerprint. One chain per field (record
    byte order) rather than one interleaved chain, so the fingerprint is
    invariant to how a record sequence is split into slices: writers chain
    per routed slice, readers chain per stored block, and the two streams
    agree as long as record order does. Chain by passing the previous value
    as ``crcs`` (updated in place when provided)."""
    fields = _field_layout(np.dtype(dtype))
    if crcs is None:
        crcs = [0] * len(fields)
    for i, (name, _, _, _) in enumerate(fields):
        col = columns[name]
        sl = col[lo:hi] if hi is not None else col[lo:]
        crcs[i] = zlib.crc32(np.ascontiguousarray(sl).data, crcs[i])
    return crcs


# ---------------------------------------------------------------------------
# Fused hash-partition + incremental-CRC kernel (the shuffle map hot path)
# ---------------------------------------------------------------------------
def route_partition_ids(keys: np.ndarray, num_partitions: int) -> np.ndarray:
    """Reducer id per key — bit-for-bit ``ClusterShuffle.partition_of_keys``,
    computed in-place over one uint64 temp (and with the modulo strength-
    reduced to a mask when the reducer count is a power of two)."""
    h = np.asarray(keys).astype(np.uint64)
    np.multiply(h, _ROUTE_MULT, out=h)
    h ^= h >> np.uint64(29)
    p = np.uint64(num_partitions)
    if num_partitions & (num_partitions - 1) == 0:
        np.bitwise_and(h, p - np.uint64(1), out=h)
    else:
        np.remainder(h, p, out=h)
    return h


def fused_partition_crc(keys: np.ndarray, columns: Dict[str, np.ndarray],
                        dtype: np.dtype, num_partitions: int,
                        crcs: Optional[List[int]] = None):
    """One fused pass over a column block: reducer hash -> dispatch plan
    (stable argsort over narrow partition ids + bincount, the
    ``runtime.cluster.dispatch_plan`` contract) -> per-column contiguous
    gather -> per-partition CRC32 chained into ``crcs``.

    Returns ``(routed, counts, offsets, crcs)`` where ``routed`` holds each
    column re-ordered so partition ``r`` occupies rows
    ``offsets[r]:offsets[r+1]`` — ready to memcpy into per-reducer pages with
    no per-record work. ``crcs[r]`` is partition ``r``'s per-field CRC chain
    (see :func:`columns_crc32`), updated incrementally so shuffle output is
    CRC-verified without a second pass."""
    h = route_partition_ids(keys, num_partitions)
    # narrow ids radix-sort ~5x faster than int64 comparison sort
    if num_partitions <= 256:
        parts = h.astype(np.uint8)
    elif num_partitions <= 65536:
        parts = h.astype(np.uint16)
    else:
        parts = h.astype(np.int64)
    order = np.argsort(parts, kind="stable")
    counts = np.bincount(parts, minlength=num_partitions)
    offsets = np.empty(num_partitions + 1, np.int64)
    offsets[0] = 0
    np.cumsum(counts, out=offsets[1:])
    fields = _field_layout(np.dtype(dtype))
    routed = {name: np.take(columns[name], order, axis=0)
              for name, _, _, _ in fields}
    if crcs is None:
        crcs = [[0] * len(fields) for _ in range(num_partitions)]
    # CRC straight off each routed column's flat byte view: the routed
    # arrays are C-contiguous, so every partition slice is one buffer
    bounds = offsets.tolist()
    for fi, (name, _, _, w) in enumerate(fields):
        flat = _col_view(routed[name])
        for r in range(num_partitions):
            lo, hi = bounds[r], bounds[r + 1]
            if hi > lo:
                crcs[r][fi] = zlib.crc32(flat[lo * w:hi * w].data,
                                         crcs[r][fi])
    return routed, counts, offsets, crcs


def segment_sum(keys: np.ndarray, vals: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized group-by-sum over one column pair: sort-free ``np.add.at``
    segment reduce keyed by ``np.unique`` — the columnar aggregation path
    (replaces per-record open-addressing inserts on co-partitioned shards)."""
    keys = np.asarray(keys, np.int64)
    vals = np.asarray(vals, np.float64)
    if len(keys) == 0:
        return keys, vals
    uk, inv = np.unique(keys, return_inverse=True)
    out = np.zeros(len(uk), dtype=np.float64)
    np.add.at(out, inv, vals)
    return uk, out
