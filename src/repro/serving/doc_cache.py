"""Device-resident hot-doc cache for the RankingService.

SDR's observation (Cohen et al.): serving cost is dominated by *moving*
document representations, not scoring them.  Under a skewed (zipf-ish)
candidate stream the same hot documents are re-gathered from the index
memmaps, re-shipped over H2D, and re-decoded on every request.  This cache
keeps the *raw codec streams* — the index's stored bytes: int8 payload and
fp32 scales for quantizing codecs, raw floats otherwise — resident on the
device, so cache-hit candidates skip ``gather()`` and the H2D copy
entirely; the prefetcher only stages misses.  Decoding happens inside the
scoring jit (for int8 layer-K/V, in-register inside the join kernel), so
the cache footprint is the narrow encoded payload: an int8 index holds
~4x more resident docs per MiB than the old decoded-float pools.

Design: **token-page pools**, paged-attention style.  Each stream is one
preallocated device tensor ``[n_pages, page_tokens, ...]`` — or, for
the streams the caller names ``head_major`` (the layer-``l`` K/V), the
``[n_pages, H, page_tokens, D]`` that the paged join kernel reads one
``(page_tokens, D)`` tile at a time; an LRU map
assigns each doc a list of ``ceil(len/page_tokens)`` pages, so short docs
no longer pin whole max-length slots.  Batch assembly is a page-table
gather (``pool[page_table]``) and miss insertion one scatter per stream —
O(1) dispatches per micro-batch regardless of hit pattern, which is what
keeps the one-jit-entry-per-batch property of the scheduler intact
(tests/test_join_attention.py guards the dispatch count).  The classic
whole-doc *slot* cache is the degenerate configuration ``page_tokens >=
doc_len`` (the default): one page per doc, same bytes, same gather.

Two pages are reserved: page 0 is the immutable **zero page** — page-table
tails beyond a doc's allocated pages point at it, so padded positions read
as zeros exactly like ``IndexReader.gather_raw``'s zero padding, and the
per-page validity pool masks them off; page 1 is the **scratch page** that
absorbs scatter padding (miss rows staged past a doc's page count) and is
never referenced by any page table.

Concurrency contract: :meth:`plan` (host bookkeeping: LRU bump, page
allocation, eviction) may run in the prefetch thread; :meth:`insert` /
:meth:`take` (the device ops) must run on the scoring thread in batch
order.  Reassigning evicted pages is safe because their bytes are only
overwritten by a later ``insert`` — every batch's ``take`` happens before
any later batch's ``insert``.  ``plan`` never evicts a doc of the batch it
is planning (those ids are pinned): victims pop in LRU order and pinned
ids are set aside and re-queued at the cold end afterwards, so each
resident is examined at most once per plan call (``last_plan_scans``), not
once per miss.  The ``capacity >= 2 * micro_batch`` constructor check
guarantees an unpinned victim always exists.

Scores are identical hit-vs-miss by construction: every row — fresh miss
or warm hit — is assembled through the same page-table gather of the same
stored bytes, so the scoring jit sees bit-identical inputs.
"""
from __future__ import annotations

import functools
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.join_attention import kv_pages_to_dense, pages_to_dense


@functools.partial(jax.jit, donate_argnums=0)
def _scatter(pool, pages, rows):
    return pool.at[pages].set(rows)


class DeviceDocCache:
    """Paged device-resident LRU over the raw per-doc index streams.

    ``capacity_bytes`` bounds device memory; the page count is derived
    from the per-page footprint of ``streams`` — a ``{name: (dtype,
    row_shape)}`` spec as produced by ``IndexReader.streams_spec()`` —
    plus one validity byte per token.  ``head_major`` maps the names of
    streams to pool as ``[n_pages, H, page_tokens, D]`` (the paged join
    kernel's K/V layout) to their ``(H, D)``; the cache owns that layout
    on insert and in :meth:`dense`.  ``page_tokens=None`` (default)
    gives whole-doc pages (slot behavior); smaller values pack variable
    -length docs tighter.  ``page_bucket=True`` lets :meth:`plan` shrink
    the page-table width to the batch's longest doc (bucketed to powers
    of two) instead of the fixed ``pages_per_doc`` — fewer gathered
    bytes, at the cost of a few extra jit shapes.
    """

    ZERO_PAGE = 0      # immutable all-zero page: page-table tail padding
    SCRATCH_PAGE = 1   # scatter-padding sink: never read

    def __init__(self, capacity_bytes: int, *, doc_len: int,
                 streams: dict, page_tokens: int | None = None,
                 head_major: dict | None = None,
                 page_bucket: bool = False, min_slots: int = 2,
                 device=None):
        if page_tokens is None:
            page_tokens = doc_len
        page_tokens = -(-int(page_tokens) // 8) * 8   # sublane multiple
        self.page_tokens = page_tokens
        self.pages_per_doc = -(-int(doc_len) // page_tokens)
        self.doc_len = int(doc_len)
        #: stage/assembly length — doc_len rounded up to whole pages
        self.padded_len = self.pages_per_doc * page_tokens
        self.page_bucket = bool(page_bucket)
        self._streams = {
            name: (np.dtype(dt), tuple(shape))
            for name, (dt, shape) in streams.items()}
        self._head_major = {n: tuple(hd) for n, hd in
                            (head_major or {}).items()}
        self._take = jax.jit(self.dense, static_argnums=0)
        for n, (h, d) in self._head_major.items():
            if int(np.prod(self._streams[n][1])) != h * d:
                raise ValueError(f"stream {n!r} rows {self._streams[n][1]} "
                                 f"do not hold {h} heads of {d}")
        row_bytes = sum(
            dt.itemsize * int(np.prod(shape, dtype=np.int64))
            for dt, shape in self._streams.values()) + 1   # + valid byte
        self.page_bytes = page_tokens * row_bytes
        self.entry_bytes = self.pages_per_doc * self.page_bytes
        n_pages = int(capacity_bytes) // self.page_bytes
        need = min_slots * self.pages_per_doc + 2          # + reserved
        if n_pages < need:
            raise ValueError(
                f"doc cache of {capacity_bytes} bytes holds only "
                f"{n_pages} pages ({self.page_bytes} B/page) but the "
                f"scheduler needs at least {need} ({min_slots} docs of "
                f"{self.pages_per_doc} pages + 2 reserved) to pin an "
                f"in-flight batch; raise doc_cache_mb to >= "
                f"{need * self.page_bytes / 2**20:.1f} MiB or shrink "
                f"micro_batch")
        self.capacity_pages = n_pages
        self.capacity = (n_pages - 2) // self.pages_per_doc  # docs, worst case
        # pools — and every page table / staged row sent to them — are
        # *committed* to ``device`` when one is given (scale-out serving
        # pins each shard worker's cache to its own device; the
        # scatter/gather jits then follow the pool's placement) — None
        # keeps jax's default placement
        self.device = device

        def _alloc(shape, dt):
            return self.put(jnp.zeros(shape, dt))

        self._pools = {
            name: _alloc(self._pool_shape(n_pages, name), dt)
            for name, (dt, _) in self._streams.items()}
        #: device per-page validity (int8 — the paged kernel's dval pool)
        self.valid_pool = _alloc((n_pages, page_tokens), jnp.int8)
        self._valid_np = np.zeros((n_pages, page_tokens), bool)
        self._pages_of: OrderedDict[int, list[int]] = OrderedDict()  # LRU
        self._free = list(range(2, n_pages))
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        #: LRU entries examined by the most recent :meth:`plan` (pinned
        #: skips + evictions) — bounded by the resident count per call
        self.last_plan_scans = 0

    def put(self, x):
        """``x`` on the cache's device (jax's default placement when the
        cache is unpinned)."""
        return jax.device_put(x, self.device)

    def _pool_shape(self, n_pages: int, name: str) -> tuple:
        if name in self._head_major:
            h, d = self._head_major[name]
            return (n_pages, h, self.page_tokens, d)
        return (n_pages, self.page_tokens) + self._streams[name][1]

    def dense(self, name: str, pool, page_table):
        """Densify stream ``name``'s pool through a page table (traceable:
        the serving jits call it on :attr:`pools`) -> ``[B, W *
        page_tokens, *row]`` — ``[B, W * page_tokens, H, D]`` for a
        head-major stream."""
        if name in self._head_major:
            return kv_pages_to_dense(pool, page_table)
        return pages_to_dense(pool, page_table)

    def __len__(self):
        return len(self._pages_of)

    @property
    def resident_docs(self) -> int:
        return len(self._pages_of)

    @property
    def resident_bytes(self) -> int:
        return (self.capacity_pages - 2 - len(self._free)) * self.page_bytes

    def _pages_for(self, length) -> int:
        length = self.doc_len if length is None else min(int(length),
                                                         self.doc_len)
        return max(1, -(-length // self.page_tokens))

    # -- host bookkeeping (prefetch-thread safe) ------------------------------
    def plan(self, doc_ids, lengths=None, n_real: int | None = None):
        """Assign every doc its page list, evicting cold docs for misses.

        ``lengths`` (optional, per-row token counts) sizes each miss's
        allocation at ``ceil(len/page_tokens)`` pages; without it every
        doc gets the full ``pages_per_doc``.  Returns ``(page_table,
        miss_ids, miss_pages)``: ``page_table`` is the ``[B, W]`` int32
        gather map (rows zero-page-padded past each doc's pages),
        ``miss_ids`` the (unique, insertion-ordered) docs the caller must
        stage, and ``miss_pages`` their ``[M, W]`` scatter map
        (scratch-page-padded).  ``W = pages_per_doc`` unless
        ``page_bucket`` shrinks it to the batch maximum.

        ``n_real`` bounds the hit/miss counters to the first ``n_real``
        rows — micro-batch shape padding (replicated trailing rows) still
        gets pages but must not inflate the hit rate."""
        if n_real is None:
            n_real = len(doc_ids)
        ids = [int(d) for d in doc_ids]
        lens = (list(lengths) if lengths is not None
                else [None] * len(ids))
        pinned = set(ids)
        cached_before = set(self._pages_of)
        pinned_popped: dict[int, list[int]] = {}
        self.last_plan_scans = 0
        width = self.pages_per_doc
        if self.page_bucket:
            width = self.bucket(max(self._pages_for(l) for l in lens),
                                self.pages_per_doc)
        miss_ids: list[int] = []
        miss_pages: list[list[int]] = []
        table: list[list[int]] = []
        for i, d in enumerate(ids):
            pages = self._pages_of.get(d)
            if pages is not None:
                self._pages_of.move_to_end(d)
            elif d in pinned_popped:            # evict-scan set it aside
                pages = self._pages_of[d] = pinned_popped.pop(d)
            else:
                need = self._pages_for(lens[i])
                pages = []
                while len(pages) < need:
                    if self._free:
                        pages.append(self._free.pop())
                        continue
                    victim = None
                    while self._pages_of:       # LRU order, skip pinned
                        victim, vpages = self._pages_of.popitem(last=False)
                        self.last_plan_scans += 1
                        if victim in pinned:
                            pinned_popped[victim] = vpages
                            victim = None
                            continue
                        break
                    if victim is None:
                        self._requeue(pinned_popped)
                        raise RuntimeError(
                            "doc cache exhausted: every resident doc is "
                            "pinned by the batch being planned (capacity "
                            "check should have prevented this)")
                    self._free.extend(vpages)
                    self.evictions += 1
                self._pages_of[d] = pages
                miss_ids.append(d)
                miss_pages.append(
                    pages + [self.SCRATCH_PAGE] * (width - len(pages)))
            if i < n_real:
                if d in cached_before:
                    self.hits += 1
                else:
                    self.misses += 1
            table.append(pages + [self.ZERO_PAGE] * (width - len(pages)))
        self._requeue(pinned_popped)
        return (np.asarray(table, np.int32), miss_ids,
                np.asarray(miss_pages, np.int32).reshape(len(miss_ids),
                                                         width))

    def _requeue(self, pinned_popped):
        """Re-insert evict-scan survivors at the cold end, preserving
        their relative LRU order."""
        for d, pages in reversed(list(pinned_popped.items())):
            self._pages_of[d] = pages
            self._pages_of.move_to_end(d, last=False)
        pinned_popped.clear()

    @staticmethod
    def bucket(n: int, cap: int) -> int:
        """Pad count: next power of two, capped at ``cap`` — keeps the
        decode/scatter jit entries to O(log cap) shapes."""
        b = 1
        while b < n:
            b *= 2
        return max(n, min(b, cap))

    # -- device ops (scoring thread, batch order) -----------------------------
    def insert(self, miss_pages, parts: dict, valid):
        """Scatter staged miss rows into the page pools.  ``parts`` maps
        stream name -> ``[M, W * page_tokens, ...]`` staged raw rows (the
        batch may be bucket-padded with repeats of the last miss — same
        pages, same rows, idempotent).  ``valid``: ``[M, W * page_tokens]``
        bool."""
        miss_pages = np.asarray(miss_pages, np.int32)
        m, w = miss_pages.shape
        flat = miss_pages.reshape(-1)
        pages_dev = self.put(flat)
        for name, rows in parts.items():
            pool = self._pools[name]
            rows = self.put(rows).astype(pool.dtype)
            if name in self._head_major:           # -> [M*W, H, page, D]
                rows = jnp.swapaxes(rows.reshape(
                    (m * w, self.page_tokens) + self._head_major[name]),
                    1, 2)
            rows = rows.reshape((m * w,) + pool.shape[1:])
            self._pools[name] = _scatter(pool, pages_dev, rows)
        valid = np.asarray(valid, bool).reshape(m * w, self.page_tokens)
        self.valid_pool = _scatter(self.valid_pool, pages_dev,
                                   self.put(valid.astype(np.int8)))
        keep = flat != self.SCRATCH_PAGE
        self._valid_np[flat[keep]] = valid[keep]

    def take(self, page_table):
        """Densify a planned batch: page-table gather per stream ->
        ``(parts, valid_np)`` with ``parts[name]`` shaped
        ``[B, W * page_tokens, *row_shape]``.

        The serving hot path skips this and indexes the :attr:`pools`
        directly inside jitted device code (its pool-fused assemble/score
        dispatches); ``take`` is the standalone accessor for tests."""
        pt = self.put(np.asarray(page_table, np.int32))
        parts = {name: self._take(name, pool, pt)
                 for name, pool in self._pools.items()}
        return parts, self.valid_rows(page_table)

    @property
    def pools(self) -> dict:
        """The device page pools by stream name — index with a page table
        inside a jit to fuse batch assembly into downstream compute
        (:attr:`valid_pool` is the matching validity pool)."""
        return self._pools

    def valid_rows(self, page_table) -> np.ndarray:
        pt = np.asarray(page_table, np.int64)
        b, w = pt.shape
        return self._valid_np[pt].reshape(b, w * self.page_tokens)
