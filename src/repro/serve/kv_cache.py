"""Block-table paged KV cache: host-side page allocator over the device
page pool built by models.model.init_paged_cache.

Layout:
  - device pool, per attention layer: k/v pages (G, n_pages, page_size,
    Hkv, hd). Page 0 is the *null page* — never allocated; inactive
    batch rows and masked prefill padding write there so the scatter in
    the decode step needs no branch.
  - block table: (max_seqs, max_pages_per_seq) int32, row = sequence
    slot, entry = page id (the slot's reserve page for unused entries,
    see below; always a valid DMA target for the Pallas kernel).

Sharding (`n_shards > 1`): the pool's page axis is partitioned into
`n_shards` equal contiguous blocks matching the GSPMD layout of the
device pool under `dist.sharding.cache_pspec` (pages on the "data"
axis shard the leading page blocks onto consecutive devices), and the
sequence slots are partitioned the same way (slot s lives on shard
`s // (max_seqs / n_shards)`, matching the batch-on-data layout of the
decode step's inputs). Every page a sequence ever touches — growth,
COW forks, shared prefixes — comes from its own shard's block, so the
decode gather and the prefill scatter stay device-local. The first
page of each shard's block (`null_page_of_shard`) is a per-shard
*reserve* page, never allocated: masked rows of that shard write there
(the engine routes inactive rows via a per-slot null-page row instead
of the constant 0), and every block-table entry past a sequence's
pages holds it. The paged decode kernel reads each shard's block with
page ids rebased onto that block and fetches a page for every
block-table entry, masked or not, so an entry outside the shard's
block would be an out-of-bounds read. All allocator invariants below
hold *per shard*; with `n_shards == 1` the layout degenerates to the
original global pool (reserve page == null page 0).

Pages are *refcounted* so completed prefill pages can be shared between
sequences through the radix prefix index (serve/prefix_cache.py): a page
may appear in several block-table rows and/or be retained by the index.
A shared page is immutable — any writer must fork it first
(`cow_for_write`, copy-on-write), which preserves the invariant that a
page is only ever written while its refcount is exactly 1.

The allocator is plain numpy/python — allocation decisions are host-side
scheduler work (microseconds) while the pool itself stays on device and
is functionally updated (donated) by decode/prefill steps. COW forks
return (src, dst) page-id pairs; the engine applies them on device via
models.model.copy_pages before the write lands.

Invariants (asserted in tests/test_paged_kv.py, per shard in
tests/test_sharded_serve.py, and the property suite
tests/test_alloc_property.py):
  - refcount conservation: free_pages + live_pages == usable_pages
    (n_pages minus one reserve page per shard), where a live page
    (refcount > 0) counts once no matter how many rows or index nodes
    reference it; the same identity holds within each shard;
  - refcount[p] == (# slots whose block table holds p) + (1 if the
    prefix index retains p else 0);
  - no page is written while refcount > 1 (cow_for_write forks first);
  - reserve pages (the null page 0 and each shard's first page) are
    never allocated, shared, or forked;
  - every page owned by slot s belongs to shard_of_slot(s)'s block;
  - block-table entries beyond a sequence's page count are the slot's
    reserve page (`null_page_of_slot`; 0 unsharded), so every entry of
    a row lies in its shard's block.
"""
from __future__ import annotations

import numpy as np

from repro.models.model import init_paged_cache, map_page_leaves


class OutOfPages(Exception):
    """Raised when an allocation cannot be satisfied; the scheduler
    responds by preempting a sequence (eviction) and retrying. The
    allocator first tries to reclaim unreferenced prefix-index pages."""


class PagedKVCache:
    def __init__(self, cfg, *, n_pages, page_size, max_seqs,
                 max_pages_per_seq=None, dtype=None, create_pool=True,
                 n_shards=1, kv_bits=0, kv_group_size=0):
        assert n_pages >= 2, "need at least the null page + one real page"
        assert n_shards >= 1
        assert n_pages % n_shards == 0, \
            f"n_pages={n_pages} must split evenly over {n_shards} shards"
        assert max_seqs % n_shards == 0, \
            f"max_seqs={max_seqs} must split evenly over {n_shards} shards"
        assert n_pages // n_shards >= 2, \
            "each shard needs its reserve page + one usable page"
        self.cfg = cfg
        self.page_size = int(page_size)
        self.n_pages = int(n_pages)
        self.max_seqs = int(max_seqs)
        self.n_shards = int(n_shards)
        self.pages_per_shard = self.n_pages // self.n_shards
        self.seqs_per_shard = self.max_seqs // self.n_shards
        if max_pages_per_seq is None:
            self.max_pages_per_seq = self.pages_per_shard - 1
        else:
            # explicit `is None` test: a falsy 0 must not silently fall
            # back to the pool-wide default (a sequence that may own
            # zero pages is a config bug, not a "use the default" ask)
            self.max_pages_per_seq = int(max_pages_per_seq)
            if self.max_pages_per_seq < 1:
                raise ValueError(
                    f"max_pages_per_seq={max_pages_per_seq!r}: must be "
                    ">= 1 (omit it or pass None for the per-shard "
                    "default)")
        self.kv_bits = int(kv_bits)
        self.kv_group_size = int(kv_group_size)
        self._dtype = dtype
        # the property-based allocator tests exercise the accounting
        # without paying for a device pool
        self.pool = (init_paged_cache(cfg, n_pages, page_size, max_seqs,
                                      dtype, kv_bits=self.kv_bits,
                                      kv_group_size=self.kv_group_size)
                     if create_pool else None)
        self._created_pool = bool(create_pool)
        self._pool_taken = False
        self.block_tables = np.repeat(
            np.asarray([self.null_page_of_slot(s) for s in range(max_seqs)],
                       np.int32)[:, None], self.max_pages_per_seq, axis=1)
        # monotone per-row versions: bumped on every block-table mutation
        # so the engine can mirror rows to a device-resident copy
        # incrementally instead of re-uploading the whole table per tick
        self.bt_version = np.zeros((max_seqs,), np.int64)
        # per-shard free lists; each shard's first page (page 0 for
        # shard 0 — the global null page) is the reserve page and never
        # enters a free list
        self._free_by_shard: list[list[int]] = [
            list(range((s + 1) * self.pages_per_shard - 1,
                       s * self.pages_per_shard, -1))
            for s in range(self.n_shards)]
        self._owned: list[list[int]] = [[] for _ in range(max_seqs)]
        self._active = np.zeros((max_seqs,), bool)
        self._refcount = np.zeros((n_pages,), np.int32)
        self.prefix_index = None          # set by RadixPrefixCache
        self.high_water = 0
        self.cow_forks = 0
        self.pages_allocated = 0

    # ---------------- shard geometry ----------------
    def shard_of_page(self, pid: int) -> int:
        return pid // self.pages_per_shard

    def shard_of_slot(self, slot: int) -> int:
        return slot // self.seqs_per_shard

    def null_page_of_shard(self, shard: int) -> int:
        """The shard's reserve page: masked/inactive rows of that shard
        write there (page 0 for shard 0 and for unsharded pools)."""
        return shard * self.pages_per_shard

    def null_page_of_slot(self, slot: int) -> int:
        """The reserve page of the slot's shard: what the slot's unused
        block-table entries hold."""
        return self.null_page_of_shard(self.shard_of_slot(slot))

    def is_reserve_page(self, pid: int) -> bool:
        """True for every shard's reserve page — page 0 and each
        shard block's first page. These are never allocated, so they
        must never gain references; `pid != 0` alone misses the
        shard > 0 reserves."""
        return pid % self.pages_per_shard == 0

    def bytes_per_page(self) -> int:
        """Device bytes one page id costs across all attention layers
        (K + V, codes + scales when binary-coded). Host-side math — no
        pool needed."""
        from repro.models.attention import paged_kv_page_bytes
        return paged_kv_page_bytes(
            self.cfg, self.page_size, self._dtype,
            kv_bits=self.kv_bits, kv_group_size=self.kv_group_size)

    def pool_bytes(self) -> int:
        return self.bytes_per_page() * self.n_pages

    def take_pool(self):
        """Hand the device pool to the caller (the engine functionally
        updates + donates it; keeping a reference here would defeat
        donation). compact() then takes the pool as an argument."""
        pool, self.pool = self.pool, None
        self._pool_taken = True
        return pool

    # ---------------- accounting ----------------
    @property
    def _free(self) -> list[int]:
        """Flat view of every free page (shard 0 first). Read-only:
        allocation pops from the per-shard lists."""
        if self.n_shards == 1:
            return self._free_by_shard[0]
        return [p for fl in self._free_by_shard for p in fl]

    @property
    def usable_pages(self) -> int:
        return self.n_pages - self.n_shards

    def usable_in_shard(self, shard: int = 0) -> int:
        # shards are equal-sized today; validate anyway so a bogus
        # shard id fails here, not as a plausible page count downstream
        assert 0 <= shard < self.n_shards, shard
        return self.pages_per_shard - 1

    @property
    def free_page_count(self) -> int:
        return sum(len(fl) for fl in self._free_by_shard)

    def free_in_shard(self, shard: int) -> int:
        return len(self._free_by_shard[shard])

    @property
    def used_pages(self) -> int:
        return self.usable_pages - self.free_page_count

    @property
    def live_pages(self) -> int:
        """Distinct pages with refcount > 0 (each counted once)."""
        return int((self._refcount > 0).sum())

    def live_in_shard(self, shard: int) -> int:
        lo = shard * self.pages_per_shard
        return int((self._refcount[lo:lo + self.pages_per_shard] > 0).sum())

    def refcount(self, pid: int) -> int:
        return int(self._refcount[pid])

    def utilization(self) -> float:
        return self.used_pages / max(self.usable_pages, 1)

    def pages_for(self, n_tokens: int) -> int:
        return -(-max(n_tokens, 1) // self.page_size)

    def active_slots(self):
        return [i for i in range(self.max_seqs) if self._active[i]]

    # ---------------- slot lifecycle ----------------
    def pick_shard(self) -> int | None:
        """Admission policy hook: the shard with the most free pages
        among shards that still have a free sequence slot (ties to the
        lowest shard id; None when every slot is taken). Trivially 0
        for unsharded pools with a free slot."""
        best, best_free = None, -1
        for s in range(self.n_shards):
            lo = s * self.seqs_per_shard
            if self._active[lo:lo + self.seqs_per_shard].all():
                continue
            if len(self._free_by_shard[s]) > best_free:
                best, best_free = s, len(self._free_by_shard[s])
        return best

    def alloc_slot(self, shard: int | None = None) -> int | None:
        """Claim the first free slot (within `shard`'s slot block when
        given)."""
        lo, hi = 0, self.max_seqs
        if shard is not None:
            lo = shard * self.seqs_per_shard
            hi = lo + self.seqs_per_shard
        for i in range(lo, hi):
            if not self._active[i]:
                self._active[i] = True
                return i
        return None

    def _reclaim(self, shortfall: int, shard: int) -> int:
        """Ask the prefix index to drop its least-recently-used
        unreferenced pages *in this shard*. Returns how many pages were
        freed."""
        if shortfall <= 0 or self.prefix_index is None:
            return 0
        return self.prefix_index.evict(shortfall, shard=shard)

    def ensure(self, slot: int, n_tokens: int) -> None:
        """Grow slot's page list to cover n_tokens, allocating from the
        slot's shard; raises OutOfPages (allocating nothing) when that
        shard can't satisfy the growth, after reclaiming unreferenced
        prefix-index pages of the same shard."""
        assert self._active[slot], slot
        need = self.pages_for(n_tokens) - len(self._owned[slot])
        if need <= 0:
            return
        if self.pages_for(n_tokens) > self.max_pages_per_seq:
            raise OutOfPages(f"slot {slot}: {n_tokens} tokens exceed "
                             f"max_pages_per_seq={self.max_pages_per_seq}")
        shard = self.shard_of_slot(slot)
        free = self._free_by_shard[shard]
        if need > len(free):
            self._reclaim(need - len(free), shard)
        if need > len(free):
            raise OutOfPages(f"slot {slot}: need {need} pages, "
                             f"{len(free)} free in shard {shard}")
        for _ in range(need):
            pid = free.pop()
            idx = len(self._owned[slot])
            self._owned[slot].append(pid)
            self.block_tables[slot, idx] = pid
            self._refcount[pid] = 1
        self.bt_version[slot] += 1
        self.pages_allocated += need
        self.high_water = max(self.high_water, self.used_pages)

    def share(self, slot: int, page_ids) -> None:
        """Attach already-live pages (a matched prefix) to a fresh slot:
        the pages become the slot's leading block-table entries and gain
        one reference each. Must precede any ensure() growth so page
        index i keeps covering tokens [i*page_size, (i+1)*page_size).
        Shared pages must live in the slot's shard — cross-shard
        attachment would break page locality."""
        assert self._active[slot], slot
        assert not self._owned[slot], "share() must precede suffix alloc"
        assert len(page_ids) <= self.max_pages_per_seq
        shard = self.shard_of_slot(slot)
        for idx, pid in enumerate(page_ids):
            assert not self.is_reserve_page(int(pid)) \
                and self._refcount[pid] > 0, pid
            assert self.shard_of_page(int(pid)) == shard, \
                (slot, pid, "cross-shard prefix attach")
            self._owned[slot].append(int(pid))
            self.block_tables[slot, idx] = pid
            self._refcount[pid] += 1
        if page_ids:
            self.bt_version[slot] += 1

    def cow_for_write(self, slot: int, start_tok: int, end_tok: int):
        """Copy-on-write: the slot is about to write token positions
        [start_tok, end_tok). Any of its pages in that range with
        refcount > 1 is forked onto a fresh page (the shared original
        keeps its other references). Returns the [(src, dst), ...]
        page copies the caller must apply to the device pool BEFORE the
        write. Raises OutOfPages (forking nothing) when the pool cannot
        supply the fork pages."""
        if end_tok <= start_tok:
            return []
        owned = self._owned[slot]
        p0, p1 = start_tok // self.page_size, (end_tok - 1) // self.page_size
        assert p1 < len(owned), (slot, start_tok, end_tok, len(owned))
        shared = [i for i in range(p0, p1 + 1)
                  if self._refcount[owned[i]] > 1]
        if not shared:
            return []
        sh = self.shard_of_slot(slot)
        free = self._free_by_shard[sh]
        if len(shared) > len(free):
            self._reclaim(len(shared) - len(free), sh)
        if len(shared) > len(free):
            raise OutOfPages(f"slot {slot}: {len(shared)} COW forks, "
                             f"{len(free)} free in shard {sh}")
        copies = []
        for i in shared:
            old = owned[i]
            new = free.pop()
            self._refcount[old] -= 1          # was > 1, never hits 0
            self._refcount[new] = 1
            owned[i] = new
            self.block_tables[slot, i] = new
            copies.append((old, new))
        self.bt_version[slot] += 1
        self.cow_forks += len(copies)
        self.pages_allocated += len(copies)
        self.high_water = max(self.high_water, self.used_pages)
        return copies

    # ---------------- prefix-index references ----------------
    def ref(self, pid: int) -> None:
        """Take a prefix-index reference on a live page."""
        assert not self.is_reserve_page(int(pid)) \
            and self._refcount[pid] > 0, pid
        self._refcount[pid] += 1

    def unref(self, pid: int) -> None:
        """Drop a reference; a page reaching refcount 0 returns to its
        home shard's free list (contents are reused by overwrite)."""
        assert self._refcount[pid] > 0, pid
        self._refcount[pid] -= 1
        if self._refcount[pid] == 0:
            self._free_by_shard[self.shard_of_page(pid)].append(pid)

    def release(self, slot: int) -> None:
        """Drop a sequence's references (completion or preemption).
        Pages still referenced elsewhere (shared prefixes, the radix
        index) stay live; the rest return to the free list."""
        for pid in self._owned[slot]:
            self.unref(pid)
        self._owned[slot] = []
        self.block_tables[slot, :] = self.null_page_of_slot(slot)
        self.bt_version[slot] += 1
        self._active[slot] = False

    def truncate(self, slot: int, n_tokens: int) -> int:
        """Speculative rollback: drop the slot's trailing pages so it
        owns exactly `pages_for(n_tokens)` — rejected draft tokens past
        a page boundary release their pages (unref: a page shared via
        the prefix index stays live for its other readers). Rejected
        tokens WITHIN the last kept page need no work: the engine
        truncates `pos`, attention masks by context length, and the
        next write overwrites the stale tail — identical to how partial
        tail pages always behave. Returns the number of pages freed."""
        keep = self.pages_for(n_tokens)
        owned = self._owned[slot]
        assert keep <= len(owned), (slot, n_tokens, len(owned))
        dropped = owned[keep:]
        self.block_tables[slot, keep:keep + len(dropped)] = \
            self.null_page_of_slot(slot)
        del owned[keep:]
        for pid in dropped:
            self.unref(pid)
        if dropped:
            self.bt_version[slot] += 1
        return len(dropped)

    def owned_pages(self, slot: int):
        return list(self._owned[slot])

    # ---------------- defrag ----------------
    def compact(self, pool=None):
        """Remap live pages onto the lowest page ids *of their shard*
        (gather on device, rewrite block tables + prefix index) and
        return the compacted pool. Paging has no *internal*
        fragmentation to fix — this exists so long-lived engines can
        shrink the pool's high-water footprint (e.g. before
        snapshotting a pool slice). Pages never cross shards, so the
        gather permutation is block-diagonal over the page axis and the
        device move stays shard-local under the GSPMD layout. Pass the
        pool explicitly when the engine took ownership via
        take_pool()."""
        import jax
        import jax.numpy as jnp

        if pool is None:
            assert not (self._created_pool and self._pool_taken), \
                "pool was taken; pass it in"
            pool = self.pool

        mapping: dict[int, int] = {}
        next_in_shard = [s * self.pages_per_shard + 1
                         for s in range(self.n_shards)]

        def remap(pid: int) -> int:
            if pid not in mapping:
                sh = self.shard_of_page(pid)
                mapping[pid] = next_in_shard[sh]
                next_in_shard[sh] += 1
            return mapping[pid]

        for slot in range(self.max_seqs):
            for j, pid in enumerate(self._owned[slot]):
                new = remap(pid)
                self._owned[slot][j] = new
                self.block_tables[slot, j] = new
            self.bt_version[slot] += 1
        if self.prefix_index is not None:
            self.prefix_index.remap(remap)
        # any remaining live page (shouldn't exist outside slots/index,
        # but keep the permutation total over live pages regardless)
        for pid in np.flatnonzero(self._refcount[1:] > 0) + 1:
            remap(int(pid))

        src = np.arange(self.n_pages, dtype=np.int32)
        new_rc = np.zeros_like(self._refcount)
        for old, new in mapping.items():
            src[new] = old
            new_rc[new] = self._refcount[old]
        self._refcount = new_rc

        if pool is not None:
            # page pools have the page axis at dim 1 (after the group
            # stack); per-slot state (mamba) is left alone. On a
            # binary-coded pool this moves codes AND scale leaves.
            pool = map_page_leaves(lambda leaf: leaf[:, jnp.asarray(src)],
                                   pool)
        self._free_by_shard = [
            list(range((s + 1) * self.pages_per_shard - 1,
                       next_in_shard[s] - 1, -1))
            for s in range(self.n_shards)]
        if not self._pool_taken:
            self.pool = pool
        return pool
