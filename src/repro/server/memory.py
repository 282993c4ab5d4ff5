"""Unified memory manager for the server tier (DESIGN.md §6.3).

Shark's cached tables are a *cache*, not primary storage (paper §3.2): any
cached partition can be dropped under memory pressure and transparently
recomputed from RDD lineage on the next access.  The seed runtime never
evicted, so that fallback path was dead code.  The MemoryManager makes it
live: it does unified byte accounting over everything the BlockManager
holds (cached partitions + in-flight shuffle output) plus the query result
cache, and enforces a configurable budget.

The budget governs *evictable cache bytes* — cached partition blocks plus
result-cache entries.  Shuffle map outputs are working memory, not cache:
a running reducer holds a fetch dependency on them, so *dropping* them
would only trade eviction for immediate lineage recovery churn.  They are
accounted and reported (`working_bytes`), and the server releases them
deterministically when their query completes (`BlockManager.drop_shuffle`);
a worker death dropping them mid-query is already handled by the
scheduler's lineage recovery.  With a spill-mode StorageManager attached,
however, the working set obeys the budget too: when cache eviction alone
cannot satisfy it, shuffle blocks are *spilled* (largest first) to
checksummed segments and fault back in on fetch — a lost segment degrades
to FetchFailed -> lineage recompute, the same contract as everything else.

Eviction policy (deterministic, documented order):
  1. cached partition blocks, least-recently-used first — cheapest to hold
     wrong and always recomputable from lineage;
  2. query-result-cache entries, LRU — tiny (final aggregates), so they are
     evicted only when partition eviction alone cannot satisfy the budget;
  3. memoized decode caches (HOT -> WARM, first half): pure derived state
     that re-materializes on the next decode;
  4. with a StorageManager attached (DESIGN.md §12), the storage-hierarchy
     rungs: adaptive recompression of resident catalog partitions
     (WARM, second half), then spilling the coldest partition to disk
     (COLD) — least-recently-scanned first.

If the just-inserted partition alone exceeds what the budget can hold even
after evicting everything else, it is itself dropped — a cache-admission
*bypass*: the query that computed it already has the batch in hand, so
correctness is unaffected.

Accounting: `cache_bytes()` always includes the memoized decode caches
(they are real memory, not free), and — when a StorageManager is attached —
the catalog's resident encoded bytes, since the storage tier can actually
release those.  Spill-file bytes live on disk, not in memory: they are
reported (`spill_bytes`) but never counted against the memory budget.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Set, Tuple

from ..core.runtime import BlockManager


class MemoryManager:
    def __init__(self, block_manager: BlockManager,
                 budget_bytes: Optional[int] = None):
        self.bm = block_manager
        self.budget_bytes = budget_bytes
        self.lock = threading.RLock()
        self._result_cache = None  # attached by the server
        self._evicted: Set[Tuple] = set()
        # counters (all monotonic; exposed via stats())
        self.evictions = 0
        self.evicted_bytes = 0
        self.recomputes = 0
        self.result_evictions = 0
        self.bypasses = 0
        self.over_budget_events = 0
        self.decode_cache_drops = 0
        self.decode_cache_dropped_bytes = 0
        self.chaos_pressure_drops = 0
        self._catalog = None
        self.storage = None        # core.storage.StorageManager, optional
        self.chaos = None          # core.faults.ChaosEngine, when installed
        self.bm.memory_manager = self

    def attach_result_cache(self, result_cache) -> None:
        self._result_cache = result_cache

    def attach_catalog(self, catalog) -> None:
        """Register the catalog whose tables' memoized decode caches
        (`Encoded._decoded`, see core/compression.py) this manager may
        release under pressure."""
        self._catalog = catalog

    def attach_storage(self, storage) -> None:
        """Attach the out-of-core storage tier (DESIGN.md §12): enables the
        recompression and spill rungs of `enforce()` and adds the catalog's
        resident encoded bytes to the governed budget.  In spill mode the
        BlockManager gains the shuffle spill/fault path too (drop mode
        keeps shuffle output pinned — dropping it mid-query just forces
        recompute storms)."""
        self.storage = storage
        if storage is not None and storage.mode == "spill":
            self.bm.shuffle_storage = storage

    def drop_decoded_caches(self) -> int:
        """Release every catalog table's memoized decode cache — pure
        derived state that re-materializes on the next decode.  Returns
        bytes freed."""
        cat = getattr(self, "_catalog", None)
        if cat is None:
            return 0
        freed = 0
        for table in list(cat._tables.values()):
            freed += table.drop_decoded()
        if freed:
            self.decode_cache_drops += 1
            self.decode_cache_dropped_bytes += freed
        return freed

    # -- accounting ----------------------------------------------------------

    def accounted_bytes(self) -> int:
        """Everything tracked: cache bytes + in-flight shuffle output."""
        rc = self._result_cache
        return (self.bm.nbytes() + (rc.nbytes if rc is not None else 0)
                + self.decoded_cache_bytes() + self.catalog_resident_bytes())

    def decoded_cache_bytes(self) -> int:
        """Memoized decode caches across catalog tables — real memory the
        budget must govern (historically unaccounted)."""
        cat = self._catalog
        if cat is None:
            return 0
        return sum(t.decoded_cache_nbytes for t in list(cat._tables.values()))

    def catalog_resident_bytes(self) -> int:
        """Resident encoded bytes of catalog tables.  Governed only when a
        storage tier is attached — without one these bytes are primary
        storage the manager cannot release, so counting them would just
        burn the budget on unevictable state."""
        if self.storage is None or self._catalog is None:
            return 0
        return sum(t.resident_nbytes
                   for t in list(self._catalog._tables.values()))

    def cache_bytes(self) -> int:
        """Evictable bytes the budget governs: partition blocks + results +
        decode memos (+ catalog resident bytes when spillable)."""
        rc = self._result_cache
        return (self.bm.part_bytes + (rc.nbytes if rc is not None else 0)
                + self.decoded_cache_bytes() + self.catalog_resident_bytes())

    # -- BlockManager hooks ---------------------------------------------------

    def on_put(self, key: Tuple) -> None:
        """A block was just inserted: enforce the budget, protecting it."""
        with self.lock:
            self._evicted.discard(key)
        self.enforce(protect=key)

    def on_miss(self, key: Tuple) -> None:
        """A cached-partition read missed.  If we evicted that block, this
        miss is the paper's recompute-from-lineage fallback in action."""
        with self.lock:
            if key in self._evicted:
                self._evicted.discard(key)
                self.recomputes += 1

    # -- enforcement ----------------------------------------------------------

    def enforce(self, protect: Optional[Tuple] = None) -> None:
        # chaos seam "memory.enforce": simulated memory pressure drops one
        # unprotected LRU cached partition — always recoverable (cached
        # partitions recompute from lineage on the next miss, exactly the
        # real eviction path below)
        if self.chaos is not None:
            trip = self.chaos.fire("memory.enforce")
            if trip is not None:
                with self.lock:
                    for key in self.bm.lru_partition_keys():
                        if key == protect:
                            continue
                        freed = self.bm.drop_block(key)
                        if freed:
                            self.evictions += 1
                            self.evicted_bytes += freed
                            self.chaos_pressure_drops += 1
                            self._evicted.add(key)
                        break
        if self.budget_bytes is None:
            return
        with self.lock:
            while self.cache_bytes() > self.budget_bytes:
                victim = None
                for key in self.bm.lru_partition_keys():
                    if key != protect:
                        victim = key
                        break
                if victim is not None:
                    freed = self.bm.drop_block(victim)
                    if freed:
                        self.evictions += 1
                        self.evicted_bytes += freed
                        self._evicted.add(victim)
                    continue
                rc = self._result_cache
                if rc is not None and rc.nbytes > 0:
                    if rc.evict_lru() > 0:
                        self.result_evictions += 1
                        continue
                # HOT -> WARM, first half: release the column store's
                # memoized decode caches (derived state that re-materializes
                # on the next decode)
                if self.drop_decoded_caches() > 0:
                    continue
                if self.storage is not None:
                    # WARM, second half: adaptively recompress resident
                    # catalog partitions (RLE / BITPACK / FOR from stats)
                    if self._recompress_pass() > 0:
                        continue
                    # WARM -> COLD: spill the least-recently-scanned
                    # partition to disk (or drop it, in drop mode)
                    if self._spill_coldest() > 0:
                        continue
                if (protect is not None and protect[0] == "part"
                        and protect in self.bm.sizes):
                    # the new block alone exceeds the budget: refuse
                    # admission rather than blow it
                    self.bm.drop_block(protect)
                    self.bypasses += 1
                    self._evicted.add(protect)
                self.over_budget_events += (
                    self.cache_bytes() > self.budget_bytes)
                break
            self._enforce_working_set(protect)

    def _enforce_working_set(self, protect: Optional[Tuple]) -> None:
        """Working-set rung: with a spill-mode storage tier attached, total
        accounted bytes (cache + shuffle output) obey the budget too —
        shuffle blocks spill largest-first and fault back in on fetch.
        Runs after the cache rungs so catalog state always yields before
        mid-query working memory does."""
        if (self.storage is None or self.storage.mode != "spill"
                or self.bm.shuffle_storage is None):
            return
        if self.accounted_bytes() <= self.budget_bytes:
            return
        for key in self.bm.shuffle_spill_candidates():
            if key == protect:
                continue
            self.bm.spill_shuffle_block(key)
            if self.accounted_bytes() <= self.budget_bytes:
                return

    # -- storage-hierarchy rungs (DESIGN.md §12) ------------------------------

    def _recompress_pass(self) -> int:
        """One WARM pass: recompress every resident catalog partition.
        Idempotent — a second pass over already-recompressed blocks frees
        nothing, so enforce() falls through to the spill rung."""
        cat = self._catalog
        if cat is None:
            return 0
        freed = 0
        for table in list(cat._tables.values()):
            for part in table.partitions:
                if part.resident:
                    freed += self.storage.recompress_partition(part)
        return freed

    def _spill_coldest(self) -> int:
        """One COLD transition: evict the least-recently-scanned resident
        catalog partition.  Lineage-bearing partitions go first (their
        recovery story is complete even if the segment is later lost); in
        drop mode they are the only candidates, since dropping without
        lineage would lose data outright."""
        cat = self._catalog
        if cat is None:
            return 0
        candidates = []
        for name, table in list(cat._tables.items()):
            for part in table.partitions:
                if part.resident and part.resident_nbytes > 0:
                    candidates.append((part.lineage is None,
                                       part.last_access, name, part))
        if self.storage.mode == "drop":
            candidates = [c for c in candidates if not c[0]]
        if not candidates:
            return 0
        _, _, name, part = min(candidates, key=lambda c: (c[0], c[1]))
        return self.storage.evict(name, part)

    # -- reporting -------------------------------------------------------------

    def stats(self) -> Dict[str, int]:
        rc = self._result_cache
        part_bytes = self.bm.part_bytes
        st = self.storage.stats() if self.storage is not None else {}
        return {
            "budget_bytes": self.budget_bytes or 0,
            "partition_bytes": part_bytes,
            "working_bytes": self.bm.nbytes() - part_bytes,  # shuffle
            "result_cache_bytes": rc.nbytes if rc is not None else 0,
            "decoded_cache_bytes": self.decoded_cache_bytes(),
            "catalog_resident_bytes": self.catalog_resident_bytes(),
            "cache_bytes": self.cache_bytes(),
            "accounted_bytes": self.accounted_bytes(),
            "partition_hits": self.bm.part_hits,
            "partition_misses": self.bm.part_misses,
            "evictions": self.evictions,
            "evicted_bytes": self.evicted_bytes,
            "recomputes": self.recomputes,
            "result_evictions": self.result_evictions,
            "bypasses": self.bypasses,
            "over_budget_events": self.over_budget_events,
            "decode_cache_drops": self.decode_cache_drops,
            "decode_cache_dropped_bytes": self.decode_cache_dropped_bytes,
            "chaos_pressure_drops": self.chaos_pressure_drops,
            # storage tier (zeros when no StorageManager is attached, so
            # stats() always carries the keys)
            "spills": st.get("spills", 0),
            "spill_bytes": st.get("spill_bytes", 0),
            "spill_reads": st.get("spill_reads", 0),
            "recompressions": st.get("recompressions", 0),
            "lineage_faults": st.get("lineage_faults", 0),
            "shuffle_spills": st.get("shuffle_spills", 0),
            "shuffle_faults": st.get("shuffle_faults", 0),
            "shuffle_lost": st.get("shuffle_lost", 0),
        }
