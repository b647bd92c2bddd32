"""DevicePool — device-resident block pages under the DeviceTransport.

Why this exists.  Every device touch through PR 17 staged host→device
and DISCARDED: scrub, resync verify, and degraded decode of the same
hot blocks re-paid the link on every pass, which is why round 5
scrubbed at 0.91 GiB/s while the device kernel did 24 GiB/s.  The
link, not the ALU, is the warm-path bound — so this module treats the
device as a MEMORY: a bounded set of fixed-size device pages (the
Ragged Paged Attention layout, PAPERS.md — fixed page size, ragged
occupancy, in-place reuse) keyed by block hash, budgeted by
``[codec] pool_mib`` SEPARATELY from the staging budget
(``max_device_staging_mib`` bounds bytes in flight; the pool bounds
bytes at rest).

Layout.  The pool's bytes live in ONE device array of
``pool_mib ÷ pool_page_kib`` pages (1,024 × 256 KiB as shipped), made
by the device codec's ``pool_alloc`` when the first pooled batch needs
it (the codec chooses how it lies there: ops/tpu_codec.py keeps words,
a page whole tiles).  What lives on the host, under the pool's one
lock, is the page table (block hash → the slots of its pages, in
order) and the free list.  A block of ``length`` bytes spans ``ceil(length / page)`` slots;
the tail page is partially filled and zero-padded (ragged occupancy —
the budget charges whole pages, so ``bytes_for(length)`` is the
page-rounded claim).

Two programs move a batch's pages, each of a fixed shape, so that a
batch costs one dispatch each way whatever its lanes hold:

  - **compose** (at dispatch; ``scrub_encode_submit_resident``): the
    ``lanes × cols`` batch is a gather from the array over the vector
    ``row_index`` builds — ``lanes × pages_for(cols)`` slots, the
    sentinel (one past the last slot) wherever a row has no page, which
    reads as zeros — with the staged miss rows laid over it.
  - **adopt** (at collect; ``adopt_lanes``): the verified miss lanes of
    the composed batch, seen as the same ``lanes × pages_for(cols)``
    pages, are scattered into the array over a vector of destination
    slots, the sentinel (dropped) for every page that is not adopted.

The array is never updated in place: ``adopt`` returns a new array and
the pool swaps its reference under the lock (a 256 MiB copy is ~0.7 ms
of a v5e's HBM, five times a pass).  So a batch composed earlier, a
``read`` from another thread and an ``invalidate`` in between all see
a whole array, and no donated buffer can be used after its donation.

Integration (ops/transport.py).  The transport consults the pool
while STAGING a scrub batch: a resident block's lane skips the host
copy and the H2D transfer entirely (``transport_staged_bytes_total``
stays flat; ``pool_hit_bytes_total`` takes the bytes), a miss stages
through the normal slot path and its verified lanes are adopted at
collect (``pool_miss_bytes_total``).  Every pool read still runs
through the device scrub kernel's hash verify — a corrupt page can
never return clean — but strict invalidation keeps hits USEFUL:
block delete, quarantine, rebalance-drop and overwrite all call
``invalidate`` synchronously before the operation acks
(block/manager.py), so the pool never serves a page for a block the
store no longer holds.  ``invalidate`` is a page-table operation: the
slots go back to the free list and only a later ``adopt`` writes them,
after every batch composed before it on the device's one stream.

Eviction clock.  LRU in SCRUB-CYCLE time, not wall time: ``tick()``
advances once per scrub pass (block/repair.py), and entries untouched
for the most cycles evict first.  Wall-clock LRU would evict the
whole working set during any long idle period even though the next
pass needs exactly the same blocks; cycle LRU keeps "the blocks the
last pass touched" resident however long the pass interval is.

Thread-safety: one lock.  ``lookup``/``adopt_lanes`` run on the
transport worker thread (the only one that dispatches a pool program),
``invalidate`` on event-loop and disk worker threads, ``tick`` on the
scrub worker — all synchronous, all cheap (dict and list ops; adopt
holds the lock over one asynchronous dispatch).
"""

from __future__ import annotations

import logging
import threading
from collections import OrderedDict
from typing import Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger("garage_tpu.ops.device_pool")


def miss_bucket(n: int, lanes: int) -> int:
    """Rows the `n` staged miss rows of a `lanes`-lane batch cross the
    link as: the next multiple of an eighth of the batch (of 32 rows at
    least), so that compose meets a closed set of shapes and the pad is
    under an eighth of a whole batch's rows."""
    if n <= 0:
        return 0
    quantum = max(32, lanes // 8)
    return min(lanes, -(-n // quantum) * quantum)


def miss_buckets(lanes: int) -> list:
    """Every value `miss_bucket` takes for a `lanes`-lane batch."""
    return sorted({miss_bucket(n, lanes) for n in range(lanes + 1)})


class _PoolEntry:
    """One resident block: the slots of its pages in the device array,
    in order, plus the bookkeeping the eviction clock needs."""

    __slots__ = ("key", "length", "slots", "tick")

    def __init__(self, key: bytes, length: int, slots: Tuple[int, ...],
                 tick: int):
        self.key = key
        self.length = length
        self.slots = slots
        self.tick = tick  # scrub cycle of the last touch


class DevicePool:
    """Bounded pool of device-resident block pages, hash-keyed."""

    def __init__(self, device, pool_bytes: int, page_bytes: int,
                 prefetch: bool = True, metrics=None, observer=None):
        self.device = device
        self.pool_bytes = max(0, int(pool_bytes))
        self.page_bytes = max(1, int(page_bytes))
        # the budget in whole pages: the slots of the device array, and
        # the sentinel index (one past the last) that a gather reads as
        # zeros and a scatter drops
        self.npages = self.pool_bytes // self.page_bytes
        self.prefetch_enabled = bool(prefetch)
        self.obs = observer
        self._lock = threading.Lock()
        # insertion/touch order IS the eviction order: lookup moves an
        # entry to the back, so the front is always the least-recently-
        # used entry of the oldest cycle (cycle order within = touch
        # order — exactly what the scrub walk produces)
        self._entries: "OrderedDict[bytes, _PoolEntry]" = OrderedDict()
        self._free = list(range(self.npages - 1, -1, -1))  # pop(): lowest
        self._array = None  # the device's pages, from the first batch on
        self._resident_bytes = 0  # page-rounded (the budget currency)
        self._tick = 0
        # always-on accounting (admin `codec info` pool block + bench)
        self.hits = 0
        self.misses = 0
        self.hit_bytes = 0
        self.miss_bytes = 0
        self.prefetch_bytes = 0
        self.adopted = 0
        self.evicted_lru = 0
        self.invalidated = 0
        if metrics is not None:
            self.m_hit = metrics.counter(
                "pool_hit_bytes_total",
                "Block bytes served from device-resident pool pages "
                "(zero link bytes moved; with pool_miss_bytes_total "
                "this attributes every scrubbed byte)")
            self.m_miss = metrics.counter(
                "pool_miss_bytes_total",
                "Block bytes staged over the host-device link because "
                "no pool page held them (adopted into the pool after "
                "the batch verifies)")
            self.m_prefetch = metrics.counter(
                "pool_prefetch_bytes_total",
                "Block bytes staged ahead of need by the scrub "
                "worker's next-range prefetch hint (background-class "
                "link work overlapping the current batch's compute)")
            self.m_evict = metrics.counter(
                "pool_evict_total",
                "Pool pages released, by reason (lru = scrub-cycle "
                "eviction under the pool_mib budget, invalidate = "
                "synchronous delete/quarantine/rebalance/overwrite "
                "eviction, replace = re-adoption of a resident hash)")
            metrics.gauge(
                "pool_resident_bytes",
                "Page-rounded bytes currently held in device-resident "
                "pool pages (bounded by [codec] pool_mib)",
                fn=lambda: float(self._resident_bytes))
            metrics.gauge(
                "pool_pages",
                "Device pages currently held by the block pool",
                fn=lambda: float(self._resident_bytes // self.page_bytes))
        else:
            self.m_hit = self.m_miss = None
            self.m_prefetch = self.m_evict = None

    # --- capability probing -------------------------------------------------

    @classmethod
    def supports_device(cls, device) -> bool:
        """The device implements the pool API: the page array, the
        resident-lane scrub submission that composes from it, the
        adoption into it and the readback."""
        return all(hasattr(device, name) for name in (
            "pool_alloc", "scrub_encode_submit_resident", "pool_adopt",
            "pool_read"))

    # --- geometry -----------------------------------------------------------

    def pages_for(self, length: int) -> int:
        """Pages a block of `length` bytes spans (ragged occupancy: the
        tail page is partially filled)."""
        return max(1, -(-int(length) // self.page_bytes))

    def bytes_for(self, length: int) -> int:
        """Page-rounded budget charge for a block of `length` bytes."""
        return self.pages_for(length) * self.page_bytes

    def row_index(self, lanes: int, cols: int, rows) -> np.ndarray:
        """The index vector of the two pool programs for a `lanes ×
        cols` batch, whose rows they see as `pages_for(cols)` pages each
        (a row narrower than a page is one, zero-extended): a slot for
        every page of every row, in row order, the sentinel where there
        is none (for compose a miss lane, a pad lane, the pages past a
        short block; for adopt whatever is not adopted).  `rows`:
        (lane, slots) of the lanes that have slots."""
        per = self.pages_for(cols)
        index = np.full((lanes * per,), self.npages, dtype=np.int32)
        for lane, slots in rows:
            index[lane * per:lane * per + len(slots)] = slots
        return index

    def array(self):
        """The device's page array (made on first use)."""
        with self._lock:
            return self._array_locked()

    def _array_locked(self):
        if self._array is None:
            self._array = self.device.pool_alloc(self.npages,
                                                 self.page_bytes)
        return self._array

    # --- the scrub-cycle clock ----------------------------------------------

    def tick(self) -> int:
        """Advance the eviction clock by one scrub cycle (called at
        every scrub pass start, block/repair.py)."""
        with self._lock:
            self._tick += 1
            return self._tick

    # --- lookup / adopt -----------------------------------------------------

    def lookup(self, key: bytes, length: int) -> Optional[_PoolEntry]:
        """The entry for `key` if resident with a matching length,
        bumping it to most-recently-used in the current cycle.  A
        length mismatch (impossible for content-addressed blocks
        unless something rewrote the store behind the pool's back) is
        treated as a miss AND evicts the suspect entry — serving it
        would at best fail the device verify, at worst mask a bug."""
        key = bytes(key)
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                return None
            if e.length != int(length):
                self._drop_locked(key, "invalidate")
                logger.warning(
                    "pool entry %s length %d != looked-up %d: evicted",
                    key.hex()[:16], e.length, length)
                return None
            e.tick = self._tick
            self._entries.move_to_end(key)
            return e

    def contains(self, key: bytes) -> bool:
        """Residency check with NO LRU side effect (the feeder's
        gate-refresh short-circuit and the prefetch filter)."""
        with self._lock:
            return bytes(key) in self._entries

    def contains_all(self, keys) -> bool:
        """True when every key is resident (and there is at least one):
        a batch a pool hit would fully satisfy."""
        with self._lock:
            if not self._entries:
                return False
            got_any = False
            for k in keys:
                got_any = True
                if bytes(k) not in self._entries:
                    return False
            return got_any

    def adopt_lanes(self, batch, lanes: int, cols: int,
                    verified: Sequence[Tuple[int, bytes, int]]
                    ) -> Tuple[int, int]:
        """Admit the VERIFIED miss lanes of one composed `lanes × cols`
        device batch — (lane, key, length) each — with one device
        program: every lane gets its slots from the free list, evicting
        LRU entries until the budget fits, and the batch's pages are
        scattered into them.  A block bigger than the whole budget is
        refused.  Re-adopting a resident hash replaces the old pages —
        the overwrite shape of strict invalidation.  → (lanes, pages)
        adopted."""
        with self._lock:
            made = []
            for lane, key, n in verified:
                e = self._reserve_locked(bytes(key), int(n))
                if e is not None:
                    made.append((lane, e))
            # a batch larger than the budget evicts its own first lanes
            # for its last: what is still in the table holds its slots
            # alone, so no slot is written twice
            made = [(lane, e) for lane, e in made
                    if self._entries.get(e.key) is e]
            if not made:
                return 0, 0
            dst = self.row_index(lanes, cols,
                                 [(lane, e.slots) for lane, e in made])
            try:
                self._array = self.device.pool_adopt(
                    self._array_locked(), batch, dst)
            except BaseException:
                # a slot that was not written must not be served
                for _lane, e in made:
                    self._drop_locked(e.key, "invalidate")
                raise
            self.adopted += len(made)
            return len(made), sum(len(e.slots) for _lane, e in made)

    def _reserve_locked(self, key: bytes, length: int
                        ) -> Optional[_PoolEntry]:
        """The page-table half of an adoption: `key`'s entry with slots
        of its own, or None (refused)."""
        need = self.pages_for(length)
        if need > self.npages:
            if self.obs is not None:
                self.obs.event("pool_refuse", reason="over_budget",
                               nbytes=need * self.page_bytes)
            return None
        if key in self._entries:
            self._drop_locked(key, "replace")
        while len(self._free) < need:
            self._drop_locked(next(iter(self._entries)), "lru")
            self.evicted_lru += 1
        slots = tuple(self._free.pop() for _ in range(need))
        e = self._entries[key] = _PoolEntry(key, length, slots, self._tick)
        self._resident_bytes += need * self.page_bytes
        return e

    def _drop_locked(self, key: bytes, reason: str) -> None:
        e = self._entries.pop(key, None)
        if e is None:
            return
        self._resident_bytes -= len(e.slots) * self.page_bytes
        # the slots are free for a later adopt; their bytes stay until
        # it overwrites them, and nothing reads them meanwhile
        self._free.extend(reversed(e.slots))
        if self.m_evict is not None:
            self.m_evict.inc(reason=reason)

    # --- strict invalidation ------------------------------------------------

    def invalidate(self, key: bytes, reason: str = "invalidate") -> bool:
        """Synchronously evict `key` — called BEFORE the store acks a
        delete/quarantine/rebalance-drop/overwrite (block/manager.py),
        so the pool can never serve a page for a block the store no
        longer holds.  Returns whether anything was resident."""
        with self._lock:
            present = bytes(key) in self._entries
            if present:
                self._drop_locked(bytes(key), "invalidate")
                self.invalidated += 1
        if present and self.obs is not None:
            self.obs.event("pool_invalidate", reason=reason,
                           hash=bytes(key).hex()[:16])
        return present

    def clear(self) -> None:
        with self._lock:
            for key in list(self._entries):
                self._drop_locked(key, "invalidate")

    # --- byte attribution (the transport's staging loop calls these) --------

    def note_hit(self, nbytes: int) -> None:
        self.hits += 1
        self.hit_bytes += int(nbytes)
        if self.m_hit is not None:
            self.m_hit.inc(int(nbytes))

    def note_miss(self, nbytes: int, prefetch: bool = False) -> None:
        """Miss accounting: a PREFETCH batch's staging is attributed to
        its own family, so pool_hit + pool_miss still equals exactly
        the bytes the scrub itself asked for."""
        if prefetch:
            self.prefetch_bytes += int(nbytes)
            if self.m_prefetch is not None:
                self.m_prefetch.inc(int(nbytes))
            return
        self.misses += 1
        self.miss_bytes += int(nbytes)
        if self.m_miss is not None:
            self.m_miss.inc(int(nbytes))

    # --- readback (tests / smoke: bit-identity proof) -----------------------

    def read(self, key: bytes) -> Optional[bytes]:
        """The resident block's bytes fetched back from its device
        pages (one gather + D2H — test/debug surface, not a data path),
        trimmed to the ragged tail.  None when not resident."""
        with self._lock:
            e = self._entries.get(bytes(key))
            if e is None:
                return None
            array, slots, length = self._array, e.slots, e.length
        return self.device.pool_read(array, slots, length)

    # --- introspection ------------------------------------------------------

    @property
    def resident_bytes(self) -> int:
        return self._resident_bytes

    def stats(self) -> dict:
        with self._lock:
            return {
                "pool_bytes": self.pool_bytes,
                "page_bytes": self.page_bytes,
                "resident_bytes": self._resident_bytes,
                "resident_blocks": len(self._entries),
                "resident_pages": self._resident_bytes // self.page_bytes,
                "tick": self._tick,
                "hits": self.hits,
                "misses": self.misses,
                "hit_bytes": self.hit_bytes,
                "miss_bytes": self.miss_bytes,
                "prefetch_bytes": self.prefetch_bytes,
                "adopted": self.adopted,
                "evicted_lru": self.evicted_lru,
                "invalidated": self.invalidated,
                "prefetch": self.prefetch_enabled,
            }
