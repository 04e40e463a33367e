"""HBM-resident scan cache.

The north star keeps the scan path operating "over HBM-resident
RecordBatches" — steady-state queries should not re-decode Parquet,
re-encode columns, or re-run the merge sort.  This cache stores each
segment's POST-MERGE device windows keyed by

    (segment_start, frozenset of SST ids, column tuple)

so correctness falls out structurally: any write or compaction changes
the segment's SST set and therefore misses the cache (no explicit
invalidation hooks, no staleness).  Predicates and aggregation run AFTER
the merge, so one cached entry serves every query shape over the same
data.

A device-decode plan's segments cache differently (ops/device_decode.py):
their rows never become windows, so the entry is the segment's
SegmentSlice — the rows its Eq/In leaves admit, padded, ON THE DEVICE,
with the layout and route that go with them — keyed by the same
(segment_start, SST ids) and, as columns, the plan's columns plus the
canonical form of those leaves.  A later query with the same key
dispatches from it whatever its window, with nothing read, assembled,
narrowed or uploaded.  Both kinds share the one byte budget and the one
LRU order.

Eviction is LRU by total cached BYTES — column buffers across their
real widths plus an allowance for the per-window aggregation memos
(each memo slot can hold a capacity-sized gid array); dropping an entry
releases its device buffers through JAX's reference counting.
"""

from __future__ import annotations

from collections import OrderedDict

from horaedb_tpu.utils import registry, trace_add

# shared labeled families across the cache tiers (tier="hbm" here,
# tier="tier2" in storage/encoded_cache.py) — one series per tier
# instead of per-tier metric names
_HITS = registry.counter("scan_cache_hits_total",
                         "scan cache hits by tier").labels(tier="hbm")
_MISSES = registry.counter("scan_cache_misses_total",
                           "scan cache misses by tier").labels(tier="hbm")
_EVICTIONS = registry.counter("scan_cache_evictions_total",
                              "scan cache evictions by tier"
                              ).labels(tier="hbm")

CacheKey = tuple

# DeviceBatch.memo allowance multiplier: the reader's byte-bounded memo
# store (storage.read._memo_store) caps each window's memo values at
# MEMO_SLOTS * (capacity*4 + 128) REAL bytes — entries vary in size (a
# window_groups gid is 4 B/row, a dev_cols entry 12 B/row, i.e. three
# "slots" worth), so at the current value the worst-case resident pair
# (gid + dev_cols = 16 B/row) fits exactly.  Lowering MEMO_SLOTS below
# 3 would make a single dev_cols entry exceed the budget and thrash.
MEMO_SLOTS = 4


def segment_cache_key(segment_start: int, sst_ids, columns) -> CacheKey:
    return (segment_start, frozenset(sst_ids), tuple(columns))


def windows_nbytes(windows: list) -> int:
    """HBM cost of a cached entry: every column buffer at its real
    width, plus the memo allowance per window."""
    total = 0
    for w in windows:
        for col in w.columns.values():
            total += int(col.dtype.itemsize) * w.capacity
        total += MEMO_SLOTS * (w.capacity * 4 + 128)
    return total


class ByteLRU:
    """Byte-budgeted LRU core (event-loop owned — no lock).  Counters
    are the caller's registry counters, so every cache built on this
    core is operator-visible on /metrics."""

    def __init__(self, max_bytes: int, hits=None, misses=None,
                 evictions=None, trace_tier: str = ""):
        self.max_bytes = max_bytes
        self._entries: "OrderedDict[CacheKey, tuple[object, int]]" = \
            OrderedDict()
        self._total_bytes = 0
        self._hits = hits
        self._misses = misses
        self._evictions = evictions
        self.hits = 0
        self.misses = 0
        # per-query attribution name ("cache_<tier>_*" trace counters
        # on the ambient trace); "" = no trace attribution — each LRU
        # built on this core must name its own tier, exactly like it
        # passes its own registry counters
        self.trace_tier = trace_tier

    def get(self, key: CacheKey):
        entry = self._entries.get(key)
        if entry is None:
            self.record_miss()
            return None
        self._entries.move_to_end(key)
        self._count_hit(entry)
        return entry[0]

    def peek_entry(self, key: CacheKey):
        """Stats-free, recency-free lookup.  For callers that must
        VALIDATE an entry before it counts as served (PartsMemo
        coverage): they account the outcome themselves via
        record_hit/record_miss, so a found-but-unusable entry is not
        reported as a hit."""
        entry = self._entries.get(key)
        return None if entry is None else entry[0]

    def record_miss(self) -> None:
        self.misses += 1
        if self._misses is not None:
            self._misses.inc()
        if self.trace_tier:
            trace_add(f"cache_{self.trace_tier}_misses")

    def record_hit(self, key: CacheKey) -> None:
        entry = self._entries.get(key)
        if entry is None:
            return
        self._entries.move_to_end(key)
        self._count_hit(entry)

    def _count_hit(self, entry) -> None:
        self.hits += 1
        if self._hits is not None:
            self._hits.inc()
        if self.trace_tier:
            trace_add(f"cache_{self.trace_tier}_hits")
            trace_add(f"cache_{self.trace_tier}_bytes", entry[1])

    def put(self, key: CacheKey, value, nbytes: int) -> None:
        if self.max_bytes <= 0 or nbytes > self.max_bytes:
            return
        if key in self._entries:
            self._total_bytes -= self._entries.pop(key)[1]
        self._entries[key] = (value, nbytes)
        self._total_bytes += nbytes
        while self._total_bytes > self.max_bytes and self._entries:
            _, (_, evicted) = self._entries.popitem(last=False)
            self._total_bytes -= evicted
            if self._evictions is not None:
                self._evictions.inc()

    def clear(self) -> None:
        self._entries.clear()
        self._total_bytes = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def total_bytes(self) -> int:
        return self._total_bytes

    def values(self):
        """Resident values in LRU order (no recency update) — the
        reader's HBM-eviction sweep walks cached windows through this."""
        return [v for v, _nbytes in self._entries.values()]

    @property
    def total_bytes(self) -> int:
        return self._total_bytes

    def __len__(self) -> int:
        return len(self._entries)


class ScanCache(ByteLRU):
    """Post-merge window cache (see module docstring): the ByteLRU core
    with window-aware byte accounting and the scan_cache_* counters."""

    def __init__(self, max_bytes: int):
        super().__init__(max_bytes, hits=_HITS, misses=_MISSES,
                         evictions=_EVICTIONS, trace_tier="hbm")

    def put(self, key: CacheKey, windows: list) -> None:  # type: ignore[override]
        super().put(key, windows, windows_nbytes(windows))

    def put_slice(self, key: CacheKey, seg_slice) -> None:
        """Admit a device-decode SegmentSlice, charged at the device
        bytes of its padded columns (capacity x 4 B x columns); one
        over the whole budget is declined like any other entry."""
        super().put(key, seg_slice, seg_slice.nbytes)

    def slices(self) -> list:
        """The resident SegmentSlices (every entry that is not a
        windows list), in LRU order."""
        return [v for v in self.values() if not isinstance(v, list)]

    def drop_slices(self) -> None:
        """Release every SegmentSlice's device arrays and keep the
        windows: the reader's HBM-evicted state (tests, benchmarks)."""
        for key in [k for k, (v, _n) in self._entries.items()
                    if not isinstance(v, list)]:
            self._total_bytes -= self._entries.pop(key)[1]

    def clear(self) -> None:
        """Drop every entry (releases device buffers via refcounting).
        Used by cold-path benchmarks and tests; production invalidation
        is structural (SST-set keys), never explicit."""
        super().clear()
