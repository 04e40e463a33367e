"""The scan cache (tier "hbm"): one class, two accounts.

The north star keeps the scan path operating "over HBM-resident
RecordBatches" — steady-state queries should not re-decode Parquet,
re-encode columns, or re-run the merge sort.  This cache stores each
segment's POST-MERGE windows keyed by

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
narrowed or uploaded.

The two kinds live in different memories, so each has an ACCOUNT of its
own: a byte budget, an LRU order, and its own evictions and declines.

  windows  the merge runs on the host, so a window's columns are numpy
           arrays in HOST RAM; what it keeps on the device is its memo
           (device column copies, gid arrays), charged up front as an
           allowance per window.  Budget: the reader's `cache_bytes`
           ([scan] cache_max_bytes, or cache_max_rows x 32 B).
  slice    a SegmentSlice's padded columns are DEVICE arrays.  Budget:
           a share of what the device reports (the reader derives it,
           storage/read.py), or `cache_bytes` where it reports nothing.

A slice never evicts a window and a window never evicts a slice.

Eviction is LRU by the account's cached BYTES — a window's column
buffers across their real widths plus an allowance for the per-window
aggregation memos (each memo slot can hold a capacity-sized gid
array), a slice's capacity x 4 B x columns; dropping an entry releases
its device buffers through JAX's reference counting.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Optional

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
# the tier's two accounts (module docstring), one child per kind; the
# tier-wide families above stay the sum over both
ACCOUNT_KINDS = ("windows", "slice")
_ACCOUNT_BYTES = registry.gauge(
    "scan_cache_account_bytes",
    "charged bytes of one scan-cache account, summed over the open "
    "readers")
_ACCOUNT_ENTRIES = registry.gauge(
    "scan_cache_account_entries",
    "entries of one scan-cache account, summed over the open readers")
_ACCOUNT_BUDGET = registry.gauge(
    "scan_cache_account_budget_bytes",
    "byte budget of one scan-cache account per reader (last reader "
    "opened; 0 once it closed)")
_ACCOUNT_EVENTS = registry.counter(
    "scan_cache_account_events_total",
    "entries an account's LRU threw out for room (evicted) and "
    "entries larger than its whole budget that it never took "
    "(declined)")

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
                 evictions=(), declined=None, gauges=None,
                 trace_tier: str = ""):
        self.max_bytes = max_bytes
        self._entries: "OrderedDict[CacheKey, tuple[object, int]]" = \
            OrderedDict()
        self._total_bytes = 0
        self._hits = hits
        self._misses = misses
        # a tuple: every counter in it counts the same evictions (a
        # tier's total beside an account's own)
        self._evictions = evictions
        self._declined = declined
        # (bytes, entries): process-global gauges moved by this LRU's
        # deltas, so several instances sum on them and clear() takes
        # this one's share off
        self._gauges = gauges
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.declined = 0
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

    def _charge(self, nbytes: int, entries: int) -> None:
        self._total_bytes += nbytes
        if self._gauges is not None:
            self._gauges[0].inc(nbytes)
            self._gauges[1].inc(entries)

    def put(self, key: CacheKey, value, nbytes: int) -> None:
        if self.max_bytes <= 0 or nbytes > self.max_bytes:
            # nothing is evicted for what cannot stay
            self.declined += 1
            if self._declined is not None:
                self._declined.inc()
            return
        if key in self._entries:
            self._charge(-self._entries.pop(key)[1], -1)
        self._entries[key] = (value, nbytes)
        self._charge(nbytes, 1)
        while self._total_bytes > self.max_bytes and self._entries:
            _, (_, evicted) = self._entries.popitem(last=False)
            self._charge(-evicted, -1)
            self.evictions += 1
            for counter in self._evictions:
                counter.inc()

    def clear(self) -> None:
        self._charge(-self._total_bytes, -len(self._entries))
        self._entries.clear()

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def total_bytes(self) -> int:
        return self._total_bytes

    def values(self):
        """Resident values in LRU order (no recency update) — the
        reader's HBM-eviction sweep walks cached windows through this."""
        return [v for v, _nbytes in self._entries.values()]


class ScanCache(ByteLRU):
    """The tier's one cache with its two accounts (module docstring).
    The inherited ByteLRU core IS the windows account — `get`, `put`,
    `values`, `max_bytes`, `total_bytes`, `len` speak of windows, as
    they always did; the slice account is a second core beside it,
    reached through `get_slice` / `put_slice` / `slices`.  Hits, misses
    and evictions of both count on the tier's scan_cache_* families
    (and the `cache_hbm_*` trace counters); bytes, evictions and
    declines also per account."""

    def __init__(self, max_bytes: int,
                 slice_max_bytes: Optional[int] = None):
        """`slice_max_bytes` None: the slice account gets the windows'
        budget (a budget each, of one size)."""
        if slice_max_bytes is None:
            slice_max_bytes = max_bytes
        super().__init__(max_bytes, **self._account("windows"))
        self.slice_account = ByteLRU(slice_max_bytes,
                                     **self._account("slice"))
        self._publish_budgets(max_bytes, slice_max_bytes)

    @staticmethod
    def _account(kind: str) -> dict:
        labels = {"tier": "hbm", "kind": kind}
        return dict(
            hits=_HITS, misses=_MISSES,
            evictions=(_EVICTIONS, _ACCOUNT_EVENTS.labels(
                event="evicted", **labels)),
            declined=_ACCOUNT_EVENTS.labels(event="declined", **labels),
            gauges=(_ACCOUNT_BYTES.labels(**labels),
                    _ACCOUNT_ENTRIES.labels(**labels)),
            trace_tier="hbm")

    @staticmethod
    def _publish_budgets(windows: int, slices: int) -> None:
        for kind, budget in zip(ACCOUNT_KINDS, (windows, slices)):
            _ACCOUNT_BUDGET.labels(tier="hbm", kind=kind).set(budget)

    def put(self, key: CacheKey, windows: list) -> None:  # type: ignore[override]
        super().put(key, windows, windows_nbytes(windows))

    def get_slice(self, key: CacheKey):
        return self.slice_account.get(key)

    def put_slice(self, key: CacheKey, seg_slice) -> None:
        """Admit a device-decode SegmentSlice to the slice account,
        charged at the device bytes of its padded columns (capacity x
        4 B x columns); one over that account's whole budget is
        declined, and no window is ever evicted for a slice."""
        self.slice_account.put(key, seg_slice, seg_slice.nbytes)

    def slices(self) -> list:
        """The resident SegmentSlices, in LRU order."""
        return self.slice_account.values()

    def drop_slices(self) -> None:
        """Release every SegmentSlice's device arrays and keep the
        windows: the reader's HBM-evicted state (tests, benchmarks)."""
        self.slice_account.clear()

    def account_stats(self) -> dict:
        """Per account: budget, charged bytes, entries, and what its
        LRU threw out or never took (the /stats section)."""
        return {kind: {"budget_bytes": lru.max_bytes,
                       "bytes": lru.total_bytes,
                       "entries": len(lru),
                       "evicted": lru.evictions,
                       "declined": lru.declined}
                for kind, lru in zip(ACCOUNT_KINDS,
                                     (self, self.slice_account))}

    def clear(self) -> None:
        """Drop every entry of both accounts (releases device buffers
        via refcounting).  Used by cold-path benchmarks and tests;
        production invalidation is structural (SST-set keys), never
        explicit."""
        super().clear()
        self.slice_account.clear()

    def close(self) -> None:
        """clear() and the budget gauges back to 0: a closed reader
        leaves no phantom budget (last-writer semantics, like every
        process-global gauge that is not a sum)."""
        self.clear()
        self._publish_budgets(0, 0)
