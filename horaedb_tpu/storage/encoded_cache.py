"""Tier-2 scan cache: host-RAM per-SST encoded sidecar parts.

The HBM scan cache (storage/scan_cache.py) keys whole segments by their
SST set, so EVERY write or compaction misses the whole segment and
forces a full object-store re-read + re-merge — even when all but one
tiny SST is unchanged (the post-flush cliff).  This cache sits under it
with per-SST granularity:

    tier 1 (HBM)      post-merge windows, key = (segment, SST set, ...)
    tier 2 (host RAM) per-SST EncodedSegment parts, key = immutable SST id
    tier 3 (store)    {id}.enc sidecars / {id}.sst parquet

A tier-1 miss rebuilds windows from tier-2 parts without touching the
object store, and only the SSTs a flush/compaction actually removed
leave tier 2 (`invalidate`) — everything else stays resident.  The WAL
flusher and the compactor hold the freshly-encoded columns in hand at
write time and insert them here (`admit`, write-through), so a query
landing right after a flush reads nothing from the store at all.

Correctness is structural, exactly like tier 1: SST ids are immutable
and never reused, so an entry can never be stale.  Entries hold the
columns of ONE complete SST — block-pruned partial loads are never
admitted (they are row subsets tied to one predicate).  What such a
load learns of the SST itself is kept, though: its footer
(sidecar.SstFooter: parsed header, block statistics, dictionaries; a
few KB against a part's MBs), by the same id, charged to the same
budget and dropped with the SST, so that a store many times this cache
pays a point query's column ranges and not its metadata again.

The cache also owns the negative path: SST ids known to lack a usable
sidecar (pre-feature files, failed best-effort writes) are memoized
per id so cold scans skip doomed GETs.  Negative entries are strictly
per-SST — a cross-SST assembly failure must NOT poison its siblings
(see read._read_segment_encoded).

Ownership: event-loop owned, like tier 1 — gets/puts happen on the
reader's loop; the CPU-heavy deserialize runs on worker pools before
insertion.  No lock.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Optional

from horaedb_tpu.utils import registry, trace_add

# tier-labeled children of the shared scan-cache families (the hbm
# tier lives in storage/scan_cache.py); admissions/invalidated are
# tier-2-only concepts but carry the label for a uniform query surface
_HITS = registry.counter(
    "scan_cache_hits_total",
    "scan cache hits by tier").labels(tier="tier2")
_MISSES = registry.counter(
    "scan_cache_misses_total",
    "scan cache misses by tier").labels(tier="tier2")
_EVICTIONS = registry.counter(
    "scan_cache_evictions_total",
    "scan cache evictions by tier").labels(tier="tier2")
_ADMISSIONS = registry.counter(
    "scan_cache_admissions_total",
    "write-through insertions from flush/compaction sidecar builds"
    ).labels(tier="tier2")
_INVALIDATED = registry.counter(
    "scan_cache_invalidated_total",
    "cache entries dropped because their SST was deleted"
    ).labels(tier="tier2")
_BYTES = registry.gauge(
    "scan_cache_bytes",
    "resident cache bytes by tier (host RAM)").labels(tier="tier2")
# the footers kept beside the parts: their own label, so that
# tier="tier2" stays "a segment's rows served from host RAM"
_FOOTER_HITS = registry.counter(
    "scan_cache_hits_total",
    "scan cache hits by tier").labels(tier="tier2_footer")
_FOOTER_MISSES = registry.counter(
    "scan_cache_misses_total",
    "scan cache misses by tier").labels(tier="tier2_footer")

# negative-entry bound: clear-all on overflow (re-learning a miss costs
# one GET; unbounded growth costs RAM forever)
_MISSING_MAX = 65536


def _base_size(arr) -> Optional[tuple[int, int]]:
    """(id, byte size) of the buffer an array view PINS, or None for an
    owning array.  np.frombuffer views keep the whole downloaded blob
    alive, so the LRU must charge the blob — charging only the view's
    nbytes would let resident RAM exceed the configured budget by the
    blob-to-wanted-columns ratio."""
    base = getattr(arr, "base", None)
    while isinstance(base, type(arr)) and base.base is not None:
        base = base.base  # view-of-view: walk to the owning object
    if base is None:
        return None
    try:
        return id(base), memoryview(base).nbytes
    except TypeError:
        return id(base), int(getattr(base, "nbytes", arr.nbytes))


def _part_nbytes(cols: dict) -> int:
    """Host bytes one {name: (arr, enc)} part keeps RESIDENT: each
    distinct pinned base buffer counted once at its full size, owning
    arrays at their own size, plus dictionary payloads (object
    dictionaries count their string/bytes content, not just the
    pointer array)."""
    total = 0
    bases: dict[int, int] = {}
    for arr, enc in cols.values():
        pinned = _base_size(arr)
        if pinned is not None:
            bases[pinned[0]] = pinned[1]
        else:
            total += int(arr.nbytes)
        d = getattr(enc, "dictionary", None)
        if d is not None:
            if d.dtype == object:
                total += int(d.nbytes) + sum(len(v) for v in d)
            else:
                pinned = _base_size(d)
                if pinned is not None:
                    bases[pinned[0]] = pinned[1]
                else:
                    total += int(d.nbytes)
    return total + sum(bases.values())


class EncodedSegmentCache:
    """Byte-LRU of per-SST encoded parts + the per-SST negative memo.

    An entry maps one immutable SST id to {column name: (unpadded np
    array, ColumnEncoding)} plus the SST's row count.  `get` hits only
    when every wanted column is resident; inserts for an id MERGE
    column sets, so a projection-narrow read widens the entry instead
    of replacing it."""

    def __init__(self, max_bytes: int, write_through: bool = True):
        self.max_bytes = max_bytes
        self.write_through = write_through
        # sst_id -> (cols dict, n_rows, charged bytes)
        self._entries: "OrderedDict[int, tuple[dict, int, int]]" = \
            OrderedDict()
        # sst_id -> (sidecar.SstFooter, charged bytes)
        self._footers: "OrderedDict[int, tuple]" = OrderedDict()
        self._total_bytes = 0
        self._missing: set[int] = set()
        self._failed_assemblies: set[frozenset] = set()
        self.hits = 0
        self.misses = 0
        self.admissions = 0
        self.evictions = 0
        self.invalidated = 0
        self.footer_hits = 0
        self.footer_misses = 0

    @property
    def enabled(self) -> bool:
        return self.max_bytes > 0

    @property
    def total_bytes(self) -> int:
        return self._total_bytes

    def __len__(self) -> int:
        return len(self._entries)

    # ---- read path --------------------------------------------------------

    def get(self, sst_id: int, want) -> Optional[tuple[dict, int]]:
        """({name: (arr, enc)} restricted to `want`, n_rows) when every
        wanted column is resident, else None.  Counts a miss even when
        disabled so operators see the tier working (or not) on
        /metrics."""
        entry = self._entries.get(sst_id)
        if entry is None or not set(want) <= entry[0].keys():
            self.misses += 1
            _MISSES.inc()
            trace_add("cache_tier2_misses")
            return None
        self._entries.move_to_end(sst_id)
        self.hits += 1
        _HITS.inc()
        cols, n, nbytes = entry
        trace_add("cache_tier2_hits")
        trace_add("cache_tier2_bytes", nbytes)
        return {nm: cols[nm] for nm in want}, n

    def peek(self, sst_id: int, want) -> bool:
        """Stats-free residency probe: True iff get() would hit.  No
        LRU bump, no hit/miss counters, no trace attribution — the
        scan pipeline's is-it-worth-it probe runs this over every
        to-read segment and must not distort cache telemetry (the real
        read that follows does the counting)."""
        entry = self._entries.get(sst_id)
        return entry is not None and set(want) <= entry[0].keys()

    def get_footer(self, sst_id: int):
        """The SST's footer as far as earlier pruned loads fetched it
        (sidecar.load_sst_encoded fills it further), or None."""
        entry = self._footers.get(sst_id)
        if entry is None:
            self.footer_misses += 1
            _FOOTER_MISSES.inc()
            return None
        self._footers.move_to_end(sst_id)
        self.footer_hits += 1
        _FOOTER_HITS.inc()
        return entry[0]

    def put_footer(self, sst_id: int, footer) -> None:
        """Keep (or re-charge, after a load added sections to it) the
        footer of one SST."""
        if not self.enabled:
            return
        old = self._footers.pop(sst_id, None)
        if old is not None:
            self._account(-old[1])
        nbytes = footer.nbytes
        if nbytes > self.max_bytes:
            return
        self._footers[sst_id] = (footer, nbytes)
        self._account(nbytes)
        self._evict()

    def put(self, sst_id: int, cols: dict, n_rows: int) -> None:
        """Read-path insert of a COMPLETE part (all rows of the SST for
        these columns).  ZERO-COPY: the arrays are deserialize's views
        into the downloaded blob, which they keep alive.  The charged
        bytes are the wanted columns' + dictionaries' — a slight
        undercount (the blob's header and block-stats sections ride
        along unpinned-by-name), bounded small because sidecars only
        store the columns scans read and `want` includes essentially
        all of them.  Copying here measurably slowed true-cold scans
        (one extra full-segment memcpy per cold query)."""
        if not self.enabled:
            return
        self._insert(sst_id, dict(cols), n_rows)

    # ---- write path -------------------------------------------------------

    def admit(self, sst_id: int, cols: dict, n_rows: int) -> bool:
        """Write-through insert from the flush/compaction sidecar build
        — the ONE admission door for writers (tools/lint.py rejects
        direct put/get outside the reader).  The arrays are freshly
        encoded (not blob views), so no copy is taken.  Returns whether
        the entry was admitted."""
        if not self.enabled or not self.write_through:
            return False
        self._insert(sst_id, dict(cols), n_rows)
        if sst_id in self._entries:
            self.admissions += 1
            _ADMISSIONS.inc()
            return True
        return False

    def _insert(self, sst_id: int, cols: dict, n_rows: int) -> None:
        old = self._entries.pop(sst_id, None)
        if old is not None:
            self._account(-old[2])
            merged = dict(old[0])
            merged.update(cols)  # widen: keep columns the new part lacks
            cols = merged
        nbytes = _part_nbytes(cols)
        if nbytes > self.max_bytes:
            return
        self._entries[sst_id] = (cols, n_rows, nbytes)
        self._account(nbytes)
        self._missing.discard(sst_id)
        self._evict()

    def _evict(self) -> None:
        """Back under the budget: parts in LRU order, and footers only
        once no part is left (a footer is a part's ten-thousandth and
        costs a pruned load eight store calls to learn again)."""
        while self._total_bytes > self.max_bytes:
            if self._entries:
                _, (_, _, evicted) = self._entries.popitem(last=False)
            elif self._footers:
                _, (_, evicted) = self._footers.popitem(last=False)
            else:
                return
            self._account(-evicted)
            self.evictions += 1
            _EVICTIONS.inc()

    # ---- lifecycle --------------------------------------------------------

    def invalidate(self, sst_ids) -> int:
        """Drop entries whose SSTs a compaction/GC just deleted.  Purely
        memory hygiene — ids are immutable so stale entries are
        impossible — but deleted SSTs will never be read again and must
        not squat in the budget.  Their negative memos drop too (the
        ids are gone for good; keeping tombstones wastes the bound)."""
        n = 0
        for sid in sst_ids:
            entry = self._entries.pop(sid, None)
            if entry is not None:
                self._account(-entry[2])
                n += 1
            footer = self._footers.pop(sid, None)
            if footer is not None:
                self._account(-footer[1])
            self._missing.discard(sid)
        if n:
            self.invalidated += n
            _INVALIDATED.inc(n)
        return n

    def clear(self) -> None:
        """Benchmark/test hook (true-cold legs); production invalidation
        is per-SST via invalidate().  Composition-failure memos drop
        too (derived state); per-SST `missing` memos survive — they
        record broken OBJECTS, not cache state."""
        self._account(-self._total_bytes)
        self._entries.clear()
        self._footers.clear()
        self._failed_assemblies.clear()

    def _account(self, delta: int) -> None:
        self._total_bytes += delta
        _BYTES.inc(delta)  # delta-based: the gauge aggregates instances

    # ---- negative path ----------------------------------------------------

    def mark_missing(self, sst_id: int) -> None:
        """Memoize one SST id as permanently sidecar-less.  STRICTLY per
        id: callers must only mark ids whose OWN sidecar failed (absent
        or unparseable) — never siblings of a cross-SST failure."""
        if len(self._missing) > _MISSING_MAX:
            self._missing.clear()
        self._missing.add(sst_id)

    def is_missing(self, sst_id: int) -> bool:
        return sst_id in self._missing

    def mark_assembly_failed(self, sst_ids) -> None:
        """Memoize one COMPOSITION (frozenset of SST ids) whose
        cross-SST assembly failed — e.g. a union dictionary at the pad
        sentinel.  Objects are immutable so the failure is permanent
        for this exact set, and later cold scans skip its sidecar GETs
        — but the member ids stay individually valid: any OTHER
        composition (post-compaction, other segments) tries afresh.
        This replaces the old whole-set `missing` memo, which poisoned
        every member forever."""
        if len(self._failed_assemblies) > _MISSING_MAX:
            self._failed_assemblies.clear()
        self._failed_assemblies.add(frozenset(sst_ids))

    def is_assembly_failed(self, sst_ids) -> bool:
        return frozenset(sst_ids) in self._failed_assemblies

    # ---- observability ----------------------------------------------------

    def stats(self) -> dict:
        return {
            "entries": len(self._entries),
            "bytes": self._total_bytes,
            "max_bytes": self.max_bytes,
            "write_through": self.write_through,
            "hits": self.hits,
            "misses": self.misses,
            "admissions": self.admissions,
            "evictions": self.evictions,
            "invalidated": self.invalidated,
            "footers": len(self._footers),
            "footer_hits": self.footer_hits,
            "footer_misses": self.footer_misses,
            "negative_entries": len(self._missing),
            "failed_assemblies": len(self._failed_assemblies),
        }
