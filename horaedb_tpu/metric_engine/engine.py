"""MetricEngine: the manager pipeline and the five tables.

Write path (ref: metric_engine README pipeline; bodies built from RFC):
  samples -> MetricManager.populate_metric_ids
          -> IndexManager.populate_series_ids (+ index/series/tags rows)
          -> SampleManager.persist (data table rows)

Tables (RFC:106-137), each a TimeMergeStorage with the same segment
duration — the RFC's `Date` dimension is implied by the segment, so
index entries are re-registered once per (segment, series), exactly how
VictoriaMetrics scopes its inverted index by date:

  metrics {metric_name, field_name | metric_id, field_id, field_type}
  series  {metric_id, tsid | series_key}
  tags    {metric_id, tag_key, tag_value | exists}      (label_values)
  index   {metric_id, tag_key, tag_value, tsid | exists} (inverted index)
  data    {metric_id, tsid, field_id, timestamp | value}

Stage-1 divergence from the RFC, by design: data rows carry plain
(timestamp, value) columns instead of the RFC's opaque 30-minute
compressed chunks (RFC:218-231) — fixed-width columns are what the TPU
scan path wants; the chunk encoding belongs to the Append/BytesMerge
path and can layer on later without changing this API.
"""

from __future__ import annotations

import asyncio
import time
from typing import Optional

import numpy as np
import pyarrow as pa

from horaedb_tpu.common.error import Error, ensure
from horaedb_tpu.common.memledger import ledger as memledger
from horaedb_tpu.objstore import ObjectStore
from horaedb_tpu.ops import And, Eq, In, TimeRangePred
from horaedb_tpu.ops import buckets as buckets_ops
from horaedb_tpu.ops.downsample import ALL_AGGS
from horaedb_tpu.ops.last import LastSpec, combine_fields, last_on_host
from horaedb_tpu.ops.select import SelectSpec, compare
from horaedb_tpu.storage.config import StorageConfig
from horaedb_tpu.storage.read import (AggregateSpec, ScanRequest,
                                      join_on_host)
from horaedb_tpu.storage.scan_cache import ByteLRU
from horaedb_tpu.storage.storage import CloudObjectStorage, WriteRequest
from horaedb_tpu.storage.types import TimeRange, Timestamp
from horaedb_tpu.utils import registry, span, span_note
from horaedb_tpu.metric_engine.types import (
    Sample,
    field_id_of,
    metric_id_of,
    series_key_of,
    tsid_of,
    tsids_of_keys,
)

_TABLE_SCHEMAS = {
    "metrics": (pa.schema([
        ("metric_name", pa.string()), ("field_name", pa.string()),
        ("metric_id", pa.uint64()), ("field_id", pa.uint64()),
        ("field_type", pa.int32()),
    ]), 2),
    "series": (pa.schema([
        ("metric_id", pa.uint64()), ("tsid", pa.uint64()),
        ("series_key", pa.binary()),
    ]), 2),
    "tags": (pa.schema([
        ("metric_id", pa.uint64()), ("tag_key", pa.string()),
        ("tag_value", pa.string()), ("exists", pa.int32()),
    ]), 3),
    "index": (pa.schema([
        ("metric_id", pa.uint64()), ("tag_key", pa.string()),
        ("tag_value", pa.string()), ("tsid", pa.uint64()),
        ("exists", pa.int32()),
    ]), 4),
    "data": (pa.schema([
        ("metric_id", pa.uint64()), ("tsid", pa.uint64()),
        ("field_id", pa.uint64()), ("timestamp", pa.int64()),
        ("value", pa.float64()),
    ]), 4),
}

# chunked data table (RFC:218-231): (ts, value) pairs batch-encoded into
# opaque payloads, one row per (series, field, chunk window); Append mode
# so the BytesMerge path concatenates same-key payloads across files
_CHUNKED_DATA_SCHEMA = (pa.schema([
    ("metric_id", pa.uint64()), ("tsid", pa.uint64()),
    ("field_id", pa.uint64()), ("chunk_ts", pa.int64()),
    ("payload", pa.binary()),
]), 4)

FIELD_TYPE_FLOAT = 0
# keep per-segment registration dedup state for this many most-recently-
# USED segments (LRU): live ingest and steady backfill each keep their
# working set warm without unbounded growth
_SEEN_SEGMENTS_KEPT = 4


async def _collect(stream) -> list[pa.RecordBatch]:
    return [b async for b in stream]


def _unique_pairs(major, minor):
    """np.unique over (major, minor) int pairs, lexicographic order.

    Packs both (rebased to their minima) into ONE int64 when ranges
    allow — `np.unique(..., axis=0)` argsorts a structured view, which
    measured 2x the whole bulk-write numpy time at 2M rows; the
    structured path remains as the overflow fallback.  Returns
    (uniq_major, uniq_minor, first_index, inverse)."""
    import numpy as np

    maj = np.asarray(major).astype(np.int64, copy=False)
    mino = np.asarray(minor).astype(np.int64, copy=False)
    if len(maj) == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z, z, z
    mlo, nlo = int(maj.min()), int(mino.min())
    span = int(mino.max()) - nlo + 1
    if (int(maj.max()) - mlo + 1) * span < 2**62:
        packed = (maj - mlo) * np.int64(span) + (mino - nlo)
        u, first, inv = np.unique(packed, return_index=True,
                                  return_inverse=True)
        return u // span + mlo, u % span + nlo, first, inv
    mat = np.stack([maj, mino], axis=1)
    up, first, inv = np.unique(mat, axis=0, return_index=True,
                               return_inverse=True)
    return up[:, 0], up[:, 1], first, inv.reshape(-1)


def _empty_result() -> pa.Table:
    return pa.table({"tsid": pa.array([], type=pa.uint64()),
                     "timestamp": pa.array([], type=pa.int64()),
                     "value": pa.array([], type=pa.float64())})


class _SegmentSeen:
    """Bounded (segment -> seen keys) registration cache.  Keys are added
    only AFTER the registration write succeeds, so a failed write is
    retried on the next ingest instead of being skipped forever.

    Eviction is RECENCY-based (LRU on read AND write), not
    newest-segment-by-key: a steady backfill stream into old segments
    keeps those segments' entries alive, instead of missing the cache on
    every batch and rewriting metrics/series/index rows each time."""

    def __init__(self, keep: int = _SEEN_SEGMENTS_KEPT):
        from collections import OrderedDict

        self._by_segment: "OrderedDict[int, set]" = OrderedDict()
        self._keep = keep

    def __contains__(self, seg_key: tuple) -> bool:
        seg, key = seg_key
        entry = self._by_segment.get(seg)
        if entry is None:
            return False
        self._by_segment.move_to_end(seg)
        return key in entry

    def add(self, seg: int, key) -> None:
        if seg in self._by_segment:
            self._by_segment.move_to_end(seg)
        self._by_segment.setdefault(seg, set()).add(key)
        while len(self._by_segment) > self._keep:
            self._by_segment.popitem(last=False)


class MetricManager:
    """name -> MetricId resolution + metrics-table registration
    (ref: metric/mod.rs:25-50, body from RFC)."""

    def __init__(self, table: CloudObjectStorage, segment_ms: int):
        self.table = table
        self.segment_ms = segment_ms
        self._seen = _SegmentSeen()
        self._resolve_cache: dict[str, tuple[int, float]] = {}
        self._known_fields: dict[str, frozenset] = {}

    async def populate_metric_ids(self, samples: list[Sample]) -> None:
        by_seg: dict[int, dict] = {}
        for s in samples:
            s.name_id = metric_id_of(s.name)
            seg = int(Timestamp(s.timestamp).truncate_by(self.segment_ms))
            key = (s.name, s.field_name)
            if (seg, key) not in self._seen:
                by_seg.setdefault(seg, {})[key] = s.name_id
        for seg, items in by_seg.items():
            names = [k[0] for k in items]
            fnames = [k[1] for k in items]
            batch = pa.record_batch(
                [pa.array(names),
                 pa.array(fnames),
                 pa.array(list(items.values()), type=pa.uint64()),
                 pa.array([field_id_of(f) for f in fnames], type=pa.uint64()),
                 pa.array([FIELD_TYPE_FLOAT] * len(items), type=pa.int32())],
                schema=self.table.schema().user_schema)
            # registration rows cover the WHOLE segment so any query window
            # inside the segment finds them (Date == segment, RFC:104)
            await self.table.write(WriteRequest(
                batch, TimeRange.new(seg, seg + self.segment_ms)))
            # mark seen only after a durable write — retries must re-register
            for key in items:
                self._seen.add(seg, key)

    # positive name->id resolutions are cached briefly: the mapping is
    # immutable once registered, so the only staleness is a metric whose
    # data fully expired still resolving for up to the TTL — its query
    # returns empty grids either way.  Negatives are NOT cached (a
    # concurrent first write must become visible immediately).
    _RESOLVE_TTL_S = 10.0

    async def resolve(self, metric_name: str,
                      time_range: TimeRange) -> Optional[int]:
        """metric name -> id via the metrics table (cache-through)."""
        import time as _time

        now = _time.monotonic()
        hit = self._resolve_cache.get(metric_name)
        if hit is not None and hit[1] > now:
            return hit[0]
        batches = await _collect(self.table.scan(ScanRequest(
            range=time_range, predicate=Eq("metric_name", metric_name))))
        for b in batches:
            if b.num_rows:
                mid = b.column(
                    b.schema.names.index("metric_id"))[0].as_py()
                if len(self._resolve_cache) > 1024:
                    self._resolve_cache.clear()
                self._resolve_cache[metric_name] = (
                    mid, now + self._RESOLVE_TTL_S)
                return mid
        return None

    async def list_metrics(self, time_range: TimeRange) -> list[str]:
        """Distinct metric names active in the window."""
        names: set[str] = set()
        for b in await _collect(self.table.scan(ScanRequest(
                range=time_range))):
            col = b.column(b.schema.names.index("metric_name"))
            names.update(col.to_pylist())
        return sorted(names)

    async def unknown_fields(self, metric_name: str, fields: list[str],
                             time_range: TimeRange) -> list[str]:
        """Those of `fields` that `metric_name` has no registration
        for, in any segment.  A registration is never withdrawn, so
        names once seen are remembered for good; a name not among
        them is looked up, in the window first and then over the whole
        table (only a name no write ever registered pays that, each
        time: a first write shows at once)."""
        known = self._known_fields.get(metric_name, frozenset())
        for rng in (time_range,
                    TimeRange.new(int(Timestamp.MIN), int(Timestamp.MAX))):
            if set(fields) <= known:
                break
            known = known | frozenset(
                await self.list_fields(metric_name, rng))
            if len(self._known_fields) > 1024:
                self._known_fields.clear()
            self._known_fields[metric_name] = known
        return [f for f in fields if f not in known]

    async def list_fields(self, metric_name: str,
                          time_range: TimeRange) -> list[str]:
        """Distinct field names registered for a metric in the window."""
        fields: set[str] = set()
        for b in await _collect(self.table.scan(ScanRequest(
                range=time_range,
                predicate=Eq("metric_name", metric_name)))):
            col = b.column(b.schema.names.index("field_name"))
            fields.update(col.to_pylist())
        return sorted(fields)


# A segment of the index table is written once, when the segment first
# sees a series, and read by every filtered query after that: its
# POSTING LISTS, (metric_id, tag_key, tag_value) -> tsids, are kept per
# segment and keyed by the segment's SST set (SegmentVersion.ids), the
# scan cache's structural invalidation.  One byte-capped LRU an
# IndexManager; a segment whose index SSTs hold more rows than
# _POSTINGS_MAX_ROWS is never built: one build is one unfiltered scan of
# it and a dict of its labels in Python, and at that many rows the
# lists fit the byte cap even if every row is a label of its own
# (131,072 x about 450 B), so nothing under the row cap is built again
# at every query for want of room.
_POSTINGS_MAX_BYTES = 64 << 20
_POSTINGS_MAX_ROWS = 1 << 17
# what a posting list costs beside its tsids and its two strings' text:
# the key tuple, the boxed metric id, two str headers, the array view
# and the dict slot (an estimate; CPython 3.12, 64 bit)
_POSTINGS_KEY_BYTES = 384
_POSTINGS = {
    outcome: registry.counter(
        "index_postings_total",
        "segments of the index table a filtered query resolved its "
        "series in, by how: hit = from the segment's posting lists kept "
        "in memory under its SST set; build = the lists were built "
        "first, by one unfiltered scan of the segment; bypass = by a "
        "filtered scan of the index table (rows in a memtable, or a "
        "segment over the row cap)"
    ).labels(outcome=outcome)
    for outcome in ("hit", "build", "bypass")
}
_POSTINGS_GAUGES = (
    registry.gauge(
        "index_postings_bytes",
        "bytes of the posting lists kept in memory, summed over the "
        "open index managers"),
    registry.gauge(
        "index_postings_segments",
        "segments whose posting lists are kept in memory, summed over "
        "the open index managers"))


def _posting_lists(batches: list[pa.RecordBatch]) -> tuple[dict, int]:
    """One segment's index rows as {(metric_id, tag_key, tag_value):
    its tsids, sorted, as np.uint64} (unique: the segment's merge
    leaves one row a key), and the bytes that holds.  The lists are
    views of one array."""
    tbl = pa.Table.from_batches(batches).combine_chunks()
    if not tbl.num_rows:
        return {}, 0
    mid = tbl.column("metric_id").chunk(0).to_numpy()
    tsid = tbl.column("tsid").chunk(0).to_numpy()
    keys = tbl.column("tag_key").chunk(0).dictionary_encode()
    vals = tbl.column("tag_value").chunk(0).dictionary_encode()
    kc, vc = keys.indices.to_numpy(), vals.indices.to_numpy()
    order = np.lexsort((tsid, vc, kc, mid))
    mid, kc, vc, tsid = mid[order], kc[order], vc[order], tsid[order]
    first = np.ones(len(tsid), dtype=bool)  # of its posting list
    first[1:] = ((mid[1:] != mid[:-1]) | (kc[1:] != kc[:-1])
                 | (vc[1:] != vc[:-1]))
    starts = np.flatnonzero(first)
    ends = np.append(starts[1:], len(tsid))
    key_text, val_text = keys.dictionary.to_pylist(), \
        vals.dictionary.to_pylist()
    lists = {(m, key_text[k], val_text[v]): tsid[s:e]
             for m, k, v, s, e in zip(
                 mid[starts].tolist(), kc[starts].tolist(),
                 vc[starts].tolist(), starts.tolist(), ends.tolist())}
    nbytes = tsid.nbytes + sum(
        _POSTINGS_KEY_BYTES + len(k) + len(v) for _, k, v in lists)
    return lists, nbytes


def _series_lists(batches: list[pa.RecordBatch]) -> tuple[dict, int]:
    """One segment's rows of the series table as {metric_id: its
    tsids, sorted, as np.uint64} (unique: the segment's merge leaves
    one row a series), and the bytes that holds.  The lists are views
    of one array."""
    tbl = pa.Table.from_batches(batches).combine_chunks()
    if not tbl.num_rows:
        return {}, 0
    mid = tbl.column("metric_id").chunk(0).to_numpy()
    tsid = tbl.column("tsid").chunk(0).to_numpy()
    order = np.lexsort((tsid, mid))
    mid, tsid = mid[order], tsid[order]
    starts = np.flatnonzero(np.append(True, mid[1:] != mid[:-1]))
    ends = np.append(starts[1:], len(tsid))
    lists = {m: tsid[s:e] for m, s, e in zip(
        mid[starts].tolist(), starts.tolist(), ends.tolist())}
    return lists, tsid.nbytes + _POSTINGS_KEY_BYTES * len(lists)


class IndexManager:
    """TSID resolution + series/tags/index registration per segment
    (ref: index/mod.rs:25-44, body from RFC:86-137)."""

    def __init__(self, series: CloudObjectStorage, tags: CloudObjectStorage,
                 index: CloudObjectStorage, segment_ms: int):
        self.series = series
        self.tags = tags
        self.index = index
        self.segment_ms = segment_ms
        self._seen = _SegmentSeen()  # (segment, tsid)
        # segment start -> (the SST ids the lists were built from, the
        # lists): one entry a segment, so a newer version's filing
        # drops the older one
        self._postings = ByteLRU(_POSTINGS_MAX_BYTES,
                                 gauges=_POSTINGS_GAUGES)
        root = getattr(index, "root_path", "")
        self._postings_account = memledger.register(
            f"index_postings:{root}", lambda m: m._postings.total_bytes,
            anchor=self, kind="index_postings",
            budget=_POSTINGS_MAX_BYTES, owner=root)

    def close(self) -> None:
        """Clear-on-close: the lists can never be read again, and the
        ledger account goes with them."""
        self._postings.clear()
        memledger.deregister(self._postings_account)
        self._postings_account = None

    async def populate_series_ids(self, samples: list[Sample]) -> None:
        new: dict[int, dict[int, Sample]] = {}
        for s in samples:
            ensure(s.name_id is not None, "populate_metric_ids must run first")
            s.series_id = tsid_of(s.name, s.labels)
            seg = int(Timestamp(s.timestamp).truncate_by(self.segment_ms))
            if (seg, s.series_id) not in self._seen:
                new.setdefault(seg, {})[s.series_id] = s
        for seg, by_tsid in new.items():
            await self._register(seg, list(by_tsid.values()))
            # mark seen only after durable registration (retry on failure)
            for tsid in by_tsid:
                self._seen.add(seg, tsid)

    async def _register(self, seg: int, samples: list[Sample]) -> None:
        # whole-segment range: see MetricManager.populate_metric_ids
        rng = TimeRange.new(seg, seg + self.segment_ms)
        series_schema = self.series.schema().user_schema
        mids, tsids, keys = [], [], []
        t_mids, t_keys, t_vals = [], [], []
        i_mids, i_keys, i_vals, i_tsids = [], [], [], []
        for s in samples:
            mids.append(s.name_id)
            tsids.append(s.series_id)
            keys.append(series_key_of(s.name, s.labels))
            for lb in s.labels:
                t_mids.append(s.name_id)
                t_keys.append(lb.name)
                t_vals.append(lb.value)
                i_mids.append(s.name_id)
                i_keys.append(lb.name)
                i_vals.append(lb.value)
                i_tsids.append(s.series_id)
        await self.series.write(WriteRequest(pa.record_batch(
            [pa.array(mids, type=pa.uint64()), pa.array(tsids, type=pa.uint64()),
             pa.array(keys, type=pa.binary())], schema=series_schema), rng))
        if t_mids:
            ones = pa.array([1] * len(t_mids), type=pa.int32())
            await self.tags.write(WriteRequest(pa.record_batch(
                [pa.array(t_mids, type=pa.uint64()), pa.array(t_keys),
                 pa.array(t_vals), ones],
                schema=self.tags.schema().user_schema), rng))
            await self.index.write(WriteRequest(pa.record_batch(
                [pa.array(i_mids, type=pa.uint64()), pa.array(i_keys),
                 pa.array(i_vals), pa.array(i_tsids, type=pa.uint64()),
                 pa.array([1] * len(i_mids), type=pa.int32())],
                schema=self.index.schema().user_schema), rng))

    async def find_tsids(self, metric_id: int,
                         filters: list[tuple[str, str]],
                         time_range: TimeRange) -> Optional[set[int]]:
        """Inverted-index lookup: intersect TSID sets per label filter.
        Returns None when no filters were given (= all series).

        A segment answers from its posting lists (see _POSTINGS_MAX_BYTES)
        wherever an SST set names its content; where none does (rows
        in a memtable: the version is None) or the segment is over the
        row cap, the filtered scan answers for it.  Either way the
        answer is the scan's, with no window in which an acknowledged
        registration is not found: a write changes the segment's
        version or makes it None."""
        if not filters:
            return None
        kept, scanned = await self._segment_lists(
            self.index, lambda seg: seg, _posting_lists, time_range)
        result: Optional[set[int]] = None
        for key, value in filters:
            tsids: set[int] = set()
            for lists in kept:
                found = lists.get((metric_id, key, value))
                if found is not None:
                    tsids.update(found.tolist())
            if scanned:
                tsids |= await self._scan_tsids(metric_id, key, value,
                                                time_range, scanned)
            result = tsids if result is None else (result & tsids)
            if not result:
                return set()
        return result

    async def _scan_tsids(self, metric_id: int, key: str, value: str,
                          time_range: TimeRange,
                          segments: set[int]) -> set[int]:
        """One filter's series by a filtered scan of `segments` of the
        index table."""
        pred = And([Eq("metric_id", metric_id), Eq("tag_key", key),
                    Eq("tag_value", value)])
        tsids: set[int] = set()
        for b in await _collect(self.index.scan(
                ScanRequest(range=time_range, predicate=pred),
                segment_filter=segments.__contains__)):
            col = b.column(b.schema.names.index("tsid"))
            tsids.update(col.to_pylist())
        return tsids

    async def series_of(self, metric_id: int,
                        time_range: TimeRange) -> np.ndarray:
        """Every series of the metric registered in a segment of the
        range, ascending (np.uint64): what a walk with no label filter
        has to account for.  From the SERIES table, which holds a row
        for every series (the index table has none for a series
        without labels), under find_tsids' rules: a segment's lists
        ({metric: its series}) kept under its SST set in the same LRU,
        counted by the same outcomes, the filtered scan where no SST
        set names the segment's content or it is over the row cap."""
        kept, scanned = await self._segment_lists(
            self.series, lambda seg: ("series", seg), _series_lists,
            time_range)
        found = [lists[metric_id] for lists in kept if metric_id in lists]
        if scanned:
            for b in await _collect(self.series.scan(
                    ScanRequest(range=time_range,
                                predicate=Eq("metric_id", metric_id)),
                    segment_filter=scanned.__contains__)):
                found.append(b.column(
                    b.schema.names.index("tsid")).to_numpy())
        if not found:
            return np.zeros(0, np.uint64)
        return np.unique(np.concatenate(found)).astype(np.uint64)

    async def _segment_lists(self, table, key_of, build,
                             time_range: TimeRange) -> tuple[list, set]:
        """(the lists of every segment of `table` in the range whose
        SST set names its content: kept ones where that set is the one
        they were built from, built now where it is not; the segments
        a filtered scan must answer for: rows in a memtable, or over
        the row cap).  Each segment counted by its outcome, and the
        counts noted on the open span."""
        versions = await table.segment_versions(time_range)
        kept, scanned = [], set()
        counts = dict.fromkeys(_POSTINGS, 0)
        for seg, version in versions.items():
            if version is None or version.rows > _POSTINGS_MAX_ROWS:
                outcome = "bypass"
                scanned.add(seg)
            else:
                entry = self._postings.peek_entry(key_of(seg))
                if entry is not None and entry[0] == version.ids:
                    outcome = "hit"
                    self._postings.record_hit(key_of(seg))
                    kept.append(entry[1])
                else:
                    outcome = "build"
                    kept.append(await self._build_lists(
                        table, seg, key_of(seg), build, version.ids,
                        time_range))
            counts[outcome] += 1
            _POSTINGS[outcome].inc()
        span_note(postings=" ".join(f"{k}={n}" for k, n in counts.items()))
        return kept, scanned

    async def _build_lists(self, table, seg: int, key, build, ids: tuple,
                           time_range: TimeRange) -> dict:
        """The lists of segment `seg` of `table`, by one unfiltered
        scan of it as `time_range` selects its SSTs; filed under `ids`
        only if that is still the segment's version once the scan is
        done (an SST set never comes back, so equal before and after
        means unchanged in between; what a write in between made the
        scan read is this query's answer and nobody else's)."""
        lists, nbytes = build(await _collect(table.scan(
            ScanRequest(range=time_range),
            segment_filter=lambda s: s == seg)))
        now = (await table.segment_versions(time_range)).get(seg)
        if now is not None and now.ids == ids:
            self._postings.put(key, (ids, lists), nbytes)
        return lists

    async def label_values(self, metric_id: int, tag_key: str,
                           time_range: TimeRange) -> list[str]:
        """(RFC: tags table accelerates LabelValues)."""
        vals: set[str] = set()
        for b in await _collect(self.tags.scan(ScanRequest(
                range=time_range,
                predicate=And([Eq("metric_id", metric_id),
                               Eq("tag_key", tag_key)])))):
            col = b.column(b.schema.names.index("tag_value"))
            vals.update(col.to_pylist())
        return sorted(vals)

    async def label_names(self, metric_id: int,
                          time_range: TimeRange) -> list[str]:
        """Distinct tag keys of a metric in the window."""
        keys: set[str] = set()
        for b in await _collect(self.tags.scan(ScanRequest(
                range=time_range, predicate=Eq("metric_id", metric_id)))):
            col = b.column(b.schema.names.index("tag_key"))
            keys.update(col.to_pylist())
        return sorted(keys)

    async def resolve_series_keys(self, metric_id: int, tsids: list[int],
                                  time_range: TimeRange) -> dict[int, bytes]:
        pred = And([Eq("metric_id", metric_id),
                    In("tsid", tsids)]) if tsids else Eq("metric_id", metric_id)
        out: dict[int, bytes] = {}
        for b in await _collect(self.series.scan(ScanRequest(
                range=time_range, predicate=pred))):
            t = b.column(b.schema.names.index("tsid")).to_pylist()
            k = b.column(b.schema.names.index("series_key")).to_pylist()
            out.update(zip(t, k))
        return out


class SampleManager:
    """Data-table persistence (ref: data/mod.rs:25-44, body from RFC)."""

    def __init__(self, table: CloudObjectStorage, segment_ms: int):
        self.table = table
        self.segment_ms = segment_ms

    async def persist_chunked(self, samples: list[Sample],
                              chunk_window_ms: int) -> None:
        """Opaque-chunk layout: one row per (series, field, chunk window)
        holding the encoded (ts, value) payload (RFC:218-231)."""
        import numpy as np

        from horaedb_tpu.metric_engine import chunks

        groups: dict[tuple, list[Sample]] = {}
        for s in samples:
            ensure(s.series_id is not None, "populate_series_ids must run first")
            # trunc-toward-zero breaks the window-containment invariant
            # for pre-epoch times; chunked mode rejects them explicitly
            ensure(s.timestamp >= 0,
                   "chunked data mode requires non-negative timestamps")
            chunk_ts = int(Timestamp(s.timestamp).truncate_by(chunk_window_ms))
            groups.setdefault(
                (s.name_id, s.series_id, field_id_of(s.field_name), chunk_ts),
                []).append(s)

        by_seg: dict[int, list[tuple]] = {}
        for key, grp in groups.items():
            seg = int(Timestamp(key[3]).truncate_by(self.segment_ms))
            payload = chunks.encode_chunk(
                np.asarray([s.timestamp for s in grp], dtype=np.int64),
                np.asarray([s.value for s in grp], dtype=np.float64))
            by_seg.setdefault(seg, []).append((*key, payload))
        for seg, rows in sorted(by_seg.items()):
            # the file covers its chunk WINDOWS in full, so any query range
            # overlapping a window finds the file
            lo = min(r[3] for r in rows)
            hi = max(r[3] for r in rows) + chunk_window_ms
            batch = pa.record_batch(
                [pa.array([r[0] for r in rows], type=pa.uint64()),
                 pa.array([r[1] for r in rows], type=pa.uint64()),
                 pa.array([r[2] for r in rows], type=pa.uint64()),
                 pa.array([r[3] for r in rows], type=pa.int64()),
                 pa.array([r[4] for r in rows], type=pa.binary())],
                schema=self.table.schema().user_schema)
            await self.table.write(WriteRequest(
                batch, TimeRange.new(lo, hi)))

    async def persist(self, samples: list[Sample]) -> None:
        by_seg: dict[int, list[Sample]] = {}
        for s in samples:
            ensure(s.series_id is not None, "populate_series_ids must run first")
            seg = int(Timestamp(s.timestamp).truncate_by(self.segment_ms))
            by_seg.setdefault(seg, []).append(s)
        for seg, seg_samples in sorted(by_seg.items()):
            lo = min(s.timestamp for s in seg_samples)
            hi = max(s.timestamp for s in seg_samples)
            batch = pa.record_batch(
                [pa.array([s.name_id for s in seg_samples], type=pa.uint64()),
                 pa.array([s.series_id for s in seg_samples], type=pa.uint64()),
                 pa.array([field_id_of(s.field_name) for s in seg_samples],
                          type=pa.uint64()),
                 pa.array([s.timestamp for s in seg_samples], type=pa.int64()),
                 pa.array([s.value for s in seg_samples], type=pa.float64())],
                schema=self.table.schema().user_schema)
            await self.table.write(WriteRequest(
                batch, TimeRange.new(lo, hi + 1)))


# moved once a /query_multi request, never per row or cell: a span costs
# 24 us on a GIL-bound server (ROADMAP C8), so the single-field path
# that the point queries share gets none of this
_MULTI_QUERIES = registry.counter(
    "query_multi_total", "multi-field downsample queries")
_MULTI_FIELDS = registry.counter(
    "query_multi_fields_total",
    "fields scanned by multi-field downsample queries (one pushdown "
    "scan each; fields a rollup tier served are not among them)")
_MULTI_SCAN_SECONDS = registry.counter(
    "query_multi_scan_seconds_total",
    "wall seconds inside the per-field scans of multi-field downsample "
    "queries")

_ROWS_QUERIES = registry.counter(
    "query_rows_total",
    "row selections under a value predicate (query_rows_where)")
_ROWS_SELECT_SECONDS = registry.counter(
    "query_rows_select_seconds_total",
    "wall seconds inside the select of row selections (plan, the "
    "device's or the host's route over every segment, the combine)")

_LAST_QUERIES = registry.counter(
    "query_last_total",
    "requests for the newest row of every series (query_last)")
_LAST_SECONDS = registry.counter(
    "query_last_seconds_total",
    "wall seconds inside the walk of query_last requests (plan, the "
    "device's or the host's route over every segment asked, the "
    "combine)")

_BUCKETS_QUERIES = registry.counter(
    "query_buckets_total",
    "requests for the newest buckets of one field over all series "
    "(query_buckets)")
_BUCKETS_SECONDS = registry.counter(
    "query_buckets_seconds_total",
    "wall seconds inside the walk of query_buckets requests (plan, the "
    "device's or the host's route over every segment asked, the "
    "combine)")

_CHUNK_CACHE_HITS = registry.counter(
    "chunk_decode_cache_hits_total",
    "chunked-layout decode cache hits (the chunked scan cache)")
_CHUNK_CACHE_MISSES = registry.counter(
    "chunk_decode_cache_misses_total",
    "chunked-layout decode cache misses")
_CHUNK_CACHE_EVICTIONS = registry.counter(
    "chunk_decode_cache_evictions_total",
    "chunked-layout decode cache evictions")


def _rows_table(out: dict, fields: list[str]) -> pa.Table:
    """A row answer's columns ({groups, timestamps, values, found}: a
    field's flags None where it was found at every row) as the Arrow
    table /query_rows and /query_last serve: tsid, timestamp, one
    nullable float32 column a field."""
    return pa.table(
        [pa.array(out["groups"], type=pa.uint64()),
         pa.array(out["timestamps"], type=pa.int64())]
        + [pa.array(v, type=pa.float32(),
                    mask=None if f is None or not len(v)
                    else ~np.asarray(f))
           for v, f in zip(out["values"], out["found"])],
        names=["tsid", "timestamp"] + list(fields))


class MetricEngine:
    """The user-facing metric API over five storage instances.

    chunked_data=True switches the data table to the RFC's opaque-chunk
    layout: (ts, value) pairs batch-encoded per (series, field, chunk
    window) with Append/BytesMerge semantics (RFC:218-231).  Better
    compression and tiny row counts; queries decode chunks on host, so
    the aggregate pushdown applies only to the row layout."""

    def __init__(self, tables: dict[str, CloudObjectStorage], segment_ms: int,
                 chunked_data: bool = False,
                 chunk_window_ms: int = 30 * 60 * 1000):
        self.tables = tables
        self.segment_ms = segment_ms
        self.chunked_data = chunked_data
        self.chunk_window_ms = chunk_window_ms
        self.metric_manager = MetricManager(tables["metrics"], segment_ms)
        self.index_manager = IndexManager(tables["series"], tables["tags"],
                                          tables["index"], segment_ms)
        self.sample_manager = SampleManager(tables["data"], segment_ms)
        # standing rollup tiers (rollup/manager.py); populated by open()
        # when a [rollup] config enables them
        self.rollups = None
        # self-monitoring meta-ingest (metric_engine/meta.py); populated
        # by open() when a [meta] config enables it
        self.meta = None
        # the worker pools the five tables share; open() hands them over
        self._runtimes = None
        # chunked layout: the Append-mode data table bypasses the
        # reader's scan cache (host merge, uncached), so decoded sample
        # arrays get their own byte-budgeted LRU — keyed by (predicate,
        # exact range, SST-id set) with the scan cache's structural
        # invalidation (any write/compaction changes the SST set).
        # Budget: the data-table scan-cache bytes, which chunked mode
        # otherwise leaves unused.
        if chunked_data:
            self._chunk_cache = ByteLRU(
                tables["data"].reader.cache_budget_bytes,
                hits=_CHUNK_CACHE_HITS, misses=_CHUNK_CACHE_MISSES,
                evictions=(_CHUNK_CACHE_EVICTIONS,), trace_tier="chunk")
            # memory plane: the chunked engine's decoded-sample LRU is
            # a byte budget like any reader cache (common/memledger.py)
            self._chunk_mem_account = memledger.register(
                "chunk_cache:engine",
                lambda e: e._chunk_cache.total_bytes, anchor=self,
                kind="chunk_cache",
                budget=tables["data"].reader.cache_budget_bytes,
                owner="metric_engine")
        else:
            self._chunk_cache = None
            self._chunk_mem_account = None

    @classmethod
    async def open(cls, root_path: str, store: ObjectStore,
                   segment_ms: int = 2 * 3600 * 1000,
                   config: Optional[StorageConfig] = None,
                   chunked_data: bool = False,
                   chunk_window_ms: int = 30 * 60 * 1000,
                   wal_config=None, rollup_config=None,
                   meta_config=None, scanagent_config=None
                   ) -> "MetricEngine":
        import dataclasses

        if chunked_data:
            ensure(chunk_window_ms <= segment_ms
                   and segment_ms % chunk_window_ms == 0,
                   "chunk window must evenly divide the segment duration")
        # argument-only check, BEFORE any table/pool opens so a bad
        # combination cannot leak schedulers or worker pools: the
        # rollup maintenance/serve contract is per-cell bit equality
        # with the row-layout downsample pushdown; the chunked (Append)
        # layout has no such pushdown to mirror
        if rollup_config is not None and rollup_config.enabled:
            ensure(not chunked_data,
                   "[rollup] requires the row data layout "
                   "(chunked_data = false)")
        from horaedb_tpu.common import runtimes as runtimes_mod
        from horaedb_tpu.utils.compile_cache import enable_compile_cache

        # second process on the same machine reuses every compiled scan
        # program (the reference pays zero compile cost; we amortize ours)
        enable_compile_cache()

        tables = {}
        schemas = dict(_TABLE_SCHEMAS)
        if chunked_data:
            schemas["data"] = _CHUNKED_DATA_SCHEMA
        # one set of worker pools shared by all five tables — the
        # reference's StorageRuntimes are likewise engine-wide.  The
        # [scan] decode_workers override must be applied HERE: tables
        # receive these shared pools, so CloudObjectStorage's own
        # from_config never runs under the engine
        eng_cfg = config or StorageConfig()
        shared_runtimes = runtimes_mod.from_config(
            eng_cfg.threads, sst_override=eng_cfg.scan.decode_workers)
        wal_on = wal_config is not None and wal_config.enabled
        if wal_on:
            ensure(wal_config.dir,
                   "[wal] enabled requires wal.dir (or a Local object "
                   "store the server can derive it from)")
        try:
            for name, (schema, num_pks) in schemas.items():
                cfg = config or StorageConfig()
                if chunked_data and name == "data":
                    from horaedb_tpu.storage.config import UpdateMode

                    cfg = dataclasses.replace(cfg,
                                              update_mode=UpdateMode.APPEND)
                table = await CloudObjectStorage.open(
                    f"{root_path}/{name}", segment_ms, store, schema,
                    num_pks, cfg, runtimes=shared_runtimes)
                tables[name] = table
                if wal_on:
                    from horaedb_tpu.storage.config import UpdateMode
                    from horaedb_tpu.wal import IngestStorage

                    if table.schema().update_mode is UpdateMode.OVERWRITE:
                        import os

                        tables[name] = await IngestStorage.open(
                            table, os.path.join(wal_config.dir, name),
                            wal_config)
                    else:
                        # Append tables (the chunked data layout) have
                        # no __seq__ dedup, so replay could duplicate
                        # rows — they keep the direct write path
                        import logging as _logging

                        _logging.getLogger(__name__).info(
                            "wal: table %r is Append-mode; ingest WAL "
                            "skipped", name)
        except BaseException:
            # close whatever opened so a failed open leaks neither
            # schedulers nor worker pools
            for t in tables.values():
                await t.close()
            shared_runtimes.close()
            raise
        self = cls(tables, segment_ms, chunked_data=chunked_data,
                   chunk_window_ms=chunk_window_ms)
        self._runtimes = shared_runtimes
        if rollup_config is not None and rollup_config.enabled:
            from horaedb_tpu.rollup import RollupManager

            try:
                self.rollups = await RollupManager.open(
                    root_path, store, segment_ms, rollup_config,
                    config, shared_runtimes, tables["data"])
            except BaseException:
                await self.close()
                raise
            self.rollups.attach(self)
            # flush completions make segments rollable (wal/ingest.py)
            data = tables["data"]
            if hasattr(data, "memtable_segments"):
                data.on_flush = self.rollups.note_flush
        if meta_config is not None and meta_config.enabled:
            # self-monitoring: scrape the process's own MetricsRegistry
            # into a __meta metrics table through this engine's normal
            # write path (metric_engine/meta.py)
            from horaedb_tpu.metric_engine.meta import MetaIngest

            try:
                self.meta = MetaIngest(self, meta_config)
                await self.meta.start()
            except BaseException:
                await self.close()
                raise
        if (scanagent_config is not None and scanagent_config.active
                and not chunked_data):
            # near-data scan routing ([scanagent]): the DATA table's
            # aggregate scans — the cold dashboard path — consult the
            # shard map and route covered segments to their store-shard
            # agents (scanagent/client.py).  The index/series/tags
            # tables stay direct: their scans are row-shaped and tiny.
            from horaedb_tpu.scanagent import ScanAgentClient, ScanRouter

            try:
                self._scanagent_client = ScanAgentClient(scanagent_config)
                data = tables["data"]
                base = getattr(data, "inner", data)  # unwrap WAL front
                base.reader.scan_router = ScanRouter(
                    scanagent_config, self._scanagent_client,
                    base.root_path, base.schema().user_schema,
                    base.schema().num_primary_keys,
                    base.segment_duration_ms)
            except BaseException:
                await self.close()
                raise
        return self

    async def close(self) -> None:
        if getattr(self, "_scanagent_client", None) is not None:
            await self._scanagent_client.close()
            self._scanagent_client = None
        if self.meta is not None:
            # the meta scraper writes through this engine: stop it
            # before anything under it goes away
            await self.meta.stop()
            self.meta = None
        if self.rollups is not None:
            await self.rollups.close()
            self.rollups = None
        for t in self.tables.values():
            await t.close()
        self.index_manager.close()
        if self._chunk_cache is not None:
            # clear-on-close: a closed engine's decoded chunks can
            # never be read again, and the ledger account goes with it
            self._chunk_cache.clear()
            memledger.deregister(self._chunk_mem_account)
            self._chunk_mem_account = None
        if self._runtimes is not None:
            self._runtimes.close()

    @property
    def runtimes(self):
        """The named pools (common/runtimes.py) this engine's tables
        share: the HTTP front end writes a large downsample answer on
        `sst`, beside the scans' jobs."""
        return self._runtimes

    async def stats(self) -> dict:
        """Data volume actually stored (rows/bytes per table, from the
        manifests) plus the ingest plane's buffered state (memtables +
        WAL backlog) — the cluster's rebalancing load signal and the
        operator's durability dashboard."""
        tables = {}
        rows = size = sst_count = 0
        mem_rows = mem_bytes = wal_backlog = 0
        last_flush_age = None
        wal_enabled = False
        for name, t in self.tables.items():
            ssts = await t.manifest.all_ssts()
            t_rows = sum(f.meta.num_rows for f in ssts)
            t_size = sum(f.meta.size for f in ssts)
            tables[name] = {"ssts": len(ssts), "rows": t_rows,
                            "bytes": t_size}
            rows += t_rows
            size += t_size
            sst_count += len(ssts)
            ingest = getattr(t, "ingest_stats", None)
            if ingest is not None:
                wal_enabled = True
                ing = ingest()
                tables[name]["ingest"] = ing
                mem_rows += ing["memtable_rows"]
                mem_bytes += ing["memtable_bytes"]
                wal_backlog += ing["wal_backlog_bytes"]
                age = ing["last_flush_age_s"]
                if age is not None and (last_flush_age is None
                                        or age > last_flush_age):
                    last_flush_age = age  # the most stale table
            # per-table cache tiers (HBM windows / host-RAM encoded
            # parts / HBM stacks) — the operator's residency dashboard
            reader = getattr(t, "reader", None)
            if reader is not None and hasattr(reader, "cache_stats"):
                tables[name]["cache"] = reader.cache_stats()
        out = {"rows": rows, "bytes": size, "ssts": sst_count,
               "tables": tables}
        cache_tables = [v["cache"] for v in tables.values()
                        if "cache" in v]
        if cache_tables:
            out["cache"] = {
                "scan_cache_bytes": sum(
                    c["scan_cache"]["bytes"] for c in cache_tables),
                "encoded_cache_bytes": sum(
                    c["encoded_cache"]["bytes"] for c in cache_tables),
                "encoded_cache_entries": sum(
                    c["encoded_cache"]["entries"] for c in cache_tables),
                "encoded_cache_hits": sum(
                    c["encoded_cache"]["hits"] for c in cache_tables),
                "encoded_cache_misses": sum(
                    c["encoded_cache"]["misses"] for c in cache_tables),
            }
        if wal_enabled:
            out["memtable_rows"] = mem_rows
            out["memtable_bytes"] = mem_bytes
            out["wal_backlog_bytes"] = wal_backlog
            out["last_flush_age_s"] = last_flush_age
        if self.rollups is not None:
            # per-rollup lag (newest raw seq vs newest rolled-up seq)
            # and segment coverage — the stale-tier alerting surface
            out["rollups"] = await self.rollups.stats()
        return out

    async def flush(self) -> dict:
        """Force-drain every WAL-fronted table's memtables to SSTs
        (POST /admin/flush).  Returns rows flushed per table."""
        out = {}
        for name, t in self.tables.items():
            flush_all = getattr(t, "flush_all", None)
            if flush_all is not None:
                out[name] = {"flushed_rows": await flush_all()}
        return out

    # ---- write ------------------------------------------------------------

    async def write(self, samples: list[Sample]) -> None:
        """The three-stage pipeline (ref: metric_engine README diagram)."""
        if not samples:
            return
        try:
            with span("engine.write", samples=len(samples)):
                await self.metric_manager.populate_metric_ids(samples)
                await self.index_manager.populate_series_ids(samples)
                if self.chunked_data:
                    await self.sample_manager.persist_chunked(
                        samples, self.chunk_window_ms)
                else:
                    await self.sample_manager.persist(samples)
        finally:
            # the delta feed, noted AFTER the writes so a maintenance
            # pass cannot consume the note while the rows are still
            # uncommitted (acked rows then get read-your-writes
            # dirtiness) — and in the finally so a PARTIALLY-failed
            # multi-segment write still dirties whatever may have
            # committed (over-dirtying is harmless, staleness is not)
            if self.rollups is not None:
                by_metric: dict[str, set] = {}
                for s in samples:
                    by_metric.setdefault(s.name, set()).add(
                        int(Timestamp(s.timestamp).truncate_by(
                            self.segment_ms)))
                self.rollups.note_write(by_metric)

    async def write_arrow(self, metric: str, tag_columns: list[str],
                          batch: pa.RecordBatch,
                          field: str = "value") -> None:
        """Vectorized bulk ingest: an Arrow batch with columns
        [*tag_columns, 'timestamp' int64, 'value' float64] for one metric.

        The scalar write() path builds a Python Sample per point; this
        path touches Python only once per UNIQUE series (for SeaHash id
        derivation and index registration) and moves the per-row work —
        series-code assignment, segment splitting, column assembly — into
        Arrow/numpy.  This is the ingest path benchmarks and remote-write
        bulk endpoints should use.
        """
        import numpy as np
        import pyarrow.compute as pc

        from horaedb_tpu.metric_engine.types import Label

        n = batch.num_rows
        if n == 0:
            return
        ensure("timestamp" in batch.schema.names
               and "value" in batch.schema.names,
               "write_arrow needs 'timestamp' and 'value' columns")
        for c in tag_columns:
            ensure(c in batch.schema.names,
                   f"write_arrow tag column {c!r} missing from batch")
            ensure(batch.column(batch.schema.names.index(c)).null_count == 0,
                   f"write_arrow tag column {c!r} contains nulls")
        # normalize idiomatic Arrow types up front (timestamp('ms') etc.)
        # so type mismatches fail here as Error, not deep in numpy
        try:
            ts_col = batch.column(
                batch.schema.names.index("timestamp")).cast(pa.int64())
            val_col = batch.column(
                batch.schema.names.index("value")).cast(pa.float64())
        except pa.ArrowInvalid as e:
            raise Error.context(
                "write_arrow timestamp/value columns must cast to "
                "int64/float64", e)
        ensure(ts_col.null_count == 0 and val_col.null_count == 0,
               "write_arrow timestamp/value columns contain nulls")

        # unique series via per-tag dictionary codes combined into one
        # composite code (Arrow C++ encodes; numpy combines); extreme
        # tag-cardinality products that would overflow the composite
        # fall back to exact row-wise unique over the code matrix
        # instead of rejecting the batch
        tag_arrays = [batch.column(batch.schema.names.index(c))
                      for c in tag_columns]
        per_tag_codes = []
        code_space = 1
        for arr in tag_arrays:
            d = pc.dictionary_encode(arr)
            d = d.combine_chunks() if isinstance(d, pa.ChunkedArray) else d
            per_tag_codes.append(np.asarray(d.indices).astype(np.int64))
            code_space *= max(1, len(d.dictionary))
        if code_space < 2**62:
            composite = np.zeros(n, dtype=np.int64)
            for c in per_tag_codes:
                card = int(c.max()) + 1 if len(c) else 1
                composite = composite * card + c
            uniq_codes, codes = np.unique(composite, return_inverse=True)
            num_series = len(uniq_codes)
        else:
            mat = np.stack(per_tag_codes, axis=1)
            uniq_rows, codes = np.unique(mat, axis=0, return_inverse=True)
            codes = codes.reshape(-1)
            num_series = len(uniq_rows)

        ts_np = ts_col.to_numpy()
        # segment assignment must match Timestamp.truncate_by (truncation
        # toward zero, not numpy floor) so pre-epoch rows land where their
        # registration does
        seg = self.segment_ms
        q = np.where(ts_np >= 0, ts_np // seg, -((-ts_np) // seg))
        seg_ids = q * seg

        # registration must happen per (segment, series) — the index is
        # Date-scoped (RFC:104), so a series spanning segments registers
        # in each one.  One Python trip per unique pair; dense per-batch
        # codes stand in for the series identity (bijective with the
        # composite/tag-row within one batch).  q is already the exact
        # segment index (seg_ids = q * seg).
        _, _, pair_rows, _ = _unique_pairs(q, codes)
        reg_samples = []
        tsid_of_code = np.full(num_series, 0, dtype=np.uint64)
        mid = metric_id_of(metric)
        series_keys = []
        code_idxes = []
        for row in pair_rows:
            row = int(row)
            labels = [Label(c, str(tag_arrays[j][row].as_py()))
                      for j, c in enumerate(tag_columns)]
            series_keys.append(series_key_of(metric, labels))
            code_idxes.append(int(codes[row]))
            reg_samples.append(Sample(metric, labels, int(ts_np[row]), 0.0,
                                      field_name=field))
        # ONE native SeaHash call for every unique series in the batch
        tsid_of_code[code_idxes] = tsids_of_keys(series_keys)
        # registration rides the scalar pipeline (per-segment dedup caches
        # make it cheap); data rows go straight to the data table
        await self.metric_manager.populate_metric_ids(reg_samples)
        await self.index_manager.populate_series_ids(reg_samples)

        val_np = val_col.to_numpy()
        tsids = tsid_of_code[codes]
        data = self.tables["data"]
        fid = field_id_of(field)
        if self.chunked_data:
            await self._write_arrow_chunked(mid, fid, codes, tsid_of_code,
                                            ts_np, val_np)
            return
        # per-segment SST writes are independent (one file + one
        # manifest delta each): overlap them with bounded concurrency so
        # a batch spanning many segments isn't serialized on parquet
        # encode round trips.  The mask is built INSIDE the permit (at
        # most 4 row masks live at once) and a TaskGroup settles every
        # sibling before a failure propagates — no write may still be
        # running after write_arrow raises.
        sem = asyncio.Semaphore(4)

        async def write_segment(seg: int) -> None:
            async with sem:
                m = seg_ids == seg
                seg_ts = ts_np[m]
                out = pa.record_batch(
                    [pa.array(np.full(int(m.sum()), mid, dtype=np.uint64)),
                     pa.array(tsids[m]),
                     pa.array(np.full(int(m.sum()), fid, dtype=np.uint64)),
                     pa.array(seg_ts, type=pa.int64()),
                     pa.array(val_np[m], type=pa.float64())],
                    schema=data.schema().user_schema)
                await data.write(WriteRequest(
                    out,
                    TimeRange.new(int(seg_ts.min()), int(seg_ts.max()) + 1)))

        try:
            if hasattr(asyncio, "TaskGroup"):  # py3.11+
                try:
                    async with asyncio.TaskGroup() as tg:
                        for seg in np.unique(seg_ids):
                            tg.create_task(write_segment(int(seg)))
                except BaseException as eg:
                    # preserve the pre-TaskGroup error surface: callers
                    # catching concrete types (Error, pa.ArrowInvalid,
                    # OSError, ...) must not be handed an
                    # ExceptionGroup; mixed-type failures still
                    # collapse to ONE exception instead of re-combining
                    # into a group.
                    if hasattr(eg, "exceptions"):
                        raise eg.exceptions[0]
                    raise
            else:
                # py3.10: no TaskGroup/ExceptionGroup.  gather with
                # return_exceptions settles EVERY sibling before the
                # first failure propagates — the same
                # no-write-still-running guarantee (leaking an
                # in-flight parquet encode past the caller corrupts
                # later work on the shared pools).
                tasks = [asyncio.ensure_future(write_segment(int(seg)))
                         for seg in np.unique(seg_ids)]
                results = await asyncio.gather(*tasks,
                                               return_exceptions=True)
                for r in results:
                    if isinstance(r, BaseException):
                        raise r
        finally:
            # noted AFTER the writes, in the finally: see write() — a
            # partially-failed batch still dirties whatever committed
            if self.rollups is not None:
                self.rollups.note_write(
                    {metric: {int(s) for s in np.unique(seg_ids)}})

    async def _write_arrow_chunked(self, mid, fid, codes, tsid_of_code,
                                   ts_np, val_np) -> None:
        """Bulk path for the chunked layout: group rows by (series, chunk
        window) in numpy, encode one payload per group."""
        import numpy as np

        from horaedb_tpu.metric_engine import chunks

        ensure(int(ts_np.min()) >= 0,
               "chunked data mode requires non-negative timestamps")
        window = self.chunk_window_ms
        chunk_idx = ts_np // window
        u_codes, u_cidx, _, inv = _unique_pairs(codes, chunk_idx)
        uniq_pairs = np.stack([u_codes, u_cidx * window], axis=1)
        order = np.argsort(inv, kind="stable")
        boundaries = np.concatenate(
            [[0], np.cumsum(np.bincount(inv, minlength=len(uniq_pairs)))])

        by_seg: dict[int, list[tuple]] = {}
        for g in range(len(uniq_pairs)):
            rows = order[boundaries[g]:boundaries[g + 1]]
            code_idx, c_ts = int(uniq_pairs[g, 0]), int(uniq_pairs[g, 1])
            payload = chunks.encode_chunk(ts_np[rows], val_np[rows])
            seg = int(Timestamp(c_ts).truncate_by(self.segment_ms))
            by_seg.setdefault(seg, []).append(
                (int(tsid_of_code[code_idx]), c_ts, payload))
        data = self.tables["data"]
        for seg, rows in sorted(by_seg.items()):
            lo = min(r[1] for r in rows)
            hi = max(r[1] for r in rows) + window
            batch = pa.record_batch(
                [pa.array(np.full(len(rows), mid, dtype=np.uint64)),
                 pa.array([r[0] for r in rows], type=pa.uint64()),
                 pa.array(np.full(len(rows), fid, dtype=np.uint64)),
                 pa.array([r[1] for r in rows], type=pa.int64()),
                 pa.array([r[2] for r in rows], type=pa.binary())],
                schema=data.schema().user_schema)
            await data.write(WriteRequest(batch, TimeRange.new(lo, hi)))

    # ---- read -------------------------------------------------------------

    async def _resolve_data_predicate(self, metric: str,
                                      filters: list[tuple[str, str]],
                                      time_range: TimeRange, field: str,
                                      ts_leaf: bool = True):
        """Shared resolve + data-table predicate construction for both
        raw and downsample queries; None means provably empty.

        `ts_leaf=False` omits the time-range leaf: bucket-ALIGNED
        downsample queries enforce [start, end) exactly through the
        aggregate grid cut, and a predicate without the range makes the
        scan-cache windows and per-window aggregation memos fully
        RANGE-INDEPENDENT — rotating/zooming dashboard queries over the
        same data share one set of cached merge windows instead of
        re-reading per range."""
        parts = await self._data_pred_parts(metric, filters, time_range,
                                            ts_leaf)
        if parts is None:
            return None
        return And([parts[0], Eq("field_id", field_id_of(field))]
                   + parts[1:])

    async def _data_pred_parts(self, metric: str,
                               filters: list[tuple[str, str]],
                               time_range: TimeRange,
                               ts_leaf: bool = True):
        """The field-independent predicate leaves (metric id, time leaf,
        tsid In) shared by single- and multi-field queries; None means
        provably empty."""
        mid = await self.metric_manager.resolve(metric, time_range)
        if mid is None:
            return None
        tsids = await self.index_manager.find_tsids(mid, filters, time_range)
        if tsids is not None and not tsids:
            return None
        preds = [Eq("metric_id", mid)]
        if self.chunked_data:
            # a chunk's row key is its window start; a window overlapping
            # the query starts at or after truncate(start, window)
            # (chunked mode stores only non-negative timestamps, so the
            # truncation is a true floor)
            lo = int(Timestamp(max(0, int(time_range.start))).truncate_by(
                self.chunk_window_ms))
            preds.append(TimeRangePred("chunk_ts", lo, int(time_range.end)))
        elif ts_leaf:
            preds.append(TimeRangePred("timestamp", int(time_range.start),
                                       int(time_range.end)))
        if tsids is not None:
            preds.append(In("tsid", sorted(tsids)))
        return preds

    async def query(self, metric: str, filters: list[tuple[str, str]],
                    time_range: TimeRange, field: str = "value") -> pa.Table:
        """Raw samples of one field of a metric matching all label filters,
        as an Arrow table (tsid, timestamp, value)."""
        with span("resolve", metric=metric):
            pred = await self._resolve_data_predicate(metric, filters,
                                                      time_range, field)
        if pred is None:
            return _empty_result()
        with span("scan", metric=metric):
            qp = await self.tables["data"].plan_query(ScanRequest(
                range=time_range, predicate=pred))
            batches = await _collect(self.tables["data"].execute_plan(qp))
        if not batches:
            return _empty_result()
        if self.chunked_data:
            with span("chunk_decode"):
                return self._decode_chunk_batches(batches, time_range)
        tbl = pa.Table.from_batches(batches)
        return tbl.select(["tsid", "timestamp", "value"])

    @staticmethod
    def _decode_chunk_arrays(batches: list[pa.RecordBatch],
                             time_range: TimeRange):
        """THE chunk-decode semantics (payload -> (tsid, ts, value)
        numpy arrays, [start, end) masked), shared by the row-table and
        device-downsample paths so they cannot drift.  Returns None when
        no samples survive the mask."""
        import numpy as np

        from horaedb_tpu import native
        from horaedb_tpu.metric_engine import chunks

        out_tsid: list[np.ndarray] = []
        out_ts: list[np.ndarray] = []
        out_val: list[np.ndarray] = []
        lo, hi = int(time_range.start), int(time_range.end)
        for b in batches:
            payload_arr = b.column(b.schema.names.index("payload"))
            # one FFI call decodes EVERY row's chunks (delta-of-delta ts,
            # XOR/scaled values, per-row dedup) — the numpy twin below
            # pays ~30 interpreter dispatches per chunk instead
            got = native.chunk_decode_batch(payload_arr)
            if got is not None:
                ts, vals, counts = got
                tsids = np.repeat(
                    b.column(b.schema.names.index("tsid")).to_numpy(
                        zero_copy_only=False), counts)
                m = (ts >= lo) & (ts < hi)
                if m.any():
                    out_ts.append(ts[m])
                    out_val.append(vals[m])
                    out_tsid.append(tsids[m])
                continue
            tsid_col = b.column(b.schema.names.index("tsid")).to_pylist()
            payloads = payload_arr.to_pylist()
            for tsid, payload in zip(tsid_col, payloads):
                ts, vals = chunks.decode_chunks(payload)
                m = (ts >= lo) & (ts < hi)
                if m.any():
                    out_ts.append(ts[m])
                    out_val.append(vals[m])
                    out_tsid.append(np.full(int(m.sum()), tsid,
                                            dtype=np.uint64))
        if not out_ts:
            return None
        return (np.concatenate(out_tsid), np.concatenate(out_ts),
                np.concatenate(out_val))

    def _decode_chunk_batches(self, batches: list[pa.RecordBatch],
                              time_range: TimeRange) -> pa.Table:
        decoded = self._decode_chunk_arrays(batches, time_range)
        if decoded is None:
            return _empty_result()
        tsid_np, ts_np, val_np = decoded
        return pa.table({
            "tsid": pa.array(tsid_np, type=pa.uint64()),
            "timestamp": pa.array(ts_np, type=pa.int64()),
            "value": pa.array(val_np, type=pa.float64()),
        })

    async def resolve_series(self, metric: str, tsids: list[int],
                             time_range: TimeRange) -> dict[int, bytes]:
        """tsid -> human-readable series key, via the series table."""
        mid = await self.metric_manager.resolve(metric, time_range)
        if mid is None:
            return {}
        return await self.index_manager.resolve_series_keys(
            mid, tsids, time_range)

    async def query_downsample(self, metric: str,
                               filters: list[tuple[str, str]],
                               time_range: TimeRange, bucket_ms: int,
                               field: str = "value",
                               aggs: tuple = ALL_AGGS,
                               use_rollup: bool = True) -> dict:
        """GROUP BY series, time(bucket) — the north-star query, executed
        as an aggregate pushdown: the data-table merge output is
        downsampled on device without ever materializing rows as Arrow.
        `aggs` restricts which aggregates are computed (count always
        rides along).  Returns {tsids, num_buckets,
        aggs: {agg -> (series, bucket) grid}}.

        When a standing rollup covers (metric, field, bucket), the grid
        is assembled from pre-aggregated tier cells plus a raw-computed
        tail for the not-yet-rolled segments — bit-identical to the
        from-raw path (docs/rollups.md).  `use_rollup=False` forces the
        raw path (the equivalence tests' recompute side).
        """
        num_buckets, aligned = self._downsample_grid(time_range, bucket_ms)
        if self.chunked_data:
            with span("downsample_chunked", metric=metric,
                      bucket_ms=bucket_ms):
                return await self._downsample_chunked(
                    metric, filters, time_range, bucket_ms, num_buckets,
                    field=field, which=tuple(aggs))
        if use_rollup:
            out, resolved = await self._try_rollup_serve(
                metric, filters, time_range, bucket_ms, num_buckets,
                field, tuple(aggs))
            if out is not None:
                return out
        else:
            resolved = None
        with span("resolve", metric=metric):
            pred = await self._resolved_or_build_predicate(
                metric, filters, time_range, field, not aligned, resolved)
        with span("downsample", metric=metric, bucket_ms=bucket_ms):
            return await self._scan_downsample(pred, time_range,
                                               bucket_ms, num_buckets,
                                               aggs)

    def _pred_from_resolved(self, resolved, field: str,
                            time_range: TimeRange, ts_leaf: bool):
        """The _data_pred_parts leaf shape, rebuilt from an
        already-resolved (mid, tsids) pair — same leaves in the same
        order, so scan-cache keys cannot drift between the paths."""
        mid, tsids = resolved
        preds = [Eq("metric_id", mid), Eq("field_id", field_id_of(field))]
        if ts_leaf:
            preds.append(TimeRangePred("timestamp", int(time_range.start),
                                       int(time_range.end)))
        if tsids is not None:
            preds.append(In("tsid", sorted(tsids)))
        return And(preds)

    async def _resolved_or_build_predicate(self, metric, filters,
                                           time_range, field: str,
                                           ts_leaf: bool, resolved):
        """Raw-path predicate, reusing the rollup probe's resolve +
        index lookup when one ran (a covered-but-lagging query must not
        pay the index resolution twice)."""
        if resolved is not None:
            return self._pred_from_resolved(resolved, field, time_range,
                                            ts_leaf)
        return await self._resolve_data_predicate(metric, filters,
                                                  time_range, field,
                                                  ts_leaf=ts_leaf)

    async def _try_rollup_serve(self, metric, filters, time_range,
                                bucket_ms: int, num_buckets: int,
                                field: str, aggs: tuple):
        """Rollup coverage check + serve.  Returns (result, resolved):
        result None means take the raw path; resolved carries the
        probe's (mid, tsids) for the raw path to reuse.  All
        rollup-tier reads route through here (the planner's coverage
        API — tools/lint.py enforces it)."""
        if self.rollups is None or not self.rollups.covers(
                metric, field, bucket_ms, time_range):
            return None, None
        with span("rollup_plan", metric=metric, bucket_ms=bucket_ms):
            mid = await self.metric_manager.resolve(metric, time_range)
            if mid is None:
                return {"tsids": [], "num_buckets": num_buckets,
                        "aggs": {}}, None
            tsids = await self.index_manager.find_tsids(mid, filters,
                                                        time_range)
            if tsids is not None and not tsids:
                return {"tsids": [], "num_buckets": num_buckets,
                        "aggs": {}}, None
        out = await self.rollups.try_serve(metric, mid, tsids, time_range,
                                           bucket_ms, field, aggs)
        return out, (mid, tsids)

    def _downsample_grid(self, time_range: TimeRange,
                         bucket_ms: int) -> tuple[int, bool]:
        """Shared bucket-grid math: (num_buckets, aligned).

        A bucket-ALIGNED range's grid cut ([0, num_buckets) on range
        -relative buckets) IS the time filter, exactly — the scan omits
        the ts leaf so cached windows/memos serve every aligned range.
        Only when the span covers at least one segment, though: there
        the read amplification is bounded by the two boundary segments
        (<= 2x), while a narrow query over a wide segment would decode
        the whole segment for a sliver (config-2 point queries keep
        their row-group pruning)."""
        span = int(time_range.end) - int(time_range.start)
        ensure(span < 2**31,
               f"query window of {span}ms exceeds the int32 offset range "
               "(~24.8 days); split the query into smaller windows")
        num_buckets = -(-span // bucket_ms)
        aligned = span % bucket_ms == 0 and span >= self.segment_ms
        return num_buckets, aligned

    async def _scan_downsample(self, pred, time_range: TimeRange,
                               bucket_ms: int, num_buckets: int,
                               aggs: tuple, top_k=None) -> dict:
        """Shared scan + result shaping for the row-layout downsample
        paths (single- and multi-field MUST stay in lockstep — parity
        -tested).  All aggregate shapes route through one QueryPlan."""
        if pred is None:
            return {"tsids": [], "num_buckets": num_buckets, "aggs": {}}
        spec = AggregateSpec(group_col="tsid", ts_col="timestamp",
                             value_col="value",
                             range_start=int(time_range.start),
                             bucket_ms=bucket_ms, num_buckets=num_buckets,
                             which=tuple(aggs))
        qp = await self.tables["data"].plan_query(
            ScanRequest(range=time_range, predicate=pred), spec=spec,
            top_k=top_k)
        group_values, grids = await self.tables["data"].execute_plan(qp)
        return {"tsids": [int(t) for t in group_values],
                "num_buckets": num_buckets,
                "aggs": grids if len(group_values) else {}}

    async def query_topk(self, metric: str,
                         filters: list[tuple[str, str]],
                         time_range: TimeRange, bucket_ms: int, k: int,
                         by: str = "max", largest: bool = True,
                         field: str = "value",
                         aggs: tuple = ALL_AGGS,
                         use_rollup: bool = True) -> dict:
        """Top-k series ranked by one aggregate over the window (BASELINE
        config 4's 'top-k hosts by max(cpu)' shape) — the downsample
        QueryPlan with a TopK stage on top.  Result rows come back best
        -first.  Row layout only (chunked tables downsample then rank
        host-side the same way)."""
        import numpy as np

        from horaedb_tpu.storage.plan import TopKSpec, apply_top_k

        ensure(by in ALL_AGGS,
               f"unknown top-k aggregate {by!r}; supported: {ALL_AGGS}")
        which = tuple(sorted(set(aggs) | {by}))
        if self.chunked_data:
            out = await self.query_downsample(metric, filters, time_range,
                                              bucket_ms, field=field,
                                              aggs=which)
            if out["tsids"]:
                values, grids = apply_top_k(
                    np.asarray(out["tsids"], dtype=np.uint64),
                    out["aggs"], TopKSpec(k=k, by=by, largest=largest))
                out["tsids"] = [int(t) for t in values]
                out["aggs"] = grids
            return out
        num_buckets, aligned = self._downsample_grid(time_range, bucket_ms)
        resolved = None
        if use_rollup:
            # a rollup-covered top-k is the covered downsample grid
            # with the TopK stage applied host-side (the chunked path's
            # shape) — same grids in, same slice out
            out, resolved = await self._try_rollup_serve(
                metric, filters, time_range, bucket_ms, num_buckets,
                field, which)
            if out is not None:
                if out["tsids"]:
                    values, grids = apply_top_k(
                        np.asarray(out["tsids"], dtype=np.uint64),
                        out["aggs"], TopKSpec(k=k, by=by, largest=largest))
                    out["tsids"] = [int(t) for t in values]
                    out["aggs"] = grids
                return out
        pred = await self._resolved_or_build_predicate(
            metric, filters, time_range, field, not aligned, resolved)
        return await self._scan_downsample(
            pred, time_range, bucket_ms, num_buckets, which,
            top_k=TopKSpec(k=k, by=by, largest=largest))

    async def query_downsample_multi(self, metric: str,
                                     filters: list[tuple[str, str]],
                                     time_range: TimeRange, bucket_ms: int,
                                     fields: list[str],
                                     aggs: tuple = ALL_AGGS,
                                     use_rollup: bool = True) -> dict:
        """GROUP BY series, time(bucket) over SEVERAL fields of one
        metric (TSBS devops queries touch up to 10 fields) with ONE
        metric/index resolve shared by every field's scan.  Returns
        {field: result}, each result shaped exactly like
        query_downsample's.

        Fields PARTITION the data table's rows (one row per sample per
        field, RFC docs/rfcs/20240827-metric-engine.md:106-137), so the
        per-field pushdown scans below each decode only their own
        field's rows — N fields cost one pass over the union, not N.
        A shared-window variant (push In(field_id, all) once, mask each
        field post-merge) was slower on the host path (a CPU run, not
        a chip number).
        With device-layout sidecars the leaf-filtered load is cheap,
        while N masked aggregations over the UNION of rows cost N full
        passes.  What the chip makes of this path is read in the
        benchmark's cell `s1000_double_groupby_all` (PERF.md).

        Traced like query_downsample, as children of the request's
        root: one `resolve` span around the shared resolve and one
        `downsample` span a field scanned (`field=` names it).
        """
        ensure(len(fields) > 0, "fields must be non-empty")
        _MULTI_QUERIES.inc()
        if self.chunked_data:
            return {f: await self.query_downsample(
                metric, filters, time_range, bucket_ms, field=f, aggs=aggs)
                for f in fields}
        num_buckets, aligned = self._downsample_grid(time_range, bucket_ms)
        out = {}
        remaining = list(fields)
        resolved = None
        covered = ([] if not use_rollup or self.rollups is None else
                   [f for f in remaining if self.rollups.covers(
                       metric, f, bucket_ms, time_range)])
        if covered:
            # per-field routing with ONE shared resolve: covered fields
            # read their rollup tier, the rest reuse (mid, tsids) below
            with span("resolve", metric=metric), \
                    span("rollup_plan", metric=metric, bucket_ms=bucket_ms):
                mid = await self.metric_manager.resolve(metric,
                                                        time_range)
                tsids = (None if mid is None else
                         await self.index_manager.find_tsids(
                             mid, filters, time_range))
            if mid is None or (tsids is not None and not tsids):
                return {f: {"tsids": [], "num_buckets": num_buckets,
                            "aggs": {}} for f in fields}
            resolved = (mid, tsids)
            for f in covered:
                served = await self.rollups.try_serve(
                    metric, mid, tsids, time_range, bucket_ms, f,
                    tuple(aggs))
                if served is not None:
                    out[f] = served
                    remaining.remove(f)
            if not remaining:
                return out
        parts = None
        if resolved is None:
            with span("resolve", metric=metric):
                parts = await self._data_pred_parts(metric, filters,
                                                    time_range,
                                                    ts_leaf=not aligned)
        # deliberately SEQUENTIAL: each scan already pipelines its own
        # IO against pool work, and gathering all fields was slower on
        # the host path (a CPU run, as above) — ten interleaved merges
        # thrash the worker pool and caches
        t0 = time.perf_counter()
        for f in remaining:
            if resolved is not None:
                pred = self._pred_from_resolved(resolved, f, time_range,
                                                not aligned)
            else:
                pred = (None if parts is None else
                        And([parts[0], Eq("field_id", field_id_of(f))]
                            + parts[1:]))
            with span("downsample", metric=metric, bucket_ms=bucket_ms,
                      field=f):
                out[f] = await self._scan_downsample(
                    pred, time_range, bucket_ms, num_buckets, aggs)
        _MULTI_FIELDS.inc(len(remaining))
        _MULTI_SCAN_SECONDS.inc(time.perf_counter() - t0)
        return out

    async def query_rows_where(self, metric: str,
                               filters: list[tuple[str, str]],
                               time_range: TimeRange, where_field: str,
                               op: str, value: float,
                               fields: list[str]) -> pa.Table:
        """Every reading of `where_field` in range whose CURRENT value
        (after last-write-wins dedup: what query() returns for it)
        satisfies `op` (gt, ge, lt, le) against `value`, compared as
        float32, with the values of `fields` at the same (series,
        timestamp): TSBS's high-cpu-* shape, a predicate on the VALUE
        answered as rows.  An Arrow table (tsid uint64, timestamp
        int64, one nullable float32 column a field, in the order
        asked), sorted by (tsid, timestamp); a field without a sample
        at a selected key is null there; nothing approximate or cut.

        In this data model (one stored row a sample a FIELD) that is a
        scan of the predicate's field and a join of the others, run
        with ONE shared resolve: on the device over the resident
        decode slices, or by the host decode route (storage/read.py::
        select_segments decides per segment, and counts).  A chunked
        table scans each field by query() and joins on the host.

        Traced as children of the request's root: one `resolve` span,
        one `select` span (its children a segment: route=, rows_in=,
        rows_out=).  Raises Error (a 400) for a field the metric does
        not have in the range, before any scan."""
        ensure(len(fields) > 0, "fields must be non-empty")
        ensure(len(set(fields)) == len(fields), "fields must be distinct")
        ensure(not {"tsid", "timestamp"} & set(fields),
               "a field may not be named tsid or timestamp")
        spec = SelectSpec(group_col="tsid", ts_col="timestamp",
                          value_col="value", op=op, threshold=value)
        _ROWS_QUERIES.inc()
        distinct = [where_field] + [f for f in fields if f != where_field]
        with span("resolve", metric=metric):
            unknown = await self.metric_manager.unknown_fields(
                metric, distinct, time_range)
            ensure(not unknown,
                   f"unknown field(s) {unknown} of metric {metric!r} in "
                   f"the range")
            parts = await self._data_pred_parts(metric, filters,
                                                time_range)
        t0 = time.perf_counter()
        with span("select", metric=metric, fields=len(fields)):
            if parts is None:
                out = {"groups": [], "timestamps": [],
                       "values": [[] for _ in fields],
                       "found": [[] for _ in fields]}
            elif self.chunked_data:
                out = await self._rows_where_chunked(
                    metric, filters, time_range, spec, where_field, fields)
            else:
                reqs = [ScanRequest(range=time_range, predicate=And(
                    [parts[0], Eq("field_id", field_id_of(f))] + parts[1:]))
                    for f in distinct]
                qp = await self.tables["data"].plan_select(
                    reqs, spec, [distinct.index(f) for f in fields])
                out = await self.tables["data"].execute_plan(qp)
        _ROWS_SELECT_SECONDS.inc(time.perf_counter() - t0)
        return _rows_table(out, fields)

    async def _rows_where_chunked(self, metric, filters, time_range,
                                  spec: SelectSpec, where_field: str,
                                  fields: list[str]) -> dict:
        """query_rows_where over a chunked table: the predicate's field
        by query() (chunks decoded, deduplicated), its float32 values
        put to the predicate, every other field by query() and joined
        on the host."""
        def columns(tbl: pa.Table):
            return (tbl.column("tsid").to_numpy(),
                    tbl.column("timestamp").to_numpy(),
                    tbl.column("value").to_numpy().astype(np.float32))

        groups, ts, vals = columns(await self.query(
            metric, filters, time_range, field=where_field))
        order = np.flatnonzero(
            compare(vals, spec.op, np.float32(spec.threshold)))
        order = order[np.lexsort((ts[order], groups[order]))]
        groups, ts, vals = groups[order], ts[order], vals[order]
        values, found = [], []
        for f in fields:
            if f == where_field:
                values.append(vals)
                found.append(np.ones(len(ts), bool))
                continue
            v, ok = join_on_host(groups, ts, *columns(await self.query(
                metric, filters, time_range, field=f)))
            values.append(v)
            found.append(ok)
        return {"groups": groups, "timestamps": ts, "values": values,
                "found": found}

    async def query_last(self, metric: str,
                         filters: list[tuple[str, str]],
                         fields: list[str], start: Optional[int] = None,
                         end: Optional[int] = None) -> pa.Table:
        """The newest row of every series of `metric` that passes the
        label filters: TSBS's lastpoint, a status page's and an instant
        query's question.  For every such series with at least one
        CURRENT sample (after last-write-wins dedup: what query()
        returns) of a field asked in [start, end), an absent bound
        unbounded, ONE row: tsid (uint64), timestamp (int64: the
        greatest at which any field asked has such a sample), then one
        nullable float32 column a field, in the order asked: the
        field's value at exactly that timestamp, null where it has no
        sample there.  Rows ascend by tsid.  A series without a sample
        has no row.  Exact and whole: no look-back unless the client
        names one, no cut after some segments; a series whose newest
        sample lies in the oldest segment is answered.

        ONE shared resolve for all fields, which also names the series
        the walk must account for: the label filters' posting lists, or
        every series of the metric (IndexManager.series_of); never a
        time cut-off.  The walk (CloudObjectStorage.scan_last) takes
        the data table's segments newest first and stops when no series
        is missing: on the device over the resident decode slices, or
        by the row scan (storage/read.py::last_segment decides per
        segment, and counts).  A chunked table scans each field by
        query() and reduces on the host.

        Traced as children of the request's root: one `resolve` span,
        one `last` span (its children a segment asked: route=,
        series_in=, series_out=, rows_read=).  Raises Error (a 400) for
        a field the metric does not have, before any scan; a metric
        nobody wrote answers its columns and no row."""
        ensure(len(fields) > 0, "fields must be non-empty")
        ensure(len(set(fields)) == len(fields), "fields must be distinct")
        ensure(not {"tsid", "timestamp"} & set(fields),
               "a field may not be named tsid or timestamp")
        rng = TimeRange.new(int(Timestamp.MIN) if start is None else start,
                            int(Timestamp.MAX) if end is None else end)
        ensure(rng.start < rng.end, "start must lie before end")
        spec = LastSpec(group_col="tsid", ts_col="timestamp",
                        value_col="value")
        _LAST_QUERIES.inc()
        with span("resolve", metric=metric):
            mid = await self.metric_manager.resolve(metric, rng)
            expect = tsids = None
            if mid is not None:
                unknown = await self.metric_manager.unknown_fields(
                    metric, fields, rng)
                ensure(not unknown,
                       f"unknown field(s) {unknown} of metric {metric!r}")
                tsids = await self.index_manager.find_tsids(mid, filters,
                                                            rng)
                expect = (await self.index_manager.series_of(mid, rng)
                          if tsids is None
                          else np.asarray(sorted(tsids), dtype=np.uint64))
        t0 = time.perf_counter()
        with span("last", metric=metric, fields=len(fields)):
            if expect is None or not len(expect):
                out = {"groups": [], "timestamps": [],
                       "values": [[] for _ in fields],
                       "found": [None for _ in fields]}
            elif self.chunked_data:
                out = await self._last_chunked(metric, filters, rng, fields,
                                               expect)
            else:
                leaves = [Eq("metric_id", mid)]
                if start is not None or end is not None:
                    leaves.append(TimeRangePred(
                        "timestamp", int(rng.start), int(rng.end)))
                if tsids is not None:
                    leaves.append(In("tsid", sorted(tsids)))
                reqs = [ScanRequest(range=rng, predicate=And(
                    [leaves[0], Eq("field_id", field_id_of(f))]
                    + leaves[1:])) for f in fields]
                qp = await self.tables["data"].plan_last(reqs, spec, expect)
                out = await self.tables["data"].execute_plan(qp)
        _LAST_SECONDS.inc(time.perf_counter() - t0)
        return _rows_table(out, fields)

    async def _last_chunked(self, metric, filters, rng: TimeRange,
                            fields: list[str], expect) -> dict:
        """query_last over a chunked table: every field by query()
        (chunks decoded, deduplicated) over the whole range at once,
        the series' last rows and their combine on the host."""
        per_field = []
        for f in fields:
            tbl = await self.query(metric, filters, rng, field=f)
            per_field.append(last_on_host(
                tbl.column("tsid").to_numpy(),
                tbl.column("timestamp").to_numpy(),
                tbl.column("value").to_numpy().astype(np.float32)))
        part = combine_fields(per_field, expect)
        return {"groups": part.groups, "timestamps": part.timestamps,
                "values": part.values,
                "found": [None if f.all() else f for f in part.found]}

    async def query_buckets(self, metric: str,
                            filters: list[tuple[str, str]], field: str,
                            bucket_ms: int, limit: int, aggs: list[str],
                            start: Optional[int] = None,
                            end: Optional[int] = None) -> pa.Table:
        """One field of `metric` aggregated ACROSS every series that
        passes the label filters, by time bucket, the `limit` newest
        buckets: TSBS's groupby-orderby-limit, a dashboard's fleet
        panel, PromQL's `max(...)` without `by`.  Buckets are
        epoch-aligned: bucket k is [k * bucket_ms, (k + 1) *
        bucket_ms).  A bucket EXISTS if some such series has a CURRENT
        sample (after last-write-wins dedup: what query() returns) of
        `field` in it at start <= timestamp < end, an absent bound
        unbounded.  The answer holds the `limit` newest existing
        buckets (fewer if fewer exist), DESCENDING by bucket start, one
        row each: bucket (int64, its start), count (int64: the samples
        folded, over all series), then a float32 column an aggregate
        asked, in the order asked, of max, min (bit for bit a stored
        value), sum, avg (float32-rounded).  The bucket that holds
        `end` is answered from its samples before `end` alone.  Exact
        and whole: no look-back unless the client names one, no cut
        after some segments, and no rollup tier is read (a rollup cell
        is a series' cell).

        ONE resolve (the label filters' posting lists; no filter stays
        "every series": no series set is built).  The walk
        (CloudObjectStorage.scan_buckets) takes the data table's
        segments newest first from `end`'s and stops when `limit`
        buckets exist that no older segment can add to: on the device
        over the resident decode slice of the field, or by the row
        scan (storage/read.py::buckets_segment decides per segment,
        and counts).  A chunked table scans the field by query() and
        folds on the host.

        Traced as children of the request's root: one `resolve` span,
        one `buckets` span (its children a segment asked: route=,
        reason=, rows_read=, rows_used=, buckets_out=).  Raises Error
        (a 400) for a field the metric does not have or an aggregate
        that does not exist, before any scan; a metric nobody wrote
        answers its columns and no row."""
        ensure(len(aggs) > 0 and len(set(aggs)) == len(aggs),
               "aggs must be a non-empty list of distinct aggregates")
        unknown = [a for a in aggs if a not in buckets_ops.AGGS]
        ensure(not unknown, f"unknown aggregate(s) {unknown}; of "
                            f"{list(buckets_ops.AGGS)}")
        ensure(bucket_ms >= 1, "bucket_ms must be at least 1")
        ensure(1 <= limit <= buckets_ops.MAX_LIMIT,
               f"limit must lie in 1..{buckets_ops.MAX_LIMIT}")
        rng = TimeRange.new(int(Timestamp.MIN) if start is None else start,
                            int(Timestamp.MAX) if end is None else end)
        ensure(rng.start < rng.end, "start must lie before end")
        spec = buckets_ops.BucketsSpec(
            group_col="tsid", ts_col="timestamp", value_col="value",
            bucket_ms=int(bucket_ms), aggs=tuple(aggs))
        _BUCKETS_QUERIES.inc()
        with span("resolve", metric=metric):
            mid = await self.metric_manager.resolve(metric, rng)
            tsids = None
            if mid is not None:
                unknown = await self.metric_manager.unknown_fields(
                    metric, [field], rng)
                ensure(not unknown,
                       f"unknown field(s) {unknown} of metric {metric!r}")
                tsids = await self.index_manager.find_tsids(mid, filters,
                                                            rng)
        t0 = time.perf_counter()
        with span("buckets", metric=metric, field=field, limit=limit):
            if mid is None or (tsids is not None and not tsids):
                out = buckets_ops.answer_columns(buckets_ops.Merged(),
                                                 spec, limit)
            elif self.chunked_data:
                tbl = await self.query(metric, filters, rng, field=field)
                merged = buckets_ops.Merged()
                merged.add(buckets_ops.buckets_on_host(
                    tbl.column("timestamp").to_numpy(),
                    tbl.column("value").to_numpy().astype(np.float32),
                    spec, int(rng.start), int(rng.end)), limit)
                out = buckets_ops.answer_columns(merged, spec, limit)
            else:
                # the time leaf always rides, bounded or not: one
                # program whatever bounds a request names
                leaves = [Eq("metric_id", mid),
                          Eq("field_id", field_id_of(field)),
                          TimeRangePred("timestamp", int(rng.start),
                                        int(rng.end))]
                if tsids is not None:
                    leaves.append(In("tsid", sorted(tsids)))
                qp = await self.tables["data"].plan_buckets(
                    ScanRequest(range=rng, predicate=And(leaves)), spec,
                    limit)
                out = await self.tables["data"].execute_plan(qp)
        _BUCKETS_SECONDS.inc(time.perf_counter() - t0)
        return pa.table(
            [pa.array(out["bucket"], type=pa.int64()),
             pa.array(out["count"], type=pa.int64())]
            + [pa.array(out[a], type=pa.float32()) for a in aggs],
            names=["bucket", "count"] + list(aggs))

    async def _downsample_chunked(self, metric: str, filters, time_range,
                                  bucket_ms: int, num_buckets: int,
                                  field: str = "value",
                                  which: tuple = ALL_AGGS) -> dict:
        """Chunked-layout downsample that NEVER builds an Arrow row
        table: chunk payloads batch-decode (numpy-vectorized) straight
        into the fixed-width arrays the device aggregation consumes
        (VERDICT r2 item 5; RFC 20240827:218-231 is the layout).  Same
        pushdown grids as the row layout — parity-tested.

        Repeat queries skip the (uncached Append-mode) scan AND the
        decode via the engine's decode LRU: the key is (canonical
        predicate, exact range, the data table's overlapping SST ids),
        so any write or compaction structurally invalidates, exactly
        like the row layout's scan cache.  The cached entry also memoizes
        the padded device arrays, so a repeat only re-runs the compiled
        aggregate."""
        from horaedb_tpu.ops.filter import canonical_predicate_key

        pred = await self._resolve_data_predicate(metric, filters,
                                                  time_range, field)
        if pred is None:
            return {"tsids": [], "num_buckets": num_buckets, "aggs": {}}
        key = entry = None
        if self._chunk_cache is not None:
            ssts = await self.tables["data"].manifest.find_ssts(time_range)
            key = (canonical_predicate_key(pred),
                   int(time_range.start), int(time_range.end),
                   tuple(sorted(f.id for f in ssts)))
            entry = self._chunk_cache.get(key)
        fresh = entry is None
        if fresh:
            batches = await _collect(self.tables["data"].scan(ScanRequest(
                range=time_range, predicate=pred)))
            decoded = self._decode_chunk_arrays(batches, time_range)
            if decoded is None:
                return {"tsids": [], "num_buckets": num_buckets,
                        "aggs": {}}
            entry = {"decoded": decoded, "memo": {}}
        tsid_np, ts_np, val_np = entry["decoded"]
        out = self._downsample_arrays(tsid_np, ts_np, val_np, time_range,
                                      bucket_ms, num_buckets, which=which,
                                      memo=entry["memo"])
        if fresh and key is not None:
            # charge AFTER the memo is built so the device padded
            # arrays are counted at their real size
            dev = entry["memo"].get("dev", {})
            nbytes = 24 * len(ts_np) + 1024 + sum(
                int(a.nbytes) for a in dev.values()
                if hasattr(a, "nbytes"))
            self._chunk_cache.put(key, entry, nbytes)
        return out

    @staticmethod
    def _host_bucket_grids(gid, ts_rel, vals, num_groups: int,
                           bucket_ms: int, num_buckets: int,
                           which: tuple) -> dict:
        """numpy twin of ops.downsample.time_bucket_aggregate for host
        -bound backends: accumulation cores shared with the reader's
        window partials (read.host_cell_grids), finished with the
        device path's empty-cell conventions (count 0, min +inf,
        max -inf, avg/last NaN), float32 outputs."""
        import numpy as np

        from horaedb_tpu.storage.read import host_cell_grids

        which = set(which)
        want = set(which) | ({"sum"} if "avg" in which else set())
        ncells = num_groups * num_buckets
        shape = (num_groups, num_buckets)
        cell = gid.astype(np.int64) * num_buckets + ts_rel // bucket_ms
        cores = host_cell_grids(cell, np.asarray(vals), ts_rel, ncells,
                                want)
        count = cores["count"].astype(np.float32)
        out = {"count": count.reshape(shape)}
        empty = count == 0
        if "sum" in which:
            out["sum"] = cores["sum"].astype(np.float32).reshape(shape)
        if "avg" in which:
            with np.errstate(invalid="ignore"):
                avg = np.where(empty, np.nan,
                               cores["sum"] / np.maximum(count, 1.0))
            out["avg"] = avg.astype(np.float32).reshape(shape)
        for k in ("min", "max"):
            if k in which:
                out[k] = cores[k].astype(np.float32).reshape(shape)
        if "last" in which:
            lt, li = cores["last"]
            last = np.full(ncells, np.nan)
            has = li >= 0
            last[has] = np.asarray(vals)[li[has]]
            out["last"] = last.astype(np.float32).reshape(shape)
        return out

    def _downsample_rows(self, tbl: pa.Table, time_range: TimeRange,
                         bucket_ms: int, num_buckets: int,
                         which: tuple = ALL_AGGS) -> dict:
        if tbl.num_rows == 0:
            return {"tsids": [], "num_buckets": num_buckets, "aggs": {}}
        return self._downsample_arrays(
            tbl.column("tsid").to_numpy(), tbl.column("timestamp").to_numpy(),
            tbl.column("value").to_numpy(), time_range, bucket_ms,
            num_buckets, which=which)

    def _downsample_arrays(self, tsid_np, ts_np, val_np,
                           time_range: TimeRange, bucket_ms: int,
                           num_buckets: int,
                           which: tuple = ALL_AGGS,
                           memo: Optional[dict] = None) -> dict:
        """`memo` (chunk decode cache entries pass one) holds the padded
        DEVICE arrays after the first aggregate, so repeats upload
        nothing.  Valid because the cache key pins the exact time range
        (ts offsets are range_start-relative)."""
        import numpy as np

        import jax.numpy as jnp

        from horaedb_tpu.ops.downsample import time_bucket_aggregate
        from horaedb_tpu.ops.encode import pad_capacity

        n = len(ts_np)
        dev = memo.get("dev") if memo is not None else None
        if dev is None:
            # dense group ids WITHOUT a full-length np.unique: chunk
            # decode emits long per-row runs of equal tsids, so
            # dense-ify the run VALUES (~one per chunk row) and repeat
            # the codes over run lengths — identical output to
            # np.unique(tsid_np, return_inverse=True) at a fraction of
            # the cost (the argsort of 10M u64s was the chunked cold
            # path's largest single op)
            if n:
                new_run = np.empty(n, dtype=bool)
                new_run[0] = True
                np.not_equal(tsid_np[1:], tsid_np[:-1], out=new_run[1:])
                run_idx = np.flatnonzero(new_run)
                uniq, inv = np.unique(tsid_np[run_idx],
                                      return_inverse=True)
                run_lens = np.diff(np.append(run_idx, n))
                gid = np.repeat(inv.astype(np.int32), run_lens)
            else:
                uniq = np.empty(0, dtype=np.uint64)
                gid = np.empty(0, dtype=np.int32)
            ts_rel = ts_np - int(time_range.start)
            dev = {"uniq": uniq, "gid_host": gid, "ts_rel": ts_rel,
                   "val_host": val_np}
            if memo is not None:
                memo["dev"] = dev
        uniq = dev["uniq"]
        from horaedb_tpu.storage.read import host_agg_default

        if host_agg_default():
            # numpy twin on host-bound backends (same trade-off as the
            # reader's _host_agg_ok: bincount beats XLA-CPU's segmented
            # scatters ~20x and there is no transfer to amortize)
            host = self._host_bucket_grids(dev["gid_host"], dev["ts_rel"],
                                           dev["val_host"], len(uniq),
                                           bucket_ms, num_buckets, which)
        else:
            if "ts" not in dev:
                cap = pad_capacity(n)
                pad = lambda a, d: np.pad(a.astype(d), (0, cap - n))
                dev["ts"] = jnp.asarray(pad(dev["ts_rel"], np.int32))
                dev["gid"] = jnp.asarray(pad(dev["gid_host"], np.int32))
                dev["val"] = jnp.asarray(pad(dev["val_host"], np.float32))
            aggs = time_bucket_aggregate(
                dev["ts"], dev["gid"], dev["val"],
                n, bucket_ms, num_groups=len(uniq),
                num_buckets=num_buckets, which=which)
            host = {k: np.asarray(v) for k, v in aggs.items()}
        if "last" in which:
            # match the pushdown path's grid keys (it emits last_ts only
            # alongside last): per-cell max sample time (absolute ms as
            # float, NaN for empty cells)
            gid_h, ts_rel = dev["gid_host"], dev["ts_rel"]
            cell = gid_h.astype(np.int64) * num_buckets + ts_rel // bucket_ms
            last_ts = np.full(len(uniq) * num_buckets, -np.inf)
            np.maximum.at(last_ts, cell, ts_rel.astype(np.float64))
            last_ts = last_ts.reshape(len(uniq), num_buckets)
            host["last_ts"] = np.where(np.isinf(last_ts), np.nan,
                                       last_ts + int(time_range.start))
        return {"tsids": [int(t) for t in uniq],
                "num_buckets": num_buckets, "aggs": host}

    async def label_values(self, metric: str, tag_key: str,
                           time_range: TimeRange) -> list[str]:
        mid = await self.metric_manager.resolve(metric, time_range)
        if mid is None:
            return []
        return await self.index_manager.label_values(mid, tag_key, time_range)

    async def label_names(self, metric: str,
                          time_range: TimeRange) -> list[str]:
        """Distinct tag keys of a metric in the window (Prometheus
        /api/v1/labels analogue)."""
        mid = await self.metric_manager.resolve(metric, time_range)
        if mid is None:
            return []
        return await self.index_manager.label_names(mid, time_range)

    async def list_metrics(self, time_range: TimeRange) -> list[str]:
        """Distinct metric names active in the window (Prometheus
        /api/v1/label/__name__/values analogue)."""
        return await self.metric_manager.list_metrics(time_range)

    async def list_fields(self, metric: str,
                          time_range: TimeRange) -> list[str]:
        """Distinct field names of a metric in the window."""
        return await self.metric_manager.list_fields(metric, time_range)
