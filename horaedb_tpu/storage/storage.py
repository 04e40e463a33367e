"""TimeMergeStorage facade (ref: src/storage/src/storage.rs).

`CloudObjectStorage` splits data into `segment_duration` time segments.
write() sorts a batch by PK, stamps builtin columns with the file id as
sequence, writes one Parquet SST, and records it in the manifest
(ref: storage.rs:188-224, 306-332).  scan() groups manifest hits by
segment and executes one device merge-dedup program per segment
(ref: storage.rs:334-369 + our read.py).  On-disk layout matches the
reference (storage.rs:125-135):

    {root_path}/manifest/snapshot
    {root_path}/manifest/delta/{id}
    {root_path}/data/{id}.sst
"""

from __future__ import annotations

import abc
import asyncio
import time
from dataclasses import dataclass
from typing import AsyncIterator, NamedTuple, Optional

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

import logging

from horaedb_tpu.common.error import ensure
from horaedb_tpu.objstore import (
    NotFoundError,
    ObjectStore,
    RetryingObjectStore,
    RetryPolicy,
)
from horaedb_tpu.storage import parquet_io, sidecar
from horaedb_tpu.storage.gc import Scrubber, ScrubReport
from horaedb_tpu.storage.config import StorageConfig, UpdateMode
from horaedb_tpu.storage.manifest import Manifest
from horaedb_tpu.storage.read import ParquetReader, ScanPlan, ScanRequest
from horaedb_tpu.storage.sst import FileMeta, SstFile, segment_of, sst_path
from horaedb_tpu.storage.types import (
    StorageSchema,
    TimeRange,
    Timestamp,
)
from horaedb_tpu.utils import registry

logger = logging.getLogger(__name__)

_WRITE_LATENCY = registry.histogram(
    "storage_write_seconds", "write path latency")
_ROWS_WRITTEN = registry.counter(
    "storage_rows_written_total", "rows written")


@dataclass
class WriteRequest:
    """(ref: storage.rs:58-63)"""

    batch: pa.RecordBatch  # user schema (no builtin columns)
    time_range: TimeRange
    # When false, the caller guarantees the batch does not cross a segment
    # boundary (the load generator path).
    enable_check: bool = True


@dataclass
class WriteResult:
    id: int
    seq: int
    size: int


class SegmentVersion(NamedTuple):
    """What a scan of one segment would read: the ids of the segment's
    SSTs that a range selects, sorted, and their rows summed.  An SST
    is immutable and its id names its object for good (a process-wide
    counter seeded from the wall clock: storage/sst.py), so two equal
    id tuples of one table mean the same rows — every write, flush,
    compaction, scrub or manifest reload that changes the content
    changes the tuple."""

    ids: tuple
    rows: int


class TimeMergeStorage(abc.ABC):
    """Engine facade (ref: storage.rs:76-89)."""

    @abc.abstractmethod
    def schema(self) -> StorageSchema: ...

    @abc.abstractmethod
    async def write(self, req: WriteRequest) -> WriteResult: ...

    @abc.abstractmethod
    def scan(self, req: ScanRequest) -> AsyncIterator[pa.RecordBatch]: ...

    @abc.abstractmethod
    async def compact(self) -> None: ...

    @abc.abstractmethod
    async def segment_versions(
            self, time_range: TimeRange
    ) -> dict[int, Optional[SegmentVersion]]:
        """Segment start -> the version of every segment a scan of
        `time_range` would read; None where rows outside any SST would
        be merged in (no SST set names that content)."""


async def _scan_segment(scan, req, start: int) -> list:
    """The batches `scan` (a row scan: merged, deduplicated, the
    memtable's rows overlaid where it is the WAL wrapper's) returns for
    the one segment that begins at `start`."""
    rows = scan(req, segment_filter=lambda s: s == start)
    try:
        return [b async for b in rows]
    finally:
        await rows.aclose()


class CloudObjectStorage(TimeMergeStorage):
    def __init__(self, root_path: str, segment_duration_ms: int,
                 store: ObjectStore, user_schema: pa.Schema,
                 num_primary_keys: int, config: Optional[StorageConfig] = None,
                 runtimes=None):
        from horaedb_tpu.common import runtimes as runtimes_mod

        config = config or StorageConfig()
        self.root_path = root_path.rstrip("/")
        self.segment_duration_ms = segment_duration_ms
        self.store = store
        self.config = config
        self._schema = StorageSchema.try_new(user_schema, num_primary_keys,
                                             config.update_mode)
        self.manifest: Optional[Manifest] = None
        self.scrubber: Optional[Scrubber] = None
        # dedicated worker pools (ref: StorageRuntimes, storage.rs:91-104);
        # shared when a parent (e.g. MetricEngine) passes its own
        self._own_runtimes = runtimes is None
        self.runtimes = runtimes or runtimes_mod.from_config(
            config.threads, sst_override=config.scan.decode_workers)
        self.reader = ParquetReader(store, self.root_path, self._schema,
                                    config, segment_duration_ms,
                                    runtimes=self.runtimes)
        self.compact_scheduler = None  # populated by open()

    @classmethod
    async def open(cls, *args, **kwargs) -> "CloudObjectStorage":
        self = cls(*args, **kwargs)
        # The manifest plane gets the engine's ONE retry layer: a single
        # transient store error must not fail an otherwise-healthy
        # acknowledged write on backends without built-in retries.  The
        # data plane stays single-shot — SST put failures surface to the
        # write path's rollback discipline (and its tests).
        manifest_store: ObjectStore = self.store
        rc = self.config.retry
        if rc.enabled:
            manifest_store = RetryingObjectStore(self.store, RetryPolicy(
                max_retries=rc.max_retries,
                base_backoff_s=rc.base_backoff.seconds,
                max_backoff_s=rc.max_backoff.seconds,
                op_deadline_s=(rc.op_deadline.seconds
                               if rc.op_deadline else None),
                budget=float(rc.budget),
                budget_refill_per_s=rc.budget_refill_per_s))
        self.manifest = await Manifest.open(self.root_path, manifest_store,
                                            self.config.manifest,
                                            runtimes=self.runtimes)
        # the scrubber reconciles against the RAW store: its deletes are
        # already a retry loop (next pass), and reads that fail simply
        # postpone reclamation
        self.scrubber = Scrubber(self.root_path, self.store, self.manifest,
                                 self.config.scrub.grace_period.seconds)
        self.reader.resolve_segment_ssts = self._segment_ssts_now
        await self._start_compaction()
        return self

    async def scrub(self, grace_override_s: Optional[float] = None
                    ) -> ScrubReport:
        """One orphan-reconcile pass (see storage/gc.py); also the
        POST /admin/scrub entry point."""
        ensure(self.scrubber is not None, "storage not opened")
        return await self.scrubber.scrub(grace_override_s=grace_override_s)

    async def _segment_ssts_now(self, segment_start: int,
                                scan_range: Optional[TimeRange]):
        """CURRENT SSTs of one segment that overlap the scan's requested
        range — a streamed segment uses this to survive a compaction
        race mid-segment (read.py).  The range filter mirrors
        build_scan_plan's manifest.find_ssts so recovery cannot leak
        rows from SSTs the original plan excluded."""
        ssts = await self.manifest.all_ssts()
        return [f for f in ssts
                if segment_of(f, self.segment_duration_ms) == segment_start
                and (scan_range is None
                     or f.meta.time_range.overlaps(scan_range))]

    async def _start_compaction(self) -> None:
        from horaedb_tpu.storage.compaction import Scheduler

        self.compact_scheduler = Scheduler(self)
        await self.compact_scheduler.start()

    async def close(self) -> None:
        if self.compact_scheduler is not None:
            await self.compact_scheduler.stop()
        if self.manifest is not None:
            await self.manifest.close()
        # release EVERY reader-owned cache tier (and the process-wide
        # byte gauges + ledger accounts behind them): a closed table's
        # entries can never be read again, and /debug/memory must not
        # serve phantom tables
        self.reader.close()
        if self._own_runtimes:
            self.runtimes.close()

    # ------------------------------------------------------------------

    def schema(self) -> StorageSchema:
        return self._schema

    def _sort_batch(self, batch: pa.RecordBatch) -> pa.RecordBatch:
        """Sort by primary keys ascending (ref: storage.rs:243-255 does
        this via a DataFusion SortExec; arrow-native sort here)."""
        keys = [(n, "ascending") for n in self._schema.primary_key_names]
        return batch.take(pc.sort_indices(batch, sort_keys=keys))

    def validate_write(self, req: WriteRequest) -> None:
        """All write-path invariants, split out so the WAL ingest front
        end (wal/ingest.py) rejects a bad batch BEFORE logging it."""
        ensure(self.manifest is not None, "storage not opened")
        ensure(req.batch.schema.equals(self._schema.user_schema),
               "write batch schema mismatch")
        # Nulls are rejected at write time: the device scan path carries no
        # null mask, so a null-bearing SST would poison every later scan
        # and compaction of its segment.
        for name, col in zip(req.batch.schema.names, req.batch.columns):
            ensure(col.null_count == 0,
                   f"write batch column {name!r} contains nulls")
        if req.enable_check:
            start_seg = req.time_range.start.truncate_by(self.segment_duration_ms)
            end_seg = Timestamp(int(req.time_range.end) - 1).truncate_by(
                self.segment_duration_ms)
            ensure(start_seg == end_seg,
                   f"write batch crosses segment boundary: {req.time_range}")

    async def write(self, req: WriteRequest) -> WriteResult:
        self.validate_write(req)
        return await self._write_batch(req)

    async def _write_batch(self, req: WriteRequest) -> WriteResult:
        t0 = time.perf_counter()
        file_id = SstFile.allocate_id()

        def prep():  # sort + builtin stamping are CPU work — off the loop
            sorted_batch = self._sort_batch(req.batch)
            return self._schema.fill_builtin_columns(sorted_batch,
                                                     sequence=file_id)

        stamped = await self.runtimes.run("sst", prep)
        result = await self._persist_stamped(file_id, stamped,
                                             req.time_range)
        _WRITE_LATENCY.observe(time.perf_counter() - t0)
        return result

    async def write_stamped(self, table: pa.Table,
                            time_range: TimeRange,
                            pre_commit=None) -> WriteResult:
        """Memtable-flush write path (wal/ingest.py): rows arrive with
        `__seq__` already filled per row (each entry's original write
        seq).  Seqs are PRESERVED — restamping would let a flush racing
        a concurrent write elevate old rows above a newer seq — so the
        SST is sorted by (PK, __seq__) and dedup keeps working off the
        original write order, exactly like a compaction output (which
        also carries heterogeneous per-row seqs).

        `pre_commit` (an async callable) runs AFTER the SST/sidecar
        puts and immediately before the manifest add — the replication
        fencing seam: the SST upload can take a whole lease TTL, so
        ownership must be revalidated at the publish point, not just
        when the flush started.  A raise leaves an orphan SST object
        but no manifest entry — invisible to every reader.
        """
        ensure(self.manifest is not None, "storage not opened")
        ensure(table.schema.names == self._schema.arrow_schema.names,
               "write_stamped expects the full stamped schema")
        file_id = SstFile.allocate_id()

        def prep():
            keys = [(n, "ascending") for n in self._schema.primary_key_names]
            keys.append((self._schema.arrow_schema.names[self._schema.seq_idx],
                         "ascending"))
            ordered = table.take(pc.sort_indices(table, sort_keys=keys))
            return ordered.combine_chunks().to_batches()[0]

        stamped = await self.runtimes.run("sst", prep)
        return await self._persist_stamped(file_id, stamped, time_range,
                                           pre_commit=pre_commit)

    async def _persist_stamped(self, file_id: int, stamped: pa.RecordBatch,
                               time_range: TimeRange,
                               pre_commit=None) -> WriteResult:
        """THE persist tail shared by the direct write path and the WAL
        flush path (write_stamped): SST put overlapped with the sidecar
        put, which completes BEFORE the manifest add — readers never
        see a manifest-listed SST whose sidecar is still in flight, so
        a sidecar miss is permanent per id (the reader memoizes misses
        on that contract).  max_sequence tracks the file id: the
        snapshot codec reconstructs it as the id anyway."""
        path = sst_path(self.root_path, file_id)
        size, _ = await asyncio.gather(
            parquet_io.write_sst(self.store, path, [stamped],
                                 self.config.write, self._schema,
                                 runtimes=self.runtimes),
            self._write_sidecar(file_id, stamped))
        if pre_commit is not None:
            await pre_commit()
        meta = FileMeta(max_sequence=file_id, num_rows=stamped.num_rows,
                        size=size, time_range=time_range)
        await self.manifest.add_file(file_id, meta)
        _ROWS_WRITTEN.inc(stamped.num_rows)
        return WriteResult(id=file_id, seq=file_id, size=size)

    async def _write_sidecar(self, file_id: int,
                             stamped: pa.RecordBatch) -> None:
        """Best-effort device-layout sidecar next to the SST (see
        storage/sidecar.py): pure cache — any failure is logged and
        swallowed, reads fall back to parquet.  The freshly-encoded
        columns are write-through-admitted into the reader's tier-2
        cache (storage/encoded_cache.py): both the direct write path
        and the WAL flusher land here (_persist_stamped), so a query
        right after a write/flush rebuilds its segment without a single
        object-store read."""
        if (self._schema.update_mode is not UpdateMode.OVERWRITE
                or not self.config.write.enable_sidecar
                or stamped.num_rows > self.config.write.sidecar_max_rows):
            return
        try:
            def build():
                cols = sidecar.encode_columns(stamped)
                if cols is None:
                    return None, None
                return cols, sidecar.serialize(cols, stamped.num_rows)

            cols, data = await self.runtimes.run("sst", build)
            if data is None:
                return
            # admit BEFORE the put: the entry is valid the instant the
            # columns exist (ids are immutable), and the SST only
            # becomes reader-visible after the manifest add anyway
            self.reader.encoded_cache.admit(file_id, cols,
                                            stamped.num_rows)
            await self.store.put(
                sidecar.sidecar_path(self.root_path, file_id), data)
        except Exception as exc:  # noqa: BLE001 — cache write only
            logger.warning("sidecar write failed for sst %s: %s",
                           file_id, exc)

    # Scans race with compaction: the manifest can reference an SST that
    # compaction deletes before the scan's parquet read runs.  The data
    # lives on in the compacted output, so the remedy is a fresh plan for
    # the not-yet-yielded segments (bounded retries).
    _SCAN_RETRIES = 3

    async def scan(self, req: ScanRequest,
                   first_plan: Optional[ScanPlan] = None,
                   keep_builtin: bool = False,
                   segment_filter=None) -> AsyncIterator[pa.RecordBatch]:
        # explicit aclose on abandonment: an `async for` left mid-loop
        # does NOT close its source, and GC-time finalization would let
        # the scan pipeline's in-flight tasks outlive the query into
        # table teardown (deterministic-teardown discipline, PR 3/8)
        seg_iter = self.scan_segments(req, first_plan=first_plan,
                                      keep_builtin=keep_builtin,
                                      segment_filter=segment_filter)
        try:
            async for _seg, batch in seg_iter:
                if batch is not None:
                    yield batch
        finally:
            await seg_iter.aclose()

    async def scan_segments(self, req: ScanRequest,
                            first_plan: Optional[ScanPlan] = None,
                            keep_builtin: bool = False,
                            segment_filter=None):
        """scan() with segment attribution: yields (segment_start,
        batch) parts plus a (segment_start, None) completion marker per
        segment — the hybrid WAL scan (wal/ingest.py) overlays memtable
        rows per segment and needs to know when one is complete.
        `segment_filter(segment_start) -> bool` restricts the scan to a
        stable subset across compaction-race replans."""
        done: set[int] = set()
        for attempt in range(self._SCAN_RETRIES + 1):
            # attempt 0 may reuse a caller-built plan (plan_query):
            # one manifest lookup per query; a stale plan just races
            # into the NotFoundError replan below like any other scan
            plan = (first_plan if attempt == 0 and first_plan is not None
                    else await self.build_scan_plan(
                        req, keep_builtin=keep_builtin))
            plan.segments = [s for s in plan.segments
                             if s.segment_start not in done
                             and (segment_filter is None
                                  or segment_filter(s.segment_start))]
            exec_iter = self.reader.execute_segments(plan)
            try:
                async for seg_start, batch in exec_iter:
                    if batch is None:
                        # explicit completion marker: only now is the
                        # segment retry-safe to skip (it may have
                        # spanned several window batches)
                        done.add(seg_start)
                    yield seg_start, batch
                return
            except NotFoundError:
                if attempt == self._SCAN_RETRIES:
                    raise
                logger.info("scan raced a compaction (sst vanished); "
                            "replanning remaining segments")
            finally:
                # deterministic teardown on abandonment/error: drain
                # the read pipeline NOW, not at GC finalization
                await exec_iter.aclose()

    async def scan_aggregate(self, req: ScanRequest, spec,
                             first_plan: Optional[ScanPlan] = None,
                             top_k=None):
        """Downsample pushdown: merge + GROUP BY group_col, time(bucket)
        on device; returns (group_values, grids).  See read.AggregateSpec.
        The fused path (single-device host_perm) accumulates into one
        query-global device grid and restarts whole on a compaction
        race; the parts path skips segments completed before the race
        on its replan.

        `top_k` (a plan.TopKSpec) pushes the ranking into the combine:
        the parts path folds per-group spans into a bounded score pass
        and materializes only the k winners (combine_top_k) — the full
        groups x buckets grid is never built.  The fused path's grids
        already live on device, so it keeps the host-side slice."""
        if first_plan is None:
            first_plan = await self._plan_aggregate(req, spec)
        # a caller-built plan that plan_query did not route is routed here
        route = (first_plan.route
                 or self.reader.aggregate_route(first_plan, spec))
        # per-trace memory attribution (common/memledger.py): a cold
        # aggregate moves megabytes into the cache tiers — the trace
        # records which account they landed in
        mem_marks = self.reader._mem_delta_marks()
        try:
            if route in ("replay", "fused_acc"):
                from horaedb_tpu.storage.plan import apply_top_k

                counted: set = set()  # ops metrics survive restarts
                plan = first_plan
                for attempt in range(self._SCAN_RETRIES + 1):
                    try:
                        values, grids = \
                            await self.reader.execute_aggregate_fused(
                                plan, spec, counted=counted)
                        if top_k is not None:
                            values, grids = apply_top_k(values, grids,
                                                        top_k)
                        return values, grids
                    except NotFoundError:
                        if attempt == self._SCAN_RETRIES:
                            raise
                        logger.info("fused aggregate raced a compaction; "
                                    "restarting")
                        plan = await self.build_scan_plan(req)
            done: dict[int, list] = {}
            for attempt in range(self._SCAN_RETRIES + 1):
                # attempt 0 reuses the plan built for the fused gate —
                # one manifest lookup per query, not two
                plan = first_plan if attempt == 0 \
                    else await self.build_scan_plan(req)
                plan.segments = [s for s in plan.segments
                                 if s.segment_start not in done]
                try:
                    async for seg_start, parts in \
                            self.reader.aggregate_segments(
                                plan, spec, top_k=top_k):
                        done[seg_start] = parts
                    break
                except NotFoundError:
                    if attempt == self._SCAN_RETRIES:
                        raise
                    logger.info("aggregate scan raced a compaction; "
                                "replanning")
            all_parts = [p for seg in sorted(done) for p in done[seg]]
            return self.reader.finalize_aggregate(all_parts, spec,
                                                  top_k=top_k)
        finally:
            self.reader._mem_delta_attribute(mem_marks)

    async def scan_select(self, reqs: list, spec, asked: list,
                          first_plans: Optional[list] = None) -> dict:
        """Row selection under a value predicate: the rows of reqs[0]
        (the predicate's field) whose current value passes `spec`
        (ops/select.SelectSpec), with the values of the fields asked
        at the same (series, timestamp).  `reqs[1:]` are the other
        distinct fields' requests, over the same range; `asked` names,
        by index into `reqs`, the field of each value column.  Returns
        {groups, timestamps, values: [...], found: [...]} (a field's
        flags None where it was found at every row), sorted by
        (group, timestamp): see ParquetReader.select_segments for the
        routes.  A compaction race replans the segments not yet
        answered."""
        done: dict[int, object] = {}
        mem_marks = self.reader._mem_delta_marks()
        try:
            for attempt in range(self._SCAN_RETRIES + 1):
                plans = (first_plans if attempt == 0
                         and first_plans is not None
                         else await self._plan_select(reqs))
                for plan in plans:
                    plan.segments = [s for s in plan.segments
                                     if s.segment_start not in done]
                pump = self.reader.select_segments(plans, spec, asked)
                try:
                    async for seg_start, part in pump:
                        done[seg_start] = part
                    break
                except NotFoundError:
                    if attempt == self._SCAN_RETRIES:
                        raise
                    logger.info("select raced a compaction; replanning")
                finally:
                    await pump.aclose()
            return self.reader.finalize_select(
                [done[seg] for seg in sorted(done)], len(asked))
        finally:
            self.reader._mem_delta_attribute(mem_marks)

    async def _plan_select(self, reqs: list) -> list:
        """A select's scan.plan phase: ONE manifest lookup, a plan a
        field over the same SSTs."""
        with self.reader._phase("scan.plan") as planned:
            ensure(self.manifest is not None, "storage not opened")
            ssts = await self.manifest.find_ssts(reqs[0].range)
            plans = [self.reader.build_plan(ssts, req) for req in reqs]
            planned.fields.update(route="select",
                                  segments=len(plans[0].segments))
        return plans

    async def plan_select(self, reqs: list, spec, asked: list):
        """The SelectPlan of a row selection (storage/plan.py)."""
        from horaedb_tpu.storage.plan import SelectPlan

        return SelectPlan(scans=await self._plan_select(reqs),
                          requests=reqs, select=spec, asked=asked)

    async def _walk_newest_first(self, time_range: TimeRange,
                                 first_segments: Optional[list], overlaid,
                                 ask, answered, what: str) -> dict:
        """THE newest-first walk, under the stop rule of its caller:
        the segments of `time_range` (and those `overlaid` adds) newest
        first, `ask(start, ssts)` awaited for one segment at a time
        until `answered()` says that no older segment can change the
        answer, or no segment is left.  Returns {segment start: what
        `ask` returned}.  A compaction race (NotFoundError) replans the
        segments not yet answered; the memtable's marks are attributed
        as a scan's are."""
        done: dict[int, object] = {}
        mem_marks = self.reader._mem_delta_marks()
        try:
            for attempt in range(self._SCAN_RETRIES + 1):
                segments = (first_segments if attempt == 0
                            and first_segments is not None
                            else await self._plan_last(time_range,
                                                       overlaid))
                try:
                    for start, ssts in segments:
                        if answered():
                            break
                        if start in done:
                            continue
                        done[start] = await ask(start, ssts)
                    break
                except NotFoundError:
                    if attempt == self._SCAN_RETRIES:
                        raise
                    logger.info("%s walk raced a compaction; replanning",
                                what)
            return done
        finally:
            self.reader._mem_delta_attribute(mem_marks)

    async def scan_last(self, reqs: list, spec, expect,
                        first_segments: Optional[list] = None,
                        overlaid=frozenset(), scan=None) -> dict:
        """The newest row of every series of `expect` (ascending): a
        walk over the segments NEWEST FIRST that asks each for the last
        rows (ops/last.LastSpec) of the series still missing, over
        `reqs` (a request a field asked, all over one range), and stops
        when none is missing or no segment is left: see
        ParquetReader.last_segment for the routes.  A series found in a
        segment is answered there whole: segments partition time, so
        its greatest timestamp over the fields, and every field's
        sample at it, lie in the newest segment that holds a row of it.
        Returns {groups, timestamps, values: [...], found: [...]} (a
        field's flags None where it was found at every row), ascending
        by group.

        `overlaid` names segments that hold rows outside any SST (the
        WAL's memtables: wal/ingest.py); they, and every segment the
        device route declines, are answered through `scan` (the row
        scan, the caller's where it overlays those rows).  A compaction
        race replans the segments not yet answered."""
        scan = scan or self.scan
        missing = np.asarray(expect)

        async def ask(start: int, ssts: list):
            nonlocal missing
            part = await self._last_of_segment(
                start, ssts, reqs, spec, missing, start in overlaid, scan)
            missing = np.setdiff1d(missing, part.groups,
                                   assume_unique=True)
            return part

        done = await self._walk_newest_first(
            reqs[0].range, first_segments, overlaid, ask,
            lambda: not len(missing), "last-row")
        return self.reader.finalize_select(
            [done[seg] for seg in sorted(done)], len(reqs))

    async def scan_buckets(self, req, spec, limit: int,
                           first_segments: Optional[list] = None,
                           overlaid=frozenset(), scan=None) -> dict:
        """The `limit` newest buckets (ops/buckets.BucketsSpec: one
        field folded over ALL series by epoch-aligned time bucket) that
        hold a sample of `req`'s range: the same walk, asking each
        segment for its buckets (ParquetReader.buckets_segment has the
        routes), under another stop rule: `limit` buckets exist and the
        oldest of them begins at or after the start of the oldest
        segment read, so that no older segment, which holds only older
        timestamps, can add a row to any of them.  A bucket that
        straddles two segments is the fold of both parts.  Returns
        {bucket, count, an array an aggregate asked}, descending by
        bucket.  `overlaid`, `scan` and the compaction race as
        scan_last."""
        from horaedb_tpu.ops.buckets import Merged

        scan = scan or self.scan
        merged = Merged()
        oldest_read = None

        async def ask(start: int, ssts: list):
            nonlocal oldest_read
            part = await self._buckets_of_segment(
                start, ssts, req, spec, start in overlaid, scan)
            merged.add(part, limit)
            # a replan after a compaction race may name a newer segment
            oldest_read = start if oldest_read is None \
                else min(oldest_read, start)
            return part

        def answered() -> bool:
            newest = merged.newest(limit)
            return len(newest) == limit and newest[-1] >= oldest_read

        done = await self._walk_newest_first(
            req.range, first_segments, overlaid, ask, answered, "buckets")
        return self.reader.finalize_buckets(
            [done[seg] for seg in sorted(done, reverse=True)], merged,
            spec, limit)

    async def _buckets_of_segment(self, start: int, ssts: list, req, spec,
                                  overlaid: bool, scan):
        reason = "memtable"
        if not overlaid:
            got = await self.reader.buckets_segment(
                self.reader.build_plan(ssts, req), spec,
                self.segment_duration_ms)
            if not isinstance(got, str):
                return got
            reason = got
        return self.reader.buckets_segment_host(
            await _scan_segment(scan, req, start), spec, req.range, reason,
            start)

    async def _last_of_segment(self, start: int, ssts: list, reqs: list,
                               spec, missing, overlaid: bool, scan):
        reason = "memtable"
        if not overlaid:
            got = await self.reader.last_segment(
                [self.reader.build_plan(ssts, req) for req in reqs], spec,
                missing)
            if not isinstance(got, str):
                return got
            reason = got
        scanned = [await _scan_segment(scan, req, start) for req in reqs]
        return self.reader.last_segment_host(scanned, spec, missing, reason,
                                             start)

    async def _plan_last(self, time_range: TimeRange,
                         overlaid=frozenset()) -> list:
        """A newest-first walk's scan.plan phase: ONE manifest lookup,
        the segments it names (and those `overlaid` adds) newest first,
        each with its SSTs."""
        with self.reader._phase("scan.plan") as planned:
            ensure(self.manifest is not None, "storage not opened")
            by_segment: dict[int, list[SstFile]] = {
                seg: [] for seg in overlaid}
            for f in await self.manifest.find_ssts(time_range):
                by_segment.setdefault(
                    segment_of(f, self.segment_duration_ms), []).append(f)
            planned.fields.update(route="last", segments=len(by_segment))
        return sorted(by_segment.items(), reverse=True)

    async def plan_last(self, reqs: list, spec, expect):
        """The LastPlan of a last-row walk (storage/plan.py)."""
        from horaedb_tpu.storage.plan import LastPlan

        return LastPlan(segments=await self._plan_last(reqs[0].range),
                        requests=reqs, last=spec, expect=np.asarray(expect))

    async def plan_buckets(self, req, spec, limit: int):
        """The BucketsPlan of a bucket walk (storage/plan.py)."""
        from horaedb_tpu.storage.plan import BucketsPlan

        return BucketsPlan(segments=await self._plan_last(req.range),
                           request=req, buckets=spec, limit=limit)

    async def build_scan_plan(self, req: ScanRequest,
                              keep_builtin: bool = False) -> ScanPlan:
        """Manifest lookup + plan build: a `scan.plan` phase span."""
        with self.reader._phase("scan.plan") as planned:
            plan = await self._build_scan_plan(req, keep_builtin)
            planned.fields["segments"] = len(plan.segments)
        return plan

    async def _plan_aggregate(self, req: ScanRequest, spec) -> ScanPlan:
        """An aggregate's scan.plan phase, one span: manifest lookup,
        plan build, and the choice of route (kept on the plan)."""
        with self.reader._phase("scan.plan") as planned:
            plan = await self._build_scan_plan(req)
            plan.route = self.reader.aggregate_route(plan, spec)
            planned.fields.update(route=plan.route,
                                  segments=len(plan.segments))
        return plan

    async def _build_scan_plan(self, req: ScanRequest,
                               keep_builtin: bool = False) -> ScanPlan:
        ensure(self.manifest is not None, "storage not opened")
        ssts = await self.manifest.find_ssts(req.range)
        return self.reader.build_plan(ssts, req, keep_builtin=keep_builtin)

    async def segment_versions(
            self, time_range: TimeRange
    ) -> dict[int, Optional[SegmentVersion]]:
        """The manifest lookup and the grouping of _build_scan_plan,
        in memory and with nothing planned or read."""
        ensure(self.manifest is not None, "storage not opened")
        by_segment: dict[int, list[SstFile]] = {}
        for f in await self.manifest.find_ssts(time_range):
            by_segment.setdefault(
                segment_of(f, self.segment_duration_ms), []).append(f)
        return {seg: SegmentVersion(tuple(sorted(f.id for f in files)),
                                    sum(f.meta.num_rows for f in files))
                for seg, files in by_segment.items()}

    async def plan_query(self, req: ScanRequest, spec=None, top_k=None):
        """Build the composable QueryPlan every query shape routes
        through (see storage/plan.py): scan -> aggregate? -> top_k?."""
        from horaedb_tpu.storage.plan import QueryPlan

        ensure(spec is not None or top_k is None,
               "top-k requires an aggregate stage")
        scan = (await self.build_scan_plan(req) if spec is None
                else await self._plan_aggregate(req, spec))
        return QueryPlan(scan=scan, request=req, aggregate=spec,
                         top_k=top_k)

    def execute_plan(self, qp):
        """Execute a QueryPlan.  Row-scan plans return the async batch
        iterator; aggregate plans return an awaitable of
        (group_values, grids), select and last plans an awaitable of
        the rows' columns (scan_select, scan_last).  A top-k stage is pushed down into the
        combine (scan_aggregate top_k=) so the parts path never builds
        the full groups x buckets grid.  The plan built by plan_query
        is the first attempt's scan plan — one manifest lookup per
        query, not two."""
        from horaedb_tpu.storage.plan import (BucketsPlan, LastPlan,
                                              SelectPlan)

        if isinstance(qp, SelectPlan):
            return self.scan_select(qp.requests, qp.select, qp.asked,
                                    first_plans=qp.scans)
        if isinstance(qp, LastPlan):
            return self.scan_last(qp.requests, qp.last, qp.expect,
                                  first_segments=qp.segments)
        if isinstance(qp, BucketsPlan):
            return self.scan_buckets(qp.request, qp.buckets, qp.limit,
                                     first_segments=qp.segments)
        if qp.aggregate is None:
            return self.scan(qp.request, first_plan=qp.scan)
        return self.scan_aggregate(qp.request, qp.aggregate,
                                   first_plan=qp.scan, top_k=qp.top_k)

    async def compact(self) -> None:
        if self.compact_scheduler is not None:
            await self.compact_scheduler.trigger()

    @property
    def value_idxes(self) -> list[int]:
        return self._schema.value_idxes
