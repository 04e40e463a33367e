"""Whole-engine tests (ref tests: storage.rs:390-490, compaction picker
tests picker.rs:201-236, plan golden test read.rs:575-617)."""

import asyncio

import numpy as np

import pyarrow as pa
import pytest

from horaedb_tpu.common import Error, ReadableDuration
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.ops import Eq, Gt, TimeRangePred
from horaedb_tpu.storage.compaction import Task, TimeWindowCompactionStrategy
from horaedb_tpu.storage.config import (
    StorageConfig,
    UpdateMode,
    from_dict,
)
from horaedb_tpu.storage.read import ScanRequest, describe_plan
from horaedb_tpu.storage.sst import FileMeta, SstFile
from horaedb_tpu.storage.storage import CloudObjectStorage, WriteRequest
from horaedb_tpu.storage.types import TimeRange, Timestamp

SEGMENT_MS = 3_600_000  # 1h


def user_schema():
    return pa.schema([
        pa.field("host", pa.string()),
        pa.field("ts", pa.int64()),
        pa.field("cpu", pa.float64()),
    ])


def make_batch(rows):
    hosts, tss, cpus = zip(*rows)
    return pa.record_batch(
        [pa.array(list(hosts)), pa.array(list(tss), type=pa.int64()),
         pa.array(list(cpus), type=pa.float64())],
        schema=user_schema())


async def open_storage(store=None, update_mode=UpdateMode.OVERWRITE,
                       config=None):
    cfg = config or StorageConfig(update_mode=update_mode)
    # keep background compaction quiet during tests
    cfg.scheduler.schedule_interval = ReadableDuration.parse("1h")
    return await CloudObjectStorage.open(
        "db", SEGMENT_MS, store or MemoryObjectStore(), user_schema(),
        num_primary_keys=2, config=cfg)


async def collect(stream):
    out = []
    async for b in stream:
        out.append(b)
    return out


def rows_of(batches):
    out = []
    for b in batches:
        out.extend(zip(b.column(0).to_pylist(), b.column(1).to_pylist(),
                       b.column(2).to_pylist()))
    return out


def test_write_f64_overflow_clamps_to_f32_range():
    """End-to-end overflow policy: a 1e39 value survives the f64→f32
    device encoding as ±f32::MAX — finite, aggregate-safe — instead of
    silently turning into inf (VERDICT item 7)."""
    async def go():
        s = await open_storage()
        try:
            await s.write(WriteRequest(
                make_batch([("h", 5, 1e39), ("h", 6, -1e39)]),
                TimeRange.new(5, 7)))
            got = rows_of(await collect(
                s.scan(ScanRequest(range=TimeRange.new(0, 100)))))
            f32_max = float(np.finfo(np.float32).max)
            assert [v for _, _, v in got] == [f32_max, -f32_max]
            assert all(np.isfinite(v) for _, _, v in got)
        finally:
            await s.close()

    asyncio.run(go())


class TestWriteScan:
    def test_write_then_scan_dedups_across_files(self):
        """The reference's core scenario (storage.rs:390-490): two writes
        with overlapping PKs; the later file's rows win."""

        async def go():
            s = await open_storage()
            try:
                await s.write(WriteRequest(
                    make_batch([("a", 1000, 1.0), ("b", 2000, 2.0),
                                ("c", 3000, 3.0)]),
                    TimeRange.new(1000, 3001)))
                await s.write(WriteRequest(
                    make_batch([("b", 2000, 20.0), ("d", 1500, 4.0)]),
                    TimeRange.new(1500, 2001)))
                got = rows_of(await collect(s.scan(
                    ScanRequest(range=TimeRange.new(0, 10_000)))))
                assert got == [("a", 1000, 1.0), ("b", 2000, 20.0),
                               ("c", 3000, 3.0), ("d", 1500, 4.0)]
            finally:
                await s.close()

        asyncio.run(go())

    def test_scan_with_predicate(self):
        async def go():
            s = await open_storage()
            try:
                await s.write(WriteRequest(
                    make_batch([("a", 1000, 1.0), ("b", 2000, 2.0),
                                ("c", 3000, 3.0)]),
                    TimeRange.new(1000, 3001)))
                got = rows_of(await collect(s.scan(ScanRequest(
                    range=TimeRange.new(0, 10_000), predicate=Gt("cpu", 1.5)))))
                assert got == [("b", 2000, 2.0), ("c", 3000, 3.0)]
                got = rows_of(await collect(s.scan(ScanRequest(
                    range=TimeRange.new(0, 10_000), predicate=Eq("host", "a")))))
                assert got == [("a", 1000, 1.0)]
                got = rows_of(await collect(s.scan(ScanRequest(
                    range=TimeRange.new(0, 10_000),
                    predicate=TimeRangePred("ts", 1500, 2500)))))
                assert got == [("b", 2000, 2.0)]
            finally:
                await s.close()

        asyncio.run(go())

    def test_projection(self):
        async def go():
            s = await open_storage()
            try:
                await s.write(WriteRequest(
                    make_batch([("a", 1000, 1.0)]), TimeRange.new(1000, 1001)))
                batches = await collect(s.scan(ScanRequest(
                    range=TimeRange.new(0, 10_000), projections=[2])))
                # projection [cpu] is augmented with the forced pks (appended
                # after the requested columns, ref: types.rs:202-215);
                # builtins are stripped from the output
                assert batches[0].schema.names == ["cpu", "host", "ts"]
            finally:
                await s.close()

        asyncio.run(go())

    def test_scan_range_excludes_files(self):
        async def go():
            s = await open_storage()
            try:
                await s.write(WriteRequest(
                    make_batch([("a", 1000, 1.0)]), TimeRange.new(1000, 1001)))
                far = 10 * SEGMENT_MS
                await s.write(WriteRequest(
                    make_batch([("z", far, 9.0)]), TimeRange.new(far, far + 1)))
                got = rows_of(await collect(s.scan(
                    ScanRequest(range=TimeRange.new(0, 2000)))))
                assert got == [("a", 1000, 1.0)]
            finally:
                await s.close()

        asyncio.run(go())

    def test_multi_segment_scan_ordered(self):
        async def go():
            s = await open_storage()
            try:
                seg2 = SEGMENT_MS + 500
                await s.write(WriteRequest(
                    make_batch([("z", seg2, 9.0)]),
                    TimeRange.new(seg2, seg2 + 1)))
                await s.write(WriteRequest(
                    make_batch([("a", 1000, 1.0)]), TimeRange.new(1000, 1001)))
                batches = await collect(s.scan(
                    ScanRequest(range=TimeRange.new(0, 10 * SEGMENT_MS))))
                assert len(batches) == 2  # one per segment, ascending
                assert rows_of(batches) == [("a", 1000, 1.0), ("z", seg2, 9.0)]
            finally:
                await s.close()

        asyncio.run(go())

    def test_write_cross_segment_rejected(self):
        async def go():
            s = await open_storage()
            try:
                with pytest.raises(Error, match="crosses segment"):
                    await s.write(WriteRequest(
                        make_batch([("a", 1000, 1.0)]),
                        TimeRange.new(1000, SEGMENT_MS + 10)))
                # same write with the check disabled is accepted
                await s.write(WriteRequest(
                    make_batch([("a", 1000, 1.0)]),
                    TimeRange.new(1000, SEGMENT_MS + 10), enable_check=False))
            finally:
                await s.close()

        asyncio.run(go())

    def test_schema_mismatch_rejected(self):
        async def go():
            s = await open_storage()
            try:
                bad = pa.record_batch({"x": pa.array([1])})
                with pytest.raises(Error, match="schema"):
                    await s.write(WriteRequest(bad, TimeRange.new(0, 1)))
            finally:
                await s.close()

        asyncio.run(go())


class TestAppendMode:
    def test_bytes_merge_concat(self):
        async def go():
            schema = pa.schema([pa.field("k", pa.string()),
                                pa.field("payload", pa.binary())])
            cfg = StorageConfig(update_mode=UpdateMode.APPEND)
            cfg.scheduler.schedule_interval = ReadableDuration.parse("1h")
            s = await CloudObjectStorage.open(
                "db", SEGMENT_MS, MemoryObjectStore(), schema,
                num_primary_keys=1, config=cfg)
            try:
                b1 = pa.record_batch([pa.array(["k1", "k2"]),
                                      pa.array([b"ab", b"xy"], type=pa.binary())],
                                     schema=schema)
                b2 = pa.record_batch([pa.array(["k1"]),
                                      pa.array([b"cd"], type=pa.binary())],
                                     schema=schema)
                await s.write(WriteRequest(b1, TimeRange.new(0, 10)))
                await s.write(WriteRequest(b2, TimeRange.new(0, 10)))
                batches = await collect(s.scan(
                    ScanRequest(range=TimeRange.new(0, 100))))
                got = {}
                for b in batches:
                    for k, v in zip(b.column(0).to_pylist(), b.column(1).to_pylist()):
                        got[k] = v
                assert got == {"k1": b"abcd", "k2": b"xy"}
            finally:
                await s.close()

        asyncio.run(go())


class TestPlanShape:
    def test_plan_golden_text(self):
        async def go():
            s = await open_storage()
            try:
                await s.write(WriteRequest(
                    make_batch([("a", 1000, 1.0)]), TimeRange.new(1000, 1001)))
                await s.write(WriteRequest(
                    make_batch([("b", 2000, 2.0)]), TimeRange.new(2000, 2001)))
                plan = await s.build_scan_plan(ScanRequest(
                    range=TimeRange.new(0, 10_000), predicate=Eq("host", "a")))
                ids = sorted(f.id for seg in plan.segments for f in seg.ssts)
                text = describe_plan(plan)
                expected = "\n".join([
                    "MergeScan: mode=Overwrite, keep_builtin=False",
                    "  Segment[start=0]: DeviceMergeDedup",
                    "    Filter: Eq(column='host', value='a')",
                    f"    ParquetScan: files=[{ids[0]}.sst, {ids[1]}.sst], "
                    "columns=['host', 'ts', 'cpu', '__seq__'], pushdown=yes",
                ])
                assert text == expected
            finally:
                await s.close()

        asyncio.run(go())


class TestPushedComplete:
    """A fully-pushed (PK-only And) predicate skips the post-merge
    re-evaluation; anything else must not.  The skip is provably a
    no-op only while build_plan, conjunct_leaves_ex and the read paths
    agree on the pushed leaf set — these tests pin that agreement."""

    def test_flag_shapes(self):
        from horaedb_tpu.ops.filter import And, Ge, Or

        async def go():
            s = await open_storage()
            try:
                await s.write(WriteRequest(
                    make_batch([("a", 1000, 1.0)]), TimeRange.new(1000, 1001)))
                pk_only = await s.build_scan_plan(ScanRequest(
                    range=TimeRange.new(0, 10_000),
                    predicate=And((Eq("host", "a"),
                                   TimeRangePred("ts", 0, 10_000)))))
                assert pk_only.pushed_complete
                with_value = await s.build_scan_plan(ScanRequest(
                    range=TimeRange.new(0, 10_000),
                    predicate=And((Eq("host", "a"), Ge("cpu", 1.0)))))
                assert not with_value.pushed_complete
                disjunct = await s.build_scan_plan(ScanRequest(
                    range=TimeRange.new(0, 10_000),
                    predicate=Or((Eq("host", "a"), Eq("host", "b")))))
                assert not disjunct.pushed_complete
                no_pred = await s.build_scan_plan(ScanRequest(
                    range=TimeRange.new(0, 10_000)))
                assert not no_pred.pushed_complete
            finally:
                await s.close()

        asyncio.run(go())

    def test_skip_returns_identical_rows(self):
        import dataclasses

        async def go():
            s = await open_storage()
            try:
                # overlapping writes: dedup actually has work to do
                await s.write(WriteRequest(
                    make_batch([("a", 1000, 1.0), ("b", 2000, 2.0)]),
                    TimeRange.new(1000, 2001)))
                await s.write(WriteRequest(
                    make_batch([("a", 1000, 9.0), ("c", 1500, 3.0)]),
                    TimeRange.new(1000, 1501)))
                req = ScanRequest(range=TimeRange.new(0, 10_000),
                                  predicate=Eq("host", "a"))
                plan = await s.build_scan_plan(req)
                assert plan.pushed_complete
                forced = dataclasses.replace(plan, pushed_complete=False)

                async def rows(p):
                    out = []
                    async for _seg, b in s.reader.execute_segments(p):
                        if b is not None:
                            out.extend(zip(b.column("host").to_pylist(),
                                           b.column("ts").to_pylist(),
                                           b.column("cpu").to_pylist()))
                    return sorted(out)

                got_skip = await rows(plan)
                got_eval = await rows(forced)
                assert got_skip == got_eval == [("a", 1000, 9.0)]
            finally:
                await s.close()

        asyncio.run(go())


def mkfile(fid, start, end, size=100):
    f = SstFile(fid, FileMeta(max_sequence=fid, num_rows=10, size=size,
                              time_range=TimeRange.new(start, end)))
    return f


class TestPickerStrategy:
    def strategy(self, **kw):
        defaults = dict(segment_duration_ms=100, new_sst_max_size=1000,
                        input_sst_max_num=4, input_sst_min_num=2)
        defaults.update(kw)
        return TimeWindowCompactionStrategy(**defaults)

    def test_picks_newest_qualifying_segment(self):
        st = self.strategy()
        ssts = [mkfile(1, 0, 10), mkfile(2, 20, 30),          # old segment
                mkfile(3, 100, 110), mkfile(4, 120, 130)]     # new segment
        task = st.pick_candidate(ssts, None)
        assert sorted(f.id for f in task.inputs) == [3, 4]
        assert all(f.in_compaction for f in task.inputs)

    def test_in_compaction_files_excluded(self):
        st = self.strategy()
        ssts = [mkfile(1, 0, 10), mkfile(2, 20, 30)]
        ssts[0].mark_compaction()
        assert st.pick_candidate(ssts, None) is None

    def test_min_num_required(self):
        st = self.strategy(input_sst_min_num=3)
        ssts = [mkfile(1, 0, 10), mkfile(2, 20, 30)]
        assert st.pick_candidate(ssts, None) is None

    def test_size_budget_smallest_first(self):
        st = self.strategy(new_sst_max_size=250)  # budget 275
        ssts = [mkfile(1, 0, 10, size=100), mkfile(2, 20, 30, size=100),
                mkfile(3, 40, 50, size=100), mkfile(4, 60, 70, size=500)]
        task = st.pick_candidate(ssts, None)
        assert sorted(f.id for f in task.inputs) == [1, 2]

    def test_max_num_cap(self):
        st = self.strategy(input_sst_max_num=3)
        ssts = [mkfile(i, i * 10, i * 10 + 5) for i in range(1, 7)]
        task = st.pick_candidate(ssts, None)
        assert len(task.inputs) == 3

    def test_ttl_expired_split_out(self):
        st = self.strategy()
        ssts = [mkfile(1, 0, 10), mkfile(2, 20, 30),
                mkfile(3, 100, 110), mkfile(4, 120, 130)]
        # expire_time=50: files ending before 50 are expired
        task = st.pick_candidate(ssts, Timestamp(50))
        assert sorted(f.id for f in task.expireds) == [1, 2]
        assert sorted(f.id for f in task.inputs) == [3, 4]


class TestCompactionEndToEnd:
    def test_compaction_streams_output_in_bounded_chunks(self):
        """The compaction rewrite must hand the store MANY chunks (one
        per flushed row group), never one whole-SST buffer — the
        bounded-RSS contract of write_sst_streaming."""
        async def go():
            store = MemoryObjectStore()
            chunk_sizes: list[int] = []
            real_put_stream = store.put_stream

            async def spying_put_stream(path, chunks):
                async def spy():
                    async for c in chunks:
                        chunk_sizes.append(len(c))
                        yield c

                return await real_put_stream(path, spy())

            store.put_stream = spying_put_stream
            cfg = from_dict(StorageConfig, {
                "scheduler": {"schedule_interval": "1h",
                              "input_sst_min_num": 2},
                "write": {"max_row_group_size": 1024}})
            s = await CloudObjectStorage.open(
                "db", SEGMENT_MS, store, user_schema(),
                num_primary_keys=2, config=cfg)
            try:
                rng = np.random.default_rng(0)
                for _ in range(2):
                    n = 8000
                    rows = [(f"t{int(t) % 50:02d}", int(t), float(v))
                            for t, v in zip(
                                rng.integers(0, SEGMENT_MS, n),
                                rng.random(n))]
                    await s.write(WriteRequest(
                        make_batch(sorted(rows)),
                        TimeRange.new(0, SEGMENT_MS)))
                task = await s.compact_scheduler.picker.pick_candidate()
                assert task is not None
                await s.compact_scheduler.executor.execute(task)
                # many row-group-sized chunks, not one monolith
                assert len(chunk_sizes) > 4, chunk_sizes
                total = sum(chunk_sizes)
                assert max(chunk_sizes) < total, chunk_sizes
                # output readable and deduped
                out = [b async for b in s.scan(ScanRequest(
                    range=TimeRange.new(0, SEGMENT_MS), predicate=None,
                    projections=None))]
                assert sum(b.num_rows for b in out) > 0
            finally:
                await s.close()

        asyncio.run(go())

    def test_compact_merges_files_and_cleans_up(self):
        async def go():
            store = MemoryObjectStore()
            cfg = from_dict(StorageConfig, {
                "scheduler": {"schedule_interval": "1h",
                              "input_sst_min_num": 2}})
            s = await CloudObjectStorage.open(
                "db", SEGMENT_MS, store, user_schema(),
                num_primary_keys=2, config=cfg)
            try:
                for i, rows in enumerate([
                    [("a", 1000, 1.0), ("b", 2000, 2.0)],
                    [("b", 2000, 20.0), ("c", 3000, 3.0)],
                    [("c", 3000, 30.0)],
                ]):
                    await s.write(WriteRequest(
                        make_batch(rows), TimeRange.new(1000, 3001)))
                assert len(await s.manifest.all_ssts()) == 3

                task = await s.compact_scheduler.picker.pick_candidate()
                assert task is not None and len(task.inputs) == 3
                await s.compact_scheduler.executor.execute(task)

                ssts = await s.manifest.all_ssts()
                assert len(ssts) == 1
                new = ssts[0]
                assert new.meta.num_rows == 3
                assert new.meta.time_range == TimeRange.new(1000, 3001)
                # old objects gone, new object (+ its device-layout
                # sidecar) present
                objs = sorted(m.path for m in await store.list("db/data/"))
                assert objs == [f"db/data/{new.id}.enc",
                                f"db/data/{new.id}.sst"]
                # data still correct post-compaction (dedup survived)
                got = rows_of(await collect(s.scan(
                    ScanRequest(range=TimeRange.new(0, 10_000)))))
                assert got == [("a", 1000, 1.0), ("b", 2000, 20.0),
                               ("c", 3000, 30.0)]
                # compacting again finds nothing (single file below min)
                assert await s.compact_scheduler.picker.pick_candidate() is None
            finally:
                await s.close()

        asyncio.run(go())

    def test_scan_after_compaction_dedups_vs_new_writes(self):
        async def go():
            store = MemoryObjectStore()
            cfg = from_dict(StorageConfig, {
                "scheduler": {"schedule_interval": "1h",
                              "input_sst_min_num": 2}})
            s = await CloudObjectStorage.open(
                "db", SEGMENT_MS, store, user_schema(),
                num_primary_keys=2, config=cfg)
            try:
                await s.write(WriteRequest(
                    make_batch([("a", 1000, 1.0)]), TimeRange.new(1000, 1001)))
                await s.write(WriteRequest(
                    make_batch([("a", 1000, 2.0)]), TimeRange.new(1000, 1001)))
                task = await s.compact_scheduler.picker.pick_candidate()
                await s.compact_scheduler.executor.execute(task)
                # a write AFTER compaction must still shadow compacted rows
                await s.write(WriteRequest(
                    make_batch([("a", 1000, 3.0)]), TimeRange.new(1000, 1001)))
                got = rows_of(await collect(s.scan(
                    ScanRequest(range=TimeRange.new(0, 10_000)))))
                assert got == [("a", 1000, 3.0)]
            finally:
                await s.close()

        asyncio.run(go())


class TestReviewRegressions:
    """Regression coverage for review findings."""

    def test_null_writes_rejected(self):
        async def go():
            s = await open_storage()
            try:
                bad = pa.record_batch(
                    [pa.array(["a"]), pa.array([1000], type=pa.int64()),
                     pa.array([None], type=pa.float64())],
                    schema=user_schema())
                with pytest.raises(Error, match="nulls"):
                    await s.write(WriteRequest(bad, TimeRange.new(1000, 1001)))
            finally:
                await s.close()

        asyncio.run(go())

    def test_memory_gate_rejection_does_not_underflow(self):
        async def go():
            cfg = from_dict(StorageConfig, {
                "scheduler": {"schedule_interval": "1h", "memory_limit": "1KB"}})
            s = await open_storage(config=cfg)
            try:
                big = Task(inputs=[mkfile(1, 0, 10, size=4096)])
                ex = s.compact_scheduler.executor
                for _ in range(3):
                    with pytest.raises(Error, match="memory"):
                        await ex.execute(big)
                assert ex.inused_memory == 0  # no underflow
                assert not big.inputs[0].in_compaction  # re-pickable
            finally:
                await s.close()

        asyncio.run(go())

    def test_projected_scan_sorts_by_schema_pk_order(self):
        async def go():
            s = await open_storage()
            try:
                await s.write(WriteRequest(
                    make_batch([("b", 1000, 1.0), ("a", 2000, 2.0)]),
                    TimeRange.new(1000, 2001)))
                batches = await collect(s.scan(ScanRequest(
                    range=TimeRange.new(0, 10_000), projections=[1])))
                # projection [ts] reorders columns, but output must still be
                # sorted by schema PK order (host, ts)
                b = batches[0]
                hosts = b.column(b.schema.names.index("host")).to_pylist()
                assert hosts == ["a", "b"]
            finally:
                await s.close()

        asyncio.run(go())


class TestAppendModeProjection:
    def test_bytes_merge_with_reordering_projection(self):
        """Projection puts the value column first; host merge must still
        group by the true PK (review regression)."""

        async def go():
            schema = pa.schema([pa.field("k", pa.string()),
                                pa.field("payload", pa.binary())])
            cfg = StorageConfig(update_mode=UpdateMode.APPEND)
            cfg.scheduler.schedule_interval = ReadableDuration.parse("1h")
            s = await CloudObjectStorage.open(
                "db", SEGMENT_MS, MemoryObjectStore(), schema,
                num_primary_keys=1, config=cfg)
            try:
                b1 = pa.record_batch([pa.array(["k1", "k2"]),
                                      pa.array([b"ab", b"xy"], type=pa.binary())],
                                     schema=schema)
                b2 = pa.record_batch([pa.array(["k1"]),
                                      pa.array([b"cd"], type=pa.binary())],
                                     schema=schema)
                await s.write(WriteRequest(b1, TimeRange.new(0, 10)))
                await s.write(WriteRequest(b2, TimeRange.new(0, 10)))
                batches = await collect(s.scan(ScanRequest(
                    range=TimeRange.new(0, 100), projections=[1])))
                got = {}
                for b in batches:
                    ki = b.schema.names.index("k")
                    pi = b.schema.names.index("payload")
                    for k, v in zip(b.column(ki).to_pylist(),
                                    b.column(pi).to_pylist()):
                        got[k] = v
                assert got == {"k1": b"abcd", "k2": b"xy"}
            finally:
                await s.close()

        asyncio.run(go())


class TestStreamedRead:
    """Segments above scan.stream_read_min_rows are read window-by-window
    (pass 1 plans value-range windows from one PK column, pass 2 reads
    each range via parquet pushdown) — host materialization stays
    bounded by the window budget, output identical to the bulk read."""

    def _write_big_segment(self):
        import numpy as np

        rng = np.random.default_rng(42)
        n_per, ssts, hosts = 1500, 4, 40
        batches = []
        for _ in range(ssts):
            h = rng.integers(0, hosts, n_per)
            ts = rng.integers(0, SEGMENT_MS, n_per)
            v = rng.random(n_per) * 10
            batches.append(pa.record_batch(
                [pa.array([f"host_{int(i):02d}" for i in h]),
                 pa.array(ts, type=pa.int64()),
                 pa.array(v, type=pa.float64())],
                schema=user_schema()))
        return batches

    def _run(self, cfg_scan, spy=None):
        async def go():
            cfg = from_dict(StorageConfig, {"scan": cfg_scan})
            cfg.scheduler.schedule_interval = ReadableDuration.parse("1h")
            s = await CloudObjectStorage.open(
                "db", SEGMENT_MS, MemoryObjectStore(), user_schema(),
                num_primary_keys=2, config=cfg)
            try:
                if spy is not None:
                    inner = s.reader._dispatch_merged_windows

                    def spying(batch):
                        spy.append(batch.num_rows)
                        return inner(batch)

                    s.reader._dispatch_merged_windows = spying
                for b in self._write_big_segment():
                    await s.write(WriteRequest(
                        b, TimeRange.new(0, SEGMENT_MS)))
                got = rows_of(await collect(
                    s.scan(ScanRequest(range=TimeRange.new(0, SEGMENT_MS)))))
                return sorted(got)
            finally:
                await s.close()

        return asyncio.run(go())

    def test_streamed_equals_bulk_with_bounded_windows(self):
        spy: list = []
        # use_sidecar off: this test pins the PARQUET two-pass
        # streamer's windowing contract (the sidecar stream has its own
        # parity tests in test_sidecar.TestStreamedSidecar)
        streamed = self._run(
            {"stream_read_min_rows": 2000, "max_window_rows": 1024,
             "use_sidecar": False},
            spy=spy)
        bulk = self._run({"stream_read_min_rows": 0,
                          "max_window_rows": 1 << 20})
        assert streamed == bulk
        assert len(streamed) > 0
        # every materialized window stayed within the budget (one host's
        # rows can't split, so allow that skew)
        assert spy and max(spy) <= 1024 + 600, spy

    def test_byte_threshold_streams_wide_segments(self):
        """A segment can be host-RAM-huge at a low row count (wide
        schema): the BYTE knob must trigger streaming when the row knob
        would not, with identical output."""
        spy: list = []
        streamed = self._run(
            # row knob far above the data; byte knob far below it
            # (use_sidecar off: pins the parquet streamer specifically)
            {"stream_read_min_rows": 1 << 30,
             "stream_read_min_bytes": 4096, "max_window_rows": 1024,
             "use_sidecar": False},
            spy=spy)
        bulk = self._run({"stream_read_min_rows": 0,
                          "stream_read_min_bytes": 0,
                          "max_window_rows": 1 << 20})
        assert streamed == bulk
        # windows were bounded -> the streamed path actually engaged
        assert spy and max(spy) <= 1024 + 600, spy

    def test_streamed_mesh_equals_bulk(self):
        streamed = self._run(
            {"stream_read_min_rows": 2000, "max_window_rows": 1024,
             "mesh": {"enabled": True}})
        bulk = self._run({"stream_read_min_rows": 0,
                          "max_window_rows": 1 << 20})
        assert streamed == bulk

    def test_fused_aggregate_restarts_on_compaction_race(self, monkeypatch):
        """The fused path's all-or-nothing retry: a NotFoundError
        mid-aggregate (SST vanished under compaction) restarts with a
        fresh plan and returns the full, duplicate-free grids; ops
        metrics for re-scanned segments are not double-counted."""
        monkeypatch.setenv("HORAEDB_FUSED_AGG", "1")

        async def go():
            from horaedb_tpu.objstore import NotFoundError
            from horaedb_tpu.storage.read import _ROWS_SCANNED, AggregateSpec

            s = await open_storage()
            try:
                rows = [("a", 1000, 1.0), ("a", 2000, 2.0),
                        ("b", 1000, 3.0), ("b", 2000, 4.0)]
                await s.write(WriteRequest(make_batch(rows),
                                           TimeRange.new(1000, 2001)))
                rows_scanned_before = _ROWS_SCANNED.value
                real = s.reader.execute_aggregate_fused
                calls = {"n": 0}

                async def flaky(plan, spec, counted=None):
                    calls["n"] += 1
                    if calls["n"] == 1:
                        # scan everything FIRST (metrics counted), then
                        # fail — the restart must not re-count
                        await real(plan, spec, counted=counted)
                        raise NotFoundError("sst vanished (simulated "
                                            "compaction race)")
                    return await real(plan, spec, counted=counted)

                monkeypatch.setattr(s.reader, "execute_aggregate_fused",
                                    flaky)
                spec = AggregateSpec(group_col="host", ts_col="ts",
                                     value_col="cpu", range_start=0,
                                     bucket_ms=10_000, num_buckets=1,
                                     which=("sum", "count"))
                values, grids = await s.scan_aggregate(
                    ScanRequest(range=TimeRange.new(0, 10_000)), spec)
                assert calls["n"] == 2  # raced once, restarted once
                got = {str(v): float(np.asarray(grids["sum"])[i, 0])
                       for i, v in enumerate(values)}
                assert got == {"a": 3.0, "b": 7.0}
                assert float(np.asarray(grids["count"]).sum()) == 4.0
                # both attempts scanned the segment, but the shared
                # `counted` set means ops metrics saw it ONCE
                assert _ROWS_SCANNED.value - rows_scanned_before == 4
            finally:
                await s.close()

        asyncio.run(go())

    def test_streamed_scan_survives_mid_segment_compaction(self):
        """Append-mode streamed segments yield one batch per window
        WHILE later windows are still being read: an SST vanishing in
        between (compaction race) must neither fail the scan nor
        duplicate already-yielded windows — the segment re-resolves its
        CURRENT SSTs and continues with the remaining value ranges.
        Local store: deleted files raise FileNotFoundError, which must
        map to the retryable NotFoundError."""
        import tempfile

        import numpy as np

        from horaedb_tpu.objstore import LocalObjectStore

        schema = pa.schema([pa.field("host", pa.string()),
                            pa.field("ts", pa.int64()),
                            pa.field("payload", pa.binary())])

        def batches():
            rng = np.random.default_rng(3)
            out = []
            for _ in range(4):
                h = rng.integers(0, 40, 1500)
                out.append(pa.record_batch(
                    [pa.array([f"host_{int(i):02d}" for i in h]),
                     pa.array(rng.integers(0, SEGMENT_MS, 1500),
                              type=pa.int64()),
                     pa.array([b"%d" % v for v in
                               rng.integers(0, 100, 1500)],
                              type=pa.binary())],
                    schema=schema))
            return out

        async def go():
            with tempfile.TemporaryDirectory() as root:
                cfg = from_dict(StorageConfig, {
                    "scan": {"stream_read_min_rows": 2000,
                             "max_window_rows": 1024},
                    "scheduler": {"schedule_interval": "1h",
                                  "input_sst_min_num": 2}})
                cfg.update_mode = UpdateMode.APPEND
                s = await CloudObjectStorage.open(
                    "db", SEGMENT_MS, LocalObjectStore(root), schema,
                    num_primary_keys=2, config=cfg)
                try:
                    for b in batches():
                        await s.write(WriteRequest(
                            b, TimeRange.new(0, SEGMENT_MS)))
                    expected = sorted(rows_of(await collect(s.scan(
                        ScanRequest(range=TimeRange.new(0, SEGMENT_MS))))))

                    got = []
                    stream = s.scan(
                        ScanRequest(range=TimeRange.new(0, SEGMENT_MS)))
                    first = await stream.__anext__()
                    got.extend(rows_of([first]))
                    # compaction deletes every input SST while the
                    # stream still has windows to read
                    task = await s.compact_scheduler.picker.pick_candidate()
                    assert task is not None
                    await s.compact_scheduler.executor.execute(task)
                    async for b in stream:
                        got.extend(rows_of([b]))
                    assert sorted(got) == expected
                finally:
                    await s.close()

        asyncio.run(go())

    def test_streamed_append_mode_equals_bulk(self):
        """Append (host BytesMerge) tables stream too."""
        import numpy as np

        schema = pa.schema([pa.field("host", pa.string()),
                            pa.field("ts", pa.int64()),
                            pa.field("payload", pa.binary())])

        def batches():
            rng = np.random.default_rng(7)
            out = []
            for _ in range(4):
                h = rng.integers(0, 40, 1500)
                ts = rng.integers(0, SEGMENT_MS, 1500)
                out.append(pa.record_batch(
                    [pa.array([f"host_{int(i):02d}" for i in h]),
                     pa.array(ts, type=pa.int64()),
                     pa.array([b"%d" % v for v in
                               rng.integers(0, 100, 1500)],
                              type=pa.binary())],
                    schema=schema))
            return out

        def run(scan_cfg):
            async def go():
                cfg = from_dict(StorageConfig, {"scan": scan_cfg})
                cfg.update_mode = UpdateMode.APPEND
                cfg.scheduler.schedule_interval = ReadableDuration.parse("1h")
                s = await CloudObjectStorage.open(
                    "db", SEGMENT_MS, MemoryObjectStore(), schema,
                    num_primary_keys=2, config=cfg)
                try:
                    for b in batches():
                        await s.write(WriteRequest(
                            b, TimeRange.new(0, SEGMENT_MS)))
                    got = rows_of(await collect(s.scan(
                        ScanRequest(range=TimeRange.new(0, SEGMENT_MS)))))
                    return sorted(got)
                finally:
                    await s.close()

            return asyncio.run(go())

        streamed = run({"stream_read_min_rows": 2000,
                        "max_window_rows": 1024})
        bulk = run({"stream_read_min_rows": 0, "max_window_rows": 1 << 20})
        assert streamed == bulk and len(streamed) > 0


class TestWindowedScan:
    """Bounded-HBM windowed execution must be semantically invisible."""

    def _open_small_window(self, window):
        cfg = StorageConfig()
        cfg.scheduler.schedule_interval = ReadableDuration.parse("1h")
        cfg.scan.max_window_rows = window
        return cfg

    def test_windowed_equals_single_shot(self):
        async def go():
            import numpy as np
            rng = np.random.default_rng(3)
            rows_per_write = 200
            writes = []
            for _ in range(4):
                hosts = [f"h{int(i):03d}" for i in rng.integers(0, 40, rows_per_write)]
                tss = rng.integers(1000, 3000, rows_per_write).tolist()
                cpus = rng.random(rows_per_write).round(3).tolist()
                writes.append(list(zip(hosts, tss, cpus)))

            async def run_with(window):
                s = await CloudObjectStorage.open(
                    "db", SEGMENT_MS, MemoryObjectStore(), user_schema(), 2,
                    self._open_small_window(window))
                try:
                    for w in writes:
                        await s.write(WriteRequest(
                            make_batch(w), TimeRange.new(1000, 3000)))
                    return rows_of(await collect(s.scan(
                        ScanRequest(range=TimeRange.new(0, 10_000)))))
                finally:
                    await s.close()

            single = await run_with(1 << 20)
            windowed = await run_with(97)  # forces many windows
            assert windowed == single
            # also with a predicate
            async def run_pred(window):
                s = await CloudObjectStorage.open(
                    "db2", SEGMENT_MS, MemoryObjectStore(), user_schema(), 2,
                    self._open_small_window(window))
                try:
                    for w in writes:
                        await s.write(WriteRequest(
                            make_batch(w), TimeRange.new(1000, 3000)))
                    return rows_of(await collect(s.scan(ScanRequest(
                        range=TimeRange.new(0, 10_000),
                        predicate=Gt("cpu", 0.5)))))
                finally:
                    await s.close()

            assert await run_pred(97) == await run_pred(1 << 20)

        asyncio.run(go())

    def test_skewed_key_exceeding_window(self):
        """One PK value with more rows than the window budget still
        dedups correctly (gets an oversized window of its own)."""

        async def go():
            s = await CloudObjectStorage.open(
                "db", SEGMENT_MS, MemoryObjectStore(), user_schema(), 2,
                self._open_small_window(8))
            try:
                rows = [("hot", 1000 + i, float(i)) for i in range(30)]
                rows += [("cold", 1000, 0.5)]
                await s.write(WriteRequest(
                    make_batch(rows), TimeRange.new(1000, 1031)))
                # duplicate writes for the hot key
                await s.write(WriteRequest(
                    make_batch([("hot", 1005, 99.0)]),
                    TimeRange.new(1005, 1006)))
                got = rows_of(await collect(s.scan(
                    ScanRequest(range=TimeRange.new(0, 10_000)))))
                assert len(got) == 31
                assert ("hot", 1005, 99.0) in got
                assert got == sorted(got)  # globally PK-sorted
            finally:
                await s.close()

        asyncio.run(go())


class TestScanCache:
    def _cfg(self, cache_rows=1 << 20):
        cfg = StorageConfig()
        cfg.scheduler.schedule_interval = ReadableDuration.parse("1h")
        cfg.scan.cache_max_rows = cache_rows
        return cfg

    def test_repeat_scan_hits_cache(self):
        async def go():
            s = await open_storage(config=self._cfg())
            try:
                await s.write(WriteRequest(
                    make_batch([("a", 1000, 1.0), ("b", 2000, 2.0)]),
                    TimeRange.new(1000, 2001)))
                cache = s.reader.scan_cache
                r1 = rows_of(await collect(s.scan(
                    ScanRequest(range=TimeRange.new(0, 10_000)))))
                assert len(cache) == 1
                r2 = rows_of(await collect(s.scan(
                    ScanRequest(range=TimeRange.new(0, 10_000)))))
                assert r1 == r2
                # a different predicate still reuses the cached merge
                # (no pushdown parts changed -> same key) when the
                # predicate is value-only
                r3 = rows_of(await collect(s.scan(ScanRequest(
                    range=TimeRange.new(0, 10_000),
                    predicate=Gt("cpu", 1.5)))))
                assert r3 == [("b", 2000, 2.0)]
            finally:
                await s.close()

        asyncio.run(go())

    def test_write_invalidates_structurally(self):
        async def go():
            s = await open_storage(config=self._cfg())
            try:
                await s.write(WriteRequest(
                    make_batch([("a", 1000, 1.0)]), TimeRange.new(1000, 1001)))
                r1 = rows_of(await collect(s.scan(
                    ScanRequest(range=TimeRange.new(0, 10_000)))))
                # new write changes the SST set -> new key -> fresh merge
                await s.write(WriteRequest(
                    make_batch([("a", 1000, 9.0)]), TimeRange.new(1000, 1001)))
                r2 = rows_of(await collect(s.scan(
                    ScanRequest(range=TimeRange.new(0, 10_000)))))
                assert r1 == [("a", 1000, 1.0)]
                assert r2 == [("a", 1000, 9.0)]
            finally:
                await s.close()

        asyncio.run(go())

    def test_compaction_invalidates_structurally(self):
        async def go():
            cfg = self._cfg()
            cfg.scheduler.input_sst_min_num = 2
            s = await open_storage(config=cfg)
            try:
                for v in (1.0, 2.0):
                    await s.write(WriteRequest(
                        make_batch([("a", 1000, v)]),
                        TimeRange.new(1000, 1001)))
                assert rows_of(await collect(s.scan(
                    ScanRequest(range=TimeRange.new(0, 10_000))))) == \
                    [("a", 1000, 2.0)]
                task = await s.compact_scheduler.picker.pick_candidate()
                await s.compact_scheduler.executor.execute(task)
                assert rows_of(await collect(s.scan(
                    ScanRequest(range=TimeRange.new(0, 10_000))))) == \
                    [("a", 1000, 2.0)]
            finally:
                await s.close()

        asyncio.run(go())

    def test_eviction_bound(self):
        import numpy as np

        from horaedb_tpu.ops.encode import DeviceBatch
        from horaedb_tpu.storage.scan_cache import ScanCache, windows_nbytes

        def window(capacity):
            return DeviceBatch(
                columns={"a": np.zeros(capacity, np.int32)},
                encodings={}, n_valid=capacity, capacity=capacity)

        unit = windows_nbytes([window(8)])
        c = ScanCache(max_bytes=int(unit * 2.5))
        c.put(("k1",), [window(8)])
        c.put(("k2",), [window(8)])
        assert c.total_bytes == 2 * unit and len(c) == 2
        c.put(("k3",), [window(8)])  # evicts k1 (LRU)
        assert c.total_bytes == 2 * unit
        assert c.get(("k1",)) is None
        assert c.get(("k2",)) is not None
        # oversized entries are not cached
        c.put(("big",), [window(8192)])
        assert c.get(("big",)) is None

    def test_byte_accounting_counts_columns_and_memos(self):
        import numpy as np

        from horaedb_tpu.ops.encode import DeviceBatch
        from horaedb_tpu.storage.scan_cache import (
            MEMO_SLOTS,
            windows_nbytes,
        )

        w = DeviceBatch(
            columns={"a": np.zeros(256, np.int32),
                     "b": np.zeros(256, np.float32),
                     "c": np.zeros(256, np.int32)},
            encodings={}, n_valid=100, capacity=256)
        got = windows_nbytes([w])
        assert got == 3 * 4 * 256 + MEMO_SLOTS * (256 * 4 + 128)

    def test_disabled_cache(self):
        async def go():
            s = await open_storage(config=self._cfg(cache_rows=0))
            try:
                await s.write(WriteRequest(
                    make_batch([("a", 1000, 1.0)]), TimeRange.new(1000, 1001)))
                await collect(s.scan(ScanRequest(range=TimeRange.new(0, 10_000))))
                assert len(s.reader.scan_cache) == 0
            finally:
                await s.close()

        asyncio.run(go())


class TestTtlGc:
    def test_expired_only_gc_runs_without_rewrite(self):
        async def go():
            from horaedb_tpu.common import ReadableDuration, now_ms

            store = MemoryObjectStore()
            cfg = from_dict(StorageConfig, {
                "scheduler": {"schedule_interval": "1h", "ttl": "1h",
                              "input_sst_min_num": 5}})
            s = await CloudObjectStorage.open(
                "db", SEGMENT_MS, store, user_schema(), 2, cfg)
            try:
                now = now_ms()
                old = now - 3 * SEGMENT_MS  # ended long before now-ttl
                await s.write(WriteRequest(
                    make_batch([("old", old, 1.0)]),
                    TimeRange.new(old, old + 1)))
                await s.write(WriteRequest(
                    make_batch([("new", now, 2.0)]),
                    TimeRange.new(now, now + 1)))
                assert len(await s.manifest.all_ssts()) == 2

                task = await s.compact_scheduler.picker.pick_candidate()
                assert task is not None
                assert task.inputs == [] and len(task.expireds) == 1
                await s.compact_scheduler.executor.execute(task)

                ssts = await s.manifest.all_ssts()
                assert len(ssts) == 1  # expired file gone from manifest
                objs = [m.path for m in await store.list("db/data/")]
                # expired sst AND its sidecar gone; survivor keeps both
                assert sorted(objs) == [f"db/data/{ssts[0].id}.enc",
                                        f"db/data/{ssts[0].id}.sst"]
                got = rows_of(await collect(s.scan(
                    ScanRequest(range=TimeRange.new(0, now + SEGMENT_MS)))))
                assert got == [("new", now, 2.0)]
            finally:
                await s.close()

        asyncio.run(go())


class TestAppendModeWindowing:
    def test_windowed_append_equals_single_shot(self):
        async def go():
            import numpy as np
            schema = pa.schema([pa.field("k", pa.string()),
                                pa.field("payload", pa.binary())])
            rng = np.random.default_rng(5)

            async def run(window):
                cfg = StorageConfig(update_mode=UpdateMode.APPEND)
                cfg.scheduler.schedule_interval = ReadableDuration.parse("1h")
                cfg.scan.max_window_rows = window
                s = await CloudObjectStorage.open(
                    "db", SEGMENT_MS, MemoryObjectStore(), schema, 1, cfg)
                try:
                    for _ in range(3):
                        n = 300
                        keys = [f"k{int(i):03d}"
                                for i in rng.integers(0, 40, n)]
                        payloads = [bytes([i % 250, (i * 7) % 250])
                                    for i in range(n)]
                        b = pa.record_batch(
                            [pa.array(keys),
                             pa.array(payloads, type=pa.binary())],
                            schema=schema)
                        await s.write(WriteRequest(b, TimeRange.new(0, 10)))
                    out = {}
                    order = []
                    async for b in s.scan(ScanRequest(
                            range=TimeRange.new(0, 100))):
                        for k, v in zip(b.column(0).to_pylist(),
                                        b.column(1).to_pylist()):
                            out[k] = v
                            order.append(k)
                    return out, order
                finally:
                    await s.close()

            rng = np.random.default_rng(5)
            full, order_full = await run(1 << 20)
            rng = np.random.default_rng(5)
            windowed, order_win = await run(64)
            assert windowed == full
            assert order_win == sorted(order_win)  # global key order kept
        asyncio.run(go())


class TestPrunedRead:
    """read_pruned must keep exactly the rows pq.read_table(filters=...)
    keeps, across group-pruning, residual, constant-elision, and
    degenerate-projection shapes."""

    def _file(self, nulls=False):
        import io

        import pyarrow.parquet as pq

        n = 3000
        mid = np.full(n, 42, dtype=np.uint64)
        tsid = np.sort(np.random.default_rng(0).integers(
            0, 1 << 40, 7).astype(np.uint64).repeat(n // 7 + 1)[:n])
        ts = np.tile(np.arange(n // 10, dtype=np.int64) * 1000, 10)[:n]
        val = np.random.default_rng(1).random(n)
        if nulls:
            ts_arr = pa.array(
                [None if i == 17 else int(t) for i, t in enumerate(ts)],
                type=pa.int64())
        else:
            ts_arr = pa.array(ts, type=pa.int64())
        tbl = pa.table({"metric_id": pa.array(mid), "tsid": pa.array(tsid),
                        "timestamp": ts_arr,
                        "value": pa.array(val, type=pa.float64())})
        sink = io.BytesIO()
        pq.write_table(tbl, sink, row_group_size=256,
                       compression="snappy", write_statistics=True)
        return sink.getvalue()

    def _both(self, data, columns, leaves, expr):
        import pyarrow.parquet as pq

        from horaedb_tpu.storage.parquet_io import read_pruned

        pf = pq.ParquetFile(pa.BufferReader(data))
        try:
            pruned = read_pruned(pf, columns, leaves)
        finally:
            pf.close()
        ref = pq.read_table(pa.BufferReader(data), columns=columns,
                            filters=expr)
        return pruned, ref

    @pytest.mark.parametrize("shape", ["range", "eq_const", "eq_tsid",
                                       "in", "empty", "all", "gt"])
    def test_matches_expression_path(self, shape):
        import pyarrow.compute as pc

        from horaedb_tpu.ops.filter import Ge, In, Lt

        data = self._file()
        cases = {
            "range": ([TimeRangePred("timestamp", 50_000, 150_000)],
                      (pc.field("timestamp") >= 50_000)
                      & (pc.field("timestamp") < 150_000)),
            "eq_const": ([Eq("metric_id", 42),
                          TimeRangePred("timestamp", 0, 100_000)],
                         (pc.field("metric_id") == 42)
                         & (pc.field("timestamp") >= 0)
                         & (pc.field("timestamp") < 100_000)),
            "eq_tsid": ([Eq("metric_id", 42)], pc.field("metric_id") == 42),
            "in": ([In("tsid", frozenset([1, 2]))],
                   pc.field("tsid").isin([1, 2])),
            "empty": ([Eq("metric_id", 7)], pc.field("metric_id") == 7),
            "all": ([Ge("timestamp", 0)], pc.field("timestamp") >= 0),
            "gt": ([Lt("timestamp", 1234)], pc.field("timestamp") < 1234),
        }
        leaves, expr = cases[shape]
        cols = ["metric_id", "tsid", "timestamp", "value"]
        pruned, ref = self._both(data, cols, leaves, expr)
        assert pruned.schema.names == ref.schema.names
        assert pruned.sort_by("timestamp").equals(
            ref.sort_by("timestamp").cast(pruned.schema))

    def test_all_columns_elided_keeps_row_count(self):
        import pyarrow.compute as pc

        data = self._file()
        pruned, ref = self._both(
            data, ["metric_id"], [Eq("metric_id", 42)],
            pc.field("metric_id") == 42)
        assert pruned.num_rows == ref.num_rows == 3000
        assert pruned.column("metric_id").to_pylist()[:3] == [42, 42, 42]

    def test_all_columns_elided_with_residual_keeps_rows(self):
        import pyarrow.compute as pc

        data = self._file()
        pruned, ref = self._both(
            data, ["metric_id"],
            [Eq("metric_id", 42),
             TimeRangePred("timestamp", 30_000, 200_000)],
            (pc.field("metric_id") == 42)
            & (pc.field("timestamp") >= 30_000)
            & (pc.field("timestamp") < 200_000))
        assert pruned.num_rows == ref.num_rows > 0
        assert pruned.schema.names == ["metric_id"]

    def test_nulls_in_predicate_column_fall_back(self):
        import pyarrow.parquet as pq

        from horaedb_tpu.storage.parquet_io import (
            _PruneUnsupported,
            read_pruned,
        )

        data = self._file(nulls=True)
        pf = pq.ParquetFile(pa.BufferReader(data))
        try:
            with pytest.raises(_PruneUnsupported):
                read_pruned(pf, None,
                            [TimeRangePred("timestamp", 0, 10_000)])
        finally:
            pf.close()

    def _nan_file(self):
        """Constant-valued float column with interspersed NaNs: parquet
        min/max statistics IGNORE NaN ([1.0, NaN, 1.0] reports
        min=max=1.0, null_count=0), so neither constant-elision nor a
        'full'-verdict proof may trust float stats."""
        import io

        import pyarrow.parquet as pq

        n = 2000
        mid = np.full(n, 42, dtype=np.uint64)
        ts = np.arange(n, dtype=np.int64) * 1000
        val = np.ones(n)
        val[::37] = np.nan
        tbl = pa.table({"metric_id": pa.array(mid),
                        "timestamp": pa.array(ts, type=pa.int64()),
                        "value": pa.array(val, type=pa.float64())})
        sink = io.BytesIO()
        pq.write_table(tbl, sink, row_group_size=256,
                       compression="snappy", write_statistics=True)
        return sink.getvalue()

    def test_nan_float_column_never_elided(self):
        import pyarrow.compute as pc

        data = self._nan_file()
        pruned, ref = self._both(
            data, ["timestamp", "value"],
            [Eq("metric_id", 42), TimeRangePred("timestamp", 0, 500_000)],
            (pc.field("metric_id") == 42)
            & (pc.field("timestamp") >= 0)
            & (pc.field("timestamp") < 500_000))
        assert pruned.num_rows == ref.num_rows
        # assert_array_equal treats NaN == NaN; Table.equals does not
        got = pruned.sort_by("timestamp").column("value").to_numpy()
        want = ref.sort_by("timestamp").column("value").to_numpy()
        assert np.isnan(got).sum() == np.isnan(want).sum() > 0
        np.testing.assert_array_equal(got, want)

    def test_float_full_verdict_keeps_nan_filter(self):
        # stats say min=max=1.0 so 'Gt 0.5' looks 'full', but the NaN
        # rows fail the comparison — they must be filtered out exactly
        # like the expression path does
        import pyarrow.compute as pc

        from horaedb_tpu.ops.filter import Gt

        data = self._nan_file()
        pruned, ref = self._both(
            data, ["timestamp", "value"], [Gt("value", 0.5)],
            pc.field("value") > 0.5)
        assert pruned.num_rows == ref.num_rows > 0
        assert not np.isnan(pruned.column("value").to_numpy()).any()

    def test_conjunct_leaves_shapes(self):
        from horaedb_tpu.ops.filter import And, Ne, Or
        from horaedb_tpu.storage.parquet_io import conjunct_leaves

        pks = {"metric_id", "timestamp"}
        assert conjunct_leaves(None, pks) is None
        assert conjunct_leaves(Eq("value", 1.0), pks) is None  # dropped
        got = conjunct_leaves(
            And((Eq("metric_id", 1), Eq("value", 2.0),
                 TimeRangePred("timestamp", 0, 10))), pks)
        assert got is not None and len(got) == 2
        assert conjunct_leaves(
            Or((Eq("metric_id", 1), Eq("metric_id", 2))), pks) is None
        assert conjunct_leaves(Ne("metric_id", 1), pks) is None
