"""POST /query_buckets (ISSUE 48): one field aggregated ACROSS series by
time bucket, the newest buckets first (TSBS `groupby-orderby-limit`'s
shape at test size), served, on BOTH routes, against a plain reference
that imports nothing of the program (tests/buckets_reference.py: the
acknowledged writes in order, last write wins, a plain loop into a
dictionary keyed by bucket).

One server for the module.  `[scan.decode] mode` is read per query
(HORAEDB_DEVICE_DECODE), so the same store answers a request on the
device route ("1": the field's resident decode slice folded by
ops/buckets.py) and on the host route ("0": the row scan, folded in
numpy), and every case compares each with the reference and the two
with each other buffer for buffer.  A test that writes appends to the
store's list of writes: the reference moves with it.

The data walk further than TSBS's: a whole segment holds nothing, every
series is silent for an hour inside another, one host reports half the
time and one falls silent early, so that buckets differ in their
counts, the walk passes empty stretches and a request's five buckets
come from one, two or three segments.

max, min and count are compared bit for bit.  `usage_user` and
`usage_system` hold multiples of 1/8 under 100, whose sums (at most a
few thousand a bucket) are exact in float32 in any order, so their sum
and avg are compared bit for bit too, on both routes; `usage_idle`
holds full-width float32 fractions, and its sum and avg are held to
SUM_RTOL: the device folds a bucket's float32 values in float32 in an
order of its own (a tree), the reference rounds the exact sum once; a
bucket of n values then differs by at most about log2(n) roundings of
2**-24 each, 1e-6 for the few hundred values of a bucket here."""

import asyncio
import io
import json

import numpy as np
import pyarrow as pa
import pytest
from aiohttp.test_utils import TestClient, TestServer
from pyarrow import ipc

from horaedb_tpu.common import ReadableDuration
from horaedb_tpu.metric_engine import MetricEngine
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.ops import device_decode
from horaedb_tpu.server import main as server_main
from horaedb_tpu.server.config import ServerConfig
from horaedb_tpu.server.main import ServerState, build_app
from horaedb_tpu.storage.config import StorageConfig, from_dict
from horaedb_tpu.storage.types import TimeRange
from horaedb_tpu.utils import registry, tracing
from horaedb_tpu.wal import WalConfig

from tests.buckets_reference import newest_buckets

HOUR = 3_600_000
SEGMENT_MS = 2 * HOUR
T0 = 1_700_000_000_000 // SEGMENT_MS * SEGMENT_MS
TICK_MS = 60_000
BUCKET_MS = 5 * TICK_MS
HOSTS, SEGMENTS = 6, 4
SEG_TICKS = SEGMENT_MS // TICK_MS
TICKS = SEGMENTS * SEG_TICKS
FIELDS = ["usage_user", "usage_system", "usage_idle"]
LAST = TICKS - 11          # the data end inside the newest segment
EMPTY_SEGMENT = 1          # nobody reports in this segment
GAP = (2 * SEG_TICKS + 30, 2 * SEG_TICKS + 90)   # an hour of silence
HALF = 5                   # reports at even ticks alone
QUIET = 4                  # falls silent inside the newest segment
QUIET_FROM = 3 * SEG_TICKS + 40
SUM_RTOL = 1e-6
ALL_AGGS = ["max", "min", "sum", "avg"]


def ts_of(tick: int) -> int:
    return T0 + tick * TICK_MS


def seg_start(k: int) -> int:
    return T0 + k * SEGMENT_MS


def arrow_body(hosts, ticks, values) -> bytes:
    batch = pa.record_batch({
        "hostname": pa.array([f"host_{h}" for h in hosts]),
        "rack": pa.array([f"rack_{h % 2}" for h in hosts]),
        "timestamp": pa.array(np.asarray([ts_of(t) for t in ticks],
                                         dtype=np.int64)),
        "value": pa.array(np.asarray(values, dtype=np.float64))})
    sink = io.BytesIO()
    with ipc.new_stream(sink, batch.schema) as w:
        w.write_batch(batch)
    return sink.getvalue()


def storage_config() -> StorageConfig:
    return from_dict(StorageConfig,
                     {"scheduler": {"schedule_interval": "1h"}})


def reports() -> np.ndarray:
    out = np.ones((TICKS, HOSTS), dtype=bool)
    out[LAST + 1:] = False
    out[EMPTY_SEGMENT * SEG_TICKS:(EMPTY_SEGMENT + 1) * SEG_TICKS] = False
    out[GAP[0]:GAP[1]] = False
    out[1::2, HALF] = False
    out[QUIET_FROM:, QUIET] = False
    return out


class Served:
    def __init__(self, loop):
        self.loop = loop
        rng = np.random.default_rng(480048)
        eighths = rng.integers(0, 800, (2, TICKS, HOSTS)) / 8.0
        self.values = np.concatenate(
            [eighths, rng.random((1, TICKS, HOSTS)) * 100.0]
        ).astype(np.float32)
        # (host, field, timestamp, value) in the order acknowledged
        self.writes: list = []

    def run(self, coro):
        return self.loop.run_until_complete(coro)

    async def open(self):
        self.engine = await MetricEngine.open(
            "m", MemoryObjectStore(), segment_ms=SEGMENT_MS,
            config=storage_config())
        self.client = TestClient(TestServer(build_app(
            ServerState(self.engine, ServerConfig()))))
        await self.client.start_server()
        tick, host = np.nonzero(reports())
        for f, field in enumerate(FIELDS):
            await self.write(field, host, tick, self.values[f][tick, host])
        await self.compact()
        r = await self.client.post("/query_last", json={
            "metric": "cpu", "fields": FIELDS})
        tsids = ipc.open_stream(await r.read()).read_all() \
            .column("tsid").to_pylist()
        keys = await self.engine.resolve_series(
            "cpu", [int(t) for t in tsids], TimeRange.new(T0, T0 + 1))
        self.host_of = {
            tsid: next(h for h in range(HOSTS)
                       if f"host_{h}".encode() in key)
            for tsid, key in keys.items()}
        assert sorted(self.host_of.values()) == list(range(HOSTS))

    async def close(self):
        await self.client.close()
        await self.engine.close()

    @property
    def data(self):
        return self.engine.tables["data"]

    async def compact(self):
        """Every segment's SSTs become one, as the benchmark's set-up
        leaves them."""
        sched = self.data.compact_scheduler
        while (task := await sched.picker.pick_candidate()) is not None:
            await sched.executor.execute(task)

    async def sst_ids(self, segment: int) -> set:
        return {f.id for f in await self.data.manifest.find_ssts(
            TimeRange.new(seg_start(segment), seg_start(segment + 1)))}

    async def write(self, field: str, hosts, ticks, values):
        r = await self.client.post(
            f"/write_arrow?metric=cpu&tags=hostname,rack&field={field}",
            data=arrow_body(hosts, ticks, values))
        assert r.status == 200, await r.text()
        assert (await r.json())["written"] == len(hosts)
        self.writes.extend(
            (int(h), field, ts_of(int(t)), np.float32(v))
            for h, t, v in zip(hosts, ticks, values))

    async def post(self, body: dict, route: str):
        mp = pytest.MonkeyPatch()
        mp.setenv("HORAEDB_DEVICE_DECODE", route)
        try:
            return await self.client.post("/query_buckets", json=body)
        finally:
            mp.undo()

    async def buckets(self, route: str, field="usage_user",
                      bucket_ms=BUCKET_MS, limit=5, aggs=("max",),
                      start=None, end=None, filters=None,
                      metric="cpu") -> pa.Table:
        body = {"metric": metric, "field": field, "bucket_ms": bucket_ms,
                "limit": limit, "aggs": list(aggs)}
        for k, v in (("start", start), ("end", end), ("filters", filters)):
            if v is not None:
                body[k] = v
        r = await self.post(body, route)
        assert r.status == 200, await r.text()
        assert r.content_type == "application/vnd.apache.arrow.stream"
        return ipc.open_stream(await r.read()).read_all()

    def check(self, tbl: pa.Table, field="usage_user",
              bucket_ms=BUCKET_MS, limit=5, aggs=("max",), start=None,
              end=None, hosts=None) -> list:
        """`tbl` is the reference's answer: the same buckets,
        descending, each with the reference's count and values."""
        return check_table(tbl, newest_buckets(
            self.writes, field, bucket_ms, limit, list(aggs), start, end,
            series=hosts), aggs, exact=field != "usage_idle")

    async def both(self, hosts=None, **ask) -> list:
        """The request on both routes: each is the reference's answer,
        and the two are each other's, buffer for buffer."""
        dev = await self.buckets("1", **ask)
        host = await self.buckets("0", **ask)
        ask.pop("filters", None)
        ask.pop("metric", None)
        want = self.check(dev, hosts=hosts, **ask)
        self.check(host, hosts=hosts, **ask)
        if ask.get("field") != "usage_idle" \
                or not {"sum", "avg"} & set(ask.get("aggs", ())):
            same_buffers(dev, host)
        return want


def check_table(tbl: pa.Table, want: list, aggs, exact=True) -> list:
    aggs = list(aggs)
    assert tbl.schema.names == ["bucket", "count"] + aggs
    assert tbl.schema.field("bucket").type == pa.int64()
    assert tbl.schema.field("count").type == pa.int64()
    assert all(tbl.schema.field(a).type == pa.float32() for a in aggs)
    assert all(tbl.column(n).null_count == 0 for n in tbl.schema.names)
    bucket = tbl.column("bucket").to_pylist()
    assert all(a > b for a, b in zip(bucket, bucket[1:])), \
        "not strictly descending by bucket"
    assert bucket == [b for b, _n, _v in want], "the buckets differ"
    assert tbl.column("count").to_pylist() == [n for _b, n, _v in want]
    for c, a in enumerate(aggs):
        got = tbl.column(a).to_numpy()
        ref = np.asarray([v[c] for _b, _n, v in want], dtype=np.float32)
        if exact or a in ("max", "min"):
            assert got.tobytes() == ref.tobytes(), (a, got, ref)
        else:
            np.testing.assert_allclose(got, ref, rtol=SUM_RTOL)
    return want


def same_buffers(a: pa.Table, b: pa.Table) -> None:
    assert a.equals(b)
    for name in a.schema.names:
        assert np.array_equal(
            np.asarray(a.column(name).combine_chunks()).view(np.uint8),
            np.asarray(b.column(name).combine_chunks()).view(np.uint8)), \
            name


@pytest.fixture(scope="module")
def served():
    mp = pytest.MonkeyPatch()
    mp.setenv("HORAEDB_HOST_AGG", "0")
    loop = asyncio.new_event_loop()
    s = Served(loop)
    try:
        s.run(s.open())
        yield s
        s.run(s.close())
    finally:
        loop.close()
        mp.undo()


def segments_by_route(name="scan_buckets_segments_total") -> dict:
    fam = registry.counter(name)
    return {(dict(k).get("route"), dict(k).get("reason")): c.value
            for k, c in (fam._children or {}).items()}


def moved(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def counter(name: str, **labels) -> float:
    c = registry.counter(name)
    return (c.labels(**labels) if labels else c).value


async def asked(served, **ask) -> dict:
    """The segments one device-route request asks, by route."""
    c0 = segments_by_route()
    await served.buckets("1", **ask)
    return moved(c0, segments_by_route())


# ---------------------------------------------------------------------------
# answers
# ---------------------------------------------------------------------------


def test_the_newest_buckets_of_the_table_with_no_bound(served):
    """TSBS's shape without its `end`: the five newest buckets that
    hold a sample, from the newest segment alone; the newest is
    partial (the data end inside it) and the quiet host and the one
    that reports half the time make the counts differ."""
    async def go():
        want = await served.both()
        assert [b for b, _n, _v in want] == [
            ts_of(LAST) // BUCKET_MS * BUCKET_MS - k * BUCKET_MS
            for k in range(5)]
        assert len({n for _b, n, _v in want}) > 1
        assert await asked(served) == {("device", ""): 1}
    served.run(go())


@pytest.mark.parametrize("end, segments", [
    (seg_start(3) + HOUR + 7 * TICK_MS + 1, 1),
    (seg_start(3) + HOUR + 2 * BUCKET_MS, 1),
    (seg_start(3) + HOUR + 2 * BUCKET_MS + 1, 1),
    (seg_start(3), 1),
    (seg_start(3) + 2 * BUCKET_MS, 2),
    (seg_start(3) + 4 * BUCKET_MS, 2),
    (seg_start(3) + 4 * BUCKET_MS + 1, 1),
    (seg_start(3) + 1, 2),
], ids=["inside_a_bucket", "on_a_buckets_edge", "a_millisecond_past_it",
        "on_a_segment_boundary", "two_buckets_after_a_boundary",
        "four_buckets_after_it", "a_millisecond_into_the_fifth",
        "one_sample_after_it"])
def test_an_end_is_exclusive_and_its_bucket_partial(served, end, segments):
    """The bucket that holds `end` is answered from its samples before
    `end`; on a bucket's edge that bucket does not exist; on a segment
    boundary the newer segment is not even planned; within five
    buckets after one, the older segment gives the rest."""
    async def go():
        want = await served.both(end=end)
        assert len(want) == 5
        assert want[0][0] == (end - 1) // BUCKET_MS * BUCKET_MS
        assert sum((await asked(served, end=end)).values()) == segments
    served.run(go())


def test_a_start_leaves_fewer_buckets_than_the_limit(served):
    """Only what the client names: a `start` two buckets and a half
    before the data's end leaves three buckets, the oldest partial;
    one that begins after the data leaves none."""
    async def go():
        start = ts_of(LAST) - 2 * BUCKET_MS - 2 * TICK_MS
        want = await served.both(start=start)
        assert 2 <= len(want) <= 4
        assert want[-1][0] == start // BUCKET_MS * BUCKET_MS
        assert await served.both(start=ts_of(LAST + 1)) == []
        assert len(await served.both(
            start=seg_start(3) - 2 * BUCKET_MS,
            end=seg_start(3) + BUCKET_MS)) == 3
    served.run(go())


def test_empty_stretches_are_skipped_not_answered(served):
    """An hour in which every series is silent, and a whole segment
    that holds nothing: the buckets before them are the next newest,
    and the walk passes the empty segment without reading it (the
    manifest does not name it)."""
    async def go():
        end = ts_of(GAP[1]) + 2 * BUCKET_MS
        want = await served.both(end=end)
        starts = [b for b, _n, _v in want]
        assert starts[:2] == [end - BUCKET_MS, end - 2 * BUCKET_MS]
        assert starts[2] == ts_of(GAP[0]) - BUCKET_MS
        assert await asked(served, end=end) == {("device", ""): 1}
        # two buckets of segment 2, then segment 0's newest three
        end = seg_start(2) + 2 * BUCKET_MS
        want = await served.both(end=end)
        assert [b for b, _n, _v in want][2] == seg_start(1) - BUCKET_MS
        assert await asked(served, end=end) == {("device", ""): 2}
        # more than the table holds: every segment read, all answered
        everything = await served.both(limit=10_000)
        assert sum(n for _b, n, _v in everything) == int(reports().sum())
        assert await asked(served, limit=10_000) == {("device", ""): 3}
    served.run(go())


def test_a_bucket_that_straddles_two_segments_is_folded_from_both(served):
    """7 min do not divide 2 h: the bucket that holds the boundary of
    the two newest segments has rows in both, the walk reads on while
    the oldest bucket answered begins before the oldest segment read,
    and the count is the sum of both parts."""
    async def go():
        b = 7 * TICK_MS
        boundary = seg_start(3)
        assert boundary % b, "this boundary lies on the 7 min grid"
        straddling = boundary // b * b
        end = straddling + 3 * b
        want = await served.both(bucket_ms=b, limit=3, end=end,
                                 aggs=ALL_AGGS)
        assert want[-1][0] == straddling
        ticks = [t for t in range(TICKS) if straddling <= ts_of(t)
                 < straddling + b]
        assert want[-1][1] == int(reports()[ticks].sum())
        assert min(ticks) < 3 * SEG_TICKS <= max(ticks)
        assert await asked(served, bucket_ms=b, limit=3, end=end) \
            == {("device", ""): 2}
        # with one bucket less the walk may stop after one segment
        assert await asked(served, bucket_ms=b, limit=2, end=end) \
            == {("device", ""): 1}
        await served.both(bucket_ms=b, limit=40, aggs=ALL_AGGS)
        await served.both(bucket_ms=45 * TICK_MS + 1, limit=7)
    served.run(go())


@pytest.mark.parametrize("filters, hosts", [
    ({"hostname": "host_2"}, {2}),
    ({"rack": "rack_1"}, {1, 3, 5}),
    ({"rack": "rack_0", "hostname": "host_4"}, {4}),
    ({"hostname": "host_99"}, set()),
    ([["rack", "rack_0"]], {0, 2, 4}),
], ids=["one_series", "many_series", "two_labels", "no_series",
        "pairs_form"])
def test_a_label_filter_names_the_series_folded(served, filters, hosts):
    async def go():
        want = await served.both(filters=filters, hosts=hosts,
                                 aggs=["max", "min"])
        assert len(want) == (5 if hosts else 0)
        if hosts == {4}:    # quiet since QUIET_FROM: older buckets
            assert want[0][0] == ts_of(QUIET_FROM - 1) // BUCKET_MS \
                * BUCKET_MS
    served.run(go())


@pytest.mark.parametrize("field", FIELDS)
@pytest.mark.parametrize("aggs", [
    ["min"], ["sum"], ["avg"], ["avg", "max"], ["min", "sum", "avg", "max"],
], ids=lambda a: "_".join(a))
def test_min_sum_and_avg_beside_max_in_the_order_asked(served, field, aggs):
    async def go():
        await served.both(field=field, aggs=aggs, limit=30,
                          end=seg_start(3) + 40 * TICK_MS + 1)
    served.run(go())


def test_a_metric_nobody_wrote_answers_its_columns_and_no_row(served):
    async def go():
        for route in "10":
            tbl = await served.buckets(route, metric="mem", aggs=ALL_AGGS)
            assert tbl.num_rows == 0
            assert tbl.schema.names == ["bucket", "count"] + ALL_AGGS
            assert tbl.schema.field("avg").type == pa.float32()
        # and bounds before or after any data: nothing to plan
        assert await served.both(end=T0 - HOUR) == []
        assert await served.both(start=T0 + 30 * SEGMENT_MS) == []
    served.run(go())


# ---------------------------------------------------------------------------
# writes: a maximum overwritten, a newer sample, a compaction
# ---------------------------------------------------------------------------


def test_an_overwrite_of_a_buckets_maximum_by_a_smaller_value(served):
    """The new write is a second SST of its segment: the slice misses
    once (its key holds the SST ids), the bucket's maximum is the next
    largest current value, its count stands, and the older write does
    not shine through."""
    async def go():
        tick = LAST - 7
        bucket = ts_of(tick) // BUCKET_MS * BUCKET_MS
        before = {b: (n, v) for b, n, v in await served.both()}
        ticks = [t for t in range(TICKS)
                 if bucket <= ts_of(t) < bucket + BUCKET_MS]
        cell = served.values[0][ticks] * reports()[ticks]
        t, h = np.unravel_index(np.argmax(cell), cell.shape)
        assert before[bucket][1][0] == cell[t, h]
        miss0 = device_decode._RESIDENT["miss"].value
        await served.write("usage_user", [h], [ticks[t]], [0.125])
        after = {b: (n, v) for b, n, v in await served.both()}
        assert device_decode._RESIDENT["miss"].value > miss0
        assert after[bucket][0] == before[bucket][0]
        assert after[bucket][1][0] < before[bucket][1][0]
    served.run(go())


def test_a_write_and_a_compaction_between_two_queries(served):
    """A NEWER sample opens a bucket of its own: the next answer
    begins with it (count 1); the slice that missed is admitted again
    (the query after finds it resident), and after a compaction
    (other SST ids: one more miss) the answer is the same."""
    async def go():
        await served.both()
        await served.write("usage_user", [1], [LAST + 7], [77.5])
        want = await served.both()
        assert want[0] == (ts_of(LAST + 7) // BUCKET_MS * BUCKET_MS, 1,
                           [np.float32(77.5)])
        hit0 = device_decode._RESIDENT["hit"].value
        miss0 = device_decode._RESIDENT["miss"].value
        before = await served.buckets("1")
        assert device_decode._RESIDENT["miss"].value == miss0
        assert device_decode._RESIDENT["hit"].value - hit0 == 1
        ssts0 = await served.sst_ids(3)
        await served.compact()
        await served.both()
        # three SSTs or fewer in the segment are left as they are
        assert device_decode._RESIDENT["miss"].value \
            == miss0 + (await served.sst_ids(3) != ssts0)
        same_buffers(await served.buckets("1"), before)
    served.run(go())


def test_query_last_walks_as_it_did(served):
    """The walk is one: /query_last still asks a segment at a time
    until no series is missing, under its own counters and spans, and
    moves none of this route's."""
    async def go():
        b0 = segments_by_route()
        l0 = segments_by_route("scan_last_segments_total")
        mp = pytest.MonkeyPatch()
        mp.setenv("HORAEDB_DEVICE_DECODE", "1")
        try:
            r = await served.client.post("/query_last", json={
                "metric": "cpu", "fields": ["usage_system"]})
        finally:
            mp.undo()
        assert r.status == 200
        tbl = ipc.open_stream(await r.read()).read_all()
        assert tbl.num_rows == HOSTS
        assert moved(l0, segments_by_route("scan_last_segments_total")) \
            == {("device", ""): 1}
        assert moved(b0, segments_by_route()) == {}
        tree = (await (await served.client.get(
            f"/debug/traces/{r.headers[tracing.TRACE_HEADER]}")).json())[
                "tree"]
        steps = [c["name"] for c in tree["children"]
                 if c["name"] != "admission_wait"]
        assert steps == ["parse", "resolve", "last", "respond"]
    served.run(go())


# ---------------------------------------------------------------------------
# other tables: the WAL's memtable, the chunked layout, an unsorted slice
# ---------------------------------------------------------------------------


def small_writes(hosts: int, ticks: int, fields: list, seed: int) -> list:
    rng = np.random.default_rng(seed)
    return [(h, f, ts_of(t), np.float32(rng.integers(0, 800) / 8.0))
            for f in fields for t in range(ticks) for h in range(hosts)]


async def write_all(e: MetricEngine, writes: list) -> None:
    by_field: dict = {}
    for h, f, ts, v in writes:
        by_field.setdefault(f, []).append((h, ts, v))
    for f, rows in by_field.items():
        batch = pa.record_batch({
            "hostname": pa.array([f"host_{h}" for h, _, _ in rows]),
            "timestamp": pa.array([ts for _, ts, _ in rows],
                                  type=pa.int64()),
            "value": pa.array([float(v) for _, _, v in rows])})
        await e.write_arrow("cpu", ["hostname"], batch, field=f)


def test_rows_still_in_the_memtable_are_answered(tmp_path, monkeypatch):
    """The WAL on and nothing flushed by a timer: the newest samples
    lie in memtables (one segment lives there alone, one has an SST
    under its memtable).  Those segments are answered through the row
    scan that overlays them (`memtable`, no fallback, no flush), a
    flushed one from its slice."""
    monkeypatch.setenv("HORAEDB_HOST_AGG", "0")
    monkeypatch.setenv("HORAEDB_DEVICE_DECODE", "1")
    fields = ["usage_user", "usage_system"]
    hosts = 3
    flushed = small_writes(hosts, SEG_TICKS + 20, fields, seed=7)
    later = [(h, f, ts_of(t), np.float32(v)) for h, f, t, v in [
        (0, "usage_user", SEG_TICKS + 19, 0.5),        # an overwrite
        (1, "usage_user", SEG_TICKS + 25, 2.5),        # a newer bucket
        (2, "usage_user", 2 * SEG_TICKS + 3, 3.5)]]    # a new segment
    writes = flushed + later

    async def go():
        wal = WalConfig(
            enabled=True, dir=str(tmp_path / "wal"), flush_rows=10 ** 6,
            flush_bytes=1 << 30, flush_age=ReadableDuration.parse("1h"),
            flush_interval=ReadableDuration.parse("1h"),
            max_group_wait=ReadableDuration.from_millis(0))
        e = await MetricEngine.open(
            "walled", MemoryObjectStore(), segment_ms=SEGMENT_MS,
            config=storage_config(), wal_config=wal)
        try:
            await write_all(e, flushed)
            await e.flush()
            await write_all(e, later)
            overlaid = {T0 + SEGMENT_MS, T0 + 2 * SEGMENT_MS}
            assert e.tables["data"].memtable_segments() == overlaid
            c0, f0 = segments_by_route(), counter(
                "scan_decode_fallback_total")
            tbl = await e.query_buckets("cpu", [], "usage_user",
                                        BUCKET_MS, 5, ALL_AGGS)
            routes = moved(c0, segments_by_route())
            assert counter("scan_decode_fallback_total") == f0
            assert e.tables["data"].memtable_segments() == overlaid, \
                "no flush"
            c0 = segments_by_route()
            old = await e.query_buckets(
                "cpu", [], "usage_user", BUCKET_MS, 3, ["max"],
                end=T0 + SEGMENT_MS)
            return tbl, routes, old, moved(c0, segments_by_route())
        finally:
            await e.close()

    tbl, routes, old, old_routes = asyncio.run(go())
    want = check_table(tbl, newest_buckets(
        writes, "usage_user", BUCKET_MS, 5, ALL_AGGS), ALL_AGGS)
    assert [n for _b, n, _v in want[:2]] == [1, 1]
    # one bucket in the memtable-only segment, four in the one with an
    # SST under its memtable: two segments, both overlaid
    assert routes == {("host", "memtable"): 2}
    check_table(old, newest_buckets(
        writes, "usage_user", BUCKET_MS, 3, ["max"], end=T0 + SEGMENT_MS),
        ["max"])
    assert old_routes == {("device", ""): 1}


def test_a_chunked_table_answers_the_references_rows():
    """The chunked layout has no decode slices: the field is scanned
    by query() and folded on the host; an overwrite, a bound and a
    filter decide as in the row layout."""
    fields = ["usage_user", "usage_system"]
    hosts, ticks = 3, 40
    writes = small_writes(hosts, ticks, fields, seed=48)
    writes += [(0, "usage_user", ts_of(ticks - 1), np.float32(1.5)),
               (2, "usage_user", ts_of(ticks + 45), np.float32(99.5))]

    async def go():
        e = await MetricEngine.open(
            "chunked", MemoryObjectStore(), segment_ms=SEGMENT_MS,
            chunked_data=True, chunk_window_ms=30 * 60_000)
        try:
            for h, f, ts, v in writes:
                batch = pa.record_batch({
                    "hostname": pa.array([f"host_{h}"]),
                    "timestamp": pa.array([ts], type=pa.int64()),
                    "value": pa.array([float(v)])})
                await e.write_arrow("cpu", ["hostname"], batch, field=f)
            tbl = await e.query_buckets("cpu", [], "usage_user",
                                        BUCKET_MS, 5, ALL_AGGS)
            cut = await e.query_buckets("cpu", [], "usage_user",
                                        BUCKET_MS, 5, ["max"],
                                        end=ts_of(ticks) - 1)
            one = await e.query_buckets(
                "cpu", [("hostname", "host_1")], "usage_system",
                7 * TICK_MS, 3, ["min", "avg"], start=ts_of(5))
            return tbl, cut, one
        finally:
            await e.close()

    tbl, cut, one = asyncio.run(go())
    want = check_table(tbl, newest_buckets(
        writes, "usage_user", BUCKET_MS, 5, ALL_AGGS), ALL_AGGS)
    assert want[0][:2] == (ts_of(ticks + 45), 1)
    check_table(cut, newest_buckets(
        writes, "usage_user", BUCKET_MS, 5, ["max"],
        end=ts_of(ticks) - 1), ["max"])
    check_table(one, newest_buckets(
        writes, "usage_system", 7 * TICK_MS, 3, ["min", "avg"],
        start=ts_of(5), series={1}), ["min", "avg"])


def test_a_slice_that_is_not_sorted_takes_the_host_route(monkeypatch):
    """A field of few rows beside one of many in one segment: the
    many-rowed field's slice is not narrowed (no smaller capacity), so
    it decodes with the other field's rows between a series' own and
    (series, timestamp) falls.  The device route declines the segment
    (`unsorted`, counted as a fallback), the row scan answers it, and
    the answer is the reference's on both."""
    monkeypatch.setenv("HORAEDB_HOST_AGG", "0")
    hosts = 3
    writes = small_writes(hosts, 60, ["usage_user"], seed=5) \
        + small_writes(hosts, 4, ["usage_extra"], seed=6)

    async def go():
        e = await MetricEngine.open(
            "unsorted", MemoryObjectStore(), segment_ms=SEGMENT_MS,
            config=storage_config())
        try:
            await write_all(e, writes)
            sched = e.tables["data"].compact_scheduler
            while (task := await sched.picker.pick_candidate()) is not None:
                await sched.executor.execute(task)
            out = {}
            for route in "10":
                monkeypatch.setenv("HORAEDB_DEVICE_DECODE", route)
                c0, f0 = segments_by_route(), counter(
                    "scan_decode_fallback_total", reason="unsorted")
                tbl = await e.query_buckets("cpu", [], "usage_user",
                                            BUCKET_MS, 5, ALL_AGGS)
                out[route] = (tbl, moved(c0, segments_by_route()),
                              counter("scan_decode_fallback_total",
                                      reason="unsorted") - f0)
            return out
        finally:
            await e.close()

    out = asyncio.run(go())
    want = newest_buckets(writes, "usage_user", BUCKET_MS, 5, ALL_AGGS)
    for route in "10":
        check_table(out[route][0], want, ALL_AGGS)
    same_buffers(out["1"][0], out["0"][0])
    assert out["1"][1:] == ({("host", "unsorted"): 1}, 1)
    assert out["0"][1:] == ({("host", "mode_host"): 1}, 0)


@pytest.mark.parametrize("bucket_ms, reason, counts", [
    (1000, "buckets", [HOSTS - 2, HOSTS - 1] * 2),
    (30 * 24 * HOUR, "range", [int(reports().sum())]),
], ids=["one_second", "thirty_days"])
def test_a_grid_the_device_does_not_fold_takes_the_host_route(
        served, bucket_ms, reason, counts):
    """One-second buckets cut a 2 h segment into 7,200 cells, and the
    masked fold costs rows x buckets (`buckets`); a bucket of thirty
    days puts a row's time since the grid's start past int32
    (`range`): the segment is folded on the host, counted as a
    fallback; the answer is the reference's."""
    async def go():
        f0 = counter("scan_decode_fallback_total", reason=reason)
        end = ts_of(LAST) + 1
        want = await served.both(bucket_ms=bucket_ms, limit=4, end=end)
        assert [n for _b, n, _v in want] == counts
        routes = await asked(served, bucket_ms=bucket_ms, limit=4, end=end)
        assert set(routes) == {("host", reason)}
        assert counter("scan_decode_fallback_total", reason=reason) - f0 \
            == 2 * sum(routes.values())
    served.run(go())


# ---------------------------------------------------------------------------
# the counters, the spans, the pool, the plan
# ---------------------------------------------------------------------------


def test_the_counters_follow_the_walk(served):
    def read() -> dict:
        return {"read": counter("scan_buckets_rows_total", side="read",
                                route="device"),
                "used": counter("scan_buckets_rows_total", side="used",
                                route="device"),
                "calls": counter("scan_buckets_calls_total"),
                "requests": counter("query_buckets_total"),
                "wall": counter("query_buckets_seconds_total"),
                "cells": counter("respond_cells_total"),
                "loop": counter("respond_encode_total", where="loop")}

    end = seg_start(3) + 2 * BUCKET_MS

    async def go():
        await served.buckets("1", end=end)
        before, c0 = read(), segments_by_route()
        tbl = await served.buckets("1", end=end)
        return tbl, moved(before, read()), moved(c0, segments_by_route())
    tbl, d, segs = served.run(go())
    assert segs == {("device", ""): 2}
    # the two slices asked, whole: every key of the two segments before
    # `end` at least (an SST that begins after `end` is not planned),
    # every row written into them at most (an overwrite not compacted
    # away yet is a second row of its key)
    rows = [(h, ts) for h, f, ts, _v in served.writes
            if f == "usage_user" and seg_start(2) <= ts < seg_start(4)]
    assert len({(h, ts) for h, ts in rows if ts < end}) \
        <= d["read"] <= len(rows)
    assert d["read"] > d["used"]
    assert d["used"] == sum(tbl.column("count").to_pylist())
    assert d["calls"] == 2          # a segment: one call
    assert d["requests"] == 1 and d["wall"] > 0
    assert d["cells"] == tbl.num_rows * tbl.num_columns == 15
    assert d["loop"] == 1


def test_the_request_is_traced_as_a_query_with_its_four_steps(served):
    end = seg_start(3) + 2 * BUCKET_MS

    async def go():
        r = await served.post({
            "metric": "cpu", "field": "usage_user", "bucket_ms": BUCKET_MS,
            "limit": 5, "aggs": ["max"], "end": end,
            "filters": {"rack": "rack_1"}}, "1")
        assert r.status == 200
        trace_id = r.headers[tracing.TRACE_HEADER]
        lst = await (await served.client.get(
            "/debug/traces?limit=8&kind=query")).json()
        mine = [t for t in lst["traces"] if t["trace_id"] == trace_id]
        assert mine and mine[0]["root"] == "/query_buckets"
        tbl = ipc.open_stream(await r.read()).read_all()
        return tbl, (await (await served.client.get(
            f"/debug/traces/{trace_id}")).json())["tree"]
    tbl, tree = served.run(go())
    steps = [c for c in tree["children"] if c["name"] != "admission_wait"]
    assert [c["name"] for c in steps] \
        == ["parse", "resolve", "buckets", "respond"]
    assert "postings" in steps[1]["fields"]
    inner = {c["name"] for c in steps[2]["children"]}
    assert {"scan.plan", "scan.windows", "scan.dispatch", "scan.d2h",
            "scan.combine", "buckets.segment"} <= inner
    segs = [c["fields"] for c in steps[2]["children"]
            if c["name"] == "buckets.segment"]
    assert [s["route"] for s in segs] == ["device"] * 2
    assert [s["reason"] for s in segs] == [""] * 2
    # newest first; a segment's used rows are those of answered buckets
    assert [s["segment"] for s in segs] == [seg_start(3), seg_start(2)]
    assert [s["buckets_out"] for s in segs] == [2, 24 - 12]
    assert sum(s["rows_used"] for s in segs) \
        == sum(tbl.column("count").to_pylist())
    assert all(s["rows_read"] > s["rows_used"] > 0 for s in segs)


def test_a_large_answer_is_written_on_the_pool(served, monkeypatch):
    async def go(where: str) -> float:
        c0 = counter("respond_encode_total", where=where)
        await served.buckets("1")
        return counter("respond_encode_total", where=where) - c0
    assert served.run(go("loop")) == 1
    monkeypatch.setattr(server_main, "_RESPOND_POOL_MIN_CELLS", 10)
    assert served.run(go("pool")) == 1


def test_the_plan_names_its_segments_newest_first(served):
    from horaedb_tpu.ops import And, Eq
    from horaedb_tpu.ops.buckets import BucketsSpec
    from horaedb_tpu.storage.read import ScanRequest
    from horaedb_tpu.storage.types import Timestamp

    async def go():
        rng = TimeRange.new(int(Timestamp.MIN), seg_start(3) + 1)
        qp = await served.data.plan_buckets(
            ScanRequest(range=rng, predicate=And([Eq("metric_id", 1)])),
            BucketsSpec("tsid", "timestamp", "value", 60_000, ("max",)), 5)
        return qp.describe()
    text = served.run(go()).splitlines()
    assert text[0] == ("Buckets: ts=timestamp, value=value, "
                       "bucket_ms=60000, aggs=['max'], over all series, "
                       "newest first, stops at 5 bucket(s) that no older "
                       "segment can add to")
    assert [ln.split(":")[0] for ln in text[1:]] == [
        f"  Segment {seg_start(k)}" for k in (3, 2, 0)]


# ---------------------------------------------------------------------------
# the 400s: before any scan
# ---------------------------------------------------------------------------

GOOD = {"metric": "cpu", "field": "usage_user", "bucket_ms": 60_000,
        "limit": 5, "aggs": ["max"], "start": T0, "end": T0 + HOUR}


def _with(**changes) -> dict:
    body = json.loads(json.dumps(GOOD))
    for k, v in changes.items():
        if v is None:
            del body[k]
        else:
            body[k] = v
    return body


@pytest.mark.parametrize("body, says", [
    (_with(field="nope"), "unknown field"),
    (_with(field=None), "field"),
    (_with(field=["usage_user"]), "field must be a string"),
    (_with(aggs=[]), "aggs"),
    (_with(aggs=["max", "max"]), "aggs"),
    (_with(aggs="max"), "aggs"),
    (_with(aggs=None), "aggs"),
    (_with(aggs=["max", "median"]), "unknown aggregate"),
    (_with(aggs=["count"]), "unknown aggregate"),
    (_with(bucket_ms=0), "bucket_ms must be at least 1"),
    (_with(bucket_ms=None), "bucket_ms"),
    (_with(bucket_ms="a minute"), "invalid literal"),
    (_with(limit=0), "limit must lie in 1..10000"),
    (_with(limit=10_001), "limit must lie in 1..10000"),
    (_with(limit=None), "limit"),
    (_with(start=T0 + HOUR), "start must lie before end"),
    (_with(start=T0 + 2 * HOUR), "start must lie before end"),
    (_with(end="noon"), "invalid literal"),
    (_with(metric=None), "metric"),
    (_with(compression="snappy"), "compression"),
], ids=["unknown_field", "no_field", "field_not_a_string", "aggs_empty",
        "aggs_twice", "aggs_not_a_list", "no_aggs", "unknown_aggregate",
        "count_is_always_there", "bucket_zero", "no_bucket",
        "bucket_not_a_number", "limit_zero", "limit_too_large",
        "no_limit", "start_is_end", "start_after_end",
        "end_not_a_number", "no_metric", "unknown_compression"])
@pytest.mark.parametrize("route", ["1", "0"], ids=["device", "host"])
def test_a_bad_request_is_a_400_before_any_scan(served, body, says, route):
    async def go():
        scans0 = counter("query_buckets_seconds_total")
        r = await served.post(body, route)
        assert r.status == 400, await r.text()
        assert says in (await r.json())["error"]
        assert counter("query_buckets_seconds_total") == scans0
    served.run(go())
