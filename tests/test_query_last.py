"""POST /query_last (ISSUE 43): the newest row of every series, TSBS
`lastpoint`'s shape at test size, served, on BOTH routes, against a
plain reference that imports nothing of the program
(tests/last_reference.py: the acknowledged writes in order, last write
wins, a series' greatest timestamp over the fields asked).

One server for the module.  `[scan.decode] mode` is read per query
(HORAEDB_DEVICE_DECODE), so the same store answers a request on the
device route ("1": the series' last rows taken from the resident decode
slices, ops/last.py) and on the host route ("0": the row scan, reduced
in numpy), and every case compares each with the reference and the two
with each other bit for bit.  A test that writes appends to the store's
list of writes: the reference moves with it.

The data walk further than TSBS's: one host reports in the oldest
segment alone, one falls silent inside the middle one, one field
misses a host's newest tick and one field alone has another's."""

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

from tests.last_reference import last_rows

HOUR = 3_600_000
SEGMENT_MS = 2 * HOUR
T0 = 1_700_000_000_000 // SEGMENT_MS * SEGMENT_MS
TICK_MS = 60_000
HOSTS, SEGMENTS = 6, 3
SEG_TICKS = SEGMENT_MS // TICK_MS
TICKS = SEGMENTS * SEG_TICKS
FIELDS = ["usage_user", "usage_system", "usage_idle", "usage_nice"]
LAST = TICKS - 11      # the data end inside the newest segment
OLDEST_ONLY = 5        # reports in the oldest segment alone
QUIET = 4              # falls silent inside the middle segment
QUIET_FROM = SEG_TICKS + 40
NICE_MISSES = 2        # usage_nice has no sample at this host's last tick
IDLE_ALONE = 3         # at this host's last tick only usage_idle reports


def ts_of(tick: int) -> int:
    return T0 + tick * TICK_MS


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


def reports(f: int) -> np.ndarray:
    out = np.ones((TICKS, HOSTS), dtype=bool)
    out[LAST + 1:] = False
    out[SEG_TICKS:, OLDEST_ONLY] = False
    out[QUIET_FROM:, QUIET] = False
    if FIELDS[f] == "usage_nice":
        out[LAST, NICE_MISSES] = False
    if FIELDS[f] != "usage_idle":
        out[LAST, IDLE_ALONE] = False
    return out


class Served:
    def __init__(self, loop):
        self.loop = loop
        rng = np.random.default_rng(430043)
        self.values = (rng.random((len(FIELDS), TICKS, HOSTS)) * 100.0
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
        for f, field in enumerate(FIELDS):
            tick, host = np.nonzero(reports(f))
            await self.write(field, host, tick,
                             self.values[f][tick, host])
        await self.compact()
        tbl = await self.last(FIELDS, route="0")
        keys = await self.engine.resolve_series(
            "cpu", [int(t) for t in tbl.column("tsid").to_pylist()],
            TimeRange.new(T0, T0 + 1))
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
            return await self.client.post("/query_last", json=body)
        finally:
            mp.undo()

    async def last(self, fields, route: str, start=None, end=None,
                   filters=None, metric="cpu") -> pa.Table:
        body = {"metric": metric, "fields": list(fields)}
        for k, v in (("start", start), ("end", end), ("filters", filters)):
            if v is not None:
                body[k] = v
        r = await self.post(body, route)
        assert r.status == 200, await r.text()
        assert r.content_type == "application/vnd.apache.arrow.stream"
        return ipc.open_stream(await r.read()).read_all()

    def check(self, tbl: pa.Table, fields, start=None, end=None,
              hosts=None) -> int:
        """`tbl` is the reference's answer: the same series in tsid
        order, each at the reference's timestamp, every value bit for
        bit, nulls where it has none."""
        assert tbl.schema.names == ["tsid", "timestamp"] + list(fields)
        assert tbl.schema.field("tsid").type == pa.uint64()
        assert tbl.schema.field("timestamp").type == pa.int64()
        assert tbl.column("tsid").null_count == 0
        assert tbl.column("timestamp").null_count == 0
        want = last_rows(self.writes, fields, start, end, series=hosts)
        tsid = tbl.column("tsid").to_numpy()
        assert (tsid[1:] > tsid[:-1]).all(), \
            "not strictly ascending by tsid"
        got = sorted(zip([self.host_of[int(t)] for t in tsid],
                         tbl.column("timestamp").to_pylist(),
                         range(len(tsid))))
        assert [(h, t) for h, t, _ in got] \
            == [(h, t) for h, t, _ in want], "the rows differ"
        for c, field in enumerate(fields):
            assert tbl.schema.field(field).type == pa.float32()
            col = tbl.column(field).to_pylist()
            for (h, t, i), (_h, _t, vals) in zip(got, want):
                have = col[i]
                if vals[c] is None:
                    assert have is None, (field, h, t)
                else:
                    assert have is not None and \
                        np.float32(have).tobytes() == vals[c].tobytes(), \
                        (field, h, t)
        return len(want)

    async def both(self, fields, start=None, end=None, filters=None,
                   hosts=None) -> int:
        """The request on both routes: each is the reference's answer,
        and the two are each other's, buffer for buffer."""
        dev = await self.last(fields, "1", start, end, filters)
        host = await self.last(fields, "0", start, end, filters)
        n = self.check(dev, fields, start, end, hosts)
        self.check(host, fields, start, end, hosts)
        same_buffers(dev, host)
        return n


def same_buffers(a: pa.Table, b: pa.Table) -> None:
    assert a.equals(b)
    for name in a.schema.names:
        x = a.column(name).combine_chunks()
        y = b.column(name).combine_chunks()
        assert x.null_count == y.null_count
        assert np.array_equal(
            np.asarray(x.fill_null(0)).view(np.uint8),
            np.asarray(y.fill_null(0)).view(np.uint8)), name


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


def segments_by_route() -> dict:
    fam = registry.counter("scan_last_segments_total")
    return {(dict(k).get("route"), dict(k).get("reason")): c.value
            for k, c in (fam._children or {}).items()}


def moved(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def counter(name: str, **labels) -> float:
    c = registry.counter(name)
    return (c.labels(**labels) if labels else c).value


# ---------------------------------------------------------------------------
# answers
# ---------------------------------------------------------------------------


def test_every_hosts_newest_row_with_no_bound(served):
    """TSBS lastpoint's shape: no bound, every series, all fields.  The
    host of the oldest segment alone and the one quiet since the middle
    one are answered at their own newest timestamps; the walk asks
    three segments and the last two for ever fewer series."""
    async def go():
        assert await served.both(FIELDS) == HOSTS
        c0 = segments_by_route()
        tbl = await served.last(FIELDS, "1")
        assert moved(c0, segments_by_route()) == {("device", ""): SEGMENTS}
        at = {served.host_of[int(t)]: ts for t, ts in zip(
            tbl.column("tsid").to_pylist(),
            tbl.column("timestamp").to_pylist())}
        assert at[0] == ts_of(LAST)
        assert at[OLDEST_ONLY] == ts_of(SEG_TICKS - 1)
        assert at[QUIET] == ts_of(QUIET_FROM - 1)
    served.run(go())


def test_the_walk_stops_at_the_first_segment_that_answers_everyone(served):
    """A filter on the hosts that report to the end: ONE segment asked,
    as in TSBS's data, where every host reports at every tick."""
    async def go():
        await served.last(FIELDS, "1", filters={"hostname": "host_1"})
        c0 = segments_by_route()
        assert await served.both(
            FIELDS, filters={"hostname": "host_1"}, hosts={1}) == 1
        assert moved(c0, segments_by_route()) == {
            ("device", ""): 1, ("host", "mode_host"): 1}
    served.run(go())


@pytest.mark.parametrize("end", [
    T0 + 3 * HOUR + 7, ts_of(SEG_TICKS + 30), T0 + 2 * SEGMENT_MS,
    T0 + SEGMENT_MS], ids=["inside_a_segment", "on_a_tick",
                           "on_a_boundary", "the_oldest_segment_alone"])
def test_an_end_is_an_instant_query_at_that_time(served, end):
    """`end` is exclusive: a sample AT it is not seen, the one a tick
    before is; on a segment's boundary the newer segments are not even
    planned."""
    async def go():
        assert await served.both(FIELDS, end=end) == HOSTS
        c0 = segments_by_route()
        await served.last(FIELDS, "1", end=end)
        asked = sum(moved(c0, segments_by_route()).values())
        assert asked == (end - 1 - T0) // SEGMENT_MS + 1
    served.run(go())


def test_a_start_is_a_look_back_that_drops_a_quiet_series(served):
    """Only what the client names: with `start` at the newest
    segment's edge the two hosts that fell silent before it have no
    row; with one inside the middle segment the one quiet since then
    is back."""
    async def go():
        assert await served.both(FIELDS, start=T0 + 2 * SEGMENT_MS) \
            == HOSTS - 2
        assert await served.both(FIELDS, start=ts_of(SEG_TICKS + 10)) \
            == HOSTS - 1
        assert await served.both(
            FIELDS, start=ts_of(QUIET_FROM), end=ts_of(LAST)) == HOSTS - 2
        assert await served.both(
            FIELDS, start=T0 + HOUR + 7, end=T0 + 3 * HOUR + 7) == HOSTS
    served.run(go())


def test_a_field_that_misses_the_rows_timestamp_is_null(served):
    """usage_nice has no sample at one host's newest tick, and at
    another's only usage_idle reports: nulls there, the row stands at
    the newest timestamp any field asked has."""
    async def go():
        await served.both(FIELDS)
        tbl = await served.last(FIELDS, "1")
        row = {served.host_of[int(t)]: i for i, t in enumerate(
            tbl.column("tsid").to_pylist())}
        ts = tbl.column("timestamp").to_pylist()
        assert ts[row[NICE_MISSES]] == ts[row[IDLE_ALONE]] == ts_of(LAST)
        assert tbl.column("usage_nice")[row[NICE_MISSES]].as_py() is None
        assert tbl.column("usage_user")[row[NICE_MISSES]].as_py() \
            is not None
        alone = [tbl.column(f)[row[IDLE_ALONE]].as_py() for f in FIELDS]
        assert [v is None for v in alone] == [True, True, False, True]
        # asked alone, a field stands at ITS newest timestamp
        n = await served.both(["usage_nice"])
        assert n == HOSTS
        one = await served.last(["usage_nice"], "1")
        at = {served.host_of[int(t)]: ts for t, ts in zip(
            one.column("tsid").to_pylist(),
            one.column("timestamp").to_pylist())}
        assert at[NICE_MISSES] == at[IDLE_ALONE] == ts_of(LAST - 1)
        assert one.column("usage_nice").null_count == 0
        # and the order asked is the order answered
        await served.both(["usage_idle", "usage_user"])
    served.run(go())


@pytest.mark.parametrize("filters, hosts", [
    ({"hostname": "host_2"}, {2}),
    ({"rack": "rack_1"}, {1, 3, 5}),
    ({"rack": "rack_0", "hostname": "host_4"}, {4}),
    ({"hostname": "host_99"}, set()),
    ([["rack", "rack_0"]], {0, 2, 4}),
], ids=["one_series", "many_series", "two_labels", "no_series",
        "pairs_form"])
def test_a_label_filter_names_the_series_the_walk_accounts_for(
        served, filters, hosts):
    async def go():
        assert await served.both(FIELDS, filters=filters, hosts=hosts) \
            == len(hosts)
    served.run(go())


def test_a_metric_nobody_wrote_answers_its_columns_and_no_row(served):
    async def go():
        for route in "10":
            tbl = await served.last(FIELDS, route, metric="mem")
            assert tbl.num_rows == 0
            assert tbl.schema.names == ["tsid", "timestamp"] + FIELDS
            assert tbl.schema.field("usage_user").type == pa.float32()
        # and bounds before any data: nothing to plan, same shape
        assert await served.both(FIELDS, end=T0 - HOUR) == 0
        assert await served.both(FIELDS, start=T0 + 30 * SEGMENT_MS) == 0
    served.run(go())


# ---------------------------------------------------------------------------
# writes: the newest sample overwritten, a newer one, a compaction
# ---------------------------------------------------------------------------


def test_an_overwrite_of_the_newest_sample_is_the_answer(served):
    """The new write is a second SST of its segment: the segment's
    slices miss once (their key holds the SST ids), the answer holds
    the new value at the same timestamp, and the older write does not
    shine through."""
    async def go():
        await served.both(FIELDS)
        miss0 = device_decode._RESIDENT["miss"].value
        await served.write("usage_user", [0], [LAST], [12.625])
        await served.both(FIELDS)
        assert device_decode._RESIDENT["miss"].value > miss0
        tbl = await served.last(["usage_user"], "1")
        row = [served.host_of[int(t)] for t in
               tbl.column("tsid").to_pylist()].index(0)
        assert tbl.column("timestamp")[row].as_py() == ts_of(LAST)
        assert tbl.column("usage_user")[row].as_py() == 12.625
    served.run(go())


def test_a_write_and_a_compaction_between_two_queries(served):
    """A NEWER sample of one field for one host: the next answer stands
    at its timestamp with the other fields null; the slices that missed
    are admitted again (the query after finds all resident), and after
    a compaction (other SST ids: one more miss) the answer is the
    same."""
    async def go():
        await served.both(FIELDS)
        await served.write("usage_system", [1], [LAST + 5], [77.5])
        assert await served.both(FIELDS) == HOSTS
        hit0 = device_decode._RESIDENT["hit"].value
        miss0 = device_decode._RESIDENT["miss"].value
        before = await served.last(FIELDS, "1")
        assert device_decode._RESIDENT["miss"].value == miss0
        assert device_decode._RESIDENT["hit"].value - hit0 \
            == SEGMENTS * len(FIELDS)
        row = [served.host_of[int(t)] for t in
               before.column("tsid").to_pylist()].index(1)
        assert before.column("timestamp")[row].as_py() == ts_of(LAST + 5)
        assert [before.column(f)[row].as_py() for f in FIELDS] \
            == [None, 77.5, None, None]
        await served.compact()
        assert await served.both(FIELDS) == HOSTS
        assert device_decode._RESIDENT["miss"].value > miss0
        after = await served.last(FIELDS, "1")
        same_buffers(after, before)
    served.run(go())


# ---------------------------------------------------------------------------
# other tables: the WAL's memtable, the chunked layout, an unsorted slice
# ---------------------------------------------------------------------------


def small_writes(hosts: int, ticks: int, fields: list, seed: int) -> list:
    rng = np.random.default_rng(seed)
    return [(h, f, ts_of(t), np.float32(rng.random() * 100.0))
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


async def hosts_of(e: MetricEngine, tbl: pa.Table, hosts: int) -> dict:
    keys = await e.resolve_series(
        "cpu", [int(t) for t in tbl.column("tsid").to_pylist()],
        TimeRange.new(T0, T0 + 1))
    return {tsid: next(h for h in range(hosts)
                       if f"host_{h}".encode() in key)
            for tsid, key in keys.items()}


def rows_of(tbl: pa.Table, host_of: dict, fields: list) -> list:
    return sorted(
        (host_of[int(t)], ts, [None if v is None else np.float32(v)
                               for v in vals])
        for t, ts, *vals in zip(
            tbl.column("tsid").to_pylist(),
            tbl.column("timestamp").to_pylist(),
            *[tbl.column(f).to_pylist() for f in fields]))


def test_rows_still_in_the_memtable_are_answered(tmp_path, monkeypatch):
    """The WAL on and nothing flushed by a timer: the newest samples
    lie in memtables (one segment lives there alone, one has an SST
    under its memtable).  Those segments are answered through the row
    scan that overlays them (`memtable`, no fallback), the flushed one
    from its slices; /query_rows, which reads SST state, flushes
    first."""
    monkeypatch.setenv("HORAEDB_HOST_AGG", "0")
    monkeypatch.setenv("HORAEDB_DEVICE_DECODE", "1")
    fields = ["usage_user", "usage_system"]
    hosts = 3
    flushed = small_writes(hosts, SEG_TICKS + 20, fields, seed=7)
    later = [(h, f, ts_of(t), np.float32(v)) for h, f, t, v in [
        (0, "usage_user", SEG_TICKS + 19, 1.5),     # an overwrite
        (1, "usage_user", SEG_TICKS + 25, 2.5),     # newer, one field
        (2, "usage_system", 2 * SEG_TICKS + 3, 3.5)]]   # a new segment

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
            assert e.tables["data"].memtable_segments() \
                == {T0 + SEGMENT_MS, T0 + 2 * SEGMENT_MS}
            c0, f0 = segments_by_route(), counter(
                "scan_decode_fallback_total")
            tbl = await e.query_last("cpu", [], fields)
            routes = moved(c0, segments_by_route())
            assert counter("scan_decode_fallback_total") == f0
            assert e.tables["data"].memtable_segments() \
                == {T0 + SEGMENT_MS, T0 + 2 * SEGMENT_MS}, "no flush"
            bounded = await e.query_last("cpu", [], fields,
                                         end=T0 + SEGMENT_MS)
            rows = await e.query_rows_where(
                "cpu", [], TimeRange.new(T0, T0 + 3 * SEGMENT_MS),
                "usage_user", "lt", 2.0, ["usage_user"])
            return (tbl, routes, bounded, rows,
                    await hosts_of(e, tbl, hosts))
        finally:
            await e.close()

    tbl, routes, bounded, rows, host_of = asyncio.run(go())
    writes = flushed + later
    assert rows_of(tbl, host_of, fields) == last_rows(writes, fields)
    # host 2's row is in the memtable-only segment, the others' in the
    # one with an SST under its memtable: two segments, both overlaid
    assert routes == {("host", "memtable"): 2}
    assert rows_of(bounded, host_of, fields) \
        == last_rows(writes, fields, end=T0 + SEGMENT_MS)
    got = {(host_of[int(t)], ts) for t, ts in zip(
        rows.column("tsid").to_pylist(),
        rows.column("timestamp").to_pylist())}
    assert (0, ts_of(SEG_TICKS + 19)) in got


def test_a_chunked_table_answers_the_references_rows():
    """The chunked layout has no decode slices: each field is scanned
    by query() and reduced on the host; an overwrite of the newest
    sample, a field that misses it and a bound decide as in the row
    layout."""
    fields = ["usage_user", "usage_system"]
    hosts, ticks = 3, 40
    writes = small_writes(hosts, ticks, fields, seed=43)
    writes = [w for w in writes
              if (w[0], w[1], w[2]) != (1, "usage_system", ts_of(ticks - 1))]
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
            tbl = await e.query_last("cpu", [], fields)
            cut = await e.query_last("cpu", [], fields, end=ts_of(ticks))
            one = await e.query_last("cpu", [("hostname", "host_1")],
                                     fields, start=ts_of(5))
            return tbl, cut, one, await hosts_of(e, tbl, hosts)
        finally:
            await e.close()

    tbl, cut, one, host_of = asyncio.run(go())
    want = last_rows(writes, fields)
    assert rows_of(tbl, host_of, fields) == want
    assert want[0][2][0] == np.float32(1.5)
    assert want[1][2][0] is not None and want[1][2][1] is None
    assert want[2][1:] == (ts_of(ticks + 45), [np.float32(99.5), None])
    assert rows_of(cut, host_of, fields) \
        == last_rows(writes, fields, end=ts_of(ticks))
    assert rows_of(one, host_of, fields) \
        == last_rows(writes, fields, start=ts_of(5), series={1})


def test_a_slice_that_is_not_sorted_takes_the_host_route(monkeypatch):
    """A field of few rows beside one of many in one segment: the
    many-rowed field's slice is not narrowed (no smaller capacity), so
    it decodes with the other field's rows between a series' own and
    (series, timestamp) falls.  The device route declines the segment
    (`unsorted`, counted as a fallback), the row scan answers it, and
    the answer is the reference's on both."""
    monkeypatch.setenv("HORAEDB_HOST_AGG", "0")
    fields = ["usage_user", "usage_extra"]
    hosts = 3
    writes = small_writes(hosts, 60, fields[:1], seed=5) \
        + small_writes(hosts, 4, fields[1:], seed=6)

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
                tbl = await e.query_last("cpu", [], fields)
                out[route] = (tbl, moved(c0, segments_by_route()),
                              counter("scan_decode_fallback_total",
                                      reason="unsorted") - f0)
            return out, await hosts_of(e, out["1"][0], hosts)
        finally:
            await e.close()

    out, host_of = asyncio.run(go())
    want = last_rows(writes, fields)
    for route in "10":
        assert rows_of(out[route][0], host_of, fields) == want
    same_buffers(out["1"][0], out["0"][0])
    assert out["1"][1:] == ({("host", "unsorted"): 1}, 1)
    assert out["0"][1:] == ({("host", "mode_host"): 1}, 0)


# ---------------------------------------------------------------------------
# the counters, the spans, the pool
# ---------------------------------------------------------------------------


def test_the_counters_follow_the_walk(served):
    def read() -> dict:
        return {"read": counter("scan_last_rows_total", side="read",
                                route="device"),
                "answered": counter("scan_last_rows_total",
                                    side="answered", route="device"),
                "calls": counter("scan_last_calls_total"),
                "requests": counter("query_last_total"),
                "wall": counter("query_last_seconds_total"),
                "cells": counter("respond_cells_total"),
                "loop": counter("respond_encode_total", where="loop")}

    async def go():
        await served.last(FIELDS, "1")
        before, c0 = read(), segments_by_route()
        tbl = await served.last(FIELDS, "1")
        return tbl, moved(before, read()), moved(c0, segments_by_route())
    tbl, d, segs = served.run(go())
    assert segs == {("device", ""): SEGMENTS}
    stored = len({(w[0], w[1], w[2]) for w in served.writes})
    assert d["read"] == stored          # every row of every slice asked
    nulls = sum(tbl.column(f).null_count for f in FIELDS)
    assert d["answered"] == tbl.num_rows * len(FIELDS) - nulls
    assert d["calls"] == SEGMENTS       # the fields of a segment: one
    assert d["requests"] == 1 and d["wall"] > 0
    assert d["cells"] == tbl.num_rows * tbl.num_columns
    assert d["loop"] == 1


def test_the_request_is_traced_as_a_query_with_its_four_steps(served):
    async def go():
        r = await served.post({"metric": "cpu", "fields": FIELDS}, "1")
        assert r.status == 200
        trace_id = r.headers[tracing.TRACE_HEADER]
        lst = await (await served.client.get(
            "/debug/traces?limit=8&kind=query")).json()
        mine = [t for t in lst["traces"] if t["trace_id"] == trace_id]
        assert mine and mine[0]["root"] == "/query_last"
        return (await (await served.client.get(
            f"/debug/traces/{trace_id}")).json())["tree"]
    tree = served.run(go())
    steps = [c for c in tree["children"] if c["name"] != "admission_wait"]
    assert [c["name"] for c in steps] \
        == ["parse", "resolve", "last", "respond"]
    assert "postings" in steps[1]["fields"]
    last = steps[2]
    inner = {c["name"] for c in last["children"]}
    assert {"scan.plan", "scan.windows", "scan.dispatch", "scan.d2h",
            "scan.combine", "last.segment"} <= inner
    segs = [c["fields"] for c in last["children"]
            if c["name"] == "last.segment"]
    assert [s["route"] for s in segs] == ["device"] * SEGMENTS
    # newest first, each asked for the series still missing
    assert [s["segment"] for s in segs] == [
        T0 + k * SEGMENT_MS for k in reversed(range(SEGMENTS))]
    assert [s["series_in"] for s in segs] == [HOSTS, 2, 1]
    assert [s["series_out"] for s in segs] == [HOSTS - 2, 1, 1]
    assert all(s["rows_read"] > 0 for s in segs)


def test_a_large_answer_is_written_on_the_pool(served, monkeypatch):
    async def go(where: str) -> float:
        c0 = counter("respond_encode_total", where=where)
        await served.last(FIELDS, "1")
        return counter("respond_encode_total", where=where) - c0
    assert served.run(go("loop")) == 1
    monkeypatch.setattr(server_main, "_RESPOND_POOL_MIN_CELLS", 10)
    assert served.run(go("pool")) == 1


def test_the_plan_names_its_segments_newest_first(served):
    from horaedb_tpu.ops import And, Eq
    from horaedb_tpu.ops.last import LastSpec
    from horaedb_tpu.storage.read import ScanRequest
    from horaedb_tpu.storage.types import Timestamp

    async def go():
        rng = TimeRange.new(int(Timestamp.MIN), int(Timestamp.MAX))
        qp = await served.data.plan_last(
            [ScanRequest(range=rng, predicate=And([Eq("metric_id", 1)]))],
            LastSpec("tsid", "timestamp", "value"), [3, 5])
        return qp.describe()
    text = served.run(go()).splitlines()
    assert text[0] == ("Last: group=tsid, ts=timestamp, value=value, "
                       "fields=1, series=2, newest first, stops when no "
                       "series is missing")
    assert [ln.split(":")[0] for ln in text[1:]] == [
        f"  Segment {T0 + k * SEGMENT_MS}"
        for k in reversed(range(SEGMENTS))]


# ---------------------------------------------------------------------------
# the 400s: before any scan
# ---------------------------------------------------------------------------

GOOD = {"metric": "cpu", "fields": ["usage_user", "usage_system"],
        "start": T0, "end": T0 + HOUR}


def _with(**changes) -> dict:
    body = json.loads(json.dumps(GOOD))
    for k, v in changes.items():
        if v is None:
            del body[k]
        else:
            body[k] = v
    return body


@pytest.mark.parametrize("body, says", [
    (_with(fields=["usage_user", "nope"]), "unknown field"),
    (_with(fields=[]), "fields"),
    (_with(fields=["usage_user", "usage_user"]), "fields"),
    (_with(fields="usage_user"), "fields"),
    (_with(fields=None), "fields"),
    (_with(fields=["usage_user", "timestamp"]), "tsid or timestamp"),
    (_with(start=T0 + HOUR), "start must lie before end"),
    (_with(start=T0 + 2 * HOUR), "start must lie before end"),
    (_with(end="noon"), "invalid literal"),
    (_with(metric=None), "metric"),
    (_with(compression="snappy"), "compression"),
], ids=["unknown_field", "fields_empty", "fields_twice",
        "fields_not_a_list", "no_fields", "a_reserved_name",
        "start_is_end", "start_after_end", "end_not_a_number",
        "no_metric", "unknown_compression"])
@pytest.mark.parametrize("route", ["1", "0"], ids=["device", "host"])
def test_a_bad_request_is_a_400_before_any_scan(served, body, says, route):
    async def go():
        scans0 = counter("query_last_seconds_total")
        r = await served.post(body, route)
        assert r.status == 400, await r.text()
        assert says in (await r.json())["error"]
        assert counter("query_last_seconds_total") == scans0
    served.run(go())
