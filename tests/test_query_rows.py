"""POST /query_rows (ISSUE 41): rows under a value predicate, TSBS
`high-cpu-*`'s shape at test size, served, on BOTH routes, against a
plain reference that imports nothing of the program
(tests/rows_reference.py: the acknowledged writes in order, last write
wins, then the predicate).

One server for the module.  `[scan.decode] mode` is read per query
(HORAEDB_DEVICE_DECODE), so the same store answers a request on the
device route ("1": select and join over the resident decode slices,
ops/select.py) and on the host route ("0": host decode, the value leaf
after the merge, a numpy join), and every case compares each with the
reference and the two with each other bit for bit.  A test that writes
appends to the store's list of writes: the reference moves with it."""

import asyncio
import io
import json

import numpy as np
import pyarrow as pa
import pytest
from aiohttp.test_utils import TestClient, TestServer
from pyarrow import ipc

from horaedb_tpu.metric_engine import MetricEngine
from horaedb_tpu.common import deviceprof
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.ops import device_decode
from horaedb_tpu.ops import select as select_ops
from horaedb_tpu.server import main as server_main
from horaedb_tpu.server.config import ServerConfig
from horaedb_tpu.server.main import ServerState, build_app
from horaedb_tpu.storage.config import StorageConfig, from_dict
from horaedb_tpu.storage.types import TimeRange
from horaedb_tpu.utils import registry, tracing

from tests.rows_reference import rows_where

HOUR = 3_600_000
SEGMENT_MS = 2 * HOUR
T0 = 1_700_000_000_000 // SEGMENT_MS * SEGMENT_MS
TICK_MS = 60_000
HOSTS, SEGMENTS = 6, 3
TICKS = SEGMENTS * SEGMENT_MS // TICK_MS
FIELDS = ["usage_user", "usage_system", "usage_idle", "usage_nice"]
# TSBS's ten cpu fields (the benchmark's request asks them all)
TEN = FIELDS + ["usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
                "usage_guest", "usage_guest_nice"]
# usage_nice reports nothing for this host over these ticks: nulls
SILENT = (2, 100, 260)
# usage_idle was never written for the last segment at all
IDLE_TICKS = 2 * SEGMENT_MS // TICK_MS

# over both segment edges, off every tick; and one on ticks exactly
WINDOW = (T0 + HOUR + 7, T0 + 5 * HOUR + 7)
ON_TICKS = (T0 + 30 * TICK_MS, T0 + 200 * TICK_MS)
DAY = (T0, T0 + SEGMENTS * SEGMENT_MS)


def ts_of(tick: int) -> int:
    return T0 + tick * TICK_MS


def arrow_body(hosts, ticks, values) -> bytes:
    batch = pa.record_batch({
        "hostname": pa.array([f"host_{h}" for h in hosts]),
        "timestamp": pa.array(np.asarray([ts_of(t) for t in ticks],
                                         dtype=np.int64)),
        "value": pa.array(np.asarray(values, dtype=np.float64))})
    sink = io.BytesIO()
    with ipc.new_stream(sink, batch.schema) as w:
        w.write_batch(batch)
    return sink.getvalue()


class Served:
    def __init__(self, loop, fields=FIELDS, holes=True):
        self.loop = loop
        self.fields = list(fields)
        # whether usage_nice and usage_idle leave out what SILENT and
        # IDLE_TICKS say, or every field reports at every key
        self.holes = holes
        rng = np.random.default_rng(410041)
        self.values = (rng.random((len(self.fields), TICKS, HOSTS)) * 100.0
                       ).astype(np.float32)
        # (host, field, timestamp, value) in the order acknowledged
        self.writes: list = []

    def run(self, coro):
        return self.loop.run_until_complete(coro)

    async def open(self):
        cfg = from_dict(StorageConfig, {
            "scheduler": {"schedule_interval": "1h"}})
        self.engine = await MetricEngine.open(
            "m", MemoryObjectStore(), segment_ms=SEGMENT_MS, config=cfg)
        self.client = TestClient(TestServer(build_app(
            ServerState(self.engine, ServerConfig()))))
        await self.client.start_server()
        for f, field in enumerate(self.fields):
            tick, host = np.nonzero(self.reports(f))
            await self.write(field, host, tick,
                             self.values[f][tick, host])
        await self.compact()
        tbl = await self.rows(DAY, "usage_user", "lt", 1000.0,
                              ["usage_user"], route="0")
        keys = await self.engine.resolve_series(
            "cpu", [int(t) for t in set(tbl.column("tsid").to_pylist())],
            TimeRange.new(T0, T0 + 1))
        self.host_of = {
            tsid: next(h for h in range(HOSTS)
                       if f"host_{h}".encode() in key)
            for tsid, key in keys.items()}
        assert sorted(self.host_of.values()) == list(range(HOSTS))

    def reports(self, f: int) -> np.ndarray:
        out = np.ones((TICKS, HOSTS), dtype=bool)
        if self.holes and self.fields[f] == "usage_nice":
            out[SILENT[1]:SILENT[2], SILENT[0]] = False
        if self.holes and self.fields[f] == "usage_idle":
            out[IDLE_TICKS:] = False
        return out

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
            f"/write_arrow?metric=cpu&tags=hostname&field={field}",
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
            return await self.client.post("/query_rows", json=body)
        finally:
            mp.undo()

    async def rows(self, window, where_field, op, value, fields,
                   route: str, filters=None) -> pa.Table:
        body = {"metric": "cpu", "start": window[0], "end": window[1],
                "where": {"field": where_field, "op": op, "value": value},
                "fields": list(fields)}
        if filters is not None:
            body["filters"] = filters
        r = await self.post(body, route)
        assert r.status == 200, await r.text()
        assert r.content_type == "application/vnd.apache.arrow.stream"
        return ipc.open_stream(await r.read()).read_all()

    def check(self, tbl: pa.Table, window, where_field, op, value,
              fields, hosts=None):
        """`tbl` is the reference's answer: the same rows in the same
        order, every value bit for bit, nulls where it has none."""
        assert tbl.schema.names == ["tsid", "timestamp"] + list(fields)
        assert tbl.schema.field("tsid").type == pa.uint64()
        assert tbl.schema.field("timestamp").type == pa.int64()
        want = rows_where(self.writes, window[0], window[1], where_field,
                          op, value, fields, series=hosts)
        tsid = tbl.column("tsid").to_numpy()
        ts = tbl.column("timestamp").to_numpy()
        order = np.lexsort((ts, tsid))
        assert np.array_equal(order, np.arange(len(ts))), \
            "not sorted by (tsid, timestamp)"
        got = sorted(zip([self.host_of[int(t)] for t in tsid],
                         ts.tolist(), range(len(ts))))
        assert [(h, t) for h, t, _ in got] \
            == [(h, t) for h, t, _ in want], "the row sets differ"
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

    async def both(self, window, where_field, op, value, fields,
                   filters=None, hosts=None) -> int:
        """The request on both routes: each is the reference's answer,
        and the two are each other's, buffer for buffer."""
        dev = await self.rows(window, where_field, op, value, fields,
                              "1", filters)
        host = await self.rows(window, where_field, op, value, fields,
                               "0", filters)
        n = self.check(dev, window, where_field, op, value, fields, hosts)
        self.check(host, window, where_field, op, value, fields, hosts)
        assert dev.equals(host)
        for name in dev.schema.names:
            a = dev.column(name).combine_chunks()
            b = host.column(name).combine_chunks()
            assert a.null_count == b.null_count
            assert np.array_equal(
                np.asarray(a.fill_null(0)).view(np.uint8),
                np.asarray(b.fill_null(0)).view(np.uint8)), name
        return n


def _serve(**kwargs):
    mp = pytest.MonkeyPatch()
    mp.setenv("HORAEDB_HOST_AGG", "0")
    loop = asyncio.new_event_loop()
    s = Served(loop, **kwargs)
    try:
        s.run(s.open())
        yield s
        s.run(s.close())
    finally:
        loop.close()
        mp.undo()


@pytest.fixture(scope="module")
def served():
    yield from _serve()


@pytest.fixture(scope="module")
def ten():
    """A store of its own with all ten fields at every key: every
    field's slice is the predicate's row for row, and all nine joined
    share their statics."""
    yield from _serve(fields=TEN, holes=False)


def segments_by_route() -> dict:
    fam = registry.counter("scan_select_segments_total")
    return {labels: child.value
            for labels, child in (fam._children or {}).items()}


def moved(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def counter(name: str, **labels) -> float:
    c = registry.counter(name)
    return (c.labels(**labels) if labels else c).value


def program() -> dict:
    """The row-selecting program's ledger entry: calls that compiled
    and calls that did not."""
    for r in deviceprof.profiler.snapshot()["fns"]:
        if r["fn"] == "_select_rows_joined_jit":
            return {"compiles": r["compiles"], "calls": r["compiles"]
                    + r["dispatches"]}
    return {"compiles": 0, "calls": 0}


# ---------------------------------------------------------------------------
# answers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [WINDOW, ON_TICKS, DAY],
                         ids=["off_ticks", "on_ticks", "whole_day"])
def test_rows_over_90_are_the_references_on_both_routes(served, window):
    """TSBS high-cpu-all's shape: every reading of usage_user over
    90.0 with all the fields of its row, over several segments; a range
    whose start is a tick (in) and whose end is a tick (out)."""
    n = served.run(served.both(window, "usage_user", "gt", 90.0, FIELDS))
    assert n > 0


@pytest.mark.parametrize("op", ["gt", "ge", "lt", "le"])
def test_a_value_equal_to_the_threshold_under_each_op(served, op):
    """The threshold is a value the store holds: `ge` and `le` take
    its row, `gt` and `lt` do not (the reference decides; both routes
    agree)."""
    threshold = float(served.values[0][150, 3])
    async def go():
        n = await served.both(WINDOW, "usage_user", op, threshold,
                              ["usage_user", "usage_system"])
        tbl = await served.rows(WINDOW, "usage_user", op, threshold,
                                ["usage_user"], "1")
        at = [(served.host_of[int(t)], ts) for t, ts in zip(
            tbl.column("tsid").to_pylist(),
            tbl.column("timestamp").to_pylist())]
        assert ((3, ts_of(150)) in at) == (op in ("ge", "le"))
        return n
    assert served.run(go()) > 0


def test_threshold_is_compared_as_float32(served):
    """A threshold between a stored float32 and its float64 neighbour
    rounds to the stored value: `gt` then leaves that row out."""
    v = float(served.values[0][151, 1])
    above = float(np.nextafter(np.float64(v), np.inf))
    assert np.float32(above) == np.float32(v) and above > v
    served.run(served.both(WINDOW, "usage_user", "ge", above,
                           ["usage_user"]))


def test_a_field_without_a_sample_at_a_selected_key_is_null(served):
    """usage_nice is silent for one host over 160 ticks and usage_idle
    was never written for the last segment: nulls there, values
    everywhere else, no row dropped."""
    async def go():
        await served.both(DAY, "usage_user", "gt", 80.0, FIELDS)
        tbl = await served.rows(DAY, "usage_user", "gt", 80.0, FIELDS,
                                "1")
        assert 0 < tbl.column("usage_nice").null_count < tbl.num_rows
        assert 0 < tbl.column("usage_idle").null_count < tbl.num_rows
        assert tbl.column("usage_user").null_count == 0
    served.run(go())


def test_the_predicates_field_need_not_be_asked(served):
    served.run(served.both(WINDOW, "usage_system", "lt", 5.0,
                           ["usage_nice", "usage_user"]))


def test_an_empty_answer_has_the_columns_and_no_row(served):
    async def go():
        n = await served.both(WINDOW, "usage_user", "gt", 1000.0, FIELDS)
        assert n == 0
        # a window before any data: nothing to plan, same shape
        assert await served.both((T0 - 5 * HOUR, T0 - HOUR), "usage_user",
                                 "gt", 1.0, FIELDS) == 0
    served.run(go())


def test_a_label_filter_selects_one_hosts_rows(served):
    """TSBS high-cpu-1's shape."""
    n = served.run(served.both(
        WINDOW, "usage_user", "gt", 90.0, FIELDS,
        filters={"hostname": "host_4"}, hosts={4}))
    assert n > 0


def test_a_label_filter_that_matches_no_series(served):
    n = served.run(served.both(
        WINDOW, "usage_user", "gt", 90.0, FIELDS,
        filters={"hostname": "host_99"}, hosts=set()))
    assert n == 0


ONE_PROGRAM_CASES = {
    # window, predicate's field, op, threshold, fields asked, calls
    "all_fields": (WINDOW, "usage_user", "gt", 90.0, FIELDS, 2),
    "a_subset": (WINDOW, "usage_user", "gt", 90.0,
                 ["usage_system", "usage_user"], 1),
    "predicates_field_not_asked": (WINDOW, "usage_system", "lt", 10.0,
                                   ["usage_user", "usage_idle"], 2),
    # usage_idle has no slice in the last segment: every key a null
    "a_field_with_no_row_in_a_segment": (
        (T0 + 4 * HOUR, T0 + 6 * HOUR), "usage_user", "gt", 90.0,
        ["usage_idle", "usage_system"], 1),
    # usage_nice lacks a host's samples over 160 ticks: its slices are
    # not the predicate's row for row there, so every key is searched
    # for (the other fields' values are taken), and some are not found
    "a_field_that_is_searched_and_lacks_keys": (
        (ts_of(SILENT[1] - 10), ts_of(SILENT[2] + 10)), "usage_user",
        "gt", 50.0, ["usage_nice", "usage_system"], 1),
    "only_the_predicates_field": (DAY, "usage_user", "ge", 95.0,
                                  ["usage_user"], 1),
}


@pytest.mark.parametrize("case", list(ONE_PROGRAM_CASES))
def test_the_one_program_answers_as_the_host_route(served, case):
    """The select and every field's join run as ONE call a group of
    segments that share their program (the last segment, where
    usage_idle has no slice, is a group of its own when that field is
    asked), and the rows, the values and the nulls are the host
    route's byte for byte, which makes no device call."""
    window, field, op, value, fields, calls = ONE_PROGRAM_CASES[case]

    async def go():
        c0 = program()["calls"]
        dev = await served.rows(window, field, op, value, fields, "1")
        c1 = program()["calls"]
        await served.rows(window, field, op, value, fields, "0")
        assert program()["calls"] == c1
        assert await served.both(window, field, op, value, fields) > 0
        return dev, c1 - c0
    dev, ran = served.run(go())
    assert ran == calls
    if case == "a_field_that_is_searched_and_lacks_keys":
        assert 0 < dev.column("usage_nice").null_count < dev.num_rows
        assert dev.column("usage_system").null_count == 0
    if case == "a_field_with_no_row_in_a_segment":
        assert dev.column("usage_idle").null_count == dev.num_rows > 0


# ---------------------------------------------------------------------------
# writes: dedup before the predicate, a second SST, the slice's miss
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("direction", ["down", "up"])
def test_an_overwrite_across_the_threshold_decides_the_row(served,
                                                           direction):
    """The predicate is put to the CURRENT value: a reading over 90
    overwritten by one under it leaves the answer (the older write
    must not shine through), one under 90 overwritten by one over it
    joins it.  The new write is a second SST of its segment, and the
    segment's slices miss once (their key holds the SST ids)."""
    user = served.values[0]
    pick = np.argwhere((user > 90.0) if direction == "down"
                       else (user < 10.0))
    tick, host = next((int(t), int(h)) for t, h in pick
                      if 70 < t < 110 and (h, "usage_user", ts_of(int(t)))
                      not in {(w[0], w[1], w[2])
                              for w in served.writes[-4:]})
    new = 5.25 if direction == "down" else 95.75
    window = (T0 + HOUR, T0 + 2 * HOUR)

    async def go():
        before = await served.rows(window, "usage_user", "gt", 90.0,
                                   FIELDS, "1")
        had = (host, ts_of(tick)) in {
            (served.host_of[int(t)], ts) for t, ts in zip(
                before.column("tsid").to_pylist(),
                before.column("timestamp").to_pylist())}
        assert had == (direction == "down")
        miss0 = device_decode._RESIDENT["miss"].value
        await served.write("usage_user", [host], [tick], [new])
        await served.both(window, "usage_user", "gt", 90.0, FIELDS)
        assert device_decode._RESIDENT["miss"].value > miss0
        after = await served.rows(window, "usage_user", "gt", 90.0,
                                  FIELDS, "1")
        has = (host, ts_of(tick)) in {
            (served.host_of[int(t)], ts) for t, ts in zip(
                after.column("tsid").to_pylist(),
                after.column("timestamp").to_pylist())}
        assert has == (direction == "up")
    served.run(go())


def test_a_write_between_two_queries_shows_in_the_next_answer(served):
    """A new row (a tick no field had, so only its own field is found
    there) in a segment whose slices were resident: the next answer
    holds it, on both routes, and the one after finds every slice
    resident again."""
    # usage_idle has no row in this segment: nothing of it can be
    # resident, so its slice would miss every time
    fields = [f for f in FIELDS if f != "usage_idle"]

    async def go():
        window = (T0 + 4 * HOUR, T0 + 6 * HOUR)
        await served.both(window, "usage_user", "gt", 99.0, fields)
        new_ts_tick = TICKS - 1
        await served.write("usage_user", [0], [new_ts_tick], [99.5])
        await served.write("usage_system", [0], [new_ts_tick], [1.5])
        n = await served.both(window, "usage_user", "gt", 99.0, fields)
        assert n > 0
        hit0 = device_decode._RESIDENT["hit"].value
        miss0 = device_decode._RESIDENT["miss"].value
        tbl = await served.rows(window, "usage_user", "gt", 99.0, fields,
                                "1")
        assert device_decode._RESIDENT["miss"].value == miss0
        assert device_decode._RESIDENT["hit"].value - hit0 == len(fields)
        row = [i for i, (t, ts) in enumerate(zip(
            tbl.column("tsid").to_pylist(),
            tbl.column("timestamp").to_pylist()))
            if served.host_of[int(t)] == 0 and ts == ts_of(new_ts_tick)]
        assert len(row) == 1
        assert tbl.column("usage_user")[row[0]].as_py() == 99.5
        assert tbl.column("usage_system")[row[0]].as_py() == 1.5
    served.run(go())


def test_compaction_between_two_queries_leaves_the_answer(served):
    """The segments' SSTs become one again: other ids, so the slices
    miss once more, and the answer is the same."""
    async def go():
        before = await served.rows(DAY, "usage_user", "gt", 90.0, FIELDS,
                                   "1")
        await served.compact()
        await served.both(DAY, "usage_user", "gt", 90.0, FIELDS)
        after = await served.rows(DAY, "usage_user", "gt", 90.0, FIELDS,
                                  "1")
        assert after.equals(before)
    served.run(go())


def test_a_chunked_table_answers_the_references_rows():
    """The chunked layout has no decode slices: each field is scanned
    by query() and joined on the host; an overwrite across the
    threshold and a field missing at a key decide as in the row
    layout."""
    fields = ["usage_user", "usage_system"]
    rng = np.random.default_rng(41)
    ticks, hosts = 40, 3
    writes = [(h, f, ts_of(t), np.float32(v))
              for f in fields for t in range(ticks) for h in range(hosts)
              for v in [rng.random() * 100.0]]
    over = next(w for w in writes if w[1] == "usage_user" and w[3] > 90.0)
    under = next(w for w in writes if w[1] == "usage_user" and w[3] < 10.0)
    # usage_system never reports at the key that is overwritten upwards
    writes = [w for w in writes
              if (w[0], w[1], w[2]) != (under[0], "usage_system", under[2])]
    writes += [(over[0], "usage_user", over[2], np.float32(1.5)),
               (under[0], "usage_user", under[2], np.float32(99.5))]

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
            window = TimeRange.new(T0, ts_of(ticks))
            tbl = await e.query_rows_where("cpu", [], window, "usage_user",
                                           "gt", 90.0, fields)
            keys = await e.resolve_series(
                "cpu", [int(t) for t in set(tbl.column("tsid").to_pylist())],
                TimeRange.new(T0, T0 + 1))
            return tbl, {tsid: next(h for h in range(hosts)
                                    if f"host_{h}".encode() in key)
                         for tsid, key in keys.items()}
        finally:
            await e.close()

    tbl, host_of = asyncio.run(go())
    want = rows_where(writes, T0, ts_of(ticks), "usage_user", "gt", 90.0,
                      fields)
    got = sorted(zip([host_of[int(t)] for t in tbl.column("tsid").to_pylist()],
                     tbl.column("timestamp").to_pylist(),
                     tbl.column("usage_user").to_pylist(),
                     tbl.column("usage_system").to_pylist()))
    assert (over[0], over[2]) not in {(h, t) for h, t, _, _ in got}
    assert (under[0], under[2]) in {(h, t) for h, t, _, _ in got}
    assert [(h, t, None if a is None else np.float32(a),
             None if b is None else np.float32(b)) for h, t, a, b in got] \
        == [(h, t, v[0], v[1]) for h, t, v in want]
    assert any(v[1] is None for _, _, v in want)


# ---------------------------------------------------------------------------
# the routes, the ladder, the counters, the spans
# ---------------------------------------------------------------------------


def test_each_route_counts_its_segments(served):
    async def go():
        await served.rows(WINDOW, "usage_user", "gt", 90.0, FIELDS, "1")
        c0 = segments_by_route()
        await served.rows(WINDOW, "usage_user", "gt", 90.0, FIELDS, "1")
        c1 = segments_by_route()
        await served.rows(WINDOW, "usage_user", "gt", 90.0, FIELDS, "0")
        c2 = segments_by_route()
        return moved(c0, c1), moved(c1, c2)
    dev, host = served.run(go())
    assert {dict(k).get("route"): v for k, v in dev.items()} \
        == {"device": SEGMENTS}
    assert {(dict(k).get("route"), dict(k).get("reason")): v
            for k, v in host.items()} == {("host", "mode_host"): SEGMENTS}


def test_an_overflow_climbs_the_ladder_and_cuts_nothing(served):
    """720 rows a slice (capacity 1,024: rungs 128, 512, 1,024) and a
    predicate that takes a tenth of them, then a quarter, then nearly
    all: the tenth fits the first rung; the first query that selects
    more than its rung holds overflows and runs again one rung up (once
    a group of segments that share their programs), the answer is
    whole, and the rung is remembered."""
    assert select_ops.capacity_ladder(1024) == (128, 512, 1024)

    async def go():
        select_ops._RUNG.clear()
        o0 = counter("scan_select_overflow_total")
        await served.both(DAY, "usage_user", "gt", 90.0, FIELDS)
        assert counter("scan_select_overflow_total") == o0
        assert set(select_ops._RUNG.values()) <= {0}
        c0 = program()["calls"]
        await served.both(DAY, "usage_user", "gt", 75.0, FIELDS)
        o1 = counter("scan_select_overflow_total")
        groups = len(select_ops._RUNG)
        assert groups >= 1 and o1 - o0 == groups     # 128 -> 512
        assert set(select_ops._RUNG.values()) == {1}
        # ONE call a group and rung: the one that overflowed, and the
        # one a rung up
        assert program()["calls"] - c0 == 2 * groups
        c1 = program()["calls"]
        await served.both(DAY, "usage_user", "gt", 75.0, FIELDS)
        assert counter("scan_select_overflow_total") == o1
        assert program()["calls"] - c1 == groups
        await served.both(DAY, "usage_user", "lt", 95.0, FIELDS)
        assert counter("scan_select_overflow_total") == o1 + groups
        assert set(select_ops._RUNG.values()) == {2}
    served.run(go())


def test_a_ten_field_request_is_one_device_call(ten):
    """TSBS high-cpu-all's request: the predicate's field and nine
    more over three segments that share their program are ONE call of
    ONE program (one select, nine fields joined inside it, thirty
    slices batched), and the host route calls no program at all."""
    def read() -> dict:
        return {"calls": program()["calls"],
                "select": counter("scan_select_calls_total", kind="select"),
                "join": counter("scan_select_calls_total", kind="join"),
                "batched_calls": counter("scan_decode_batch_total"),
                "batched": counter("scan_decode_batch_slices_total",
                                   mode="batched"),
                "single": counter("scan_decode_batch_slices_total",
                                  mode="single")}

    async def go():
        assert await ten.both(DAY, "usage_user", "gt", 90.0, TEN) > 0
        c0 = read()
        await ten.rows(DAY, "usage_user", "gt", 90.0, TEN, "1")
        c1 = read()
        await ten.rows(DAY, "usage_user", "gt", 90.0, TEN, "0")
        return moved(c0, c1), moved(c1, read())
    dev, host = ten.run(go())
    assert dev == {"calls": 1, "select": 1, "join": 9, "batched_calls": 1,
                   "batched": SEGMENTS * len(TEN)}
    assert host == {}


def test_a_request_seen_before_compiles_nothing(ten):
    """A second request of a shape compiles nothing; one that asks
    fewer of the fields runs the program that exists (its stack filled,
    as a group of three segments fills four slots) and is the host
    route's answer all the same; a field in another order neither."""
    async def go():
        await ten.both(DAY, "usage_user", "gt", 90.0, TEN)
        p0 = program()
        await ten.both(DAY, "usage_user", "gt", 90.0, TEN)
        assert await ten.both(DAY, "usage_user", "gt", 90.0, TEN[:5]) > 0
        assert await ten.both(DAY, "usage_user", "gt", 90.0,
                              [TEN[7], TEN[2], TEN[9]]) > 0
        p1 = program()
        assert p1["compiles"] == p0["compiles"]
        assert p1["calls"] - p0["calls"] == 3
        # another predicate's field, with the same statics: the same
        await ten.both(DAY, "usage_system", "gt", 90.0, TEN[:3])
        assert program()["compiles"] == p1["compiles"]
        # another op is another program, and more fields than any
        # program of a shape joins one more
        await ten.both(DAY, "usage_user", "lt", 10.0, TEN[:3])
        assert program()["compiles"] - p1["compiles"] == 1
        await ten.both(DAY, "usage_user", "lt", 10.0, TEN[:4])
        assert program()["compiles"] - p1["compiles"] == 2
        await ten.both(DAY, "usage_user", "lt", 10.0, TEN[:2])
        assert program()["compiles"] - p1["compiles"] == 2
    ten.run(go())


def test_the_stack_budget_cuts_a_group_by_its_fields(ten, monkeypatch):
    """A call stacks (1 + joined fields) x slots slices inside the
    program: under a budget of twenty slices the ten-field request's
    three segments go out two and one, a two-field request's in one
    call, and both answers are the host route's."""
    ran = []
    run_group = select_ops._run_group

    def spy(group, *args):
        ran.append((len(group), group[0][0].seg.nbytes))
        return run_group(group, *args)

    monkeypatch.setattr(select_ops, "_run_group", spy)

    async def go():
        await ten.rows(DAY, "usage_user", "gt", 90.0, TEN, "1")
        assert [n for n, _ in ran] == [SEGMENTS]
        monkeypatch.setattr(device_decode, "_BATCH_MAX_STACK_BYTES",
                            20 * ran[0][1])
        del ran[:]
        assert await ten.both(DAY, "usage_user", "gt", 90.0, TEN) > 0
        assert [n for n, _ in ran] == [2, 1]
        del ran[:]
        assert await ten.both(DAY, "usage_user", "gt", 90.0,
                              ["usage_user", "usage_irq"]) > 0
        assert [n for n, _ in ran] == [SEGMENTS]
        # under one segment's worth a call still takes one segment
        monkeypatch.setattr(device_decode, "_BATCH_MAX_STACK_BYTES",
                            ran[0][1])
        del ran[:]
        assert await ten.both(DAY, "usage_user", "gt", 90.0, TEN) > 0
        assert [n for n, _ in ran] == [1] * SEGMENTS
    ten.run(go())


def test_the_rows_counters_follow_the_answer(served):
    def read() -> dict:
        return {"scanned": counter("scan_select_rows_total",
                                   side="scanned", route="device"),
                "selected": counter("scan_select_rows_total",
                                    side="selected", route="device"),
                "found": counter("scan_select_cells_total", kind="found"),
                "null": counter("scan_select_cells_total", kind="null"),
                "requests": counter("query_rows_total"),
                "wall": counter("query_rows_select_seconds_total"),
                "cpu": counter("scan_select_cpu_seconds_total")}

    async def go():
        await served.rows(WINDOW, "usage_user", "gt", 90.0, FIELDS, "1")
        before = read()
        tbl = await served.rows(WINDOW, "usage_user", "gt", 90.0, FIELDS,
                                "1")
        return tbl, moved(before, read())
    tbl, d = served.run(go())
    in_range = len(rows_where(served.writes, *WINDOW, "usage_user", "lt",
                              1000.0, ["usage_user"]))
    nulls = sum(tbl.column(f).null_count for f in FIELDS)
    assert d["scanned"] == in_range
    assert d["selected"] == tbl.num_rows
    assert d.get("null", 0) == nulls
    assert d["found"] == tbl.num_rows * len(FIELDS) - nulls
    assert d["requests"] == 1 and d["wall"] > 0 and d.get("cpu", 0) >= 0


def test_the_request_is_traced_as_a_query_with_its_four_steps(served):
    async def go():
        r = await served.post({
            "metric": "cpu", "start": WINDOW[0], "end": WINDOW[1],
            "where": {"field": "usage_user", "op": "gt", "value": 90.0},
            "fields": FIELDS}, "1")
        assert r.status == 200
        trace_id = r.headers[tracing.TRACE_HEADER]
        lst = await (await served.client.get(
            "/debug/traces?limit=8&kind=query")).json()
        mine = [t for t in lst["traces"] if t["trace_id"] == trace_id]
        assert mine and mine[0]["root"] == "/query_rows"
        tree = (await (await served.client.get(
            f"/debug/traces/{trace_id}")).json())["tree"]
        return tree
    tree = served.run(go())
    steps = [c for c in tree["children"] if c["name"] != "admission_wait"]
    assert [c["name"] for c in steps] \
        == ["parse", "resolve", "select", "respond"]
    select = steps[2]
    inner = {c["name"] for c in select["children"]}
    assert {"scan.plan", "scan.windows", "scan.dispatch", "scan.d2h",
            "scan.combine", "select.segment"} <= inner
    segs = [c for c in select["children"] if c["name"] == "select.segment"]
    assert len(segs) == SEGMENTS
    assert all(c["fields"]["route"] == "device" for c in segs)
    assert sum(c["fields"]["rows_out"] for c in segs) > 0
    assert all(c["fields"]["rows_in"] >= c["fields"]["rows_out"]
               for c in segs)


def test_a_large_answer_is_written_on_the_pool(served, monkeypatch):
    """From _RESPOND_POOL_MIN_CELLS values (rows x columns) up the
    serializer runs as one job on the `sst` pool, as a downsample
    answer's encoder does; under it on the loop's thread."""
    async def go(where: str) -> float:
        c0 = counter("respond_encode_total", where=where)
        cells0 = counter("respond_cells_total")
        tbl = await served.rows(WINDOW, "usage_user", "lt", 1000.0,
                                FIELDS, "1")
        assert counter("respond_cells_total") - cells0 \
            == tbl.num_rows * tbl.num_columns
        return counter("respond_encode_total", where=where) - c0
    assert served.run(go("loop")) == 1
    monkeypatch.setattr(server_main, "_RESPOND_POOL_MIN_CELLS", 1000)
    assert served.run(go("pool")) == 1


# ---------------------------------------------------------------------------
# the 400s: before any scan
# ---------------------------------------------------------------------------

GOOD = {"metric": "cpu", "start": WINDOW[0], "end": WINDOW[1],
        "where": {"field": "usage_user", "op": "gt", "value": 90.0},
        "fields": ["usage_user", "usage_system"]}


def _with(**changes) -> dict:
    body = json.loads(json.dumps(GOOD))
    for k, v in changes.items():
        if k.startswith("where_"):
            body["where"][k[6:]] = v
        elif v is None:
            del body[k]
        else:
            body[k] = v
    return body


@pytest.mark.parametrize("body, says", [
    (_with(where_field="usage_nope"), "unknown field"),
    (_with(fields=["usage_user", "nope"]), "unknown field"),
    (_with(where_op="eq"), "where.op"),
    (_with(where_value="ninety"), "where.value"),
    (_with(where_value=True), "where.value"),
    (_with(fields=[]), "fields"),
    (_with(fields=["usage_user", "usage_user"]), "fields"),
    (_with(fields="usage_user"), "fields"),
    (_with(where=None), "where"),
    (_with(start=None), "start"),
    (_with(compression="snappy"), "compression"),
], ids=["unknown_where_field", "unknown_field", "unknown_op",
        "value_not_a_number", "value_a_bool", "fields_empty",
        "fields_twice", "fields_not_a_list", "no_where", "no_start",
        "unknown_compression"])
@pytest.mark.parametrize("route", ["1", "0"], ids=["device", "host"])
def test_a_bad_request_is_a_400_before_any_scan(served, body, says,
                                                route):
    async def go():
        scans0 = counter("query_rows_select_seconds_total")
        r = await served.post(body, route)
        assert r.status == 400, await r.text()
        assert says in (await r.json())["error"]
        assert counter("query_rows_select_seconds_total") == scans0
    served.run(go())
