"""A label filter's posting lists (metric_engine/engine.py::IndexManager):
kept per segment of the index table under that segment's SST set, and
held to ONE contract: `find_tsids` answers what the filtered scan of the
index table answers, at once, whatever was written, flushed, compacted
or replayed in between.  The reference below is that scan, as the
engine made it for every filter of every query before the lists."""

import asyncio
import random

import pytest
from aiohttp.test_utils import TestClient, TestServer

from horaedb_tpu.common import ReadableDuration
from horaedb_tpu.common.memledger import ledger
from horaedb_tpu.metric_engine import Label, MetricEngine, Sample
from horaedb_tpu.metric_engine import engine as engine_mod
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.ops import And, Eq
from horaedb_tpu.server.config import ServerConfig
from horaedb_tpu.server.main import ServerState, build_app
from horaedb_tpu.storage.config import StorageConfig, from_dict
from horaedb_tpu.storage.read import ScanRequest
from horaedb_tpu.storage.types import TimeRange
from horaedb_tpu.utils import registry, tracing
from horaedb_tpu.wal import WalConfig

HOUR = 3_600_000
SEGMENT_MS = 2 * HOUR
T0 = 1_700_000_000_000 // SEGMENT_MS * SEGMENT_MS
SEGMENTS = 3
HOSTS = 24
RANGES = {
    "inside_one": (T0 + HOUR // 2, T0 + HOUR),
    "cuts_two": (T0 + HOUR, T0 + 3 * HOUR),
    "all_three": (T0, T0 + SEGMENTS * SEGMENT_MS),
    "last_alone": (T0 + 2 * SEGMENT_MS + 5, T0 + 2 * SEGMENT_MS + 9),
}


def run(coro):
    return asyncio.run(coro)


def labels_of(host: int) -> list[tuple[str, str]]:
    return [("hostname", f"host_{host}"), ("region", f"r{host % 5}"),
            ("os", f"os{host % 3}")]


def sample(host: int, ts: int, value: float = 1.0) -> Sample:
    return Sample(name="cpu", labels=[Label(k, v) for k, v in labels_of(host)],
                  timestamp=ts, value=value)


def in_segment(host: int, seg: int) -> bool:
    """Which hosts a segment has seen: every segment another set, so
    that a range's answer is a union no single segment holds."""
    return (host + seg) % 4 != 0


def storage_config() -> StorageConfig:
    return from_dict(StorageConfig,
                     {"scheduler": {"schedule_interval": "1h"}})


def wal_config(wal_dir) -> WalConfig:
    return WalConfig(enabled=True, dir=str(wal_dir), flush_rows=10 ** 6,
                     flush_bytes=1 << 30,
                     flush_age=ReadableDuration.parse("1h"),
                     flush_interval=ReadableDuration.parse("1h"),
                     max_group_wait=ReadableDuration.from_millis(0))


async def open_engine(store=None, wal_dir=None) -> MetricEngine:
    return await MetricEngine.open(
        "db", store or MemoryObjectStore(), segment_ms=SEGMENT_MS,
        config=storage_config(),
        wal_config=None if wal_dir is None else wal_config(wal_dir))


async def load(engine: MetricEngine) -> None:
    for seg in range(SEGMENTS):
        await engine.write([sample(h, T0 + seg * SEGMENT_MS + h)
                            for h in range(HOSTS) if in_segment(h, seg)])


async def scan_answer(engine, filters, rng: TimeRange):
    """The reference: one filtered scan of the index table a filter,
    intersected (the body of find_tsids before the posting lists)."""
    mid = await engine.metric_manager.resolve("cpu", rng)
    result = None
    for key, value in filters:
        pred = And([Eq("metric_id", mid), Eq("tag_key", key),
                    Eq("tag_value", value)])
        tsids = set()
        async for b in engine.index_manager.index.scan(
                ScanRequest(range=rng, predicate=pred)):
            tsids.update(b.column(b.schema.names.index("tsid")).to_pylist())
        result = tsids if result is None else result & tsids
    return mid, result


async def both(engine, filters, rng: TimeRange):
    mid, want = await scan_answer(engine, filters, rng)
    got = await engine.index_manager.find_tsids(mid, filters, rng)
    return got, want


def outcomes() -> dict:
    return {o: c.value for o, c in engine_mod._POSTINGS.items()}


def moved(before: dict) -> dict:
    return {o: n - before[o] for o, n in outcomes().items()}


def filed(engine, seg: int):
    """The SST ids a segment's lists are filed under, or None."""
    entry = engine.index_manager._postings.peek_entry(seg)
    return None if entry is None else entry[0]


async def version_ids(engine, seg: int):
    rng = TimeRange.new(seg, seg + SEGMENT_MS)
    v = (await engine.index_manager.index.segment_versions(rng)).get(seg)
    return None if v is None else v.ids


async def host_tsid(engine, host: int, rng: TimeRange) -> int:
    _, tsids = await scan_answer(engine, [labels_of(host)[0]], rng)
    assert len(tsids) == 1
    return next(iter(tsids))


class Loaded:
    """One loaded engine on a loop of its own, for the cases that only
    read (what one case leaves in the LRU is the next one's luck: the
    answer may not depend on it)."""

    def __init__(self):
        self.loop = asyncio.new_event_loop()
        self.engine = self.run(open_engine())
        self.run(load(self.engine))

    def run(self, coro):
        return self.loop.run_until_complete(coro)

    def close(self):
        self.run(self.engine.close())
        self.loop.close()


@pytest.fixture(scope="module")
def loaded():
    store = Loaded()
    yield store
    store.close()


# --- (a) the lists answer what the scan answers ---------------------------

@pytest.mark.parametrize("rng_name", sorted(RANGES))
@pytest.mark.parametrize("n_filters", [1, 2, 3])
@pytest.mark.parametrize("seed", range(4))
def test_equals_the_scan_over_random_label_sets(loaded, seed, n_filters,
                                                rng_name):
    rnd = random.Random(f"{seed}/{n_filters}/{rng_name}")
    rng = TimeRange.new(*RANGES[rng_name])
    # a host's own labels (an answer with that host in it, where the
    # range's segments saw it), then labels drawn apart (often no host
    # has all of them)
    own = rnd.sample(labels_of(rnd.randrange(HOSTS)), n_filters)
    apart = [rnd.choice(labels_of(rnd.randrange(HOSTS)))
             for _ in range(n_filters)]
    for filters in (own, apart):
        got, want = loaded.run(both(loaded.engine, filters, rng))
        assert got == want and isinstance(got, set)
    if n_filters == 1 and rng_name == "all_three":
        assert len(got) >= 1


@pytest.mark.parametrize("filters, empty", [
    ([("hostname", "host_nobody")], True),
    ([("no_such_tag", "r1")], True),
    ([("region", "r1"), ("hostname", "host_nobody")], True),
    ([("hostname", "host_1"), ("region", "r2")], True),   # each has hosts
    ([("region", "r1"), ("os", "os1")], False),
    ([], None),
])
def test_unknown_values_empty_intersections_and_no_filter(loaded, filters,
                                                          empty):
    rng = TimeRange.new(*RANGES["all_three"])
    before = outcomes()
    got, want = loaded.run(both(loaded.engine, filters, rng))
    if empty is None:
        # no filter = all series: None, before the lists are looked at
        assert got is None and not any(moved(before).values())
        return
    assert got == want
    assert (got == set()) is empty
    assert sum(moved(before).values()) == SEGMENTS


# --- (b) an acknowledged registration is in the very next answer ----------

@pytest.mark.parametrize("path", ["direct", "wal"])
def test_a_series_written_into_a_queried_segment_is_found_at_once(
        tmp_path, path):
    async def go():
        engine = await open_engine(
            wal_dir=tmp_path if path == "wal" else None)
        try:
            await load(engine)
            if path == "wal":
                await engine.flush()
            rng = TimeRange.new(T0, T0 + HOUR)
            region = [("region", "r4")]
            got, want = await both(engine, region, rng)
            assert got == want and filed(engine, T0) is not None
            old_ids = filed(engine, T0)
            # host 104 is new to every segment and of region r4
            assert labels_of(104)[1] == region[0]
            await engine.write([sample(104, T0 + 77)])
            new = await host_tsid(engine, 104, rng)
            assert new not in got
            before = outcomes()
            got2, want2 = await both(engine, region, rng)
            assert got2 == want2 == got | {new}
            if path == "direct":
                # the segment's SST set has grown: the old key misses
                assert moved(before) == {"hit": 0, "build": 1, "bypass": 0}
                assert filed(engine, T0) != old_ids
                return
            # the registration is in a memtable: no SST set names the
            # segment's content, so the scan answers and the lists of
            # the SSTs beneath stay as they were
            assert moved(before) == {"hit": 0, "build": 0, "bypass": 1}
            assert filed(engine, T0) == old_ids
            await engine.flush()
            before = outcomes()
            got3, want3 = await both(engine, region, rng)
            assert got3 == want3 == got2
            assert moved(before) == {"hit": 0, "build": 1, "bypass": 0}
            assert filed(engine, T0) == await version_ids(engine, T0) \
                != old_ids
            before = outcomes()
            assert (await both(engine, region, rng))[0] == got2
            assert moved(before) == {"hit": 1, "build": 0, "bypass": 0}
        finally:
            await engine.close()

    run(go())


# --- (c) compaction: same answer, new key, old entry gone -----------------

def test_the_index_table_compacted_between_two_queries():
    async def go():
        engine = await open_engine()
        try:
            # five registration batches = five index SSTs in one segment
            for batch in range(5):
                await engine.write([sample(batch * 4 + h, T0 + batch)
                                    for h in range(4)])
            rng = TimeRange.new(T0, T0 + HOUR)
            filters = [("os", "os1")]
            got, want = await both(engine, filters, rng)
            old_ids = filed(engine, T0)
            assert got == want and len(got) > 1 and len(old_ids) == 5
            index = engine.index_manager.index
            while (task := await
                   index.compact_scheduler.picker.pick_candidate()) \
                    is not None:
                await index.compact_scheduler.executor.execute(task)
            new_ids = await version_ids(engine, T0)
            assert len(new_ids) == 1 and not set(new_ids) & set(old_ids)
            before = outcomes()
            got2, want2 = await both(engine, filters, rng)
            assert got2 == want2 == got
            assert moved(before) == {"hit": 0, "build": 1, "bypass": 0}
            # one entry a segment: the older version went when the
            # newer was filed
            assert filed(engine, T0) == new_ids
            assert len(engine.index_manager._postings) == 1
        finally:
            await engine.close()

    run(go())


# --- (d) reopen with WAL replay -------------------------------------------

def test_reopen_with_wal_replay(tmp_path):
    async def go():
        store = MemoryObjectStore()
        engine = await open_engine(store, tmp_path)
        await load(engine)
        await engine.flush()
        rng = TimeRange.new(T0, T0 + HOUR)
        region = [("region", "r4")]
        flushed, _ = await both(engine, region, rng)
        await engine.write([sample(104, T0 + 77)])  # acked, WAL only
        # kill -9: no final flush
        for t in engine.tables.values():
            await t.abort()
        engine.index_manager.close()
        engine._runtimes.close()
        engine = await open_engine(store, tmp_path)
        try:
            before = outcomes()
            got, want = await both(engine, region, rng)
            new = await host_tsid(engine, 104, rng)
            assert got == want == flushed | {new}
            assert moved(before) == {"hit": 0, "build": 0, "bypass": 1}
            await engine.flush()
            assert (await both(engine, region, rng))[0] == got
            before = outcomes()
            assert (await both(engine, region, rng))[0] == got
            assert moved(before) == {"hit": 1, "build": 0, "bypass": 0}
        finally:
            await engine.close()

    run(go())


# --- (e) a write between the version read and the build -------------------

def test_a_write_during_the_build_is_not_filed_under_the_old_key():
    async def go():
        engine = await open_engine()
        try:
            await load(engine)
            im = engine.index_manager
            rng = TimeRange.new(T0, T0 + HOUR)
            region = [("region", "r4")]
            old_ids = await version_ids(engine, T0)
            real_scan = im.index.scan
            raced = []

            def scan_after_a_write(req, **kw):
                async def gen():
                    if not raced:
                        raced.append(True)
                        await engine.write([sample(104, T0 + 77)])
                    async for b in real_scan(req, **kw):
                        yield b
                return gen()

            im.index.scan = scan_after_a_write
            try:
                mid = await engine.metric_manager.resolve("cpu", rng)
                got = await im.find_tsids(mid, region, rng)
            finally:
                del im.index.scan
            assert raced
            new = await host_tsid(engine, 104, rng)
            # the build's own scan came after the write and saw it
            assert new in got
            # and what it read was filed under no key: not the old one,
            # which it is not the content of, nor the new one, which
            # nobody had read
            assert filed(engine, T0) is None
            assert await version_ids(engine, T0) != old_ids
            before = outcomes()
            got2, want2 = await both(engine, region, rng)
            assert got2 == want2 == got
            assert moved(before) == {"hit": 0, "build": 1, "bypass": 0}
            assert filed(engine, T0) == await version_ids(engine, T0)
        finally:
            await engine.close()

    run(go())


def test_queries_beside_a_registering_writer_never_miss_an_acked_series():
    """Eight tasks resolve one label while a ninth registers new hosts
    under it, on one loop: every answer holds every series whose write
    was acknowledged before the query began (builds race the writes;
    none may be filed under a key it is not the content of)."""
    async def go():
        engine = await open_engine()
        try:
            await load(engine)
            rng = TimeRange.new(T0, T0 + HOUR)
            region = [("region", "r0")]
            mid = await engine.metric_manager.resolve("cpu", rng)
            acked: list[int] = []
            missed = []

            async def writer():
                for n in range(12):
                    host = 100 + 5 * n       # region r0, new to the segment
                    await engine.write([sample(host, T0 + 200 + n)])
                    acked.append(await host_tsid(engine, host, rng))

            async def reader():
                while len(acked) < 12:
                    owed = set(acked)
                    got = await engine.index_manager.find_tsids(
                        mid, region, rng)
                    missed.extend(owed - got)
                    # a hit awaits nothing that blocks: without a real
                    # pause eight readers keep the GIL from the
                    # writer's pool jobs
                    await asyncio.sleep(0.001)

            await asyncio.wait_for(
                asyncio.gather(writer(), *(reader() for _ in range(8))), 60)
            assert not missed
            got, want = await both(engine, region, rng)
            assert got == want and set(acked) <= got
            assert filed(engine, T0) == await version_ids(engine, T0)
        finally:
            await engine.close()

    run(go())


# --- (f) bounded and accounted --------------------------------------------

def test_a_segment_over_the_row_cap_is_scanned(monkeypatch):
    async def go():
        engine = await open_engine()
        try:
            await load(engine)
            # one more host in the two later segments
            await engine.write([sample(104, T0 + seg * SEGMENT_MS)
                                for seg in (1, 2)])
            rng = TimeRange.new(*RANGES["all_three"])
            rows = [v.rows for v in (await engine.index_manager.index
                                     .segment_versions(rng)).values()]
            assert len(rows) == SEGMENTS and min(rows) != max(rows)
            # the cap between the smallest segment and the others
            monkeypatch.setattr(engine_mod, "_POSTINGS_MAX_ROWS", min(rows))
            before = outcomes()
            got, want = await both(engine, [("os", "os2")], rng)
            assert got == want and got
            small = rows.count(min(rows))
            assert moved(before) == {"hit": 0, "build": small,
                                     "bypass": SEGMENTS - small}
            assert len(engine.index_manager._postings) == small
        finally:
            await engine.close()

    run(go())


def test_lru_under_the_cap_and_the_ledger_account():
    async def go():
        engine = await open_engine()
        im = engine.index_manager
        account = im._postings_account
        gauge = registry.gauge("index_postings_bytes")
        gauge0 = gauge.value
        try:
            await load(engine)
            assert account.kind == "index_postings" and account.bytes() == 0
            assert ledger.get(account.name) is account
            rng = TimeRange.new(*RANGES["all_three"])
            filters = [("os", "os2")]
            got, want = await both(engine, filters, rng)
            assert got == want and len(im._postings) == SEGMENTS
            held = im._postings.total_bytes
            assert account.bytes() == held == gauge.value - gauge0 > 0
            # what GET /debug/memory serves
            shown = [i for i in ledger.snapshot()["accounts"][
                "index_postings"]["instances"] if i["name"] == account.name]
            assert shown and shown[0]["bytes"] == held
            assert shown[0]["budget"] == engine_mod._POSTINGS_MAX_BYTES
            # room for two of the three: the least recently used goes
            im._postings.max_bytes = held - 1
            first = TimeRange.new(T0, T0 + HOUR)
            await engine.write([sample(104, T0 + 77)])  # a new version
            got, want = await both(engine, filters, first)
            assert got == want
            assert len(im._postings) == SEGMENTS - 1
            assert filed(engine, T0) is not None
            assert filed(engine, T0 + SEGMENT_MS) is None
            assert account.bytes() == im._postings.total_bytes <= held - 1
            # and the answer over all three does not depend on it
            got, want = await both(engine, filters, rng)
            assert got == want
        finally:
            await engine.close()
        assert account.bytes() == 0 and gauge.value == gauge0
        assert ledger.get(account.name) is None

    run(go())


# --- (g), (h) over HTTP ---------------------------------------------------

QUERY = {"metric": "cpu", "start": T0, "end": T0 + HOUR,
         "bucket_ms": 600_000, "filters": {"hostname": "host_5"}}


async def _served(fn):
    engine = await open_engine()
    client = TestClient(TestServer(build_app(
        ServerState(engine, ServerConfig()))))
    await client.start_server()
    try:
        await load(engine)
        return await fn(client, engine)
    finally:
        await client.close()
        await engine.close()


async def _resolve_span(client, path: str, body: dict) -> dict:
    r = await client.post(path, json=body)
    assert r.status == 200
    tree = (await (await client.get(
        f"/debug/traces/{r.headers[tracing.TRACE_HEADER]}")).json())["tree"]
    return next(c for c in tree["children"] if c["name"] == "resolve")


def test_the_counter_and_the_resolve_span_say_how(monkeypatch):
    async def go(client, engine):
        before = outcomes()
        built = await _resolve_span(client, "/query", QUERY)
        hit = await _resolve_span(client, "/query", QUERY)
        # the build scans the index table under the span (after the
        # metric's own first resolve, of the metrics table), a hit
        # nothing
        assert [c["fields"]["table"] for c in built["children"]
                if c["name"] == "scan.plan"] == ["metrics", "index"]
        assert not [c for c in hit.get("children", ())
                    if c["name"].startswith("scan.")]
        said = [built["fields"], hit["fields"]]
        two = dict(QUERY, end=T0 + 3 * HOUR)
        said.append((await _resolve_span(client, "/query", two))["fields"])
        monkeypatch.setattr(engine_mod, "_POSTINGS_MAX_ROWS", 0)
        said.append((await _resolve_span(client, "/query", two))["fields"])
        assert [f["postings"] for f in said] == [
            "hit=0 build=1 bypass=0", "hit=1 build=0 bypass=0",
            "hit=1 build=1 bypass=0", "hit=0 build=0 bypass=2"]
        assert moved(before) == {"hit": 2, "build": 2, "bypass": 2}
        # a query with no filter looks nothing up and says nothing
        plain = {k: v for k, v in QUERY.items() if k != "filters"}
        before = outcomes()
        assert "postings" not in (
            await _resolve_span(client, "/query", plain))["fields"]
        assert not any(moved(before).values())
        text = registry.render()
        for series in ('index_postings_total{outcome="hit"}',
                       'index_postings_total{outcome="build"}',
                       'index_postings_total{outcome="bypass"}',
                       "index_postings_bytes", "index_postings_segments"):
            assert f"\n{series} " in text

    run(_served(go))


@pytest.mark.parametrize("path, extra", [
    ("/query", {}),
    ("/query_multi", {"fields": ["value"]}),
    ("/query_topk", {"k": 2, "by": "max"}),
    ("/query", {"filters": {"hostname": "host_nobody"}}),
])
def test_a_filtered_request_answers_as_it_did_by_the_scan(monkeypatch, path,
                                                          extra):
    """Status, headers and bytes from the lists (built, then hit) are
    those of the filtered scan, which is every request's path once the
    row cap is 0 (the program before the lists)."""
    async def go(client, engine):
        async def post():
            r = await client.post(path, json=dict(QUERY, **extra))
            headers = {k: v for k, v in r.headers.items()
                       if k not in (tracing.TRACE_HEADER, "Date",
                                    "X-Trace-Summary")}
            return r.status, headers, await r.read()

        built, hit = await post(), await post()
        monkeypatch.setattr(engine_mod, "_POSTINGS_MAX_ROWS", 0)
        before = outcomes()
        scanned = await post()
        assert moved(before) == {"hit": 0, "build": 0, "bypass": 1}
        assert built == hit == scanned
        assert scanned[0] == 200 and scanned[2]

    run(_served(go))
