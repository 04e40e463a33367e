"""POST /query_multi on the device-decode route (ISSUE 34): TSBS
`double-groupby-all`'s shape at test size, served, against a plain
numpy reference that imports nothing of the program.

The store is the benchmark's deployment in small: several hosts, ten
`usage_*` fields written one body a field over POST /write_arrow, three
2 h segments compacted to one SST each, the route forced the way
tests/test_device_decode.py forces it (`[scan.decode] mode = "device"`,
HORAEDB_HOST_AGG=0), the parts memo off so that every query
dispatches.  One server for the module: a test leaves the store
answering the reference (the one that writes moves the reference with
it)."""

import asyncio
import io
import time

import numpy as np
import pyarrow as pa
import pytest
from aiohttp.test_utils import TestClient, TestServer
from pyarrow import ipc

from horaedb_tpu.common import deviceprof
from horaedb_tpu.metric_engine import MetricEngine
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.ops import device_decode
from horaedb_tpu.server.config import ServerConfig
from horaedb_tpu.server.main import ServerState, build_app
from horaedb_tpu.storage.config import StorageConfig, from_dict
from horaedb_tpu.storage.types import TimeRange
from horaedb_tpu.utils import registry

HOUR = 3_600_000
SEGMENT_MS = 2 * HOUR
T0 = 1_700_000_000_000 // SEGMENT_MS * SEGMENT_MS
TICK_MS = 60_000
HOSTS, SEGMENTS = 5, 3
TICKS = SEGMENTS * SEGMENT_MS // TICK_MS
FIELDS = ["usage_user", "usage_system", "usage_idle", "usage_nice",
          "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
          "usage_guest", "usage_guest_nice"]
# the last host is silent for these ticks: whole buckets stay empty
SILENT = (HOSTS - 1, 55, 190)
GRIDS = ("count", "sum", "avg", "min", "max", "last", "last_ts")

# 4 h by 1 h, off the bucket grid, over both segment edges; B is A
# moved: the same shapes, other rows in every bucket
WINDOW_A = (T0 + HOUR + 7, T0 + 5 * HOUR + 7)
WINDOW_B = (T0 + HOUR + 13 * TICK_MS + 3, T0 + 5 * HOUR + 13 * TICK_MS + 3)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def reference(values: np.ndarray, reports: np.ndarray, start: int, end: int,
              bucket_ms: int) -> dict:
    """{grid: (hosts, buckets) float64} of one field.  `values` is
    (ticks, hosts) float32, tick t stamped T0 + t * TICK_MS, `reports`
    says which points exist.  Bucket b holds the points stamped in
    [start + b * bucket, min(end, start + (b + 1) * bucket)): counted
    from `start`, at any phase.  An empty cell reads as the endpoint
    writes it: count and sum 0, min +inf, max -inf, the rest NaN."""
    nb = -(-(end - start) // bucket_ms)
    ts = T0 + np.arange(values.shape[0], dtype=np.int64) * TICK_MS
    out = {g: np.full((values.shape[1], nb), np.nan) for g in GRIDS}
    out["count"][:] = 0.0
    out["sum"][:] = 0.0
    out["min"][:] = np.inf
    out["max"][:] = -np.inf
    for h in range(values.shape[1]):
        for b in range(nb):
            lo = start + b * bucket_ms
            rows = np.flatnonzero((ts >= lo)
                                  & (ts < min(end, lo + bucket_ms))
                                  & reports[:, h])
            if not rows.size:
                continue
            cell = values[rows, h].astype(np.float64)
            out["count"][h, b] = rows.size
            out["sum"][h, b] = cell.sum()
            out["avg"][h, b] = cell.sum() / rows.size
            out["min"][h, b] = cell.min()
            out["max"][h, b] = cell.max()
            out["last"][h, b] = cell[-1]
            out["last_ts"][h, b] = ts[rows[-1]]
    return out


def assert_is_the_reference(got: dict, ref: dict, order: list, ctx: str):
    """One field's body against its reference: counts, min, max, last
    and last_ts exact; sums and averages float32-rounded."""
    assert got["num_buckets"] == ref["count"].shape[1], ctx
    assert set(got["aggs"]) == set(GRIDS), ctx
    for g in GRIDS:
        grid = np.array(got["aggs"][g], dtype=np.float64)
        want = ref[g][order]
        assert grid.shape == want.shape, f"{ctx}: {g} shape"
        if g in ("sum", "avg"):
            np.testing.assert_allclose(grid, want, rtol=1e-5, atol=0,
                                       err_msg=f"{ctx}: {g}")
        else:
            assert np.array_equal(grid, want, equal_nan=True), \
                f"{ctx}: {g} differs"


# ---------------------------------------------------------------------------
# the served store
# ---------------------------------------------------------------------------


class CountingStore(MemoryObjectStore):
    """Counts every read the store is asked for."""

    def __init__(self):
        super().__init__()
        self.reads = 0

    async def get(self, path):
        self.reads += 1
        return await super().get(path)

    async def get_range(self, path, start, end):
        self.reads += 1
        return await super().get_range(path, start, end)


def arrow_body(values: np.ndarray, reports: np.ndarray, lo: int,
               hi: int) -> bytes:
    """Ticks [lo, hi) of one field, scrape order (a tick reports every
    host that reports), as POST /write_arrow takes them."""
    tick, host = np.nonzero(reports[lo:hi])
    batch = pa.record_batch({
        "hostname": pa.array([f"host_{h}" for h in host]),
        "timestamp": pa.array(T0 + (tick + lo).astype(np.int64) * TICK_MS),
        "value": pa.array(values[lo:hi][tick, host].astype(np.float64))})
    sink = io.BytesIO()
    with ipc.new_stream(sink, batch.schema) as w:
        w.write_batch(batch)
    return sink.getvalue()


class Served:
    """The server, its client and what the store must answer."""

    def __init__(self, loop):
        self.loop = loop
        rng = np.random.default_rng(340034)
        self.values = (rng.random((len(FIELDS), TICKS, HOSTS)) * 100.0
                       ).astype(np.float32)
        self.reports = np.ones((TICKS, HOSTS), dtype=bool)
        self.reports[SILENT[1]:SILENT[2], SILENT[0]] = False
        self.store = CountingStore()

    def run(self, coro):
        return self.loop.run_until_complete(coro)

    async def open(self):
        cfg = from_dict(StorageConfig, {
            "scan": {"decode": {"mode": "device"}},
            "scheduler": {"schedule_interval": "1h"}})
        self.engine = await MetricEngine.open(
            "m", self.store, segment_ms=SEGMENT_MS, config=cfg)
        self.client = TestClient(TestServer(build_app(
            ServerState(self.engine, ServerConfig()))))
        await self.client.start_server()
        for f in range(len(FIELDS)):
            await self.write(f, 0, TICKS)
        data = self.data
        # ten SSTs a segment (one a field) become one, as the
        # benchmark's set-up leaves them
        while (task := await
               data.compact_scheduler.picker.pick_candidate()) is not None:
            await data.compact_scheduler.executor.execute(task)
        data.reader.parts_memo.lru.max_bytes = 0
        body = await self.multi(*WINDOW_A)
        self.host_of = await self.hosts_of(body[FIELDS[0]]["tsids"])
        # the first plan after the last compaction can still see that
        # segment's ten old SSTs: its slice, keyed by them, would stay
        # beside the one SST's until the LRU wants the room
        data.reader.scan_cache.drop_slices()

    async def close(self):
        await self.client.close()
        await self.engine.close()

    @property
    def data(self):
        return self.engine.tables["data"]

    async def write(self, f: int, lo: int, hi: int):
        r = await self.client.post(
            f"/write_arrow?metric=cpu&tags=hostname&field={FIELDS[f]}",
            data=arrow_body(self.values[f], self.reports, lo, hi))
        assert r.status == 200, await r.text()
        assert (await r.json())["written"] == int(
            self.reports[lo:hi].sum())

    async def hosts_of(self, tsids: list) -> dict:
        keys = await self.engine.resolve_series(
            "cpu", [int(t) for t in tsids], TimeRange.new(T0, T0 + 1))
        out = {}
        for tsid, key in keys.items():
            host, = [h for h in range(HOSTS)
                     if f"host_{h}".encode() in key]
            out[str(tsid)] = host
        return out

    async def post(self, path: str, body: dict):
        r = await self.client.post(path, json=body)
        assert r.status == 200, await r.text()
        return r, await r.json()

    async def multi(self, start: int, end: int, fields=FIELDS,
                    bucket_ms: int = HOUR) -> dict:
        return (await self.post("/query_multi", {
            "metric": "cpu", "fields": list(fields), "start": start,
            "end": end, "bucket_ms": bucket_ms}))[1]

    async def warm(self, window) -> dict:
        """Until a query of `window` finds every slice resident."""
        for _ in range(4):
            c0 = resident_outcomes()
            body = await self.multi(*window)
            if moved(c0, resident_outcomes())["miss"] == 0:
                return body
        raise AssertionError("the slices never all stayed resident")

    def check(self, body: dict, window, ctx: str, fields=FIELDS):
        assert list(body) == list(fields), ctx
        for name in fields:
            f = FIELDS.index(name)
            order = [self.host_of[t] for t in body[name]["tsids"]]
            assert sorted(order) == list(range(HOSTS)), f"{ctx}: {name}"
            assert_is_the_reference(
                body[name], reference(self.values[f], self.reports,
                                      *window, HOUR),
                order, f"{ctx}: {name}")


@pytest.fixture(scope="module")
def served():
    mp = pytest.MonkeyPatch()
    # the aggregate runs in the XLA programs the chip runs, not in the
    # numpy twin the CPU backend would pick
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


def resident_outcomes() -> dict:
    return {o: c.value for o, c in device_decode._RESIDENT.items()}


def moved(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def compiles_so_far() -> int:
    return sum(f["compiles"] for f in deviceprof.profiler.snapshot()["fns"])


def decode_dispatches() -> int:
    return sum(f["compiles"] + f["dispatches"]
               for f in deviceprof.profiler.snapshot()["fns"]
               if f["fn"] == "_decode_aggregate_jit")


def batch_counts() -> dict:
    """Slices by how they reached the device, and batched calls."""
    return {**{m: c.value for m, c in device_decode._BATCH_SLICES.items()},
            "calls": device_decode._BATCH_CALLS.value}


def multi_counters() -> dict:
    return {n: registry.counter(n).value
            for n in ("query_multi_total", "query_multi_fields_total",
                      "query_multi_scan_seconds_total")}


# ---------------------------------------------------------------------------
# answers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [WINDOW_A, WINDOW_B],
                         ids=["window_a", "window_b"])
def test_every_grid_of_every_field_is_the_references(served, window):
    """A start off the bucket grid, a window over both segment edges,
    a host silent for whole buckets: all seven grids of all ten fields,
    each field's every segment through the fused decode program: a
    slice that missed by a call of its own, a field's resident slices
    in ONE call (whichever window runs first misses, the other
    hits)."""
    n0, c0, b0 = decode_dispatches(), resident_outcomes(), batch_counts()
    body = served.run(served.multi(*window))
    served.check(body, window, "served")
    probed = moved(c0, resident_outcomes())
    slices = len(FIELDS) * SEGMENTS
    assert probed["bypass"] == 0
    assert (probed["hit"], probed["miss"]) in ((0, slices), (slices, 0))
    sent = moved(b0, batch_counts())
    assert sent == {"single": probed["miss"], "batched": probed["hit"],
                    "calls": len(FIELDS) if probed["hit"] else 0}
    assert decode_dispatches() - n0 == sent["single"] + sent["calls"]
    # the silent host's empty cells are in the answer, not left out
    counts = np.array(body[FIELDS[0]]["aggs"]["count"])
    assert (counts == 0).any() and (counts == HOUR // TICK_MS).any()


@pytest.mark.parametrize("field", FIELDS)
def test_a_fields_answer_is_querys_answer_for_that_field(served, field):
    """`/query_multi`'s body of a field is `/query`'s with `field=`:
    the same grids, cell for cell, the same series in the same order."""
    async def go():
        multi = await served.multi(*WINDOW_B, fields=[field])
        _, single = await served.post("/query", {
            "metric": "cpu", "field": field, "start": WINDOW_B[0],
            "end": WINDOW_B[1], "bucket_ms": HOUR})
        return multi, single

    multi, single = served.run(go())
    assert list(multi) == [field]
    assert multi[field] == single
    assert single["tsids"]


def test_a_field_nothing_was_written_for_answers_empty(served):
    body = served.run(served.multi(
        *WINDOW_A, fields=[FIELDS[2], "usage_nothing"]))
    assert body["usage_nothing"] == {"tsids": [], "num_buckets": 4,
                                     "aggs": {}}
    served.check({FIELDS[2]: body[FIELDS[2]]}, WINDOW_A, "beside it",
                 fields=[FIELDS[2]])


# ---------------------------------------------------------------------------
# resident slices
# ---------------------------------------------------------------------------


def test_another_window_is_served_wholly_from_resident_slices(served):
    """Thirty slices (ten fields x three segments) stay on the device
    in the slices' account; a query of ANOTHER window dispatches from
    them: no store read, no tier-2 probe, no upload, no compile."""
    served.run(served.warm(WINDOW_A))
    reader = served.data.reader
    acct = reader.cache_stats()["scan_cache"]["accounts"]["slice"]
    assert acct["entries"] == len(FIELDS) * SEGMENTS
    assert acct["bytes"] == reader.scan_cache.slice_account.total_bytes > 0
    assert acct["bytes"] % acct["entries"] == 0    # one capacity for all
    tier2 = reader.encoded_cache
    reads, probes = served.store.reads, tier2.hits + tier2.misses
    compiles, c0 = compiles_so_far(), resident_outcomes()
    h2d = deviceprof.profiler.snapshot()["transfer"]["h2d"]
    body = served.run(served.multi(*WINDOW_B))
    assert moved(c0, resident_outcomes()) == {
        "hit": len(FIELDS) * SEGMENTS, "miss": 0, "bypass": 0}
    assert served.store.reads == reads
    assert tier2.hits + tier2.misses == probes
    assert deviceprof.profiler.snapshot()["transfer"]["h2d"] == h2d
    assert compiles_so_far() == compiles
    served.check(body, WINDOW_B, "from resident slices")


def test_a_resident_slice_does_not_keep_the_fetched_object_alive(served):
    """Read through the store (tier 2 emptied, as at a scale it cannot
    hold), a segment's dictionaries are views of the fetched object's
    bytes; a slice that kept such a view would pin the whole object in
    host memory for as long as it is resident (173 MB a slice at TSBS
    scale 1000: 20.8 GB for ten fields' day, seen by no account).  The
    slices own what they keep, and answer as before."""
    reader = served.data.reader
    reader.encoded_cache.clear()
    reader.scan_cache.drop_slices()
    reads = served.store.reads
    body = served.run(served.warm(WINDOW_A))
    assert served.store.reads > reads           # the load path ran
    slices = reader.scan_cache.slices()
    assert len(slices) == len(FIELDS) * SEGMENTS
    for sl in slices:
        assert sl.es is None
        kept = [sl.values, sl.run_offsets, *sl.key_consts] + [
            enc.dictionary for enc in sl.encodings.values()]
        for arr in kept:
            assert arr is None or arr.base is None, (arr.dtype, arr.shape)
        assert sl.values is sl.encodings["tsid"].dictionary
    served.check(body, WINDOW_A, "slices read through the store")


def test_a_write_to_one_field_misses_the_written_segment_only(served):
    """The SST set of a segment is the slices' key, whatever field the
    new SST holds: a write of ONE field into the middle segment misses
    that segment's slice of every field (ten misses; re-narrowing one
    field's would take a key by field), hits the other two segments'
    twenty, and the written field answers with the new values."""
    f = 3
    lo = SEGMENT_MS // TICK_MS + 10            # the middle segment
    hi = lo + 25
    served.run(served.warm(WINDOW_A))
    before = served.run(served.multi(*WINDOW_A))
    served.values[f, lo:hi] += np.float32(0.5)
    served.run(served.write(f, lo, hi))
    c0 = resident_outcomes()
    after = served.run(served.multi(*WINDOW_A))
    assert moved(c0, resident_outcomes()) == {
        "hit": len(FIELDS) * (SEGMENTS - 1), "miss": len(FIELDS),
        "bypass": 0}
    served.check(after, WINDOW_A, "after the write")
    for name in FIELDS:
        assert (after[name] == before[name]) == (name != FIELDS[f]), name
    # the re-read segment's slices are resident again
    served.run(served.warm(WINDOW_A))


# ---------------------------------------------------------------------------
# spans and counters
# ---------------------------------------------------------------------------


def test_span_tree_and_counters_of_a_query_multi_request(served):
    """One `resolve`, one `downsample` a field (naming it, in the
    request's order) and one `respond`, all children of the root, which
    is what benchmark/harness/counters.py::query_spans sums per query;
    the three counters move once a request."""
    async def go():
        c0 = multi_counters()
        t0 = time.perf_counter()
        r, _ = await served.post("/query_multi", {
            "metric": "cpu", "fields": FIELDS, "start": WINDOW_A[0],
            "end": WINDOW_A[1], "bucket_ms": HOUR})
        wall = time.perf_counter() - t0
        c1 = multi_counters()
        trace = await (await served.client.get(
            f"/debug/traces/{r.headers['X-Trace-Id']}")).json()
        # a /query moves none of the three
        await served.post("/query", {
            "metric": "cpu", "field": FIELDS[0], "start": WINDOW_A[0],
            "end": WINDOW_A[1], "bucket_ms": HOUR})
        return c0, c1, multi_counters(), wall, trace["tree"]

    c0, c1, c2, wall, tree = served.run(go())
    assert tree["name"] == "/query_multi"
    children = tree["children"]
    names = [c["name"] for c in children]
    assert names.count("resolve") == 1 and names.count("respond") == 1
    assert names.count("downsample") == len(FIELDS)
    scans = [c for c in children if c["name"] == "downsample"]
    assert [c["fields"]["field"] for c in scans] == FIELDS
    assert names.index("resolve") < names.index("downsample") \
        < names.index("respond") == len(names) - 1
    # nothing of the engine's hangs off the root beside them
    assert set(names) <= {"admission_wait", "parse", "resolve",
                          "downsample", "respond"}
    delta = moved(c0, c1)
    assert delta["query_multi_total"] == 1
    assert delta["query_multi_fields_total"] == len(FIELDS)
    scan_s = delta["query_multi_scan_seconds_total"]
    span_s = sum(c["duration_ms"] for c in scans) / 1e3
    assert 0 < span_s <= scan_s <= wall
    assert scan_s - span_s < 0.25        # the loop's own statements
    assert c2 == c1


def test_counters_are_exported_at_rest(served):
    text = served.run(served.client.get("/metrics"))
    text = served.run(text.text())
    for name in ("query_multi_total", "query_multi_fields_total",
                 "query_multi_scan_seconds_total"):
        assert f"\n{name} " in text, name
