"""Data of the TSBS devops cpu shape through every A/B of the scan.

The subsystem tests hold each A/B of the scan (pipeline on/off, sparse
and dense combine, device and host decode, the cache tiers, the mesh,
the fused accumulator, the host aggregate) on a synthetic `(k, ts, v)`
table.  This file feeds the same A/Bs what a TSBS deployment stores:
several hosts x ten `usage_*` fields, a `region` tag beside `host`, a
10 s scrape, rows arriving shuffled and not host-major, seven 2 h
segments, one overlapping re-write for the dedup, compacted to one SST
a segment.  Each case runs one leg against its control on ONE store —
the same series, and the same grids bit for bit wherever the
subsystem's own test holds that pair to bit identity — checks from the
device plane's ledger that the leg ran the programs of its route and
no other, and compares the default leg with a plain numpy group-by of
the generator's arrays under the repo's rule: counts, min, max and last
exact, sums and averages to 1e-5 relative.

In process: one engine over a MemoryObjectStore for the module, no
server, no child, no wall clock."""

import asyncio
import contextlib

import numpy as np
import pyarrow as pa
import pytest

from horaedb_tpu.common import deviceprof
from horaedb_tpu.metric_engine import Label, MetricEngine, tsid_of
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.ops.downsample import ALL_AGGS
from horaedb_tpu.storage import pipeline as pipeline_mod
from horaedb_tpu.storage.config import StorageConfig, from_dict
from horaedb_tpu.storage.types import TimeRange

HOUR = 3_600_000
SEGMENT_MS = 2 * HOUR
T0 = 1_700_000_000_000 // SEGMENT_MS * SEGMENT_MS
TICK_MS = 10_000
HOSTS, SEGMENTS = 8, 7
TICKS = SEGMENTS * SEGMENT_MS // TICK_MS
SEED = 470047
FIELDS = ["usage_user", "usage_system", "usage_idle", "usage_nice",
          "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
          "usage_guest", "usage_guest_nice"]
REGIONS = ["us-east-1", "us-west-1", "eu-west-1"]
# the re-write: these ticks of every host, over the first segment edge,
# these fields, other values
REWRITE_TICKS = (SEGMENT_MS // TICK_MS - 90, SEGMENT_MS // TICK_MS + 45)
REWRITE_FIELDS = FIELDS[:3]
FORCERS = ("HORAEDB_HOST_AGG", "HORAEDB_DEVICE_DECODE",
           "HORAEDB_FUSED_AGG", "HORAEDB_DEVCOL_STACK")


# ---------------------------------------------------------------------------
# the generator (TSBS devops, cpu-only) and the plain reference
# ---------------------------------------------------------------------------


def generate(seed: int, hosts: int = HOSTS, ticks=(0, TICKS),
             fields=FIELDS, shuffle: bool = True) -> dict:
    """Flat columns of one scrape stream: `host_id` int32, `ts` int64
    and a float32 column a field, a row a host a tick.  Each series is
    a random walk from a level of its own, folded into [0, 100].
    Host-major then time unless `shuffle`, which interleaves the rows
    as a fleet's scrapes arrive."""
    rng = np.random.default_rng(seed)
    n = ticks[1] - ticks[0]
    cols = {"host_id": np.repeat(np.arange(hosts, dtype=np.int32), n),
            "ts": np.tile(T0 + np.arange(*ticks, dtype=np.int64) * TICK_MS,
                          hosts)}
    for f in fields:
        walk = rng.uniform(0, 100, (hosts, 1)) \
            + rng.normal(0, 0.3, (hosts, n)).cumsum(axis=1)
        cols[f] = np.abs(walk % 200.0 - 100.0).astype(np.float32).ravel()
    if shuffle:
        perm = rng.permutation(hosts * n)
        cols = {name: col[perm] for name, col in cols.items()}
    return cols


def region_of(host: int) -> str:
    return REGIONS[host % len(REGIONS)]


def record_batch(cols: dict, field: str) -> pa.RecordBatch:
    """One field of `cols` as MetricEngine.write_arrow takes it, the
    `host` and `region` tags as strings."""
    hosts = cols["host_id"]
    return pa.record_batch({
        "host": pa.array([f"host_{h}" for h in hosts]),
        "region": pa.array([region_of(h) for h in hosts]),
        "timestamp": pa.array(cols["ts"]),
        "value": pa.array(cols[field].astype(np.float64))})


def reference(writes: list, field: str, hosts: list, start: int, end: int,
              bucket_ms: int) -> dict:
    """{agg: (len(hosts), buckets) float64} by a plain group-by of the
    generator's columns.  `writes` are in the order written: of two
    rows of one (host, ts) the later write stands.  Bucket b holds
    [start + b * bucket, min(end, start + (b + 1) * bucket))."""
    writes = [w for w in writes if field in w]
    host = np.concatenate([w["host_id"] for w in writes])
    ts = np.concatenate([w["ts"] for w in writes])
    v = np.concatenate([w[field] for w in writes]).astype(np.float64)
    seq = np.concatenate([np.full(len(w["ts"]), i)
                          for i, w in enumerate(writes)])
    order = np.lexsort((seq, ts, host))
    host, ts, v = host[order], ts[order], v[order]
    stands = np.append((host[1:] != host[:-1]) | (ts[1:] != ts[:-1]), True)
    keep = stands & (ts >= start) & (ts < end) & np.isin(host, hosts)
    host, ts, v = host[keep], ts[keep], v[keep]
    nb = -(-(end - start) // bucket_ms)
    cell = np.searchsorted(np.asarray(hosts), host) * nb \
        + (ts - start) // bucket_ms
    cells = len(hosts) * nb
    out = {"count": np.bincount(cell, minlength=cells).astype(np.float64),
           "sum": np.bincount(cell, weights=v, minlength=cells),
           "min": np.full(cells, np.inf), "max": np.full(cells, -np.inf),
           "last": np.full(cells, np.nan)}
    np.minimum.at(out["min"], cell, v)
    np.maximum.at(out["max"], cell, v)
    out["last"][cell] = v   # (host, ts) order: a cell's last row stands
    with np.errstate(invalid="ignore", divide="ignore"):
        out["avg"] = out["sum"] / out["count"]
    return {agg: grid.reshape(len(hosts), nb) for agg, grid in out.items()}


def test_generator_shapes_and_determinism():
    a = generate(7, hosts=4, ticks=(0, 10), fields=FIELDS[:2])
    b = generate(7, hosts=4, ticks=(0, 10), fields=FIELDS[:2])
    assert set(a) == {"host_id", "ts", "usage_user", "usage_system"}
    assert all(len(col) == 4 * 10 for col in a.values())
    assert all(np.array_equal(a[name], b[name]) for name in a)
    assert a["usage_user"].dtype == np.float32
    assert ((a["usage_user"] >= 0) & (a["usage_user"] <= 100)).all()
    assert np.diff(np.unique(a["ts"])).tolist() == [TICK_MS] * 9
    other = generate(8, hosts=4, ticks=(0, 10), fields=FIELDS[:2])
    assert not np.array_equal(a["usage_user"], other["usage_user"])


def test_generator_shuffle_keeps_the_row_set():
    plain = generate(7, hosts=3, ticks=(0, 50), fields=FIELDS[:1],
                     shuffle=False)
    mixed = generate(7, hosts=3, ticks=(0, 50), fields=FIELDS[:1])
    # host-major then time; shuffled is neither
    assert (np.diff(plain["host_id"]) >= 0).all()
    assert (np.diff(mixed["host_id"]) < 0).any()
    assert (np.diff(mixed["ts"]) < 0).any()
    assert sorted(zip(*plain.values())) == sorted(zip(*mixed.values()))


def test_record_batch_carries_a_region_of_more_than_one_value():
    cols = generate(7, hosts=10, ticks=(0, 3), fields=FIELDS[:3])
    batch = record_batch(cols, "usage_idle")
    assert batch.schema.names == ["host", "region", "timestamp", "value"]
    assert batch.num_rows == 30
    assert len(set(batch.column("region").to_pylist())) == len(REGIONS)
    # a host keeps its region
    pairs = set(zip(batch.column("host").to_pylist(),
                    batch.column("region").to_pylist()))
    assert len(pairs) == 10


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------

# the programs a route is made of, by their names in the device plane's
# ledger (tests/test_route_table.py)
FUSED = ("_fused_one_call_jit", "_fused_acc_init_jit",
         "_fused_round_accumulate_jit", "_fused_finalize_jit",
         "_group_has_data_jit")
DECODE = ("_decode_aggregate_jit",)
PARTS_XLA = ("_batched_window_partials_jit",)
MESH = ("mesh_run_partials",)


def calls() -> dict:
    """Calls so far (a compile is one too) of the routes' programs."""
    return {r["fn"]: r["compiles"] + r["dispatches"]
            for r in deviceprof.profiler.snapshot()["fns"]
            if r["fn"] in FUSED + DECODE + PARTS_XLA + MESH}


class CountingStore(MemoryObjectStore):
    """Counts the reads of the data table's stored rows: its SSTs and
    their sidecars, not its manifest (which a background merge reads
    when it likes) and not the other four tables' objects."""

    def __init__(self):
        super().__init__()
        self.data_reads = 0
        self.rows_under = None     # the data table's `{root}/data/`

    def _counts(self, path: str) -> bool:
        return (self.rows_under is not None
                and path.startswith(self.rows_under)
                and path.endswith((".sst", ".enc")))

    async def get(self, path):
        self.data_reads += self._counts(path)
        return await super().get(path)

    async def get_range(self, path, start, end):
        self.data_reads += self._counts(path)
        return await super().get_range(path, start, end)


class Store:
    """The engine, what was written, and what a case compares: a leg's
    answer to a query, and the programs that ran for it, are found
    once and kept."""

    def __init__(self, loop):
        self.loop = loop
        self.writes = [
            generate(SEED),
            generate(SEED + 1, ticks=REWRITE_TICKS, fields=REWRITE_FIELDS)]
        self.tsid = [tsid_of("cpu", [Label("host", f"host_{h}"),
                                     Label("region", region_of(h))])
                     for h in range(HOSTS)]
        self.objects = CountingStore()
        self.answers = {}

    def run(self, coro):
        return self.loop.run_until_complete(coro)

    async def open(self):
        cfg = from_dict(StorageConfig, {
            "scan": {"mesh": {"enabled": True}},
            "scheduler": {"schedule_interval": "1h"}})
        self.engine = await MetricEngine.open(
            "tsbs", self.objects, segment_ms=SEGMENT_MS, config=cfg)
        self.data = self.engine.tables["data"]
        self.reader = self.data.reader
        self.objects.rows_under = f"{self.data.root_path}/data/"
        # the mesh is built once and attached by its leg alone: with it
        # detached the reader routes as an engine without [scan.mesh]
        # (tests/test_mesh_scan.py's control)
        self.mesh, self.reader.scan_mesh = self.reader.scan_mesh, None
        assert self.mesh is not None
        # compactions are run below, by hand and to the end: the
        # background loops would pick beside them and leave the set of
        # SSTs to chance
        for table in self.engine.tables.values():
            await table.compact_scheduler.stop()
        for cols in self.writes:
            for field in FIELDS:
                if field in cols:
                    await self.engine.write_arrow(
                        "cpu", ["host", "region"],
                        record_batch(cols, field), field=field)
        await self.engine.flush()
        picker = self.data.compact_scheduler.picker
        while (task := await picker.pick_candidate()) is not None:
            await self.data.compact_scheduler.executor.execute(task)

    def cold(self):
        self.reader.scan_cache.clear()
        self.reader.encoded_cache.clear()
        self.reader.parts_memo.clear()

    async def answer(self, leg: str, query: str) -> tuple:
        """(the leg's answer to the query, {program: calls it made})."""
        if (leg, query) not in self.answers:
            before = calls()
            got = await LEGS[leg](self, QUERIES[query])
            ran = {fn: n - before.get(fn, 0) for fn, n in calls().items()
                   if n != before.get(fn, 0)}
            self.answers[leg, query] = got, ran
        return self.answers[leg, query]


@pytest.fixture(scope="module")
def store():
    loop = asyncio.new_event_loop()
    s = Store(loop)
    try:
        s.run(s.open())
        yield s
        s.run(s.engine.close())
    finally:
        loop.close()


def test_the_store_is_the_shape(store):
    """What the legs scan: one SST a segment after the compaction, the
    re-written rows once, ten fields of every host and two tags."""
    async def go():
        ssts = await store.data.manifest.all_ssts()
        whole = TimeRange.new(T0, T0 + SEGMENTS * SEGMENT_MS)
        fields = await store.engine.list_fields("cpu", whole)
        regions = await store.engine.label_values("cpu", "region", whole)
        return ssts, fields, regions

    ssts, fields, regions = store.run(go())
    assert len(ssts) == SEGMENTS
    assert sum(f.meta.num_rows for f in ssts) == len(FIELDS) * HOSTS * TICKS
    assert sorted(fields) == sorted(FIELDS)
    assert sorted(regions) == sorted(REGIONS)


# ---------------------------------------------------------------------------
# the queries: {field: {tsids, num_buckets, aggs}} each
# ---------------------------------------------------------------------------

# off the bucket grid, over segment edges, inside the data
WINDOW_1H = (T0 + 3 * HOUR + 20 * 60_000 + 7, T0 + 4 * HOUR + 20 * 60_000 + 7)
WINDOW_12H = (T0 + HOUR + 7, T0 + 13 * HOUR + 7)
ONE_HOST = 5


class Query:
    def __init__(self, window, bucket_ms, fields, hosts, top_k=0):
        self.window, self.bucket_ms = window, bucket_ms
        self.fields, self.hosts, self.top_k = fields, hosts, top_k

    async def ask(self, engine) -> dict:
        filters = ([("host", f"host_{self.hosts[0]}")]
                   if len(self.hosts) == 1 else [])
        shape = ("cpu", filters, TimeRange.new(*self.window), self.bucket_ms)
        if len(self.fields) > 1:
            return await engine.query_downsample_multi(*shape, self.fields)
        field, = self.fields
        if self.top_k:
            out = await engine.query_topk(*shape, self.top_k, by="max",
                                          field=field)
        else:
            out = await engine.query_downsample(*shape, field=field)
        return {field: out}


QUERIES = {
    # TSBS single-groupby-1-1-1: one host, one field, 1 h by 1 m
    "single_groupby_1_1_1": Query(WINDOW_1H, 60_000, FIELDS[:1],
                                  [ONE_HOST]),
    # TSBS double-groupby-1: every host, one field, 12 h by 1 h
    "double_groupby_1": Query(WINDOW_12H, HOUR, FIELDS[:1],
                              list(range(HOSTS))),
    # TSBS double-groupby-all: every host, all ten fields
    "double_groupby_all": Query(WINDOW_12H, HOUR, FIELDS,
                                list(range(HOSTS))),
    # BASELINE config 4: the 5 hosts of the largest max(usage_user)
    "topk_5_by_max": Query(WINDOW_12H, HOUR, FIELDS[:1],
                           list(range(HOSTS)), top_k=5),
}


# ---------------------------------------------------------------------------
# the legs
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def forced(**env):
    """The four route forcers cleared, then `env` set, for a block."""
    with pytest.MonkeyPatch.context() as mp:
        for name in FORCERS:
            mp.delenv(name, raising=False)
        for name, value in env.items():
            mp.setenv(name, value)
        yield


# the XLA window kernel under a host-decoded, single-device parts scan:
# what the device-decode dispatch and the mesh program are held to
# (tests/test_device_decode.py, tests/test_mesh_scan.py)
XLA = {"HORAEDB_HOST_AGG": "0", "HORAEDB_FUSED_AGG": "0"}


async def default_cold(s, q):
    with forced():
        s.cold()
        return await q.ask(s.engine)


async def pipeline_off(s, q):
    fetches = pipeline_mod.STAGE_SECONDS["fetch"].count
    s.data.config.scan.pipeline.enabled = False
    try:
        got = await default_cold(s, q)
    finally:
        s.data.config.scan.pipeline.enabled = True
    # the pre-pipeline pump served it: no stage of the pipeline ran
    assert pipeline_mod.STAGE_SECONDS["fetch"].count == fetches
    return got


async def combine_dense(s, q):
    s.data.config.scan.combine.mode = "dense"
    try:
        return await default_cold(s, q)
    finally:
        s.data.config.scan.combine.mode = "sparse"


async def cache_ladder(s, q):
    """The same query down the cache's rungs: everything resident, the
    device state dropped, tier 1 cleared over a warm tier 2, both
    cleared.  The parts memo is emptied at every rung so that each one
    scans; only the last reads the store.  The rungs' answers, the
    last one returned, are one."""
    rungs = (lambda: None, s.reader.drop_hbm_state,
             s.reader.scan_cache.clear, s.cold)
    with forced():
        await q.ask(s.engine)
        got, reads = [], []
        for drop in rungs:
            drop()
            s.reader.parts_memo.clear()
            before = s.objects.data_reads
            got.append(await q.ask(s.engine))
            reads.append(s.objects.data_reads - before)
    assert reads[:3] == [0, 0, 0] and reads[3] > 0, reads
    for rung in got[:-1]:
        assert_same(rung, got[-1], ALL_AGGS, "a rung of the cache ladder")
    return got[-1]


async def xla_cold(s, q):
    with forced(**XLA):
        s.cold()
        return await q.ask(s.engine)


async def decode_device(s, q):
    s.data.config.scan.decode.mode = "device"
    try:
        return await xla_cold(s, q)
    finally:
        s.data.config.scan.decode.mode = "auto"


async def mesh_on(s, q):
    s.reader.scan_mesh = s.mesh
    try:
        return await xla_cold(s, q)
    finally:
        s.reader.scan_mesh = None


async def fused_forced(s, q):
    with forced(HORAEDB_FUSED_AGG="1"):
        s.cold()
        return await q.ask(s.engine)


async def host_agg_forced(s, q):
    with forced(HORAEDB_HOST_AGG="1"):
        s.cold()
        return await q.ask(s.engine)


LEGS = {"default": default_cold, "pipeline_off": pipeline_off,
        "combine_dense": combine_dense, "cache_ladder": cache_ladder,
        "xla": xla_cold, "decode_device": decode_device, "mesh": mesh_on,
        "fused_forced": fused_forced, "host_agg_forced": host_agg_forced}

# a cell's selections: the grids no order of folding moves
SELECTIONS = ("count", "min", "max", "last")
# leg -> (its control, the grids held bit for bit, the programs that
# may run: none is the numpy twin of the CPU backend)
PAIRS = {
    "pipeline_off": ("default", ALL_AGGS, ()),   # tests/test_pipeline.py
    "combine_dense": ("default", ALL_AGGS, ()),  # tests/test_combine.py
    "cache_ladder": ("default", ALL_AGGS, ()),   # tests/test_scan_cache.py
    "mesh": ("xla", ALL_AGGS, MESH),             # tests/test_mesh_scan.py
    # the dispatch sums a cell's run of rows where the window kernel
    # scatters them: sums are bit for bit on integer-valued cells only
    # (tests/test_device_decode.py), and TSBS's are not
    "decode_device": ("xla", SELECTIONS, DECODE),
    # these fold sums in another order and width: the reference's rule
    "fused_forced": ("default", (), FUSED),      # tests/test_route_table.py
    "host_agg_forced": ("xla", (), ()),
}


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------


def assert_grids(got: dict, want: dict, exact: tuple, ctx: str):
    """Two sets of grids over one cell set: the `exact` ones bit for
    bit, the others under the repo's rule (counts, min, max and last
    equal; sums and averages to 1e-5 relative)."""
    assert set(got) >= set(ALL_AGGS), ctx
    for agg in ALL_AGGS:
        g, w = np.asarray(got[agg]), np.asarray(want[agg])
        assert g.shape == w.shape, f"{ctx}: {agg} shape"
        if agg in exact:
            assert g.dtype == w.dtype and g.tobytes() == w.tobytes(), \
                f"{ctx}: {agg} differs"
        elif agg in ("sum", "avg"):
            np.testing.assert_allclose(
                g.astype(np.float64), w.astype(np.float64), rtol=1e-5,
                atol=0, err_msg=f"{ctx}: {agg}")
        else:
            assert np.array_equal(g, w), f"{ctx}: {agg} differs"


def assert_same(a: dict, b: dict, exact: tuple, ctx: str):
    """Two answers to one query: the same fields, series and grids."""
    assert list(a) == list(b), ctx
    for field in a:
        assert a[field]["tsids"] == b[field]["tsids"], f"{ctx}: {field}"
        assert a[field]["num_buckets"] == b[field]["num_buckets"]
        assert set(a[field]["aggs"]) == set(b[field]["aggs"]), ctx
        assert_grids(a[field]["aggs"], b[field]["aggs"], exact,
                     f"{ctx}: {field}")


def assert_is_the_reference(s: Store, q: Query, got: dict, ctx: str):
    assert list(got) == list(q.fields), ctx
    for field in q.fields:
        ref = reference(s.writes, field, q.hosts, *q.window, q.bucket_ms)
        # every host reports every tick: no cell of a window is empty
        assert (ref["count"] > 0).all()
        hosts = sorted(q.hosts, key=lambda h: s.tsid[h])
        if q.top_k:
            score = ref["max"].max(axis=1)
            assert len(set(score)) == len(score)   # no tie to break
            hosts = [q.hosts[i] for i in np.argsort(-score)[:q.top_k]]
        assert got[field]["tsids"] == [s.tsid[h] for h in hosts], \
            f"{ctx}: {field} series"
        rows = [q.hosts.index(h) for h in hosts]
        assert got[field]["num_buckets"] == ref["count"].shape[1]
        assert_grids(got[field]["aggs"],
                     {agg: grid[rows] for agg, grid in ref.items()}, (),
                     f"{ctx} against numpy: {field}")


@pytest.mark.parametrize("query", list(QUERIES))
@pytest.mark.parametrize("leg", list(PAIRS))
def test_leg_answers_as_its_control_and_default_as_the_reference(
        store, leg, query):
    control, exact, programs = PAIRS[leg]
    q = QUERIES[query]
    got, ran = store.run(store.answer(leg, query))
    want, _ = store.run(store.answer(control, query))
    assert_same(got, want, exact, f"{leg} against {control}")
    # the leg ran its route: its programs and no other's
    assert set(ran) <= set(programs) and bool(ran) == bool(programs), ran
    assert_is_the_reference(store, q, got, leg)
    default, ran = store.run(store.answer("default", query))
    assert not ran, ran
    assert_is_the_reference(store, q, default, "default")
