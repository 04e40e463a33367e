"""Point queries over a store larger than its caches (ISSUE 27): the
plan is over the scan-cache budget, so the fused accumulator declines
and the fused device decode serves it; every SST is over
`_PARTIAL_MIN_BYTES`, so its sidecar is loaded block-pruned; tier 2 is
smaller than two parts, so whole-part admissions evict.  Answers are
compared with numpy (counts, min, max, last exact; sums and averages to
1e-5) first and repeated, over one and two segments, before and after a
write and a compaction into the same segment, and byte for byte with
the same queries after the caches were emptied (which drops the SST
footers this PR keeps in tier 2).  A store that fits its caches makes
exactly the store calls it made before the footers existed.

The sizes are the scale-1000 cell's divided down: `BLOCK_ROWS` 512,
the probe and the partial-fetch floor a few KB, 40 hosts x 360 ticks a
segment."""

import asyncio

import numpy as np
import pyarrow as pa
import pytest

from horaedb_tpu.common import ReadableDuration
from horaedb_tpu.common import runtimes as runtimes_mod
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.ops import device_decode
from horaedb_tpu.ops import filter as F
from horaedb_tpu.ops.downsample import ALL_AGGS
from horaedb_tpu.storage import sidecar
from horaedb_tpu.storage.config import (StorageConfig, ThreadsConfig,
                                        from_dict)
from horaedb_tpu.storage.encoded_cache import EncodedSegmentCache
from horaedb_tpu.storage.read import AggregateSpec, ScanRequest
from horaedb_tpu.storage.storage import CloudObjectStorage, WriteRequest
from horaedb_tpu.storage.types import TimeRange

SEGMENT_MS = 3_600_000
TICK_MS = 10_000
TICKS = SEGMENT_MS // TICK_MS
HOSTS = 40
BUCKET_MS = 60_000
SCHEMA = pa.schema([("k", pa.string()), ("ts", pa.int64()),
                    ("v", pa.float64())])


@pytest.fixture(scope="module")
def runtimes():
    rt = runtimes_mod.from_config(ThreadsConfig())
    yield rt
    rt.close()


@pytest.fixture
def small_blocks(monkeypatch):
    """The sidecar's block and probe sizes divided down, and the XLA
    programs forced as on a chip (the fused accumulator stays off: on
    the CPU backend its gate declines, as the budget does at scale)."""
    monkeypatch.setattr(sidecar, "BLOCK_ROWS", 512)
    monkeypatch.setattr(sidecar, "_HEAD_BYTES", 8192)
    monkeypatch.setattr(sidecar, "_PARTIAL_MIN_BYTES", 4096)
    monkeypatch.setenv("HORAEDB_DEVICE_DECODE", "1")
    monkeypatch.setenv("HORAEDB_HOST_AGG", "0")
    monkeypatch.delenv("HORAEDB_FUSED_AGG", raising=False)


class CountingStore(MemoryObjectStore):
    """Calls and bytes of the sidecar objects, by operation."""

    def __init__(self):
        super().__init__()
        self.calls = {"get": 0, "get_range": 0}
        self.nbytes = {"get": 0, "get_range": 0}

    def _note(self, op, path, data):
        if path.endswith(sidecar.SIDECAR_SUFFIX):
            self.calls[op] += 1
            self.nbytes[op] += len(data)

    async def get(self, path):
        data = await super().get(path)
        self._note("get", path, data)
        return data

    async def get_range(self, path, start, end):
        data = (await MemoryObjectStore.get(self, path))[start:end]
        self._note("get_range", path, data)
        return data

    def snapshot(self):
        return dict(self.calls), dict(self.nbytes)


def storage_config(**scan):
    cfg = from_dict(StorageConfig, {
        "scheduler": {"schedule_interval": "1h", "input_sst_min_num": 2},
        "scan": scan})
    cfg.manifest.merge_interval = ReadableDuration.parse("1h")
    cfg.scrub.interval = ReadableDuration.parse("1h")
    return cfg


async def open_storage(store, runtimes, **scan):
    return await CloudObjectStorage.open(
        "db", SEGMENT_MS, store, SCHEMA, 2, storage_config(**scan),
        runtimes=runtimes)


class Model:
    """What was written, newest write of a (host, tick) winning: the
    numpy side of every comparison."""

    def __init__(self, segments: int):
        self.values = np.full((HOSTS, segments * TICKS), np.nan,
                              dtype=np.float32)

    def write_request(self, rng, seg: int, hosts, ticks) -> WriteRequest:
        hosts, ticks = np.asarray(hosts), np.asarray(ticks)
        h = np.repeat(hosts, len(ticks))
        t = np.tile(ticks, len(hosts)) + seg * TICKS
        # quarters: a cell's sum is exact in float32 in any association,
        # so a pruned load and a whole one (other rows around the
        # host's, another tree over the cell's rows since the run
        # reduction of ISSUE 31) still answer byte for byte
        v = (np.round(rng.random(len(h)) * 400) / 4).astype(np.float32)
        self.values[h, t] = v
        ts = t.astype(np.int64) * TICK_MS
        batch = pa.record_batch(
            [pa.array([f"host_{i}" for i in h]), pa.array(ts),
             pa.array(v.astype(np.float64))], schema=SCHEMA)
        return WriteRequest(batch, TimeRange.new(int(ts.min()),
                                                 int(ts.max()) + 1))

    def reference(self, host: int, lo: int, hi: int) -> dict:
        n = -(-(hi - lo) // BUCKET_MS)
        out = {a: np.zeros(n) for a in ALL_AGGS}
        for b in range(n):
            t0 = lo + b * BUCKET_MS
            t1 = min(hi, t0 + BUCKET_MS)
            ticks = np.arange(-(-t0 // TICK_MS), -(-t1 // TICK_MS))
            vals = self.values[host, ticks]
            vals = vals[~np.isnan(vals)]
            if not len(vals):
                continue
            out["count"][b] = len(vals)
            out["sum"][b] = vals.astype(np.float64).sum()
            out["avg"][b] = out["sum"][b] / len(vals)
            out["min"][b], out["max"][b] = vals.min(), vals.max()
            out["last"][b] = vals[-1]
        return out


def point_query(host: int, lo: int, hi: int):
    spec = AggregateSpec(group_col="k", ts_col="ts", value_col="v",
                         range_start=lo, bucket_ms=BUCKET_MS,
                         num_buckets=-(-(hi - lo) // BUCKET_MS),
                         which=ALL_AGGS)
    req = ScanRequest(range=TimeRange.new(lo, hi),
                      predicate=F.Eq("k", f"host_{host}"))
    return req, spec


def check_against_numpy(got, model: Model, host: int, lo: int, hi: int):
    values, grids = got
    assert list(values) == [f"host_{host}"]
    ref = model.reference(host, lo, hi)
    occupied = ref["count"] > 0
    assert occupied.any()
    count = np.asarray(grids["count"])[0]
    assert np.array_equal(count, ref["count"])
    for a in ("min", "max", "last"):
        g = np.asarray(grids[a], dtype=np.float64)[0]
        assert np.array_equal(g[occupied], ref[a][occupied]), a
    for a in ("sum", "avg"):
        g = np.asarray(grids[a], dtype=np.float64)[0]
        err = np.abs(g[occupied] - ref[a][occupied]) / np.maximum(
            np.abs(ref[a][occupied]), 1e-30)
        assert err.max() <= 1e-5, a


def same_bytes(a, b):
    assert np.array_equal(a[0], b[0])
    assert set(a[1]) == set(b[1])
    for k in a[1]:
        assert np.asarray(a[1][k]).tobytes() == np.asarray(b[1][k]).tobytes()


def empty_caches(s, tier2=True):
    """Everything a later query could be served from; with `tier2`
    false the footers (and any part) stay, the windows and the memo of
    finished parts go: a repeated query then loads its blocks again."""
    s.reader.scan_cache.clear()
    s.reader.parts_memo.clear()
    if tier2:
        s.reader.encoded_cache.clear()


async def compact_all(s, segments=2):
    """Until every segment is one SST at rest; the scheduler's own loop
    may hold a task of its own meanwhile."""
    sched = s.compact_scheduler
    for _ in range(500):
        task = await sched.picker.pick_candidate()
        if task is not None:
            await sched.executor.execute(task)
        ssts = await s.manifest.all_ssts()
        if len(ssts) == segments and not any(f.in_compaction for f in ssts):
            return
        await asyncio.sleep(0.01)
    raise AssertionError("compaction did not come to rest")


async def load_two_segments(s, model, rng):
    """Each segment as two bodies (even and odd ticks), compacted to
    one SST a segment: a loaded store at rest."""
    hosts = np.arange(HOSTS)
    for seg in range(2):
        await s.write(model.write_request(rng, seg, hosts,
                                          np.arange(0, TICKS, 2)))
        await s.write(model.write_request(rng, seg, hosts,
                                          np.arange(1, TICKS, 2)))
    await compact_all(s)


# one part is 40 x 360 rows x 4 columns x 4 B = 230 KB: tier 2 holds one
TIER2_ONE_PART = 300_000
WINDOWS = {"one_segment": (600_000 + 1, 600_000 + 1 + 1_800_000),
           "two_segments": (SEGMENT_MS - 900_000 + 7,
                            SEGMENT_MS + 900_000 + 7)}


@pytest.mark.parametrize("shape", list(WINDOWS))
def test_point_queries_over_a_pruned_store_match_numpy(
        runtimes, small_blocks, shape):
    lo, hi = WINDOWS[shape]

    async def go():
        rng = np.random.default_rng(27)
        store, model = CountingStore(), Model(2)
        s = await open_storage(store, runtimes, cache_max_rows=4096,
                               cache={"tier2_max_bytes": TIER2_ONE_PART})
        try:
            await load_two_segments(s, model, rng)
            cache = s.reader.encoded_cache
            assert cache.evictions > 0      # the second part evicted the first
            ssts = sum(len(seg.ssts) for seg in (await s._plan_aggregate(
                *point_query(0, 0, 2 * SEGMENT_MS))).segments)
            assert ssts == 2
            dispatched = device_decode._STAGE_ROWS.value
            fetched = sidecar._LOAD_ROWS["fetched"].value
            stored = sidecar._LOAD_ROWS["stored"].value
            empty_caches(s)
            whole_gets = store.calls["get"]
            answers = {}
            for rnd in ("first", "repeated"):
                empty_caches(s, tier2=False)
                for host in (3, 17, 39):
                    req, spec = point_query(host, lo, hi)
                    plan = await s._plan_aggregate(req, spec)
                    assert plan.route == "device_decode"
                    got = await s.scan_aggregate(req, spec)
                    check_against_numpy(got, model, host, lo, hi)
                    if rnd == "repeated":
                        same_bytes(got, answers[host])
                    answers[host] = got
            assert device_decode._STAGE_ROWS.value > dispatched
            d_fetched = sidecar._LOAD_ROWS["fetched"].value - fetched
            d_stored = sidecar._LOAD_ROWS["stored"].value - stored
            assert 0 < d_fetched <= 0.15 * d_stored     # block-pruned
            assert cache.footer_hits >= 5 * len(plan.segments)
            assert store.calls["get"] == whole_gets
            # the same queries with nothing kept from earlier loads
            for host, kept in answers.items():
                empty_caches(s)
                same_bytes(await s.scan_aggregate(
                    *point_query(host, lo, hi)), kept)
            # a write into a stored segment (newer values win), then a
            # compaction into it: the old SSTs' footers go with them
            await s.write(model.write_request(rng, 0, [3, 17],
                                              np.arange(50, 250)))
            for host in (3, 17, 39):
                check_against_numpy(await s.scan_aggregate(
                    *point_query(host, lo, hi)), model, host, lo, hi)
            await compact_all(s)
            live = {f.id for seg in (await s._plan_aggregate(
                *point_query(0, 0, 2 * SEGMENT_MS))).segments
                for f in seg.ssts}
            assert len(live) == 2 and set(cache._footers) <= live
            for host in (3, 17, 39):
                got = await s.scan_aggregate(*point_query(host, lo, hi))
                check_against_numpy(got, model, host, lo, hi)
                empty_caches(s)
                same_bytes(await s.scan_aggregate(
                    *point_query(host, lo, hi)), got)
            assert cache.total_bytes == sum(
                e[2] for e in cache._entries.values()) + sum(
                f[1] for f in cache._footers.values())
        finally:
            await s.close()

    asyncio.run(go())


def test_a_block_pruned_load_is_never_kept_as_a_resident_slice(
        runtimes, small_blocks):
    """A pruned load's blocks were chosen by the range leaf too, so
    what a dispatch narrows it to belongs to one window: the scan cache
    is offered nothing, and a second query for the same host with
    another window over the same segment loads its own blocks from the
    store and is right."""
    lo, hi = WINDOWS["one_segment"]

    async def go():
        rng = np.random.default_rng(31)
        store, model = CountingStore(), Model(2)
        s = await open_storage(store, runtimes, cache_max_rows=4096,
                               cache={"tier2_max_bytes": TIER2_ONE_PART})
        try:
            await load_two_segments(s, model, rng)
            empty_caches(s)
            s.reader.parts_memo.lru.max_bytes = 0  # every query dispatches
            probes = {o: c.value
                      for o, c in device_decode._RESIDENT.items()}
            ranged = []
            for shift in (0, 300_000):
                before = store.calls["get_range"]
                got = await s.scan_aggregate(
                    *point_query(17, lo + shift, hi + shift))
                check_against_numpy(got, model, 17, lo + shift, hi + shift)
                ranged.append(store.calls["get_range"] - before)
            assert {o: c.value - probes[o]
                    for o, c in device_decode._RESIDENT.items()} \
                == {"hit": 0, "miss": 2, "bypass": 0}
            assert all(n > 0 for n in ranged), ranged
            assert s.reader.cache_stats()["scan_cache"]["entries"] == 0
            assert len(s.reader.encoded_cache) == 0  # no whole part either
        finally:
            await s.close()

    asyncio.run(go())


def test_a_second_pruned_load_pays_its_column_ranges_only(
        runtimes, small_blocks):
    async def go():
        rng = np.random.default_rng(5)
        store, model = CountingStore(), Model(2)
        s = await open_storage(store, runtimes, cache_max_rows=4096,
                               cache={"tier2_max_bytes": TIER2_ONE_PART})
        try:
            await load_two_segments(s, model, rng)
            empty_caches(s)
            lo, hi = WINDOWS["one_segment"]
            cache = s.reader.encoded_cache
            c0, _ = store.snapshot()
            await s.scan_aggregate(*point_query(3, lo, hi))
            c1, b1 = store.snapshot()
            await s.scan_aggregate(*point_query(30, lo, hi))
            c2, b2 = store.snapshot()
            first = c1["get_range"] - c0["get_range"]
            second = c2["get_range"] - c1["get_range"]
            # k, ts, v, __seq__: one range each of one or two blocks;
            # the first load also probed the head and fetched the
            # statistics of k and ts and the dictionaries of k, __seq__
            assert second == 4 and first >= second + 4
            assert (b2["get_range"] - b1["get_range"]
                    <= 4 * 4 * 2 * sidecar.BLOCK_ROWS)
            assert (cache.footer_misses, cache.footer_hits) == (1, 1)
            assert cache.stats()["footers"] == 1
            assert c2["get"] == c0["get"]
            # nothing of a pruned load is admitted as a part
            assert len(cache) == 0
            hist = sidecar._LOAD_SECONDS
            assert hist["columns"].count >= 8 and hist["head"].count >= 1
        finally:
            await s.close()

    asyncio.run(go())


def test_a_store_that_fits_its_caches_makes_the_parents_calls(
        runtimes, small_blocks):
    """Default budgets: the compactor's write-through leaves both parts
    in tier 2, the queries read nothing from the store and never probe
    a footer.  Six dispatches as on PR 26's tree; since PR 29 the two
    that meet a segment a host's earlier window already narrowed and
    uploaded run from the slice resident on the device: no tier-2
    read and no upload for those."""
    async def go():
        rng = np.random.default_rng(9)
        store, model = CountingStore(), Model(2)
        s = await open_storage(store, runtimes)
        try:
            await load_two_segments(s, model, rng)
            cache = s.reader.encoded_cache
            assert (len(cache), cache.evictions) == (2, 0)
            hits = cache.hits
            rows = {side: c.value
                    for side, c in device_decode._DECODE_ROWS.items()}
            probes = {o: c.value
                      for o, c in device_decode._RESIDENT.items()}
            for shape, (lo, hi) in WINDOWS.items():
                for host in (3, 17):
                    got = await s.scan_aggregate(
                        *point_query(host, lo, hi))
                    check_against_numpy(got, model, host, lo, hi)
            assert store.snapshot() == ({"get": 0, "get_range": 0},
                                        {"get": 0, "get_range": 0})
            # 2 x 1 + 2 x 2 segments; segment 0 of the second window
            # is resident for either host
            assert {o: c.value - probes[o]
                    for o, c in device_decode._RESIDENT.items()} \
                == {"hit": 2, "miss": 4, "bypass": 0}
            assert cache.hits - hits == 4
            assert (cache.footer_hits, cache.footer_misses) == (0, 0)
            assert cache.stats()["footers"] == 0
            # six dispatches, each planned over a whole segment of
            # tier 2 and handed its host's rows of it: four uploaded
            # them, two found them on the device
            moved = {side: c.value - rows[side]
                     for side, c in device_decode._DECODE_ROWS.items()}
            assert moved == {"stored": 6 * HOSTS * TICKS,
                             "uploaded": 4 * TICKS}
        finally:
            await s.close()

    asyncio.run(go())


def _footer(n_sections: int, size: int = 100) -> sidecar.SstFooter:
    return sidecar.SstFooter(
        {"n_rows": 1, "sections": [], "columns": []}, 64, 40,
        sections={(i, size): b"x" * size for i in range(n_sections)})


def test_footers_are_charged_evicted_last_and_dropped_with_the_sst():
    cache = EncodedSegmentCache(max_bytes=10_000)
    part = {"c": (np.zeros(1000, dtype=np.int32), None)}    # 4,000 B
    assert cache.get_footer(1) is None and cache.footer_misses == 1
    cache.put_footer(1, _footer(2))
    assert cache.total_bytes == 240 and cache.get_footer(1) is not None
    # a put of the same footer, grown by a load, replaces its charge
    grown = cache.get_footer(1)
    grown.sections[(9, 100)] = b"y" * 100
    cache.put_footer(1, grown)
    assert cache.total_bytes == 340 and cache.stats()["footers"] == 1
    cache.put(1, part, 1000)
    cache.put(2, part, 1000)
    cache.put_footer(2, _footer(1))
    assert cache.total_bytes == 8_000 + 340 + 140 and cache.evictions == 0
    # over the budget: the oldest PART goes, both footers stay
    cache.put(3, part, 1000)
    assert cache.evictions == 1 and cache.peek(1, {"c"}) is False
    assert cache.stats()["footers"] == 2
    assert cache.total_bytes == 8_000 + 480
    # an SST deleted by a compaction takes its footer along
    assert cache.invalidate([2]) == 1
    assert cache.stats()["footers"] == 1 and cache.total_bytes == 4_340
    cache.clear()
    assert cache.total_bytes == 0 and cache.stats()["footers"] == 0
    assert cache.get_footer(1) is None


def test_footers_alone_stay_within_the_budget_and_off_when_disabled():
    cache = EncodedSegmentCache(max_bytes=500)
    for sst in range(4):
        cache.put_footer(sst, _footer(1))           # 140 B each
    assert cache.stats()["footers"] == 3 and cache.total_bytes == 420
    assert cache.get_footer(0) is None              # the oldest went
    cache.put_footer(9, _footer(10))                # 1,040 B: never kept
    assert cache.get_footer(9) is None and cache.total_bytes <= 500
    off = EncodedSegmentCache(max_bytes=0)
    off.put_footer(1, _footer(1))
    assert off.get_footer(1) is None and off.total_bytes == 0
    # off the process-global scan_cache_bytes{tier="tier2"} gauge again
    cache.clear()
