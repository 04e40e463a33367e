"""Device-native decode tests (ISSUE 12): the fused sidecar-decode +
filter + merge-dedup + bucket-aggregate dispatch (ops/device_decode.py)
byte-compared against the host-decode control across agg sets, filters,
ranges, top-k, and seeded write/flush/compact/evict interleavings, plus
per-reason fallback counters, `[scan.decode]` config plumbing, and the
decode-seam lint rule.

The seeded chaos test rides `make chaos` with knobs DECODE_SEED /
DECODE_SCHEDULES; the fast tier-1 variant runs a fixed small subset.
Both legs force HORAEDB_HOST_AGG=0 so the control aggregates with the
same XLA window kernel the fused dispatch calls — the A/B then isolates
exactly WHERE decode/filter/merge ran, which is the bit-identity claim
(the numpy f64 twin is a different rounding schedule by design, same as
the fused-aggregate precedent)."""

import asyncio
import os
import random

import numpy as np
import pyarrow as pa
import pytest

from horaedb_tpu.common import ReadableDuration, deviceprof
from horaedb_tpu.common import runtimes as runtimes_mod
from horaedb_tpu.common.error import Error
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.ops import device_decode, encode
from horaedb_tpu.ops import filter as F
from horaedb_tpu.ops.downsample import ALL_AGGS
from horaedb_tpu.storage.config import (
    StorageConfig,
    ThreadsConfig,
    from_dict,
)
from horaedb_tpu.storage.plan import TopKSpec
from horaedb_tpu.storage.read import AggregateSpec, ScanRequest
from horaedb_tpu.storage.storage import CloudObjectStorage, WriteRequest
from horaedb_tpu.storage.types import TimeRange
from horaedb_tpu.utils import registry

SEED = int(os.environ.get("DECODE_SEED", "1337"), 0)
SCHEDULES = int(os.environ.get("DECODE_SCHEDULES", "20"), 0)

SEGMENT_MS = 3_600_000
SCHEMA = pa.schema([("k", pa.string()), ("ts", pa.int64()),
                    ("v", pa.float64())])

WHICH_SETS = (("avg",), ("min", "max"), ("count",), ("sum", "avg"),
              ("last",), ("avg", "max", "last"), ALL_AGGS)


@pytest.fixture(scope="module")
def runtimes():
    rt = runtimes_mod.from_config(ThreadsConfig())
    yield rt
    rt.close()


def run(coro):
    return asyncio.run(coro)


def batch(rows):
    k, t, v = zip(*rows)
    return pa.record_batch(
        [pa.array(list(k)), pa.array(list(t), type=pa.int64()),
         pa.array(list(v), type=pa.float64())], schema=SCHEMA)


def wreq(rows):
    lo = min(r[1] for r in rows)
    hi = max(r[1] for r in rows) + 1
    return WriteRequest(batch(rows), TimeRange.new(lo, hi))


def storage_config(**scan):
    cfg = from_dict(StorageConfig, {
        "scheduler": {"schedule_interval": "1h", "input_sst_min_num": 2},
        "scan": scan,
    })
    cfg.manifest.merge_interval = ReadableDuration.parse("1h")
    cfg.scrub.interval = ReadableDuration.parse("1h")
    return cfg


async def open_storage(store, runtimes, **scan):
    return await CloudObjectStorage.open(
        "db", SEGMENT_MS, store, SCHEMA, 2,
        storage_config(**scan), runtimes=runtimes)


def agg_spec(lo: int, hi: int, bucket_ms: int = 60_000,
             which=("avg", "max", "last")) -> AggregateSpec:
    return AggregateSpec(group_col="k", ts_col="ts", value_col="v",
                         range_start=lo, bucket_ms=bucket_ms,
                         num_buckets=max(1, -(-(hi - lo) // bucket_ms)),
                         which=which)


async def write_segments(s, rng, segments=3, rows_per=150, keys=6):
    for seg in range(segments):
        rows = [(f"k{rng.randint(0, keys - 1)}",
                 seg * SEGMENT_MS + rng.randrange(0, SEGMENT_MS - 1000,
                                                  250),
                 float(rng.randint(0, 10**6))) for _ in range(rows_per)]
        await s.write(wreq(rows))


def clear_caches(s, memo=True):
    s.reader.scan_cache.clear()
    s.reader.encoded_cache.clear()
    if memo:
        s.reader.parts_memo.clear()


def _assert_same(a, b, ctx=""):
    va, ga = a
    vb, gb = b
    assert np.array_equal(va, vb), f"{ctx}: group values differ"
    assert set(ga) == set(gb), f"{ctx}: agg keys {set(ga)} != {set(gb)}"
    for k in ga:
        assert np.asarray(ga[k]).tobytes() == np.asarray(gb[k]).tobytes(), \
            f"{ctx}: grid {k!r} differs"


def fallback_count(reason: str) -> float:
    return device_decode._FALLBACK_CHILDREN[reason].value


class _ForceXlaAgg:
    """Force HORAEDB_HOST_AGG=0 for a block: the host-decode control
    then aggregates with the same XLA window kernel the fused dispatch
    calls, isolating decode/filter/merge location (see module doc)."""

    def __enter__(self):
        self._old = os.environ.get("HORAEDB_HOST_AGG")
        os.environ["HORAEDB_HOST_AGG"] = "0"

    def __exit__(self, *exc):
        if self._old is None:
            os.environ.pop("HORAEDB_HOST_AGG", None)
        else:
            os.environ["HORAEDB_HOST_AGG"] = self._old


def decode_rows() -> float:
    from horaedb_tpu.ops.device_decode import _STAGE_ROWS

    return _STAGE_ROWS.value


# ---------------------------------------------------------------------------
# direct bit-identity + routing
# ---------------------------------------------------------------------------


def test_device_vs_host_bit_identity_basic(runtimes):
    """Overlapping writes (cross-SST duplicate PKs exercising the
    device dedup), every agg set, filters incl. In/range, top-k: the
    device leg must routinely serve segments from the fused dispatch
    (stage counter moves) and every grid must byte-match host decode."""
    async def go():
        rng = random.Random(SEED)
        s = await open_storage(MemoryObjectStore(), runtimes,
                               decode={"mode": "device"})
        try:
            await write_segments(s, rng, segments=2, rows_per=200)
            # duplicate PKs across SSTs: same keys re-written
            await s.write(wreq([("k0", 100, 7.0), ("k1", 350, 8.0)]))
            await s.write(wreq([("k0", 100, 9.0), ("k2", 600, 1.0)]))
            preds = (None, F.Eq("k", "k1"), F.In("k", ["k0", "k4"]),
                     F.And((F.Ge("ts", 1000), F.Lt("ts", SEGMENT_MS))),
                     F.Eq("k", "nope"))
            with _ForceXlaAgg():
                for which in WHICH_SETS:
                    for pred in preds:
                        spec = agg_spec(0, 2 * SEGMENT_MS, which=which)
                        req = ScanRequest(
                            range=TimeRange.new(0, 2 * SEGMENT_MS),
                            predicate=pred)
                        before = decode_rows()
                        clear_caches(s)
                        s.config.scan.decode.mode = "device"
                        dev = await s.scan_aggregate(req, spec)
                        if pred != F.Eq("k", "nope"):
                            assert decode_rows() > before, \
                                "device route did not engage"
                        clear_caches(s)
                        s.config.scan.decode.mode = "host"
                        host = await s.scan_aggregate(req, spec)
                        _assert_same(dev, host, f"{which} {pred}")
                        s.config.scan.decode.mode = "device"
                # top-k pushdown over device parts
                tk = TopKSpec(k=2, by="max")
                spec = agg_spec(0, 2 * SEGMENT_MS, which=("max", "avg"))
                req = ScanRequest(range=TimeRange.new(0, 2 * SEGMENT_MS))
                clear_caches(s)
                dev = await s.scan_aggregate(req, spec, top_k=tk)
                clear_caches(s)
                s.config.scan.decode.mode = "host"
                host = await s.scan_aggregate(req, spec, top_k=tk)
                _assert_same(dev, host, "top-k")
        finally:
            await s.close()

    run(go())


def test_streamed_segments_device_decode(runtimes):
    """Segments over the stream threshold serve window-by-window; the
    deferred window-range leaves keep device windows exactly disjoint
    (cross-window dedup correctness) and grids byte-match host."""
    async def go():
        rng = random.Random(SEED + 1)
        s = await open_storage(
            MemoryObjectStore(), runtimes,
            decode={"mode": "device"},
            stream_read_min_rows=64, max_window_rows=128)
        try:
            await write_segments(s, rng, segments=2, rows_per=400)
            # overlapping rewrite so streamed windows must dedup
            await write_segments(s, rng, segments=2, rows_per=100)
            spec = agg_spec(0, 2 * SEGMENT_MS, which=("avg", "last"))
            req = ScanRequest(range=TimeRange.new(0, 2 * SEGMENT_MS))
            with _ForceXlaAgg():
                before = decode_rows()
                clear_caches(s)
                dev = await s.scan_aggregate(req, spec)
                assert decode_rows() > before
                clear_caches(s)
                s.config.scan.decode.mode = "host"
                host = await s.scan_aggregate(req, spec)
            _assert_same(dev, host, "streamed")
        finally:
            await s.close()

    run(go())


def test_sort_free_routing_counted(runtimes):
    """Compaction-aware sort-free routing (ISSUE 15 satellite, k-way
    merge ISSUE 19): single-SST segments route past the device lax.sort
    AND the host sortedness check ((pk, seq)-sorted by construction),
    multi-SST segments that check sorted skip the sort too, and
    interleaved ones with known per-run boundaries take the device
    k-way merge (route="kway") — the full sort survives only as the
    counted fallback — each per segment on scan_decode_sort_*_total."""

    def counts():
        return (device_decode._SORT_SKIPPED["compacted"].value,
                device_decode._SORT_SKIPPED["checked"].value,
                device_decode._SORT_SKIPPED["kway"].value,
                device_decode._SORT_RAN.value)

    async def go():
        rng = random.Random(SEED + 3)
        s = await open_storage(MemoryObjectStore(), runtimes,
                               decode={"mode": "device"})
        try:
            with _ForceXlaAgg():
                # segment 0: one SST -> compacted route, no check
                await write_segments(s, rng, segments=1, rows_per=120)
                spec = agg_spec(0, SEGMENT_MS, which=("avg",))
                req = ScanRequest(range=TimeRange.new(0, SEGMENT_MS))
                c0 = counts()
                clear_caches(s)
                await s.scan_aggregate(req, spec)
                c1 = counts()
                assert c1[0] == c0[0] + 1 and c1[3] == c0[3]
                # overlapping second SST with interleaving PK ranges:
                # the concat is unsorted -> the per-SST runs k-way
                # merge on device; the full sort does NOT run
                await s.write(wreq([("k0", 10, 1.0), ("k5", 20, 2.0)]))
                clear_caches(s)
                await s.scan_aggregate(req, spec)
                c2 = counts()
                assert c2[2] == c1[2] + 1, (c1, c2)
                assert c2[3] == c1[3], (c1, c2)
                # disjoint-PK second write CAN still concat sorted —
                # whichever way it lands, routed-vs-sorted must sum to
                # one more segment dispatch
                assert sum(c2) == sum(c1) + 1
        finally:
            await s.close()

    run(go())


# ---------------------------------------------------------------------------
# narrowing: a segment cut to its key leaves' rows before the upload
# ---------------------------------------------------------------------------

# the data table's key shape: (metric, series, field, timestamp)
NARROW_SCHEMA = pa.schema([("m", pa.string()), ("host", pa.string()),
                           ("field", pa.string()), ("ts", pa.int64()),
                           ("v", pa.float64())])
NARROW_HOSTS, NARROW_FIELDS, NARROW_TICKS = 6, 4, 40


def narrow_rows(rng, ticks, base=0.0):
    """One run of the segment: every host x field x tick, except that
    the last host reports its last field only."""
    rows = []
    for h in range(NARROW_HOSTS):
        for f in range(NARROW_FIELDS):
            if h == NARROW_HOSTS - 1 and f != NARROW_FIELDS - 1:
                continue
            for t in ticks:
                rows.append(("cpu", f"h{h}", f"f{f}", t * 60_000 + 7,
                             base + float(rng.randint(0, 10**6))))
    return rows


def narrow_wreq(rows):
    cols = list(zip(*rows))
    rb = pa.record_batch(
        [pa.array(list(c), type=f.type)
         for c, f in zip(cols, NARROW_SCHEMA)], schema=NARROW_SCHEMA)
    return WriteRequest(rb, TimeRange.new(min(cols[3]), max(cols[3]) + 1))


def narrow_writes(route: str) -> list:
    """The row lists open_narrow_storage writes, one a run."""
    rng = random.Random(SEED + 7)
    writes = [narrow_rows(rng, range(NARROW_TICKS))]
    if route != "presorted":
        # rewrites of every fourth tick (keep-last must win) and ticks
        # the first run lacks: the concatenation is unsorted
        writes.append(narrow_rows(
            rng, list(range(0, NARROW_TICKS, 4))
            + list(range(NARROW_TICKS, NARROW_TICKS + 8)), base=0.5))
    return writes


async def open_narrow_storage(runtimes, route: str):
    """One segment that takes `route`: one SST for `presorted`, two
    interleaved ones with duplicate keys across them otherwise."""
    s = await CloudObjectStorage.open(
        "db", SEGMENT_MS, MemoryObjectStore(), NARROW_SCHEMA, 4,
        storage_config(decode={"mode": "device"}), runtimes=runtimes)
    for rows in narrow_writes(route):
        await s.write(narrow_wreq(rows))
    return s


def narrow_spec(lo, hi, which=ALL_AGGS):
    return AggregateSpec(group_col="host", ts_col="ts", value_col="v",
                         range_start=lo, bucket_ms=300_000,
                         num_buckets=-(-(hi - lo) // 300_000),
                         which=which)


class _PlanSpy:
    """Records (segment handed in, what plan_dispatch made of it)."""

    def __init__(self, monkeypatch):
        self.calls = []
        real = device_decode.plan_dispatch

        def spy(es, *a, **kw):
            got = real(es, *a, **kw)
            self.calls.append((es, got))
            return got

        monkeypatch.setattr(device_decode, "plan_dispatch", spy)

    def plans(self):
        return [(es, got) for es, got in self.calls
                if isinstance(got, device_decode.DecodePlan)]


def decode_row_sides():
    return (device_decode._DECODE_ROWS["stored"].value,
            device_decode._DECODE_ROWS["uploaded"].value)


def route_counts():
    return {"presorted": device_decode._SORT_SKIPPED["compacted"].value
            + device_decode._SORT_SKIPPED["checked"].value,
            "kway": device_decode._SORT_SKIPPED["kway"].value,
            "sorted": device_decode._SORT_RAN.value}


MID = (SEGMENT_MS // 4 + 7, 3 * SEGMENT_MS // 4 + 7)
# leaves -> (predicate, scan range, whether the upload shrinks;
#            None = no dispatch at all)
NARROW_LEAVES = {
    "eq_field": (F.Eq("field", "f1"), (0, SEGMENT_MS), True),
    "eq_field_in_tsid": (F.And((F.Eq("field", "f1"),
                                F.In("host", ["h0", "h3", "zz"]))),
                         (0, SEGMENT_MS), True),
    "eq_field_time_range": (F.And((F.Eq("field", "f1"),
                                   F.TimeRangePred("ts", *MID))),
                            MID, True),
    "eq_keeps_everything": (F.Eq("m", "cpu"), (0, SEGMENT_MS), False),
    "eq_absent_from_dictionary": (F.Eq("field", "nope"),
                                  (0, SEGMENT_MS), None),
    "eq_zero_rows_of_present_codes": (
        F.And((F.Eq("field", "f1"), F.Eq("host", "h5"))),
        (0, SEGMENT_MS), None),
}


@pytest.mark.parametrize("leaves", sorted(NARROW_LEAVES))
@pytest.mark.parametrize("route", ["presorted", "kway", "sorted"])
def test_narrowed_dispatch_is_byte_equal(runtimes, monkeypatch, route,
                                         leaves):
    """A dispatch narrowed on host to its Eq/In leaves' rows gives the
    grids of the same dispatch un-narrowed (every stored row uploaded
    and masked on the device) and of the host-decode control, on every
    route."""
    pred, (lo, hi), shrinks = NARROW_LEAVES[leaves]
    if route == "sorted":  # decline the k-way merge: the full sort runs
        monkeypatch.setattr(device_decode, "_KWAY_MAX_RUNS", 1)

    async def go():
        s = await open_narrow_storage(runtimes, route)
        try:
            spec = narrow_spec(lo, hi)
            req = ScanRequest(range=TimeRange.new(lo, hi), predicate=pred)
            with _ForceXlaAgg():
                clear_caches(s)
                sides0, routes0 = decode_row_sides(), route_counts()
                narrowed = await s.scan_aggregate(req, spec)
                sides1, routes1 = decode_row_sides(), route_counts()
                with monkeypatch.context() as m:
                    m.setattr(device_decode, "_narrow_to_key_leaves",
                              lambda es, *_a: es)
                    clear_caches(s)
                    whole = await s.scan_aggregate(req, spec)
                sides2 = decode_row_sides()
                clear_caches(s)
                s.config.scan.decode.mode = "host"
                host = await s.scan_aggregate(req, spec)
            stored = sides1[0] - sides0[0]
            uploaded = sides1[1] - sides0[1]
            if shrinks is None:
                assert (stored, uploaded) == (0, 0)
                assert routes1 == routes0
            else:
                assert stored > 0
                assert (uploaded < stored) == shrinks
                assert routes1[route] == routes0[route] + 1, \
                    (routes0, routes1)
            if leaves != "eq_absent_from_dictionary":
                # the control leg dispatched every stored row
                assert sides2[0] - sides1[0] == sides2[1] - sides1[1] > 0
            _assert_same(narrowed, whole, f"{route} {leaves} un-narrowed")
            _assert_same(narrowed, host, f"{route} {leaves} host")
            if shrinks is not None:
                assert len(narrowed[0]) > 0
        finally:
            await s.close()

    run(go())


def test_capacity_follows_key_leaves_not_the_window(runtimes, monkeypatch):
    """Two windows at different offsets over one segment, the same
    Eq leaf: range leaves stay on the device, so both plans have one
    capacity and the second compiles nothing."""
    spy = _PlanSpy(monkeypatch)

    async def go():
        s = await open_narrow_storage(runtimes, "presorted")
        try:
            sizes = []
            with _ForceXlaAgg():
                for off in (0, 11 * 60_000):
                    lo, hi = MID[0] + off, MID[1] + off
                    req = ScanRequest(
                        range=TimeRange.new(lo, hi),
                        predicate=F.And((F.Eq("field", "f2"),
                                         F.TimeRangePred("ts", lo, hi))))
                    clear_caches(s)
                    await s.scan_aggregate(req, narrow_spec(
                        lo, hi, which=("avg",)))
                    sizes.append(
                        device_decode._decode_aggregate_jit._cache_size())
            return sizes
        finally:
            await s.close()

    sizes = run(go())
    (es_a, plan_a), (es_b, plan_b) = spy.plans()
    assert plan_a.cap == plan_b.cap < encode.pad_capacity(es_a.n)
    assert plan_a.es.n == plan_b.es.n  # the field's rows, whatever the window
    assert plan_a.consts[1].tolist() != plan_b.consts[1].tolist()
    assert sizes[1] == sizes[0]


def test_decode_rows_counter_and_unselective_plan(runtimes, monkeypatch):
    """scan_decode_rows_total counts each planned dispatch's stored and
    uploaded rows; a plan with no Eq/In leaf, or one that stays in its
    capacity bucket, reports equal sides and uploads the arrays it was
    handed."""
    spy = _PlanSpy(monkeypatch)

    async def go():
        s = await open_narrow_storage(runtimes, "presorted")
        try:
            out = []
            with _ForceXlaAgg():
                for pred in (F.Eq("field", "f0"), None,
                             F.In("field", ["f0", "f1", "f2"])):
                    req = ScanRequest(range=TimeRange.new(0, SEGMENT_MS),
                                      predicate=pred)
                    before = decode_row_sides()
                    rows0 = decode_rows()
                    clear_caches(s)
                    await s.scan_aggregate(
                        req, narrow_spec(0, SEGMENT_MS, which=("max",)))
                    after = decode_row_sides()
                    out.append((after[0] - before[0],
                                after[1] - before[1],
                                decode_rows() - rows0))
            return out
        finally:
            await s.close()

    selective, no_leaf, same_bucket = run(go())
    (es0, p0), (es1, p1), (es2, p2) = spy.plans()
    stored = es0.n
    per_field = (NARROW_HOSTS - 1) * NARROW_TICKS
    assert stored == per_field * NARROW_FIELDS + NARROW_TICKS
    # ops-metric parity: the stage's rows stay the stored rows
    assert selective == (stored, per_field, stored)
    assert p0.es is not es0 and p0.es.n == per_field
    assert p0.src_rows == p0.n_valid == stored
    assert all(len(a) == per_field for a in p0.es.columns.values())
    for got, (es, plan) in ((no_leaf, (es1, p1)),
                            (same_bucket, (es2, p2))):
        assert got == (stored, stored, stored)
        assert plan.es is es and plan.cap == encode.pad_capacity(stored)
    assert 'scan_decode_rows_total{side="uploaded"}' in registry.render()


# ---------------------------------------------------------------------------
# resident slices: what a miss narrowed and uploaded stays on the device
# ---------------------------------------------------------------------------


def resident_outcomes():
    return {o: c.value for o, c in device_decode._RESIDENT.items()}


def moved(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def batch_counts() -> dict:
    """Slices by how they reached the device, and batched calls."""
    return {**{m: c.value for m, c in device_decode._BATCH_SLICES.items()},
            "calls": device_decode._BATCH_CALLS.value}


def compiles_so_far() -> int:
    """What `device.compiles_in_window` reads: the compile ledger."""
    return sum(f["compiles"] for f in deviceprof.profiler.snapshot()["fns"])


def field_query(lo, hi, field="f1", which=ALL_AGGS):
    """One field's rows of the window [lo, hi): the data table's query
    shape, a key leaf and a range leaf."""
    pred = F.And((F.Eq("field", field), F.TimeRangePred("ts", lo, hi)))
    return (ScanRequest(range=TimeRange.new(lo, hi), predicate=pred),
            narrow_spec(lo, hi, which=which))


async def host_control(s, req, spec):
    s.config.scan.decode.mode = "host"
    try:
        clear_caches(s)
        return await s.scan_aggregate(req, spec)
    finally:
        s.config.scan.decode.mode = "device"
        clear_caches(s)


def memo_off(s):
    """Every query dispatches: the parts memo serves none."""
    s.reader.parts_memo.lru.max_bytes = 0


# two windows over the one segment, at different offsets
WINDOW_A = (SEGMENT_MS // 8 + 7, 5 * SEGMENT_MS // 8 + 7)
WINDOW_B = (3 * SEGMENT_MS // 8 + 11, 7 * SEGMENT_MS // 8 + 11)


@pytest.mark.parametrize("route", ["presorted", "kway"])
def test_resident_hit_is_the_miss_byte_for_byte(runtimes, route):
    """What a miss narrowed, padded and uploaded is kept by the scan
    cache; a query with the same key leaves and ANOTHER window
    dispatches from it: no tier-2 read, no upload, no compile, and the
    grids a miss gives for that window, byte for byte."""
    async def go():
        s = await open_narrow_storage(runtimes, route)
        try:
            memo_off(s)
            with _ForceXlaAgg():
                clear_caches(s)
                tier2 = s.reader.encoded_cache
                c0, r0 = resident_outcomes(), decode_row_sides()
                miss_a = await s.scan_aggregate(*field_query(*WINDOW_A))
                c1, r1 = resident_outcomes(), decode_row_sides()
                assert moved(c0, c1) == {"hit": 0, "miss": 1, "bypass": 0}
                # the slice: one field's rows in their capacity bucket,
                # six 4-byte columns, charged to the slices' account
                # and reported by its ledger account as it stands
                kept = r1[1] - r0[1]
                stats = s.reader.cache_stats()["scan_cache"]
                nbytes = encode.pad_capacity(int(kept)) * 4 * 6
                assert 0 < kept < r1[0] - r0[0]
                assert (stats["entries"], stats["decode_slices"]) == (1, 1)
                assert stats["bytes"] == stats["decode_slice_bytes"] \
                    == s.reader.scan_cache.slice_account.total_bytes \
                    == nbytes
                assert s.reader._scan_cache_resident_bytes() == 0
                programs = device_decode._decode_aggregate_jit._cache_size()
                compiles, reads = compiles_so_far(), tier2.hits
                h2d = deviceprof.profiler.snapshot()["transfer"]["h2d"]
                hit_b = await s.scan_aggregate(*field_query(*WINDOW_B))
                hit_a = await s.scan_aggregate(*field_query(*WINDOW_A))
                c2, r2 = resident_outcomes(), decode_row_sides()
                assert moved(c1, c2) == {"hit": 2, "miss": 0, "bypass": 0}
                # stored counts as before, nothing crossed to the device
                assert r2[0] - r1[0] == 2 * (r1[0] - r0[0])
                assert r2[1] == r1[1]
                assert deviceprof.profiler.snapshot()["transfer"]["h2d"] \
                    == h2d
                assert tier2.hits == reads
                assert compiles_so_far() == compiles
                assert device_decode._decode_aggregate_jit._cache_size() \
                    == programs
                s.reader.scan_cache.clear()
                miss_b = await s.scan_aggregate(*field_query(*WINDOW_B))
                assert resident_outcomes()["miss"] == c2["miss"] + 1
                host_b = await host_control(s, *field_query(*WINDOW_B))
            _assert_same(hit_a, miss_a, f"{route} window A hit-vs-miss")
            _assert_same(hit_b, miss_b, f"{route} window B hit-vs-miss")
            _assert_same(hit_b, host_b, f"{route} window B hit-vs-host")
            assert len(hit_b[0]) > 0
            assert hit_a[1]["sum"].tobytes() != hit_b[1]["sum"].tobytes()
        finally:
            await s.close()

    run(go())


def test_resident_slice_misses_after_a_write_and_after_a_compaction(
        runtimes):
    """The SST ids are the key: a write into the segment (one more
    SST) and a compaction of it (one new SST for all) each miss, and
    answer with the rows the segment then holds."""
    async def go():
        s = await open_narrow_storage(runtimes, "presorted")
        try:
            memo_off(s)
            rng = random.Random(SEED + 11)
            answers = []

            async def hit_after_miss(what):
                c0 = resident_outcomes()
                first = await s.scan_aggregate(*field_query(*WINDOW_A))
                c1 = resident_outcomes()
                assert moved(c0, c1) == {"hit": 0, "miss": 1, "bypass": 0}, \
                    what
                again = await s.scan_aggregate(*field_query(*WINDOW_A))
                assert moved(c1, resident_outcomes()) \
                    == {"hit": 1, "miss": 0, "bypass": 0}, what
                _assert_same(first, again, what)
                host = await host_control(s, *field_query(*WINDOW_A))
                _assert_same(first, host, f"{what} vs host")
                answers.append(first)

            with _ForceXlaAgg():
                clear_caches(s)
                await hit_after_miss("as loaded")
                await s.scan_aggregate(*field_query(*WINDOW_A))  # resident
                # rewrites of every fourth tick: keep-last must show
                await s.write(narrow_wreq(narrow_rows(
                    rng, list(range(0, NARROW_TICKS, 4)), base=0.25)))
                await hit_after_miss("after a write")
                await s.scan_aggregate(*field_query(*WINDOW_A))  # resident
                task = await s.compact_scheduler.picker.pick_candidate()
                await s.compact_scheduler.executor.execute(task)
                assert len(await s.manifest.all_ssts()) == 1
                await hit_after_miss("after a compaction")
            loaded, written, compacted = answers
            assert loaded[1]["last"].tobytes() \
                != written[1]["last"].tobytes()
            _assert_same(written, compacted, "a compaction moves no value")
        finally:
            await s.close()

    run(go())


async def write_second_segment(s, rng):
    rows = [(m, h, f, ts + SEGMENT_MS, v)
            for m, h, f, ts, v in narrow_rows(rng, range(NARROW_TICKS))]
    await s.write(narrow_wreq(rows))


@pytest.mark.parametrize("budget", ["one_slice", "no_slice"])
def test_resident_slices_evict_under_a_small_budget_and_stay_correct(
        runtimes, budget):
    """Two segments' slices under a scan-cache budget that holds one
    of them, or none: LRU eviction (or a declined put) sends the next
    query down the miss path, which answers as the host does."""
    slice_bytes = encode.pad_capacity(
        (NARROW_HOSTS - 1) * NARROW_TICKS) * 4 * 6
    max_bytes = {"one_slice": slice_bytes + 100,
                 "no_slice": slice_bytes - 100}[budget]

    async def go():
        s = await CloudObjectStorage.open(
            "db", SEGMENT_MS, MemoryObjectStore(), NARROW_SCHEMA, 4,
            storage_config(decode={"mode": "device"},
                           cache_max_bytes=max_bytes), runtimes=runtimes)
        try:
            memo_off(s)
            rng = random.Random(SEED + 7)
            await s.write(narrow_wreq(narrow_rows(rng,
                                                  range(NARROW_TICKS))))
            await write_second_segment(s, rng)
            lo, hi = WINDOW_A[0], SEGMENT_MS + WINDOW_A[1]
            evictions = registry.family("scan_cache_evictions_total") \
                .labels(tier="hbm")
            with _ForceXlaAgg():
                clear_caches(s)
                c0, e0 = resident_outcomes(), evictions.value
                first = await s.scan_aggregate(*field_query(lo, hi))
                second = await s.scan_aggregate(
                    *field_query(lo + 60_000, hi + 60_000))
                stats = s.reader.cache_stats()["scan_cache"]
                got = moved(c0, resident_outcomes())
                if budget == "one_slice":
                    # each admission evicts the other segment's slice.
                    # The second query finds segment 1's, and holds it
                    # through the eviction that segment 0's re-admission
                    # causes while it is still in flight
                    assert stats["decode_slices"] == 1
                    assert stats["bytes"] == slice_bytes <= max_bytes
                    assert evictions.value - e0 == 2
                    assert got == {"hit": 1, "miss": 3, "bypass": 0}
                else:
                    assert (stats["decode_slices"], stats["bytes"]) == (0, 0)
                    assert evictions.value == e0
                    assert got == {"hit": 0, "miss": 4, "bypass": 0}
                host = await host_control(
                    s, *field_query(lo + 60_000, hi + 60_000))
            _assert_same(second, host, f"{budget} second query vs host")
            assert len(first[0]) > 0
        finally:
            await s.close()

    run(go())


def test_streamed_windows_are_never_admitted(runtimes):
    """A segment read window by window is never whole in one piece
    (its rows came by synthetic range leaves): no slice is kept, a
    second query over the same key reads again and is right."""
    async def go():
        s = await CloudObjectStorage.open(
            "db", SEGMENT_MS, MemoryObjectStore(), NARROW_SCHEMA, 4,
            storage_config(decode={"mode": "device"},
                           stream_read_min_rows=64, max_window_rows=128),
            runtimes=runtimes)
        try:
            memo_off(s)
            rng = random.Random(SEED + 7)
            await s.write(narrow_wreq(narrow_rows(rng,
                                                  range(NARROW_TICKS))))
            with _ForceXlaAgg():
                clear_caches(s)
                c0, r0 = resident_outcomes(), decode_row_sides()
                await s.scan_aggregate(*field_query(*WINDOW_A))
                r1 = decode_row_sides()
                got = await s.scan_aggregate(*field_query(*WINDOW_B))
                r2 = decode_row_sides()
                assert moved(c0, resident_outcomes()) \
                    == {"hit": 0, "miss": 2, "bypass": 0}
                assert s.reader.cache_stats()["scan_cache"]["entries"] == 0
                assert r2[1] - r1[1] == r1[1] - r0[1] > 0  # uploaded again
                host = await host_control(s, *field_query(*WINDOW_B))
            _assert_same(got, host, "streamed, second window vs host")
        finally:
            await s.close()

    run(go())


def test_only_a_whole_segment_carries_its_slice_to_the_cache(runtimes):
    """The dispatch hands the loop a slice to keep only for a segment
    that held every row of its SSTs (EncodedSegment.whole, set by the
    reader where tier 2's own completeness test passes): a block-pruned
    load or an overlaid segment leaves the flag down, the dispatch
    runs the same and the cache is offered nothing."""
    async def go():
        s = await open_narrow_storage(runtimes, "presorted")
        try:
            memo_off(s)
            seen = []
            real = s.reader._dispatch_device_decode

            def unwhole(es, plan):
                seen.append(es.whole)
                es.whole = False  # as a pruned or overlaid read leaves it
                return real(es, plan)

            with _ForceXlaAgg():
                clear_caches(s)
                s.reader._dispatch_device_decode = unwhole
                c0 = resident_outcomes()
                a = await s.scan_aggregate(*field_query(*WINDOW_A))
                b = await s.scan_aggregate(*field_query(*WINDOW_B))
                assert seen == [True, True]  # the reader saw whole reads
                assert moved(c0, resident_outcomes()) \
                    == {"hit": 0, "miss": 2, "bypass": 0}
                assert s.reader.cache_stats()["scan_cache"]["entries"] == 0
                s.reader._dispatch_device_decode = real
                host = await host_control(s, *field_query(*WINDOW_B))
            _assert_same(b, host, "un-whole, second window vs host")
            assert len(a[0]) > 0
        finally:
            await s.close()

    run(go())


def test_mesh_round_plans_bypass_the_resident_slices(runtimes):
    """[scan.mesh] defers the dispatch to rounds that group HOST
    DecodePlans: such a plan neither probes nor admits."""
    async def go():
        s = await CloudObjectStorage.open(
            "db", SEGMENT_MS, MemoryObjectStore(), NARROW_SCHEMA, 4,
            storage_config(decode={"mode": "device"},
                           mesh={"enabled": True}), runtimes=runtimes)
        try:
            memo_off(s)
            rng = random.Random(SEED + 7)
            await s.write(narrow_wreq(narrow_rows(rng,
                                                  range(NARROW_TICKS))))
            with _ForceXlaAgg():
                clear_caches(s)
                c0 = resident_outcomes()
                await s.scan_aggregate(*field_query(*WINDOW_A))
                got = await s.scan_aggregate(*field_query(*WINDOW_B))
                assert moved(c0, resident_outcomes()) \
                    == {"hit": 0, "miss": 0, "bypass": 2}
                assert s.reader.cache_stats()["scan_cache"]["entries"] == 0
                s.config.scan.mesh.enabled = False
                host = await host_control(s, *field_query(*WINDOW_B))
            _assert_same(got, host, "mesh rounds vs host")
        finally:
            await s.close()

    run(go())


def test_resident_counter_is_exported_at_rest():
    text = registry.render()
    for outcome in ("hit", "miss", "bypass"):
        assert f'scan_decode_resident_total{{outcome="{outcome}"}}' in text


# ---------------------------------------------------------------------------
# the reduction a slice takes: by runs where its (group, ts) never
# falls, by scatters where it does (ISSUE 31)
# ---------------------------------------------------------------------------

# what the leaves admit -> (key leaf, fields admitted)
REDUCE_LEAVES = {
    # one field: rows in (host, ts) order, every cell one run of rows
    "runs": (F.Eq("field", "f1"), ("f1",)),
    # two fields by one In: within a host ts starts over at the second
    # field, so a cell's rows lie in two stretches
    "scatter": (F.In("field", ["f0", "f1"]), ("f0", "f1")),
}


def reduce_kinds():
    return {k: c.value for k, c in device_decode._DECODE_REDUCE.items()}


def narrow_reference(route: str, fields, lo: int, hi: int) -> dict:
    """rows_reference of what open_narrow_storage wrote."""
    return rows_reference(narrow_writes(route), fields, lo, hi)


def rows_reference(writes: list, fields, lo: int, hi: int) -> dict:
    """{host: {agg: (buckets,) f64}} of the row lists `writes` in the
    order written (a later write of a key replacing the earlier), over
    [lo, hi) by 300 s buckets, in numpy."""
    latest = {(h, f, t): v for rows in writes
              for _m, h, f, t, v in rows}
    n = -(-(hi - lo) // 300_000)
    out: dict = {}
    # (host, field, ts) order: the order the rows decode in, so that on
    # a tie of timestamps `last` is the later FIELD's value
    for (h, f, t), v in sorted(latest.items()):
        if f not in fields or not lo <= t < hi:
            continue
        g = out.setdefault(h, {
            "count": np.zeros(n), "sum": np.zeros(n),
            "min": np.full(n, np.inf), "max": np.full(n, -np.inf),
            "last": np.zeros(n), "last_ts": np.full(n, -1)})
        b = (t - lo) // 300_000
        g["count"][b] += 1
        g["sum"][b] += v
        g["min"][b] = min(g["min"][b], v)
        g["max"][b] = max(g["max"][b], v)
        if t >= g["last_ts"][b]:
            g["last_ts"][b], g["last"][b] = t, v
    return out


def assert_matches_reference(got, ref: dict, ctx: str):
    values, grids = got
    assert [str(v) for v in values] == sorted(ref), ctx
    for row, host in enumerate(sorted(ref)):
        want = ref[host]
        occupied = want["count"] > 0
        assert np.array_equal(np.asarray(grids["count"])[row],
                              want["count"]), (ctx, host)
        for a in ("min", "max", "last"):
            g = np.asarray(grids[a], dtype=np.float64)[row]
            assert np.array_equal(g[occupied], want[a][occupied]), \
                (ctx, host, a)
        g = np.asarray(grids["sum"], dtype=np.float64)[row]
        err = np.abs(g[occupied] - want["sum"][occupied]) \
            / np.maximum(np.abs(want["sum"][occupied]), 1e-30)
        assert err.max() <= 1e-6, (ctx, host)
        # the two grids derived from those: all seven are checked
        if "last_ts" in grids:
            g = np.asarray(grids["last_ts"], dtype=np.float64)[row]
            assert np.array_equal(g[occupied], want["last_ts"][occupied]), \
                (ctx, host, "last_ts")
        if "avg" in grids:
            g = np.asarray(grids["avg"], dtype=np.float64)[row]
            avg = want["sum"][occupied] / want["count"][occupied]
            assert np.abs(g[occupied] - avg).max() \
                <= 1e-6 * np.abs(avg).max(), (ctx, host, "avg")


@pytest.mark.parametrize("kind", sorted(REDUCE_LEAVES))
@pytest.mark.parametrize("route", ["presorted", "kway", "sorted"])
def test_reduction_follows_the_order_of_the_slice(runtimes, monkeypatch,
                                                  route, kind):
    """plan_segment decides from the narrowed columns whether a slice's
    cells are runs of rows; the program is compiled for that answer,
    `scan_decode_reduce_total{kind}` counts every planned dispatch by
    it, and either way the grids are numpy's and the host control's.  A
    resident hit re-dispatches under the kind its slice carries and
    compiles nothing."""
    leaf, fields = REDUCE_LEAVES[kind]
    other = "scatter" if kind == "runs" else "runs"
    if route == "sorted":  # decline the k-way merge: the full sort runs
        monkeypatch.setattr(device_decode, "_KWAY_MAX_RUNS", 1)
    spy = _PlanSpy(monkeypatch)

    def query(lo, hi):
        pred = F.And((leaf, F.TimeRangePred("ts", lo, hi)))
        return (ScanRequest(range=TimeRange.new(lo, hi), predicate=pred),
                narrow_spec(lo, hi))

    async def go():
        s = await open_narrow_storage(runtimes, route)
        try:
            memo_off(s)
            with _ForceXlaAgg():
                clear_caches(s)
                k0, c0 = reduce_kinds(), resident_outcomes()
                miss_a = await s.scan_aggregate(*query(*WINDOW_A))
                k1, c1 = reduce_kinds(), resident_outcomes()
                assert moved(k0, k1) == {kind: 1, other: 0}
                assert moved(c0, c1) == {"hit": 0, "miss": 1, "bypass": 0}
                (_es, plan), = spy.plans()
                assert plan.route == route
                assert plan.cells_sorted == (kind == "runs")
                compiles = compiles_so_far()
                hit_b = await s.scan_aggregate(*query(*WINDOW_B))
                k2, c2 = reduce_kinds(), resident_outcomes()
                assert moved(k1, k2) == {kind: 1, other: 0}
                assert moved(c1, c2) == {"hit": 1, "miss": 0, "bypass": 0}
                assert compiles_so_far() == compiles
                host_b = await host_control(s, *query(*WINDOW_B))
            assert_matches_reference(
                miss_a, narrow_reference(route, fields, *WINDOW_A),
                f"{route} {kind} window A")
            assert_matches_reference(
                hit_b, narrow_reference(route, fields, *WINDOW_B),
                f"{route} {kind} window B")
            # count/min/max/last to the byte against the scatter-ordered
            # control; its sums too, on these integer-valued cells
            _assert_same(hit_b, host_b, f"{route} {kind} hit-vs-host")
        finally:
            await s.close()

    run(go())


def test_reduce_counter_is_exported_at_rest():
    text = registry.render()
    for kind in ("runs", "scatter"):
        assert f'scan_decode_reduce_total{{kind="{kind}"}}' in text


# ---------------------------------------------------------------------------
# the slices' own account: a budget derived from the device, an LRU
# order and counts apart from the windows' (ISSUE 32)
# ---------------------------------------------------------------------------

SLICE_BYTES = encode.pad_capacity((NARROW_HOSTS - 1) * NARROW_TICKS) * 4 * 6


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


def report_device_limit(monkeypatch, limit):
    """The reader under test opens on a device that reports `limit`
    bytes (None: one that reports nothing, as XLA-CPU does)."""
    monkeypatch.setattr("horaedb_tpu.storage.read.device_bytes_limit",
                        lambda: limit)


def limit_for_slices(n: float) -> int:
    """A reported limit whose derived slice budget holds `n` slices."""
    from horaedb_tpu.storage.read import _DEVICE_SLICE_SHARE

    return int((n * SLICE_BYTES + 64) / _DEVICE_SLICE_SHARE)


def account_events(kind: str) -> dict:
    fam = registry.family("scan_cache_account_events_total")
    return {e: fam.labels(tier="hbm", kind=kind, event=e).value
            for e in ("evicted", "declined")}


async def open_sliced_storage(runtimes, segments: int, store=None,
                              **scan):
    """`segments` one-SST segments of the narrow rows under a WINDOWS
    budget smaller than one slice; returns the storage and its
    writes."""
    s = await CloudObjectStorage.open(
        "db", SEGMENT_MS, store or MemoryObjectStore(), NARROW_SCHEMA, 4,
        storage_config(decode={"mode": "device"},
                       cache_max_bytes=SLICE_BYTES - 100, **scan),
        runtimes=runtimes)
    rng = random.Random(SEED + 13)
    writes = []
    for seg in range(segments):
        writes.append([(m, h, f, ts + seg * SEGMENT_MS, v)
                       for m, h, f, ts, v
                       in narrow_rows(rng, range(NARROW_TICKS))])
        await s.write(narrow_wreq(writes[-1]))
    memo_off(s)
    return s, writes


def test_slices_over_the_windows_budget_stay_on_a_device_that_holds_them(
        runtimes, monkeypatch):
    """Three segments' slices are three times the windows budget (which
    holds none of them) and fit what the device's share allows: the
    second query dispatches all three from the device, with no store
    call, tier-2 probe or upload, in one call of the batched program,
    the third with no compile either, and all answers are numpy's."""
    report_device_limit(monkeypatch, limit_for_slices(3))

    async def go():
        store = CountingStore()
        s, writes = await open_sliced_storage(runtimes, 3, store)
        try:
            assert s.reader.cache_budget_bytes < SLICE_BYTES \
                < 3 * SLICE_BYTES <= s.reader.slice_budget_bytes
            lo, hi = WINDOW_A[0], 2 * SEGMENT_MS + WINDOW_A[1]
            tier2 = s.reader.encoded_cache
            with _ForceXlaAgg():
                clear_caches(s)
                c0 = resident_outcomes()
                first = await s.scan_aggregate(*field_query(lo, hi))
                c1 = resident_outcomes()
                assert moved(c0, c1) == {"hit": 0, "miss": 3, "bypass": 0}
                acct = s.reader.cache_stats()["scan_cache"]["accounts"]
                assert acct["slice"] == {
                    "budget_bytes": s.reader.slice_budget_bytes,
                    "bytes": 3 * SLICE_BYTES, "entries": 3,
                    "evicted": 0, "declined": 0}
                assert (acct["windows"]["bytes"],
                        acct["windows"]["entries"]) == (0, 0)
                reads, probes = store.reads, tier2.hits + tier2.misses
                h2d = deviceprof.profiler.snapshot()["transfer"]["h2d"]
                b0 = batch_counts()
                again = await s.scan_aggregate(*field_query(lo, hi))
                # three hits of one plan share ONE call of the batched
                # program (four slices' shape), compiled by the first
                # query that finds them together and by none after it
                compiles = compiles_so_far()
                other = await s.scan_aggregate(
                    *field_query(lo + 300_000, hi - 300_000))
                assert moved(c1, resident_outcomes()) \
                    == {"hit": 6, "miss": 0, "bypass": 0}
                assert moved(b0, batch_counts()) \
                    == {"batched": 6, "single": 0, "calls": 2}
                assert store.reads == reads
                assert tier2.hits + tier2.misses == probes
                assert deviceprof.profiler.snapshot()["transfer"]["h2d"] \
                    == h2d
                assert compiles_so_far() == compiles
            _assert_same(first, again, "hit vs miss")
            assert_matches_reference(
                first, rows_reference(writes, ("f1",), lo, hi), "miss")
            assert_matches_reference(
                other, rows_reference(writes, ("f1",), lo + 300_000,
                                      hi - 300_000), "hit, other window")
        finally:
            await s.close()

    run(go())


@pytest.mark.parametrize("holds", ["one_of_two", "none"])
def test_a_device_budget_under_one_query_counts_on_its_own_account(
        runtimes, monkeypatch, holds):
    """A device that holds one of a query's two slices, or not even
    one: the slice account evicts, or declines, and says so under its
    own kind; the windows account counts nothing; every answer is
    numpy's."""
    report_device_limit(monkeypatch, limit_for_slices(
        {"one_of_two": 1, "none": 0.9}[holds]))
    tier_evictions = registry.family("scan_cache_evictions_total") \
        .labels(tier="hbm")

    async def go():
        s, writes = await open_sliced_storage(runtimes, 2)
        try:
            lo, hi = WINDOW_A[0], SEGMENT_MS + WINDOW_A[1]
            with _ForceXlaAgg():
                clear_caches(s)
                e0 = {k: account_events(k) for k in ("slice", "windows")}
                t0, c0 = tier_evictions.value, resident_outcomes()
                first = await s.scan_aggregate(*field_query(lo, hi))
                second = await s.scan_aggregate(
                    *field_query(lo + 60_000, hi + 60_000))
                got = moved(c0, resident_outcomes())
                events = moved(e0["slice"], account_events("slice"))
                acct = s.reader.scan_cache.account_stats()
            if holds == "one_of_two":
                assert got == {"hit": 1, "miss": 3, "bypass": 0}
                assert events == {"evicted": 2, "declined": 0}
                assert (acct["slice"]["entries"],
                        acct["slice"]["bytes"]) == (1, SLICE_BYTES)
            else:
                assert got == {"hit": 0, "miss": 4, "bypass": 0}
                assert events == {"evicted": 0, "declined": 4}
                assert (acct["slice"]["entries"],
                        acct["slice"]["bytes"]) == (0, 0)
            assert (acct["slice"]["evicted"], acct["slice"]["declined"]) \
                == (events["evicted"], events["declined"])
            # the tier's total is the sum; the windows' account is still
            assert tier_evictions.value - t0 == events["evicted"]
            assert account_events("windows") == e0["windows"]
            assert (acct["windows"]["evicted"],
                    acct["windows"]["declined"]) == (0, 0)
            assert_matches_reference(
                first, rows_reference(writes, ("f1",), lo, hi), holds)
            assert_matches_reference(
                second, rows_reference(writes, ("f1",), lo + 60_000,
                                       hi + 60_000), holds)
        finally:
            await s.close()

    run(go())


@pytest.mark.parametrize("reported, cache_max_bytes, want", [
    # a share of what the device reports, whatever the windows have
    (16 * 2**30, 1 << 20, "share"),
    (limit_for_slices(3), SLICE_BYTES - 100, "share"),
    # XLA-CPU reports nothing: the budget the slices had before
    (None, 1 << 20, "cache_bytes"),
    # a cache turned off is off for both accounts
    (16 * 2**30, 0, "cache_bytes"),
])
def test_slice_budget_is_derived_from_the_device(
        runtimes, monkeypatch, reported, cache_max_bytes, want):
    from horaedb_tpu.common.memledger import ledger
    from horaedb_tpu.storage.read import _DEVICE_SLICE_SHARE

    report_device_limit(monkeypatch, reported)

    async def go():
        s = await CloudObjectStorage.open(
            "db", SEGMENT_MS, MemoryObjectStore(), NARROW_SCHEMA, 4,
            storage_config(decode={"mode": "device"},
                           cache_max_bytes=cache_max_bytes,
                           cache_max_rows=0),
            runtimes=runtimes)
        try:
            r = s.reader
            budget = (int(reported * _DEVICE_SLICE_SHARE)
                      if want == "share" else cache_max_bytes)
            assert r.slice_budget_bytes == budget
            assert r.scan_cache.slice_account.max_bytes == budget
            # the windows, the stacks and the route gate keep theirs
            assert r.scan_cache.max_bytes == r.cache_budget_bytes \
                == r._stack_cache_max == cache_max_bytes
            acct, = [a for a in ledger.snapshot()["accounts"]
                     ["scan_cache_device"]["instances"]]
            assert acct["budget"] == budget
            budgets = registry.family("scan_cache_account_budget_bytes")
            assert budgets.labels(tier="hbm", kind="slice").value == budget
            assert budgets.labels(tier="hbm", kind="windows").value \
                == cache_max_bytes
        finally:
            await s.close()

    run(go())


def test_device_bytes_limit_reads_the_ledgers_device_reader(monkeypatch):
    from horaedb_tpu.common import memledger

    def devices(*limits):
        return lambda: [{"device": f"tpu:{i}", "bytes_in_use": 1,
                         "bytes_limit": lim, "peak_bytes_in_use": None}
                        for i, lim in enumerate(limits)]

    # this backend (XLA-CPU) reports no memory_stats at all
    assert memledger.device_bytes_limit() is None
    monkeypatch.setattr(memledger, "device_memory", devices(900, 700))
    assert memledger.device_bytes_limit() == 700
    monkeypatch.setattr(memledger, "device_memory", devices(None))
    assert memledger.device_bytes_limit() is None
    monkeypatch.setattr(memledger, "device_memory", devices())
    assert memledger.device_bytes_limit() is None


def test_slices_on_the_device_account_miss_after_a_write_and_a_compaction(
        runtimes, monkeypatch):
    """Under the derived budget as under the old one, the SST ids are
    the key: a write into a segment and a compaction of it each miss
    that segment's slice alone, and the answer is numpy's over the
    rows the store then holds."""
    report_device_limit(monkeypatch, limit_for_slices(4))

    async def go():
        s, writes = await open_sliced_storage(runtimes, 2)
        try:
            rng = random.Random(SEED + 17)
            lo, hi = WINDOW_A[0], SEGMENT_MS + WINDOW_A[1]

            async def query(what, misses):
                c0 = resident_outcomes()
                got = await s.scan_aggregate(*field_query(lo, hi))
                assert moved(c0, resident_outcomes()) == {
                    "hit": 2 - misses, "miss": misses, "bypass": 0}, what
                assert_matches_reference(
                    got, rows_reference(writes, ("f1",), lo, hi), what)
                return got

            with _ForceXlaAgg():
                clear_caches(s)
                loaded = await query("as loaded", 2)
                await query("resident", 0)
                # rewrites of every fourth tick of segment 0
                writes.append(narrow_rows(
                    rng, list(range(0, NARROW_TICKS, 4)), base=0.25))
                await s.write(narrow_wreq(writes[-1]))
                written = await query("after a write", 1)
                await query("resident again", 0)
                task = await s.compact_scheduler.picker.pick_candidate()
                await s.compact_scheduler.executor.execute(task)
                compacted = await query("after a compaction", 1)
                await query("resident once more", 0)
            assert loaded[1]["last"].tobytes() \
                != written[1]["last"].tobytes()
            _assert_same(written, compacted, "a compaction moves no value")
            # the stale slices were never served and only age out
            assert s.reader.scan_cache.account_stats()["slice"][
                "entries"] == 4
        finally:
            await s.close()

    run(go())


def test_slice_account_leaves_the_ledger_and_its_gauges_on_close(
        runtimes, monkeypatch):
    from horaedb_tpu.common.memledger import ledger

    report_device_limit(monkeypatch, limit_for_slices(2))
    gauge = registry.family("scan_cache_account_bytes") \
        .labels(tier="hbm", kind="slice")
    entries = registry.family("scan_cache_account_entries") \
        .labels(tier="hbm", kind="slice")
    budgets = registry.family("scan_cache_account_budget_bytes")

    async def go():
        before, held = gauge.value, entries.value
        s, _writes = await open_sliced_storage(runtimes, 2)
        try:
            with _ForceXlaAgg():
                await s.scan_aggregate(*field_query(
                    WINDOW_A[0], SEGMENT_MS + WINDOW_A[1]))
            assert gauge.value - before == 2 * SLICE_BYTES
            assert entries.value - held == 2
            sampled = ledger.sample_once()["accounts"]
            assert sampled["scan_cache_device"] == 2 * SLICE_BYTES
            # the windows' account no longer reports the slices
            assert sampled["scan_cache"] == 0
        finally:
            await s.close()
        assert "scan_cache_device" not in ledger.kinds()
        assert ledger.sample_once()["accounts"].get(
            "scan_cache_device", 0) == 0
        assert (gauge.value, entries.value) == (before, held)
        for kind in ("slice", "windows"):
            assert budgets.labels(tier="hbm", kind=kind).value == 0

    run(go())


def test_account_series_are_rendered_for_both_kinds():
    """What /metrics serves of an open cache: budget, bytes and entries
    of each account, its evictions and declines, and the tier's own
    families beside them."""
    from horaedb_tpu.storage.scan_cache import ScanCache

    cache = ScanCache(4_096, slice_max_bytes=8_192)
    try:
        text = registry.render()
        for kind, budget in (("windows", 4_096), ("slice", 8_192)):
            labels = f'kind="{kind}",tier="hbm"'
            assert f"scan_cache_account_budget_bytes{{{labels}}} " \
                f"{budget}" in text
            assert f"scan_cache_account_bytes{{{labels}}}" in text
            assert f"scan_cache_account_entries{{{labels}}}" in text
            for event in ("evicted", "declined"):
                assert "scan_cache_account_events_total{" \
                    f'event="{event}",{labels}}}' in text
        assert 'scan_cache_evictions_total{tier="hbm"}' in text
    finally:
        cache.close()


# ---------------------------------------------------------------------------
# fallback reasons
# ---------------------------------------------------------------------------


def test_fallback_reasons(runtimes):
    async def go():
        rng = random.Random(SEED + 2)

        async def query(s, pred=None, which=("avg",)):
            spec = agg_spec(0, SEGMENT_MS, which=which)
            req = ScanRequest(range=TimeRange.new(0, SEGMENT_MS),
                              predicate=pred)
            clear_caches(s)
            return await s.scan_aggregate(req, spec)

        # predicate: Or shapes / value-column leaves have no pushed
        # conjunction -> host decode, counted once per plan
        s = await open_storage(MemoryObjectStore(), runtimes,
                               decode={"mode": "device"})
        try:
            await write_segments(s, rng, segments=1)
            before = fallback_count("predicate")
            await query(s, pred=F.Or((F.Eq("k", "k1"), F.Eq("k", "k2"))))
            assert fallback_count("predicate") == before + 1
            # oversized In lists trace a capacity x k compare: refused
            before = fallback_count("predicate")
            await query(s, pred=F.In("k", [f"x{i}" for i in range(200)]))
            assert fallback_count("predicate") == before + 1
            # budget: a segment whose padded upload exceeds the cap
            before = fallback_count("budget")
            s.config.scan.decode.max_upload_bytes = 64
            await query(s)
            assert fallback_count("budget") >= before + 1
            s.config.scan.decode.max_upload_bytes = 256 << 20
            # host mode: no counting — the operator chose
            before_all = {r: fallback_count(r)
                          for r in device_decode.FALLBACK_REASONS}
            s.config.scan.decode.mode = "host"
            await query(s)
            assert {r: fallback_count(r)
                    for r in device_decode.FALLBACK_REASONS} == before_all
        finally:
            await s.close()

        # no_sidecar: sidecars disabled at the scan layer
        s = await open_storage(MemoryObjectStore(), runtimes,
                               decode={"mode": "device"},
                               use_sidecar=False)
        try:
            await write_segments(s, rng, segments=1)
            before = fallback_count("no_sidecar")
            await query(s)
            assert fallback_count("no_sidecar") == before + 1
        finally:
            await s.close()

        # parquet: sidecar objects missing for a decode-eligible plan
        s = await open_storage(MemoryObjectStore(), runtimes,
                               decode={"mode": "device"})
        try:
            s.config.write.enable_sidecar = False
            await write_segments(s, rng, segments=1)
            before = fallback_count("parquet")
            await query(s)
            assert fallback_count("parquet") >= before + 1
        finally:
            await s.close()

    run(go())


def test_fused_aggregate_yields_to_forced_decode(runtimes):
    """HORAEDB_FUSED_AGG=1 keeps the fused path (existing coverage);
    without the force, [scan.decode] mode=device routes an eligible
    plan to the parts path."""
    async def go():
        rng = random.Random(SEED + 3)
        s = await open_storage(MemoryObjectStore(), runtimes,
                               decode={"mode": "device"})
        try:
            await write_segments(s, rng, segments=1)
            req = ScanRequest(range=TimeRange.new(0, SEGMENT_MS))
            plan = await s.build_scan_plan(req)
            old = os.environ.get("HORAEDB_FUSED_AGG")
            try:
                os.environ["HORAEDB_FUSED_AGG"] = "1"
                assert s.reader.fused_aggregate_ok(plan) is True
                os.environ.pop("HORAEDB_FUSED_AGG", None)
                assert s.reader.fused_aggregate_ok(plan) is False
                assert s.reader._device_decode_plan_ok(plan) is True
                s.config.scan.decode.mode = "host"
                assert s.reader._device_decode_plan_ok(plan) is False
            finally:
                if old is None:
                    os.environ.pop("HORAEDB_FUSED_AGG", None)
                else:
                    os.environ["HORAEDB_FUSED_AGG"] = old
        finally:
            await s.close()

    run(go())


# ---------------------------------------------------------------------------
# seeded chaos: device vs host byte-identity under structural churn
# ---------------------------------------------------------------------------


def _chaos_schedule(i: int, runtimes):
    """One seeded schedule: random writes/compactions/evictions
    interleaved with downsample and top-k queries over random ranges,
    agg subsets, and filters — each query runs device-warm (memo may
    serve), device-cold, and host-cold, and all three must be
    byte-identical.  One op races a query against a mid-scan
    compaction; odd schedules force streamed segments so the deferred
    window-range leaves are exercised."""
    async def go():
        rng = random.Random(SEED + i)
        scan_kw = {"decode": {"mode": "device"}}
        if i % 2:
            scan_kw.update(stream_read_min_rows=64, max_window_rows=128)
        s = await open_storage(MemoryObjectStore(), runtimes, **scan_kw)

        async def checked_query():
            lo = rng.randrange(0, 2 * SEGMENT_MS, 250)
            hi = lo + rng.randrange(250, 3 * SEGMENT_MS, 250)
            which = WHICH_SETS[rng.randrange(len(WHICH_SETS))]
            bucket_ms = rng.choice([250, 60_000])
            spec = agg_spec(lo, hi, bucket_ms=bucket_ms, which=which)
            pred = rng.choice([None, F.Eq("k", f"k{rng.randint(0, 5)}"),
                               F.In("k", ["k1", "k3", "k5"]),
                               F.Ge("ts", SEGMENT_MS // 2)])
            req = ScanRequest(range=TimeRange.new(lo, hi), predicate=pred)
            tk = None
            if rng.random() < 0.3:
                by_pool = [a for a in which if a != "last_ts"] + ["count"]
                tk = TopKSpec(k=rng.randint(1, 4),
                              by=rng.choice(by_pool),
                              largest=rng.random() < 0.5)
            s.config.scan.decode.mode = "device"
            warm = await s.scan_aggregate(req, spec, top_k=tk)
            clear_caches(s)
            cold = await s.scan_aggregate(req, spec, top_k=tk)
            clear_caches(s)
            s.config.scan.decode.mode = "host"
            control = await s.scan_aggregate(req, spec, top_k=tk)
            s.config.scan.decode.mode = "device"
            ctx = f"schedule {i} lo={lo} hi={hi} which={which} " \
                  f"pred={pred} tk={tk}"
            _assert_same(warm, cold, f"{ctx} warm-vs-cold")
            _assert_same(cold, control, f"{ctx} device-vs-host")

        async def compact_once():
            sched = s.compact_scheduler
            task = await sched.picker.pick_candidate()
            if task is not None:
                await sched.executor.execute(task)

        try:
            with _ForceXlaAgg():
                await write_segments(s, rng, segments=3, rows_per=120)
                for _op in range(8):
                    op = rng.choice(["write", "write", "query", "query",
                                     "compact", "evict", "race"])
                    if op == "write":
                        seg = rng.randint(0, 2)
                        rows = [(f"k{rng.randint(0, 5)}",
                                 seg * SEGMENT_MS + rng.randint(0, 999),
                                 float(rng.randint(0, 10**6)))
                                for _ in range(rng.randint(1, 30))]
                        await s.write(wreq(rows))
                    elif op == "compact":
                        await compact_once()
                    elif op == "evict":
                        clear_caches(s, memo=rng.random() < 0.5)
                    elif op == "race":
                        await asyncio.gather(checked_query(),
                                             compact_once())
                    else:
                        await checked_query()
                await checked_query()
        finally:
            await s.close()

    run(go())


@pytest.mark.slow
def test_seeded_decode_chaos(runtimes):
    for i in range(SCHEDULES):
        _chaos_schedule(i, runtimes)


def test_seeded_decode_chaos_fast(runtimes):
    """Tier-1 variant: a fixed small slice of the chaos schedules
    (one bulk, one streamed)."""
    for i in range(2):
        _chaos_schedule(i, runtimes)


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------


def test_decode_config_toml():
    cfg = from_dict(StorageConfig, {
        "scan": {"decode": {"mode": "device",
                            "max_upload_bytes": 1 << 20}}})
    assert cfg.scan.decode.mode == "device"
    assert cfg.scan.decode.max_upload_bytes == 1 << 20
    assert StorageConfig().scan.decode.mode == "auto"
    with pytest.raises(Error):
        from_dict(StorageConfig, {"scan": {"decode": {"mod": "x"}}})


def test_bad_decode_mode_rejected_at_open(runtimes):
    async def go():
        with pytest.raises(Error, match="scan.decode"):
            await open_storage(MemoryObjectStore(), runtimes,
                               decode={"mode": "gpu"})

    run(go())


def test_env_force_overrides_config(runtimes):
    async def go():
        s = await open_storage(MemoryObjectStore(), runtimes,
                               decode={"mode": "host"})
        try:
            old = os.environ.get("HORAEDB_DEVICE_DECODE")
            try:
                os.environ["HORAEDB_DEVICE_DECODE"] = "1"
                assert s.reader._decode_mode() == "device"
                os.environ["HORAEDB_DEVICE_DECODE"] = "0"
                assert s.reader._decode_mode() == "host"
                os.environ.pop("HORAEDB_DEVICE_DECODE", None)
                assert s.reader._decode_mode() == "host"
            finally:
                if old is None:
                    os.environ.pop("HORAEDB_DEVICE_DECODE", None)
                else:
                    os.environ["HORAEDB_DEVICE_DECODE"] = old
        finally:
            await s.close()

    run(go())


# ---------------------------------------------------------------------------
# lint rule: decode goes through the dispatch seam
# ---------------------------------------------------------------------------


def test_lint_decode_seam_rule(tmp_path):
    """Host-decoding an EncodedSegment's encoded buffers (deserialize /
    assemble / concat / decode_column ...) outside storage/sidecar.py,
    ops/, and the reader's dispatch seam is an error; the seam files
    themselves stay clean."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "lint_under_test",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "lint.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)

    bad = ("from horaedb_tpu.storage import sidecar\n\n\n"
           "def f(bufs, want):\n"
           "    return sidecar.deserialize(bufs[0], want)\n")
    ok = ("def f(session):\n"
          "    return session.load_window([])\n")
    edir = tmp_path / "horaedb_tpu" / "metric_engine"
    edir.mkdir(parents=True)
    (edir / "x.py").write_text(bad)
    problems = lint.lint_file(edir / "x.py")
    assert any("decode" in p and "seam" in p for p in problems), problems
    (edir / "y.py").write_text(ok)
    assert not lint.lint_file(edir / "y.py")
    sdir = tmp_path / "horaedb_tpu" / "storage"
    sdir.mkdir(parents=True)
    (sdir / "read.py").write_text(bad)
    assert not lint.lint_file(sdir / "read.py")
    # the real tree is clean under the rule
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for rel in ("horaedb_tpu/storage/read.py",
                "horaedb_tpu/storage/sidecar.py",
                "horaedb_tpu/metric_engine/engine.py"):
        assert not [p for p in lint.lint_file(
            __import__("pathlib").Path(repo) / rel) if "seam" in p]
