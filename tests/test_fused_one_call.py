"""The fused aggregate of ONE small round (ISSUE 44): one program fed
numpy stacks and one download, against the four-call round sequence it
is composed from.

CPU backend with `HORAEDB_FUSED_AGG=1` (the route an accelerator takes
by default).  The round sequence is reached on the same storage, over
the same scan-cached windows, by setting the size bound to 0, which is
the one thing the choice between the two reads besides the windows
themselves."""

import asyncio

import numpy as np
import pyarrow as pa
import pytest

from horaedb_tpu.common import ReadableDuration, deviceprof
from horaedb_tpu.common import runtimes as runtimes_mod
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.ops import filter as F
from horaedb_tpu.ops.downsample import ALL_AGGS
from horaedb_tpu.storage import read as read_mod
from horaedb_tpu.storage.config import (StorageConfig, ThreadsConfig,
                                        from_dict)
from horaedb_tpu.storage.read import AggregateSpec, ScanRequest
from horaedb_tpu.storage.storage import CloudObjectStorage, WriteRequest
from horaedb_tpu.storage.types import TimeRange

SEGMENT_MS = 3_600_000
TICK_MS = 10_000
TICKS = SEGMENT_MS // TICK_MS
BUCKET_MS = 60_000
SCHEMA = pa.schema([("k", pa.string()), ("ts", pa.int64()),
                    ("v", pa.float64())])
FULL = [f"host_{i:02d}" for i in range(5)]
ONE_CALL = "_fused_one_call_jit"
ROUNDS = ("_fused_acc_init_jit", "_fused_round_accumulate_jit",
          "_fused_finalize_jit", "_group_has_data_jit")

# (host, first tick, ticks) a segment: segment 0 has 1,880 rows (a
# window of capacity 2,048), segment 1 has 360 (capacity 512), so a
# range over both stacks two windows of different capacities; `late`
# reports only at the end of segment 1 and `tiny_*` only briefly
LAYOUT = {
    0: [(h, 0, TICKS) for h in FULL] + [("tiny_a", 100, 40),
                                        ("tiny_b", 200, 40)],
    1: [(h, 0, 60) for h in FULL] + [("late", 300, 60)],
}
# [lo, hi) in ms, none aligned to a bucket or a segment
RANGES = {
    "one_window": (600_007, 3_000_007),
    "two_windows": (SEGMENT_MS - 1_200_000 + 7,
                    SEGMENT_MS + 1_500_000 + 7),
    # the window's rows run to the segment's end, the range stops at
    # its middle: `late` is a group of the window with no row in range
    "overhang_empty_group": (SEGMENT_MS + 7, SEGMENT_MS + 1_500_007),
}


def rows_in_range(lo: int, hi: int) -> int:
    return sum(lo <= seg * SEGMENT_MS + (first + i) * TICK_MS < hi
               for seg, hosts in LAYOUT.items()
               for _h, first, n in hosts for i in range(n))


@pytest.fixture(scope="module")
def runtimes():
    rt = runtimes_mod.from_config(ThreadsConfig())
    yield rt
    rt.close()


@pytest.fixture(autouse=True)
def fused_on_cpu(monkeypatch):
    for name in ("HORAEDB_HOST_AGG", "HORAEDB_DEVICE_DECODE",
                 "HORAEDB_DEVCOL_STACK"):
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setenv("HORAEDB_FUSED_AGG", "1")


def write_requests(seed: int, layout=LAYOUT):
    rng = np.random.default_rng(seed)
    for seg, hosts in layout.items():
        names, ts = [], []
        for h, first, n in hosts:
            names += [h] * n
            ts += [seg * SEGMENT_MS + (first + i) * TICK_MS
                   for i in range(n)]
        ts = np.asarray(ts, dtype=np.int64)
        batch = pa.record_batch(
            [pa.array(names), pa.array(ts),
             pa.array((rng.random(len(ts)) * 100).astype(np.float32)
                      .astype(np.float64))], schema=SCHEMA)
        yield WriteRequest(batch, TimeRange.new(int(ts.min()),
                                                int(ts.max()) + 1))


async def open_storage(runtimes, scan: dict = None, layout=LAYOUT):
    cfg = from_dict(StorageConfig, {
        "scheduler": {"schedule_interval": "1h", "input_sst_min_num": 2},
        "scan": scan or {}})
    cfg.manifest.merge_interval = ReadableDuration.parse("1h")
    cfg.scrub.interval = ReadableDuration.parse("1h")
    s = await CloudObjectStorage.open(
        "db", SEGMENT_MS, MemoryObjectStore(), SCHEMA, 2, cfg,
        runtimes=runtimes)
    for wr in write_requests(44, layout):
        await s.write(wr)
    return s


def spec_of(lo: int, hi: int, which=ALL_AGGS) -> AggregateSpec:
    return AggregateSpec(group_col="k", ts_col="ts", value_col="v",
                         range_start=lo, bucket_ms=BUCKET_MS,
                         num_buckets=-(-(hi - lo) // BUCKET_MS),
                         which=which)


def fn_calls() -> dict:
    return {r["fn"]: r["compiles"] + r["dispatches"]
            for r in deviceprof.profiler.snapshot()["fns"]}


def compiles(fn: str) -> int:
    return sum(r["compiles"] for r in deviceprof.profiler.snapshot()["fns"]
               if r["fn"] == fn)


def aggregates() -> dict:
    return {c: child.value
            for c, child in read_mod._FUSED_AGGREGATES.items()}


def transfers() -> dict:
    return {d: dict(t) for d, t in deviceprof.profiler.transfer.items()}


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def same_bytes(a, b, what) -> None:
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, what
    assert a.tobytes() == b.tobytes(), what


WHICH = {"all": ALL_AGGS, "max": ("max",), "avg": ("avg",),
         "last": ("last",)}


@pytest.mark.parametrize("which", list(WHICH))
@pytest.mark.parametrize("shape", list(RANGES))
def test_one_call_is_the_round_sequence_bit_for_bit(shape, which, runtimes,
                                                    monkeypatch):
    """The same windows down both: what the device hands back (every
    grid before the host's conventions, and the any-data mask) and what
    the scan answers are byte-equal, dtypes included."""
    lo, hi = RANGES[shape]
    spec = spec_of(lo, hi, WHICH[which])
    req = ScanRequest(range=TimeRange.new(lo, hi))

    async def go():
        s = await open_storage(runtimes)
        try:
            handed = []
            real = s.reader._fused_result

            def spy(values, fused, spec_):
                handed.append(fused)
                return real(values, fused, spec_)

            monkeypatch.setattr(s.reader, "_fused_result", spy)
            n0 = aggregates()
            one = await s.scan_aggregate(req, spec)
            assert delta(aggregates(), n0) == {"one": 1}
            monkeypatch.setattr(read_mod, "_ONE_CALL_MAX_ROWS", 0)
            rounds = await s.scan_aggregate(req, spec)
            assert delta(aggregates(), n0) == {"one": 1, "rounds": 1}
            return one, rounds, handed
        finally:
            await s.close()

    (v1, g1), (v2, g2), ((dev1, has1), (dev2, has2)) = asyncio.run(go())
    assert isinstance(has1, np.ndarray) and not isinstance(has2, np.ndarray)
    same_bytes(has1, has2, "mask")
    assert sorted(dev1) == sorted(dev2)
    for k in dev1:
        assert isinstance(dev1[k], np.ndarray), k
        same_bytes(dev1[k], dev2[k], k)
    assert [str(v) for v in v1] == [str(v) for v in v2] == (
        FULL if shape != "one_window" else FULL + ["tiny_a", "tiny_b"])
    # `late` is a group of segment 1's window in the two cases whose
    # range it misses: the mask is what dropped it
    assert (not np.asarray(has1).all()) == (shape != "one_window")
    assert sorted(g1) == sorted(g2)
    want = set(WHICH[which]) | {"count"}
    if "last" in want:
        want.add("last_ts")
    assert set(g1) == want
    for k in g1:
        same_bytes(g1[k], g2[k], k)
    assert np.asarray(g1["count"]).sum() == rows_in_range(lo, hi)
    if "last_ts" in g1:
        assert g1["last_ts"].dtype == np.float64
        seen = g1["last_ts"][np.asarray(g1["count"]) > 0]
        assert seen.min() >= lo and seen.max() < hi


# 200 hosts x 360 ticks in one segment: a window of capacity 131,072,
# past the bound of 65,536 stacked rows
BIG = {0: [(f"big_{i:03d}", 0, TICKS) for i in range(200)]}
CARRIED = {
    # (scan config, layout, range, predicate) -> calls label, programs
    "one_small_round": ({}, LAYOUT, "two_windows", None, "one"),
    "one_host": ({}, LAYOUT, "two_windows", F.Eq("k", "host_03"), "one"),
    "over_the_size_bound": ({}, BIG, "one_window", None, "rounds"),
    "more_windows_than_a_round": ({"agg_batch_windows": 1}, LAYOUT,
                                  "two_windows", None, "rounds"),
}


@pytest.mark.parametrize("case", list(CARRIED))
def test_how_many_calls_carry_a_fused_aggregate(case, runtimes):
    """One small round: exactly one counted device call, one upload
    and one download, nothing kept for a later query.  A round over
    the size bound, or more windows than a round holds: the rounds'
    four programs, as before."""
    scan, layout, shape, predicate, label = CARRIED[case]
    lo, hi = RANGES[shape]
    spec = spec_of(lo, hi)
    req = ScanRequest(range=TimeRange.new(lo, hi), predicate=predicate)

    async def go():
        s = await open_storage(runtimes, scan, layout)
        try:
            reader = s.reader
            plan = await s._plan_aggregate(req, spec)
            assert plan.route == "fused_acc"
            n0, f0, t0 = aggregates(), fn_calls(), transfers()
            values, grids = await s.scan_aggregate(req, spec,
                                                   first_plan=plan)
            assert delta(aggregates(), n0) == {label: 1}
            ran = delta(fn_calls(), f0)
            t1 = transfers()
            if label == "one":
                assert ran == {ONE_CALL: 1}
                assert t1["d2h"]["count"] - t0["d2h"]["count"] == 1
                assert t1["h2d"]["count"] - t0["h2d"]["count"] == 1
                # three (2, 2048) columns, remap (2, 8), shift, lo
                if predicate is None:
                    assert t1["h2d"]["bytes"] - t0["h2d"]["bytes"] \
                        == 3 * 2 * 2048 * 4 + 2 * 8 * 4 + 2 * 2 * 4
                assert all(isinstance(g, np.ndarray)
                           for g in grids.values())
                assert not reader._stack_cache
                assert not reader._replay_cache
                for key in list(reader.scan_cache._entries):
                    for w in reader.scan_cache.get(key):
                        assert not any(mk[0] == "dev_cols"
                                       for mk in w.memo)
                # the same query again: the one call again, no replay
                plan = await s._plan_aggregate(req, spec)
                assert plan.route == "fused_acc"
                again = await s.scan_aggregate(req, spec, first_plan=plan)
                assert delta(aggregates(), n0) == {"one": 2}
                assert delta(fn_calls(), f0) == {ONE_CALL: 2}
                assert reader._replay_hits == 0
                for k in grids:
                    same_bytes(grids[k], again[1][k], k)
            else:
                assert set(ran) == set(ROUNDS)
                assert ran["_fused_round_accumulate_jit"] \
                    == (2 if case == "more_windows_than_a_round" else 1)
                assert len(reader._replay_cache) == 1
                plan = await s._plan_aggregate(req, spec)
                assert plan.route == "replay"
                again = await s.scan_aggregate(req, spec, first_plan=plan)
                assert delta(aggregates(), n0) == {"rounds": 1,
                                                   "replay": 1}
                # the replay's empty-group drop is the rounds' (`late`)
                assert list(again[0]) == list(values)
                for k in grids:
                    same_bytes(grids[k], again[1][k], k)
            return values, grids
        finally:
            await s.close()

    values, grids = asyncio.run(go())
    assert len(values) == (1 if predicate is not None
                           else 200 if layout is BIG else 5)


def test_a_second_query_of_the_same_shape_compiles_nothing(runtimes):
    """The program is keyed by the round's padded shapes, never by how
    many groups the query found: another host, and two hosts where
    there was one, run what the first query compiled."""
    lo, hi = RANGES["one_window"]
    spec = spec_of(lo, hi)

    async def go():
        s = await open_storage(runtimes)
        try:
            async def ask(predicate):
                values, _grids = await s.scan_aggregate(
                    ScanRequest(range=TimeRange.new(lo, hi),
                                predicate=predicate), spec)
                return len(values)

            n0 = aggregates()
            assert await ask(F.Eq("k", "tiny_a")) == 1
            c0, f0 = compiles(ONE_CALL), fn_calls()
            assert await ask(F.Eq("k", "tiny_b")) == 1
            assert await ask(F.In("k", ["tiny_a", "tiny_b"])) == 2
            assert compiles(ONE_CALL) == c0
            assert delta(fn_calls(), f0) == {ONE_CALL: 2}
            assert delta(aggregates(), n0) == {"one": 3}
        finally:
            await s.close()

    asyncio.run(go())
