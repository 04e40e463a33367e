"""A plan's resident slices go to the device as ONE batch (ISSUE 36):
one pool job, one call of the batched fused decode program a group of
slices that may share it, one download, and one DevicePart a segment
that is what the segment's own dispatch gave, bit for bit.

The control is the per-slice route itself: `singly()` makes every
plan a group of one, which `dispatch_resident` hands to `execute_plan`
— the call a resident slice got before the batch existed."""

from __future__ import annotations

import asyncio
import random

import numpy as np
import pyarrow as pa
import pytest

from horaedb_tpu.common import deviceprof
from horaedb_tpu.common.error import Error
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.ops import device_decode
from horaedb_tpu.ops import filter as F
from horaedb_tpu.storage.read import ScanRequest
from horaedb_tpu.storage.storage import CloudObjectStorage, WriteRequest
from horaedb_tpu.storage.types import TimeRange
from horaedb_tpu.utils import registry

from test_device_decode import (  # noqa: F401  (runtimes: a fixture)
    NARROW_SCHEMA,
    NARROW_TICKS,
    SEED,
    SEGMENT_MS,
    WINDOW_A,
    _assert_same,
    _ForceXlaAgg,
    assert_matches_reference,
    batch_counts,
    clear_caches,
    compiles_so_far,
    decode_row_sides,
    fallback_count,
    field_query,
    memo_off,
    moved,
    narrow_rows,
    narrow_spec,
    narrow_wreq,
    reduce_kinds,
    resident_outcomes,
    rows_reference,
    run,
    runtimes,
    storage_config,
)


def counts() -> dict:
    """Every counter the route keeps, flat."""
    stored, uploaded = decode_row_sides()
    return {**{f"resident.{k}": v for k, v in resident_outcomes().items()},
            **{f"reduce.{k}": v for k, v in reduce_kinds().items()},
            "rows.stored": stored, "rows.uploaded": uploaded,
            "fallback.range": fallback_count("range")}


def batch_programs() -> int:
    return device_decode._decode_batch_jit._cache_size()


def singly(monkeypatch):
    """Every plan a group of one: each resident slice by a call of its
    own (execute_plan), as before the batch."""
    monkeypatch.setattr(device_decode.DecodePlan, "batch_key",
                        lambda self: id(self))


class ResidentSpy:
    """The plans each query handed to `dispatch_resident`."""

    def __init__(self, monkeypatch):
        self.calls: list = []
        real = device_decode.dispatch_resident

        def spy(plans, table=""):
            self.calls.append(list(plans))
            return real(plans, table)

        monkeypatch.setattr(device_decode, "dispatch_resident", spy)


async def open_segments(runtimes, starts, fields=None, **scan):
    """One one-SST segment of the narrow rows at each hour of `starts`
    (the rows of `fields` only, where given); the storage and its
    writes."""
    s = await CloudObjectStorage.open(
        "db", SEGMENT_MS, MemoryObjectStore(), NARROW_SCHEMA, 4,
        storage_config(decode={"mode": "device"}, **scan),
        runtimes=runtimes)
    rng = random.Random(SEED + 36)
    writes = []
    for k, start in enumerate(starts):
        keep = None if fields is None else fields[k]
        writes.append([(m, h, f, ts + start * SEGMENT_MS, v)
                       for m, h, f, ts, v
                       in narrow_rows(rng, range(NARROW_TICKS))
                       if keep is None or f in keep])
        await s.write(narrow_wreq(writes[-1]))
    memo_off(s)
    return s, writes


def assert_same_part(got, want, ctx):
    assert (got.n_valid, got.nbytes) == (want.n_valid, want.nbytes), ctx
    assert got.resident is None and want.resident is None, ctx
    values, lo, grids = got.part
    w_values, w_lo, w_grids = want.part
    assert lo == w_lo and np.array_equal(values, w_values), ctx
    assert set(grids) == set(w_grids), ctx
    for name, grid in grids.items():
        assert grid.dtype == w_grids[name].dtype \
            and grid.shape == w_grids[name].shape \
            and grid.tobytes() == w_grids[name].tobytes(), (ctx, name)
        assert grid.base is None, (ctx, name, "a view pins the download")


# ---------------------------------------------------------------------------
# the batched parts are the per-slice parts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [2, 3, 7, 8, 9])
def test_batched_parts_are_the_per_slice_parts_bit_for_bit(
        runtimes, monkeypatch, n):
    """`n` resident slices of one plan: the batch's DevicePart of each
    segment is what that segment's own dispatch gives, on every grid,
    `n_valid` and `nbytes`, and the served answer is numpy's."""
    spy = ResidentSpy(monkeypatch)

    async def go():
        s, writes = await open_segments(runtimes, range(n))
        try:
            lo, hi = WINDOW_A[0], (n - 1) * SEGMENT_MS + WINDOW_A[1]
            with _ForceXlaAgg():
                clear_caches(s)
                await s.scan_aggregate(*field_query(lo, hi))  # admits
                b0, c0 = batch_counts(), resident_outcomes()
                hit = await s.scan_aggregate(
                    *field_query(lo + 60_000, hi - 60_000))
            assert moved(c0, resident_outcomes()) \
                == {"hit": n, "miss": 0, "bypass": 0}
            assert moved(b0, batch_counts()) \
                == {"batched": n, "single": 0, "calls": 1}
            plans = spy.calls[-1]
            assert len(plans) == n
            assert len({dp.batch_key() for dp in plans}) == 1
            batched = device_decode.finalize_resident(
                device_decode.dispatch_resident(plans))
            single = [device_decode.execute_plan(dp).finalize()
                      for dp in plans]
            for k, (got, want) in enumerate(zip(batched, single)):
                assert_same_part(got, want, f"n={n} slice {k}")
            # every slice has rows of its own in the window
            assert all(p.n_valid > 0 for p in batched)
            assert_matches_reference(
                hit, rows_reference(writes, ("f1",), lo + 60_000,
                                    hi - 60_000), f"n={n} served")
        finally:
            await s.close()

    run(go())


def test_five_to_eight_slices_share_one_program(runtimes):
    """The number of slices is rounded up to a power of two as rows
    are to their capacity: windows over five, six, seven and eight
    segments run ONE compiled program (the first of them compiles it,
    unless a test before this one has), and a second call of any of
    them compiles nothing."""
    async def go():
        s, writes = await open_segments(runtimes, range(8))
        try:
            hi = 7 * SEGMENT_MS + WINDOW_A[1]
            with _ForceXlaAgg():
                clear_caches(s)
                await s.scan_aggregate(*field_query(WINDOW_A[0], hi))
                before = batch_programs()
                for round_ in range(2):
                    for n in (5, 6, 7, 8):
                        lo = (8 - n) * SEGMENT_MS + WINDOW_A[0] + round_
                        b0 = batch_counts()
                        got = await s.scan_aggregate(*field_query(lo, hi))
                        assert moved(b0, batch_counts()) == {
                            "batched": n, "single": 0, "calls": 1}
                        assert_matches_reference(
                            got, rows_reference(writes, ("f1",), lo, hi),
                            f"{n} slices")
                        if (round_, n) == (0, 5):
                            programs = batch_programs()
                            compiles = compiles_so_far()
                            assert programs <= before + 1
                        assert batch_programs() == programs
                        assert compiles_so_far() == compiles
        finally:
            await s.close()

    run(go())


def test_a_smaller_batch_takes_the_program_a_larger_one_compiled(
        runtimes, monkeypatch):
    """A query that the parts memo served some segments of brings
    fewer slices than its neighbours.  Its two, three or four go into
    the eight-slice program that is compiled already, the further
    slots filler and never run: nothing compiles (a served window
    would stall on it), and the parts are the per-slice parts."""
    spy = ResidentSpy(monkeypatch)
    monkeypatch.setattr(device_decode, "_BATCH_COMPILED", {})
    slots = []
    real = device_decode._decode_batch_jit

    def call(cols, key_consts, run_offsets, nums, **static):
        slots.append((int(nums[0, 0]), len(cols)))
        return real(cols, key_consts, run_offsets, nums, **static)

    monkeypatch.setattr(device_decode, "_decode_batch_jit", call)

    async def go():
        s, writes = await open_segments(runtimes, range(8))
        try:
            hi = 7 * SEGMENT_MS + WINDOW_A[1]
            with _ForceXlaAgg():
                clear_caches(s)
                await s.scan_aggregate(*field_query(WINDOW_A[0], hi))
                await s.scan_aggregate(*field_query(WINDOW_A[0] + 1, hi))
                assert slots == [(8, 8)]
                programs, compiles = real._cache_size(), compiles_so_far()
                for n in (2, 3, 4):
                    lo = (8 - n) * SEGMENT_MS + WINDOW_A[0]
                    b0 = batch_counts()
                    got = await s.scan_aggregate(*field_query(lo, hi))
                    assert moved(b0, batch_counts()) == {
                        "batched": n, "single": 0, "calls": 1}
                    assert slots[-1] == (n, 8)
                    assert_matches_reference(
                        got, rows_reference(writes, ("f1",), lo, hi),
                        f"{n} slices in 8")
                    plans = spy.calls[-1]
                    batched = device_decode.finalize_resident(
                        device_decode.dispatch_resident(plans))
                    for k, dp in enumerate(plans):
                        assert_same_part(
                            batched[k],
                            device_decode.execute_plan(dp).finalize(),
                            f"{n} slices in 8, slice {k}")
                assert real._cache_size() == programs
                assert compiles_so_far() == compiles
                # the stack's budget still binds: eight slots over it,
                # so the two slices mint the program of their own size
                monkeypatch.setattr(
                    device_decode, "_BATCH_MAX_STACK_BYTES",
                    2 * spy.calls[-1][0].seg.nbytes + 1)
                await s.scan_aggregate(*field_query(
                    6 * SEGMENT_MS + WINDOW_A[0], hi))
                assert slots[-1] == (2, 2)
        finally:
            await s.close()

    run(go())


def test_a_call_stacks_no_more_than_its_budget(runtimes, monkeypatch):
    """The stacked columns are a temporary that no account is charged
    for: a group whose slices pass the budget goes out in several
    calls, a slice too large to share one by a call of its own, and
    the parts are the same."""
    spy = ResidentSpy(monkeypatch)

    async def go():
        s, _writes = await open_segments(runtimes, range(5))
        try:
            lo, hi = WINDOW_A[0], 4 * SEGMENT_MS + WINDOW_A[1]
            with _ForceXlaAgg():
                clear_caches(s)
                await s.scan_aggregate(*field_query(lo, hi))
                whole = await s.scan_aggregate(*field_query(lo + 1, hi))
                plans = spy.calls[-1]
                nbytes = plans[0].seg.nbytes
                for room, want in (
                        (2, {"batched": 4, "single": 1, "calls": 2}),
                        (1, {"batched": 0, "single": 5, "calls": 0})):
                    monkeypatch.setattr(
                        device_decode, "_BATCH_MAX_STACK_BYTES",
                        room * nbytes + 1)
                    b0 = batch_counts()
                    cut = await s.scan_aggregate(*field_query(lo + 1, hi))
                    assert moved(b0, batch_counts()) == want
                    _assert_same(cut, whole, f"room for {room}")
        finally:
            await s.close()

    run(go())


# ---------------------------------------------------------------------------
# plans that are not one group of hits: today's answer, today's counts
# ---------------------------------------------------------------------------

FAR = 30 * 24  # hours: further from hour 0 than an int32 of ms holds


async def _scattering_among_sorted(runtimes):
    """Two fields by one In: a segment that holds both scatters, one
    that holds only the first reduces by runs (and compiles its In to
    one code): two programs, so two groups."""
    both, one = ("f0", "f1"), ("f0",)
    s, writes = await open_segments(
        runtimes, range(4), fields=[one, both, one, one])
    lo, hi = WINDOW_A[0], 3 * SEGMENT_MS + WINDOW_A[1]

    def query(lo, hi):
        pred = F.And((F.In("field", list(both)),
                      F.TimeRangePred("ts", lo, hi)))
        return (ScanRequest(range=TimeRange.new(lo, hi), predicate=pred),
                narrow_spec(lo, hi))

    await s.scan_aggregate(*query(lo, hi))
    return s, query(lo + 60_000, hi), rows_reference(
        writes, both, lo + 60_000, hi), \
        {"batched": 3, "single": 1, "calls": 1}, \
        {"resident.hit": 4, "reduce.runs": 3, "reduce.scatter": 1}


async def _miss_among_hits(runtimes, at=1):
    """A write into one of three segments (the second) changes its SST
    set: that segment is read, narrowed and uploaded beside the batch
    of the other two."""
    s, writes = await open_segments(runtimes, range(3))
    lo, hi = WINDOW_A[0], 2 * SEGMENT_MS + WINDOW_A[1]
    await s.scan_aggregate(*field_query(lo, hi))
    late = [(m, h, f, ts + at * SEGMENT_MS, v + 0.5) for m, h, f, ts, v
            in narrow_rows(random.Random(SEED + 37), range(0, 40, 3))]
    await s.write(narrow_wreq(late))
    s.reader.encoded_cache.clear()  # the miss goes to the store
    return s, field_query(lo + 60_000, hi), rows_reference(
        writes + [late], ("f1",), lo + 60_000, hi), \
        {"batched": 2, "single": 1, "calls": 1}, \
        {"resident.hit": 2, "resident.miss": 1, "reduce.runs": 3}


async def _range_fallback(runtimes):
    """Two segments a month after the window's start: the shift to
    range-relative time overflows int32, so the probe's plan over
    their resident slices declines (`range`, a miss), the read's plan
    declines again and is counted, and the host's windows refuse the
    query as they always have.  The near segments' batch is in flight
    as a task by then."""
    s, writes = await open_segments(runtimes, (0, 1, FAR, FAR + 1))
    for first in (0, FAR):  # both pairs of slices resident
        await s.scan_aggregate(*field_query(
            first * SEGMENT_MS + WINDOW_A[0],
            (first + 1) * SEGMENT_MS + WINDOW_A[1]))
    lo, hi = WINDOW_A[0], (FAR + 1) * SEGMENT_MS + WINDOW_A[1]
    return s, field_query(lo + 60_000, hi), \
        "query range too far from segment epoch", \
        {"batched": 2, "single": 0, "calls": 1}, \
        {"resident.hit": 2, "resident.miss": 2, "reduce.runs": 2,
         "fallback.range": 2}


SCENARIOS = {"scattering_among_sorted": _scattering_among_sorted,
             "miss_among_hits": _miss_among_hits,
             "range_fallback": _range_fallback}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_mixed_plans_give_todays_answer_and_todays_counts(
        runtimes, scenario):
    """The same store, the same query, once with the batch and once
    with every slice by a call of its own: the same answer byte for
    byte, the same counts of `scan_decode_resident_total`,
    `scan_decode_reduce_total` and `scan_decode_rows_total`; numpy's
    answer; and the new counters read what was dispatched how."""
    async def served(mp):
        with _ForceXlaAgg():
            s, query, ref, want_batch, want = \
                await SCENARIOS[scenario](runtimes)
            try:
                if mp is not None:
                    singly(mp)
                c0, b0 = counts(), batch_counts()
                try:
                    got = await s.scan_aggregate(*query)
                except Error as exc:
                    got = str(exc)
                return got, moved(c0, counts()), \
                    moved(b0, batch_counts()), ref, want_batch, want
            finally:
                await s.close()

    batched, c_batched, b_batched, ref, want_batch, want = run(served(None))
    with pytest.MonkeyPatch.context() as mp:
        single, c_single, b_single, *_ = run(served(mp))
    if isinstance(ref, str):  # the refusal, word for word
        assert batched == single == ref
    else:
        _assert_same(batched, single, scenario)
        assert_matches_reference(batched, ref, scenario)
    assert c_batched == c_single
    assert {k: v for k, v in c_batched.items()
            if v and not k.startswith("rows.")} == want
    assert c_batched["rows.stored"] > 0 == c_batched["rows.uploaded"] \
        or scenario == "miss_among_hits"
    assert b_batched == want_batch
    slices = want_batch["batched"] + want_batch["single"]
    assert b_single == {"batched": 0, "single": slices, "calls": 0}


SHARD_SCHEMA = pa.schema([("m", pa.string()), ("host", pa.string()),
                          ("field", pa.string()), ("shard", pa.int32()),
                          ("ts", pa.int64()), ("v", pa.float64())])


def test_window_leaves_that_match_nothing_dispatch_nothing(runtimes):
    """A threshold no int32 reaches, on a raw int32 key: the probe's
    plan of every resident slice is DevicePart(part=None), a hit with
    nothing to dispatch, and the answer is empty."""
    def shard_query(lo, hi, least):
        pred = F.And((F.Eq("field", "f1"), F.Ge("shard", least),
                      F.TimeRangePred("ts", lo, hi)))
        return (ScanRequest(range=TimeRange.new(lo, hi), predicate=pred),
                narrow_spec(lo, hi))

    async def go():
        s = await CloudObjectStorage.open(
            "db", SEGMENT_MS, MemoryObjectStore(), SHARD_SCHEMA, 5,
            storage_config(decode={"mode": "device"}), runtimes=runtimes)
        try:
            rng = random.Random(SEED + 38)
            for seg in range(3):
                rows = [(m, h, f, 1, ts + seg * SEGMENT_MS, v)
                        for m, h, f, ts, v
                        in narrow_rows(rng, range(NARROW_TICKS))]
                cols = list(zip(*rows))
                await s.write(WriteRequest(pa.record_batch(
                    [pa.array(list(c), type=f.type)
                     for c, f in zip(cols, SHARD_SCHEMA)],
                    schema=SHARD_SCHEMA),
                    TimeRange.new(min(cols[4]), max(cols[4]) + 1)))
            memo_off(s)
            lo, hi = WINDOW_A[0], 2 * SEGMENT_MS + WINDOW_A[1]
            with _ForceXlaAgg():
                clear_caches(s)
                some = await s.scan_aggregate(*shard_query(lo, hi, 0))
                c0, b0 = counts(), batch_counts()
                again = await s.scan_aggregate(*shard_query(lo, hi, 1))
                assert moved(b0, batch_counts()) \
                    == {"batched": 3, "single": 0, "calls": 1}
                c1, b1 = counts(), batch_counts()
                none = await s.scan_aggregate(
                    *shard_query(lo, hi, 2**40))
            _assert_same(some, again, "the threshold admits every row")
            assert len(some[0]) > 0 and len(none[0]) == 0
            assert {k: v for k, v in moved(c0, c1).items() if v
                    and not k.startswith("rows.")} \
                == {"resident.hit": 3, "reduce.runs": 3}
            # hits, with nothing planned and nothing dispatched
            assert {k: v for k, v in moved(c1, counts()).items() if v} \
                == {"resident.hit": 3}
            assert moved(b1, batch_counts()) \
                == {"batched": 0, "single": 0, "calls": 0}
        finally:
            await s.close()

    run(go())


# ---------------------------------------------------------------------------
# both schedules
# ---------------------------------------------------------------------------


def test_pipelined_and_sequential_schedules_yield_the_same_parts(
        runtimes):
    """A miss that goes to the store among hits: the pipeline reads it
    while the batch is a task beside it; with `[scan.pipeline]` off the
    pump does.  Either way `_cached_windows` yields the same segments
    in plan order with the same parts."""
    async def yielded(enabled):
        with _ForceXlaAgg():
            s, query, ref, *_ = await _miss_among_hits(runtimes)
            try:
                s.config.scan.pipeline.enabled = enabled
                seen, real = [], s.reader._cached_windows

                async def recording(plan):
                    async for seg, windows, read_s in real(plan):
                        seen.append((seg.segment_start, list(windows),
                                     plan.pipeline_active))
                        yield seg, windows, read_s

                s.reader._cached_windows = recording
                got = await s.scan_aggregate(*query)
                assert_matches_reference(got, ref, f"pipeline {enabled}")
                return got, seen
            finally:
                await s.close()

    on, seen_on = run(yielded(True))
    off, seen_off = run(yielded(False))
    _assert_same(on, off, "pipeline on vs off")
    assert [active for *_, active in seen_on] == [True] * 3
    assert [active for *_, active in seen_off] == [False] * 3
    assert [start for start, *_ in seen_on] \
        == [start for start, *_ in seen_off] \
        == [0, SEGMENT_MS, 2 * SEGMENT_MS]
    for (start, w_on, _), (_, w_off, _) in zip(seen_on, seen_off):
        (p_on,), (p_off,) = w_on, w_off
        assert_same_part(p_on, p_off, f"segment {start}")


def test_an_abandoned_scan_leaves_no_task_behind(runtimes):
    """The batch runs as a task beside the reads; a consumer that
    stops at the first segment, the one that was read, leaves no task
    pending, and the next scan answers as ever."""
    async def go():
        with _ForceXlaAgg():
            s, query, ref, *_ = await _miss_among_hits(runtimes, at=0)
            try:
                plan = await s._plan_aggregate(*query)
                before = asyncio.all_tasks()
                segments = s.reader.aggregate_segments(plan, query[1])
                async for seg_start, _parts in segments:
                    assert seg_start == 0
                    break
                await segments.aclose()
                assert {t for t in asyncio.all_tasks()
                        if not t.done()} <= before
                assert_matches_reference(
                    await s.scan_aggregate(*query), ref, "the next scan")
            finally:
                await s.close()

    run(go())


# ---------------------------------------------------------------------------
# the counters at rest
# ---------------------------------------------------------------------------


def test_batch_counters_are_exported_at_rest():
    text = registry.render()
    for mode in ("batched", "single"):
        assert f'scan_decode_batch_slices_total{{mode="{mode}"}}' in text
    assert "scan_decode_batch_total" in text


def test_the_batched_program_is_booked_under_the_routes_name():
    """One ledger entry for the route: `route.dispatches_per_query`
    counts calls, of either program."""
    assert device_decode._decode_batch_jit._rec \
        is device_decode._decode_aggregate_jit._rec
    assert device_decode._decode_batch_jit._rec.name \
        == "_decode_aggregate_jit"
    assert deviceprof.profiler._record("_decode_aggregate_jit") \
        is device_decode._decode_aggregate_jit._rec
