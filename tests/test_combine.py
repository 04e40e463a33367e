"""Sparse/streaming combine tests (ISSUE 9): sparse-vs-dense
bit-identity (unit fuzz + seeded end-to-end chaos across agg sets,
filters, ranges, and mid-scan compaction), the top-k pushdown's
O(k x buckets) materialization bound, delta-summation memo rebasing /
invalidation, requested-aggs-only allocation, `[scan.combine]` config
plumbing, and the dense-grid lint rule.

The seeded chaos test rides `make chaos` with knobs COMBINE_SEED /
COMBINE_SCHEDULES; the fast tier-1 variant runs a fixed small
subset."""

import asyncio
import os
import random

import numpy as np
import pyarrow as pa
import pytest

from horaedb_tpu.common import ReadableDuration
from horaedb_tpu.common import runtimes as runtimes_mod
from horaedb_tpu.common.error import Error
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.ops.downsample import ALL_AGGS
from horaedb_tpu.storage import combine as combine_mod
from horaedb_tpu.storage.config import (
    StorageConfig,
    ThreadsConfig,
    from_dict,
)
from horaedb_tpu.storage.plan import TopKSpec, apply_top_k
from horaedb_tpu.storage.read import AggregateSpec, ScanRequest
from horaedb_tpu.storage.storage import CloudObjectStorage, WriteRequest
from horaedb_tpu.storage.types import TimeRange

SEED = int(os.environ.get("COMBINE_SEED", "1337"), 0)
SCHEDULES = int(os.environ.get("COMBINE_SCHEDULES", "25"), 0)

SEGMENT_MS = 3_600_000
I64_MIN = np.iinfo(np.int64).min
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


# ---------------------------------------------------------------------------
# synthetic-part fuzz: sparse == dense, top-k pushdown == host top-k
# ---------------------------------------------------------------------------


def _rand_parts(rng: np.random.Generator, num_buckets: int,
                universe: np.ndarray, n_parts: int) -> list:
    """Random partial grids with the device kernel's conventions:
    sorted unique group values, f32 cells with combine identities in
    empty cells, int64 last_ts with the I64_MIN sentinel."""
    parts = []
    for _ in range(n_parts):
        if rng.random() < 0.4:
            values = universe  # full-group part: the fast-paste shape
        else:
            k = int(rng.integers(1, len(universe) + 1))
            values = np.sort(rng.choice(universe, size=k, replace=False))
        lo = int(rng.integers(0, num_buckets))
        width = int(rng.integers(1, num_buckets - lo + 1))
        g = len(values)
        count = rng.integers(0, 3, (g, width)).astype(np.float32)
        has = count > 0
        vals = rng.normal(size=(g, width)).astype(np.float32)
        grids = {
            "count": count,
            "sum": np.where(has, vals * count, 0.0).astype(np.float32),
            "min": np.where(has, vals - 1.0, np.inf).astype(np.float32),
            "max": np.where(has, vals + 1.0, -np.inf).astype(np.float32),
            "last": np.where(has, vals, 0.0).astype(np.float32),
            "last_ts": np.where(
                has, rng.integers(0, 10**9, (g, width)), I64_MIN
            ).astype(np.int64),
        }
        parts.append((values.copy(), lo, grids))
    return parts


def _assert_same(a, b, ctx=""):
    va, ga = a
    vb, gb = b
    assert np.array_equal(va, vb), f"{ctx}: group values differ"
    assert set(ga) == set(gb), f"{ctx}: agg keys {set(ga)} != {set(gb)}"
    for k in ga:
        assert np.asarray(ga[k]).tobytes() == np.asarray(gb[k]).tobytes(), \
            f"{ctx}: grid {k!r} differs"


def test_sparse_dense_bit_identity_fuzz():
    rng = np.random.default_rng(SEED)
    for it in range(60):
        num_buckets = int(rng.integers(1, 40))
        universe = np.sort(rng.choice(
            np.arange(1, 500, dtype=np.uint64),
            size=int(rng.integers(1, 12)), replace=False))
        parts = _rand_parts(rng, num_buckets, universe,
                            int(rng.integers(0, 8)))
        for which in WHICH_SETS:
            sparse = combine_mod.combine_parts(
                parts, num_buckets, which=which, mode="sparse")
            dense = combine_mod.combine_parts(
                parts, num_buckets, which=which, mode="dense")
            _assert_same(sparse, dense, f"iter {it} which={which}")


def test_requested_aggs_only_allocated():
    """Both folds emit exactly the requested aggregates (plus their
    carried deps: count always, last_ts with last) — no six-grid set
    for a subset query."""
    rng = np.random.default_rng(SEED)
    universe = np.arange(1, 5, dtype=np.uint64)
    parts = _rand_parts(rng, 10, universe, 3)
    for which, keys in ((("avg",), {"count", "avg"}),
                        (("min", "max"), {"count", "min", "max"}),
                        (("last",), {"count", "last", "last_ts"}),
                        (("count",), {"count"})):
        for mode in combine_mod.COMBINE_MODES:
            _v, grids = combine_mod.combine_parts(
                parts, 10, which=which, mode=mode)
            assert set(grids) == keys, (which, mode)


def _dense_top_k(parts, num_buckets, which, tk):
    """The control: dense combine + finalize's empty-group drop + host
    apply_top_k over the full grid."""
    values, grids = combine_mod.combine_aggregate_parts(
        parts, num_buckets, which=which)
    if len(values):
        nonzero = grids["count"].sum(axis=1) > 0
        values = values[nonzero]
        grids = {k: v[nonzero] for k, v in grids.items()}
    return apply_top_k(values, grids, tk)


def test_top_k_pushdown_matches_dense_fuzz():
    rng = np.random.default_rng(SEED + 1)
    for it in range(60):
        num_buckets = int(rng.integers(1, 30))
        universe = np.sort(rng.choice(
            np.arange(1, 500, dtype=np.uint64),
            size=int(rng.integers(1, 14)), replace=False))
        parts = _rand_parts(rng, num_buckets, universe,
                            int(rng.integers(0, 8)))
        which = WHICH_SETS[int(rng.integers(0, len(WHICH_SETS)))]
        by_pool = [a for a in which if a != "last_ts"] + ["count"]
        tk = TopKSpec(k=int(rng.integers(1, 6)),
                      by=by_pool[int(rng.integers(0, len(by_pool)))],
                      largest=bool(rng.integers(0, 2)))
        pushed = combine_mod.combine_top_k(parts, num_buckets, which, tk)
        control = _dense_top_k(parts, num_buckets, which, tk)
        _assert_same(pushed, control, f"iter {it} which={which} tk={tk}")


def test_top_k_requires_ranking_agg():
    with pytest.raises(Error, match="top-k"):
        combine_mod.combine_top_k(
            [], 4, ("avg",), TopKSpec(k=2, by="max"))


def test_top_k_materialized_cells_bounded():
    """The pushdown's materialized output is O(k x buckets x aggs),
    independent of group cardinality — asserted via the
    scan_combine_materialized_cells_total counter."""
    rng = np.random.default_rng(SEED + 2)
    num_buckets, k = 16, 3
    deltas = []
    for g in (40, 400):
        universe = np.arange(1, g + 1, dtype=np.uint64)
        parts = _rand_parts(rng, num_buckets, universe, 4)
        before = combine_mod._MATERIALIZED.value
        _values, grids = combine_mod.combine_top_k(
            parts, num_buckets, ("avg", "max"), TopKSpec(k=k, by="max"))
        deltas.append(combine_mod._MATERIALIZED.value - before)
        assert len(next(iter(grids.values()))) <= k
    assert deltas[0] == deltas[1] == k * num_buckets * 3  # count,avg,max


# ---------------------------------------------------------------------------
# end-to-end: storage fixtures
# ---------------------------------------------------------------------------


def batch(rows):
    k, t, v = zip(*rows)
    return pa.record_batch(
        [pa.array(list(k)), pa.array(list(t), type=pa.int64()),
         pa.array(list(v), type=pa.float64())], schema=SCHEMA)


def wreq(rows):
    lo = min(r[1] for r in rows)
    hi = max(r[1] for r in rows) + 1
    return WriteRequest(batch(rows), TimeRange.new(lo, hi))


def storage_config(**combine):
    cfg = from_dict(StorageConfig, {
        "scheduler": {"schedule_interval": "1h", "input_sst_min_num": 2},
        "scan": {"combine": combine} if combine else {},
    })
    cfg.manifest.merge_interval = ReadableDuration.parse("1h")
    cfg.scrub.interval = ReadableDuration.parse("1h")
    return cfg


async def open_storage(store, runtimes, **combine):
    return await CloudObjectStorage.open(
        "db", SEGMENT_MS, store, SCHEMA, 2,
        storage_config(**combine), runtimes=runtimes)


def agg_spec(lo: int, hi: int, bucket_ms: int = 60_000,
             which=("avg", "max", "last")) -> AggregateSpec:
    return AggregateSpec(group_col="k", ts_col="ts", value_col="v",
                         range_start=lo, bucket_ms=bucket_ms,
                         num_buckets=max(1, -(-(hi - lo) // bucket_ms)),
                         which=which)


async def write_segments(s, rng, segments=4, rows_per=250, keys=6):
    for seg in range(segments):
        rows = [(f"k{rng.randint(0, keys - 1)}",
                 seg * SEGMENT_MS + rng.randint(0, SEGMENT_MS - 1000),
                 float(i)) for i in range(rows_per)]
        await s.write(wreq(rows))


def clear_caches(s, memo=True):
    s.reader.scan_cache.clear()
    s.reader.encoded_cache.clear()
    if memo:
        s.reader.parts_memo.clear()


async def fresh_dense(s, req, spec, top_k=None):
    """The bit-identity control: dense mode, every cache/memo cold."""
    mode = s.config.scan.combine.mode
    s.config.scan.combine.mode = "dense"
    clear_caches(s)
    try:
        if top_k is None:
            return await s.scan_aggregate(req, spec)
        values, grids = await s.scan_aggregate(req, spec)
        return apply_top_k(values, grids, top_k)
    finally:
        s.config.scan.combine.mode = mode


# ---------------------------------------------------------------------------
# delta-summation memo
# ---------------------------------------------------------------------------


class TestPartsMemo:
    def test_narrowed_range_served_from_memo(self, runtimes):
        """A full-span query records per-segment partials; a narrowed
        range (same bucket grid phase) serves its interior segments
        from the memo, bit-identical to a cold recompute."""

        async def go():
            s = await open_storage(MemoryObjectStore(), runtimes)
            try:
                await write_segments(s, random.Random(SEED))
                full_span = (0, 4 * SEGMENT_MS)
                await s.scan_aggregate(
                    ScanRequest(range=TimeRange.new(*full_span)),
                    agg_spec(*full_span))
                assert s.reader.parts_memo.stats()["entries"] == 4
                lo, hi = SEGMENT_MS, 3 * SEGMENT_MS
                clear_caches(s, memo=False)
                h0 = s.reader.parts_memo.stats()["hits"]
                narrow = await s.scan_aggregate(
                    ScanRequest(range=TimeRange.new(lo, hi)),
                    agg_spec(lo, hi))
                assert s.reader.parts_memo.stats()["hits"] - h0 == 2
                control = await fresh_dense(
                    s, ScanRequest(range=TimeRange.new(lo, hi)),
                    agg_spec(lo, hi))
                _assert_same(narrow, control, "narrowed range")
            finally:
                await s.close()

        run(go())

    def test_widened_range_recomputes(self, runtimes):
        """Widening past the recorded grid reaches buckets the stored
        partials were clipped away from — the memo must refuse
        (uncovered) and the recompute must stay correct."""

        async def go():
            s = await open_storage(MemoryObjectStore(), runtimes)
            try:
                await write_segments(s, random.Random(SEED + 1))
                # recorded range ends MID-segment, so the stored
                # partials are clipped inside segment 1 — a wider query
                # reaches the clipped-away buckets and must recompute
                lo, hi = SEGMENT_MS, SEGMENT_MS + SEGMENT_MS // 2
                await s.scan_aggregate(
                    ScanRequest(range=TimeRange.new(lo, hi)),
                    agg_spec(lo, hi))
                clear_caches(s, memo=False)
                unc0 = combine_mod._MEMO_UNCOVERED.value
                h0 = s.reader.parts_memo.stats()["hits"]
                wide_span = (0, 4 * SEGMENT_MS)
                wide = await s.scan_aggregate(
                    ScanRequest(range=TimeRange.new(*wide_span)),
                    agg_spec(*wide_span))
                assert combine_mod._MEMO_UNCOVERED.value > unc0
                # a found-but-uncovered entry did NOT serve — it must
                # not count as a hit (refine_memo_fraction rides this)
                assert s.reader.parts_memo.stats()["hits"] == h0
                control = await fresh_dense(
                    s, ScanRequest(range=TimeRange.new(*wide_span)),
                    agg_spec(*wide_span))
                _assert_same(wide, control, "widened range")
            finally:
                await s.close()

        run(go())

    def test_write_invalidates_structurally(self, runtimes):
        """A write changes the segment's SST set, so the stale entry
        misses by key — no explicit invalidation, same discipline as
        the scan cache."""

        async def go():
            s = await open_storage(MemoryObjectStore(), runtimes)
            try:
                await write_segments(s, random.Random(SEED + 2),
                                     segments=2)
                span = (0, 2 * SEGMENT_MS)
                req = ScanRequest(range=TimeRange.new(*span))
                await s.scan_aggregate(req, agg_spec(*span))
                await s.write(wreq([("k0", 5000, 1e6)]))
                clear_caches(s, memo=False)
                after = await s.scan_aggregate(req, agg_spec(*span))
                control = await fresh_dense(s, req, agg_spec(*span))
                _assert_same(after, control, "post-write")
                # the new write's max must be visible (memo did not
                # serve the stale partials)
                _values, grids = after
                assert np.nanmax(np.asarray(grids["max"])) == 1e6
            finally:
                await s.close()

        run(go())

    def test_memo_disabled_by_zero_budget(self, runtimes):
        async def go():
            s = await open_storage(MemoryObjectStore(), runtimes,
                                   memo_max_bytes=0)
            try:
                await write_segments(s, random.Random(SEED), segments=2)
                span = (0, 2 * SEGMENT_MS)
                await s.scan_aggregate(
                    ScanRequest(range=TimeRange.new(*span)),
                    agg_spec(*span))
                assert s.reader.parts_memo.stats()["entries"] == 0
                # the memo's residency is an operator surface
                assert "parts_memo" in s.reader.cache_stats()
            finally:
                await s.close()

        run(go())


def test_dense_mode_disables_topk_pushdown(runtimes):
    """[scan.combine] mode = "dense" must A/B the WHOLE pre-change
    path: a top-k query materializes the full grid and ranks host-side
    (apply_top_k) instead of the pushdown, bit-identical to
    sparse+pushdown."""

    async def go():
        s = await open_storage(MemoryObjectStore(), runtimes)
        try:
            await write_segments(s, random.Random(SEED + 7))
            span = (0, 4 * SEGMENT_MS)
            req = ScanRequest(range=TimeRange.new(*span))
            spec = agg_spec(*span)  # emits count/avg/max/last/last_ts
            tk = TopKSpec(k=2, by="max")
            pushed = await s.scan_aggregate(req, spec, top_k=tk)
            clear_caches(s)
            s.config.scan.combine.mode = "dense"
            try:
                m0 = combine_mod._MATERIALIZED.value
                dense = await s.scan_aggregate(req, spec, top_k=tk)
                # the control materialized the FULL grid (all groups);
                # the pushdown would have stopped at k x buckets x aggs
                assert (combine_mod._MATERIALIZED.value - m0
                        > tk.k * spec.num_buckets * 5)
            finally:
                s.config.scan.combine.mode = "sparse"
            _assert_same(pushed, dense, "dense-mode top-k control")
        finally:
            await s.close()

    run(go())


def test_bad_combine_mode_rejected_at_open(runtimes):
    async def go():
        with pytest.raises(Error, match="scan.combine"):
            await open_storage(MemoryObjectStore(), runtimes,
                               mode="bogus")

    run(go())


def test_config_roundtrip():
    cfg = from_dict(StorageConfig, {
        "scan": {"combine": {"mode": "dense",
                             "memo_max_bytes": 1 << 20}}})
    assert cfg.scan.combine.mode == "dense"
    assert cfg.scan.combine.memo_max_bytes == 1 << 20
    assert StorageConfig().scan.combine.mode == "sparse"


# ---------------------------------------------------------------------------
# seeded end-to-end chaos: sparse+memo == sparse cold == dense cold
# ---------------------------------------------------------------------------


def _chaos_schedule(i: int, runtimes):
    """One seeded schedule: random writes/compactions/evictions
    interleaved with downsample and top-k queries over random ranges,
    agg subsets, and filters — each query runs sparse-with-memo (warm,
    the serving shape), then sparse cold, then dense cold, and all
    three must be byte-identical.  One op races a query against a
    mid-scan compaction."""
    from horaedb_tpu.ops import filter as F

    async def go():
        rng = random.Random(SEED + i)
        s = await open_storage(MemoryObjectStore(), runtimes)

        async def checked_query():
            lo = rng.randrange(0, 2 * SEGMENT_MS, 250)
            hi = lo + rng.randrange(250, 3 * SEGMENT_MS, 250)
            which = WHICH_SETS[rng.randrange(len(WHICH_SETS))]
            bucket_ms = rng.choice([250, 60_000])
            spec = agg_spec(lo, hi, bucket_ms=bucket_ms, which=which)
            pred = rng.choice([None, F.Eq("k", f"k{rng.randint(0, 5)}"),
                               F.Ge("ts", SEGMENT_MS // 2)])
            req = ScanRequest(range=TimeRange.new(lo, hi), predicate=pred)
            if rng.random() < 0.35:
                by_pool = [a for a in which if a != "last_ts"] + ["count"]
                tk = TopKSpec(k=rng.randint(1, 4),
                              by=rng.choice(by_pool),
                              largest=rng.random() < 0.5)
                warm = await s.scan_aggregate(req, spec, top_k=tk)
                clear_caches(s)
                cold = await s.scan_aggregate(req, spec, top_k=tk)
                control = await fresh_dense(s, req, spec, top_k=tk)
            else:
                tk = None
                warm = await s.scan_aggregate(req, spec)
                clear_caches(s)
                cold = await s.scan_aggregate(req, spec)
                control = await fresh_dense(s, req, spec)
            ctx = f"schedule {i} lo={lo} hi={hi} which={which} tk={tk}"
            _assert_same(warm, cold, f"{ctx} warm-vs-cold")
            _assert_same(cold, control, f"{ctx} sparse-vs-dense")

        async def compact_once():
            sched = s.compact_scheduler
            task = await sched.picker.pick_candidate()
            if task is not None:
                await sched.executor.execute(task)

        try:
            await write_segments(s, rng, segments=3, rows_per=120)
            for _op in range(10):
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
                    # mid-scan structural churn: the query and a
                    # compaction interleave at await points; the replan
                    # -on-race machinery must keep all legs identical
                    await asyncio.gather(checked_query(), compact_once())
                else:
                    await checked_query()
            await checked_query()
        finally:
            await s.close()

    run(go())


@pytest.mark.slow
def test_seeded_combine_chaos(runtimes):
    for i in range(SCHEDULES):
        _chaos_schedule(i, runtimes)


def test_seeded_combine_chaos_fast(runtimes):
    """Tier-1 variant: a fixed small slice of the chaos schedules."""
    for i in range(2):
        _chaos_schedule(i, runtimes)


# ---------------------------------------------------------------------------
# lint rule
# ---------------------------------------------------------------------------


def test_lint_dense_grid_rule(tmp_path):
    """A dense (g, num_buckets) numpy allocation under horaedb_tpu/ is
    an error outside storage/combine.py; bucket-free 2-D shapes and
    combine.py itself are clean."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "lint_under_test",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "lint.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)

    bad = ("import numpy as np\n\n\n"
           "def f(g, num_buckets):\n"
           "    return np.zeros((g, num_buckets))\n")
    ok = ("import numpy as np\n\n\n"
          "def f(g, width):\n"
          "    return np.zeros((g, width))\n")
    edir = tmp_path / "horaedb_tpu" / "metric_engine"
    edir.mkdir(parents=True)
    (edir / "x.py").write_text(bad)
    problems = lint.lint_file(edir / "x.py")
    assert any("combine" in p for p in problems), problems
    (edir / "y.py").write_text(ok)
    assert not lint.lint_file(edir / "y.py")
    sdir = tmp_path / "horaedb_tpu" / "storage"
    sdir.mkdir(parents=True)
    (sdir / "combine.py").write_text(bad)
    assert not lint.lint_file(sdir / "combine.py")
