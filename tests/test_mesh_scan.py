"""2-D (time x series) mesh scan tests (ISSUE 15): the [scan.mesh]
segmented-reduction combine byte-compared against the single-chip
control across agg sets, filters, ranges, top-k, and seeded
write/compact/evict interleavings — including a simulated lost-shard
schedule exercising the per-round single-chip fallback and a deadline
-mid-mesh cancel with zero leaked tasks — plus the O(k x buckets x
aggs) top-k egress bound (counter-asserted at two cardinalities), the
sum-overlap exactness gate, `[scan.mesh]` config plumbing, and the
mesh-construction lint rule.

The seeded chaos test rides `make chaos` with knobs MESH_SEED /
MESH_SCHEDULES; the fast tier-1 variant runs a fixed small subset.
Both legs force HORAEDB_HOST_AGG=0 so the control aggregates with the
same XLA window kernel the mesh program calls — the A/B then isolates
exactly WHERE the combine ran (the PR 12 bit-identity convention; the
numpy f64 twin is a different rounding schedule by design)."""

import asyncio
import os
import random

import jax
import numpy as np
import pyarrow as pa
import pytest

from horaedb_tpu.common import ReadableDuration
from horaedb_tpu.common import runtimes as runtimes_mod
from horaedb_tpu.common.deadline import (
    Deadline,
    DeadlineExceeded,
    deadline_scope,
)
from horaedb_tpu.common.error import Error
from horaedb_tpu.objstore import FaultInjectingStore, MemoryObjectStore
from horaedb_tpu.ops import filter as F
from horaedb_tpu.ops.downsample import ALL_AGGS
from horaedb_tpu.storage import read as read_mod
from horaedb_tpu.storage.config import (
    StorageConfig,
    ThreadsConfig,
    from_dict,
)
from horaedb_tpu.storage.plan import TopKSpec
from horaedb_tpu.storage.read import AggregateSpec, ScanRequest
from horaedb_tpu.storage.storage import CloudObjectStorage, WriteRequest
from horaedb_tpu.storage.types import TimeRange

SEED = int(os.environ.get("MESH_SEED", "1337"), 0)
SCHEDULES = int(os.environ.get("MESH_SCHEDULES", "12"), 0)

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
    scan.setdefault("mesh", {"enabled": True})
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


def mesh_fallbacks(reason: str) -> float:
    child = read_mod._MESH_FALLBACK_CHILDREN.get(reason)
    return 0.0 if child is None else child.value


class _ForceXlaAgg:
    """Force HORAEDB_HOST_AGG=0 (and the fused accumulator off) for a
    block: the mesh-off control then aggregates with the same XLA
    window kernel the mesh program shards, isolating WHERE the combine
    ran (see module doc)."""

    def __enter__(self):
        self._old = {k: os.environ.get(k)
                     for k in ("HORAEDB_HOST_AGG", "HORAEDB_FUSED_AGG")}
        os.environ["HORAEDB_HOST_AGG"] = "0"
        os.environ["HORAEDB_FUSED_AGG"] = "0"

    def __exit__(self, *exc):
        for k, v in self._old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


class _MeshOff:
    """Run the same reader with the mesh detached — THE single-chip
    control leg (aggregate_segments routes through the plain pump
    exactly as a mesh-disabled engine would)."""

    def __init__(self, s):
        self.reader = s.reader

    def __enter__(self):
        self._mesh = self.reader.scan_mesh
        self.reader.scan_mesh = None

    def __exit__(self, *exc):
        self.reader.scan_mesh = self._mesh


async def _query_both(s, req, spec, tk=None, ctx=""):
    """One query served mesh-warm, mesh-cold, and by the single-chip
    control — all three byte-compared."""
    warm = await s.scan_aggregate(req, spec, top_k=tk)
    clear_caches(s)
    cold = await s.scan_aggregate(req, spec, top_k=tk)
    clear_caches(s)
    with _MeshOff(s):
        control = await s.scan_aggregate(req, spec, top_k=tk)
    _assert_same(warm, cold, f"{ctx} warm-vs-cold")
    _assert_same(cold, control, f"{ctx} mesh-vs-off")
    return control


# ---------------------------------------------------------------------------
# direct bit-identity + routing
# ---------------------------------------------------------------------------


def test_mesh_vs_off_bit_identity_basic(runtimes, monkeypatch):
    """Overlapping writes (cross-SST duplicate PKs exercising dedup
    through the mesh rounds), every agg set, filters incl. In/range,
    and top-k by every ranking: mesh-on grids must be byte-identical
    with the single-chip control, and rounds must actually run."""

    async def go():
        s = await open_storage(MemoryObjectStore(), runtimes)
        try:
            rng = random.Random(SEED)
            await write_segments(s, rng, segments=6, rows_per=200)
            # duplicate-PK overwrites across SSTs
            await write_segments(s, rng, segments=3, rows_per=150)
            lo, hi = 0, 6 * SEGMENT_MS
            rounds0 = read_mod._MESH_ROUNDS.value
            for which in WHICH_SETS:
                spec = agg_spec(lo, hi, which=which)
                for pred in (None, F.Eq("k", "k3"),
                             F.In("k", ["k1", "k4"]),
                             F.Ge("ts", SEGMENT_MS // 2)):
                    req = ScanRequest(range=TimeRange.new(lo, hi),
                                      predicate=pred)
                    await _query_both(s, req, spec,
                                      ctx=f"{which} pred={pred}")
            for tk in (TopKSpec(k=3, by="max"),
                       TopKSpec(k=2, by="min", largest=False),
                       TopKSpec(k=3, by="last"),
                       TopKSpec(k=2, by="avg"),
                       TopKSpec(k=1, by="count")):
                which = tuple(sorted({tk.by, "avg", "count"}
                                     & set(ALL_AGGS))) or ("avg",)
                if tk.by not in which:
                    which = which + (tk.by,)
                spec = agg_spec(lo, hi, which=which)
                req = ScanRequest(range=TimeRange.new(lo, hi))
                await _query_both(s, req, spec, tk=tk, ctx=f"tk={tk}")
            assert read_mod._MESH_ROUNDS.value > rounds0, \
                "mesh never dispatched a round"
            # a repeat whose rounds run again (parts memo emptied,
            # windows still cached) takes its time-sharded round
            # stacks from the stack cache: no (T, cap) stack is built
            # or uploaded again, only the rounds' (T,) segment ids
            spec = agg_spec(lo, hi, which=("avg",))
            req = ScanRequest(range=TimeRange.new(lo, hi))
            first = await s.scan_aggregate(req, spec)
            s.reader.parts_memo.clear()
            hits0 = s.reader._stack_cache_hits
            misses0 = s.reader._stack_cache_misses
            puts = []
            real_put = jax.device_put

            def counting_put(x, *a, **kw):
                puts.append(np.shape(x))
                return real_put(x, *a, **kw)

            monkeypatch.setattr(jax, "device_put", counting_put)
            again = await s.scan_aggregate(req, spec)
            monkeypatch.setattr(jax, "device_put", real_put)
            assert s.reader._stack_cache_hits > hits0
            assert s.reader._stack_cache_misses == misses0
            assert all(len(shape) <= 1 for shape in puts), \
                f"repeat meshed query uploaded: {puts}"
            _assert_same(first, again, "repeat from the stack cache")
        finally:
            await s.close()

    with _ForceXlaAgg():
        run(go())


def test_topk_mesh_bounded_egress(runtimes):
    """The acceptance bound: per-chip combine egress of the device
    -scored top-k path is O(k x buckets x aggs) per run part plus an
    O(groups) score vector — asserted against the cell counter at TWO
    cardinalities, so the bound provably does not scale with the
    group count."""

    async def go(keys: int):
        s = await open_storage(MemoryObjectStore(), runtimes)
        try:
            rng = random.Random(SEED)
            await write_segments(s, rng, segments=4, rows_per=400,
                                 keys=keys)
            lo, hi = 0, 4 * SEGMENT_MS
            spec = agg_spec(lo, hi, which=("avg", "max"))
            tk = TopKSpec(k=3, by="max")
            req = ScanRequest(range=TimeRange.new(lo, hi))
            clear_caches(s)
            served0 = read_mod._MESH_TOPK.value
            cells0 = read_mod._MESH_PART_CELLS.value
            got = await s.scan_aggregate(req, spec, top_k=tk)
            assert read_mod._MESH_TOPK.value == served0 + 1, \
                "top-k did not take the device-scored mesh path"
            cells = read_mod._MESH_PART_CELLS.value - cells0
            # <= runs x k x per-run width x grids; runs = 4 segments,
            # grids = count/avg needs (count,sum,avg? parts carry
            # count+sum+min? parts carry the partial set) — bound
            # loosely by parts * k * num_buckets * 8 grid kinds
            bound = 4 * tk.k * spec.num_buckets * 8
            assert cells <= bound, (cells, bound)
            with _MeshOff(s):
                clear_caches(s)
                control = await s.scan_aggregate(req, spec, top_k=tk)
            _assert_same(got, control, f"topk keys={keys}")
            return cells
        finally:
            await s.close()

    with _ForceXlaAgg():
        small = run(go(6))
        large = run(go(200))
        # the egress must not scale with cardinality (scores are
        # counted separately): identical k/buckets -> identical bound
        assert large <= small * 2, (small, large)


def test_lost_shard_round_fallback(runtimes):
    """A mesh round dispatch that dies (lost shard / XLA failure)
    falls back to the single-chip kernel PER ROUND, is counted, and
    the query's grids stay byte-identical."""

    async def go():
        s = await open_storage(MemoryObjectStore(), runtimes)
        try:
            rng = random.Random(SEED + 1)
            await write_segments(s, rng, segments=5, rows_per=150)
            lo, hi = 0, 5 * SEGMENT_MS
            spec = agg_spec(lo, hi)
            req = ScanRequest(range=TimeRange.new(lo, hi))
            with _MeshOff(s):
                control = await s.scan_aggregate(req, spec)
            clear_caches(s)
            real = s.reader._run_mesh_round
            fails = {"left": 2}

            def flaky(items, spec_, plan, **kw):
                if fails["left"] > 0:
                    fails["left"] -= 1
                    raise RuntimeError("simulated lost shard")
                return real(items, spec_, plan, **kw)

            s.reader._run_mesh_round = flaky
            before = mesh_fallbacks("mesh_error")
            try:
                got = await s.scan_aggregate(req, spec)
            finally:
                s.reader._run_mesh_round = real
            assert mesh_fallbacks("mesh_error") == before + 2
            assert fails["left"] == 0, "fault never fired"
            _assert_same(got, control, "lost-shard fallback")

            # the top-k WINNER pass loses a shard (scoring succeeded):
            # the query downgrades to full-width parts, still
            # byte-identical with the control's combine_top_k
            tk = TopKSpec(k=2, by="max")
            spec_tk = agg_spec(lo, hi, which=("max", "avg"))
            clear_caches(s)
            with _MeshOff(s):
                ctl_tk = await s.scan_aggregate(req, spec_tk, top_k=tk)
            clear_caches(s)
            calls = {"scoreless": 0}

            def flaky_pass2(items, spec_, plan, **kw):
                if kw.get("download", True) is False:
                    calls["scoreless"] += 1
                    if calls["scoreless"] == 3:  # first pass-2 round
                        raise RuntimeError("lost shard in winner pass")
                return real(items, spec_, plan, **kw)

            s.reader._run_mesh_round = flaky_pass2
            before = mesh_fallbacks("mesh_error")
            try:
                got_tk = await s.scan_aggregate(req, spec_tk, top_k=tk)
            finally:
                s.reader._run_mesh_round = real
            assert calls["scoreless"] >= 3, "winner pass never ran"
            assert mesh_fallbacks("mesh_error") == before + 1
            _assert_same(got_tk, ctl_tk, "winner-pass downgrade")
        finally:
            await s.close()

    with _ForceXlaAgg():
        run(go())


def test_deadline_mid_mesh_cancels_no_leaked_tasks(runtimes):
    """A DeadlineExceeded mid-mesh-scan must drain the in-flight round
    task before control returns: zero scan-spawned tasks alive at
    teardown (the pipeline discipline, extended to the mesh pump)."""

    async def go():
        store = FaultInjectingStore(MemoryObjectStore(), seed=SEED,
                                    latency_range=(0.05, 0.05))
        s = await open_storage(store, runtimes)
        try:
            for seg in range(6):
                await s.write(wreq([
                    (f"k{j % 4}", seg * SEGMENT_MS + j, float(j))
                    for j in range(300)]))
            clear_caches(s)
            tasks_before = asyncio.all_tasks()
            with deadline_scope(Deadline.after(0.02, "test query")):
                with pytest.raises(DeadlineExceeded):
                    req = ScanRequest(range=TimeRange.new(
                        0, 6 * SEGMENT_MS))
                    await s.scan_aggregate(req, agg_spec(
                        0, 6 * SEGMENT_MS))
            leaked = [t for t in asyncio.all_tasks() - tasks_before
                      if not t.done()]
            assert not leaked, f"mesh scan leaked tasks: {leaked}"
            # the top-k mesh path checkpoints between rounds too
            with deadline_scope(Deadline.after(0.02, "topk query")):
                with pytest.raises(DeadlineExceeded):
                    req = ScanRequest(range=TimeRange.new(
                        0, 6 * SEGMENT_MS))
                    await s.scan_aggregate(
                        req, agg_spec(0, 6 * SEGMENT_MS,
                                      which=("max", "avg")),
                        top_k=TopKSpec(k=2, by="max"))
            leaked = [t for t in asyncio.all_tasks() - tasks_before
                      if not t.done()]
            assert not leaked, f"mesh top-k leaked tasks: {leaked}"
        finally:
            await s.close()

    with _ForceXlaAgg():
        run(go())


def test_sum_overlap_gate_falls_back(runtimes):
    """A multi-window segment whose PK-split boundary shares a group
    across windows must NOT f32-combine sum cells on the mesh: the
    round falls back (reason=sum_overlap) and stays byte-identical."""

    async def go():
        # tiny windows force PK-range splitting within one segment;
        # a single hot key guarantees the boundary split
        s = await open_storage(MemoryObjectStore(), runtimes,
                               max_window_rows=128,
                               stream_read_min_rows=64)
        try:
            rows = [("hot", j * 7, float(j)) for j in range(900)]
            await s.write(wreq(rows))
            lo, hi = 0, SEGMENT_MS
            spec = agg_spec(lo, hi, which=("sum", "avg"))
            req = ScanRequest(range=TimeRange.new(lo, hi))
            before = mesh_fallbacks("sum_overlap")
            got = await s.scan_aggregate(req, spec)
            with _MeshOff(s):
                clear_caches(s)
                control = await s.scan_aggregate(req, spec)
            _assert_same(got, control, "sum-overlap")
            assert mesh_fallbacks("sum_overlap") > before
            # the same shape WITHOUT sum/avg stays on the mesh
            clear_caches(s)
            rounds0 = read_mod._MESH_ROUNDS.value
            await s.scan_aggregate(req, agg_spec(lo, hi,
                                                 which=("min", "max")))
            assert read_mod._MESH_ROUNDS.value > rounds0
        finally:
            await s.close()

    with _ForceXlaAgg():
        run(go())


def test_fallback_wider_than_host_round(runtimes):
    """A mesh chunk can be wider than [scan] agg_batch_windows (time
    axis > the single-chip round width): the per-round fallback must
    split it instead of overrunning _flush_host_round's stacks
    (review-found IndexError on the declared failure seam)."""

    async def go():
        s = await open_storage(MemoryObjectStore(), runtimes,
                               agg_batch_windows=2)
        try:
            rng = random.Random(SEED + 4)
            await write_segments(s, rng, segments=4, rows_per=120)
            lo, hi = 0, 4 * SEGMENT_MS
            spec = agg_spec(lo, hi)
            req = ScanRequest(range=TimeRange.new(lo, hi))
            with _MeshOff(s):
                control = await s.scan_aggregate(req, spec)
            clear_caches(s)
            real = s.reader._run_mesh_round

            def always_fails(items, spec_, plan, **kw):
                raise RuntimeError("simulated mesh loss")

            s.reader._run_mesh_round = always_fails
            try:
                got = await s.scan_aggregate(req, spec)
            finally:
                s.reader._run_mesh_round = real
            _assert_same(got, control, "wide fallback")
        finally:
            await s.close()

    with _ForceXlaAgg():
        run(go())


def test_misaligned_run_falls_back(runtimes):
    """Parquet-streamed chunks carry their OWN ts epochs, so a
    segment's windows can disagree on their first bucket `lo` — a
    cell-wise mesh combine would shift rows by whole buckets (found by
    review; this reproducer returned WRONG counts before the
    run_misaligned gate).  Sidecars are disabled to force the
    per-chunk-epoch encode path."""

    async def go():
        s = await open_storage(MemoryObjectStore(), runtimes,
                               stream_read_min_rows=64,
                               max_window_rows=128,
                               use_sidecar=False)
        try:
            # each key's rows start 5 minutes later, so pk-chunk
            # epochs land in different buckets
            rows = []
            for ki in range(10):
                base = ki * 300_000
                rows += [(f"k{ki}", base + j * 500,
                          float(ki * 1000 + j)) for j in range(120)]
            await s.write(wreq(rows))
            spec = agg_spec(0, SEGMENT_MS,
                            which=("min", "max", "count"))
            req = ScanRequest(range=TimeRange.new(0, SEGMENT_MS))
            before = mesh_fallbacks("run_misaligned")
            got = await s.scan_aggregate(req, spec)
            assert mesh_fallbacks("run_misaligned") > before
            with _MeshOff(s):
                clear_caches(s)
                control = await s.scan_aggregate(req, spec)
            _assert_same(got, control, "misaligned-run")
        finally:
            await s.close()

    with _ForceXlaAgg():
        run(go())


# ---------------------------------------------------------------------------
# seeded chaos
# ---------------------------------------------------------------------------


def _chaos_schedule(i: int, runtimes):
    """One seeded schedule: random writes/compactions/evictions
    interleaved with downsample and top-k queries over random ranges,
    agg subsets, and filters — each query runs mesh-warm (memo may
    serve), mesh-cold, and single-chip-control, all byte-identical.
    One op races a query against a mid-scan compaction; odd schedules
    force streamed segments + tiny windows so multi-slot runs and the
    sum-overlap gate are exercised; schedule 2 injects a transient
    mesh failure per query (the lost-shard schedule)."""

    async def go():
        rng = random.Random(SEED + i)
        scan_kw = {}
        if i % 2:
            scan_kw.update(stream_read_min_rows=64, max_window_rows=128)
        if i % 4 == 1:
            # parquet-streamed chunks (no sidecar) carry per-chunk ts
            # epochs: the run_misaligned gate's territory
            scan_kw.update(use_sidecar=False)
        s = await open_storage(MemoryObjectStore(), runtimes, **scan_kw)
        lose_shards = i % 3 == 2
        real_round = s.reader._run_mesh_round

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
            if rng.random() < 0.35:
                by_pool = [a for a in which if a != "last_ts"] + ["count"]
                tk = TopKSpec(k=rng.randint(1, 4),
                              by=rng.choice(by_pool),
                              largest=rng.random() < 0.5)
            if lose_shards:
                fails = {"left": rng.randint(0, 2)}

                def flaky(items, spec_, plan, **kw):
                    if fails["left"] > 0:
                        fails["left"] -= 1
                        raise RuntimeError("simulated lost shard")
                    return real_round(items, spec_, plan, **kw)

                s.reader._run_mesh_round = flaky
            try:
                await _query_both(
                    s, req, spec, tk=tk,
                    ctx=f"schedule {i} lo={lo} hi={hi} which={which} "
                        f"pred={pred} tk={tk}")
            finally:
                s.reader._run_mesh_round = real_round

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
def test_seeded_mesh_chaos(runtimes):
    for i in range(SCHEDULES):
        _chaos_schedule(i, runtimes)


def test_seeded_mesh_chaos_fast(runtimes):
    """Tier-1 variant: a fixed small slice of the chaos schedules (one
    bulk, one streamed/tiny-window, one lost-shard)."""
    for i in range(3):
        _chaos_schedule(i, runtimes)


# ---------------------------------------------------------------------------
# config plumbing + lint + stats
# ---------------------------------------------------------------------------


def test_mesh_config_toml():
    cfg = from_dict(StorageConfig, {
        "scan": {"mesh": {"enabled": True, "time": 4, "series": 2,
                          "max_grid_bytes": 1 << 20}}})
    assert cfg.scan.mesh.enabled and cfg.scan.mesh.time == 4
    assert cfg.scan.mesh.series == 2
    assert cfg.scan.mesh.max_grid_bytes == 1 << 20
    assert StorageConfig().scan.mesh.enabled is False
    with pytest.raises(Error):
        from_dict(StorageConfig, {"scan": {"mesh": {"enable": True}}})


def test_bad_mesh_shapes_rejected_at_open(runtimes):
    async def go():
        # series must be a power of two (it must divide padded group
        # spaces)
        with pytest.raises(Error, match="power of two"):
            await open_storage(MemoryObjectStore(), runtimes,
                               mesh={"enabled": True, "time": 1,
                                     "series": 3})

    run(go())


def test_default_scan_shape():
    from horaedb_tpu.parallel import default_scan_shape

    assert default_scan_shape(8) == (4, 2)
    assert default_scan_shape(4) == (2, 2)
    assert default_scan_shape(2) == (2, 1)
    assert default_scan_shape(1) == (1, 1)
    assert default_scan_shape(7) == (7, 1)


def test_mesh_stats_section(runtimes):
    async def go():
        s = await open_storage(MemoryObjectStore(), runtimes)
        try:
            stats = s.reader.cache_stats()
            assert stats["mesh"]["enabled"] is True
            assert stats["mesh"]["shape"] == {"time": 4, "series": 2}
            assert "stalls" in stats["mesh"]
        finally:
            await s.close()

    run(go())


def test_empty_minmax_cells_canonical():
    """Count-0 min/max cells must read the documented +/-inf
    identities even when a part's span touched them with the device
    kernel's F32_MAX fills — empty-cell bytes must not depend on
    round/part composition (the mesh's runs carry different group
    unions than the control's rounds)."""
    from horaedb_tpu.storage import combine as combine_mod

    f32max = np.float32(np.finfo(np.float32).max)
    values = np.asarray(["a", "b"], dtype=object)
    grids = {
        "count": np.asarray([[1, 0], [0, 0]], dtype=np.float32),
        "min": np.asarray([[2.0, f32max], [f32max, f32max]],
                          dtype=np.float32),
        "max": np.asarray([[2.0, -f32max], [-f32max, -f32max]],
                          dtype=np.float32),
    }
    for mode in ("sparse", "dense"):
        vals, out = combine_mod.combine_parts(
            [(values, 0, grids)], 2, which=("min", "max"), mode=mode)
        assert np.isposinf(out["min"][0, 1]) and np.isposinf(
            out["min"][1, 0]), mode
        assert np.isneginf(out["max"][0, 1]) and np.isneginf(
            out["max"][1, 1]), mode
        assert out["min"][0, 0] == 2.0 and out["max"][0, 0] == 2.0


def test_lint_mesh_rule(tmp_path):
    import subprocess
    import sys

    bad_dir = tmp_path / "horaedb_tpu" / "storage"
    bad_dir.mkdir(parents=True)
    bad = bad_dir / "rogue.py"
    bad.write_text(
        "import numpy as np\n"
        "from jax.sharding import Mesh\n\n\n"
        "def f(devices):\n"
        "    return Mesh(np.array(devices), ('seg',))\n")
    ok_dir = tmp_path / "horaedb_tpu" / "parallel"
    ok_dir.mkdir(parents=True)
    ok = ok_dir / "fine.py"
    ok.write_text(
        "import numpy as np\n"
        "from jax.sharding import Mesh\n\n\n"
        "def f(devices):\n"
        "    return Mesh(np.array(devices), ('seg',))\n")
    lint = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "lint.py")
    out = subprocess.run([sys.executable, lint, str(bad), str(ok)],
                         capture_output=True, text=True)
    assert "Mesh/shard_map/NamedSharding" in out.stdout
    assert "rogue.py" in out.stdout and "fine.py" not in out.stdout


def test_existing_mesh_call_sites_enumerated():
    """The mesh-construction rule's ground truth: every current
    Mesh/shard_map/NamedSharding construction site lives under
    horaedb_tpu/parallel/ — enumerated here so a new site fails THIS
    test with a readable location even before lint runs."""
    import ast
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent / "horaedb_tpu"
    sites = []
    for path in sorted(root.rglob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            name = func.id if isinstance(func, ast.Name) else (
                func.attr if isinstance(func, ast.Attribute) else None)
            if name in ("Mesh", "shard_map", "NamedSharding"):
                sites.append((str(path.relative_to(root)), node.lineno))
    outside = [s for s in sites if not s[0].startswith("parallel/")]
    assert not outside, f"mesh construction outside parallel/: {outside}"
    assert {s[0].split("/")[1] for s in sites} == {"mesh.py", "scan.py"}
