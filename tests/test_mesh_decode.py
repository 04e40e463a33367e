"""Mesh-placed fused decode tests (ISSUE 19): the per-round shard_map
program that starts from RAW ENCODED sidecar buffers — each time slot
uploads its slot's encoded columns and runs leaf-filter + merge-dedup
+ bucket-aggregate + the ppermute segmented combine in one jitted
dispatch — byte-compared three ways against BOTH controls:

  mesh+decode  — [scan.mesh] rounds fed by deferred fused-decode plans
  decode-only  — same fused decode, mesh detached (single-chip combine)
  mesh-only    — same mesh rounds over host-decoded windows

across agg sets, filters, ranges, and top-k (selection AND the
additive count/sum/avg rankings riding the compensated (hi, lo) score
plane), under seeded chaos schedules that interleave writes,
compactions, evictions, lost shards, and mid-scan compaction races.
Plus: the k-way merge routing evidence (multi-SST segments skip the
full device lax.sort), the additive top-k O(k x buckets x aggs)
egress bound at two group cardinalities, the fused-round budget
downgrade, open-time mode-conflict rejection, eviction coverage for
the mesh decode state, and the lax.sort-outside-ops/merge lint rule.

The seeded chaos test rides `make chaos` with knobs MESHDECODE_SEED /
MESHDECODE_SCHEDULES; the fast tier-1 variant runs a fixed small
subset.  All legs force HORAEDB_HOST_AGG=0 so every control aggregates
with the same XLA window kernel (the PR 12 bit-identity convention)."""

import asyncio
import os
import random

import numpy as np
import pyarrow as pa
import pytest

from horaedb_tpu.common import ReadableDuration
from horaedb_tpu.common import runtimes as runtimes_mod
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.ops import device_decode as dd_mod
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

SEED = int(os.environ.get("MESHDECODE_SEED", "1337"), 0)
SCHEDULES = int(os.environ.get("MESHDECODE_SCHEDULES", "10"), 0)

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
    scan.setdefault("decode", {"mode": "device"})
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


def decode_fallbacks(reason: str) -> float:
    child = dd_mod._FALLBACK_CHILDREN.get(reason)
    return 0.0 if child is None else child.value


class _ForceXlaAgg:
    """Force HORAEDB_HOST_AGG=0 (and the fused accumulator off) for a
    block: every control leg then aggregates with the same XLA window
    kernel the mesh/decode programs call, isolating WHERE the combine
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
    """Run the same reader with the mesh detached — the decode-only
    control leg (fused decode still runs, combine is single-chip)."""

    def __init__(self, s):
        self.reader = s.reader

    def __enter__(self):
        self._mesh = self.reader.scan_mesh
        self.reader.scan_mesh = None

    def __exit__(self, *exc):
        self.reader.scan_mesh = self._mesh


class _HostDecode:
    """Run the same reader with decode forced to host — the mesh-only
    control leg (identical [scan.mesh] rounds over host windows)."""

    def __init__(self, s):
        self.cfg = s.config.scan.decode

    def __enter__(self):
        self._old = self.cfg.mode
        self.cfg.mode = "host"

    def __exit__(self, *exc):
        self.cfg.mode = self._old


async def _query_three(s, req, spec, tk=None, ctx=""):
    """One query served mesh+decode warm, mesh+decode cold, decode-only
    (mesh off), and mesh-only (host decode) — all four byte-compared."""
    warm = await s.scan_aggregate(req, spec, top_k=tk)
    clear_caches(s)
    cold = await s.scan_aggregate(req, spec, top_k=tk)
    clear_caches(s)
    with _MeshOff(s):
        dec_only = await s.scan_aggregate(req, spec, top_k=tk)
    clear_caches(s)
    with _HostDecode(s):
        mesh_only = await s.scan_aggregate(req, spec, top_k=tk)
    clear_caches(s)
    _assert_same(warm, cold, f"{ctx} warm-vs-cold")
    _assert_same(cold, dec_only, f"{ctx} meshdecode-vs-decodeonly")
    _assert_same(cold, mesh_only, f"{ctx} meshdecode-vs-meshonly")
    return cold


# ---------------------------------------------------------------------------
# direct bit-identity + routing
# ---------------------------------------------------------------------------


def test_mesh_decode_vs_both_controls_bit_identity(runtimes):
    """Overlapping writes (cross-SST duplicate PKs — multi-run
    interleaved segments riding the device k-way merge), every agg
    set, filters incl. In/range, and selection top-k: mesh+fused-decode
    grids must be byte-identical with BOTH controls, fused rounds must
    actually dispatch, and the multi-run segments must take the k-way
    route (scan_decode_sort_skipped_total{route="kway"}) with the full
    device lax.sort never paid."""

    async def go():
        s = await open_storage(MemoryObjectStore(), runtimes)
        try:
            rng = random.Random(SEED)
            await write_segments(s, rng, segments=6, rows_per=200)
            # duplicate-PK overwrites: segments 0-2 now interleave SSTs
            await write_segments(s, rng, segments=3, rows_per=150)
            lo, hi = 0, 6 * SEGMENT_MS
            rounds0 = read_mod._MESH_ROUNDS.value
            kway0 = dd_mod._SORT_SKIPPED["kway"].value
            sorted0 = dd_mod._SORT_RAN.value
            for which in WHICH_SETS:
                spec = agg_spec(lo, hi, which=which)
                for pred in (None, F.Eq("k", "k3"),
                             F.In("k", ["k1", "k4"]),
                             F.Ge("ts", SEGMENT_MS // 2)):
                    req = ScanRequest(range=TimeRange.new(lo, hi),
                                      predicate=pred)
                    await _query_three(s, req, spec,
                                       ctx=f"{which} pred={pred}")
            for tk in (TopKSpec(k=3, by="max"),
                       TopKSpec(k=2, by="min", largest=False),
                       TopKSpec(k=3, by="last")):
                which = ("avg", "min", "max", "last")
                spec = agg_spec(lo, hi, which=which)
                req = ScanRequest(range=TimeRange.new(lo, hi))
                await _query_three(s, req, spec, tk=tk, ctx=f"tk={tk}")
            assert read_mod._MESH_ROUNDS.value > rounds0, \
                "mesh never dispatched a fused-decode round"
            assert dd_mod._SORT_SKIPPED["kway"].value > kway0, \
                "multi-SST segments never took the k-way merge route"
            assert dd_mod._SORT_RAN.value == sorted0, \
                "a fused dispatch paid the full device lax.sort"
        finally:
            await s.close()

    with _ForceXlaAgg():
        run(go())


def test_round_over_narrowed_plans_of_unequal_kept_counts(runtimes,
                                                          monkeypatch):
    """Plans narrowed on host to their Eq leaf's rows
    (ops/device_decode.plan_dispatch) keep one static_key, so they
    still stack into ONE round, which pads every slot to its largest
    narrowed capacity: grids byte-equal to the single-device dispatches
    and to host decode."""
    kept = (10, 100, 200, 30)
    rounds: list = []
    real_round = read_mod.ParquetReader._run_mesh_decode_round

    def spy(self, chunk, spec):
        rounds.append([(dp.src_rows, dp.es.n, dp.cap) for _s, dp in chunk])
        return real_round(self, chunk, spec)

    monkeypatch.setattr(read_mod.ParquetReader, "_run_mesh_decode_round",
                        spy)

    async def go():
        s = await open_storage(MemoryObjectStore(), runtimes)
        try:
            rng = random.Random(SEED + 5)
            for seg, n in enumerate(kept):
                step = (SEGMENT_MS - 1000) // 300
                rows = [(f"k{1 + i % 5}", seg * SEGMENT_MS + i * step,
                         float(rng.randint(0, 10**6)))
                        for i in range(300)]
                rows += [("k0", seg * SEGMENT_MS + 3 + i * step,
                          float(rng.randint(0, 10**6)))
                         for i in range(n)]
                await s.write(wreq(rows))
            lo, hi = 0, len(kept) * SEGMENT_MS
            req = ScanRequest(range=TimeRange.new(lo, hi),
                              predicate=F.Eq("k", "k0"))
            got = await _query_three(
                s, req, agg_spec(lo, hi, which=ALL_AGGS), ctx="narrowed")
            assert got[0].tolist() == ["k0"]
        finally:
            await s.close()

    with _ForceXlaAgg():
        run(go())
    assert rounds, "no fused-decode round ran"
    for chunk in rounds:  # warm and cold: the same one round
        assert [(src, n) for src, n, _cap in chunk] \
            == [(300 + n, n) for n in kept]
        assert sorted({cap for _src, _n, cap in chunk}) == [128, 256]


def test_additive_topk_identity_device_served(runtimes):
    """count/sum/avg rankings ride the compensated (hi, lo) device
    score plane: each query must be DEVICE-served (the mesh top-k
    counter grows, no additive_topk downgrade) and byte-identical with
    the single-chip combine_top_k control, both ranking directions.
    Decode stays host here — the topk_decode gate keeps mixed-
    provenance parts out of device scoring by design."""

    async def go():
        s = await open_storage(MemoryObjectStore(), runtimes,
                               decode={"mode": "host"})
        try:
            rng = random.Random(SEED + 7)
            await write_segments(s, rng, segments=5, rows_per=200)
            await write_segments(s, rng, segments=2, rows_per=120)
            lo, hi = 0, 5 * SEGMENT_MS
            req = ScanRequest(range=TimeRange.new(lo, hi))
            lossy0 = mesh_fallbacks("additive_topk")
            for tk in (TopKSpec(k=3, by="count"),
                       TopKSpec(k=2, by="sum"),
                       TopKSpec(k=3, by="avg"),
                       TopKSpec(k=2, by="sum", largest=False),
                       TopKSpec(k=1, by="avg", largest=False),
                       TopKSpec(k=4, by="count", largest=False)):
                which = ("avg", "sum") if tk.by != "count" else ("avg",)
                spec = agg_spec(lo, hi, which=which)
                clear_caches(s)
                served0 = read_mod._MESH_TOPK.value
                got = await s.scan_aggregate(req, spec, top_k=tk)
                assert read_mod._MESH_TOPK.value == served0 + 1, \
                    f"additive top-k not device-served: {tk}"
                clear_caches(s)
                with _MeshOff(s):
                    control = await s.scan_aggregate(req, spec,
                                                     top_k=tk)
                _assert_same(got, control, f"additive tk={tk}")
            assert mesh_fallbacks("additive_topk") == lossy0, \
                "additive score plane went lossy on in-gamut data"
        finally:
            await s.close()

    with _ForceXlaAgg():
        run(go())


def test_additive_topk_bounded_egress(runtimes):
    """The additive-ranking acceptance bound: device-scored count/sum/
    avg top-k egress is O(k x buckets x aggs) per run part plus an
    O(groups) score vector — asserted against the part-cell counter at
    TWO group cardinalities, so the bound provably does not scale with
    the group count."""

    async def go(keys: int):
        s = await open_storage(MemoryObjectStore(), runtimes,
                               decode={"mode": "host"})
        try:
            rng = random.Random(SEED)
            await write_segments(s, rng, segments=4, rows_per=400,
                                 keys=keys)
            lo, hi = 0, 4 * SEGMENT_MS
            spec = agg_spec(lo, hi, which=("sum", "avg"))
            tk = TopKSpec(k=3, by="sum")
            req = ScanRequest(range=TimeRange.new(lo, hi))
            clear_caches(s)
            served0 = read_mod._MESH_TOPK.value
            cells0 = read_mod._MESH_PART_CELLS.value
            got = await s.scan_aggregate(req, spec, top_k=tk)
            assert read_mod._MESH_TOPK.value == served0 + 1, \
                "additive top-k did not take the device-scored path"
            cells = read_mod._MESH_PART_CELLS.value - cells0
            # <= parts x k x num_buckets x grid kinds (4 segments)
            bound = 4 * tk.k * spec.num_buckets * 8
            assert cells <= bound, (cells, bound)
            with _MeshOff(s):
                clear_caches(s)
                control = await s.scan_aggregate(req, spec, top_k=tk)
            _assert_same(got, control, f"additive topk keys={keys}")
            return cells
        finally:
            await s.close()

    with _ForceXlaAgg():
        small = run(go(6))
        large = run(go(200))
        # the winner egress must not scale with cardinality (scores
        # are counted separately): identical k/buckets, same bound
        assert large <= small * 2, (small, large)


def test_mesh_decode_budget_downgrade(runtimes):
    """A fused round whose stacked upload or grid exceeds the
    [scan.decode]/[scan.mesh] caps must downgrade PER ITEM to the
    single-dispatch decode path (reason=mesh_decode_budget), staying
    byte-identical with the controls."""

    async def go():
        s = await open_storage(MemoryObjectStore(), runtimes)
        try:
            rng = random.Random(SEED + 3)
            await write_segments(s, rng, segments=4, rows_per=200)
            lo, hi = 0, 4 * SEGMENT_MS
            spec = agg_spec(lo, hi)
            req = ScanRequest(range=TimeRange.new(lo, hi))
            control = await _query_three(s, req, spec, ctx="pre-budget")
            clear_caches(s)
            real = s.config.scan.mesh.max_grid_bytes
            before = mesh_fallbacks("mesh_decode_budget")
            s.config.scan.mesh.max_grid_bytes = 1
            try:
                got = await s.scan_aggregate(req, spec)
            finally:
                s.config.scan.mesh.max_grid_bytes = real
            assert mesh_fallbacks("mesh_decode_budget") > before, \
                "tiny grid budget never tripped the fused-round gate"
            _assert_same(got, control, "budget downgrade")
        finally:
            await s.close()

    with _ForceXlaAgg():
        run(go())


def test_lost_shard_decode_round_fallback(runtimes):
    """A fused-decode round dispatch that dies (lost shard / XLA
    failure) falls back to per-item single-dispatch decode, is counted
    (reason=mesh_error), and the query's grids stay byte-identical."""

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
            real = s.reader._run_mesh_decode_round
            fails = {"left": 2}

            def flaky(chunk, spec_):
                if fails["left"] > 0:
                    fails["left"] -= 1
                    raise RuntimeError("simulated lost shard")
                return real(chunk, spec_)

            s.reader._run_mesh_decode_round = flaky
            before = mesh_fallbacks("mesh_error")
            try:
                got = await s.scan_aggregate(req, spec)
            finally:
                s.reader._run_mesh_decode_round = real
            assert mesh_fallbacks("mesh_error") == before + 2
            assert fails["left"] == 0, "fault never fired"
            _assert_same(got, control, "lost-shard decode fallback")
        finally:
            await s.close()

    with _ForceXlaAgg():
        run(go())


# ---------------------------------------------------------------------------
# seeded chaos
# ---------------------------------------------------------------------------


def _chaos_schedule(i: int, runtimes):
    """One seeded schedule: random writes/compactions/evictions
    interleaved with downsample and top-k queries (selection AND
    additive rankings) over random ranges, agg subsets, and filters —
    each query runs mesh+decode warm, cold, decode-only, and
    mesh-only, all byte-identical.  One op races a query against a
    mid-scan compaction; odd schedules force streamed segments + tiny
    windows; schedule 2 injects transient fused-round failures (the
    lost-shard schedule)."""

    async def go():
        rng = random.Random(SEED + i)
        scan_kw = {}
        if i % 2:
            scan_kw.update(stream_read_min_rows=64, max_window_rows=128)
        if i % 4 == 1:
            # parquet-streamed chunks (no sidecar) carry per-chunk ts
            # epochs: nothing is decode-eligible, so the fused path
            # must DECLINE cleanly and stay identical
            scan_kw.update(use_sidecar=False)
        s = await open_storage(MemoryObjectStore(), runtimes, **scan_kw)
        lose_shards = i % 3 == 2
        real_round = s.reader._run_mesh_decode_round

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
            if rng.random() < 0.4:
                by_pool = [a for a in which if a != "last_ts"] + ["count"]
                tk = TopKSpec(k=rng.randint(1, 4),
                              by=rng.choice(by_pool),
                              largest=rng.random() < 0.5)
            if lose_shards:
                fails = {"left": rng.randint(0, 2)}

                def flaky(chunk, spec_):
                    if fails["left"] > 0:
                        fails["left"] -= 1
                        raise RuntimeError("simulated lost shard")
                    return real_round(chunk, spec_)

                s.reader._run_mesh_decode_round = flaky
            try:
                await _query_three(
                    s, req, spec, tk=tk,
                    ctx=f"schedule {i} lo={lo} hi={hi} which={which} "
                        f"pred={pred} tk={tk}")
            finally:
                s.reader._run_mesh_decode_round = real_round

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
def test_seeded_mesh_decode_chaos(runtimes):
    for i in range(SCHEDULES):
        _chaos_schedule(i, runtimes)


def test_seeded_mesh_decode_chaos_fast(runtimes):
    """Tier-1 variant: a fixed small slice of the chaos schedules (one
    bulk, one streamed/no-sidecar, one lost-shard)."""
    for i in range(3):
        _chaos_schedule(i, runtimes)


# ---------------------------------------------------------------------------
# config plumbing + eviction + lint
# ---------------------------------------------------------------------------


def test_close_evicts_mesh_decode_state(runtimes):
    """drop_hbm_state() must evict the fused-round stacks and device
    scalars; close() must additionally drop the compiled mesh programs
    and zero the mesh score-state gauge — 'HBM evicted' has to mean
    the mesh-resident decode state too, or long-lived readers leak
    device memory across tenants."""

    async def go():
        s = await open_storage(MemoryObjectStore(), runtimes)
        try:
            rng = random.Random(SEED + 5)
            await write_segments(s, rng, segments=3, rows_per=150)
            req = ScanRequest(range=TimeRange.new(0, 3 * SEGMENT_MS))
            await s.scan_aggregate(req, agg_spec(0, 3 * SEGMENT_MS))
            r = s.reader
            assert r._mesh_run_fns, "no compiled mesh program cached"
            assert r._stack_cache, "no fused-round stacks cached"
            assert any(k[0] == "meshdecode" for k in r._stack_cache), \
                "decode round stacks missing from the stack cache"
            r.drop_hbm_state()
            assert not r._stack_cache and r._stack_cache_bytes == 0
            assert not r._scalar_cache
            # compiled programs deliberately survive eviction (a
            # warm and an evicted run then compare recompile-free)
            assert r._mesh_run_fns
            assert r._mesh_state_bytes == 0
        finally:
            await s.close()
        assert not s.reader._mesh_run_fns, \
            "close() left compiled mesh programs alive"
        assert s.reader._mesh_state_bytes == 0

    with _ForceXlaAgg():
        run(go())


def test_lint_lax_sort_rule(tmp_path):
    """tools/lint.py must flag jax.lax.sort call sites under
    horaedb_tpu/ outside ops/merge.py (the device sort has ONE seam so
    presorted / k-way-mergeable inputs can bypass it) and leave
    merge.py and noqa'd lines alone."""
    import subprocess
    import sys

    bad_dir = tmp_path / "horaedb_tpu" / "storage"
    bad_dir.mkdir(parents=True)
    bad = bad_dir / "rogue.py"
    bad.write_text(
        "import jax\n\n\n"
        "def f(keys):\n"
        "    return jax.lax.sort(keys, num_keys=2)\n")
    ok_dir = tmp_path / "horaedb_tpu" / "ops"
    ok_dir.mkdir(parents=True)
    ok = ok_dir / "merge.py"
    ok.write_text(
        "import jax\n\n\n"
        "def f(keys):\n"
        "    return jax.lax.sort(keys, num_keys=2)\n")
    waived = bad_dir / "waived.py"
    waived.write_text(
        "from jax import lax\n\n\n"
        "def f(keys):\n"
        "    return lax.sort(keys, num_keys=2)  # noqa: device-sort\n")
    lint = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "lint.py")
    out = subprocess.run(
        [sys.executable, lint, str(bad), str(ok), str(waived)],
        capture_output=True, text=True)
    assert "jax.lax.sort called" in out.stdout
    assert str(bad) in out.stdout
    assert str(ok) not in out.stdout
    assert str(waived) not in out.stdout


def test_existing_lax_sort_sites_enumerated():
    """The lax.sort rule's ground truth: every current device-sort
    call site lives in ops/merge.py — enumerated here so a new site
    fails THIS test with a readable location even before lint runs."""
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
            if not isinstance(func, ast.Attribute) \
                    or not func.attr.startswith("sort"):
                continue
            chain = []
            cur = func.value
            while isinstance(cur, ast.Attribute):
                chain.append(cur.attr)
                cur = cur.value
            if isinstance(cur, ast.Name):
                chain.append(cur.id)
            if "lax" in chain:
                sites.append((str(path.relative_to(root)), node.lineno))
    assert sites, "no device lax.sort site found at all"
    outside = [x for x in sites if x[0] != "ops/merge.py"]
    assert not outside, f"device lax.sort outside ops/merge.py: {outside}"


def test_lint_env_switch_rule(tmp_path):
    """tools/lint.py must flag an os.environ / os.getenv access of a
    HORAEDB_* name under horaedb_tpu/ outside the five that remain, in
    every form a read takes, so a deleted switch (the downsample and
    merge implementations went with theirs) does not come back as a
    new one; the remaining names, other prefixes and files outside
    the package pass."""
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "lint_under_test",
        pathlib.Path(__file__).resolve().parent.parent / "tools" / "lint.py")
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)

    pkg = tmp_path / "horaedb_tpu" / "ops"
    pkg.mkdir(parents=True)
    for i, read in enumerate((
            'os.environ.get("HORAEDB_MERGE_IMPL", "host_perm")',
            'os.getenv("HORAEDB_DOWNSAMPLE_IMPL")',
            'os.environ["HORAEDB_NEW_ROUTE"]',
            '"HORAEDB_NEW_ROUTE" in os.environ',
            'os.environ.pop("HORAEDB_NEW_ROUTE", None)')):
        bad = pkg / f"rogue{i}.py"
        bad.write_text(f"import os\n\nX = {read}\n")
        assert any("environment switch" in p
                   for p in lint.lint_file(bad)), read
    fine = pkg / "fine.py"
    fine.write_text(
        "import os\n\n"
        + "".join(f'{n[8:]} = os.environ.get("{n}", "")\n'
                  for n in sorted(lint._ENV_SWITCHES))
        + 'SEED = os.environ.get("TORTURE_SEED")\n'
        + 'DOC = "HORAEDB_MERGE_IMPL went in PR 30"\n')
    assert not lint.lint_file(fine)
    outside = tmp_path / "tests" / "x.py"
    outside.parent.mkdir()
    outside.write_text('import os\n\nX = os.environ["HORAEDB_ANYTHING"]\n')
    assert not lint.lint_file(outside)
