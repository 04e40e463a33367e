"""The route table (ISSUE 30): which aggregate plan takes which route.

For each row: build the reader and the plan, assert
`ParquetReader.aggregate_route`, run the aggregate, and assert that the
route that RAN is the one named — from the device plane's own ledger
(per-program dispatch counts, what `GET /debug/device` serves and the
benchmark's `route.dispatches_per_query` reads), the fallback counters
(`scan_decode_fallback_total{reason}`, `scan_mesh_fallback_total
{reason}`), the mesh round counter and the replay hits — and that the
answer equals a numpy reference of the same semantics.  A row fails if
the label and the programs that ran disagree.

Rows that say `accel` monkeypatch the one backend observation the gates
read (`jax.default_backend`): the programs then run on XLA-CPU, as the
chip's would.  Three rows are the benchmark's cells: `accel_under_budget`
(s100_single_groupby), `accel_over_budget` (s100_double_groupby) and
`accel_over_budget_block_pruned_host` (s1000_single_groupby)."""

import asyncio
import dataclasses
import pathlib

import jax
import numpy as np
import pyarrow as pa
import pytest

from horaedb_tpu.common import ReadableDuration, deviceprof
from horaedb_tpu.common import runtimes as runtimes_mod
from horaedb_tpu.common.error import Error
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.ops import device_decode
from horaedb_tpu.ops import filter as F
from horaedb_tpu.ops.downsample import ALL_AGGS
from horaedb_tpu.storage import read as read_mod
from horaedb_tpu.storage import sidecar
from horaedb_tpu.storage.config import (ScanConfig, StorageConfig,
                                        ThreadsConfig, UpdateMode,
                                        from_dict)
from horaedb_tpu.storage.plan import TopKSpec
from horaedb_tpu.storage.read import AggregateSpec, ScanRequest
from horaedb_tpu.storage.storage import CloudObjectStorage, WriteRequest
from horaedb_tpu.storage.types import TimeRange

SEGMENT_MS = 3_600_000
TICK_MS = 10_000
TICKS = SEGMENT_MS // TICK_MS
HOSTS = 40
SEGMENTS = 2
BUCKET_MS = 60_000
SCHEMA = pa.schema([("k", pa.string()), ("ts", pa.int64()),
                    ("v", pa.float64())])
# 2 segments x 40 hosts x 360 ticks = 28,800 stored rows: under the
# default scan-cache budget (4,194,304 rows), over this one
OVER_BUDGET = {"cache_max_rows": 4096}
# a window that starts and ends inside a segment, across the boundary
LO, HI = SEGMENT_MS - 1_200_000 + 7, SEGMENT_MS + 1_500_000 + 7

FUSED = ("_fused_acc_init_jit", "_fused_round_accumulate_jit",
         "_fused_finalize_jit", "_group_has_data_jit")
# a fused aggregate of ONE small round of host rows (ISSUE 44)
ONE_CALL = ("_fused_one_call_jit",)
DECODE = ("_decode_aggregate_jit",)
PARTS_XLA = ("_batched_window_partials_jit",)
MESH = ("mesh_run_partials", "mesh_decode_partials")
ROUTE_FNS = FUSED + ONE_CALL + DECODE + PARTS_XLA + MESH


@pytest.fixture(scope="module")
def runtimes():
    rt = runtimes_mod.from_config(ThreadsConfig())
    yield rt
    rt.close()


class World:
    """What was written (one body a segment, every host at every tick)
    and the numpy side of every comparison."""

    def __init__(self, seed: int):
        rng = np.random.default_rng(seed)
        self.values = (rng.random((HOSTS, SEGMENTS * TICKS)) * 100
                       ).astype(np.float32)

    def write_requests(self):
        for seg in range(SEGMENTS):
            ticks = np.arange(seg * TICKS, (seg + 1) * TICKS)
            h = np.repeat(np.arange(HOSTS), TICKS)
            t = np.tile(ticks, HOSTS)
            ts = t.astype(np.int64) * TICK_MS
            batch = pa.record_batch(
                [pa.array([f"host_{i:02d}" for i in h]), pa.array(ts),
                 pa.array(self.values[h, t].astype(np.float64))],
                schema=SCHEMA)
            yield WriteRequest(batch, TimeRange.new(int(ts.min()),
                                                    int(ts.max()) + 1))

    def reference(self, lo: int, hi: int, hosts, keep=None) -> dict:
        """{host name: {agg: (buckets,) f64}} over [lo, hi), rows
        admitted by `keep(values)`; hosts without a row are absent, as
        the engine drops empty groups."""
        n = -(-(hi - lo) // BUCKET_MS)
        ts = np.arange(self.values.shape[1], dtype=np.int64) * TICK_MS
        out = {}
        for h in hosts:
            v = self.values[h]
            m = (ts >= lo) & (ts < hi)
            if keep is not None:
                m &= keep(v)
            if not m.any():
                continue
            b = ((ts[m] - lo) // BUCKET_MS).astype(np.int64)
            vv = v[m].astype(np.float64)
            g = {"count": np.bincount(b, minlength=n).astype(np.float64),
                 "sum": np.bincount(b, weights=vv, minlength=n),
                 "min": np.full(n, np.inf), "max": np.full(n, -np.inf),
                 "last": np.zeros(n)}
            np.minimum.at(g["min"], b, vv)
            np.maximum.at(g["max"], b, vv)
            g["last"][b] = vv  # ticks ascend: the later row stays
            g["avg"] = g["sum"] / np.maximum(g["count"], 1)
            out[f"host_{h:02d}"] = g
        return out


def check_answer(got, ref: dict, which=ALL_AGGS, top_k=None):
    values, grids = got
    names = [str(v) for v in values]
    if top_k is not None:
        score = {nm: (g[top_k.by][g["count"] > 0].max()) for nm, g
                 in ref.items()}
        best = sorted(score, key=score.get, reverse=True)[:top_k.k]
        assert names == best
    else:
        assert names == sorted(ref)
    for row, nm in enumerate(names):
        want = ref[nm]
        occupied = want["count"] > 0
        assert np.array_equal(np.asarray(grids["count"])[row],
                              want["count"]), nm
        for a in set(which) & {"min", "max", "last"}:
            g = np.asarray(grids[a], dtype=np.float64)[row]
            assert np.array_equal(g[occupied], want[a][occupied]), (nm, a)
        for a in set(which) & {"sum", "avg"}:
            g = np.asarray(grids[a], dtype=np.float64)[row]
            err = np.abs(g[occupied] - want[a][occupied]) / np.maximum(
                np.abs(want[a][occupied]), 1e-30)
            assert err.max() <= 1e-5, (nm, a)


def calls() -> dict:
    """Calls (a compile is one too) of the programs a route is made
    of, from the device plane's ledger."""
    return {r["fn"]: r["compiles"] + r["dispatches"]
            for r in deviceprof.profiler.snapshot()["fns"]
            if r["fn"] in ROUTE_FNS}


def decode_fallbacks() -> dict:
    return {r: c.value for r, c in device_decode._FALLBACK_CHILDREN.items()}


def mesh_fallbacks() -> dict:
    return {r: c.value for r, c in read_mod._MESH_FALLBACK_CHILDREN.items()}


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


class _CoveringRouter:
    """A near-data router that covers the plan's segments and is not
    active: the gate asks `covers_any`, the pump asks `active`."""

    active = False

    @staticmethod
    def covers_any(segments) -> bool:
        return bool(segments)


@dataclasses.dataclass
class Row:
    name: str
    route: str
    ran: tuple                    # the programs that may be dispatched
    accel: bool = False
    scan: dict = dataclasses.field(default_factory=dict)
    env: dict = dataclasses.field(default_factory=dict)
    hosts: tuple = tuple(range(HOSTS))
    predicate: object = None
    keep: object = None           # the reference's side of `predicate`
    decode_reason: dict = dataclasses.field(default_factory=dict)
    mesh_reason: dict = dataclasses.field(default_factory=dict)
    top_k: object = None
    which: tuple = ALL_AGGS
    warm: bool = False            # the same plan once before, cached
    router: bool = False
    small_blocks: bool = False    # the sidecar's block sizes cut down
    n_decode: int = 0             # exact `_decode_aggregate_jit` count
    raises: str = ""              # the scan is refused with this Error
    mode: UpdateMode = UpdateMode.OVERWRITE


HOST7 = dict(hosts=(7,), predicate=F.Eq("k", "host_07"))
MESH_ON = {"mesh": {"enabled": True}}
ONE_WINDOW_A_ROUND = {"agg_batch_windows": 1}
MANY = [f"host_{i:02d}" for i in range(HOSTS)] \
    + [f"absent_{i}" for i in range(30)]

ROWS = [
    Row("cpu_auto", "parts", ()),
    # one host over two segments: two windows, one small round
    Row("accel_under_budget", "fused_acc", ONE_CALL, accel=True, **HOST7),
    # the one call records no replay: its repeat is the one call again
    Row("accel_under_budget_again", "fused_acc", ONE_CALL, accel=True,
        warm=True, **HOST7),
    # more windows than a round holds: the rounds' four programs, and
    # a repeat replays their recorded stacks
    Row("accel_under_budget_two_rounds", "fused_acc", FUSED, accel=True,
        scan=ONE_WINDOW_A_ROUND, **HOST7),
    Row("accel_under_budget_two_rounds_again", "replay", FUSED,
        accel=True, scan=ONE_WINDOW_A_ROUND, warm=True, **HOST7),
    Row("accel_over_budget", "device_decode", DECODE, accel=True,
        scan=OVER_BUDGET, n_decode=SEGMENTS),
    Row("accel_over_budget_block_pruned_host", "device_decode", DECODE,
        accel=True, scan=OVER_BUDGET, small_blocks=True,
        n_decode=SEGMENTS, **HOST7),
    Row("accel_over_budget_no_sidecar", "parts", PARTS_XLA, accel=True,
        scan={**OVER_BUDGET, "use_sidecar": False},
        decode_reason={"no_sidecar": 1}),
    Row("accel_mode_device_under_budget", "device_decode", DECODE,
        accel=True, scan={"decode": {"mode": "device"}},
        n_decode=SEGMENTS),
    # a value leaf under an AGGREGATE stays declined (the grids of rows
    # that pass a value predicate are no route's yet); the same leaf
    # answered as ROWS has a device route of its own: SELECT_ROWS below
    Row("mode_device_value_leaf", "parts", (),
        scan={"decode": {"mode": "device"}},
        predicate=F.Gt("v", 50.0), keep=lambda v: v > 50.0,
        decode_reason={"predicate": 1}),
    # an In past the device's list: declined for aggregates and, by
    # the same leaf_shape_supported, for the select
    # (select_mode_device_oversized_in_list)
    Row("mode_device_oversized_in_list", "parts", (),
        scan={"decode": {"mode": "device"}},
        predicate=F.In("k", MANY), decode_reason={"predicate": 1}),
    Row("accel_mode_host_over_budget", "parts", PARTS_XLA, accel=True,
        scan={**OVER_BUDGET, "decode": {"mode": "host"}}),
    # the aggregate pushdown is Overwrite-only: the label is one no
    # scan runs under, and `append_mode` is probed, never counted
    Row("append_mode", "parts", (), scan={"decode": {"mode": "device"}},
        mode=UpdateMode.APPEND, raises="Overwrite"),
    Row("mesh_eligible", "mesh", MESH, scan=MESH_ON),
    Row("mesh_topk_by_unrequested_agg", "mesh", MESH, scan=MESH_ON,
        top_k=TopKSpec(k=3, by="max"), which=("avg",),
        mesh_reason={"topk_by": 1},
        # counted, scanned on the mesh, and refused by the combine
        raises="needs that aggregate"),
    Row("mesh_topk_decode_parts", "mesh", MESH,
        scan={**MESH_ON, "decode": {"mode": "device"}},
        top_k=TopKSpec(k=3, by="max"), mesh_reason={"topk_decode": 1}),
    Row("mesh_topk_over_budget", "mesh", MESH,
        scan={**MESH_ON, **OVER_BUDGET},
        top_k=TopKSpec(k=3, by="max"), mesh_reason={"topk_budget": 1}),
    Row("mesh_topk_winner_sliced", "mesh", MESH, scan=MESH_ON,
        top_k=TopKSpec(k=3, by="max")),
    Row("accel_router_covers_under_budget", "parts", PARTS_XLA,
        accel=True, router=True),
    Row("fused_forced_over_budget", "fused_acc", ONE_CALL,
        scan=OVER_BUDGET, env={"HORAEDB_FUSED_AGG": "1"}),
    Row("accel_fused_forced_off_under_budget", "device_decode", DECODE,
        accel=True, env={"HORAEDB_FUSED_AGG": "0"}, n_decode=SEGMENTS),
    Row("accel_decode_forced_off_over_budget", "parts", PARTS_XLA,
        accel=True, scan=OVER_BUDGET,
        env={"HORAEDB_DEVICE_DECODE": "0"}),
    Row("benchmark_rehearsal_environment", "device_decode", DECODE,
        env={"HORAEDB_HOST_AGG": "0", "HORAEDB_DEVICE_DECODE": "1"},
        n_decode=SEGMENTS),
]


def storage_config(row: Row) -> StorageConfig:
    cfg = from_dict(StorageConfig, {
        "scheduler": {"schedule_interval": "1h", "input_sst_min_num": 2},
        "scan": row.scan})
    cfg.manifest.merge_interval = ReadableDuration.parse("1h")
    cfg.scrub.interval = ReadableDuration.parse("1h")
    cfg.update_mode = row.mode
    return cfg


@pytest.mark.parametrize("row", ROWS, ids=[r.name for r in ROWS])
def test_route_named_is_the_route_that_ran(row, runtimes, monkeypatch):
    for name in ("HORAEDB_HOST_AGG", "HORAEDB_DEVICE_DECODE",
                 "HORAEDB_FUSED_AGG", "HORAEDB_DEVCOL_STACK"):
        monkeypatch.delenv(name, raising=False)
    for name, value in row.env.items():
        monkeypatch.setenv(name, value)
    if row.accel:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if row.small_blocks:
        monkeypatch.setattr(sidecar, "BLOCK_ROWS", 512)
        monkeypatch.setattr(sidecar, "_HEAD_BYTES", 8192)
        monkeypatch.setattr(sidecar, "_PARTIAL_MIN_BYTES", 4096)

    async def go():
        world = World(seed=30)
        s = await CloudObjectStorage.open(
            "db", SEGMENT_MS, MemoryObjectStore(), SCHEMA, 2,
            storage_config(row), runtimes=runtimes)
        try:
            for wr in world.write_requests():
                await s.write(wr)
            reader = s.reader
            if row.router:
                reader.scan_router = _CoveringRouter()
            spec = AggregateSpec(
                group_col="k", ts_col="ts", value_col="v", range_start=LO,
                bucket_ms=BUCKET_MS,
                num_buckets=-(-(HI - LO) // BUCKET_MS), which=row.which)
            req = ScanRequest(range=TimeRange.new(LO, HI),
                              predicate=row.predicate)
            ref = world.reference(LO, HI, row.hosts, row.keep)
            if row.warm:
                check_answer(await s.scan_aggregate(req, spec), ref)
            else:
                # cold: nothing a write left behind serves the scan
                reader.scan_cache.clear()
                reader.encoded_cache.clear()
                reader.parts_memo.clear()

            plan = await s._plan_aggregate(req, spec)
            assert len(plan.segments) == SEGMENTS
            assert plan.route == row.route \
                == reader.aggregate_route(plan, spec)

            fns0, dec0, mesh0 = calls(), decode_fallbacks(), \
                mesh_fallbacks()
            rounds0 = read_mod._MESH_ROUNDS.value
            replays0 = reader._replay_hits
            fetched0 = sidecar._LOAD_ROWS["fetched"].value
            stored0 = sidecar._LOAD_ROWS["stored"].value
            if row.raises:
                with pytest.raises(Error, match=row.raises):
                    await s.scan_aggregate(req, spec, first_plan=plan,
                                           top_k=row.top_k)
            else:
                got = await s.scan_aggregate(req, spec, first_plan=plan,
                                             top_k=row.top_k)
                check_answer(got, ref, row.which, row.top_k)

            ran = delta(calls(), fns0)
            assert set(ran) <= set(row.ran), ran
            assert bool(ran) == bool(row.ran) or row.raises, ran
            if row.route in ("fused_acc", "replay"):
                assert set(ran) == set(row.ran)
                assert ran.get("_fused_round_accumulate_jit", 0) \
                    == (SEGMENTS if row.ran == FUSED else 0)
            if row.n_decode:
                assert ran == {"_decode_aggregate_jit": row.n_decode}
            assert (read_mod._MESH_ROUNDS.value > rounds0) \
                == (row.route == "mesh" and bool(ran))
            assert reader._replay_hits - replays0 \
                == (1 if row.route == "replay" else 0)
            assert delta(decode_fallbacks(), dec0) == row.decode_reason
            assert delta(mesh_fallbacks(), mesh0) == row.mesh_reason
            if row.small_blocks:
                # one host's blocks of each SST, not the SSTs
                fetched = sidecar._LOAD_ROWS["fetched"].value - fetched0
                stored = sidecar._LOAD_ROWS["stored"].value - stored0
                assert 0 < fetched < stored == SEGMENTS * HOSTS * TICKS
        finally:
            await s.close()

    asyncio.run(go())


def test_every_plan_level_reason_has_a_row():
    """The counted reasons a PLAN can show (the per-segment and
    per-round ones belong to tests/test_device_decode.py and
    tests/test_mesh_scan.py).  `append_mode` is probed without counting
    only (aggregate_segments refuses a non-Overwrite plan before its
    gate): its row pins that.  `topk_router` needs live agents
    (tests/test_scanagent.py)."""
    seen_decode = {r for row in ROWS for r in row.decode_reason}
    assert seen_decode == {"no_sidecar", "predicate"}
    seen_mesh = {r for row in ROWS for r in row.mesh_reason}
    assert seen_mesh == {"topk_by", "topk_decode", "topk_budget"}
    assert "merge_impl" not in read_mod.MESH_FALLBACK_REASONS
    assert "mesh" not in device_decode.FALLBACK_REASONS
    assert {r.route for r in ROWS} == {
        "parts", "fused_acc", "replay", "device_decode", "mesh"}


# ---------------------------------------------------------------------------
# the select: rows under a value predicate (ISSUE 41)
# ---------------------------------------------------------------------------

SELECT = ("_select_rows_joined_jit",)


@dataclasses.dataclass
class SelectRow:
    name: str
    route: str                    # "device" | "host"
    reason: str = ""              # the host route's counted reason
    accel: bool = False
    scan: dict = dataclasses.field(default_factory=dict)
    env: dict = dataclasses.field(default_factory=dict)
    hosts: tuple = tuple(range(HOSTS))
    predicate: object = None
    decode_reason: dict = dataclasses.field(default_factory=dict)
    mode: UpdateMode = UpdateMode.OVERWRITE


SELECT_ROWS = [
    SelectRow("select_cpu_auto", "host", "cpu_auto"),
    SelectRow("select_accel_auto", "device", accel=True),
    SelectRow("select_accel_auto_one_host", "device", accel=True, **HOST7),
    SelectRow("select_mode_device", "device",
              scan={"decode": {"mode": "device"}}),
    SelectRow("select_accel_mode_host", "host", "mode_host", accel=True,
              scan={"decode": {"mode": "host"}}),
    SelectRow("select_accel_decode_forced_off", "host", "mode_host",
              accel=True, env={"HORAEDB_DEVICE_DECODE": "0"}),
    SelectRow("select_benchmark_rehearsal_environment", "device",
              env={"HORAEDB_HOST_AGG": "0", "HORAEDB_DEVICE_DECODE": "1"}),
    SelectRow("select_accel_no_sidecar", "host", "no_sidecar", accel=True,
              scan={"use_sidecar": False},
              decode_reason={"no_sidecar": SEGMENTS}),
    SelectRow("select_mode_device_oversized_in_list", "host", "predicate",
              scan={"decode": {"mode": "device"}},
              predicate=F.In("k", MANY),
              decode_reason={"predicate": SEGMENTS}),
]


def select_segments() -> dict:
    fam = read_mod._SELECT_SEGMENTS
    return {(dict(k)["route"], dict(k)["reason"]): c.value
            for k, c in (fam._children or {}).items()}


@pytest.mark.parametrize("row", SELECT_ROWS,
                         ids=[r.name for r in SELECT_ROWS])
def test_select_route_named_is_the_route_that_ran(row, runtimes,
                                                  monkeypatch):
    """`v > 90` answered as rows: which route takes the segments, from
    scan_select_segments_total{route,reason}, the device plane's ledger
    (the select programs ran, or no program did) and the fallback
    counter; and the rows are the reference's."""
    from horaedb_tpu.ops.select import SelectSpec

    for name in ("HORAEDB_HOST_AGG", "HORAEDB_DEVICE_DECODE",
                 "HORAEDB_FUSED_AGG", "HORAEDB_DEVCOL_STACK"):
        monkeypatch.delenv(name, raising=False)
    for name, value in row.env.items():
        monkeypatch.setenv(name, value)
    if row.accel:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def select_calls() -> dict:
        return {r["fn"]: r["compiles"] + r["dispatches"]
                for r in deviceprof.profiler.snapshot()["fns"]
                if r["fn"] in SELECT + ROUTE_FNS}

    async def go():
        world = World(seed=41)
        s = await CloudObjectStorage.open(
            "db", SEGMENT_MS, MemoryObjectStore(), SCHEMA, 2,
            storage_config(row), runtimes=runtimes)
        try:
            for wr in world.write_requests():
                await s.write(wr)
            s.reader.scan_cache.clear()
            s.reader.encoded_cache.clear()
            # the window is a leaf, as the engine's resolve writes it
            leaves = [F.TimeRangePred("ts", LO, HI)] \
                + ([] if row.predicate is None else [row.predicate])
            req = ScanRequest(range=TimeRange.new(LO, HI),
                              predicate=F.And(leaves))
            spec = SelectSpec(group_col="k", ts_col="ts", value_col="v",
                              op="gt", threshold=90.0)
            seg0, fns0, dec0 = select_segments(), select_calls(), \
                decode_fallbacks()
            qp = await s.plan_select([req], spec, [0])
            assert "Select: group=k, ts=ts, value=v gt 90.0" \
                in qp.describe()
            out = await s.execute_plan(qp)

            ts = np.arange(world.values.shape[1], dtype=np.int64) * TICK_MS
            want = [(f"host_{h:02d}", int(t), world.values[h, i])
                    for h in row.hosts for i, t in enumerate(ts)
                    if LO <= t < HI and world.values[h, i] > 90.0]
            assert want
            got = list(zip([str(g) for g in out["groups"]],
                           out["timestamps"].tolist(), out["values"][0]))
            assert [(g, t) for g, t, _ in got] \
                == [(g, t) for g, t, _ in want]
            assert all(a.tobytes() == np.float32(b).tobytes()
                       for (_, _, a), (_, _, b) in zip(got, want))
            assert out["found"][0] is None      # found at every row

            assert delta(select_segments(), seg0) \
                == {(row.route, row.reason): SEGMENTS}
            ran = delta(select_calls(), fns0)
            assert ran == ({"_select_rows_joined_jit": 1}
                           if row.route == "device" else {}), ran
            assert delta(decode_fallbacks(), dec0) == row.decode_reason
        finally:
            await s.close()

    asyncio.run(go())


def test_every_select_reason_a_plan_can_show_has_a_row():
    """The per-segment reasons (unsorted, streamed, parquet, encoding,
    dtype, budget) need a segment that shows them; the plan's and the
    mode's are all here but `append_mode`: an Append table merges
    bytes, and no value column of one can carry the predicate."""
    assert {r.reason for r in SELECT_ROWS} == {
        "", "cpu_auto", "mode_host", "no_sidecar", "predicate"}
    assert {r.route for r in SELECT_ROWS} == {"device", "host"}


# ---------------------------------------------------------------------------
# the last rows: the newest row of every series (ISSUE 43)
# ---------------------------------------------------------------------------

LAST = ("_last_rows_jit",)

# the select's rows, route for route and reason for reason (one gate:
# ParquetReader._select_route), and one of its own: a leaf that is not
# a key leaf and bounds something else than the timestamp is declined
# per segment, where the slice is planned
LAST_ROWS = [dataclasses.replace(r, name=r.name.replace("select", "last"))
             for r in SELECT_ROWS] + [
    SelectRow("last_mode_device_edge_off_the_timestamp", "host",
              "predicate", scan={"decode": {"mode": "device"}},
              hosts=tuple(range(6, HOSTS)), predicate=F.Gt("k", "host_05"),
              decode_reason={"predicate": SEGMENTS}),
]


def last_segments() -> dict:
    fam = read_mod._LAST_SEGMENTS
    return {(dict(k)["route"], dict(k)["reason"]): c.value
            for k, c in (fam._children or {}).items()}


@pytest.mark.parametrize("row", LAST_ROWS, ids=[r.name for r in LAST_ROWS])
def test_last_route_named_is_the_route_that_ran(row, runtimes, monkeypatch):
    """Every host's newest row under a bound: which route takes the
    segments the walk asks, from scan_last_segments_total{route,reason},
    the device plane's ledger (the last-row program ran once a segment,
    or no program did) and the fallback counter; and the rows are the
    reference's.  Host 3 reports in the older segment alone, so the
    walk asks both."""
    from horaedb_tpu.ops.last import LastSpec

    for name in ("HORAEDB_HOST_AGG", "HORAEDB_DEVICE_DECODE",
                 "HORAEDB_FUSED_AGG", "HORAEDB_DEVCOL_STACK"):
        monkeypatch.delenv(name, raising=False)
    for name, value in row.env.items():
        monkeypatch.setenv(name, value)
    if row.accel:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def last_calls() -> dict:
        return {r["fn"]: r["compiles"] + r["dispatches"]
                for r in deviceprof.profiler.snapshot()["fns"]
                if r["fn"] in LAST + SELECT + ROUTE_FNS}

    async def go():
        world = World(seed=43)
        quiet = min(row.hosts)
        s = await CloudObjectStorage.open(
            "db", SEGMENT_MS, MemoryObjectStore(), SCHEMA, 2,
            storage_config(row), runtimes=runtimes)
        try:
            for seg, wr in enumerate(world.write_requests()):
                if seg:     # the quiet host: no row past the first segment
                    keep = pa.compute.not_equal(
                        wr.batch.column(0), f"host_{quiet:02d}")
                    wr = WriteRequest(wr.batch.filter(keep), wr.time_range)
                await s.write(wr)
            s.reader.scan_cache.clear()
            s.reader.encoded_cache.clear()
            leaves = [F.TimeRangePred("ts", LO, HI)] \
                + ([] if row.predicate is None else [row.predicate])
            req = ScanRequest(range=TimeRange.new(LO, HI),
                              predicate=F.And(leaves))
            spec = LastSpec(group_col="k", ts_col="ts", value_col="v")
            expect = np.array(sorted(f"host_{h:02d}" for h in row.hosts),
                              dtype=object)
            seg0, fns0, dec0 = last_segments(), last_calls(), \
                decode_fallbacks()
            qp = await s.plan_last([req], spec, expect)
            assert qp.describe().startswith(
                "Last: group=k, ts=ts, value=v, fields=1, "
                f"series={len(row.hosts)}, newest first")
            out = await s.execute_plan(qp)

            ts = np.arange(world.values.shape[1], dtype=np.int64) * TICK_MS
            want = []
            for h in row.hosts:
                seen = (ts >= LO) & (ts < (SEGMENT_MS if h == quiet else HI))
                i = int(np.flatnonzero(seen)[-1])
                want.append((f"host_{h:02d}", int(ts[i]),
                             world.values[h, i]))
            got = list(zip([str(g) for g in out["groups"]],
                           out["timestamps"].tolist(), out["values"][0]))
            assert [(g, t) for g, t, _ in got] \
                == [(g, t) for g, t, _ in want]
            assert all(a.tobytes() == np.float32(b).tobytes()
                       for (_, _, a), (_, _, b) in zip(got, want))
            assert out["found"][0] is None      # found at every row

            assert delta(last_segments(), seg0) \
                == {(row.route, row.reason): SEGMENTS}
            ran = delta(last_calls(), fns0)
            # one call a segment asked; none where the newer segment
            # holds no row of the one (quiet) host named: a key leaf
            # that provably matches nothing dispatches nothing
            assert ran == ({"_last_rows_jit":
                            SEGMENTS - (len(row.hosts) == 1)}
                           if row.route == "device" else {}), ran
            assert delta(decode_fallbacks(), dec0) == row.decode_reason
        finally:
            await s.close()

    asyncio.run(go())


def test_every_last_reason_a_plan_can_show_has_a_row():
    """The select's census (one gate), and `predicate` twice: the
    plan's (an In past the device's list) and a slice's (an edge off
    the timestamp).  The per-segment reasons of a READ (unsorted,
    streamed, parquet, encoding, dtype, budget) need a segment that
    shows them, and `memtable` a WAL: tests/test_query_last.py has an
    unsorted slice and a memtable's rows."""
    assert {r.reason for r in LAST_ROWS} == {
        "", "cpu_auto", "mode_host", "no_sidecar", "predicate"}
    assert {r.route for r in LAST_ROWS} == {"device", "host"}
    assert [r.name for r in LAST_ROWS if r.reason == "predicate"] == [
        "last_mode_device_oversized_in_list",
        "last_mode_device_edge_off_the_timestamp"]
    assert len(LAST_ROWS) == len(SELECT_ROWS) + 1


# ---------------------------------------------------------------------------
# the buckets: one field over ALL series by time bucket (ISSUE 48)
# ---------------------------------------------------------------------------

BUCKETS = ("_buckets_jit",)


@dataclasses.dataclass
class BucketsRow(SelectRow):
    bucket_ms: int = BUCKET_MS


# the select's rows, route for route and reason for reason (one gate:
# ParquetReader._select_route), and one of its own: a grid wider than
# the device folds is declined per segment, where the slice is planned
BUCKETS_ROWS = [BucketsRow(**dict(
    dataclasses.asdict(r), name=r.name.replace("select", "buckets"),
    predicate=r.predicate, mode=r.mode)) for r in SELECT_ROWS] + [
    BucketsRow("buckets_mode_device_grid_wider_than_the_device_folds",
               "host", "buckets", scan={"decode": {"mode": "device"}},
               bucket_ms=2_000, decode_reason={"buckets": SEGMENTS}),
]


def buckets_segments() -> dict:
    fam = read_mod._BUCKETS_SEGMENTS
    return {(dict(k)["route"], dict(k)["reason"]): c.value
            for k, c in (fam._children or {}).items()}


@pytest.mark.parametrize("row", BUCKETS_ROWS,
                         ids=[r.name for r in BUCKETS_ROWS])
def test_buckets_route_named_is_the_route_that_ran(row, runtimes,
                                                   monkeypatch):
    """max and min over the hosts named by time bucket, every bucket of
    a window across the boundary (a `limit` past what it holds, so the
    walk asks both segments): which route takes them, from
    scan_buckets_segments_total{route,reason}, the device plane's
    ledger (the bucket program ran once a segment, or no program did)
    and the fallback counter; and the buckets are the reference's."""
    from horaedb_tpu.ops.buckets import BucketsSpec

    for name in ("HORAEDB_HOST_AGG", "HORAEDB_DEVICE_DECODE",
                 "HORAEDB_FUSED_AGG", "HORAEDB_DEVCOL_STACK"):
        monkeypatch.delenv(name, raising=False)
    for name, value in row.env.items():
        monkeypatch.setenv(name, value)
    if row.accel:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def calls() -> dict:
        return {r["fn"]: r["compiles"] + r["dispatches"]
                for r in deviceprof.profiler.snapshot()["fns"]
                if r["fn"] in BUCKETS + LAST + SELECT + ROUTE_FNS}

    async def go():
        world = World(seed=48)
        s = await CloudObjectStorage.open(
            "db", SEGMENT_MS, MemoryObjectStore(), SCHEMA, 2,
            storage_config(row), runtimes=runtimes)
        try:
            for wr in world.write_requests():
                await s.write(wr)
            s.reader.scan_cache.clear()
            s.reader.encoded_cache.clear()
            leaves = [F.TimeRangePred("ts", LO, HI)] \
                + ([] if row.predicate is None else [row.predicate])
            req = ScanRequest(range=TimeRange.new(LO, HI),
                              predicate=F.And(leaves))
            spec = BucketsSpec(group_col="k", ts_col="ts", value_col="v",
                               bucket_ms=row.bucket_ms,
                               aggs=("max", "min"))
            seg0, fns0, dec0 = buckets_segments(), calls(), \
                decode_fallbacks()
            qp = await s.plan_buckets(req, spec, 10_000)
            assert qp.describe().startswith(
                f"Buckets: ts=ts, value=v, bucket_ms={row.bucket_ms}, "
                f"aggs=['max', 'min'], over all series, newest first")
            out = await s.execute_plan(qp)

            ts = np.arange(world.values.shape[1], dtype=np.int64) * TICK_MS
            seen = np.flatnonzero((ts >= LO) & (ts < HI))
            cell = ts[seen] // row.bucket_ms * row.bucket_ms
            want = sorted(set(cell.tolist()), reverse=True)
            vals = world.values[list(row.hosts)]
            assert out["bucket"].tolist() == want
            assert out["count"].tolist() == [
                int((cell == b).sum()) * len(row.hosts) for b in want]
            for agg, fold in (("max", np.max), ("min", np.min)):
                ref = np.array([fold(vals[:, seen[cell == b]])
                                for b in want], dtype=np.float32)
                assert out[agg].tobytes() == ref.tobytes(), agg

            assert delta(buckets_segments(), seg0) \
                == {(row.route, row.reason): SEGMENTS}
            assert delta(calls(), fns0) == (
                {"_buckets_jit": SEGMENTS} if row.route == "device"
                else {})
            assert delta(decode_fallbacks(), dec0) == row.decode_reason
        finally:
            await s.close()

    asyncio.run(go())


def test_every_buckets_reason_a_plan_can_show_has_a_row():
    """The select's census (one gate), and `buckets`: a segment cut
    into more cells than the device folds.  The per-segment reasons of
    a READ (unsorted, streamed, parquet, encoding, dtype, budget) need
    a segment that shows them, and `memtable` a WAL:
    tests/test_query_buckets.py has an unsorted slice and a memtable's
    rows; `range` (a bucket of 25 days or more) needs such a bucket."""
    assert {r.reason for r in BUCKETS_ROWS} == {
        "", "cpu_auto", "mode_host", "no_sidecar", "predicate", "buckets"}
    assert {r.route for r in BUCKETS_ROWS} == {"device", "host"}
    assert len(BUCKETS_ROWS) == len(SELECT_ROWS) + 1


# ---------------------------------------------------------------------------
# the options that went
# ---------------------------------------------------------------------------


def test_mesh_devices_is_an_unknown_key():
    with pytest.raises(Error, match="mesh_devices"):
        from_dict(StorageConfig, {"scan": {"mesh_devices": 4}})


def test_example_toml_parses_and_names_every_scan_field():
    """docs/example.toml is the file the benchmark's server starts
    from: it parses (unknown keys are rejected) to the defaults, and
    every field of [scan] has its key there, commented or set — but
    for `use_sidecar`, which the file never had (ISSUE 30 leaves every
    other line of it letter for letter, so the gap is pinned here, not
    filled)."""
    import tomllib

    from horaedb_tpu.server.config import load_config

    path = pathlib.Path(__file__).resolve().parent.parent \
        / "docs" / "example.toml"
    cfg = load_config(str(path))
    assert cfg.metric_engine.time_merge_storage.scan == ScanConfig()
    text = path.read_text()
    table = tomllib.loads(text)["metric_engine"]["time_merge_storage"]["scan"]
    missing = {f.name for f in dataclasses.fields(ScanConfig)
               if f.name not in table and f"# {f.name} = " not in text}
    assert missing == {"use_sidecar"}
