"""Replication plane tests (cluster/replication.py): WAL shipping +
lease-fenced ownership + failover + the auto-rebalance envelope, plus
the seeded failover chaos harness (knobs REPL_SEED / REPL_SCHEDULES,
wired into `make chaos`).

Invariants under test (ISSUE 16 acceptance):
  * zero acked writes lost across kill -9 + promotion, and the
    promoted follower serves grids byte-identical with a single-copy
    control engine fed the same writes;
  * a primary that lost its lease can never commit (stale-epoch flush
    refused at the fencing point, no manifest/SST published);
  * a 409 stale-owner answer mid-gather degrades to a routed retry or
    a partial answer, never a hard client error.
"""

import asyncio
import os
import random

import numpy as np
import pyarrow as pa
import pytest

from horaedb_tpu.cluster import Cluster
from horaedb_tpu.cluster.replication import (
    LeaseManager,
    LocalWalSource,
    RebalanceConfig,
    RebalanceExecutor,
    ReplicationConfig,
    ReplicationError,
    ReplicationHub,
    StaleEpochError,
    StaleOwnerError,
    install_fence,
    promote,
)
from horaedb_tpu.common import ReadableDuration
from horaedb_tpu.metric_engine import Label, MetricEngine, Sample
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.storage.types import TimeRange
from horaedb_tpu.wal import WalConfig
from horaedb_tpu.wal.log import Wal, encode_record, verify_frames

REPL_SEED = int(os.environ.get("REPL_SEED", "1337"), 0)
REPL_SCHEDULES = int(os.environ.get("REPL_SCHEDULES", "10"), 0)

T0 = 1_700_000_000_000
HOUR = 3_600_000


def run(coro):
    return asyncio.run(coro)


def sample(name, labels, ts, value):
    return Sample(name=name, labels=[Label(k, v) for k, v in labels],
                  timestamp=ts, value=value)


def wal_config(wal_dir, **kw):
    """Flush thresholds pinned sky-high: tests drive flushes
    explicitly so the WAL backlog (the shipped tail) is deterministic."""
    defaults = dict(enabled=True, dir=str(wal_dir), flush_rows=10**6,
                    flush_bytes=1 << 30,
                    flush_age=ReadableDuration.parse("1h"),
                    flush_interval=ReadableDuration.parse("1h"),
                    max_group_wait=ReadableDuration.from_millis(0))
    defaults.update(kw)
    return WalConfig(**defaults)


BATCH_SCHEMA = pa.schema([("k", pa.string()), ("ts", pa.int64()),
                          ("v", pa.float64())])


def batch(rows):
    k, t, v = zip(*rows)
    return pa.record_batch(
        [pa.array(list(k)), pa.array(list(t), type=pa.int64()),
         pa.array(list(v), type=pa.float64())], schema=BATCH_SCHEMA)


class Clock:
    """Injected ms clock for lease TTL tests — no wall-time sleeps."""

    def __init__(self, now=T0):
        self.now = now

    def __call__(self):
        return self.now

    def advance(self, ms):
        self.now += ms


async def kill_engine(engine):
    """Simulated kill -9: abort every WAL-fronted table (NO final
    flush — the acked-but-unflushed tail stays only in the WAL) and
    release the engine's runtime threads."""
    for t in engine.tables.values():
        abort = getattr(t, "abort", None)
        if abort is not None:
            await abort()
        else:
            await t.close()
    if getattr(engine, "_runtimes", None) is not None:
        engine._runtimes.close()


async def grid_of(engine, metric, rng, bucket_ms=1000):
    out = await engine.query_downsample(metric, [], rng,
                                        bucket_ms=bucket_ms,
                                        aggs=("sum", "count", "max"))
    return out


def grids_byte_identical(a, b):
    assert list(map(str, a["tsids"])) == list(map(str, b["tsids"]))
    assert a["num_buckets"] == b["num_buckets"]
    assert set(a["aggs"]) == set(b["aggs"])
    for agg, grid in a["aggs"].items():
        ga = np.asarray(grid)
        gb = np.asarray(b["aggs"][agg])
        assert ga.tobytes() == gb.tobytes(), f"{agg} grid differs"


# ---------------------------------------------------------------------------
# satellite (a): WAL segment listing / high-watermark / tail reads /
# retention hook


class TestWalIntrospection:
    def test_segments_and_high_watermark(self, tmp_path):
        async def go():
            cfg = wal_config(tmp_path, segment_bytes=1)  # seal per group
            wal = Wal(str(tmp_path), cfg)
            wal.replay()
            wal.start()
            assert wal.high_watermark == 0
            b = batch([("a", 1, 1.0)])
            for seq in (3, 7, 9):
                await wal.append(seq, TimeRange.new(1, 2), b)
            segs = wal.segments()
            assert [s["id"] for s in segs] == sorted(s["id"] for s in segs)
            assert wal.high_watermark == 9
            # per-segment max_seq covers every committed seq exactly
            assert sorted(s["max_seq"] for s in segs if s["max_seq"]) == \
                [3, 7, 9]
            assert all(s["size"] > 0 for s in segs if s["max_seq"])
            await wal.close()

        run(go())

    def test_high_watermark_survives_replay(self, tmp_path):
        async def go():
            cfg = wal_config(tmp_path)
            wal = Wal(str(tmp_path), cfg)
            wal.replay()
            wal.start()
            await wal.append(5, TimeRange.new(1, 2), batch([("a", 1, 1.0)]))
            await wal.append(8, TimeRange.new(2, 3), batch([("b", 2, 2.0)]))
            await wal.close()
            wal2 = Wal(str(tmp_path), cfg)
            wal2.replay()
            assert wal2.high_watermark == 8
            assert max(s["max_seq"] for s in wal2.segments()) == 8
            await wal2.close()

        run(go())

    def test_read_tail_frame_aligned(self, tmp_path):
        async def go():
            cfg = wal_config(tmp_path)
            wal = Wal(str(tmp_path), cfg)
            wal.replay()
            wal.start()
            b = batch([("a", 1, 1.0), ("b", 2, 2.0)])
            for seq in (1, 2, 3):
                await wal.append(seq, TimeRange.new(1, 3), b)
            seg = wal.segments()[0]
            # full read: every frame verifies, watermark matches
            blob, sealed = await wal.read_tail(seg["id"], 0, 1 << 20)
            assert len(blob) == seg["size"] and sealed is False
            aligned, max_seq, count = verify_frames(blob)
            assert (aligned, max_seq, count) == (len(blob), 3, 3)
            # resume from a frame boundary: the remainder verifies too
            one = len(encode_record(1, TimeRange.new(1, 3), b))
            rest, _ = await wal.read_tail(seg["id"], one, 1 << 20)
            a2, m2, c2 = verify_frames(rest)
            assert (a2, m2, c2) == (len(rest), 3, 2)
            # caught up -> empty blob, not None
            assert await wal.read_tail(seg["id"], seg["size"], 64) == \
                (b"", False)
            # max_bytes caps the chunk
            head, _ = await wal.read_tail(seg["id"], 0, 10)
            assert len(head) == 10
            # unknown segment -> None (truncated; follower resyncs)
            assert await wal.read_tail(seg["id"] + 999, 0, 64) is None
            await wal.close()

        run(go())

    def test_verify_frames_rejects_corruption(self):
        b = batch([("a", 1, 1.0)])
        rec = encode_record(4, TimeRange.new(1, 2), b)
        # torn tail: only the whole frames count
        aligned, max_seq, count = verify_frames(rec * 2 + rec[:7])
        assert (aligned, max_seq, count) == (2 * len(rec), 4, 2)
        # flipped payload byte: crc stops the walk at the corruption
        bad = bytearray(rec * 2)
        bad[len(rec) + 12] ^= 0xFF
        aligned, _, count = verify_frames(bytes(bad))
        assert (aligned, count) == (len(rec), 1)
        assert verify_frames(b"") == (0, 0, 0)

    def test_flushed_seq_is_contiguous_prefix(self, tmp_path):
        """Memtables are per time-segment and flush OUT OF ORDER over
        one shared WAL with interleaved seqs: flushing the newer batch
        (2, 4) must not report flushed_seq=4 while 1 and 3 are still
        only WAL-resident — a follower would count them caught up and
        a failover would lose them."""
        async def go():
            cfg = wal_config(tmp_path)
            wal = Wal(str(tmp_path), cfg)
            wal.replay()
            wal.start()
            b = batch([("a", 1, 1.0)])
            for seq in (1, 2, 3, 4):
                await wal.append(seq, TimeRange.new(1, 2), b)
            assert wal.flushed_seq == 0
            wal.mark_flushed([2, 4])  # newer segment flushed first
            assert wal.flushed_seq == 0  # 1 and 3 still WAL-only
            wal.mark_flushed([1])
            assert wal.flushed_seq == 2  # 3 still pending
            wal.mark_flushed([3])
            assert wal.flushed_seq == 4  # prefix complete
            await wal.close()

        run(go())

    def test_retention_hook_blocks_truncation(self, tmp_path):
        async def go():
            cfg = wal_config(tmp_path, segment_bytes=1)
            wal = Wal(str(tmp_path), cfg)
            wal.replay()
            wal.start()
            b = batch([("a", 1, 1.0)])
            await wal.append(1, TimeRange.new(1, 2), b)
            await wal.append(2, TimeRange.new(1, 2), b)
            wal.mark_flushed([1, 2])
            # hook refuses: flushed + sealed segments stay on disk
            asked = []
            wal.retention = lambda seg_id, max_seq: (
                asked.append((seg_id, max_seq)) or False)
            assert await wal.truncate() == 0
            assert asked and all(seq <= 2 for _, seq in asked)
            # hook allows -> default behavior returns bit-for-bit
            wal.retention = None
            assert await wal.truncate() >= 1
            await wal.close()

        run(go())


# ---------------------------------------------------------------------------
# lease-fenced ownership


class TestLease:
    def test_epoch_monotonic_across_holders(self):
        async def go():
            clock = Clock()
            mgr = LeaseManager(MemoryObjectStore(), "metrics", clock=clock)
            a = await mgr.acquire(7, "node-a", ttl_ms=10_000)
            assert a.epoch == 1
            # live lease is exclusive
            with pytest.raises(ReplicationError):
                await mgr.acquire(7, "node-b", ttl_ms=10_000)
            # the holder itself may re-acquire (epoch still bumps)
            a2 = await mgr.acquire(7, "node-a", ttl_ms=10_000)
            assert a2.epoch == 2
            # expiry opens the door; the new holder's epoch is greater
            clock.advance(20_000)
            b = await mgr.acquire(7, "node-b", ttl_ms=10_000)
            assert b.epoch == 3
            # release leaves an expired TOMBSTONE, not a deletion: the
            # epoch sequence must survive a release/re-acquire cycle
            # (strict monotonicity across everything that ever
            # committed), so the next holder continues it
            await b.release()
            tomb = await mgr.read(7)
            assert tomb is not None and tomb.epoch == 3
            assert tomb.holder == "" and tomb.expires_at_ms == 0
            c = await mgr.acquire(7, "node-c", ttl_ms=10_000)
            assert c.epoch == 4

        run(go())

    def test_check_fences_stolen_lease(self):
        async def go():
            clock = Clock()
            mgr = LeaseManager(MemoryObjectStore(), "metrics", clock=clock)
            a = await mgr.acquire(7, "node-a", ttl_ms=10_000)
            a.grant_ttl_ms(10_000)
            await a.check()  # live and ours
            clock.advance(11_000)
            b = await mgr.acquire(7, "node-b", ttl_ms=10_000)
            with pytest.raises(StaleEpochError):
                await a.check()
            assert a.lost
            # a lost lease stays lost (no store read needed)
            with pytest.raises(StaleEpochError):
                await a.check()
            # renewal must never resurrect the stolen lease either
            with pytest.raises(StaleEpochError):
                await a.renew()
            await b.check()

        run(go())

    def test_expiry_without_thief_still_refuses(self):
        async def go():
            clock = Clock()
            mgr = LeaseManager(MemoryObjectStore(), "metrics", clock=clock)
            a = await mgr.acquire(7, "node-a", ttl_ms=5_000)
            a.grant_ttl_ms(5_000)
            clock.advance(6_000)
            # conservative: expired un-renewed refuses even though no
            # one stole it (under-serve beats double-commit)
            with pytest.raises(StaleEpochError):
                await a.check()

        run(go())

    def test_stale_epoch_flush_refused_no_commit(self, tmp_path):
        """The acceptance invariant: after losing the lease, the old
        primary's flush fails AT the commit point — no SST, no manifest
        entry — and the acked rows stay scan-visible for the new
        primary's replay to cover."""
        async def go():
            clock = Clock()
            store = MemoryObjectStore()
            engine = await MetricEngine.open(
                "repl/region_7", store, segment_ms=2 * HOUR,
                wal_config=wal_config(tmp_path / "wal"))
            try:
                mgr = LeaseManager(store, "repl", clock=clock)
                lease = await mgr.acquire(7, "node-a", ttl_ms=10_000)
                lease.grant_ttl_ms(10_000)
                install_fence(engine, lease)
                await engine.write([
                    sample("cpu", [("host", "h1")], T0 + i, float(i))
                    for i in range(4)])
                ssts_before = (await engine.stats())["ssts"]
                # steal the lease (expiry + new holder at higher epoch)
                clock.advance(11_000)
                await mgr.acquire(7, "node-b", ttl_ms=10_000)
                with pytest.raises(StaleEpochError):
                    await engine.flush()
                stats = await engine.stats()
                assert stats["ssts"] == ssts_before  # nothing committed
                # acked rows remain served (re-inserted post-failure)
                rng = TimeRange.new(T0, T0 + HOUR)
                tbl = await engine.query("cpu", [("host", "h1")], rng)
                assert sorted(tbl.column("value").to_pylist()) == \
                    [0.0, 1.0, 2.0, 3.0]
            finally:
                install_fence(engine, None)
                await engine.close()

        run(go())

    def test_lease_stolen_mid_sst_upload_cannot_commit(self, tmp_path):
        """The worst-case split-brain window: the lease is stolen
        DURING the SST upload (which can run a whole lease TTL), after
        the flush's pre-flight fence check already passed.  The
        publish-point re-check (write_stamped's pre_commit) must still
        refuse — the SST object may exist but no manifest entry ever
        appears, so no reader sees it."""
        async def go():
            clock = Clock()
            hooks = {"steal": None}

            class StealingStore(MemoryObjectStore):
                async def put(self, path, data):
                    if path.endswith(".sst") and hooks["steal"]:
                        steal, hooks["steal"] = hooks["steal"], None
                        await steal()
                    await super().put(path, data)

            store = StealingStore()
            engine = await MetricEngine.open(
                "repl/region_9", store, segment_ms=2 * HOUR,
                wal_config=wal_config(tmp_path / "wal"))
            try:
                mgr = LeaseManager(store, "repl", clock=clock)
                lease = await mgr.acquire(9, "node-a", ttl_ms=10_000)
                lease.grant_ttl_ms(10_000)
                install_fence(engine, lease)
                await engine.write([
                    sample("cpu", [("host", "h1")], T0 + i, float(i))
                    for i in range(4)])
                ssts_before = (await engine.stats())["ssts"]

                async def steal():
                    clock.advance(11_000)
                    await mgr.acquire(9, "node-b", ttl_ms=10_000)

                hooks["steal"] = steal
                with pytest.raises(StaleEpochError):
                    await engine.flush()
                stats = await engine.stats()
                assert stats["ssts"] == ssts_before  # nothing published
                # acked rows stay served for the new primary's replay
                rng = TimeRange.new(T0, T0 + HOUR)
                tbl = await engine.query("cpu", [("host", "h1")], rng)
                assert sorted(tbl.column("value").to_pylist()) == \
                    [0.0, 1.0, 2.0, 3.0]
            finally:
                install_fence(engine, None)
                await engine.close()

        run(go())


# ---------------------------------------------------------------------------
# the tentpole path: ship the WAL, kill the primary, promote the mirror


class TestShipAndPromote:
    def test_promote_byte_identical_zero_loss(self, tmp_path):
        async def go():
            clock = Clock()
            store = MemoryObjectStore()
            primary = await MetricEngine.open(
                "repl/region_7", store, segment_ms=2 * HOUR,
                wal_config=wal_config(tmp_path / "p_wal"))
            # single-copy control: same writes, never killed
            control = await MetricEngine.open(
                "ctl/region_7", MemoryObjectStore(), segment_ms=2 * HOUR,
                wal_config=wal_config(tmp_path / "c_wal"))
            promoted = None
            try:
                flushed = [
                    sample("cpu", [("host", f"h{i}")], T0 + 100 * i,
                           float(i)) for i in range(8)]
                await primary.write(flushed)
                await control.write(flushed)
                await primary.flush()  # these rows live in shared SSTs
                await control.flush()
                tail = [
                    sample("cpu", [("host", f"h{i}")], T0 + 100 * i + 50,
                           float(10 * i)) for i in range(8)]
                await primary.write(tail)   # acked, WAL-only
                await control.write(tail)

                hub = ReplicationHub(primary)
                from horaedb_tpu.cluster.replication import WalFollower
                follower = WalFollower(
                    LocalWalSource(hub, "f1"),
                    str(tmp_path / "mirror"), region=7)
                await follower.poll_once()
                assert follower.lag() == 0
                assert follower.healthy()
                status = hub.status()
                assert status["followers"]["f1"]["lag_seqs"] == 0

                # kill -9 the primary: acked tail exists ONLY in the
                # mirrored WAL now
                hub.close()
                await follower.close()
                await kill_engine(primary)
                primary = None

                mgr = LeaseManager(store, "repl", clock=clock)
                promoted, lease = await promote(
                    "repl", store, 7, mgr, "node-b",
                    str(tmp_path / "mirror"),
                    wal_config(tmp_path / "p_wal"),
                    segment_ms=2 * HOUR)
                rng = TimeRange.new(T0, T0 + 10_000)
                # zero acked-write loss: every row of both batches
                tbl = await promoted.query("cpu", [], rng)
                assert tbl.num_rows == 16
                got = sorted(tbl.column("value").to_pylist())
                want = sorted([float(i) for i in range(8)]
                              + [float(10 * i) for i in range(8)])
                assert got == want
                # grids byte-identical with the single-copy control
                grids_byte_identical(await grid_of(promoted, "cpu", rng),
                                     await grid_of(control, "cpu", rng))
                # the promoted engine is fenced at the new epoch and
                # can commit (it owns the lease)
                assert lease.epoch == 1
                await promoted.flush()
            finally:
                if primary is not None:
                    await primary.close()
                await control.close()
                if promoted is not None:
                    install_fence(promoted, None)
                    await promoted.close()

        run(go())

    def test_promoted_follower_resolves_filters_as_the_scan_does(
            self, tmp_path):
        """The promoted follower's label filters: the series the
        primary flushed are in SSTs it adopts through the manifest
        (posting lists, built from them), the primary's acked tail is
        in its replayed memtables (no SST set names that segment's
        content: the filtered scan answers) — either way what the
        scan of the index table answers, nothing lost."""
        async def go():
            from horaedb_tpu.cluster.replication import WalFollower
            from horaedb_tpu.metric_engine import engine as engine_mod
            from horaedb_tpu.ops import And, Eq
            from horaedb_tpu.storage.read import ScanRequest

            def outcomes():
                return {o: c.value
                        for o, c in engine_mod._POSTINGS.items()}

            async def resolve(engine, host, rng):
                mid = await engine.metric_manager.resolve("cpu", rng)
                before = outcomes()
                got = await engine.index_manager.find_tsids(
                    mid, [("host", host)], rng)
                how = {o: n - before[o] for o, n in outcomes().items()}
                want = set()
                async for b in engine.index_manager.index.scan(ScanRequest(
                        range=rng, predicate=And([
                            Eq("metric_id", mid), Eq("tag_key", "host"),
                            Eq("tag_value", host)]))):
                    want.update(b.column("tsid").to_pylist())
                assert got == want and len(got) == 1
                return how

            clock = Clock()
            store = MemoryObjectStore()
            primary = await MetricEngine.open(
                "repl/region_7", store, segment_ms=2 * HOUR,
                wal_config=wal_config(tmp_path / "p_wal"))
            promoted = None
            try:
                # the tail's series registers in the NEXT 2 h segment
                late = T0 + 2 * HOUR
                await primary.write([
                    sample("cpu", [("host", f"h{i}")], T0 + i, float(i))
                    for i in range(4)])
                await primary.flush()
                await primary.write([
                    sample("cpu", [("host", "tail")], late, 9.0)])
                hub = ReplicationHub(primary)
                follower = WalFollower(LocalWalSource(hub, "f1"),
                                       str(tmp_path / "mirror"), region=7)
                await follower.poll_once()
                hub.close()
                await follower.close()
                await kill_engine(primary)
                primary = None
                promoted, _lease = await promote(
                    "repl", store, 7, LeaseManager(store, "repl",
                                                   clock=clock),
                    "node-b", str(tmp_path / "mirror"),
                    wal_config(tmp_path / "p_wal"), segment_ms=2 * HOUR)
                early = TimeRange.new(T0, T0 + 10)
                assert await resolve(promoted, "h2", early) \
                    == {"hit": 0, "build": 1, "bypass": 0}
                assert await resolve(promoted, "h3", early) \
                    == {"hit": 1, "build": 0, "bypass": 0}
                both = TimeRange.new(T0, late + 10)
                assert await resolve(promoted, "tail", both) \
                    == {"hit": 1, "build": 0, "bypass": 1}
                await promoted.flush()
                assert await resolve(promoted, "tail", both) \
                    == {"hit": 1, "build": 1, "bypass": 0}
            finally:
                if primary is not None:
                    await primary.close()
                if promoted is not None:
                    install_fence(promoted, None)
                    await promoted.close()

        run(go())

    def test_follower_restart_recovers_watermark(self, tmp_path):
        """A restarted follower (fresh WalFollower over an existing
        mirror) rebuilds its shipped watermark from the mirror's own
        frames — it must not report full lag over bytes it already
        holds, and a torn tail from a death mid-append is truncated
        back to a frame boundary."""
        async def go():
            from horaedb_tpu.cluster.replication import WalFollower

            store = MemoryObjectStore()
            engine = await MetricEngine.open(
                "rr/region_0", store, segment_ms=2 * HOUR,
                wal_config=wal_config(tmp_path / "wal"))
            try:
                await engine.write([
                    sample("cpu", [("host", "a")], T0 + i, float(i))
                    for i in range(4)])
                hub = ReplicationHub(engine)
                mirror = tmp_path / "mirror"
                f1 = WalFollower(LocalWalSource(hub, "f"), str(mirror))
                await f1.poll_once()
                assert f1.lag() == 0
                await f1.close()
                # simulate a death mid-append: torn trailing bytes
                victim = next(mirror.rglob("*.wal"))
                with open(victim, "ab") as fh:
                    fh.write(b"\x01torn")
                # the restarted follower recovers without re-shipping
                f2 = WalFollower(LocalWalSource(hub, "f"), str(mirror))
                shipped = await f2.poll_once()
                assert f2.lag() == 0
                assert shipped == 0  # nothing re-shipped
                # torn tail truncated back to whole frames
                blob = victim.read_bytes()
                aligned, _, _ = verify_frames(blob)
                assert aligned == len(blob)
                await f2.close()
                hub.close()
            finally:
                await engine.close()

        run(go())

    def test_retention_waits_for_follower_ack(self, tmp_path):
        async def go():
            store = MemoryObjectStore()
            engine = await MetricEngine.open(
                "repl/region_1", store, segment_ms=2 * HOUR,
                wal_config=wal_config(tmp_path / "wal", segment_bytes=1))
            try:
                hub = ReplicationHub(engine)
                hub.register_follower("f1")  # registered, nothing acked
                await engine.write([
                    sample("cpu", [("host", "a")], T0 + i, float(i))
                    for i in range(4)])
                await engine.flush()
                # flush truncates — but the follower hasn't acked, so
                # sealed segments survive for shipping
                segs = {log: [s for s in segs if s["sealed"]]
                        for log, segs in hub.snapshot()["logs"].items()}
                assert any(segs.values())
                # a fresh mirror can still catch up from zero
                from horaedb_tpu.cluster.replication import WalFollower
                follower = WalFollower(LocalWalSource(hub, "f1"),
                                       str(tmp_path / "mirror"), region=1)
                await follower.poll_once()
                assert follower.lag() == 0
                # acked now: the next truncation drops the backlog
                for wal in (t.wal for t in engine.tables.values()
                            if getattr(t, "wal", None) is not None):
                    await wal.truncate()
                remaining = sum(
                    1 for segs in hub.snapshot()["logs"].values()
                    for s in segs if s["sealed"])
                assert remaining == 0
                await follower.close()
                hub.close()
            finally:
                await engine.close()

        run(go())

    def test_dead_follower_stops_pinning_retention(self, tmp_path):
        """A follower that registered once and then died for good must
        not block WAL truncation forever: past `follower_ttl` its acks
        drop out of the retention quorum, so primary disk stays
        bounded, and /repl/status marks it stale.  A comeback poll
        re-arms retention."""
        async def go():
            clock = Clock()
            store = MemoryObjectStore()
            engine = await MetricEngine.open(
                "repl/region_2", store, segment_ms=2 * HOUR,
                wal_config=wal_config(tmp_path / "wal", segment_bytes=1))
            try:
                cfg = ReplicationConfig(
                    follower_ttl=ReadableDuration.from_secs(30))
                hub = ReplicationHub(engine, cfg, clock=clock)
                hub.register_follower("f1")  # ...then dies for good
                await engine.write([
                    sample("cpu", [("host", "a")], T0 + i, float(i))
                    for i in range(4)])
                await engine.flush()

                def sealed_count():
                    return sum(1 for segs in hub.snapshot()["logs"].values()
                               for s in segs if s["sealed"])

                # still inside the TTL: retention pins sealed segments
                assert sealed_count() > 0
                status = hub.status()
                assert status["followers"]["f1"]["stale"] is False
                assert status["retention_held_by"] == ["f1"]
                # past the TTL: the dead follower stops pinning
                clock.advance(31_000)
                status = hub.status()
                assert status["followers"]["f1"]["stale"] is True
                assert status["retention_held_by"] == []
                for wal in (t.wal for t in engine.tables.values()
                            if getattr(t, "wal", None) is not None):
                    await wal.truncate()
                assert sealed_count() == 0
                # a comeback poll refreshes liveness (and retention)
                hub.snapshot(follower_id="f1")
                assert hub.status()["followers"]["f1"]["stale"] is False
                hub.close()
            finally:
                await engine.close()

        run(go())

    def test_unregistered_follower_keeps_default(self, tmp_path):
        """No followers -> retention defers to the WAL default: a
        single-copy node truncates exactly as before (bit-for-bit)."""
        async def go():
            store = MemoryObjectStore()
            engine = await MetricEngine.open(
                "solo/region_0", store, segment_ms=2 * HOUR,
                wal_config=wal_config(tmp_path / "wal", segment_bytes=1))
            try:
                hub = ReplicationHub(engine)
                await engine.write([
                    sample("cpu", [("host", "a")], T0 + i, float(i))
                    for i in range(4)])
                await engine.flush()
                sealed = sum(
                    1 for segs in hub.snapshot()["logs"].values()
                    for s in segs if s["sealed"])
                assert sealed == 0  # truncated on flush as always
                hub.close()
            finally:
                await engine.close()

        run(go())


# ---------------------------------------------------------------------------
# satellite (b): 409 stale-owner mid-gather -> routed retry or partial


class _StaleBackend:
    """Region backend whose reads always answer 409 stale-owner."""

    def __init__(self, region, owner=None):
        self.region = region
        self.owner = owner
        self.calls = 0

    async def query(self, *a, **kw):
        self.calls += 1
        raise StaleOwnerError(f"region {self.region} moved",
                              region=self.region, owner=self.owner)

    async def query_downsample(self, *a, **kw):
        raise StaleOwnerError(f"region {self.region} moved",
                              region=self.region, owner=self.owner)

    async def label_values(self, *a, **kw):
        raise StaleOwnerError(f"region {self.region} moved",
                              region=self.region, owner=self.owner)

    async def close(self):
        pass


class TestGatherStaleOwner:
    def _seed_cluster(self):
        async def open_c():
            c = await Cluster.open("cluster", MemoryObjectStore(),
                                   num_regions=2, segment_ms=2 * HOUR)
            await c.write([
                sample("cpu", [("host", f"h{i:03d}")], T0 + 1000, float(i))
                for i in range(32)])
            return c
        return open_c

    def test_stale_owner_degrades_to_partial(self):
        async def go():
            c = await self._seed_cluster()()
            try:
                rng = TimeRange.new(T0, T0 + HOUR)
                full = sorted((await c.query("cpu", [], rng))
                              .column("value").to_pylist())
                assert len(full) == 32
                old = c.regions[1]
                c.repoint_region(1, _StaleBackend(1))
                # no resolver: one hop degrades to a partial answer,
                # never a hard error
                tbl, meta = await c.query_gather("cpu", [], rng)
                assert meta.partial and meta.missing_regions == [1]
                assert "stale" in meta.errors[1].lower() or \
                    "moved" in meta.errors[1]
                assert 0 < tbl.num_rows < 32
                c.repoint_region(1, old)
            finally:
                await c.close()

        run(go())

    def test_stale_owner_routed_retry_recovers(self):
        async def go():
            c = await self._seed_cluster()()
            try:
                rng = TimeRange.new(T0, T0 + HOUR)
                real = c.regions[1]
                stale = _StaleBackend(1, owner="node-b")
                c.repoint_region(1, stale)
                resolved = []

                async def resolver(rid, exc):
                    resolved.append((rid, exc.owner))
                    return real

                c.owner_resolver = resolver
                tbl, meta = await c.query_gather("cpu", [], rng)
                # ONE routed hop: complete answer, region repointed
                assert not meta.partial and meta.missing_regions == []
                assert tbl.num_rows == 32
                assert resolved == [(1, "node-b")]
                assert c.regions[1] is real
                # subsequent gathers hit the healed backend directly
                tbl2, meta2 = await c.query_gather("cpu", [], rng)
                assert tbl2.num_rows == 32 and not meta2.partial
            finally:
                await c.close()

        run(go())

    def test_resolver_failure_still_partial(self):
        async def go():
            c = await self._seed_cluster()()
            # repoint_region leaves the old backend to its caller
            real = c.regions[1]
            try:
                rng = TimeRange.new(T0, T0 + HOUR)
                c.repoint_region(1, _StaleBackend(1))

                async def bad_resolver(rid, exc):
                    raise RuntimeError("meta service down")

                c.owner_resolver = bad_resolver
                tbl, meta = await c.query_gather("cpu", [], rng)
                assert meta.partial and meta.missing_regions == [1]
            finally:
                await c.close()
                await real.close()

        run(go())


# ---------------------------------------------------------------------------
# tentpole part 3: the auto-rebalance envelope


class _PlanCluster:
    """Stub cluster exposing exactly what RebalanceExecutor consumes."""

    def __init__(self, plan):
        self.rebalance_survey = {"at_ms": T0, "plan": plan}
        self.splits = []

    async def split_region(self, rid, pivot, new_rid, ttl_ms):
        self.splits.append((rid, pivot, new_rid, ttl_ms))


def _split_entry(rid=0, new_rid=9):
    return {"region": rid, "kind": "split", "pivot_key": 1 << 62,
            "new_region_id": new_rid, "reason": "hot shard"}


class TestRebalanceExecutor:
    def test_gate_order_and_outcomes(self):
        async def go():
            clock = Clock()
            cluster = _PlanCluster([_split_entry()])
            # disabled: recorded, nothing executes
            ex = RebalanceExecutor(cluster, RebalanceConfig(), clock=clock)
            assert (await ex.run_once())[0]["outcome"] == "disabled"
            # enabled but dry_run (the default envelope): still no moves
            ex = RebalanceExecutor(
                cluster, RebalanceConfig(enabled=True), clock=clock)
            rec = (await ex.run_once())[0]
            assert rec["outcome"] == "dry_run"
            assert rec["detail"] == "hot shard"
            assert cluster.splits == []
            # fully armed: the split executes with the config's TTL
            cfg = RebalanceConfig(enabled=True, dry_run=False)
            ex = RebalanceExecutor(cluster, cfg, clock=clock)
            assert (await ex.run_once())[0]["outcome"] == "executed"
            assert cluster.splits == [(0, 1 << 62, 9, cfg.table_ttl_ms)]
            # cooldown: the same region refuses a second move until the
            # window lapses
            assert (await ex.run_once())[0]["outcome"] == "cooldown"
            clock.advance(cfg.cooldown.seconds * 1000 + 1)
            assert (await ex.run_once())[0]["outcome"] == "executed"
            assert [r["outcome"] for r in ex.history] == \
                ["executed", "cooldown", "executed"]

        run(go())

    def test_replica_health_and_throttle_gates(self):
        async def go():
            clock = Clock()
            cluster = _PlanCluster([_split_entry()])
            cfg = RebalanceConfig(enabled=True, dry_run=False)
            ex = RebalanceExecutor(cluster, cfg, clock=clock)
            ex.replica_healthy = lambda rid: False
            assert (await ex.run_once())[0]["outcome"] == \
                "replica_unhealthy"
            assert cluster.splits == []
            # require_replica_healthy=False ignores the probe
            cfg2 = RebalanceConfig(enabled=True, dry_run=False,
                                   require_replica_healthy=False)
            ex2 = RebalanceExecutor(cluster, cfg2, clock=clock)
            ex2.replica_healthy = lambda rid: False
            assert (await ex2.run_once())[0]["outcome"] == "executed"
            # throttle: at the concurrency cap nothing new starts
            ex3 = RebalanceExecutor(cluster, cfg, clock=clock)
            ex3._inflight = cfg.max_concurrent_moves
            assert (await ex3.run_once())[0]["outcome"] == "throttled"

        run(go())

    def test_move_needs_target_hook(self):
        async def go():
            clock = Clock()
            entry = {"region": 2, "kind": "move", "reason": "skew"}
            cluster = _PlanCluster([entry])
            cfg = RebalanceConfig(enabled=True, dry_run=False)
            ex = RebalanceExecutor(cluster, cfg, clock=clock)
            assert (await ex.run_once())[0]["outcome"] == "no_target"

            async def decline(rid, e):
                return False

            ex.move_target = decline
            assert (await ex.run_once())[0]["outcome"] == "declined"
            moved = []

            async def adopt(rid, e):
                moved.append(rid)
                return True

            ex.move_target = adopt
            assert (await ex.run_once())[0]["outcome"] == "executed"
            assert moved == [2]

        run(go())

    def test_split_pivot_from_routing(self):
        async def go():
            c = await Cluster.open("cluster", MemoryObjectStore(),
                                   num_regions=2, segment_ms=2 * HOUR)
            try:
                pivot = c.split_pivot(0)
                rule = next(r for r in c.routing.rules
                            if r.region_id == 0)
                assert rule.start_key < pivot < rule.end_key
            finally:
                await c.close()

        run(go())


# ---------------------------------------------------------------------------
# server plane: /repl/* endpoints, 409 middleware, config sections


class TestServerRepl:
    def test_repl_endpoints_and_stale_owner_409(self, tmp_path):
        async def go():
            from aiohttp.test_utils import TestClient, TestServer

            from horaedb_tpu.server.config import ServerConfig
            from horaedb_tpu.server.main import ServerState, build_app

            engine = await MetricEngine.open(
                "m", MemoryObjectStore(), segment_ms=2 * HOUR,
                wal_config=wal_config(tmp_path / "wal"))
            cfg = ServerConfig()
            cfg.replication.enabled = True
            state = ServerState(engine, cfg)
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                r = await client.post("/write", json={"samples": [
                    {"name": "m1", "labels": {"h": "a"},
                     "timestamp": T0, "value": 1.5}]})
                assert r.status == 200
                # the shipping surface: listing registers the follower
                r = await client.get("/repl/wal/segments",
                                     params={"follower": "f1"})
                assert r.status == 200
                snap = await r.json()
                assert snap["high_watermarks"] and snap["logs"]
                log, segs = next((log, segs) for log, segs
                                 in snap["logs"].items() if segs)
                seg = segs[0]
                r = await client.get("/repl/wal/read", params={
                    "log": log, "segment": str(seg["id"]), "offset": "0",
                    "max_bytes": str(1 << 20)})
                assert r.status == 200
                assert r.headers["X-Wal-Sealed"] in ("0", "1")
                blob = await r.read()
                aligned, max_seq, _ = verify_frames(blob)
                assert aligned == len(blob) > 0
                # truncated segment -> X-Wal-Gone, not an error
                r = await client.get("/repl/wal/read", params={
                    "log": log, "segment": "999999", "offset": "0",
                    "max_bytes": "64"})
                assert r.status == 200
                assert r.headers["X-Wal-Gone"] == "1"
                # out-of-range offset/max_bytes answer 400, not a 500
                # out of Wal.read_tail's internal ensure()
                for bad in ({"offset": "-1", "max_bytes": "64"},
                            {"offset": "0", "max_bytes": "0"}):
                    r = await client.get("/repl/wal/read", params={
                        "log": log, "segment": str(seg["id"]), **bad})
                    assert r.status == 400
                r = await client.post("/repl/wal/ack", json={
                    "follower": "f1", "acks": {log: max_seq}})
                assert r.status == 200
                r = await client.get("/repl/status")
                body = await r.json()
                assert body["role"] == "primary"
                assert body["followers"]["f1"]["acks"][log] == max_seq
                # losing the lease turns the data plane into 409s...
                state.stale_owner = {"region": 7, "epoch": 3,
                                     "reason": "lease stolen"}
                r = await client.post("/query", json={
                    "metric": "m1", "start": T0, "end": T0 + 10})
                assert r.status == 409
                body = await r.json()
                assert body["region"] == 7 and body["epoch"] == 3
                r = await client.post("/write", json={"samples": []})
                assert r.status == 409
                # ...but the ops plane keeps answering (ungoverned)
                r = await client.get("/repl/status")
                assert r.status == 200
                assert (await r.json())["stale_owner"]["region"] == 7
            finally:
                await client.close()
                await state.stop_replication()
                await engine.close()

        run(go())

    def test_repl_disabled_answers_501(self, tmp_path):
        async def go():
            from aiohttp.test_utils import TestClient, TestServer

            from horaedb_tpu.server.config import ServerConfig
            from horaedb_tpu.server.main import ServerState, build_app

            engine = await MetricEngine.open(
                "m", MemoryObjectStore(), segment_ms=2 * HOUR,
                wal_config=wal_config(tmp_path / "wal"))
            state = ServerState(engine, ServerConfig())
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                for path in ("/repl/wal/segments", "/repl/wal/read"):
                    r = await client.get(path)
                    assert r.status == 501
                r = await client.get("/repl/status")
                assert r.status == 200  # status always answers
                assert (await r.json())["role"] == "none"
            finally:
                await client.close()
                await engine.close()

        run(go())

    def test_config_sections_parse_and_validate(self):
        from horaedb_tpu.common import Error
        from horaedb_tpu.server.config import ServerConfig, _dc_from_dict

        cfg = _dc_from_dict(ServerConfig, {
            "replication": {"enabled": True, "region": 3,
                            "primary_url": "http://127.0.0.1:5001",
                            "mirror_dir": "/tmp/mirror",
                            "lease_ttl": "8s", "renew_interval": "2s"},
            "rebalance": {"enabled": True, "dry_run": False,
                          "cooldown": "60s", "max_concurrent_moves": 2},
        })
        assert cfg.replication.region == 3
        assert cfg.replication.lease_ttl.seconds == 8.0
        assert cfg.rebalance.max_concurrent_moves == 2
        with pytest.raises(Error):
            _dc_from_dict(ServerConfig, {"replication": {"bogus": 1}})

    def test_load_config_validations(self, tmp_path):
        pytest.importorskip("tomllib")
        from horaedb_tpu.common import Error
        from horaedb_tpu.server.config import load_config

        p = tmp_path / "cfg.toml"
        p.write_text('[replication]\nenabled = true\n'
                     'lease_ttl = "2s"\nrenew_interval = "5s"\n')
        with pytest.raises(Error, match="renew_interval"):
            load_config(str(p))
        p.write_text('[replication]\nenabled = true\n'
                     'primary_url = "http://x:1"\n')
        with pytest.raises(Error, match="mirror_dir"):
            load_config(str(p))
        p.write_text('[rebalance]\nenabled = true\nskew_ratio = 0.5\n')
        with pytest.raises(Error, match="skew_ratio"):
            load_config(str(p))


# ---------------------------------------------------------------------------
# satellite (c): seeded failover chaos.  The fast variant runs a fixed
# small subset in tier-1; `make chaos` sweeps REPL_SCHEDULES seeded
# rounds (kill -9 at random points mid-ingest, lease-expiry races,
# double-failover flapping).


async def _chaos_round(tmp_path, rnd, round_idx):
    """One randomized failover drill: seeded writes with interleaved
    flushes and follower polls, kill -9 at a random point, promote the
    mirror, verify zero acked-write loss and exactly-once visibility."""
    from horaedb_tpu.cluster.replication import WalFollower

    clock = Clock()
    store = MemoryObjectStore()
    root = f"chaos{round_idx}"
    wal_dir = tmp_path / f"p{round_idx}"
    mirror = tmp_path / f"m{round_idx}"
    engine = await MetricEngine.open(
        f"{root}/region_0", store, segment_ms=2 * HOUR,
        wal_config=wal_config(wal_dir))
    hub = ReplicationHub(engine)
    follower = WalFollower(LocalWalSource(hub, "f"), str(mirror),
                           region=0)
    acked = {}  # (host, ts) -> last acked value
    promoted = None
    try:
        n_batches = rnd.randrange(2, 7)
        for b in range(n_batches):
            rows = [(f"h{rnd.randrange(6)}", T0 + 100 * rnd.randrange(40),
                     float(rnd.randrange(1000))) for _ in
                    range(rnd.randrange(1, 12))]
            # last write to a series+ts wins (OVERWRITE semantics):
            # dedup within the batch the same way
            await engine.write([
                sample("cpu", [("host", h)], ts, v) for h, ts, v in rows])
            for h, ts, v in rows:
                acked[(h, ts)] = v
            if rnd.random() < 0.4:
                await engine.flush()
            if rnd.random() < 0.7:
                await follower.poll_once()
        # final catch-up poll with probability — a lagging follower
        # that missed the last batch would NOT be freshest; this drill
        # always catches up first (lag-aware promotion is asserted via
        # follower.lag() below)
        await follower.poll_once()
        assert follower.lag() == 0
        hub.close()
        await follower.close()
        await kill_engine(engine)
        engine = None

        mgr = LeaseManager(store, root, clock=clock)
        promoted, lease = await promote(
            root, store, 0, mgr, "node-b", str(mirror),
            wal_config(wal_dir), segment_ms=2 * HOUR)
        rng = TimeRange.new(T0 - 1, T0 + 100 * 41)
        tbl = await promoted.query("cpu", [], rng)
        hosts = tbl.column("tsid").to_pylist()
        del hosts
        # exactly-once per (series, ts): no dupes, no losses, last
        # acked value wins
        by_host = {}
        for h in {h for h, _ in acked}:
            t = await promoted.query("cpu", [("host", h)], rng)
            pairs = list(zip(t.column("timestamp").to_pylist(),
                             t.column("value").to_pylist()))
            assert len(pairs) == len(set(ts for ts, _ in pairs)), \
                f"duplicate (series, ts) rows on host {h}"
            by_host[h] = dict(pairs)
        for (h, ts), v in acked.items():
            assert by_host[h].get(ts) == v, \
                f"acked write lost or stale: {h}@{ts}"
        total = sum(len(d) for d in by_host.values())
        assert total == len(acked)
        # the fence holds after failover too: steal the lease, the
        # promoted primary's next flush must refuse
        clock.advance(60_000)
        await mgr.acquire(0, "node-c", ttl_ms=10_000)
        with pytest.raises(StaleEpochError):
            await promoted.flush()
        assert lease.lost
    finally:
        if engine is not None:
            hub.close()
            await follower.close()
            await engine.close()
        if promoted is not None:
            install_fence(promoted, None)
            await promoted.close()


async def _lease_race_round(rnd):
    """Seeded lease-expiry race: contenders pile onto an expired lease;
    at most one wins, epochs stay monotonic, and every loser's fence
    refuses."""
    clock = Clock()
    store = MemoryObjectStore()
    mgr = LeaseManager(store, "race", clock=clock)
    a = await mgr.acquire(0, "node-a", ttl_ms=5_000)
    epoch0 = a.epoch
    clock.advance(rnd.randrange(5_001, 9_000))
    contenders = [f"node-{c}" for c in "bcd"[:rnd.randrange(2, 4)]]
    rnd.shuffle(contenders)
    results = await asyncio.gather(
        *(mgr.acquire(0, who, ttl_ms=5_000) for who in contenders),
        return_exceptions=True)
    winners = [r for r in results if not isinstance(r, BaseException)]
    losers = [r for r in results if isinstance(r, BaseException)]
    assert all(isinstance(e, ReplicationError) for e in losers)
    # the old holder is fenced no matter who won
    with pytest.raises(StaleEpochError):
        await a.check()
    for w in winners:
        assert w.epoch > epoch0
    # the record's holder is exactly one of the winners, and ITS fence
    # check passes; any other "winner" lost the read-back race
    rec = await mgr.read(0)
    assert rec is not None and rec.holder in {w.record.holder
                                              for w in winners}
    live = [w for w in winners if w.record.holder == rec.holder
            and w.epoch == rec.epoch]
    assert len(live) == 1
    await live[0].check()
    for w in winners:
        if w is not live[0]:
            with pytest.raises(StaleEpochError):
                await w.check()


async def _double_failover_round(tmp_path, rnd, round_idx):
    """Flapping drill: primary dies -> B promotes; B dies -> C promotes
    from B's mirror chain.  Every acked write survives both hops and
    epochs climb monotonically."""
    from horaedb_tpu.cluster.replication import WalFollower

    clock = Clock()
    store = MemoryObjectStore()
    root = f"flap{round_idx}"
    a_wal = tmp_path / f"fa{round_idx}"
    b_mirror = tmp_path / f"fb{round_idx}"
    c_mirror = tmp_path / f"fc{round_idx}"
    mgr = LeaseManager(store, root, clock=clock)
    a = await MetricEngine.open(f"{root}/region_0", store,
                                segment_ms=2 * HOUR,
                                wal_config=wal_config(a_wal))
    b = c = None
    acked = {}
    try:
        rows = [(f"h{i}", T0 + 100 * i, float(rnd.randrange(100)))
                for i in range(rnd.randrange(3, 10))]
        await a.write([sample("cpu", [("host", h)], ts, v)
                       for h, ts, v in rows])
        acked.update({(h, ts): v for h, ts, v in rows})
        hub_a = ReplicationHub(a)
        fb = WalFollower(LocalWalSource(hub_a, "b"), str(b_mirror))
        await fb.poll_once()
        assert fb.lag() == 0
        hub_a.close()
        await fb.close()
        await kill_engine(a)
        a = None

        b, lease_b = await promote(root, store, 0, mgr, "node-b",
                                   str(b_mirror), wal_config(a_wal),
                                   segment_ms=2 * HOUR)
        epoch_b = lease_b.epoch
        rows2 = [(f"g{i}", T0 + 100 * i + 7, float(rnd.randrange(100)))
                 for i in range(rnd.randrange(1, 6))]
        await b.write([sample("cpu", [("host", h)], ts, v)
                       for h, ts, v in rows2])
        acked.update({(h, ts): v for h, ts, v in rows2})
        if rnd.random() < 0.5:
            await b.flush()
        hub_b = ReplicationHub(b)
        fc = WalFollower(LocalWalSource(hub_b, "c"), str(c_mirror))
        await fc.poll_once()
        assert fc.lag() == 0
        hub_b.close()
        await fc.close()
        install_fence(b, None)  # the fence object dies with the node
        await kill_engine(b)
        b = None

        clock.advance(60_000)  # B's lease expires with it
        c, lease_c = await promote(root, store, 0, mgr, "node-c",
                                   str(c_mirror), wal_config(a_wal),
                                   segment_ms=2 * HOUR)
        assert lease_c.epoch > epoch_b
        rng = TimeRange.new(T0 - 1, T0 + 100_000)
        for (h, ts), v in acked.items():
            t = await c.query("cpu", [("host", h)], rng)
            got = dict(zip(t.column("timestamp").to_pylist(),
                           t.column("value").to_pylist()))
            assert got.get(ts) == v, f"lost across double failover: {h}"
    finally:
        if a is not None:
            await a.close()
        if b is not None:
            install_fence(b, None)
            await b.close()
        if c is not None:
            install_fence(c, None)
            await c.close()


class TestFailoverChaosFast:
    """Tier-1 subset: two fixed-seed rounds of each drill."""

    def test_failover_round_fast(self, tmp_path):
        async def go():
            for i in range(2):
                await _chaos_round(tmp_path, random.Random(REPL_SEED + i),
                                   i)

        run(go())

    def test_lease_race_fast(self):
        async def go():
            for i in range(2):
                await _lease_race_round(random.Random(REPL_SEED + i))

        run(go())

    def test_double_failover_fast(self, tmp_path):
        async def go():
            await _double_failover_round(
                tmp_path, random.Random(REPL_SEED), 0)

        run(go())


@pytest.mark.slow
class TestFailoverChaos:
    """`make chaos`: REPL_SCHEDULES seeded rounds per drill."""

    def test_failover_chaos(self, tmp_path):
        async def go():
            for i in range(REPL_SCHEDULES):
                await _chaos_round(tmp_path,
                                   random.Random(REPL_SEED + 1000 + i), i)

        run(go())

    def test_lease_race_chaos(self):
        async def go():
            for i in range(max(REPL_SCHEDULES * 4, 20)):
                await _lease_race_round(
                    random.Random(REPL_SEED + 2000 + i))

        run(go())

    def test_double_failover_flapping(self, tmp_path):
        async def go():
            for i in range(max(REPL_SCHEDULES // 2, 2)):
                await _double_failover_round(
                    tmp_path, random.Random(REPL_SEED + 3000 + i), i)

        run(go())


# ---------------------------------------------------------------------------
# ISSUE 17 tentpole (a): self-driving failover — StandbyMonitor
# elections.  Knobs FAILOVER_SEED / FAILOVER_SCHEDULES (wired into
# `make chaos`); the fast class runs one fixed-seed round in tier-1.


FAILOVER_SEED = int(os.environ.get("FAILOVER_SEED", "1337"), 0)
FAILOVER_SCHEDULES = int(os.environ.get("FAILOVER_SCHEDULES", "5"), 0)


async def _until(clock, pred, what, real_timeout_s=30.0, step_ms=100):
    """Advance the injected clock until `pred()` — the ONLY thing the
    harness does while the monitors detect, elect, and promote."""
    loop = asyncio.get_event_loop()
    deadline = loop.time() + real_timeout_s
    while not pred():
        assert loop.time() < deadline, f"drill stalled waiting: {what}"
        clock.advance(step_ms)
        await asyncio.sleep(0.02)


async def _self_driving_round(tmp_path, rnd, round_idx):
    """The acceptance drill: kill -9 A -> a StandbyMonitor promotes B;
    kill -9 the winner -> the surviving monitor promotes C.  The
    harness ONLY kills and advances time — ZERO operator/harness
    promote() calls; each election is the lease's monotonic-epoch
    acquire, raced by the monitors themselves.  Asserts exactly one
    live winner per election, strictly climbing epochs, and zero
    acked-write loss across both hops."""
    from horaedb_tpu.cluster.replication import (FailoverConfig,
                                                 StandbyMonitor,
                                                 WalFollower)

    clock = Clock()
    store = MemoryObjectStore()
    root = f"sd{round_idx}"
    a_wal = tmp_path / f"sda{round_idx}"
    holders = ("node-b", "node-c")
    mirrors = {h: tmp_path / f"sd{h}{round_idx}" for h in holders}
    mgr = LeaseManager(store, root, clock=clock)
    cfg = FailoverConfig(
        enabled=True,
        grace=ReadableDuration.from_millis(300),
        jitter=0.5,
        check_interval=ReadableDuration.from_millis(10),
        fitness_wait=ReadableDuration.from_millis(30),
        cooldown=ReadableDuration.from_millis(200))
    a = await MetricEngine.open(f"{root}/region_0", store,
                                segment_ms=2 * HOUR,
                                wal_config=wal_config(a_wal))
    lease_a = await mgr.acquire(0, "node-a", ttl_ms=5_000)
    install_fence(a, lease_a)
    hubs = {"node-a": ReplicationHub(a)}
    followers = {}
    monitors = {}
    promoted = {}  # holder -> (engine, lease), filled by on_promoted
    open_engines = []
    acked = {}

    def wire(holder):
        follower = WalFollower(LocalWalSource(hubs["node-a"], holder),
                               str(mirrors[holder]), region=0)

        async def on_promoted(engine, lease):
            promoted[holder] = (engine, lease)
            open_engines.append(engine)
            hubs[holder] = ReplicationHub(engine)

        async def retarget(rec):
            # the loser path: fall back to tailing whoever holds the
            # lease now (in-process topology -> the winner's hub)
            hub = hubs.get(rec.holder)
            if hub is not None:
                await follower.retarget(LocalWalSource(hub, holder))

        followers[holder] = follower
        monitors[holder] = StandbyMonitor(
            follower, mgr, 0, holder, cfg, wal_config(a_wal),
            segment_ms=2 * HOUR, lease_ttl_ms=5_000,
            on_promoted=on_promoted, retarget=retarget, clock=clock,
            rng=random.Random(rnd.randrange(1 << 30)))

    try:
        for h in holders:
            wire(h)
            monitors[h].start()
        rows = [(f"h{i}", T0 + 100 * i, float(rnd.randrange(100)))
                for i in range(rnd.randrange(3, 10))]
        await a.write([sample("cpu", [("host", h)], ts, v)
                       for h, ts, v in rows])
        acked.update({(h, ts): v for h, ts, v in rows})
        if rnd.random() < 0.5:
            await a.flush()
        for f in followers.values():
            await f.poll_once()
            assert f.lag() == 0
        # ---- kill -9 A.  Its lease simply stops being renewed; the
        # monitors must notice the expiry, wait out their jittered
        # grace windows, and run the election themselves.
        hubs.pop("node-a").close()
        install_fence(a, None)
        await kill_engine(a)
        a = None
        await _until(clock, lambda: promoted, "first election")
        rec = await mgr.read(0)
        assert len(promoted) == 1, "exactly one winner per election"
        w1 = rec.holder
        assert w1 in promoted
        e1, l1 = promoted[w1]
        assert l1.epoch > lease_a.epoch
        assert monitors[w1].role == "primary"
        assert monitors[w1].last_outcome["outcome"] == "won"
        loser = next(h for h in holders if h != w1)
        # the loser self-heals: next live-lease tick retargets its
        # tailing at the winner (possibly after a lost-election
        # cooldown — that cooldown IS the flapping suppression)
        await _until(
            clock,
            lambda: monitors[loser]._retargeted_epoch == l1.epoch,
            "loser retarget", step_ms=20)
        assert monitors[loser].role == "standby"
        # writes to the new primary ship down the retargeted chain
        rows2 = [(f"g{i}", T0 + 100 * i + 7, float(rnd.randrange(100)))
                 for i in range(rnd.randrange(2, 6))]
        await e1.write([sample("cpu", [("host", h)], ts, v)
                        for h, ts, v in rows2])
        acked.update({(h, ts): v for h, ts, v in rows2})
        await followers[loser].poll_once()
        assert followers[loser].lag() == 0
        # ---- kill -9 the winner.  Only the losing monitor survives;
        # it must wait out cooldown + lease expiry + grace, then take
        # epoch 3 on its own.
        hubs.pop(w1).close()
        install_fence(e1, None)
        await kill_engine(e1)
        open_engines.remove(e1)
        await _until(clock, lambda: loser in promoted,
                     "second election")
        e2, l2 = promoted[loser]
        assert l2.epoch > l1.epoch > lease_a.epoch
        rec = await mgr.read(0)
        assert rec.holder == loser and rec.epoch == l2.epoch
        # zero acked-write loss across both self-driven hops
        rng_q = TimeRange.new(T0 - 1, T0 + 100_000)
        for (h, ts), v in acked.items():
            t = await e2.query("cpu", [("host", h)], rng_q)
            got = dict(zip(t.column("timestamp").to_pylist(),
                           t.column("value").to_pylist()))
            assert got.get(ts) == v, \
                f"acked write lost across self-driving failover: {h}"
        # operator surface: the election history is inspectable
        st = monitors[loser].election_state()
        assert st["role"] == "primary"
        assert st["last_outcome"]["outcome"] == "won"
    finally:
        for mon in monitors.values():
            await mon.close()
        for f in followers.values():
            await f.close()
        for hub in hubs.values():
            hub.close()
        if a is not None:
            await a.close()
        for e in open_engines:
            install_fence(e, None)
            await e.close()


class TestSelfDrivingFailoverFast:
    """Tier-1: one fixed-seed round of the zero-harness-promote
    double-failover drill."""

    def test_self_driving_double_failover(self, tmp_path):
        run(_self_driving_round(tmp_path, random.Random(FAILOVER_SEED),
                                0))


@pytest.mark.slow
class TestSelfDrivingFailover:
    """`make chaos`: FAILOVER_SCHEDULES seeded rounds (jitter seeds,
    batch shapes, and flush points vary per round)."""

    def test_self_driving_sweep(self, tmp_path):
        async def go():
            for i in range(FAILOVER_SCHEDULES):
                await _self_driving_round(
                    tmp_path, random.Random(FAILOVER_SEED + 4000 + i),
                    i)

        run(go())


class TestStandbyMonitorUnits:
    def _stub_follower(self, tmp_path, shipped=None):
        import types

        return types.SimpleNamespace(
            shipped_seqs=dict(shipped or {}), _flushed={},
            mirror_dir=str(tmp_path / "mm"), lag=lambda: 0)

    def test_store_partition_never_arms(self, tmp_path):
        """An unreadable store must surface as a loop error, never as
        an armed grace deadline: partitions elect nobody."""
        from horaedb_tpu.cluster.replication import (FailoverConfig,
                                                     StandbyMonitor)

        class _BoomStore(MemoryObjectStore):
            async def get(self, path):
                raise ConnectionError("store partition")

        async def go():
            clock = Clock()
            mgr = LeaseManager(_BoomStore(), "part", clock=clock)
            mon = StandbyMonitor(
                self._stub_follower(tmp_path), mgr, 0, "node-x",
                FailoverConfig(enabled=True),
                wal_config(tmp_path / "w"), clock=clock)
            for _ in range(3):
                clock.advance(60_000)  # way past any TTL
                with pytest.raises(ConnectionError):
                    await mon._tick()
            assert mon._grace_deadline_ms is None
            assert mon.attempts == 0 and mon.role == "standby"

        run(go())

    def test_defers_to_fresher_sibling(self, tmp_path):
        """At its deadline a standby with a strictly fitter FRESH
        sibling stands down (outcome `deferred`, cooldown armed) and
        leaves the lease untouched."""
        import json as _json

        from horaedb_tpu.cluster.replication import (FailoverConfig,
                                                     StandbyMonitor)

        async def go():
            clock = Clock()
            store = MemoryObjectStore()
            mgr = LeaseManager(store, "defer", clock=clock)
            cfg = FailoverConfig(
                enabled=True,
                fitness_wait=ReadableDuration.from_millis(0),
                cooldown=ReadableDuration.from_millis(500))
            mon = StandbyMonitor(
                self._stub_follower(tmp_path, shipped={"log": 5}),
                mgr, 0, "node-x", cfg, wal_config(tmp_path / "w"),
                clock=clock)
            await store.put(
                "defer/leases/region_0.fitness.node-y.json",
                _json.dumps({"holder": "node-y", "fitness": 9,
                             "at_ms": clock()}).encode())
            mon._grace_deadline_ms = clock() - 1
            await mon._elect()
            assert mon.last_outcome["outcome"] == "deferred"
            assert "node-y" in mon.last_outcome["detail"]
            assert mon._cooldown_until_ms > clock()
            assert await mgr.read(0) is None  # nobody promoted
            # a STALE fitter record never blocks: the sibling is gone
            clock.advance(120_000)
            mon._cooldown_until_ms = 0
            assert await mon._fresher_sibling() is None

        run(go())

    def test_repl_status_election_surface(self, tmp_path):
        """/repl/status on a standby: role flips to `standby` and the
        election dict (observed epoch, grace deadline, last outcome)
        rides along — satellite (6)."""
        async def go():
            from aiohttp.test_utils import TestClient, TestServer

            from horaedb_tpu.cluster.replication import (
                FailoverConfig, StandbyMonitor, WalFollower)
            from horaedb_tpu.server.config import ServerConfig
            from horaedb_tpu.server.main import ServerState, build_app

            engine = await MetricEngine.open(
                "m", MemoryObjectStore(), segment_ms=2 * HOUR,
                wal_config=wal_config(tmp_path / "wal"))
            cfg = ServerConfig()
            cfg.replication.enabled = True
            state = ServerState(engine, cfg)
            follower = WalFollower(
                LocalWalSource(state.repl, "standby-1"),
                str(tmp_path / "mirror"), region=0)
            state.follower = follower
            state.monitor = StandbyMonitor(
                follower,
                LeaseManager(MemoryObjectStore(), "metrics"),
                0, "standby-1", FailoverConfig(enabled=True),
                cfg.wal)
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                r = await client.get("/repl/status")
                body = await r.json()
                assert body["role"] == "standby"
                el = body["election"]
                assert el["holder"] == "standby-1"
                assert el["observed_epoch"] == 0
                assert el["grace_deadline_ms"] is None
                assert el["attempts"] == 0
            finally:
                await client.close()
                await state.monitor.close()
                await follower.close()
                await state.stop_replication()
                await engine.close()

        run(go())


# ---------------------------------------------------------------------------
# ISSUE 17 tentpole (c): lease-backed routing — the 409 routed retry
# against REAL lease records (satellite 3), not stubbed resolvers.


class _CountingStore(MemoryObjectStore):
    def __init__(self):
        super().__init__()
        self.gets = 0

    async def get(self, path):
        self.gets += 1
        return await super().get(path)


class TestLeaseRouting:
    async def _seeded(self, store):
        c = await Cluster.open("cluster", store, num_regions=2,
                               segment_ms=2 * HOUR)
        await c.write([
            sample("cpu", [("host", f"h{i:03d}")], T0 + 1000, float(i))
            for i in range(32)])
        return c

    def test_routed_retry_follows_real_lease(self):
        """A 409 mid-gather re-resolves from the LIVE lease record the
        new primary's election wrote — full answer, region healed."""
        async def go():
            store = MemoryObjectStore()
            c = await self._seeded(store)
            try:
                rng = TimeRange.new(T0, T0 + HOUR)
                real = c.regions[1]
                c.repoint_region(1, _StaleBackend(1, owner="node-b"))
                resolver = c.enable_lease_routing(
                    backend_factory=lambda rec:
                        real if rec.holder == "node-b" else None)
                assert c.owner_resolver is resolver
                # the failover that triggers those 409s: node-b's
                # takeover wrote this record (same path promote() uses)
                mgr = LeaseManager(store, "cluster")
                await mgr.acquire(1, "node-b", ttl_ms=60_000,
                                  url="http://node-b:5001")
                tbl, meta = await c.query_gather("cpu", [], rng)
                assert not meta.partial and tbl.num_rows == 32
                assert c.regions[1] is real
            finally:
                await c.close()

        run(go())

    def test_no_live_lease_degrades_to_partial(self):
        """Mid-election there is NO owner: an expired record resolves
        to None and the gather degrades to a partial answer."""
        async def go():
            store = MemoryObjectStore()
            c = await self._seeded(store)
            # repoint_region leaves the old backend to its caller
            real = c.regions[1]
            try:
                rng = TimeRange.new(T0, T0 + HOUR)
                c.repoint_region(1, _StaleBackend(1))
                c.enable_lease_routing(backend_factory=lambda rec: c)
                # written far in the (injected) past -> expired by the
                # resolver's real clock
                mgr = LeaseManager(store, "cluster", clock=Clock())
                await mgr.acquire(1, "node-dead", ttl_ms=1_000)
                tbl, meta = await c.query_gather("cpu", [], rng)
                assert meta.partial and meta.missing_regions == [1]
            finally:
                await c.close()
                await real.close()

        run(go())

    def test_resolver_cache_ttl_and_contradiction(self):
        """A 409 storm costs one lease read per TTL; a hint that
        contradicts the cached record busts the cache immediately."""
        from horaedb_tpu.cluster.placement import LeaseOwnerResolver

        async def go():
            clock = Clock()
            store = _CountingStore()
            mgr = LeaseManager(store, "r", clock=clock)
            await mgr.acquire(0, "node-b", ttl_ms=600_000, url="u-b")
            backend = object()
            resolver = LeaseOwnerResolver(
                mgr, backend_factory=lambda rec: backend,
                cache_ttl_ms=1000, clock=clock)
            exc = StaleOwnerError("x", region=0, owner="node-b")
            assert await resolver(0, exc) is backend
            g = store.gets
            for _ in range(5):  # storm within the TTL: all cache hits
                assert await resolver(0, exc) is backend
            assert store.gets == g
            clock.advance(1001)  # TTL lapse -> one re-read
            assert await resolver(0, exc) is backend
            assert store.gets == g + 1
            # contradicting owner hint -> immediate re-read
            exc2 = StaleOwnerError("x", region=0, owner="node-z")
            assert await resolver(0, exc2) is backend
            assert store.gets == g + 2

        run(go())

    def test_mid_gather_failover_routes_to_new_owner(self):
        """The election completes WHILE the gather is in flight: the
        409 that follows routes to the record the election just wrote."""
        async def go():
            store = MemoryObjectStore()
            c = await self._seeded(store)
            started = asyncio.Event()
            release = asyncio.Event()

            class _Blocking:
                async def query(self, *a, **kw):
                    started.set()
                    await release.wait()
                    raise StaleOwnerError("owner moved mid-gather",
                                          region=1, owner="node-b")

                async def close(self):
                    pass

            try:
                rng = TimeRange.new(T0, T0 + HOUR)
                real = c.regions[1]
                c.repoint_region(1, _Blocking())
                c.enable_lease_routing(
                    backend_factory=lambda rec:
                        real if rec.holder == "node-b" else None)
                task = asyncio.ensure_future(
                    c.query_gather("cpu", [], rng))
                await started.wait()
                # failover lands mid-gather
                mgr = LeaseManager(store, "cluster")
                await mgr.acquire(1, "node-b", ttl_ms=60_000)
                release.set()
                tbl, meta = await task
                assert not meta.partial and tbl.num_rows == 32
                assert c.regions[1] is real
            finally:
                await c.close()

        run(go())


# ---------------------------------------------------------------------------
# ISSUE 17 tentpole (b): the closed placement loop


class TestPlacementController:
    def test_closes_replica_health_seam(self):
        from horaedb_tpu.cluster.placement import PlacementController

        async def go():
            clock = Clock()
            cluster = _PlanCluster([_split_entry()])
            cfg = RebalanceConfig(enabled=True, dry_run=False)
            ctl = PlacementController(cluster, cfg, clock=clock)
            ex = RebalanceExecutor(cluster, cfg, clock=clock)
            ctl.attach(ex)
            lag = {"v": 5}
            ctl.register_lag_probe(0, lambda: lag["v"])
            rec = (await ex.run_once())[0]
            assert rec["outcome"] == "replica_unhealthy"
            assert ctl.history[-1]["outcome"] == "unhealthy"
            assert cluster.splits == []
            lag["v"] = 0  # replica caught up -> the move proceeds
            assert (await ex.run_once())[0]["outcome"] == "executed"
            assert len(cluster.splits) == 1

        run(go())

    def test_move_target_picks_least_loaded_willing_node(self):
        from horaedb_tpu.cluster.placement import PlacementController

        async def go():
            clock = Clock()
            entry = {"region": 2, "kind": "move", "reason": "skew"}
            cluster = _PlanCluster([entry])
            cfg = RebalanceConfig(enabled=True, dry_run=False)
            ctl = PlacementController(cluster, cfg, clock=clock)
            ex = RebalanceExecutor(cluster, cfg, clock=clock)
            ctl.attach(ex)
            # no registered nodes: the controller answers "no" (the
            # executor sees a decline) and records WHY on its side
            assert (await ex.run_once())[0]["outcome"] == "declined"
            assert ctl.history[-1]["outcome"] == "no_target"
            calls = []

            async def decline(rid, e):
                calls.append(("light", rid))
                return False

            async def adopt(rid, e):
                calls.append(("heavy", rid))
                return True

            ctl.register_node("light", decline, load=lambda: 1)
            ctl.register_node("heavy", adopt, load=lambda: 7)
            assert (await ex.run_once())[0]["outcome"] == "executed"
            # least-loaded asked first; its decline falls through
            assert calls == [("light", 2), ("heavy", 2)]
            assert ctl.history[-1]["detail"] == "-> heavy"

        run(go())

    def test_promotion_choice_freshest_then_name(self):
        from horaedb_tpu.cluster.placement import PlacementController

        async def go():
            ctl = PlacementController(object(), clock=Clock())
            assert ctl.choose_promotion(0) is None
            assert await ctl.promote_region(0) is None
            assert ctl.history[-1]["outcome"] == "no_standby"
            order = []

            def std(name, fit, result):
                async def p():
                    order.append(name)
                    return result
                ctl.register_standby(0, name, lambda: fit, p)

            std("node-c", 9, "engine-c")
            std("node-b", 5, "engine-b")
            assert ctl.choose_promotion(0) == "node-c"  # freshest
            assert await ctl.promote_region(0) == "engine-c"
            assert order == ["node-c"]
            assert ctl.history[-1]["outcome"] == "executed"
            # fitness tie breaks deterministically by holder name
            ctl2 = PlacementController(object(), clock=Clock())
            ctl2.register_standby(1, "node-z", lambda: 5, std)
            ctl2.register_standby(1, "node-a", lambda: 5, std)
            assert ctl2.choose_promotion(1) == "node-a"

        run(go())

    def test_refresh_folds_survey_and_lag(self):
        from horaedb_tpu.cluster.placement import PlacementController

        class _SurveyCluster:
            rebalance_survey = {"at_ms": T0, "stats": {
                0: {"rows": 10, "bytes": 100, "rules": 1},
                1: {"rows": 20, "bytes": 200, "rules": 1}}}

        async def go():
            ctl = PlacementController(_SurveyCluster(), clock=Clock())
            ctl.register_lag_probe(1, lambda: 3)
            snap = await ctl.refresh()
            assert snap["regions"][0]["lag_seqs"] is None
            assert snap["regions"][0]["healthy"]  # no probe: vacuous
            assert snap["regions"][1]["lag_seqs"] == 3
            assert not snap["regions"][1]["healthy"]
            assert ctl.snapshot is snap

        run(go())


class TestFailoverConfig:
    """Satellite (1): the new [failover] / [replication] validations."""

    def _load(self, tmp_path, text):
        from horaedb_tpu.server.config import load_config

        p = tmp_path / "cfg.toml"
        p.write_text(text)
        return load_config(str(p))

    REPL = ('[replication]\nenabled = true\nregion = 0\n'
            'primary_url = "http://x:1"\nmirror_dir = "/tmp/m"\n'
            'lease_ttl = "8s"\nrenew_interval = "2s"\n')

    def test_renew_interval_must_be_under_half_ttl(self, tmp_path):
        pytest.importorskip("tomllib")
        from horaedb_tpu.common import Error

        # exactly ttl/2 is rejected too: one missed renewal must leave
        # margin before the fence expires
        with pytest.raises(Error, match="renew_interval"):
            self._load(tmp_path,
                       '[replication]\nenabled = true\n'
                       'lease_ttl = "4s"\nrenew_interval = "2s"\n')

    def test_failover_needs_replication_follower(self, tmp_path):
        pytest.importorskip("tomllib")
        from horaedb_tpu.common import Error

        with pytest.raises(Error, match="replication"):
            self._load(tmp_path, '[failover]\nenabled = true\n')
        with pytest.raises(Error, match="primary_url"):
            self._load(tmp_path,
                       '[replication]\nenabled = true\n'
                       '[failover]\nenabled = true\n')

    def test_grace_must_cover_one_renew_interval(self, tmp_path):
        pytest.importorskip("tomllib")
        from horaedb_tpu.common import Error

        with pytest.raises(Error, match="grace"):
            self._load(tmp_path, self.REPL +
                       '[failover]\nenabled = true\ngrace = "1s"\n')

    def test_valid_failover_section_parses(self, tmp_path):
        pytest.importorskip("tomllib")
        cfg = self._load(tmp_path, self.REPL +
                         '[failover]\nenabled = true\ngrace = "5s"\n'
                         'jitter = 0.25\ncheck_interval = "250ms"\n')
        assert cfg.failover.enabled
        assert cfg.failover.grace.seconds == 5.0
        assert cfg.failover.jitter == 0.25
        assert cfg.failover.check_interval.seconds == 0.25
