"""Background-plane observability (ISSUE 7): the loop registry +
watchdog (common/loops.py), op traces (utils/tracing.py), and the
self-monitoring meta-ingest (metric_engine/meta.py)."""

import asyncio
import logging
import threading
import time

import pytest

from horaedb_tpu.common import ReadableDuration, cancel_and_wait
from horaedb_tpu.common import loops as loops_mod
from horaedb_tpu.common.loops import LoopRegistry, loops
from horaedb_tpu.common.runtimes import Runtimes
from horaedb_tpu.metric_engine import MetricEngine
from horaedb_tpu.metric_engine.meta import MetaConfig, MetaIngest
from horaedb_tpu.objstore import InstrumentedStore, MemoryObjectStore
from horaedb_tpu.storage.types import TimeRange
from horaedb_tpu.utils import op_trace, recorder, registry, tracing
from horaedb_tpu.wal.config import WalConfig

T0 = 1_700_000_000_000
HOUR = 3_600_000


def run(coro):
    return asyncio.run(coro)


def _stall_count(kind: str) -> float:
    return registry.counter("loop_stalled_total").labels(loop=kind).value


async def _open_wal_engine(tmp_path, **kw):
    return await MetricEngine.open(
        f"{tmp_path}/m", MemoryObjectStore(), segment_ms=2 * HOUR,
        wal_config=WalConfig(
            enabled=True, dir=str(tmp_path / "wal"),
            flush_interval=ReadableDuration.parse("50ms")), **kw)


class TestLoopRegistry:
    def test_spawn_registers_beats_and_deregisters(self):
        reg = LoopRegistry()

        async def go():
            beats = asyncio.Event()

            async def loop_body(hb):
                while True:
                    hb.beat()
                    hb.ok()
                    beats.set()
                    await asyncio.sleep(0.01)

            t = reg.spawn(loop_body, name="toy:x", owner="test",
                          period_s=0.01, _watch=False)
            await asyncio.wait_for(beats.wait(), 2)
            snap = reg.snapshot()
            assert [s["name"] for s in snap] == ["toy:x"]
            assert snap[0]["kind"] == "toy"
            assert snap[0]["alive"] and not snap[0]["stalled"]
            assert snap[0]["iterations"] >= 1
            assert snap[0]["last_success_age_s"] is not None
            await cancel_and_wait(t)
            # done-callback deregisters: no phantom entries
            assert reg.snapshot() == []
            assert reg.summary()["registered"] == 0

        run(go())

    def test_stall_flag_fires_once_and_clears_on_recovery(self, caplog):
        clock = [0.0]
        reg = LoopRegistry(clock=lambda: clock[0])
        h = reg.register("toy:stall", period_s=1.0)
        h.beat()

        clock[0] = 2.0  # age 2 < threshold max(5, 4*1) = 5
        assert reg.check_once() == []
        clock[0] = 6.0  # age 6 > 5
        before = _stall_count("toy")
        with caplog.at_level(logging.WARNING, "horaedb_tpu.trace.slow"):
            assert reg.check_once() == ["toy:stall"]
        assert h.stalled
        assert _stall_count("toy") == before + 1
        assert any("loop stalled: toy:stall" in r.message
                   for r in caplog.records)
        # a second sweep does NOT re-fire the same episode
        clock[0] = 7.0
        assert reg.check_once() == []
        assert _stall_count("toy") == before + 1
        # recovery: a beat clears the flag on the next sweep
        h.beat()
        clock[0] = 7.5
        assert reg.check_once() == []
        assert not h.stalled
        # a NEW stall is a new episode
        clock[0] = 20.0
        assert reg.check_once() == ["toy:stall"]
        assert _stall_count("toy") == before + 2
        reg.deregister(h)

    def test_idle_loops_exempt_until_next_beat(self):
        clock = [0.0]
        reg = LoopRegistry(clock=lambda: clock[0])
        h = reg.register("toy:idle", period_s=0.1)
        h.beat()
        h.idle()  # parked on an unbounded wait
        clock[0] = 1e4
        assert reg.check_once() == []  # healthy silence
        h.beat()  # woke up
        clock[0] = 2e4
        assert reg.check_once() == ["toy:idle"]
        reg.deregister(h)

    def test_duplicate_live_names_uniquified(self):
        reg = LoopRegistry()
        a = reg.register("wal-commit:/x")
        b = reg.register("wal-commit:/x")
        assert a.name != b.name and b.name.startswith("wal-commit:/x#")
        assert a.kind == b.kind == "wal-commit"
        reg.deregister(a)
        reg.deregister(b)

    def test_explicit_threshold_wins_and_summary_reports(self):
        clock = [0.0]
        reg = LoopRegistry(clock=lambda: clock[0])
        h = reg.register("slowop", period_s=0.1, stall_threshold_s=900.0)
        # a declared threshold is a FLOOR that still scales with the
        # period: a slow-poll config must not flap a healthy loop
        slow_poll = reg.register("slowpoll", period_s=600.0,
                                 stall_threshold_s=120.0)
        assert reg.resolved_threshold(slow_poll) == pytest.approx(
            reg.stall_factor * 600.0)
        reg.deregister(slow_poll)
        h.beat()
        h.error(RuntimeError("boom"))
        clock[0] = 100.0  # far past factor*period, under 900
        assert reg.check_once() == []
        s = reg.summary()
        assert s["erroring"] == ["slowop"]
        assert s["stalled"] == []
        snap = reg.snapshot()[0]
        assert snap["stall_threshold_s"] == 900.0
        assert snap["consecutive_errors"] == 1
        assert "boom" in snap["last_error"]
        clock[0] = 1000.0
        assert reg.check_once() == ["slowop"]
        assert reg.summary()["stalled"] == ["slowop"]
        reg.deregister(h)
        # deregistering a stalled loop leaves no phantom in the summary
        assert reg.summary()["stalled"] == []


class TestWatchdogOnRealLoops:
    def test_injected_flusher_stall_detected_and_recovers(
            self, tmp_path, caplog):
        """Acceptance: a test-hookable stall in a REAL loop (the WAL
        flusher) is detected within its threshold, increments
        loop_stalled_total, lands in the slow log, and clears on
        recovery."""
        async def go():
            e = await _open_wal_engine(tmp_path)
            try:
                ing = e.tables["data"]
                h = loops.get(ing._flusher_task.get_name())
                assert h is not None and h.kind == "wal-flusher"
                h.stall_threshold_s = 0.2
                before = _stall_count("wal-flusher")
                ing.test_stall_s = 5.0  # wedge the next iteration
                await asyncio.sleep(0.35)  # > threshold, < the wedge
                with caplog.at_level(logging.WARNING,
                                     "horaedb_tpu.trace.slow"):
                    fired = loops.check_once()
                assert h.name in fired and h.stalled
                assert _stall_count("wal-flusher") == before + 1
                assert any("loop stalled" in r.message
                           and "wal-flusher" in r.message
                           for r in caplog.records)
                # recovery: un-wedge, let the loop beat again
                ing.test_stall_s = 0.0
                await asyncio.wait_for(_wait_beat(h), 10)
                loops.check_once()
                assert not h.stalled
                assert loops.summary()["stalled"] == []
            finally:
                await e.close()

        async def _wait_beat(h):
            it = h.iterations
            while h.iterations == it:
                await asyncio.sleep(0.02)

        run(go())

    def test_stalled_loop_cancelled_deregisters_cleanly(self, tmp_path):
        """Acceptance: a loop that stalls, gets flagged, then is
        cancelled via cancel_and_wait must deregister — no phantom
        "stalled" loops after close."""
        async def go():
            e = await _open_wal_engine(tmp_path)
            try:
                ing = e.tables["data"]
                h = loops.get(ing._flusher_task.get_name())
                h.stall_threshold_s = 0.1
                ing.test_stall_s = 60.0  # parked in the wedge sleep
                await asyncio.sleep(0.25)
                loops.check_once()
                assert h.stalled
                # the cancel lands inside the injected sleep
                await cancel_and_wait(ing._flusher_task)
                assert loops.get(h.name) is None
                assert h.name not in loops.summary()["stalled"]
                assert all(s["name"] != h.name for s in loops.snapshot())
            finally:
                await e.close()

        run(go())

    def test_cancel_swallow_schedule_still_deregisters(self):
        """The bpo-37658 shape: a loop that swallows the first cancel
        (wait_for completing in the same tick) must still end — and
        deregister — under cancel_and_wait's re-delivery."""
        async def go():
            swallowed = {"n": 0}

            async def sticky_loop(hb):
                while True:
                    hb.beat()
                    try:
                        await asyncio.sleep(3600)
                    except asyncio.CancelledError:
                        if swallowed["n"] == 0:
                            swallowed["n"] += 1
                            continue  # swallow the first delivery
                        raise

            t = loops.spawn(sticky_loop, name="sticky-loop:t",
                            owner="test")
            name = t.get_name()
            await asyncio.sleep(0.05)
            assert loops.get(name) is not None
            await cancel_and_wait(t)
            assert swallowed["n"] == 1
            assert t.done()
            assert loops.get(name) is None

        run(go())

    def test_every_engine_loop_registers(self, tmp_path):
        """Acceptance: every background loop in the process appears in
        the registry with a live heartbeat."""
        async def go():
            from horaedb_tpu.rollup import RollupConfig

            e = await MetricEngine.open(
                f"{tmp_path}/m", MemoryObjectStore(), segment_ms=2 * HOUR,
                wal_config=WalConfig(enabled=True,
                                     dir=str(tmp_path / "wal")),
                rollup_config=RollupConfig(enabled=True,
                                           tiers=["1m", "1h"]),
                meta_config=MetaConfig(enabled=True))
            try:
                kinds = {h.kind for h in loops.handles()
                         if not h.dead()}
                for expected in ("wal-commit", "wal-flusher",
                                 "compact-picker", "compact-executor",
                                 "orphan-scrubber", "manifest-merger",
                                 "rollup", "meta-ingest", "watchdog"):
                    assert expected in kinds, expected
                for s in loops.snapshot():
                    assert s["alive"], s["name"]
                    # everything beat (or registered) within the sweep
                    assert s["heartbeat_age_s"] < 30.0, s
            finally:
                await e.close()

        run(go())


def _role_cpu(role: str) -> float:
    return registry.counter("process_thread_cpu_seconds_total").labels(
        role=role).value


def _burn_cpu(seconds: float) -> float:
    """Spin until this thread has used `seconds` of CPU; what it used."""
    c0 = time.thread_time()
    while time.thread_time() - c0 < seconds:
        pass
    return time.thread_time() - c0


def _flush_turns() -> None:
    """The running loop's busy / select sums into their counters, as
    the sampler's next tick would."""
    asyncio.get_running_loop()._selector.select.__self__.flush()


def _with_sampler(body):
    """`body(reg)` on a loop whose registry runs the stall sampler (the
    selector wrapped, the first tick past, so that the role account
    holds what the threads used before it); then the registry's loops
    are cancelled, as a closing loop cancels them."""
    async def go():
        reg = LoopRegistry()
        reg.ensure_watchdog()
        await asyncio.sleep(0.25)
        try:
            return await body(reg)
        finally:
            tasks = [h.task for h in reg.handles()]
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)

    return run(go())


class TestHostAccounts:
    """The busy / select account of the loop's thread and the role
    account of every thread's CPU (docs/observability.md, loop
    registry)."""

    def test_busy_and_select_add_up_to_the_loops_wall(self):
        async def body(_reg):
            busy = registry.counter("event_loop_busy_seconds_total")
            idle = registry.counter("event_loop_select_seconds_total")
            # readings taken right after a wake-up: the turn under way
            # has booked nothing yet and is microseconds old
            await asyncio.sleep(0.01)
            _flush_turns()
            b0, s0, t0 = busy.value, idle.value, time.perf_counter()
            for _ in range(8):
                await asyncio.sleep(0.04)
                t = time.perf_counter()
                while time.perf_counter() - t < 0.04:
                    pass
            await asyncio.sleep(0.01)
            _flush_turns()
            return (busy.value - b0, idle.value - s0,
                    time.perf_counter() - t0)

        busy_s, select_s, wall = _with_sampler(body)
        assert abs(busy_s + select_s - wall) <= 0.02 * wall
        assert 0.32 <= busy_s <= 0.32 + 0.25 * wall
        assert select_s >= 0.30

    def test_a_blocking_call_on_the_loop_is_busy_time_and_no_cpu(self):
        async def body(_reg):
            busy = registry.counter("event_loop_busy_seconds_total")
            _flush_turns()
            b0, c0 = busy.value, _role_cpu("loop")
            time.sleep(0.4)  # a blocking call on the loop's own thread
            await asyncio.sleep(0.25)  # a tick reads the clocks
            _flush_turns()
            return busy.value - b0, _role_cpu("loop") - c0

        busy_s, loop_cpu = _with_sampler(body)
        assert busy_s >= 0.4
        assert loop_cpu < 0.1  # it stood still: busy - CPU names it

    def test_the_wrap_is_undone_when_the_sampler_ends(self):
        seen = {}

        async def body(_reg):
            sel = asyncio.get_running_loop()._selector
            seen["sel"] = sel
            return "select" in sel.__dict__

        assert _with_sampler(body) is True
        assert "select" not in seen["sel"].__dict__

    def test_a_loop_without_a_selector_is_left_alone(self):
        class Other:
            pass

        assert loops_mod._wrap_selector(Other()) is None

    def test_a_burning_pool_job_moves_its_role_by_its_cpu(self):
        async def body(_reg):
            rt = Runtimes(sst_threads=1, compact_threads=1,
                          manifest_threads=1)
            try:
                # a thread's CPU before its first reading is `other`'s
                await rt.run("sst", _burn_cpu, 0.0)
                await asyncio.sleep(0.25)
                before = {r: _role_cpu(r) for r in ("sst", "compact")}
                used = await rt.run("sst", _burn_cpu, 0.3)
                await asyncio.sleep(0.25)  # a tick, the thread alive
                return used, {r: _role_cpu(r) - v
                              for r, v in before.items()}
            finally:
                rt.close()

        used, moved = _with_sampler(body)
        assert abs(moved["sst"] - used) <= 0.1 * used
        assert moved["compact"] < 0.01

    def test_the_roles_add_up_to_the_process_cpu(self):
        async def body(_reg):
            _burn_cpu(0.05)
            await asyncio.sleep(0.15)
            at_tick = (sum(_role_cpu(r) for r in loops_mod._ROLES),
                       time.process_time())
            loops_mod.sample_thread_cpu()
            return at_tick, (sum(_role_cpu(r) for r in loops_mod._ROLES),
                             time.process_time())

        (tick_sum, tick_now), (read_sum, read_now) = _with_sampler(body)
        # within one tick (0.1 s of every core the process keeps busy)
        # of the process's clock; level with it right after a reading
        assert 0.0 <= tick_now - tick_sum < 1.0
        assert 0.0 <= read_now - read_sum < 0.02

    def test_a_pool_shut_down_mid_run_breaks_nothing(self):
        async def body(reg):
            rt = Runtimes(sst_threads=2, compact_threads=1,
                          manifest_threads=1)
            ids = await asyncio.gather(
                *(rt.run("sst", lambda: (time.sleep(0.05),
                                         threading.get_native_id())[1])
                  for _ in range(2)))
            await asyncio.sleep(0.15)  # read while they live
            assert set(ids) <= set(loops_mod._cpu_last)
            rt.close()  # the threads die under the sampler
            dead = None
            for _ in range(100):  # the kernel reaps them in its time
                dead = [loops_mod._thread_cpu_seconds(i) for i in ids]
                if dead == [None] * len(ids):
                    break
                await asyncio.sleep(0.02)
            await asyncio.sleep(0.25)
            sampler, = [h for h in reg.handles()
                        if h.name.startswith("stall-sampler")]
            return dead, ids, sampler.alive(), sampler.consecutive_errors

        dead, ids, alive, errors = _with_sampler(body)
        assert dead == [None] * len(ids)  # EINVAL, nothing dereferenced
        assert alive and errors == 0
        assert not set(ids) & set(loops_mod._cpu_last)


class TestOpTraces:
    def test_flush_scrub_roll_compaction_op_traces(self, tmp_path):
        """Acceptance: op traces for compaction, flush, roll, and
        scrub appear with kind="op" and objstore attribution."""
        async def go():
            from horaedb_tpu.rollup import RollupConfig
            from horaedb_tpu.storage.config import StorageConfig, from_dict

            cfg = from_dict(StorageConfig, {
                "scheduler": {"input_sst_min_num": 2,
                              "schedule_interval": "100ms"}})
            store = InstrumentedStore(MemoryObjectStore())
            e = await MetricEngine.open(
                f"{tmp_path}/m", store, segment_ms=2 * HOUR, config=cfg,
                wal_config=WalConfig(enabled=True,
                                     dir=str(tmp_path / "wal")),
                rollup_config=RollupConfig(enabled=True,
                                           tiers=["1m", "1h"],
                                           specs=["cpu"]))
            try:
                from horaedb_tpu.metric_engine import Label, Sample

                recorder.clear()
                for batch in range(2):  # two flushes -> two data SSTs
                    await e.write([Sample(
                        name="cpu", labels=[Label("host", f"h{i % 3}")],
                        timestamp=T0 + batch + i * 1000, value=float(i))
                        for i in range(50)])
                    await e.flush()
                await e.rollups.roll_now()
                await e.tables["data"].scrub()
                await e.tables["data"].compact()  # trigger the picker
                for _ in range(100):
                    ops = {t["op"] for t in recorder.list(
                        200, kind="op")}
                    if "compaction" in ops:
                        break
                    await asyncio.sleep(0.1)
                ops = recorder.list(200, kind="op")
                by_op = {}
                for t in ops:
                    by_op.setdefault(t["op"], []).append(t)
                for expected in ("flush", "rollup_pass", "scrub",
                                 "compaction", "wal_commit"):
                    assert expected in by_op, (expected, sorted(by_op))
                # full trace: kind tagged, attribution present
                flush_d = recorder.get(by_op["flush"][0]["trace_id"])
                assert flush_d["kind"] == "op" and flush_d["op"] == "flush"
                assert any(k.startswith("objstore_put")
                           for k in flush_d["counters"]), flush_d
                comp_d = recorder.get(
                    by_op["compaction"][0]["trace_id"])
                assert any(s["name"] == "compaction.execute"
                           for s in comp_d["spans"])
                assert any(k.startswith("objstore_")
                           for k in comp_d["counters"])
                # the query ring stays op-free
                assert all(t["kind"] == "query"
                           for t in recorder.list(200, kind="query"))
            finally:
                await e.close()

        run(go())

    def test_ambient_trace_wins_over_op_trace(self):
        """An op inside a traced request records as that trace's span,
        not a separate op trace (attribution follows causality)."""
        recorder.clear()
        trace = tracing.Trace("t1", "/query")
        with tracing.trace_scope(trace):
            with op_trace("flush", segment=1) as t:
                assert t is None  # no new trace minted
        d = trace.finish()
        assert any(s["name"] == "flush" for s in d["spans"])
        assert recorder.list(10, kind="op") == []

    def test_op_slow_threshold_hits_slow_log(self, caplog):
        before = registry.counter("slow_ops_total").value
        before_q = registry.counter("slow_queries_total").value
        with caplog.at_level(logging.WARNING, "horaedb_tpu.trace.slow"):
            with op_trace("scrub", slow_s=0.0):
                pass
        assert registry.counter("slow_ops_total").value == before + 1
        # a slow OP is not a slow QUERY: the PR-5 metric stays clean
        assert registry.counter("slow_queries_total").value == before_q
        assert any("slow op scrub" in r.message for r in caplog.records)
        # and without the override, the op default (30 s) applies
        with op_trace("scrub"):
            pass
        d = recorder.list(1, op="scrub")[0]
        assert d["slow"] is False

    def test_op_ring_does_not_evict_query_ring(self):
        recorder.clear()
        q = recorder.start("/query")
        recorder.finish(q)
        for i in range(recorder.op_ring_size + 10):
            with op_trace("wal_commit"):
                pass
        assert len(recorder.list(0, kind="op")) == recorder.op_ring_size
        qs = recorder.list(0, kind="query")
        assert [t["trace_id"] for t in qs] == [q.trace_id]


class TestMetaIngest:
    def test_scraped_metrics_queryable_and_rollup_served(self, tmp_path):
        """Acceptance: metrics scraped by meta-ingest are queryable via
        the standard query path and served by a registered rollup."""
        async def go():
            from horaedb_tpu.rollup import RollupConfig

            e = await MetricEngine.open(
                f"{tmp_path}/m", MemoryObjectStore(), segment_ms=2 * HOUR,
                wal_config=WalConfig(enabled=True,
                                     dir=str(tmp_path / "wal")),
                rollup_config=RollupConfig(enabled=True,
                                           tiers=["1m", "1h"]),
                meta_config=MetaConfig(enabled=True))
            try:
                assert ("__meta", "value") in e.rollups.specs
                probe = registry.gauge(
                    "meta_probe_gauge",
                    "test probe scraped by meta-ingest")
                probe.set(42.5)
                n = await e.meta.scrape_once()
                assert n > 0
                await e.flush()
                await e.rollups.roll_now()
                now = e.meta._clock()
                lo = (int(now) // (2 * HOUR)) * (2 * HOUR)
                rng = TimeRange.new(lo, lo + 2 * HOUR)
                # raw rows through the standard query path
                tbl = await e.query("__meta",
                                    [("name", "meta_probe_gauge")], rng)
                assert tbl.num_rows >= 1
                assert tbl.column("value").to_pylist()[-1] == 42.5
                # and the rollup actually serves the aligned query
                served = registry.counter(
                    "rollup_served_queries_total")
                before = served.total
                out = await e.query_downsample(
                    "__meta", [("name", "meta_probe_gauge")], rng,
                    bucket_ms=60_000)
                assert served.total > before
                assert len(out["tsids"]) == 1
            finally:
                await e.close()

        run(go())

    def test_no_meta_about_meta_recursion(self):
        """Acceptance: meta writes never enqueue meta-about-meta
        recursion — a reentrant scrape is skipped, and a scrape never
        contains samples produced by its own write."""
        async def go():
            calls = []
            skipped = registry.counter("meta_scrapes_skipped_total")

            class FakeEngine:
                rollups = None

                async def write(self, samples):
                    calls.append(samples)
                    # a metric the write path itself bumps:
                    registry.gauge(
                        "meta_probe_during_write",
                        "bumped inside the meta write").set(1.0)
                    # and a reentrant scrape attempt (the recursion
                    # shape): MUST be skipped, not queued
                    if len(calls) == 1:
                        before = skipped.value
                        assert await mi.scrape_once() == 0
                        assert skipped.value == before + 1

            mi = MetaIngest(FakeEngine(), MetaConfig(enabled=True))
            n1 = await mi.scrape_once()
            assert n1 > 0 and len(calls) == 1
            names1 = {l.value for s in calls[0] for l in s.labels
                      if l.name == "name"}
            # snapshot-before-write: the during-write metric is absent
            assert "meta_probe_during_write" not in names1
            # ... and present in the NEXT pass
            await mi.scrape_once()
            names2 = {l.value for s in calls[1] for l in s.labels
                      if l.name == "name"}
            assert "meta_probe_during_write" in names2

        run(go())

    def test_max_series_cap_and_sample_shape(self):
        async def go():
            calls = []

            class FakeEngine:
                rollups = None

                async def write(self, samples):
                    calls.append(samples)

            dropped = registry.counter("meta_samples_dropped_total")
            before = dropped.value
            mi = MetaIngest(FakeEngine(),
                            MetaConfig(enabled=True, max_series=5,
                                       metric="__meta"))
            assert await mi.scrape_once() == 5
            assert dropped.value > before
            for s in calls[0]:
                assert s.name == "__meta"
                assert any(l.name == "name" for l in s.labels)
                assert s.field_name == "value"

        run(go())


class TestClusterHealthErrors:
    def test_ping_exception_counted_and_surfaced(self, tmp_path):
        """Satellite fix: heartbeat exceptions are counted per region
        and surfaced with a timestamp instead of being swallowed."""
        async def go():
            from horaedb_tpu.cluster.cluster import Cluster
            from horaedb_tpu.cluster.router import RoutingTable

            class BadBackend:
                async def ping(self):
                    raise RuntimeError("tls handshake exploded")

            class GoodBackend:
                async def ping(self):
                    return True

            c = Cluster({1: BadBackend(), 2: GoodBackend()},
                        RoutingTable.uniform([1, 2]), str(tmp_path),
                        MemoryObjectStore(), 2 * HOUR, None)
            errs = registry.counter("health_monitor_errors_total")
            before = errs.labels(region="1").value
            alive = await c.check_health_once()
            # the round SURVIVES the bad backend and still pings region 2
            assert alive == {1: False, 2: True}
            assert errs.labels(region="1").value == before + 1
            assert 1 in c._health_errors
            assert "tls handshake" in c._health_errors[1]["error"]
            assert c._health_errors[1]["at_ms"] > 0
            backlog = c._health_backlog()
            assert "tls handshake" in backlog["last_errors"]["1"]["error"]
            # consecutive failures still drive the dead mark
            await c.check_health_once()
            assert 1 in c.dead_regions and 2 not in c.dead_regions

        run(go())


class TestServerSurface:
    async def _client(self, **cfg_kw):
        from aiohttp.test_utils import TestClient, TestServer

        from horaedb_tpu.server.config import ServerConfig
        from horaedb_tpu.server.main import ServerState, build_app

        engine = await MetricEngine.open("m", MemoryObjectStore(),
                                         segment_ms=2 * HOUR)
        state = ServerState(engine, ServerConfig(**cfg_kw))
        client = TestClient(TestServer(build_app(state)))
        await client.start_server()
        return client, engine

    def test_debug_tasks_and_stats_loops(self):
        async def go():
            client, engine = await self._client()
            try:
                r = await client.get("/debug/tasks")
                assert r.status == 200
                body = await r.json()
                kinds = {lp["kind"] for lp in body["loops"]}
                assert "compact-picker" in kinds
                assert "manifest-merger" in kinds
                for lp in body["loops"]:
                    for key in ("alive", "stalled", "heartbeat_age_s",
                                "stall_threshold_s",
                                "consecutive_errors"):
                        assert key in lp
                assert body["watchdog"]["enabled"] is True
                r = await client.get("/stats")
                stats = await r.json()
                assert stats["loops"]["registered"] >= 1
                assert stats["loops"]["stalled"] == []
            finally:
                await client.close()
                await engine.close()

        run(go())

    def test_debug_traces_kind_and_op_filters(self):
        async def go():
            client, engine = await self._client()
            try:
                recorder.clear()
                r = await client.post("/admin/scrub")
                assert r.status == 200
                r = await client.get("/debug/traces?kind=op")
                traces = (await r.json())["traces"]
                assert traces and all(t["kind"] == "op" for t in traces)
                assert any(t["op"] == "scrub" for t in traces)
                r = await client.get("/debug/traces?op=scrub")
                traces = (await r.json())["traces"]
                assert traces and all(t["op"] == "scrub"
                                      for t in traces)
                # op traces are fetchable as full trees
                r = await client.get(
                    f"/debug/traces/{traces[0]['trace_id']}")
                assert r.status == 200
                tree = await r.json()
                assert tree["kind"] == "op"
                r = await client.get("/debug/traces?kind=bogus")
                assert r.status == 400
                # the query listing excludes ops
                r = await client.get("/debug/traces?kind=query")
                assert all(t["kind"] == "query"
                           for t in (await r.json())["traces"])
            finally:
                await client.close()
                await engine.close()

        run(go())


class TestConfig:
    def test_watchdog_and_meta_toml(self, tmp_path):
        from horaedb_tpu.server.config import load_config

        p = tmp_path / "c.toml"
        p.write_text("""
[watchdog]
enabled = true
interval = "2s"
stall_factor = 8.0
min_stall = "10s"

[meta]
enabled = true
interval = "30s"
metric = "__health"
max_series = 128
rollup = false

[trace]
op_ring_size = 64
op_slow_threshold = "45s"
op_sample_rate = 0.5
""")
        cfg = load_config(str(p))
        assert cfg.watchdog.interval.seconds == 2.0
        assert cfg.watchdog.stall_factor == 8.0
        assert cfg.meta.enabled and cfg.meta.metric == "__health"
        assert cfg.meta.max_series == 128 and cfg.meta.rollup is False
        assert cfg.trace.op_ring_size == 64
        assert cfg.trace.op_slow_threshold.seconds == 45.0
        assert cfg.trace.op_sample_rate == 0.5

    def test_bad_meta_and_watchdog_rejected(self, tmp_path):
        from horaedb_tpu.common import Error
        from horaedb_tpu.server.config import load_config

        p = tmp_path / "bad.toml"
        p.write_text("[meta]\nenabled = true\nmax_series = 0\n")
        with pytest.raises(Error):
            load_config(str(p))
        p.write_text("[watchdog]\nstall_factor = 0.5\n")
        with pytest.raises(Error):
            load_config(str(p))

    def test_lint_rejects_unwatched_loop_spawn(self, tmp_path):
        """Satellite: a bare create_task of a loop coroutine under
        horaedb_tpu/ is a lint error; the spawn helper is not."""
        import sys
        sys.path.insert(0, "tools")
        try:
            import lint
        finally:
            sys.path.pop(0)
        bad = tmp_path / "horaedb_tpu" / "thing.py"
        bad.parent.mkdir(parents=True)
        bad.write_text(
            "import asyncio\n\n\n"
            "def start(self):\n"
            "    self._t = asyncio.create_task(self._poll_loop())\n")
        problems = lint.lint_file(bad)
        assert any("loop spawned" in p for p in problems), problems
        good = tmp_path / "horaedb_tpu" / "ok.py"
        good.write_text(
            "from horaedb_tpu.common.loops import loops\n\n\n"
            "def start(self):\n"
            "    self._t = loops.spawn(self._poll_loop, name='x')\n")
        assert lint.lint_file(good) == []
