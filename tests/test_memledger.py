"""Memory plane (ISSUE 14): the process-global memory ledger
(common/memledger.py) — pull/flow accounts, unattributed = RSS - Σ
accounts, pressure-watermark hysteresis, engine wiring (every budget-
bearing component registers; every account zeroes and deregisters on
close), per-trace attribution, the /debug/memory + /stats surfaces,
and the budget-field lint rule."""

import asyncio
import gc
import pathlib

import pytest

from horaedb_tpu.common import ReadableDuration
from horaedb_tpu.common.memledger import (
    MemoryLedger,
    device_memory,
    ledger,
    read_rss_bytes,
)
from horaedb_tpu.metric_engine import Label, MetricEngine, Sample
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.storage.types import TimeRange
from horaedb_tpu.utils import registry, tracing
from horaedb_tpu.wal.config import WalConfig

T0 = 1_700_000_000_000
HOUR = 3_600_000


def run(coro):
    return asyncio.run(coro)


class _Holder:
    """Weak-anchorable stand-in for a cache."""

    def __init__(self, n):
        self.nbytes = n


class TestLedgerCore:
    def test_pull_accounts_and_unattributed_math(self):
        led = MemoryLedger(rss_reader=lambda: 10_000)
        a = _Holder(3_000)
        b = _Holder(4_000)
        led.register("cache_a:t1", lambda h: h.nbytes, anchor=a,
                     budget=8_000)
        led.register("cache_b:t1", lambda h: h.nbytes, anchor=b)
        s = led.sample_once()
        assert s["attributed_bytes"] == 7_000
        assert s["rss_bytes"] == 10_000
        assert s["unattributed_bytes"] == 3_000
        # double counting must be VISIBLE, not floored away
        b.nbytes = 9_000
        s = led.sample_once()
        assert s["unattributed_bytes"] == -2_000

    def test_flow_account_balance_and_high_water(self):
        led = MemoryLedger(rss_reader=lambda: 0)
        f = led.flow("wire")
        f.charge(100)
        f.charge(50)
        assert f.bytes() == 150
        f.credit(120)
        assert f.bytes() == 30
        assert f.high_water == 150
        assert led.sample_once()["accounts"]["wire"] == 30

    def test_dead_anchor_prunes(self):
        led = MemoryLedger(rss_reader=lambda: 0)
        a = _Holder(1_000)
        led.register("orphan:t", lambda h: h.nbytes, anchor=a)
        assert led.sample_once()["accounts"]["orphan"] == 1_000
        del a
        gc.collect()
        s = led.sample_once()
        assert "orphan" not in s["accounts"]
        assert led.get("orphan:t") is None

    def test_duplicate_names_uniquify(self):
        led = MemoryLedger(rss_reader=lambda: 0)
        a, b = _Holder(1), _Holder(2)
        first = led.register("scan_cache:/same", lambda h: h.nbytes,
                             anchor=a)
        second = led.register("scan_cache:/same", lambda h: h.nbytes,
                              anchor=b)
        assert first.name != second.name
        assert second.kind == "scan_cache"
        assert led.sample_once()["accounts"]["scan_cache"] == 3

    def test_kind_gauge_zeroes_after_deregister(self):
        led = MemoryLedger(rss_reader=lambda: 0)
        a = _Holder(500)
        acct = led.register("zgauge:t", lambda h: h.nbytes, anchor=a)
        led.sample_once()
        fam = registry.gauge("memory_account_bytes")
        assert fam.labels(account="zgauge").value == 500
        led.deregister(acct)
        led.sample_once()
        assert fam.labels(account="zgauge").value == 0

    def test_device_account_excluded_from_host_attribution(self):
        """host=False accounts (HBM stacks on accelerator backends)
        report per kind but stay OUT of the total subtracted from host
        RSS — they are not host memory and double-subtracting would
        push unattributed negative by their size."""
        led = MemoryLedger(rss_reader=lambda: 1_000)
        a, d = _Holder(600), _Holder(400)
        led.register("heap:t", lambda h: h.nbytes, anchor=a)
        led.register("hbm:t", lambda h: h.nbytes, anchor=d, host=False)
        s = led.sample_once()
        assert s["accounts"] == {"heap": 600, "hbm": 400}
        assert s["attributed_bytes"] == 600
        assert s["unattributed_bytes"] == 400
        snap = led.snapshot()
        assert snap["accounts"]["hbm"]["host"] is False

    def test_summary_disabled_does_no_sampling(self):
        calls = []

        def rss():
            calls.append(1)
            return 0

        led = MemoryLedger(rss_reader=rss)
        led.sample_once()
        led.configure(enabled=False)
        n = len(calls)
        out = led.summary()
        assert out["enabled"] is False
        assert len(calls) == n  # served the last sample, no new walk

    def test_rss_reader_reads_proc(self):
        rss = read_rss_bytes()
        assert rss is not None and rss > 10 << 20  # a live interpreter


class TestPressure:
    def _led(self):
        led = MemoryLedger(rss_reader=lambda: 0)
        led.configure(soft_bytes=100, hard_bytes=200, hysteresis=0.1)
        return led

    def test_episode_counting_with_hysteresis(self):
        led = self._led()
        led.sample_once(rss=50)
        assert led.pressure_level == 0
        led.sample_once(rss=120)
        assert led.pressure_level == 1
        assert led.pressure_episodes == {"soft": 1, "hard": 0}
        # staying over soft is the SAME episode
        led.sample_once(rss=150)
        assert led.pressure_episodes["soft"] == 1
        led.sample_once(rss=210)
        assert led.pressure_level == 2
        assert led.pressure_episodes == {"soft": 1, "hard": 1}
        # inside the hysteresis band (>= 200 * 0.9): still hard
        led.sample_once(rss=185)
        assert led.pressure_level == 2
        # below the band: de-escalate to the raw level
        led.sample_once(rss=170)
        assert led.pressure_level == 1
        # soft clears only below 100 * 0.9
        led.sample_once(rss=95)
        assert led.pressure_level == 1
        led.sample_once(rss=80)
        assert led.pressure_level == 0
        # a NEW crossing is a NEW episode
        led.sample_once(rss=130)
        assert led.pressure_episodes == {"soft": 2, "hard": 1}

    def test_jump_straight_to_hard_counts_both(self):
        led = self._led()
        led.sample_once(rss=500)
        assert led.pressure_level == 2
        assert led.pressure_episodes == {"soft": 1, "hard": 1}

    def test_disabled_watermarks_pin_zero(self):
        led = MemoryLedger(rss_reader=lambda: 0)
        led.configure(soft_bytes=-1, hard_bytes=-1)
        assert led.soft_bytes is None and led.hard_bytes is None
        led.sample_once(rss=1 << 50)
        assert led.pressure_level == 0


async def _open_full_engine(tmp_path):
    from horaedb_tpu.rollup import RollupConfig

    return await MetricEngine.open(
        f"{tmp_path}/m", MemoryObjectStore(), segment_ms=2 * HOUR,
        wal_config=WalConfig(enabled=True, dir=str(tmp_path / "wal"),
                             flush_interval=ReadableDuration.parse("1h")),
        rollup_config=RollupConfig(enabled=True, tiers=["1m", "1h"]))


def _lint_mapping():
    """tools/lint.py's budget-field -> account-kind mapping, imported
    by path (tools/ is not a package) so this test and the lint rule
    can never drift apart."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "lint", pathlib.Path(__file__).parent.parent / "tools" / "lint.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestEngineWiring:
    def test_every_budget_component_registers(self, tmp_path):
        """Acceptance (the enumerate-and-assert test): every budget-
        bearing component of a fully-wired engine has a live ledger
        account — driven from the SAME mapping the lint rule enforces,
        plus the process-level flow accounts."""
        lint = _lint_mapping()

        async def go():
            import horaedb_tpu.scanagent.client  # noqa: F401 — wire acct

            e = await _open_full_engine(tmp_path)
            try:
                await e.write([Sample(
                    name="cpu", labels=[Label("host", "h1")],
                    timestamp=T0 + i, value=float(i))
                    for i in range(50)])
                await e.flush()
                kinds = ledger.kinds()
                for field, kind in lint._BUDGET_FIELD_ACCOUNTS.items():
                    assert kind in kinds, (field, kind, sorted(kinds))
                for kind in ("wal_backlog", "rollup_state",
                             "objstore_memory", "streamed_mmap",
                             "scanagent_wire"):
                    assert kind in kinds, (kind, sorted(kinds))
            finally:
                await e.close()

        run(go())

    def test_lint_rule_passes_on_repo_and_catches_new_budget(
            self, tmp_path):
        lint = _lint_mapping()
        repo = pathlib.Path(__file__).parent.parent
        files = [p for p in (repo / "horaedb_tpu").rglob("*.py")]
        assert lint.lint_budget_accounts(files) == []
        # a new unmapped budget field is an error
        bad = tmp_path / "horaedb_tpu_new_component.py"
        bad.write_text(
            "from dataclasses import dataclass\n"
            "@dataclass\nclass FooConfig:\n"
            "    foo_max_bytes: int = 1024\n")
        problems = lint.lint_budget_accounts(files + [bad])
        assert len(problems) == 1 and "foo_max_bytes" in problems[0]
        # mapped but never registered is ALSO an error
        lint._BUDGET_FIELD_ACCOUNTS["foo_max_bytes"] = "foo_cache"
        try:
            problems = lint.lint_budget_accounts(files + [bad])
            assert len(problems) == 1 and "foo_cache" in problems[0]
        finally:
            del lint._BUDGET_FIELD_ACCOUNTS["foo_max_bytes"]

    def test_close_deregisters_and_zeroes_gauges(self, tmp_path):
        """Acceptance: after engine close every engine-owned account is
        gone from the ledger (no phantom tables on /debug/memory) and
        every underlying byte gauge reads 0."""
        async def go():
            e = await _open_full_engine(tmp_path)
            await e.write([Sample(
                name="cpu", labels=[Label("host", "h1")],
                timestamp=T0 + i, value=float(i)) for i in range(200)])
            await e.flush()
            await e.query_downsample(
                "cpu", [], TimeRange.new(T0, T0 + 10_000),
                bucket_ms=1000, aggs=("avg",))
            kinds = ledger.kinds()
            for kind in ("scan_cache", "encoded_cache", "parts_memo",
                         "memtable", "wal_backlog", "rollup_state",
                         "mesh_state"):
                assert kind in kinds, kind
            await e.close()
            gone = ("scan_cache", "stack_cache", "encoded_cache",
                    "parts_memo", "memtable", "wal_backlog",
                    "rollup_state", "chunk_cache", "mesh_state")
            after = ledger.kinds()
            for kind in gone:
                assert kind not in after, kind
            s = ledger.sample_once()
            for kind in gone:
                assert s["accounts"].get(kind, 0) == 0, kind
            # the pre-existing global gauges hold the same discipline
            assert registry.gauge("memtable_bytes").value == 0
            assert registry.gauge("scan_cache_bytes").labels(
                tier="tier2").value == 0
            assert registry.gauge(
                "scan_pipeline_inflight_bytes").value == 0

        run(go())

    def test_chunked_engine_chunk_cache_account(self, tmp_path):
        async def go():
            e = await MetricEngine.open(
                f"{tmp_path}/c", MemoryObjectStore(),
                segment_ms=2 * HOUR, chunked_data=True)
            try:
                assert "chunk_cache" in ledger.kinds()
            finally:
                await e.close()
            assert "chunk_cache" not in ledger.kinds()

        run(go())

    def test_sampler_loop_registers(self, tmp_path):
        """The RSS sampler rides the loop registry (PR-7 discipline):
        it appears on /debug/tasks and heartbeats."""
        from horaedb_tpu.common.loops import loops

        async def go():
            e = await MetricEngine.open(
                f"{tmp_path}/m", MemoryObjectStore(), segment_ms=2 * HOUR)
            try:
                kinds = {h.kind for h in loops.handles() if not h.dead()}
                assert "mem-sampler" in kinds
            finally:
                await e.close()

        run(go())


class TestChargeCredit:
    def test_pipeline_inflight_balances_through_scan(self, tmp_path):
        """charge/credit balance: after a multi-segment cold aggregate
        completes (pipeline teardown included), the pipeline_inflight
        account reads 0 — in-flight bytes never leak into steady
        state."""
        async def go():
            e = await MetricEngine.open(
                f"{tmp_path}/m", MemoryObjectStore(), segment_ms=HOUR)
            try:
                for seg in range(3):
                    await e.write([Sample(
                        name="cpu", labels=[Label("host", f"h{i % 5}")],
                        timestamp=T0 + seg * HOUR + i * 100,
                        value=float(i)) for i in range(500)])
                table = e.tables["data"]
                _clear = table.reader.scan_cache.clear
                _clear()
                table.reader.encoded_cache.clear()
                await e.query_downsample(
                    "cpu", [], TimeRange.new(T0, T0 + 3 * HOUR),
                    bucket_ms=60_000, aggs=("avg",))
                acct = ledger.get("pipeline_inflight")
                assert acct is not None
                assert acct.bytes() == 0
            finally:
                await e.close()

        run(go())

    def test_streamed_mmap_account_credits_on_release(self, tmp_path):
        """The streamed-SST mmap flow account charges at map time and
        credits when the LAST buffer reference drops (weakref
        finalizer) — a completed fallback stream leaves no balance."""
        from horaedb_tpu.storage import parquet_io

        async def go():
            store = MemoryObjectStore()
            payload = b"x" * 100_000
            await store.put("big.sst", payload)
            acct = ledger.get("streamed_mmap")
            assert acct is not None
            before = acct.bytes()
            buf = await parquet_io._fetch_mapped(store, "big.sst",
                                                 None, "sst")
            assert bytes(buf) == payload
            assert acct.bytes() == before + len(payload)
            del buf
            gc.collect()
            assert acct.bytes() == before

        run(go())


class TestTraceAttribution:
    def test_cold_scan_mem_deltas_on_trace(self, tmp_path):
        """A traced cold aggregate records mem_account_delta_<kind>
        counters showing which cache tier its resident bytes landed
        in."""
        async def go():
            e = await MetricEngine.open(
                f"{tmp_path}/m", MemoryObjectStore(), segment_ms=2 * HOUR)
            try:
                await e.write([Sample(
                    name="cpu", labels=[Label("host", f"h{i % 5}")],
                    timestamp=T0 + i * 100, value=float(i))
                    for i in range(2000)])
                table = e.tables["data"]
                table.reader.scan_cache.clear()
                table.reader.encoded_cache.clear()
                table.reader.parts_memo.clear()
                tracing.recorder.configure(enabled=True, sample_rate=1.0)
                trace = tracing.recorder.start("/query")
                with tracing.trace_scope(trace):
                    await e.query_downsample(
                        "cpu", [], TimeRange.new(T0, T0 + 300_000),
                        bucket_ms=60_000, aggs=("avg",))
                tracing.recorder.finish(trace)
                deltas = {k: v for k, v in trace.counters.items()
                          if k.startswith("mem_account_delta_")}
                assert deltas, trace.counters
                assert deltas.get("mem_account_delta_encoded_cache",
                                  0) > 0, deltas
            finally:
                await e.close()

        run(go())

    def test_disabled_ledger_skips_attribution(self, tmp_path):
        async def go():
            e = await MetricEngine.open(
                f"{tmp_path}/m", MemoryObjectStore(), segment_ms=2 * HOUR)
            try:
                await e.write([Sample(
                    name="cpu", labels=[Label("host", "h1")],
                    timestamp=T0 + i * 100, value=float(i))
                    for i in range(500)])
                table = e.tables["data"]
                table.reader.scan_cache.clear()
                table.reader.encoded_cache.clear()
                ledger.configure(enabled=False)
                try:
                    trace = tracing.recorder.start("/query")
                    with tracing.trace_scope(trace):
                        await e.query_downsample(
                            "cpu", [], TimeRange.new(T0, T0 + 60_000),
                            bucket_ms=60_000, aggs=("avg",))
                    tracing.recorder.finish(trace)
                finally:
                    ledger.configure(enabled=True)
                assert not any(k.startswith("mem_account_delta_")
                               for k in trace.counters)
            finally:
                await e.close()

        run(go())


class TestDeviceAccounting:
    def test_device_memory_guarded_on_cpu(self):
        """CPU backends report no memory_stats: the probe returns a
        (possibly empty) list, never raises, and the snapshot carries
        the devices section regardless."""
        devs = device_memory()
        assert isinstance(devs, list)
        for d in devs:
            assert d["bytes_in_use"] >= 0
        led = MemoryLedger(rss_reader=lambda: 0)
        assert "devices" in led.snapshot()


class TestServerSurface:
    def test_debug_memory_and_stats_sections(self):
        from aiohttp.test_utils import TestClient, TestServer

        from horaedb_tpu.server.config import ServerConfig
        from horaedb_tpu.server.main import ServerState, build_app

        async def go():
            engine = await MetricEngine.open(
                "memsrv", MemoryObjectStore(), segment_ms=2 * HOUR)
            state = ServerState(engine, ServerConfig())
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                r = await client.get("/debug/memory")
                assert r.status == 200
                body = await r.json()
                assert body["rss_bytes"] > 0
                assert "scan_cache" in body["accounts"]
                grp = body["accounts"]["scan_cache"]
                assert grp["budget"] > 0 and "utilization" in grp
                assert grp["instances"][0]["name"]
                assert body["pressure"]["level"] == 0
                assert "devices" in body
                r = await client.get("/stats")
                mem = (await r.json())["memory"]
                assert mem["rss_bytes"] > 0
                assert mem["attributed_bytes"] >= 0
                assert "accounts" in mem
                r = await client.get("/metrics")
                text = await r.text()
                assert "memory_rss_bytes" in text
                assert "memory_unattributed_bytes" in text
                assert "memory_account_bytes" in text
            finally:
                await client.close()
                await engine.close()

        run(go())

    def test_memory_config_toml(self, tmp_path):
        from horaedb_tpu.server.config import load_config

        p = tmp_path / "cfg.toml"
        p.write_text(
            "[memory]\n"
            "enabled = true\n"
            'interval = "2s"\n'
            'soft_limit = "1GiB"\n'
            'hard_limit = "2GiB"\n'
            "hysteresis = 0.1\n")
        cfg = load_config(str(p))
        assert cfg.memory.interval.seconds == 2.0
        assert cfg.memory.soft_limit.bytes == 1 << 30
        assert cfg.memory.hard_limit.bytes == 2 << 30
        assert cfg.memory.hysteresis == 0.1
        bad = tmp_path / "bad.toml"
        bad.write_text(
            "[memory]\n"
            'soft_limit = "4GiB"\n'
            'hard_limit = "1GiB"\n')
        with pytest.raises(Exception,
                           match="soft_limit must not exceed"):
            load_config(str(bad))
