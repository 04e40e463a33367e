"""End-to-end query tracing (docs/observability.md): span trees, the
trace ring, labeled metrics, cross-region stitching, per-trace I/O
attribution, and the slow-query log."""

import asyncio
import json
import logging
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from horaedb_tpu.common import ReadableDuration
from horaedb_tpu.common.runtimes import Runtimes
from horaedb_tpu.metric_engine import Label, MetricEngine, Sample
from horaedb_tpu.objstore import InstrumentedStore, MemoryObjectStore
from horaedb_tpu.server.config import ServerConfig, load_config
from horaedb_tpu.server.main import ServerState, build_app
from horaedb_tpu.utils import metrics as metrics_mod
from horaedb_tpu.utils import tracing
from horaedb_tpu.utils.tracing import (
    export_payload,
    recorder,
    span,
    span_tree,
    trace_add,
    trace_scope,
)

T0 = 1_700_000_000_000
HOUR = 3_600_000


def run(coro):
    return asyncio.run(coro)


def sample(name, labels, ts, value):
    return Sample(name=name, labels=[Label(k, v) for k, v in labels],
                  timestamp=ts, value=value)


@pytest.fixture(autouse=True)
def _reset_recorder():
    """The recorder is process-global (like the registry): restore the
    default config after each test so suites can't bleed."""
    yield
    recorder.configure(enabled=True, ring_size=256, slow_threshold_s=1.0,
                       sample_rate=1.0)


# ---------------------------------------------------------------------------
# Span / trace units


class TestSpans:
    def test_one_sync_span_in_four_reads_the_cpu_clock_and_counts_fourfold(
            self, monkeypatch):
        """A `sync` span draws whether it reads its thread's CPU clock
        (the read is a system call); the counter takes a sampled span's
        CPU over the sampling rate, an estimate of the CPU of them all.
        A span that does not declare itself never reads it."""
        draws = iter([0.1, 0.5, 0.9, 0.3] * 5)
        monkeypatch.setattr(tracing, "_random", lambda: next(draws))
        cpu = metrics_mod.registry.counter(
            "test_sync_span_cpu_seconds_total", "sampled CPU of a test")
        trace = recorder.start("cpu_sampling")
        with trace_scope(trace):
            for _ in range(20):
                with span("burn", sync=True, cpu=cpu):
                    t0 = time.thread_time()
                    while time.thread_time() - t0 < 0.002:
                        pass
            with span("undeclared", cpu=cpu):
                pass
        spans = recorder.finish(trace)["spans"]
        burns = [s for s in spans if s["name"] == "burn"]
        read = [s for s in burns if "cpu_ms" in s]
        assert len(burns) == 20 and len(read) == 5
        assert all(2.0 <= s["cpu_ms"] <= s["duration_ms"] + 0.1
                   for s in read)
        assert cpu.value == pytest.approx(
            sum(s["cpu_ms"] for s in read) / 1e3 / tracing.CPU_SAMPLE,
            abs=1e-4)
        undeclared, = [s for s in spans if s["name"] == "undeclared"]
        assert "cpu_ms" not in undeclared

    def test_span_tree_records_nesting_fields_and_status(self):
        trace = recorder.start("root_op")
        with trace_scope(trace):
            with span("outer", table="cpu"):
                with span("inner"):
                    pass
            with pytest.raises(ValueError):
                with span("failing"):
                    raise ValueError("boom")
        d = recorder.finish(trace)
        by_name = {s["name"]: s for s in d["spans"]}
        assert set(by_name) == {"root_op", "outer", "inner", "failing"}
        root = by_name["root_op"]
        assert root["parent_id"] == "" and root["status"] == "ok"
        assert by_name["outer"]["parent_id"] == root["span_id"]
        assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
        assert by_name["outer"]["fields"] == {"table": "cpu"}
        assert by_name["failing"]["status"] == "error"
        tree = span_tree(d)["tree"]
        assert tree["name"] == "root_op"
        assert {c["name"] for c in tree["children"]} == {"outer", "failing"}
        assert tree["children"][0]["children"][0]["name"] == "inner"

    def test_span_without_trace_still_observes_histogram(self):
        h = metrics_mod.registry.histogram("span_tr_noctx_seconds",
                                           "span tr_noctx duration")
        before = h.count
        with span("tr_noctx"):
            pass
        assert h.count == before + 1
        assert tracing.active_trace() is None

    def test_trace_add_attributes_and_finished_trace_drops(self):
        trace = recorder.start("adds")
        with trace_scope(trace):
            trace_add("widgets", 2)
            trace_add("widgets")
        recorder.finish(trace)
        assert trace.counters["widgets"] == 3
        trace.add("widgets", 99)  # after finish: dropped
        assert trace.counters["widgets"] == 3

    def test_chunk_cache_does_not_masquerade_as_hbm_tier(self):
        """Each LRU built on the ByteLRU core names its own trace
        tier, exactly like its registry counters — the chunked-mode
        sample cache must not attribute as cache_hbm_*."""
        from horaedb_tpu.storage.scan_cache import ByteLRU, ScanCache

        chunk = ByteLRU(1 << 20, trace_tier="chunk")
        bare = ByteLRU(1 << 20)
        hbm = ScanCache(1 << 20)
        chunk.put("k", "v", 8)
        t = recorder.start("q")
        with trace_scope(t):
            chunk.get("k")
            chunk.get("absent")
            bare.get("absent")
            hbm.get(("seg", frozenset(), ()))
        recorder.finish(t)
        assert t.counters["cache_chunk_hits"] == 1
        assert t.counters["cache_chunk_misses"] == 1
        assert t.counters["cache_hbm_misses"] == 1
        assert t.counters.get("cache_hbm_hits") is None

    def test_pool_threads_inherit_the_trace_context(self):
        async def go():
            rts = Runtimes(sst_threads=1)
            try:
                trace = recorder.start("pool")
                with trace_scope(trace):
                    await rts.run("sst", trace_add, "pool_work", 2)
                recorder.finish(trace)
                assert trace.counters["pool_work"] == 2
            finally:
                rts.close()

        run(go())

    def test_ring_bound_and_listing_order(self):
        recorder.configure(ring_size=3)
        ids = []
        for i in range(5):
            t = recorder.start(f"op{i}")
            ids.append(t.trace_id)
            recorder.finish(t)
        listed = recorder.list()
        assert len(listed) == 3
        # newest first, oldest two evicted
        assert [t["trace_id"] for t in listed] == ids[:1:-1]
        assert recorder.get(ids[0]) is None
        assert recorder.get(ids[-1]) is not None

    def test_sampling_and_forced_traces(self):
        recorder.configure(sample_rate=0.0)
        assert recorder.start("never") is None
        forced = recorder.start("forced", trace_id="abc123", forced=True)
        assert forced is not None and forced.trace_id == "abc123"
        recorder.configure(enabled=False)
        assert recorder.start("off", forced=True) is None


class TestSlowLog:
    def test_threshold_breach_fires_slow_log_and_counter(self):
        recorder.configure(slow_threshold_s=0.0)  # everything is slow
        slow0 = tracing._SLOW_QUERIES.value
        records = []
        handler = logging.Handler()
        handler.emit = records.append
        tracing.slow_logger.addHandler(handler)
        try:
            t = recorder.start("slowop")
            d = recorder.finish(t)
        finally:
            tracing.slow_logger.removeHandler(handler)
        assert d["slow"] is True
        assert tracing._SLOW_QUERIES.value == slow0 + 1
        assert records and t.trace_id in records[0].getMessage()

    def test_timeout_status_is_slow_regardless_of_threshold(self):
        recorder.configure(slow_threshold_s=3600.0)
        t = recorder.start("fast_but_dead")
        d = recorder.finish(t, status="timeout")
        assert d["slow"] is True and d["status"] == "timeout"


class TestExportStitching:
    def _completed(self, n_spans=3, field_pad=""):
        t = recorder.start("peer_op")
        with trace_scope(t):
            for i in range(n_spans):
                with span(f"s{i}", pad=field_pad):
                    pass
        return recorder.finish(t)

    def test_export_import_reparents_and_folds_counters(self):
        peer = recorder.start("/query_arrow", trace_id="feed1")
        with trace_scope(peer):
            with span("peer_scan"):
                trace_add("objstore_get_total", 4)
        blob = export_payload(recorder.finish(peer))

        local = recorder.start("/query")
        with trace_scope(local):
            with span("rpc", path="/query_arrow"):
                tracing.ingest_export(blob)
        d = recorder.finish(local)
        by_name = {s["name"]: s for s in d["spans"]}
        rpc = by_name["rpc"]
        # the peer's ROOT reparents under the rpc span; its own child
        # keeps its original parent
        assert by_name["/query_arrow"]["parent_id"] == rpc["span_id"]
        assert by_name["peer_scan"]["parent_id"] == \
            by_name["/query_arrow"]["span_id"]
        assert d["counters"]["objstore_get_total"] == 4

    def test_oversized_export_degrades_not_breaks(self):
        d = self._completed(n_spans=40, field_pad="x" * 200)
        blob = export_payload(d, limit=2000)
        assert len(blob) <= 2000
        payload = json.loads(blob)
        assert payload["dropped_spans"] > 0
        # roots survive the cut (shallowest-first retention)
        kept = {s["name"] for s in payload["spans"]}
        assert "peer_op" in kept

    def test_malformed_export_is_dropped(self):
        """Stitching is best-effort: ANY malformed export — bad JSON,
        wrong shapes, non-dict spans — drops without raising (a raise
        here would fail an otherwise-successful RPC and charge the
        breaker)."""
        local = recorder.start("/query")
        with trace_scope(local):
            tracing.ingest_export("{not json")
            tracing.ingest_export(None)
            tracing.ingest_export('{"spans": [null]}')
            tracing.ingest_export('{"spans": "zzz", "counters": []}')
            tracing.ingest_export('{"spans": [{"span_id": 3}],'
                                  ' "counters": {"x": "NaNgarbage",'
                                  ' "ok": 2, "b": true}}')
        d = recorder.finish(local)
        # only the root + the one dict-shaped span survived; only the
        # numeric (non-bool) counter folded
        assert len(d["spans"]) == 2
        assert d["counters"] == {"ok": 2}

    def test_counter_heavy_export_terminates_within_limit(self):
        """A counter bag bigger than the whole header budget must not
        spin export_payload forever (observed hang: the span shrink
        loop never emptied and counters were never slimmed)."""
        t = recorder.start("fat")
        with trace_scope(t):
            for i in range(400):
                trace_add(f"counter_with_a_long_name_{i:04d}", i * 1.5)
        d = recorder.finish(t)
        blob = export_payload(d, limit=2000)
        assert len(blob) <= 2000
        payload = json.loads(blob)
        assert payload["counters"].get("dropped_counters", 0) > 0

    def test_import_bounds_hold_against_a_flooding_peer(self):
        big = {"spans": [{"span_id": f"s{i}", "parent_id": "zz",
                          "name": "x", "start_ms": i, "duration_ms": 1,
                          "status": "ok", "fields": {}}
                         for i in range(2000)],
               "counters": {f"k{i}": 1 for i in range(2000)}}
        local = recorder.start("/query")
        with trace_scope(local):
            tracing.ingest_export(json.dumps(big))
        d = recorder.finish(local)
        assert len(d["spans"]) <= 513  # import cap + root
        assert len(d["counters"]) <= 256
        # and the resulting export still fits a header
        assert len(export_payload(d)) <= tracing.EXPORT_LIMIT


# ---------------------------------------------------------------------------
# Labeled metrics


class TestLabeledMetrics:
    def test_counter_labels_render_and_total(self):
        r = metrics_mod.MetricsRegistry()
        fam = r.counter("tr_evt_total", "events by kind")
        fam.labels(kind="a").inc(2)
        fam.labels(kind="b").inc()
        assert fam.labels(kind="a").value == 2
        assert fam.total == 3
        text = r.render()
        assert '# TYPE tr_evt_total counter' in text
        assert 'tr_evt_total{kind="a"} 2.0' in text
        # purely-labeled family: no phantom bare series
        assert "\ntr_evt_total 0" not in text

    def test_bare_metric_keeps_rendering_and_mixed_families_work(self):
        r = metrics_mod.MetricsRegistry()
        bare = r.counter("tr_bare_total", "bare")
        text = r.render()
        assert "tr_bare_total 0.0" in text  # untouched bare still renders
        bare.inc()
        bare.labels(k="v").inc(5)
        text = r.render()
        assert "tr_bare_total 1.0" in text
        assert 'tr_bare_total{k="v"} 5.0' in text

    def test_histogram_labels_share_buckets_and_render_le_grid(self):
        r = metrics_mod.MetricsRegistry()
        fam = r.histogram("tr_lat_seconds", "latency", buckets=(0.1, 1.0))
        fam.labels(stage="x").observe(0.5)
        text = r.render()
        assert 'tr_lat_seconds_bucket{stage="x",le="1.0"} 1' in text
        assert 'tr_lat_seconds_count{stage="x"} 1' in text

    def test_render_is_sorted_and_label_values_escaped(self):
        r = metrics_mod.MetricsRegistry()
        r.counter("tr_zz_total", "z").inc()
        r.counter("tr_aa_total", "a").labels(v='say "hi"\n').inc()
        text = r.render()
        assert text.index("tr_aa_total") < text.index("tr_zz_total")
        assert 'v="say \\"hi\\"\\n"' in text

    def test_span_bucket_override_reaches_the_registry(self):
        with span("tr_longop", buckets=metrics_mod.WIDE_BUCKETS):
            pass
        h = metrics_mod.registry.histogram("span_tr_longop_seconds",
                                           "span tr_longop duration")
        assert h.buckets == metrics_mod.WIDE_BUCKETS


# ---------------------------------------------------------------------------
# Per-trace object-store attribution (objstore/middleware.py)


class TestInstrumentedStoreAttribution:
    def test_gets_attribute_to_the_active_trace_then_to_none(self):
        async def go():
            store = InstrumentedStore(MemoryObjectStore())
            await store.put("k", b"12345")
            trace = recorder.start("q")
            with trace_scope(trace):
                await store.get("k")
                await store.get_range("k", 1, 4)
            recorder.finish(trace)
            assert trace.counters["objstore_get_total"] == 1
            assert trace.counters["objstore_get_range_total"] == 1
            assert trace.counters["objstore_get_bytes"] == 5 + 3
            assert trace.counters["objstore_get_ms"] >= 0
            # once the query ended, further ops attribute to nothing
            with trace_scope(trace):
                await store.get("k")
            assert trace.counters["objstore_get_total"] == 1
            # puts outside any trace: no error, no attribution
            await store.put("k2", b"x")

        run(go())


# ---------------------------------------------------------------------------
# Server integration


class TestServerTracing:
    def test_query_returns_trace_id_and_debug_endpoints_serve_it(self):
        async def go():
            engine = await MetricEngine.open(
                "tr_db", InstrumentedStore(MemoryObjectStore()),
                segment_ms=2 * HOUR)
            state = ServerState(engine, ServerConfig())
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                r = await client.post("/write", json={"samples": [
                    {"name": "cpu", "labels": {"host": "h1"},
                     "timestamp": T0 + i, "value": float(i)}
                    for i in range(20)]})
                assert r.status == 200
                assert r.headers.get("X-Trace-Id")
                r = await client.post("/query", json={
                    "metric": "cpu", "start": T0, "end": T0 + 1000})
                assert r.status == 200
                tid = r.headers["X-Trace-Id"]
                assert "total=" in r.headers["X-Trace-Summary"]

                r = await client.get(f"/debug/traces/{tid}")
                assert r.status == 200
                d = await r.json()
                assert d["trace_id"] == tid and d["status"] == "ok"
                tree = d["tree"]
                assert tree["name"] == "/query"
                names = {c["name"] for c in tree["children"]}
                assert "admission_wait" in names
                assert {"resolve", "scan"} <= names

                r = await client.get("/debug/traces")
                listed = (await r.json())["traces"]
                assert any(t["trace_id"] == tid for t in listed)
                r = await client.get("/debug/traces/deadbeef")
                assert r.status == 404
                m = await (await client.get("/metrics")).text()
                assert "traces_recorded_total" in m
            finally:
                await client.close()
                await engine.close()

        run(go())

    def test_unsampled_request_still_gets_a_trace_id(self):
        async def go():
            engine = await MetricEngine.open(
                "tr_db0", MemoryObjectStore(), segment_ms=2 * HOUR)
            cfg = ServerConfig()
            cfg.trace.sample_rate = 0.0
            state = ServerState(engine, cfg)
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                r = await client.post("/query", json={
                    "metric": "cpu", "start": T0, "end": T0 + 1000})
                assert r.status == 200
                tid = r.headers.get("X-Trace-Id")
                assert tid
                # unsampled: never recorded
                assert (await client.get(
                    f"/debug/traces/{tid}")).status == 404
            finally:
                await client.close()
                await engine.close()

        run(go())

    def test_slow_query_log_fires_on_deadline_exceeded(self):
        """A query killed by its deadline (504) is slow BY DEFINITION:
        the slow log fires even with a sky-high threshold."""

        class SlowEngine:
            async def query(self, metric, filters, rng, field="value"):
                await asyncio.sleep(5.0)

        async def go():
            cfg = ServerConfig()
            cfg.admission.query_timeout = ReadableDuration.parse("100ms")
            cfg.trace.slow_threshold = ReadableDuration.parse("1h")
            state = ServerState(SlowEngine(), cfg)
            slow0 = tracing._SLOW_QUERIES.value
            records = []
            handler = logging.Handler()
            handler.emit = records.append
            tracing.slow_logger.addHandler(handler)
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                r = await client.post("/query", json={
                    "metric": "cpu", "start": T0, "end": T0 + 1000})
                assert r.status == 504
                tid = r.headers["X-Trace-Id"]
            finally:
                await client.close()
                tracing.slow_logger.removeHandler(handler)
            assert tracing._SLOW_QUERIES.value == slow0 + 1
            assert records and tid in records[0].getMessage()
            d = recorder.get(tid)
            assert d["status"] == "timeout" and d["slow"] is True

        run(go())


# ---------------------------------------------------------------------------
# Distributed stitching across real HTTP regions (the DCN plane)


class TestDistributedTrace:
    def test_two_region_gather_yields_one_stitched_trace(self):
        async def go():
            import aiohttp

            from horaedb_tpu.cluster import Cluster, RemoteRegion
            from horaedb_tpu.common.time_ext import now_ms

            engine7 = await MetricEngine.open(
                "tr_r7", MemoryObjectStore(), segment_ms=2 * HOUR)
            engine9 = await MetricEngine.open(
                "tr_r9", MemoryObjectStore(), segment_ms=2 * HOUR)
            server7 = TestServer(build_app(
                ServerState(engine7, ServerConfig())))
            server9 = TestServer(build_app(
                ServerState(engine9, ServerConfig())))
            await server7.start_server()
            await server9.start_server()
            session = aiohttp.ClientSession()
            c = await Cluster.open("tr_cluster", MemoryObjectStore(),
                                   num_regions=1, segment_ms=2 * HOUR)
            coord_state = ServerState(c, ServerConfig())
            client = TestClient(TestServer(build_app(coord_state)))
            await client.start_server()
            try:
                c.routing.split(0, 1 << 62, 7, now_ms(), 30 * 24 * HOUR)
                c.routing.split(7, 3 << 61, 9, now_ms(), 30 * 24 * HOUR)
                c.add_remote_region(
                    7, RemoteRegion(str(server7.make_url("/")), session))
                c.add_remote_region(
                    9, RemoteRegion(str(server9.make_url("/")), session))
                await c.stop_health_monitor()
                await c.write([sample("cpu", [("host", f"h{i:02d}")],
                                      T0 + 1000, float(i))
                               for i in range(48)])

                r = await client.post("/query", json={
                    "metric": "cpu", "filters": {},
                    "start": T0, "end": T0 + HOUR})
                assert r.status == 200
                tid = r.headers["X-Trace-Id"]
                data = await r.json()
                assert len(data["values"]) == 48  # all regions answered

                r = await client.get(f"/debug/traces/{tid}")
                assert r.status == 200
                d = recorder.get(tid)
                spans = d["spans"]
                # ONE trace: the coordinator's root + both regions'
                # imported span trees under their region_call/rpc spans
                regions = {s["fields"].get("region") for s in spans
                           if s["name"] == "region_call"}
                assert {7, 9} <= regions
                peer_roots = [s for s in spans
                              if s["name"] == "/query_arrow"]
                assert len(peer_roots) == 2
                rpc_ids = {s["span_id"]: s for s in spans
                           if s["name"] == "rpc"}
                for root in peer_roots:
                    assert root["parent_id"] in rpc_ids
                # the peers' engine spans came across too
                assert sum(1 for s in spans if s["name"] == "resolve") >= 2
            finally:
                await client.close()
                await c.close()
                await session.close()
                await server7.close()
                await server9.close()
                await engine7.close()
                await engine9.close()

        run(go())


class TestTraceConfig:
    def test_trace_section_loads_from_toml(self, tmp_path):
        p = tmp_path / "cfg.toml"
        p.write_text("""
port = 5001
[trace]
enabled = true
ring_size = 32
slow_threshold = "250ms"
sample_rate = 0.5
""")
        cfg = load_config(str(p))
        assert cfg.trace.ring_size == 32
        assert cfg.trace.slow_threshold.seconds == 0.25
        assert cfg.trace.sample_rate == 0.5

    def test_bad_sample_rate_rejected(self, tmp_path):
        from horaedb_tpu.common import Error

        p = tmp_path / "cfg.toml"
        p.write_text("[trace]\nsample_rate = 1.5\n")
        with pytest.raises(Error):
            load_config(str(p))
