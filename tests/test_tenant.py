"""Multi-tenant isolation tests (docs/robustness.md, tenant isolation
failure domains): weighted-fair admission, per-tenant quotas, fast-fail
at ingress, load-aware Retry-After, reload hygiene, hot-shard
surfacing, and the seeded multi-tenant chaos harness
(TENANT_SEED / TENANT_SCHEDULES, wired into `make chaos`)."""

import asyncio
import os
import pathlib
import random
import sys
import time

import pyarrow as pa
import pytest
from aiohttp.test_utils import TestClient, TestServer

from horaedb_tpu.common import Error, ReadableDuration
from horaedb_tpu.common.tenant import (
    QuotaExceeded,
    TenantRegistry,
    TokenBucket,
    charge_scan_bytes,
    current_tenant,
    tenant_scope,
    tenants_from_dict,
)
from horaedb_tpu.common.deadline import checkpoint
from horaedb_tpu.metric_engine import Label, MetricEngine, Sample
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.server.config import (AdmissionConfig, ServerConfig,
                                       load_config)
from horaedb_tpu.server.main import (FairAdmissionController,
                                     ServerState, _ServiceRate,
                                     _load_aware_retry_after, build_app)
from horaedb_tpu.storage.types import TimeRange
from horaedb_tpu.utils import registry
from horaedb_tpu.wal.config import WalConfig

T0 = 1_700_000_000_000
HOUR = 3_600_000
ROOT = pathlib.Path(__file__).resolve().parents[1]

TENANT_SEED = int(os.environ.get("TENANT_SEED", "1337"))
TENANT_SCHEDULES = int(os.environ.get("TENANT_SCHEDULES", "20"))


def run(coro):
    return asyncio.run(coro)


def _empty_table():
    return pa.table({"tsid": pa.array([], pa.uint64()),
                     "timestamp": pa.array([], pa.int64()),
                     "value": pa.array([], pa.float64())})


def metric_value(text: str, name: str):
    total = None
    for line in text.splitlines():
        if line.startswith(name) and len(line) > len(name) \
                and line[len(name)] in ' {,}':
            total = (total or 0.0) + float(line.split()[-1])
    return total


class DuckEngine:
    """Duck-typed engine: queries sleep per-metric delays, writes are
    counted — drives admission/fairness tests without storage."""

    def __init__(self, delays=None, write_delay_s: float = 0.0):
        self.delays = delays or {}
        self.write_delay_s = write_delay_s
        self.tables = {}
        self.queries = []
        self.writes = 0

    async def query(self, metric, filters, rng, field="value"):
        self.queries.append(metric)
        delay = self.delays.get(metric, 0.0)
        if delay:
            await asyncio.sleep(delay)
        return _empty_table()

    async def write(self, samples):
        self.writes += len(samples)
        if self.write_delay_s:
            await asyncio.sleep(self.write_delay_s)

    async def stats(self):
        return {"rows": 0, "bytes": 0}

    async def close(self):
        pass


def _cfg(tenants=None, **adm) -> ServerConfig:
    cfg = ServerConfig()
    if adm:
        cfg.admission = AdmissionConfig(**adm)
    if tenants is not None:
        cfg.tenants = tenants_from_dict(tenants)
    return cfg


async def _client(engine, cfg):
    state = ServerState(engine, cfg)
    client = TestClient(TestServer(build_app(state)))
    await client.start_server()
    return client, state


QUERY = {"metric": "m", "filters": {}, "start": T0, "end": T0 + HOUR}


# ---------------------------------------------------------------------------
# token bucket


class TestTokenBucket:
    def test_refill_admit_and_deficit(self):
        clock = [0.0]
        b = TokenBucket(100.0, 200.0, clock=lambda: clock[0])
        assert b.admit(150)           # burst covers it
        assert not b.admit(100)       # only 50 left
        assert b.admit(50)
        assert b.level == 0
        clock[0] += 1.0               # +100 tokens
        assert b.admit(100)
        # charge() goes into deficit; delay_until reports the refill eta
        b.charge(250)
        assert b.in_deficit
        assert 2.4 < b.delay_until(0.0) <= 2.51
        clock[0] += 3.0
        assert not b.in_deficit

    def test_oversize_cost_admitted_only_on_full_bucket(self):
        clock = [0.0]
        b = TokenBucket(10.0, 50.0, clock=lambda: clock[0])
        assert b.admit(500)           # full bucket: oversize passes...
        assert b.level == -450        # ...into deficit
        assert not b.admit(500)       # and not again until refilled
        clock[0] += 50.0              # refill back to burst
        assert b.admit(500)


# ---------------------------------------------------------------------------
# [tenants] config


class TestTenantsConfig:
    def test_inheritance_and_overrides(self):
        cfg = tenants_from_dict({
            "enabled": True,
            "default": {"weight": 2.0, "max_queued": 16,
                        "scan_bytes_per_s": "1MiB"},
            "tenant": {"gold": {"weight": 8.0},
                       "capped": {"max_in_flight": 2}}})
        assert cfg.enabled
        gold = cfg.tenants["gold"]
        assert gold.weight == 8.0
        assert gold.max_queued == 16          # inherited
        assert gold.scan_bytes_per_s.bytes == 1 << 20
        assert cfg.tenants["capped"].weight == 2.0
        assert cfg.tenants["capped"].max_in_flight == 2

    def test_validation_errors(self):
        with pytest.raises(Error, match="unknown \\[tenants\\] keys"):
            tenants_from_dict({"banana": 1})
        with pytest.raises(Error, match="weight must be a positive"):
            tenants_from_dict({"default": {"weight": 0}})
        with pytest.raises(Error, match="bad tenant name"):
            tenants_from_dict({"tenant": {"bad name!": {}}})
        with pytest.raises(Error, match="tenants.default"):
            tenants_from_dict({"tenant": {"default": {}}})
        with pytest.raises(Error, match="expects a size"):
            tenants_from_dict({"default": {"wal_bytes_per_s": 1.5}})

    def test_toml_roundtrip(self, tmp_path):
        p = tmp_path / "cfg.toml"
        p.write_text("""
port = 5001

[tenants]
enabled = true
max_auto_tenants = 8

[tenants.default]
weight = 1.0
max_queued = 32

[tenants.tenant.dashboards]
weight = 4.0
scan_bytes_per_s = "64MiB"

[tenants.tenant.batch]
weight = 0.5
wal_bytes_per_s = "1MiB"
wal_burst_bytes = "4MiB"
""")
        cfg = load_config(str(p))
        assert cfg.tenants.enabled
        assert cfg.tenants.max_auto_tenants == 8
        assert cfg.tenants.tenants["dashboards"].weight == 4.0
        assert (cfg.tenants.tenants["batch"].wal_burst_bytes.bytes
                == 4 << 20)
        # disabled by default: the pre-tenant server shape
        assert not ServerConfig().tenants.enabled

    def test_registry_resolution_and_auto_cap(self):
        reg = TenantRegistry(tenants_from_dict({
            "enabled": True, "auto_tenants": True, "max_auto_tenants": 2,
            "tenant": {"a": {"weight": 2.0}}}))
        assert reg.resolve(None).name == "default"
        assert reg.resolve("a").limits.weight == 2.0
        assert reg.resolve("x1").auto and reg.resolve("x2").auto
        # beyond the cap, unknown names share the default tenant
        assert reg.resolve("x3").name == "default"
        with pytest.raises(Error, match="bad X-Tenant"):
            reg.resolve("no spaces allowed")
        # auto_tenants OFF (the default — X-Tenant is unauthenticated,
        # so a fresh name must not mean a fresh fair share): unknown
        # names all share the default tenant
        reg = TenantRegistry(tenants_from_dict({"enabled": True}))
        assert reg.resolve("rotating-name-1").name == "default"


# ---------------------------------------------------------------------------
# weighted-fair admission (controller level)


class TestFairAdmission:
    def _reg(self, **tenants):
        return TenantRegistry(tenants_from_dict(
            {"enabled": True, "tenant": tenants}))

    def test_stride_shares_under_contention(self):
        """One slot, both tenants backlogged: grants follow the 3:1
        weights regardless of how deep the abuser's queue is."""
        async def go():
            fair = FairAdmissionController(
                AdmissionConfig(max_concurrent_queries=1))
            reg = self._reg(a={"weight": 3.0, "max_queued": 64},
                            b={"weight": 1.0, "max_queued": 64})
            a, b = reg.resolve("a"), reg.resolve("b")
            assert await fair.acquire(a, None) == "ok"  # hold the slot
            grants = []

            async def waiter(t):
                assert await fair.acquire(t, 10) == "ok"
                grants.append(t.name)

            tasks = [asyncio.create_task(waiter(b)) for _ in range(4)]
            tasks += [asyncio.create_task(waiter(a)) for _ in range(12)]
            await asyncio.sleep(0)  # all enqueue
            order = []
            current = a
            for _ in range(16):
                fair.release(current)        # frees the slot, grants next
                await asyncio.sleep(0.001)   # let the waiter run
                assert grants, "a queued waiter should have been granted"
                current = reg.resolve(grants[-1])
                order.append(grants[-1])
            fair.release(current)
            for t in tasks:
                await t
            # stride: b's grants are interleaved at its weighted share
            # (roughly every 3rd-4th slot) despite a queueing 3x
            # deeper — never starved, never batched at the end
            assert order.count("b") == 4
            pos = [i for i, n in enumerate(order) if n == "b"]
            assert pos[-1] <= 11, order   # all served in the first 12
            gaps = [b2 - b1 for b1, b2 in zip(pos, pos[1:])]
            assert all(2 <= g <= 6 for g in gaps), order
            assert fair.active == 0 and fair.queued() == 0

        run(go())

    def test_max_in_flight_cap_and_scoped_shed(self):
        async def go():
            fair = FairAdmissionController(
                AdmissionConfig(max_concurrent_queries=8))
            reg = self._reg(capped={"max_in_flight": 2, "max_queued": 1})
            c = reg.resolve("capped")
            assert await fair.acquire(c, None) == "ok"
            assert await fair.acquire(c, None) == "ok"
            # at its cap: queues even though global slots are free
            t = asyncio.create_task(fair.acquire(c, 5))
            await asyncio.sleep(0)
            assert fair.queued(c) == 1
            # its queue bound: shed, scoped to this tenant
            assert await fair.acquire(c, 0.01) == "shed"
            # another tenant is untouched by the capped one's backlog
            other = reg.resolve("other")
            assert await fair.acquire(other, None) == "ok"
            fair.release(c)
            assert await t == "ok"
            fair.release(c)
            fair.release(c)
            fair.release(other)

        run(go())

    def test_global_max_queued_bounds_total(self):
        """[admission] max_queued stays the TOTAL queue bound in fair
        mode — per-tenant queues must not multiply the operator's
        queued-memory envelope."""
        async def go():
            fair = FairAdmissionController(AdmissionConfig(
                max_concurrent_queries=1, max_queued=2))
            reg = self._reg(a={"max_queued": 64}, b={"max_queued": 64})
            a, b = reg.resolve("a"), reg.resolve("b")
            assert await fair.acquire(a, None) == "ok"
            t1 = asyncio.create_task(fair.acquire(a, 5))
            t2 = asyncio.create_task(fair.acquire(b, 5))
            await asyncio.sleep(0)
            assert fair.queued() == 2
            # per-tenant bounds (64) have room, but the global total
            # (2) is reached: shed
            assert await fair.acquire(b, 5) == "shed"
            fair.release(a)       # stride grants b first (lowest pass)
            assert await t2 == "ok"
            fair.release(b)
            assert await t1 == "ok"
            fair.release(a)
            assert fair.active == 0 and fair.queued() == 0

        run(go())

    def test_queue_timeout_returns_timeout(self):
        async def go():
            fair = FairAdmissionController(
                AdmissionConfig(max_concurrent_queries=1))
            reg = self._reg()
            t = reg.resolve("t")
            assert await fair.acquire(t, None) == "ok"
            assert await fair.acquire(t, 0.02) == "timeout"
            fair.release(t)
            assert fair.active == 0

        run(go())


# ---------------------------------------------------------------------------
# load-aware Retry-After


class TestRetryAfter:
    def test_service_rate_window(self):
        clock = [0.0]
        r = _ServiceRate(clock=lambda: clock[0])
        assert r.per_second() is None
        for _ in range(10):
            clock[0] += 0.5
            r.record()
        assert r.per_second() == pytest.approx(10 / 4.5)
        clock[0] += 100.0  # everything ages out of the window
        assert r.per_second() is None

    def test_eta_floor_and_cap(self):
        cfg = AdmissionConfig(
            retry_after=ReadableDuration.parse("1s"),
            max_retry_after=ReadableDuration.parse("30s"))
        assert _load_aware_retry_after(cfg, 100, None) == "1"   # no data
        assert _load_aware_retry_after(cfg, 0, 10.0) == "1"     # floor
        assert _load_aware_retry_after(cfg, 19, 2.0) == "10"    # eta
        assert _load_aware_retry_after(cfg, 1000, 0.5) == "30"  # cap

    def test_http_responses_carry_retry_after(self):
        async def go():
            client, _ = await _client(
                DuckEngine(delays={"m": 0.5}),
                _cfg(tenants={"enabled": True,
                              "default": {"max_queued": 1}},
                     max_concurrent_queries=1,
                     queue_timeout=ReadableDuration.parse("50ms")))
            try:
                resps = await asyncio.gather(*(
                    client.post("/query", json=QUERY) for _ in range(4)))
                statuses = sorted(r.status for r in resps)
                assert statuses == [200, 429, 429, 503]
                for r in resps:
                    if r.status in (429, 503):
                        assert int(r.headers["Retry-After"]) >= 1
                    if r.status == 429:
                        assert "tenant" in (await r.json())["error"]
            finally:
                await client.close()

        run(go())


# ---------------------------------------------------------------------------
# tenant middleware end to end


class TestTenantMiddleware:
    def test_isolation_between_tenants(self):
        """An abuser saturating its own queue gets scoped 429s while a
        compliant tenant's queries are admitted immediately."""
        async def go():
            engine = DuckEngine(delays={"heavy": 0.4, "light": 0.0})
            client, _ = await _client(engine, _cfg(
                tenants={"enabled": True,
                         "tenant": {"abuser": {"max_in_flight": 1,
                                               "max_queued": 1},
                                    "dash": {"weight": 4.0}}},
                max_concurrent_queries=4))
            try:
                # the registry is process-global (another test may
                # also shed an "abuser" tenant): assert deltas
                m0 = await (await client.get("/metrics")).text()
                shed0 = metric_value(
                    m0, 'server_queries_shed_total{tenant="abuser"') or 0
                heavy = dict(QUERY, metric="heavy")
                abuse = [asyncio.create_task(client.post(
                    "/query", json=heavy,
                    headers={"X-Tenant": "abuser"})) for _ in range(6)]
                await asyncio.sleep(0.05)
                t0 = time.monotonic()
                r = await client.post("/query",
                                      json=dict(QUERY, metric="light"),
                                      headers={"X-Tenant": "dash"})
                dash_latency = time.monotonic() - t0
                assert r.status == 200
                assert dash_latency < 0.3  # never behind the abuser
                statuses = sorted(
                    (await asyncio.gather(*abuse)), key=lambda r: r.status)
                codes = [r.status for r in statuses]
                # 1 in flight + 1 queued; the other 4 shed at the
                # abuser's own queue bound
                assert codes.count(429) == 4 and codes.count(200) == 2
                m = await (await client.get("/metrics")).text()
                assert (metric_value(
                    m, 'server_queries_shed_total{tenant="abuser"')
                    - shed0) == 4
                assert metric_value(
                    m, 'server_queries_shed_total{tenant="dash"') is None
            finally:
                await client.close()

        run(go())

    def test_default_tenant_and_bad_name(self):
        async def go():
            client, state = await _client(
                DuckEngine(), _cfg(tenants={"enabled": True}))
            try:
                r = await client.post("/query", json=QUERY)
                assert r.status == 200
                r = await client.post("/query", json=QUERY,
                                      headers={"X-Tenant": "bad name"})
                assert r.status == 400
                stats = await (await client.get("/stats")).json()
                assert "default" in stats["tenants"]
                assert stats["tenants"]["default"]["queries"] >= 1
            finally:
                await client.close()

        run(go())

    def test_disabled_reproduces_pretenant_behavior(self):
        """[tenants] absent: no tenant machinery binds — no tenants
        stats section, bare (unlabeled) shed counters, X-Tenant
        ignored."""
        async def go():
            engine = DuckEngine(delays={"m": 0.3})
            client, state = await _client(engine, _cfg(
                max_concurrent_queries=1, max_queued=1,
                queue_timeout=ReadableDuration.parse("50ms")))
            try:
                assert state.tenants is None
                assert state.fair_admission is None
                shed0 = registry.counter(
                    "server_queries_shed_total").value
                resps = await asyncio.gather(*(
                    client.post("/query", json=QUERY,
                                headers={"X-Tenant": "ignored"})
                    for _ in range(4)))
                assert sorted(r.status for r in resps) == \
                    [200, 429, 429, 503]
                # sheds land on the BARE series (no tenant label)
                assert registry.counter(
                    "server_queries_shed_total").value - shed0 == 2
                stats = await (await client.get("/stats")).json()
                assert "tenants" not in stats
            finally:
                await client.close()

        run(go())

    def test_trace_root_carries_tenant(self):
        async def go():
            client, _ = await _client(
                DuckEngine(), _cfg(tenants={"enabled": True,
                                            "auto_tenants": True}))
            try:
                r = await client.post("/query", json=QUERY,
                                      headers={"X-Tenant": "acme"})
                trace_id = r.headers["X-Trace-Id"]
                tree = await (await client.get(
                    f"/debug/traces/{trace_id}")).json()
                assert tree["tree"]["fields"]["tenant"] == "acme"
            finally:
                await client.close()

        run(go())

    def test_admin_tenants_reload_removes_metrics(self):
        """Satellite: a tenant dropped at reload stops rendering on
        /metrics — no phantom series forever."""
        async def go():
            client, _ = await _client(DuckEngine(), _cfg(
                tenants={"enabled": True,
                         "tenant": {"keep": {}, "gone": {}}}))
            try:
                for name in ("keep", "gone"):
                    r = await client.post("/query", json=QUERY,
                                          headers={"X-Tenant": name})
                    assert r.status == 200
                m = await (await client.get("/metrics")).text()
                assert 'tenant="gone"' in m and 'tenant="keep"' in m
                r = await client.post(
                    "/admin/tenants", json={"tenant": {"keep": {}}})
                assert r.status == 200
                body = await r.json()
                assert body["removed"] == ["gone"]
                m = await (await client.get("/metrics")).text()
                assert 'tenant="gone"' not in m
                assert 'tenant="keep"' in m
                # GET surface + validation
                r = await client.get("/admin/tenants")
                assert "keep" in (await r.json())["tenants"]
                r = await client.post("/admin/tenants",
                                      json={"enabled": False})
                assert r.status == 400
            finally:
                await client.close()

        run(go())


# ---------------------------------------------------------------------------
# fast-fail at ingress (expired deadlines never consume slots)


class TestFastFail:
    def test_dead_on_arrival_deadline_is_504_before_any_work(self):
        """X-Deadline-Ms <= 0 declares the budget already spent: 504
        at ingress — no admission slot, no queue entry, and for writes
        no WAL frame/fsync."""
        async def go():
            engine = DuckEngine(delays={"m": 0.1})
            client, _ = await _client(engine, _cfg(
                tenants={"enabled": True, "tenant": {"doa": {}}}))
            try:
                r = await client.post("/query", json=QUERY,
                                      headers={"X-Tenant": "doa",
                                               "X-Deadline-Ms": "0"})
                assert r.status == 504
                assert engine.queries == []
                body = {"samples": [{"name": "w", "labels": {},
                                     "timestamp": T0, "value": 1.0}]}
                r = await client.post("/write", json=body,
                                      headers={"X-Tenant": "doa",
                                               "X-Deadline-Ms": "0"})
                assert r.status == 504
                assert engine.writes == 0
                m = await (await client.get("/metrics")).text()
                assert metric_value(
                    m, 'server_requests_timed_out_total{tenant="doa"') == 2
            finally:
                await client.close()

        run(go())

    def test_expired_while_queued_is_504_not_503(self):
        async def go():
            engine = DuckEngine(delays={"m": 0.6})
            client, _ = await _client(engine, _cfg(
                max_concurrent_queries=1,
                queue_timeout=ReadableDuration.parse("5s")))
            try:
                t504 = registry.counter(
                    "server_requests_timed_out_total").value
                holder = asyncio.create_task(
                    client.post("/query", json=QUERY))
                await asyncio.sleep(0.05)
                # deadline (100ms) expires while queued behind the
                # 600ms holder: 504, and the slot was never consumed
                r = await client.post("/query", json=QUERY,
                                      headers={"X-Deadline-Ms": "100"})
                assert r.status == 504
                assert (await holder).status == 200
                assert len(engine.queries) == 1  # dead request never ran
                assert registry.counter(
                    "server_requests_timed_out_total").value > t504
                # and it is a 504, not a queue-timeout 503 — the 503
                # counter did not move for it
            finally:
                await client.close()

        run(go())

    def test_per_tenant_deadline_cap(self):
        """An operator-capped tenant cannot hold server time past its
        envelope (max_query_time), whatever the client asks for;
        uncapped tenants keep the [admission] default."""
        async def go():
            engine = DuckEngine(delays={"m": 0.6})
            client, _ = await _client(engine, _cfg(
                tenants={"enabled": True,
                         "tenant": {"batch":
                                    {"max_query_time": "100ms"}}}))
            try:
                t0 = time.monotonic()
                r = await client.post("/query", json=QUERY,
                                      headers={"X-Tenant": "batch"})
                assert r.status == 504
                assert time.monotonic() - t0 < 0.5
                # the cap also wins over a LARGER client ask
                r = await client.post("/query", json=QUERY,
                                      headers={"X-Tenant": "batch",
                                               "X-Deadline-Ms": "5000"})
                assert r.status == 504
                r = await client.post("/query", json=QUERY,
                                      headers={"X-Tenant": "gold"})
                assert r.status == 200
            finally:
                await client.close()

        run(go())

    def test_fair_mode_expired_while_queued(self):
        async def go():
            engine = DuckEngine(delays={"m": 0.6})
            client, _ = await _client(engine, _cfg(
                tenants={"enabled": True, "tenant": {"t": {}}},
                max_concurrent_queries=1,
                queue_timeout=ReadableDuration.parse("5s")))
            try:
                holder = asyncio.create_task(
                    client.post("/query", json=QUERY))
                await asyncio.sleep(0.05)
                r = await client.post("/query", json=QUERY,
                                      headers={"X-Tenant": "t",
                                               "X-Deadline-Ms": "100"})
                assert r.status == 504
                assert (await holder).status == 200
                assert len(engine.queries) == 1
                m = await (await client.get("/metrics")).text()
                assert metric_value(
                    m, 'server_requests_timed_out_total{tenant="t"') == 1
            finally:
                await client.close()

        run(go())


# ---------------------------------------------------------------------------
# resource quotas (scan bytes + WAL rate)


class TestQuotas:
    def test_scan_byte_budget_breach_raises_at_checkpoint(self):
        reg = TenantRegistry(tenants_from_dict({
            "enabled": True,
            "tenant": {"scanner": {"scan_bytes_per_s": "1kb",
                                   "scan_burst_bytes": "2kb"}}}))
        t = reg.resolve("scanner")
        with tenant_scope(t):
            assert current_tenant() is t
            charge_scan_bytes(1024)
            checkpoint()                      # within burst: fine
            charge_scan_bytes(10240)          # deep into deficit
            with pytest.raises(QuotaExceeded) as ei:
                checkpoint()
            assert ei.value.resource == "scan_bytes"
            assert ei.value.retry_after_s > 1.0
        checkpoint()  # outside the scope: no ambient tenant, no raise

    def test_engine_scan_quota_end_to_end(self):
        """A real engine scan charges the ambient tenant and a
        breached budget 429s the query at a cooperative checkpoint."""
        async def go():
            engine = await MetricEngine.open(
                "m", MemoryObjectStore(), segment_ms=2 * HOUR)
            reg = TenantRegistry(tenants_from_dict({
                "enabled": True,
                "tenant": {"abuser": {"scan_bytes_per_s": "1b",
                                      "scan_burst_bytes": "64b"}}}))
            try:
                samples = [
                    Sample(name="cpu",
                           labels=[Label("host", f"h{i % 50:02d}")],
                           timestamp=T0 + i * 1000, value=float(i))
                    for i in range(5000)]
                await engine.write(samples)  # ungoverned: no scope
                rng_ = TimeRange.new(T0, T0 + HOUR)
                abuser = reg.resolve("abuser")
                with tenant_scope(abuser):
                    # the first scan may complete (bytes are charged
                    # post-read) but leaves the bucket in deficit...
                    try:
                        await engine.query("cpu", [], rng_)
                    except QuotaExceeded:
                        pass
                    # ...so the next one dies at its first checkpoint
                    with pytest.raises(QuotaExceeded):
                        await engine.query("cpu", [], rng_)
                # the compliant (unlimited) default tenant still scans
                with tenant_scope(reg.resolve(None)):
                    tbl = await engine.query("cpu", [], rng_)
                    # the hour-long range covers the first 3600 of the
                    # 5000 one-per-second samples
                    assert tbl.num_rows == 3600
            finally:
                await engine.close()

        run(go())

    def test_wal_rate_quota_maps_to_429(self, tmp_path):
        async def go():
            engine = await MetricEngine.open(
                "m", MemoryObjectStore(), segment_ms=2 * HOUR,
                wal_config=WalConfig(enabled=True, dir=str(tmp_path)))
            client, _ = await _client(engine, _cfg(
                tenants={"enabled": True,
                         "tenant": {"flood": {"wal_bytes_per_s": "64b",
                                              "wal_burst_bytes":
                                                  "16kb"}}}))
            try:
                body = {"samples": [
                    {"name": "cpu", "labels": {"host": f"h{i}"},
                     "timestamp": T0 + i, "value": 1.0}
                    for i in range(20)]}
                # the burst admits the first batch(es) — one engine
                # write is several WAL appends (data + index tables) —
                # then the 64 B/s rate shuts the flood down
                r = await client.post("/write", json=body,
                                      headers={"X-Tenant": "flood"})
                assert r.status == 200
                rejected = None
                for _ in range(50):
                    r = await client.post("/write", json=body,
                                          headers={"X-Tenant": "flood"})
                    if r.status == 429:
                        rejected = r
                        break
                    assert r.status == 200
                assert rejected is not None, "flood was never limited"
                out = await rejected.json()
                assert out["quota"] == "wal_rate"
                assert out["tenant"] == "flood"
                assert int(rejected.headers["Retry-After"]) >= 1
                # another tenant's writes are not rate-limited
                r = await client.post("/write", json=body,
                                      headers={"X-Tenant": "polite"})
                assert r.status == 200
                m = await (await client.get("/metrics")).text()
                assert metric_value(
                    m, 'tenant_quota_rejections_total{'
                       'resource="wal_rate",tenant="flood"') == 1
            finally:
                await client.close()
                await engine.close()

        run(go())


class TestFlushBarrierScoping:
    def test_flushing_overlaps_is_range_scoped(self):
        """The aggregate pre-flush barrier waits only for in-flight
        flushes whose rows overlap the query's range — a dashboard
        aggregate must not stall behind another tenant's disjoint
        bulk-ingest flush (the flush-lock coupling the config-15
        harness exposed)."""
        from horaedb_tpu.wal.ingest import IngestStorage

        class Mt:
            def __init__(self, rng):
                self.time_range = rng

        ing = IngestStorage.__new__(IngestStorage)
        day = 86_400_000
        ing.__dict__["_flushing"] = {
            0: [Mt(TimeRange.new(T0 - day, T0 - day + HOUR))]}
        # disjoint query range: no barrier
        assert not ing._flushing_overlaps(TimeRange.new(T0, T0 + HOUR))
        # overlapping range / whole-table flush: barrier
        assert ing._flushing_overlaps(
            TimeRange.new(T0 - day, T0 - day + 1))
        assert ing._flushing_overlaps(None)
        # an unanswerable memtable range is conservatively overlapping
        ing.__dict__["_flushing"] = {0: [Mt(None)]}
        assert ing._flushing_overlaps(TimeRange.new(T0, T0 + HOUR))


# ---------------------------------------------------------------------------
# hot-shard surfacing


class TestRebalanceSurface:
    def test_survey_load_plans_split_and_backlog(self):
        async def go():
            from horaedb_tpu.cluster import Cluster
            from horaedb_tpu.cluster.router import (PartitionRule,
                                                    RoutingTable)

            c = await Cluster.open("skew", MemoryObjectStore(),
                                   num_regions=3, segment_ms=2 * HOUR)
            try:
                c.routing = RoutingTable(rules=[
                    PartitionRule(start_key=0, end_key=(1 << 64) - 1,
                                  region_id=1)])
                await c.write([
                    Sample(name="mem",
                           labels=[Label("host", f"h{i:03d}")],
                           timestamp=T0 + (i % 60) * 60_000,
                           value=float(i))
                    for i in range(600)])
                out = await c.survey_load(skew_ratio=1.5)
                assert out["plan"] and out["plan"][0]["region"] == 1
                assert "split_region(1" in out["plan"][0][
                    "split_proposal"]
                assert out["plan"][0]["new_region_id"] not in c.regions
                # cached for the health monitor's /debug/tasks backlog
                backlog = c._health_backlog()
                assert backlog["rebalance"]["plan"] == out["plan"]
            finally:
                await c.close()

        run(go())

    def test_admin_rebalance_endpoint(self):
        async def go():
            # single-engine server: 501
            client, _ = await _client(DuckEngine(), _cfg())
            try:
                r = await client.post("/admin/rebalance")
                assert r.status == 501
            finally:
                await client.close()

            class ClusterDuck(DuckEngine):
                async def survey_load(self, skew_ratio=2.0):
                    return {"at_ms": 1, "skew_ratio": skew_ratio,
                            "region_stats": {}, "plan": []}

            client, _ = await _client(ClusterDuck(), _cfg())
            try:
                r = await client.post("/admin/rebalance?skew_ratio=3.5")
                assert r.status == 200
                assert (await r.json())["skew_ratio"] == 3.5
                r = await client.post("/admin/rebalance?skew_ratio=0.5")
                assert r.status == 400
            finally:
                await client.close()

        run(go())


# ---------------------------------------------------------------------------
# lint rule: no handler outside the middleware chain


class TestLintRule:
    def _lint(self, tmp_path, body: str):
        sys.path.insert(0, str(ROOT / "tools"))
        try:
            import lint as lint_mod
        finally:
            sys.path.pop(0)
        p = tmp_path / "horaedb_tpu" / "server" / "main.py"
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(body)
        return lint_mod.lint_file(p)

    HEADER = ('_QUERY_ENDPOINTS = frozenset({"/query"})\n'
              '_WRITE_ENDPOINTS = frozenset({"/write"})\n'
              '_UNGOVERNED_ENDPOINTS = frozenset({"/metrics"})\n\n\n')

    def test_unlisted_route_rejected(self, tmp_path):
        problems = self._lint(tmp_path, self.HEADER + (
            "def build(routes):\n"
            '    @routes.post("/sneaky")\n'
            "    async def sneaky(req):\n"
            "        return None\n"))
        assert any("outside the admission+tenant middleware chain"
                   in p for p in problems)

    def test_listed_routes_pass_and_sets_required(self, tmp_path):
        assert self._lint(tmp_path, self.HEADER + (
            "def build(routes):\n"
            '    @routes.post("/query")\n'
            "    async def q(req):\n"
            "        return None\n")) == []
        problems = self._lint(
            tmp_path, 'def build(routes):\n'
                      '    @routes.get("/query")\n'
                      '    async def q(req):\n'
                      '        return None\n')
        assert any("endpoint set" in p for p in problems)

    def test_repo_server_passes(self):
        sys.path.insert(0, str(ROOT / "tools"))
        try:
            import lint as lint_mod
        finally:
            sys.path.pop(0)
        problems = lint_mod.lint_file(
            ROOT / "horaedb_tpu" / "server" / "main.py")
        assert problems == []


# ---------------------------------------------------------------------------
# seeded multi-tenant chaos (TENANT_SEED / TENANT_SCHEDULES)


async def _chaos_round(seed: int) -> dict:
    """One seeded open-loop round: an abusive tenant floods slow scans
    and writes while two compliant dashboard tenants issue light
    queries on a schedule.  Returns per-tenant latencies/status counts
    plus the server's per-tenant shed accounting."""
    rng = random.Random(seed)
    engine = DuckEngine(delays={"heavy": 0.05 + rng.random() * 0.05,
                                "light": 0.002},
                        write_delay_s=0.001)
    client, state = await _client(engine, _cfg(
        tenants={"enabled": True,
                 "tenant": {"abuser": {"weight": 1.0, "max_in_flight": 2,
                                       "max_queued": 4},
                            "dash1": {"weight": 4.0},
                            "dash2": {"weight": 4.0}}},
        max_concurrent_queries=2,
        queue_timeout=ReadableDuration.parse("2s"),
        query_timeout=ReadableDuration.parse("10s")))
    lat: dict = {"abuser": [], "dash1": [], "dash2": []}
    codes: dict = {"abuser": {}, "dash1": {}, "dash2": {}}

    async def fire(tenant: str, payload: dict, path: str):
        t0 = time.monotonic()
        r = await client.post(path, json=payload,
                              headers={"X-Tenant": tenant})
        await r.release()
        lat[tenant].append(time.monotonic() - t0)
        codes[tenant][r.status] = codes[tenant].get(r.status, 0) + 1

    try:
        # unmeasured warm-up: one request of each shape, so a fresh
        # process's first-touch costs (aiohttp/json/engine paths,
        # ~1s+ on a cold 2-core box) don't land in round 0's p99
        for tenant, path, payload in (
                ("dash1", "/query", dict(QUERY, metric="light")),
                ("abuser", "/query", dict(QUERY, metric="heavy")),
                ("abuser", "/write", {"samples": [
                    {"name": "w", "labels": {"h": "1"},
                     "timestamp": T0, "value": 1.0}]})):
            r = await client.post(path, json=payload,
                                  headers={"X-Tenant": tenant})
            await r.release()
        # the registry is process-global: diff the per-tenant shed
        # counters against a baseline so rounds don't bleed together
        m0 = await (await client.get("/metrics")).text()
        shed0 = {name: metric_value(
            m0, f'server_queries_shed_total{{tenant="{name}"') or 0
            for name in codes}
        # open-loop schedules: arrivals fire at their appointed times
        # regardless of completions (closed-loop would hide overload)
        tasks = []
        events = []
        heavy = dict(QUERY, metric="heavy")
        light = dict(QUERY, metric="light")
        wbody = {"samples": [{"name": "w", "labels": {"h": "1"},
                              "timestamp": T0, "value": 1.0}]}
        t = 0.0
        for _ in range(30):   # abuser: ~60/s mixed floods
            t += rng.expovariate(60.0)
            events.append((t, "abuser",
                           (heavy, "/query") if rng.random() < 0.7
                           else (wbody, "/write")))
        for dash in ("dash1", "dash2"):
            t = 0.0
            for _ in range(12):  # compliant: steady ~24/s dashboards
                t += rng.expovariate(24.0)
                events.append((t, dash, (light, "/query")))
        events.sort(key=lambda e: e[0])
        start = time.monotonic()
        for at, tenant, (payload, path) in events:
            delay = start + at - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.create_task(
                fire(tenant, payload, path)))
        await asyncio.gather(*tasks)
        m = await (await client.get("/metrics")).text()
        shed = {name: (metric_value(
            m, f'server_queries_shed_total{{tenant="{name}"') or 0)
            - shed0[name] for name in codes}
        return {"lat": lat, "codes": codes, "shed": shed}
    finally:
        await client.close()


def _assert_chaos_invariants(out: dict) -> None:
    for dash in ("dash1", "dash2"):
        ls = sorted(out["lat"][dash])
        p99 = ls[min(len(ls) - 1, int(0.99 * len(ls)))]
        # bounded by the abuser's max_in_flight share of the pool, not
        # by its queue depth: generous CI bound, but far below the
        # multi-second collapse global FIFO admission produces here
        assert p99 < 1.0, f"{dash} p99 {p99:.3f}s under abuse"
        assert out["codes"][dash].get(200, 0) == 12, out["codes"]
    # no starvation: the abuser still completes its fair share
    assert out["codes"]["abuser"].get(200, 0) >= 1, out["codes"]
    # correct per-tenant shed accounting: every abuser 429 (and only
    # abuser ones) landed on its labeled shed counter.  429s can also
    # be quota rejections in other configs; here only admission sheds.
    assert out["shed"]["abuser"] == out["codes"]["abuser"].get(429, 0)
    assert out["shed"]["dash1"] == out["codes"]["dash1"].get(429, 0) == 0
    assert out["shed"]["dash2"] == out["codes"]["dash2"].get(429, 0) == 0


class TestMultiTenantChaos:
    def test_chaos_fast(self):
        """Tier-1 variant: two seeded rounds."""
        for i in range(2):
            out = run(_chaos_round(TENANT_SEED + i))
            _assert_chaos_invariants(out)

    @pytest.mark.slow
    def test_chaos_full(self):
        """`make chaos`: TENANT_SCHEDULES seeded rounds of randomized
        multi-tenant interleavings."""
        for i in range(TENANT_SCHEDULES):
            out = run(_chaos_round(TENANT_SEED + i))
            _assert_chaos_invariants(out)
