"""HTTP server tests (ref: src/server endpoints + our query surface)."""

import asyncio

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from horaedb_tpu.metric_engine import MetricEngine
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.server import main as server_main
from horaedb_tpu.server.config import ServerConfig, load_config
from horaedb_tpu.server.main import ServerState, build_app
from horaedb_tpu.common import Error
from horaedb_tpu.utils import registry

T0 = 1_700_000_000_000
HOUR = 3_600_000


async def make_client():
    engine = await MetricEngine.open("m", MemoryObjectStore(),
                                     segment_ms=2 * HOUR)
    state = ServerState(engine, ServerConfig())
    client = TestClient(TestServer(build_app(state)))
    await client.start_server()
    return client, state, engine


def run(coro):
    return asyncio.run(coro)


@pytest.fixture(params=["loop", "pool"])
def respond_on(request, monkeypatch):
    """A downsample answer written on the event loop's own thread (as
    every answer under _RESPOND_POOL_MIN_CELLS cells is) and on a
    thread of the `sst` pool (as the larger ones are)."""
    monkeypatch.setattr(server_main, "_RESPOND_POOL_MIN_CELLS",
                        2 ** 62 if request.param == "loop" else 0)
    written = registry.family("respond_encode_total")
    before = {w: written.labels(where=w).value for w in ("loop", "pool")}
    yield request.param
    moved = {w: written.labels(where=w).value - before[w] for w in before}
    other = "pool" if request.param == "loop" else "loop"
    assert moved[request.param] >= 1 and moved[other] == 0


class TestEndpoints:
    def test_hello_toggle_compact_metrics(self):
        async def go():
            client, state, engine = await make_client()
            try:
                r = await client.get("/")
                assert r.status == 200 and "horaedb-tpu" in await r.text()
                r = await client.get("/toggle")
                assert "write_enabled=False" in await r.text()
                assert state.write_enabled is False
                r = await client.get("/compact")
                assert r.status == 200
                r = await client.get("/metrics")
                assert r.status == 200
                body = await r.text()
                # per-plan-stage attribution is exported (VERDICT r2 #9)
                # as ONE labeled family (docs/observability.md)
                assert "scan_stage_seconds" in body
                for stage in ("parquet_read", "encode_merge",
                              "device_aggregate", "combine"):
                    assert f'stage="{stage}"' in body, stage
            finally:
                await client.close()
                await engine.close()

        run(go())

    def test_admin_scrub_endpoint(self):
        async def go():
            client, _state, engine = await make_client()
            try:
                r = await client.post("/admin/scrub")
                assert r.status == 200
                body = await r.json()
                # one report per engine table, with the reconcile fields
                assert set(body) == set(engine.tables)
                for report in body.values():
                    assert {"data_objects", "referenced", "orphans_seen",
                            "orphans_deleted"} <= set(report)
                r = await client.post("/admin/scrub?grace_ms=banana")
                assert r.status == 400
            finally:
                await client.close()
                await engine.close()

        run(go())

    def test_write_then_query_roundtrip(self):
        async def go():
            client, _state, engine = await make_client()
            try:
                samples = [
                    {"name": "cpu", "labels": {"host": "a"},
                     "timestamp": T0 + i * 60_000, "value": float(i)}
                    for i in range(5)
                ] + [
                    {"name": "cpu", "labels": {"host": "b"},
                     "timestamp": T0, "value": 99.0}
                ]
                r = await client.post("/write", json={"samples": samples})
                assert r.status == 200 and (await r.json())["written"] == 6

                r = await client.post("/query", json={
                    "metric": "cpu", "filters": {"host": "a"},
                    "start": T0, "end": T0 + HOUR})
                body = await r.json()
                assert r.status == 200
                assert body["values"] == [0.0, 1.0, 2.0, 3.0, 4.0]

                r = await client.get("/label_values", params={
                    "metric": "cpu", "key": "host",
                    "start": str(T0), "end": str(T0 + HOUR)})
                assert (await r.json())["values"] == ["a", "b"]
            finally:
                await client.close()
                await engine.close()

        run(go())

    def test_downsample_query(self, respond_on):
        async def go():
            client, _state, engine = await make_client()
            try:
                samples = [
                    {"name": "cpu", "labels": {"host": "a"},
                     "timestamp": T0 + i * 60_000, "value": float(i)}
                    for i in range(10)
                ]
                await client.post("/write", json={"samples": samples})
                r = await client.post("/query", json={
                    "metric": "cpu", "filters": {},
                    "start": T0, "end": T0 + 600_000,
                    "bucket_ms": 300_000})
                body = await r.json()
                assert body["num_buckets"] == 2
                assert body["aggs"]["count"] == [[5.0, 5.0]]
                assert body["aggs"]["avg"] == [[2.0, 7.0]]
            finally:
                await client.close()
                await engine.close()

        run(go())

    def test_query_topk(self, respond_on):
        async def go():
            client, _state, engine = await make_client()
            try:
                samples = []
                for h, peak in (("a", 10.0), ("b", 50.0), ("c", 30.0)):
                    samples += [
                        {"name": "cpu", "labels": {"host": h},
                         "timestamp": T0 + i * 60_000,
                         "value": peak - i} for i in range(5)]
                await client.post("/write", json={"samples": samples})
                r = await client.post("/query_topk", json={
                    "metric": "cpu", "filters": {},
                    "start": T0, "end": T0 + 600_000,
                    "bucket_ms": 300_000, "k": 2, "by": "max"})
                body = await r.json()
                assert len(body["tsids"]) == 2  # best first: b then c
                assert body["aggs"]["max"][0][0] == 50.0
                assert body["aggs"]["max"][1][0] == 30.0
                # missing k -> 400
                r = await client.post("/query_topk", json={
                    "metric": "cpu", "start": T0, "end": T0 + 1,
                    "bucket_ms": 1000})
                assert r.status == 400
            finally:
                await client.close()
                await engine.close()

        run(go())

    def test_query_multi_field(self, respond_on):
        async def go():
            client, _state, engine = await make_client()
            try:
                samples = []
                for f, base in (("usage_user", 1.0), ("usage_system", 5.0)):
                    samples += [
                        {"name": "cpu", "labels": {"host": "a"},
                         "timestamp": T0 + i * 60_000,
                         "value": base + i, "field": f} for i in range(4)]
                await client.post("/write", json={"samples": samples})
                r = await client.post("/query_multi", json={
                    "metric": "cpu", "filters": {},
                    "start": T0, "end": T0 + 600_000,
                    "bucket_ms": 600_000,
                    "fields": ["usage_user", "usage_system"]})
                body = await r.json()
                assert set(body) == {"usage_user", "usage_system"}
                assert body["usage_user"]["aggs"]["sum"] == [[10.0]]
                assert body["usage_system"]["aggs"]["sum"] == [[26.0]]
                r = await client.post("/query_multi", json={
                    "metric": "cpu", "start": T0, "end": T0 + 1,
                    "bucket_ms": 1000, "fields": []})
                assert r.status == 400
            finally:
                await client.close()
                await engine.close()

        run(go())

    def test_bad_requests(self):
        async def go():
            client, _state, engine = await make_client()
            try:
                r = await client.post("/write", json={"nope": []})
                assert r.status == 400
                r = await client.post("/query", json={"metric": "x"})
                assert r.status == 400
                r = await client.get("/label_values", params={"metric": "x"})
                assert r.status == 400
            finally:
                await client.close()
                await engine.close()

        run(go())


class TestConfig:
    def test_example_toml_loads(self):
        cfg = load_config("docs/example.toml")
        assert cfg.port == 5000
        assert cfg.metric_engine.segment_duration.millis == 2 * HOUR
        assert cfg.metric_engine.time_merge_storage.manifest.hard_merge_threshold == 90

    def test_s3_requires_settings(self, tmp_path):
        p = tmp_path / "s3.toml"
        p.write_text('[metric_engine.object_store]\nkind = "S3Like"\n')
        with pytest.raises(Error, match="endpoint, bucket"):
            load_config(str(p))
        p.write_text('[metric_engine.object_store]\nkind = "S3Like"\n'
                     '[metric_engine.object_store.s3]\n'
                     'endpoint = "http://127.0.0.1:9000"\n'
                     'bucket = "tsdb"\nkey_id = "k"\nkey_secret = "s"\n')
        cfg = load_config(str(p))
        assert cfg.metric_engine.object_store.s3.bucket == "tsdb"

    def test_unknown_store_kind_rejected(self, tmp_path):
        p = tmp_path / "bad.toml"
        p.write_text('[metric_engine.object_store]\nkind = "Gcs"\n')
        with pytest.raises(Error, match="Local or S3Like"):
            load_config(str(p))

    def test_unknown_key_rejected(self, tmp_path):
        p = tmp_path / "bad.toml"
        p.write_text("prot = 5000\n")
        with pytest.raises(Error, match="unknown config keys"):
            load_config(str(p))


class TestConfigValidation:
    def test_wrong_scalar_types_fail_at_load(self, tmp_path):
        p = tmp_path / "bad.toml"
        p.write_text("port = '5000'\n")
        with pytest.raises(Error, match="integer"):
            load_config(str(p))
        p.write_text("[metric_engine]\nsegment_duration = 7200000\n")
        with pytest.raises(Error, match="duration string"):
            load_config(str(p))
        p.write_text("[test]\nenable_write = 'false'\n")
        with pytest.raises(Error, match="boolean"):
            load_config(str(p))


class TestArrowIpcIngest:
    def test_write_arrow_endpoint_roundtrip(self):
        async def go():
            import io

            import pyarrow as pa
            import pyarrow.ipc

            client, _state, engine = await make_client()
            try:
                batch = pa.record_batch({
                    "host": pa.array(["a", "b", "a"]),
                    "timestamp": pa.array([T0, T0 + 1000, T0 + 2000],
                                          type=pa.int64()),
                    "value": pa.array([1.0, 2.0, 3.0], type=pa.float64()),
                })
                sink = io.BytesIO()
                with pyarrow.ipc.new_stream(sink, batch.schema) as w:
                    w.write_batch(batch)
                r = await client.post(
                    "/write_arrow?metric=cpu&tags=host",
                    data=sink.getvalue())
                assert r.status == 200 and (await r.json())["written"] == 3
                r = await client.post("/query", json={
                    "metric": "cpu", "filters": {"host": "a"},
                    "start": T0, "end": T0 + HOUR})
                assert (await r.json())["values"] == [1.0, 3.0]
            finally:
                await client.close()
                await engine.close()

        run(go())

    def test_write_arrow_bad_body(self):
        async def go():
            client, _state, engine = await make_client()
            try:
                r = await client.post("/write_arrow?metric=cpu&tags=host",
                                      data=b"not arrow")
                assert r.status == 400
                r = await client.post("/write_arrow", data=b"")
                assert r.status == 400  # missing metric
            finally:
                await client.close()
                await engine.close()

        run(go())

    def test_remote_region_write_arrow(self):
        async def go():
            import aiohttp
            from aiohttp.test_utils import TestServer

            import pyarrow as pa

            from horaedb_tpu.cluster import RemoteRegion
            from horaedb_tpu.storage.types import TimeRange

            engine = await MetricEngine.open("m2", MemoryObjectStore(),
                                             segment_ms=2 * HOUR)
            server = TestServer(build_app(ServerState(engine, ServerConfig())))
            await server.start_server()
            session = aiohttp.ClientSession()
            remote = RemoteRegion(str(server.make_url("/")), session)
            try:
                batch = pa.record_batch({
                    "host": pa.array(["x"] * 5),
                    "timestamp": pa.array([T0 + i * 1000 for i in range(5)],
                                          type=pa.int64()),
                    "value": pa.array([float(i) for i in range(5)],
                                      type=pa.float64()),
                })
                await remote.write_arrow("cpu", ["host"], batch)
                t = await remote.query("cpu", [("host", "x")],
                                       TimeRange.new(T0, T0 + HOUR))
                assert t.num_rows == 5
            finally:
                await remote.close()
                await session.close()
                await server.close()
                await engine.close()

        run(go())


class TestRangeFunctionEndpoint:
    def test_rate_over_http(self, respond_on):
        async def go():
            client, _state, engine = await make_client()
            try:
                samples = [{"name": "reqs", "labels": {"h": "a"},
                            "timestamp": T0 + i * 60_000,
                            "value": float(i * 60)} for i in range(4)]
                await client.post("/write", json={"samples": samples})
                r = await client.post("/query", json={
                    "metric": "reqs", "filters": {}, "start": T0,
                    "end": T0 + 240_000, "bucket_ms": 60_000, "fn": "rate"})
                body = await r.json()
                assert r.status == 200
                assert body["aggs"]["rate"][0][1:] == [1.0, 1.0, 1.0]
                r = await client.post("/query", json={
                    "metric": "reqs", "filters": {}, "start": T0,
                    "end": T0 + 240_000, "bucket_ms": 60_000, "fn": "evil"})
                assert r.status == 400
            finally:
                await client.close()
                await engine.close()

        run(go())

    def test_fn_whitelist(self):
        async def go():
            client, _state, engine = await make_client()
            try:
                for bad in ("np", "annotations", 5, "_per_bucket_last"):
                    r = await client.post("/query", json={
                        "metric": "x", "filters": {}, "start": T0,
                        "end": T0 + 60_000, "bucket_ms": 60_000, "fn": bad})
                    assert r.status == 400, bad
            finally:
                await client.close()
                await engine.close()

        run(go())


class TestArrowQueryEndpoint:
    def test_query_arrow_roundtrip(self):
        async def go():
            import pyarrow.ipc

            client, _state, engine = await make_client()
            try:
                samples = [{"name": "cpu", "labels": {"h": "a"},
                            "timestamp": T0 + i * 1000, "value": float(i)}
                           for i in range(10)]
                await client.post("/write", json={"samples": samples})
                r = await client.post("/query_arrow", json={
                    "metric": "cpu", "filters": {"h": "a"},
                    "start": T0, "end": T0 + HOUR})
                assert r.status == 200
                tbl = pyarrow.ipc.open_stream(await r.read()).read_all()
                assert tbl.column("value").to_pylist() == \
                    [float(i) for i in range(10)]
                r = await client.post("/query_arrow", json={"metric": "x"})
                assert r.status == 400
            finally:
                await client.close()
                await engine.close()

        run(go())

    def test_query_arrow_downsample_matches_json(self):
        """The Arrow downsample encoding must carry exactly the grids
        the JSON endpoint serves (NaN in Arrow == null in JSON)."""
        async def go():
            import pyarrow.ipc

            from horaedb_tpu.common.ipc import downsample_from_arrow

            client, _state, engine = await make_client()
            try:
                samples = [{"name": "cpu", "labels": {"host": "a"},
                            "timestamp": T0 + i * 60_000,
                            "value": float(i)} for i in range(10)]
                # host b reports only the first bucket: NaN cells in avg
                samples += [{"name": "cpu", "labels": {"host": "b"},
                             "timestamp": T0, "value": 7.0}]
                await client.post("/write", json={"samples": samples})
                req = {"metric": "cpu", "filters": {},
                       "start": T0, "end": T0 + 600_000,
                       "bucket_ms": 300_000}
                r = await client.post("/query", json=req)
                jbody = await r.json()
                r = await client.post("/query_arrow",
                                      json={**req, "compression": "zstd"})
                assert r.status == 200
                out = downsample_from_arrow(
                    pyarrow.ipc.open_stream(await r.read()).read_all())
                assert [str(t) for t in out["tsids"]] == jbody["tsids"]
                assert out["num_buckets"] == jbody["num_buckets"]
                assert set(out["aggs"]) == set(jbody["aggs"])
                for k, jgrid in jbody["aggs"].items():
                    expect = np.array(
                        [[np.nan if c is None else c for c in row]
                         for row in jgrid], dtype=np.float64)
                    np.testing.assert_array_equal(out["aggs"][k], expect,
                                                  err_msg=k)
                # fn rides the arrow plane too
                r = await client.post("/query_arrow", json={
                    **req, "fn": "delta", "compression": "zstd"})
                assert r.status == 200
                out = downsample_from_arrow(
                    pyarrow.ipc.open_stream(await r.read()).read_all())
                assert "delta" in out["aggs"]
                r = await client.post("/query_arrow",
                                      json={**req, "fn": "np"})
                assert r.status == 400
                # non-numeric bucket_ms is a 400, not a 500
                for ep in ("/query", "/query_arrow"):
                    r = await client.post(ep, json={
                        **req, "bucket_ms": "5m"})
                    assert r.status == 400, ep
            finally:
                await client.close()
                await engine.close()

        run(go())


class TestChunkedServerConfig:
    def test_chunked_toml(self, tmp_path):
        p = tmp_path / "c.toml"
        p.write_text('[metric_engine]\nchunked_data = true\n'
                     'chunk_window = "15m"\n')
        cfg = load_config(str(p))
        assert cfg.metric_engine.chunked_data is True
        assert cfg.metric_engine.chunk_window.millis == 15 * 60 * 1000
