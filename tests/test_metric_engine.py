"""Metric engine tests (the reference's managers are todo!(); scenarios
come from RFC 20240827's example section: http_requests with
url/code/job labels)."""

import asyncio

import numpy as np
import pyarrow as pa
import pytest

from horaedb_tpu.common.seahash import hash64
from horaedb_tpu.metric_engine import (
    Label,
    MetricEngine,
    Sample,
    metric_id_of,
    series_key_of,
    tsid_of,
)
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.storage.read import ScanRequest
from horaedb_tpu.storage.types import TimeRange

HOUR = 3_600_000
T0 = 1_700_000_000_000


def sample(name, labels, ts, value):
    return Sample(name=name, labels=[Label(k, v) for k, v in labels],
                  timestamp=ts, value=value)


def http_samples():
    return [
        sample("http_requests", [("url", "/api/put"), ("code", "200"),
                                 ("job", "proxy")], T0 + 1000, 100.0),
        sample("http_requests", [("url", "/api/query"), ("code", "200"),
                                 ("job", "proxy")], T0 + 2000, 10.0),
        sample("http_requests", [("url", "/api/put"), ("code", "500"),
                                 ("job", "proxy")], T0 + 3000, 1.0),
        sample("grpc_requests", [("job", "proxy")], T0 + 1000, 7.0),
    ]


async def open_engine(store=None):
    return await MetricEngine.open("metrics_db", store or MemoryObjectStore(),
                                   segment_ms=2 * HOUR)


class TestSeaHash:
    def test_deterministic_and_distinct(self):
        a = hash64(b"http_requests")
        assert a == hash64(b"http_requests")
        assert a != hash64(b"grpc_requests")
        assert a != hash64(b"http_requests ")

    def test_chunking_boundaries(self):
        # exercise 8-byte lane and 32-byte block boundaries
        seen = set()
        for n in [0, 1, 7, 8, 9, 16, 31, 32, 33, 64, 100]:
            h = hash64(bytes(range(n % 256))[:n] * 1)
            seen.add(h)
        assert len(seen) == 11  # no collisions among sizes

    def test_ids(self):
        s = http_samples()[0]
        assert metric_id_of("http_requests") < 2**63
        key = series_key_of(s.name, s.labels)
        # sorted label order, metric-scoped
        assert key == b"http_requests{code=200,job=proxy,url=/api/put}"
        assert tsid_of(s.name, s.labels) == hash64(key) & (2**63 - 1)
        # label order must not matter
        assert tsid_of(s.name, list(reversed(s.labels))) == \
            tsid_of(s.name, s.labels)


class TestWriteQuery:
    def test_write_then_query_with_filters(self):
        async def go():
            e = await open_engine()
            try:
                await e.write(http_samples())
                rng = TimeRange.new(T0, T0 + HOUR)

                tbl = await e.query("http_requests", [], rng)
                assert tbl.num_rows == 3
                assert sorted(tbl.column("value").to_pylist()) == [1.0, 10.0, 100.0]

                tbl = await e.query("http_requests", [("code", "200")], rng)
                assert sorted(tbl.column("value").to_pylist()) == [10.0, 100.0]

                tbl = await e.query("http_requests",
                                    [("code", "200"), ("url", "/api/put")], rng)
                assert tbl.column("value").to_pylist() == [100.0]
                assert tbl.column("tsid").to_pylist() == \
                    [tsid_of("http_requests",
                             [Label("url", "/api/put"), Label("code", "200"),
                              Label("job", "proxy")])]

                # no match
                tbl = await e.query("http_requests", [("code", "404")], rng)
                assert tbl.num_rows == 0
                tbl = await e.query("nope", [], rng)
                assert tbl.num_rows == 0
            finally:
                await e.close()

        asyncio.run(go())

    def test_same_series_overwrite_dedup(self):
        """Same (series, ts) written twice: last write wins — the engine's
        cross-file dedup reaches through the metric layer."""

        async def go():
            e = await open_engine()
            try:
                s1 = http_samples()[:1]
                await e.write(s1)
                s2 = [sample("http_requests",
                             [("url", "/api/put"), ("code", "200"),
                              ("job", "proxy")], T0 + 1000, 999.0)]
                await e.write(s2)
                tbl = await e.query("http_requests", [("url", "/api/put")],
                                    TimeRange.new(T0, T0 + HOUR))
                vals = tbl.column("value").to_pylist()
                assert vals == [999.0]
            finally:
                await e.close()

        asyncio.run(go())

    def test_label_values(self):
        async def go():
            e = await open_engine()
            try:
                await e.write(http_samples())
                rng = TimeRange.new(T0, T0 + HOUR)
                assert await e.label_values("http_requests", "url", rng) == \
                    ["/api/put", "/api/query"]
                assert await e.label_values("http_requests", "code", rng) == \
                    ["200", "500"]
                assert await e.label_values("http_requests", "nope", rng) == []
            finally:
                await e.close()

        asyncio.run(go())

    def test_time_range_filtering(self):
        async def go():
            e = await open_engine()
            try:
                await e.write(http_samples())
                tbl = await e.query("http_requests", [],
                                    TimeRange.new(T0 + 1500, T0 + 2500))
                assert tbl.column("value").to_pylist() == [10.0]
            finally:
                await e.close()

        asyncio.run(go())

    def test_multi_segment_series_reregistration(self):
        """A series active in two segments must be indexed in both (the
        RFC's Date-scoped index via segment duration)."""

        async def go():
            e = await open_engine()
            try:
                labels = [("host", "web-1")]
                await e.write([sample("cpu", labels, T0 + 1000, 1.0)])
                t_next = T0 + 2 * HOUR + 1000  # next segment
                await e.write([sample("cpu", labels, t_next, 2.0)])
                # query restricted to the SECOND segment still finds the series
                tbl = await e.query("cpu", [("host", "web-1")],
                                    TimeRange.new(T0 + 2 * HOUR, T0 + 4 * HOUR))
                assert tbl.column("value").to_pylist() == [2.0]
                # and a spanning query finds both points
                tbl = await e.query("cpu", [("host", "web-1")],
                                    TimeRange.new(T0, T0 + 4 * HOUR))
                assert sorted(tbl.column("value").to_pylist()) == [1.0, 2.0]
            finally:
                await e.close()

        asyncio.run(go())

    def test_query_downsample(self):
        async def go():
            e = await open_engine()
            try:
                samples = []
                for host, base in [("web-1", 10.0), ("web-2", 50.0)]:
                    for i in range(10):
                        samples.append(sample(
                            "cpu", [("host", host)],
                            T0 + i * 60_000, base + i))
                await e.write(samples)
                out = await e.query_downsample(
                    "cpu", [], TimeRange.new(T0, T0 + 600_000),
                    bucket_ms=300_000)
                assert out["num_buckets"] == 2
                assert len(out["tsids"]) == 2
                aggs = out["aggs"]
                # each series: buckets of 5 points each
                np.testing.assert_array_equal(aggs["count"],
                                              [[5, 5], [5, 5]])
                by_tsid = dict(zip(out["tsids"], aggs["sum"]))
                web1 = tsid_of("cpu", [Label("host", "web-1")])
                web2 = tsid_of("cpu", [Label("host", "web-2")])
                assert by_tsid[web1].tolist() == [60.0, 85.0]   # 10..14, 15..19
                assert by_tsid[web2].tolist() == [260.0, 285.0]
            finally:
                await e.close()

        asyncio.run(go())

    @pytest.mark.parametrize("fused", ["0", "1"])
    def test_aligned_fast_path_tsid_set_matches_ts_leaf_path(
            self, monkeypatch, fused):
        """The bucket-aligned fast path omits the ts leaf, so boundary
        -segment rows outside [start, end) decode too; a series whose
        rows ALL lie outside the range must not surface as an all-zero
        -count group.  The query range must STRADDLE a segment boundary
        (start mid-segment) or the out-of-range SST is never planned and
        the leak can't occur; both the parts (fused=0) and fused device
        paths must drop the empty group."""
        monkeypatch.setenv("HORAEDB_FUSED_AGG", fused)

        async def go():
            e = await open_engine()
            try:
                seg0 = T0 - T0 % (2 * HOUR)
                samples = []
                # series A: rows across [seg0, seg0+4h)
                for i in range(48):
                    samples.append(sample("cpu", [("host", "in-range")],
                                          seg0 + i * 5 * 60_000, float(i)))
                # series B: rows ONLY in [seg0, seg0+30min) — inside the
                # query's boundary segment, outside the query range
                for i in range(6):
                    samples.append(sample("cpu", [("host", "out-of-range")],
                                          seg0 + i * 5 * 60_000 + 1,
                                          99.0))
                await e.write(samples)
                # starts MID-segment: the boundary segment decodes whole
                # (B's rows included), the grid cut must drop B entirely
                rng_q = TimeRange.new(seg0 + HOUR, seg0 + 3 * HOUR)
                # span == 2h == segment_ms, bucket divides span -> aligned
                aligned = await e.query_downsample(
                    "cpu", [], rng_q, bucket_ms=HOUR)
                # repeat (one small round: the one call again, which
                # records no replay; tests/test_fused_one_call.py has
                # the rounds' and the replay's drop): dropped too
                replay = await e.query_downsample(
                    "cpu", [], rng_q, bucket_ms=HOUR)
                # 7-minute bucket does not divide the span -> ts-leaf path
                leafed = await e.query_downsample(
                    "cpu", [], rng_q, bucket_ms=7 * 60_000)
                b = tsid_of("cpu", [Label("host", "out-of-range")])
                for out in (aligned, replay):
                    assert b not in out["tsids"]
                    assert sorted(out["tsids"]) == sorted(leafed["tsids"])
                    counts = np.asarray(out["aggs"]["count"])
                    assert (counts.sum(axis=1) > 0).all()
            finally:
                await e.close()

        asyncio.run(go())

    def test_multi_field_downsample_parity_and_shared_reads(self):
        """query_downsample_multi must return exactly what N per-field
        query_downsample calls return, while reading the data table's
        rows ONCE in total (fields partition the rows; each field's
        pushdown scan decodes only its own partition)."""
        from horaedb_tpu.storage.read import _STAGE_ROWS

        FIELDS = ["usage_user", "usage_system", "usage_idle"]
        N_ROWS = 3 * 40 * len(FIELDS)

        async def go():
            store = MemoryObjectStore()
            e = await MetricEngine.open("mf", store, segment_ms=2 * HOUR)
            try:
                rng = np.random.default_rng(21)
                samples = []
                for host in ("web-1", "web-2", "db-1"):
                    for i in range(40):
                        for j, f in enumerate(FIELDS):
                            samples.append(Sample(
                                name="cpu",
                                labels=[Label("host", host)],
                                timestamp=T0 + i * 60_000 + j,
                                value=float(rng.random() * 100),
                                field_name=f))
                await e.write(samples)
                rng_q = TimeRange.new(T0, T0 + HOUR)
                singles = {}
                for f in FIELDS:
                    singles[f] = await e.query_downsample(
                        "cpu", [], rng_q, bucket_ms=300_000, field=f)
            finally:
                await e.close()
            # fresh engine: the multi query runs cold, nothing cached
            e = await MetricEngine.open("mf", store, segment_ms=2 * HOUR)
            try:
                # data table reads go through sidecars (OVERWRITE mode);
                # metric/index resolve reads are parquet and not counted
                read_before = _STAGE_ROWS["sidecar_read"].value
                multi = await e.query_downsample_multi(
                    "cpu", [], rng_q, bucket_ms=300_000, fields=FIELDS)
                read_rows = _STAGE_ROWS["sidecar_read"].value - read_before
                # ONE pass over the data (all fields' rows), not N; the
                # one-off metrics-table resolve adds its own few rows
                assert N_ROWS <= read_rows <= N_ROWS + len(FIELDS), \
                    read_rows
                for f in FIELDS:
                    assert multi[f]["tsids"] == singles[f]["tsids"], f
                    assert set(multi[f]["aggs"]) == set(singles[f]["aggs"])
                    np.testing.assert_array_equal(
                        np.asarray(multi[f]["aggs"]["count"]),
                        np.asarray(singles[f]["aggs"]["count"]),
                        err_msg=f)
                    for k in multi[f]["aggs"]:
                        np.testing.assert_allclose(
                            np.asarray(multi[f]["aggs"][k]),
                            np.asarray(singles[f]["aggs"][k]),
                            rtol=1e-5, atol=1e-5, err_msg=f"{f}/{k}")
            finally:
                await e.close()

        asyncio.run(go())

    def test_persistence_across_reopen(self):
        async def go():
            store = MemoryObjectStore()
            e = await open_engine(store)
            await e.write(http_samples())
            await e.close()

            e2 = await MetricEngine.open("metrics_db", store,
                                         segment_ms=2 * HOUR)
            try:
                rng = TimeRange.new(T0, T0 + HOUR)
                tbl = await e2.query("http_requests", [("job", "proxy")], rng)
                assert tbl.num_rows == 3
                assert await e2.label_values("http_requests", "code", rng) == \
                    ["200", "500"]
            finally:
                await e2.close()

        asyncio.run(go())


class TestReviewRegressions:
    def test_distinct_fields_do_not_collide(self):
        async def go():
            e = await open_engine()
            try:
                labels = [("host", "a")]
                await e.write([
                    Sample("mem", [Label("host", "a")], T0 + 1000, 1.0,
                           field_name="used"),
                    Sample("mem", [Label("host", "a")], T0 + 1000, 2.0,
                           field_name="free"),
                ])
                rng = TimeRange.new(T0, T0 + HOUR)
                used = await e.query("mem", labels, rng, field="used")
                free = await e.query("mem", labels, rng, field="free")
                assert used.column("value").to_pylist() == [1.0]
                assert free.column("value").to_pylist() == [2.0]
            finally:
                await e.close()

        asyncio.run(go())

    def test_failed_registration_retried(self):
        """A failed index write must not poison the seen-cache."""

        async def go():
            e = await open_engine()
            try:
                s = [sample("cpu", [("host", "x")], T0 + 1000, 1.0)]
                # sabotage the index table write once
                orig = e.index_manager.index.write
                calls = {"n": 0}

                async def flaky(req):
                    calls["n"] += 1
                    if calls["n"] == 1:
                        raise RuntimeError("transient store error")
                    return await orig(req)

                e.index_manager.index.write = flaky
                with pytest.raises(RuntimeError):
                    await e.write(s)
                # retry succeeds and the series becomes queryable
                await e.write(s)
                tbl = await e.query("cpu", [("host", "x")],
                                    TimeRange.new(T0, T0 + HOUR))
                assert tbl.column("value").to_pylist() == [1.0]
            finally:
                await e.close()

        asyncio.run(go())

    def test_downsample_window_span_guarded(self):
        async def go():
            e = await open_engine()
            try:
                from horaedb_tpu.common import Error
                with pytest.raises(Error, match="24.8 days"):
                    await e.query_downsample(
                        "cpu", [], TimeRange.new(0, 40 * 24 * 3600 * 1000),
                        bucket_ms=3_600_000)
            finally:
                await e.close()

        asyncio.run(go())

    def test_resolve_series(self):
        async def go():
            e = await open_engine()
            try:
                await e.write(http_samples())
                rng = TimeRange.new(T0, T0 + HOUR)
                tbl = await e.query("http_requests", [("code", "500")], rng)
                tsid = tbl.column("tsid")[0].as_py()
                keys = await e.resolve_series("http_requests", [tsid], rng)
                assert keys[tsid] == \
                    b"http_requests{code=500,job=proxy,url=/api/put}"
            finally:
                await e.close()

        asyncio.run(go())

    def test_seen_cache_bounded(self):
        async def go():
            e = await open_engine()
            try:
                # write into 8 distinct segments; cache keeps only 4
                for i in range(8):
                    await e.write([sample("cpu", [("h", "x")],
                                          T0 + i * 2 * HOUR, float(i))])
                segs = e.index_manager._seen._by_segment
                assert len(segs) <= 4
            finally:
                await e.close()

        asyncio.run(go())

    def test_seen_cache_backfill_no_rewrite_churn(self):
        """Steady backfill into an OLD segment must keep hitting the
        seen-cache: registration rows are written once, not once per
        batch (the LRU keeps recently-USED segments, not newest-keyed)."""
        async def go():
            e = await open_engine()
            try:
                # populate newer segments so a newest-by-key policy would
                # evict the old one
                for i in range(1, 6):
                    await e.write([sample("cpu", [("h", "new")],
                                          T0 + i * 2 * HOUR, 1.0)])
                index = e.tables["index"]
                writes_before = None
                # repeated backfill batches into the OLDEST segment
                for j in range(5):
                    await e.write([sample("cpu", [("h", "old")],
                                          T0 + 60_000 + j, float(j))])
                    n_ssts = len(await index.manifest.all_ssts())
                    if writes_before is None:
                        writes_before = n_ssts  # first batch registers
                    else:
                        assert n_ssts == writes_before, (
                            "backfill batch re-registered index rows: "
                            f"{n_ssts} SSTs vs {writes_before}")
            finally:
                await e.close()

        asyncio.run(go())


class TestAggregatePushdown:
    def test_multi_segment_downsample_combines(self):
        """Series spanning segments: per-segment partial grids must
        combine into one correct result (incl. last across segments)."""

        async def go():
            e = await open_engine()
            try:
                samples = []
                # segment 1: ts in [T0, ...); segment 2: +2h
                for seg_base, off in [(T0, 0.0), (T0 + 2 * HOUR, 100.0)]:
                    for host in ["a", "b"]:
                        for i in range(6):
                            samples.append(sample(
                                "cpu", [("host", host)],
                                seg_base + i * 60_000,
                                off + (10.0 if host == "a" else 50.0) + i))
                await e.write(samples)
                rng = TimeRange.new(T0, T0 + 2 * HOUR + 600_000)
                out = await e.query_downsample("cpu", [], rng,
                                               bucket_ms=HOUR)
                assert len(out["tsids"]) == 2
                aggs = out["aggs"]
                assert out["num_buckets"] == 3
                # bucket 0 holds segment-1 points, bucket 2 segment-2 points
                np.testing.assert_array_equal(aggs["count"][:, 0], [6, 6])
                np.testing.assert_array_equal(aggs["count"][:, 1], [0, 0])
                np.testing.assert_array_equal(aggs["count"][:, 2], [6, 6])
                by = dict(zip(out["tsids"], range(2)))
                a_row = by[tsid_of("cpu", [Label("host", "a")])]
                # segment 1 values: 10..15 -> sum 75; segment 2: 110..115
                assert aggs["sum"][a_row, 0] == 75.0
                assert aggs["sum"][a_row, 2] == 675.0
                # last of the whole range comes from segment 2's final point
                assert aggs["last"][a_row, 2] == 115.0
                assert np.isnan(aggs["avg"][a_row, 1])
                assert aggs["min"][a_row, 0] == 10.0
                assert aggs["max"][a_row, 2] == 115.0
            finally:
                await e.close()

        asyncio.run(go())

    def test_pushdown_respects_label_filter(self):
        async def go():
            e = await open_engine()
            try:
                for host, v in [("a", 1.0), ("b", 2.0)]:
                    await e.write([sample("cpu", [("host", host)],
                                          T0 + 1000, v)])
                out = await e.query_downsample(
                    "cpu", [("host", "b")], TimeRange.new(T0, T0 + HOUR),
                    bucket_ms=HOUR)
                assert out["tsids"] == [tsid_of("cpu", [Label("host", "b")])]
                assert out["aggs"]["sum"][0, 0] == 2.0
            finally:
                await e.close()

        asyncio.run(go())


from horaedb_tpu.common import Error


class TestBulkArrowIngest:
    def test_write_arrow_equals_scalar_write(self):
        async def go():
            import pyarrow as pa
            rng = np.random.default_rng(0)
            n, hosts = 2000, 20
            hs = [f"h{int(i):02d}" for i in rng.integers(0, hosts, n)]
            regions = ["east" if h < "h10" else "west" for h in hs]
            ts = (T0 + rng.integers(0, 3 * HOUR, n)).tolist()
            vals = rng.random(n).round(4).tolist()
            batch = pa.record_batch({
                "host": pa.array(hs), "region": pa.array(regions),
                "timestamp": pa.array(ts, type=pa.int64()),
                "value": pa.array(vals, type=pa.float64()),
            })

            e_bulk = await open_engine()
            e_ref = await open_engine()
            try:
                await e_bulk.write_arrow("cpu", ["host", "region"], batch)
                await e_ref.write([
                    sample("cpu", [("host", h), ("region", r)], t, v)
                    for h, r, t, v in zip(hs, regions, ts, vals)
                ])
                rng_q = TimeRange.new(T0, T0 + 4 * HOUR)
                for filters in ([], [("host", "h03")],
                                [("region", "east")],
                                [("host", "h15"), ("region", "west")]):
                    a = await e_bulk.query("cpu", filters, rng_q)
                    b = await e_ref.query("cpu", filters, rng_q)
                    ka = sorted(zip(a.column("tsid").to_pylist(),
                                    a.column("timestamp").to_pylist(),
                                    a.column("value").to_pylist()))
                    kb = sorted(zip(b.column("tsid").to_pylist(),
                                    b.column("timestamp").to_pylist(),
                                    b.column("value").to_pylist()))
                    assert ka == kb, filters
                assert await e_bulk.label_values("cpu", "region", rng_q) == \
                    await e_ref.label_values("cpu", "region", rng_q)
            finally:
                await e_bulk.close()
                await e_ref.close()

        asyncio.run(go())

    def test_write_arrow_high_cardinality_fallback(self):
        """A tag-cardinality product beyond the composite code space
        must fall back to exact row-wise grouping, not reject the
        batch — results identical to the scalar write path."""
        async def go():
            import pyarrow as pa
            rng = np.random.default_rng(4)
            n, tags = 120, 11  # 100-ish uniques ** 11 >> 2**62
            cols = {f"t{j}": [f"v{int(x):03d}" for x in
                              rng.integers(0, 100, n)]
                    for j in range(tags)}
            ts = (T0 + rng.integers(0, HOUR, n)).tolist()
            vals = rng.random(n).round(4).tolist()
            batch = pa.record_batch({
                **{k: pa.array(v) for k, v in cols.items()},
                "timestamp": pa.array(ts, type=pa.int64()),
                "value": pa.array(vals, type=pa.float64()),
            })
            tag_names = list(cols)
            e_bulk = await open_engine()
            e_ref = await open_engine()
            try:
                await e_bulk.write_arrow("cpu", tag_names, batch)
                await e_ref.write([
                    sample("cpu",
                           [(k, cols[k][i]) for k in tag_names], ts[i],
                           vals[i])
                    for i in range(n)
                ])
                rng_q = TimeRange.new(T0, T0 + 2 * HOUR)
                a = await e_bulk.query("cpu", [], rng_q)
                b = await e_ref.query("cpu", [], rng_q)
                ka = sorted(zip(a.column("tsid").to_pylist(),
                                a.column("timestamp").to_pylist(),
                                a.column("value").to_pylist()))
                kb = sorted(zip(b.column("tsid").to_pylist(),
                                b.column("timestamp").to_pylist(),
                                b.column("value").to_pylist()))
                assert ka == kb and len(ka) > 0
            finally:
                await e_bulk.close()
                await e_ref.close()

        asyncio.run(go())

    def test_write_arrow_multi_segment(self):
        async def go():
            import pyarrow as pa
            e = await open_engine()
            try:
                ts = [T0 + 1000, T0 + 2 * HOUR + 1000, T0 + 4 * HOUR + 1000]
                batch = pa.record_batch({
                    "host": pa.array(["a", "a", "a"]),
                    "timestamp": pa.array(ts, type=pa.int64()),
                    "value": pa.array([1.0, 2.0, 3.0], type=pa.float64()),
                })
                await e.write_arrow("cpu", ["host"], batch)
                t = await e.query("cpu", [("host", "a")],
                                  TimeRange.new(T0, T0 + 6 * HOUR))
                assert sorted(t.column("value").to_pylist()) == [1.0, 2.0, 3.0]
            finally:
                await e.close()

        asyncio.run(go())

    def test_write_arrow_later_segment_queryable(self):
        """Regression: a series' data in a later segment must be indexed
        there too — a query window that misses the first segment still
        finds it (the review's reproduced bug)."""

        async def go():
            import pyarrow as pa
            e = await open_engine()
            try:
                batch = pa.record_batch({
                    "host": pa.array(["a", "a"]),
                    "timestamp": pa.array([T0 + 1000, T0 + 4 * HOUR + 1000],
                                          type=pa.int64()),
                    "value": pa.array([1.0, 2.0], type=pa.float64()),
                })
                await e.write_arrow("cpu", ["host"], batch)
                later = TimeRange.new(T0 + 4 * HOUR, T0 + 6 * HOUR)
                t = await e.query("cpu", [("host", "a")], later)
                assert t.column("value").to_pylist() == [2.0]
                t = await e.query("cpu", [], later)
                assert t.column("value").to_pylist() == [2.0]
                assert await e.label_values("cpu", "host", later) == ["a"]
            finally:
                await e.close()

        asyncio.run(go())

    def test_write_arrow_missing_tag_column(self):
        async def go():
            import pyarrow as pa
            e = await open_engine()
            try:
                batch = pa.record_batch({
                    "host": pa.array(["a"]),
                    "timestamp": pa.array([T0], type=pa.int64()),
                    "value": pa.array([1.0], type=pa.float64()),
                })
                with pytest.raises(Error, match="hsot"):
                    await e.write_arrow("cpu", ["hsot"], batch)
            finally:
                await e.close()

        asyncio.run(go())

    def test_write_arrow_type_normalization_and_nulls(self):
        async def go():
            import pyarrow as pa
            e = await open_engine()
            try:
                # idiomatic Arrow timestamp type casts cleanly
                batch = pa.record_batch({
                    "host": pa.array(["a"]),
                    "timestamp": pa.array([T0], type=pa.timestamp("ms")),
                    "value": pa.array([1], type=pa.int32()),
                })
                await e.write_arrow("cpu", ["host"], batch)
                t = await e.query("cpu", [("host", "a")],
                                  TimeRange.new(T0, T0 + HOUR))
                assert t.column("value").to_pylist() == [1.0]
                # null tags rejected with the framework Error
                bad = pa.record_batch({
                    "host": pa.array(["a", None]),
                    "timestamp": pa.array([T0, T0], type=pa.int64()),
                    "value": pa.array([1.0, 2.0], type=pa.float64()),
                })
                with pytest.raises(Error, match="nulls"):
                    await e.write_arrow("cpu", ["host"], bad)
                # non-castable timestamp rejected
                bad2 = pa.record_batch({
                    "host": pa.array(["a"]),
                    "timestamp": pa.array(["yesterday"]),
                    "value": pa.array([1.0], type=pa.float64()),
                })
                with pytest.raises(Error, match="cast"):
                    await e.write_arrow("cpu", ["host"], bad2)
            finally:
                await e.close()

        asyncio.run(go())


class TestRangeFunctions:
    def grids(self, last_rows):
        last = np.array(last_rows, dtype=np.float64)
        return {"last": last, "count": np.where(np.isnan(last), 0, 1)}

    def test_delta(self):
        from horaedb_tpu.metric_engine import delta
        out = delta(self.grids([[1.0, 4.0, 2.0]]), 60_000)
        assert np.isnan(out[0, 0])
        assert out[0, 1:].tolist() == [3.0, -2.0]

    def test_increase_with_reset(self):
        from horaedb_tpu.metric_engine import increase
        # counter: 10 -> 25 -> reset to 5 -> 12
        out = increase(self.grids([[10.0, 25.0, 5.0, 12.0]]), 60_000)
        assert np.isnan(out[0, 0])
        assert out[0, 1:].tolist() == [15.0, 5.0, 7.0]

    def test_rate(self):
        from horaedb_tpu.metric_engine import rate
        out = rate(self.grids([[0.0, 120.0]]), 60_000)
        assert out[0, 1] == 2.0  # 120 over 60s

    def test_nan_propagates_through_empty_buckets(self):
        from horaedb_tpu.metric_engine import increase
        out = increase(self.grids([[1.0, np.nan, 5.0]]), 60_000)
        assert np.isnan(out[0, 1]) and np.isnan(out[0, 2])


class TestChunkedDataMode:
    def test_chunk_codec_roundtrip(self):
        from horaedb_tpu.metric_engine import chunks
        rng = np.random.default_rng(0)
        ts = T0 + rng.permutation(500).astype(np.int64) * 1000
        vals = rng.random(500)
        buf = chunks.encode_chunk(ts, vals)
        got_ts, got_vals = chunks.decode_chunks(buf)
        order = np.argsort(ts)
        np.testing.assert_array_equal(got_ts, ts[order])
        np.testing.assert_array_equal(got_vals, vals[order])
        # concatenated payloads decode + last-wins dedup
        buf2 = chunks.encode_chunk(np.array([int(ts[order][0])]),
                                   np.array([999.0]))
        ts2, vals2 = chunks.decode_chunks(buf + buf2)
        assert len(ts2) == 500
        assert vals2[0] == 999.0  # later chunk shadows

    def test_chunk_codec_corruption(self):
        from horaedb_tpu.common import Error
        from horaedb_tpu.metric_engine import chunks
        buf = chunks.encode_chunk(np.array([T0]), np.array([1.0]))
        with pytest.raises(Error, match="magic"):
            chunks.decode_chunks(b"\x00" + buf[1:])
        with pytest.raises(Error, match="truncated"):
            chunks.decode_chunks(buf[:-4])

    async def _open_chunked(self, store=None):
        return await MetricEngine.open(
            "chunked_db", store or MemoryObjectStore(), segment_ms=2 * HOUR,
            chunked_data=True, chunk_window_ms=30 * 60 * 1000)

    def test_write_query_roundtrip_chunked(self):
        async def go():
            e = await self._open_chunked()
            try:
                await e.write(http_samples())
                rng = TimeRange.new(T0, T0 + HOUR)
                tbl = await e.query("http_requests", [("code", "200")], rng)
                assert sorted(tbl.column("value").to_pylist()) == [10.0, 100.0]
                # time-range filtering reaches inside chunks
                tbl = await e.query("http_requests", [],
                                    TimeRange.new(T0 + 1500, T0 + 2500))
                assert tbl.column("value").to_pylist() == [10.0]
            finally:
                await e.close()

        asyncio.run(go())

    def test_cross_file_merge_last_wins_chunked(self):
        """Two writes of the same (series, ts): BytesMerge concatenates the
        chunks and decode-side dedup keeps the later sequence's value."""

        async def go():
            e = await self._open_chunked()
            try:
                await e.write([sample("cpu", [("h", "a")], T0 + 1000, 1.0)])
                await e.write([sample("cpu", [("h", "a")], T0 + 1000, 2.0)])
                tbl = await e.query("cpu", [("h", "a")],
                                    TimeRange.new(T0, T0 + HOUR))
                assert tbl.column("value").to_pylist() == [2.0]
            finally:
                await e.close()

        asyncio.run(go())

    def test_downsample_chunked(self):
        async def go():
            e = await self._open_chunked()
            try:
                samples = [sample("cpu", [("h", "a")], T0 + i * 60_000,
                                  float(i)) for i in range(10)]
                await e.write(samples)
                out = await e.query_downsample(
                    "cpu", [], TimeRange.new(T0, T0 + 600_000),
                    bucket_ms=300_000)
                assert out["aggs"]["count"].tolist() == [[5.0, 5.0]]
                assert out["aggs"]["sum"].tolist() == [[10.0, 35.0]]
                assert out["aggs"]["last"].tolist() == [[4.0, 9.0]]
                # aggregate restriction applies on the chunked path too
                sub = await e.query_downsample(
                    "cpu", [], TimeRange.new(T0, T0 + 600_000),
                    bucket_ms=300_000, aggs=("avg",))
                assert "min" not in sub["aggs"] and "sum" not in sub["aggs"]
                assert sub["aggs"]["avg"].tolist() == [[2.0, 7.0]]
            finally:
                await e.close()

        asyncio.run(go())

    def test_chunked_host_and_device_aggregation_match(self, monkeypatch):
        """HORAEDB_HOST_AGG gates _downsample_arrays between the numpy
        twin (_host_bucket_grids) and the device time_bucket_aggregate;
        both must produce the same grids — the device branch would
        otherwise lose all CPU CI coverage (the host twin is the CPU
        default)."""
        def run(forced):
            monkeypatch.setenv("HORAEDB_HOST_AGG", forced)

            async def go():
                e = await self._open_chunked()
                try:
                    rng = np.random.default_rng(3)
                    samples = [
                        sample("cpu", [("h", f"h{int(h)}")],
                               T0 + int(t) * 60_000, float(v))
                        for h, t, v in zip(rng.integers(0, 5, 600),
                                           rng.integers(0, 30, 600),
                                           rng.random(600) * 50)]
                    await e.write(samples)
                    return await e.query_downsample(
                        "cpu", [], TimeRange.new(T0, T0 + 1_800_000),
                        bucket_ms=300_000)
                finally:
                    await e.close()

            return asyncio.run(go())

        host, dev = run("1"), run("0")
        assert host["tsids"] == dev["tsids"]
        assert set(host["aggs"]) == set(dev["aggs"])
        np.testing.assert_array_equal(np.asarray(host["aggs"]["count"]),
                                      np.asarray(dev["aggs"]["count"]))
        for k in host["aggs"]:
            np.testing.assert_allclose(
                np.asarray(host["aggs"][k], dtype=np.float64),
                np.asarray(dev["aggs"][k], dtype=np.float64),
                rtol=2e-5, atol=1e-5, err_msg=k)

    def test_chunked_downsample_parity_with_row_layout_no_row_table(self):
        """The chunked fast path must produce the SAME grids as the row
        layout on identical samples, and must never materialize an
        Arrow row table (payload -> numpy -> device)."""
        async def go():
            rng = np.random.default_rng(11)
            n = 4000
            samples = [
                sample("cpu", [("h", f"h{int(h):02d}")],
                       T0 + int(t), float(v))
                for h, t, v in zip(rng.integers(0, 7, n),
                                   rng.integers(0, 2 * HOUR, n),
                                   rng.random(n) * 100)
            ]
            row_e = await open_engine()
            chunk_e = await self._open_chunked()
            try:
                await row_e.write(samples)
                await chunk_e.write(samples)
                rng_q = TimeRange.new(T0, T0 + 2 * HOUR)

                called = []
                orig = chunk_e.query

                async def spying_query(*a, **kw):
                    called.append(a)
                    return await orig(*a, **kw)

                chunk_e.query = spying_query
                want = await row_e.query_downsample("cpu", [], rng_q,
                                                    bucket_ms=600_000)
                got = await chunk_e.query_downsample("cpu", [], rng_q,
                                                     bucket_ms=600_000)
                assert called == [], "chunked downsample built a row table"
                assert got["tsids"] == want["tsids"]
                for key in want["aggs"]:
                    np.testing.assert_allclose(
                        np.asarray(got["aggs"][key], dtype=np.float64),
                        np.asarray(want["aggs"][key], dtype=np.float64),
                        rtol=1e-5, err_msg=key)
            finally:
                await row_e.close()
                await chunk_e.close()

        asyncio.run(go())

    def test_chunked_decode_cache_hits_and_invalidates(self):
        """Repeat chunked downsamples serve from the decode LRU (the
        Append scan is uncached, so this is the chunked layout's scan
        cache); a write changes the data table's SST set and must
        invalidate so fresh samples appear."""
        async def go():
            e = await self._open_chunked()
            try:
                samples = [sample("cpu", [("h", f"h{i % 5}")],
                                  T0 + i * 10_000, float(i))
                           for i in range(3000)]
                await e.write(samples)
                rng_q = TimeRange.new(T0, T0 + HOUR)

                first = await e.query_downsample("cpu", [], rng_q,
                                                 bucket_ms=300_000)
                assert e._chunk_cache.hits == 0
                second = await e.query_downsample("cpu", [], rng_q,
                                                  bucket_ms=300_000)
                assert e._chunk_cache.hits == 1
                for key in first["aggs"]:
                    np.testing.assert_array_equal(
                        np.asarray(first["aggs"][key]),
                        np.asarray(second["aggs"][key]), err_msg=key)
                # a different bucket size reuses the SAME decoded entry
                other = await e.query_downsample("cpu", [], rng_q,
                                                 bucket_ms=600_000)
                assert e._chunk_cache.hits == 2
                assert other["num_buckets"] != second["num_buckets"]

                total1 = float(np.asarray(second["aggs"]["count"]).sum())
                await e.write([sample("cpu", [("h", "h0")],
                                      T0 + 5_000, 42.0)])
                hits = e._chunk_cache.hits
                third = await e.query_downsample("cpu", [], rng_q,
                                                 bucket_ms=300_000)
                assert e._chunk_cache.hits == hits, \
                    "stale decode entry served after a write"
                total3 = float(np.asarray(third["aggs"]["count"]).sum())
                assert total3 == total1 + 1
            finally:
                await e.close()

        asyncio.run(go())

    def test_chunked_storage_is_compact(self):
        """One row per (series, chunk window), not per point."""

        async def go():
            store = MemoryObjectStore()
            e = await self._open_chunked(store)
            try:
                samples = [sample("cpu", [("h", "a")], T0 + i * 1000, float(i))
                           for i in range(1000)]
                await e.write(samples)
                batches = []
                from horaedb_tpu.storage.read import ScanRequest
                async for b in e.tables["data"].scan(
                        ScanRequest(range=TimeRange.new(T0, T0 + 2 * HOUR))):
                    batches.append(b)
                rows = sum(b.num_rows for b in batches)
                assert rows == 1  # 1000 points in one 30-min chunk row
            finally:
                await e.close()

        asyncio.run(go())

    def test_write_arrow_chunked(self):
        async def go():
            import pyarrow as pa
            e = await self._open_chunked()
            try:
                n = 200
                rng = np.random.default_rng(1)
                hosts = [f"h{int(i)}" for i in rng.integers(0, 4, n)]
                ts = (T0 + rng.integers(0, 2 * HOUR - 1, n)).tolist()
                vals = rng.random(n).round(4).tolist()
                batch = pa.record_batch({
                    "host": pa.array(hosts),
                    "timestamp": pa.array(ts, type=pa.int64()),
                    "value": pa.array(vals, type=pa.float64()),
                })
                await e.write_arrow("cpu", ["host"], batch)
                tbl = await e.query("cpu", [], TimeRange.new(T0, T0 + 2 * HOUR))
                got = sorted(zip(tbl.column("timestamp").to_pylist(),
                                 tbl.column("value").to_pylist()))
                # last-wins on duplicate (series, ts): build expected the
                # same way
                exp = {}
                for h, t, v in zip(hosts, ts, vals):
                    exp[(h, t)] = v
                assert len(got) == len(set(zip(hosts, ts)))
                assert sorted(t for (_h, t) in exp) == [t for t, _ in got]
                # negative timestamps rejected
                bad = pa.record_batch({
                    "host": pa.array(["a"]),
                    "timestamp": pa.array([-5], type=pa.int64()),
                    "value": pa.array([1.0], type=pa.float64()),
                })
                with pytest.raises(Error, match="non-negative"):
                    await e.write_arrow("cpu", ["host"], bad)
            finally:
                await e.close()

        asyncio.run(go())

    def test_last_ts_absolute_across_paths(self):
        """Pushdown and chunked downsample paths must expose last_ts in
        the same (absolute ms) unit — the cluster merge compares them."""

        async def go():
            e_row = await open_engine()
            e_chunk = await self._open_chunked()
            try:
                for e in (e_row, e_chunk):
                    await e.write([sample("cpu", [("h", "a")],
                                          T0 + 90_000, 5.0)])
                    out = await e.query_downsample(
                        "cpu", [], TimeRange.new(T0, T0 + 600_000),
                        bucket_ms=300_000)
                    lt = out["aggs"]["last_ts"][0, 0]
                    assert lt == T0 + 90_000, (type(e), lt)
            finally:
                await e_row.close()
                await e_chunk.close()

        asyncio.run(go())

    def test_compaction_in_chunked_mode(self):
        """BytesMerge compaction over chunk rows: payloads concatenate,
        data stays correct, file count drops."""

        async def go():
            from horaedb_tpu.storage.config import StorageConfig, from_dict

            store = MemoryObjectStore()
            cfg = from_dict(StorageConfig, {
                "scheduler": {"schedule_interval": "1h",
                              "input_sst_min_num": 2}})
            e = await MetricEngine.open(
                "cdb", store, segment_ms=2 * HOUR, config=cfg,
                chunked_data=True, chunk_window_ms=30 * 60 * 1000)
            try:
                for v in (1.0, 2.0, 3.0):
                    await e.write([sample("cpu", [("h", "a")],
                                          T0 + 1000, v)])
                data = e.tables["data"]
                assert len(await data.manifest.all_ssts()) == 3
                task = await data.compact_scheduler.picker.pick_candidate()
                assert task is not None
                await data.compact_scheduler.executor.execute(task)
                assert len(await data.manifest.all_ssts()) == 1
                # last write still wins after physical merge
                tbl = await e.query("cpu", [("h", "a")],
                                    TimeRange.new(T0, T0 + HOUR))
                assert tbl.column("value").to_pylist() == [3.0]
            finally:
                await e.close()

        asyncio.run(go())


class TestDiscoveryApis:
    def test_label_names_and_list_metrics(self):
        async def go():
            e = await open_engine()
            try:
                await e.write(http_samples())
                rng = TimeRange.new(T0, T0 + HOUR)
                assert await e.label_names("http_requests", rng) == \
                    ["code", "job", "url"]
                assert await e.label_names("grpc_requests", rng) == ["job"]
                assert await e.label_names("nope", rng) == []
                assert await e.list_metrics(rng) == \
                    ["grpc_requests", "http_requests"]
            finally:
                await e.close()

        asyncio.run(go())

    def test_list_fields(self):
        async def go():
            e = await open_engine()
            try:
                await e.write([
                    sample("mem", [("h", "a")], T0 + 1000, 1.0),
                ])
                await e.write([Sample("mem", [Label("h", "a")], T0 + 1000,
                                      2.0, field_name="free")])
                rng = TimeRange.new(T0, T0 + HOUR)
                assert await e.list_fields("mem", rng) == ["free", "value"]
                assert await e.list_fields("nope", rng) == []
            finally:
                await e.close()

        asyncio.run(go())
