"""Device-layout sidecar tests (storage/sidecar.py): format round-trip,
cross-SST concat, and the engine-level guarantees — parity with the
parquet path, and fallback on any invalid/missing sidecar."""

import asyncio

import numpy as np
import pyarrow as pa

from horaedb_tpu.ops import encode
from horaedb_tpu.storage import sidecar

HOUR = 3_600_000
T0 = 1_700_000_000_000 - 1_700_000_000_000 % (2 * HOUR)


def _stamped_batch(n=1000, hosts=7, seed=0):
    rng = np.random.default_rng(seed)
    tsid = np.sort(rng.integers(0, 1 << 62, hosts).astype(np.uint64)
                   [rng.integers(0, hosts, n)])
    ts = T0 + rng.integers(0, HOUR, n).astype(np.int64)
    order = np.lexsort((ts, tsid))
    return pa.record_batch({
        "tsid": pa.array(tsid[order], type=pa.uint64()),
        "timestamp": pa.array(ts[order], type=pa.int64()),
        "value": pa.array(rng.random(n), type=pa.float64()),
        "__seq__": pa.array(np.full(n, 17, dtype=np.uint64)),
    })


class TestFormat:
    def test_round_trip(self):
        batch = _stamped_batch()
        blob = sidecar.build(batch)
        assert blob is not None
        got = sidecar.deserialize(blob)
        assert got is not None
        cols, n = got
        assert n == batch.num_rows
        # arrays decode back to the exact source values
        for name in batch.schema.names:
            arr, enc = cols[name]
            decoded = encode.decode_column(arr, enc, n)
            if name == "value":
                np.testing.assert_allclose(
                    decoded.to_numpy(),
                    batch.column(name).to_numpy().astype(np.float32))
            else:
                assert decoded.to_pylist() == \
                    batch.column(name).to_pylist()

    def test_string_dictionary_round_trip(self):
        names = np.array(["web-%d" % (i % 5) for i in range(100)],
                         dtype=object)
        batch = pa.record_batch({"host": pa.array(list(names)),
                                 "v": pa.array(np.arange(100.0))})
        blob = sidecar.build(batch)
        got = sidecar.deserialize(blob)
        assert got is not None
        cols, n = got
        arr, enc = cols["host"]
        assert enc.kind == "dict" and list(enc.dictionary) == \
            ["web-0", "web-1", "web-2", "web-3", "web-4"]
        assert encode.decode_column(arr, enc, n).to_pylist() == list(names)

    def test_want_subset_and_missing_column(self):
        blob = sidecar.build(_stamped_batch())
        got = sidecar.deserialize(blob, want={"timestamp"})
        assert got is not None and set(got[0]) == {"timestamp"}
        assert sidecar.deserialize(blob, want={"nope"}) is None

    def test_corrupt_blobs_return_none(self):
        blob = sidecar.build(_stamped_batch())
        assert sidecar.deserialize(b"") is None
        assert sidecar.deserialize(b"NOTMAGIC" + blob[8:]) is None
        assert sidecar.deserialize(blob[:40]) is None
        # header length pointing past the end
        bad = bytearray(blob)
        bad[8:12] = (2**31 - 1).to_bytes(4, "little")
        assert sidecar.deserialize(bytes(bad)) is None

    def test_null_column_not_encodable(self):
        batch = pa.record_batch({
            "a": pa.array([1, None, 3], type=pa.int64())})
        assert sidecar.build(batch) is None

    def test_reserved_column_skipped(self):
        batch = pa.record_batch({
            "a": pa.array([1, 2], type=pa.int64()),
            "__reserved__": pa.array([None, None], type=pa.uint64())})
        blob = sidecar.build(batch)
        got = sidecar.deserialize(blob)
        assert got is not None and set(got[0]) == {"a"}


class TestConcat:
    def _enc(self, **cols):
        batch = pa.record_batch(cols)
        return sidecar.encode_columns(batch)

    def test_offset_rebase(self):
        a = self._enc(ts=pa.array([100, 200], type=pa.int64()))
        b = self._enc(ts=pa.array([50, 300], type=pa.int64()))
        cols, encs, n = sidecar.concat_encoded([a, b], ["ts"])
        assert n == 4 and encs["ts"].kind == "offset"
        vals = cols["ts"].astype(np.int64) + encs["ts"].epoch
        assert vals.tolist() == [100, 200, 50, 300]

    def test_dict_union_remap(self):
        a = self._enc(id=pa.array(np.array([2**40, 2**50], dtype=np.uint64)))
        b = self._enc(id=pa.array(np.array([2**45, 2**50], dtype=np.uint64)))
        # force dict on both (span within one part may fit int32 — these
        # spans don't, so encode_column picked dict)
        assert a["id"][1].kind == "dict" and b["id"][1].kind == "dict"
        cols, encs, n = sidecar.concat_encoded([a, b], ["id"])
        assert encs["id"].kind == "dict"
        vals = encs["id"].dictionary[cols["id"]]
        assert vals.tolist() == [2**40, 2**50, 2**45, 2**50]

    def test_mixed_offset_dict_falls_back_to_dict(self):
        a = self._enc(x=pa.array([10, 20], type=pa.int64()))  # offset
        b = self._enc(x=pa.array(
            np.array([5, 2**40], dtype=np.int64)))  # dict (span)
        assert a["x"][1].kind == "offset" and b["x"][1].kind == "dict"
        cols, encs, n = sidecar.concat_encoded([a, b], ["x"])
        assert encs["x"].kind == "dict"
        vals = encs["x"].dictionary[cols["x"]]
        assert vals.tolist() == [10, 20, 5, 2**40]

    def test_string_union(self):
        a = self._enc(h=pa.array(["b", "c"]))
        b = self._enc(h=pa.array(["a", "c"]))
        cols, encs, n = sidecar.concat_encoded([a, b], ["h"])
        assert list(encs["h"].dictionary) == ["a", "b", "c"]
        assert encs["h"].dictionary[cols["h"]].tolist() == \
            ["b", "c", "a", "c"]


class TestEngineParity:
    """The same cold query must return identical results whether served
    from sidecars or the parquet decode path — and any broken sidecar
    must silently fall back."""

    def _dataset(self):
        import pyarrow as pa

        rng = np.random.default_rng(5)
        n, hosts = 6000, 11
        names = np.array([f"h{i:02d}" for i in range(hosts)], dtype=object)
        return pa.record_batch({
            "host": pa.array(names[rng.integers(0, hosts, n)]),
            "timestamp": pa.array(
                T0 + rng.integers(0, 4 * HOUR - 1, n), type=pa.int64()),
            "value": pa.array(rng.random(n) * 50, type=pa.float64()),
        })

    async def _open(self, store, name, use_sidecar=True):
        from horaedb_tpu.metric_engine import MetricEngine
        from horaedb_tpu.storage.config import StorageConfig, from_dict

        cfg = from_dict(StorageConfig, {
            "scan": {"use_sidecar": use_sidecar}})
        return await MetricEngine.open(name, store, segment_ms=2 * HOUR,
                                       config=cfg)

    def _run_query(self, use_sidecar, mutate=None, filters=None):
        from horaedb_tpu.objstore import MemoryObjectStore
        from horaedb_tpu.storage.types import TimeRange

        async def go():
            store = MemoryObjectStore()
            e = await self._open(store, "par", use_sidecar=use_sidecar)
            try:
                batch = self._dataset()
                # two overlapping writes per segment: multi-SST segments
                await e.write_arrow("cpu", ["host"], batch)
                await e.write_arrow("cpu", ["host"], batch.slice(0, 2000))
            finally:
                await e.close()
            if mutate is not None:
                await mutate(store)
            e = await self._open(store, "par", use_sidecar=use_sidecar)
            try:
                out = await e.query_downsample(
                    "cpu", filters or [],
                    TimeRange.new(T0, T0 + 4 * HOUR), bucket_ms=600_000)
                rows = await e.query(
                    "cpu", filters or [],
                    TimeRange.new(T0 + HOUR, T0 + 2 * HOUR))
                return out, rows.sort_by([("tsid", "ascending"),
                                          ("timestamp", "ascending")])
            finally:
                await e.close()

        return asyncio.run(go())

    def _assert_same(self, a, b):
        out_a, rows_a = a
        out_b, rows_b = b
        assert out_a["tsids"] == out_b["tsids"]
        assert set(out_a["aggs"]) == set(out_b["aggs"])
        for k in out_a["aggs"]:
            np.testing.assert_array_equal(np.asarray(out_a["aggs"][k]),
                                          np.asarray(out_b["aggs"][k]),
                                          err_msg=k)
        assert rows_a.equals(rows_b)

    def test_cold_parity_with_parquet_path(self):
        self._assert_same(self._run_query(True), self._run_query(False))

    def test_cold_parity_with_tag_filter(self):
        flt = [("host", "h03")]
        self._assert_same(self._run_query(True, filters=flt),
                          self._run_query(False, filters=flt))

    def test_corrupt_sidecar_falls_back(self):
        async def corrupt(store):
            for meta in await store.list("par/data/data/"):
                if meta.path.endswith(".enc"):
                    await store.put(meta.path, b"garbage-not-a-sidecar")

        # results must match the parquet path exactly despite every
        # sidecar being garbage
        self._assert_same(self._run_query(True, mutate=corrupt),
                          self._run_query(False))

    def test_missing_sidecar_falls_back(self):
        async def drop(store):
            for meta in await store.list("par/data/data/"):
                if meta.path.endswith(".enc"):
                    await store.delete(meta.path)

        self._assert_same(self._run_query(True, mutate=drop),
                          self._run_query(False))

    def test_sidecars_written_and_used(self):
        from horaedb_tpu.objstore import MemoryObjectStore
        from horaedb_tpu.storage.read import _STAGE_ROWS
        from horaedb_tpu.storage.types import TimeRange

        async def go():
            store = MemoryObjectStore()
            e = await self._open(store, "used")
            try:
                await e.write_arrow("cpu", ["host"], self._dataset())
            finally:
                await e.close()
            encs = [m for m in await store.list("used/data/data/")
                    if m.path.endswith(".enc")]
            ssts = [m for m in await store.list("used/data/data/")
                    if m.path.endswith(".sst")]
            assert len(encs) == len(ssts) > 0
            e = await self._open(store, "used")
            try:
                before = _STAGE_ROWS["sidecar_read"].value
                await e.query_downsample(
                    "cpu", [], TimeRange.new(T0, T0 + 4 * HOUR),
                    bucket_ms=600_000)
                after = _STAGE_ROWS["sidecar_read"].value
                assert after > before  # the cold scan used sidecars
            finally:
                await e.close()

        asyncio.run(go())


class TestBlockPruning:
    """load_sst_encoded must fetch only candidate row blocks for
    selective leaves — and stay row-level equivalent to the full load
    (the exact leaf mask still applies in assemble_parts)."""

    def _make(self, n=450_000, groups=500):
        rng = np.random.default_rng(13)
        tsid = np.sort(rng.integers(0, 1 << 62, groups).astype(np.uint64)
                       [rng.integers(0, groups, n)])
        ts = np.empty(n, dtype=np.int64)
        # ts ascending within each tsid run (PK order), global walk
        ts[:] = T0 + np.arange(n, dtype=np.int64) % (4 * HOUR)
        order = np.lexsort((ts, tsid))
        batch = pa.record_batch({
            "tsid": pa.array(tsid[order], type=pa.uint64()),
            "timestamp": pa.array(np.sort(ts)[order] % (4 * HOUR) + T0,
                                  type=pa.int64()),
            "value": pa.array(rng.random(n), type=pa.float64()),
            "__seq__": pa.array(np.full(n, 9, dtype=np.uint64)),
        })
        blob = sidecar.build(batch)
        assert blob is not None and len(blob) > 1 << 20
        return batch, blob

    def _store(self, blob):
        import asyncio

        from horaedb_tpu.objstore import MemoryObjectStore

        class CountingStore(MemoryObjectStore):
            def __init__(self):
                super().__init__()
                self.get_bytes = 0
                self.range_bytes = 0
                self.full_gets = 0

            async def get(self, path):
                b = await super().get(path)
                self.get_bytes += len(b)
                self.full_gets += 1
                return b

            async def get_range(self, path, start, end):
                # bypass MemoryObjectStore's get()-based range impl so
                # range reads don't count as full GETs
                data = await MemoryObjectStore.get(self, path)
                b = data[start:end]
                self.range_bytes += len(b)
                return b

        store = CountingStore()
        asyncio.run(store.put("s/data/1.enc", blob))
        return store

    def _load(self, store, leaves):
        import asyncio

        want = {"tsid", "timestamp", "value", "__seq__"}
        return asyncio.run(sidecar.load_sst_encoded(
            store, "s/data/1.enc", want, leaves))

    def test_point_leaf_parity_and_fewer_bytes(self):
        from horaedb_tpu.ops.filter import In

        batch, blob = self._make()
        full = sidecar.deserialize(blob)
        assert full is not None
        # pick a tsid from the middle of the file
        target = int(batch.column("tsid")[len(batch) // 2].as_py())
        leaves = [In("tsid", [target])]
        store = self._store(blob)
        got = self._load(store, leaves)
        assert got is not None
        cols, n = got
        assert 0 < n < batch.num_rows  # pruned, conservatively
        # exact equivalence AFTER the leaf mask
        es_pruned = sidecar.assemble_parts(
            [got], ["tsid", "timestamp", "value", "__seq__"], leaves)
        es_full = sidecar.assemble_parts(
            [full], ["tsid", "timestamp", "value", "__seq__"], leaves)
        assert es_pruned.n == es_full.n > 0
        for nm in es_full.names:
            a, b = es_pruned.columns[nm], es_full.columns[nm]
            ea, eb = es_pruned.encodings[nm], es_full.encodings[nm]
            if ea.kind == "dict":
                np.testing.assert_array_equal(ea.dictionary[a],
                                              eb.dictionary[b])
            elif ea.kind == "offset":
                np.testing.assert_array_equal(
                    a.astype(np.int64) + ea.epoch,
                    b.astype(np.int64) + eb.epoch)
            else:
                np.testing.assert_array_equal(a, b)
        # the point query must NOT download the whole object
        assert store.full_gets == 0
        assert store.range_bytes < len(blob) // 2

    def test_unselective_leaf_falls_back_to_whole_read(self):
        from horaedb_tpu.ops.filter import Ge

        batch, blob = self._make()
        store = self._store(blob)
        got = self._load(store, [Ge("timestamp", T0)])  # matches all
        assert got is not None and got[1] == batch.num_rows
        # pruning saved nothing -> ONE plain GET after the small probe
        # (zero-copy on host-backed stores; the probe bytes are noise)
        assert store.full_gets == 1
        assert store.range_bytes < len(blob) // 4

    def test_absent_key_returns_empty_part(self):
        from horaedb_tpu.ops.filter import Eq

        batch, blob = self._make()
        store = self._store(blob)
        # a tsid NOT in this SST's dictionary: every block prunes away
        # and the loader returns a valid EMPTY part, not an error
        got = self._load(store, [Eq("tsid", 12345)])
        assert got is not None and got[1] == 0
        es = sidecar.assemble_parts(
            [got], ["tsid", "timestamp", "value", "__seq__"],
            [Eq("tsid", 12345)])
        assert es is not None and es.n == 0
        assert store.full_gets == 0
        assert store.range_bytes < len(blob) // 4

    def test_no_leaves_full_get(self):
        _batch, blob = self._make()
        store = self._store(blob)
        got = self._load(store, [])
        assert got is not None and got[1] == _batch.num_rows
        assert store.full_gets == 1


class TestStreamedSidecar:
    """Segments over the stream threshold must serve from sidecar
    value-range windows — row-level identical to the parquet two-pass
    streamer, including cross-SST dedup inside windows."""

    def _run(self, use_sidecar, mutate=None):
        from horaedb_tpu.metric_engine import MetricEngine
        from horaedb_tpu.objstore import MemoryObjectStore
        from horaedb_tpu.storage.config import StorageConfig, from_dict
        from horaedb_tpu.storage.read import _STAGE_ROWS
        from horaedb_tpu.storage.types import TimeRange

        cfg_d = {"scan": {"stream_read_min_rows": 4096,
                          "max_window_rows": 2048,
                          "use_sidecar": use_sidecar}}

        async def go():
            rng = np.random.default_rng(17)
            n, hosts = 30_000, 20
            names = np.array([f"h{i:02d}" for i in range(hosts)],
                             dtype=object)
            batch = pa.record_batch({
                "host": pa.array(names[rng.integers(0, hosts, n)]),
                "timestamp": pa.array(
                    T0 + rng.integers(0, 2 * HOUR - 1, n),
                    type=pa.int64()),
                "value": pa.array(rng.random(n) * 9, type=pa.float64()),
            })
            store = MemoryObjectStore()
            cfg = from_dict(StorageConfig, cfg_d)
            e = await MetricEngine.open("ss", store, segment_ms=2 * HOUR,
                                        config=cfg)
            try:
                # two overlapping writes: dedup must work ACROSS the
                # streamed windows' SST runs
                await e.write_arrow("cpu", ["host"], batch)
                await e.write_arrow("cpu", ["host"], batch.slice(0, 9000))
            finally:
                await e.close()
            if mutate is not None:
                await mutate(store)
            e = await MetricEngine.open("ss", store, segment_ms=2 * HOUR,
                                        config=cfg)
            try:
                side0 = _STAGE_ROWS["sidecar_read"].value
                out = await e.query_downsample(
                    "cpu", [], TimeRange.new(T0, T0 + 2 * HOUR),
                    bucket_ms=600_000)
                rows = await e.query(
                    "cpu", [("host", "h07")],
                    TimeRange.new(T0, T0 + HOUR))
                side_rows = _STAGE_ROWS["sidecar_read"].value - side0
                return (out, rows.sort_by([("tsid", "ascending"),
                                           ("timestamp", "ascending")]),
                        side_rows)
            finally:
                await e.close()

        return asyncio.run(go())

    def test_streamed_parity_with_parquet_streamer(self):
        a_out, a_rows, a_side = self._run(True)
        b_out, b_rows, b_side = self._run(False)
        assert a_side > 0        # the sidecar stream actually served
        assert b_side == 0       # and the parquet leg really didn't
        assert a_out["tsids"] == b_out["tsids"]
        for k in a_out["aggs"]:
            np.testing.assert_array_equal(
                np.asarray(a_out["aggs"][k]),
                np.asarray(b_out["aggs"][k]), err_msg=k)
        assert a_rows.equals(b_rows) and a_rows.num_rows > 0

    def test_streamed_falls_back_on_corrupt_sidecar(self):
        async def corrupt(store):
            for meta in await store.list("ss/data/data/"):
                if meta.path.endswith(".enc"):
                    await store.put(meta.path, b"junk")

        a_out, a_rows, _ = self._run(True, mutate=corrupt)
        b_out, b_rows, _ = self._run(False)
        assert a_out["tsids"] == b_out["tsids"]
        for k in a_out["aggs"]:
            np.testing.assert_array_equal(
                np.asarray(a_out["aggs"][k]),
                np.asarray(b_out["aggs"][k]), err_msg=k)
        assert a_rows.equals(b_rows)

    def test_streamed_meshed_matches_single_device(self):
        """The mesh twin streams sidecar windows too; grids must match
        the single-device run (counts exact, sums to f32 ulp)."""
        a_out, _a_rows, a_side = self._run(True)
        m_out, _m_rows, m_side = self._run_meshed()
        assert a_side > 0 and m_side > 0
        assert a_out["tsids"] == m_out["tsids"]
        np.testing.assert_array_equal(
            np.asarray(a_out["aggs"]["count"]),
            np.asarray(m_out["aggs"]["count"]))
        for k in a_out["aggs"]:
            np.testing.assert_allclose(
                np.asarray(a_out["aggs"][k], dtype=np.float64),
                np.asarray(m_out["aggs"][k], dtype=np.float64),
                rtol=2e-5, atol=1e-5, err_msg=k)

    def _run_meshed(self):
        from horaedb_tpu.metric_engine import MetricEngine
        from horaedb_tpu.objstore import MemoryObjectStore
        from horaedb_tpu.storage.config import StorageConfig, from_dict
        from horaedb_tpu.storage.read import _STAGE_ROWS
        from horaedb_tpu.storage.types import TimeRange

        async def go():
            rng = np.random.default_rng(17)
            n, hosts = 30_000, 20
            names = np.array([f"h{i:02d}" for i in range(hosts)],
                             dtype=object)
            batch = pa.record_batch({
                "host": pa.array(names[rng.integers(0, hosts, n)]),
                "timestamp": pa.array(
                    T0 + rng.integers(0, 2 * HOUR - 1, n),
                    type=pa.int64()),
                "value": pa.array(rng.random(n) * 9, type=pa.float64()),
            })
            store = MemoryObjectStore()
            cfg = from_dict(StorageConfig, {
                "scan": {"stream_read_min_rows": 4096,
                         "max_window_rows": 2048,
                         "mesh": {"enabled": True}}})
            e = await MetricEngine.open("ssm", store, segment_ms=2 * HOUR,
                                        config=cfg)
            try:
                await e.write_arrow("cpu", ["host"], batch)
                await e.write_arrow("cpu", ["host"], batch.slice(0, 9000))
                side0 = _STAGE_ROWS["sidecar_read"].value
                out = await e.query_downsample(
                    "cpu", [], TimeRange.new(T0, T0 + 2 * HOUR),
                    bucket_ms=600_000)
                return out, _STAGE_ROWS["sidecar_read"].value - side0
            finally:
                await e.close()

        out, side = asyncio.run(go())
        return out, None, side
