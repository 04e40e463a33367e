"""Fused-aggregate, replay and stacking tests, and the program-level
contract of the 2-D scan mesh's segmented reduction on the
8-virtual-device CPU mesh (tests/test_mesh_scan.py covers the engine
half).
"""

import jax.numpy as jnp
import numpy as np
import pytest


class TestFusedAggregate:
    """The fused device-accumulated aggregate (the accelerator default —
    one query-global grid on device, nothing downloaded per flush) must
    match the per-flush host-fold parts path on the same data."""

    @pytest.mark.parametrize("seed", range(4))
    def test_fused_matches_parts_misaligned_ranges(self, seed, monkeypatch):
        """Property: with the query range start NOT aligned to bucket or
        segment boundaries, boundary buckets receive rows from TWO
        segments' windows — the fused scatter-add/min/max and the
        sequential last RMW must still equal the parts f64 fold (counts
        exact, floats to f32 ulp)."""
        import asyncio

        import pyarrow as pa

        from horaedb_tpu.metric_engine import MetricEngine
        from horaedb_tpu.objstore import MemoryObjectStore
        from horaedb_tpu.storage.config import StorageConfig, from_dict
        from horaedb_tpu.storage.types import TimeRange

        SEG = 7_200_000
        T0 = (1_700_000_000_000 // SEG) * SEG
        rng = np.random.default_rng(100 + seed)
        # deliberately awkward: range start offset by a non-bucket
        # multiple, bucket width that does not divide the segment
        q_start = T0 + int(rng.integers(1, 500_000))
        bucket_ms = int(rng.choice([70_000, 130_000, 410_000]))
        span = int(rng.integers(2, 4)) * SEG - int(rng.integers(0, 90_000))

        async def run(fused: str):
            monkeypatch.setenv("HORAEDB_FUSED_AGG", fused)
            cfg = from_dict(StorageConfig, {
                "scan": {"max_window_rows": 700}})
            e = await MetricEngine.open(f"mis{seed}{fused}",
                                        MemoryObjectStore(),
                                        segment_ms=SEG, config=cfg)
            try:
                n, hosts = 5000, 13
                names = np.array([f"h{i:02d}" for i in range(hosts)],
                                 dtype=object)
                batch = pa.record_batch({
                    "host": pa.array(names[rng2.integers(0, hosts, n)]),
                    "timestamp": pa.array(
                        T0 + rng2.integers(0, 3 * SEG, n),
                        type=pa.int64()),
                    "value": pa.array(rng2.random(n) * 50,
                                      type=pa.float64()),
                })
                await e.write_arrow("cpu", ["host"], batch)
                return await e.query_downsample(
                    "cpu", [], TimeRange.new(q_start, q_start + span),
                    bucket_ms=bucket_ms)
            finally:
                await e.close()

        rng2 = np.random.default_rng(200 + seed)
        parts = asyncio.run(run("0"))
        rng2 = np.random.default_rng(200 + seed)  # identical data
        fused = asyncio.run(run("1"))
        assert parts["tsids"] == fused["tsids"]
        np.testing.assert_array_equal(
            np.asarray(parts["aggs"]["count"]),
            np.asarray(fused["aggs"]["count"]))
        for key in ("sum", "min", "max", "avg", "last", "last_ts"):
            np.testing.assert_allclose(
                np.asarray(parts["aggs"][key], dtype=np.float64),
                np.asarray(fused["aggs"][key], dtype=np.float64),
                rtol=1e-6, err_msg=f"{key} seed={seed}")

    def test_fused_matches_parts_path(self, monkeypatch):
        import asyncio

        import pyarrow as pa

        from horaedb_tpu.metric_engine import MetricEngine
        from horaedb_tpu.objstore import MemoryObjectStore
        from horaedb_tpu.storage.config import StorageConfig, from_dict
        from horaedb_tpu.storage.types import TimeRange

        T0 = (1_700_000_000_000 // 7_200_000) * 7_200_000
        SPAN = 6 * 3_600_000  # 3 segments

        async def run():
            cfg = from_dict(StorageConfig, {
                "scan": {"max_window_rows": 512}})  # several windows/seg
            e = await MetricEngine.open("fused", MemoryObjectStore(),
                                        segment_ms=7_200_000, config=cfg)
            try:
                rng = np.random.default_rng(7)
                n, hosts = 6000, 17
                names = np.array([f"h{i:02d}" for i in range(hosts)],
                                 dtype=object)
                sel = rng.integers(0, hosts, n)
                batch = pa.record_batch({
                    "host": pa.array(names[sel]),
                    "timestamp": pa.array(
                        T0 + rng.integers(0, SPAN - 1, n), type=pa.int64()),
                    "value": pa.array(rng.random(n) * 100,
                                      type=pa.float64()),
                })
                await e.write_arrow("cpu", ["host"], batch)
                # duplicate overwrite batch: dedup must hold in both paths
                await e.write_arrow("cpu", ["host"], batch)
                return await e.query_downsample(
                    "cpu", [], TimeRange.new(T0, T0 + SPAN),
                    bucket_ms=600_000)
            finally:
                await e.close()

        results = {}
        for mode in ("0", "1"):
            monkeypatch.setenv("HORAEDB_FUSED_AGG", mode)
            results[mode] = asyncio.run(run())
        parts, fused = results["0"], results["1"]
        assert parts["tsids"] == fused["tsids"]
        np.testing.assert_array_equal(
            np.asarray(parts["aggs"]["count"]),
            np.asarray(fused["aggs"]["count"]))
        for key in ("sum", "min", "max", "avg", "last", "last_ts"):
            np.testing.assert_allclose(
                np.asarray(parts["aggs"][key], dtype=np.float64),
                np.asarray(fused["aggs"][key], dtype=np.float64),
                rtol=1e-6, err_msg=key)


class TestFusedReplay:
    """Repeat fused queries replay the recorded round composition in one
    pool dispatch (ROADMAP r3 priority 1) — and fall back to the full
    path the moment any underlying cache entry moves."""

    @staticmethod
    async def _open_engine(name):
        from horaedb_tpu.metric_engine import MetricEngine
        from horaedb_tpu.objstore import MemoryObjectStore
        from horaedb_tpu.storage.config import StorageConfig, from_dict

        # more windows than a round holds, so the aggregate runs as
        # ROUNDS: ONE small round is carried by one call and records no
        # replay (tests/test_fused_one_call.py)
        cfg = from_dict(StorageConfig, {
            "scan": {"max_window_rows": 512, "agg_batch_windows": 4}})
        return await MetricEngine.open(name, MemoryObjectStore(),
                                       segment_ms=7_200_000, config=cfg)

    @staticmethod
    def _mkbatch(seed, n=4000, hosts=11, t0=None, span=None):
        import pyarrow as pa

        rng = np.random.default_rng(seed)
        names = np.array([f"h{i:02d}" for i in range(hosts)], dtype=object)
        sel = rng.integers(0, hosts, n)
        return pa.record_batch({
            "host": pa.array(names[sel]),
            "timestamp": pa.array(t0 + rng.integers(0, span - 1, n),
                                  type=pa.int64()),
            "value": pa.array(rng.random(n) * 100, type=pa.float64()),
        })

    def test_replay_hit_matches_full_path(self, monkeypatch):
        import asyncio

        from horaedb_tpu.storage.types import TimeRange

        monkeypatch.setenv("HORAEDB_FUSED_AGG", "1")
        T0 = (1_700_000_000_000 // 7_200_000) * 7_200_000
        SPAN = 6 * 3_600_000

        async def run():
            e = await self._open_engine("replay1")
            try:
                await e.write_arrow("cpu", ["host"],
                                    self._mkbatch(3, t0=T0, span=SPAN))
                reader = e.tables["data"].reader

                async def q():
                    return await e.query_downsample(
                        "cpu", [], TimeRange.new(T0, T0 + SPAN),
                        bucket_ms=600_000)

                first = await q()
                assert reader._replay_hits == 0
                second = await q()
                assert reader._replay_hits == 1, \
                    "repeat fused query must take the replay path"
                third = await q()
                assert reader._replay_hits == 2
                return first, second, third
            finally:
                await e.close()

        first, second, third = asyncio.run(run())
        assert first["tsids"] == second["tsids"] == third["tsids"]
        for key in first["aggs"]:
            np.testing.assert_array_equal(
                np.asarray(first["aggs"][key]),
                np.asarray(second["aggs"][key]), err_msg=key)
            np.testing.assert_array_equal(
                np.asarray(second["aggs"][key]),
                np.asarray(third["aggs"][key]), err_msg=key)

    def test_replay_with_multiple_rounds_per_segment(self, monkeypatch):
        """One segment spanning several accumulate rounds of equal
        (batch_w, cap): the chunk-offset component of the stack key
        keeps the rounds distinct, so the repeat query still replays
        (regression: colliding keys evicted each other and every
        replay missed)."""
        import asyncio

        from horaedb_tpu.metric_engine import MetricEngine
        from horaedb_tpu.objstore import MemoryObjectStore
        from horaedb_tpu.storage.config import StorageConfig, from_dict
        from horaedb_tpu.storage.types import TimeRange

        monkeypatch.setenv("HORAEDB_FUSED_AGG", "1")
        T0 = (1_700_000_000_000 // 7_200_000) * 7_200_000
        SPAN = 2 * 3_600_000  # ONE segment

        async def run():
            cfg = from_dict(StorageConfig, {
                # 6000 rows / 512-row windows = 12 windows; 2 per round
                # = 6 rounds, all sharing (seg0, batch_w, cap)
                "scan": {"max_window_rows": 512, "agg_batch_windows": 2}})
            e = await MetricEngine.open("replay4", MemoryObjectStore(),
                                        segment_ms=7_200_000, config=cfg)
            try:
                await e.write_arrow(
                    "cpu", ["host"],
                    self._mkbatch(8, n=6000, t0=T0, span=SPAN))
                reader = e.tables["data"].reader

                async def q():
                    return await e.query_downsample(
                        "cpu", [], TimeRange.new(T0, T0 + SPAN),
                        bucket_ms=600_000)

                first = await q()
                second = await q()
                assert reader._replay_hits == 1, \
                    "multi-round segments must still replay"
                return first, second
            finally:
                await e.close()

        first, second = asyncio.run(run())
        for key in first["aggs"]:
            np.testing.assert_array_equal(
                np.asarray(first["aggs"][key]),
                np.asarray(second["aggs"][key]), err_msg=key)

    def test_replay_invalidated_by_write(self, monkeypatch):
        """A write changes the segment's SST set: the replay key no
        longer matches and the fresh rows must appear in the result."""
        import asyncio

        from horaedb_tpu.storage.types import TimeRange

        monkeypatch.setenv("HORAEDB_FUSED_AGG", "1")
        T0 = (1_700_000_000_000 // 7_200_000) * 7_200_000
        SPAN = 2 * 3_600_000  # one segment

        async def run():
            e = await self._open_engine("replay2")
            try:
                await e.write_arrow("cpu", ["host"],
                                    self._mkbatch(4, t0=T0, span=SPAN))
                reader = e.tables["data"].reader

                async def q():
                    return await e.query_downsample(
                        "cpu", [], TimeRange.new(T0, T0 + SPAN),
                        bucket_ms=600_000, aggs=("sum",))

                await q()
                before = await q()
                hits = reader._replay_hits
                assert hits >= 1
                await e.write_arrow("cpu", ["host"],
                                    self._mkbatch(5, t0=T0, span=SPAN))
                after = await q()
                assert reader._replay_hits == hits, \
                    "stale replay entry must not serve post-write queries"
                return before, after
            finally:
                await e.close()

        before, after = asyncio.run(run())
        tot_before = np.nansum(np.asarray(before["aggs"]["count"]))
        tot_after = np.nansum(np.asarray(after["aggs"]["count"]))
        assert tot_after > tot_before  # the second batch's rows arrived

    def test_replay_falls_back_on_evictions(self, monkeypatch):
        """Scan-cache clear and stack-LRU eviction each break the
        recorded identity: the query silently re-runs the full path and
        re-records, still returning correct grids."""
        import asyncio

        from horaedb_tpu.storage.types import TimeRange

        monkeypatch.setenv("HORAEDB_FUSED_AGG", "1")
        T0 = (1_700_000_000_000 // 7_200_000) * 7_200_000
        SPAN = 4 * 3_600_000

        async def run():
            e = await self._open_engine("replay3")
            try:
                await e.write_arrow("cpu", ["host"],
                                    self._mkbatch(6, t0=T0, span=SPAN))
                reader = e.tables["data"].reader

                async def q():
                    return await e.query_downsample(
                        "cpu", [], TimeRange.new(T0, T0 + SPAN),
                        bucket_ms=600_000)

                base = await q()
                await q()
                hits = reader._replay_hits

                # stack LRU eviction alone -> replay validation fails
                with reader._stack_cache_lock:
                    reader._stack_cache.clear()
                    reader._stack_cache_bytes = 0
                after_stack = await q()
                assert reader._replay_hits == hits

                # re-recorded: next query replays again
                await q()
                assert reader._replay_hits == hits + 1

                # full scan-cache clear -> windows re-read, still correct
                reader.scan_cache.clear()
                after_clear = await q()
                assert reader._replay_hits == hits + 1
                return base, after_stack, after_clear
            finally:
                await e.close()

        base, after_stack, after_clear = asyncio.run(run())
        for other in (after_stack, after_clear):
            assert base["tsids"] == other["tsids"]
            for key in base["aggs"]:
                np.testing.assert_array_equal(
                    np.asarray(base["aggs"][key]),
                    np.asarray(other["aggs"][key]), err_msg=key)


class TestVariedRangeStacking:
    """Varied-range queries (distinct specs -> full-stack misses) must
    produce identical grids whether rounds stack from per-window
    memoized device columns (accelerator default) or the numpy bulk
    path, and must reuse the range-independent window memos."""

    def _run(self, monkeypatch, devcol: str):
        import asyncio

        import pyarrow as pa

        from horaedb_tpu.metric_engine import MetricEngine
        from horaedb_tpu.objstore import MemoryObjectStore
        from horaedb_tpu.storage.config import StorageConfig, from_dict
        from horaedb_tpu.storage.types import TimeRange

        monkeypatch.setenv("HORAEDB_FUSED_AGG", "1")
        monkeypatch.setenv("HORAEDB_DEVCOL_STACK", devcol)
        T0 = (1_700_000_000_000 // 7_200_000) * 7_200_000
        SPAN = 8 * 3_600_000  # 4 segments

        async def go():
            # 16 windows in rounds of 8: the stacks are the ROUNDS'
            # (one small round goes up as one call's numpy arguments)
            cfg = from_dict(StorageConfig, {
                "scan": {"max_window_rows": 512, "agg_batch_windows": 8}})
            e = await MetricEngine.open(f"varied{devcol}",
                                        MemoryObjectStore(),
                                        segment_ms=7_200_000, config=cfg)
            try:
                rng = np.random.default_rng(11)
                n, hosts = 8000, 13
                names = np.array([f"h{i:02d}" for i in range(hosts)],
                                 dtype=object)
                sel = rng.integers(0, hosts, n)
                batch = pa.record_batch({
                    "host": pa.array(names[sel]),
                    "timestamp": pa.array(
                        T0 + rng.integers(0, SPAN - 1, n),
                        type=pa.int64()),
                    "value": pa.array(rng.random(n) * 100,
                                      type=pa.float64()),
                })
                await e.write_arrow("cpu", ["host"], batch)
                outs = []
                # rotating bucket-aligned half-span ranges + full range
                for s, d in ((0, SPAN), (0, SPAN // 2),
                             (SPAN // 4, SPAN // 2),
                             (SPAN // 2, SPAN // 2)):
                    outs.append(await e.query_downsample(
                        "cpu", [], TimeRange.new(T0 + s, T0 + s + d),
                        bucket_ms=600_000))
                return outs
            finally:
                await e.close()

        return asyncio.run(go())

    def test_devcol_stacking_matches_numpy_path(self, monkeypatch):
        a = self._run(monkeypatch, "0")
        b = self._run(monkeypatch, "1")
        for i, (x, y) in enumerate(zip(a, b)):
            assert x["tsids"] == y["tsids"], f"range {i}"
            for key in x["aggs"]:
                np.testing.assert_array_equal(
                    np.asarray(x["aggs"][key]),
                    np.asarray(y["aggs"][key]),
                    err_msg=f"range {i} {key}")

    def test_varied_ranges_reuse_window_memos(self, monkeypatch):
        """After a full-range query, a different (aligned) range must
        hit both the window-groups memo and the device-column memo —
        the only per-round uploads left are remap/shift/lo."""
        import asyncio

        import pyarrow as pa

        from horaedb_tpu.metric_engine import MetricEngine
        from horaedb_tpu.objstore import MemoryObjectStore
        from horaedb_tpu.storage.config import StorageConfig, from_dict
        from horaedb_tpu.storage.types import TimeRange

        monkeypatch.setenv("HORAEDB_FUSED_AGG", "1")
        monkeypatch.setenv("HORAEDB_DEVCOL_STACK", "1")
        T0 = (1_700_000_000_000 // 7_200_000) * 7_200_000
        SPAN = 4 * 3_600_000

        async def go():
            cfg = from_dict(StorageConfig, {
                "scan": {"max_window_rows": 4096,
                         "agg_batch_windows": 1}})  # two rounds
            e = await MetricEngine.open("variedmemo", MemoryObjectStore(),
                                        segment_ms=7_200_000, config=cfg)
            try:
                rng = np.random.default_rng(12)
                n, hosts = 5000, 7
                names = np.array([f"h{i}" for i in range(hosts)],
                                 dtype=object)
                batch = pa.record_batch({
                    "host": pa.array(names[rng.integers(0, hosts, n)]),
                    "timestamp": pa.array(
                        T0 + rng.integers(0, SPAN - 1, n),
                        type=pa.int64()),
                    "value": pa.array(rng.random(n), type=pa.float64()),
                })
                await e.write_arrow("cpu", ["host"], batch)
                reader = e.tables["data"].reader

                await e.query_downsample(
                    "cpu", [], TimeRange.new(T0, T0 + SPAN),
                    bucket_ms=600_000)
                # snapshot the memoized device cols per cached window
                before = {}
                for key in list(reader.scan_cache._entries):
                    for w in reader.scan_cache.get(key):
                        for mk, mv in w.memo.items():
                            before[(id(w), mk)] = mv
                assert any(mk[0] == "dev_cols" for _, mk in before)

                await e.query_downsample(
                    "cpu", [], TimeRange.new(T0, T0 + SPAN // 2),
                    bucket_ms=600_000)
                # same objects still memoized — nothing was rebuilt
                for key in list(reader.scan_cache._entries):
                    for w in reader.scan_cache.get(key):
                        for mk, mv in w.memo.items():
                            if (id(w), mk) in before:
                                assert mv is before[(id(w), mk)], mk
            finally:
                await e.close()

        asyncio.run(go())


    def test_device_parts_kernel_matches_numpy_twin(self, monkeypatch):
        """HORAEDB_HOST_AGG=0 forces the vmap device kernel
        (_batched_window_partials_jit) on the CPU backend, pinning it
        against the numpy twin that is the CPU default — the kernel must
        keep CI coverage even though CPU runs prefer the host path."""
        monkeypatch.setenv("HORAEDB_HOST_AGG", "1")
        host = self._run(monkeypatch, "0")
        monkeypatch.setenv("HORAEDB_HOST_AGG", "0")
        dev = self._run(monkeypatch, "0")
        for i, (x, y) in enumerate(zip(host, dev)):
            assert x["tsids"] == y["tsids"], f"range {i}"
            np.testing.assert_array_equal(
                np.asarray(x["aggs"]["count"]),
                np.asarray(y["aggs"]["count"]), err_msg=f"range {i}")
            for key in x["aggs"]:
                # device kernel accumulates f32; numpy twin f64
                np.testing.assert_allclose(
                    np.asarray(x["aggs"][key]),
                    np.asarray(y["aggs"][key]),
                    rtol=2e-5, atol=1e-5, err_msg=f"range {i} {key}")


class TestMeshRunPartials:
    """Program-level contract of the 2-D scan mesh's segmented
    reduction (parallel.scan.mesh_run_partials): each time slot's
    output equals its segment-run prefix combined with the pairwise
    op, byte-exactly — the engine-level bit-identity claim rests on
    this (tests/test_mesh_scan.py covers the end-to-end half)."""

    @pytest.mark.parametrize("seed", [0, 1])
    def test_segmented_combine_byte_exact(self, seed):
        from horaedb_tpu.ops.downsample import (
            ALL_AGGS,
            window_local_partials,
        )
        from horaedb_tpu.parallel.mesh import scan_mesh
        from horaedb_tpu.parallel.scan import (
            mesh_run_partials,
            shard_time_axis,
        )

        mesh2 = scan_mesh(4, 2)
        T, CAPW, GW, W = 4, 64, 8, 16
        rng = np.random.default_rng(seed)
        ts = rng.integers(0, W * 100, (T, CAPW)).astype(np.int32)
        gid = rng.integers(-1, GW, (T, CAPW)).astype(np.int32)
        vals = (rng.random((T, CAPW)) * 50).astype(np.float32)
        remap = np.tile(np.arange(GW, dtype=np.int32), (T, 1))
        zeros = np.zeros(T, dtype=np.int32)
        seg_ids = np.array([0, 0, 1, 2], dtype=np.int32)
        fn = mesh_run_partials(mesh2, num_groups=GW, num_buckets=W,
                               which=ALL_AGGS)
        out = fn(shard_time_axis(mesh2, ts), shard_time_axis(mesh2, gid),
                 shard_time_axis(mesh2, vals),
                 shard_time_axis(mesh2, remap),
                 shard_time_axis(mesh2, zeros),
                 shard_time_axis(mesh2, zeros),
                 shard_time_axis(mesh2, seg_ids), jnp.int32(W),
                 jnp.asarray([100], dtype=jnp.int32))

        def one(t):
            return {k: np.asarray(v) for k, v in window_local_partials(
                jnp.asarray(ts[t]), jnp.asarray(gid[t]),
                jnp.asarray(vals[t]), jnp.asarray(remap[t]),
                jnp.int32(0), jnp.int32(0), jnp.int32(W), jnp.int32(100),
                num_groups=GW, num_buckets=W, which=ALL_AGGS).items()}

        def comb(cur, prev):
            got = {"count": cur["count"] + prev["count"],
                   "sum": cur["sum"] + prev["sum"],
                   "min": np.minimum(cur["min"], prev["min"]),
                   "max": np.maximum(cur["max"], prev["max"])}
            take = cur["last_ts"] >= prev["last_ts"]
            got["last"] = np.where(take, cur["last"], prev["last"])
            got["last_ts"] = np.where(take, cur["last_ts"],
                                      prev["last_ts"])
            return got

        ps = [one(t) for t in range(T)]
        # run 0 = slots 0..1, run 1 = slot 2, run 2 = slot 3: tails
        # hold the whole run, mid-run slots the inclusive prefix
        want = {0: ps[0], 1: comb(ps[1], ps[0]), 2: ps[2], 3: ps[3]}
        for t, ref in want.items():
            for k in ref:
                got = np.asarray(out[k][t])
                assert got.tobytes() == ref[k].astype(
                    got.dtype).tobytes(), (t, k)
