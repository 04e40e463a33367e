"""Device plane (ISSUE 20): the process-global device profiler
(common/deviceprof.py) — the compile ledger behind every deviceprof.jit
seam, recompile-storm episodes naming the churning cache-key dimension,
per-trace device twins on cold scans (absent on memo-served repeats),
transfer accounting, clear-on-close zeroing, the /debug/device + /stats
surfaces, the [deviceprof] config keys, and the bare-jax.jit lint rule
with its enumerate-and-assert ground truth."""

import asyncio
import contextlib
import logging
import os
import random

import numpy as np
import pyarrow as pa
import pytest

import jax.numpy as jnp

from horaedb_tpu.common import ReadableDuration, deviceprof
from horaedb_tpu.common import runtimes as runtimes_mod
from horaedb_tpu.common.deviceprof import DeviceProfiler
from horaedb_tpu.common.memledger import ledger as memledger
from horaedb_tpu.metric_engine import MetricEngine
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.storage.config import (
    StorageConfig,
    ThreadsConfig,
    from_dict,
)
from horaedb_tpu.storage.read import AggregateSpec, ScanRequest
from horaedb_tpu.storage.storage import CloudObjectStorage, WriteRequest
from horaedb_tpu.storage.types import TimeRange
from horaedb_tpu.utils import registry, tracing

T0 = 1_700_000_000_000
HOUR = 3_600_000
SEGMENT_MS = 3_600_000
SCHEMA = pa.schema([("k", pa.string()), ("ts", pa.int64()),
                    ("v", pa.float64())])


def run(coro):
    return asyncio.run(coro)


def _arr(n, seed=0):
    return jnp.asarray(np.arange(n, dtype=np.float32) + seed)


# ---- storage-level rig: a device-decode scan is the real cold path ----------


def _runtimes():
    return runtimes_mod.from_config(ThreadsConfig())


async def _open_device_storage(rt):
    cfg = from_dict(StorageConfig, {
        "scheduler": {"schedule_interval": "1h", "input_sst_min_num": 2},
        "scan": {"decode": {"mode": "device"}},
    })
    cfg.manifest.merge_interval = ReadableDuration.parse("1h")
    cfg.scrub.interval = ReadableDuration.parse("1h")
    return await CloudObjectStorage.open(
        "db", SEGMENT_MS, MemoryObjectStore(), SCHEMA, 2, cfg,
        runtimes=rt)


async def _write_segments(s, rng, segments=2, rows_per=200):
    for seg in range(segments):
        rows = [(f"k{rng.randint(0, 5)}",
                 seg * SEGMENT_MS + rng.randrange(0, SEGMENT_MS - 1000,
                                                  250),
                 float(rng.randint(0, 10**6))) for _ in range(rows_per)]
        lo = min(r[1] for r in rows)
        hi = max(r[1] for r in rows) + 1
        k, t, v = zip(*rows)
        b = pa.record_batch(
            [pa.array(list(k)), pa.array(list(t), type=pa.int64()),
             pa.array(list(v), type=pa.float64())], schema=SCHEMA)
        await s.write(WriteRequest(b, TimeRange.new(lo, hi)))


def _clear_caches(s):
    s.reader.scan_cache.clear()
    s.reader.encoded_cache.clear()
    s.reader.parts_memo.clear()


def _agg_scan():
    spec = AggregateSpec(group_col="k", ts_col="ts", value_col="v",
                         range_start=0, bucket_ms=60_000,
                         num_buckets=120, which=("avg", "max"))
    return ScanRequest(range=TimeRange.new(0, 2 * SEGMENT_MS)), spec


@contextlib.contextmanager
def _force_xla_agg():
    old = os.environ.get("HORAEDB_HOST_AGG")
    os.environ["HORAEDB_HOST_AGG"] = "0"
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("HORAEDB_HOST_AGG", None)
        else:
            os.environ["HORAEDB_HOST_AGG"] = old


class TestCompileLedger:
    def test_cold_compiles_warm_dispatches(self):
        prof = DeviceProfiler()
        f = prof.jit(lambda x: x + 1, name="unit_cold_warm")
        f(_arr(8))
        f(_arr(8, seed=1))  # same shape: cached dispatch
        rec = prof._record("unit_cold_warm")
        assert rec.compiles == 1
        assert rec.dispatches == 1
        assert rec.compile_seconds > 0
        f(_arr(16))  # new shape: recompile
        assert rec.compiles == 2
        # the triggering key names the dimensions jit keys on
        assert dict(rec.last_key)["a0.shape"] == (16,)

    def test_programs_sharing_a_name_each_book_their_compile(self):
        """Builders mint many jitted programs under one ledger name
        (the mesh rounds): each one's first call is a compile, not a
        dispatch — on the chip that is where a cold round's seconds
        go."""
        prof = DeviceProfiler()
        f = prof.jit(lambda x: x + 1, name="unit_shared")
        g = prof.jit(lambda x: x + 2, name="unit_shared")
        f(_arr(8))
        g(_arr(8))
        g(_arr(8, seed=1))
        rec = prof._record("unit_shared")
        assert (rec.compiles, rec.dispatches) == (2, 1)

    def test_decorator_forms_register(self):
        prof = DeviceProfiler()

        @prof.jit
        def unit_bare(x):
            return x * 2

        @prof.jit(static_argnames=("k",))
        def unit_static(x, k):
            return x[:k]

        unit_bare(_arr(4))
        unit_static(_arr(8), k=3)
        names = {r.name for r in prof.records()}
        assert {"unit_bare", "unit_static"} <= names

    def test_disabled_profiler_is_passthrough(self):
        prof = DeviceProfiler()
        prof.configure(enabled=False)
        f = prof.jit(lambda x: x - 1, name="unit_disabled")
        out = f(_arr(4))
        assert out.shape == (4,)
        assert prof._record("unit_disabled").compiles == 0

    def test_aot_attributes_forward(self):
        """lower/eval_shape keep working through the wrapper (AOT call
        sites must not care whether the seam is profiled)."""
        prof = DeviceProfiler()
        f = prof.jit(lambda x: x + 1, name="unit_aot")
        shape = f.eval_shape(_arr(8))
        assert tuple(shape.shape) == (8,)


class TestStorms:
    def _storm_prof(self):
        t = [0.0]
        prof = DeviceProfiler(clock=lambda: t[0])
        prof.configure(storm_threshold=3, storm_window_s=60.0)
        return prof, t

    def test_storm_fires_once_per_episode(self, caplog):
        prof, t = self._storm_prof()
        f = prof.jit(lambda x: x * 2, name="unit_storm")
        rec = prof._record("unit_storm")
        with caplog.at_level(logging.WARNING, "horaedb_tpu.trace.slow"):
            for n in range(3, 9):  # six shapes, six compiles, one window
                f(_arr(n))
        assert rec.compiles == 6
        assert rec.storms == 1  # one episode, one flag
        assert rec.storm_active
        storm_lines = [r.message for r in caplog.records
                       if "recompile storm" in r.message]
        assert len(storm_lines) == 1
        # the slow log names the churning key dimension
        assert "a0.shape" in storm_lines[0]
        assert "unit_storm" in storm_lines[0]

    def test_window_drain_starts_new_episode(self):
        prof, t = self._storm_prof()
        f = prof.jit(lambda x: x * 3, name="unit_storm2")
        rec = prof._record("unit_storm2")
        for n in range(3, 7):
            f(_arr(n))
        assert rec.storms == 1
        t[0] = 1000.0  # window drains; episode over
        for n in range(20, 24):
            f(_arr(n))
        assert rec.storms == 2
        assert not rec.storm_active or rec.storms == 2


class TestTransferAccounting:
    def test_device_put_charges_h2d(self):
        before = deviceprof.profiler.transfer["h2d"]["bytes"]
        deviceprof.device_put(np.zeros(1024, dtype=np.float32))
        after = deviceprof.profiler.transfer["h2d"]["bytes"]
        assert after - before == 4096

    def test_charge_d2h_and_trace_twin(self):
        tracing.recorder.configure(enabled=True, sample_rate=1.0)
        trace = tracing.recorder.start("/query")
        with tracing.trace_scope(trace):
            deviceprof.charge_transfer("d2h", 2048)
        tracing.recorder.finish(trace)
        assert trace.counters.get("device_d2h_bytes") == 2048.0

    def test_encode_batch_charges_via_caller_put(self):
        import pyarrow as pa

        from horaedb_tpu.ops import encode

        batch = pa.RecordBatch.from_pydict({
            "ts": pa.array(np.arange(100, dtype=np.int64)),
            "val": pa.array(np.ones(100), type=pa.float64())})
        before = deviceprof.profiler.transfer["h2d"]["bytes"]
        import jax

        encode.encode_batch(batch, device_put=jax.device_put)
        mid = deviceprof.profiler.transfer["h2d"]["bytes"]
        assert mid > before  # a plain jax put is charged at the seam
        # the profiler's own put must not double-count
        encode.encode_batch(batch, device_put=deviceprof.device_put)
        per_batch = mid - before
        assert (deviceprof.profiler.transfer["h2d"]["bytes"] - mid
                == per_batch)


class TestRoundTimeline:
    def test_record_round_quality_fields(self):
        prof = DeviceProfiler(clock=lambda: 42.0)
        prof.record_round("mesh_run", slots=3, capacity=4,
                          rows_per_shard=[100, 50, 150],
                          padding_rows=212, upload_bytes=4096,
                          seconds=0.01)
        (r,) = prof.snapshot()["rounds"]
        assert r["fill_ratio"] == 0.75
        assert r["padding_rows"] == 212
        assert r["row_imbalance"] == 1.5  # 150 / mean(100)
        assert r["shard_rows"] == [100, 50, 150]
        assert not r["stack_hit"]

    def test_rounds_ring_bounded(self):
        prof = DeviceProfiler()
        prof.configure(rounds_kept=4)
        for i in range(10):
            prof.record_round("mesh_run", slots=i, capacity=16)
        rounds = prof.snapshot()["rounds"]
        assert len(rounds) == 4
        assert rounds[-1]["slots"] == 9


class TestTraceTwins:
    def test_cold_scan_records_twins_memo_repeat_does_not(self):
        """A cold device-decode aggregate pays device work, so its
        trace carries the stage_device_* and transfer twins and it
        passes the sync seam (`scan.device_wait` observes it: the one
        record of the wait, a span where something still ran); the
        identical repeat is memo-served — no jit dispatch, no twins,
        no seam (the attribution proves WHERE wall went, so a scan
        that did no device work must show none)."""
        async def go():
            rt = _runtimes()
            s = await _open_device_storage(rt)
            try:
                await _write_segments(s, random.Random(7))
                _clear_caches(s)
                tracing.recorder.configure(enabled=True, sample_rate=1.0)

                def seams_passed() -> int:
                    return sum(
                        v for name, labels, v in registry.family(
                            "scan_phase_seconds").samples()
                        if name.endswith("_count")
                        and labels.get("phase") == "scan.device_wait")

                async def traced_scan():
                    before = seams_passed()
                    trace = tracing.recorder.start("/scan")
                    with tracing.trace_scope(trace):
                        await s.scan_aggregate(*_agg_scan())
                    tracing.recorder.finish(trace)
                    out = {k: v for k, v in trace.counters.items()
                           if k in ("stage_device_compile_ms",
                                    "stage_device_dispatch_ms",
                                    "device_h2d_bytes",
                                    "device_d2h_bytes")}
                    if seams_passed() > before:
                        out["scan.device_wait"] = seams_passed() - before
                    return out

                with _force_xla_agg():
                    cold = await traced_scan()
                    # the fused dispatch compiled or dispatched, synced,
                    # and moved bytes both ways — all on the trace
                    assert ("stage_device_compile_ms" in cold
                            or "stage_device_dispatch_ms" in cold), cold
                    assert "scan.device_wait" in cold, cold
                    assert "stage_device_exec_ms" not in cold, cold
                    assert cold.get("device_h2d_bytes", 0) > 0, cold
                    assert cold.get("device_d2h_bytes", 0) > 0, cold
                    warm = await traced_scan()
                assert not warm, warm
            finally:
                await s.close()
                rt.close()

        run(go())


class TestClearOnClose:
    def test_clear_zeroes_families_and_state(self):
        prof = deviceprof.profiler
        f = prof.jit(lambda x: x + 7, name="unit_clear")
        f(_arr(8))
        deviceprof.device_put(np.zeros(64, dtype=np.float32))
        prof.record_round("mesh_run", slots=1, capacity=2)
        prof.clear()
        snap = prof.snapshot()
        for rec in snap["fns"]:
            assert rec["compiles"] == 0 and rec["dispatches"] == 0, rec
        assert snap["rounds"] == []
        for d in ("h2d", "d2h"):
            assert snap["transfer"][d]["bytes"] == 0
        # the registry families render no phantom series for any fn
        # this profiler accounted (unit profilers elsewhere in the
        # suite share the families — their children are theirs)
        names = {r.name for r in prof.records()}
        for fam in (deviceprof._COMPILES, deviceprof._DISPATCHES,
                    deviceprof._STORMS):
            for _series, lbls, _val in fam.samples():
                assert lbls.get("fn") not in names, (lbls, names)
        assert deviceprof._TRANSFER_BYTES.samples() == []
        # post-clear calls on an already-compiled shape are DISPATCHES
        # (jit's cache survived the clear; ours must agree)
        f(_arr(8))
        rec = prof._record("unit_clear")
        assert rec.compiles == 0
        assert rec.dispatches == 1
        prof.clear()

    def test_engine_close_clears_device_plane(self):
        async def go():
            rt = _runtimes()
            s = await _open_device_storage(rt)
            try:
                await _write_segments(s, random.Random(11))
                _clear_caches(s)
                with _force_xla_agg():
                    await s.scan_aggregate(*_agg_scan())
                assert any(r["compiles"] or r["dispatches"]
                           for r in deviceprof.profiler.snapshot()["fns"])
                assert deviceprof.profiler.transfer["h2d"]["bytes"] > 0
            finally:
                await s.close()
                rt.close()
            snap = deviceprof.profiler.snapshot()
            for rec in snap["fns"]:
                assert rec["compiles"] == 0 and rec["dispatches"] == 0, \
                    rec
            assert snap["transfer"]["h2d"]["bytes"] == 0
            assert snap["transfer"]["d2h"]["bytes"] == 0
            assert memledger._device_high_water == {}

        run(go())


class TestServerSurface:
    def test_debug_device_and_stats_sections(self):
        from aiohttp.test_utils import TestClient, TestServer

        from horaedb_tpu.server.config import ServerConfig
        from horaedb_tpu.server.main import ServerState, build_app

        async def go():
            engine = await MetricEngine.open(
                "devsrv", MemoryObjectStore(), segment_ms=2 * HOUR)
            state = ServerState(engine, ServerConfig())
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                r = await client.post("/write", json={"samples": [
                    {"name": "cpu", "labels": {"host": "h1"},
                     "timestamp": T0 + i * 1000, "value": float(i)}
                    for i in range(200)]})
                assert r.status == 200
                # drive a seam so the compile table has a live row
                f = deviceprof.jit(lambda x: x * 2, name="unit_srv")
                f(_arr(8))
                deviceprof.device_put(np.zeros(32, dtype=np.float32))
                r = await client.get("/debug/device")
                assert r.status == 200
                body = await r.json()
                assert body["enabled"] is True
                assert body["storm"]["threshold"] >= 2
                fns = {f["fn"]: f for f in body["fns"]}
                assert fns["unit_srv"]["compiles"] == 1
                assert fns["unit_srv"]["last_key"], fns["unit_srv"]
                assert set(body["transfer"]) == {"h2d", "d2h"}
                assert "rounds" in body and "devices" in body
                # the device as JAX reports it, and where its compile
                # cache lives (off on the CPU backend)
                assert body["backend"] == {
                    "platform": "cpu", "kind": "cpu", "count": 8,
                    "compile_cache_dir": None}
                r = await client.get("/stats")
                dp = (await r.json())["deviceprof"]
                assert dp["fns"] >= 1
                assert "transfer_bytes" in dp
                r = await client.get("/metrics")
                text = await r.text()
                assert "device_compiles_total" in text
                assert "device_dispatch_seconds" in text
            finally:
                await client.close()
                await engine.close()

        run(go())

    def test_deviceprof_config_toml(self, tmp_path):
        from horaedb_tpu.server.config import load_config

        p = tmp_path / "cfg.toml"
        p.write_text(
            "[deviceprof]\n"
            "enabled = true\n"
            'storm_window = "30s"\n'
            "storm_threshold = 4\n"
            "rounds = 64\n")
        cfg = load_config(str(p))
        assert cfg.deviceprof.storm_window.seconds == 30.0
        assert cfg.deviceprof.storm_threshold == 4
        assert cfg.deviceprof.rounds == 64
        bad = tmp_path / "bad.toml"
        bad.write_text("[deviceprof]\nstorm_threshold = 1\n")
        with pytest.raises(Exception, match="storm_threshold"):
            load_config(str(bad))


class TestLintRule:
    def test_lint_bare_jax_jit_rule(self, tmp_path):
        """tools/lint.py must flag bare jax.jit under horaedb_tpu/ in
        all three forms (decorator, functools.partial, direct call),
        leave common/deviceprof.py alone, and honor noqa."""
        import subprocess
        import sys

        bad_dir = tmp_path / "horaedb_tpu" / "storage"
        bad_dir.mkdir(parents=True)
        bad = bad_dir / "rogue.py"
        bad.write_text(
            "import functools\n\nimport jax\n\n\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return x\n\n\n"
            "@functools.partial(jax.jit, static_argnames=('k',))\n"
            "def g(x, k):\n"
            "    return x[:k]\n\n\n"
            "def h(fn):\n"
            "    return jax.jit(fn)\n")
        ok_dir = tmp_path / "horaedb_tpu" / "common"
        ok_dir.mkdir(parents=True)
        ok = ok_dir / "deviceprof.py"
        ok.write_text(
            "import jax\n\n\n"
            "def wrap(fn):\n"
            "    return jax.jit(fn)\n")
        waived = bad_dir / "waived.py"
        waived.write_text(
            "import jax\n\n\n"
            "@jax.jit  # noqa: unprofiled baseline\n"
            "def f(x):\n"
            "    return x\n")
        lint = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "lint.py")
        out = subprocess.run(
            [sys.executable, lint, str(bad), str(ok), str(waived)],
            capture_output=True, text=True)
        assert "bare jax.jit" in out.stdout
        assert out.stdout.count(f"{bad}:") == 3
        assert str(ok) not in out.stdout
        assert str(waived) not in out.stdout


def test_existing_jax_jit_sites_enumerated():
    """The bare-jax.jit rule's ground truth: every current `jax.jit`
    reference under horaedb_tpu/ lives in common/deviceprof.py (the
    one seam); none is waived by a noqa — enumerated here so a new
    site fails THIS test with a readable location even before lint
    runs."""
    import ast
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent / "horaedb_tpu"
    unprofiled = []
    waived_files = set()
    for path in sorted(root.rglob("*.py")):
        text = path.read_text()
        lines = text.splitlines()
        tree = ast.parse(text, filename=str(path))
        rel = str(path.relative_to(root))
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Attribute)
                    and node.attr == "jit"
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "jax"):
                continue
            if rel == "common/deviceprof.py":
                continue
            src = lines[node.lineno - 1] \
                if node.lineno <= len(lines) else ""
            if "noqa" in src:
                waived_files.add(rel)
            else:
                unprofiled.append((rel, node.lineno))
    assert not unprofiled, \
        f"bare jax.jit outside common/deviceprof.py: {unprofiled}"
    # waivers are a conscious, enumerated set: growing it means a seam
    # the compile ledger will never see — update this list deliberately
    assert not waived_files, waived_files
