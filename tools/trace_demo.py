#!/usr/bin/env python
"""`make trace-demo`: start a local server, write + query a metric,
then fetch the query's trace and pretty-print its span tree.

With `--ops`, also exercise the BACKGROUND plane — force SSTs, trigger
a compaction and a rollup maintenance pass — then pretty-print the most
recent op traces (/debug/traces?kind=op) alongside the query tree.

With `--device` (`make trace-demo-device`), exercise the DEVICE plane
instead: drive a cold fused mesh-decode aggregate directly against a
throwaway CloudObjectStorage, repeat it warm, and pretty-print the
compile/dispatch/exec/transfer attribution the profiler collected —
the same tables `GET /debug/device` serves, plus the per-trace device
twins showing the warm repeat paid nothing.

Usage: python tools/trace_demo.py [--port N] [--ops | --device]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _print_tree(node: dict, depth: int = 0) -> None:
    pad = "  " * depth
    fields = " ".join(f"{k}={v}" for k, v in
                      (node.get("fields") or {}).items())
    status = node.get("status", "?")
    mark = "" if status == "ok" else f" [{status.upper()}]"
    print(f"{pad}{node.get('name', '?'):<28s} "
          f"{node.get('duration_ms', 0):>9.2f} ms{mark}"
          f"{('  ' + fields) if fields else ''}")
    for child in node.get("children", []):
        _print_tree(child, depth + 1)


async def _print_op_trace(s, base: str, timeout, op: str,
                          deadline_s: float = 20.0) -> None:
    """Poll /debug/traces?op= until the newest trace of that op shows,
    then print its tree (ops complete asynchronously to the admin
    calls that provoked them)."""
    t_end = asyncio.get_running_loop().time() + deadline_s
    trace_id = None
    while asyncio.get_running_loop().time() < t_end:
        async with s.get(f"{base}/debug/traces?op={op}&limit=1",
                         timeout=timeout) as r:
            traces = (await r.json())["traces"]
        if traces:
            trace_id = traces[0]["trace_id"]
            break
        await asyncio.sleep(0.2)
    if trace_id is None:
        print(f"\n== no {op} op trace appeared within {deadline_s}s ==")
        return
    async with s.get(f"{base}/debug/traces/{trace_id}",
                     timeout=timeout) as r:
        trace = await r.json()
    print(f"\n== op trace: {op} ({trace_id}, "
          f"status={trace['status']}, slow={trace.get('slow')}) ==")
    _print_tree(trace["tree"])
    counters = {k: round(v, 2)
                for k, v in sorted(trace.get("counters", {}).items())}
    if counters:
        print(json.dumps(counters, indent=2))


def _device_twins(trace) -> dict:
    return {k: round(v, 2) for k, v in sorted(trace.counters.items())
            if k.startswith("stage_device_") or k.startswith("device_")}


async def device_main() -> int:
    """The --device leg: cold fused mesh-decode round, warm repeat,
    then the profiler's attribution tables (docs/observability.md,
    device plane)."""
    import random

    # the bit-identity convention: aggregate with the XLA window
    # kernel so the fused dispatch actually runs on the device path
    os.environ["HORAEDB_HOST_AGG"] = "0"

    from horaedb_tpu.common import ReadableDuration, deviceprof
    from horaedb_tpu.common import runtimes as runtimes_mod
    from horaedb_tpu.objstore import MemoryObjectStore
    from horaedb_tpu.storage.config import (
        StorageConfig,
        ThreadsConfig,
        from_dict,
    )
    from horaedb_tpu.storage.read import AggregateSpec, ScanRequest
    from horaedb_tpu.storage.storage import CloudObjectStorage, WriteRequest
    from horaedb_tpu.storage.types import TimeRange
    from horaedb_tpu.utils import tracing

    import pyarrow as pa

    segment_ms = 3_600_000
    schema = pa.schema([("k", pa.string()), ("ts", pa.int64()),
                        ("v", pa.float64())])
    cfg = from_dict(StorageConfig, {
        "scheduler": {"schedule_interval": "1h", "input_sst_min_num": 2},
        "scan": {"mesh": {"enabled": True},
                 "decode": {"mode": "device"}},
    })
    cfg.manifest.merge_interval = ReadableDuration.parse("1h")
    cfg.scrub.interval = ReadableDuration.parse("1h")
    rt = runtimes_mod.from_config(ThreadsConfig())
    s = await CloudObjectStorage.open(
        "db", segment_ms, MemoryObjectStore(), schema, 2, cfg,
        runtimes=rt)
    try:
        rng = random.Random(1337)
        for seg in range(3):
            rows = [(f"k{rng.randint(0, 7)}",
                     seg * segment_ms + rng.randrange(
                         0, segment_ms - 1000, 250),
                     float(rng.randint(0, 10**6))) for _ in range(300)]
            lo = min(r[1] for r in rows)
            hi = max(r[1] for r in rows) + 1
            k, t, v = zip(*rows)
            b = pa.record_batch(
                [pa.array(list(k)), pa.array(list(t), type=pa.int64()),
                 pa.array(list(v), type=pa.float64())], schema=schema)
            await s.write(WriteRequest(b, TimeRange.new(lo, hi)))
        s.reader.scan_cache.clear()
        s.reader.encoded_cache.clear()
        s.reader.parts_memo.clear()
        deviceprof.profiler.clear()
        tracing.recorder.configure(enabled=True, sample_rate=1.0)

        spec = AggregateSpec(
            group_col="k", ts_col="ts", value_col="v", range_start=0,
            bucket_ms=60_000,
            num_buckets=-(-(3 * segment_ms) // 60_000),
            which=("avg", "max", "last"))
        req = ScanRequest(range=TimeRange.new(0, 3 * segment_ms))

        async def traced_scan(name):
            trace = tracing.recorder.start(name)
            t0 = asyncio.get_running_loop().time()
            with tracing.trace_scope(trace):
                await s.scan_aggregate(req, spec)
            wall_ms = (asyncio.get_running_loop().time() - t0) * 1e3
            tracing.recorder.finish(trace)
            return trace, wall_ms

        cold, cold_ms = await traced_scan("/scan-cold")
        warm, warm_ms = await traced_scan("/scan-warm")

        snap = deviceprof.profiler.snapshot()
        print("== compile ledger (cold mesh-decode + warm repeat) ==")
        hdr = (f"{'fn':<34s} {'comp':>4s} {'comp_ms':>8s} "
               f"{'disp':>4s} {'disp_ms':>8s}")
        print(hdr)
        for rec in snap["fns"]:
            if not (rec["compiles"] or rec["dispatches"]):
                continue
            print(f"{rec['fn']:<34.34s} {rec['compiles']:>4d} "
                  f"{rec['compile_seconds'] * 1e3:>8.1f} "
                  f"{rec['dispatches']:>4d} "
                  f"{rec['dispatch_seconds'] * 1e3:>8.1f}")
        print("\n== waits for the device (scan.device_wait spans) ==")
        for label, tr in (("cold", cold), ("warm", warm)):
            for sp in tr.spans:
                if sp["name"] == "scan.device_wait":
                    print(f"  {label}: {sp['fields'].get('fn', ''):<30s} "
                          f"{sp['duration_ms']:>8.1f} ms wall "
                          f"{sp.get('cpu_ms', 0.0):>8.1f} ms cpu")
        print("\n== transfers ==")
        for d, t in snap["transfer"].items():
            print(f"  {d}: {t['bytes']:>10d} B in {t['count']:>3d} "
                  f"transfers ({t['seconds'] * 1e3:.2f} ms)")
        if snap["rounds"]:
            print("\n== mesh round timeline ==")
            for r in snap["rounds"]:
                rows_s = ""
                if "row_imbalance" in r:
                    rows_s = (f" imbalance={r['row_imbalance']} "
                              f"shard_rows={r['shard_rows']}")
                print(f"  {r['kind']:<12s} fill={r['fill_ratio']} "
                      f"({r['slots']}/{r['capacity']}) "
                      f"pad_rows={r['padding_rows']} "
                      f"stack_hit={r['stack_hit']}{rows_s}")
        print(f"\n== cold scan ({cold_ms:.1f} ms wall) device twins ==")
        print(json.dumps(_device_twins(cold), indent=2))
        print(f"\n== warm repeat ({warm_ms:.1f} ms wall) device twins "
              f"(memo-served: expect none) ==")
        print(json.dumps(_device_twins(warm), indent=2))
    finally:
        await s.close()
        rt.close()
    return 0


async def main(port: int, ops: bool = False) -> int:
    import aiohttp

    from horaedb_tpu.server.config import ServerConfig, load_config
    from horaedb_tpu.server.main import run_server

    t0 = 1_700_000_000_000
    with tempfile.TemporaryDirectory(prefix="trace-demo-") as tmp:
        config = load_config(None)
        config = ServerConfig(
            port=port, test=config.test, admission=config.admission,
            breaker=config.breaker, wal=config.wal, trace=config.trace,
            metric_engine=config.metric_engine, rollup=config.rollup,
            watchdog=config.watchdog, meta=config.meta)
        config.metric_engine.object_store.data_dir = tmp
        if ops:
            # make the background plane fire fast: eager compaction
            # (2 small SSTs qualify) and standing rollups on the demo
            # metric
            sched = config.metric_engine.time_merge_storage.scheduler
            sched.input_sst_min_num = 2
            config.rollup.enabled = True
            config.rollup.specs = ["demo.cpu"]
        ready = asyncio.Event()
        server = asyncio.create_task(run_server(config, ready=ready))
        await asyncio.wait_for(ready.wait(), 30)
        base = f"http://127.0.0.1:{port}"
        async with aiohttp.ClientSession() as s:
            timeout = aiohttp.ClientTimeout(total=30)
            samples = [{"name": "demo.cpu",
                        "labels": {"host": f"h{i % 4}"},
                        "timestamp": t0 + i * 1000, "value": float(i)}
                       for i in range(400)]
            async with s.post(f"{base}/write",
                              json={"samples": samples},
                              timeout=timeout) as r:
                assert r.status == 200, await r.text()
                print(f"write trace:  {r.headers.get('X-Trace-Id')}  "
                      f"({r.headers.get('X-Trace-Summary')})")
            async with s.post(f"{base}/query", json={
                    "metric": "demo.cpu", "start": t0,
                    "end": t0 + 400_000, "bucket_ms": 60_000},
                    timeout=timeout) as r:
                assert r.status == 200, await r.text()
                trace_id = r.headers["X-Trace-Id"]
                print(f"query trace:  {trace_id}  "
                      f"({r.headers.get('X-Trace-Summary')})")
            async with s.get(f"{base}/debug/traces/{trace_id}",
                             timeout=timeout) as r:
                assert r.status == 200, await r.text()
                trace = await r.json()
            print(f"\n== span tree for {trace_id} "
                  f"(status={trace['status']}, "
                  f"slow={trace.get('slow')}) ==")
            _print_tree(trace["tree"])
            counters = {k: round(v, 2) for k, v in
                        sorted(trace.get("counters", {}).items())}
            print("\n== per-trace counters ==")
            print(json.dumps(counters, indent=2))
            if ops:
                # second SST in the same segment, then provoke the two
                # showcase ops: a compaction rewrite and a roll pass
                samples2 = [{"name": "demo.cpu",
                             "labels": {"host": f"h{i % 4}"},
                             "timestamp": t0 + i * 1000 + 500,
                             "value": float(i) * 2}
                            for i in range(400)]
                async with s.post(f"{base}/write",
                                  json={"samples": samples2},
                                  timeout=timeout) as r:
                    assert r.status == 200, await r.text()
                async with s.get(f"{base}/compact", timeout=timeout) as r:
                    assert r.status == 200, await r.text()
                async with s.post(f"{base}/admin/rollups",
                                  json={"roll": True},
                                  timeout=timeout) as r:
                    assert r.status == 200, await r.text()
                await _print_op_trace(s, base, timeout, "compaction")
                await _print_op_trace(s, base, timeout, "rollup_pass",
                                      deadline_s=5.0)
                async with s.get(f"{base}/debug/tasks",
                                 timeout=timeout) as r:
                    tasks = await r.json()
                print("\n== /debug/tasks (background loops) ==")
                for lp in tasks["loops"]:
                    print(f"  {lp['kind']:<18s} alive={lp['alive']} "
                          f"hb_age={lp['heartbeat_age_s']:>7.3f}s "
                          f"stalled={lp['stalled']} "
                          f"errs={lp['consecutive_errors']}")
        server.cancel()
        try:
            await server
        except (asyncio.CancelledError, Exception):
            pass
    return 0


if __name__ == "__main__":
    parser = argparse.ArgumentParser("trace-demo")
    parser.add_argument("--port", type=int, default=5123)
    parser.add_argument("--ops", action="store_true",
                        help="also provoke + pretty-print background "
                             "op traces (compaction, roll pass) and "
                             "the /debug/tasks loop table")
    parser.add_argument("--device", action="store_true",
                        help="device-plane demo: cold fused "
                             "mesh-decode round + warm repeat, then "
                             "the compile/dispatch/exec/transfer "
                             "attribution tables")
    args = parser.parse_args()
    if args.device:
        sys.exit(asyncio.run(device_main()))
    sys.exit(asyncio.run(main(args.port, ops=args.ops)))
