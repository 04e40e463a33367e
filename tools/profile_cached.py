"""Profile the CACHED query path (scan-cache hit, all stacks memoized).

Usage:  JAX_PLATFORMS=cpu python tools/profile_cached.py [rows]

Prints a cProfile of repeated cached query_downsample calls plus a
wall-clock breakdown, to attribute the residual per-query host time
(ROADMAP round-3 priority 1: trim per-query asyncio hops).
"""
import asyncio
import cProfile
import io
import os
import pstats
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pyarrow as pa


async def main(rows: int, iters: int) -> None:
    from horaedb_tpu.metric_engine import MetricEngine
    from horaedb_tpu.objstore import MemoryObjectStore
    from horaedb_tpu.storage.config import StorageConfig, from_dict
    from horaedb_tpu.storage.types import TimeRange

    hosts = 100
    interval = 10_000
    bucket_ms = 60_000
    per_host = max(1, rows // hosts)
    span = per_host * interval
    segment_ms = 2 * 3600 * 1000
    T0 = (1_700_000_000_000 // segment_ms) * segment_ms
    rng = np.random.default_rng(0)
    n = per_host * hosts
    ts = T0 + np.repeat(np.arange(per_host, dtype=np.int64) * interval, hosts)
    host_id = np.tile(np.arange(hosts, dtype=np.int32), per_host)
    vals = (rng.random(n) * 100).astype(np.float64)
    names = pa.array([f"host_{i:03d}" for i in range(hosts)])

    cfg = from_dict(StorageConfig, {
        "scheduler": {"schedule_interval": "1h"},
        "scan": {"cache_max_rows": rows * 4},
    })
    e = await MetricEngine.open("bench", MemoryObjectStore(),
                                segment_ms=segment_ms, config=cfg)
    chunk = max(1, 1_000_000 // hosts) * hosts
    for lo in range(0, n, chunk):
        hi = min(n, lo + chunk)
        batch = pa.record_batch({
            "host": pa.DictionaryArray.from_arrays(
                pa.array(host_id[lo:hi]), names),
            "timestamp": pa.array(ts[lo:hi], type=pa.int64()),
            "value": pa.array(vals[lo:hi], type=pa.float64()),
        })
        await e.write_arrow("cpu", ["host"], batch)

    async def query():
        return await e.query_downsample(
            "cpu", [], TimeRange.new(T0, T0 + span), bucket_ms=bucket_ms,
            aggs=("avg",))

    # warm: compile + populate caches
    await query()
    await query()

    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        await query()
        times.append(time.perf_counter() - t0)
    print(f"cached p50 {np.percentile(times, 50) * 1e3:.2f} ms  "
          f"min {min(times) * 1e3:.2f} ms  over {iters} iters")

    pr = cProfile.Profile()
    pr.enable()
    for _ in range(iters):
        await query()
    pr.disable()
    s = io.StringIO()
    ps = pstats.Stats(pr, stream=s).sort_stats("cumulative")
    ps.print_stats(45)
    print(s.getvalue())
    await e.close()


if __name__ == "__main__":
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 2_000_000
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    asyncio.run(main(rows, iters))
