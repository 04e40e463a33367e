#!/usr/bin/env python
"""Driver benchmark: BASELINE config #1 — single-table
`avg(value) GROUP BY time(1m)` over 10M rows, 1 tag — measured
END-TO-END through the real engine: `MetricEngine.query_downsample`
(object-store parquet read -> device encode -> merge-dedup ->
downsample), cold (scan cache cleared) and cached (HBM-resident
windows, the north-star serving mode).

The CPU baseline is numpy bincount aggregation of the same rows fully
in memory — conservative in the device's disfavor: it skips the parquet
read and merge the engine pays for.

Prints ONE JSON line:
  {"metric": ..., "value": <cached p50 ms>, "unit": "ms",
   "vs_baseline": <cached_p50 / cpu_p50>,        # <= 0.5 north star
   "cold_p50_ms": ..., "cold_vs_baseline": ...,  # full-path numbers
   "backend": "<jax platform>", "fallback": <bool>, ...}

`backend` is the JAX platform the run executed on; `fallback: true`
marks a run on the CPU backend — such numbers are NOT device numbers.
The script runs on whatever backend JAX initializes and never
re-executes itself on another; the chip path's gate is chip_smoke.py.

Env knobs: BENCH_ROWS (default 10_000_000), BENCH_ITERS (default 20),
BENCH_CONFIG (default 1 = end-to-end engine; 0 = device kernel
microbench; 2-17 delegate to horaedb_tpu.bench.suite, 6 being the
manifest snapshot codec, 7 the mixed read/write churn workload,
8 the durable-ingest WAL group-commit bench, 9 the tiered scan-cache
cold ladder, 10 the query-tracing overhead A/B, 11 the
standing-rollup dashboard mix vs the raw cold scan, 12 the
background-plane overhead A/B, 13 the pipelined cold-scan ladder
vs the [scan.pipeline] off control, 14 the sparse-combine/top-k/memo
ladder, 15 the open-loop multi-tenant SLO harness, 16 the
device-native decode A/B vs the [scan.decode] host control, 17
the near-data scan-agent dashboard mix — agent-served partials vs
shipped segments over the seeded fault store, 19 the 2-D mesh-scan
A/B, and 22 the mesh-placed fused-decode A/B — stored bytes to
ranked answer vs the PR 15 mesh vs the single-chip control).
"""

import asyncio
import json
import os
import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _load_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def load_scale_proven() -> dict:
    """Largest row count the engine has been soak-proven at (written by
    tools/scale_run.py), surfaced as max_rows_proven in every payload."""
    return _load_json(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_results",
        "scale_proven.json"))


# ---------------------------------------------------------------------------
# config 1 (default): end-to-end MetricEngine.query_downsample
# ---------------------------------------------------------------------------


def run_engine_headline(rows: int, iters: int) -> dict:
    import pyarrow as pa

    from horaedb_tpu.common.error import Error
    from horaedb_tpu.metric_engine import MetricEngine
    from horaedb_tpu.metric_engine.types import Label, tsid_of
    from horaedb_tpu.objstore import MemoryObjectStore
    from horaedb_tpu.storage.config import StorageConfig, from_dict
    from horaedb_tpu.storage.types import TimeRange

    # BENCH_HOSTS scales CARDINALITY: the query window must fit int32
    # ms offsets (~24.8 days), so beyond ~20M rows the ladder grows
    # hosts at a fixed tick count instead of growing the time span —
    # the TSBS-devops shape of "more rows" is more hosts anyway
    hosts = int(os.environ.get("BENCH_HOSTS", 100))
    interval = 10_000  # 10s scrape
    bucket_ms = 60_000
    per_host = max(1, rows // hosts)
    span = per_host * interval
    assert span < 2**31, ("query window must fit int32 offsets — raise "
                          "BENCH_HOSTS to scale by cardinality instead")
    num_buckets = -(-span // bucket_ms)
    segment_ms = 2 * 3600 * 1000  # reference default segment duration
    T0 = (1_700_000_000_000 // segment_ms) * segment_ms

    # time-major TSBS-like layout: every 10s tick reports all 100 hosts
    rng = np.random.default_rng(0)
    n = per_host * hosts
    ts = T0 + np.repeat(np.arange(per_host, dtype=np.int64) * interval, hosts)
    host_id = np.tile(np.arange(hosts, dtype=np.int32), per_host)
    vals = (rng.random(n) * 100).astype(np.float64)
    names = pa.array([f"host_{i:03d}" for i in range(hosts)])
    log(f"engine headline: {n:,} rows, {hosts} hosts x {num_buckets} "
        f"buckets, {span // segment_ms + 1} segments")

    # ---- CPU baseline: numpy aggregate of the same rows, in memory ----
    # defined up front so its trials INTERLEAVE with the engine's cached
    # queries: on a busy 1-core box the two legs must see the same
    # scheduler conditions or the vs_baseline ratio swings 2x run-to-run
    # (paired trials make the <=0.5x target falsifiable)
    ts_off = ts - T0
    cell = host_id.astype(np.int64) * num_buckets + ts_off // bucket_ms
    ncells = hosts * num_buckets

    def cpu_run():
        counts = np.bincount(cell, minlength=ncells)
        sums = np.bincount(cell, weights=vals, minlength=ncells)
        with np.errstate(invalid="ignore"):
            return sums / counts, counts

    ingest_box: dict = {}

    async def setup() -> MetricEngine:
        scan_cfg = {"cache_max_rows": rows * 4}
        # A/B knob: windows per aggregation round (default 16); bigger
        # rounds = fewer dispatches
        if os.environ.get("BENCH_AGG_WINDOWS"):
            scan_cfg["agg_batch_windows"] = int(
                os.environ["BENCH_AGG_WINDOWS"])
        cfg = from_dict(StorageConfig, {
            "scheduler": {"schedule_interval": "1h"},
            # cache must hold every segment's windows for the cached
            # (HBM-resident) number to mean anything at this row count
            "scan": scan_cfg,
        })
        e = await MetricEngine.open("bench", MemoryObjectStore(),
                                    segment_ms=segment_ms, config=cfg)
        t0 = time.perf_counter()
        # chunked, time-contiguous ingest: each chunk touches few segments
        chunk = max(1, 1_000_000 // hosts) * hosts
        for lo in range(0, n, chunk):
            hi = min(n, lo + chunk)
            batch = pa.record_batch({
                "host": pa.DictionaryArray.from_arrays(
                    pa.array(host_id[lo:hi]), names),
                "timestamp": pa.array(ts[lo:hi], type=pa.int64()),
                "value": pa.array(vals[lo:hi], type=pa.float64()),
            })
            for attempt in range(5):
                try:
                    await e.write_arrow("cpu", ["host"], batch)
                    break
                except Error:
                    # manifest delta backpressure (hard threshold): what a
                    # real writer does — force the fold, retry the chunk.
                    # Duplicate rows from the partial write are deduped by
                    # (tsid, ts) last-wins, so the retry is idempotent.
                    log(f"write backpressure (attempt {attempt}); "
                        "folding manifest deltas")
                    await e.tables["data"].manifest.trigger_merge()
            else:
                raise Error("ingest failed after 5 backpressure retries")
        ingest_box["s"] = time.perf_counter() - t0
        log(f"ingest: {n:,} rows in {ingest_box['s']:.1f}s")
        return e

    async def query(e: MetricEngine) -> dict:
        return await e.query_downsample(
            "cpu", [], TimeRange.new(T0, T0 + span), bucket_ms=bucket_ms,
            aggs=("avg",))  # the workload is avg GROUP BY time

    def clear_tiers(e: MetricEngine):
        # TRUE-cold: drop tier-1 HBM windows AND tier-2 host-RAM
        # encoded parts — otherwise the tier-2 cache (ISSUE 4) serves
        # the "cold" leg from RAM and the number stops measuring the
        # full object-store path (bench config 9 measures the tiers).
        # The delta-summation parts memo (ISSUE 9) would likewise
        # serve a repeat full-span "cold" query without scanning —
        # config 14's refine leg measures it on purpose; here it must
        # be cleared too.
        reader = e.tables["data"].reader
        reader.scan_cache.clear()
        reader.encoded_cache.clear()
        reader.parts_memo.clear()

    async def bench(e: MetricEngine):
        t0 = time.perf_counter()
        out = await query(e)  # compile + first full read
        compile_s = time.perf_counter() - t0

        from horaedb_tpu.storage.read import plan_stage_snapshot

        cold_times = []
        stage_profile = {}
        for i in range(max(2, iters // 5)):
            clear_tiers(e)
            before = plan_stage_snapshot()
            t0 = time.perf_counter()
            out = await query(e)
            cold_times.append(time.perf_counter() - t0)
            if i == 0:
                after = plan_stage_snapshot()
                stage_profile = {
                    k: round(after[k] - before[k], 3)
                    for k in after if after[k] != before[k]}

        cached_times = []
        base_times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            out = await query(e)
            cached_times.append(time.perf_counter() - t0)
            # paired baseline trial under the same scheduler conditions
            t0 = time.perf_counter()
            cpu_run()
            base_times.append(time.perf_counter() - t0)

        # varied-load leg: rotating half-span windows (bucket-aligned,
        # TSBS-style "random range" shape).  12 distinct ranges exceed
        # the 8-slot fused-replay LRU, so plan-level replay/result
        # caching cannot serve ANY of these — they measure the
        # steady-state engine under realistic non-identical queries
        # (scan cache still holds the windows; stacks re-stack from
        # per-window device columns on accelerators).
        half = (span // 2 // bucket_ms) * bucket_ms
        step = max(bucket_ms, (span - half) // 11 // bucket_ms * bucket_ms)
        starts = [T0 + i * step for i in range(12)
                  if T0 + i * step + half <= T0 + span]
        from horaedb_tpu.storage.read import _REPLAY_SLOTS

        varied_p50 = None
        if half == 0:
            # tiny --rows: a zero-length range would time empty scans
            log("varied leg skipped: span too small for a half-span "
                "bucket-aligned window")
        else:
            if len(starts) <= _REPLAY_SLOTS:
                # the ranges would fit the replay LRU and the "no
                # replay" label would lie — flag it
                log(f"varied leg: only {len(starts)} distinct ranges "
                    f"(<= {_REPLAY_SLOTS} replay slots); number may "
                    "include replay hits")
            varied_times = []
            for i in range(max(iters, 2 * len(starts))):
                s = starts[i % len(starts)]
                t0 = time.perf_counter()
                await e.query_downsample(
                    "cpu", [], TimeRange.new(s, s + half),
                    bucket_ms=bucket_ms, aggs=("avg",))
                varied_times.append(time.perf_counter() - t0)
            # steady state: every range visited once before timing
            steady = varied_times[len(starts):] or varied_times
            varied_p50 = float(np.percentile(steady, 50))
        return (out, compile_s, float(np.percentile(cold_times, 50)),
                float(np.percentile(cached_times, 50)), varied_p50,
                stage_profile, cached_times, base_times)

    async def main_async():
        e = await setup()
        try:
            return await bench(e)
        finally:
            await e.close()

    (out, compile_s, cold_p50, cached_p50, varied_p50, stage_profile,
     cached_times, base_times) = asyncio.run(main_async())
    log(f"compile+first query: {compile_s:.1f}s")
    log(f"cold stage profile: {stage_profile}")
    log(f"cold p50 (parquet->encode->merge->downsample): "
        f"{cold_p50 * 1e3:.1f} ms ({n / cold_p50 / 1e6:.0f}M rows/s)")
    log(f"cached p50 (HBM-resident windows): {cached_p50 * 1e3:.1f} ms "
        f"({n / cached_p50 / 1e6:.0f}M rows/s/chip)")
    if varied_p50 is not None:
        log(f"varied p50 (rotating half-span ranges, no replay): "
            f"{varied_p50 * 1e3:.1f} ms")

    # paired per-trial ratios: engine trial i over the baseline trial
    # run right after it — the ratio's median/IQR is robust to the
    # box-wide slowdowns that used to swing the unpaired ratio 2x
    ratios = np.array(cached_times) / np.array(base_times)
    vs_baseline = float(np.percentile(ratios, 50))
    iqr = (float(np.percentile(ratios, 25)),
           float(np.percentile(ratios, 75)))
    cpu_p50 = float(np.percentile(base_times, 50))
    ref_avg, ref_counts = cpu_run()
    log(f"cpu baseline p50 (in-memory, interleaved): "
        f"{cpu_p50 * 1e3:.2f} ms ({n / cpu_p50 / 1e6:.0f}M rows/s)")
    log(f"paired vs_baseline: p50 {vs_baseline:.3f}, "
        f"IQR [{iqr[0]:.3f}, {iqr[1]:.3f}]")

    # ---- cross-check the engine's grids against numpy -----------------
    tsid_by_host = np.array(
        [tsid_of("cpu", [Label("host", f"host_{i:03d}")])
         for i in range(hosts)], dtype=np.uint64)
    order = {int(t): i for i, t in enumerate(out["tsids"])}
    assert len(order) == hosts, f"expected {hosts} series, got {len(order)}"
    perm = np.array([order[int(t)] for t in tsid_by_host])
    got_counts = np.asarray(out["aggs"]["count"])[perm]
    np.testing.assert_array_equal(got_counts.reshape(-1),
                                  ref_counts.astype(got_counts.dtype))
    occ = ref_counts.reshape(hosts, num_buckets) > 0
    got_avg = np.asarray(out["aggs"]["avg"], dtype=np.float64)[perm]
    np.testing.assert_allclose(got_avg[occ],
                               ref_avg.reshape(hosts, num_buckets)[occ],
                               rtol=2e-4)

    return {
        "metric": (f"end-to-end avg GROUP BY time(1m) via "
                   f"MetricEngine.query_downsample, {n / 1e6:.1f}M rows, "
                   f"p50 (cached)"),
        "value": round(cached_p50 * 1e3, 3),
        "unit": "ms",
        # median of PAIRED per-trial ratios (engine/baseline interleaved)
        "vs_baseline": round(vs_baseline, 4),
        "vs_baseline_iqr": [round(iqr[0], 4), round(iqr[1], 4)],
        "cold_p50_ms": round(cold_p50 * 1e3, 3),
        "cold_vs_baseline": round(cold_p50 / cpu_p50, 4),
        # rotating half-span ranges (12 distinct specs > the 8-slot
        # replay LRU, so plan replay cannot serve them): the realistic
        # varied-load number; ~half the rows per query.  None when the
        # span is too small for a half-span bucket-aligned window.
        "varied_p50_ms": (None if varied_p50 is None
                          else round(varied_p50 * 1e3, 3)),
        "cpu_baseline_p50_ms": round(cpu_p50 * 1e3, 3),
        "compile_first_s": round(compile_s, 2),
        "rows": n,
        # the BASELINE metric is "rows scanned/sec/chip"
        "rows_per_s_cached": round(n / cached_p50),
        "rows_per_s_cold": round(n / cold_p50),
        "ingest_s": round(ingest_box.get("s", 0.0), 1),
        # per-plan-stage attribution of one cold query (seconds/rows/
        # bytes deltas from the scan_stage_* registry metrics)
        "stage_profile": stage_profile,
    }


# ---------------------------------------------------------------------------
# config 0: device kernel microbench (the former headline — kept for
# kernel-level regression tracking; NOT the driver's number)
# ---------------------------------------------------------------------------


def cpu_baseline(ts_off, gid, vals, bucket_ms, num_groups, num_buckets, iters):
    """numpy: avg per (group, minute-bucket) via bincount."""
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        bucket = ts_off // bucket_ms
        cell = gid.astype(np.int64) * num_buckets + bucket
        sums = np.bincount(cell, weights=vals, minlength=num_groups * num_buckets)
        counts = np.bincount(cell, minlength=num_groups * num_buckets)
        with np.errstate(invalid="ignore"):
            avg = sums / counts
        avg.sum()  # force materialization
        times.append(time.perf_counter() - t0)
    return float(np.percentile(times, 50))


def run_kernel_microbench(rows: int, iters: int) -> dict:
    from horaedb_tpu.bench.tsbs import TsbsConfig, generate_cpu_arrays

    # 100 hosts, 1 field, span sized to produce `rows` points
    interval = 10_000
    num_hosts = 100
    span = (rows // num_hosts) * interval
    cfg = TsbsConfig(num_hosts=num_hosts, num_fields=1, interval_ms=interval,
                     span_ms=span)
    t0 = time.perf_counter()
    cols = generate_cpu_arrays(cfg)
    n = len(cols["ts"])
    bucket_ms = 60_000
    num_buckets = -(-span // bucket_ms)
    ts_off = (cols["ts"] - cfg.start_ms).astype(np.int64)
    gid = cols["host_id"]
    vals = cols["usage_user"].astype(np.float32)
    log(f"generated {n:,} rows in {time.perf_counter()-t0:.1f}s; "
        f"{num_hosts} hosts x {num_buckets} buckets")

    cpu_p50 = cpu_baseline(ts_off, gid, vals.astype(np.float64), bucket_ms,
                           num_hosts, num_buckets, max(3, iters // 4))
    log(f"cpu baseline p50: {cpu_p50*1e3:.2f} ms "
        f"({n/cpu_p50/1e6:.0f}M rows/s)")

    import jax

    from horaedb_tpu.ops.downsample import time_bucket_aggregate

    dev = jax.devices()[0]
    log(f"device: {dev} ({dev.platform})")

    assert ts_off.max() < 2**31, "ts offsets must fit int32"
    cap = 1 << (n - 1).bit_length()
    pad = lambda a, d: np.pad(a.astype(d), (0, cap - n))
    d_ts = jax.device_put(pad(ts_off, np.int32), dev)
    d_gid = jax.device_put(pad(gid, np.int32), dev)
    d_vals = jax.device_put(pad(vals, np.float32), dev)

    # the workload is avg GROUP BY time: compute only what it needs
    # (count rides along for the cross-check)
    which = ("avg", "count")
    t0 = time.perf_counter()
    out = time_bucket_aggregate(d_ts, d_gid, d_vals, n, bucket_ms,
                                num_groups=num_hosts, num_buckets=num_buckets,
                                which=which)
    jax.block_until_ready(out["avg"])
    log(f"compile+first run: {time.perf_counter()-t0:.1f}s")

    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = time_bucket_aggregate(d_ts, d_gid, d_vals, n, bucket_ms,
                                    num_groups=num_hosts,
                                    num_buckets=num_buckets, which=which)
        jax.block_until_ready(out["avg"])
        times.append(time.perf_counter() - t0)
    tpu_p50 = float(np.percentile(times, 50))
    log(f"device p50: {tpu_p50*1e3:.2f} ms ({n/tpu_p50/1e6:.0f}M rows/s/chip)")

    # sanity: the timed kernel's counts AND averages must match numpy
    bucket = ts_off // bucket_ms
    cell = gid.astype(np.int64) * num_buckets + bucket
    counts = np.bincount(cell, minlength=num_hosts * num_buckets)
    sums = np.bincount(cell, weights=vals.astype(np.float64),
                       minlength=num_hosts * num_buckets)
    assert int(np.asarray(out["count"]).sum()) == n
    np.testing.assert_array_equal(
        np.asarray(out["count"]).reshape(-1), counts)
    occupied = counts > 0
    np.testing.assert_allclose(
        np.asarray(out["avg"], dtype=np.float64).reshape(-1)[occupied],
        (sums / np.maximum(counts, 1))[occupied], rtol=2e-4)

    return {
        "metric": (f"device kernel: avg GROUP BY time(1m), "
                   f"{n/1e6:.1f}M rows, p50"),
        "value": round(tpu_p50 * 1e3, 3),
        "unit": "ms",
        "vs_baseline": round(tpu_p50 / cpu_p50, 4),
    }


def main() -> None:
    rows = int(os.environ.get("BENCH_ROWS", 10_000_000))
    iters = int(os.environ.get("BENCH_ITERS", 20))
    try:
        config = int(os.environ.get("BENCH_CONFIG", 1))
    except ValueError:
        sys.exit(f"BENCH_CONFIG must be 0-23, got "
                 f"{os.environ.get('BENCH_CONFIG')!r}")

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from horaedb_tpu.bench.suite import provenance

    if config == 1:
        result = run_engine_headline(rows, iters)
    elif config == 0:
        result = run_kernel_microbench(rows, iters)
    else:
        from horaedb_tpu.bench.suite import RUNNERS

        if config not in RUNNERS:
            sys.exit(f"BENCH_CONFIG must be 0-23, got {config}")
        result = RUNNERS[config](rows, iters)
    # a config's own backend/fallback labels win (config 6 is pure host
    # work and must never read as a device number)
    for k, v in provenance().items():
        result.setdefault(k, v)
    import resource

    result["max_rss_mb"] = round(
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024)
    scale = load_scale_proven()
    if scale:
        result["max_rows_proven"] = scale.get("max_rows_proven")
        result["scale_evidence"] = scale.get("source")
    print(json.dumps(result))


if __name__ == "__main__":
    main()
