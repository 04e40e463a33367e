#!/usr/bin/env python
"""Compile both Pallas entry points at serving shapes and compare them
with the XLA program — chip_smoke.py's last step, run as a child that
holds the chip alone.

Entry points (ops/pallas_kernels.py):
  1. pallas_time_bucket_aggregate — the single-shot aggregate behind
     HORAEDB_DOWNSAMPLE_IMPL=pallas, vs ops.downsample's XLA program;
  2. pallas_window_partials — traced INSIDE the fused device-decode
     dispatch (ops/device_decode._decode_aggregate_jit, use_pallas=True)
     vs the same dispatch with use_pallas=False.

Shapes are the served ones: `--cap` rows (2^20 = [scan] max_window_rows),
128 groups x the 128-bucket window grid a 2 h segment emits at 1 m
buckets, `which` = all aggregates.  A Mosaic refusal raises with the
compiler's words (exit 1).  Prints one JSON line: per-program first-call
(compile) seconds and a steady-state wall — observations, not a
benchmark.

Usage: python tools/pallas_check.py [--platform tpu|cpu] [--cap N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GROUPS, REAL_GROUPS, WIDTH, BUCKET_MS = 128, 100, 128, 60_000
SEGMENT_BUCKETS = 120


def timed(fn, repeats: int):
    """(result, first-call seconds, median steady seconds)."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    walls = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        walls.append(time.perf_counter() - t0)
    return out, round(first, 3), round(float(np.median(walls)), 5)


def require(cond: bool, msg: str) -> None:
    if not cond:  # not `assert`: the check must survive python -O
        raise AssertionError(msg)


def compare(name: str, got: dict, ref: dict) -> None:
    require(set(got) == set(ref), f"{name}: {sorted(got)} != {sorted(ref)}")
    occ = np.asarray(ref["count"]) > 0
    require(occ.any(), f"{name}: reference grid is empty")
    for key in sorted(ref):
        g, r = np.asarray(got[key]), np.asarray(ref[key])
        require(g.shape == r.shape, f"{name}: {key} {g.shape} != {r.shape}")
        if key in ("sum", "avg"):
            # accumulation order differs between the two programs
            np.testing.assert_allclose(g[occ], r[occ], rtol=1e-5,
                                       err_msg=f"{name}: {key}")
        else:
            np.testing.assert_array_equal(g[occ], r[occ],
                                          err_msg=f"{name}: {key}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"))
    ap.add_argument("--cap", type=int, default=1 << 20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from horaedb_tpu.ops import device_decode, downsample
    from horaedb_tpu.ops.pallas_kernels import pallas_time_bucket_aggregate

    platform = jax.devices()[0].platform
    if platform != args.platform:
        print(f"pallas_check: jax runs on {platform!r}, expected "
              f"{args.platform!r}", file=sys.stderr)
        return 1

    cap = args.cap
    n = cap - cap // 8  # trailing padding rows, like a real window
    rng = np.random.default_rng(0)
    # one segment's rows in (group, ts) order: REAL_GROUPS series, each
    # a run of increasing ts across the segment's 120 buckets
    gid = np.sort(rng.integers(0, REAL_GROUPS, n)).astype(np.int32)
    ts = rng.integers(0, SEGMENT_BUCKETS * BUCKET_MS, n).astype(np.int32)
    order = np.lexsort((ts, gid))
    gid, ts = gid[order], ts[order]
    pad = lambda a: np.pad(a, (0, cap - n))  # noqa: E731
    d_gid, d_ts = jnp.asarray(pad(gid)), jnp.asarray(pad(ts))
    d_seq = jnp.asarray(pad(np.arange(n, dtype=np.int32)))
    d_val = jnp.asarray(pad((rng.random(n) * 100).astype(np.float32)))
    which = downsample.ALL_AGGS
    interpret = downsample.pallas_interpret()
    # interpret mode (the CPU dry run) is slow and its walls mean nothing
    repeats = 1 if interpret else 5
    report = {"platform": platform, "cap": cap, "groups": GROUPS,
              "width": WIDTH, "which": list(which),
              "interpret": interpret}

    # 1. the single-shot aggregate
    shape = dict(num_groups=GROUPS, num_buckets=WIDTH, which=which)
    ref, c_ref, w_ref = timed(lambda: downsample._time_bucket_aggregate_impl(
        d_ts, d_gid, d_val, n, BUCKET_MS, **shape), repeats)
    got, c_got, w_got = timed(lambda: pallas_time_bucket_aggregate(
        d_ts, d_gid, d_val, n, BUCKET_MS, interpret=interpret, **shape),
        repeats)
    compare("time_bucket_aggregate", got, ref)
    report["time_bucket_aggregate"] = {
        "xla": {"first_s": c_ref, "steady_s": w_ref},
        "pallas": {"first_s": c_got, "steady_s": w_got}}

    # 2. the partials kernel traced inside the fused decode dispatch
    def decode(use_pallas: bool):
        return device_decode._decode_aggregate_jit(
            (d_gid, d_ts, d_seq, d_val), n, (), np.int32(0), np.int32(0),
            np.int32(WIDTH), np.int32(BUCKET_MS), jnp.int32(0),
            key_slots=(0, 1, 2), num_pks=2, group_pos=0, ts_pos=1,
            val_slot=3, leaf_prog=(), g_pad=GROUPS, width=WIDTH,
            which=which, use_pallas=use_pallas, route="presorted",
            num_runs=0)

    (ref, ref_rows), c_ref, w_ref = timed(lambda: decode(False), repeats)
    (got, got_rows), c_got, w_got = timed(lambda: decode(True), repeats)
    require(int(ref_rows) == int(got_rows),
            f"kept rows {int(got_rows)} != {int(ref_rows)}")
    compare("decode_aggregate", got, ref)
    report["decode_aggregate"] = {
        "xla": {"first_s": c_ref, "steady_s": w_ref},
        "pallas": {"first_s": c_got, "steady_s": w_got}}
    report["match"] = True
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
