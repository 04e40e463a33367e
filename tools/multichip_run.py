#!/usr/bin/env python
"""Multichip dryrun runner that ALWAYS records a result.

A run that times out must still produce a structured record so the
history distinguishes "timed out" from "never ran".  This runner
executes `__graft_entry__.dryrun_multichip(N)` in a subprocess under a
hard timeout and writes `bench_results/multichip_rNN.json` (next free
index) with an explicit `status` of "ok" | "timeout" | "error" — on
EVERY outcome, including the process being killed.

Usage:
    python tools/multichip_run.py [--devices 8] [--timeout 600]
                                  [--out PATH]

`make multichip` wraps this with the tier-1 defaults.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def next_record_path() -> str:
    results = os.path.join(ROOT, "bench_results")
    os.makedirs(results, exist_ok=True)
    taken = set()
    for p in glob.glob(os.path.join(results, "multichip_r*.json")):
        m = re.search(r"multichip_r(\d+)\.json$", p)
        if m:
            taken.add(int(m.group(1)))
    n = 1
    while n in taken:
        n += 1
    return os.path.join(results, f"multichip_r{n:02d}.json")


def run(n_devices: int, timeout_s: float, mode: str = "dryrun",
        rows: int = 2_000_000) -> dict:
    if mode == "mesh":
        # the mesh-scan A/B: BENCH_CONFIG=22 (ISSUE 19) runs the
        # mesh-placed FUSED-DECODE scan (stored bytes to ranked
        # answer) vs the PR 15 mesh-over-host-windows leg vs the
        # single-chip control, with in-bench bit-identity across all
        # three legs, k-way-merge routing asserts, and the additive
        # top-k egress bound at two group cardinalities
        # (BENCH_CONFIG=19 remains the PR 15 two-leg A/B, selectable
        # via MESH_BENCH_CONFIG).  Without an accelerator the rung is
        # the CPU virtual mesh (--xla_force_host_platform_device_count,
        # ignored by other backends); the record's backend/fallback
        # labels say which it was.  This parent never imports jax, so
        # the child is the only process that can hold a chip
        cmd = [sys.executable, "bench.py"]
        env = dict(os.environ)
        env["BENCH_CONFIG"] = env.get("MESH_BENCH_CONFIG", "22")
        env.setdefault("BENCH_ROWS", str(rows))
        env["MESH_BENCH_DEVICES"] = str(n_devices)
        flags = env.get("XLA_FLAGS", "")
        want = f"--xla_force_host_platform_device_count={n_devices}"
        if "host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = f"{flags} {want}".strip()
    else:
        cmd = [sys.executable, "-c",
               f"import __graft_entry__; "
               f"__graft_entry__.dryrun_multichip({n_devices}); "
               f"print('dryrun OK')"]
        env = None
    t0 = time.perf_counter()
    record = {"mode": mode, "n_devices": n_devices,
              "timeout_s": timeout_s, "cmd": " ".join(cmd)}
    try:
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                              text=True, timeout=timeout_s, env=env)
        record["rc"] = proc.returncode
        record["ok"] = proc.returncode == 0
        # rc=124 is how an outer `timeout(1)` reports — classify it as
        # a timeout even when the hang happened below us
        record["status"] = ("ok" if proc.returncode == 0 else
                            "timeout" if proc.returncode == 124 else
                            "error")
        record["tail"] = (proc.stderr or proc.stdout or "")[-2000:]
        if mode == "mesh" and proc.returncode == 0:
            # bench.py prints ONE result JSON on its last stdout line
            for line in reversed(proc.stdout.strip().splitlines()):
                try:
                    record["result"] = json.loads(line)
                    break
                except json.JSONDecodeError:
                    continue
    except subprocess.TimeoutExpired as exc:
        # THE recording-gap fix: a killed run still writes a record
        record["rc"] = 124
        record["ok"] = False
        record["status"] = "timeout"
        tail = exc.stderr or exc.stdout or b""
        if isinstance(tail, bytes):
            tail = tail.decode(errors="replace")
        record["tail"] = tail[-2000:]
    record["elapsed_s"] = round(time.perf_counter() - t0, 1)
    return record


def main() -> int:
    parser = argparse.ArgumentParser("multichip_run")
    parser.add_argument("--devices", type=int, default=8)
    parser.add_argument("--timeout", type=float, default=600.0)
    parser.add_argument("--mode", choices=("dryrun", "mesh"),
                        default="dryrun",
                        help="dryrun = the shard_map program dryrun; "
                             "mesh = the BENCH_CONFIG=19 mesh-scan "
                             "A/B with in-bench bit-identity checks")
    parser.add_argument("--rows", type=int, default=2_000_000)
    parser.add_argument("--out", default=None,
                        help="record path (default: next "
                             "bench_results/multichip_rNN.json)")
    args = parser.parse_args()
    record = run(args.devices, args.timeout, mode=args.mode,
                 rows=args.rows)
    path = args.out or next_record_path()
    with open(path, "w", encoding="utf-8") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    print(json.dumps({"record": os.path.relpath(path, ROOT), **record}))
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
