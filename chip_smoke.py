#!/usr/bin/env python
"""Chip smoke: the served scan path, end to end, on the accelerator.

Drives the repo's headline deployment through the entry points a user
calls: BASELINE.json config 1 in the TSBS devops cpu-only shape
(100 hosts, 10 s scrape, one field, 10,000,000 rows =
100,000 ticks over 139 two-hour segments) on a LocalObjectStore on
disk, served by `python -m horaedb_tpu.server` under docs/example.toml
with only `port` and `data_dir` changed.  Rows go in as 1M-row Arrow
IPC bodies over POST /write_arrow and every acknowledged body is read
back; five query shapes go over HTTP and are checked against a numpy
reference built from --seed in this process; the route that served
each is read from the server's own counters (GET /debug/device,
GET /metrics).  Then the server is stopped and a SECOND process on the
same data and compile-cache directories repeats two queries.

This process never imports jax: each server child is the one holder of
the chip, and each exits before the next starts.  The platform it
expects defaults to `tpu`; a server that reports another fails the run.
A tiny dry run is by explicit `--platform cpu --rows N`, never by
detection.

`--chips 4` runs the same data and queries through ONE server with
`[scan.mesh] enabled = true` and `[scan.decode] mode = "device"` (a
2x2 mesh), then a single-chip control server on the same data whose
answers the mesh's must equal (counts exact, sums to f32 ulp).

It writes only logs and a JSON summary (ending `"claim": null` — the
timings in it are observations from one run, not benchmark results) to
its output directory; the data directory lives in the system temp dir
(TMPDIR) and is deleted on exit.  Exit code 0 and a last stdout line
`{"ok": true, "device": {...}}` only when every step passed.
"""

from __future__ import annotations

import argparse
import http.client
import io
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np
import pyarrow as pa
from pyarrow import ipc

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

# numpy/pyarrow-only modules of the package: importing them must not
# pull jax in (asserted at exit)
from horaedb_tpu import native  # noqa: E402
from horaedb_tpu.common.seahash import hash64  # noqa: E402

HOSTS = 100
INTERVAL_MS = 10_000
BUCKET_MS = 60_000
SEGMENT_MS = 2 * 3600 * 1000
T0 = (1_700_000_000_000 // SEGMENT_MS) * SEGMENT_MS
BODY_ROWS = 1_000_000
DAY_MS = 24 * 3600 * 1000
ALL_AGGS = ("count", "sum", "min", "max", "avg", "last")

# scan_mesh_fallback_total reasons that are a structural property of a
# query shape under [scan.decode] mode = "device", not a failure
# (docs/parallel.md: device-decode parts cannot join device top-k
# scoring, so the ranking folds full-width mesh parts).  Every other
# reason, `mesh_error` first, fails the run.
MESH_STRUCTURAL = ("topk_decode",)


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# data + numpy reference
# ---------------------------------------------------------------------------


class Dataset:
    """The seeded rows, time-major (every 10 s tick reports all
    hosts), and the plain numpy answers to the queries."""

    def __init__(self, rows: int, seed: int):
        self.ticks = max(1, rows // HOSTS)
        self.rows = self.ticks * HOSTS
        self.span = self.ticks * INTERVAL_MS
        rng = np.random.default_rng(seed)
        # (ticks, hosts) value grid; row i of the flat layout is
        # tick i // HOSTS, host i % HOSTS.  Values are f32-representable
        # (the engine stores values as float32), so an acknowledged
        # point reads back bit for bit and min/max/last compare exactly
        self.grid = (rng.random((self.ticks, HOSTS)) * 100.0).astype(
            np.float32).astype(np.float64)
        self.host_names = [f"host_{i:03d}" for i in range(HOSTS)]
        # TSID = SeaHash(canonical series key) & i63 (RFC; the same rule
        # as metric_engine.types.tsid_of, here via the pure-Python hash)
        self.tsid_of_host = [
            str(hash64(f"cpu{{host={h}}}".encode()) & ((1 << 63) - 1))
            for h in self.host_names]
        self.host_of_tsid = {t: i for i, t in enumerate(self.tsid_of_host)}
        self._refs: dict = {}  # (start, end) -> reference grids

    def body(self, lo_tick: int, hi_tick: int) -> bytes:
        n_ticks = hi_tick - lo_tick
        ts = T0 + np.repeat(
            np.arange(lo_tick, hi_tick, dtype=np.int64) * INTERVAL_MS, HOSTS)
        host_id = np.tile(np.arange(HOSTS, dtype=np.int32), n_ticks)
        batch = pa.record_batch({
            "host": pa.DictionaryArray.from_arrays(
                pa.array(host_id), pa.array(self.host_names)),
            "timestamp": pa.array(ts, type=pa.int64()),
            "value": pa.array(self.grid[lo_tick:hi_tick].reshape(-1),
                              type=pa.float64()),
        })
        sink = io.BytesIO()
        with ipc.new_stream(sink, batch.schema) as w:
            w.write_batch(batch)
        return sink.getvalue()

    def _tick_range(self, start: int, end: int) -> tuple[int, int]:
        lo = max(0, -(-(start - T0) // INTERVAL_MS))
        hi = min(self.ticks, -(-(end - T0) // INTERVAL_MS))
        return lo, max(lo, hi)

    def downsample(self, start: int, end: int) -> dict:
        """Reference grids, (hosts, buckets) f64, for a bucket-aligned
        range: every bucket is `per` consecutive ticks of the grid."""
        assert (start - T0) % BUCKET_MS == 0
        if (start, end) in self._refs:
            return self._refs[start, end]
        per = BUCKET_MS // INTERVAL_MS
        lo, hi = self._tick_range(start, end)
        nb = -(-(end - start) // BUCKET_MS)
        sub = np.full((nb * per, HOSTS), np.nan)
        sub[:hi - lo] = self.grid[lo:hi]
        cube = sub.reshape(nb, per, HOSTS)
        present = ~np.isnan(cube)
        count = present.sum(axis=1)
        with np.errstate(all="ignore"):
            total = np.nansum(cube, axis=1)
            out = {
                "count": count.astype(np.float64),
                "sum": total,
                "avg": total / count,
                "min": np.nanmin(cube, axis=1),
                "max": np.nanmax(cube, axis=1),
            }
        # last = value at the greatest ts of the bucket
        last_idx = np.maximum(count - 1, 0)
        out["last"] = np.take_along_axis(
            cube, last_idx[:, None, :], axis=1)[:, 0, :]
        ref = {k: v.T for k, v in out.items()}  # (hosts, buckets)
        self._refs[start, end] = ref
        return ref

    def raw(self, host: int, start: int, end: int):
        lo, hi = self._tick_range(start, end)
        ts = T0 + np.arange(lo, hi, dtype=np.int64) * INTERVAL_MS
        return ts, self.grid[lo:hi, host]


def grid_of(resp: dict, agg: str) -> np.ndarray:
    # JSON null (an empty cell) becomes NaN under a float dtype
    return np.array(resp["aggs"][agg], dtype=np.float64)


def compare_grids(name: str, got: dict, ref: dict, rows: list[int]) -> None:
    """The repo's rule (__graft_entry__ holds to it too): counts exact,
    sums/avgs to f32 rounding; selections (min/max/last) exact."""
    occupied = ref["count"][rows] > 0
    for agg in ALL_AGGS:
        g = grid_of(got, agg)
        r = ref[agg][rows]
        check(g.shape == r.shape,
              f"{name}: {agg} grid shape {g.shape} != {r.shape}")
        if agg == "count":
            check(np.array_equal(g, r), f"{name}: counts differ")
        elif agg in ("sum", "avg"):
            check(np.allclose(g[occupied], r[occupied], rtol=1e-5, atol=0),
                  f"{name}: {agg} differs from the reference")
        else:
            check(np.array_equal(g[occupied], r[occupied]),
                  f"{name}: {agg} differs from the reference")
        check(np.isfinite(g[occupied]).all(), f"{name}: {agg} not finite")


# ---------------------------------------------------------------------------
# the server child
# ---------------------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def render_config(port: int, data_dir: str, scan_overrides: dict) -> str:
    """docs/example.toml with `port` and `data_dir` changed — plus, for
    the multi-chip legs only, the named [scan.*] switches."""
    with open(os.path.join(ROOT, "docs", "example.toml"),
              encoding="utf-8") as f:
        text = f.read()

    def set_key(text: str, section: str, key: str, value: str) -> str:
        head = re.escape(f"[{section}]") if section else r"\A"
        pat = re.compile(
            rf"({head}(?:(?!^\[).)*?^{re.escape(key)} = )[^\n#]*",
            re.S | re.M)
        out, n = pat.subn(lambda m: m.group(1) + value, text, count=1)
        check(n == 1, f"docs/example.toml: no `{key}` under [{section}]")
        return out

    text = set_key(text, "", "port", str(port))
    text = set_key(text, "metric_engine.object_store", "data_dir",
                   json.dumps(data_dir))
    scan = "metric_engine.time_merge_storage.scan"
    for (sub, key), value in scan_overrides.items():
        text = set_key(text, f"{scan}.{sub}" if sub else scan, key, value)
    return text


class Server:
    """One `python -m horaedb_tpu.server` child — the process that
    holds the chip.  Stopped (SIGINT: graceful close) and WAITED for
    before anything else may touch the device."""

    def __init__(self, name: str, out_dir: str, data_dir: str,
                 scan_overrides: dict, env_extra: dict):
        self.name = name
        self.port = free_port()
        self.cfg_path = os.path.join(out_dir, f"{name}.toml")
        with open(self.cfg_path, "w", encoding="utf-8") as f:
            f.write(render_config(self.port, data_dir, scan_overrides))
        self.log_path = os.path.join(out_dir, f"{name}.log")
        self.log = open(self.log_path, "w", encoding="utf-8")
        env = dict(os.environ, **env_extra)
        self.served_log_bytes = None
        self.t_start = time.perf_counter()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "horaedb_tpu.server",
             "--config", self.cfg_path],
            cwd=ROOT, env=env, stdout=self.log, stderr=subprocess.STDOUT)

    def request(self, method: str, path: str, body=None,
                timeout: float = 600.0):
        headers = {}
        if isinstance(body, (dict, list)):
            body = json.dumps(body).encode()
            headers["Content-Type"] = "application/json"
        # the server's default deadlines (30 s) are sized for serving;
        # a first query here includes XLA compiles, so ask for the
        # maximum the server grants (`max_timeout`)
        headers["X-Deadline-Ms"] = "300000"
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=timeout)
        try:
            conn.request(method, path, body=body, headers=headers)
            resp = conn.getresponse()
            data = resp.read()
        finally:
            conn.close()
        check(resp.status == 200,
              f"{self.name}: {method} {path.split('?')[0]} -> "
              f"{resp.status} {data[:300]!r}")
        return data

    def post_json(self, path: str, body: dict) -> dict:
        return json.loads(self.request("POST", path, body))

    def wait_ready(self, timeout: float = 300.0) -> float:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"{self.name}: server exited rc={self.proc.returncode} "
                    f"before listening; see {self.log_path}\n{self.tail()}")
            try:
                self.request("GET", "/", timeout=2.0)
                return time.perf_counter() - self.t_start
            except (OSError, http.client.HTTPException):
                time.sleep(0.2)
        raise SmokeFailure(f"{self.name}: not listening after {timeout}s")

    def tail(self, n: int = 2000) -> str:
        self.log.flush()
        with open(self.log_path, encoding="utf-8", errors="replace") as f:
            return f.read()[-n:]

    def stop(self) -> None:
        """Graceful stop, then wait: the chip is free only once the
        process is gone."""
        if self.proc.poll() is None:
            self.log.flush()
            # the interrupt's own KeyboardInterrupt traceback is not a
            # failure: check_logs reads tracebacks up to here only
            self.served_log_bytes = os.path.getsize(self.log_path)
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        self.log.close()

    # ---- the server's own counters ----------------------------------------

    def device(self) -> dict:
        return json.loads(self.request("GET", "/debug/device"))

    def metric(self, family: str) -> dict:
        """{label-string: value} for one family from GET /metrics."""
        out = {}
        for line in self.request("GET", "/metrics").decode().splitlines():
            m = re.match(rf"{family}(?:\{{(.*)\}})? (\S+)$", line)
            if m:
                out[m.group(1) or ""] = float(m.group(2))
        return out


def fn_table(dev: dict) -> dict:
    return {f["fn"]: f for f in dev["fns"]}


def route_delta(before: dict, after: dict) -> dict:
    """What the device plane did between two /debug/device reads:
    per-fn (compiles + dispatches) deltas and transfer bytes."""
    b, a = fn_table(before), fn_table(after)
    fns = {}
    for name, rec in a.items():
        prev = b.get(name, {"compiles": 0, "dispatches": 0})
        d = (rec["compiles"] - prev["compiles"]
             + rec["dispatches"] - prev["dispatches"])
        if d:
            fns[name] = d
    return {
        "fns": fns,
        "h2d_bytes": (after["transfer"]["h2d"]["bytes"]
                      - before["transfer"]["h2d"]["bytes"]),
        "d2h_bytes": (after["transfer"]["d2h"]["bytes"]
                      - before["transfer"]["d2h"]["bytes"]),
    }


def cache_entries(cache_dir) -> int:
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(len(files) for _r, _d, files in os.walk(cache_dir))


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


class Smoke:
    def __init__(self, args, out_dir: str, data_dir: str):
        self.args = args
        self.out_dir = out_dir
        self.data_dir = data_dir
        self.on_chip = args.platform != "cpu"
        self.data = Dataset(args.rows, args.seed)
        self.summary: dict = {
            "rows": self.data.rows, "seed": args.seed,
            "chips": args.chips, "expected_platform": args.platform,
            "steps": [],
        }
        self.device_id = None
        self.cache_dir = None
        self.routes_seen: set = set()
        with open(os.path.join(ROOT, "docs", "example.toml"),
                  encoding="utf-8") as f:
            self.cache_rows = int(re.search(
                r"^cache_max_rows = (\d+)", f.read(), re.M).group(1))
        end = T0 + self.data.span
        d = self.data
        # "<= 4 days": under the default cache budget at 10M rows; a
        # smaller dry run takes half its span.  Bucket-aligned, rotating
        # by a third of the slack so stacks re-stack per range.
        sub = min(4 * DAY_MS, d.span // 2) // BUCKET_MS * BUCKET_MS
        step = max(BUCKET_MS, (d.span - sub) // 3 // BUCKET_MS * BUCKET_MS)
        self.full = (T0, end)
        self.sub_ranges = [(T0 + i * step, T0 + i * step + sub)
                           for i in range(3)
                           if T0 + i * step + sub <= end]
        hour = min(3600 * 1000, d.span // 2) // BUCKET_MS * BUCKET_MS
        mid = T0 + (d.span // 2) // BUCKET_MS * BUCKET_MS
        self.point = (mid, mid + hour)
        self.point_host = 42

    # ---- steps ------------------------------------------------------------

    def step(self, name: str, **fields) -> None:
        rec = {"step": name, **fields}
        self.summary["steps"].append(rec)
        say(f"step {name}: " + " ".join(
            f"{k}={json.dumps(v)}" for k, v in fields.items()))

    def start(self, name: str,
              scan_overrides: dict | None = None) -> Server:
        env = {}
        if not self.on_chip:
            # the CPU backend serves aggregates from the numpy twin and
            # keeps the persistent cache off; the dry run forces the
            # XLA programs and the cache on so the counter, cache and
            # restart plumbing below is exercised.  The chip run sets
            # nothing: it takes the routes the platform selects.
            env.update(HORAEDB_COMPILE_CACHE="1", HORAEDB_HOST_AGG="0",
                       HORAEDB_DEVICE_DECODE="1")
            if self.args.chips > 1:
                env["XLA_FLAGS"] = (
                    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_"
                    f"platform_device_count={self.args.chips}").strip()
        srv = Server(name, self.out_dir, self.data_dir,
                     scan_overrides or {}, env)
        try:
            ready_s = srv.wait_ready()
            dev = srv.device()
            backend = dev["backend"]
            say(f"{name}: platform: {backend['platform']} device_kind: "
                f"{backend['kind']} devices: {backend['count']}")
            check(backend["platform"] == self.args.platform,
                  f"{name}: server runs on platform "
                  f"{backend['platform']!r}, expected "
                  f"{self.args.platform!r}")
            if self.on_chip:
                check(backend["count"] == self.args.chips,
                      f"{name}: {backend['count']} devices, expected "
                      f"{self.args.chips}")
            self.device_id = {k: backend[k]
                              for k in ("platform", "kind", "count")}
            self.cache_dir = backend["compile_cache_dir"]
            self.step(f"{name}.start", ready_s=round(ready_s, 2),
                      compile_cache_dir=self.cache_dir)
        except BaseException:
            srv.stop()
            raise
        return srv

    def ingest(self, srv: Server) -> None:
        d = self.data
        body_ticks = BODY_ROWS // HOSTS
        t_all = time.perf_counter()
        bodies = 0
        for lo in range(0, d.ticks, body_ticks):
            hi = min(d.ticks, lo + body_ticks)
            payload = d.body(lo, hi)
            ack = json.loads(srv.request(
                "POST", "/write_arrow?metric=cpu&tags=host", payload))
            check(ack.get("written") == (hi - lo) * HOSTS,
                  f"ingest: body [{lo},{hi}) acked {ack}")
            # every acknowledged body is read back, exactly
            self.read_back(srv, host=bodies % HOSTS,
                           tick=lo + (hi - lo) // 2)
            bodies += 1
        self.step("ingest", rows=d.rows, bodies=bodies,
                  seconds=round(time.perf_counter() - t_all, 2))

    def read_back(self, srv: Server, host: int, tick: int) -> None:
        """Raw rows for one host over ten minutes (/query without
        bucket_ms): the acknowledged points, bit for bit."""
        start = T0 + tick * INTERVAL_MS
        ts, vals = self.data.raw(host, start, start + 600_000)
        got = srv.post_json("/query", {
            "metric": "cpu", "filters": {"host": self.data.host_names[host]},
            "start": start, "end": start + 600_000})
        check(got["tsids"] == [self.data.tsid_of_host[host]] * len(ts),
              f"read-back host {host} tick {tick}: tsids differ")
        check(got["timestamps"] == ts.tolist(),
              f"read-back host {host} tick {tick}: timestamps differ")
        check(got["values"] == vals.tolist(),
              f"read-back host {host} tick {tick}: values differ")

    def query_grid(self, srv: Server, name: str, rng: tuple, host=None,
                   routes=None, route=None) -> tuple[dict, dict]:
        """One /query downsample, checked against the reference and —
        given `routes` — against the route it must have taken; returns
        (answer, {wall_s, fns, h2d_bytes, d2h_bytes})."""
        body = {"metric": "cpu", "start": rng[0], "end": rng[1],
                "bucket_ms": BUCKET_MS}
        rows = list(range(HOSTS))
        if host is not None:
            body["filters"] = {"host": self.data.host_names[host]}
            rows = [host]
        before = srv.device()
        t0 = time.perf_counter()
        got = srv.post_json("/query", body)
        wall = time.perf_counter() - t0
        after = srv.device()
        order = [self.data.host_of_tsid[t] for t in got["tsids"]]
        check(sorted(order) == rows,
              f"{name}: expected series {rows[:3]}.., got {len(order)}")
        ref = self.data.downsample(*rng)
        compare_grids(name, got, ref, order)
        info = {"wall_s": round(wall, 3), **route_delta(before, after)}
        self.expect_route(name, info, routes, route)
        return got, info

    def route_for(self, rng: tuple) -> str:
        """The reader's own gate (storage/read.py _fused_agg_ok_base):
        a plan whose segments hold more rows than the scan-cache budget
        ([scan] cache_max_rows, 32 B a row) is declined by the fused
        accumulator and, under [scan.decode] mode = "auto", decoded on
        the device.  At 10M rows the full range is over it and four
        days are under."""
        per_seg = SEGMENT_MS // INTERVAL_MS
        seg_lo = (rng[0] - T0) // SEGMENT_MS
        seg_hi = -(-(min(rng[1], T0 + self.data.span) - T0) // SEGMENT_MS)
        ticks = min(seg_hi * per_seg, self.data.ticks) - seg_lo * per_seg
        return "decode" if ticks * HOSTS > self.cache_rows else "fused"

    def expect_route(self, name: str, delta: dict, routes: dict,
                     route) -> None:
        """A right grid from the numpy twin or from host decode has not
        passed: on the chip the route's device programs must have run.
        `route` None = any of `routes`; a route `routes` does not name
        carries no expectation."""
        if not self.on_chip or not routes:
            return
        fns = routes.get(route, ()) if route else sum(routes.values(), ())
        if not fns:
            return
        hit = [f for f in fns if delta["fns"].get(f, 0) > 0]
        check(bool(hit), f"{name}: none of {fns} dispatched — route "
                         f"was {delta['fns']}")
        self.routes_seen.update(
            r for r, fs in routes.items()
            if any(delta["fns"].get(f, 0) > 0 for f in fs))

    def queries(self, srv: Server, routes: dict, warm_routes: dict) -> dict:
        """The five query shapes.  `routes` are checked on the cold
        queries; `warm_routes` on the repeats, the point query and the
        top-k — only the routes that dispatch again when warm (the
        fused accumulator re-runs its rounds; a decoded range's repeat
        is folded from the parts memo without dispatching anything)."""
        answers = {}
        # 1. full range (over the cache budget -> device decode), then
        # 2. <= 4 days (under it -> fused accumulator + stacks): once
        # cold, then repeated — the sub-range over rotating ranges
        for key, ranges in (("full", [self.full]), ("sub", self.sub_ranges)):
            answers[key], cold = self.query_grid(
                srv, f"{key}.cold", ranges[0], routes=routes,
                route=self.route_for(ranges[0]))
            if self.on_chip:
                check(cold["h2d_bytes"] > 0, f"{key}.cold: no h2d bytes")
            self.step(f"query.{key}.cold", **cold)
            walls = []
            for i in range(self.args.repeats * len(ranges)):
                rng = ranges[i % len(ranges)]
                _, rep = self.query_grid(
                    srv, f"{key}.repeat{i}", rng, routes=warm_routes,
                    route=self.route_for(rng))
                walls.append(rep["wall_s"])
            self.step(f"query.{key}.repeat", walls_s=walls, fns=rep["fns"])

        # 3. one host, one hour (BASELINE config 2's shape)
        _, point = self.query_grid(srv, "point", self.point,
                                   host=self.point_host, routes=warm_routes)
        self.step("query.point", **point)

        # 4. top-k hosts by max (BASELINE config 4's shape)
        answers["topk"] = self.topk(srv, warm_routes)

        # 5. raw rows, one host, ten minutes
        t0 = time.perf_counter()
        self.read_back(srv, host=7, tick=self.data.ticks // 3)
        self.step("query.raw", wall_s=round(time.perf_counter() - t0, 3))
        return answers

    def topk(self, srv: Server, routes: dict) -> dict:
        rng = self.sub_ranges[-1]
        before = srv.device()
        t0 = time.perf_counter()
        got = srv.post_json("/query_topk", {
            "metric": "cpu", "start": rng[0], "end": rng[1],
            "bucket_ms": BUCKET_MS, "k": 10, "by": "max"})
        wall = time.perf_counter() - t0
        delta = route_delta(before, srv.device())
        ref = self.data.downsample(*rng)
        # best-first by score; hosts that tie on the score (f32 maxima
        # of ~35k samples do) may come back in either order
        score = ref["max"].max(axis=1)
        order = [self.data.host_of_tsid[t] for t in got["tsids"]]
        check(len(set(order)) == 10
              and score[order].tolist() == np.sort(score)[::-1][:10].tolist(),
              f"topk: hosts {order} are not the reference's ten best")
        compare_grids("topk", got, ref, order)
        self.expect_route("topk", delta, routes, None)
        self.step("query.topk", wall_s=round(wall, 3), **delta)
        return got

    def final_counters(self, srv: Server, allow_mesh=()) -> dict:
        """Fallback counters must be zero but for the reasons a leg
        declares structural; returns the device-plane totals."""
        dev = srv.device()
        fallbacks = {}
        for family, allowed in (
                ("scan_decode_fallback_total", ()),
                ("scan_mesh_fallback_total", allow_mesh)):
            seen = {k: v for k, v in srv.metric(family).items() if v}
            bad = {k: v for k, v in seen.items()
                   if not any(f'reason="{r}"' in k for r in allowed)}
            check(not bad, f"{srv.name}: {family} = {bad}")
            fallbacks[family] = seen
        if self.on_chip:
            devices = dev["devices"]
            check(len(devices) == self.args.chips
                  and all(d["device"].startswith("tpu:")
                          for d in devices),
                  f"{srv.name}: devices = {devices}")
            check(devices[0]["bytes_in_use"] > 0,
                  f"{srv.name}: no device memory in use: {devices}")
        out = {"compile_seconds": {f["fn"]: f["compile_seconds"]
                                   for f in dev["fns"] if f["compiles"]},
               "transfer": dev["transfer"], "devices": dev["devices"],
               "fallbacks": fallbacks}
        self.step(f"{srv.name}.counters", **out)
        return out

    def check_native(self) -> None:
        """`make clean` removed any prebuilt library: the one on disk
        now is the server's own on-demand build, and it loads."""
        lib = os.path.join(ROOT, "native", "libhoraedb_native.so")
        check(os.path.exists(lib),
              "the server did not build native/libhoraedb_native.so")
        check(native.available(), "the native library does not load")

    def check_logs(self, srv: Server) -> None:
        with open(srv.log_path, "rb") as f:
            raw = f.read()
        text = raw.decode(errors="replace")
        served = raw[:srv.served_log_bytes].decode(errors="replace")
        for needle, where in (("using numpy fallbacks", text),
                              ("using fallbacks", text),
                              ("compile cache unavailable", text),
                              ("Traceback", served)):
            check(needle not in where,
                  f"{srv.name}: log says {needle!r}; see {srv.log_path}")

    # ---- legs -------------------------------------------------------------

    def run_one_chip(self) -> None:
        routes = {"decode": ("_decode_aggregate_jit",),
                  "fused": ("_fused_round_accumulate_jit",)}
        srv = self.start("server1")
        # nothing has compiled yet: what the cache held before this run
        entries0 = cache_entries(self.cache_dir)
        try:
            self.ingest(srv)
            self.queries(srv, routes, {"fused": routes["fused"]})
            first = self.final_counters(srv)
        finally:
            srv.stop()
        self.check_logs(srv)
        self.check_native()
        if self.on_chip and self.data.rows >= 10_000_000:
            check({"decode", "fused"} <= self.routes_seen,
                  f"routes taken: {self.routes_seen} — the deployment "
                  f"size must drive both")
        entries1 = cache_entries(self.cache_dir)

        # restart: a second process, same data dir, same cache dir
        srv = self.start("server2")
        try:
            _, a = self.query_grid(srv, "restart.full", self.full,
                                   routes=routes,
                                   route=self.route_for(self.full))
            _, b = self.query_grid(srv, "restart.sub", self.sub_ranges[0],
                                   routes=routes,
                                   route=self.route_for(self.sub_ranges[0]))
            self.read_back(srv, host=3, tick=self.data.ticks // 2)
            second = self.final_counters(srv)
        finally:
            srv.stop()
        self.check_logs(srv)
        entries2 = cache_entries(self.cache_dir)
        c1 = sum(first["compile_seconds"].values())
        c2 = sum(second["compile_seconds"].values())
        self.step("restart", first_compile_s=round(c1, 2),
                  second_compile_s=round(c2, 2),
                  cache_entries=[entries0, entries1, entries2],
                  full_wall_s=a["wall_s"], sub_wall_s=b["wall_s"])
        check(entries1 > 0,
              f"no compile-cache entries in {self.cache_dir}")
        check(entries2 == entries1,
              f"second process wrote {entries2 - entries1} new "
              f"compile-cache entries")
        if entries0 == 0:
            # a cold first process: the second's compile seconds (trace
            # + cache load) must be a small fraction of its XLA compiles
            check(c2 < 0.5 * c1,
                  f"second process compile {c2:.1f}s is not well under "
                  f"the first's {c1:.1f}s")

    def run_four_chips(self) -> None:
        # [scan.decode] mode = "device": every eligible plan, whatever
        # its size, rides the fused mesh-decode rounds — once; repeats,
        # narrowed ranges and the top-k then fold the per-segment parts
        # those rounds left in the parts memo, dispatching nothing
        mesh_fns = ("mesh_decode_partials", "mesh_run_partials")
        routes = {"decode": mesh_fns, "fused": mesh_fns}
        srv = self.start("mesh", {("mesh", "enabled"): "true",
                                  ("decode", "mode"): '"device"'})
        try:
            self.ingest(srv)
            mesh_answers = self.queries(srv, routes, {})
            out = self.final_counters(srv, allow_mesh=MESH_STRUCTURAL)
            rounds = sum(srv.metric("scan_mesh_rounds_total").values())
            decode_rounds = fn_table(srv.device()).get(
                "mesh_decode_partials", {"dispatches": 0, "compiles": 0})
            n_decode = (decode_rounds["dispatches"]
                        + decode_rounds["compiles"])
            rows = srv.metric("scan_decode_rows_total")
            self.step("mesh.rounds", mesh_rounds=rounds,
                      mesh_decode_rounds=n_decode, decode_rows=rows)
            check(rounds > 0, "no mesh rounds ran")
            check(n_decode > 0, "no fused mesh-decode rounds ran")
            # the point query names one host: its plans reach their
            # round narrowed to that host's rows
            check(0 < rows.get('side="uploaded"', 0)
                  < rows.get('side="stored"', 0),
                  f"no narrowed plan rode a mesh round: {rows}")
            if self.on_chip:
                idle = [d for d in out["devices"]
                        if d["bytes_in_use"] <= 0]
                check(not idle, f"devices holding no bytes: {idle}")
        finally:
            srv.stop()
        self.check_logs(srv)
        self.check_native()

        # the single-chip control on the same data: mesh == one chip
        srv = self.start("control")
        try:
            ctl = {
                "full": self.query_grid(srv, "control.full", self.full)[0],
                "sub": self.query_grid(srv, "control.sub",
                                       self.sub_ranges[0])[0],
                "topk": self.topk(srv, {}),
            }
            self.final_counters(srv)
        finally:
            srv.stop()
        self.check_logs(srv)
        for key, want in ctl.items():
            got = mesh_answers[key]
            # same series; hosts tying on a top-k score may swap places
            check(sorted(got["tsids"]) == sorted(want["tsids"]),
                  f"mesh vs control {key}: series differ")
            rows = [want["tsids"].index(t) for t in got["tsids"]]
            for agg in ALL_AGGS:
                g, w = grid_of(got, agg), grid_of(want, agg)[rows]
                if agg in ("sum", "avg"):
                    same = np.allclose(g, w, rtol=1e-6, atol=0,
                                       equal_nan=True)
                else:
                    same = np.array_equal(g, w, equal_nan=True)
                check(same, f"mesh vs control {key}: {agg} differs")
        self.step("mesh_vs_control", equal=sorted(ctl))

    def run(self) -> None:
        d = self.data
        say(f"chip_smoke: rows={d.rows} hosts={HOSTS} ticks={d.ticks} "
            f"segments={-(-d.span // SEGMENT_MS)} chips={self.args.chips} "
            f"expect platform={self.args.platform}")
        # built from what git would commit: drop any prebuilt library
        # and let the program's own on-demand build make it
        subprocess.run(["make", "-C", os.path.join(ROOT, "native"),
                        "clean"], check=True, capture_output=True)
        if self.args.chips == 1:
            self.run_one_chip()
        else:
            self.run_four_chips()
        check("jax" not in sys.modules,
              "the driving process imported jax")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"),
                    help="platform the server must report (default tpu)")
    ap.add_argument("--rows", type=int, default=10_000_000)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=2,
                    help="repeats of each repeated query")
    ap.add_argument("--out", default=None,
                    help="output directory for logs + summary.json")
    args = ap.parse_args()

    out_dir = args.out or os.path.join(
        ROOT, "chiprun_out",
        "chip_smoke" if args.chips == 1 else f"chip_smoke_{args.chips}chip")
    os.makedirs(out_dir, exist_ok=True)
    data_dir = tempfile.mkdtemp(prefix="horaedb-chip-smoke-")
    smoke = Smoke(args, out_dir, data_dir)
    # a killed run still unwinds: every leg stops its server in finally
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    t0 = time.perf_counter()
    ok = False
    try:
        smoke.run()
        ok = True
    except SmokeFailure as e:
        smoke.summary["failure"] = str(e)
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
        smoke.summary.update(
            ok=ok, device=smoke.device_id,
            wall_s=round(time.perf_counter() - t0, 1), claim=None)
        with open(os.path.join(out_dir, "summary.json"), "w",
                  encoding="utf-8") as f:
            json.dump(smoke.summary, f, indent=1)
            f.write("\n")
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": smoke.device_id}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
