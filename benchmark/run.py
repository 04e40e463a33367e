"""One cell of BENCHMARK.json, once, in a new process.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts the program's server on the chip (a child process: the one
holder of the device), loads the cell's TSBS data over HTTP, warms up
until the compile ledger is still, lays a window of `--seconds` over a
closed loop of clients that is already in steady state, and only after
the window has closed parses and compares every response with the
numpy reference.  This process never imports jax and does nothing but
I/O inside the window.

The last line of stdout is the one JSON object the contract fixes.
Earlier lines: the set-up split, the route (traced runs), completions
and median latency per quarter of the window, and every number the
check compares beside its limit.

`--platform cpu` is the explicit rehearsal switch (tiny scales, here in
the sandbox); without it a run that finds no TPU fails and prints no
result.  `--control bf16` adds the control of "How correct is decided":
the reference recomputed from values rounded to bfloat16, which must
come out NOT correct.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402
from pyarrow import ipc  # noqa: E402

from benchmark.harness import counters, layers, manifest, roofline  # noqa: E402
from benchmark.harness import stats  # noqa: E402
from benchmark.harness.dataset import Dataset, round_bf16  # noqa: E402
from benchmark.harness.loadgen import LoadGen  # noqa: E402
from benchmark.harness.server import BenchError, Server  # noqa: E402

STREAM = 8192          # pre-encoded queries per run (wraps if exhausted)
TRACE_SECONDS = 3.0    # profiler window, in the middle of a traced run
MAX_WARM_PASSES = 30
SETTLE_LIMIT_S = 240.0
LAUNCHER = "benchmark.harness.launcher"


def say(msg: str) -> None:
    print(msg, flush=True)


def _sleep_until(t: float) -> None:
    left = t - time.perf_counter()
    if left > 0:
        time.sleep(left)


class Cell:
    """One run of one cell: set-up, window, check, reduction."""

    def __init__(self, root: str, workload: str, seed: int, seconds: float,
                 trace: bool, platform: str = "tpu", control: str = "none",
                 out_dir: str | None = None, launcher: str = LAUNCHER):
        self.man = manifest.load(root)
        if workload not in self.man.workloads:
            raise BenchError(f"unknown workload {workload!r}")
        self.workload = workload
        self.cell = self.man.workloads[workload]
        self.config = self.man.config(workload)
        self.traffic = self.man.traffic(workload)
        self.op = importlib.import_module(
            f"benchmark.operations.{self.traffic['operation']}")
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.platform, self.control = platform, control
        self.launcher = launcher
        self.out_dir = out_dir or os.path.join(
            root, ".bench_out", f"{workload}-seed{seed}-trace{int(trace)}")
        self.setup: dict = {}
        self.compactions = 0

    # ---- set-up -----------------------------------------------------------

    def _env(self) -> dict:
        if self.platform != "cpu":
            return {}
        # the rehearsal: on the CPU backend the engine would serve from
        # its numpy twin and host decode; force the XLA programs so the
        # counters and routes below exist.  A chip run sets nothing.
        return {"JAX_PLATFORMS": "cpu", "HORAEDB_HOST_AGG": "0",
                "HORAEDB_DEVICE_DECODE": "1", "HORAEDB_COMPILE_CACHE": "0"}

    def _ingest(self, srv: Server, data: Dataset, rng) -> None:
        ing = self.config["ingest"]
        per_body = max(1, int(ing["body_rows"]) // data.hosts)
        path = (f"/write_arrow?metric={data.metric}"
                f"&tags={','.join(data.tags)}&field=")
        ranges = [(lo, min(data.ticks, lo + per_body))
                  for lo in range(0, data.ticks, per_body)]
        # A loaded store at rest, as TSBS queries one.  Every body
        # leaves an SST in each segment it covers; the server's picker
        # rewrites a segment once it holds five, on its own 10 s timer
        # or when asked.  Left to the timer it catches some segments
        # with most but not all of their ten fields' SSTs, the rest
        # then stay apart for good, and the scan takes another, slower
        # program: which run gets that is chance.  So compaction is
        # asked for, and awaited, after every `settle_every_bodies`.
        every = int(ing.get("settle_every_bodies", 0))
        t0, settle_s, n = time.perf_counter(), 0.0, 0
        # the next body is built while the server stores this one
        with ThreadPoolExecutor(max_workers=1) as pool:
            stream = ((lo, hi, field, body) for lo, hi in ranges
                      for field, body in data.bodies(lo, hi))
            ahead = pool.submit(next, stream, None)
            while (item := ahead.result()) is not None:
                ahead = pool.submit(next, stream, None)
                lo, hi, field, body = item
                ack = json.loads(srv.request("POST", path + field, body))
                if ack.get("written") != (hi - lo) * data.hosts:
                    raise BenchError(
                        f"ingest: {field} [{lo},{hi}) acked {ack}")
                n += 1
                if every and n % every == 0:
                    ts = time.perf_counter()
                    self._settle(srv)
                    settle_s += time.perf_counter() - ts
        t1 = time.perf_counter()
        srv.request("POST", "/admin/flush")
        self._settle(srv)
        t2 = time.perf_counter()
        # a sample of the acknowledged bodies, each read back whole and
        # compared bit for bit: every row of it, and no other row
        n_bodies = len(ranges) * len(data.fields)
        picks = rng.choice(n_bodies, replace=False,
                           size=min(int(ing["readback_bodies"]), n_bodies))
        for b in picks:
            lo, hi = ranges[int(b) // len(data.fields)]
            self._read_back(srv, data, int(b) % len(data.fields), lo, hi)
        self.setup.update(ingest_s=t1 - t0 - settle_s,
                          settle_s=settle_s + t2 - t1,
                          compactions=self.compactions,
                          data_ssts=srv.get_json("/stats")["tables"]
                          ["data"]["ssts"], readback_s=time.perf_counter() - t2,
                          rows=data.rows, points=data.rows * len(data.fields),
                          bodies=n_bodies, readbacks=len(picks))

    def _settle(self, srv: Server) -> None:
        """Ask every table for compaction and wait until a further
        trigger finds nothing more to do."""
        done, t0 = None, time.perf_counter()
        while True:
            asked = time.perf_counter()
            srv.request("GET", "/compact")
            while counters.compaction_busy(
                    srv, time.perf_counter() - asked):
                if time.perf_counter() - t0 > SETTLE_LIMIT_S:
                    raise BenchError("compaction did not come to rest")
                time.sleep(0.05)
            self.compactions = counters.compactions(srv)
            if self.compactions == done:
                return
            done = self.compactions

    def _read_back(self, srv: Server, data: Dataset, f: int, lo: int,
                   hi: int) -> None:
        start = data.t0 + lo * data.interval_ms
        end = data.t0 + hi * data.interval_ms
        got = ipc.open_stream(srv.request("POST", "/query_arrow", {
            "metric": data.metric, "field": data.fields[f],
            "start": start, "end": end})).read_all()
        where = f"read-back of {data.fields[f]} ticks [{lo},{hi})"
        if got.num_rows != (hi - lo) * data.hosts:
            raise BenchError(f"{where}: {got.num_rows} rows, "
                             f"{(hi - lo) * data.hosts} were acknowledged")
        tsid = got.column("tsid").to_numpy()
        ts = got.column("timestamp").to_numpy()
        try:
            host = np.array([data.host_of_tsid[str(t)]
                             for t in np.unique(tsid)])[
                np.unique(tsid, return_inverse=True)[1]]
        except KeyError as e:
            raise BenchError(f"{where}: unknown series {e}")
        tick = (ts - data.t0) // data.interval_ms
        want = data.values[f][np.clip(tick, lo, hi - 1), host]
        if (((ts - data.t0) % data.interval_ms).any()
                or (tick < lo).any() or (tick >= hi).any()
                or len(np.unique(tick * data.hosts + host)) != got.num_rows
                or not np.array_equal(
                    got.column("value").to_numpy(),
                    want.astype(np.float64))):
            raise BenchError(f"{where}: differs from what was written")

    def _warm_up(self, srv: Server, gen: LoadGen, sweep: list) -> None:
        """Ends on a state, not a count: every segment touched by the
        sweep, then the clients' own loop until the compile ledger has
        stood still for a whole pass (at least 2 x clients queries)."""
        t0 = time.perf_counter()
        for q in sweep:
            srv.request("POST", self.traffic["endpoint"], q["body"])
        t1 = time.perf_counter()
        gen.start()
        per_pass = max(2 * int(self.traffic["clients"]),
                       int(self.traffic["warmup"]["pass_queries"]))
        passes = 0
        while True:
            before, n0 = counters.compiles(srv), gen.completed()
            while gen.completed() < n0 + per_pass:
                if srv.proc.poll() is not None:
                    raise BenchError(f"server died in warm-up\n{srv.tail()}")
                time.sleep(0.02)
            passes += 1
            if counters.compiles(srv) == before:
                break
            if passes >= MAX_WARM_PASSES:
                raise BenchError("warm-up: the compile ledger never stood "
                                 "still")
        self.setup.update(sweep_s=t1 - t0, sweep_queries=len(sweep),
                          warm_loop_s=time.perf_counter() - t1,
                          warm_passes=passes)

    # ---- the run ----------------------------------------------------------

    def run(self) -> dict:
        os.makedirs(self.out_dir, exist_ok=True)
        data_dir = tempfile.mkdtemp(prefix="horaedb-bench-")
        trace_dir = os.path.join(self.out_dir, "profile")
        shutil.rmtree(trace_dir, ignore_errors=True)
        srv = Server(self.out_dir, data_dir, self.config, self.platform,
                     self._env(), launcher=self.launcher)
        gen = None
        try:
            # the data is made while the child starts JAX
            data = Dataset(self.config, self.seed)
            rng = np.random.default_rng([int(self.seed), 0x51EED])
            queries = self.op.make_queries(self.traffic, data, rng, STREAM)
            sweep = self.op.sweep_queries(self.traffic, data)
            t_data = time.perf_counter()
            srv.wait_ready()
            device = srv.device()
            if (device["platform"] != self.platform
                    or (self.platform != "cpu"
                        and device["count"] < self.cell["chips"])):
                raise BenchError(f"device {device} is not what "
                                 f"{self.workload} asks for")
            t_ready = time.perf_counter()
            self._ingest(srv, data, rng)
            gen = LoadGen(srv.port, self.traffic["endpoint"],
                          [q["body"] for q in queries],
                          int(self.traffic["clients"]))
            self._warm_up(srv, gen, sweep)
            self.setup.update(data_s=t_data - T_PROCESS,
                              server_ready_s=t_ready - T_PROCESS)

            obs: dict = {}
            if self.trace:
                before = counters.read(srv)
                n_before = gen.completed()
            gc.collect()
            gc.freeze()
            gc.disable()
            t_start = time.perf_counter()
            if self.trace:
                t_trace = self._traced_window(srv, gen, t_start, trace_dir,
                                              obs)
            else:
                time.sleep(self.seconds)
            t_end = time.perf_counter()
            gc.enable()
            if self.trace:
                obs["counters"] = counters.delta(before, counters.read(srv))
                obs["queries"] = gen.completed() - n_before
            samples = gen.stop()
            gen = None
            say(f"compactions_in_window = "
                f"{counters.compactions(srv) - self.compactions}")
            if self.trace:
                obs["spans"] = counters.query_spans(
                    srv, self.traffic["endpoint"])
                if obs["counters"].get("device.compiles"):
                    say("compiled_in_window " + json.dumps(
                        counters.compile_keys(srv)))
            device = srv.device()
        finally:
            if gen is not None:
                gen.stop()
            srv.stop()
            shutil.rmtree(data_dir, ignore_errors=True)

        setup_s = t_start - T_PROCESS
        say("setup " + json.dumps(
            {k: (round(v, 3) if isinstance(v, float) else v)
             for k, v in {"setup_s": setup_s, **self.setup}.items()}))
        result = self._judge(samples, queries, data, t_start, t_end)
        result["metrics"]["setup_s"] = setup_s
        if self.trace:
            # queries' worth of work per second while the profiler ran
            # (whole completions in 3 s step by a quarter where a query
            # takes 2 s); the reduction turns it into queries per
            # traced device window
            obs["trace_rate"] = stats.window_rate(
                [(s.t_send, s.t_done) for s in samples if s.status == 200],
                *t_trace)
            result["metrics"] = self._layer_metrics(obs, device, data)
            device.update(busy_s=obs["trace"].get("busy_s", 0.0),
                          window_s=obs["trace"].get("window_s", 0.0))
            result["breakdown"] = {
                "device_ops": obs["trace"].get("device_ops", []),
                "idle_gaps": obs["trace"].get("idle_gaps", [])}
        else:
            result["metrics"] = {
                m["name"]: {"value": result["metrics"][m["name"]],
                            "unit": m["unit"]}
                for m in self.man.end_to_end(self.workload)}
        result["device"] = device
        return result

    def _traced_window(self, srv, gen, t_start, trace_dir, obs) -> tuple:
        """The window of a traced run: the profiler traces a few
        seconds in its middle."""
        span = min(TRACE_SECONDS, self.seconds / 2.0)
        _sleep_until(t_start + (self.seconds - span) / 2.0)
        srv.control(cmd="trace_start", dir=trace_dir)
        t0 = time.perf_counter()
        time.sleep(span)
        t1 = time.perf_counter()
        srv.control(cmd="trace_stop")
        _sleep_until(t_start + self.seconds)
        obs["trace_dir"] = trace_dir
        return t0, t1

    # ---- after the window -------------------------------------------------

    def _judge(self, samples, queries, data, t_start, t_end) -> dict:
        """Every response whose request touched the window against the
        reference; then the end-to-end numbers over the good ones:
        latencies of those that completed inside it, the rate over all
        of them by the share of each that lies inside."""
        limits = self.traffic["limits"]
        touching = [s for s in samples
                    if s.t_done >= t_start and s.t_send <= t_end]
        window = [s for s in touching if s.t_done <= t_end]
        readings, good = [], []
        t0 = time.perf_counter()
        control_vals = round_bf16(data.grid) if self.control == "bf16" \
            else None
        control_readings = []
        for s in touching:
            if s.status != 200:
                continue
            q = queries[s.index % len(queries)]
            r = self.op.check(q, s.body, data)
            readings.append(r)
            if not any(r[k] > limits[k] for k in limits):
                good.append(s)
            if control_vals is not None:
                control_readings.append(
                    self.op.check(q, s.body, data, values=control_vals))
        correct = self._verdict("check", readings, limits)
        say(f"check responses_compared = {len(readings)} of "
            f"{len(touching)} that touched the window, {len(window)} "
            f"completed in it ({time.perf_counter() - t0:.2f}s after it)")
        if control_vals is not None:
            label = f"control[{self.control}]"
            ctl_correct = self._verdict(label, control_readings, limits)
            say(f"{label} correct = {ctl_correct} (sound reading: correct "
                f"= {correct}); the final line reports the control")
            correct = ctl_correct
        if len(readings) < len(touching):
            say(f"refused_or_errored = {len(touching) - len(readings)} "
                f"statuses = "
                f"{sorted({s.status for s in touching if s.status != 200})}")
        timed = [s for s in good if s.t_done <= t_end]
        failed = len(window) - len(timed)
        rated = good
        if len(timed) < 2:
            # nothing right to time: the line stays well-formed (and
            # `correct` false) over whatever was answered at all
            rated = [s for s in touching if s.status == 200]
            timed = [s for s in rated if s.t_done <= t_end]
            say(f"fewer than 2 good responses: the numbers below are "
                f"over all {len(timed)} answered")
        if len(timed) < 2:
            raise BenchError(f"{len(timed)} answers in the window: no "
                             f"metric can be taken")
        lat = [s.t_done - s.t_send for s in timed]
        metrics = {
            "query_p50_ms": stats.percentile(lat, 50) * 1e3,
            "query_p95_ms": stats.percentile(lat, 95) * 1e3,
            "queries_per_s": stats.window_rate(
                [(s.t_send, s.t_done) for s in rated], t_start, t_end)}
        say(f"whole completions / seconds = "
            f"{len(timed) / (t_end - t_start)!r}")
        say("quarters " + json.dumps(stats.quarters(
            [(s.t_done, s.t_done - s.t_send) for s in timed],
            t_start, t_end)))
        say("longest_completion_gaps " + json.dumps(stats.longest_gaps(
            [s.t_done for s in timed], t_start)))
        say(f"sample n = {len(timed)} highest supported percentile = "
            f"p{stats.supported_percentile(len(timed))}")
        return {"correct": correct, "attempted": len(window),
                "failed": failed, "metrics": metrics}

    def _verdict(self, label: str, readings: list, limits: dict) -> bool:
        """Print each number compared beside its limit; true when there
        was something to compare and every number is within its limit."""
        total = self.op.combine(readings)
        ok_all = bool(readings)
        for k, limit in limits.items():
            ok = total[k] <= limit
            ok_all &= ok
            say(f"{label} {k} = {total[k]!r} limit {limit!r} "
                f"{'ok' if ok else 'OVER'}")
        return ok_all

    def _layer_metrics(self, obs: dict, device: dict, data) -> dict:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "benchmark.harness.xplane",
             obs["trace_dir"]], cwd=ROOT, env=env, capture_output=True,
            text=True, timeout=300)
        if proc.returncode != 0:
            raise BenchError(f"trace reduction failed: {proc.stderr[-2000:]}")
        trace = json.loads(proc.stdout.splitlines()[-1])
        trace["queries"] = obs["trace_rate"] * trace.get("window_s", 0.0)
        obs["trace"] = trace
        shutil.rmtree(obs["trace_dir"], ignore_errors=True)
        hosts = data.hosts if self.traffic["hosts"] == "all" \
            else int(self.traffic["hosts"])
        rows = hosts * int(self.traffic["window_ms"]) // data.interval_ms
        buckets = -(-int(self.traffic["window_ms"])
                    // int(self.traffic["bucket_ms"]))
        nbytes = roofline.scan_min_bytes(
            rows, int(self.config["device_row_bytes"]), hosts, buckets,
            int(self.traffic["output_grids"]))
        if trace.get("devices"):
            obs["scan"] = {"min_seconds_per_query":
                           roofline.least_seconds(nbytes, device["kind"])}
        route = {k[len("device.fn."):-len(".calls")]: v
                 for k, v in obs["counters"].items()
                 if k.startswith("device.fn.") and k.endswith(".calls")
                 and v}
        say("route " + json.dumps({
            "queries": obs["queries"], "calls_per_fn": route,
            "h2d_bytes": obs["counters"].get("device.transfer.h2d.bytes"),
            "compiles": obs["counters"].get("device.compiles"),
            "scan_min_bytes_per_query": nbytes,
            "trace": {k: trace.get(k) for k in
                      ("devices", "window_s", "busy_s", "op_seconds",
                       "op_events", "queries")}}))
        out = {}
        for m in self.man.per_layer(self.workload):
            value = layers.evaluate(self.man.reader(m["name"]), obs)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"),
                    help="cpu = explicit rehearsal; never detected")
    ap.add_argument("--control", default="none", choices=("none", "bf16"))
    ap.add_argument("--out", default=None, help="directory for logs")
    ap.add_argument("--root", default=ROOT,
                    help="directory holding BENCHMARK.json and the data "
                         "files (tests point it at a temporary copy)")
    args = ap.parse_args(argv)
    # a killed run still unwinds: the server child is stopped in finally
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        if not os.path.isdir(os.path.join(ROOT, "horaedb_tpu")):
            raise BenchError("no program beside the benchmark: nothing "
                             "to measure")
        cell = Cell(args.root, args.workload, args.seed, args.seconds,
                    bool(args.trace), args.platform, args.control, args.out)
        result = cell.run()
    except (BenchError, manifest.ManifestError) as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 1
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
