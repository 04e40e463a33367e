"""The scan's phase spans and wait counters (docs/observability.md,
scan phases): the children of `downsample` on both device routes, the
same spans on the profiler's clock, the waits where the work waits
(pools, the event loop, the collector), and the seams that split the
sync from the copy."""

import asyncio
import gc
import glob
import logging
import time

import jax
import pytest
from aiohttp.test_utils import TestClient, TestServer

from horaedb_tpu.common import deviceprof
from horaedb_tpu.common import loops as loops_mod
from horaedb_tpu.common.loops import LoopRegistry
from horaedb_tpu.common.runtimes import Runtimes
from horaedb_tpu.metric_engine import MetricEngine
from horaedb_tpu.objstore import InstrumentedStore, MemoryObjectStore
from horaedb_tpu.ops import device_decode
from horaedb_tpu.server import main as server_main
from horaedb_tpu.server.config import ServerConfig
from horaedb_tpu.server.main import ServerState, build_app
from horaedb_tpu.storage import read as read_mod
from horaedb_tpu.storage.types import TimeRange
from horaedb_tpu.utils import registry, tracing
from horaedb_tpu.utils.tracing import (
    SCAN_PHASES,
    clear_phases,
    recorder,
    span,
    trace_scope,
)

T0 = 1_700_000_000_000
HOUR = 3_600_000
QUERY = {"metric": "cpu", "start": T0 + 7, "end": T0 + 3 * HOUR + 7,
         "bucket_ms": 600_000}

DECODE_FN = "_decode_aggregate_jit"
DECODE_ENV = {"HORAEDB_DEVICE_DECODE": "1", "HORAEDB_HOST_AGG": "0"}
ROUTES = {
    # case -> (environment, phases a query of it has, the plan's route)
    "fused_acc": ({"HORAEDB_FUSED_AGG": "1", "HORAEDB_HOST_AGG": "0"},
                  set(SCAN_PHASES) - {"scan.combine"}, "fused_acc"),
    # every segment read, narrowed and uploaded (the scan cache holds
    # no slice of it)
    "device_decode": (DECODE_ENV, set(SCAN_PHASES), "device_decode"),
    # every segment's narrowed slice resident on the device: nothing
    # to narrow and nothing to group, one dispatch for them all
    "device_decode_hit": (DECODE_ENV,
                          set(SCAN_PHASES) - {"scan.group_prep"},
                          "device_decode"),
}


def resident_outcomes() -> dict:
    return {o: c.value for o, c in device_decode._RESIDENT.items()}


def drop_slices(engine) -> None:
    """The next device-decode query reads, narrows and uploads."""
    engine.tables["data"].reader.scan_cache.clear()


def run(coro):
    return asyncio.run(coro)


def phase_counts(table: str) -> dict:
    fam = registry.family("scan_phase_seconds")
    return {p: fam.labels(phase=p, table=table).count for p in SCAN_PHASES}


def sync_seams() -> int:
    """How often a sync seam of the data table was passed: every pass
    observes the `scan.device_wait` phase once, the wait it found or
    0."""
    return phase_counts("data")["scan.device_wait"]


def assert_sync_seam_per_dispatch(seams: int, dispatches, waits):
    """The device-decode route passes `deviceprof.download`'s sync seam
    once per dispatch (a batch of resident slices is one dispatch and
    one download).  By that seam's contract a dispatch still
    running at its finalize leaves a `scan.device_wait` span and one
    that has finished (XLA-CPU over a few hundred rows) an observation
    of 0 in the phase's histogram and no span: which of the two is the
    device's pace, that one of them happened per dispatch is the
    route's."""
    assert dispatches and seams == len(dispatches)
    assert len(waits) <= seams
    assert all(w["fields"]["fn"] == DECODE_FN for w in waits)


def walk(node):
    yield node
    for child in node["children"]:
        yield from walk(child)


async def served(fn):
    """`fn(client, engine)` against a served engine holding four hosts
    of 200 one-minute samples, flushed to SSTs."""
    engine = await MetricEngine.open(
        "phases_db", InstrumentedStore(MemoryObjectStore()),
        segment_ms=2 * HOUR)
    client = TestClient(TestServer(build_app(
        ServerState(engine, ServerConfig()))))
    await client.start_server()
    try:
        for h in range(4):
            r = await client.post("/write", json={"samples": [
                {"name": "cpu", "labels": {"host": f"h{h}"},
                 "timestamp": T0 + i * 60_000, "value": float(i)}
                for i in range(200)]})
            assert r.status == 200
        return await fn(client, engine)
    finally:
        await client.close()
        await engine.close()


class TestPhaseSpans:
    @pytest.mark.parametrize("route", sorted(ROUTES))
    def test_served_query_yields_every_phase_under_downsample(
            self, route, monkeypatch):
        env, want_phases, plan_route = ROUTES[route]
        for k, v in env.items():
            monkeypatch.setenv(k, v)

        async def go(client, engine):
            body = dict(QUERY, filters={"host": "h1"})
            r = await client.post("/query", json=body)  # compiles
            assert r.status == 200
            # another window each time, so the parts memo serves none.
            # The hit case asks until a query found every slice on the
            # device: a compaction behind the first flush changes the
            # SST set once, and with it the keys
            for start in range(T0 + 9, T0 + 14):
                if route == "device_decode":
                    drop_slices(engine)
                before = phase_counts("data")
                index_before = phase_counts("index")
                seams_before = sync_seams()
                probes = resident_outcomes()
                r = await client.post("/query",
                                      json=dict(body, start=start))
                assert r.status == 200
                probes = {o: n - probes[o]
                          for o, n in resident_outcomes().items()}
                if route != "device_decode_hit" or not probes["miss"]:
                    break
            tid = r.headers["X-Trace-Id"]
            tree = (await (await client.get(
                f"/debug/traces/{tid}")).json())["tree"]
            return tree, before, phase_counts("data"), index_before, \
                phase_counts("index"), \
                sync_seams() - seams_before, probes

        tree, before, after, index_before, index_after, seams, probes = \
            run(served(go))
        top = {c["name"]: c for c in tree["children"]}
        assert {"parse", "resolve", "downsample", "respond"} <= set(top)
        ds = top["downsample"]
        lo, hi = ds["start_ms"], ds["start_ms"] + ds["duration_ms"]
        phases = [c for c in ds["children"] if c["name"] in SCAN_PHASES]
        got_phases = {c["name"] for c in phases}
        if plan_route == "device_decode":
            # the wait seam, span or not: once per dispatch
            dispatches = [c for c in phases if c["name"] == "scan.dispatch"]
            assert_sync_seam_per_dispatch(
                seams, dispatches,
                [c for c in phases if c["name"] == "scan.device_wait"])
            assert got_phases | {"scan.device_wait"} == want_phases
            # a miss is a dispatch of its own, after its upload; the
            # plan's hits go out together: ONE dispatch phase that says
            # how many slices it took, carries no bytes, and is synced
            # and downloaded once
            hit = route == "device_decode_hit"
            if hit:
                batch, = dispatches
                assert batch["fields"]["slices"] >= 2
                assert probes == {"hit": batch["fields"]["slices"],
                                  "miss": 0, "bypass": 0}
                assert len([c for c in phases
                            if c["name"] == "scan.d2h"]) == 1
            else:
                assert probes == {"hit": 0, "miss": len(dispatches),
                                  "bypass": 0}
            assert all((c["fields"]["h2d_bytes"] == 0) == hit
                       for c in dispatches)
        else:
            # one small round of host rows: ONE dispatch, named, and
            # ONE pass of the download's sync seam (a span only where
            # the program still ran), one copy
            dispatch, = [c for c in phases if c["name"] == "scan.dispatch"]
            assert dispatch["fields"]["fn"] == "_fused_one_call_jit"
            assert seams == 1
            assert got_phases | {"scan.device_wait"} == want_phases
            assert len([c for c in phases if c["name"] == "scan.d2h"]) == 1
        for c in phases:
            # starts are wall clock, durations perf_counter: 1 ms slack
            assert lo - 1.0 <= c["start_ms"]
            assert c["start_ms"] + c["duration_ms"] <= hi + 1.0
            assert c["fields"]["table"] == "data"
        routed = [c["fields"]["route"] for c in phases
                  if c["name"] == "scan.plan" and "route" in c["fields"]]
        assert routed == [plan_route]
        # the pool hops close the span too, waits included
        hops = [c for c in walk(ds) if c["name"] == "pool_hop"]
        assert hops and all(
            {"pool", "wait_ms", "run_ms", "resume_ms"} <= set(c["fields"])
            for c in hops)
        # resolve scans the index table through the same reader only
        # where it builds a segment's posting lists or bypasses them
        # (its `postings` field says which): those phases carry that
        # table and stay out of the data table's histograms, which
        # moved by exactly this query's data spans
        resolve_phases = [c for c in walk(top["resolve"])
                          if c["name"] in SCAN_PHASES]
        assert all(c["fields"]["table"] != "data" for c in resolve_phases)
        scanned = "build=0 bypass=0" not in top["resolve"]["fields"][
            "postings"]
        assert scanned == any(c["fields"]["table"] == "index"
                              for c in resolve_phases)
        assert scanned == (
            index_after["scan.plan"] > index_before["scan.plan"])
        data_spans = [c for c in walk(tree) if c["name"] in SCAN_PHASES
                      and c["fields"]["table"] == "data"]
        for p in SCAN_PHASES:
            spans_of_p = sum(1 for c in data_spans if c["name"] == p)
            if p == "scan.device_wait":
                # the download's sync seam observes every pass, with a
                # span only where something still ran: the series is
                # there (and reads 0) on a route that never waits
                assert spans_of_p <= after[p] - before[p]
                if plan_route == "device_decode":
                    assert after[p] - before[p] == seams
                continue
            assert after[p] - before[p] == spans_of_p, p

    def test_narrowing_runs_inside_group_prep_and_adds_no_span(
            self, monkeypatch):
        """The device-decode dispatch's host narrowing (a segment cut
        to its key leaves' rows before the upload) is charged to the
        `scan.group_prep` span that already wraps plan_dispatch: one
        such span a segment as before, no span of a new name, and the
        `scan.dispatch` span beside it carries the smaller upload."""
        for k, v in DECODE_ENV.items():
            monkeypatch.setenv(k, v)
        calls = []
        real = device_decode._narrow_to_key_leaves

        def timed(es, *a):
            t0 = time.time() * 1e3
            out = real(es, *a)
            calls.append((t0, time.time() * 1e3, es.n, out.n))
            return out

        monkeypatch.setattr(device_decode, "_narrow_to_key_leaves", timed)

        async def go(client, engine):
            body = dict(QUERY, filters={"host": "h1"})
            r = await client.post("/query", json=body)  # compiles
            assert r.status == 200
            del calls[:]
            drop_slices(engine)  # or a resident slice narrows nothing
            r = await client.post("/query", json=dict(body,
                                                      start=T0 + 9))
            assert r.status == 200
            return (await (await client.get(
                f"/debug/traces/{r.headers['X-Trace-Id']}")).json())["tree"]

        tree = run(served(go))
        ds, = [c for c in tree["children"] if c["name"] == "downsample"]
        under = list(walk(ds))
        assert {c["name"] for c in under} \
            <= set(SCAN_PHASES) | {"downsample", "pool_hop"}
        # plan_dispatch's spans: the group_prep ones that carry rows
        preps = sorted((c for c in under if c["name"] == "scan.group_prep"
                        and "rows" in c["fields"]),
                       key=lambda c: c["start_ms"])
        dispatches = sorted((c for c in under
                             if c["name"] == "scan.dispatch"),
                            key=lambda c: c["start_ms"])
        assert len(calls) == len(preps) == len(dispatches) >= 1
        for (t0, t1, stored, kept), prep, disp in zip(
                sorted(calls), preps, dispatches):
            assert prep["fields"]["rows"] == stored and kept * 4 == stored
            # starts are wall clock, durations perf_counter: 1 ms slack
            assert prep["start_ms"] - 1.0 <= t0
            assert t1 <= prep["start_ms"] + prep["duration_ms"] + 1.0
            assert disp["fields"]["h2d_bytes"] == 6 * 4 * 128 \
                < 6 * 4 * stored

    def test_close_clears_the_tables_phase_children(self, monkeypatch):
        for k, v in DECODE_ENV.items():
            monkeypatch.setenv(k, v)

        async def go(client, _engine):
            r = await client.post("/query", json=QUERY)
            assert r.status == 200
            return registry.render()

        during = run(served(go))
        assert 'scan_phase_seconds_count{phase="scan.plan",table="data"}' \
            in during
        assert ('scan_phase_cpu_seconds_total{phase="scan.dispatch",'
                'table="data"}') in during
        assert 'table="data"' not in "".join(
            line for line in registry.render().splitlines()
            if line.startswith("scan_phase_"))

    @pytest.mark.parametrize("respond_on", ["loop", "pool"])
    @pytest.mark.parametrize("route", sorted(ROUTES))
    def test_spans_carry_cpu_where_no_await_can_be_inside(
            self, route, respond_on, monkeypatch):
        """CPU beside wall under one rule: a span that declares itself
        synchronous carries `cpu_ms` (at most its wall), whichever
        thread runs it: every site of `scan.dispatch`, `scan.d2h`,
        `scan.device_wait` (a pool thread here) and of `scan.combine`
        and `respond` (the loop's).  No other span carries it: not one
        held across an await (`downsample`, `resolve`, `parse`,
        `scan.plan`, the root), nor `scan.windows` / `scan.group_prep`,
        whose CPU nothing reads.  The phases' CPU also counts into
        scan_phase_cpu_seconds_total, the encoder's into
        respond_encode_cpu_seconds_total.  `respond` is a child of the
        root wherever the answer is written; one written on the pool
        is awaited, so there `respond` carries no CPU and its child
        `respond.encode`, the job on the pool thread, does."""
        env, _phases, _plan_route = ROUTES[route]
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        monkeypatch.setattr(tracing, "CPU_SAMPLE", 1.0)  # every one reads
        monkeypatch.setattr(server_main, "_RESPOND_POOL_MIN_CELLS",
                            0 if respond_on == "pool" else 2 ** 62)
        encoder = "respond" if respond_on == "loop" else "respond.encode"
        cpu_fam = registry.family("scan_phase_cpu_seconds_total")
        wall_fam = registry.family("scan_phase_seconds")
        enc_cpu = registry.family("respond_encode_cpu_seconds_total")
        enc_wall = registry.family("respond_encode_seconds_total")

        def totals():
            return ({p: (cpu_fam.labels(phase=p, table="data").value,
                         wall_fam.labels(phase=p, table="data").sum)
                     for p in SCAN_PHASES},
                    (enc_cpu.value, enc_wall.value))

        async def go(client, engine):
            body = dict(QUERY, filters={"host": "h1"})
            r = await client.post("/query", json=body)  # compiles
            assert r.status == 200
            if route == "device_decode":
                drop_slices(engine)
            before = totals()
            r = await client.post("/query", json=dict(body, start=T0 + 9))
            assert r.status == 200
            after = totals()
            tid = r.headers["X-Trace-Id"]
            return (await (await client.get(
                f"/debug/traces/{tid}")).json())["tree"], before, after

        tree, (before, enc_before), (after, enc_after) = run(served(go))
        spans = list(walk(tree))
        by_name = {}
        for c in spans:
            by_name.setdefault(c["name"], []).append(c)
        for c in spans:
            if "cpu_ms" in c:
                assert 0.0 <= c["cpu_ms"] <= c["duration_ms"] + 0.1, c
        declared = {"scan.dispatch", "scan.device_wait", "scan.d2h",
                    "scan.combine", encoder} & set(by_name)
        assert {"scan.dispatch", "scan.d2h", encoder} <= declared
        respond, = by_name["respond"]
        assert respond in tree["children"]
        if respond_on == "pool":
            job, = by_name["respond.encode"]
            assert job in respond["children"]
            assert job["duration_ms"] <= respond["duration_ms"]
        else:
            assert "respond.encode" not in by_name
        for name in by_name:
            carried = ["cpu_ms" in c for c in by_name[name]]
            assert all(carried) if name in declared \
                else not any(carried), name
        assert {"downsample", "resolve", "parse", "scan.plan",
                "scan.windows", tree["name"]} <= set(by_name) - declared
        for c in by_name.get("scan.device_wait", ()):
            # the waiting thread stands still while the device runs
            assert c["cpu_ms"] <= 0.2 * c["duration_ms"] + 0.5, c
        # the counters moved by what the data table's spans carry
        for p in SCAN_PHASES:
            cpu = after[p][0] - before[p][0]
            carried = sum(c["cpu_ms"] for c in by_name.get(p, ())
                          if "cpu_ms" in c
                          and c["fields"]["table"] == "data") / 1e3
            assert cpu == pytest.approx(carried, abs=1e-4), p
            assert cpu <= after[p][1] - before[p][1] + 1e-4, p
        cpu, wall = (a - b for a, b in zip(enc_after, enc_before))
        assert 0.0 < cpu <= wall + 1e-4


class TestProfilerClock:
    def test_spans_lie_in_the_xplane_at_their_own_starts(
            self, tmp_path, monkeypatch):
        """Under a profiler session `horaedb/downsample` and its phases
        are TraceMe events whose starts (relative to the root's) match
        the spans' within 5 ms — two spans held across interleaved
        awaits on the loop's thread included."""
        monkeypatch.setenv("HORAEDB_DEVICE_DECODE", "1")
        monkeypatch.setenv("HORAEDB_HOST_AGG", "0")
        rng = TimeRange.new(QUERY["start"], QUERY["end"])

        async def held(name, seconds):
            with span(name):
                await asyncio.sleep(seconds)

        async def go(_client, engine):
            await engine.query_downsample("cpu", [], rng,
                                          QUERY["bucket_ms"])  # compiles
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path),
                                     profiler_options=opts)
            try:
                trace = recorder.start("profiled")
                seams_before = sync_seams()
                drop_slices(engine)  # every phase: read, narrow, upload
                with trace_scope(trace):
                    # another range: the parts memo must not serve it
                    await engine.query_downsample(
                        "cpu", [], TimeRange.new(rng.start + 3,
                                                 rng.end + 3),
                        QUERY["bucket_ms"])
                    await asyncio.gather(held("interleaved.a", 0.03),
                                         held("interleaved.b", 0.06))
                return recorder.finish(trace), \
                    sync_seams() - seams_before
            finally:
                jax.profiler.stop_trace()

        done, seams = run(served(go))
        spans = {}
        for s in done["spans"]:
            spans.setdefault(s["name"], []).append(s)
        path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)[0]
        events = {}
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("horaedb/"):
                        events.setdefault(ev.name[len("horaedb/"):],
                                          []).append(ev)
        root_ev, = events["profiled"]
        root_span, = spans["profiled"]
        want = {"downsample", "interleaved.a", "interleaved.b"} \
            | set(SCAN_PHASES)
        # every phase is on both clocks; `scan.device_wait` as often as
        # a dispatch was still running at its finalize, the seam passed
        # once per dispatch either way
        assert_sync_seam_per_dispatch(seams, spans["scan.dispatch"],
                                      spans.get("scan.device_wait", []))
        assert want - {"scan.device_wait"} <= set(events)
        for name in want:
            got = sorted((ev.start_ns - root_ev.start_ns) / 1e6
                         for ev in events.get(name, []))
            rec = sorted(s["start_ms"] - root_span["start_ms"]
                         for s in spans.get(name, []))
            assert len(got) == len(rec), name
            for g, r in zip(got, rec):
                assert abs(g - r) < 5.0, (name, g, r)
        # interleaved on one thread, each keeps its own duration
        for name, seconds in (("interleaved.a", 0.03),
                              ("interleaved.b", 0.06)):
            ev, = events[name]
            assert seconds * 1e3 <= ev.duration_ns / 1e6 < seconds * 1e3 + 25
            assert abs(ev.duration_ns / 1e6
                       - spans[name][0]["duration_ms"]) < 5.0


class TestWaits:
    def test_a_busy_one_thread_pool_shows_its_wait(self):
        wait = registry.family("runtime_pool_wait_seconds").labels(
            pool="manifest")
        resume = registry.family("runtime_pool_resume_seconds").labels(
            pool="manifest")

        async def go():
            rt = Runtimes(sst_threads=1, compact_threads=1,
                          manifest_threads=1)
            trace = recorder.start("hops")
            try:
                with trace_scope(trace):
                    await asyncio.gather(
                        rt.run("manifest", time.sleep, 0.2),
                        rt.run("manifest", time.sleep, 0.01))
            finally:
                rt.close()
            return recorder.finish(trace)

        w0, n0, r0 = wait.sum, wait.count, resume.count
        done = run(go())
        assert wait.count - n0 == 2 and resume.count - r0 == 2
        assert wait.sum - w0 >= 0.15  # the second job sat behind the first
        # one record a hop in the trace: the span, and no counter twin
        assert not any(k.startswith("pool_") for k in done["counters"])
        hops = [s for s in done["spans"] if s["name"] == "pool_hop"]
        assert len(hops) == 2
        assert max(s["fields"]["wait_ms"] for s in hops) >= 150.0
        assert max(s["fields"]["run_ms"] for s in hops) >= 200.0
        assert all(s["duration_ms"] >= s["fields"]["wait_ms"]
                   + s["fields"]["run_ms"] for s in hops)

    def test_a_blocked_loop_shows_as_lag_stall_and_one_slow_log_line(
            self, caplog):
        lag = registry.family("event_loop_lag_seconds")
        stall = registry.family("event_loop_stall_seconds_total")

        async def go():
            reg = LoopRegistry()
            reg.ensure_watchdog()
            await asyncio.sleep(0.25)  # the sampler is ticking
            # a coroutine that blocks the loop: the tick due inside comes
            # 0.3 to 0.4 s late, whatever was left of its sleep
            time.sleep(0.4)
            await asyncio.sleep(0.25)
            for h in reg.handles():
                h.task.cancel()
            await asyncio.gather(*(h.task for h in reg.handles()),
                                 return_exceptions=True)

        n0, s0, t0 = lag.count, lag.sum, stall.value
        with caplog.at_level(logging.WARNING,
                             logger="horaedb_tpu.trace.slow"):
            run(go())
        assert lag.count - n0 >= 3
        assert lag.sum - s0 >= 0.3
        assert 0.3 <= stall.value - t0 < 0.7
        lines = [r.getMessage() for r in caplog.records
                 if r.getMessage().startswith("[stall]")]
        assert len(lines) == 1, lines
        assert "pool_queues=" in lines[0] and "gc=" in lines[0]

    def test_a_forced_collection_shows_as_a_gc_pause(self):
        loops_mod.hook_gc()
        gen2 = registry.family("process_gc_pause_seconds_total").labels(
            generation="2")
        before = gen2.value
        junk = [[i] for i in range(200_000)]
        del junk
        gc.collect()
        assert gen2.value > before


async def compiles_per_query(client, engine, fns) -> list:
    """One query three times, the parts memo off so that each repeat
    dispatches again: per query, how many programs each of `fns`
    compiled for it (its jit cache's growth)."""
    engine.tables["data"].reader.parts_memo.lru.max_bytes = 0
    out = []
    for _ in range(3):
        before = [f._cache_size() for f in fns]
        r = await client.post("/query", json=QUERY)
        assert r.status == 200
        out.append([f._cache_size() - b for f, b in zip(fns, before)])
    return out


class TestSeams:
    def test_device_decode_query_charges_d2h_seconds_and_compiles_once(
            self, monkeypatch):
        """After a device-decode query the d2h seam has seconds, not
        only bytes; and the named scopes inside the scan programs are
        metadata: the same calls compile as many programs as before
        (one per jitted function here)."""
        monkeypatch.setenv("HORAEDB_DEVICE_DECODE", "1")
        monkeypatch.setenv("HORAEDB_HOST_AGG", "0")
        d2h = registry.family("device_transfer_seconds_total")

        async def go(client, engine):
            sizes = await compiles_per_query(
                client, engine, [device_decode._decode_aggregate_jit,
                                 device_decode._decode_batch_jit])
            return (sizes, d2h.labels(direction="d2h").value,
                    deviceprof.profiler.snapshot()["transfer"]["d2h"])

        sizes, seconds, ledger = run(served(go))
        assert seconds > 0.0
        assert ledger["seconds"] > 0.0 and ledger["bytes"] > 0
        # as before the scopes: at most a program a segment for the
        # first query (its slices miss) and none for its repeats, whose
        # resident slices share ONE batched program, compiled once by
        # the first query that finds them together
        assert sizes[0][0] <= 2 and [q[0] for q in sizes[1:]] == [0, 0]
        assert sizes[0][1] == 0 and sum(q[1] for q in sizes) <= 1

    def test_download_waits_in_a_span_only_where_something_runs(
            self, monkeypatch):
        """`deviceprof.download`'s sync half: a computation still
        running at the download leaves one `scan.device_wait` span and
        its wait in the phase's histogram; one that has finished an
        observation of 0 and no span; the `scan.d2h` span either way.
        Both seams are synchronous and carry CPU beside wall: the wait
        stands still (next to no CPU), the copy cannot exceed its
        wall."""
        import jax.numpy as jnp

        monkeypatch.setattr(tracing, "CPU_SAMPLE", 1.0)  # every one reads

        @jax.jit
        def slow(x):  # ~70 ms on XLA-CPU against ~0.1 ms to get here
            return jax.lax.fori_loop(0, 30, lambda _i, a: jnp.tanh(a @ a),
                                     x)

        x = jnp.full((512, 512), 0.01, jnp.float32)
        jax.block_until_ready(slow(x))  # compiles
        hist = registry.family("scan_phase_seconds").labels(
            phase="scan.device_wait", table="seam_t")

        def downloaded(y):
            n0, s0 = hist.count, hist.sum
            trace = recorder.start("seam")
            with trace_scope(trace):
                deviceprof.download(y, fn="slow", table="seam_t")
            spans = recorder.finish(trace)["spans"]
            return spans, [s["name"] for s in spans], hist.count - n0, \
                hist.sum - s0

        running = slow(x)
        assert not running.is_ready()
        spans, names, n, waited = downloaded(running)
        assert names.count("scan.device_wait") == 1 == n
        assert names.count("scan.d2h") == 1 and waited > 0.0
        wait, = [s for s in spans if s["name"] == "scan.device_wait"]
        assert wait["fields"]["fn"] == "slow"
        for s in spans[:-1]:  # the root carries none
            assert 0.0 <= s["cpu_ms"] <= s["duration_ms"] + 0.1, s
        spans, names, n, waited = downloaded(running)  # ready by now
        assert "scan.device_wait" not in names and n == 1
        assert names.count("scan.d2h") == 1 and waited == 0.0
        clear_phases("seam_t")

    def test_fused_programs_compile_once_under_their_scopes(
            self, monkeypatch):
        monkeypatch.setenv("HORAEDB_FUSED_AGG", "1")
        monkeypatch.setenv("HORAEDB_HOST_AGG", "0")
        fns = [read_mod._fused_acc_init_jit,
               read_mod._fused_round_accumulate_jit,
               read_mod._fused_finalize_jit, read_mod._group_has_data_jit,
               read_mod._fused_one_call_jit]

        async def go(client, engine):
            sizes = await compiles_per_query(client, engine, fns)
            # the same query as rounds (the size bound is all that
            # stands between the two)
            monkeypatch.setattr(read_mod, "_ONE_CALL_MAX_ROWS", 0)
            return sizes, await compiles_per_query(client, engine, fns)

        one, rounds = run(served(go))
        # one small round: the one program, which traces the four
        # bodies and calls none of them
        assert one[0][:4] == [0] * 4 and one[0][4] <= 1
        assert one[1:] == [[0] * 5, [0] * 5]
        assert all(n <= 1 for n in rounds[0]) and rounds[0][4] == 0
        assert rounds[1:] == [[0] * 5, [0] * 5]

    def test_scopes_name_the_stages_in_the_lowered_program(self):
        """The scope names reach the operation metadata a profile
        shows (debug info of the lowered module)."""
        import jax.numpy as jnp

        from horaedb_tpu.ops import downsample

        def partials(ts, gid, vals):
            return downsample.partial_aggregate(
                ts, gid, vals, 8, 10, num_groups=2, num_buckets=4,
                which=("sum", "max"))

        text = jax.jit(partials).lower(
            jnp.zeros(8, jnp.int32), jnp.zeros(8, jnp.int32),
            jnp.zeros(8, jnp.float32)).as_text(debug_info=True)
        for scope in ("bucket_index", "scatter_count", "scatter_sum",
                      "scatter_max"):
            assert scope in text, scope
