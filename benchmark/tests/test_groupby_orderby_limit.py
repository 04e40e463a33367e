"""The cell `s1000_groupby_orderby_limit` (PR 48): its configuration, its
traffic, the operation `groupby_orderby_limit` and the five metrics it
brings, each looked up in the committed manifest BY NAME (never "the
last N of a list": a later PR appends); the stream of ends and its
sweep; `check` on a right answer and on doctored ones (a bucket dropped,
the order ascending, a bucket one minute early, a count off by one, one
max's last bit, a null: each moves its own reading and no other), for an
`end` on a segment boundary, within 240 s after one and exactly on a
minute, and under the control's values; the five readers on the
counters and spans as the program renders them and on a program without
them (the parent of PR 48: no /query_buckets at all); and a traced
rehearsal at test size on the CPU with its `--control bf16` twin.  The
tiny root of `helpers.py` gains one configuration file and one cell for
it, added here as a later PR adds its own: no committed file is
edited."""

import io
import json
import os

import numpy as np
import pyarrow as pa
import pytest
from pyarrow import ipc

from benchmark.harness import counters, layers, manifest, roofline
from benchmark.harness.dataset import Dataset, round_bf16
from benchmark.operations import groupby_orderby_limit as gol
from benchmark.tests.helpers import read_json, REPO, tiny_root, write_json
from benchmark.tests.test_rehearsal import run_cli

CELL = "s1000_groupby_orderby_limit"
CONFIG = "tsbs-devops-cpu-s1000-orderby-limit"
TRAFFIC = "groupby-orderby-limit"
BASE_CELL = "s1000_double_groupby"      # tsbs-devops-cpu-s1000-fleet's
SEG_MS = 7_200_000
MINUTE = 60_000
# name: (unit, better, source, layer, moves)
MINE = {
    "engine.resolve_ms.buckets": ("ms", "lower", "program_span",
                                  "engine and planner", "query_p50_ms"),
    "scan.buckets_ms": ("ms", "lower", "program_span", "scan",
                        "query_p50_ms"),
    "route.buckets_device_share": ("%", "higher", "program_counter",
                                   "route selection", "query_p50_ms"),
    "route.buckets_segments_per_query": ("1/query", "lower",
                                         "program_counter",
                                         "route selection",
                                         "queries_per_s"),
    "scan.buckets_rows_read_per_row_used": ("rows/row", "lower",
                                            "program_counter", "scan",
                                            "queries_per_s"),
}


def test_committed_manifest_has_the_cell_its_configuration_and_five_metrics():
    man = manifest.load(REPO)
    cell = man.workloads[CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": TRAFFIC,
                    "chips": 1, "why": cell["why"]}
    assert 0 < len(cell["why"]) <= 200
    for word in ("time < random end", "no lower bound", "all 1,000 hosts",
                 "by 1 min", "newest 5", "one resident slice of twelve",
                 "25 MB of 302 MB", "3.2 % read two", "nothing kept"):
        assert word in cell["why"], word
    entry = man.configs[CONFIG]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["reduced"] == [] and 0 < len(entry["source"]) <= 200
    assert 0 < len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    # a deployment of its own: no other configuration's source or file
    others = [c for c in man.doc["configs"] if c["name"] != CONFIG]
    assert entry["source"] not in {c["source"] for c in others}
    assert entry["file"] not in {c["file"] for c in others}
    assert "--query-type=groupby-orderby-limit" in entry["source"]
    assert "last 5 by 1m" in entry["source"]
    assert [w["name"] for w in man.doc["workloads"]
            if w["config"] == CONFIG] == [CELL]
    assert {m["name"] for m in man.end_to_end(CELL)} == {
        "query_p50_ms", "queries_per_s", "setup_s"}
    by_name = {m["name"]: m for m in man.doc["per_layer"]}
    for name, (unit, better, source, layer, moves) in MINE.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": [CELL]}, name
    # the new cell reports the five and every metric without a list; no
    # other cell reports any of the five
    for name in man.workloads:
        reported = {m["name"] for m in man.per_layer(name)}
        assert (set(MINE) <= reported) == (name == CELL), name
        assert not (set(MINE) & reported) or name == CELL, name
    reported = {m["name"] for m in man.per_layer(CELL)}
    unlisted = {m["name"] for m in man.doc["per_layer"]
                if "workloads" not in m}
    assert unlisted <= reported
    assert {"kernel.scan_roofline", "kernel.scan_ms_per_query",
            "device.compiles_in_window", "device.busy_pct",
            "route.fallbacks", "route.dispatches_per_query",
            "cache.decode_resident_hit_share", "cache.h2d_MB_per_query",
            "fetch.store_calls_per_query", "front_end.encode_ms",
            "front_end.respond_ms", "engine.postings_hit_share"} <= unlisted
    # the span metrics of the other endpoints keep their lists
    assert not {"engine.resolve_ms", "scan.downsample_ms",
                "engine.resolve_ms.multi", "engine.resolve_ms.rows",
                "scan.select_ms", "engine.resolve_ms.last",
                "scan.last_ms"} & reported
    # each once, and after every entry the parent had (appended)
    names = [m["name"] for m in man.doc["per_layer"]]
    assert all(names.count(n) == 1 for n in MINE)
    assert min(names.index(n) for n in MINE) \
        > names.index("scan.last_rows_read_per_point")
    # the same span, read the same way, as the /query cells' resolve
    assert man.reader("engine.resolve_ms.buckets")["source"] \
        == man.reader("engine.resolve_ms")["source"]
    for name in MINE:
        assert "nothing where the" in man.reader(name)["what"], name


def test_the_configuration_is_the_fleets_with_one_slice_read_a_query():
    """Data, schema, server settings and what the chip holds are
    `tsbs-devops-cpu-s1000-fleet`'s key for key (nothing cut, nothing
    set): the same twelve slices under the same keys; the guarantees
    are its own plus the buckets'; the file says what a query reads of
    them."""
    man = manifest.load(REPO)
    cfg, base = man.config(CELL), man.config(BASE_CELL)
    assert cfg["name"] == CONFIG and cfg["reduced"] == []
    assert cfg["source"] == man.configs[CONFIG]["source"]
    told = {"name", "source", "deployment", "held_on_device",
            "guarantees", "assumed"}
    assert set(cfg) == set(base) and list(cfg) == list(base)
    assert {k: v for k, v in cfg.items() if k not in told} == {
        k: v for k, v in base.items() if k not in told}
    assert cfg["server"] == {"base": "docs/example.toml", "overrides": {}}
    assert (cfg["scale"], cfg["rows"], cfg["points"]) \
        == (1000, 8_640_000, 86_400_000)
    assert cfg["device_row_bytes"] == 12 == base["device_row_bytes"]
    added = {"newest_buckets", "bucket_values"}
    assert set(cfg["guarantees"]) - set(base["guarantees"]) == added
    assert {k: v for k, v in cfg["guarantees"].items()
            if k not in added | {"staleness"}} == {
        k: v for k, v in base["guarantees"].items() if k != "staleness"}
    assert cfg["guarantees"]["staleness"].startswith(
        base["guarantees"]["staleness"])
    assert "every write acknowledged before the request" \
        in cfg["guarantees"]["staleness"]
    for word in ("newest", "no other", "never cut by a look-back",
                 "before `end` alone"):
        assert word in cfg["guarantees"]["newest_buckets"], word
    assert "`count` exact" in cfg["guarantees"]["bucket_values"]
    assert "bit for bit" in cfg["guarantees"]["bucket_values"]
    mine = {"held_on_device", "query_wording", "epoch_minutes",
            "end_granularity", "count_beside_max", "nothing_kept"}
    assert set(cfg["assumed"]) - set(base["assumed"]) \
        == mine - {"held_on_device"}
    assert {k: v for k, v in cfg["assumed"].items() if k not in mine} == {
        k: v for k, v in base["assumed"].items() if k not in mine}
    held, theirs = cfg["held_on_device"], base["held_on_device"]
    seg_rows = cfg["scale"] * SEG_MS // cfg["interval_ms"]
    cap = 1 << (seg_rows - 1).bit_length()
    assert (seg_rows, cap) == (720_000, 1_048_576)
    assert held["slice_bytes"] == cap * 4 * 6 == 25_165_824
    assert held["slices"] == cfg["span_ms"] // SEG_MS == 12
    assert held["bytes"] == held["slices"] * held["slice_bytes"] \
        == 301_989_888
    for key in ("slice_bytes", "slices", "bytes", "budget"):
        assert held[key] == theirs[key], key
    assert set(held) - set(theirs) == {"read_per_query", "share"}
    assert "3.6 %" in held["share"]
    assert round(100 * held["bytes"] / 8_454_668_032, 1) == 3.6


def test_the_traffic_is_tsbs_groupby_orderby_limit():
    man = manifest.load(REPO)
    traffic, fleet = man.traffic(CELL), man.traffic(BASE_CELL)
    assert traffic["name"] == TRAFFIC
    assert (traffic["operation"], traffic["endpoint"]) == (
        "groupby_orderby_limit", "/query_buckets")
    assert traffic["hosts"] == "all" and traffic["aggregate"] == "max"
    assert (traffic["limit"], traffic["bucket_ms"]) == (5, MINUTE)
    assert traffic["end_granularity_ms"] == 1
    assert traffic["end_min_offset_ms"] == 3_600_000
    # what the harness's roofline reads of every traffic file: five
    # minutes of every host, no grid
    assert traffic["window_ms"] == traffic["limit"] * traffic["bucket_ms"]
    assert traffic["output_grids"] == 0
    assert roofline.scan_min_bytes(
        1000 * traffic["window_ms"] // 10_000, 12, 1000, 5, 0) == 360_000
    assert traffic["warmup"] == {"sweep_stride_ms": SEG_MS,
                                 "sweep_offset_ms": 3_600_001,
                                 "pass_queries": 8}
    # an end, no start
    assert traffic["body"] == {
        "metric": "{metric}", "field": "{field}", "end": "{end}",
        "bucket_ms": "{bucket_ms}", "limit": "{limit}", "aggs": ["max"]}
    assert traffic["limits"] == dict.fromkeys(gol.READINGS, 0)
    for key in ("loop", "clients"):
        assert traffic[key] == fleet[key], key
    assert (traffic["loop"], traffic["clients"]) == ("closed", 4)


@pytest.fixture(scope="module")
def small():
    """Six hosts, ten fields, one day: names and values."""
    man = manifest.load(REPO)
    cfg = dict(man.config(CELL), scale=6)
    return man.traffic(CELL), Dataset(cfg, seed=2**31 + 48)


def test_ends_are_uniform_over_23_h_by_strata_and_the_sweep_is_twelve(small):
    traffic, data = small
    n = 276 * 8
    queries = gol.make_queries(traffic, data, np.random.default_rng(5), n)
    ends = np.array([q["end"] for q in queries])
    assert ends.min() >= data.t0 + 3_600_000
    assert ends.max() <= data.t0 + data.span_ms
    assert (ends % 1000).any(), "1 ms granularity"
    for q in queries[:50]:
        assert json.loads(q["body"]) == {
            "metric": "cpu", "field": "usage_user", "end": q["end"],
            "bucket_ms": MINUTE, "limit": 5, "aggs": ["max"]}
        assert q["hosts"] is None and q["bucket_ms"] == MINUTE
    # every block of 276 holds one end in every five minutes of the
    # 23 h, whatever the seed: the share that needs two segments (an
    # end within 240 s after a boundary) cannot move with it
    strata = (ends - data.t0 - 3_600_000) * 276 // (23 * 3_600_000 + 1)
    for at in range(0, n, 276):
        assert sorted(strata[at:at + 276]) == list(range(276))
    other = gol.make_queries(traffic, data, np.random.default_rng(6), n)
    assert [q["end"] for q in other] != list(ends)

    def two(qs) -> float:
        past = np.array([(q["end"] - data.t0) % SEG_MS for q in qs])
        return float(((past >= 1) & (past <= 4 * MINUTE)).mean())
    assert 0.025 <= two(queries) <= 0.04 and 0.025 <= two(other) <= 0.04
    sweep = gol.sweep_queries(traffic, data)
    assert [q["end"] for q in sweep] == [
        data.t0 + k * SEG_MS + 3_600_001 for k in range(12)]
    with pytest.raises(ValueError):
        gol.make_queries(dict(traffic, aggregate="min"), data,
                         np.random.default_rng(5), 1)


def answer(query: dict, data, values=None, drop=(), early=(), count=(),
           alter=(), null=(), names=None, ascending=False,
           limit=None) -> bytes:
    """What a sound server answers, from the plain loop below (NOT
    `gol.reference`): then buckets (by their place, newest = 0)
    dropped, put one minute early, their count off by one, their max
    altered by one bit or nulled."""
    grid = data.grid if values is None else values
    b, end = query["bucket_ms"], query["end"]
    rows = []
    for tick in range(data.ticks):
        ts = data.t0 + tick * data.interval_ms
        if ts >= end:
            break
        start = ts // b * b
        if not rows or rows[-1][0] != start:
            rows.append([start, 0, -np.inf])
        rows[-1][1] += data.hosts
        rows[-1][2] = max(rows[-1][2], grid[tick].max())
    rows = rows[::-1][:query["limit"] if limit is None else limit]
    rows = [[s - b * (i in early), n + (i in count), v]
            for i, (s, n, v) in enumerate(rows) if i not in drop]
    if ascending:
        rows = rows[::-1]
    vals = np.array([v for _s, _n, v in rows], dtype=np.float32)
    for i in alter:
        vals[i] = (vals[i:i + 1].view(np.uint32)
                   ^ np.uint32(1)).view(np.float32)[0]
    mask = np.zeros(len(rows), dtype=bool)
    mask[list(null)] = True
    tbl = pa.table({
        "bucket": pa.array([s for s, _n, _v in rows], type=pa.int64()),
        "count": pa.array([n for _s, n, _v in rows], type=pa.int64()),
        "max": pa.array(vals, type=pa.float32(), mask=mask)})
    if names is not None:
        tbl = tbl.rename_columns(names)
    return _stream(tbl)


def _stream(tbl: pa.Table) -> bytes:
    sink = io.BytesIO()
    with ipc.new_stream(sink, tbl.schema) as w:
        w.write_table(tbl)
    return sink.getvalue()


def _rewritten(payload: bytes, change) -> bytes:
    return _stream(change(ipc.open_stream(payload).read_all()))


def query_at(traffic, data, end: int) -> dict:
    return gol._query(traffic, data, end)


ENDS = {
    "inside a minute": 5 * SEG_MS + 3_600_000 + 34_567,
    "on a segment boundary": 4 * SEG_MS,
    "within 240 s after one": 4 * SEG_MS + 130_001,
    "a millisecond after one": 4 * SEG_MS + 1,
    "exactly on a minute": 3 * SEG_MS + 17 * MINUTE,
    "on a tick inside a minute": 3 * SEG_MS + 17 * MINUTE + 20_000,
    "the data's end": 12 * SEG_MS,
}


@pytest.mark.parametrize("what", list(ENDS))
def test_reference_is_the_five_newest_minutes_before_the_end(small, what):
    traffic, data = small
    end = data.t0 + ENDS[what]
    query = query_at(traffic, data, end)
    ref = gol.reference(query, data)
    newest = (end - 1) // MINUTE * MINUTE
    assert [b for b, _n, _v in ref] == [newest - k * MINUTE
                                        for k in range(5)]
    # whole minutes hold six ticks a host; the newest, those before end
    ticks = -(-(end - newest) // data.interval_ms)
    assert [n for _b, n, _v in ref] == [min(6, ticks) * data.hosts] \
        + [6 * data.hosts] * 4
    for b, _n, v in ref:
        lo = (b - data.t0) // data.interval_ms
        hi = lo + (min(6, ticks) if b == newest else 6)
        assert v == data.grid[lo:hi].max() and v.dtype == np.float32
    zero = dict.fromkeys(gol.READINGS, 0)
    assert gol.check(query, answer(query, data), data) == zero


def test_each_doctored_answer_moves_its_own_reading_and_no_other(small):
    traffic, data = small
    query = query_at(traffic, data, data.t0 + ENDS["inside a minute"])
    zero = dict.fromkeys(gol.READINGS, 0)
    assert gol.check(query, answer(query, data), data) == zero
    cases = {
        "a bucket dropped": (dict(drop=(2,)),
                             dict(zero, bucket_set_mismatch_rows=1)),
        "the newest dropped, a sixth in its place": (
            dict(drop=(0,), limit=6),
            dict(zero, bucket_set_mismatch_rows=2)),
        "a sixth bucket": (dict(limit=6),
                           dict(zero, bucket_set_mismatch_rows=1)),
        "the order ascending": (dict(ascending=True),
                                dict(zero, malformed_responses=1)),
        "the oldest one minute early": (
            dict(early=(4,)), dict(zero, bucket_set_mismatch_rows=2)),
        "a count off by one": (dict(count=(1,)),
                               dict(zero, count_mismatch_cells=1)),
        "one max's last bit": (dict(alter=(3,)),
                               dict(zero, value_mismatch_cells=1)),
        "a null": (dict(null=(0,)), dict(zero, value_mismatch_cells=1)),
        "a bit, a null, a count and a bucket": (
            dict(alter=(3,), null=(0,), count=(1,), drop=(4,)),
            dict(zero, value_mismatch_cells=2, count_mismatch_cells=1,
                 bucket_set_mismatch_rows=1)),
    }
    for what, (doctored, want) in cases.items():
        got = gol.check(query, answer(query, data, **doctored), data)
        assert got == want, what
    malformed = dict(zero, malformed_responses=1)
    good = answer(query, data)
    for payload in (
            b"", b"nonsense", json.dumps({"buckets": []}).encode(),
            answer(query, data, names=["bucket", "max", "count"]),
            answer(query, data, names=["minute", "count", "max"]),
            answer(query, data, early=(0,)),        # the newest twice
            _rewritten(good, lambda t: t.append_column(
                "min", t.column("max"))),
            _rewritten(good, lambda t: t.drop_columns(["count"])),
            _rewritten(good, lambda t: t.set_column(
                2, "max", t.column("max").cast(pa.float64()))),
            _rewritten(good, lambda t: t.set_column(
                1, "count", t.column("count").cast(pa.int32()))),
            _rewritten(good, lambda t: t.set_column(
                0, "bucket", pa.array(
                    [b + 1 for b in t.column("bucket").to_pylist()],
                    type=pa.int64()))),             # off the minute grid
            _rewritten(good, lambda t: t.set_column(
                1, "count", pa.array([None] + t.column("count")
                                     .to_pylist()[1:], type=pa.int64())))):
        assert gol.check(query, payload, data) == malformed
    # an answer of no row lacks all five
    assert gol.check(query, answer(query, data, limit=0), data) \
        == dict(zero, bucket_set_mismatch_rows=5)


def test_the_control_moves_the_values_and_nothing_else(small):
    """The reference from the field rounded to bfloat16 keeps buckets
    and counts (which the control cannot move: the doctored answers
    above do) and changes nearly every max (a walk's maximum over
    36 values has a fraction)."""
    traffic, data = small
    zero = dict.fromkeys(gol.READINGS, 0)
    rounded = gol.control_values(data)
    assert np.array_equal(rounded, round_bf16(data.grid))
    moved = 0
    for end in ENDS.values():
        query = query_at(traffic, data, data.t0 + end)
        under = gol.check(query, answer(query, data), data, values=rounded)
        assert {k: v for k, v in under.items()
                if k != "value_mismatch_cells"} \
            == {k: 0 for k in gol.READINGS if k != "value_mismatch_cells"}
        moved += under["value_mismatch_cells"]
        # an answer computed from the rounded values passes the control
        # and fails the sound reading
        low = answer(query, data, values=rounded)
        assert gol.check(query, low, data, values=rounded) == zero
        sound = gol.check(query, low, data)
        assert sound["value_mismatch_cells"] \
            == under["value_mismatch_cells"]
        assert gol.combine([under, sound]) == {
            k: under[k] + sound[k] for k in gol.READINGS}
    assert moved > 0.8 * 5 * len(ENDS)


def test_readers_on_the_counters_and_on_a_program_without_them():
    man = manifest.load(REPO)
    text = (
        'scan_buckets_segments_total{reason="",route="device"} 1040\n'
        'scan_buckets_segments_total{reason="memtable",route="host"} 5\n'
        'scan_buckets_rows_total{route="device",side="read"} 748800000\n'
        'scan_buckets_rows_total{route="device",side="used"} 30030000\n'
        'scan_buckets_rows_total{route="host",side="read"} 3000\n'
        'scan_buckets_rows_total{route="host",side="used"} 1000\n'
        "scan_buckets_calls_total 1040\n")
    before = {
        'metrics.scan_buckets_segments_total{reason="",route="device"}':
            10.0,
        'metrics.scan_buckets_segments_total{reason="memtable",'
        'route="host"}': 5.0,
        "metrics.scan_buckets_segments_total": 15.0,
        'metrics.scan_buckets_rows_total{route="device",side="read"}':
            7_200_000.0,
        'metrics.scan_buckets_rows_total{route="device",side="used"}':
            30_000.0,
        'metrics.scan_buckets_rows_total{route="host",side="read"}':
            3000.0,
        'metrics.scan_buckets_rows_total{route="host",side="used"}':
            1000.0}
    after: dict = {}
    counters.parse_metrics(text, after)
    obs = {"queries": 1000, "counters": counters.delta(before, after),
           "spans": {"total": [30.0, 50.0], "resolve": [0.25, 0.75],
                     "buckets": [20.0, 30.0], "respond": [1.0, 2.0]}}
    read = {name: layers.evaluate(man.reader(name), obs) for name in MINE}
    assert read == {
        "engine.resolve_ms.buckets": 0.5, "scan.buckets_ms": 25.0,
        "route.buckets_device_share": 100.0,
        "route.buckets_segments_per_query": 1.03,
        "scan.buckets_rows_read_per_row_used": pytest.approx(
            741_600_000 / 30_000_000)}
    # a walk that asks the memtable's segment too, and reads it there
    mixed = dict(obs, counters=counters.delta(
        dict(before, **{
            'metrics.scan_buckets_segments_total{reason="memtable",'
            'route="host"}': 0.0,
            "metrics.scan_buckets_segments_total": 10.0}), after))
    assert layers.evaluate(
        man.reader("route.buckets_device_share"), mixed) \
        == pytest.approx(100.0 * 1030 / 1035)
    assert layers.evaluate(
        man.reader("route.buckets_segments_per_query"), mixed) == 1.035
    # the counters are there and stood still
    still = {"queries": 10, "counters": dict.fromkeys(before, 0.0)}
    for name in ("route.buckets_device_share",
                 "route.buckets_segments_per_query",
                 "scan.buckets_rows_read_per_row_used"):
        assert layers.evaluate(man.reader(name), still) == 0.0, name
    # the parent of PR 48 renders none of them and traces no such
    # root: nothing, no error
    bare = {"queries": 100, "spans": {},
            "counters": {"metrics.respond_cells_total": 5.0,
                         "metrics.scan_last_segments_total": 7.0}}
    for name in MINE:
        assert layers.evaluate(man.reader(name), bare) is None, name


def test_a_program_without_the_endpoint_fails_before_the_load_generator(
        small, tmp_path, monkeypatch):
    """The parent of PR 48 serves no /query_buckets: `make_queries`
    (the first thing run.py asks of the operation, with the server up
    and no load generator made) raises the harness's own error, which
    ends the run rc 1 with the server stopped."""
    from benchmark.harness.server import BenchError
    from benchmark.operations import select_where

    traffic, data = small
    server = tmp_path / "horaedb_tpu" / "server"
    server.mkdir(parents=True)
    (server / "main.py").write_text('@routes.post("/query_last")\n')
    monkeypatch.setattr(select_where, "_ROOT", str(tmp_path))
    monkeypatch.setattr(
        select_where.require_endpoint, "__defaults__", (str(tmp_path),))
    with pytest.raises(BenchError, match="routes no /query_buckets"):
        gol.make_queries(traffic, data, np.random.default_rng(1), 1)


def buckets_root(dst: str) -> dict:
    """`tiny_root` plus the committed configuration at ten hosts, and a
    cell on it that reports the five."""
    doc = tiny_root(dst)
    cfg = read_json(os.path.join(REPO, "benchmark/configs", CONFIG + ".json"))
    cfg.update(name="tiny-orderby-limit", scale=10)
    cfg["ingest"] = dict(cfg["ingest"], body_rows=30_000)
    write_json(os.path.join(
        dst, "benchmark/configs/tiny-orderby-limit.json"), cfg)
    doc["configs"].append({
        "name": "tiny-orderby-limit", "source": "benchmark/tests",
        "reduced": [], "why": "CPU rehearsal",
        "file": "benchmark/configs/tiny-orderby-limit.json"})
    doc["workloads"].append({
        "name": "tiny_orderby_limit", "config": "tiny-orderby-limit",
        "traffic": TRAFFIC, "chips": 1, "why": "CPU rehearsal"})
    for m in doc["per_layer"]:
        if m["name"] in MINE:
            m["workloads"] = m["workloads"] + ["tiny_orderby_limit"]
    write_json(os.path.join(dst, "BENCHMARK.json"), doc)
    return doc


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tmp_path_factory.mktemp("orderby_limit")
    buckets_root(str(path))
    return str(path)


def test_traced_rehearsal_of_the_cell(root, tmp_path):
    proc = run_cli(root, str(tmp_path / "out"), "--trace", "1",
                   "--platform", "cpu", workload="tiny_orderby_limit")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    final = json.loads(lines[-1])
    assert final["correct"] is True and final["failed"] == 0
    assert final["attempted"] >= 2
    assert " OVER" not in "".join(
        ln for ln in lines if ln.startswith("check "))
    assert set(final["compared"]) == set(gol.READINGS)
    got = {name: m["value"] for name, m in final["metrics"].items()}
    assert set(MINE) <= set(got)
    # every per-layer metric without a list is in the line, but for
    # the three that read the DEVICE's trace, which a CPU run has not
    man = manifest.load(root)
    assert {m["name"] for m in man.doc["per_layer"]
            if "workloads" not in m and m["source"] != "device_trace"} \
        <= set(got)
    assert got["route.buckets_device_share"] == 100.0
    # one segment a query, two for an end within 240 s after a boundary
    assert 1.0 <= got["route.buckets_segments_per_query"] <= 1.25
    # 720 ticks of a 2 h segment for the 25 to 30 of five minutes
    assert 24.0 <= got["scan.buckets_rows_read_per_row_used"] <= 30.0
    assert got["route.fallbacks"] == 0.0
    assert got["cache.decode_resident_hit_share"] == 100.0
    assert got["cache.h2d_MB_per_query"] == 0.0
    assert got["fetch.sidecar_load_ms_per_query"] == 0.0
    assert got["device.compiles_in_window"] == 0.0
    # five buckets x three columns a response
    assert got["front_end.respond_cells_per_query"] == pytest.approx(
        15.0, rel=0.1)
    assert 0.0 < got["engine.resolve_ms.buckets"] < got["scan.buckets_ms"]
    assert not {"engine.resolve_ms", "scan.downsample_ms",
                "engine.resolve_ms.last", "scan.last_ms"} & set(got)
    # one call of the one program a segment asked: no answer is kept
    assert got["route.dispatches_per_query"] == pytest.approx(
        got["route.buckets_segments_per_query"],
        abs=8 / max(final["attempted"] - 1, 1))
    route = json.loads(next(ln for ln in lines
                            if ln.startswith("route "))[6:])
    assert set(route["calls_per_fn"]) == {"_buckets_jit"}
    # five minutes of every host at 12 B a row, no grid
    assert route["scan_min_bytes_per_query"] == 10 * 30 * 12
    setup = json.loads(next(ln for ln in lines
                            if ln.startswith("setup "))[6:])
    assert setup["sweep_queries"] == 12


def test_control_rehearsal_of_the_cell_is_not_correct(root, tmp_path):
    proc = run_cli(root, str(tmp_path / "out"), "--trace", "0",
                   "--platform", "cpu", "--control", "bf16",
                   workload="tiny_orderby_limit")
    assert proc.returncode == 0, proc.stderr[-2000:]
    text = proc.stdout
    final = json.loads(text.strip().splitlines()[-1])
    assert "(sound reading: correct = True)" in text
    over = [ln.split()[1] for ln in text.splitlines()
            if ln.startswith("control[bf16] ") and ln.endswith(" OVER")]
    assert set(over) == {"value_mismatch_cells"}
    assert final["correct"] is False and final["failed"] == 0
