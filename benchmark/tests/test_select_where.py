"""The cell `s100_high_cpu_all` (PR 41): its configuration, its traffic,
the operation `select_where` and the six metrics it brings, each looked
up in the committed manifest BY NAME; the shape of the query and of its
sweep; `check` on a hand-made case, on a right answer, on rows lost,
added and altered, on nulls and under the control's values; the six
readers on the counters and spans as the program renders them and on a
program without them (the parent of PR 41: no /query_rows at all); and
a traced rehearsal at test size on the CPU with its `--control bf16`
twin.  The tiny root of `helpers.py` gains one configuration file and
one cell for it, added here as a later PR adds its own: no committed
file is edited."""

import io
import json
import os

import numpy as np
import pyarrow as pa
import pytest
from pyarrow import ipc

from benchmark.harness import counters, layers, manifest
from benchmark.harness.dataset import Dataset, round_bf16
from benchmark.operations import groupby, select_where
from benchmark.tests.helpers import read_json, REPO, tiny_root, write_json
from benchmark.tests.test_rehearsal import run_cli

CELL = "s100_high_cpu_all"
CONFIG = "tsbs-devops-cpu-s100-highcpu"
TRAFFIC = "high-cpu-all"
BASE_CELL = "s100_double_groupby"       # tsbs-devops-cpu-s100's
SEG_MS = 7_200_000
# name: (unit, better, source, layer, moves)
MINE = {
    "engine.resolve_ms.rows": ("ms", "lower", "program_span",
                               "engine and planner", "query_p50_ms"),
    "scan.select_ms": ("ms", "lower", "program_span", "scan",
                       "query_p50_ms"),
    "scan.select_rows_per_query": ("rows/query", "lower",
                                   "program_counter", "scan",
                                   "query_p50_ms"),
    "scan.select_match_share": ("%", "lower", "program_counter", "scan",
                                "query_p50_ms"),
    "route.select_device_share": ("%", "higher", "program_counter",
                                  "route selection", "query_p50_ms"),
    "kernel.select_overflows_per_query": ("1/query", "lower",
                                          "program_counter",
                                          "device programs",
                                          "queries_per_s"),
}


def test_committed_manifest_has_the_cell_its_configuration_and_six_metrics():
    man = manifest.load(REPO)
    cell = man.workloads[CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": TRAFFIC,
                    "chips": 1, "why": cell["why"]}
    assert 0 < len(cell["why"]) <= 200 and "PLACEHOLDER" not in cell["why"]
    entry = man.configs[CONFIG]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["reduced"] == [] and 0 < len(entry["source"]) <= 200
    assert 0 < len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    # a deployment of its own: no other configuration's source or file
    others = [c for c in man.doc["configs"] if c["name"] != CONFIG]
    assert entry["source"] not in {c["source"] for c in others}
    assert entry["file"] not in {c["file"] for c in others}
    assert "high-cpu-all" in entry["source"] and "90" in entry["source"]
    assert {m["name"] for m in man.end_to_end(CELL)} == {
        "query_p50_ms", "queries_per_s", "setup_s"}
    by_name = {m["name"]: m for m in man.doc["per_layer"]}
    for name, (unit, better, source, layer, moves) in MINE.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": [CELL]}, name
    # the new cell reports the six and every metric without a list; no
    # other cell reports them
    for name in man.workloads:
        reported = {m["name"] for m in man.per_layer(name)}
        assert (set(MINE) <= reported) == (name == CELL), name
        assert not (set(MINE) & reported) or name == CELL, name
    reported = {m["name"] for m in man.per_layer(CELL)}
    assert {"kernel.scan_roofline", "kernel.scan_ms_per_query",
            "device.compiles_in_window", "route.fallbacks",
            "cache.decode_resident_hit_share", "front_end.respond_ms",
            "front_end.respond_pool_share"} <= reported
    # the span metrics of the /query cells keep their lists
    assert not {"engine.resolve_ms", "scan.downsample_ms",
                "engine.resolve_ms.multi"} & reported
    # added at the end of their lists, each once
    assert man.doc["workloads"][-1]["name"] == CELL
    assert man.doc["configs"][-1]["name"] == CONFIG
    assert [m["name"] for m in man.doc["per_layer"]][-6:] == list(MINE)
    # the same span, read the same way, as the /query cells' resolve
    assert man.reader("engine.resolve_ms.rows")["source"] \
        == man.reader("engine.resolve_ms")["source"]


def test_the_configuration_is_scale_100_with_ten_fields_on_the_chip():
    """Data, schema and server settings are `tsbs-devops-cpu-s100`'s
    key for key (nothing cut, nothing set); the guarantees are its own
    plus the rows'; the file adds what the deployment keeps on the
    chip, which has to agree with the slices' arithmetic, and counts
    the predicate field's rows alone in the scan's least bytes."""
    man = manifest.load(REPO)
    cfg, base = man.config(CELL), man.config(BASE_CELL)
    assert cfg["name"] == CONFIG and cfg["reduced"] == []
    assert cfg["source"] == man.configs[CONFIG]["source"]
    told = {"name", "source", "deployment", "held_on_device",
            "guarantees", "assumed"}
    assert set(cfg) - set(base) == {"held_on_device"}
    assert {k: v for k, v in cfg.items() if k not in told} == {
        k: v for k, v in base.items() if k not in told}
    assert cfg["server"] == {"base": "docs/example.toml", "overrides": {}}
    assert cfg["device_row_bytes"] == base["device_row_bytes"] == 12
    added = {"rows", "row_values"}
    assert set(cfg["guarantees"]) - set(base["guarantees"]) == added
    assert {k: v for k, v in cfg["guarantees"].items()
            if k not in added} == base["guarantees"]
    assert "exact" in cfg["guarantees"]["rows"]
    assert "never truncated" in cfg["guarantees"]["rows"]
    assert "bit for bit" in cfg["guarantees"]["row_values"]
    mine = {"device_row_bytes", "threshold_and_window", "tags_by_tsid",
            "value_width", "held_on_device"}
    assert set(cfg["assumed"]) - set(base["assumed"]) \
        == mine - {"device_row_bytes"}
    assert {k: v for k, v in cfg["assumed"].items() if k not in mine} == {
        k: v for k, v in base["assumed"].items() if k not in mine}
    held = cfg["held_on_device"]
    seg_rows = cfg["scale"] * SEG_MS // cfg["interval_ms"]
    cap = 1 << (seg_rows - 1).bit_length()
    assert (seg_rows, cap) == (72_000, 131_072)
    assert held["slice_bytes"] == cap * 4 * 6 == 3_145_728
    assert held["fields"] == len(cfg["fields"]) == 10
    assert held["segments"] == cfg["span_ms"] // SEG_MS == 12
    assert held["slices"] == held["fields"] * held["segments"] == 120
    assert held["bytes"] == held["slices"] * held["slice_bytes"] \
        == 377_487_360


def test_the_traffic_is_tsbs_high_cpu_all():
    man = manifest.load(REPO)
    traffic, one = man.traffic(CELL), man.traffic(BASE_CELL)
    assert traffic["name"] == TRAFFIC
    assert (traffic["operation"], traffic["endpoint"]) == (
        "select_where", "/query_rows")
    assert traffic["where"] == {"field": "usage_user", "op": "gt",
                                "value": 90.0}
    assert traffic["fields"] == "all" and traffic["hosts"] == "all"
    assert traffic["window_ms"] == traffic["bucket_ms"] == 43_200_000
    assert traffic["output_grids"] == 0
    assert traffic["warmup"] == {"sweep_stride_ms": 43_200_000,
                                 "pass_queries": 8}
    assert traffic["body"] == {
        "metric": "{metric}", "start": "{start}", "end": "{end}",
        "where": "{where}", "fields": "{fields}"}
    assert traffic["limits"] == dict.fromkeys(select_where.READINGS, 0)
    for key in ("loop", "clients", "window_ms", "hosts",
                "start_granularity_ms"):
        assert traffic[key] == one[key], key


@pytest.fixture(scope="module")
def small():
    """Four hosts, ten fields, one day: names, bounds and values."""
    man = manifest.load(REPO)
    cfg = dict(man.config(CELL), scale=4)
    return man.traffic(CELL), Dataset(cfg, seed=2**31 + 41)


def test_shape_of_the_query_and_of_its_sweep(small):
    """12 h of all hosts, the predicate and all ten fields in one
    body: 432,000 host-ticks tested at scale 100, seven of the twelve
    segments (a start on an edge: six), and a sweep of two windows that
    touches all twelve segments, so all 120 (field, segment) slices;
    the roofline's least bytes count the predicate's field alone."""
    traffic, data = small
    assert 100 * traffic["window_ms"] // data.interval_ms == 432_000

    def segments(q):
        return range((q["start"] - data.t0) // SEG_MS,
                     (q["end"] - 1 - data.t0) // SEG_MS + 1)

    sweep = select_where.sweep_queries(traffic, data)
    assert len(sweep) == 2
    assert {s for q in sweep for s in segments(q)} == set(range(12))
    queries = select_where.make_queries(
        traffic, data, np.random.default_rng(5), 64)
    assert {len(segments(q)) for q in queries} <= {6, 7}
    for q in queries + sweep:
        assert json.loads(q["body"]) == {
            "metric": "cpu", "start": q["start"], "end": q["end"],
            "where": {"field": "usage_user", "op": "gt", "value": 90.0},
            "fields": data.fields}
        assert q["end"] - q["start"] == traffic["window_ms"]
        assert q["hosts"] is None
        assert (q["where_field"], q["op"], q["threshold"]) == (0, "gt", 90.0)
        assert q["fields"] == list(range(10))
    # the stream is `groupby`'s: same seed, same windows
    ones = groupby.make_queries(
        dict(traffic, body={"field": "{field}"}), data,
        np.random.default_rng(5), 64)
    assert [q["start"] for q in ones] == [q["start"] for q in queries]
    with pytest.raises(ValueError):
        select_where.make_queries(
            dict(traffic, where={"field": "usage_user", "op": "eq",
                                 "value": 1.0}),
            data, np.random.default_rng(5), 1)


def answer(query: dict, data, values=None, drop=(), add=(), alter=(),
           null=(), names=None, order=None) -> bytes:
    """What a sound server answers, from the plain loop below (NOT
    `select_where.reference`): then rows dropped, added (tick, host),
    cells altered or nulled (row index, field index)."""
    grids = data.values if values is None else values
    t = np.float32(query["threshold"])
    test = {"gt": lambda v: v > t, "ge": lambda v: v >= t,
            "lt": lambda v: v < t, "le": lambda v: v <= t}[query["op"]]
    rows = []
    for tick in range(data.ticks):
        ts = data.t0 + tick * data.interval_ms
        if not query["start"] <= ts < query["end"]:
            continue
        for h in range(data.hosts):
            if test(grids[query["where_field"]][tick, h]):
                rows.append((int(data.tsid_of_host[h]), ts, tick, h))
    rows = [r for i, r in enumerate(sorted(rows)) if i not in drop]
    rows = sorted(rows + [(int(data.tsid_of_host[h]),
                           data.t0 + tick * data.interval_ms, tick, h)
                          for tick, h in add])
    if order is not None:
        rows = [rows[i] for i in order]
    cols = {"tsid": pa.array([r[0] for r in rows], type=pa.uint64()),
            "timestamp": pa.array([r[1] for r in rows], type=pa.int64())}
    for f in query["fields"]:
        vals = np.array([grids[f][r[2], r[3]] for r in rows],
                        dtype=np.float32)
        mask = np.zeros(len(rows), dtype=bool)
        for i, g in alter:
            if g == f:
                vals[i] = np.nextafter(vals[i], np.float32(1000.0))
        for i, g in null:
            if g == f:
                mask[i] = True
        cols[data.fields[f]] = pa.array(vals, type=pa.float32(), mask=mask)
    tbl = pa.table(cols)
    if names is not None:
        tbl = tbl.rename_columns(names)
    sink = io.BytesIO()
    with ipc.new_stream(sink, tbl.schema) as w:
        w.write_table(tbl)
    return sink.getvalue()


def test_reference_on_a_hand_made_case(small):
    """Three ticks of four hosts, written down: which cells pass
    `> 90` as float32, at the window's edges and at the threshold."""
    traffic, data = small
    saved = data.values[0, 10:13].copy()
    try:
        data.values[0, 10:13] = np.array(
            [[90.0, 90.000008, 12.5, 99.0],
             [89.99999, 100.0, 90.0, 0.0],
             [95.5, 3.0, 91.0, 90.0]], dtype=np.float32)
        query = {"start": data.t0 + 10 * data.interval_ms,
                 "end": data.t0 + 12 * data.interval_ms + 1, "hosts": None,
                 "where_field": 0, "op": "gt", "threshold": 90.0,
                 "fields": [0, 3]}
        mask, lo = select_where.reference(query, data)
        assert lo == 10
        assert mask.tolist() == [[False, True, False, True],
                                 [False, True, False, False],
                                 [True, False, True, False]]
        # the end is exclusive: a window that ends ON the third tick
        # leaves it out; one that starts 1 ms past the first leaves it
        mask, lo = select_where.reference(
            dict(query, start=query["start"] + 1, end=query["end"] - 1),
            data)
        assert (lo, mask.shape[0], int(mask.sum())) == (11, 1, 1)
        ge, _ = select_where.reference(dict(query, op="ge"), data)
        assert int(ge.sum()) == 5 + 3       # the three cells at 90.0 too
        le, _ = select_where.reference(dict(query, op="le"), data)
        assert (le == ~mask_of(query, data)).all()
        good = select_where.check(query, answer(query, data), data)
        assert good == dict.fromkeys(select_where.READINGS, 0)
    finally:
        data.values[0, 10:13] = saved


def mask_of(query, data):
    return select_where.reference(query, data)[0]


def test_check_on_right_lost_added_altered_null_and_control(small):
    traffic, data = small
    query = select_where.make_queries(
        traffic, data, np.random.default_rng(9), 1)[0]
    zero = dict.fromkeys(select_where.READINGS, 0)
    n = int(mask_of(query, data).sum())
    assert n > 20
    assert select_where.check(query, answer(query, data), data) == zero

    # two rows lost, one row the reference lacks (a cell under 90 in
    # the window), one off the window: four rows of mismatch, no cell
    lo, hi = data.tick_range(query["start"], query["end"])
    under = np.argwhere(~mask_of(query, data))[0]
    got = select_where.check(query, answer(
        query, data, drop=(0, 7), add=[(lo + int(under[0]), int(under[1])),
                                       (hi % data.ticks, 0)
                                       if hi < data.ticks else (lo - 1, 0)]),
        data)
    assert got == dict(zero, row_set_mismatch_rows=4)
    # one value a float32 step off, one null: two cells, no row
    got = select_where.check(query, answer(
        query, data, alter=[(3, 9)], null=[(5, 0)]), data)
    assert got == dict(zero, value_mismatch_cells=2)

    malformed = dict(zero, malformed_responses=1)
    names = ["tsid", "timestamp"] + data.fields
    for payload in (
            b"", b"nonsense", json.dumps({"tsids": []}).encode(),
            answer(query, data, names=names[:-1] + ["usage_other"]),
            answer(dict(query, fields=query["fields"][:-1]), data),
            answer(dict(query, fields=query["fields"][::-1]), data),
            answer(query, data, order=[1, 0] + list(range(2, n))),
            answer(query, data, order=[0, 0] + list(range(1, n)))):
        assert select_where.check(query, payload, data) == malformed
    # a series the data set does not have
    saved = data.host_of_tsid
    try:
        data.host_of_tsid = {k: v for k, v in saved.items()
                             if v != 0}
        assert select_where.check(query, answer(query, data), data) \
            == malformed
    finally:
        data.host_of_tsid = saved

    # the control: the reference from every field rounded to bfloat16
    # moves rows across 90.0 and changes every value with a fraction
    rounded = select_where.control_values(data)
    assert np.array_equal(rounded, round_bf16(data.values))
    under = select_where.check(query, answer(query, data), data,
                               values=rounded)
    assert under["malformed_responses"] == 0
    assert under["row_set_mismatch_rows"] > 0
    assert under["value_mismatch_cells"] > n
    # an answer computed from the rounded values passes the control
    # and fails the sound reading
    low = answer(query, data, values=rounded)
    assert select_where.check(query, low, data, values=rounded) == zero
    sound = select_where.check(query, low, data)
    assert sound["row_set_mismatch_rows"] > 0
    assert sound["value_mismatch_cells"] > 0
    assert select_where.combine([under, sound]) == {
        k: under[k] + sound[k] for k in select_where.READINGS}


def test_readers_on_the_counters_and_on_a_program_without_them():
    man = manifest.load(REPO)
    text = (
        'scan_select_segments_total{reason="",route="device"} 7070\n'
        'scan_select_segments_total{reason="mode_host",route="host"} 14\n'
        'scan_select_rows_total{route="device",side="scanned"} 4364000\n'
        'scan_select_rows_total{route="device",side="selected"} 436400\n'
        'scan_select_rows_total{route="host",side="selected"} 2000\n'
        "scan_select_overflow_total 3\n")
    before = {
        'metrics.scan_select_segments_total{reason="",route="device"}': 70.0,
        "metrics.scan_select_segments_total": 84.0,
        'metrics.scan_select_rows_total{route="device",side="scanned"}':
            44000.0,
        'metrics.scan_select_rows_total{route="device",side="selected"}':
            4400.0,
        'metrics.scan_select_rows_total{route="host",side="selected"}':
            2000.0,
        "metrics.scan_select_overflow_total": 2.0}
    after: dict = {}
    counters.parse_metrics(text, after)
    obs = {"queries": 1000, "counters": counters.delta(before, after),
           "spans": {"total": [70.0, 90.0], "resolve": [0.25, 0.75],
                     "select": [50.0, 60.0], "respond": [5.0, 7.0]}}
    read = {name: layers.evaluate(man.reader(name), obs) for name in MINE}
    assert read == {
        "engine.resolve_ms.rows": 0.5, "scan.select_ms": 55.0,
        "scan.select_rows_per_query": 432.0,
        "scan.select_match_share": 10.0,
        "route.select_device_share": 100.0,
        "kernel.select_overflows_per_query": 0.001}
    # the counters are there and stood still
    still = {"queries": 10, "counters": dict.fromkeys(before, 0.0)}
    assert layers.evaluate(man.reader("scan.select_match_share"), still) \
        == 0.0
    assert layers.evaluate(man.reader("route.select_device_share"), still) \
        == 0.0
    assert layers.evaluate(
        man.reader("kernel.select_overflows_per_query"), still) == 0.0
    # the parent of PR 41 renders none of them and traces no such
    # root: nothing, no error
    bare = {"queries": 100, "spans": {},
            "counters": {"metrics.respond_cells_total": 5.0}}
    for name in MINE:
        assert layers.evaluate(man.reader(name), bare) is None, name


def test_a_program_without_the_endpoint_fails_before_the_load_generator(
        tmp_path):
    """The parent of PR 41 serves no /query_rows: `make_queries` (the
    first thing run.py asks of the operation, with the server up and no
    load generator made) raises the harness's own error, which ends
    the run rc 1 with the server stopped."""
    from benchmark.harness.server import BenchError

    select_where.require_endpoint("/query_rows")          # this checkout
    server = tmp_path / "horaedb_tpu" / "server"
    server.mkdir(parents=True)
    (server / "main.py").write_text('@routes.post("/query_arrow")\n')
    with pytest.raises(BenchError, match="routes no /query_rows"):
        select_where.require_endpoint("/query_rows", str(tmp_path))
    select_where.require_endpoint("/query_arrow", str(tmp_path))
    # no such directory: not judged (the run's own 404 says it)
    select_where.require_endpoint("/query_rows", str(tmp_path / "none"))


def high_cpu_root(dst: str) -> dict:
    """`tiny_root` plus the committed high-cpu configuration at ten
    hosts, and a high-cpu-all cell on it that reports the six."""
    doc = tiny_root(dst)
    cfg = read_json(os.path.join(REPO, "benchmark/configs", CONFIG + ".json"))
    cfg.update(name="tiny-highcpu", scale=10)
    cfg["ingest"] = dict(cfg["ingest"], body_rows=30_000)
    write_json(os.path.join(dst, "benchmark/configs/tiny-highcpu.json"), cfg)
    doc["configs"].append({
        "name": "tiny-highcpu", "source": "benchmark/tests", "reduced": [],
        "file": "benchmark/configs/tiny-highcpu.json",
        "why": "CPU rehearsal"})
    doc["workloads"].append({
        "name": "tiny_high_cpu", "config": "tiny-highcpu",
        "traffic": TRAFFIC, "chips": 1, "why": "CPU rehearsal"})
    for m in doc["per_layer"]:
        if m["name"] in MINE:
            m["workloads"] = m["workloads"] + ["tiny_high_cpu"]
    write_json(os.path.join(dst, "BENCHMARK.json"), doc)
    return doc


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tmp_path_factory.mktemp("highcpu")
    high_cpu_root(str(path))
    return str(path)


def test_traced_rehearsal_of_the_high_cpu_cell(root, tmp_path):
    proc = run_cli(root, str(tmp_path / "out"), "--trace", "1",
                   "--platform", "cpu", workload="tiny_high_cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    final = json.loads(lines[-1])
    assert final["correct"] is True and final["failed"] == 0
    assert final["attempted"] >= 2
    assert " OVER" not in "".join(
        ln for ln in lines if ln.startswith("check "))
    assert set(final["compared"]) == set(select_where.READINGS)
    got = {name: m["value"] for name, m in final["metrics"].items()}
    assert set(MINE) <= set(got)
    assert got["route.select_device_share"] == 100.0
    assert got["route.fallbacks"] == 0.0
    assert got["cache.decode_resident_hit_share"] == 100.0
    assert got["cache.h2d_MB_per_query"] == 0.0
    # no segment is read in the window (the manifest's own merge of
    # the set-up's deltas may GET in it at this size: not a query's)
    assert got["fetch.sidecar_load_ms_per_query"] == 0.0
    assert got["fetch.sidecar_row_share"] == 0.0
    assert got["device.compiles_in_window"] == 0.0
    assert got["kernel.select_overflows_per_query"] == 0.0
    # ten hosts x 4,320 ticks tested a query, some tenth of them out,
    # twelve columns a row
    assert 0.0 < got["scan.select_match_share"] < 40.0
    rows = got["scan.select_rows_per_query"]
    assert rows == pytest.approx(
        43_200 * got["scan.select_match_share"] / 100.0, rel=0.1)
    assert got["front_end.respond_cells_per_query"] == pytest.approx(
        12 * rows, rel=0.1)
    assert 0.0 < got["engine.resolve_ms.rows"] < got["scan.select_ms"]
    assert "engine.resolve_ms" not in got and "scan.downsample_ms" not in got
    # one select call and a join a field joined (nine) a query: the
    # requests in flight at the two counter reads count their calls
    calls = got["route.dispatches_per_query"]
    assert 10.0 * (1 - 4 / max(final["attempted"], 5)) <= calls \
        <= 10.0 * (1 + 4 / max(final["attempted"] - 1, 1))
    route = json.loads(next(ln for ln in lines
                            if ln.startswith("route "))[6:])
    assert set(route["calls_per_fn"]) == {"_select_rows_jit",
                                          "_select_join_jit"}
    # the predicate field's rows in range at 12 B, no grid
    assert route["scan_min_bytes_per_query"] == 10 * 4_320 * 12


def test_control_rehearsal_of_the_high_cpu_cell_is_not_correct(
        root, tmp_path):
    proc = run_cli(root, str(tmp_path / "out"), "--trace", "0",
                   "--platform", "cpu", "--control", "bf16",
                   workload="tiny_high_cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]
    text = proc.stdout
    final = json.loads(text.strip().splitlines()[-1])
    assert "(sound reading: correct = True)" in text
    over = [ln.split()[1] for ln in text.splitlines()
            if ln.startswith("control[bf16] ") and ln.endswith(" OVER")]
    assert set(over) == {"row_set_mismatch_rows", "value_mismatch_cells"}
    assert final["correct"] is False and final["failed"] == 0
