"""The cell `s1000_lastpoint` (PR 43): its configuration, its traffic,
the operation `lastpoint` and the five metrics it brings, each looked up
in the committed manifest BY NAME (never "the last N of a list": a later
PR appends); the one request of the mix and its sweep; `check` on a right
answer and on doctored ones (a row dropped, a host twice, a timestamp one
tick early, one value's last bit, a null, columns swapped: each moves its
own reading and no other), and under the control's values; the five
readers on the counters and spans as the program renders them and on a
program without them (the parent of PR 43: no /query_last at all); and a
traced rehearsal at test size on the CPU with its `--control bf16` twin.
The tiny root of `helpers.py` gains one configuration file and one cell
for it, added here as a later PR adds its own: no committed file is
edited."""

import io
import json
import os

import numpy as np
import pyarrow as pa
import pytest
from pyarrow import ipc

from benchmark.harness import counters, layers, manifest
from benchmark.harness.dataset import Dataset, round_bf16
from benchmark.operations import lastpoint
from benchmark.tests.helpers import read_json, REPO, tiny_root, write_json
from benchmark.tests.test_rehearsal import run_cli

CELL = "s1000_lastpoint"
CONFIG = "tsbs-devops-cpu-s1000-lastpoint"
TRAFFIC = "lastpoint"
BASE_CELL = "s1000_single_groupby"      # tsbs-devops-cpu-s1000's
SEG_MS = 7_200_000
# name: (unit, better, source, layer, moves)
MINE = {
    "engine.resolve_ms.last": ("ms", "lower", "program_span",
                               "engine and planner", "query_p50_ms"),
    "scan.last_ms": ("ms", "lower", "program_span", "scan",
                     "query_p50_ms"),
    "route.last_device_share": ("%", "higher", "program_counter",
                                "route selection", "query_p50_ms"),
    "route.last_segments_per_query": ("1/query", "lower",
                                      "program_counter", "route selection",
                                      "queries_per_s"),
    "scan.last_rows_read_per_point": ("rows/point", "lower",
                                      "program_counter", "scan",
                                      "queries_per_s"),
}


def test_committed_manifest_has_the_cell_its_configuration_and_five_metrics():
    man = manifest.load(REPO)
    cell = man.workloads[CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": TRAFFIC,
                    "chips": 1, "why": cell["why"]}
    assert 0 < len(cell["why"]) <= 200
    for word in ("no time bound", "1,000 rows x 12 columns", "252 MB",
                 "newest of twelve segments", "no answer kept"):
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
    assert "--query-type=lastpoint" in entry["source"]
    assert "the last row of every host" in entry["source"]
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
                "scan.select_ms"} & reported
    # each once, and after every entry the parent had (appended)
    names = [m["name"] for m in man.doc["per_layer"]]
    assert all(names.count(n) == 1 for n in MINE)
    assert min(names.index(n) for n in MINE) \
        > names.index("engine.postings_hit_share")
    # the same span, read the same way, as the /query cells' resolve
    assert man.reader("engine.resolve_ms.last")["source"] \
        == man.reader("engine.resolve_ms")["source"]


def test_the_configuration_is_scale_1000_with_the_newest_segment_on_the_chip():
    """Data, schema and server settings are `tsbs-devops-cpu-s1000`'s
    key for key (nothing cut, nothing set); the guarantees are its own
    plus the rows'; the file adds what the deployment keeps on the
    chip, which has to agree with the slices' arithmetic, and counts
    one row a host in the scan's least bytes."""
    man = manifest.load(REPO)
    cfg, base = man.config(CELL), man.config(BASE_CELL)
    assert cfg["name"] == CONFIG and cfg["reduced"] == []
    assert cfg["source"] == man.configs[CONFIG]["source"]
    told = {"name", "source", "deployment", "held_on_device",
            "guarantees", "assumed", "device_row_bytes"}
    assert set(cfg) - set(base) == {"held_on_device"}
    assert set(base) <= set(cfg)
    assert {k: v for k, v in cfg.items() if k not in told} == {
        k: v for k, v in base.items() if k not in told}
    assert cfg["server"] == {"base": "docs/example.toml", "overrides": {}}
    assert (cfg["scale"], cfg["rows"], cfg["points"]) \
        == (1000, 8_640_000, 86_400_000)
    assert cfg["device_row_bytes"] == 120 == 10 * base["device_row_bytes"]
    added = {"last_rows", "row_values"}
    assert set(cfg["guarantees"]) - set(base["guarantees"]) == added
    assert {k: v for k, v in cfg["guarantees"].items()
            if k not in added | {"staleness"}} == {
        k: v for k, v in base["guarantees"].items() if k != "staleness"}
    assert cfg["guarantees"]["staleness"].startswith(
        base["guarantees"]["staleness"])
    assert "every write acknowledged before the request" \
        in cfg["guarantees"]["staleness"]
    assert "exact" in cfg["guarantees"]["last_rows"]
    assert "never cut by a look-back" in cfg["guarantees"]["last_rows"]
    assert "bit for bit" in cfg["guarantees"]["row_values"]
    assert "null" in cfg["guarantees"]["row_values"]
    mine = {"device_row_bytes", "query_wording", "tags_by_tsid",
            "value_width", "no_answer_kept", "held_on_device"}
    assert set(cfg["assumed"]) - set(base["assumed"]) \
        == mine - {"device_row_bytes"}
    assert {k: v for k, v in cfg["assumed"].items() if k not in mine} == {
        k: v for k, v in base["assumed"].items() if k not in mine}
    held = cfg["held_on_device"]
    seg_rows = cfg["scale"] * SEG_MS // cfg["interval_ms"]
    cap = 1 << (seg_rows - 1).bit_length()
    assert (seg_rows, cap) == (720_000, 1_048_576)
    assert held["slice_bytes"] == cap * 4 * 6 == 25_165_824
    assert held["fields"] == len(cfg["fields"]) == 10
    assert held["segments"] == 1 and cfg["span_ms"] // SEG_MS == 12
    assert held["slices"] == held["fields"] * held["segments"] == 10
    assert held["bytes"] == held["slices"] * held["slice_bytes"] \
        == 251_658_240


def test_the_traffic_is_tsbs_lastpoint():
    man = manifest.load(REPO)
    traffic, one = man.traffic(CELL), man.traffic(BASE_CELL)
    assert traffic["name"] == TRAFFIC
    assert (traffic["operation"], traffic["endpoint"]) == (
        "lastpoint", "/query_last")
    assert traffic["fields"] == "all" and traffic["hosts"] == "all"
    # what the harness's roofline reads of every traffic file: one tick
    assert traffic["window_ms"] == traffic["bucket_ms"] == 10_000
    assert traffic["output_grids"] == 0
    assert traffic["warmup"] == {"sweep_queries": 2, "pass_queries": 8}
    # no bound, no parameter
    assert traffic["body"] == {"metric": "{metric}", "fields": "{fields}"}
    assert traffic["limits"] == dict.fromkeys(lastpoint.READINGS, 0)
    for key in ("loop", "clients"):
        assert traffic[key] == one[key], key
    assert (traffic["loop"], traffic["clients"]) == ("closed", 4)


@pytest.fixture(scope="module")
def small():
    """Six hosts, ten fields, one day: names and values."""
    man = manifest.load(REPO)
    cfg = dict(man.config(CELL), scale=6)
    return man.traffic(CELL), Dataset(cfg, seed=2**31 + 43)


def test_every_request_is_the_same_request_and_the_sweep_is_two(small):
    traffic, data = small
    queries = lastpoint.make_queries(traffic, data,
                                     np.random.default_rng(5), 64)
    sweep = lastpoint.sweep_queries(traffic, data)
    assert len(queries) == 64 and len(sweep) == 2
    for q in queries + sweep:
        assert json.loads(q["body"]) == {"metric": "cpu",
                                         "fields": data.fields}
        assert q["fields"] == list(range(10)) and q["hosts"] is None
    assert len({q["body"] for q in queries + sweep}) == 1
    # the roofline's least bytes: one 120 B row a host, no grid
    from benchmark.harness import roofline

    rows = 1000 * traffic["window_ms"] // data.interval_ms
    assert roofline.scan_min_bytes(rows, 120, 1000, 1, 0) == 120_000
    with pytest.raises(ValueError):
        lastpoint.make_queries(dict(traffic, fields=["usage_user"]), data,
                               np.random.default_rng(5), 1)


def answer(query: dict, data, values=None, drop=(), twice=(), early=(),
           alter=(), null=(), names=None, order=None) -> bytes:
    """What a sound server answers, from the plain loop below (NOT
    `lastpoint.reference`): then hosts dropped, repeated, put one tick
    early, cells altered by one bit or nulled (host, field index)."""
    grids = data.values if values is None else values
    tick = data.ticks - 1
    rows = sorted((int(data.tsid_of_host[h]), h) for h in range(data.hosts)
                  if h not in drop)
    rows = sorted(rows + [r for r in rows if r[1] in twice])
    if order is not None:
        rows = [rows[i] for i in order]
    at = [tick - (h in early) for _, h in rows]
    cols = {"tsid": pa.array([t for t, _ in rows], type=pa.uint64()),
            "timestamp": pa.array(
                [data.t0 + t * data.interval_ms for t in at],
                type=pa.int64())}
    for f in query["fields"]:
        vals = np.array([grids[f][t, h] for (_, h), t in zip(rows, at)],
                        dtype=np.float32)
        mask = np.zeros(len(rows), dtype=bool)
        for i, (_, h) in enumerate(rows):
            if (h, f) in alter:
                vals[i] = (vals[i:i + 1].view(np.uint32)
                           ^ np.uint32(1)).view(np.float32)[0]
            if (h, f) in null:
                mask[i] = True
        cols[data.fields[f]] = pa.array(vals, type=pa.float32(), mask=mask)
    tbl = pa.table(cols)
    if names is not None:
        tbl = tbl.rename_columns(names)
    sink = io.BytesIO()
    with ipc.new_stream(sink, tbl.schema) as w:
        w.write_table(tbl)
    return sink.getvalue()


def _rewritten(payload: bytes, change) -> bytes:
    tbl = change(ipc.open_stream(payload).read_all())
    sink = io.BytesIO()
    with ipc.new_stream(sink, tbl.schema) as w:
        w.write_table(tbl)
    return sink.getvalue()


def extra_column(payload: bytes) -> bytes:
    return _rewritten(payload, lambda t: t.append_column(
        "usage_more", t.column("usage_user")))


def retyped(payload: bytes) -> bytes:
    return _rewritten(payload, lambda t: t.set_column(
        2, "usage_user", t.column("usage_user").cast(pa.float64())))


def test_reference_is_every_hosts_last_tick(small):
    _traffic, data = small
    newest, written = lastpoint.reference(data)
    assert newest == data.t0 + data.span_ms - data.interval_ms
    assert written.shape == (10, data.hosts)
    assert np.array_equal(written, data.values[:, -1, :])


def test_each_doctored_answer_moves_its_own_reading_and_no_other(small):
    traffic, data = small
    query = lastpoint.make_queries(traffic, data,
                                   np.random.default_rng(9), 1)[0]
    zero = dict.fromkeys(lastpoint.READINGS, 0)
    assert lastpoint.check(query, answer(query, data), data) == zero
    n = data.hosts
    cases = {
        "a row dropped": (dict(drop=(2,)),
                          dict(zero, row_set_mismatch_rows=1)),
        "two rows dropped": (dict(drop=(0, 5)),
                             dict(zero, row_set_mismatch_rows=2)),
        "a host twice": (dict(twice=(3,)),
                         dict(zero, malformed_responses=1)),
        "a timestamp one tick early": (dict(early=(4,)),
                                       dict(zero, row_set_mismatch_rows=1)),
        "one value's last bit": (dict(alter=[(1, 7)]),
                                 dict(zero, value_mismatch_cells=1)),
        "a null": (dict(null=[(5, 0)]),
                   dict(zero, value_mismatch_cells=1)),
        "a bit and a null and a row": (
            dict(alter=[(1, 7)], null=[(5, 0)], drop=(2,)),
            dict(zero, value_mismatch_cells=2, row_set_mismatch_rows=1)),
        "rows out of order": (dict(order=[1, 0] + list(range(2, n))),
                              dict(zero, malformed_responses=1)),
    }
    for what, (doctored, want) in cases.items():
        got = lastpoint.check(query, answer(query, data, **doctored), data)
        assert got == want, what
    malformed = dict(zero, malformed_responses=1)
    names = ["tsid", "timestamp"] + data.fields
    swapped = names[:2] + [names[3], names[2]] + names[4:]
    for payload in (
            b"", b"nonsense", json.dumps({"tsids": []}).encode(),
            answer(query, data, names=swapped),             # columns swapped
            answer(query, data, names=names[:-1] + ["usage_other"]),
            answer(dict(query, fields=query["fields"][:-1]), data),
            answer(dict(query, fields=query["fields"][::-1]), data),
            extra_column(answer(query, data)),
            retyped(answer(query, data))):
        assert lastpoint.check(query, payload, data) == malformed
    # a series the data set does not have
    saved = data.host_of_tsid
    try:
        data.host_of_tsid = {k: v for k, v in saved.items() if v != 0}
        assert lastpoint.check(query, answer(query, data), data) \
            == malformed
    finally:
        data.host_of_tsid = saved


def test_the_control_moves_the_values_and_nothing_else(small):
    """The reference from every field rounded to bfloat16 keeps the row
    set (which the control cannot move: the doctored answers above do)
    and changes every value with a fraction."""
    traffic, data = small
    query = lastpoint.make_queries(traffic, data,
                                   np.random.default_rng(9), 1)[0]
    zero = dict.fromkeys(lastpoint.READINGS, 0)
    rounded = lastpoint.control_values(data)
    assert np.array_equal(rounded, round_bf16(data.values))
    under = lastpoint.check(query, answer(query, data), data,
                            values=rounded)
    assert under["malformed_responses"] == 0
    assert under["row_set_mismatch_rows"] == 0
    assert under["value_mismatch_cells"] > data.hosts * 10 * 0.9
    # an answer computed from the rounded values passes the control
    # and fails the sound reading
    low = answer(query, data, values=rounded)
    assert lastpoint.check(query, low, data, values=rounded) == zero
    sound = lastpoint.check(query, low, data)
    assert sound["value_mismatch_cells"] == under["value_mismatch_cells"]
    assert lastpoint.combine([under, sound]) == {
        k: under[k] + sound[k] for k in lastpoint.READINGS}


def test_readers_on_the_counters_and_on_a_program_without_them():
    man = manifest.load(REPO)
    text = (
        'scan_last_segments_total{reason="",route="device"} 1010\n'
        'scan_last_segments_total{reason="memtable",route="host"} 5\n'
        'scan_last_rows_total{route="device",side="read"} 7207200000\n'
        'scan_last_rows_total{route="device",side="answered"} 10010000\n'
        'scan_last_rows_total{route="host",side="read"} 3000\n'
        'scan_last_rows_total{route="host",side="answered"} 1000\n'
        "scan_last_calls_total 1010\n")
    before = {
        'metrics.scan_last_segments_total{reason="",route="device"}': 10.0,
        'metrics.scan_last_segments_total{reason="memtable",route="host"}':
            5.0,
        "metrics.scan_last_segments_total": 15.0,
        'metrics.scan_last_rows_total{route="device",side="read"}':
            7_200_000.0,
        'metrics.scan_last_rows_total{route="device",side="answered"}':
            10_000.0,
        'metrics.scan_last_rows_total{route="host",side="read"}': 3000.0,
        'metrics.scan_last_rows_total{route="host",side="answered"}':
            1000.0}
    after: dict = {}
    counters.parse_metrics(text, after)
    obs = {"queries": 1000, "counters": counters.delta(before, after),
           "spans": {"total": [30.0, 50.0], "resolve": [0.25, 0.75],
                     "last": [20.0, 30.0], "respond": [1.0, 2.0]}}
    read = {name: layers.evaluate(man.reader(name), obs) for name in MINE}
    assert read == {
        "engine.resolve_ms.last": 0.5, "scan.last_ms": 25.0,
        "route.last_device_share": 100.0,
        "route.last_segments_per_query": 1.0,
        "scan.last_rows_read_per_point": 720.0}
    # a walk that asks the memtable's segment too, and reads it there
    mixed = dict(obs, counters=counters.delta(
        dict(before, **{
            'metrics.scan_last_segments_total{reason="memtable",'
            'route="host"}': 0.0,
            "metrics.scan_last_segments_total": 10.0}), after))
    assert layers.evaluate(man.reader("route.last_device_share"), mixed) \
        == pytest.approx(100.0 * 1000 / 1005)
    assert layers.evaluate(
        man.reader("route.last_segments_per_query"), mixed) == 1.005
    # the counters are there and stood still
    still = {"queries": 10, "counters": dict.fromkeys(before, 0.0)}
    for name in ("route.last_device_share", "route.last_segments_per_query",
                 "scan.last_rows_read_per_point"):
        assert layers.evaluate(man.reader(name), still) == 0.0, name
    # the parent of PR 43 renders none of them and traces no such
    # root: nothing, no error
    bare = {"queries": 100, "spans": {},
            "counters": {"metrics.respond_cells_total": 5.0}}
    for name in MINE:
        assert layers.evaluate(man.reader(name), bare) is None, name


def test_a_program_without_the_endpoint_fails_before_the_load_generator(
        small, tmp_path, monkeypatch):
    """The parent of PR 43 serves no /query_last: `make_queries` (the
    first thing run.py asks of the operation, with the server up and no
    load generator made) raises the harness's own error, which ends the
    run rc 1 with the server stopped."""
    from benchmark.harness.server import BenchError
    from benchmark.operations import select_where

    traffic, data = small
    server = tmp_path / "horaedb_tpu" / "server"
    server.mkdir(parents=True)
    (server / "main.py").write_text('@routes.post("/query_rows")\n')
    monkeypatch.setattr(select_where, "_ROOT", str(tmp_path))
    monkeypatch.setattr(
        select_where.require_endpoint, "__defaults__", (str(tmp_path),))
    with pytest.raises(BenchError, match="routes no /query_last"):
        lastpoint.make_queries(traffic, data, np.random.default_rng(1), 1)


def lastpoint_root(dst: str) -> dict:
    """`tiny_root` plus the committed lastpoint configuration at ten
    hosts, and a lastpoint cell on it that reports the five."""
    doc = tiny_root(dst)
    cfg = read_json(os.path.join(REPO, "benchmark/configs", CONFIG + ".json"))
    cfg.update(name="tiny-lastpoint", scale=10)
    cfg["ingest"] = dict(cfg["ingest"], body_rows=30_000)
    write_json(os.path.join(dst, "benchmark/configs/tiny-lastpoint.json"),
               cfg)
    doc["configs"].append({
        "name": "tiny-lastpoint", "source": "benchmark/tests", "reduced": [],
        "file": "benchmark/configs/tiny-lastpoint.json",
        "why": "CPU rehearsal"})
    doc["workloads"].append({
        "name": "tiny_lastpoint", "config": "tiny-lastpoint",
        "traffic": TRAFFIC, "chips": 1, "why": "CPU rehearsal"})
    for m in doc["per_layer"]:
        if m["name"] in MINE:
            m["workloads"] = m["workloads"] + ["tiny_lastpoint"]
    write_json(os.path.join(dst, "BENCHMARK.json"), doc)
    return doc


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tmp_path_factory.mktemp("lastpoint")
    lastpoint_root(str(path))
    return str(path)


def test_traced_rehearsal_of_the_lastpoint_cell(root, tmp_path):
    proc = run_cli(root, str(tmp_path / "out"), "--trace", "1",
                   "--platform", "cpu", workload="tiny_lastpoint")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    final = json.loads(lines[-1])
    assert final["correct"] is True and final["failed"] == 0
    assert final["attempted"] >= 2
    assert " OVER" not in "".join(
        ln for ln in lines if ln.startswith("check "))
    assert set(final["compared"]) == set(lastpoint.READINGS)
    got = {name: m["value"] for name, m in final["metrics"].items()}
    assert set(MINE) <= set(got)
    # every per-layer metric without a list is in the line, but for
    # the three that read the DEVICE's trace, which a CPU run has not
    man = manifest.load(root)
    assert {m["name"] for m in man.doc["per_layer"]
            if "workloads" not in m and m["source"] != "device_trace"} \
        <= set(got)
    assert {m["name"] for m in man.doc["per_layer"]
            if "workloads" not in m and m["source"] == "device_trace"} \
        == {"device.busy_pct", "kernel.scan_ms_per_query",
            "kernel.scan_roofline"}
    assert got["route.last_device_share"] == 100.0
    assert got["route.last_segments_per_query"] == pytest.approx(
        1.0, rel=4 / max(final["attempted"] - 1, 1))
    # 720 rows of a 2 h segment for each of a host's ten points
    assert got["scan.last_rows_read_per_point"] == 720.0
    assert got["route.fallbacks"] == 0.0
    assert got["cache.decode_resident_hit_share"] == 100.0
    assert got["cache.h2d_MB_per_query"] == 0.0
    assert got["fetch.sidecar_load_ms_per_query"] == 0.0
    assert got["device.compiles_in_window"] == 0.0
    assert got["route.batched_slice_share"] == 100.0
    # ten hosts x twelve columns a response
    assert got["front_end.respond_cells_per_query"] == pytest.approx(
        120.0, rel=0.1)
    assert 0.0 < got["engine.resolve_ms.last"] < got["scan.last_ms"]
    assert got["engine.postings_hit_share"] == 100.0
    assert "engine.resolve_ms" not in got and "scan.downsample_ms" not in got
    # one call of the one program a query: no answer is kept
    calls = got["route.dispatches_per_query"]
    assert 1 - 4 / max(final["attempted"], 5) <= calls \
        <= 1 + 4 / max(final["attempted"] - 1, 1)
    route = json.loads(next(ln for ln in lines
                            if ln.startswith("route "))[6:])
    assert set(route["calls_per_fn"]) == {"_last_rows_jit"}
    # one 120 B row a host, no grid
    assert route["scan_min_bytes_per_query"] == 10 * 120


def test_control_rehearsal_of_the_lastpoint_cell_is_not_correct(
        root, tmp_path):
    proc = run_cli(root, str(tmp_path / "out"), "--trace", "0",
                   "--platform", "cpu", "--control", "bf16",
                   workload="tiny_lastpoint")
    assert proc.returncode == 0, proc.stderr[-2000:]
    text = proc.stdout
    final = json.loads(text.strip().splitlines()[-1])
    assert "(sound reading: correct = True)" in text
    over = [ln.split()[1] for ln in text.splitlines()
            if ln.startswith("control[bf16] ") and ln.endswith(" OVER")]
    assert set(over) == {"value_mismatch_cells"}
    assert final["correct"] is False and final["failed"] == 0
