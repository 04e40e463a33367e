"""The cell `s1000_double_groupby_all` (PR 34): its configuration, its
traffic, the operation `groupby_multi` and the four metrics it brings,
each looked up in the committed manifest BY NAME; the shape of the
query and of its sweep; `check` on a right answer, on one altered grid
of one field, on a missing field and under the control's values; and a
traced rehearsal at test size on the CPU of an all-fields cell, with
its `--control bf16` twin.  The tiny root of `helpers.py` gains one
configuration file and one cell for it, added here as a later PR adds
its own: no committed file is edited."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import counters, layers, manifest
from benchmark.harness.dataset import AGGS, Dataset, round_bf16
from benchmark.operations import groupby, groupby_multi
from benchmark.tests.helpers import read_json, REPO, tiny_root, write_json
from benchmark.tests.test_rehearsal import run_cli

CELL = "s1000_double_groupby_all"
CONFIG = "tsbs-devops-cpu-s1000-allfields"
TRAFFIC = "double-groupby-all"
FIELDS_PER_QUERY = "engine.fields_per_query"
MS_PER_FIELD = "scan.downsample_ms_per_field"
# the two span readings of the multi-field path: `engine.resolve_ms` and
# `scan.downsample_ms` read the same span names but are listed for the
# four cells the benchmark had, because this cell's parent has no such
# spans under a /query_multi root and a listless metric must be in every
# traced line of both sides
RESOLVE_MS = "engine.resolve_ms.multi"
DOWNSAMPLE_MS = "scan.downsample_ms.multi"
MINE = (FIELDS_PER_QUERY, MS_PER_FIELD, RESOLVE_MS, DOWNSAMPLE_MS)
ACCEPTED_CELLS = ["s100_double_groupby", "s100_single_groupby",
                  "s1000_single_groupby", "s1000_double_groupby"]
SEG_MS = 7_200_000


def test_committed_manifest_has_the_all_fields_cell_by_name():
    man = manifest.load(REPO)
    cell = man.workloads[CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": TRAFFIC,
                    "chips": 1, "why": cell["why"]}
    assert 0 < len(cell["why"]) <= 200
    entry = man.configs[CONFIG]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["reduced"] == [] and 0 < len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    # a deployment of its own: no other configuration's source or file
    others = [c for c in man.doc["configs"] if c["name"] != CONFIG]
    assert entry["source"] not in {c["source"] for c in others}
    assert entry["file"] not in {c["file"] for c in others}
    assert {m["name"] for m in man.end_to_end(CELL)} == {
        "query_p50_ms", "queries_per_s", "setup_s"}
    by_name = {m["name"]: m for m in man.doc["per_layer"]}
    assert by_name[FIELDS_PER_QUERY] == {
        "name": FIELDS_PER_QUERY, "unit": "1/query", "better": "lower",
        "source": "program_counter", "layer": "engine and planner",
        "moves": "query_p50_ms", "workloads": [CELL]}
    assert by_name[MS_PER_FIELD] == {
        "name": MS_PER_FIELD, "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "scan",
        "moves": "query_p50_ms", "workloads": [CELL]}
    for name, layer in ((RESOLVE_MS, "engine and planner"),
                        (DOWNSAMPLE_MS, "scan")):
        assert by_name[name] == {
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": layer,
            "moves": "query_p50_ms", "workloads": [CELL]}
        # the same span, read the same way, as the single-field metric
        assert man.reader(name)["source"] \
            == man.reader(name[:-len(".multi")])["source"]
        assert by_name[name[:-len(".multi")]]["workloads"] == ACCEPTED_CELLS
    # the new cell reports the four and every metric without a list; no
    # other cell reports them, and the new cell does not report the two
    # span metrics its parent could not give
    for name in man.workloads:
        reported = {m["name"] for m in man.per_layer(name)}
        assert (set(MINE) <= reported) == (name == CELL), name
        assert ({"engine.resolve_ms", "scan.downsample_ms"} <= reported) \
            == (name != CELL), name
    assert {"kernel.scan_roofline", "front_end.encode_ms",
            "front_end.respond_cells_per_query"} \
        <= {m["name"] for m in man.per_layer(CELL)}


def test_the_configuration_is_scale_1000_with_ten_fields_on_the_chip():
    """Data, schema, guarantees and server settings are
    `tsbs-devops-cpu-s1000`'s key for key (nothing cut, nothing set);
    the file adds what the deployment keeps on the chip, which has to
    agree with the slices' arithmetic, and counts ten fields' rows in
    the scan's least bytes."""
    man = manifest.load(REPO)
    cfg, base = man.config(CELL), man.config("s1000_single_groupby")
    assert cfg["name"] == CONFIG and cfg["reduced"] == []
    assert cfg["source"] == man.configs[CONFIG]["source"]
    told = {"name", "source", "deployment", "held_on_device",
            "device_row_bytes", "assumed"}
    assert set(cfg) - set(base) == {"held_on_device"}
    assert {k: v for k, v in cfg.items() if k not in told} == {
        k: v for k, v in base.items() if k not in told}
    assert cfg["server"] == {"base": "docs/example.toml", "overrides": {}}
    assert {k: v for k, v in cfg["assumed"].items()
            if k not in ("held_on_device", "device_row_bytes")} == {
        k: v for k, v in base["assumed"].items()
        if k != "device_row_bytes"}
    assert cfg["device_row_bytes"] == len(cfg["fields"]) \
        * base["device_row_bytes"] == 120
    held = cfg["held_on_device"]
    seg_rows = cfg["scale"] * SEG_MS // cfg["interval_ms"]
    cap = 1 << (seg_rows - 1).bit_length()
    assert (seg_rows, cap) == (720_000, 1_048_576)
    assert held["slice_bytes"] == cap * 4 * 6
    assert held["fields"] == len(cfg["fields"]) == 10
    assert held["segments"] == cfg["span_ms"] // SEG_MS == 12
    assert held["slices"] == held["fields"] * held["segments"] == 120
    assert held["bytes"] == held["slices"] * held["slice_bytes"] \
        == 3_019_898_880


def test_the_traffic_is_double_groupby_1_over_all_fields():
    man = manifest.load(REPO)
    traffic, one = man.traffic(CELL), man.traffic("s1000_double_groupby")
    assert traffic["name"] == TRAFFIC
    assert (traffic["operation"], traffic["endpoint"]) == (
        "groupby_multi", "/query_multi")
    assert traffic["output_grids"] == 10 * one["output_grids"] == 70
    assert traffic["warmup"] == {"sweep_stride_ms": 43_200_000,
                                 "pass_queries": 8}
    assert traffic["body"] == {
        "metric": "{metric}", "fields": "{fields}", "start": "{start}",
        "end": "{end}", "bucket_ms": "{bucket_ms}"}
    for key in ("loop", "clients", "window_ms", "bucket_ms", "hosts",
                "aggregate", "start_granularity_ms", "limits"):
        assert traffic[key] == one[key], key


@pytest.fixture(scope="module")
def small():
    """Four hosts, ten fields, one day: names, bounds and values."""
    man = manifest.load(REPO)
    cfg = dict(man.config(CELL), scale=4)
    return man.traffic(CELL), Dataset(cfg, seed=2**31 + 34)


def test_shape_of_the_query_and_of_its_sweep(small):
    """12 h of all hosts and all ten fields in one body: 4.32M rows a
    field in range at scale 1000, seven of the twelve segments (a start
    on an edge: six), and a sweep of two windows that touches all
    twelve segments, so all 120 (field, segment) slices."""
    traffic, data = small
    assert 1000 * traffic["window_ms"] // data.interval_ms == 4_320_000

    def segments(q):
        return range((q["start"] - data.t0) // SEG_MS,
                     (q["end"] - 1 - data.t0) // SEG_MS + 1)

    sweep = groupby_multi.sweep_queries(traffic, data)
    assert len(sweep) == 2
    assert {s for q in sweep for s in segments(q)} == set(range(12))
    queries = groupby_multi.make_queries(
        traffic, data, np.random.default_rng(5), 64)
    assert {len(segments(q)) for q in queries} <= {6, 7}
    for q in queries + sweep:
        body = json.loads(q["body"])
        assert body == {"metric": "cpu", "fields": data.fields,
                        "start": q["start"], "end": q["end"],
                        "bucket_ms": 3_600_000}
        assert q["end"] - q["start"] == traffic["window_ms"]
        assert q["hosts"] is None
    # the stream is `groupby`'s: same seed, same windows
    ones = groupby.make_queries(
        dict(traffic, body={"field": "{field}"}), data,
        np.random.default_rng(5), 64)
    assert [q["start"] for q in ones] == [q["start"] for q in queries]


def right_answer(query: dict, data, values=None) -> dict:
    """What a sound server answers: each field's reference grids (the
    six compared and a seventh that is not), series in another order
    than the data set's."""
    order = list(range(data.hosts))[::-1]
    out = {}
    for f, field in enumerate(data.fields):
        ref = data.groupby(
            query["start"], query["end"], query["bucket_ms"], hosts=order,
            values=(data.values if values is None else values)[f])
        aggs = {a: [[None if x != x else x for x in row]
                    for row in ref[a].tolist()] for a in AGGS}
        aggs["last_ts"] = aggs["count"]
        out[field] = {"tsids": [data.tsid_of_host[h] for h in order],
                      "num_buckets": ref["count"].shape[1], "aggs": aggs}
    return out


def test_check_on_right_altered_missing_and_control(small):
    traffic, data = small
    query = groupby_multi.make_queries(
        traffic, data, np.random.default_rng(9), 1)[0]
    answer = right_answer(query, data)
    good = groupby_multi.check(query, json.dumps(answer).encode(), data)
    assert good == {"malformed_responses": 0, "count_mismatch_cells": 0,
                    "select_mismatch_cells": 0,
                    "sum_avg_max_rel_err": good["sum_avg_max_rel_err"]}
    assert good["sum_avg_max_rel_err"] < 1e-12

    # ONE cell of ONE grid of the LAST field: the first field's grids
    # alone would not show it
    wrong = json.loads(json.dumps(answer))
    wrong[data.fields[-1]]["aggs"]["max"][2][5] += 1.0
    wrong[data.fields[4]]["aggs"]["count"][0][0] += 1.0
    wrong[data.fields[7]]["aggs"]["avg"][1][1] *= 1.001
    got = groupby_multi.check(query, json.dumps(wrong).encode(), data)
    assert (got["malformed_responses"], got["count_mismatch_cells"],
            got["select_mismatch_cells"]) == (0, 1, 1)
    assert 9e-4 < got["sum_avg_max_rel_err"] < 1.1e-3

    malformed = {"malformed_responses": 1, "count_mismatch_cells": 0,
                 "select_mismatch_cells": 0, "sum_avg_max_rel_err": 0.0}
    missing = {f: b for f, b in answer.items() if f != data.fields[3]}
    extra = dict(answer, usage_other=answer[data.fields[0]])
    lost_series = json.loads(json.dumps(answer))
    lost_series[data.fields[6]]["tsids"][0] = "12345"
    short = json.loads(json.dumps(answer))
    short[data.fields[2]]["aggs"]["sum"] = \
        short[data.fields[2]]["aggs"]["sum"][1:]
    for body in (missing, extra, lost_series, short,
                 answer[data.fields[0]], [], "nonsense"):
        assert groupby_multi.check(
            query, json.dumps(body).encode(), data) == malformed
    assert groupby_multi.check(query, b"{", data) == malformed

    # the control: run.py hands the first field's rounded grid; every
    # field is held to its OWN values rounded to bfloat16, once a run
    flag = round_bf16(data.grid)
    under = groupby_multi.check(query, json.dumps(answer).encode(), data,
                                values=flag)
    assert under["malformed_responses"] == 0
    assert under["count_mismatch_cells"] == 0
    assert under["select_mismatch_cells"] > 0
    assert under["sum_avg_max_rel_err"] > 1e-5
    rounded = data.values_bf16
    assert np.array_equal(rounded, round_bf16(data.values))
    groupby_multi.check(query, json.dumps(answer).encode(), data,
                        values=flag)
    assert data.values_bf16 is rounded
    # an answer computed from the rounded values passes the control
    # and fails the sound reading: on every field but the first too
    low = right_answer(query, data, values=rounded)
    for f in (0, len(data.fields) - 1):
        mixed = dict(answer, **{data.fields[f]: low[data.fields[f]]})
        body = json.dumps(mixed).encode()
        assert groupby_multi.check(query, body, data)[
            "select_mismatch_cells"] > 0
    assert groupby_multi.check(
        query, json.dumps(low).encode(), data, values=flag)[
            "select_mismatch_cells"] == 0
    assert groupby_multi.combine([good, got]) == got


def test_readers_on_the_counters_and_on_a_program_without_them():
    man = manifest.load(REPO)
    per_query, per_field = man.reader(FIELDS_PER_QUERY), \
        man.reader(MS_PER_FIELD)
    text = ("# TYPE query_multi_total counter\nquery_multi_total 130\n"
            "query_multi_fields_total 1300\n"
            "query_multi_scan_seconds_total 60.5\n")
    before = {"metrics.query_multi_total": 30.0,
              "metrics.query_multi_fields_total": 300.0,
              "metrics.query_multi_scan_seconds_total": 10.5}
    after: dict = {}
    counters.parse_metrics(text, after)
    obs = {"queries": 100, "counters": counters.delta(before, after)}
    assert layers.evaluate(per_query, obs) == 10.0
    assert layers.evaluate(per_field, obs) == 50.0
    still = {"queries": 100, "counters": {k: 0.0 for k in before}}
    assert layers.evaluate(per_query, still) == 0.0
    assert layers.evaluate(per_field, still) == 0.0
    # the parent of PR 34 renders none of the three: nothing, no error
    bare = {"queries": 100, "counters": {"metrics.respond_cells_total": 5.0}}
    assert layers.evaluate(per_query, bare) is None
    assert layers.evaluate(per_field, bare) is None
    # nor has its /query_multi root the two spans: `query_spans` gives
    # `total` and `respond` alone there, and the two readers nothing
    resolve, downsample = man.reader(RESOLVE_MS), man.reader(DOWNSAMPLE_MS)
    parent = {"spans": {"total": [1600.0, 1500.0], "respond": [150.0, 140.0]}}
    assert layers.evaluate(resolve, parent) is None
    assert layers.evaluate(downsample, parent) is None
    change = {"spans": dict(parent["spans"], resolve=[0.25, 0.75],
                            downsample=[1400.0, 1300.0])}
    assert layers.evaluate(resolve, change) == 0.5
    assert layers.evaluate(downsample, change) == 1350.0


def all_fields_root(dst: str) -> dict:
    """`tiny_root` plus the tiny configuration counted as the
    all-fields deployment counts it, and an all-fields cell on it."""
    doc = tiny_root(dst)
    cfg = read_json(os.path.join(dst, "benchmark/configs/tiny.json"))
    cfg.update(name="tiny-allfields",
               device_row_bytes=12 * len(cfg["fields"]))
    write_json(os.path.join(
        dst, "benchmark/configs/tiny-allfields.json"), cfg)
    doc["configs"].append({
        "name": "tiny-allfields", "source": "benchmark/tests",
        "reduced": [], "file": "benchmark/configs/tiny-allfields.json",
        "why": "CPU rehearsal"})
    doc["workloads"].append({
        "name": "tiny_all", "config": "tiny-allfields",
        "traffic": TRAFFIC, "chips": 1, "why": "CPU rehearsal"})
    for m in doc["per_layer"]:
        if m["name"] in MINE:
            m["workloads"] = m["workloads"] + ["tiny_all"]
    write_json(os.path.join(dst, "BENCHMARK.json"), doc)
    return doc


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tmp_path_factory.mktemp("allfields")
    all_fields_root(str(path))
    return str(path)


def test_traced_rehearsal_of_the_all_fields_cell(root, tmp_path):
    proc = run_cli(root, str(tmp_path / "out"), "--trace", "1",
                   "--platform", "cpu", workload="tiny_all")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    final = json.loads(lines[-1])
    assert final["correct"] is True and final["failed"] == 0
    assert final["attempted"] >= 2
    assert " OVER" not in "".join(
        ln for ln in lines if ln.startswith("check "))
    got = {name: m["value"] for name, m in final["metrics"].items()}
    fields = 10
    assert got[FIELDS_PER_QUERY] == fields
    # ten `downsample` spans a query, summed; `resolve` once; the
    # front end's residual is not the whole query
    assert got[RESOLVE_MS] > 0
    assert got[DOWNSAMPLE_MS] > 0
    assert 0.5 < got[DOWNSAMPLE_MS] / (
        fields * got[MS_PER_FIELD]) < 2.0, got
    assert got["front_end.encode_ms"] < got[DOWNSAMPLE_MS]
    assert "engine.resolve_ms" not in got
    assert "scan.downsample_ms" not in got
    assert got["front_end.respond_ms"] <= got["front_end.encode_ms"] + 1.0
    # seven grids a field, ten hosts, twelve buckets
    assert got["front_end.respond_cells_per_query"] == 7 * fields * 10 * 12
    assert got["route.fallbacks"] == 0.0
    # 70 a query (a start on a segment's edge: 60); the requests in
    # flight at the two counter reads (four clients, so at most four)
    # count their calls, not themselves
    assert 60.0 <= got["route.dispatches_per_query"] \
        <= 70.0 * (1 + 4 / max(final["attempted"] - 1, 1))
    route = json.loads(next(ln for ln in lines
                            if ln.startswith("route "))[6:])
    assert set(route["calls_per_fn"]) == {"_decode_aggregate_jit"}
    hosts, ticks = 10, 43_200_000 // 10_000
    assert route["scan_min_bytes_per_query"] == \
        hosts * ticks * 120 + hosts * 12 * 70 * 4


def test_control_rehearsal_of_the_all_fields_cell_is_not_correct(
        root, tmp_path):
    proc = run_cli(root, str(tmp_path / "out"), "--trace", "0",
                   "--platform", "cpu", "--control", "bf16",
                   workload="tiny_all")
    assert proc.returncode == 0, proc.stderr[-2000:]
    text = proc.stdout
    final = json.loads(text.strip().splitlines()[-1])
    assert "(sound reading: correct = True)" in text
    assert "control[bf16] select_mismatch_cells" in text
    over = [ln.split()[1] for ln in text.splitlines()
            if ln.startswith("control[bf16] ") and ln.endswith(" OVER")]
    assert set(over) == {"select_mismatch_cells", "sum_avg_max_rel_err"}
    assert final["correct"] is False and final["failed"] == 0
