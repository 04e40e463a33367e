"""Operation `groupby_hostsample` and the scale-1000 cell that uses it:
the committed manifest loads with the configuration, the cell and its
four per-layer metrics; the sampled sweep touches every segment with
`7 x sweep_hosts` queries and leaves the query stream as `groupby`
makes it; and one rehearsal at test size on the CPU, for which the tiny
root of `helpers.py` gains one more traffic file and cell (added here,
as a later PR adds its own: no committed file is edited)."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import layers, manifest
from benchmark.harness.dataset import Dataset
from benchmark.operations import groupby, groupby_hostsample
from benchmark.tests.helpers import REPO, read_json, tiny_root, write_json
from benchmark.tests.test_rehearsal import run_cli

CELL = "s1000_single_groupby"
NEW_METRICS = {"fetch.store_calls_per_query": ("fetch", "1/query"),
               "fetch.sidecar_row_share": ("fetch", "%"),
               "fetch.sidecar_load_ms_per_query": ("fetch", "ms"),
               "cache.tier2_hit_share": ("caches", "%")}


def hostsample_root(dst: str, sweep_hosts: int = 4) -> dict:
    """`tiny_root` plus a two-hour point-query mix under the new
    operation and a cell that runs it."""
    doc = tiny_root(dst)
    mix = read_json(os.path.join(
        REPO, "benchmark/traffic/single-groupby-1-1-1-hostsample.json"))
    mix.update(name="hostsample-2h", window_ms=7_200_000, bucket_ms=600_000)
    mix["warmup"] = dict(mix["warmup"], sweep_hosts=sweep_hosts)
    write_json(os.path.join(dst, "benchmark/traffic/hostsample-2h.json"), mix)
    doc["workloads"].append({"name": "tiny_hostsample", "config": "tiny",
                             "traffic": "hostsample-2h", "chips": 1,
                             "why": "CPU rehearsal"})
    for m in doc["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("tiny_hostsample")
    write_json(os.path.join(dst, "BENCHMARK.json"), doc)
    return doc


def test_committed_manifest_has_the_scale_1000_cell():
    man = manifest.load(REPO)
    cell = man.workloads[CELL]
    assert cell["config"] == "tsbs-devops-cpu-s1000" and cell["chips"] == 1
    assert cell["traffic"] == "single-groupby-1-1-1-hostsample"
    entry = man.configs["tsbs-devops-cpu-s1000"]
    cfg = man.config(CELL)
    assert entry["reduced"] == [] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert (cfg["scale"], cfg["rows"], cfg["points"]) == (
        1000, 8_640_000, 86_400_000)
    assert cfg["server"] == {"base": "docs/example.toml", "overrides": {}}
    assert {m["name"] for m in man.end_to_end(CELL)} == {
        "query_p50_ms", "queries_per_s", "setup_s"}
    per_layer = {m["name"]: m for m in man.per_layer(CELL)}
    for name, (layer, unit) in NEW_METRICS.items():
        m = per_layer[name]
        assert (m["layer"], m["unit"], m["moves"]) == (
            layer, unit, "query_p50_ms")
        assert "workloads" not in m      # every cell says if it works it
        assert man.reader(name)["source"]["kind"] in ("counter", "ratio")
    # the older cells keep their lines: the new entries came last
    assert [w["name"] for w in man.doc["workloads"]][-1] == CELL
    assert [m["name"] for m in man.doc["per_layer"]][-4:] == list(NEW_METRICS)


def test_traffic_is_the_point_query_mix_with_another_warm_up():
    old = read_json(os.path.join(
        REPO, "benchmark/traffic/single-groupby-1-1-1.json"))
    new = read_json(os.path.join(
        REPO, "benchmark/traffic/single-groupby-1-1-1-hostsample.json"))
    assert new["operation"] == "groupby_hostsample"
    assert new["warmup"].pop("sweep_hosts") == 16
    for key in set(old) - {"name", "source", "operation"}:
        assert new[key] == old[key], key
    assert set(new) == set(old)


def test_readers_of_the_new_metrics_on_counters():
    man = manifest.load(REPO)
    obs = {"queries": 100, "counters": {
        "metrics.objstore_get_total": 50.0,
        "metrics.objstore_get_range_total": 950.0,
        'metrics.sidecar_load_rows_total{side="fetched"}': 98_304.0 * 150,
        'metrics.sidecar_load_rows_total{side="stored"}': 7_200_000.0 * 150,
        "metrics.sidecar_load_seconds_sum": 1.5,
        "stats.tables.data.cache.encoded_cache.hits": 30.0,
        "stats.tables.data.cache.encoded_cache.misses": 10.0}}
    read = {n: layers.evaluate(man.reader(n), obs) for n in NEW_METRICS}
    assert read["fetch.store_calls_per_query"] == 10.0
    assert read["fetch.sidecar_row_share"] == pytest.approx(1.3653, abs=1e-3)
    assert read["fetch.sidecar_load_ms_per_query"] == pytest.approx(15.0)
    assert read["cache.tier2_hit_share"] == 75.0
    # a program that lacks the counters (the parent): nothing, no error
    bare = {"queries": 100, "counters": {}}
    assert all(layers.evaluate(man.reader(n), bare) is None
               for n in NEW_METRICS)
    # counters that are there and did not move: the share reads 0
    still = {"queries": 100, "counters": dict.fromkeys(obs["counters"], 0.0)}
    assert layers.evaluate(
        man.reader("fetch.sidecar_row_share"), still) == 0.0
    assert layers.evaluate(man.reader("cache.tier2_hit_share"), still) == 0.0


@pytest.mark.parametrize("config,hosts", [
    ("tsbs-devops-cpu-s1000", 16), ("tsbs-devops-cpu-s100", 16)])
def test_sampled_sweep_touches_every_segment(config, hosts):
    man = manifest.load(REPO)
    traffic = man.traffic(CELL)
    cfg = dict(read_json(os.path.join(
        REPO, f"benchmark/configs/{config}.json")), span_ms=7_200_000)
    # a two-hour data set is enough for host names and bounds; the
    # sweep is laid over the configuration's own day below
    data = Dataset(cfg, seed=5)
    data.span_ms = 86_400_000
    sweep = groupby_hostsample.sweep_queries(traffic, data)
    assert len(sweep) == 7 * hosts
    step = data.hosts // hosts
    assert {q["hosts"][0] for q in sweep} == set(range(0, step * hosts, step))
    seg_ms = 7_200_000
    shapes = set()
    touched = set()
    for q in sweep:
        segs = range((q["start"] - data.t0) // seg_ms,
                     (q["end"] - 1 - data.t0) // seg_ms + 1)
        touched.update(segs)
        shapes.add(len(segs))
    assert touched == set(range(12)) and shapes == {1, 2}
    full = groupby.sweep_queries(traffic, data)
    assert len(full) == 7 * data.hosts
    assert all(q in full for q in sweep)


def test_query_stream_is_groupbys_own():
    man = manifest.load(REPO)
    old, new = man.traffic("s100_single_groupby"), man.traffic(CELL)
    data = Dataset(dict(man.config("s100_single_groupby"),
                        span_ms=7_200_000), seed=9)
    data.span_ms = 86_400_000
    a = groupby.make_queries(old, data, np.random.default_rng(3), 256)
    b = groupby_hostsample.make_queries(new, data,
                                        np.random.default_rng(3), 256)
    assert a == b
    assert groupby_hostsample.check is groupby.check
    assert groupby_hostsample.combine is groupby.combine
    assert groupby_hostsample.READINGS is groupby.READINGS


def test_fewer_hosts_than_the_sample_sweeps_them_all(tmp_path):
    hostsample_root(str(tmp_path), sweep_hosts=16)
    man = manifest.load(str(tmp_path))
    data = Dataset(man.config("tiny_hostsample"), seed=1)
    sweep = groupby_hostsample.sweep_queries(
        man.traffic("tiny_hostsample"), data)
    assert {q["hosts"][0] for q in sweep} == set(range(data.hosts))


def test_traced_rehearsal_of_the_new_operation(tmp_path):
    root = tmp_path / "root"
    root.mkdir()
    hostsample_root(str(root))
    proc = run_cli(str(root), str(tmp_path / "out"), "--trace", "1",
                   "--platform", "cpu", workload="tiny_hostsample")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    final = json.loads(lines[-1])
    assert final["correct"] is True and final["failed"] == 0
    setup = json.loads(next(ln for ln in lines
                            if ln.startswith("setup "))[6:])
    assert setup["sweep_queries"] == 4 * 7     # 4 hosts x seven windows
    assert set(NEW_METRICS) <= set(final["metrics"])
    for name in NEW_METRICS:
        assert final["metrics"][name]["value"] >= 0.0
