"""The cell `s1000_double_groupby` and the metric it brings,
`cache.hbm_evictions_per_query` (PR 32): both in the committed manifest,
looked up by name; the reader on the series as `/metrics` renders it,
on one that stood still and on a program without it; and one traced
rehearsal at test size on the CPU of an all-hosts cell whose
device-decode slices exceed the budget they fall back to there (the
windows' `cache_bytes`: XLA-CPU reports no `bytes_limit`), which is the
thrash the cell meets on a chip whose slices share the windows' budget.
The tiny root of `helpers.py` gains one configuration file and one cell
for it, added here as a later PR adds its own: no committed file is
edited."""

import json
import os

import numpy as np

from benchmark.harness import counters, layers, manifest
from benchmark.harness.dataset import Dataset
from benchmark.operations import groupby
from benchmark.tests.helpers import REPO, read_json, tiny_root, write_json
from benchmark.tests.test_rehearsal import run_cli

CELL = "s1000_double_groupby"
CONFIG = "tsbs-devops-cpu-s1000-fleet"
METRIC = "cache.hbm_evictions_per_query"
SERIES = 'scan_cache_evictions_total{tier="hbm"}'

# one field's slice of a tiny segment: 10 hosts x 720 ticks in their
# capacity bucket, six 4-byte columns
TINY_SLICE_BYTES = 8_192 * 4 * 6
# [scan] cache_max_rows of the rehearsal: x 32 B = 512 KiB, which holds
# two such slices where a 12 h query wants seven in segment order
TINY_CACHE_ROWS = 16_384


def test_committed_manifest_has_the_fleet_wide_cell_and_its_metric():
    man = manifest.load(REPO)
    cell = man.workloads[CELL]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "double-groupby-1", 1)
    assert man.traffic(CELL) == man.traffic("s100_double_groupby")
    entry = man.configs[CONFIG]
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert [c["name"] for c in man.doc["configs"]][-1] == CONFIG
    # a deployment of its own: no other configuration's source or file
    others = [c for c in man.doc["configs"] if c["name"] != CONFIG]
    assert entry["source"] not in {c["source"] for c in others}
    assert entry["file"] not in {c["file"] for c in others}
    assert {m["name"] for m in man.end_to_end(CELL)} == {
        "query_p50_ms", "queries_per_s", "setup_s"}
    entry, = [m for m in man.doc["per_layer"] if m["name"] == METRIC]
    assert entry == {"name": METRIC, "unit": "1/query", "better": "lower",
                     "source": "program_counter", "layer": "caches",
                     "moves": "query_p50_ms"}
    # no `workloads` list: every cell reports it
    for name in man.workloads:
        assert METRIC in {m["name"] for m in man.per_layer(name)}
    reader = man.reader(METRIC)
    assert reader["source"] == {
        "kind": "counter", "per": "query",
        "counters": [f"metrics.{SERIES}"]}
    # the new entries came last; what was there keeps its place
    assert [w["name"] for w in man.doc["workloads"]][-1] == CELL
    assert [m["name"] for m in man.doc["per_layer"]][-1] == METRIC


def test_the_fleet_configuration_is_scale_1000_and_states_what_the_chip_holds():
    """The data, schema, guarantees and server settings are
    `tsbs-devops-cpu-s1000`'s key for key (nothing cut, nothing set);
    what the file adds is the state the deployment keeps on the chip,
    which has to agree with the slices' arithmetic."""
    man = manifest.load(REPO)
    cfg, base = man.config(CELL), man.config("s1000_single_groupby")
    assert cfg["name"] == CONFIG and cfg["reduced"] == []
    assert cfg["source"] == man.configs[CONFIG]["source"]
    told = {"name", "source", "deployment", "held_on_device", "assumed"}
    assert set(cfg) - set(base) == {"held_on_device"}
    assert {k: v for k, v in cfg.items() if k not in told} == {
        k: v for k, v in base.items() if k not in told}
    assert cfg["server"] == {"base": "docs/example.toml", "overrides": {}}
    assert {k: v for k, v in cfg["assumed"].items()
            if k != "held_on_device"} == base["assumed"]
    held = cfg["held_on_device"]
    seg_rows = cfg["scale"] * 7_200_000 // cfg["interval_ms"]
    cap = 1 << (seg_rows - 1).bit_length()
    assert (seg_rows, cap) == (720_000, 1_048_576)
    assert held["slice_bytes"] == cap * 4 * 6
    assert held["slices"] == cfg["span_ms"] // 7_200_000 == 12
    assert held["bytes"] == held["slices"] * held["slice_bytes"] == 301_989_888


def test_shape_of_the_query_and_of_its_sweep():
    """12 h of all 1,000 hosts: 4.32M rows of one field in range, seven
    of the twelve 2 h segments (a start on a segment's edge: six), and
    a sweep of two windows that touches all twelve."""
    man = manifest.load(REPO)
    traffic = man.traffic(CELL)
    cfg = dict(man.config(CELL), span_ms=7_200_000)
    data = Dataset(cfg, seed=2**31 + 7)     # two hours: names and bounds
    data.span_ms = 86_400_000
    assert data.hosts == 1000
    assert data.hosts * traffic["window_ms"] // data.interval_ms == 4_320_000
    seg_ms = 7_200_000

    def segments(q):
        return range((q["start"] - data.t0) // seg_ms,
                     (q["end"] - 1 - data.t0) // seg_ms + 1)

    sweep = groupby.sweep_queries(traffic, data)
    assert len(sweep) == 2
    assert {s for q in sweep for s in segments(q)} == set(range(12))
    queries = groupby.make_queries(traffic, data,
                                   np.random.default_rng(5), 256)
    assert {len(segments(q)) for q in queries} <= {6, 7}
    assert all(q["end"] - q["start"] == traffic["window_ms"]
               and q["hosts"] is None for q in queries)


def test_reader_on_the_series_and_on_a_program_without_it():
    reader = manifest.load(REPO).reader(METRIC)
    text = (f"# TYPE scan_cache_evictions_total counter\n"
            f"{SERIES} 4120\n"
            f'scan_cache_evictions_total{{tier="tier2"}} 9000\n'
            f'scan_cache_account_events_total{{event="evicted",kind="slice",'
            f'tier="hbm"}} 4100\n')
    before, after = {f"metrics.{SERIES}": 20.0}, {}
    counters.parse_metrics(text, after)
    obs = {"queries": 50, "counters": counters.delta(before, after)}
    # tier 2's evictions and the per-account twin are other series
    assert layers.evaluate(reader, obs) == 82.0
    still = {"queries": 50, "counters": {f"metrics.{SERIES}": 0.0}}
    assert layers.evaluate(reader, still) == 0.0
    # a program that never rendered the series: nothing, no error
    bare = {"queries": 50, "counters": {
        'metrics.scan_cache_evictions_total{tier="tier2"}': 3.0}}
    assert layers.evaluate(reader, bare) is None
    assert layers.evaluate(reader, {"queries": 0, "counters":
                                    obs["counters"]}) is None


def fleet_root(dst: str) -> dict:
    """`tiny_root` plus the tiny configuration under a scan-cache
    budget of two slices, and an all-hosts 12 h cell that runs it."""
    doc = tiny_root(dst)
    cfg = read_json(os.path.join(dst, "benchmark/configs/tiny.json"))
    cfg.update(name="tiny-two-slices")
    cfg["server"] = dict(cfg["server"], overrides={
        "metric_engine.time_merge_storage.scan.cache_max_rows":
            TINY_CACHE_ROWS})
    write_json(os.path.join(
        dst, "benchmark/configs/tiny-two-slices.json"), cfg)
    doc["configs"].append({
        "name": "tiny-two-slices", "source": "benchmark/tests",
        "reduced": [], "file": "benchmark/configs/tiny-two-slices.json",
        "why": "CPU rehearsal"})
    doc["workloads"].append({
        "name": "tiny_fleet", "config": "tiny-two-slices",
        "traffic": "double-groupby-1", "chips": 1, "why": "CPU rehearsal"})
    for m in doc["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("tiny_fleet")
    write_json(os.path.join(dst, "BENCHMARK.json"), doc)
    return doc


def test_traced_rehearsal_of_slices_over_the_fallback_budget(tmp_path):
    root = tmp_path / "root"
    root.mkdir()
    fleet_root(str(root))
    assert 2 * TINY_SLICE_BYTES <= TINY_CACHE_ROWS * 32 < 3 * TINY_SLICE_BYTES
    proc = run_cli(str(root), str(tmp_path / "out"), "--trace", "1",
                   "--platform", "cpu", workload="tiny_fleet")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    final = json.loads(lines[-1])
    # evicted slices are read, narrowed and uploaded again: the answers
    # are still the reference's, on every grid
    assert final["correct"] is True and final["failed"] == 0
    assert " OVER" not in "".join(
        ln for ln in lines if ln.startswith("check "))
    got = {name: m["value"] for name, m in final["metrics"].items()}
    # most admissions throw out a slice that a query in flight still
    # wants (four clients share the two that stay: some do hit)
    assert got[METRIC] >= 2.0, got
    assert got["cache.decode_resident_hit_share"] < 70.0, got
    assert got["cache.h2d_MB_per_query"] > 0.0
    assert got["route.fallbacks"] == 0.0
    route = json.loads(next(ln for ln in lines
                            if ln.startswith("route "))[6:])
    assert set(route["calls_per_fn"]) == {"_decode_aggregate_jit"}
