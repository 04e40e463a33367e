"""The per-layer metrics that read the host's accounts (PR 37): the busy
/ select account of the loop's thread, every thread's CPU by role, CPU
beside wall in the synchronous phases and in the response encoder.
Each reader file on a synthetic observation that names the samples as
`/metrics` renders them, on one that lacks them (the parent commit:
nothing to read, nothing raised), in the manifest, and in the line of a
traced rehearsal of each route."""

import json

import pytest

from benchmark.harness import counters, layers, manifest
from benchmark.tests.helpers import REPO, read_json, tiny_root, write_json
from benchmark.tests.test_rehearsal import run_cli

METRICS_TEXT = """\
# TYPE event_loop_busy_seconds_total counter
event_loop_busy_seconds_total 27.0
event_loop_select_seconds_total 18.0
process_thread_cpu_seconds_total{role="loop"} 20.0
process_thread_cpu_seconds_total{role="sst"} 16.0
process_thread_cpu_seconds_total{role="compact"} 0.5
process_thread_cpu_seconds_total{role="manifest"} 0.5
process_thread_cpu_seconds_total{role="other"} 8.0
scan_phase_seconds_sum{phase="scan.dispatch",table="data"} 4.0
scan_phase_seconds_sum{phase="scan.dispatch",table="index"} 9.0
scan_phase_seconds_sum{phase="scan.combine",table="data"} 1.0
scan_phase_seconds_sum{phase="scan.d2h",table="data"} 2.0
scan_phase_cpu_seconds_total{phase="scan.dispatch",table="data"} 3.0
scan_phase_cpu_seconds_total{phase="scan.dispatch",table="index"} 9.0
scan_phase_cpu_seconds_total{phase="scan.d2h",table="data"} 0.5
respond_encode_seconds_total 10.0
respond_encode_cpu_seconds_total 8.0
"""

# over 1000 queries
WANT = {
    "front_end.loop_busy_pct": 60.0,
    "front_end.loop_cpu_ms_per_query": 20.0,
    "scan.pool_cpu_ms_per_query": 16.0,
    "front_end.host_cores_used": 1.0,
    "scan.dispatch_ms_per_query": 4.0,
    "scan.combine_ms_per_query": 1.0,
    "scan.dispatch_cpu_share": 75.0,
    "cache.d2h_cpu_share": 25.0,
    "front_end.respond_cpu_share": 80.0,
}
# the two that read what the program had before this PR
ON_THE_PARENT = {"scan.dispatch_ms_per_query", "scan.combine_ms_per_query"}
# the fused accumulator finalizes on the device: no scan.combine there
COMBINE = "scan.combine_ms_per_query"
COMBINE_CELLS = ["s100_double_groupby", "s1000_single_groupby",
                 "s1000_double_groupby", "s1000_double_groupby_all"]


def obs_from(text: str) -> dict:
    after: dict = {}
    counters.parse_metrics(text, after)
    return {"spans": {}, "counters": counters.delta({}, after),
            "queries": 1000}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_file_on_a_synthetic_observation(name):
    reader = manifest.load(REPO).reader(name)
    assert reader["source"]["kind"] in ("counter", "ratio")
    value = layers.evaluate(reader, obs_from(METRICS_TEXT))
    assert value == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_file_on_a_program_without_the_accounts(name):
    """The parent: the phase histograms are there, the accounts are
    not.  The two metrics over the histograms read; the rest find
    nothing and raise nothing."""
    parent = "".join(
        line + "\n" for line in METRICS_TEXT.splitlines()
        if line.startswith("scan_phase_seconds")
        or line.startswith("respond_encode_seconds"))
    value = layers.evaluate(manifest.load(REPO).reader(name),
                            obs_from(parent))
    if name in ON_THE_PARENT:
        assert value == pytest.approx(WANT[name])
    else:
        assert value is None


@pytest.mark.parametrize("name", ["scan.dispatch_cpu_share",
                                  "cache.d2h_cpu_share",
                                  "front_end.respond_cpu_share"])
def test_a_share_whose_wall_did_not_move_reads_zero(name):
    still = obs_from(METRICS_TEXT)
    still["counters"] = dict.fromkeys(still["counters"], 0.0)
    assert layers.evaluate(manifest.load(REPO).reader(name), still) == 0.0


def test_manifest_lists_them_with_the_accepted_keys():
    man = manifest.load(REPO)
    by_name = {m["name"]: m for m in man.doc["per_layer"]}
    # appended, in the order of the table: nothing put before them
    assert [m["name"] for m in man.doc["per_layer"]][-len(WANT):] == [
        "front_end.loop_busy_pct", "front_end.loop_cpu_ms_per_query",
        "scan.pool_cpu_ms_per_query", "front_end.host_cores_used",
        "scan.dispatch_ms_per_query", COMBINE,
        "scan.dispatch_cpu_share", "cache.d2h_cpu_share",
        "front_end.respond_cpu_share"]
    for name in WANT:
        keys = {"name", "unit", "better", "source", "layer", "moves"}
        if name == COMBINE:
            keys.add("workloads")
            assert by_name[name]["workloads"] == COMBINE_CELLS
        assert set(by_name[name]) == keys
        assert by_name[name]["source"] == "program_counter"
    for cell in man.workloads:
        listed = {m["name"] for m in man.per_layer(cell)}
        want = set(WANT) - ({COMBINE} if cell not in COMBINE_CELLS
                            else set())
        assert want <= listed, cell


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny cells, with the one listed metric listing the tiny cell
    whose route has the phase (device decode: parts are combined)."""
    path = str(tmp_path_factory.mktemp("manifest"))
    tiny_root(path)
    doc = read_json(f"{path}/BENCHMARK.json")
    for m in doc["per_layer"]:
        if m["name"] == COMBINE:
            m["workloads"] = m["workloads"] + ["tiny_double"]
    write_json(f"{path}/BENCHMARK.json", doc)
    return path


@pytest.mark.parametrize("workload", ["tiny_single_2h", "tiny_double"])
def test_traced_rehearsal_prints_every_new_metric(root, tmp_path, workload):
    proc = run_cli(root, str(tmp_path), "--trace", "1", "--platform", "cpu",
                   workload=workload)
    assert proc.returncode == 0, proc.stderr[-2000:]
    final = json.loads(proc.stdout.strip().splitlines()[-1])
    assert final["correct"] is True
    got = {k: v["value"] for k, v in final["metrics"].items()}
    want = set(WANT) - ({COMBINE} if workload != "tiny_double" else set())
    assert want <= set(got), sorted(want - set(got))
    assert (COMBINE in got) == (workload == "tiny_double")
    # the accounts close wherever they run: the loop's busy share and
    # the encoder's CPU share are shares (the phases' CPU is sampled
    # and scaled: an estimate, which a short run may read over 100)
    for name in ("front_end.loop_busy_pct", "front_end.respond_cpu_share"):
        assert 0.0 <= got[name] <= 100.5, (name, got[name])
    for name in ("scan.dispatch_cpu_share", "cache.d2h_cpu_share"):
        assert got[name] >= 0.0, (name, got[name])
    assert got["front_end.loop_busy_pct"] > 0.0
    assert got["front_end.respond_cpu_share"] > 0.0
    assert got["front_end.host_cores_used"] > 0.1
    assert got["front_end.loop_cpu_ms_per_query"] > 0.0
    assert got["scan.pool_cpu_ms_per_query"] > 0.0
    assert got["scan.dispatch_ms_per_query"] > 0.0
