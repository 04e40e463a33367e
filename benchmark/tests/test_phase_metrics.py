"""The per-layer metrics that read the program's phase spans and wait
counters (PR 25): each reader file on a synthetic observation that
names the samples as `/metrics` renders them, on one that lacks them
(the parent commit: nothing to read, nothing raised), in the manifest,
and in the line of a traced rehearsal of each route."""

import json

import pytest

from benchmark.harness import counters, layers, manifest
from benchmark.tests.helpers import REPO
from benchmark.tests.test_rehearsal import root, run_cli  # noqa: F401

METRICS_TEXT = """\
# TYPE scan_phase_seconds histogram
scan_phase_seconds_bucket{phase="scan.plan",table="data",le="0.5"} 40
scan_phase_seconds_sum{phase="scan.plan",table="data"} 0.02
scan_phase_seconds_count{phase="scan.plan",table="data"} 40
scan_phase_seconds_sum{phase="scan.plan",table="index"} 9.0
scan_phase_seconds_sum{phase="scan.windows",table="data"} 0.03
scan_phase_seconds_sum{phase="scan.windows",table="index"} 9.0
scan_phase_seconds_sum{phase="scan.group_prep",table="data"} 0.04
scan_phase_seconds_sum{phase="scan.device_wait",table="data"} 0.05
runtime_pool_wait_seconds_sum{pool="sst"} 0.06
runtime_pool_wait_seconds_sum{pool="compact"} 9.0
runtime_pool_resume_seconds_sum{pool="sst"} 0.01
device_transfer_seconds_total{direction="d2h"} 0.08
device_transfer_seconds_total{direction="h2d"} 9.0
event_loop_stall_seconds_total 0.3
process_gc_pause_seconds_total{generation="0"} 0.001
process_gc_pause_seconds_total{generation="1"} 0.002
process_gc_pause_seconds_total{generation="2"} 0.1
"""

WANT = {
    "front_end.respond_ms": 2.0,
    "front_end.loop_stall_ms": 300.0,
    "front_end.gc_pause_ms": 103.0,
    "route.plan_ms_per_query": 1.0,
    "cache.windows_ms_per_query": 1.5,
    "cache.d2h_ms_per_query": 4.0,
    "scan.host_prep_ms_per_query": 2.0,
    "scan.pool_wait_ms_per_query": 3.5,
    "device.wait_ms_per_query": 2.5,
}


def obs_from(text: str) -> dict:
    after: dict = {}
    counters.parse_metrics(text, after)
    return {"spans": {"total": [9.0, 10.0, 11.0],
                      "respond": [1.0, 2.0, 3.0]},
            "counters": counters.delta({}, after), "queries": 20}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_file_on_a_synthetic_observation(name):
    man = manifest.load(REPO)
    value = layers.evaluate(man.reader(name), obs_from(METRICS_TEXT))
    assert value == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_file_finds_nothing_on_a_program_without_the_source(name):
    man = manifest.load(REPO)
    parent = {"spans": {"total": [9.0], "downsample": [7.0]},
              "counters": {"device.calls": 4.0}, "queries": 20}
    assert layers.evaluate(man.reader(name), parent) is None


def test_manifest_lists_them_in_both_cells_with_the_accepted_keys():
    man = manifest.load(REPO)
    for cell in ("s100_double_groupby", "s100_single_groupby"):
        listed = {m["name"]: m for m in man.per_layer(cell)}
        assert set(WANT) <= set(listed)
        for name in WANT:
            assert set(listed[name]) == {"name", "unit", "better",
                                         "source", "layer", "moves"}
            assert listed[name]["better"] == "lower"


@pytest.mark.parametrize("workload", ["tiny_single_2h", "tiny_double"])
def test_traced_rehearsal_prints_every_new_metric(root, tmp_path,  # noqa: F811
                                                  workload):
    proc = run_cli(root, str(tmp_path), "--trace", "1", "--platform", "cpu",
                   workload=workload)
    assert proc.returncode == 0, proc.stderr[-2000:]
    final = json.loads(proc.stdout.strip().splitlines()[-1])
    assert final["correct"] is True
    assert set(WANT) <= set(final["metrics"]), final["metrics"]
    for name in WANT:
        assert final["metrics"][name]["value"] >= 0.0
