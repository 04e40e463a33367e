"""One whole run at a tiny scale on the CPU backend (`--platform cpu`,
the explicit rehearsal switch): a well-formed final line; the control
(reference from bfloat16-rounded values) and a broken timed path both
come out `correct: false`; and without the switch a machine with no TPU
gets no result at all."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import run as bench_run
from benchmark.tests.helpers import REPO, tiny_root

ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def run_cli(root, out, *extra, workload="tiny_single"):
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", workload,
         "--seed", str(2**31 + 12345), "--seconds", "3", "--root", root,
         "--out", out, *extra],
        cwd=REPO, env=ENV, capture_output=True, text=True, timeout=300)
    return proc


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tmp_path_factory.mktemp("manifest")
    tiny_root(str(path))
    return str(path)


def test_no_tpu_and_no_flag_is_a_failure_not_a_fallback(root, tmp_path):
    proc = run_cli(root, str(tmp_path), "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""          # no result line
    assert "platform" in proc.stderr or "chips" in proc.stderr


def test_benchmark_alone_without_the_program_gives_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the files under
    `paths`: another exit code than 0, and no result."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "s100_single_groupby", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--platform", "cpu"],
        cwd=tmp_path, env=ENV, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "nothing to measure" in proc.stderr


def test_rehearsal_prints_a_well_formed_final_line(root, tmp_path):
    proc = run_cli(root, str(tmp_path), "--trace", "0", "--platform", "cpu",
                   "--control", "bf16", workload="tiny_double")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    final = json.loads(lines[-1])
    assert set(final) == {"correct", "attempted", "failed", "metrics",
                          "device"}
    assert set(final["metrics"]) == {"query_p50_ms", "query_p95_ms",
                                     "queries_per_s", "setup_s"}
    for name, m in final["metrics"].items():
        assert m["value"] > 0 and m["unit"], name
    assert final["attempted"] > 8 and final["failed"] == 0
    assert final["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(final["device"])
    text = proc.stdout
    assert text.count("\ncheck ") >= 4 and "setup {" in text
    assert "quarters [" in text
    # every sound reading is inside its limit; the control is not, and
    # with --control the final line reports the control
    assert " OVER" not in "".join(
        ln for ln in lines if ln.startswith("check "))
    assert "control[bf16] select_mismatch_cells" in text
    assert "(sound reading: correct = True)" in text
    assert final["correct"] is False


def test_traced_rehearsal_reports_layer_metrics(root, tmp_path):
    proc = run_cli(root, str(tmp_path), "--trace", "1", "--platform", "cpu",
                   workload="tiny_single_2h")
    assert proc.returncode == 0, proc.stderr[-2000:]
    final = json.loads(proc.stdout.strip().splitlines()[-1])
    assert final["correct"] is True
    got = set(final["metrics"])
    # spans and counters read on any backend; the device trace has no
    # device plane on the CPU, so those readers return nothing — and
    # the metric added by the test's own file is among the ones read
    assert {"scan.downsample_ms", "engine.resolve_ms",
            "front_end.encode_ms", "route.dispatches_per_query",
            "cache.d2h_MB_per_query",
            "device.compiles_in_window"} <= got
    assert "kernel.scan_roofline" not in got
    assert "route {" in proc.stdout


def test_broken_timed_path_is_not_correct(root, tmp_path):
    """Skips the look for a chip, drives the rest of a run over a
    server whose answers are altered where they are produced."""
    cell = bench_run.Cell(root, "tiny_single", seed=7, seconds=2.0,
                          trace=False, platform="cpu",
                          out_dir=str(tmp_path),
                          launcher="benchmark.tests.broken_launcher")
    result = cell.run()
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] > 0
