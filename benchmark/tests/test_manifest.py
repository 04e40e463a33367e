"""The loader: what the driver would refuse is refused here first, the
committed manifest passes, and a configuration, a traffic mix and a
per-layer metric are each added as new files plus entries."""

import copy
import os

import pytest

from benchmark.harness import manifest
from benchmark.tests.helpers import (REPO, read_json, tiny_root,
                                     write_json)


def test_committed_manifest_meets_the_contract():
    man = manifest.load(REPO)
    doc = man.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= doc["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    for w in doc["workloads"]:
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in doc["configs"]:
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert read_json(os.path.join(REPO, c["file"]))["name"] == c["name"]
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
    used = {w["config"] for w in doc["workloads"]}
    assert used == {c["name"] for c in doc["configs"]}


def test_new_cell_needs_only_new_files_and_entries(tmp_path):
    before = {}
    for sub in ("configs", "traffic", "layer_metrics"):
        d = os.path.join(REPO, "benchmark", sub)
        before.update({os.path.join(d, f): open(os.path.join(d, f)).read()
                       for f in os.listdir(d)})
    tiny_root(str(tmp_path))
    man = manifest.load(str(tmp_path))
    assert man.config("tiny_single_2h")["scale"] == 10
    assert man.traffic("tiny_single_2h")["window_ms"] == 7_200_000
    names = [m["name"] for m in man.per_layer("tiny_single_2h")]
    assert "cache.d2h_MB_per_query" in names
    assert man.reader("cache.d2h_MB_per_query")["source"]["kind"] == "counter"
    # the committed files were copied, never edited
    for path, text in before.items():
        rel = os.path.relpath(path, REPO)
        assert open(os.path.join(tmp_path, rel)).read() == text
    # and the committed cells still resolve in the grown manifest
    assert man.traffic("s100_double_groupby")["name"] == "double-groupby-1"


def _root_with(tmp_path, edit):
    doc = tiny_root(str(tmp_path))
    doc = copy.deepcopy(doc)
    edit(doc)
    write_json(os.path.join(tmp_path, "BENCHMARK.json"), doc)
    return str(tmp_path)


@pytest.mark.parametrize("edit,needle", [
    (lambda d: d["workloads"][0].update(name="s100 double"), "name"),
    (lambda d: d["workloads"][0].update(name="s100/double"), "name"),
    (lambda d: d["end_to_end"][0].update(unit="queries per s"), "unit"),
    (lambda d: d["per_layer"][0].update(unit="µs"), "unit"),
    (lambda d: d["per_layer"][0].update(moves="tokens_per_s"), "moves"),
    # an arrow into a metric that one of the reader's cells does not
    # report is refused
    (lambda d: (d["end_to_end"][1].update(workloads=["s100_double_groupby"]),
                d["per_layer"][0].update(moves="query_p95_ms",
                                         workloads=["s100_single_groupby"])),
     "does not report"),
    (lambda d: d["workloads"][0].update(config="absent"), "config"),
    (lambda d: d["workloads"][0].update(chips=2), "chips"),
    (lambda d: d["end_to_end"].pop(), "setup_s"),
    (lambda d: d["per_layer"][0].update(better="faster"), "better"),
])
def test_loader_refuses_what_the_driver_would(tmp_path, edit, needle):
    with pytest.raises(manifest.ManifestError, match=needle):
        manifest.load(_root_with(tmp_path, edit))


def test_reader_file_must_agree_with_its_entry(tmp_path):
    root = _root_with(tmp_path, lambda d: None)
    path = os.path.join(root, "benchmark/layer_metrics/scan.downsample_ms.json")
    write_json(path, dict(read_json(path), unit="s"))
    with pytest.raises(manifest.ManifestError, match="unit differs"):
        manifest.load(root)
