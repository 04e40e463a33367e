"""A temporary manifest root: the committed BENCHMARK.json and data
files copied, plus a tiny configuration, its cells and one more
per-layer metric ADDED as new files and new entries — no committed
file is edited, which is how a later PR brings its own cell."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def write_json(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f, indent=1)


def read_json(path: str):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def tiny_root(dst: str) -> dict:
    """Returns the manifest document written to `dst`/BENCHMARK.json."""
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "benchmark", sub),
                        os.path.join(dst, "benchmark", sub))
    doc = read_json(os.path.join(REPO, "BENCHMARK.json"))

    # a configuration: its own file of sizes
    cfg = read_json(os.path.join(
        REPO, "benchmark", "configs", "tsbs-devops-cpu-s100.json"))
    cfg.update(name="tiny", scale=10, span_ms=86_400_000)
    cfg["ingest"] = dict(cfg["ingest"], body_rows=30_000)
    write_json(os.path.join(dst, "benchmark/configs/tiny.json"), cfg)
    doc["configs"].append({
        "name": "tiny", "source": "benchmark/tests", "reduced": [],
        "file": "benchmark/configs/tiny.json", "why": "CPU rehearsal"})

    # a traffic mix: a data file the general generator reads
    mix = read_json(os.path.join(
        REPO, "benchmark", "traffic", "single-groupby-1-1-1.json"))
    mix.update(name="single-groupby-1-1-2h", window_ms=7_200_000,
               bucket_ms=600_000)
    write_json(os.path.join(
        dst, "benchmark/traffic/single-groupby-1-1-2h.json"), mix)

    # a per-layer metric: a small declarative reader of its own
    reader = {"name": "cache.d2h_MB_per_query", "layer": "caches",
              "unit": "MB/query", "moves": "query_p50_ms",
              "source": {"kind": "counter", "per": "query", "scale": 1e-6,
                         "counters": ["device.transfer.d2h.bytes"]}}
    write_json(os.path.join(
        dst, "benchmark/layer_metrics/cache.d2h_MB_per_query.json"), reader)
    doc["per_layer"].append({
        "name": reader["name"], "unit": reader["unit"], "better": "lower",
        "source": "program_counter", "layer": reader["layer"],
        "moves": reader["moves"]})

    cells = {"tiny_double": "double-groupby-1",
             "tiny_single": "single-groupby-1-1-1",
             "tiny_single_2h": "single-groupby-1-1-2h"}
    for name, traffic in cells.items():
        doc["workloads"].append({"name": name, "config": "tiny",
                                 "traffic": traffic, "chips": 1,
                                 "why": "CPU rehearsal"})
    for m in doc["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + list(cells)
    write_json(os.path.join(dst, "BENCHMARK.json"), doc)
    return doc
