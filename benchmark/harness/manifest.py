"""BENCHMARK.json and the data files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by name:

    configs/<config>.json        (the manifest's `file` for the config)
    traffic/<traffic>.json
    layer_metrics/<metric>.json

so a later PR adds a cell by adding files and entries and edits nothing
that is there.  `load` checks the names, units and arrows the driver
would refuse, before any process is started.
"""

from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def _need(cond: bool, msg: str) -> None:
    if not cond:
        raise ManifestError(msg)


def _read_json(root: str, rel: str) -> dict:
    path = os.path.join(root, rel)
    _need(os.path.isfile(path), f"{rel}: no such file")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: str):
        self.root = root
        self.doc = _read_json(root, "BENCHMARK.json")
        self.bench_dir = self.doc["paths"][0]
        self.workloads = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self._validate()

    # ---- which metrics a cell reports --------------------------------------

    def end_to_end(self, workload: str) -> list[dict]:
        return [m for m in self.doc["end_to_end"]
                if workload in m.get("workloads", self.workloads)]

    def per_layer(self, workload: str) -> list[dict]:
        reported = {m["name"] for m in self.end_to_end(workload)}
        return [m for m in self.doc["per_layer"]
                if (workload in m["workloads"] if "workloads" in m
                    else m["moves"] in reported)]

    # ---- the files of one cell ----------------------------------------------

    def config(self, workload: str) -> dict:
        entry = self.configs[self.workloads[workload]["config"]]
        return _read_json(self.root, entry["file"])

    def traffic(self, workload: str) -> dict:
        name = self.workloads[workload]["traffic"]
        return _read_json(self.root, f"{self.bench_dir}/traffic/{name}.json")

    def reader(self, metric: str) -> dict:
        return _read_json(
            self.root, f"{self.bench_dir}/layer_metrics/{metric}.json")

    # ---- checks ---------------------------------------------------------------

    def _validate(self) -> None:
        metrics = self.doc["end_to_end"] + self.doc["per_layer"]
        for kind, entries in (("workload", self.doc["workloads"]),
                              ("config", self.doc["configs"]),
                              ("metric", metrics)):
            names = [e["name"] for e in entries]
            for n in names:
                _need(bool(NAME.match(n)), f"{kind} name {n!r}: letters, "
                      f"digits, _ . - only, at most 64")
            _need(len(set(names)) == len(names), f"duplicate {kind} name")
        for m in metrics:
            _need(bool(UNIT.match(m["unit"])),
                  f"{m['name']}: unit {m['unit']!r} has a character the "
                  f"driver refuses")
            _need(m["better"] in ("lower", "higher"),
                  f"{m['name']}: better is lower or higher")
            _need(m["source"] in SOURCES, f"{m['name']}: source")
            for w in m.get("workloads", ()):
                _need(w in self.workloads,
                      f"{m['name']}: unknown workload {w!r}")
        e2e = {m["name"] for m in self.doc["end_to_end"]}
        _need("setup_s" in e2e, "end_to_end lacks setup_s")
        for w in self.doc["workloads"]:
            _need(bool(NAME.match(w["traffic"])), f"traffic {w['traffic']!r}")
            _need(w["config"] in self.configs,
                  f"{w['name']}: unknown config {w['config']!r}")
            _need(w["chips"] in (1, 4), f"{w['name']}: chips is 1 or 4")
            reported = {m["name"] for m in self.end_to_end(w["name"])}
            _need("setup_s" in reported and len(reported) >= 2,
                  f"{w['name']}: reports setup_s and one more metric")
            layer = self.per_layer(w["name"])
            _need(bool(layer), f"{w['name']}: no per-layer metric")
            for m in layer:
                _need(m["moves"] in reported,
                      f"{m['name']}: moves {m['moves']!r}, which "
                      f"{w['name']} does not report")
        for m in self.doc["per_layer"]:
            _need(m["moves"] in e2e,
                  f"{m['name']}: moves {m['moves']!r}, not an "
                  f"end-to-end metric")
            reader = self.reader(m["name"])
            for key in ("layer", "unit", "moves"):
                _need(reader.get(key) == m[key],
                      f"layer_metrics/{m['name']}.json: {key} differs "
                      f"from BENCHMARK.json")
        for c in self.doc["configs"]:
            _need(c["file"].startswith(self.bench_dir + "/"),
                  f"{c['name']}: file lies outside {self.bench_dir}/")


def load(root: str) -> Manifest:
    return Manifest(root)
