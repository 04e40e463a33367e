"""Reduction of a jax.profiler trace (.xplane.pb) to device metrics.

`python -m benchmark.harness.xplane <trace dir or .xplane.pb>` prints
one JSON object.  Run as a process of its own after the server has
exited: it imports jax only to read the file (no backend is touched).

- busy: the union of the intervals in which an operation ran on a
  device (the plane's "XLA Ops" line), averaged over the device planes;
- window: first device operation's start to the last one's end (the
  host tracer starts earlier and stops later than the device's, so the
  host planes' extent would add idle time that was never traced);
- device_ops: seconds per operation name, largest first;
- idle_gaps: the longest gaps between busy intervals, each named after
  the most specific host event that covers most of it (the innermost
  frame or TraceMe spanning at least half the gap, frames that only
  wait — select, get, wait, acquire — left out: an idle pool thread
  covers every gap), "unattributed" where the host plane shows none.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import sys

DEVICE_PREFIXES = ("/device:TPU:", "/device:GPU:")
OPS_LINE = "XLA Ops"
TOP = 10
# python-tracer frames (`$file.py:12 name`) that only wait
WAIT_NAMES = frozenset((
    "select", "poll", "get", "wait", "wait_for", "acquire", "sleep",
    "result", "join", "_run_once", "run_forever", "run", "_worker"))


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb*"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load_planes(path: str) -> list[dict]:
    """[{name, lines: [{name, events: [(name, start_ns, dur_ns)]}]}]."""
    from jax.profiler import ProfileData

    path = find_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def short_name(hlo: str) -> str:
    """`%fusion.16 = s32[8200]{0:T(1024)} fusion(...)` -> `fusion.16
    s32[8200]`: the instruction's name and result shape (the program
    gives its kernels no names of their own yet)."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:80]
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{name.lstrip('%')} {shape}"[:80]


def union_intervals(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _ops_line(plane: dict) -> dict | None:
    for line in plane["lines"]:
        if line["name"] == OPS_LINE:
            return line
    return None


def attribute_gap(lo: float, hi: float, host_events) -> str:
    """The shortest host event covering at least half of [lo, hi)."""
    best, best_dur = "unattributed", None
    need = (hi - lo) / 2.0
    for name, start, dur in host_events:
        if name.startswith("$") and name.rsplit(" ", 1)[-1] in WAIT_NAMES:
            continue
        overlap = min(hi, start + dur) - max(lo, start)
        if overlap >= need and (best_dur is None or dur < best_dur):
            best, best_dur = name, dur
    return best


def reduce_planes(planes: list[dict]) -> dict:
    device_planes = [p for p in planes
                     if p["name"].startswith(DEVICE_PREFIXES)
                     and _ops_line(p) is not None
                     and _ops_line(p)["events"]]
    out: dict = {"devices": len(device_planes)}
    if not device_planes:
        return out
    ops = [ev for p in device_planes for ev in _ops_line(p)["events"]]
    t0 = min(s for _n, s, _d in ops)
    t1 = max(s + d for _n, s, d in ops)
    out["window_s"] = (t1 - t0) / 1e9
    host_events = [ev for p in planes
                   if not p["name"].startswith(DEVICE_PREFIXES)
                   for line in p["lines"] for ev in line["events"]]
    busy = 0.0
    by_name: dict = {}
    gaps = []
    for p in device_planes:
        events = _ops_line(p)["events"]
        merged = union_intervals((s, s + d) for _n, s, d in events)
        busy += sum(hi - lo for lo, hi in merged)
        for name, _s, d in events:
            name = short_name(name)
            by_name[name] = by_name.get(name, 0.0) + d
        edges = [t0] + [t for iv in merged for t in iv] + [t1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out["busy_s"] = busy / len(device_planes) / 1e9
    out["op_seconds"] = sum(by_name.values()) / 1e9
    out["op_events"] = sum(len(_ops_line(p)["events"])
                           for p in device_planes)
    out["device_ops"] = [
        [name, ns / 1e9] for name, ns in
        sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]]
    out["idle_gaps"] = [
        [attribute_gap(lo, hi, host_events)[:80], (hi - lo) / 1e9]
        for lo, hi in gaps[:TOP]]
    return out


def reduce_trace(path: str) -> dict:
    return reduce_planes(load_planes(path))


if __name__ == "__main__":
    print(json.dumps(reduce_trace(sys.argv[1])))
