"""Operation `groupby`: TSBS's group-by-time query shapes
(single-groupby-*, double-groupby-*) over POST /query with `bucket_ms`.

A traffic file gives the window, the bucket, how many hosts a query
names (`"hosts": "all"` or a number drawn uniformly), the granularity
of the window's start, and a body template whose "{name}" values are
filled per query.  The seed decides hosts and starts only: every query
of a mix covers the same window length, and starts are clipped so that
no window leaves the data's span.
"""

from __future__ import annotations

import json

import numpy as np

from benchmark.harness.dataset import AGGS, EXACT_AGGS, ROUNDED_AGGS

# the numbers `correct` compares, each with a limit in the traffic file
READINGS = ("malformed_responses", "count_mismatch_cells",
            "select_mismatch_cells", "sum_avg_max_rel_err")


def _no_readings() -> dict:
    return {k: (0.0 if k == "sum_avg_max_rel_err" else 0) for k in READINGS}


def _fill(template, values: dict):
    if isinstance(template, dict):
        return {k: _fill(v, values) for k, v in template.items()}
    if (isinstance(template, str) and template.startswith("{")
            and template.endswith("}") and template[1:-1] in values):
        return values[template[1:-1]]
    return template


def _query(traffic: dict, data, start: int, hosts) -> dict:
    end = start + int(traffic["window_ms"])
    values = {"metric": data.metric, "field": data.field,
              "start": int(start), "end": int(end),
              "bucket_ms": int(traffic["bucket_ms"])}
    if hosts is not None:
        values["host"] = data.host_names[hosts[0]]
    body = json.dumps(_fill(traffic["body"], values)).encode()
    return {"start": int(start), "end": int(end), "hosts": hosts,
            "bucket_ms": values["bucket_ms"], "body": body}


def _start_bounds(traffic: dict, data) -> tuple[int, int]:
    gran = int(traffic["start_granularity_ms"])
    last = data.t0 + data.span_ms - int(traffic["window_ms"])
    if last < data.t0:
        raise ValueError("the traffic's window is longer than the data")
    return gran, (last - data.t0) // gran


def make_queries(traffic: dict, data, rng: np.random.Generator,
                 n: int) -> list[dict]:
    gran, steps = _start_bounds(traffic, data)
    starts = data.t0 + rng.integers(0, steps + 1, size=n) * gran
    if traffic["hosts"] == "all":
        return [_query(traffic, data, s, None) for s in starts]
    if traffic["hosts"] != 1:
        raise ValueError("groupby: hosts is \"all\" or 1")
    hosts = rng.integers(0, data.hosts, size=n)
    return [_query(traffic, data, s, [int(h)])
            for s, h in zip(starts, hosts)]


def sweep_queries(traffic: dict, data) -> list[dict]:
    """Warm-up pass: windows at a fixed stride (from `sweep_offset_ms`)
    across the whole span, the last one clipped to its end: every
    segment touched, off the bucket grid by one granule so that the
    sweep compiles the shapes the traffic uses.  A mix that names one
    host sends each window for every host in turn: the scan cache
    holds one host's window of a segment at a time, and in a server
    that has been up for a while every one of them is resident."""
    gran, steps = _start_bounds(traffic, data)
    warm = traffic["warmup"]
    stride = int(warm["sweep_stride_ms"])
    offset = int(warm.get("sweep_offset_ms", 0))
    at = []
    while not at or at[-1] < steps:
        at.append(min(steps, (offset + len(at) * stride) // gran
                      + (1 if steps else 0)))
    hosts = [None] if traffic["hosts"] == "all" \
        else [[h] for h in range(data.hosts)]
    return [_query(traffic, data, data.t0 + step * gran, h)
            for h in hosts for step in at]


def check(query: dict, payload: bytes, data, values=None) -> dict:
    """One response against the reference (or, with `values`, against
    the control's value grid).  Counts exact; min/max/last exact; sums
    and averages by their largest relative error."""
    out = _no_readings()
    try:
        got = json.loads(payload)
        order = [data.host_of_tsid[t] for t in got["tsids"]]
        grids = {a: np.array(got["aggs"][a], dtype=np.float64)
                 for a in AGGS}
    except (ValueError, KeyError, TypeError):
        out["malformed_responses"] = 1
        return out
    want_hosts = (list(range(data.hosts)) if query["hosts"] is None
                  else list(query["hosts"]))
    ref = data.groupby(query["start"], query["end"], query["bucket_ms"],
                       hosts=order, values=values)
    if (sorted(order) != want_hosts
            or any(grids[a].shape != ref[a].shape for a in AGGS)):
        out["malformed_responses"] = 1
        return out
    occupied = ref["count"] > 0
    out["count_mismatch_cells"] = int(
        (grids["count"] != ref["count"]).sum())
    for a in EXACT_AGGS:
        out["select_mismatch_cells"] += int(
            (grids[a][occupied] != ref[a][occupied]).sum())
    for a in ROUNDED_AGGS:
        g, r = grids[a][occupied], ref[a][occupied]
        if g.size:
            with np.errstate(invalid="ignore"):
                err = np.abs(g - r) / np.maximum(np.abs(r), 1e-30)
            err = np.where(np.isfinite(g), err, np.inf)
            out["sum_avg_max_rel_err"] = max(
                out["sum_avg_max_rel_err"], float(err.max()))
    return out


def combine(readings: list[dict]) -> dict:
    """Fold per-response readings: counts add, the gap is the widest."""
    total = _no_readings()
    for r in readings:
        for k in READINGS:
            if k == "sum_avg_max_rel_err":
                total[k] = max(total[k], r[k])
            else:
                total[k] += r[k]
    return total
