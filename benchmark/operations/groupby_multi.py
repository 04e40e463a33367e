"""Operation `groupby_multi`: TSBS's group-by-time shapes over ALL the
fields of the row (double-groupby-all; cpu-max-all-* with `"hosts": 1`)
in one POST /query_multi.

`groupby`'s query stream, sweep, readings and limits, unchanged: the
traffic's body template carries `"fields": "{fields}"`, filled here
with the configuration's field list in its order, and the rest of a
query (window, start, host) is `groupby`'s.  The response maps each
field to the `/query` downsample shape.  `check` holds every grid of
every field to the reference computed from that field's own values; a
missing or an extra field, like a wrong series or shape in any field,
makes the one response malformed.
"""

from __future__ import annotations

import json

import numpy as np

from benchmark.harness.dataset import (AGGS, EXACT_AGGS, ROUNDED_AGGS,
                                       round_bf16)
from benchmark.operations import groupby
from benchmark.operations.groupby import READINGS, combine  # noqa: F401


def _with_fields(traffic: dict, data) -> dict:
    return dict(traffic, body=groupby._fill(
        traffic["body"], {"fields": list(data.fields)}))


def make_queries(traffic: dict, data, rng, n: int) -> list[dict]:
    return groupby.make_queries(_with_fields(traffic, data), data, rng, n)


def sweep_queries(traffic: dict, data) -> list[dict]:
    return groupby.sweep_queries(_with_fields(traffic, data), data)


def _control_values(data) -> np.ndarray:
    """Every field's values rounded to bfloat16: once a run, kept on
    the data set, not once a response."""
    if getattr(data, "values_bf16", None) is None:
        data.values_bf16 = round_bf16(data.values)
    return data.values_bf16


def check(query: dict, payload: bytes, data, values=None) -> dict:
    """One response against the reference, field by field: counts
    exact; min/max/last exact; sums and averages by their largest
    relative error, over all fields.  `values` (run.py hands the
    control's grid of the first field) only says that the control is
    asked for: each field is then compared with its OWN values rounded
    to bfloat16."""
    out = groupby._no_readings()
    grids_of = data.values if values is None else _control_values(data)
    want_hosts = (list(range(data.hosts)) if query["hosts"] is None
                  else list(query["hosts"]))
    try:
        got = json.loads(payload)
        if set(got) != set(data.fields):
            raise KeyError("fields")
        for f, field in enumerate(data.fields):
            order = [data.host_of_tsid[t] for t in got[field]["tsids"]]
            grids = {a: np.array(got[field]["aggs"][a], dtype=np.float64)
                     for a in AGGS}
            ref = data.groupby(query["start"], query["end"],
                               query["bucket_ms"], hosts=order,
                               values=grids_of[f])
            if (sorted(order) != want_hosts
                    or any(grids[a].shape != ref[a].shape for a in AGGS)):
                raise ValueError("series or shape")
            occupied = ref["count"] > 0
            out["count_mismatch_cells"] += int(
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
    except (ValueError, KeyError, TypeError):
        return dict(groupby._no_readings(), malformed_responses=1)
    return out
