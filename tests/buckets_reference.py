"""The plain reference of POST /query_buckets: from the list of
acknowledged writes, in order, the rows the endpoint must answer.

Imports numpy alone (nothing of `horaedb_tpu.ops`, `.storage` or
`.metric_engine`), and shares no step with the program: a dictionary
keyed by (series, field, timestamp) takes the writes in their order, so
the last write wins; a plain loop over its items puts every current
sample of the field within the bounds into a dictionary keyed by its
epoch-aligned bucket, whatever its series; the answer is the `limit`
newest keys of that dictionary, descending.  A bucket's sum is the
exact sum of its float32 values (math.fsum) rounded to float32 once,
its average that sum over its count rounded once."""

import math

import numpy as np


def current_values(writes: list) -> dict:
    """{(series, field, timestamp): float32} after `writes`, a list of
    (series, field, timestamp, value) in the order acknowledged."""
    state = {}
    for series, field, ts, value in writes:
        state[series, field, int(ts)] = np.float32(value)
    return state


def newest_buckets(writes: list, field: str, bucket_ms: int, limit: int,
                   aggs: list, start=None, end=None, series=None) -> list:
    """[(bucket start, count, [float32, one an aggregate asked])],
    descending by bucket start: the `limit` newest buckets that hold a
    current sample of `field` at start <= timestamp < end (a bound that
    is None does not bind); `series`, if given, keeps only those
    series."""
    cells: dict = {}
    for (s, f, ts), value in current_values(writes).items():
        if f != field:
            continue
        if start is not None and ts < start:
            continue
        if end is not None and ts >= end:
            continue
        if series is not None and s not in series:
            continue
        cells.setdefault(ts // bucket_ms * bucket_ms, []).append(value)
    rows = []
    for bucket in sorted(cells, reverse=True)[:limit]:
        vals = cells[bucket]
        total = math.fsum(float(v) for v in vals)
        one = {"max": max(vals), "min": min(vals),
               "sum": np.float32(total),
               "avg": np.float32(total / len(vals))}
        rows.append((bucket, len(vals), [one[a] for a in aggs]))
    return rows
