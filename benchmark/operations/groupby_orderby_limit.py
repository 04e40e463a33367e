"""Operation `groupby_orderby_limit`: TSBS's groupby-orderby-limit over
POST /query_buckets: max of the first cpu field over ALL hosts by
epoch-aligned minute, the `limit` newest minutes that hold a sample
before a random `end`, with NO lower time bound.

The seed decides the ends only: `end` is uniform over [t0 +
`end_min_offset_ms`, t0 + span] at `end_granularity_ms` (TSBS: the end
of MustRandWindow(1 h)), drawn by `groupby._starts`' strata, one
`window_ms` long each, so that every consecutive block of the stream
holds one end from every five minutes of the range, in a seeded order:
an end within `limit` - 1 buckets after a segment boundary needs the
older segment for its last bucket, and no seed moves the share of such
requests.  The sweep is one request a `sweep_stride_ms` from
`sweep_offset_ms` on (an end an hour and a millisecond into every 2 h
segment): each reads, narrows and uploads its segment's slice, and the
first compiles the program.  The response is an Arrow IPC stream:
`bucket` (int64, the bucket's start), `count` (int64) and one float32
column an aggregate asked, descending by bucket.

`check` is the benchmark's own reference, from the data set's values
and nothing of the program: every host reports at every tick, so the
buckets are the `limit` newest minutes that begin before `end` (the
first tick of a minute lies on its start), each with hosts x its ticks
before `end` as its count and the float32 max over them (every cell was
written once, so last-write-wins has nothing to decide here, and no
minute is empty; the program's tests overwrite, leave gaps and walk
further).  It reads

  malformed_responses       the stream does not parse, a column is
                            missing, extra, out of order or of another
                            type, `bucket` or `count` holds a null, the
                            buckets are not strictly descending, a
                            bucket is not on the bucket grid;
  bucket_set_mismatch_rows  buckets the reference has and the answer
                            lacks + buckets the answer has and the
                            reference lacks;
  count_mismatch_cells      over the buckets both have: a count that is
                            not the reference's;
  value_mismatch_cells      over the buckets both have: a max not
                            bit-equal to the reference's, or a null.

Under the control (`values` = the field rounded to bfloat16) the
buckets and counts stay (they do not depend on the values) and every
max is compared with the max of the rounded values.
"""

from __future__ import annotations

import json

import numpy as np
import pyarrow as pa
from pyarrow import ipc

from benchmark.harness.dataset import round_bf16
from benchmark.operations import groupby, select_where

# the numbers `correct` compares, each with a limit in the traffic file
READINGS = ("malformed_responses", "bucket_set_mismatch_rows",
            "count_mismatch_cells", "value_mismatch_cells")


def _no_readings() -> dict:
    return dict.fromkeys(READINGS, 0)


def _malformed() -> dict:
    return dict(_no_readings(), malformed_responses=1)


def _query(traffic: dict, data, end: int) -> dict:
    if traffic["hosts"] != "all" or traffic["aggregate"] != "max":
        raise ValueError("groupby_orderby_limit: hosts are \"all\" and "
                         "the aggregate is max")
    asked = {"end": int(end), "bucket_ms": int(traffic["bucket_ms"]),
             "limit": int(traffic["limit"])}
    body = json.dumps(groupby._fill(
        traffic["body"], {"metric": data.metric, "field": data.field,
                          **asked})).encode()
    return dict(asked, hosts=None, body=body)


def make_queries(traffic: dict, data, rng: np.random.Generator,
                 n: int) -> list[dict]:
    # a program without the endpoint ends here, rc 1, with the server
    # stopped (select_where.require_endpoint says why here)
    select_where.require_endpoint(traffic["endpoint"])
    gran = int(traffic["end_granularity_ms"])
    first = data.t0 + int(traffic["end_min_offset_ms"])
    steps = (data.t0 + data.span_ms - first) // gran
    if steps < 0:
        raise ValueError("the traffic's first end lies after the data")
    ends = first + groupby._starts(
        steps, max(1, int(traffic["window_ms"]) // gran), rng, n) * gran
    return [_query(traffic, data, e) for e in ends]


def sweep_queries(traffic: dict, data) -> list[dict]:
    warm = traffic["warmup"]
    ends = range(data.t0 + int(warm["sweep_offset_ms"]),
                 data.t0 + data.span_ms + 1, int(warm["sweep_stride_ms"]))
    return [_query(traffic, data, e) for e in ends]


def control_values(data) -> np.ndarray:
    """What `check` takes as `values` under the control: the queried
    field's values rounded to bfloat16.  Made once a run, before the
    check's workers are forked."""
    return round_bf16(data.grid)


def reference(query: dict, data, values=None) -> list:
    """[(bucket start, count, float32 max)], descending: the `limit`
    newest buckets that hold a tick before the query's `end`."""
    grid = data.grid if values is None else values
    b, end = query["bucket_ms"], query["end"]
    rows = []
    bucket = (end - 1) // b * b
    while len(rows) < query["limit"] and bucket + b > data.t0:
        lo, hi = data.tick_range(bucket, min(bucket + b, end))
        if hi > lo:
            rows.append((bucket, (hi - lo) * data.hosts,
                         grid[lo:hi].max()))
        bucket -= b
    return rows


def check(query: dict, payload: bytes, data, values=None) -> dict:
    """One response against the reference (or, with `values`, against
    the control's value grid)."""
    try:
        got = ipc.open_stream(payload).read_all()
        if (got.schema.names != ["bucket", "count", "max"]
                or got.schema.field("bucket").type != pa.int64()
                or got.schema.field("count").type != pa.int64()
                or got.schema.field("max").type != pa.float32()
                or got.column("bucket").null_count
                or got.column("count").null_count):
            return _malformed()
        bucket = got.column("bucket").to_numpy()
        count = got.column("count").to_numpy()
        col = got.column("max").combine_chunks()
        nulls = np.asarray(col.is_null())
        bits = np.asarray(col.fill_null(0), dtype=np.float32) \
            .view(np.uint32)
    except (pa.ArrowInvalid, OSError, ValueError, TypeError):
        return _malformed()
    if (bucket[1:] >= bucket[:-1]).any() \
            or (bucket % query["bucket_ms"]).any():
        return _malformed()       # out of order, twice, or off the grid
    out = _no_readings()
    want = {b: (n, np.float32(v).view(np.uint32))
            for b, n, v in reference(query, data, values)}
    both = [i for i, b in enumerate(bucket.tolist()) if b in want]
    out["bucket_set_mismatch_rows"] = \
        len(want) - len(both) + len(bucket) - len(both)
    for i in both:
        n, v = want[int(bucket[i])]
        out["count_mismatch_cells"] += int(count[i] != n)
        out["value_mismatch_cells"] += int(nulls[i] or bits[i] != v)
    return out


def combine(readings: list[dict]) -> dict:
    """Fold per-response readings: every number is a count."""
    total = _no_readings()
    for r in readings:
        for k in READINGS:
            total[k] += r[k]
    return total
