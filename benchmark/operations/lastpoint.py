"""Operation `lastpoint`: TSBS's lastpoint over POST /query_last: the
last row of every host, with no time bound and no parameter, all the
fields of the row.

Every request of the mix is the same request (the seed decides the
data, not the question): the traffic's body template carries
`"fields": "{fields}"`, filled here with the configuration's field list
in its order, and nothing else but the metric.  The sweep is the same
request `warmup.sweep_queries` times: the first reads, narrows and
uploads what the walk asks (the newest segment's slice a field) and
compiles the program; the second finds it resident.  The response is an
Arrow IPC stream: `tsid` (uint64), `timestamp` (int64) and one float32
column a field asked, ascending by tsid, one row a series.

`check` is the benchmark's own reference, from the data set's values
and nothing of the program: every host reports at every tick, so a
host's last row stands at the data's last tick and holds the ten
float32 written there (every cell was written once, so last-write-wins
has nothing to decide here, and no host is quiet; the program's tests
overwrite, silence hosts and walk further).  It reads

  malformed_responses     the stream does not parse, a column is
                          missing, extra, out of order or of another
                          type, a series is unknown or there twice, the
                          rows are not strictly ascending by tsid;
  row_set_mismatch_rows   hosts the answer lacks + rows whose timestamp
                          is not the host's newest tick;
  value_mismatch_cells    over the rows at their host's newest tick, a
                          field at a time: a value not bit-equal to the
                          float32 written, or a null (every field was
                          written at every tick).

Under the control (`values` = every field rounded to bfloat16) the rows
stay (the row set does not depend on the values) and every value is
compared with its rounded twin.
"""

from __future__ import annotations

import json

import numpy as np
import pyarrow as pa
from pyarrow import ipc

from benchmark.harness.dataset import round_bf16
from benchmark.operations import groupby, select_where

# the numbers `correct` compares, each with a limit in the traffic file
READINGS = ("malformed_responses", "row_set_mismatch_rows",
            "value_mismatch_cells")


def _no_readings() -> dict:
    return dict.fromkeys(READINGS, 0)


def _malformed() -> dict:
    return dict(_no_readings(), malformed_responses=1)


def _query(traffic: dict, data) -> dict:
    if traffic["fields"] != "all" or traffic["hosts"] != "all":
        raise ValueError("lastpoint: fields and hosts are \"all\"")
    body = json.dumps(groupby._fill(
        traffic["body"], {"metric": data.metric,
                          "fields": list(data.fields)})).encode()
    return {"fields": list(range(len(data.fields))), "hosts": None,
            "body": body}


def make_queries(traffic: dict, data, rng, n: int) -> list[dict]:
    # a program without the endpoint ends here, rc 1, with the server
    # stopped (select_where.require_endpoint says why here)
    select_where.require_endpoint(traffic["endpoint"])
    return [_query(traffic, data)] * n


def sweep_queries(traffic: dict, data) -> list[dict]:
    return [_query(traffic, data)] * int(traffic["warmup"]["sweep_queries"])


def control_values(data) -> np.ndarray:
    """What `check` takes as `values` under the control: every field's
    values rounded to bfloat16.  Made once a run, before the check's
    workers are forked."""
    return round_bf16(data.values)


def reference(data, values=None) -> tuple:
    """(the newest timestamp, the (fields, hosts) float32 written at
    it): every host's last row."""
    grids = data.values if values is None else values
    return (data.t0 + (data.ticks - 1) * data.interval_ms,
            grids[:, data.ticks - 1, :])


def check(query: dict, payload: bytes, data, values=None) -> dict:
    """One response against the reference (or, with `values`, against
    the control's value grids)."""
    names = [data.fields[f] for f in query["fields"]]
    try:
        got = ipc.open_stream(payload).read_all()
        if (got.schema.names != ["tsid", "timestamp"] + names
                or got.schema.field("tsid").type != pa.uint64()
                or got.schema.field("timestamp").type != pa.int64()
                or any(got.schema.field(n).type != pa.float32()
                       for n in names)
                or got.column("tsid").null_count
                or got.column("timestamp").null_count):
            return _malformed()
        tsid = got.column("tsid").to_numpy()
        ts = got.column("timestamp").to_numpy()
        host = np.array([data.host_of_tsid[str(t)] for t in tsid],
                        dtype=np.int64)
    except (pa.ArrowInvalid, OSError, KeyError, ValueError, TypeError):
        return _malformed()
    if (tsid[1:] <= tsid[:-1]).any():
        return _malformed()       # a series twice, or out of order
    out = _no_readings()
    newest, written = reference(data, values)
    at = ts == newest
    out["row_set_mismatch_rows"] = int(
        data.hosts - len(host) + (~at).sum())
    for name, f in zip(names, query["fields"]):
        col = got.column(name).combine_chunks()
        nulls = np.asarray(col.is_null())[at]
        bits = np.asarray(col.fill_null(0), dtype=np.float32)[at] \
            .view(np.uint32)
        want = np.ascontiguousarray(written[f][host[at]]).view(np.uint32)
        out["value_mismatch_cells"] += int((nulls | (bits != want)).sum())
    return out


def combine(readings: list[dict]) -> dict:
    """Fold per-response readings: every number is a count."""
    total = _no_readings()
    for r in readings:
        for k in READINGS:
            total[k] += r[k]
    return total
