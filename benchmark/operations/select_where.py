"""Operation `select_where`: TSBS's threshold query shapes (high-cpu-all;
high-cpu-1 with `"hosts": 1`) over POST /query_rows: every reading of
one field in a window whose value passes a predicate, answered as ROWS
with the other fields of the row at the same series and timestamp.

`groupby`'s query stream and sweep, unchanged (the same windows, the
same stratified starts): the traffic's body template carries
`"where": "{where}"` and `"fields": "{fields}"`, filled here with the
traffic's predicate and the configuration's field list in its order.
The response is an Arrow IPC stream: `tsid` (uint64), `timestamp`
(int64) and one float32 column a field asked, sorted by (tsid,
timestamp), a null where a field has no sample at a selected key.

`check` is the benchmark's own reference, from the data set's values
and nothing of the program: the rows are the (tick, host) cells of the
window whose predicate field's float32 passes the threshold rounded to
float32 (every cell was written once, so last-write-wins has nothing
to decide here; the program's tests overwrite).  It reads

  malformed_responses     the stream does not parse, a column is
                          missing, extra, out of order or of another
                          type, a series is unknown, the rows are not
                          strictly ascending by (tsid, timestamp);
  row_set_mismatch_rows   rows the reference has and the answer lacks
                          + rows the answer has and the reference
                          lacks (a timestamp off the ticks or outside
                          the window among them);
  value_mismatch_cells    over the rows both have, a field at a time:
                          a value not bit-equal to the float32
                          written, or a null (every field was written
                          at every tick).

Under the control (`values` = every field rounded to bfloat16) the
reference selects by the rounded predicate field, which moves rows
across the threshold, and compares every value with its rounded twin.
"""

from __future__ import annotations

import glob
import os

import numpy as np
import pyarrow as pa
from pyarrow import ipc

from benchmark.harness.dataset import round_bf16
from benchmark.harness.server import BenchError
from benchmark.operations import groupby

# the checkout this file lies in: the program is beside the benchmark
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the numbers `correct` compares, each with a limit in the traffic file
READINGS = ("malformed_responses", "row_set_mismatch_rows",
            "value_mismatch_cells")

_COMPARE = {"gt": np.greater, "ge": np.greater_equal,
            "lt": np.less, "le": np.less_equal}


def _no_readings() -> dict:
    return dict.fromkeys(READINGS, 0)


def _malformed() -> dict:
    return dict(_no_readings(), malformed_responses=1)


def _fields(traffic: dict, data) -> list:
    return list(data.fields) if traffic["fields"] == "all" \
        else list(traffic["fields"])


def _filled(traffic: dict, data) -> dict:
    where = traffic["where"]
    if where["op"] not in _COMPARE or where["field"] not in data.fields:
        raise ValueError(f"select_where: where is {where!r}")
    return dict(traffic, body=groupby._fill(
        traffic["body"], {"fields": _fields(traffic, data),
                          "where": dict(where)}))


def _asked(traffic: dict, data, queries: list) -> list:
    """Each query with what `check` needs of the traffic beside it."""
    where = traffic["where"]
    extra = {"where_field": data.fields.index(where["field"]),
             "op": where["op"], "threshold": float(where["value"]),
             "fields": [data.fields.index(f)
                        for f in _fields(traffic, data)]}
    return [dict(q, **extra) for q in queries]


def require_endpoint(endpoint: str, root: str = _ROOT) -> None:
    """A program whose server routes no `endpoint` has nothing to
    measure in this cell, and says so HERE, while the harness has a
    server and no load generator yet.  The sweep's first request would
    fail too (404), but run.py unwinds an error between the
    generator's making and its start by stopping threads that never
    ran, which raises past the server's stop and leaves the child
    holding the chip (PR 41's parent did: every later run of that call
    found the TPU in use).  Read off the server's sources as text
    (nothing of the program is imported); a checkout without that
    directory is not judged."""
    sources = glob.glob(os.path.join(root, "horaedb_tpu", "server", "*.py"))
    if sources and not any(f'"{endpoint}"' in open(path).read()
                           for path in sources):
        raise BenchError(f"the program beside the benchmark routes no "
                         f"{endpoint}: this cell cannot run on it")


def make_queries(traffic: dict, data, rng, n: int) -> list[dict]:
    require_endpoint(traffic["endpoint"])
    return _asked(traffic, data, groupby.make_queries(
        _filled(traffic, data), data, rng, n))


def sweep_queries(traffic: dict, data) -> list[dict]:
    return _asked(traffic, data, groupby.sweep_queries(
        _filled(traffic, data), data))


def control_values(data) -> np.ndarray:
    """What `check` takes as `values` under the control: every field's
    values rounded to bfloat16.  Made once a run, before the check's
    workers are forked."""
    return round_bf16(data.values)


def reference(query: dict, data, values=None) -> tuple:
    """(the (ticks of the window, hosts) mask of the rows the query
    selects, the window's first tick)."""
    grids = data.values if values is None else values
    lo, hi = data.tick_range(query["start"], query["end"])
    cols = slice(None) if query["hosts"] is None else list(query["hosts"])
    mask = np.zeros((hi - lo, data.hosts), dtype=bool)
    mask[:, cols] = _COMPARE[query["op"]](
        grids[query["where_field"]][lo:hi][:, cols],
        np.float32(query["threshold"]))
    return mask, lo


def check(query: dict, payload: bytes, data, values=None) -> dict:
    """One response against the reference (or, with `values`, against
    the control's value grids)."""
    grids = data.values if values is None else values
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
        series, code = np.unique(tsid, return_inverse=True)
        host = np.array([data.host_of_tsid[str(t)] for t in series],
                        dtype=np.int64)[code] if len(tsid) \
            else np.zeros(0, np.int64)
    except (pa.ArrowInvalid, OSError, KeyError, ValueError, TypeError):
        return _malformed()
    if len(ts) > 1:
        same = tsid[1:] == tsid[:-1]
        if ((tsid[1:] < tsid[:-1]) | (same & (ts[1:] <= ts[:-1]))).any():
            return _malformed()
    out = _no_readings()
    want, lo = reference(query, data, values)
    tick = (ts - data.t0) // data.interval_ms - lo
    on_grid = (((ts - data.t0) % data.interval_ms == 0)
               & (tick >= 0) & (tick < want.shape[0])
               & (ts >= query["start"]) & (ts < query["end"]))
    have = np.zeros_like(want)
    have[tick[on_grid], host[on_grid]] = True   # keys are unique: sorted
    both = on_grid.copy()
    both[on_grid] = want[tick[on_grid], host[on_grid]]
    out["row_set_mismatch_rows"] = int(
        (want & ~have).sum() + (~both).sum())
    t, h = tick[both] + lo, host[both]
    for name, f in zip(names, query["fields"]):
        col = got.column(name).combine_chunks()
        nulls = np.asarray(col.is_null())[both]
        bits = np.asarray(col.fill_null(0), dtype=np.float32)[both] \
            .view(np.uint32)
        written = np.ascontiguousarray(grids[f][t, h]).view(np.uint32)
        out["value_mismatch_cells"] += int(
            (nulls | (bits != written)).sum())
    return out


def combine(readings: list[dict]) -> dict:
    """Fold per-response readings: every number is a count."""
    total = _no_readings()
    for r in readings:
        for k in READINGS:
            total[k] += r[k]
    return total
