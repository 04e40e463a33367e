"""The plain reference of POST /query_rows: from the list of
acknowledged writes, in order, the rows the endpoint must answer.

Imports numpy alone (nothing of `horaedb_tpu.ops`, `.storage` or
`.metric_engine`), and shares no step with the program: a dictionary
keyed by (series, field, timestamp) takes the writes in their order, so
the last write wins; the value predicate is put to what is left, as
float32 against the threshold rounded to float32."""

import numpy as np

COMPARE = {"gt": np.greater, "ge": np.greater_equal,
           "lt": np.less, "le": np.less_equal}


def current_values(writes: list) -> dict:
    """{(series, field, timestamp): float32} after `writes`, a list of
    (series, field, timestamp, value) in the order acknowledged."""
    state = {}
    for series, field, ts, value in writes:
        state[series, field, int(ts)] = np.float32(value)
    return state


def rows_where(writes: list, start: int, end: int, where_field: str,
               op: str, threshold: float, fields: list,
               series=None) -> list:
    """[(series, timestamp, [float32 or None, one a field asked])],
    sorted by (series, timestamp): every (series, timestamp) with
    start <= timestamp < end whose current value of `where_field`
    satisfies `op` against float32(threshold); `series`, if given,
    keeps only those series."""
    state = current_values(writes)
    t = np.float32(threshold)
    out = []
    for (s, f, ts), v in state.items():
        if f != where_field or not start <= ts < end:
            continue
        if series is not None and s not in series:
            continue
        if COMPARE[op](v, t):
            out.append((s, ts, [state.get((s, g, ts)) for g in fields]))
    return sorted(out, key=lambda row: row[:2])
