"""The plain reference of POST /query_last: from the list of
acknowledged writes, in order, the rows the endpoint must answer.

Imports numpy alone (nothing of `horaedb_tpu.ops`, `.storage` or
`.metric_engine`), and shares no step with the program: a dictionary
keyed by (series, field, timestamp) takes the writes in their order, so
the last write wins; a series' row stands at the greatest timestamp at
which any field asked has a current sample within the bounds, and
holds each field's value at exactly that timestamp or None."""

import numpy as np


def current_values(writes: list) -> dict:
    """{(series, field, timestamp): float32} after `writes`, a list of
    (series, field, timestamp, value) in the order acknowledged."""
    state = {}
    for series, field, ts, value in writes:
        state[series, field, int(ts)] = np.float32(value)
    return state


def last_rows(writes: list, fields: list, start=None, end=None,
              series=None) -> list:
    """[(series, timestamp, [float32 or None, one a field asked])],
    ascending by series: one row for every series with a current sample
    of a field asked at start <= timestamp < end (a bound that is None
    does not bind); `series`, if given, keeps only those series."""
    state = current_values(writes)
    newest: dict = {}
    for (s, f, ts) in state:
        if f not in fields:
            continue
        if start is not None and ts < start:
            continue
        if end is not None and ts >= end:
            continue
        if series is not None and s not in series:
            continue
        if s not in newest or ts > newest[s]:
            newest[s] = ts
    return [(s, ts, [state.get((s, f, ts)) for f in fields])
            for s, ts in sorted(newest.items())]
