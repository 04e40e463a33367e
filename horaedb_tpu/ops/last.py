"""The newest row of every series: the first program whose output is
one row a SERIES.

"What is each series' newest sample" (TSBS `lastpoint`, a status
page, a Prometheus instant query) has no bucket and no value
predicate: in this data model (one stored row a sample a FIELD) it is,
a field, the last kept row of every series run of the field's slice,
and, a series, the fields' rows put side by side at the greatest of
their timestamps.  The device part runs over the slices the aggregate
route keeps resident (ops/device_decode.SegmentSlice: one field of one
segment, narrowed, padded, on the device) and keeps nothing of its own
there or between requests:

  last    — a field's slice through the aggregate program's own
            decode, filter and dedup (device_decode.rows_sorted_kept:
            the window's PK leaves, then keep-last of every PK run),
            then ONE more boundary pass over the series key alone: a
            kept row whose next row is another series', or no row at
            all, is the last of its series.  The slice decodes with
            (series code, timestamp) never falling
            (SegmentSlice.cells_sorted; a slice without it takes the
            host route) and the only leaves that are not key leaves
            bound the timestamp, so the rows a window admits of one
            series lie together and that row holds the series'
            greatest admitted timestamp, under its last write.  Those
            rows' (series code, timestamp offset, value) are compacted
            in row order into the slice's series count rounded up to a
            power of two (SegmentSlice.g_pad): a running count of the
            flags and one binary search a place, nothing scattered.
            A slice never holds more series than that, so nothing
            overflows.

The asked fields' slices of one segment go out in one call (the
batching of device_decode.execute_batch, as ops/select.py takes it: the
slices' device arrays as they lie, one small host array of the window's
numbers, a loop over the live slices) and come back in ONE download.
The fields' rows become one row a series on the host (combine_fields:
a thousand series by ten fields is a few numpy passes): the greatest
timestamp over the fields, a field's value only where its own newest
timestamp is that one.  The host route (rows the row scan merged and
deduplicated, last_on_host) ends in the same combine.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from horaedb_tpu.common import deviceprof
from horaedb_tpu.ops import device_decode
from horaedb_tpu.ops import select as select_ops
from horaedb_tpu.ops.device_decode import (SegmentSlice, rows_sorted_kept,
                                           slice_columns, slice_consts,
                                           stack_slices)
from horaedb_tpu.ops.select import SelectWindow
from horaedb_tpu.utils import registry

_CALLS = registry.counter(
    "scan_last_calls_total",
    "calls of the last-row program: the asked fields' resident slices "
    "of one segment (decode, filter, dedup, the series' last rows, "
    "compaction)")


@dataclass(frozen=True)
class LastSpec:
    """The newest row of every `group_col` by `ts_col`, with its
    `value_col`."""

    group_col: str
    ts_col: str
    value_col: str


@dataclass
class LastRows:
    """One segment's answer: a row a series found in it."""

    groups: np.ndarray            # the series, ascending (uint64)
    timestamps: np.ndarray        # int64: the greatest over the fields
    values: list                  # float32 arrays, one a field asked
    found: list                   # bool arrays: the field has a sample
    #                               at exactly that timestamp
    rows_read: int = 0            # rows put through the decode


def plan_window(seg: SegmentSlice, leaves,
                ts_col: str) -> "SelectWindow | None | str":
    """A slice under a request's leaves: a SelectWindow, None where a
    leaf provably matches nothing, or the reason the slice's last rows
    cannot be taken on the device (`unsorted`: its decoded rows are not
    in (series, timestamp) order; `predicate`: a leaf that is not a key
    leaf bounds something else than the timestamp, so a series' admitted
    rows need not lie together)."""
    got = select_ops.plan_window(seg, leaves)
    if not isinstance(got, SelectWindow):
        return got
    ts_slot = seg.upload_names.index(ts_col)
    keyed = {device_decode._OP_EQ, device_decode._OP_IN}
    if any(slot != ts_slot for slot, op in got.leaf_prog
           if op not in keyed):
        return "predicate"
    return got


@deviceprof.jit(static_argnames=select_ops._DECODE_STATICS + ("series",))
def _last_rows_jit(cols: tuple, key_consts: tuple, run_offsets: tuple,
                   nums, *, series: int, group_pos: int, ts_pos: int,
                   **static):
    """The asked fields' slices of one segment: per slice the rows the
    request's leaves admit, deduplicated, then the last of every series
    run, compacted in row order into `series` places.

    `nums` as ops/select._select_rows takes it, int32 [1 + slices,
    1 + window constants]: nums[0, 0] the live slices, row 1 + i slice
    i's rows and the constants of its leaves that are not key leaves.
    Returns (codes, ts, values)[slices, series] and (found, kept)
    [slices]: `found` series have a row (the places past it hold code
    -1), `kept` rows were left by the dedup."""
    stacked, keyed_stacked, offs = stack_slices(cols, key_consts,
                                                run_offsets)

    def one(i):
        row = nums[1 + i]
        valid_s, keys_s, val_s, kept = rows_sorted_kept(
            slice_columns(stacked, i), row[0],
            slice_consts(static["leaf_prog"], keyed_stacked, row, i, 1),
            offs[i], **static)
        with jax.named_scope("last"):
            code = keys_s[group_pos]
            ends = jnp.concatenate(
                [(code[:-1] != code[1:]) | ~valid_s[1:],
                 jnp.ones(1, dtype=bool)])
            pos = jnp.cumsum((kept & ends).astype(jnp.int32))
            found = pos[-1]
            # place j holds the row at which the count reaches j + 1
            rows = jnp.minimum(
                jnp.searchsorted(
                    pos, jnp.arange(1, series + 1, dtype=jnp.int32),
                    side="left").astype(jnp.int32),
                code.shape[0] - 1)
            live = jnp.arange(series, dtype=jnp.int32) < found
            codes = jnp.where(live, code[rows], -1)
            ts = jnp.where(live, keys_s[ts_pos][rows], 0)
            vals = jnp.where(live, val_s[rows], 0)
        return codes, ts, vals, found, jnp.sum(kept.astype(jnp.int32))

    return select_ops._per_slice(one, nums[0, 0], len(cols))


def _run_group(group: list, phase, table: str) -> list:
    """One call and one download for slices whose windows share their
    program: per slice (series, timestamps, values), the series
    ascending."""
    first = group[0]
    slots = 1 << (len(group) - 1).bit_length()
    with phase("scan.dispatch", sync=True, h2d_bytes=0,
               slices=len(group)):
        out = select_ops._first_call(
            ("last", first.batch_key(), slots),
            lambda: _last_rows_jit(
                *select_ops._slices_args(group, slots),
                series=first.seg.g_pad, **first.statics()))
        _CALLS.inc()
        device_decode.note_batched(len(group), 1)
    codes, ts, vals, found, _kept = deviceprof.download(
        out, fn="_last_rows_jit", table=table)
    rows = []
    for i, w in enumerate(group):
        n, seg = int(found[i]), w.seg
        # a dictionary holds an unsigned key as int64 (ops/encode:
        # nothing past i64::MAX reaches the device): back to the
        # column's own type, which is what the row scan returns
        kind = seg.encodings[seg.upload_names[
            seg.key_slots[seg.group_pos]]].arrow_type.to_pandas_dtype()
        rows.append((seg.values[codes[i, :n]].astype(kind, copy=False),
                     ts[i, :n].astype(np.int64) + seg.ts_epoch,
                     vals[i, :n]))
    return rows


def last_resident(windows: list, phase, table: str = "") -> list:
    """The last rows of one segment's fields whose slices are on the
    device.  `windows`: one SelectWindow a field asked, None where the
    field provably has no row in the segment.  Fields whose windows may
    share their program go out together, cut where a call's stacked
    columns would pass device_decode's stack budget.  Returns, a field,
    (series, timestamps, values) or None."""
    groups: dict = {}
    for f, w in enumerate(windows):
        if w is not None:
            groups.setdefault(w.batch_key(), []).append(f)
    out: list = [None] * len(windows)
    for group in groups.values():
        room = device_decode._BATCH_MAX_STACK_BYTES \
            // windows[group[0]].seg.nbytes
        per_call = max(1, 1 << (room.bit_length() - 1)) if room else 1
        for at in range(0, len(group), per_call):
            call = group[at:at + per_call]
            for f, rows in zip(call, _run_group(
                    [windows[f] for f in call], phase, table)):
                out[f] = rows
    return out


def last_on_host(groups: np.ndarray, ts: np.ndarray,
                 vals: np.ndarray) -> tuple:
    """One field's rows as a read returns them (deduplicated: a key
    once) to the last row of every series: (series ascending,
    timestamps, values)."""
    order = np.lexsort((ts, groups))
    groups, ts, vals = groups[order], ts[order], vals[order]
    ends = np.ones(len(groups), dtype=bool)
    ends[:-1] = groups[1:] != groups[:-1]
    return groups[ends], ts[ends], vals[ends]


def combine_fields(fields: list, keep: np.ndarray,
                   rows_read: int = 0) -> LastRows:
    """The fields' last rows of one segment ((series, timestamps,
    values) or None, one a field asked) as one row a series among
    `keep` (ascending): the greatest timestamp over the fields, a
    field's value only where its own timestamp is that one."""
    fields = [None if f is None or not len(f[0]) else f for f in fields]
    have = [f for f in fields if f is not None]
    if not have:
        return LastRows(groups=np.zeros(0, np.uint64),
                        timestamps=np.zeros(0, np.int64),
                        values=[np.zeros(0, np.float32)] * len(fields),
                        found=[np.zeros(0, bool)] * len(fields),
                        rows_read=rows_read)
    series = have[0][0] if all(
        f[0] is have[0][0] or np.array_equal(f[0], have[0][0])
        for f in have[1:]) else np.unique(
            np.concatenate([f[0] for f in have]))
    series = series[np.isin(series, keep, assume_unique=True)]
    lowest = np.iinfo(np.int64).min
    ts = np.full((len(fields), len(series)), lowest, dtype=np.int64)
    vals = np.zeros((len(fields), len(series)), dtype=np.float32)
    for f, rows in enumerate(fields):
        if rows is None:
            continue
        at = np.searchsorted(rows[0], series)
        hit = rows[0][np.minimum(at, len(rows[0]) - 1)] == series
        ts[f, hit] = rows[1][at[hit]]
        vals[f, hit] = rows[2][at[hit]]
    newest = ts.max(axis=0)
    return LastRows(groups=series, timestamps=newest,
                    values=list(vals),
                    found=list((ts == newest) & (ts != lowest)),
                    rows_read=rows_read)
