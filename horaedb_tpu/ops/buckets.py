"""An aggregate ACROSS series by time bucket: the first program whose
output has NO series axis.

"One number a time bucket over every matching series" (TSBS
`groupby-orderby-limit`, a dashboard's fleet panel, PromQL's `max(...)`
without `by`) is, in this data model (one stored row a sample a FIELD),
a fold of one field's kept rows into their buckets whatever their
series.  The device part runs over the slices the aggregate route
keeps resident (ops/device_decode.SegmentSlice: one field of one
segment, narrowed, padded, on the device) and keeps nothing of its own
there or between requests:

  buckets — a field's slice through the aggregate program's own decode,
            filter and dedup (device_decode.rows_sorted_kept: the
            window's PK leaves and its time bounds, then keep-last of
            every PK run), then every kept row folded into bucket
            (timestamp - grid start) // bucket_ms, the grid starting at
            the epoch-aligned bucket that holds the segment's start:
            `count` and the asked aggregates as [slices, buckets]
            grids.  The rows lie in (series, time) order, so a
            bucket's rows do not lie together: the fold is a masked
            reduce of the rows against a bucket iota, bucket by bucket
            over rows that stay in their own layout (nothing sorted,
            scattered or gathered), and its cost follows rows x
            buckets: a grid wider than _MAX_BUCKETS takes the host
            route.  `buckets` is static (the segment's bucket count
            rounded up to a multiple of 128); the bounds, the bucket's
            length, the grid's start and the row count are run-time
            numbers beside the leaves' constants, so that no `end`
            compiles anything.

One call and ONE download a segment.  Which buckets are answered (the
newest `limit` that hold a sample) is the walk's to decide, on the
host, from the cells downloaded (CloudObjectStorage.scan_buckets); a
bucket that straddles two segments is the fold of both parts
(Merged).  The host route (rows the row scan merged and
deduplicated, buckets_on_host) answers a segment in the same form.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp

from horaedb_tpu.common import deviceprof
from horaedb_tpu.ops import device_decode
from horaedb_tpu.ops import select as select_ops
from horaedb_tpu.ops.device_decode import (SegmentSlice, rows_sorted_kept,
                                           slice_columns, slice_consts,
                                           stack_slices, window_numbers)
from horaedb_tpu.ops.select import SelectWindow
from horaedb_tpu.utils import registry

# what a request may ask beside `count`, and what the fold computes of
# it: an average is its bucket's sum over its count, on the host
AGGS = ("max", "min", "sum", "avg")
_FOLDED = ("max", "min", "sum")

# the most buckets a request may ask for
MAX_LIMIT = 10_000

# the widest grid the device folds: the masked reduce costs rows x
# buckets, so a segment cut into more buckets than this (a 2 h segment
# by less than 7.1 s) is folded on the host, by a sort
_MAX_BUCKETS = 1024

_CALLS = registry.counter(
    "scan_buckets_calls_total",
    "calls of the bucket-fold program: one field's resident slice of "
    "one segment (decode, filter, dedup, every kept row folded into "
    "its time bucket over all series)")


@dataclass(frozen=True)
class BucketsSpec:
    """`value_col` folded over all `group_col` by epoch-aligned buckets
    of `bucket_ms` on `ts_col`: `count` and `aggs`, in the order
    asked."""

    group_col: str
    ts_col: str
    value_col: str
    bucket_ms: int
    aggs: tuple

    @property
    def folded(self) -> tuple:
        """What the fold computes for `aggs`, in the program's order."""
        want = set(self.aggs) | ({"sum"} if "avg" in self.aggs else set())
        return tuple(a for a in _FOLDED if a in want)


@dataclass
class SegmentBuckets:
    """One segment's answer: its buckets that hold a sample within the
    bounds, ascending, each with what the segment's rows fold to."""

    starts: np.ndarray            # int64: the buckets' starts
    count: np.ndarray             # int64: rows folded
    aggs: dict                    # max, min: float32; sum: float64
    rows_read: int = 0            # rows put through the decode
    segment_start: int = 0
    route: str = ""
    reason: str = ""


@dataclass
class BucketsWindow(SelectWindow):
    """A SelectWindow with the grid its rows fold into."""

    base: int = 0                 # the first bucket's start (host time)
    buckets: int = 0              # the grid's width (static)

    def batch_key(self) -> tuple:
        return super().batch_key() + (self.buckets,)


def plan_window(seg: SegmentSlice, leaves, segment_start: int,
                segment_ms: int,
                bucket_ms: int) -> "BucketsWindow | None | str":
    """A slice under a request's leaves and bucket: a BucketsWindow,
    None where a leaf provably matches nothing, or the reason the
    segment is folded on the host (select's reasons; `buckets`: the
    grid is wider than the device folds; `range`: a bucket so long
    that a row's time since the grid's start passes int32)."""
    got = select_ops.plan_window(seg, leaves)
    if not isinstance(got, SelectWindow):
        return got
    base = segment_start // bucket_ms * bucket_ms
    span = segment_start + segment_ms - base
    buckets = -(-span // bucket_ms)
    if buckets > _MAX_BUCKETS:
        return "buckets"
    if max(span, bucket_ms, abs(seg.ts_epoch - base)) >= 2**31:
        return "range"
    return BucketsWindow(got.seg, got.leaf_prog, got.consts, base=base,
                         buckets=-(-buckets // 128) * 128)


def fold_rows(ts_rel, vals, kept, bucket_ms, *, buckets: int,
              which: tuple) -> tuple:
    """Rows (time since the grid's start, value; `kept` marks those
    that count) folded into `buckets` cells whatever their order:
    (count int32[buckets], {aggregate: float32[buckets]}).  Traced."""
    cell = jnp.where(kept, ts_rel // bucket_ms, -1)
    hit = cell[None, :] == jnp.arange(buckets, dtype=jnp.int32)[:, None]
    count = jnp.sum(hit, axis=1, dtype=jnp.int32)
    row = vals[None, :]
    out = {}
    if "max" in which:
        out["max"] = jnp.max(jnp.where(hit, row, -jnp.inf), axis=1)
    if "min" in which:
        out["min"] = jnp.min(jnp.where(hit, row, jnp.inf), axis=1)
    if "sum" in which:
        out["sum"] = jnp.sum(jnp.where(hit, row, 0.0), axis=1)
    return count, out


@deviceprof.jit(static_argnames=select_ops._DECODE_STATICS
                + ("buckets", "which"))
def _buckets_jit(cols: tuple, key_consts: tuple, run_offsets: tuple,
                 nums, *, buckets: int, which: tuple, group_pos: int,
                 ts_pos: int, **static):
    """A field's slices: per slice the rows the request's leaves admit
    (its time bounds among them), deduplicated, then folded into
    `buckets` cells by time alone.

    `nums` is the call's host array, int32 [1 + slices, 2 + window
    constants]: nums[0] = (live slices, bucket_ms), row 1 + i slice
    i's rows, the grid's start less the slice's timestamp epoch, and
    the constants of its leaves that are not key leaves.  Returns
    (count[slices, buckets], {aggregate: [slices, buckets]},
    kept[slices]): `kept` rows were left by the dedup, and every one of
    them is in `count` (a segment's rows lie inside its grid)."""
    del group_pos
    stacked, keyed_stacked, offs = stack_slices(cols, key_consts,
                                                run_offsets)
    bucket_ms = nums[0, 1]

    def one(i):
        row = nums[1 + i]
        _valid, keys_s, val_s, kept = rows_sorted_kept(
            slice_columns(stacked, i), row[0],
            slice_consts(static["leaf_prog"], keyed_stacked, row, i, 2),
            offs[i], **static)
        with jax.named_scope("buckets"):
            count, aggs = fold_rows(keys_s[ts_pos] - row[1], val_s, kept,
                                    bucket_ms, buckets=buckets,
                                    which=which)
        return count, aggs, jnp.sum(kept.astype(jnp.int32))

    return select_ops._per_slice(one, nums[0, 0], len(cols))


def _nums(window: BucketsWindow, bucket_ms: int) -> np.ndarray:
    """The one slice's host numbers, as _buckets_jit takes them."""
    row = np.concatenate(
        [np.asarray([window.seg.n, window.base - window.seg.ts_epoch],
                    dtype=np.int32),
         *window_numbers(window.leaf_prog, window.consts)])
    nums = np.zeros((2, len(row)), dtype=np.int32)
    nums[0, :2] = 1, bucket_ms
    nums[1] = row
    return nums


def buckets_resident(window: "BucketsWindow | None", spec: BucketsSpec,
                     phase, table: str = "") -> SegmentBuckets:
    """One segment's buckets from its field's slice on the device: one
    call, one download.  `window` None: the field provably has no row
    in the segment."""
    if window is None:
        return _no_buckets(spec)
    seg, which = window.seg, spec.folded
    with phase("scan.dispatch", sync=True, h2d_bytes=0, slices=1):
        out = select_ops._first_call(
            ("buckets", window.batch_key(), which),
            lambda: _buckets_jit(
                (seg.cols_dev,), (seg.key_consts_dev,), (seg.offs_dev,),
                _nums(window, spec.bucket_ms), buckets=window.buckets,
                which=which, **window.statics()))
        _CALLS.inc()
        device_decode.note_batched(1, 1)
    count, aggs, kept = deviceprof.download(out, fn="_buckets_jit",
                                            table=table)
    count = count[0]
    if int(count.sum()) != int(kept[0]):
        raise AssertionError(
            f"segment slice at epoch {seg.ts_epoch}: {int(kept[0])} rows "
            f"kept, {int(count.sum())} inside its bucket grid")
    at = np.flatnonzero(count)
    return SegmentBuckets(
        starts=window.base + at.astype(np.int64) * spec.bucket_ms,
        count=count[at].astype(np.int64),
        aggs={a: (g[0, at].astype(np.float64) if a == "sum" else g[0, at])
              for a, g in aggs.items()},
        rows_read=seg.n)


def _no_buckets(spec: BucketsSpec, rows_read: int = 0) -> SegmentBuckets:
    return SegmentBuckets(
        starts=np.zeros(0, np.int64), count=np.zeros(0, np.int64),
        aggs={a: np.zeros(0, np.float64 if a == "sum" else np.float32)
              for a in spec.folded}, rows_read=rows_read)


def buckets_on_host(ts: np.ndarray, vals: np.ndarray, spec: BucketsSpec,
                    start: int, end: int) -> SegmentBuckets:
    """One field's rows as a read returns them (deduplicated: a key
    once) folded into their buckets in numpy: the host route's twin of
    the program, and its control.  A bucket's float32 sum is its rows'
    exact sum rounded once, as the program's partial is a float32."""
    inside = (ts >= start) & (ts < end)
    ts, vals = ts[inside], vals[inside]
    if not len(ts):
        return _no_buckets(spec, rows_read=len(inside))
    order = np.argsort(ts // spec.bucket_ms, kind="stable")
    cell, vals = (ts // spec.bucket_ms)[order], vals[order]
    first = np.flatnonzero(np.concatenate([[True], cell[1:] != cell[:-1]]))
    aggs = {}
    for a in spec.folded:
        if a == "sum":
            aggs[a] = np.add.reduceat(vals.astype(np.float64), first) \
                .astype(np.float32).astype(np.float64)
        else:
            aggs[a] = (np.maximum if a == "max"
                       else np.minimum).reduceat(vals, first)
    return SegmentBuckets(
        starts=cell[first].astype(np.int64) * spec.bucket_ms,
        count=np.diff(np.append(first, len(cell))).astype(np.int64),
        aggs=aggs, rows_read=len(inside))


@dataclass
class Merged:
    """The buckets a walk has found so far, by their start: a bucket
    that straddles two segments is the fold of both parts."""

    count: dict = field(default_factory=dict)
    aggs: dict = field(default_factory=dict)

    def add(self, part: SegmentBuckets, limit: int) -> None:
        """`part`'s `limit` newest buckets: no other of its buckets can
        be among the `limit` newest of the whole."""
        at = range(max(0, len(part.starts) - limit), len(part.starts))
        for i, start in zip(at, part.starts[at.start:].tolist()):
            if start not in self.count:
                self.count[start] = int(part.count[i])
                self.aggs[start] = {a: v[i] for a, v in part.aggs.items()}
                continue
            self.count[start] += int(part.count[i])
            have = self.aggs[start]
            for a, v in part.aggs.items():
                have[a] = (max(have[a], v[i]) if a == "max"
                           else min(have[a], v[i]) if a == "min"
                           else have[a] + v[i])

    def newest(self, limit: int) -> list:
        """The starts of the `limit` newest buckets, descending."""
        return sorted(self.count, reverse=True)[:limit]


def answer_columns(merged: Merged, spec: BucketsSpec, limit: int) -> dict:
    """{bucket, count, one array an aggregate asked}: the `limit`
    newest buckets of `merged`, descending by start."""
    starts = merged.newest(limit)
    out = {"bucket": np.asarray(starts, dtype=np.int64),
           "count": np.asarray([merged.count[s] for s in starts],
                               dtype=np.int64)}
    for a in spec.aggs:
        if a == "avg":
            col = [merged.aggs[s]["sum"] / merged.count[s] for s in starts]
        else:
            col = [merged.aggs[s][a] for s in starts]
        out[a] = np.asarray(col, dtype=np.float32)
    return out
