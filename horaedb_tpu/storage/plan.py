"""Composable logical query plan over the merge-scan.

The reference plugs its per-segment MergeExec into arbitrary DataFusion
ExecutionPlan trees (/root/reference/src/storage/src/read.rs:429-494,
storage.rs:359-368).  This engine's query surface is three shapes —
row scan (+filter/project), downsample aggregate, top-k — which used to
be hardwired in their entry points.  `QueryPlan` is the single internal
currency instead: every entry point builds one, the storage facade
executes it, and `describe()` renders the plan text the golden tests
pin (the analogue of the reference's DisplayableExecutionPlan tests,
read.rs:575-617).

Deliberately NOT a DataFusion clone: the operator set is the closed set
the TPU execution actually supports (compiled merge + grid aggregation
+ top-k), so there is no generic optimizer — building a plan IS the
optimization (pushdown/pruning happen in build_plan, aggregation fuses
in the reader).
"""

from __future__ import annotations

import textwrap
from dataclasses import dataclass
from typing import Optional

import numpy as np

from horaedb_tpu.common.error import ensure
from horaedb_tpu.storage.read import (
    AggregateSpec,
    ScanPlan,
    ScanRequest,
    describe_plan,
)


@dataclass(frozen=True)
class TopKSpec:
    """Rank groups by one aggregate grid and keep the best k.

    `by` names a grid in the aggregate output (it must be in the
    spec's `which`); a group's score is that grid's best cell across
    buckets with data (max for largest=True, min otherwise)."""

    k: int
    by: str = "max"
    largest: bool = True


@dataclass
class QueryPlan:
    """scan -> filter (inside scan) -> aggregate? -> top_k?

    `scan` is the physical merge-scan plan captured at build time: it
    renders in describe() and serves as the FIRST attempt's plan in
    execute_plan (one manifest lookup per query); compaction races make
    it stale, in which case execution replans exactly like any raced
    scan."""

    scan: ScanPlan
    request: ScanRequest
    aggregate: Optional[AggregateSpec] = None
    top_k: Optional[TopKSpec] = None

    def describe(self) -> str:
        text = describe_plan(self.scan)
        if self.aggregate is not None:
            spec = self.aggregate
            text = (f"Aggregate: group={spec.group_col}, "
                    f"ts={spec.ts_col}, value={spec.value_col}, "
                    f"bucket={spec.bucket_ms}ms, "
                    f"buckets={spec.num_buckets}, "
                    f"which={tuple(spec.which)}\n"
                    + textwrap.indent(text, "  "))
        if self.top_k is not None:
            tk = self.top_k
            text = (f"TopK: k={tk.k}, by={tk.by}, largest={tk.largest}\n"
                    + textwrap.indent(text, "  "))
        return text


@dataclass
class SelectPlan:
    """select (value predicate, after the dedup) -> join (the other
    fields at the selected keys) over one scan a field.

    `scans[0]` and `requests[0]` are the predicate's field's, the rest
    the other distinct fields', all over the same SSTs; `asked[i]`
    names, by index into them, the field of value column i.  Which
    route a segment takes (the device's resident slices or the host
    decode) is decided per segment where it runs
    (ParquetReader.select_segments) and counted there."""

    scans: list
    requests: list
    select: object                # ops/select.SelectSpec
    asked: list

    def describe(self) -> str:
        spec = self.select
        text = (f"Select: group={spec.group_col}, ts={spec.ts_col}, "
                f"value={spec.value_col} {spec.op} {spec.threshold!r}, "
                f"columns={list(self.asked)}\n")
        return text + "\n".join(
            textwrap.indent(describe_plan(scan), "  ").rstrip("\n")
            for scan in self.scans)


@dataclass
class LastPlan:
    """last (the newest row of every series, a field a scan) over the
    table's segments NEWEST FIRST, with no time range of its own: the
    walk asks one segment for the last rows of the series still
    missing and stops when none is missing or no segment is left.

    `segments` is the manifest's answer when the plan was built,
    (segment start, its SSTs), newest first; `requests` hold a request
    a field asked, all over the same range (unbounded where the client
    gave no bound); `expect` are the series the walk accounts for,
    ascending.  Which route a segment takes (the device's resident
    slices or the row scan) is decided per segment where it runs
    (ParquetReader.last_segment) and counted there."""

    segments: list
    requests: list
    last: object                  # ops/last.LastSpec
    expect: np.ndarray

    def describe(self) -> str:
        spec = self.last
        text = (f"Last: group={spec.group_col}, ts={spec.ts_col}, "
                f"value={spec.value_col}, fields={len(self.requests)}, "
                f"series={len(self.expect)}, newest first, stops when "
                f"no series is missing\n")
        return text + "\n".join(
            f"  Segment {start}: {len(ssts)} sst(s)"
            for start, ssts in self.segments)


@dataclass
class BucketsPlan:
    """buckets (one field folded over ALL series by time bucket, the
    `limit` newest buckets that hold a sample) over the table's
    segments NEWEST FIRST, with an open lower bound unless the client
    named one: the walk asks one segment for its buckets and stops when
    `limit` buckets exist and none of them can still gain from an
    older segment, or no segment is left.

    `segments` is the manifest's answer when the plan was built,
    (segment start, its SSTs), newest first; `request` is the one
    field's scan over the range (unbounded where the client gave no
    bound).  Which route a segment takes (the device's resident slice
    or the row scan) is decided per segment where it runs
    (ParquetReader.buckets_segment) and counted there."""

    segments: list
    request: object               # storage/read.ScanRequest
    buckets: object               # ops/buckets.BucketsSpec
    limit: int

    def describe(self) -> str:
        spec = self.buckets
        text = (f"Buckets: ts={spec.ts_col}, value={spec.value_col}, "
                f"bucket_ms={spec.bucket_ms}, aggs={list(spec.aggs)}, "
                f"over all series, newest first, stops at {self.limit} "
                f"bucket(s) that no older segment can add to\n")
        return text + "\n".join(
            f"  Segment {start}: {len(ssts)} sst(s)"
            for start, ssts in self.segments)


def apply_top_k(group_values: np.ndarray, grids: dict,
                tk: TopKSpec) -> tuple[np.ndarray, dict]:
    """Host top-k over finalized grids: by the time grids exist the
    group axis is small (one row per series), so ranking is a numpy
    argsort — the device's job was reducing rows to grids, not sorting
    k scores.  Returns (values, grids) sliced to the k best groups,
    best first."""
    ensure(tk.by in grids,
           f"top-k by {tk.by!r} needs that aggregate in the spec's "
           f"`which`; have {sorted(grids)}")
    if not len(group_values):
        return group_values, grids
    by = np.asarray(grids[tk.by], dtype=np.float64)
    count = np.asarray(grids["count"])
    if tk.largest:
        score = np.where(count > 0, by, -np.inf).max(axis=1)
        order = np.argsort(-score, kind="stable")
    else:
        score = np.where(count > 0, by, np.inf).min(axis=1)
        order = np.argsort(score, kind="stable")
    idx = order[:tk.k]
    return (np.asarray(group_values)[idx],
            {name: np.asarray(g)[idx] for name, g in grids.items()})
