"""Merge-scan read path — the north-star pipeline.

The reference builds, per time segment, a DataFusion physical plan
  ParquetExec(+pruning) → FilterExec → SortPreservingMergeExec → MergeExec
and streams batches through it (ref: src/storage/src/read.rs:429-494).

The TPU redesign keeps the same operator boundary but executes each
segment as one compiled device program (see ops/):

  ParquetScan (host, async)      — read + concat all SSTs in the segment
  Encode (host)                  — Arrow → int32/f32 device batch
  Filter (device mask)           — predicate tree → validity mask
  MergeDedup (device)            — sort (pk...,seq) + segmented last-select
  Decode (host)                  — device batch → Arrow, builtin columns
                                   stripped unless keep_builtin

Append mode routes the merge through the host BytesMergeOperator instead
(variable-length values; fixed-width device design).

Plans are described as text via `describe_plan` for golden plan-shape
tests, the analogue of the reference's DisplayableExecutionPlan test
(ref: read.rs:575-617).
"""

from __future__ import annotations

import asyncio
import contextlib
import functools
import logging
import threading
import time
import weakref
from dataclasses import dataclass, replace as dc_replace
from typing import AsyncIterator, Optional

import numpy as np
import pyarrow as pa

import jax
import jax.numpy as jnp

from horaedb_tpu.common import deviceprof
from horaedb_tpu.common.deadline import checkpoint as deadline_checkpoint
from horaedb_tpu.common.error import Error, ensure
from horaedb_tpu.common.memledger import (
    device_bytes_limit,
    ledger as memledger,
)
from horaedb_tpu.common.tenant import charge_scan_bytes
from horaedb_tpu.objstore import NotFoundError, ObjectStore
from horaedb_tpu.ops import downsample as downsample_ops
from horaedb_tpu.ops import encode, filter as filter_ops
from horaedb_tpu.storage.config import StorageConfig, UpdateMode
from horaedb_tpu.storage.operator import build_operator
from horaedb_tpu.storage.sst import SstFile, segment_of, sst_path
from horaedb_tpu.storage.types import (
    RESERVED_COLUMN_NAME,
    SEQ_COLUMN_NAME,
    StorageSchema,
    TimeRange,
)
from horaedb_tpu.ops import buckets as buckets_ops
from horaedb_tpu.ops import device_decode
from horaedb_tpu.ops import last as last_ops
from horaedb_tpu.ops import select as select_ops
from horaedb_tpu.storage import combine as combine_mod, parquet_io, sidecar
from horaedb_tpu.utils import active_trace, phase, registry, span, trace_add
from horaedb_tpu.utils.tracing import clear_phases

logger = logging.getLogger(__name__)

_SCAN_LATENCY = registry.histogram(
    "storage_scan_seconds", "merge-scan latency per segment")
_ROWS_SCANNED = registry.counter(
    "storage_rows_scanned_total", "rows produced by merge-scan")

# Per-plan-stage attribution (the reference wires ExecutionPlanMetricsSet
# through its reader, read.rs:84; ours records real numbers): seconds,
# rows, and bytes per pipeline stage, cumulative in the registry.
# One labeled family per unit (stage= label) instead of a metric name
# per stage; per-QUERY attribution additionally lands on the ambient
# trace via tracing.trace_add (docs/observability.md).
_PLAN_STAGES = ("parquet_read", "sidecar_read", "encode_merge",
                "stack_build", "device_decode", "device_aggregate",
                "mesh_aggregate", "combine")
_STAGE_SECONDS = {
    s: registry.histogram("scan_stage_seconds",
                          "wall seconds per merge-scan plan stage"
                          ).labels(stage=s)
    for s in _PLAN_STAGES
}
_STAGE_ROWS = {
    s: registry.counter("scan_stage_rows_total",
                        "rows entering each plan stage").labels(stage=s)
    for s in ("parquet_read", "sidecar_read", "encode_merge",
              "device_decode")
}
_STAGE_BYTES = {
    s: registry.counter("scan_stage_bytes_total",
                        "bytes entering each plan stage").labels(stage=s)
    for s in ("parquet_read", "sidecar_read", "stack_build",
              "device_decode")
}
# cache-effectiveness counters (ops parity with scan_cache_*): the
# replay and stack LRUs are the reason repeat/varied queries are fast —
# a production operator needs their hit rates on /metrics
_REPLAY_HITS = registry.counter(
    "scan_replay_hits_total", "fused-replay plan cache hits")
_REPLAY_ROWS = registry.counter(
    "scan_replay_rows_total",
    "rows served from fused-replay hits without re-scanning")
_REPLAY_MISSES = registry.counter(
    "scan_replay_misses_total", "fused-replay plan cache misses")
_FUSED_AGGREGATES = {
    c: registry.counter(
        "scan_fused_aggregates_total",
        "fused aggregates run, by how many device calls carried them: "
        "`one` (a single small round of host rows: one program, one "
        "download), `rounds` (init, an accumulate a round, finalize, "
        "mask), `replay` (the rounds again from recorded device stacks)"
    ).labels(calls=c)
    for c in ("one", "rounds", "replay")
}
_STACK_HITS = registry.counter(
    "scan_stack_cache_hits_total",
    "per-range round-stack LRU hits (small remap/shift/lo entries)")
_STACK_MISSES = registry.counter(
    "scan_stack_cache_misses_total",
    "per-range round-stack LRU misses")
_COLSTACK_HITS = registry.counter(
    "scan_colstack_cache_hits_total",
    "range-independent column-stack LRU hits (the big ts/gid/val "
    "arrays — the expensive reuse)")
_COLSTACK_MISSES = registry.counter(
    "scan_colstack_cache_misses_total",
    "range-independent column-stack LRU misses")
_INCR_REMERGE = registry.counter(
    "scan_incremental_remerge_total",
    "segments re-merged from tier-2-resident parts with only the "
    "missing SSTs fetched (the post-flush path)")

# ---- [scan.mesh] telemetry (docs/parallel.md) ------------------------------
_MESH_ROUNDS = registry.counter(
    "scan_mesh_rounds_total",
    "window rounds dispatched onto the 2-D scan mesh")
_MESH_PARTS = registry.counter(
    "scan_mesh_parts_total",
    "per-segment run parts produced by the on-mesh segmented combine")
_MESH_PART_CELLS = registry.counter(
    "scan_mesh_part_cells_total",
    "aggregate grid cells downloaded from the mesh (run parts + top-k "
    "winner slices) — the per-chip combine egress the top-k pushdown "
    "bounds at O(k x buckets x aggs) per run")
_MESH_SCORE_CELLS = registry.counter(
    "scan_mesh_score_cells_total",
    "per-group score/has cells downloaded by the top-k mesh path "
    "(O(groups), never O(groups x buckets))")
_MESH_TOPK = registry.counter(
    "scan_mesh_topk_total",
    "top-k queries served by the device-scored, winner-sliced mesh "
    "path")
# every way a round/plan declines the mesh, so an operator can tell a
# misconfigured mesh from unsupported data (mirrors
# scan_decode_fallback_total's discipline)
MESH_FALLBACK_REASONS = (
    "sum_overlap",   # a run's windows share a (group, bucket) sum cell
    "count_bound",   # time_axis x capacity would overflow f32 counts
    "grid_budget",   # round's transient grid exceeds max_grid_bytes
    "lo_range",      # a window's bucket offset exceeds the query grid
    "run_misaligned",  # a run's windows disagree on their first bucket
    "mesh_error",    # a round dispatch raised (lost shard / XLA error)
    "topk_by",       # ranking agg not selection-exact (count/sum/avg)
    "topk_router",   # near-data agents cover segments: no global score
    "topk_decode",   # device-decode parts can't join device scoring
    "topk_budget",   # two-phase window pinning exceeds the cache budget
    "additive_topk",  # an additive score add was not provably exact
    "mesh_decode_budget",  # a fused-decode round exceeds upload/grid caps
)
_MESH_FALLBACKS = registry.counter(
    "scan_mesh_fallback_total",
    "mesh scans that left their preferred route, by reason: topk_* "
    "reasons downgrade the egress-bounded winner-sliced top-k to "
    "FULL-WIDTH MESH parts (still on the mesh); every other reason "
    "re-runs that round on the single-chip kernel — the declared "
    "failure seams (docs/parallel.md)")
_MESH_FALLBACK_CHILDREN = {r: _MESH_FALLBACKS.labels(reason=r)
                           for r in MESH_FALLBACK_REASONS}
_MESH_AXIS_DEVICES = {
    a: registry.gauge(
        "scan_mesh_axis_devices",
        "devices per scan-mesh axis (0 = mesh off)").labels(axis=a)
    for a in ("time", "series")
}


def note_mesh_fallback(reason: str) -> None:
    child = _MESH_FALLBACK_CHILDREN.get(reason)
    if child is None:  # unknown reasons still count, labeled verbatim
        child = _MESH_FALLBACKS.labels(reason=reason)
        _MESH_FALLBACK_CHILDREN[reason] = child
    child.inc()
    trace_add(f"mesh_fallback_{reason}", 1)


# the row-selecting route (select_segments): every segment of a select
# by the route that answered it and, on the host route, why
_SELECT_SEGMENTS = registry.counter(
    "scan_select_segments_total",
    "segments of row selections under a value predicate by the route "
    "that answered them: device = selected and joined on the device "
    "from resident slices (ops/select.py), host = the host decode "
    "route with the value leaf evaluated after the merge and a numpy "
    "join, for `reason` (mode_host and cpu_auto are the [scan.decode] "
    "mode's choice; every other reason also counts in "
    "scan_decode_fallback_total)")
_SELECT_ROWS = registry.counter(
    "scan_select_rows_total",
    "rows of row selections: scanned = rows of the predicate's field "
    "in range, after the dedup, that the value predicate was put to "
    "(the device route's programs report them; the host route's "
    "filter does not), selected = rows that passed it, by route")
_SELECT_CELLS = {
    kind: registry.counter(
        "scan_select_cells_total",
        "value cells of the rows selected, a row a field asked: found "
        "= the field had a sample at the row's (series, timestamp), "
        "null = it had none"
    ).labels(kind=kind)
    for kind in ("found", "null")
}
_SELECT_CPU = registry.counter(
    "scan_select_cpu_seconds_total",
    "CPU seconds of the pool threads inside the device route's job of "
    "a row selection (calls issued, the download, the rows shaped)")
# config choices, not fallbacks: counted by route only
_SELECT_MODE_REASONS = ("mode_host", "cpu_auto")
# the last-row route (last_segment): every segment a walk asked, by the
# route that answered it and, on the host route, why
_LAST_SEGMENTS = registry.counter(
    "scan_last_segments_total",
    "segments a last-row walk asked for the newest rows of the series "
    "still missing, by the route that answered them: device = the "
    "series' last rows taken on the device from resident slices "
    "(ops/last.py), host = the row scan's merged rows reduced in "
    "numpy, for `reason` (the select's reasons, and `memtable`: rows "
    "of the segment are not in an SST yet; mode_host, cpu_auto and "
    "memtable are no fallbacks, every other reason also counts in "
    "scan_decode_fallback_total)")
_LAST_ROWS = registry.counter(
    "scan_last_rows_total",
    "rows of last-row walks by route: read = rows put through the "
    "decode (the device route: the asked fields' slices as uploaded; "
    "the host route: the rows its scans returned), answered = points "
    "answered, a series found x a field with a sample at the row's "
    "timestamp")
_LAST_NO_FALLBACK = _SELECT_MODE_REASONS + ("memtable",)
# the bucket route (buckets_segment): every segment a walk asked, by
# the route that answered it and, on the host route, why
_BUCKETS_SEGMENTS = registry.counter(
    "scan_buckets_segments_total",
    "segments a bucket walk asked for their buckets over all series, "
    "by the route that answered them: device = the field's resident "
    "slice folded on the device (ops/buckets.py), host = the row "
    "scan's merged rows folded in numpy, for `reason` (the select's "
    "reasons, `buckets`: the segment's grid is wider than the device "
    "folds, and `memtable`: rows of the segment are not in an SST "
    "yet; mode_host, cpu_auto and memtable are no fallbacks, every "
    "other reason also counts in scan_decode_fallback_total)")
_BUCKETS_ROWS = registry.counter(
    "scan_buckets_rows_total",
    "rows of bucket walks by route: read = rows put through the "
    "decode (the device route: the field's slice as uploaded; the "
    "host route: the rows its scan returned), used = rows that fell "
    "into a bucket the request answered")
_VALUE_LEAF = {"gt": filter_ops.Gt, "ge": filter_ops.Ge,
               "lt": filter_ops.Lt, "le": filter_ops.Le}


def _stack_counters(key: tuple):
    # the two entry families have different hit economics: conflating
    # them would report ~50% on varied-range workloads even when the
    # expensive column reuse is perfect
    if key and key[0] == "colstack":
        return _COLSTACK_HITS, _COLSTACK_MISSES
    return _STACK_HITS, _STACK_MISSES


def _timed_stage(stage: str, phase_name: Optional[str] = None):
    """Decorator: attribute a function's wall time to a plan stage —
    both the cumulative registry histogram and (when a request trace is
    ambient; runtimes.run copies the context onto pool threads) the
    per-query trace profile.  `phase_name` also makes the call a phase
    span of the reader's table (reader methods only)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            span_ = (args[0]._phase(phase_name) if phase_name
                     else contextlib.nullcontext())
            t0 = time.perf_counter()
            try:
                with span_:
                    return fn(*args, **kwargs)
            finally:
                dt = time.perf_counter() - t0
                _STAGE_SECONDS[stage].observe(dt)
                trace_add(f"stage_{stage}_ms", dt * 1e3)
        return wrapper
    return deco


# segment tables held in memory at once by _prefetch_tables (bounds BOTH
# the row-scan and aggregate paths — including compaction's scan);
# fallback when scan.prefetch_segments is 0/unset
_PREFETCH_SEGMENTS = 4
# rows -> bytes conversion for the legacy cache_max_rows knob: a typical
# engine window is ~4 int32/f32 columns (16B) plus the memo allowance
_CACHE_BYTES_PER_ROW = 32
# the share of the device's reported memory (memory_stats()
# ["bytes_limit"]) that the scan cache's device-decode slices may hold.
# Half: slices are the one thing that should FILL the chip (a resident
# slice is a segment never read, narrowed or uploaded again), but they
# share it with what no account of this reader bounds — a dispatch's
# temporaries (several times its slice at capacity 1,048,576), the
# compiled programs' constants, the downloads in flight of four
# concurrent queries — and with the stack cache and the windows' memos,
# which keep the `cache_bytes` they had.  A share, not a row count:
# what fits is the device's to say (ROADMAP C7).  Per reader, as
# `cache_bytes` is: the data table's is the one a fleet-wide scan fills
# (TSBS cpu-only at scale 1000: twelve slices of one field, 302 MB).
_DEVICE_SLICE_SHARE = 0.5
# fused replay plans kept per reader (weakref-only entries; see
# ParquetReader._replay_cache)
_REPLAY_SLOTS = 8
# Largest stacked round (batch width x capacity, rows) that a fused
# aggregate carries to the device as numpy arguments of ONE call
# (_fused_one_call_jit).  Under it the per-call host cost of the eager
# stack (a device_put a column and window, pads, stacks, four jit
# calls) outweighs what the memoized device columns save, which is
# the upload of 12 B a row; PERF.md section 6 (PR 44) has the two unit
# costs it rests on.
_ONE_CALL_MAX_ROWS = 65_536

# [scan.decode] modes (validated at reader open; docs/example.toml)
DECODE_MODES = ("auto", "device", "host")


class _MeshFallback(Exception):
    """A mesh round declined dispatch for a counted reason — the
    caller re-runs it on the single-chip kernel (the declared mesh
    failure seam, docs/parallel.md)."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


# prep sentinel marking a deferred fused-decode plan in a mesh round's
# item list (host windows carry a real prep tuple, DeviceParts None)
_DECODE_PREP = object()

# guards every window's memo put: memo stores run on worker-pool
# threads, and the byte accounting must not drift (a lost increment
# would let real HBM exceed the scan cache's charged allowance)
_MEMO_LOCK = threading.Lock()


_IOTA_CACHE: dict = {}


def _iota(cap: int) -> np.ndarray:
    """Cached arange per (pow2-bounded, so few distinct) capacity — the
    validity compare runs per window per query and rebuilding the iota
    was measurable on the cold path.  Callers must not mutate.  Benign
    under races: colliding threads store identical arrays."""
    a = _IOTA_CACHE.get(cap)
    if a is None:
        a = np.arange(cap)
        _IOTA_CACHE[cap] = a
    return a


def _memo_store(w, key, value, nbytes: int) -> None:
    """Byte-bounded per-window memo put.  The scan cache charges each
    window MEMO_SLOTS * (capacity*4 + 128) bytes of memo allowance
    (scan_cache.windows_nbytes); this store keeps the REAL bytes held by
    memo values under that allowance — raising one without the other
    would let actual HBM/RAM use exceed the configured cache budget
    (e.g. a dev_cols entry is 12 bytes/row, three "slots" worth).
    A same-key put loses to the entry already stored (identical
    computation by a concurrent query) so bytes are only ever ADDED for
    distinct keys — no overwrite double-count."""
    from horaedb_tpu.storage.scan_cache import MEMO_SLOTS

    budget = MEMO_SLOTS * (w.capacity * 4 + 128)
    if nbytes > budget:
        # an entry larger than the whole allowance (e.g. partial grids
        # for a huge group count) must not bust the accounting — callers
        # just recompute next time
        return
    with _MEMO_LOCK:
        if key in w.memo:
            return
        if len(w.memo) >= MEMO_SLOTS or w.memo_bytes + nbytes > budget:
            w.memo.clear()
            w.memo_bytes = 0
        w.memo[key] = value
        w.memo_bytes += nbytes


@dataclass
class ScanRequest:
    """(ref: storage.rs:65-70)"""

    range: TimeRange
    predicate: Optional[filter_ops.Predicate] = None
    # indexes into the FULL storage schema (user columns + builtins)
    projections: Optional[list[int]] = None


@dataclass
class AggregateSpec:
    """Downsample pushdown: GROUP BY group_col, time(bucket) computed on
    device straight from the merge output — no Arrow materialization and
    no host re-encode on the north-star query path."""

    group_col: str
    ts_col: str
    value_col: str
    range_start: int  # host-time of bucket 0
    bucket_ms: int
    num_buckets: int
    # which aggregates to compute (canonicalized; count always rides
    # along — combining and finalize key on it)
    which: tuple = downsample_ops.ALL_AGGS

    def __post_init__(self):
        self.which = tuple(sorted(set(self.which)))


@dataclass
class SegmentPlan:
    segment_start: int
    ssts: list[SstFile]
    columns: list[str]


@dataclass
class ScanPlan:
    segments: list[SegmentPlan]
    mode: UpdateMode
    predicate: Optional[filter_ops.Predicate]
    keep_builtin: bool
    # pyarrow expression pushed into the Parquet reads (PK-only subtree
    # of `predicate`); the full predicate still applies post-merge
    pushdown: object = None
    # canonical string of the pushed subtree (scan-cache identity)
    pushdown_key: str = ""
    # flattened conjunction of the same pushed subtree for the
    # stats-pruned decode path (None: shape not prunable, use pushdown)
    prune_leaves: Optional[list] = None
    # True when the pushed subtree IS the whole predicate (every leaf a
    # PK leaf in an And shape): the read already filtered exactly these
    # rows, so post-merge re-evaluation is provably a no-op and the
    # window paths skip it (PK leaves cannot interact with last-value
    # dedup; value-column leaves — which can — force this False)
    pushed_complete: bool = False
    # compaction scans set this False: their input SST sets are deleted
    # right after, so caching them only evicts hot query entries
    use_cache: bool = True
    # which worker pool (common.runtimes) carries this plan's CPU work —
    # compaction plans use "compact" so rewrites queue behind each other
    # instead of in front of serving scans (ref: storage.rs:91-104)
    pool: str = "sst"
    # the request's time range (race re-resolution must honor it: a
    # fresh SST in the same segment but outside the requested range
    # must not leak rows into the results)
    range: Optional[TimeRange] = None
    # set by _cached_windows when it routes this plan through the scan
    # pipeline (pipeline_on() AND the has-store-I/O probe passed); the
    # device stage reads it to decide whether aggregation rounds
    # overlap the window feed — one decision per scan, both layers
    # agree (an all-tier-2-resident scan overlapping device rounds
    # with decode measurably LOSES on low-core hosts, same contention
    # as the fetch/decode stages)
    pipeline_active: bool = False
    # set by aggregate_segments when this plan is eligible for the
    # fused device-decode dispatch ([scan.decode]; ops/device_decode.py):
    # the decode stage uploads eligible EncodedSegments' raw encoded
    # buffers and fuses filter + merge-dedup + bucket-aggregate into
    # one jitted program, emitting finished per-segment parts instead
    # of host windows.  None = host decode (row scans, the control)
    decode_spec: Optional["AggregateSpec"] = None
    # set alongside decode_spec on [scan.mesh] plans: the decode stage
    # PLANS the fused dispatch (ops/device_decode.plan_dispatch) but
    # defers the upload — DecodePlans ride the windows lists into the
    # mesh pump, which batches compatible plans into per-round sharded
    # decode programs (_run_mesh_decode_round).  False = each eligible
    # segment uploads and dispatches standalone at decode time
    decode_defer: bool = False
    # set when aggregate_segments routes this plan onto the 2-D scan
    # mesh ([scan.mesh]): window rounds aggregate with the device
    # kernel even where the numpy twin would normally win (CPU
    # backend), so mesh rounds and their per-round fallbacks share one
    # rounding schedule and grids stay byte-identical within a query
    force_xla_agg: bool = False
    # the route ParquetReader.aggregate_route chose for an aggregate
    # over this plan, set where the plan is built (plan_query): the
    # `route` field of the query's scan.plan span
    route: str = ""


class ParquetReader:
    """Builds and executes per-segment merge-scan plans
    (ref: ParquetReader, read.rs:407-494)."""

    def __init__(self, store: ObjectStore, root_path: str,
                 schema: StorageSchema, config: StorageConfig,
                 segment_duration_ms: int, runtimes=None):
        self.store = store
        self.root_path = root_path
        self.schema = schema
        self.config = config
        self.segment_duration_ms = segment_duration_ms
        self.runtimes = runtimes
        # the table's name (the engine roots each table at
        # <root>/<name>): the `table` field and label of the scan's
        # phase spans, so that `resolve`'s scans of the index and tags
        # tables never count into the data table's numbers
        self.table = root_path.rstrip("/").rsplit("/", 1)[-1]
        # optional async callback (segment_start) -> current SstFiles:
        # set by CloudObjectStorage so a STREAMED segment can survive a
        # compaction race mid-segment (see _stream_window_batches) —
        # bulk segments read everything before yielding, so the outer
        # replan covers them
        self.resolve_segment_ssts = None
        from horaedb_tpu.storage.scan_cache import ScanCache

        cache_bytes = (config.scan.cache_max_bytes
                       or config.scan.cache_max_rows * _CACHE_BYTES_PER_ROW)
        # public: consumers that bypass the scan cache (chunked-mode
        # engine LRU) size their own caches off the same budget
        self.cache_budget_bytes = cache_bytes
        # the slices' own budget, from the device (read once, here): a
        # share of what it reports, or `cache_bytes` where the backend
        # reports nothing (XLA-CPU).  A cache turned off stays off for
        # both accounts.  Never the route gate: that stays
        # cache_budget_bytes
        device_limit = device_bytes_limit()
        self.slice_budget_bytes = (
            int(device_limit * _DEVICE_SLICE_SHARE)
            if device_limit and cache_bytes > 0 else cache_bytes)
        self.scan_cache = ScanCache(cache_bytes, self.slice_budget_bytes)
        if device_limit:
            logger.info(
                "scan cache %s: device slices may hold %d B (%.2f of the "
                "device's bytes_limit %d), windows %d B", root_path,
                self.slice_budget_bytes, _DEVICE_SLICE_SHARE, device_limit,
                cache_bytes)
        # flush-stack LRU: stacked (B, cap) aggregation inputs reused by
        # repeat queries over cached windows.  Separately byte-accounted
        # (stacks are far larger than the per-window memo allowance) and
        # LRU-evicted so a changed round composition can't pin dead HBM.
        import threading
        from collections import OrderedDict

        self._stack_cache: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._stack_cache_hits = 0
        self._stack_cache_misses = 0
        # fused replay plans: a completed fused aggregate records its
        # round composition (stack keys + window identities, weakrefs
        # only — no HBM pinned) so an identical repeat query re-runs
        # init -> N accumulates -> finalize in ONE pool dispatch,
        # skipping per-segment prep/memo/np.unique entirely.  Any
        # eviction or SST-set change invalidates by identity check.
        self._replay_cache: "OrderedDict[tuple, dict]" = OrderedDict()
        self._replay_hits = 0
        self._replay_misses = 0
        # tiny device constants (num_buckets, bucket_ms) memoized so a
        # fully-cached query issues literally ZERO host->device
        # transfers — every transfer costs host time per call,
        # whatever its size (how much: not measured)
        self._scalar_cache: dict = {}
        self._stack_cache_bytes = 0
        # live bytes of device-resident mesh top-k score state (the
        # mesh_state ledger account's pull gauge; event-loop owned)
        self._mesh_state_bytes = 0
        # windows live in HOST RAM (the merge runs on host), so the
        # stacks ARE the HBM working set — they get the full budget
        self._stack_cache_max = cache_bytes
        self._stack_cache_lock = threading.Lock()
        # tier 2: host-RAM per-SST encoded parts under the HBM windows
        # cache — an HBM miss rebuilds from host memory, and a changed
        # SST set re-merges incrementally (only missing SSTs fetched).
        # Also owns the per-SST sidecar-missing negative memo.
        from horaedb_tpu.storage.encoded_cache import EncodedSegmentCache

        self.encoded_cache = EncodedSegmentCache(
            config.scan.cache.tier2_max_bytes,
            write_through=config.scan.cache.write_through)
        # combine mode validated at open, not first query (bad TOML
        # must fail the server's boot, not a dashboard's first scan)
        ensure(config.scan.combine.mode in combine_mod.COMBINE_MODES,
               f"unknown [scan.combine] mode "
               f"{config.scan.combine.mode!r}; expected one of "
               f"{combine_mod.COMBINE_MODES}")
        # decode mode validated at open too: bad TOML fails the boot,
        # not a dashboard's first cold scan
        ensure(config.scan.decode.mode in DECODE_MODES,
               f"unknown [scan.decode] mode "
               f"{config.scan.decode.mode!r}; expected one of "
               f"{DECODE_MODES}")
        # delta-summation tier: per-segment aggregate partials keyed by
        # the segment's exact SST set (event-loop owned, like the scan
        # cache) — narrowed/refined dashboard ranges recompute only
        # delta segments (storage/combine.py PartsMemo)
        self.parts_memo = combine_mod.PartsMemo(
            config.scan.combine.memo_max_bytes)
        # high-water of pipeline in-flight host bytes observed by this
        # reader's scans (pipeline.PipelineBudget; /stats "pipeline")
        self._pipeline_high_water = 0
        # near-data routing ([scanagent]): a ScanRouter attached here
        # sends covered segments' aggregate scans to their store-shard
        # agents and folds the returned partials through the normal
        # combine (scanagent/client.py); None = the direct-scan control
        self.scan_router = None
        # the 2-D (time, series) scan mesh ([scan.mesh]): segments
        # shard along `time` (plan-order slot admission), group blocks
        # along `series`, segmented-reduction combine on the mesh —
        # off reproduces the single-chip path exactly (the chaos
        # suite's bit-identity control)
        self.scan_mesh = None
        self._mesh_run_fns: dict = {}
        if config.scan.mesh.enabled:
            from horaedb_tpu.parallel import scan_mesh as build_scan_mesh

            self.scan_mesh = build_scan_mesh(config.scan.mesh.time,
                                             config.scan.mesh.series)
            _MESH_AXIS_DEVICES["time"].set(
                int(self.scan_mesh.shape["time"]))
            _MESH_AXIS_DEVICES["series"].set(
                int(self.scan_mesh.shape["series"]))
        # memory plane: every reader-owned byte budget registers a
        # ledger account (common/memledger.py) tagged with its
        # configured budget; close() deregisters so /debug/memory never
        # serves phantom tables.  Anchored weakly on the reader — the
        # ledger keeps nothing alive.  The pipeline module's process
        # account (pipeline_inflight) must exist the moment a reader
        # does, not at the first pipelined scan:
        from horaedb_tpu.storage import pipeline as _pipeline  # noqa: F401
        self._mem_accounts = [
            # RESIDENT bytes, not the LRU's charged bytes: the LRU
            # charges a worst-case per-window memo ALLOWANCE (budget
            # semantics — resident can never exceed the budget), but
            # the ledger must report what is actually allocated or
            # unattributed goes negative by the unmaterialized slack
            memledger.register(
                f"scan_cache:{root_path}",
                lambda r: r._scan_cache_resident_bytes(), anchor=self,
                kind="scan_cache", budget=cache_bytes, owner=root_path),
            # the cache's other account: device-decode slices are jnp
            # arrays (padded device columns, no memo), so like the
            # stacks they are host RSS on the CPU backend alone
            memledger.register(
                f"scan_cache_device:{root_path}",
                lambda r: r.scan_cache.slice_account.total_bytes,
                anchor=self, kind="scan_cache_device",
                budget=self.slice_budget_bytes, owner=root_path,
                host=jax.default_backend() == "cpu"),
            # stacks are jnp arrays: host RAM on the CPU backend, HBM
            # on accelerators — there they are NOT host RSS (they show
            # under memory_device_bytes) and must not be subtracted
            # from it, or unattributed goes negative by the stack size
            memledger.register(
                f"stack_cache:{root_path}",
                lambda r: r._stack_cache_bytes, anchor=self,
                kind="stack_cache", budget=self._stack_cache_max,
                owner=root_path,
                host=jax.default_backend() == "cpu"),
            memledger.register(
                f"encoded_cache:{root_path}",
                lambda r: r.encoded_cache.total_bytes, anchor=self,
                kind="encoded_cache",
                budget=config.scan.cache.tier2_max_bytes,
                owner=root_path),
            memledger.register(
                f"parts_memo:{root_path}",
                lambda r: r.parts_memo.lru.total_bytes, anchor=self,
                kind="parts_memo",
                budget=config.scan.combine.memo_max_bytes,
                owner=root_path),
            # device-resident mesh top-k score state (selection or
            # compensated additive planes) held for the two-pass
            # ranking's duration; decode round stacks ride the
            # stack_cache account above
            memledger.register(
                f"mesh_state:{root_path}",
                lambda r: r._mesh_state_bytes, anchor=self,
                kind="mesh_state",
                budget=config.scan.mesh.max_grid_bytes,
                owner=root_path,
                host=jax.default_backend() == "cpu"),
        ]

    def close(self) -> None:
        """Release every reader-owned cache tier and deregister ledger
        accounts: a closed table holds ZERO attributable bytes (the
        clear-on-close gauge discipline — scan_cache_bytes{tier=} and
        the ledger's account gauges must read 0 afterwards)."""
        self.drop_hbm_state()
        self.scan_cache.close()
        self.encoded_cache.clear()
        self.parts_memo.lru.clear()
        self._scalar_cache.clear()
        # compiled mesh programs (host-window AND fused-decode): their
        # executables pin device constant buffers; a closed table keeps
        # none
        self._mesh_run_fns.clear()
        if self.scan_mesh is not None:
            # clear-on-close gauge discipline: a closed table must not
            # report a phantom mesh (last-writer semantics: the gauges
            # are process-global, like every axis-shaped gauge here)
            _MESH_AXIS_DEVICES["time"].set(0)
            _MESH_AXIS_DEVICES["series"].set(0)
        for acct in self._mem_accounts:
            memledger.deregister(acct)
        self._mem_accounts = []
        # device-plane clear-on-close: compile/dispatch/transfer
        # families and the per-device high-water marks are process
        # -global like the mesh gauges above — a closed table leaves
        # them zeroed/absent (last-writer semantics)
        deviceprof.profiler.clear()
        memledger.reset_device_high_water()
        clear_phases(self.table)

    def _phase(self, name: str, sync: bool = False, **fields):
        """A phase span of this reader's table (utils/tracing.phase);
        `sync` (no `await` inside) at every site of `scan.dispatch`,
        `scan.d2h` and `scan.combine`: their CPU is read."""
        return phase(name, self.table, sync=sync, **fields)

    def _phased(self, name: str, fn, **fields):
        """`fn` run inside a phase span: for the closures a scan hands
        to a pool."""
        def run(*args):
            with self._phase(name, **fields):
                return fn(*args)
        return run

    def _scan_cache_resident_bytes(self) -> int:
        """Actual bytes the scan cache's WINDOWS hold: column buffers
        at their allocated (capacity-padded) widths plus MATERIALIZED
        memo bytes — the ledger's pull gauge.  Differs from
        scan_cache.total_bytes, which charges the worst-case memo
        allowance up front (eviction must bound the budget; the
        ledger must report residency).  The slices are the
        scan_cache_device account's: charged at what they hold.
        Event-loop owned, like the cache itself."""
        total = 0
        for entry in self.scan_cache.values():
            for w in entry:
                total += sum(int(c.dtype.itemsize) * w.capacity
                             for c in w.columns.values())
                total += int(w.memo_bytes)
        return total

    def _mem_delta_marks(self) -> Optional[list]:
        """Per-trace memory attribution: snapshot this reader's cache
        balances at scan start; _mem_delta_attribute() records the
        deltas as mem_account_delta_<kind> trace counters — a cold
        scan's trace shows WHICH tier its resident bytes landed in.
        Reads the caches' CHARGED totals (integer reads — this runs
        twice per traced query, so the sampler-only resident-bytes
        walk has no place here).  None (no ambient trace / ledger
        disabled) skips the bookwork."""
        if not memledger.enabled or active_trace() is None:
            return None
        return [("scan_cache", self.scan_cache.total_bytes),
                ("scan_cache_device",
                 self.scan_cache.slice_account.total_bytes),
                ("stack_cache", self._stack_cache_bytes),
                ("encoded_cache", self.encoded_cache.total_bytes),
                ("parts_memo", self.parts_memo.lru.total_bytes)]

    def _mem_delta_attribute(self, marks: Optional[list]) -> None:
        if not marks:
            return
        now = dict(self._mem_delta_marks() or ())
        for kind, before in marks:
            delta = now.get(kind, before) - before
            if delta:
                trace_add(f"mem_account_delta_{kind}", delta)

    # ---- plan construction -------------------------------------------------

    def build_plan(self, ssts: list[SstFile], request: ScanRequest,
                   keep_builtin: bool = False,
                   use_cache: bool = True, pool: str = "sst") -> ScanPlan:
        columns = plan_columns(self.schema, request.projections)

        by_segment: dict[int, list[SstFile]] = {}
        for f in ssts:
            by_segment.setdefault(
                segment_of(f, self.segment_duration_ms), []).append(f)
        segments = [
            SegmentPlan(segment_start=seg, ssts=sorted(files, key=lambda f: f.id),
                        columns=columns)
            for seg, files in sorted(by_segment.items())
        ]
        pushdown = None
        pushdown_key = ""
        allowed = set(self.schema.primary_key_names)
        if request.predicate is not None:
            pushdown, pushdown_key = filter_ops.to_arrow_expression_with_key(
                request.predicate, allowed)
        prune_leaves, pushed_complete = parquet_io.conjunct_leaves_ex(
            request.predicate, allowed)
        return ScanPlan(segments=segments, mode=self.schema.update_mode,
                        predicate=request.predicate, keep_builtin=keep_builtin,
                        pushdown=pushdown, pushdown_key=pushdown_key,
                        prune_leaves=prune_leaves,
                        pushed_complete=pushed_complete,
                        use_cache=use_cache, pool=pool, range=request.range)

    # ---- execution ---------------------------------------------------------

    async def execute(self, plan: ScanPlan) -> AsyncIterator[pa.RecordBatch]:
        marks = self._mem_delta_marks()
        seg_iter = self.execute_segments(plan)
        try:
            async for _seg_start, batch in seg_iter:
                if batch is not None:
                    yield batch
        finally:
            # an abandoned consumer must drain the pipeline NOW, not
            # at GC-time async-gen finalization
            await seg_iter.aclose()
            self._mem_delta_attribute(marks)

    async def execute_segments(self, plan: ScanPlan):
        """Like execute(), but yields (segment_start, batch_or_None) —
        callers that must retry after a concurrent compaction (see
        CloudObjectStorage.scan) track completed segments by start time.
        A segment may yield SEVERAL batches (one per merge window) so
        large segments never re-materialize whole on the host, and ends
        with an explicit (segment_start, None) completion marker — only
        that marker makes the segment retry-safe to skip."""
        if plan.mode is not UpdateMode.OVERWRITE:
            # host (Append) path: uncached streaming merge.  Segments
            # over the stream threshold merge window-by-window so the
            # host bound holds for Append tables too (chunked-data
            # tables are typically the largest).
            # aclose the feed DETERMINISTICALLY on any consumer
            # exception/abandonment — otherwise its primed prefetch task
            # only dies at GC time, possibly after the caller has
            # already replanned and started a new scan
            feed = self._segment_feed(plan, plan.segments)
            try:
                async for seg, is_streamed, table, read_s in feed:
                    # cooperative deadline checkpoint: an expired query
                    # aborts between segments, not after a full scan
                    deadline_checkpoint()
                    async for out in self._append_segment(
                            seg, is_streamed, table, read_s, plan):
                        yield out
            finally:
                await feed.aclose()
            return

        windows_iter = self._cached_windows(plan)
        try:
            async for seg, windows, read_s in windows_iter:
                elapsed = 0.0  # decode work only — yields suspend into
                for w in windows:  # the consumer, not scan time
                    # per-window deadline checkpoint (the merge loop's
                    # cooperative cancellation point)
                    deadline_checkpoint()
                    t0 = time.perf_counter()
                    part = await self._run_pool(
                        plan.pool, self._window_to_arrow, w,
                        list(seg.columns), plan)
                    if part is not None and part.num_rows:
                        part = self._strip_builtin(part, plan)
                    elapsed += time.perf_counter() - t0
                    if part is not None and part.num_rows:
                        _ROWS_SCANNED.inc(part.num_rows)
                        yield seg.segment_start, part
                _SCAN_LATENCY.observe(read_s + elapsed)
                # completion marker: consumers mark the segment done now
                yield seg.segment_start, None
        finally:
            await windows_iter.aclose()

    async def _append_segment(self, seg, is_streamed: bool, table,
                              read_s: float, plan: ScanPlan):
        """One Append-mode segment's host merge, streamed or bulk.
        Yields (segment_start, batch) parts then the completion marker."""
        if is_streamed:
            spent = 0.0
            async for batch in self._stream_window_batches(
                    seg, plan, strict_no_replay=True):
                deadline_checkpoint()
                t0 = time.perf_counter()
                part = await self._run_pool(
                    plan.pool, self._merge_segment_table,
                    pa.Table.from_batches([batch]), seg, plan)
                spent += time.perf_counter() - t0
                if part is not None and part.num_rows:
                    _ROWS_SCANNED.inc(part.num_rows)
                    yield seg.segment_start, part
            _SCAN_LATENCY.observe(spent)
            yield seg.segment_start, None  # completion marker
            return
        t0 = time.perf_counter()
        batch = await self._run_pool(
            plan.pool, self._merge_segment_table, table, seg, plan)
        _SCAN_LATENCY.observe(read_s + (time.perf_counter() - t0))
        if batch is not None and batch.num_rows:
            _ROWS_SCANNED.inc(batch.num_rows)
            yield seg.segment_start, batch
        yield seg.segment_start, None  # completion marker

    def _cache_key(self, seg: SegmentPlan, plan: ScanPlan):
        from horaedb_tpu.storage.scan_cache import segment_cache_key

        # A pushdown changes WHICH rows were read pre-merge, so the
        # canonical key of the PUSHED subtree (complete, unlike pyarrow
        # expression str() which elides long isin lists) is part of the
        # cached merge output's identity.  Predicates differing only in
        # their value-column parts share one entry; with no pushdown the
        # read is full and one entry serves every predicate shape.
        return segment_cache_key(
            seg.segment_start, (f.id for f in seg.ssts),
            tuple(seg.columns) + (plan.pushdown_key,))

    # segments whose merges are dispatched but not yet synced: overlaps
    # device merge compute with the NEXT segments' host decode/encode
    _MERGE_LOOKAHEAD = 2

    async def _cached_windows(self, plan: ScanPlan):
        """Per segment, yield (seg, post-merge DeviceBatch windows,
        read_seconds) — from the HBM-resident cache when the segment's
        (SST set, columns, pushdown) is unchanged, else by reading +
        merging (and populating the cache unless the plan opted out).

        Merge programs for up to _MERGE_LOOKAHEAD upcoming segments are
        dispatched before the current segment's run counts are synced,
        so the device pipeline never drains while the host prepares the
        next segment."""
        from collections import deque

        cached: dict[int, list] = {}
        # device-decode plans: segments whose narrowed, padded slice is
        # resident on the device, as this window's DecodePlan over it
        resident: dict[int, device_decode.DecodePlan] = {}
        to_read: list[SegmentPlan] = []
        slice_columns = self._decode_slice_columns(plan)
        with self._phase("scan.windows",
                         segments=len(plan.segments)) as probe:
            for seg in plan.segments:
                if slice_columns is not None:
                    got = self._probe_decode_slice(seg, plan,
                                                   slice_columns)
                    if isinstance(got, device_decode.DecodePlan):
                        resident[id(seg)] = got
                        continue
                    if got is not None:  # provably empty: no dispatch
                        cached[id(seg)] = [got]
                        continue
                windows = (self.scan_cache.get(self._cache_key(seg, plan))
                           if plan.use_cache else None)
                if windows is None:
                    to_read.append(seg)
                else:
                    cached[id(seg)] = windows
            probe.fields["cached"] = len(cached)
            if slice_columns is not None:
                probe.fields["resident"] = len(resident)
        # the resident slices go to the device as ONE pool job, and
        # their parts into `cached`.  Beside segments to read the job
        # is a task that the first resident segment's turn awaits, so
        # that device work still overlaps the reads
        resident_job: Optional[asyncio.Future] = None
        if resident:
            deadline_checkpoint()
            job = self._resident_batch_windows(plan, resident)
            if to_read:
                resident_job = asyncio.ensure_future(job)
                cached.update(dict.fromkeys(resident, resident_job))
            else:
                cached.update(await job)
        if self.pipeline_on() and self._pipeline_has_io(plan, to_read):
            plan.pipeline_active = True
            pipe_iter = self._cached_windows_pipelined(
                plan, cached, to_read, slice_columns)
            try:
                async for out in pipe_iter:
                    yield out
            finally:
                await pipe_iter.aclose()
                await self._abandon(resident_job)
            return

        # the shared _segment_feed owns the streamed/bulk split and the
        # prefetch priming; pump() adds the merge-dispatch LOOKAHEAD on
        # top (bulk merges dispatch ahead of the yield position so the
        # device pipeline never drains).  Encodes stay SERIAL on the
        # pump: running lookahead encodes as concurrent tasks was
        # measured a net loss on low-core hosts (GIL + memory-bandwidth
        # contention with the prefetch deserializes outweighed the
        # overlap; 2-core A/B showed cold +36%).
        feed = self._segment_feed(plan, to_read).__aiter__()
        pending: "deque[tuple[SegmentPlan, str, list, float]]" = deque()
        exhausted = False

        async def pump() -> None:
            nonlocal exhausted
            try:
                fseg, is_streamed, table, read_s = await feed.__anext__()
            except StopAsyncIteration:
                exhausted = True
                return
            if is_streamed:
                # a marker only: the actual streaming happens when this
                # segment reaches the yield position
                pending.append((fseg, "stream", [], 0.0))
                return
            dispatched: list = []
            if table.num_rows:
                dispatched = await self._run_pool(
                    plan.pool, self._dispatch_segment_table, table, plan)
            pending.append((fseg, "bulk", dispatched, read_s))

        try:
            for seg in plan.segments:
                # cooperative deadline checkpoint between segments: a
                # query that ran out of budget stops reading/merging
                # instead of finishing a doomed scan
                deadline_checkpoint()
                if id(seg) in cached:
                    yield seg, await self._cached_entry(cached, seg), 0.0
                    continue
                while len(pending) <= self._MERGE_LOOKAHEAD and not exhausted:
                    await pump()
                read_seg, kind, dispatched, read_s = pending.popleft()
                assert read_seg is seg
                if kind == "stream":
                    dispatched, read_s = \
                        await self._read_streamed_dispatched(seg, plan)
                windows = await self._run_pool(
                    plan.pool, self._finalize_windows, dispatched)
                if plan.use_cache and self._cacheable_windows(windows):
                    self.scan_cache.put(self._cache_key(seg, plan),
                                        windows)
                self._admit_decode_slice(seg, slice_columns, windows)
                yield seg, windows, read_s
        finally:
            await feed.aclose()
            await self._abandon(resident_job)

    @staticmethod
    async def _abandon(job: Optional[asyncio.Future]) -> None:
        """A scan that ends, finished or abandoned, leaves no task
        behind it."""
        if job is not None:
            job.cancel()
            await asyncio.gather(job, return_exceptions=True)

    @staticmethod
    async def _cached_entry(cached: dict, seg: SegmentPlan) -> list:
        """A cached segment's windows; for one whose resident slice
        went out with the plan's batch, that job's part of it."""
        windows = cached[id(seg)]
        if isinstance(windows, asyncio.Future):
            windows = (await windows)[id(seg)]
        return windows

    # ---- device-decode slices in the scan cache ----------------------------

    def _decode_slice_columns(self, plan: ScanPlan) -> Optional[tuple]:
        """The `columns` part of a device-decode plan's scan-cache keys
        (ops/device_decode.SegmentSlice): the spec's columns and the
        key leaves' token, one per plan — or None where the plan's
        segments are neither probed nor admitted: no device-decode
        plan, one that opted out of caching, or one whose dispatch is
        deferred to the mesh rounds, which group host DecodePlans
        (counted outcome="bypass")."""
        spec = plan.decode_spec
        if spec is None:
            return None
        if plan.decode_defer or not plan.use_cache:
            device_decode.note_resident("bypass", len(plan.segments))
            return None
        return ("decode", spec.group_col, spec.ts_col, spec.value_col) \
            + device_decode.key_leaves_token(plan.prune_leaves)

    def _decode_slice_key(self, seg: SegmentPlan, slice_columns: tuple):
        from horaedb_tpu.storage.scan_cache import segment_cache_key

        # the window is NOT in the key: range leaves stay with the
        # device, so what a segment narrows to is the same rows for
        # every window over the same key leaves.  The SST ids are: a
        # write or a compaction changes them, and misses
        return segment_cache_key(
            seg.segment_start, (f.id for f in seg.ssts),
            tuple(seg.columns) + slice_columns)

    def _probe_decode_slice(self, seg: SegmentPlan, plan: ScanPlan,
                            slice_columns: tuple):
        """Loop-side probe for one segment of a device-decode plan: the
        window's DecodePlan over the resident slice (a hit), a
        DevicePart where the window's own leaves provably match
        nothing, or None (a miss: the segment is read)."""
        entry = self.scan_cache.get_slice(
            self._decode_slice_key(seg, slice_columns))
        got = None
        if entry is not None:
            got = device_decode.plan_window(
                entry, plan.decode_spec, plan.prune_leaves,
                self._window_grid_width(plan.decode_spec))
            if isinstance(got, str):
                got = None  # the miss path counts the fallback
        device_decode.note_resident("miss" if got is None else "hit")
        return got

    async def _resident_batch_windows(self, plan: ScanPlan,
                                      resident: dict) -> dict:
        """The plan's resident slices through ONE pool job: their
        windows lists, by segment as `resident` is."""
        parts = await self._run_pool(
            plan.pool, self._resident_batch_parts,
            list(resident.values()))
        return {key: [part] for key, part in zip(resident, parts)}

    def _resident_batch_parts(self, plans: list) -> list:
        """Pool-side: every hit of a plan dispatched (one `scan.dispatch`
        phase: a batched call a group of slices that may share a
        program, a call of its own for one left over), then downloaded
        and shaped, one DevicePart a plan."""
        with self._phase("scan.dispatch", sync=True, h2d_bytes=0,
                         slices=len(plans)):
            issued = device_decode.dispatch_resident(plans, self.table)
        return device_decode.finalize_resident(issued)

    def _admit_decode_slice(self, seg: SegmentPlan,
                            slice_columns: Optional[tuple],
                            windows: list) -> None:
        """Loop-side admission of what a miss uploaded (the arrays
        exist anyway: no copy).  Only a bulk read's one dispatch
        carries a slice, and only where the segment held every row of
        its SSTs (EncodedSegment.whole)."""
        if slice_columns is None or len(windows) != 1:
            return
        part, = windows
        if isinstance(part, device_decode.DevicePart) \
                and part.resident is not None:
            seg_slice, part.resident = part.resident, None
            self.scan_cache.put_slice(
                self._decode_slice_key(seg, slice_columns), seg_slice)

    def pipeline_on(self) -> bool:
        """Whether OVERWRITE cold scans run through the bounded
        producer/consumer pipeline (storage/pipeline.py);
        [scan.pipeline] enabled = false reproduces the pre-pipeline
        pump exactly."""
        return self.config.scan.pipeline.enabled

    def _pipeline_has_io(self, plan: ScanPlan, to_read: list) -> bool:
        """Whether pipelining this scan can pay for itself: the
        pipeline exists to hide object-store latency behind decode and
        device work, so a scan whose every bulk segment is already
        tier-2 resident (zero store I/O — the post-flush / warm-cache
        regime) runs the sequential pump instead.  On low-core hosts
        the stages' concurrency measurably INFLATES the same CPU work
        (GIL + XLA intra-op contention: tier2-cold 56-segment A/B
        showed encode_merge 2.8x and device rounds 2.3x slower wall
        under overlap, 0.7x end to end) — with no latency left to hide
        there is nothing to win it back.  Streamed segments read the
        store incrementally and any non-resident bulk segment fetches
        it, so either makes the pipeline worthwhile.  The probe is the
        cache's stats-free peek — it must not bump LRU recency or
        hit/miss telemetry (the real reads that follow do that)."""
        if not self._sidecar_plan_ok(plan):
            return bool(to_read)  # every read is a store read
        leaf_cols = {lf.column for lf in plan.prune_leaves or []}

        def resident(seg: SegmentPlan) -> bool:
            if self.encoded_cache.is_assembly_failed(
                    frozenset(f.id for f in seg.ssts)):
                return False
            want = set(seg.columns) | leaf_cols
            return all(self.encoded_cache.peek(f.id, want)
                       for f in seg.ssts)

        return any(self._stream_segment(seg) or not resident(seg)
                   for seg in to_read)

    async def _cached_windows_pipelined(self, plan: ScanPlan,
                                        cached: dict, to_read: list,
                                        slice_columns: Optional[tuple]):
        """Pipelined twin of the pump below: fetch and decode/merge run
        as background stages (storage/pipeline.py) while this consumer
        — the device stage's doorstep — yields segments in plan order.
        Same outputs, same cache puts, same error positions; only the
        schedule differs (tests/test_pipeline.py asserts
        bit-identically)."""
        from horaedb_tpu.storage.pipeline import ScanPipeline

        pipe = ScanPipeline(self, plan, to_read)
        try:
            for seg in plan.segments:
                # cooperative deadline checkpoint between segments,
                # same position as the pump's
                deadline_checkpoint()
                if id(seg) in cached:
                    yield seg, await self._cached_entry(cached, seg), 0.0
                    continue
                got, windows, read_s = await pipe.next_segment()
                assert got is seg
                if plan.use_cache and self._cacheable_windows(windows):
                    self.scan_cache.put(self._cache_key(seg, plan),
                                        windows)
                self._admit_decode_slice(seg, slice_columns, windows)
                yield seg, windows, read_s
        finally:
            # deterministic teardown: cancels the stage tasks and
            # AWAITS them, draining any in-flight pool job before the
            # caller proceeds to table/engine teardown
            await pipe.aclose()

    async def _read_streamed_dispatched(self, seg: SegmentPlan,
                                        plan: ScanPlan):
        """One streamed segment's windows, dispatched (pre-finalize):
        sidecar stream first, whole-segment parquet-stream fallback.
        Returns (dispatched, read_seconds) — shared by the sequential
        pump and the pipeline's decode stage so the two cannot
        drift."""
        t0 = time.perf_counter()
        dispatched: list = []
        es_iter = await self._open_sidecar_stream(seg, plan)
        if es_iter is not None:
            try:
                async for es in es_iter:
                    dispatched.extend(await self._run_pool(
                        plan.pool, self._dispatch_segment_table, es,
                        plan))
            except Exception as exc:  # noqa: BLE001
                # nothing has been yielded for this segment yet
                # (windows buffer here), so a clean whole-segment
                # fallback is safe
                logger.warning(
                    "sidecar stream failed for segment %s (%s); "
                    "falling back to parquet", seg.segment_start, exc)
                dispatched = []
                es_iter = None
        if es_iter is None:
            async for batch in self._stream_window_batches(seg, plan):
                dispatched.extend(await self._run_pool(
                    plan.pool, self._dispatch_merged_windows, batch))
        return dispatched, time.perf_counter() - t0

    def _dispatch_segment_table(self, table, plan: "ScanPlan" = None
                                ) -> list:
        """Pool-side encode+merge dispatch of one bulk segment's read
        result (pa.Table or sidecar.EncodedSegment) — the ONE body
        shared by the sequential pump and the pipeline's decode stage
        so the two cannot drift.

        Device-decode-routed plans (plan.decode_spec set) short-circuit
        here: the segment's ENCODED buffers, narrowed to the rows its
        Eq/In leaves admit, upload raw and one fused program does
        filter + merge-dedup + bucket-aggregate (ops/device_decode.py)
        — the decode pool dispatch shrinks to a mask, a memcpy-shaped
        pad and an upload.  Per-segment ineligibility falls
        back to the host path with its reason counted, resolving any
        deferred leaf mask first."""
        if isinstance(table, sidecar.EncodedSegment):
            es = table
            if plan is not None and plan.decode_spec is not None:
                disp = self._dispatch_device_decode(es, plan)
                if disp is not None:
                    return disp
                es = sidecar.apply_leaves_host(es)
            elif es.pending_leaves is not None:
                es = sidecar.apply_leaves_host(es)
            return self._dispatch_encoded_windows(es)
        if plan is not None and plan.decode_spec is not None:
            device_decode.note_fallback("parquet")
        batch = table.combine_chunks().to_batches()[0]
        return self._dispatch_merged_windows(batch)

    def _dispatch_device_decode(self, es: "sidecar.EncodedSegment",
                                plan: "ScanPlan") -> Optional[list]:
        """Dispatch one EncodedSegment through the fused device-decode
        program; None (with the reason counted) when this segment's
        layout can't ride it — the caller falls back to host decode."""
        spec = plan.decode_spec
        leaves = (es.pending_leaves if es.pending_leaves is not None
                  else [])
        with self._phase("scan.group_prep", rows=es.n):
            got = device_decode.plan_dispatch(
                es, spec, pk_names=self._pk_names_in(list(es.names)),
                seq_name=SEQ_COLUMN_NAME, leaves=leaves,
                max_bytes=self.config.scan.decode.max_upload_bytes,
                width=self._window_grid_width(spec),
                pad_capacity=encode.pad_capacity)
        if isinstance(got, str):
            device_decode.note_fallback(got)
            return None
        if isinstance(got, device_decode.DecodePlan) \
                and not plan.decode_defer:
            with self._phase("scan.dispatch", sync=True, h2d_bytes=got.cap * 4
                             * len(got.upload_names)):
                got = device_decode.execute_plan(got, self.table)
        return [got]

    def _decode_segment_windows(self, table, plan: ScanPlan) -> list:
        """The pipeline's decode stage body, one pool dispatch per
        segment: encode + k-way merge + window planning + finalize
        fused — no intermediate hand-back to the event loop between
        them.  `table` is a pa.Table or sidecar.EncodedSegment."""
        return self._finalize_windows(
            self._dispatch_segment_table(table, plan))

    async def _segment_feed(self, plan: ScanPlan,
                            segments: list[SegmentPlan]):
        """Shared streamed/bulk split: yields (seg, is_streamed,
        table_or_None, read_s) in segment order.  The bulk prefetch
        pipeline is primed immediately so object-store reads overlap any
        streamed segment processed before them."""
        streamed = {id(s) for s in segments if self._stream_segment(s)}
        bulk = [s for s in segments if id(s) not in streamed]
        read_iter = self._prefetch_tables(bulk, plan).__aiter__()
        primed: Optional[asyncio.Task] = (
            asyncio.ensure_future(read_iter.__anext__()) if bulk else None)
        try:
            for seg in segments:
                if id(seg) in streamed:
                    yield seg, True, None, 0.0
                    continue
                if primed is not None:
                    step, primed = primed, None
                    read_seg, table, read_s = await step
                else:
                    read_seg, table, read_s = await read_iter.__anext__()
                assert read_seg is seg
                yield seg, False, table, read_s
        finally:
            if primed is not None:
                primed.cancel()
                try:
                    await primed
                except (asyncio.CancelledError, Exception):
                    pass
            # deterministic teardown of the prefetch generator: its
            # eagerly-created SST read tasks must be cancelled NOW, not
            # at GC-time finalization
            await read_iter.aclose()

    async def _prefetch_tables(self, segments: list[SegmentPlan],
                               plan: ScanPlan):
        """Bounded segment prefetch shared by the row and aggregate paths:
        object-store reads overlap downstream device work while at most
        scan.prefetch_segments tables are in memory (the permit is
        released only after the consumer finishes with a segment).
        Yields (segment, table, read_seconds)."""
        sem = asyncio.Semaphore(
            max(1, self.config.scan.prefetch_segments
                or _PREFETCH_SEGMENTS))

        async def read(seg: SegmentPlan):
            await sem.acquire()
            return await self._read_segment_any(seg, plan)

        tasks = [asyncio.create_task(read(seg)) for seg in segments]
        try:
            for seg, task in zip(segments, tasks):
                table, read_s = await task
                try:
                    yield seg, table, read_s
                finally:
                    sem.release()
        finally:
            for task in tasks:
                task.cancel()
            # drain, don't just cancel: a read whose pool job (sidecar
            # deserialize, parquet decode) is mid-flight only finishes
            # after the job does — awaiting here keeps cancelled-scan
            # teardown from racing in-flight decode work (the PR 3
            # discipline), and retrieves failed reads' exceptions
            await asyncio.gather(*tasks, return_exceptions=True)

    async def _read_segment_any(self, seg: SegmentPlan, plan: ScanPlan,
                                runner=None):
        """One bulk segment's cold read — tier-2/sidecar serve resident
        parts and fetch only missing SSTs; parquet is the fallback —
        with read-stage attribution.  Returns (table, read_seconds);
        `table` is a pa.Table or sidecar.EncodedSegment.  Shared by the
        sequential prefetch and the pipeline's fetch stage (which
        bounds the CPU-side deserialize concurrency via `runner`)."""
        t0 = time.perf_counter()
        table = None
        stage = "sidecar_read"
        with self._phase("scan.windows", segment=seg.segment_start):
            if self._sidecar_plan_ok(plan):
                table = await self._read_segment_encoded(seg, plan,
                                                         runner=runner)
            if table is None:
                stage = "parquet_read"
                table = await self._read_segment_table(
                    seg, plan.pushdown, pool=plan.pool,
                    leaves=plan.prune_leaves)
        read_s = time.perf_counter() - t0
        _STAGE_SECONDS[stage].observe(read_s)
        _STAGE_ROWS[stage].inc(table.num_rows)
        _STAGE_BYTES[stage].inc(table.nbytes)
        trace_add(f"stage_{stage}_ms", read_s * 1e3)
        trace_add(f"stage_{stage}_rows", table.num_rows)
        trace_add(f"stage_{stage}_bytes", table.nbytes)
        # tenant scan-byte budget: charged where the stage bytes are
        # attributed, observed at the deadline checkpoints
        charge_scan_bytes(table.nbytes)
        return table, read_s

    def _sidecar_plan_ok(self, plan: ScanPlan) -> bool:
        """Whether this plan may serve bulk segments from device-layout
        sidecars: OVERWRITE merge only (Append's BytesMerge needs exact
        Arrow bytes), and the pushdown — when present — must have a leaf
        -conjunction form the sidecar path can evaluate host-side."""
        if not self.config.scan.use_sidecar:
            return False
        if plan.mode is not UpdateMode.OVERWRITE:
            return False
        return plan.pushdown is None or plan.prune_leaves is not None

    def _resident_segment_parts(self, seg: SegmentPlan,
                                plan: ScanPlan) -> Optional[list]:
        """Event-loop-side tier-2 residency probe: every SST's encoded
        part for this plan's column set, straight from the cache — or
        None when any part is missing (or a negative memo says the
        sidecar path is doomed), in which case the full fetch path
        decides between store reads and the parquet fallback.

        The pipeline's fetch stage uses this so ALL-RESIDENT segments
        never dispatch a pool job from fetch: on a 2-core host, N
        in-flight fetches each racing an assemble job starved the
        decode/device stages the consumer was actually waiting on
        (priority inversion measured as tier2-cold 0.74x vs the
        sequential pump) — resident segments instead assemble inside
        the decode stage's one serial pool dispatch."""
        if not self._sidecar_plan_ok(plan):
            return None
        if any(self.encoded_cache.is_missing(f.id) for f in seg.ssts):
            return None
        if self.encoded_cache.is_assembly_failed(
                frozenset(f.id for f in seg.ssts)):
            return None
        want = set(seg.columns) | {lf.column
                                   for lf in plan.prune_leaves or []}
        if not all(self.encoded_cache.peek(f.id, want) for f in seg.ssts):
            # the full fetch path probes again and counts the misses:
            # counted here too, every miss would read as two
            return None
        return [self.encoded_cache.get(f.id, want) for f in seg.ssts]

    @staticmethod
    def _parts_whole(seg: SegmentPlan, parts: list) -> bool:
        """Whether the (cols, n) parts hold every row of the segment's
        SSTs: the test tier 2 applies before it keeps a part.  A
        block-pruned load fails it — its blocks were chosen by the
        plan's range leaf too, so what a device-decode dispatch
        narrows it to belongs to one window and may not be kept under
        a key that holds none (EncodedSegment.whole)."""
        return all(n == f.meta.num_rows
                   for (_cols, n), f in zip(parts, seg.ssts))

    def _assemble_resident_segment(self, seg: SegmentPlan, parts: list,
                                   plan: ScanPlan
                                   ) -> Optional[sidecar.EncodedSegment]:
        """Pool-side assemble of tier-2-resident parts with the same
        stage attribution the fetch path gives an assembled segment.
        None = assembly failed (the CALLER memoizes the composition on
        the event loop and falls back to parquet — the cache's negative
        memos are loop-owned)."""
        t0 = time.perf_counter()
        # device-decode plans defer the leaf mask to the dispatch, which
        # narrows by the Eq/In leaves on host and leaves the rest to the
        # device (see _read_segment_encoded)
        defer = plan.decode_spec is not None
        try:
            with self._phase("scan.windows", segment=seg.segment_start):
                es = sidecar.assemble_parts(
                    parts, list(seg.columns),
                    None if defer else plan.prune_leaves)
        except Exception as exc:  # noqa: BLE001 — cache read only
            logger.warning("sidecar assembly raised for segment %s: %s",
                           seg.segment_start, exc)
            es = None
        if es is None:
            return None
        if defer:
            es.pending_leaves = list(plan.prune_leaves or [])
            es.whole = self._parts_whole(seg, parts)
        read_s = time.perf_counter() - t0
        _STAGE_SECONDS["sidecar_read"].observe(read_s)
        _STAGE_ROWS["sidecar_read"].inc(es.n)
        _STAGE_BYTES["sidecar_read"].inc(es.nbytes)
        trace_add("stage_sidecar_read_ms", read_s * 1e3)
        trace_add("stage_sidecar_read_rows", es.n)
        trace_add("stage_sidecar_read_bytes", es.nbytes)
        charge_scan_bytes(es.nbytes)
        return es

    async def _read_segment_encoded(self, seg: SegmentPlan, plan: ScanPlan,
                                    runner=None
                                    ) -> Optional[sidecar.EncodedSegment]:
        """Segment read that never touches parquet: serve each SST's
        encoded part from tier 2 when resident, fetch only the missing
        SSTs' sidecars, and assemble filtered, concatenated encoded
        columns.  This is the incremental re-merge: after a flush (one
        new small SST in an otherwise-unchanged segment) only that SST
        crosses the wire — and with write-through admission not even
        that.  None (→ parquet fallback) when any SST lacks a valid
        sidecar.  `runner` overrides the pool dispatch for the
        CPU-bound deserialize/assemble steps (the pipeline bounds
        fetch-stage CPU concurrency through it)."""
        if any(self.encoded_cache.is_missing(f.id) for f in seg.ssts):
            return None  # known-missing sidecar: skip the GETs entirely
        seg_ids = frozenset(f.id for f in seg.ssts)
        if self.encoded_cache.is_assembly_failed(seg_ids):
            return None  # this exact composition is known unassemblable
        leaves = plan.prune_leaves
        want = set(seg.columns) | {lf.column for lf in leaves or []}

        if runner is None:
            def runner(fn, *args):  # CPU-bound deserialize off the loop
                return self._run_pool(plan.pool, fn, *args)

        parts: list = [None] * len(seg.ssts)
        fetch: list[tuple[int, SstFile]] = []
        for i, f in enumerate(seg.ssts):
            part = self.encoded_cache.get(f.id, want)
            if part is None:
                fetch.append((i, f))
            else:
                parts[i] = part
        if fetch and len(fetch) < len(seg.ssts):
            _INCR_REMERGE.inc()
        # per-SST GETs overlap WITHIN the segment (one gather), and the
        # prefetch pipeline overlaps segments on top
        got = await asyncio.gather(*(
            sidecar.load_sst_encoded(
                self.store, sidecar.sidecar_path(self.root_path, f.id),
                want, leaves, runner=runner,
                footers=self.encoded_cache, sst_id=f.id)
            for _i, f in fetch), return_exceptions=True)
        for (i, f), res in zip(fetch, got):
            if isinstance(res, NotFoundError):
                # permanent for this id (SSTs/ids are immutable and the
                # sidecar is written before the SST becomes visible):
                # memo the miss so later cold scans of this segment
                # don't re-fetch the siblings' blobs just to fall back
                self.encoded_cache.mark_missing(f.id)
                return None
            if isinstance(res, BaseException):
                # transient store failure: the sidecar is a cache — fall
                # back to the authoritative parquet, never fail the scan
                logger.warning("sidecar fetch failed for sst %s: %s",
                               f.id, res)
                return None
            if res is None:
                self.encoded_cache.mark_missing(f.id)
                logger.warning("invalid sidecar for sst %s; using "
                               "parquet", f.id)
                return None
            parts[i] = res
            # only COMPLETE parts are cacheable: a block-pruned load
            # returned a row subset tied to this plan's leaves
            if res[1] == f.meta.num_rows:
                self.encoded_cache.put(f.id, res[0], res[1])
        # device-decode plans DEFER the leaf mask to the dispatch
        # (ops/device_decode.plan_dispatch): there the host narrows the
        # segment by the conjunction's Eq/In leaves where that puts it
        # in a smaller capacity bucket (the device pays per row handed
        # to it), and the fused program evaluates the whole conjunction
        # in encoded space on whatever uploads — range leaves always on
        # the device, so capacities follow the keys a query names, not
        # its window.  A per-segment fallback resolves pending leaves
        # host-side (sidecar.apply_leaves_host)
        defer = plan.decode_spec is not None
        try:
            es = await runner(sidecar.assemble_parts, parts,
                              list(seg.columns),
                              None if defer else leaves)
        except Exception as exc:  # noqa: BLE001 — cache read only
            # a part that parses but is internally inconsistent can blow
            # up deep in eval/concat; the contract is fallback, not
            # failure
            logger.warning("sidecar assembly raised for segment %s: %s",
                           seg.segment_start, exc)
            es = None
        if es is not None and defer:
            es.pending_leaves = list(leaves or [])
            es.whole = self._parts_whole(seg, parts)
        if es is None:
            # cross-SST assembly failed (e.g. an irreconcilable column
            # type across parts).  Do NOT memoize the member SSTs as
            # sidecar-missing — each part deserialized fine on its own,
            # and the same ids may assemble cleanly in other
            # compositions (the old whole-set memo permanently disabled
            # every valid sibling).  Memoize the COMPOSITION instead,
            # so repeat cold scans of this unchanged segment skip the
            # doomed sidecar GETs; any flush/compaction changes the set
            # and retries naturally.
            self.encoded_cache.mark_assembly_failed(seg_ids)
            logger.warning("sidecar assembly failed for segment %s; "
                           "using parquet", seg.segment_start)
        return es

    async def _open_sidecar_stream(self, seg: SegmentPlan, plan: ScanPlan):
        """Streamed-segment windows straight from sidecars: PK-value
        -range windows planned from per-block stats, each window loaded
        via the pruned loader with synthetic range leaves (see
        sidecar.SstStreamSession / plan_stream_windows) — no parquet
        two-pass, no Arrow.  Returns an async generator of
        EncodedSegments, or None when any SST lacks a plannable sidecar
        (the parquet streamer serves the segment instead)."""
        if not self._sidecar_plan_ok(plan):
            return None
        if any(self.encoded_cache.is_missing(f.id) for f in seg.ssts):
            return None
        leaves = plan.prune_leaves or []
        want = set(seg.columns) | {lf.column for lf in leaves}

        def runner(fn, *args):
            return self._run_pool(plan.pool, fn, *args)

        got = await asyncio.gather(*(
            sidecar.SstStreamSession.open(
                self.store, sidecar.sidecar_path(self.root_path, f.id),
                want, runner=runner)
            for f in seg.ssts), return_exceptions=True)
        sessions = []
        for f, res in zip(seg.ssts, got):
            if isinstance(res, NotFoundError) or res is None:
                # permanent per immutable id — same memo as the bulk
                # path, so later streamed scans skip the probes
                self.encoded_cache.mark_missing(f.id)
                return None
            if isinstance(res, BaseException):
                logger.warning("sidecar stream open failed for sst "
                               "%s: %s", f.id, res)
                return None
            sessions.append(res)
        planned = await sidecar.plan_stream_windows(
            sessions, self._pk_names_in(list(seg.columns)),
            self.config.scan.max_window_rows)
        if planned is None:
            return None
        part_col, ranges = planned

        async def gen():
            rows = nbytes = 0
            for lo, hi in ranges:
                wleaves = list(leaves)
                if lo is not None:
                    wleaves.append(filter_ops.Ge(part_col, lo))
                if hi is not None:
                    wleaves.append(filter_ops.Lt(part_col, hi))
                parts = await asyncio.gather(*(
                    s.load_window(wleaves) for s in sessions))
                if any(p is None for p in parts):
                    raise Error("sidecar stream window failed")
                # device-decode plans defer the exact window mask to
                # the fused dispatch — the synthetic range leaves keep
                # windows exactly disjoint there, same as the host mask
                defer = plan.decode_spec is not None
                es = await self._run_pool(
                    plan.pool, sidecar.assemble_parts, list(parts),
                    list(seg.columns), None if defer else wleaves)
                if es is None:
                    raise Error("sidecar stream assembly failed")
                if defer:
                    es.pending_leaves = list(wleaves)
                if es.n:
                    rows += es.n
                    nbytes += es.nbytes
                    yield es
            # counters commit only on a COMPLETE stream: a mid-stream
            # failure re-serves the segment via parquet, which would
            # otherwise double-count the already-yielded windows
            _STAGE_ROWS["sidecar_read"].inc(rows)
            _STAGE_BYTES["sidecar_read"].inc(nbytes)
            trace_add("stage_sidecar_read_rows", rows)
            trace_add("stage_sidecar_read_bytes", nbytes)
            charge_scan_bytes(nbytes)

        return gen()

    def drop_hbm_state(self) -> None:
        """Evict everything HBM-RESIDENT that derives from cached
        windows — round stacks, fused-replay plans, per-window memos
        (device column copies, aggregation grids) — while KEEPING the
        post-merge windows themselves, which live in host RAM.  This is
        the 'HBM evicted' rung of the cache ladder: the next query
        re-stacks/re-uploads from host windows instead of re-reading
        and re-merging.  (close() and tests only; production eviction
        is the LRUs' own.)"""
        with self._stack_cache_lock:
            # includes the mesh decode round stacks — the fused path's
            # uploaded (time, capacity) column matrices share this LRU
            self._stack_cache.clear()
            self._stack_cache_bytes = 0
        self._replay_cache.clear()
        # tiny device scalars (num_buckets, bucket_ms) are HBM too on
        # accelerators; re-uploading them is part of 'HBM evicted'
        self._scalar_cache.clear()
        # device-decode slices ARE device arrays: dropped whole (the
        # next query reads, narrows and uploads again)
        self.scan_cache.drop_slices()
        with _MEMO_LOCK:
            for windows in self.scan_cache.values():
                for w in windows:
                    w.memo.clear()
                    w.memo_bytes = 0

    def cache_stats(self) -> dict:
        """The /stats cache section: every reader-owned cache tier's
        residency and effectiveness, one dict per tier."""
        accounts = self.scan_cache.account_stats()
        slices = self.scan_cache.slice_account
        return {
            "scan_cache": {
                # both accounts summed; `max_bytes` is the windows'
                # budget (and the route gate's number)
                "entries": sum(a["entries"] for a in accounts.values()),
                "bytes": sum(a["bytes"] for a in accounts.values()),
                "max_bytes": self.scan_cache.max_bytes,
                "hits": self.scan_cache.hits + slices.hits,
                "misses": self.scan_cache.misses + slices.misses,
                # of those entries and bytes, the device-decode slices
                # resident on the device (ops/device_decode.py)
                "decode_slices": accounts["slice"]["entries"],
                "decode_slice_bytes": accounts["slice"]["bytes"],
                # budget, bytes, entries, evicted, declined of each
                "accounts": accounts,
            },
            "encoded_cache": self.encoded_cache.stats(),
            "parts_memo": self.parts_memo.stats(),
            "pipeline": {
                "enabled": self.pipeline_on(),
                "depth": self.config.scan.pipeline.depth,
                "inflight_bytes": self.config.scan.pipeline.inflight_bytes,
                "high_water_bytes": self._pipeline_high_water,
            },
            "decode": {
                "mode": self.config.scan.decode.mode,
                "resolved": self._decode_mode(),
                "max_upload_bytes":
                    self.config.scan.decode.max_upload_bytes,
            },
            "mesh": self.mesh_stats(),
            "stack_cache": {
                "entries": len(self._stack_cache),
                "bytes": self._stack_cache_bytes,
                "max_bytes": self._stack_cache_max,
                "hits": self._stack_cache_hits,
                "misses": self._stack_cache_misses,
            },
        }

    def mesh_stats(self) -> dict:
        """The /stats mesh section: axis shape, round/part volume, the
        egress counter the top-k bound is asserted against, and every
        counted fallback reason (docs/parallel.md)."""
        from horaedb_tpu.storage import pipeline as pipeline_mod

        shape = None
        if self.scan_mesh is not None:
            shape = {"time": int(self.scan_mesh.shape["time"]),
                     "series": int(self.scan_mesh.shape["series"])}
        return {
            "enabled": self.scan_mesh is not None,
            "shape": shape,
            "rounds": int(_MESH_ROUNDS.value),
            "parts": int(_MESH_PARTS.value),
            "part_cells": int(_MESH_PART_CELLS.value),
            "score_cells": int(_MESH_SCORE_CELLS.value),
            "topk_served": int(_MESH_TOPK.value),
            "fallbacks": {r: int(c.value)
                          for r, c in _MESH_FALLBACK_CHILDREN.items()
                          if c.value},
            "stalls": pipeline_mod.mesh_stall_counts(),
        }

    async def _read_segment_table(self, seg: SegmentPlan,
                                  pushdown=None,
                                  pool: str = "sst",
                                  leaves: Optional[list] = None) -> pa.Table:
        tables = await asyncio.gather(*(
            parquet_io.read_sst(self.store, sst_path(self.root_path, f.id),
                                columns=seg.columns, filters=pushdown,
                                runtimes=self.runtimes, pool=pool,
                                leaves=leaves,
                                # manifest size: big SSTs stream into a
                                # file-backed mmap instead of buffering
                                # whole in RSS (get_stream)
                                size_hint=f.meta.size)
            for f in seg.ssts
        ))
        return pa.concat_tables(tables)

    async def _run_pool(self, pool: str, fn, *args, **kwargs):
        """CPU work (parquet codec, host merge, numpy prep, device
        dispatch/sync) runs on a named worker pool, never on the event
        loop (ref: dedicated runtimes, storage.rs:91-104)."""
        return await parquet_io._run(self.runtimes, pool, fn, *args,
                                     **kwargs)

    def _strip_builtin(self, batch: Optional[pa.RecordBatch],
                       plan: ScanPlan) -> Optional[pa.RecordBatch]:
        """Drop builtin columns unless the plan keeps them — the single
        home for this rule across every row path."""
        if batch is None or plan.keep_builtin:
            return batch
        keep = [c for c in batch.schema.names
                if not self.schema.is_builtin_name(c)]
        return batch.select(keep)

    def _combine_and_strip(self, parts: list[pa.RecordBatch],
                           plan: ScanPlan) -> Optional[pa.RecordBatch]:
        """Concatenate per-window outputs and drop builtin columns unless
        the plan keeps them."""
        if not parts:
            return None
        batch = (parts[0] if len(parts) == 1 else
                 pa.Table.from_batches(parts).combine_chunks().to_batches()[0])
        return self._strip_builtin(batch, plan)

    def _merge_segment_table(self, table: pa.Table, seg: SegmentPlan,
                             plan: ScanPlan) -> Optional[pa.RecordBatch]:
        """Host (Append/BytesMerge) merge of one segment's table, with
        the same PK-range windowing as the device path when the segment
        exceeds the window budget (sort/merge work stays bounded)."""
        if table.num_rows == 0:
            return None
        batch = table.combine_chunks().to_batches()[0]
        window = self.config.scan.max_window_rows
        if batch.num_rows <= window:
            return self._strip_builtin(self._merge_on_host(batch, plan),
                                       plan)
        pk1 = batch.column(batch.schema.names.index(
            self._pk_names_in(batch.schema.names)[0]))
        # dense value-order ranks straight from Arrow (same comparator the
        # merge sort uses); cross-window order then follows value order
        ranks = np.asarray(pa.compute.rank(pk1, sort_keys="ascending",
                                           tiebreaker="dense"))
        parts = []
        for sel in _plan_pk_windows(ranks, window):
            part = self._merge_on_host(batch.take(pa.array(sel)), plan)
            if part is not None and part.num_rows:
                parts.append(part)
        return self._combine_and_strip(parts, plan)

    def _pk_names_in(self, columns: list[str]) -> list[str]:
        """PK names present, in SCHEMA order — the merge must sort by the
        declared key order even when a projection reordered columns."""
        present = set(columns)
        return [n for n in self.schema.primary_key_names if n in present]

    def _stream_segment(self, seg: SegmentPlan) -> bool:
        """True when this segment should be read window-by-window instead
        of fully materialized: manifest row count over the row threshold,
        OR stored byte size over the byte threshold — a wide-schema
        segment can be host-RAM-huge long before it hits the row knob."""
        row_thresh = self.config.scan.stream_read_min_rows
        if row_thresh <= 0:
            return False  # 0 disables streaming entirely (stable contract)
        rows = sum(f.meta.num_rows for f in seg.ssts)
        if rows <= self.config.scan.max_window_rows:
            # everything fits one window: streaming would pay the pass-1
            # scan and still materialize the same single window
            return False
        if rows > row_thresh:
            return True
        byte_thresh = self.config.scan.stream_read_min_bytes
        return byte_thresh > 0 and sum(
            f.meta.size for f in seg.ssts) > byte_thresh

    async def _stream_window_batches(self, seg: SegmentPlan, plan: ScanPlan,
                                     strict_no_replay: bool = False):
        """Streamed segment read (the reference's pull-based batch
        streaming, read.rs:346-385, re-shaped for device windows): pass 1
        streams ONE PK column's row groups to plan value-range windows of
        <= max_window_rows; pass 2 reads each window's rows via parquet
        predicate pushdown.  Host materialization is bounded by the
        window budget (plus file buffers on non-filesystem stores), not
        the segment size.  Yields one Arrow batch per window, PK-range
        ascending, each encoded WINDOW-LOCALLY downstream."""
        import pyarrow.compute as pc

        # one source per SST: local stores mmap, remote stores download
        # the object ONCE and serve both passes and every window from it
        sources = await asyncio.gather(*(
            parquet_io.open_sst_source(self.store,
                                       sst_path(self.root_path, f.id))
            for f in seg.ssts))

        pk_names = self._pk_names_in(seg.columns)
        values = counts = None
        part_col = pk_names[-1]
        for nm in pk_names:
            per_sst = await asyncio.gather(*(
                self._run_pool(plan.pool, src.value_counts, nm)
                for src in sources))
            values, counts = parquet_io.merge_value_counts(per_sst)
            if len(values) == 0:
                return  # segment is empty
            if len(values) > 1:
                part_col = nm
                break
            # constant column: windowing on it cannot bound anything
        window = self.config.scan.max_window_rows
        ranges: list[tuple] = []
        start = acc = 0
        for i, c in enumerate(counts):
            if acc and acc + int(c) > window:
                ranges.append((values[start], values[i - 1]))
                start, acc = i, 0
            acc += int(c)
        if acc:
            ranges.append((values[start], values[-1]))
        pyval = lambda x: x.item() if hasattr(x, "item") else x
        yielded_any = False
        for lo, hi in ranges:
            # streamed segments can span many windows: check the
            # deadline before paying for each window's pushdown read
            deadline_checkpoint()
            expr = (pc.field(part_col) >= pyval(lo)) \
                & (pc.field(part_col) <= pyval(hi))
            if plan.pushdown is not None:
                expr = expr & plan.pushdown
            refresh = False
            for attempt in range(3):
                try:
                    if refresh:
                        # re-resolution/re-open can themselves race a
                        # second deletion — they live INSIDE the try so
                        # that also consumes an attempt, never escapes
                        fresh = await self.resolve_segment_ssts(
                            seg.segment_start, plan.range)
                        sources = await asyncio.gather(*(
                            parquet_io.open_sst_source(
                                self.store, sst_path(self.root_path, f.id))
                            for f in fresh))
                        refresh = False
                    if not sources:
                        # the whole segment vanished (TTL GC): nothing
                        # left to stream
                        return
                    tables = await asyncio.gather(*(
                        self._run_pool(plan.pool, src.read,
                                       columns=seg.columns, filters=expr)
                        for src in sources))
                    break
                except NotFoundError:
                    # a compaction deleted an input SST mid-segment.
                    # Windows already yielded can't be retracted, so the
                    # OUTER replan would duplicate them — instead
                    # re-resolve this segment's CURRENT SSTs (the
                    # compacted output holds the same rows) and continue
                    # with the remaining value ranges, which partition
                    # rows independently of file boundaries.
                    if self.resolve_segment_ssts is None or attempt == 2:
                        if strict_no_replay and yielded_any:
                            # the CONSUMER already emitted these batches
                            # downstream (Append path): an outer replan
                            # would DUPLICATE them — fail loudly as a
                            # non-retryable error instead.  Buffering
                            # consumers (OVERWRITE/aggregate) pass
                            # strict_no_replay=False and let the replan
                            # recover duplicate-free.
                            raise Error(
                                f"streamed segment {seg.segment_start} "
                                "lost its SSTs mid-stream after retries; "
                                "failing rather than duplicating "
                                "already-emitted rows")
                        raise
                    refresh = True
            tbl = pa.concat_tables(tables)
            if tbl.num_rows:
                yielded_any = True
                yield tbl.combine_chunks().to_batches()[0]

    @_timed_stage("encode_merge", "scan.windows")
    def _dispatch_merged_windows(self, batch: pa.RecordBatch) -> list:
        """Merge one segment with bounded memory: segments above
        scan.max_window_rows are split into PK-code-range windows, each a
        complete set of PK groups, merged independently in key order
        (windows are PK-ascending, so global order is preserved).  The
        streaming analogue of the reference's pull-based MergeStream
        (SURVEY.md hard part #5).

        The merge is a host permutation-plan + run-keep over the
        pre-sorted SST runs and the windows stay HOST-resident (rows
        cross to the device only as batched stacks in the aggregate
        path).
        """
        _STAGE_ROWS["encode_merge"].inc(batch.num_rows)
        dev = encode.encode_batch(batch)  # host-resident numpy columns
        return self._dispatch_windows_dev(dev, list(batch.schema.names))

    @staticmethod
    def _encoded_to_device_batch(es: "sidecar.EncodedSegment"
                                 ) -> encode.DeviceBatch:
        """Pad sidecar columns (read-only views) to a static-shape
        capacity — the only prep the already-device-layout data needs."""
        cap = encode.pad_capacity(es.n)
        columns = {}
        for name, arr in es.columns.items():
            padded = np.zeros(cap, dtype=arr.dtype)  # calloc: tail free
            padded[:es.n] = arr
            columns[name] = padded
        return encode.DeviceBatch(columns=columns, encodings=es.encodings,
                                  n_valid=es.n, capacity=cap)

    @_timed_stage("encode_merge", "scan.windows")
    def _dispatch_encoded_windows(self, es: "sidecar.EncodedSegment"
                                  ) -> list:
        """Sidecar twin of _dispatch_merged_windows."""
        _STAGE_ROWS["encode_merge"].inc(es.n)
        return self._dispatch_windows_dev(self._encoded_to_device_batch(es),
                                          list(es.names))

    def _dispatch_windows_dev(self, dev: encode.DeviceBatch,
                              names: list) -> list:
        """Post-encode half of the segment merge, shared by the Arrow
        and sidecar reads (see _dispatch_merged_windows for the plan)."""
        pk_names = self._pk_names_in(names)
        ensure(len(pk_names) == self.schema.num_primary_keys,
               "projection lost primary key columns")
        n = dev.n_valid
        host_cols = {name: np.asarray(c)[:n] for name, c in dev.columns.items()}

        # merge-key elision (comparator cost scales with the key count):
        # - PK columns constant across the segment (e.g. a single-metric
        #   table's metric/field ids) can't affect the order;
        # - seq non-decreasing with row index (SSTs are concatenated in
        #   file-id order and seq IS the file id) means the stable PK
        #   merge already leaves the highest-seq row last per run.
        def is_const(a: np.ndarray) -> bool:
            # first!=last shortcuts the full scan for sorted columns
            return len(a) == 0 or (a[0] == a[-1] and bool((a == a[0]).all()))

        sort_pk_names = [nm for nm in pk_names
                         if not is_const(host_cols[nm])]
        if not sort_pk_names:
            sort_pk_names = pk_names[:1]
        seq_h = host_cols[SEQ_COLUMN_NAME]
        seq_ordered = bool(n == 0 or np.all(seq_h[1:] >= seq_h[:-1]))

        window = self.config.scan.max_window_rows
        if n <= window:
            selections: list[Optional[np.ndarray]] = [None]
        else:
            # partition on the first NON-constant pk so windows stay
            # meaningfully bounded even when pk 0 is constant
            selections = _plan_pk_windows(host_cols[sort_pk_names[0]], window)

        # The merge runs ENTIRELY on host: plan the k-way-merge
        # permutation over the pre-sorted SST runs, keep the last row
        # per PK run, and hand out HOST-resident windows.  No per-window
        # device round trips — the device sees rows only as large
        # stacked uploads in the aggregate path, and row scans decode
        # without a device->host fetch.
        return _host_merge_window_descs(dev, host_cols, sort_pk_names,
                                        seq_h, seq_ordered, selections, n)

    @staticmethod
    def _finalize_windows(dispatched: list) -> list:
        """Wrap the dispatched host merges as DeviceBatches.  Split from
        dispatch so callers can overlap device work across segments.
        Device-decode entries (in-flight fused dispatches) finalize
        into DeviceParts — finished per-segment aggregate partials that
        ride the same windows list."""
        out = []
        for entry in dispatched:
            if isinstance(entry, device_decode.DevicePart):
                out.append(entry)
            elif isinstance(entry, device_decode.DecodePlan):
                # deferred fused decode: the plan rides the windows
                # list into the mesh pump, which batches compatible
                # plans into one sharded per-round program
                out.append(entry)
            elif isinstance(entry, device_decode.DecodeDispatch):
                out.append(entry.finalize())
            else:
                columns, encodings, num_runs, cap = entry
                out.append(encode.DeviceBatch(
                    columns=columns, encodings=encodings,
                    n_valid=int(num_runs), capacity=cap))
        return out

    @staticmethod
    def _cacheable_windows(windows: list) -> bool:
        """Only host-decoded window lists may enter the scan cache:
        DeviceParts are aggregate partials keyed to one spec — serving
        them to a row scan or a different aggregate would be wrong, and
        repeat aggregates are already served structurally by the parts
        memo (storage/combine.py)."""
        return all(isinstance(w, encode.DeviceBatch) for w in windows)

    def _window_to_arrow(self, out_batch: encode.DeviceBatch,
                         out_names: list[str],
                         plan: ScanPlan) -> Optional[pa.RecordBatch]:
        # Predicates apply AFTER dedup: filtering before would break
        # last-value semantics when the predicate touches value columns
        # (a filtered-out newer row must still shadow an older row) —
        # PK-only predicates can't, so a fully-pushed plan skips the
        # re-evaluation (the read already filtered exactly these rows).
        k = out_batch.n_valid
        if plan.predicate is not None and not plan.pushed_complete:
            mask = filter_ops.eval_predicate(plan.predicate, out_batch)
            sel = np.flatnonzero(np.asarray(mask)[:k])
            arrow = encode.decode_to_arrow(out_batch, names=out_names)
            return arrow.take(pa.array(sel))
        return encode.decode_to_arrow(out_batch, names=out_names)

    # ---- aggregate pushdown ------------------------------------------------

    def router_covers(self, plan: ScanPlan) -> bool:
        """Whether the attached near-data router would serve any of
        this plan's segments.  scan_aggregate consults it ahead of the
        fused gate: the fused accumulator needs every segment's windows
        HOST-resident — exactly the shipped-segment cost the agents
        exist to avoid — so covered plans take the parts path."""
        return (self.scan_router is not None
                and plan.range is not None
                and self.scan_router.covers_any(plan.segments))

    def aggregate_route(self, plan: ScanPlan, spec: AggregateSpec) -> str:
        """The route an aggregate over `plan` takes, from the gates
        that decide it, none of them counting a fallback (the scan
        counts where it runs): `replay` and `fused_acc` (the fused
        device accumulator, re-run from a recorded plan or built),
        `mesh`, `device_decode`, `parts`.  The `route` field of the
        query's `scan.plan` span (CloudObjectStorage.plan_query sets it
        on the plan); scan_aggregate takes the fused path on the first
        two."""
        if self.fused_aggregate_ok(plan) and not self.router_covers(plan):
            if (plan.use_cache and self._replay_cache
                    and self._replay_key(plan, spec) in self._replay_cache):
                return "replay"
            return "fused_acc"
        if self._mesh_plan_ok(plan):
            return "mesh"
        if (plan.mode is UpdateMode.OVERWRITE
                and self._device_decode_plan_ok(plan, count=False)):
            return "device_decode"
        return "parts"

    def fused_aggregate_ok(self, plan: Optional[ScanPlan] = None) -> bool:
        """Whether the fused device-accumulated aggregate serves this
        scan (see _fused_agg_ok_base for the structural gates).  An
        explicit `[scan.decode] mode = "device"` outranks it for
        decode-eligible plans: the fused accumulator still pays host
        decode for every window, which is the wall the device-decode
        dispatch removes — forcing fused (HORAEDB_FUSED_AGG=1) still
        wins, so existing coverage keeps its path."""
        if not self._fused_agg_ok_base(plan):
            return False
        import os

        if os.environ.get("HORAEDB_FUSED_AGG", "") == "1":
            return True
        if (plan is not None and self._decode_mode() == "device"
                and self._device_decode_plan_ok(plan, count=False)):
            return False
        return True

    def _fused_agg_ok_base(self, plan: Optional[ScanPlan] = None) -> bool:
        """The fused aggregate's own gates: single-device mode, and
        by default ACCELERATOR backends only — there,
        device->host is the scarce resource (the per-flush partial
        downloads dominate) and scatters are fast; on XLA-CPU the trade
        inverts — downloads are free and scatter is the slow op, so the
        per-flush host f64 fold wins.  HORAEDB_FUSED_AGG=1/0 forces it
        on/off (tests force it on to cover the fused path on the CPU
        backend).  The mesh path keeps per-round psum combines either
        way.

        When `plan` is given, queries whose estimated row volume exceeds
        the scan-cache budget fall back to the parts path: fused is
        two-phase (all windows collected before the union group space is
        known), so unlike the parts pipeline it pins every window in
        host RAM for the query's duration — the budget is the bound."""
        if self.scan_mesh is not None:
            # [scan.mesh] supersedes the fused single-chip accumulator:
            # the mesh's parts path is the one that scales across chips
            return False
        import os

        forced = os.environ.get("HORAEDB_FUSED_AGG", "")
        if forced == "1":  # force wins over the budget gate too
            return True
        if forced == "0":
            return False
        if plan is not None:
            est_rows = sum(f.meta.num_rows
                           for seg in plan.segments for f in seg.ssts)
            if est_rows * _CACHE_BYTES_PER_ROW > self.cache_budget_bytes:
                return False
        import jax

        return jax.default_backend() != "cpu"

    def _decode_mode(self) -> str:
        """Resolved [scan.decode] mode: HORAEDB_DEVICE_DECODE=1/0
        forces device/host over the config (the test/chaos override
        convention of HORAEDB_FUSED_AGG and friends)."""
        import os

        forced = os.environ.get("HORAEDB_DEVICE_DECODE", "")
        if forced == "1":
            return "device"
        if forced == "0":
            return "host"
        return self.config.scan.decode.mode

    def _device_decode_plan_ok(self, plan: ScanPlan,
                               count: bool = True) -> bool:
        """Plan-level gate for the fused device-decode dispatch
        (ops/device_decode.py) — the decode twin of fused_aggregate_ok.
        Per-reason fallbacks are counted (scan_decode_fallback_total)
        unless `count` is False (the fused gate probes without
        recording, or structural misses would double-count).

        "auto" engages on accelerator backends for plans the fused
        aggregate declines on its own terms (the oversized-cold shape
        whose windows can't pin in RAM anyway); "device" forces the
        dispatch wherever structurally possible; "host" is the
        bit-identity control.  Per-SEGMENT gates (encodings, dtype,
        upload budget) live in _dispatch_device_decode."""
        mode = self._decode_mode()
        note = device_decode.note_fallback if count else (lambda _r: None)
        if mode == "host":
            return False
        if mode == "auto":
            import jax

            if jax.default_backend() == "cpu":
                # host numpy decode measured faster than XLA-CPU device
                # programs on this backend (the host_agg trade)
                return False
            if self._fused_agg_ok_base(plan):
                return False  # fused keeps the warm/replay path
            # auto + the 2-D scan mesh rides the mesh-placed fused
            # decode rounds (plan.decode_defer; _run_mesh_decode_round)
            # — decode shards along the time axis with the aggregation
            # instead of declining here
        if plan.mode is not UpdateMode.OVERWRITE:
            note("append_mode")
            return False
        if plan.predicate is not None and not plan.pushed_complete:
            # value-column leaves interact with last-value dedup and
            # Or/Not shapes have no pushed conjunction — host decode
            # evaluates those post-merge.  Checked BEFORE the sidecar
            # gate: an unpushable predicate also fails that one, and
            # "predicate" is the reason an operator can act on
            note("predicate")
            return False
        if not device_decode.leaf_shape_supported(plan.prune_leaves):
            note("predicate")
            return False
        if not self._sidecar_plan_ok(plan):
            note("no_sidecar")
            return False
        return True

    async def execute_aggregate_fused(self, plan: ScanPlan,
                                      spec: AggregateSpec,
                                      counted: Optional[set] = None):
        """Merge + downsample with a QUERY-GLOBAL device accumulator:
        rounds of stacked windows aggregate and scatter into one
        (groups, buckets) grid set on device; nothing is downloaded
        until the final grids.

        Two-phase by design: all windows are collected first so the
        union group space is known before any round runs (remap targets
        global rows directly).  Host RAM for the collected windows is
        the same rows the parts path would hold across its pipeline;
        the streamed-segment path still bounds per-segment
        materialization.

        Returns (group_values, grids) where grids hold DEVICE float32
        arrays (downloaded lazily by the caller — np.asarray works; the
        device work itself is complete, block_until_ready'd).  `last`
        queries additionally materialize count/last_ts on host for the
        int64 absolute-time conversion.

        A query whose windows are ONE small round of host rows (a point
        query: one series over an hour or two) is carried by one device
        call and one download inside one pool job (_fused_one_call): its
        grids are numpy already, and it records no replay."""
        if counted is None:
            counted = set()
        replay_key = None
        if plan.use_cache:
            replay_key = self._replay_key(plan, spec)
            entry = self._replay_cache.get(replay_key)
            if entry is not None:
                # segment validation touches the (lock-free, event-loop-
                # owned) scan cache HERE; only the device rounds go to
                # the pool
                fused = None
                if self._replay_segments_valid(entry):
                    fused = await self._run_pool(
                        plan.pool, self._fused_replay, entry, spec)
                if fused is not None:
                    self._replay_cache.move_to_end(replay_key)
                    self._replay_hits += 1
                    _REPLAY_HITS.inc()
                    _FUSED_AGGREGATES["replay"].inc()
                    # `counted` gates ops metrics across race restarts,
                    # exactly like the full path's per-segment gate
                    # replay rows go to their OWN counter — nothing was
                    # read, so feeding rows_scanned/scan_seconds would
                    # skew operator rows/s and latency percentiles
                    fresh = [(s, r) for s, r in entry["seg_rows"]
                             if s not in counted]
                    if fresh:
                        _REPLAY_ROWS.inc(sum(r for _, r in fresh))
                        counted.update(s for s, _ in fresh)
                    return self._fused_result(entry["values"], fused, spec)
                self._replay_cache.pop(replay_key, None)
            self._replay_misses += 1
            _REPLAY_MISSES.inc()
        items: list[tuple[int, encode.DeviceBatch, tuple]] = []
        seg_records: list[tuple] = []
        seg_rows: list[tuple] = []
        windows_iter = self._cached_windows(plan)
        try:
            async for seg, windows, read_s in windows_iter:
                s = seg.segment_start
                # `counted` survives compaction-race restarts so a
                # re-scanned segment doesn't double-count ops metrics
                count_metrics = s not in counted

                def prep(ws=windows, s=s, cm=count_metrics):
                    out = []
                    for w in ws:
                        if cm:
                            _ROWS_SCANNED.inc(w.n_valid)
                        pr = self._window_groups(w, spec, plan)
                        if pr is not None:
                            out.append((s, w, pr))
                    return out

                items.extend(await self._run_pool(
                    plan.pool,
                    self._phased("scan.group_prep", prep, segment=s)))
                if replay_key is not None:
                    seg_records.append((self._cache_key(seg, plan), tuple(
                        weakref.ref(w) for w in windows)))
                    seg_rows.append((s, sum(w.n_valid for w in windows)))
                if count_metrics:
                    _SCAN_LATENCY.observe(read_s)
                    counted.add(s)
        finally:
            await windows_iter.aclose()
        if not items:
            values, grids = combine_aggregate_parts([], spec.num_buckets,
                                                    which=spec.which)
            return values, grids
        all_values = np.unique(np.concatenate([it[2][0] for it in items]))
        g = len(all_values)
        g_pad = max(8, 1 << (g - 1).bit_length())
        local_ok = all(
            it[1].encodings[spec.ts_col].kind == "offset" for it in items)
        width = self._window_grid_width(spec) if local_ok \
            else spec.num_buckets
        max_w = max(1, self.config.scan.agg_batch_windows)
        if len(items) <= max_w and _host_rows(items, spec):
            batch_w = min(max_w, 1 << (len(items) - 1).bit_length())
            cap = max(it[1].capacity for it in items)
            if batch_w * cap <= _ONE_CALL_MAX_ROWS:
                # one small round of host rows: nothing a later query
                # could reuse is worth keeping on the device, so the
                # round goes up as the call's arguments, everything the
                # answer needs comes back in the same pool job, and no
                # replay is recorded (a repeat costs this one call)
                fused = await self._run_pool(
                    plan.pool, self._fused_one_call, items, spec, batch_w,
                    cap, g, g_pad, width, all_values, local_ok)
                _FUSED_AGGREGATES["one"].inc()
                return self._fused_result(all_values, fused, spec)
        space_fp = (g, hash(all_values.tobytes()))
        recorded_rounds: list[tuple] = []

        def build_rounds():
            # lazy: round i+1's stacks build on host while round i's
            # accumulate runs on device (dispatches are async)
            i = 0
            while i < len(items):
                chunk = items[i:i + max_w]
                batch_w = min(max_w, 1 << (len(chunk) - 1).bit_length())
                cap = max(it[1].capacity for it in chunk)
                # the chunk offset `i` disambiguates consecutive rounds
                # of one big segment that share (seg0, batch_w, cap) —
                # without it the stack LRU would overwrite round 1's
                # entry with round 2's and every replay would miss
                stack_key = self._round_stack_key(
                    chunk[0][0], spec, plan, batch_w, cap, g_pad, width,
                    space_fp) + (i,)
                with self._phase("scan.group_prep", round=i):
                    arrays = self._build_round_stacks(
                        chunk, spec, plan, batch_w, cap, g_pad, width,
                        all_values, local_ok, stack_key=stack_key)
                if replay_key is not None:
                    windows = tuple(it[1] for it in chunk)
                    recorded_rounds.append((
                        stack_key,
                        self._col_stack_key(windows, spec, plan, batch_w,
                                            cap),
                        tuple(weakref.ref(w) for w in windows)))
                i += len(chunk)
                yield arrays

        def run_rounds():
            out, t_dev = self._fused_run_device_rounds(
                build_rounds(), spec, g, g_pad, width)
            _STAGE_SECONDS["device_aggregate"].observe(t_dev)
            return out

        fused = await self._run_pool(plan.pool, run_rounds)
        _FUSED_AGGREGATES["rounds"].inc()
        if replay_key is not None:
            self._replay_cache[replay_key] = {
                "segments": seg_records,
                "rounds": recorded_rounds,
                "values": all_values,
                "g": g, "g_pad": g_pad, "width": width,
                "seg_rows": seg_rows,
            }
            self._replay_cache.move_to_end(replay_key)
            while len(self._replay_cache) > _REPLAY_SLOTS:
                self._replay_cache.popitem(last=False)
        return self._fused_result(all_values, fused, spec)

    def _replay_key(self, plan: ScanPlan, spec: AggregateSpec) -> tuple:
        """Identity of a fused aggregate over a specific plan: the
        per-segment scan-cache keys (SST ids + columns + pushdown) plus
        the full aggregate spec and predicate.  Any write or compaction
        changes a segment's SST set and therefore the key."""
        seg_keys = tuple(self._cache_key(seg, plan) for seg in plan.segments)
        return (seg_keys, spec.group_col, spec.ts_col, spec.value_col,
                spec.range_start, spec.bucket_ms, spec.num_buckets,
                spec.which,
                filter_ops.canonical_predicate_key(plan.predicate))

    def _replay_segments_valid(self, entry: dict) -> bool:
        """Every segment's scan-cache entry must still hold the exact
        window objects recorded (object identity — a re-read, eviction,
        or compaction breaks it).  Runs on the EVENT LOOP: the scan
        cache is lock-free and event-loop-owned."""
        for key, refs in entry["segments"]:
            ws = self.scan_cache.get(key)
            if (ws is None or len(ws) != len(refs)
                    or any(r() is not w for r, w in zip(refs, ws))):
                return False
        return True

    def _fused_replay(self, entry: dict, spec: AggregateSpec):
        """Re-run a recorded fused aggregate in ONE worker-pool
        dispatch: check every round's stacks are still in the
        (thread-safe) stack LRU — BEFORE any device work — then run the
        accumulate rounds straight from the cached device arrays.
        Returns (device grids, any-data mask), or None to fall back to
        the full path."""
        rounds = []
        for stack_key, col_key, refs in entry["rounds"]:
            ws = tuple(r() for r in refs)
            if any(w is None for w in ws):
                return None
            cols = self._stack_cache_get(col_key, ws)
            small = self._stack_cache_get(stack_key, ws)
            if cols is None or small is None:
                return None
            rounds.append(cols + small)
        out, t_dev = self._fused_run_device_rounds(
            rounds, spec, entry["g"], entry["g_pad"], entry["width"])
        _STAGE_SECONDS["device_aggregate"].observe(t_dev)
        return out

    def _fused_run_device_rounds(self, rounds, spec: AggregateSpec,
                                 g: int, g_pad: int, width: int):
        """The fused aggregate's device sequence, shared by the full
        path and the replay: acc init -> one accumulate per round ->
        finalize -> slice to g -> sync.  `rounds` is any iterable of
        stack tuples (a lazy generator on the full path, so stack
        building overlaps device execution).  Returns ((grids,
        per-group any-data mask), device seconds) — device time
        excludes the caller's stack building, which self-reports under
        stack_build."""
        total = self._dev_scalar(spec.num_buckets)
        bucket_ms = self._dev_scalar(spec.bucket_ms)
        t_dev = 0.0
        t0 = time.perf_counter()
        # one scan.dispatch span per enqueue, not one around the loop:
        # `rounds` is lazy, and its stack building is scan.group_prep
        with self._phase("scan.dispatch", sync=True, fn="_fused_acc_init_jit"):
            acc = _fused_acc_init_jit(num_groups=g_pad,
                                      num_buckets=spec.num_buckets,
                                      which=spec.which)
        t_dev += time.perf_counter() - t0
        for ts_s, gid_s, val_s, remap_d, shift_d, lo_dev, _lo in rounds:
            t0 = time.perf_counter()
            with self._phase("scan.dispatch", sync=True,
                             fn="_fused_round_accumulate_jit"):
                acc = _fused_round_accumulate_jit(
                    acc, ts_s, gid_s, val_s, remap_d, shift_d, lo_dev,
                    total, bucket_ms, num_groups=g_pad, width=width,
                    which=spec.which)
            t_dev += time.perf_counter() - t0
        t0 = time.perf_counter()
        with self._phase("scan.dispatch", sync=True, fn="_fused_finalize_jit"):
            final = _fused_finalize_jit(acc, spec.which)
            out = {k: v[:g] for k, v in final.items()}
            # the per-group any-data mask rides the same enqueue and the
            # same sync: _fused_result only copies it
            has_data = _group_has_data_jit(out["count"])
        deviceprof.block_until_ready((out, has_data), fn="fused_rounds",
                                     table=self.table)
        t_dev += time.perf_counter() - t0
        return (out, has_data), t_dev

    def _fused_one_call(self, items: list, spec: AggregateSpec,
                        batch_w: int, cap: int, g: int, g_pad: int,
                        width: int, group_space: np.ndarray,
                        local_ok: bool):
        """A fused aggregate whose windows are ONE small round of host
        rows, as one pool job: the round stacked in numpy, ONE call of
        _fused_one_call_jit with those arrays as its arguments (the
        upload rides the call), ONE download of the stacked grids and
        the any-data mask.  Nothing enters the stack LRU or the windows'
        memos.  Returns _fused_run_device_rounds' (grids, mask), as
        numpy and cut to the query's `g` groups."""
        t0 = time.perf_counter()
        with self._phase("scan.group_prep", round=0):
            args = _stack_host_cols(items, spec, batch_w, cap) \
                + _round_small_arrays(items, spec, batch_w, g_pad,
                                      group_space, local_ok)
        nbytes = sum(int(a.nbytes) for a in args)
        _STAGE_SECONDS["stack_build"].observe(time.perf_counter() - t0)
        _STAGE_BYTES["stack_build"].inc(nbytes)
        t0 = time.perf_counter()
        with self._phase("scan.dispatch", sync=True,
                         fn="_fused_one_call_jit"):
            out = _fused_one_call_jit(
                *args, self._dev_scalar(spec.num_buckets),
                self._dev_scalar(spec.bucket_ms), num_groups=g_pad,
                num_buckets=spec.num_buckets, width=width,
                which=spec.which)
            deviceprof.charge_transfer("h2d", nbytes)
        stacked, last_ts, has_data = deviceprof.download(
            out, fn="fused_one_call", table=self.table)
        _STAGE_SECONDS["device_aggregate"].observe(
            time.perf_counter() - t0)
        # the finalize answers `count` and every aggregate asked
        names = sorted(set(spec.which) | {"count"})
        ensure(len(names) == len(stacked), "fused grids out of step")
        grids = {k: v[:g] for k, v in zip(names, stacked)}
        if last_ts is not None:
            grids["last_ts"] = last_ts[:g]
        return grids, has_data[:g]

    def _fused_result(self, values: np.ndarray, fused: tuple,
                      spec: AggregateSpec):
        """What the fused path does on the host before the response, in
        one copy: `fused` is _fused_run_device_rounds' (grids, per-group
        any-data mask), synced already, or _fused_one_call's, which are
        numpy already and are only indexed here.

        The empty-group drop is the twin of finalize_aggregate's (the
        aligned fast path can register groups whose rows all fall
        outside the range — see that docstring): only the G-byte mask
        crosses to host, and the grids move only in the rare case a
        leak exists, so cached/replay queries stay at zero grid
        downloads.  `last` queries also bring count/last_ts back:
        absolute float ms needs int64 range, a host conversion."""
        grids, has_dev = fused
        if not len(values):
            return values, grids
        want = {"has": has_dev}
        if "last_ts" in grids:
            want.update(count=grids["count"], last_ts=grids["last_ts"])
        # _fused_one_call's are on the host already: this thread (the
        # loop's) then makes no device call
        on_host = isinstance(has_dev, np.ndarray)
        host = want if on_host else deviceprof.download(
            want, fn="fused_rounds", table=self.table)
        if not host["has"].all():
            idx = np.flatnonzero(host["has"])
            values = values[idx]
            grids = {k: v[idx] if on_host else jnp.take(v, idx, axis=0)
                     for k, v in grids.items()}
            host = {k: v[idx] for k, v in host.items()}
        if "last_ts" in grids:
            grids["last_ts"] = np.where(
                host["count"] > 0,
                host["last_ts"].astype(np.float64) + spec.range_start,
                np.nan)
        return values, grids

    async def aggregate_segments(self, plan: ScanPlan, spec: AggregateSpec,
                                 top_k=None):
        """Per segment, yield (segment_start, partial parts) — the
        retryable unit for scan_aggregate (segments already yielded are
        skipped on a replan; a segment is yielded only once ALL its
        windows are aggregated).

        Routing order: memo-served segments first (free), then — with a
        ScanRouter attached ([scanagent]) — covered segments' partials
        are fetched from their near-data agents CONCURRENTLY with the
        local pipeline scanning the uncovered rest; agent failures fall
        back per segment through the local pump (the declared fallback
        seam).  Callers fold parts in sorted segment order, so yield
        order is free whichever route served a segment.

        [scan.mesh] plans route their local scans through the 2-D mesh
        pump instead of the single-chip pump (same yield contract; per
        -round fallback through the single-chip kernel is the mesh's
        declared failure seam).  `top_k` additionally enables the
        device-scored winner-sliced mesh path, which bypasses the memo
        (its parts are winner slices — memoizing them would poison
        full-grid queries) and yields only after all compute, so a
        compaction race replans from zero, never double-counts."""
        ensure(plan.mode is UpdateMode.OVERWRITE,
               "aggregate pushdown requires Overwrite mode")
        # device-native decode ([scan.decode]): eligible plans thread
        # the aggregate spec to the decode stage, which uploads each
        # EncodedSegment's raw encoded buffers and fuses filter +
        # merge-dedup + bucket-aggregate into ONE device dispatch —
        # finished per-segment parts come back instead of host windows
        # (ops/device_decode.py; host decode is the bit-identity
        # control).  The copy keeps the caller's plan reusable.
        if self._device_decode_plan_ok(plan):
            plan = dc_replace(plan, decode_spec=spec)

        use_mesh = self._mesh_plan_ok(plan)
        if use_mesh:
            # mesh rounds and their single-chip fallbacks must share
            # one rounding schedule (see ScanPlan.force_xla_agg).
            # Decode-eligible plans additionally DEFER the fused
            # dispatch: DecodePlans ride the windows lists and batch
            # into per-round sharded decode programs on the mesh
            plan = dc_replace(plan, force_xla_agg=True,
                              decode_defer=plan.decode_spec is not None)
            if top_k is not None and self._mesh_topk_ok(plan, spec,
                                                        top_k):
                pump = self._aggregate_topk_mesh(plan, spec, top_k)
                try:
                    async for out in pump:
                        yield out
                finally:
                    await pump.aclose()
                return

        # delta summation: segments whose partials are memoized (same
        # SST set + compatible bucket grid) are served up front and
        # dropped from the scan plan entirely — a narrowed/refined
        # dashboard range re-scans only the delta segments.  Runs on
        # the event loop (the memo is event-loop owned, like the scan
        # cache).  Served segments may yield out of plan order; callers
        # fold parts in sorted segment order (the bit-identity fold
        # order), so order here is free.
        memo = self.parts_memo
        use_memo = memo.enabled and plan.use_cache
        seg_keys: dict[int, tuple] = {}
        memo_pred_key = ""
        if use_memo:
            memo_pred_key = filter_ops.canonical_predicate_key(
                plan.predicate)
            remaining = []
            for seg in plan.segments:
                key = self._cache_key(seg, plan)
                seg_keys[seg.segment_start] = key
                got = memo.probe(key, seg.segment_start,
                                 self.segment_duration_ms, spec,
                                 memo_pred_key)
                if got is None:
                    remaining.append(seg)
                else:
                    yield seg.segment_start, got
            if len(remaining) < len(plan.segments):
                plan = dc_replace(plan, segments=remaining)
            if not remaining:
                return

        def memo_store(seg_start: int, parts: list) -> None:
            if use_memo:
                memo.store(seg_keys[seg_start], spec, memo_pred_key,
                           parts)

        router = self.scan_router
        covered: list = []
        uncovered = plan.segments
        if (router is not None and router.active
                and plan.range is not None):
            covered, uncovered = router.split(plan.segments)
        # local scans route through the mesh pump when [scan.mesh] is
        # on (same yield contract, per-round single-chip fallback)
        pump_fn = (self._aggregate_segments_mesh if use_mesh
                   else self._aggregate_segments_pump)
        # every pump iteration below carries an explicit aclose on
        # abandonment: delegation must not let the pump's in-flight
        # fetch/decode/device tasks outlive a closed consumer into
        # table teardown (PR 3/8 discipline — `async for` does NOT
        # close its source, and a nested drain-generator would just
        # move the leak one level up)
        if not covered:
            pump = pump_fn(plan, spec, memo_store)
            try:
                async for out in pump:
                    yield out
            finally:
                await pump.aclose()
            return
        # near-data routing: agent RPCs run as one background gather
        # while the local pump scans the uncovered segments — the
        # coordinator's store reads and the agents' shard scans
        # overlap, and a slow agent costs its own segments only
        agent_task = asyncio.create_task(
            router.gather(plan, spec, covered))
        try:
            if uncovered:
                pump = pump_fn(
                    dc_replace(plan, segments=list(uncovered)), spec,
                    memo_store)
                try:
                    async for out in pump:
                        yield out
                finally:
                    await pump.aclose()
            served, failed = await agent_task
            agent_task = None
        finally:
            if agent_task is not None:
                # local-pump failure/cancellation: the gather must not
                # outlive the scan into table teardown (PR 3/8
                # discipline)
                agent_task.cancel()
                await asyncio.gather(agent_task, return_exceptions=True)
        for seg_start, parts in served:
            memo_store(seg_start, parts)
            yield seg_start, parts
        if failed:
            # THE declared fallback seam: failed covered segments go
            # through the exact local pump the unrouted scan uses —
            # direct store reads happen here and nowhere else on the
            # routed path (tools/lint.py enforces the nowhere-else)
            pump = pump_fn(
                dc_replace(plan, segments=list(failed)), spec,
                memo_store)
            try:
                async for out in pump:
                    yield out
            finally:
                await pump.aclose()

    async def _aggregate_segments_pump(self, plan: ScanPlan,
                                       spec: AggregateSpec, memo_store):
        """The local aggregate pipeline (store fetch -> decode ->
        device rounds) over `plan.segments`.

        Windows from different segments batch into rounds of
        `scan.agg_batch_windows` and run as ONE compiled program per
        round — the reference parallelizes segments under UnionExec
        (storage.rs:342-368); here segments share the batch leading
        axis.  Cross-segment batching is safe because
        segments partition time and windows partition PKs: no two
        windows share a (group, bucket, timestamp) cell, so the host
        combine has no tie-break subtleties."""
        from collections import deque

        batch_w = max(1, self.config.scan.agg_batch_windows)
        queue: list[tuple[int, encode.DeviceBatch, tuple]] = []
        parts: dict[int, list] = {}
        pending: dict[int, int] = {}
        arrived: "deque[int]" = deque()
        # pipelined device stage: ONE aggregation round runs as a
        # background task while this loop keeps pulling/prepping the
        # next windows from the (also pipelined) fetch/decode stages —
        # rounds still apply strictly in dispatch order, so parts per
        # segment are identical to the sequential path's.  The decision
        # is plan.pipeline_active — set by _cached_windows once it has
        # probed whether the scan has store I/O to hide — so it must be
        # read AFTER the windows iterator starts (flush can only run
        # then; asserted by the first-flush-after-first-window order)
        def pipelined() -> bool:
            return plan.pipeline_active
        flush_task: Optional[asyncio.Task] = None

        def _apply(flushed) -> None:
            for seg_start, part in flushed:
                parts[seg_start].append(part)
                pending[seg_start] -= 1

        async def settle_flush() -> None:
            nonlocal flush_task
            if flush_task is None:
                return
            t, flush_task = flush_task, None
            _apply(await t)

        async def flush_round(chunk: list) -> list:
            # stage seconds observed HERE, around the round itself
            # (pool-queue wait included): settling happens at the NEXT
            # flush, so measuring dispatch-to-settle would absorb the
            # consumer's decode/fetch waits into stage="device" and
            # contradict the stall counters the docs say to read
            # alongside it
            from horaedb_tpu.storage import pipeline as pipeline_mod

            t0 = time.perf_counter()
            out = await self._run_pool(
                plan.pool, self._flush_window_batch, chunk, spec, plan)
            pipeline_mod.observe_stage(
                "device", time.perf_counter() - t0,
                rows=sum(w.n_valid for _s, w, _p in chunk))
            return out

        async def flush(k: int) -> None:
            nonlocal flush_task
            chunk = queue[:k]
            del queue[:k]
            if all(prep is None for _s, _w, prep in chunk):
                # finished partials only: nothing to aggregate, so no
                # pool job — applied here, behind any round in flight
                await settle_flush()
                _apply([(s, w.part) for s, w, _prep in chunk])
                return
            if not pipelined():
                _apply(await self._run_pool(
                    plan.pool, self._flush_window_batch, chunk, spec,
                    plan))
                return
            # stage-boundary checkpoint: no new device round for an
            # expired query (the in-flight one drains via settle)
            deadline_checkpoint()
            await settle_flush()
            flush_task = asyncio.create_task(flush_round(chunk))

        windows_iter = self._cached_windows(plan)
        try:
            try:
                async for seg, windows, read_s in windows_iter:
                    t0 = time.perf_counter()
                    s = seg.segment_start
                    arrived.append(s)
                    parts[s] = []
                    pending[s] = 0

                    def prep_windows(ws=windows):
                        out = []
                        for w in ws:
                            # same semantics as the row path: post-dedup
                            # rows
                            _ROWS_SCANNED.inc(w.n_valid)
                            if isinstance(w, device_decode.DevicePart):
                                # already a finished aggregate partial;
                                # rides the queue (prep=None) so a
                                # segment's parts keep window order.
                                # Provably-empty parts never enqueue —
                                # a pending[] count that no flush entry
                                # repays would park the segment (and
                                # every later one) at the stream
                                # head-of-line until end-of-scan
                                if w.part is not None:
                                    out.append((w, None))
                                continue
                            prep = self._window_groups(w, spec, plan)
                            if prep is not None:
                                out.append((w, prep))
                        return out

                    if all(isinstance(w, device_decode.DevicePart)
                           for w in windows):
                        # a fused dispatch's finished partials: nothing
                        # to group, so no pool job and no phase
                        prepped = prep_windows()
                    else:
                        prepped = await self._run_pool(
                            plan.pool, self._phased(
                                "scan.group_prep", prep_windows,
                                segment=s))
                    for w, prep in prepped:
                        queue.append((s, w, prep))
                        pending[s] += 1
                    while len(queue) >= batch_w:
                        await flush(batch_w)
                    _SCAN_LATENCY.observe(read_s
                                          + (time.perf_counter() - t0))
                    while arrived and pending[arrived[0]] == 0:
                        s0 = arrived.popleft()
                        seg_parts = parts.pop(s0)
                        memo_store(s0, seg_parts)
                        yield s0, seg_parts
            finally:
                await windows_iter.aclose()
            if queue:
                await flush(len(queue))
            await settle_flush()
            while arrived:
                s0 = arrived.popleft()
                seg_parts = parts.pop(s0)
                memo_store(s0, seg_parts)
                yield s0, seg_parts
        finally:
            if flush_task is not None:
                # cancelled/failed scan: drain the in-flight device
                # round (the pool job runs to completion regardless) so
                # it never races table teardown
                flush_task.cancel()
                await asyncio.gather(flush_task, return_exceptions=True)

    # ---- the 2-D scan mesh ([scan.mesh]; docs/parallel.md) -----------------

    def _mesh_plan_ok(self, plan: ScanPlan) -> bool:
        """Plan-level [scan.mesh] routing gate; per-round gates (sum
        overlap, count bound, grid budget) live in _run_mesh_round and
        fall back per round, each with its counted reason
        (scan_mesh_fallback_total{reason=})."""
        return (self.scan_mesh is not None
                and plan.mode is UpdateMode.OVERWRITE)

    def _mesh_topk_ok(self, plan: ScanPlan, spec: AggregateSpec,
                      tk) -> bool:
        """Whether a top-k query can take the device-scored, winner
        -sliced mesh path (egress bounded at O(k x buckets x aggs) per
        run).  Selection rankings (min/max/last) score exactly on
        device; additive rankings (count/sum/avg) score through the
        compensated (hi, lo) plane — exact when every add provably is,
        with a counted `additive_topk` downgrade otherwise.  Mixed
        -provenance scans (near-data partials, device-decode parts)
        keep the full-parts path, which is still mesh-combined — just
        not egress-bounded."""
        if tk.by not in ("min", "max", "last", "count", "sum", "avg") \
                or not (tk.by == "count" or tk.by in set(spec.which)):
            # same requested-agg rule combine_top_k enforces (count is
            # always folded, so ranking by it needs no spec entry)
            note_mesh_fallback("topk_by")
            return False
        if plan.decode_spec is not None:
            note_mesh_fallback("topk_decode")
            return False
        router = self.scan_router
        if (router is not None and router.active
                and plan.range is not None
                and router.split(plan.segments)[0]):
            # agent-served segments never reach the device score state,
            # so a global ranking over it would miss their groups
            note_mesh_fallback("topk_router")
            return False
        est_rows = sum(f.meta.num_rows
                       for seg in plan.segments for f in seg.ssts)
        if est_rows * _CACHE_BYTES_PER_ROW > self.cache_budget_bytes:
            # two-phase: every window pins in host RAM until winners
            # are known (the fused path's budget discipline)
            note_mesh_fallback("topk_budget")
            return False
        return True

    def _mesh_runs(self, items: list) -> list[list]:
        """Consecutive same-segment slot runs of one round, as
        [seg_start, first_slot, last_slot] triples — the segmented
        reduction's run layout (plan-order slot admission keeps a
        segment's windows adjacent)."""
        runs: list[list] = []
        for i, (s, _w, _prep) in enumerate(items):
            if runs and runs[-1][0] == s:
                runs[-1][2] = i
            else:
                runs.append([s, i, i])
        return runs

    def _mesh_round_gates(self, items: list, runs: list,
                          spec: AggregateSpec, g_pad: int,
                          width: int, cap: int,
                          local_ok: bool) -> None:
        """Per-round exactness/budget gates; raises _MeshFallback with
        the counted reason.  Only multi-slot runs combine on the mesh,
        so the exactness gates apply to those alone."""
        T = int(self.scan_mesh.shape["time"])
        want = combine_mod.expand_which(spec.which)
        multi = any(b > a for _s, a, b in runs)
        if multi and local_ok:
            # the cell-wise run combine is only bucket-aligned when
            # every slot of a run shares the same first bucket `lo`.
            # Bulk/sidecar-streamed windows share their segment's
            # epoch, but the parquet-streamed fallback encodes each
            # chunk with its OWN epoch — those runs combine per window
            # on the single-chip kernel instead (a silent mesh combine
            # would shift rows by whole buckets AND clip rows past the
            # common window span; caught by the streamed chaos
            # schedules, regression-tested in test_mesh_scan)
            for _s, a, b in runs:
                lo0 = max(0, items[a][2][2] // spec.bucket_ms)
                for i in range(a + 1, b + 1):
                    if max(0, items[i][2][2] // spec.bucket_ms) != lo0:
                        raise _MeshFallback("run_misaligned")
        if multi and T * cap >= (1 << 24):
            # f32 integer adds stay exact below 2^24; a run's combined
            # per-cell count is bounded by slots x capacity
            raise _MeshFallback("count_bound")
        if multi and "sum" in want:
            # any shared group between two windows of one run would
            # f32-add sum cells the host folds in f64.  When the group
            # column is the LEADING primary key, window group ranges
            # are ordered, so only adjacent boundary values can repeat
            # (transitively: a group shared by non-adjacent windows
            # pinches every window between to that one group, which
            # the adjacent checks catch).  Any other group column can
            # recur in non-adjacent windows — check EVERY pair (runs
            # are at most time-axis slots wide, so this stays tiny).
            lead_pk = (self.schema.primary_key_names[0] == spec.group_col
                       if self.schema.primary_key_names else False)
            for _s, a, b in runs:
                if lead_pk:
                    for i in range(a, b):
                        va, vb = items[i][2][0], items[i + 1][2][0]
                        if len(va) > 0 and len(vb) > 0 and va[-1] == vb[0]:
                            raise _MeshFallback("sum_overlap")
                else:
                    for i in range(a, b):
                        for j in range(i + 1, b + 1):
                            if np.intersect1d(items[i][2][0],
                                              items[j][2][0]).size:
                                raise _MeshFallback("sum_overlap")
        naggs = len(want) + (1 if "last" in want else 0)
        if g_pad * width * 4 * naggs > self.config.scan.mesh.max_grid_bytes:
            raise _MeshFallback("grid_budget")

    def _run_mesh_round(self, items: list, spec: AggregateSpec,
                        plan: ScanPlan, group_space=None,
                        download: bool = True, round_salt: int = 0):
        """Dispatch one round of host windows onto the 2-D scan mesh:
        per-slot window partials (series-sharded group blocks) plus the
        on-mesh segmented time-axis combine, one compiled program
        (parallel.scan.mesh_run_partials).

        download=True (the streaming pump): downloads each run TAIL's
        combined grids and returns [(seg_start, part, repay)] entries
        shaped exactly like _flush_host_round's emission — parts enter
        the same combine/memo machinery.  download=False (the top-k
        score/winner passes): returns the device outputs + run layout,
        nothing leaves the mesh here."""
        from horaedb_tpu.parallel.scan import (
            mesh_run_partials,
            shard_time_axis,
        )

        mesh = self.scan_mesh
        T = int(mesh.shape["time"])
        series = int(mesh.shape["series"])
        ensure(len(items) <= T, "mesh round exceeds the time axis")
        runs = self._mesh_runs(items)
        cap = max(it[1].capacity for it in items)
        if group_space is None:
            group_space = np.unique(
                np.concatenate([it[2][0] for it in items]))
        g = len(group_space)
        g_pad = max(8, series, 1 << (g - 1).bit_length())
        local_ok = all(
            it[1].encodings[spec.ts_col].kind == "offset" for it in items)
        width = self._window_grid_width(spec) if local_ok \
            else spec.num_buckets
        self._mesh_round_gates(items, runs, spec, g_pad, width, cap,
                               local_ok)
        space_fp = (g, hash(group_space.tobytes()))
        # round_salt disambiguates consecutive rounds of one segment
        # that share (seg0, T, cap, ...) — without it round 2's small
        # stacks overwrite round 1's and every replay/warm repeat
        # misses (the fused path's chunk-offset lesson, read above)
        stack_key = self._round_stack_key(items[0][0], spec, plan, T,
                                          cap, g_pad, width, space_fp
                                          ) + (round_salt,)
        put = functools.partial(shard_time_axis, mesh)
        ts_s, gid_s, val_s, remap_d, shift_d, lo_dev, lo = \
            self._build_round_stacks(items, spec, plan, T, cap, g_pad,
                                     width, group_space, local_ok,
                                     stack_key=stack_key, put=put,
                                     key_salt=("mesh2",))
        if any(int(lo[b]) >= spec.num_buckets for _s, _a, b in runs):
            raise _MeshFallback("lo_range")
        fn_key = (g_pad, width, spec.which)
        fn = self._mesh_run_fns.get(fn_key)
        if fn is None:
            fn = mesh_run_partials(mesh, num_groups=g_pad,
                                   num_buckets=width, which=spec.which)
            self._mesh_run_fns[fn_key] = fn
        # plan-order slot admission per mesh column: slot i is item i;
        # padding slots get unique negative ids so they never combine
        seg_ids = -(np.arange(T, dtype=np.int32) + 1)
        for ridx, (_s, a, b) in enumerate(runs):
            seg_ids[a:b + 1] = ridx
        t0 = time.perf_counter()
        out = fn(ts_s, gid_s, val_s, remap_d, shift_d, lo_dev,
                 shard_time_axis(mesh, seg_ids),
                 self._dev_scalar(spec.num_buckets),
                 self._dev_scalar(spec.bucket_ms, "arr1"))
        _MESH_ROUNDS.inc()
        if len(items) < T:
            from horaedb_tpu.storage import pipeline as pipeline_mod

            pipeline_mod.note_mesh_stall("time")
        if g <= (series - 1) * (g_pad // series):
            from horaedb_tpu.storage import pipeline as pipeline_mod

            pipeline_mod.note_mesh_stall("series")
        rows_per_shard = [int(it[1].n_valid) for it in items]
        pad_rows = (T - len(items)) * cap \
            + sum(cap - r for r in rows_per_shard)
        if not download:
            _STAGE_SECONDS["mesh_aggregate"].observe(
                time.perf_counter() - t0)
            deviceprof.record_round(
                "mesh_run", slots=len(items), capacity=T,
                rows_per_shard=rows_per_shard, padding_rows=pad_rows,
                seconds=time.perf_counter() - t0)
            return {"out": out, "runs": runs, "lo": lo,
                    "lo_dev": lo_dev, "g": g, "width": width}
        entries: list = []
        cells = 0
        dl_bytes = 0
        # the sync, then the tail-grid copies, each its own phase
        deviceprof.block_until_ready(out, fn="mesh_run_partials",
                                     table=self.table)
        with self._phase("scan.d2h", sync=True, fn="mesh_run_partials"):
            t_dl = time.perf_counter()
            for s, a, b in runs:
                lo_run, grids = self._slice_mesh_part(
                    out, b, g, int(lo[b]), width, spec)
                cells += sum(int(v.shape[0] * v.shape[1])
                             for v in grids.values())
                dl_bytes += sum(int(v.nbytes) for v in grids.values())
                entries.append((s, (group_space, lo_run, grids),
                                b - a + 1))
            t_dl = time.perf_counter() - t_dl
        deviceprof.charge_transfer("d2h", dl_bytes, seconds=t_dl)
        _STAGE_SECONDS["mesh_aggregate"].observe(time.perf_counter() - t0)
        _MESH_PARTS.inc(len(entries))
        _MESH_PART_CELLS.inc(cells)
        deviceprof.record_round(
            "mesh_run", slots=len(items), capacity=T,
            rows_per_shard=rows_per_shard, padding_rows=pad_rows,
            seconds=time.perf_counter() - t0)
        return entries

    @staticmethod
    def _slice_mesh_part(out: dict, tail_slot: int, g: int, lo_run: int,
                         width: int, spec: AggregateSpec):
        """THE mesh part emission, shared by the streaming download and
        the top-k winner pass so the two cannot drift: slice tail slot
        `tail_slot`'s combined grids to the real group count (g < 0 =
        keep all rows, the winner-sliced shape) and the query-clipped
        width, then rebase window-local last_ts to range_start-relative
        int64 — byte-for-byte the emission _flush_host_round's per
        -window parts use.  The slices COPY so the (T, g_pad, width)
        download is not pinned by the part (the PartsMemo views
        discipline)."""
        w_eff = min(width, spec.num_buckets - lo_run)
        rows = slice(None) if g < 0 else slice(0, g)
        grids = {k: np.ascontiguousarray(
            np.asarray(v[tail_slot])[rows, :w_eff])
            for k, v in out.items()}
        if "last_ts" in grids:
            lt = grids["last_ts"].astype(np.int64)
            grids["last_ts"] = np.where(
                grids["count"] > 0, lt + lo_run * spec.bucket_ms, lt)
        return lo_run, grids

    def _flush_mesh_round(self, items: list, spec: AggregateSpec,
                          plan: ScanPlan, round_salt: int = 0) -> list:
        """Pool-side mesh round flush: DevicePart entries (finished
        fused-decode partials) pass through in position; host windows
        dispatch onto the mesh, falling back PER ROUND to the single
        -chip kernel (_flush_host_round — the declared failure seam)
        on ineligibility or a failed dispatch (lost shard, XLA error).
        Returns [(seg_start, part_or_None, repaid_windows)]."""
        out: list = []
        host_items: list = []
        deco_items: list = []
        for s, w, prep in items:
            if prep is None:
                out.append((s, w.part, 1))
            elif prep is _DECODE_PREP:
                deco_items.append((s, w))
            else:
                host_items.append((s, w, prep))
        if deco_items:
            out.extend(self._run_mesh_decode_rounds(deco_items, spec,
                                                    plan))
        if not host_items:
            return out
        try:
            out.extend(self._run_mesh_round(host_items, spec, plan,
                                            round_salt=round_salt))
            return out
        except _MeshFallback as f:
            note_mesh_fallback(f.reason)
        except Exception as exc:  # noqa: BLE001 — counted, single-chip
            # fallback below reproduces the result (chaos-asserted)
            note_mesh_fallback("mesh_error")
            logger.warning(
                "mesh round failed (%s); re-running the round on the "
                "single-chip kernel", exc)
        # single-chip rounds are capped at agg_batch_windows; a mesh
        # chunk can be wider (time axis > agg_batch_windows), so split
        # it — per-window grids are round-composition-independent, so
        # the parts are identical either way
        hb = max(1, self.config.scan.agg_batch_windows)
        flushed = []
        for i in range(0, len(host_items), hb):
            flushed.extend(self._flush_host_round(
                host_items[i:i + hb], spec, plan))
        out.extend(
            (host_items[i][0], p[1] if p is not None else None, 1)
            for i, p in enumerate(flushed))
        return out

    def _run_mesh_decode_rounds(self, deco: list, spec: AggregateSpec,
                                plan: ScanPlan) -> list:
        """Batch one flush's deferred DecodePlans into sharded fused
        -decode rounds: plans group by static_key (one compiled program
        per group) in arrival order, time-axis-wide chunks each run as
        ONE mesh dispatch.  A round that declines (budget) or fails
        (lost shard, XLA error) falls back PER ITEM to the standalone
        fused dispatch (execute_plan) — still device decode, just not
        mesh-placed; reasons counted in scan_mesh_fallback_total."""
        T = int(self.scan_mesh.shape["time"])
        groups: dict = {}
        order: list = []
        for s, dp in deco:
            k = dp.static_key()
            if k not in groups:
                groups[k] = []
                order.append(k)
            groups[k].append((s, dp))
        entries: list = []
        for k in order:
            grp = groups[k]
            for i in range(0, len(grp), T):
                chunk = grp[i:i + T]
                try:
                    entries.extend(self._run_mesh_decode_round(
                        chunk, spec))
                    continue
                except _MeshFallback as f:
                    note_mesh_fallback(f.reason)
                except Exception as exc:  # noqa: BLE001 — counted,
                    # per-item fused dispatch reproduces the parts
                    note_mesh_fallback("mesh_error")
                    logger.warning(
                        "mesh decode round failed (%s); running the "
                        "per-segment fused dispatch", exc)
                for s, dp in chunk:
                    with self._phase("scan.dispatch", sync=True):
                        disp = device_decode.execute_plan(dp, self.table)
                    part = disp.finalize()
                    entries.append((s, part.part, 1))
        return entries

    def _run_mesh_decode_round(self, chunk: list,
                               spec: AggregateSpec) -> list:
        """ONE device program from stored bytes to combined run grids:
        stack the chunk's raw encoded buffers one segment per time
        slot, run leaf-filter + (k-way merge | sort | presorted) +
        keep-last dedup + bucket aggregate + segmented ppermute combine
        in a single shard_map dispatch (parallel.scan
        .mesh_decode_partials), then download run-TAIL grids only.

        Slot-local group code spaces ARE the round rows (identity
        remap): same-segment consecutive slots share a seg id — and
        therefore combine on the mesh — only when their dictionaries,
        first bucket, group count and clipped width all match AND the
        combine is exact for the requested aggs (no additive sum
        cells, f32-count bound); everything else gets a unique id and
        comes back as its own part, exactly what the standalone fused
        dispatches would emit."""
        from horaedb_tpu.parallel.scan import (
            mesh_decode_partials,
            shard_time_axis,
        )

        mesh = self.scan_mesh
        T = int(mesh.shape["time"])
        series = int(mesh.shape["series"])
        dps = [dp for _s, dp in chunk]
        dp0 = dps[0]
        cap = max(dp.cap for dp in dps)
        g_pad = max(8, series, max(dp.g_pad for dp in dps))
        width = max(dp.use_width for dp in dps)
        want = combine_mod.expand_which(spec.which)
        naggs = len(want) + (1 if "last" in want else 0)
        ncol = len(dp0.upload_names)
        if (T * cap * 4 * ncol
                > self.config.scan.decode.max_upload_bytes
                or T * g_pad * width * 4 * naggs
                > self.config.scan.mesh.max_grid_bytes):
            raise _MeshFallback("mesh_decode_budget")
        # seg-id sharing gates — see docstring; unique negative ids on
        # padding slots so they never combine (mesh_run_partials'
        # convention)
        sharable = "sum" not in want and T * cap < (1 << 24)
        seg_ids = -(np.arange(T, dtype=np.int32) + 1)
        rid = -1
        for i, (s, dp) in enumerate(chunk):
            joined = False
            if i and sharable:
                ps, pdp = chunk[i - 1]
                joined = (ps == s and pdp.lo == dp.lo
                          and pdp.shift == dp.shift
                          and pdp.g == dp.g and pdp.w_eff == dp.w_eff
                          and np.array_equal(pdp.values, dp.values))
            if not joined:
                rid += 1
            seg_ids[i] = rid
        t0 = time.perf_counter()
        put = functools.partial(shard_time_axis, mesh)
        # decode round stacks are HBM-resident and ride the SAME LRU +
        # weakref discipline as the host-window round stacks (anchored
        # on the cached EncodedSegments instead of merged windows), so
        # warm repeats skip the re-upload and drop_hbm_state evicts
        # them with everything else stack_cache-accounted
        ncst = len(dp0.consts)
        stack_key = ("meshdecode", dp0.static_key(), cap, T,
                     tuple((s, dp.shift, dp.lo, dp.es.n,
                            tuple(c.tobytes() for c in dp.consts))
                           for s, dp in chunk))
        es_list = tuple(dp.es for dp in dps)
        cached = self._stack_cache_get(stack_key, es_list)
        if cached is not None:
            cols_dev = cached[:ncol]
            consts_dev = cached[ncol:ncol + ncst]
            nv_dev, offs_dev, shift_dev, lo_dev = cached[ncol + ncst:]
            upload_bytes = 0
        else:
            # host stacks: one (T, cap) matrix per upload column,
            # padding slots all-zero with n_valid 0 (every row invalid
            # on device)
            cols_np = [np.zeros((T, cap),
                                dtype=dp0.es.columns[nm].dtype)
                       for nm in dp0.upload_names]
            nv = np.zeros(T, dtype=np.int32)
            shift_np = np.zeros(T, dtype=np.int32)
            lo_np = np.zeros(T, dtype=np.int32)
            consts_np = [np.tile(c, (T, 1)).astype(np.int32)
                         for c in dp0.consts]
            if dp0.route == "kway":
                offs_np = np.full((T, dp0.num_runs + 1), cap,
                                  dtype=np.int32)
                offs_np[:, 0] = 0
            else:
                offs_np = np.zeros((T, 1), dtype=np.int32)
            upload_bytes = sum(c.nbytes for c in cols_np)
            for t, (s, dp) in enumerate(chunk):
                n = dp.es.n
                for j, nm in enumerate(dp0.upload_names):
                    cols_np[j][t, :n] = dp.es.columns[nm]
                nv[t] = n
                shift_np[t] = dp.shift
                lo_np[t] = dp.lo
                for ci, c in enumerate(dp.consts):
                    consts_np[ci][t] = c
                if dp0.route == "kway":
                    # rebuild against the ROUND capacity: real run
                    # bounds, then the pad zone [n, cap) as its own
                    # run, trailing runs empty at cap (the
                    # ops/merge.kway_merge_perm contract)
                    rl = dp.es.run_lengths
                    real = np.cumsum((0,) + tuple(rl))
                    offs_np[t, :len(real)] = real
                    offs_np[t, len(rl):] = cap
                    offs_np[t, len(rl)] = n
            cols_dev = tuple(put(c) for c in cols_np)
            consts_dev = tuple(put(c) for c in consts_np)
            nv_dev, offs_dev = put(nv), put(offs_np)
            shift_dev, lo_dev = put(shift_np), put(lo_np)
            self._stack_cache_put(
                stack_key, es_list,
                cols_dev + consts_dev
                + (nv_dev, offs_dev, shift_dev, lo_dev))
        fn_key = ("decode", dp0.static_key(), g_pad, width)
        fn = self._mesh_run_fns.get(fn_key)
        if fn is None:
            fn = mesh_decode_partials(
                mesh, num_groups=g_pad, num_buckets=width,
                which=spec.which, key_slots=dp0.key_slots,
                num_pks=dp0.num_pks, group_pos=dp0.group_pos,
                ts_pos=dp0.ts_pos, val_slot=dp0.val_slot,
                leaf_prog=dp0.leaf_prog, route=dp0.route,
                num_runs=dp0.num_runs, cells_sorted=dp0.cells_sorted)
            self._mesh_run_fns[fn_key] = fn
        out, _kept = fn(cols_dev, nv_dev, consts_dev, offs_dev,
                        shift_dev, lo_dev, put(seg_ids),
                        self._dev_scalar(spec.num_buckets),
                        self._dev_scalar(spec.bucket_ms, "arr1"))
        _MESH_ROUNDS.inc()
        if len(chunk) < T:
            from horaedb_tpu.storage import pipeline as pipeline_mod

            pipeline_mod.note_mesh_stall("time")
        # run-tail emission, byte-for-byte DecodeDispatch.finalize's
        # shape: slice to the tail plan's real group count and clipped
        # width (copies — the (T, g_pad, width) download must not stay
        # pinned), rebase window-local last_ts to range-relative int64
        entries: list = []
        cells = 0
        src_rows = 0
        dl_bytes = 0
        a = 0
        deviceprof.block_until_ready(out, fn="mesh_decode_partials",
                                     table=self.table)
        with self._phase("scan.d2h", sync=True, fn="mesh_decode_partials"):
            t_dl = time.perf_counter()
            for i in range(len(chunk)):
                if i + 1 < len(chunk) and seg_ids[i + 1] == seg_ids[i]:
                    continue
                s, dp = chunk[i]
                grids = {k: np.ascontiguousarray(
                    np.asarray(v[i])[:dp.g, :dp.w_eff])
                    for k, v in out.items()}
                if "last_ts" in grids:
                    lt = grids["last_ts"].astype(np.int64)
                    grids["last_ts"] = np.where(
                        grids["count"] > 0,
                        lt + dp.lo * spec.bucket_ms, lt)
                cells += sum(int(v.shape[0] * v.shape[1])
                             for v in grids.values())
                dl_bytes += sum(int(v.nbytes) for v in grids.values())
                src_rows += sum(dp2.src_rows
                                for _s2, dp2 in chunk[a:i + 1])
                entries.append(
                    (s, (dp.values, dp.lo, grids), i - a + 1))
                a = i + 1
            t_dl = time.perf_counter() - t_dl
        deviceprof.charge_transfer("d2h", dl_bytes, seconds=t_dl)
        _MESH_PARTS.inc(len(entries))
        _MESH_PART_CELLS.inc(cells)
        deviceprof.record_round(
            "mesh_decode", slots=len(chunk), capacity=T,
            rows_per_shard=[int(dp.es.n) for _s, dp in chunk],
            padding_rows=(T - len(chunk)) * cap
            + sum(cap - int(dp.es.n) for _s, dp in chunk),
            upload_bytes=upload_bytes, stack_hit=cached is not None,
            seconds=time.perf_counter() - t0)
        device_decode.observe_decode_stage(
            time.perf_counter() - t0, rows=src_rows,
            nbytes=upload_bytes)
        return entries

    async def _aggregate_segments_mesh(self, plan: ScanPlan,
                                       spec: AggregateSpec, memo_store):
        """The mesh twin of _aggregate_segments_pump: the pipeline's
        fetch/decode stages feed this device stage, which admits
        windows to mesh time slots strictly in plan order and flushes
        rounds of time-axis width.  Per-segment run parts come back
        through the same yield/memo contract, so replans, the
        PartsMemo, and the sorted-segment fold are untouched."""
        from collections import deque

        from horaedb_tpu.storage import pipeline as pipeline_mod

        batch_w = int(self.scan_mesh.shape["time"])
        queue: list[tuple[int, encode.DeviceBatch, tuple]] = []
        parts: dict[int, list] = {}
        pending: dict[int, int] = {}
        arrived: "deque[int]" = deque()

        def pipelined() -> bool:
            return plan.pipeline_active
        flush_task: Optional[asyncio.Task] = None
        flush_ordinal = 0

        def _apply(flushed) -> None:
            for seg_start, part, repay in flushed:
                if part is not None:
                    parts[seg_start].append(part)
                pending[seg_start] -= repay

        async def settle_flush() -> None:
            nonlocal flush_task
            if flush_task is None:
                return
            t, flush_task = flush_task, None
            _apply(await t)

        async def flush_round(chunk: list, salt: int) -> list:
            t0 = time.perf_counter()
            out = await self._run_pool(
                plan.pool, self._flush_mesh_round, chunk, spec, plan,
                salt)
            pipeline_mod.observe_stage(
                "device", time.perf_counter() - t0,
                rows=sum(w.n_valid for _s, w, _p in chunk))
            return out

        async def flush(k: int) -> None:
            nonlocal flush_task, flush_ordinal
            chunk = queue[:k]
            del queue[:k]
            salt = flush_ordinal
            flush_ordinal += 1
            if not pipelined():
                _apply(await self._run_pool(
                    plan.pool, self._flush_mesh_round, chunk, spec,
                    plan, salt))
                return
            # stage-boundary checkpoint: no new mesh round for an
            # expired query (the in-flight one drains via settle)
            deadline_checkpoint()
            await settle_flush()
            flush_task = asyncio.create_task(flush_round(chunk, salt))

        windows_iter = self._cached_windows(plan)
        try:
            try:
                async for seg, windows, read_s in windows_iter:
                    t0 = time.perf_counter()
                    s = seg.segment_start
                    arrived.append(s)
                    parts[s] = []
                    pending[s] = 0

                    def prep_windows(ws=windows):
                        out = []
                        for w in ws:
                            _ROWS_SCANNED.inc(w.n_valid)
                            if isinstance(w, device_decode.DecodePlan):
                                # deferred fused decode: batched into
                                # sharded rounds at flush time
                                out.append((w, _DECODE_PREP))
                                continue
                            if isinstance(w, device_decode.DevicePart):
                                if w.part is not None:
                                    out.append((w, None))
                                continue
                            prep = self._window_groups(w, spec, plan)
                            if prep is not None:
                                out.append((w, prep))
                        return out

                    for w, prep in await self._run_pool(
                            plan.pool, self._phased(
                                "scan.group_prep", prep_windows,
                                segment=s)):
                        queue.append((s, w, prep))
                        pending[s] += 1
                    while len(queue) >= batch_w:
                        await flush(batch_w)
                    _SCAN_LATENCY.observe(read_s
                                          + (time.perf_counter() - t0))
                    while arrived and pending[arrived[0]] == 0:
                        s0 = arrived.popleft()
                        seg_parts = parts.pop(s0)
                        memo_store(s0, seg_parts)
                        yield s0, seg_parts
            finally:
                await windows_iter.aclose()
            if queue:
                await flush(len(queue))
            await settle_flush()
            while arrived:
                s0 = arrived.popleft()
                seg_parts = parts.pop(s0)
                memo_store(s0, seg_parts)
                yield s0, seg_parts
        finally:
            if flush_task is not None:
                # cancelled/failed scan: drain the in-flight mesh
                # round so it never races table teardown (zero leaked
                # tasks — the deadline-mid-mesh chaos schedule asserts
                # it)
                flush_task.cancel()
                await asyncio.gather(flush_task, return_exceptions=True)

    async def _aggregate_topk_mesh(self, plan: ScanPlan,
                                   spec: AggregateSpec, tk):
        """Egress-bounded top-k on the scan mesh, two passes over the
        collected windows (two-phase like the fused path — the budget
        gate in _mesh_topk_ok bounds the pinned rows):

          score   every round's segmented-combined grids fold into a
                  device-resident (groups, buckets) score state —
                  selection ops, exact — and only a per-group
                  (score, has) vector downloads: O(groups) bytes;
          winners rank on host with combine.rank_top_k (the same
                  stable tie-break combine_top_k uses), then re-run
                  the rounds (stacks are LRU-cached) and download ONLY
                  the k winners' grid rows per run: O(k x buckets x
                  aggs) per part, independent of cardinality
                  (scan_mesh_part_cells_total asserts it).

        Yields (seg_start, winner-sliced parts); finalize_aggregate's
        combine_top_k then reproduces the full ranking byte-for-byte
        restricted to the winner set.  Any round-level ineligibility
        (sum overlap, budget, mesh error) downgrades the WHOLE query
        to full-width mesh parts — correct, just not egress-bounded."""
        from horaedb_tpu.parallel import scan as pscan

        T = int(self.scan_mesh.shape["time"])
        items: list = []
        windows_iter = self._cached_windows(plan)
        try:
            async for seg, windows, read_s in windows_iter:
                s = seg.segment_start

                def prep_windows(ws=windows, s=s):
                    out = []
                    for w in ws:
                        _ROWS_SCANNED.inc(w.n_valid)
                        prep = self._window_groups(w, spec, plan)
                        if prep is not None:
                            out.append((s, w, prep))
                    return out

                items.extend(await self._run_pool(
                    plan.pool, self._phased("scan.group_prep",
                                            prep_windows, segment=s)))
                _SCAN_LATENCY.observe(read_s)
        finally:
            await windows_iter.aclose()
        if not items:
            return
        # canonical fold order: sorted segment, window order within —
        # the order finalize folds parts in, so pass-2 part emission
        # matches the control's arithmetic order exactly
        items.sort(key=lambda it: it[0])
        all_values = np.unique(np.concatenate([it[2][0]
                                               for it in items]))
        g = len(all_values)
        series = int(self.scan_mesh.shape["series"])
        g_pad = max(8, series, 1 << (g - 1).bit_length())
        local_ok = all(it[1].encodings[spec.ts_col].kind == "offset"
                       for it in items)
        width = self._window_grid_width(spec) if local_ok \
            else spec.num_buckets
        chunks = [items[i:i + T] for i in range(0, len(items), T)]
        bucket_dev = self._dev_scalar(spec.bucket_ms)
        additive = tk.by in ("count", "sum", "avg")
        if additive:
            state = pscan.mesh_additive_init(
                g_pad, spec.num_buckets + width, tk.by)
        else:
            state = pscan.mesh_score_init(
                g_pad, spec.num_buckets + width, tk.by)
        # the score state is device-resident for the whole two-pass
        # ranking: account it (mesh_state ledger kind) and free it on
        # EVERY exit path before any parts yield
        state_bytes = sum(int(v.nbytes) for v in state.values())
        self._mesh_state_bytes += state_bytes
        downgrade = None
        finished = None
        try:
            try:
                for ci, chunk in enumerate(chunks):
                    deadline_checkpoint()

                    def score_round(chunk=chunk, state=state, ci=ci):
                        got = self._run_mesh_round(
                            chunk, spec, plan, group_space=all_values,
                            download=False, round_salt=ci)
                        if additive:
                            # TAIL slots only: a tail's segmented
                            # combine already holds its whole run,
                            # prefixes would double-count
                            tails = np.zeros(T, dtype=bool)
                            for _s, _a, b in got["runs"]:
                                tails[b] = True
                            return pscan.mesh_additive_update(
                                state, got["out"]["count"],
                                got["out"].get("sum",
                                               got["out"]["count"]),
                                jnp.asarray(tails), got["lo_dev"],
                                by=tk.by)
                        last_ts = (got["out"].get("last_ts")
                                   if tk.by == "last" else None)
                        return pscan.mesh_score_update(
                            state, got["out"][tk.by],
                            got["out"]["count"], last_ts,
                            got["lo_dev"], bucket_dev, by=tk.by)

                    state = await self._run_pool(plan.pool,
                                                 score_round)
            except _MeshFallback as f:
                downgrade = f.reason
            except NotFoundError:
                raise  # compaction race: the caller replans
            except Exception as exc:  # noqa: BLE001 — counted
                # downgrade
                downgrade = "mesh_error"
                logger.warning("mesh top-k scoring failed (%s); "
                               "serving full-width parts", exc)
            if downgrade is None:
                def finish_scores():
                    if not additive:
                        scores_d, has_d = pscan.mesh_score_finalize(
                            state, largest=tk.largest,
                            num_buckets=spec.num_buckets)
                        _MESH_SCORE_CELLS.inc(2 * g)
                        return (np.asarray(scores_d)[:g]
                                .astype(np.float64),
                                np.asarray(has_d)[:g])
                    fin = pscan.mesh_additive_finalize(
                        state, by=tk.by, largest=tk.largest,
                        num_buckets=spec.num_buckets)
                    if bool(fin["lossy"]):
                        # an add was not provably exact: the
                        # compensated pair may not match the host's
                        # f64 fold — counted downgrade to full parts,
                        # never a silently drifted winner set
                        return None
                    if tk.by == "avg":
                        # the device cannot divide bit-identically to
                        # the host, so avg downloads the full (groups,
                        # buckets) cnt/sum pairs and the host runs
                        # combine_top_k's exact score formula — the
                        # one honestly O(g x buckets) score egress
                        # (counted as such)
                        cnt = (np.asarray(fin["cnt_hi"], np.float64)
                               + np.asarray(fin["cnt_lo"],
                                            np.float64))[:g]
                        sm = (np.asarray(fin["sum_hi"], np.float64)
                              + np.asarray(fin["sum_lo"],
                                           np.float64))[:g]
                        hs = np.asarray(fin["has"])[:g]
                        _MESH_SCORE_CELLS.inc(5 * cnt.size + g)
                        with np.errstate(invalid="ignore",
                                         divide="ignore"):
                            cell = sm / np.maximum(cnt, 1)
                        fill = -np.inf if tk.largest else np.inf
                        cell = np.where(hs, cell, fill)
                        sc = (cell.max(axis=1) if tk.largest
                              else cell.min(axis=1))
                        return sc, hs.any(axis=1)
                    sc = (np.asarray(fin["score_hi"], np.float64)
                          + np.asarray(fin["score_lo"],
                                       np.float64))[:g]
                    _MESH_SCORE_CELLS.inc(3 * g)
                    return sc, np.asarray(fin["has_any"])[:g]

                finished = await self._run_pool(plan.pool,
                                                finish_scores)
        finally:
            state = None
            self._mesh_state_bytes -= state_bytes
        if downgrade is not None:
            note_mesh_fallback(downgrade)
            # full-width mesh parts through the normal chunk flush —
            # still byte-identical, just not egress-bounded (finalize's
            # host combine_top_k ranks them)
            async for out in self._yield_chunks_as_parts(chunks, spec,
                                                         plan):
                yield out
            return
        if finished is None:
            note_mesh_fallback("additive_topk")
            async for out in self._yield_chunks_as_parts(chunks, spec,
                                                         plan):
                yield out
            return
        scores, has_any = finished
        kept = np.flatnonzero(has_any)
        winners = combine_mod.rank_top_k(
            [int(r) for r in kept], scores[kept], tk)
        if not winners:
            return
        w_rows = np.asarray(sorted(winners), dtype=np.int32)
        winner_values = all_values[w_rows]
        seg_parts: dict[int, list] = {}
        cells = 0
        try:
            for ci, chunk in enumerate(chunks):
                deadline_checkpoint()

                def winner_round(chunk=chunk, ci=ci):
                    got = self._run_mesh_round(chunk, spec, plan,
                                               group_space=all_values,
                                               download=False,
                                               round_salt=ci)
                    sliced = pscan.mesh_take_rows(got["out"],
                                                  jnp.asarray(w_rows))
                    out = []
                    for s, _a, b in got["runs"]:
                        # the round's OWN grid width: a chunk whose ts
                        # encodings forced full-range grids is wider
                        # than the offset-encoded default
                        lo_run, grids = self._slice_mesh_part(
                            sliced, b, -1, int(got["lo"][b]),
                            got["width"], spec)
                        out.append((s, (winner_values, lo_run, grids)))
                    return out

                for s, part in await self._run_pool(plan.pool,
                                                    winner_round):
                    seg_parts.setdefault(s, []).append(part)
                    cells += sum(int(v.shape[0] * v.shape[1])
                                 for v in part[2].values())
        except NotFoundError:
            raise  # compaction race: the caller replans
        except Exception as exc:  # noqa: BLE001 — counted downgrade;
            # nothing has been yielded (all-or-nothing), so the full
            # -width path below replaces the winner slices wholesale
            note_mesh_fallback("mesh_error"
                               if not isinstance(exc, _MeshFallback)
                               else exc.reason)
            logger.warning("mesh top-k winner pass failed (%s); "
                           "serving full-width parts", exc)
            async for out in self._yield_chunks_as_parts(chunks, spec,
                                                         plan):
                yield out
            return
        _MESH_PART_CELLS.inc(cells)
        _MESH_TOPK.inc()
        for s in sorted(seg_parts):
            yield s, seg_parts[s]

    async def _yield_chunks_as_parts(self, chunks: list,
                                     spec: AggregateSpec,
                                     plan: ScanPlan):
        """Downgrade path for the top-k mesh route: flush the already
        -collected window chunks through the normal mesh round (its
        own per-round fallback included) and yield per-segment full
        parts — finalize's host combine_top_k ranks them instead."""
        seg_parts: dict[int, list] = {}
        for ci, chunk in enumerate(chunks):
            deadline_checkpoint()
            flushed = await self._run_pool(
                plan.pool, self._flush_mesh_round, chunk, spec, plan,
                ci)
            for s, part, _repay in flushed:
                if part is not None:
                    seg_parts.setdefault(s, []).append(part)
        for s in sorted(seg_parts):
            yield s, seg_parts[s]

    def finalize_aggregate(self, parts: list, spec: AggregateSpec,
                           top_k=None):
        """Parts to grids: the `scan.combine` phase."""
        with self._phase("scan.combine", sync=True, parts=len(parts)):
            return self._finalize_aggregate(parts, spec, top_k)

    def _finalize_aggregate(self, parts: list, spec: AggregateSpec,
                            top_k=None):
        """Combine per-window parts into the user-facing grids.

        Mode-dispatched through storage/combine.py ([scan.combine]):
        the sparse fold pastes parts straight into the output buffers;
        `dense` keeps the pre-sparse accumulator fold as the
        bit-identity control.  A `top_k` spec pushes the ranking down
        into combine (combine_top_k) so only the k winners' rows are
        ever materialized — the full groups x buckets grid is never
        built (the north-star 1B top-k's bound).  In `dense` mode the
        pushdown is OFF too: the control materializes the full grid and
        ranks host-side (apply_top_k), so the mode flag A/Bs the whole
        pre-change path, not just the fold."""
        mode = self.config.scan.combine.mode
        t0 = time.perf_counter()
        try:
            if top_k is not None and mode != "dense":
                # empty-group drop is built into the pushdown (groups
                # are dropped before ranking, same cells as the dense
                # drop below)
                group_values, grids = combine_mod.combine_top_k(
                    parts, spec.num_buckets, spec.which, top_k)
            else:
                group_values, grids = combine_mod.combine_parts(
                    parts, spec.num_buckets, which=spec.which, mode=mode)
                # drop groups with no row in ANY bucket: the aligned
                # fast path omits the ts leaf (query_downsample), so
                # boundary-segment rows outside [start, end) can
                # register a group whose every cell is empty — without
                # this the aligned and ts-leaf paths return different
                # tsid sets for the same data
                if len(group_values):
                    nonzero = grids["count"].sum(axis=1) > 0
                    if not nonzero.all():
                        group_values = group_values[nonzero]
                        grids = {k: v[nonzero] for k, v in grids.items()}
                if top_k is not None:
                    from horaedb_tpu.storage.plan import apply_top_k

                    group_values, grids = apply_top_k(group_values,
                                                      grids, top_k)
        finally:
            dt = time.perf_counter() - t0
            _STAGE_SECONDS["combine"].observe(dt)
            trace_add("stage_combine_ms", dt * 1e3)
        # last_ts is computed relative to range_start on device; expose it
        # as ABSOLUTE time so all downsample paths share one unit
        if len(group_values) and "last_ts" in grids:
            grids["last_ts"] = grids["last_ts"] + spec.range_start
        return group_values, grids

    # ---- row selection under a value predicate (ops/select.py) ------------

    def _select_route(self, plan: ScanPlan) -> Optional[str]:
        """None where a select over `plan` may run on the device, else
        the reason it takes the host route: the [scan.decode] mode's
        choice first (as _device_decode_plan_ok reads it; there is no
        fused accumulator to defer to here, so `auto` on an accelerator
        means the device), then what the plan itself rules out."""
        mode = self._decode_mode()
        if mode == "host":
            return "mode_host"
        if mode == "auto" and jax.default_backend() == "cpu":
            return "cpu_auto"
        if plan.mode is not UpdateMode.OVERWRITE:
            return "append_mode"
        if (plan.predicate is not None and not plan.pushed_complete) \
                or not device_decode.leaf_shape_supported(
                    plan.prune_leaves):
            return "predicate"
        if not self._sidecar_plan_ok(plan):
            return "no_sidecar"
        return None

    @staticmethod
    def _note_select(route: str, reason: str, segments: int = 1) -> None:
        _SELECT_SEGMENTS.labels(route=route, reason=reason).inc(segments)
        if reason and reason not in _SELECT_MODE_REASONS:
            for _ in range(segments):
                device_decode.note_fallback(reason)

    async def select_segments(self, plans: list, spec, asked: list):
        """Per segment, yield (segment_start, SelectedRows): the rows of
        plans[0] (the predicate's field) whose current value passes
        `spec`, and at their (series, timestamp) the values of the
        fields asked (`asked`: indexes into `plans`, one a column of
        the answer; plans[1:] are the other distinct fields, each over
        the same segments as plans[0]).

        The device route answers a segment from its fields' resident
        decode slices (the aggregate route's: same keys, same account;
        a miss reads, narrows and uploads the slice as that route's
        miss does and leaves it resident for both), all segments of
        the query in one pool job.  A segment the device route cannot
        take, and every segment where the plan or the mode rules it
        out, is answered by the host decode route; each with its
        reason counted."""
        segments = plans[0].segments
        ensure(all([s.segment_start for s in p.segments]
                   == [s.segment_start for s in segments] for p in plans),
               "select: the fields' plans differ in their segments")
        reason = self._select_route(plans[0])
        if reason is not None:
            self._note_select("host", reason, len(segments))
            async for out in self._select_segments_host(
                    plans, spec, asked, range(len(segments))):
                yield out
            return
        # only the columns count: they are the slices' key, which a
        # select shares with the aggregates over the same field
        carrier = AggregateSpec(
            group_col=spec.group_col, ts_col=spec.ts_col,
            value_col=spec.value_col, range_start=0, bucket_ms=1,
            num_buckets=1, which=("count",))
        plans = [dc_replace(p, decode_spec=carrier) for p in plans]
        slice_columns = [self._decode_slice_columns(p) for p in plans]
        with self._phase("scan.windows",
                         segments=len(segments)) as probe:
            resident = [[self.scan_cache.get_slice(
                self._decode_slice_key(plan.segments[k], cols))
                for plan, cols in zip(plans, slice_columns)]
                for k in range(len(segments))]
            hits = sum(s is not None for row in resident for s in row)
            device_decode.note_resident("hit", hits)
            device_decode.note_resident(
                "miss", len(segments) * len(plans) - hits)
            probe.fields["resident"] = hits
        on_device: list = []   # (position, windows: predicate's, asked)
        on_host: list = []
        for k, seg in enumerate(segments):
            deadline_checkpoint()
            windows: list = []
            for plan, cols, seg_slice in zip(plans, slice_columns,
                                             resident[k]):
                if seg_slice is None:
                    seg_slice = await self._load_select_slice(
                        plan.segments[k], plan, cols)
                got = (seg_slice if not isinstance(
                    seg_slice, device_decode.SegmentSlice)
                    else select_ops.plan_window(seg_slice,
                                                plan.prune_leaves))
                if isinstance(got, str):
                    windows = got
                    break
                windows.append(got)
            if isinstance(windows, str):
                self._note_select("host", windows)
                on_host.append(k)
            elif windows[0] is None:
                # the predicate's field has no row here: nothing to join
                self._note_select("device", "")
                yield seg.segment_start, select_ops.SelectedRows(
                    groups=np.zeros(0, np.uint64),
                    timestamps=np.zeros(0, np.int64),
                    values=[np.zeros(0, np.float32)] * len(asked),
                    found=[np.zeros(0, bool)] * len(asked), scanned=0)
            else:
                on_device.append(
                    (k, [windows[0]] + [windows[j] for j in asked]))
        if on_device:
            deadline_checkpoint()
            parts = await self._run_pool(
                plans[0].pool, self._select_resident,
                [windows for _k, windows in on_device], spec)
            for (k, _windows), part in zip(on_device, parts):
                self._note_select("device", "")
                self._count_selected(part, "device")
                yield segments[k].segment_start, part
        if on_host:
            async for out in self._select_segments_host(
                    plans, spec, asked, on_host):
                yield out

    def _select_resident(self, windows: list, spec) -> list:
        """Pool-side: the device route's one job a query."""
        cpu0 = time.thread_time()
        try:
            return select_ops.select_resident(windows, spec, self._phase,
                                              self.table)
        finally:
            _SELECT_CPU.inc(time.thread_time() - cpu0)

    @staticmethod
    def _count_selected(part, route: str) -> None:
        n = len(part.timestamps)
        if route == "device":
            _SELECT_ROWS.labels(side="scanned", route=route).inc(
                part.scanned)
        _SELECT_ROWS.labels(side="selected", route=route).inc(n)
        found = sum(int(np.count_nonzero(f)) for f in part.found)
        _SELECT_CELLS["found"].inc(found)
        _SELECT_CELLS["null"].inc(n * len(part.found) - found)
        with span("select.segment", route=route, rows_in=part.scanned,
                  rows_out=n):
            pass

    async def _load_select_slice(self, seg: SegmentPlan, plan: ScanPlan,
                                 slice_columns: tuple):
        """A missed slice read, planned and uploaded, and left resident
        where the scan cache may keep it: the SegmentSlice, None where
        the field provably has no row in the segment, or the reason
        the segment takes the host route."""
        if self._stream_segment(seg):
            return "streamed"
        table, _read_s = await self._read_segment_any(seg, plan)
        if not isinstance(table, sidecar.EncodedSegment):
            return "parquet"
        seg_slice = await self._run_pool(
            plan.pool, self._upload_select_slice, table, plan)
        if isinstance(seg_slice, device_decode.DevicePart):
            return None
        if isinstance(seg_slice, device_decode.SegmentSlice) \
                and seg_slice.admissible:
            self.scan_cache.put_slice(
                self._decode_slice_key(seg, slice_columns), seg_slice)
        return seg_slice

    def _upload_select_slice(self, es: "sidecar.EncodedSegment",
                             plan: ScanPlan):
        """Pool-side: a missed slice planned (plan_segment: the same
        narrowing, layout and route the aggregate's miss decides) and
        put on the device."""
        spec = plan.decode_spec
        with self._phase("scan.group_prep", rows=es.n):
            got = device_decode.plan_segment(
                es, spec.group_col, spec.ts_col, spec.value_col,
                pk_names=self._pk_names_in(list(es.names)),
                seq_name=SEQ_COLUMN_NAME,
                leaves=es.pending_leaves or [],
                max_bytes=self.config.scan.decode.max_upload_bytes,
                pad_capacity=encode.pad_capacity)
        if not isinstance(got, device_decode.SegmentSlice):
            return got
        with self._phase("scan.dispatch", sync=True, h2d_bytes=got.nbytes):
            device_decode.upload_slice(got)
        return got.resident()

    async def _select_segments_host(self, plans: list, spec, asked: list,
                                    positions):
        """The host route of select_segments for the segments at
        `positions`: the predicate's field through the host decode with
        the value leaf evaluated after the merge (the row scan's own
        filter), every other field asked through a row scan of the
        same segments, joined in numpy on (series, timestamp)."""
        keep = {plans[0].segments[k].segment_start for k in positions}
        if not keep:
            return
        first = plans[0]
        leaf = _VALUE_LEAF[spec.op](spec.value_col, spec.threshold)
        children = (list(first.predicate.children)
                    if isinstance(first.predicate, filter_ops.And)
                    else [] if first.predicate is None
                    else [first.predicate])

        def kept(plan: ScanPlan, predicate) -> ScanPlan:
            ssts = [f for seg in plan.segments
                    if seg.segment_start in keep for f in seg.ssts]
            return self.build_plan(
                ssts, ScanRequest(range=plan.range, predicate=predicate),
                use_cache=plan.use_cache, pool=plan.pool)

        async def rows_of(plan: ScanPlan) -> dict:
            """{segment start: (series, timestamps, float32 values)}"""
            got: dict = {start: [] for start in keep}
            scan = self.execute_segments(plan)
            try:
                async for start, batch in scan:
                    if batch is not None:
                        got[start].append(batch)
            finally:
                await scan.aclose()
            return {start: _scanned_columns(batches, spec)
                    for start, batches in got.items()}

        selected = await rows_of(kept(first, filter_ops.And(children
                                                            + [leaf])))
        others = {j: await rows_of(kept(plans[j], plans[j].predicate))
                  for j in sorted(set(asked) - {0})}
        for start in sorted(keep):
            groups, ts, vals = selected[start]
            order = np.lexsort((ts, groups))
            groups, ts, vals = groups[order], ts[order], vals[order]
            values, found = [], []
            for j in asked:
                if j == 0:
                    values.append(vals)
                    found.append(np.ones(len(ts), bool))
                    continue
                v, f = join_on_host(groups, ts, *others[j][start])
                values.append(v)
                found.append(f)
            part = select_ops.SelectedRows(
                groups=groups, timestamps=ts, values=values, found=found,
                scanned=0)
            self._count_selected(part, "host")
            yield start, part

    def finalize_select(self, parts: list, n_fields: int) -> dict:
        """Segments' SelectedRows (or a last-row walk's LastRows: one
        row a series, no series in two parts) to the answer's columns,
        sorted by (series, timestamp): the `scan.combine` phase.  A field found
        at every row has None for its flags (nothing to mask, and
        nothing to sort: this runs on the loop's thread, once a query,
        over every row of the answer)."""
        with self._phase("scan.combine", sync=True, parts=len(parts)):
            def cat(arrays, kind):
                return (np.concatenate(arrays) if arrays
                        else np.zeros(0, kind))

            groups = _cat_groups([p.groups for p in parts])
            ts = cat([p.timestamps for p in parts], np.int64)
            order = np.lexsort((ts, groups))
            return {
                "groups": groups[order], "timestamps": ts[order],
                "values": [cat([p.values[f] for p in parts],
                               np.float32)[order]
                           for f in range(n_fields)],
                "found": [None if all(p.found[f].all() for p in parts)
                          else cat([p.found[f] for p in parts],
                                   bool)[order]
                          for f in range(n_fields)]}

    # ---- the newest row of every series (ops/last.py) ---------------------

    async def last_segment(self, plans: list, spec,
                           missing: np.ndarray) -> "last_ops.LastRows | str":
        """ONE segment's newest rows (ops/last.LastSpec) of the series
        in `missing` (ascending): `plans` hold a plan a field asked,
        each over that segment alone.  The device route answers from the fields'
        resident decode slices (the aggregate route's: same keys, same
        account; a miss reads, narrows and uploads the slice as that
        route's miss does and leaves it resident for every route), the
        fields in one pool job and one download.  Where the plan, the
        mode or a slice rules the device out, the REASON comes back
        and the caller answers the segment by the row scan
        (CloudObjectStorage.scan_last: the memtable's overlay lives
        there), reduced by last_segment_host."""
        reason = self._select_route(plans[0])
        if reason is not None:
            return reason
        seg = plans[0].segments[0]
        carrier = _slice_carrier(spec)
        plans = [dc_replace(p, decode_spec=carrier) for p in plans]
        slice_columns = [self._decode_slice_columns(p) for p in plans]
        with self._phase("scan.windows", segments=1) as probe:
            resident = [self.scan_cache.get_slice(
                self._decode_slice_key(plan.segments[0], cols))
                for plan, cols in zip(plans, slice_columns)]
            hits = sum(s is not None for s in resident)
            device_decode.note_resident("hit", hits)
            device_decode.note_resident("miss", len(plans) - hits)
            probe.fields["resident"] = hits
        windows: list = []
        for plan, cols, seg_slice in zip(plans, slice_columns, resident):
            deadline_checkpoint()
            if seg_slice is None:
                seg_slice = await self._load_select_slice(
                    plan.segments[0], plan, cols)
            got = (seg_slice if not isinstance(
                seg_slice, device_decode.SegmentSlice)
                else last_ops.plan_window(seg_slice, plan.prune_leaves,
                                          spec.ts_col))
            if isinstance(got, str):
                return got
            windows.append(got)
        deadline_checkpoint()
        fields = await self._run_pool(
            plans[0].pool, last_ops.last_resident, windows, self._phase,
            self.table)
        part = last_ops.combine_fields(
            fields, missing,
            rows_read=sum(w.seg.n for w in windows if w is not None))
        self._count_last(part, "device", "", len(missing),
                         seg.segment_start)
        return part

    def last_segment_host(self, scanned: list, spec, missing: np.ndarray,
                          reason: str,
                          segment_start: int) -> "last_ops.LastRows":
        """The host route of last_segment: `scanned` holds, a field
        asked, the batches a row scan of the segment returned (merged,
        deduplicated, the memtable's rows laid over them where the WAL
        is on); the last row of every series in numpy, then the same
        combine as the device route's."""
        columns = [_scanned_columns(batches, spec) for batches in scanned]
        part = last_ops.combine_fields(
            [last_ops.last_on_host(*cols) for cols in columns], missing,
            rows_read=sum(len(cols[0]) for cols in columns))
        self._count_last(part, "host", reason, len(missing),
                         segment_start)
        return part

    @staticmethod
    def _count_last(part, route: str, reason: str, series_in: int,
                    segment_start: int) -> None:
        _LAST_SEGMENTS.labels(route=route, reason=reason).inc()
        if reason and reason not in _LAST_NO_FALLBACK:
            device_decode.note_fallback(reason)
        _LAST_ROWS.labels(side="read", route=route).inc(part.rows_read)
        _LAST_ROWS.labels(side="answered", route=route).inc(
            sum(int(np.count_nonzero(f)) for f in part.found))
        with span("last.segment", segment=segment_start, route=route,
                  reason=reason, series_in=series_in,
                  series_out=len(part.groups), rows_read=part.rows_read):
            pass

    # ---- one field over all series by time bucket (ops/buckets.py) -------

    async def buckets_segment(
            self, plan: ScanPlan, spec,
            segment_ms: int) -> "buckets_ops.SegmentBuckets | str":
        """ONE segment's buckets (ops/buckets.BucketsSpec) within the
        bounds of `plan` (over that segment alone; its time leaf holds
        the request's bounds).  The device route answers from the
        field's resident decode slice (the aggregate route's: same
        key, same account; a miss reads, narrows and uploads the slice
        as that route's miss does and leaves it resident for every
        route), in one pool job and one download, and keeps nothing
        else: no grid, no answer.  Where the plan, the mode or the
        slice rules the device out, the REASON comes back and the
        caller answers the segment by the row scan
        (CloudObjectStorage.scan_buckets: the memtable's overlay lives
        there), folded by buckets_segment_host."""
        reason = self._select_route(plan)
        if reason is not None:
            return reason
        seg = plan.segments[0]
        plan = dc_replace(plan, decode_spec=_slice_carrier(spec))
        cols = self._decode_slice_columns(plan)
        with self._phase("scan.windows", segments=1) as probe:
            seg_slice = self.scan_cache.get_slice(
                self._decode_slice_key(seg, cols))
            hit = seg_slice is not None
            device_decode.note_resident("hit" if hit else "miss")
            probe.fields["resident"] = int(hit)
        deadline_checkpoint()
        if seg_slice is None:
            seg_slice = await self._load_select_slice(seg, plan, cols)
        window = (seg_slice if not isinstance(
            seg_slice, device_decode.SegmentSlice)
            else buckets_ops.plan_window(
                seg_slice, plan.prune_leaves, seg.segment_start,
                segment_ms, spec.bucket_ms))
        if isinstance(window, str):
            return window
        deadline_checkpoint()
        part = await self._run_pool(
            plan.pool, buckets_ops.buckets_resident, window, spec,
            self._phase, self.table)
        return self._note_buckets(part, "device", "", seg.segment_start)

    def buckets_segment_host(
            self, scanned: list, spec, time_range: TimeRange, reason: str,
            segment_start: int) -> "buckets_ops.SegmentBuckets":
        """The host route of buckets_segment: `scanned` holds the
        batches a row scan of the segment returned (merged,
        deduplicated, the memtable's rows laid over them where the WAL
        is on), folded in numpy."""
        _groups, ts, vals = _scanned_columns(scanned, spec)
        part = buckets_ops.buckets_on_host(
            ts, vals, spec, int(time_range.start), int(time_range.end))
        return self._note_buckets(part, "host", reason, segment_start)

    @staticmethod
    def _note_buckets(part, route: str, reason: str, segment_start: int):
        part.route, part.reason = route, reason
        part.segment_start = segment_start
        _BUCKETS_SEGMENTS.labels(route=route, reason=reason).inc()
        if reason and reason not in _LAST_NO_FALLBACK:
            device_decode.note_fallback(reason)
        _BUCKETS_ROWS.labels(side="read", route=route).inc(part.rows_read)
        return part

    def finalize_buckets(self, parts: list, merged, spec,
                         limit: int) -> dict:
        """A walk's segments (newest first) and their merged buckets to
        the answer's columns: the `scan.combine` phase, and the
        segments' account, which waits for the answer (a row is used
        where its bucket is answered)."""
        with self._phase("scan.combine", sync=True, parts=len(parts)):
            out = buckets_ops.answer_columns(merged, spec, limit)
        for part in parts:
            used = int(part.count[np.isin(part.starts,
                                          out["bucket"])].sum())
            _BUCKETS_ROWS.labels(side="used", route=part.route).inc(used)
            with span("buckets.segment", segment=part.segment_start,
                      route=part.route, reason=part.reason,
                      rows_read=part.rows_read, rows_used=used,
                      buckets_out=len(part.starts)):
                pass
        return out

    def _window_groups(self, out_batch: encode.DeviceBatch,
                       spec: AggregateSpec, plan: ScanPlan):
        """Shared per-window prep: (group_values, gid_full, ts_shift) or
        None when the window contributes nothing.  Memoized on the batch
        (keyed by group column + full predicate) so repeat queries over
        scan-cached windows skip the dense-ification.  The memo value is
        RANGE-INDEPENDENT (values + gid); only the two-int shift depends
        on range_start and is derived per call — so varied-range queries
        over the same windows still hit the memo."""
        memo_key = ("window_groups", spec.group_col, spec.ts_col,
                    filter_ops.canonical_predicate_key(plan.predicate))
        # single atomic .get(): this now runs on worker-pool threads, so
        # a check-then-read against a concurrent clear() could KeyError;
        # duplicate computation on a lost race is benign (same result)
        miss = object()
        cached_val = out_batch.memo.get(memo_key, miss)
        if cached_val is miss:
            cached_val = self._window_groups_uncached(out_batch, spec, plan)
            # charge the capacity-sized gid only: group_values is a tiny
            # host array, and the allowance must fit this entry (4B/row)
            # PLUS a dev_cols entry (12B/row) for the same spec
            nbytes = 0 if cached_val is None else int(cached_val[1].nbytes)
            _memo_store(out_batch, memo_key, cached_val, nbytes)
        if cached_val is None:
            return None
        group_values, gid_full, epoch = cached_val
        shift = epoch - spec.range_start  # host_ts = dev_ts + epoch
        ensure(abs(shift) < 2**31, "query range too far from segment epoch")
        return group_values, gid_full, shift

    def _window_groups_uncached(self, out_batch: encode.DeviceBatch,
                                spec: AggregateSpec, plan: ScanPlan):
        k = out_batch.n_valid
        cap = out_batch.capacity
        if k == 0:
            return None
        keep = _iota(cap) < k
        mask_all = True
        if plan.predicate is not None and not plan.pushed_complete:
            mask = np.asarray(
                filter_ops.eval_predicate(plan.predicate, out_batch))
            mask_all = bool(mask[:k].all())
            keep = keep & mask
            # fully-filtered window: empty result, NOT an encoding error
            # (the ensure below must only fire for windows with rows)
            if not mask_all and not keep.any():
                return None

        ts_enc = out_batch.encodings[spec.ts_col]
        ensure(ts_enc.kind in ("offset", "numeric"),
               f"aggregate needs arithmetic timestamps, got "
               f"{ts_enc.kind!r} encoding for {spec.ts_col!r}")
        # dense group ids: one int32 column roundtrips to host (cheap),
        # values/timestamps stay on device; the dense-id array itself is
        # memoized DEVICE-resident so repeat queries over cached windows
        # upload nothing
        codes = np.asarray(out_batch.columns[spec.group_col])
        enc_g = out_batch.encodings[spec.group_col]
        if (mask_all and enc_g.kind == "dict" and len(enc_g.dictionary)
                and int(codes[:k].min()) == 0
                and int(codes[:k].max()) == len(enc_g.dictionary) - 1):
            # dict-encoded group column whose window uses the WHOLE
            # dictionary (single-window segments — sidecar loads and
            # encode_batch both produce dense sorted-rank codes): the
            # codes already ARE the dense ids and the dictionary the
            # sorted group values — skip the per-window np.unique, the
            # cold scan's hottest host op.  Windows spanning a code
            # subrange (pk-windowed big segments) fail the min/max
            # check and take the exact path below.
            gid_full = np.where(keep, codes, -1).astype(np.int32)
            group_values = enc_g.dictionary
            if isinstance(out_batch.columns[spec.group_col], np.ndarray):
                return group_values, gid_full, ts_enc.epoch
            return group_values, jnp.asarray(gid_full), ts_enc.epoch
        sel_codes = codes[keep]
        if len(sel_codes) == 0:
            return None
        uniq, dense = np.unique(sel_codes, return_inverse=True)
        gid_full = np.full(cap, -1, dtype=np.int32)
        gid_full[keep] = dense.astype(np.int32)

        group_values = _decode_group_values(
            uniq, out_batch.encodings[spec.group_col])
        # the memo stores the window's ts EPOCH, not a shift: the caller
        # derives shift = epoch - range_start so the memo entry serves
        # every query range.  Host windows keep a host gid (stacked +
        # uploaded per round); device windows memoize it device-resident
        if isinstance(out_batch.columns[spec.group_col], np.ndarray):
            return group_values, gid_full, ts_enc.epoch
        return group_values, jnp.asarray(gid_full), ts_enc.epoch

    def _dev_scalar(self, val: int, kind: str = "i32"):
        """Memoized tiny device constants: 'i32' scalar or 'arr1'
        one-element int32 array."""
        key = (kind, int(val))
        a = self._scalar_cache.get(key)
        if a is None:
            a = (jnp.asarray([int(val)], dtype=jnp.int32) if kind == "arr1"
                 else jnp.int32(val))
            self._scalar_cache[key] = a
        return a

    def _stack_cache_get(self, key: tuple, windows_now: tuple):
        with self._stack_cache_lock:
            hits, misses = _stack_counters(key)
            entry = self._stack_cache.get(key)
            if entry is None:
                self._stack_cache_misses += 1
                misses.inc()
                return None
            stored_refs, arrays, nbytes = entry
            # WEAK references: the entry must not pin evicted windows'
            # column buffers in HBM; a dead ref or changed composition
            # means the round was re-read — drop the stale stack
            if len(stored_refs) != len(windows_now) or not all(
                    ref() is w for ref, w in zip(stored_refs, windows_now)):
                del self._stack_cache[key]
                self._stack_cache_bytes -= nbytes
                self._stack_cache_misses += 1
                misses.inc()
                return None
            self._stack_cache.move_to_end(key)
            self._stack_cache_hits += 1
            hits.inc()
            return arrays

    def _stack_cache_put(self, key: tuple, windows_now: tuple,
                         arrays: tuple) -> None:
        nbytes = sum(int(a.nbytes) for a in arrays)
        refs = tuple(weakref.ref(w) for w in windows_now)
        with self._stack_cache_lock:
            if nbytes > self._stack_cache_max:
                return
            old = self._stack_cache.pop(key, None)
            if old is not None:
                self._stack_cache_bytes -= old[2]
            self._stack_cache[key] = (refs, arrays, nbytes)
            self._stack_cache_bytes += nbytes
            while (self._stack_cache_bytes > self._stack_cache_max
                   and self._stack_cache):
                _, (_, _, evicted) = self._stack_cache.popitem(last=False)
                self._stack_cache_bytes -= evicted

    def _window_grid_width(self, spec: AggregateSpec) -> int:
        """Static per-window grid width: a window's rows span at most one
        segment, so its buckets span at most segment_ms/bucket_ms (+2
        for epoch/range misalignment).  Per-window grids cover only that
        local range and carry a bucket offset into the host combine —
        a full-query-width grid per window would move groups x
        total_buckets cells to host PER WINDOW (10s of MB each on long
        ranges) instead of groups x window_span."""
        need = self.segment_duration_ms // max(1, spec.bucket_ms) + 2
        return int(min(spec.num_buckets,
                       max(8, 1 << (need - 1).bit_length())))

    def _devcol_stack_ok(self) -> bool:
        """Whether host windows should stack from per-window memoized
        DEVICE columns instead of a fresh numpy stack + bulk upload.
        On accelerators the device copies make varied-range queries
        (distinct specs -> full-stack misses) re-stack cached HBM arrays
        with only KB-sized remap/shift uploads; on XLA-CPU the numpy
        stack is a memcpy and the extra dispatches would only slow it.
        HORAEDB_DEVCOL_STACK=1/0 forces (tests cover the device-col
        path on the CPU backend)."""
        import os

        forced = os.environ.get("HORAEDB_DEVCOL_STACK", "")
        if forced in ("0", "1"):
            return forced == "1"
        import jax

        return jax.default_backend() != "cpu"

    def _host_agg_ok(self) -> bool:
        """Whether window rounds aggregate with the numpy twin instead of
        the vmap device kernel (_batched_window_partials_jit).  Default:
        host on the CPU backend (numpy bincount beats XLA-CPU's
        segmented scatters ~20x), device elsewhere.  HORAEDB_HOST_AGG=1/0
        forces, mirroring HORAEDB_DEVCOL_STACK, so CPU CI keeps coverage
        of the device parts kernel."""
        return host_agg_default()

    def _window_device_cols(self, w: encode.DeviceBatch,
                            spec: AggregateSpec, plan: ScanPlan,
                            gid: np.ndarray):
        """(ts, gid, value) device copies of one host window at its own
        capacity — all range-independent, memoized on the window (same
        MEMO_SLOTS bound the scan cache charges for)."""
        memo_key = ("dev_cols", spec.group_col, spec.ts_col,
                    spec.value_col,
                    filter_ops.canonical_predicate_key(plan.predicate))
        miss = object()
        got = w.memo.get(memo_key, miss)
        if got is not miss:
            return got
        # through the accounted seam: this is the accelerator default's
        # bulk upload (device_transfer_bytes_total{direction="h2d"})
        out = tuple(
            deviceprof.device_put(np.asarray(col, dtype=dtype))
            for col, dtype in ((w.columns[spec.ts_col], np.int32),
                               (gid, np.int32),
                               (w.columns[spec.value_col], np.float32)))
        _memo_store(w, memo_key, out, sum(int(a.nbytes) for a in out))
        return out

    @staticmethod
    def _round_stack_key(seg0: int, spec: AggregateSpec, plan: ScanPlan,
                         batch_w: int, cap: int, g_pad: int, width: int,
                         space_fp: tuple) -> tuple:
        """Stack-LRU identity of one round's RANGE-DEPENDENT small
        arrays (remap/shift/lo — KBs; shared with the fused replay
        recording, so the key must be computed ONE way)."""
        return (seg0, spec.group_col, spec.ts_col,
                spec.value_col, spec.bucket_ms, spec.range_start,
                batch_w, cap, g_pad, width, space_fp,
                filter_ops.canonical_predicate_key(plan.predicate))

    @staticmethod
    def _col_stack_key(windows_now: tuple, spec: AggregateSpec,
                       plan: ScanPlan, batch_w: int, cap: int) -> tuple:
        """Stack-LRU identity of one round's RANGE-INDEPENDENT stacked
        columns (ts/gid/val — the big HBM arrays).  Keyed by the window
        object ids (validated by identity refs on get, so id reuse after
        eviction can't alias), NOT by range/bucket/group-space: every
        query whose round has the same composition reuses the big
        stacks and only rebuilds the small remap/shift/lo arrays."""
        return ("colstack", tuple(id(w) for w in windows_now),
                spec.group_col, spec.ts_col, spec.value_col, batch_w, cap,
                filter_ops.canonical_predicate_key(plan.predicate))

    def _build_round_stacks(self, items: list, spec: AggregateSpec,
                            plan: ScanPlan, batch_w: int, cap: int,
                            g_pad: int, width: int,
                            group_space: np.ndarray, local_ok: bool,
                            stack_key: Optional[tuple] = None,
                            put=None, key_salt: tuple = ()):
        """Stack one round of windows for the aggregation program,
        keeping host<->device transfers and dispatches few (each costs
        host time per call; how much is not measured):

        - HOST windows (the default merge layout) stack in numpy and
          cross to the device as ONE transfer per array — not one per
          window per column — or, on accelerators, re-stack per-window
          memoized device columns (_window_device_cols) so only the
          FIRST query over a window pays the upload;
        - remap/shift/lo are placed on device HERE and cached, so a
          full cache hit issues ZERO transfers.

        Stacked inputs live in a reader-level LRU split in TWO entries:
        the big ts/gid/val stacks under a range-independent key
        (_col_stack_key — shared by every query range over the same
        round composition) and the small remap/shift/lo arrays under
        the full range-dependent key.  Each entry carries the round's
        window OBJECTS: a hit requires the exact same DeviceBatches
        (object identity — stable while scan-cached), which both
        prevents id-reuse collisions and makes entries
        self-invalidating; byte accounting and eviction live in
        _stack_cache_put.

        Returns (ts_s, gid_s, val_s, remap_d, shift_d, lo_d, lo_host).
        """
        # an explicit `put` (the 2-D mesh rounds pass shard_time_axis)
        # keys its entries with `key_salt` so sharded and single-device
        # stacks of one composition never alias in the LRU
        sharded = put is not None
        if put is None:
            put = deviceprof.device_put
        if stack_key is None:
            space_fp = (len(group_space), hash(group_space.tobytes()))
            stack_key = self._round_stack_key(items[0][0], spec, plan,
                                              batch_w, cap, g_pad, width,
                                              space_fp)
        stack_key = stack_key + key_salt
        windows_now = tuple(it[1] for it in items)
        col_key = self._col_stack_key(windows_now, spec, plan, batch_w,
                                      cap) + key_salt
        cols = self._stack_cache_get(col_key, windows_now)
        small = self._stack_cache_get(stack_key, windows_now)
        if cols is not None and small is not None:
            return cols + small
        t_build = time.perf_counter()
        built_bytes = 0
        host_rows = _host_rows(items, spec)
        if cols is None:
            if host_rows and (sharded or not self._devcol_stack_ok()):
                ts_m, gid_m, val_m = _stack_host_cols(items, spec,
                                                      batch_w, cap)
                ts_s, gid_s, val_s = put(ts_m), put(gid_m), put(val_m)
            else:
                ts_rows, gid_rows, val_rows = [], [], []
                for d, (_seg_start, w, (_values, gid_dev, _sh)) in \
                        enumerate(items):
                    if host_rows:
                        # range-independent device copies, memoized per
                        # window: a varied-range query re-stacks cached
                        # device arrays instead of re-uploading the rows
                        ts_d, gid_dev, val_d = self._window_device_cols(
                            w, spec, plan, gid_dev)
                    else:
                        ts_d = w.columns[spec.ts_col]
                        val_d = w.columns[spec.value_col]
                    if w.capacity < cap:
                        pad_n = cap - w.capacity
                        ts_d = jnp.pad(ts_d, (0, pad_n))
                        gid_dev = jnp.pad(gid_dev, (0, pad_n),
                                          constant_values=-1)
                        val_d = jnp.pad(val_d, (0, pad_n))
                    ts_rows.append(jnp.asarray(ts_d))
                    gid_rows.append(jnp.asarray(gid_dev))
                    val_rows.append(jnp.asarray(val_d))
                if len(items) < batch_w:  # pad round with no-op windows
                    empty_gid = jnp.full(cap, -1, dtype=jnp.int32)
                    zeros_i = jnp.zeros(cap, dtype=jnp.int32)
                    zeros_f = jnp.zeros(cap, dtype=jnp.float32)
                    for _ in range(batch_w - len(items)):
                        ts_rows.append(zeros_i)
                        gid_rows.append(empty_gid)
                        val_rows.append(zeros_f)
                ts_s = jnp.stack(ts_rows)
                gid_s = jnp.stack(gid_rows)
                val_s = jnp.stack(val_rows)
                if sharded:
                    ts_s, gid_s, val_s = put(ts_s), put(gid_s), put(val_s)
            cols = (ts_s, gid_s, val_s)
            built_bytes += sum(int(a.nbytes) for a in cols)
            self._stack_cache_put(col_key, windows_now, cols)
        if small is None:
            remap, shift, lo = _round_small_arrays(
                items, spec, batch_w, g_pad, group_space, local_ok)
            small = (put(remap), put(shift), put(lo), lo)
            built_bytes += sum(int(a.nbytes) for a in small[:3])
            self._stack_cache_put(stack_key, windows_now, small)
        _STAGE_SECONDS["stack_build"].observe(time.perf_counter() - t_build)
        _STAGE_BYTES["stack_build"].inc(built_bytes)
        return cols + small

    def _flush_window_batch(self, items: list, spec: AggregateSpec,
                            plan: ScanPlan) -> list:
        """Aggregate one round of windows (possibly from several
        segments) as a single compiled program, staying device-resident
        between merge and aggregate.

        items: [(seg_start, window, (group_values, gid_dev, shift))].
        Returns [(seg_start, (round_values, bucket_lo, partial grids))]
        in item order; every part shares the round's union group values
        (rows a window didn't touch have count 0 and fold away in the
        combiner).  Rounds are padded to the full batch width with empty
        windows so one program shape serves every flush.

        Device-decode entries (prep None, window a DevicePart) pass
        through in position — their grids were computed by the fused
        dispatch — so a segment's parts fold in window order whichever
        route each window took."""
        has_device = any(prep is None for _s, _w, prep in items)
        if has_device:
            out: list = [None] * len(items)
            host_pos: list[int] = []
            host_items: list = []
            for i, (s, w, prep) in enumerate(items):
                if prep is None:
                    if w.part is not None:
                        out[i] = (s, w.part)
                else:
                    host_pos.append(i)
                    host_items.append((s, w, prep))
            if host_items:
                for i, p in zip(host_pos, self._flush_host_round(
                        host_items, spec, plan)):
                    out[i] = p
            return [p for p in out if p is not None]
        return [p for p in self._flush_host_round(items, spec, plan)
                if p is not None]

    def _flush_host_round(self, items: list, spec: AggregateSpec,
                          plan: ScanPlan) -> list:
        """One round of HOST-decoded windows aggregated by the batched
        kernel (or its numpy twin) — returns one entry per item, None
        for windows that contribute nothing."""
        if (not plan.force_xla_agg) and self._host_agg_ok() and all(
                isinstance(it[1].columns[spec.ts_col], np.ndarray)
                for it in items):
            # XLA-CPU's segmented scatters run ~20x slower than numpy's
            # bincount and there is no transfer to amortize — aggregate
            # where the rows already live (the accelerator trade-off is
            # the opposite; see _build_round_stacks).  Per-window partial
            # grids are memoized range-independently, so repeat/varied
            # queries slice cached grids instead of re-scanning rows.
            return _host_window_partials(items, spec, plan)

        # pow2 width >= len(items): full rounds share one program,
        # tail/small queries use narrower ones (bounded variants)
        batch_w = min(max(1, self.config.scan.agg_batch_windows),
                      1 << (len(items) - 1).bit_length())
        round_values = np.unique(np.concatenate([it[2][0] for it in items]))
        g = len(round_values)
        g_pad = max(8, 1 << (g - 1).bit_length())
        cap = max(it[1].capacity for it in items)
        # offset-encoded ts columns bound each window's bucket range (the
        # epoch is the segment table's min ts); anything else falls back
        # to full-range grids with lo=0
        local_ok = all(
            it[1].encodings[spec.ts_col].kind == "offset" for it in items)
        width = self._window_grid_width(spec) if local_ok \
            else spec.num_buckets

        with self._phase("scan.group_prep", windows=len(items)):
            ts_s, gid_s, val_s, remap_d, shift_d, lo_dev, lo = \
                self._build_round_stacks(items, spec, plan, batch_w, cap,
                                         g_pad, width, round_values,
                                         local_ok)
        total = self._dev_scalar(spec.num_buckets)
        t_dev = time.perf_counter()
        with self._phase("scan.dispatch", sync=True, windows=len(items)):
            stacked = _batched_window_partials_jit(
                ts_s, gid_s, val_s, remap_d, shift_d,
                lo_dev, total, self._dev_scalar(spec.bucket_ms),
                num_groups=g_pad, num_buckets=width, which=spec.which)
        # per-window partials fold on host in f64 (bit-equal to the
        # single-window path); padding windows are sliced away
        host = deviceprof.download(stacked, fn="batched_window_partials",
                                   table=self.table)
        _STAGE_SECONDS["device_aggregate"].observe(
            time.perf_counter() - t_dev)
        parts = []
        for d in range(len(items)):
            lo_d = int(lo[d])
            w_eff = min(width, spec.num_buckets - lo_d)
            grids = {k: v[d, :g, :w_eff] for k, v in host.items()}
            if "last_ts" in grids:
                # re-base window-local last_ts to range_start-relative so
                # parts with different offsets compare correctly
                lt = grids["last_ts"].astype(np.int64)
                grids["last_ts"] = np.where(
                    grids["count"] > 0, lt + lo_d * spec.bucket_ms, lt)
            parts.append((items[d][0], (round_values, lo_d, grids)))
        return parts

    def _merge_on_host(self, batch: pa.RecordBatch,
                       plan: ScanPlan) -> pa.RecordBatch:
        pk_names = self._pk_names_in(batch.schema.names)
        sort_keys = [(n, "ascending") for n in pk_names + [SEQ_COLUMN_NAME]]
        idx = pa.compute.sort_indices(batch, sort_keys=sort_keys)
        batch = batch.take(idx)
        names = batch.schema.names
        value_idxes = [names.index(n) for n in names
                       if n not in pk_names and n != SEQ_COLUMN_NAME]
        op = build_operator(plan.mode, value_idxes)
        # explicit indices: a projection may have reordered columns
        merged = op.merge_sorted_batch(
            batch, pk_indices=[names.index(n) for n in pk_names])
        # fully-pushed PK-only predicates were applied at read time and
        # cannot interact with the merge — same skip as the window paths
        if plan.predicate is not None and not plan.pushed_complete:
            mask = _eval_predicate_host(plan.predicate, merged)
            merged = merged.filter(pa.array(mask))
        return merged


_ACC_TS_MIN = jnp.int32(-(2**31))


# cells ceiling for a memoized full-span window grid (~256 MB of f32
# per aggregate); beyond it the window recomputes range-clipped,
# unmemoized grids instead of allocating the full span
_HOST_GRID_MAX_CELLS = 64 << 20


def host_agg_default() -> bool:
    """THE host-vs-device aggregation default, shared by every numpy
    -twin gate (reader windows, engine chunked downsample): host on the
    CPU backend, device elsewhere; HORAEDB_HOST_AGG=1/0 forces."""
    import os

    forced = os.environ.get("HORAEDB_HOST_AGG", "")
    if forced in ("0", "1"):
        return forced == "1"
    return jax.default_backend() == "cpu"


def host_cell_grids(cell: np.ndarray, vv: np.ndarray, tsv, ncells: int,
                    want) -> dict:
    """Shared host accumulation cores over flat grid cells, used by the
    window partials below and the engine's chunked downsample twin:
    {"count" int64, "sum"? f64, "min"? (+inf fill), "max"? (-inf fill),
    "last"? (lt int64 ts-per-cell with _ACC_TS_MIN fill, li int64
    position-in-vv per cell with -1 fill)} — callers apply their own
    empty-cell conventions.  `tsv` is only read for "last"."""
    out = {"count": np.bincount(cell, minlength=ncells)}
    if "sum" in want:
        out["sum"] = np.bincount(cell, weights=vv, minlength=ncells)
    if "min" in want:
        mn = np.full(ncells, np.inf)
        np.minimum.at(mn, cell, vv)
        out["min"] = mn
    if "max" in want:
        mx = np.full(ncells, -np.inf)
        np.maximum.at(mx, cell, vv)
        out["max"] = mx
    if "last" in want:
        lt = np.full(ncells, int(_ACC_TS_MIN), dtype=np.int64)
        np.maximum.at(lt, cell, tsv)
        at_max = tsv == lt[cell]
        pos = np.flatnonzero(at_max)  # later position wins cell ties
        li = np.full(ncells, -1, dtype=np.int64)
        np.maximum.at(li, cell[at_max], pos)
        out["last"] = (lt, li)
    return out


def _host_window_full_grids(w: encode.DeviceBatch, values: np.ndarray,
                            gid: np.ndarray, epoch: int, phase: int,
                            bucket_ms: int, want: frozenset,
                            ts_col: str, value_col: str,
                            clip: Optional[tuple] = None):
    """One window's partial grids over its FULL ts span, in absolute
    phase-shifted buckets A = (host_ts - phase) // bucket_ms — no query
    range anywhere, so the result is reusable by every query sharing
    (bucket_ms, phase).  Returns (A0, grids): grids cover absolute
    buckets [A0, A0 + W); last_ts is ABSOLUTE host ms (int64, I32_MIN
    sentinel in empty cells).

    `clip=(lo_ms, hi_ms)` bounds the rows to a host-ts range first —
    the fallback shape when the unclipped span would exceed
    _HOST_GRID_MAX_CELLS (returns the string "toobig" in that case so
    the caller can re-invoke clipped and skip the memo)."""
    g = len(values)
    ts_abs = np.asarray(w.columns[ts_col]).astype(np.int64) + epoch
    vals = np.asarray(w.columns[value_col], dtype=np.float64)
    valid = gid >= 0
    if clip is not None:
        valid = valid & (ts_abs >= clip[0]) & (ts_abs < clip[1])
    if not valid.any():
        return None
    A = (ts_abs - phase) // bucket_ms
    A0 = int(A[valid].min())
    W = int(A[valid].max()) - A0 + 1
    ncells = g * W
    if clip is None and ncells > _HOST_GRID_MAX_CELLS:
        return "toobig"
    cell = (gid.astype(np.int64) * W + (A - A0))[valid]
    vv = vals[valid]
    # +/-inf identities for untouched min/max cells — masked rows land
    # in the device kernel's overflow segment, so empty cells read the
    # segmented op's identity, not the F32_MAX row filler
    cores = host_cell_grids(cell, vv, ts_abs[valid], ncells, want)
    grids = {"count": cores["count"].astype(np.float32).reshape(g, W)}
    for k in ("sum", "min", "max"):
        if k in cores:
            grids[k] = cores[k].astype(np.float32).reshape(g, W)
    if "last" in cores:
        lt, li = cores["last"]
        last = np.zeros(ncells)
        has = li >= 0
        last[has] = vv[li[has]]
        grids["last"] = last.astype(np.float32).reshape(g, W)
        grids["last_ts"] = lt.reshape(g, W)
    return A0, grids


def _host_window_partials(items: list, spec: AggregateSpec,
                          plan: ScanPlan) -> list:
    """numpy twin of _batched_window_partials_jit for the CPU backend.

    Each window's full-span grids are memoized RANGE-INDEPENDENTLY on
    the window (keyed by bucket width + range phase + predicate +
    aggregates); a query only slices the cached grids to its bucket
    range and rebases last_ts — repeat AND varied-range queries over
    scan-cached windows skip row aggregation entirely.  Grid
    conventions (combine identities, f32 cells, later-row last
    tie-break) match the device kernel, so combine_aggregate_parts
    cannot tell the paths apart.  Returns one entry per item —
    (seg_start, (values, lo, grids)) or None for a window that
    contributes nothing — aligned so _flush_window_batch can merge
    routes by position."""
    t_dev = time.perf_counter()
    want = frozenset(spec.which) | (
        {"sum"} if "avg" in spec.which else set())
    phase = spec.range_start % spec.bucket_ms
    q0 = (spec.range_start - phase) // spec.bucket_ms
    parts = []
    for seg_start, w, (values, gid_full, sh) in items:
        epoch = sh + spec.range_start
        key = ("host_partials", spec.ts_col, spec.value_col,
               spec.group_col, filter_ops.canonical_predicate_key(
                   plan.predicate), spec.bucket_ms, phase, want)
        miss = object()
        full = w.memo.get(key, miss)
        if full is miss:
            full = _host_window_full_grids(
                w, values, np.asarray(gid_full), epoch, phase,
                spec.bucket_ms, want, spec.ts_col, spec.value_col)
            if full == "toobig":
                # full-span grid too large to hold: compute clipped to
                # the query's grid bounds, and don't memoize (the clip
                # makes it range-dependent)
                full = _host_window_full_grids(
                    w, values, np.asarray(gid_full), epoch, phase,
                    spec.bucket_ms, want, spec.ts_col, spec.value_col,
                    clip=(spec.range_start, spec.range_start
                          + spec.num_buckets * spec.bucket_ms))
            else:
                nbytes = 0 if full is None else sum(
                    int(a.nbytes) for a in full[1].values())
                _memo_store(w, key, full, nbytes)
        if full is None:
            parts.append(None)
            continue
        A0, grids_full = full
        W = grids_full["count"].shape[1]
        # trim the absolute-bucket grid to the query's range
        lo_q = A0 - q0
        cut = max(0, -lo_q)
        lo = max(0, lo_q)
        w_eff = min(W - cut, spec.num_buckets - lo)
        if w_eff <= 0:
            parts.append(None)
            continue
        sl = slice(cut, cut + w_eff)
        grids = {k: v[:, sl] for k, v in grids_full.items()
                 if k != "last_ts"}
        if "last_ts" in grids_full:
            lt = grids_full["last_ts"][:, sl]
            # memo holds ABSOLUTE host ms; parts carry range-relative
            grids["last_ts"] = np.where(grids["count"] > 0,
                                        lt - spec.range_start,
                                        int(_ACC_TS_MIN))
        parts.append((seg_start, (values, lo, grids)))
    _STAGE_SECONDS["device_aggregate"].observe(time.perf_counter() - t_dev)
    return parts


def _host_rows(items: list, spec: AggregateSpec) -> bool:
    """Whether every window of a round is host rows: numpy columns and
    a numpy group-id column (_window_groups keeps a host window's on
    the host)."""
    return all(isinstance(it[1].columns[spec.ts_col], np.ndarray)
               and isinstance(it[2][1], np.ndarray) for it in items)


def _stack_host_cols(items: list, spec: AggregateSpec, batch_w: int,
                     cap: int):
    """One round of host windows stacked in numpy to (batch_w, cap):
    (ts, gid, value), a window's tail and the round's padding windows
    reading as no row (gid -1)."""
    ts_m = np.zeros((batch_w, cap), dtype=np.int32)
    gid_m = np.full((batch_w, cap), -1, dtype=np.int32)
    val_m = np.zeros((batch_w, cap), dtype=np.float32)
    for d, (_seg_start, w, (_values, gid, _sh)) in enumerate(items):
        ts_m[d, : w.capacity] = w.columns[spec.ts_col]
        gid_m[d, : w.capacity] = gid
        val_m[d, : w.capacity] = w.columns[spec.value_col]
    return ts_m, gid_m, val_m


def _round_small_arrays(items: list, spec: AggregateSpec, batch_w: int,
                        g_pad: int, group_space: np.ndarray,
                        local_ok: bool):
    """One round's RANGE-DEPENDENT arrays, in numpy: each window's
    group remap into `group_space`, its timestamp shift and its first
    bucket (remap (batch_w, g_pad), shift and lo (batch_w,))."""
    remap = np.zeros((batch_w, g_pad), dtype=np.int32)
    shift = np.zeros(batch_w, dtype=np.int32)
    lo = np.zeros(batch_w, dtype=np.int32)
    for d, (_seg_start, _w, (values, _gid, sh)) in enumerate(items):
        remap[d, : len(values)] = np.searchsorted(group_space, values)
        shift[d] = sh
        if local_ok:
            lo[d] = max(0, sh // spec.bucket_ms)
    return remap, shift, lo


@deviceprof.jit(static_argnames=("num_groups", "num_buckets", "which"))
def _fused_acc_init_jit(*, num_groups: int, num_buckets: int, which: tuple):
    """Query-global device accumulator grids with combine-identity
    inits (matching ops.downsample partial conventions)."""
    shape = (num_groups, num_buckets)
    want = set(which)
    if "avg" in want:
        want.add("sum")
    with jax.named_scope("acc_init"):
        acc = {"count": jnp.zeros(shape, jnp.float32)}
        if "sum" in want:
            acc["sum"] = jnp.zeros(shape, jnp.float32)
        if "min" in want:
            acc["min"] = jnp.full(shape, jnp.finfo(jnp.float32).max,
                                  jnp.float32)
        if "max" in want:
            acc["max"] = jnp.full(shape, -jnp.finfo(jnp.float32).max,
                                  jnp.float32)
        if "last" in want:
            acc["last"] = jnp.zeros(shape, jnp.float32)
            acc["last_ts"] = jnp.full(shape, _ACC_TS_MIN, jnp.int32)
    return acc


@deviceprof.jit(static_argnames=("num_groups", "width", "which"),
                donate_argnums=(0,))
def _fused_round_accumulate_jit(acc, ts, gid, vals, remap, shift, lo, total,
                                bucket_ms, *, num_groups: int, width: int,
                                which: tuple):
    """One round of windows aggregated AND scattered into the
    query-global accumulator, entirely on device.

    This replaces the per-flush host fold: instead of downloading
    (B, G, width) partial grids every round (a device->host transfer
    and a sync per round), each round's window-local
    grids land in `acc` via bucket-offset scatters and only the final
    grids ever leave the device.  `acc` is donated — the accumulator
    updates in place round over round.

    Correctness of the scatter combine: count/sum add their identity
    (0) for cells a window didn't touch; min/max scatter through
    .at[].min/.max with +/-F32_MAX identities; `last` does a sequential
    gather-compare-scatter per window (window order = segment order, so
    `>=` keeps later-window ties, matching the host combiner)."""
    from horaedb_tpu.ops import downsample

    def one(ts_b, gid_b, vals_b, remap_b, shift_b, lo_b):
        return downsample.window_local_partials(
            ts_b, gid_b, vals_b, remap_b, shift_b, lo_b, total, bucket_ms,
            num_groups=num_groups, num_buckets=width, which=which)

    p = jax.vmap(one)(ts, gid, vals, remap, shift, lo)
    w_iota = jnp.arange(width, dtype=jnp.int32)

    def body(d, acc):
        cols = lo[d] + w_iota
        out = dict(acc)
        with jax.named_scope("accumulate_count"):
            out["count"] = acc["count"].at[:, cols].add(p["count"][d],
                                                        mode="drop")
        if "sum" in acc:
            with jax.named_scope("accumulate_sum"):
                out["sum"] = acc["sum"].at[:, cols].add(p["sum"][d],
                                                        mode="drop")
        if "min" in acc:
            with jax.named_scope("accumulate_min"):
                out["min"] = acc["min"].at[:, cols].min(p["min"][d],
                                                        mode="drop")
        if "max" in acc:
            with jax.named_scope("accumulate_max"):
                out["max"] = acc["max"].at[:, cols].max(p["max"][d],
                                                        mode="drop")
        if "last" in acc:
            with jax.named_scope("accumulate_last"):
                # fill_value must be a hashable Python scalar (jaxpr
                # param)
                cur_ts = acc["last_ts"].at[:, cols].get(
                    mode="fill", fill_value=-(2**31))
                cur_last = acc["last"].at[:, cols].get(mode="fill",
                                                       fill_value=0.0)
                win_has = p["count"][d] > 0
                win_ts = jnp.where(win_has,
                                   p["last_ts"][d] + lo[d] * bucket_ms,
                                   _ACC_TS_MIN)
                take = win_has & (win_ts >= cur_ts)
                out["last"] = acc["last"].at[:, cols].set(
                    jnp.where(take, p["last"][d], cur_last), mode="drop")
                out["last_ts"] = acc["last_ts"].at[:, cols].set(
                    jnp.where(take, win_ts, cur_ts), mode="drop")
        return out

    return jax.lax.fori_loop(0, ts.shape[0], body, acc)


@deviceprof.jit(static_argnames=("which",))
def _fused_finalize_jit(acc: dict, which: tuple) -> dict:
    """Device finalize of the fused accumulator.  Conventions match
    combine_aggregate_parts: min/max empty cells read +/-inf, avg/last
    NaN.  last_ts stays int32 (range-relative) — the absolute float
    conversion needs int64 range and happens on host."""
    count = acc["count"]
    requested = set(which) | {"count"}
    out = {"count": count}
    with jax.named_scope("finalize"):
        empty = count == 0
        nan = jnp.float32(jnp.nan)
        if "sum" in acc and "sum" in requested:
            out["sum"] = acc["sum"]
        if "sum" in acc and "avg" in requested:
            out["avg"] = jnp.where(empty, nan,
                                   acc["sum"] / jnp.maximum(count, 1.0))
        if "min" in acc and "min" in requested:
            out["min"] = jnp.where(empty, jnp.float32(jnp.inf),
                                   acc["min"])
        if "max" in acc and "max" in requested:
            out["max"] = jnp.where(empty, -jnp.float32(jnp.inf),
                                   acc["max"])
        if "last" in acc and "last" in requested:
            out["last"] = jnp.where(empty, nan, acc["last"])
            out["last_ts"] = acc["last_ts"]
    return out


@deviceprof.jit
def _group_has_data_jit(count):
    """Per-group any-data mask — G bools, the only bytes the aligned
    fast path's empty-group check ever downloads."""
    with jax.named_scope("group_has_data"):
        return (count > 0).any(axis=1)


@deviceprof.jit(static_argnames=("num_groups", "num_buckets", "width",
                                 "which"))
def _fused_one_call_jit(ts, gid, vals, remap, shift, lo, total, bucket_ms, *,
                        num_groups: int, num_buckets: int, width: int,
                        which: tuple):
    """A fused aggregate of ONE round as one program: the accumulator's
    identities, the round's accumulate, the finalize and the per-group
    any-data mask, composed from the bodies of the four programs that
    _fused_run_device_rounds calls in turn, so the grids are theirs bit
    for bit.  Rows past the query's groups (`num_groups` is padded) stay
    empty; the caller cuts them on the host, so the number of groups
    mints no program.  The float32 grids leave stacked, in the order of
    their sorted names: every leaf is a copy of its own at the download,
    and a give-up of the GIL.  Returns (grids, `last_ts` or None, mask).
    """
    acc = _fused_acc_init_jit.__wrapped__(
        num_groups=num_groups, num_buckets=num_buckets, which=which)
    acc = _fused_round_accumulate_jit.__wrapped__(
        acc, ts, gid, vals, remap, shift, lo, total, bucket_ms,
        num_groups=num_groups, width=width, which=which)
    final = _fused_finalize_jit.__wrapped__(acc, which)
    has_data = _group_has_data_jit.__wrapped__(final["count"])
    last_ts = final.pop("last_ts", None)
    return jnp.stack([final[k] for k in sorted(final)]), last_ts, has_data


@deviceprof.jit(static_argnames=("num_groups", "num_buckets", "which"))
def _batched_window_partials_jit(ts, gid, vals, remap, shift, lo, total,
                                 bucket_ms, num_groups: int,
                                 num_buckets: int, which: tuple):
    """vmap over the window axis — one device dispatch aggregates a
    whole round of windows into window-LOCAL grids of `num_buckets`
    buckets starting at each window's `lo` bucket."""
    from horaedb_tpu.ops import downsample

    def one(ts_b, gid_b, vals_b, remap_b, shift_b, lo_b):
        return downsample.window_local_partials(
            ts_b, gid_b, vals_b, remap_b, shift_b, lo_b, total, bucket_ms,
            num_groups=num_groups, num_buckets=num_buckets, which=which)

    return jax.vmap(one)(ts, gid, vals, remap, shift, lo)


def _decode_group_values(codes: np.ndarray, enc) -> np.ndarray:
    """Device group codes -> host values (dictionary entries / epoch
    shift), in the same (sorted) order as the codes."""
    if enc.kind == "dict":
        return enc.dictionary[codes]
    if enc.kind == "offset":
        return codes.astype(np.int64) + enc.epoch
    return codes


@_timed_stage("combine")
def combine_aggregate_parts(parts: list[tuple[np.ndarray, int, dict]],
                            num_buckets: int,
                            which: tuple = downsample_ops.ALL_AGGS
                            ) -> tuple[np.ndarray, dict]:
    """Compatibility shim over storage/combine.py's DENSE fold (the
    bit-identity control).  The reader's own finalize path dispatches
    by [scan.combine] mode instead; standalone callers (cluster-tier
    helpers, old tests) keep this name."""
    return combine_mod.combine_aggregate_parts(parts, num_buckets,
                                               which=which)


def _slice_carrier(spec) -> AggregateSpec:
    """The decode_spec under which a route that is no aggregate (a
    LastSpec's, a BucketsSpec's) probes and admits decode slices: only
    the columns count, they are the slices' key, which those routes
    share with the aggregates over the same field."""
    return AggregateSpec(
        group_col=spec.group_col, ts_col=spec.ts_col,
        value_col=spec.value_col, range_start=0, bucket_ms=1,
        num_buckets=1, which=("count",))


def _scanned_columns(batches: list, spec) -> tuple:
    """A row scan's batches as (groups, int64 timestamps, float32
    values), by the columns `spec` (a SelectSpec or a LastSpec)
    names."""
    cols = [np.concatenate([
        b.column(name).to_numpy(zero_copy_only=False)
        for b in batches]) if batches else np.zeros(0, kind)
        for name, kind in ((spec.group_col, np.uint64),
                           (spec.ts_col, np.int64),
                           (spec.value_col, np.float32))]
    return cols[0], cols[1].astype(np.int64), cols[2].astype(np.float32)


def _cat_groups(arrays: list) -> np.ndarray:
    """Segments' group values as one array.  A slice's dictionary holds
    an unsigned key as int64 (ops/encode: nothing past i64::MAX reaches
    the device) where the row scan returns it unsigned, and numpy would
    join the two, or either with an empty part of the other kind, as
    float64, which rounds a series id: integers of both kinds are
    joined unsigned, and an empty part decides nothing."""
    arrays = [a for a in arrays if len(a)]
    if not arrays:
        return np.zeros(0, np.uint64)
    kinds = {a.dtype.kind for a in arrays}
    if kinds == {"i", "u"}:
        arrays = [a.astype(np.uint64) for a in arrays]
    return np.concatenate(arrays)


def join_on_host(groups: np.ndarray, ts: np.ndarray, f_groups, f_ts,
                  f_vals) -> tuple:
    """A field's (series, timestamp) -> value rows (unique keys: the
    scan deduplicated them) looked up at the keys (groups, ts):
    (float32 values, found flags)."""
    n = len(ts)
    if not n or not len(f_ts):
        return np.zeros(n, np.float32), np.zeros(n, bool)
    order = np.lexsort((f_ts, f_groups))
    f_groups, f_ts, f_vals = f_groups[order], f_ts[order], f_vals[order]
    # both sides as one sortable number: the series' rank among the
    # field's, then the timestamp's offset
    series = np.unique(f_groups)
    t0 = int(min(f_ts.min(), ts.min()))
    width = int(max(f_ts.max(), ts.max())) - t0 + 1
    ensure(len(series) * width < 2**62,
           "select: (series, timestamp) span too wide to join")
    f_key = np.searchsorted(series, f_groups).astype(np.int64) * width \
        + (f_ts - t0)
    rank = np.searchsorted(series, groups)
    known = series[np.minimum(rank, len(series) - 1)] == groups
    key = rank.astype(np.int64) * width + (ts - t0)
    at = np.minimum(np.searchsorted(f_key, key), len(f_key) - 1)
    found = known & (f_key[at] == key)
    return np.where(found, f_vals[at], np.float32(0)), found


def _is_lex_sorted(keys: list[np.ndarray]) -> bool:
    """True iff rows are non-decreasing under lexicographic key order."""
    n = len(keys[0])
    if n <= 1:
        return True
    still_equal = np.ones(n - 1, dtype=bool)
    for c in keys:
        if bool(np.any(still_equal & (c[:-1] > c[1:]))):
            return False
        still_equal &= c[:-1] == c[1:]
        if not still_equal.any():
            return True
    return True


def _plan_merge_perm(sort_cols: list[np.ndarray],
                     seq: Optional[np.ndarray]) -> Optional[np.ndarray]:
    """Host half of the k-way merge of pre-sorted SST runs.

    The reference never re-sorts SST data: its per-file streams are
    already PK-ordered and SortPreservingMergeExec merges them
    (ref: src/storage/src/read.rs:455-480).  Our SSTs are written
    PK-sorted too (storage.py), so the scan's device program does not
    need an O(n log n) `lax.sort` — it needs, at most, a permutation
    that interleaves the pre-sorted runs.  That permutation is planned
    here, on the host, where the decoded parquet columns already live:

    - verify sortedness first (O(n) compares): single-SST segments and
      non-overlapping time-partitioned writes need NO work at all;
    - otherwise pack the lexicographic key into one int64 and use
      numpy's stable (radix, O(n)) argsort — effectively a k-way merge
      whose cost is independent of comparator depth;
    - keys whose combined range exceeds int64 fall back to np.lexsort.

    `seq` must be passed ONLY when rows are not already in ascending
    sequence order (stability preserves row order within equal keys,
    which is what last-wins dedup needs).  Returns None when rows are
    already sorted, else an int32 permutation over the input rows.
    """
    keys = list(sort_cols) + ([] if seq is None else [seq])
    n = len(keys[0])
    if n <= 1:
        return None
    # sortedness first: single-SST segments and non-overlapping writes
    # (the common cold case) exit here after ~one compare pass, before
    # paying any key-packing arithmetic
    if _is_lex_sorted(keys):
        return None
    packed = None
    span_prod = 1
    for c in keys:  # most-significant first
        c64 = c.astype(np.int64, copy=False)
        lo = int(c64.min())
        span = int(c64.max()) - lo + 1
        if span_prod * span >= 2**63:
            packed = None
            break
        span_prod *= span
        part = c64 - lo
        packed = part if packed is None else packed * span + part
    if packed is not None:
        return np.argsort(packed, kind="stable").astype(np.int32)
    return np.lexsort(tuple(reversed(keys))).astype(np.int32)


def _window_merge_sel(sort_cols: list[np.ndarray], seq_h: np.ndarray,
                      seq_ordered: bool, sel: np.ndarray) -> np.ndarray:
    """Compose a window selection with its planned merge permutation —
    the ONE place the (sort cols, seq-ordering) contract is applied to a
    window, so every path orders rows identically."""
    perm = _plan_merge_perm([c[sel] for c in sort_cols],
                            None if seq_ordered else seq_h[sel])
    return sel if perm is None else sel[perm]


def _batch_merge_perm(sort_cols: list[np.ndarray], seq_h: np.ndarray,
                      seq_ordered: bool, n: int) -> Optional[np.ndarray]:
    """Whole-batch twin of _window_merge_sel: perm over rows [0, n) or
    None when already sorted."""
    return _plan_merge_perm([c[:n] for c in sort_cols],
                            None if seq_ordered else seq_h[:n])


def _host_merge_window_descs(dev: encode.DeviceBatch, host_cols: dict,
                             sort_pk_names: list[str], seq_h: np.ndarray,
                             seq_ordered: bool, selections: list,
                             n: int) -> list:
    """THE host merge: per window, plan the k-way-merge permutation
    over pre-sorted SST runs (_plan_merge_perm contract), keep the last
    row of each PK run, and emit padded HOST-resident column dicts.

    Returns [(cols, encodings, n_valid, capacity)] — deduped, PK-sorted
    windows ready for _finalize_windows to wrap as DeviceBatches."""
    descs = []
    sort_cols = [host_cols[nm] for nm in sort_pk_names]
    for sel in selections:
        if sel is not None and not len(sel):
            continue
        if sel is None:
            base = _batch_merge_perm(sort_cols, seq_h, seq_ordered, n)
        else:
            base = _window_merge_sel(sort_cols, seq_h, seq_ordered, sel)
        keys = (sort_cols if base is None
                else [c[base] for c in sort_cols])
        keep = _host_dedup_keep(keys)
        k = int(keep.sum())
        if k == 0:
            continue
        if base is None:
            if k == n and sel is None:
                # no duplicates, already padded by encode_batch
                descs.append(({kk: np.asarray(v) for kk, v
                               in dev.columns.items()},
                              dev.encodings, n, dev.capacity))
                continue
            idx = np.flatnonzero(keep)
        else:
            idx = base if k == len(base) else base[keep]
        cap = encode.pad_capacity(k)
        cols = {kk: np.pad(v[idx], (0, cap - k))
                for kk, v in host_cols.items()}
        descs.append((cols, dev.encodings, k, cap))
    return descs


def _host_dedup_keep(sort_cols: list[np.ndarray]) -> np.ndarray:
    """Boolean keep-mask over PK-SORTED rows: the LAST row of each
    equal-PK run survives (rows arrive with the preferred — highest
    sequence — row last; see _plan_merge_perm's ordering contract).

    This is the host half of last-value dedup: with the permutation
    already planned on host, the run-boundary compare is a single vectorized pass over columns the
    host just decoded — shipping rows to the device only to compare
    neighbours and ship survivors back would pay two transfers for
    an O(n) bandwidth-bound op.  The devices' FLOPs are saved for the
    aggregation grids."""
    n = len(sort_cols[0])
    if n == 0:
        return np.zeros(0, dtype=bool)
    keep = np.empty(n, dtype=bool)
    keep[-1] = True
    diff = np.zeros(n - 1, dtype=bool)
    for c in sort_cols:
        diff |= c[:-1] != c[1:]
    keep[:-1] = diff
    return keep


def _plan_pk_windows(pk1_codes: np.ndarray, window: int) -> list[np.ndarray]:
    """Partition rows into PK-range windows of <= `window` rows.

    Rows sharing a first-PK code always land in one window (dedup only
    needs equal-PK rows co-located; later PK columns refine within a
    code).  Greedy packing over the contiguous code histogram; a single
    code with more rows than `window` gets a window of its own (which may
    exceed the budget — correctness over the soft limit).  Windows are
    code-ascending, so concatenated outputs stay globally PK-sorted.
    """
    # factorize to dense ranks: cost scales with DISTINCT keys, not the
    # code value span (offset-encoded int PKs can span ~2^31 sparsely)
    _, inv, counts = np.unique(pk1_codes, return_inverse=True,
                               return_counts=True)
    order = np.argsort(inv, kind="stable")
    boundaries = np.cumsum(np.concatenate([[0], counts]))
    # greedy packing by searchsorted over the cumulative histogram:
    # O(windows x log keys) instead of a Python iteration per DISTINCT
    # key (high-cardinality segments made this loop the window-prep
    # hot spot on low-core hosts — ROADMAP item 1 residual)
    nkeys = len(counts)
    windows: list[np.ndarray] = []
    s = 0
    while s < nkeys:
        e = int(np.searchsorted(boundaries, boundaries[s] + window,
                                side="right")) - 1
        if e <= s:
            e = s + 1  # single code over budget: a window of its own
        windows.append(order[boundaries[s]:boundaries[e]])
        s = e
    return windows


def _eval_predicate_host(pred, batch: pa.RecordBatch) -> np.ndarray:
    """Host twin of ops.filter.eval_predicate over an Arrow batch."""
    F = filter_ops
    if isinstance(pred, F.And):
        out = np.ones(batch.num_rows, dtype=bool)
        for c in pred.children:
            out &= _eval_predicate_host(c, batch)
        return out
    if isinstance(pred, F.Or):
        out = np.zeros(batch.num_rows, dtype=bool)
        for c in pred.children:
            out |= _eval_predicate_host(c, batch)
        return out
    if isinstance(pred, F.Not):
        return ~_eval_predicate_host(pred.child, batch)
    col = batch.column(batch.schema.names.index(pred.column))
    return F.leaf_mask_host(pred, col.to_numpy(zero_copy_only=False))


def plan_columns(schema: StorageSchema,
                 projections: Optional[list[int]]) -> list[str]:
    """THE column set a merge plan reads for a projection — shared by
    build_plan and the memtable-overlay path (wal/ingest.py) so hybrid
    and pure-SST scans cannot disagree on shape."""
    proj = schema.fill_required_projections(projections)
    if proj is None:
        columns = list(schema.arrow_schema.names)
    else:
        columns = [schema.arrow_schema.names[i] for i in proj]
    # __reserved__ is never read (all-null, unused); __seq__ must be
    # read for dedup even when it will be stripped from the output.
    columns = [c for c in columns if c != RESERVED_COLUMN_NAME]
    if SEQ_COLUMN_NAME not in columns:
        columns.append(SEQ_COLUMN_NAME)
    return columns


def merge_memtable_overlay(schema: StorageSchema,
                           sst_parts: list[pa.RecordBatch],
                           mem_batches: list[pa.RecordBatch],
                           predicate,
                           columns: list[str],
                           keep_builtin: bool) -> Optional[pa.RecordBatch]:
    """Host merge of ONE segment's already-merged SST rows with its
    memtable overlay — the hybrid scan's last stage (wal/ingest.py).

    Both sources carry per-row `__seq__` (sst_parts from a
    keep_builtin plan, mem_batches stamped with each entry's write
    seq), so OVERWRITE's last-value rule is one sort by (PK, __seq__)
    keeping the final row of every PK run.  The full predicate applies
    AFTER dedup, matching the pure-SST path (value-column leaves can
    interact with last-value dedup, so filtering first would resurrect
    overwritten rows); the caller therefore scans overlay segments
    without a predicate.  Ordering invariant: seqs are preserved end to
    end, so a replayed memtable row and its flushed SST twin tie on
    (PK, seq) with identical values — either winning is exactly-once.
    """
    import pyarrow.compute as pc

    from horaedb_tpu.storage.operator import LastValueOperator

    target = pa.schema([schema.arrow_schema.field(
        schema.arrow_schema.names.index(c)) for c in columns])
    parts = []
    for b in list(sst_parts) + list(mem_batches):
        if b.num_rows == 0:
            continue
        b = b.select(columns)
        if not b.schema.equals(target):
            b = b.cast(target)
        parts.append(b)
    if not parts:
        return None
    table = pa.Table.from_batches(parts, schema=target)
    sort_keys = [(n, "ascending") for n in schema.primary_key_names]
    sort_keys.append((SEQ_COLUMN_NAME, "ascending"))
    table = table.take(pc.sort_indices(table, sort_keys=sort_keys))
    batch = table.combine_chunks().to_batches()[0]
    # keep-last-of-PK-run is THE LastValue rule — reuse the operator
    # (native run-detection kernel included) so overlay and SST merges
    # cannot drift
    pk_indices = [columns.index(n) for n in schema.primary_key_names]
    batch = LastValueOperator().merge_sorted_batch(batch, pk_indices)
    if predicate is not None and batch.num_rows:
        mask = _eval_predicate_host(predicate, batch)
        batch = batch.take(np.flatnonzero(mask))
    if not keep_builtin:
        batch = batch.select([c for c in batch.schema.names
                              if not StorageSchema.is_builtin_name(c)])
    return batch


def describe_plan(plan: ScanPlan) -> str:
    """Indented plan text for golden tests (analogue of the reference's
    DisplayableExecutionPlan assertion, read.rs:575-617)."""
    lines = [f"MergeScan: mode={plan.mode.value}, keep_builtin={plan.keep_builtin}"]
    for seg in plan.segments:
        lines.append(f"  Segment[start={seg.segment_start}]: "
                     f"{'DeviceMergeDedup' if plan.mode is UpdateMode.OVERWRITE else 'HostBytesMerge'}")
        if plan.predicate is not None:
            lines.append(f"    Filter: {plan.predicate!r}")
        files = ", ".join(f"{f.id}.sst" for f in seg.ssts)
        pushed = ", pushdown=yes" if plan.pushdown is not None else ""
        lines.append(f"    ParquetScan: files=[{files}], "
                     f"columns={seg.columns}{pushed}")
    return "\n".join(lines)
