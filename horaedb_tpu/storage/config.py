"""Engine configuration (ref: src/storage/src/config.rs).

Field names and defaults track the reference's TOML keys so configs are
interchangeable: scheduler (config.rs:24-50), parquet encodings (52-94),
per-column overrides (96-103), write props (105-133), manifest (135-155),
UpdateMode (166-172).  Unknown keys are rejected (serde deny_unknown_fields
equivalent) by `from_dict`.
"""

from __future__ import annotations

import dataclasses
import enum
import functools
import typing
from dataclasses import dataclass, field
from typing import Any, Optional

from horaedb_tpu.common import Error, ReadableDuration, ReadableSize, ensure


class UpdateMode(enum.Enum):
    """Row-merge semantics for duplicate primary keys (ref: config.rs:166-172).

    OVERWRITE keeps the row with the highest sequence (LastValueOperator);
    APPEND concatenates binary value columns (BytesMergeOperator).
    """

    OVERWRITE = "Overwrite"
    APPEND = "Append"


class CompressionCodec(enum.Enum):
    UNCOMPRESSED = "uncompressed"
    SNAPPY = "snappy"
    ZSTD = "zstd"
    LZ4 = "lz4"
    GZIP = "gzip"


@dataclass
class SchedulerConfig:
    """Compaction scheduler knobs (ref: config.rs:24-50)."""

    schedule_interval: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.from_secs(10))
    max_pending_compaction_tasks: int = 10
    # Executor memory gate (ref: executor.rs:93-114 uses 2 GiB default).
    memory_limit: ReadableSize = field(default_factory=lambda: ReadableSize.gb(2))
    # Picker thresholds (ref: picker.rs defaults).
    max_record_batch_size: int = 8192
    input_sst_max_num: int = 30
    input_sst_min_num: int = 5
    new_sst_max_size: ReadableSize = field(default_factory=lambda: ReadableSize.gb(1))
    ttl: Optional[ReadableDuration] = None


@dataclass
class ColumnOptions:
    """Per-column parquet writer overrides (ref: config.rs:96-103)."""

    enable_dict: Optional[bool] = None
    enable_bloom_filter: Optional[bool] = None
    encoding: Optional[str] = None
    compression: Optional[CompressionCodec] = None


@dataclass
class WriteConfig:
    """Parquet writer properties (ref: config.rs:105-133)."""

    max_row_group_size: int = 8192
    write_batch_size: int = 1024
    enable_sorting_columns: bool = True
    enable_dict: bool = False
    enable_bloom_filter: bool = False
    encoding: Optional[str] = None
    compression: CompressionCodec = CompressionCodec.SNAPPY
    column_options: dict[str, ColumnOptions] = field(default_factory=dict)
    # persist a device-layout sidecar ({id}.enc) next to each OVERWRITE
    # -mode SST so cold scans skip parquet decode + re-encode entirely
    # (no reference analogue; see storage/sidecar.py)
    enable_sidecar: bool = True
    # compaction outputs above this row count skip the sidecar.  NOTE:
    # unlike the parquet rewrite (streamed, ~MBs of RSS), the sidecar's
    # encoded columns accumulate in RAM until the rewrite finishes —
    # ~12 bytes/row, so the default caps that at ~768 MiB.  Lower it on
    # memory-constrained nodes; large compactions past the cap simply
    # fall back to parquet-only cold reads.
    sidecar_max_rows: int = 64 << 20


@dataclass
class ManifestConfig:
    """Manifest merge thresholds (ref: config.rs:135-155, manifest/mod.rs:48-50)."""

    channel_size: int = 3
    merge_interval: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.from_secs(5))
    min_merge_threshold: int = 10
    hard_merge_threshold: int = 90
    soft_merge_threshold: int = 50
    # how long a writer may throttle waiting for the background fold to
    # drain below the soft threshold before proceeding toward the hard
    # limit (no reference analogue: its merger runs on its own threads)
    soft_merge_max_wait: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.from_secs(2))


@dataclass
class RetryConfig:
    """Object-store retry middleware for the manifest plane (no
    reference analogue — see objstore/middleware.py).  This is the ONE
    engine-level retry layer: the S3 backend keeps its protocol-level
    retries, and the data plane (SST puts/reads) stays single-shot so
    write-path failures surface to the caller's rollback discipline."""

    enabled: bool = True
    max_retries: int = 2
    base_backoff: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.from_millis(50))
    max_backoff: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.from_secs(2))
    # total per-op wall clock including retries; None = unbounded
    op_deadline: Optional[ReadableDuration] = None
    # shared retry token bucket: capacity + refill rate (tokens/second)
    budget: int = 32
    budget_refill_per_s: float = 4.0


@dataclass
class ScrubConfig:
    """Orphan scrubber (storage/gc.py): reconciles data/ objects against
    the manifest and deletes unreferenced objects that stay orphaned for
    a full grace period.  The grace period must comfortably exceed the
    longest plausible gap between an SST put and its manifest add (a
    write or compaction in flight) — minutes, not seconds."""

    enabled: bool = True
    interval: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.from_secs(600))
    grace_period: ReadableDuration = field(
        default_factory=lambda: ReadableDuration.from_secs(600))


@dataclass
class ScanCacheConfig:
    """Tier-2 scan cache: host-RAM per-SST encoded sidecar parts under
    the HBM windows cache (see storage/encoded_cache.py).  An HBM miss
    rebuilds windows from host memory, and a flush/compaction
    invalidates nothing but the SSTs it actually removed — steady
    writes no longer re-cliff reads."""

    # host-RAM byte budget for per-SST encoded parts (0 disables tier 2
    # entirely: every HBM miss re-reads the object store, the
    # pre-tiering behavior)
    tier2_max_bytes: int = 256 << 20
    # write-through admission: the WAL flusher and the compactor insert
    # freshly-encoded parts at write time, so a query landing right
    # after a flush never touches the object store
    write_through: bool = True


@dataclass
class ScanCombineConfig:
    """Aggregate combine/finalize knobs ([scan.combine]; see
    storage/combine.py).  `mode = "sparse"` (default) folds partial
    grids straight into the final output buffers as per-series bucket
    runs and materializes only the requested aggregates — top-k
    queries never build the full groups x buckets grid.  `"dense"`
    reproduces the pre-sparse fold exactly (the bit-identity control
    the chaos suite compares against)."""

    mode: str = "sparse"
    # byte budget for the delta-summation memo: per-segment aggregate
    # partials keyed by the segment's exact SST set, served to
    # narrowed/refined ranges of the same dashboard query shape so only
    # delta segments recompute.  0 disables the memo entirely.
    memo_max_bytes: int = 128 << 20


@dataclass
class ScanDecodeConfig:
    """Device-native decode ([scan.decode]; see ops/device_decode.py):
    eligible aggregate scans upload a segment's ENCODED sidecar buffers
    raw and fuse dict-decode + leaf filter + merge-dedup +
    bucket-aggregate into one jitted device dispatch, so host CPU
    touches the bytes only to move them (ROADMAP item 2).

    mode:
      "auto"   — engage on accelerator backends for plans the fused
                 aggregate declines anyway (the oversized/cold shape);
                 never on XLA-CPU, where host numpy decode measured
                 faster (the host_agg trade).
      "device" — force the fused dispatch wherever structurally
                 eligible (A/B tests and the chaos suite's device leg;
                 takes precedence over the fused aggregate).
      "host"   — the pre-change host decode everywhere: THE bit
                 -identity control (the seeded chaos suite
                 byte-compares the two).
    HORAEDB_DEVICE_DECODE=1/0 forces device/host over the config.
    Structurally-ineligible plans/segments fall back per reason to
    scan_decode_fallback_total{reason=} (docs/observability.md)."""

    mode: str = "auto"
    # HBM admission per segment dispatch: a segment whose padded upload
    # would exceed this decodes on host instead (reason="budget")
    max_upload_bytes: int = 256 << 20


@dataclass
class ScanPipelineConfig:
    """Cold-scan pipelining ([scan.pipeline]): the cold read path runs
    as a bounded producer/consumer pipeline — a fetch stage that keeps
    up to `depth` segments' store reads in flight (tier-2-resident
    parts skip the store entirely), a decode/merge stage on the CPU
    pool, and the device stage consuming finished windows — instead of
    phase-at-a-time per segment.  `enabled = false` reproduces the
    pre-pipeline sequential path exactly (results are bit-identical
    either way; the seeded chaos suite asserts it)."""

    enabled: bool = True
    # segments in flight across the whole pipeline (fetch started ->
    # consumed); replaces [scan] prefetch_segments when enabled.  On a
    # 25 ms-latency object store every unit of depth hides another
    # segment's round trips behind the current segment's decode.
    depth: int = 32
    # host-RAM byte budget for in-flight pipeline state (fetched
    # encoded parts/tables + decoded-but-unconsumed windows).  A slow
    # device stage backpressures fetch/decode here instead of
    # ballooning RAM; one oversized segment is still always admitted
    # (progress over the soft bound).
    inflight_bytes: int = 256 << 20


@dataclass
class ScanMeshConfig:
    """In-region 2-D device mesh for the aggregate scan ([scan.mesh];
    parallel/mesh.py, docs/parallel.md): plan segments shard along the
    `time` axis (one merge window per slot, plan-order admission),
    group/tsid blocks along the `series` axis, with an on-mesh
    segmented-reduction combine so a segment-run's windows fold on the
    mesh and only per-run (and, for top-k, per-winner) grids leave a
    chip.  `enabled = false` (default) reproduces the single-chip path
    exactly — THE bit-identity control the seeded chaos suite compares
    against (tests/test_mesh_scan.py)."""

    enabled: bool = False
    # axis sizes; 0 = auto (all local devices, factored by
    # parallel.mesh.default_scan_shape).  `series` must be a power of
    # two — it must divide the padded group space.
    time: int = 0
    series: int = 0
    # per-device admission gate for one round's transient partial grid
    # (g_pad x width x aggs x 4B): rounds that would exceed it fall
    # back to the single-chip kernel (reason="grid_budget").  Pure
    # admission bound, no resident bytes — the sliced per-shard state
    # is 1/series of it and freed when the round's parts download.
    max_grid_bytes: int = 256 << 20


@dataclass
class ScanConfig:
    """Device scan execution knobs (no reference analogue — the TPU
    build's HBM-budget control, SURVEY.md hard part #5)."""

    # max rows per compiled device window; segments larger than this are
    # processed as PK-range-partitioned windows
    max_window_rows: int = 1 << 20
    # HBM-resident post-merge cache budget in rows (0 disables); keyed by
    # (segment, SST set, columns) so writes/compaction invalidate
    # structurally.  The cache accounts BYTES (column widths + memo
    # allowance); this row knob converts at _CACHE_BYTES_PER_ROW unless
    # cache_max_bytes overrides it.  Governs the windows' account, the
    # stack cache and the route gate (read.py: cache_budget_bytes); the
    # device-decode slices' account is sized from the device's reported
    # bytes_limit and falls back to this budget only where the backend
    # reports none.
    cache_max_rows: int = 4 << 20
    # explicit budget in bytes for the scan cache (0 = derive from
    # cache_max_rows).  Cached scan windows are HOST-resident (RAM: the
    # merge runs on host) and the flush-stack cache — the stacked
    # aggregation inputs actually living in HBM — gets the same budget;
    # worst-case HBM is 1x this value.
    cache_max_bytes: int = 0
    # single-device aggregate rounds: windows (across segments) batched
    # into one compiled program per round — the UnionExec axis as a vmap.
    agg_batch_windows: int = 16
    # segments whose manifest row count exceeds this stream window-by-
    # window: a first pass over one PK column plans value-range windows,
    # then each window's rows are read via parquet predicate pushdown,
    # so host materialization is bounded by the window budget instead of
    # the segment size (the reference's pull-streaming, read.rs:346-385).
    # 0 disables streaming entirely (always read whole segments).
    stream_read_min_rows: int = 8 << 20
    # byte twin of the row knob (manifest SST sizes): a segment UNDER
    # the row threshold still streams when its stored bytes exceed this
    # — row counts under-estimate host RAM for wide schemas.  Only
    # consulted when streaming is enabled (stream_read_min_rows > 0)
    # and the segment spans more than one window; 0 disables the byte
    # trigger.
    stream_read_min_bytes: int = 512 << 20
    # read device-layout sidecars ({id}.enc) on OVERWRITE-mode bulk
    # segment reads when present (see storage/sidecar.py); disable to
    # force the parquet decode path
    use_sidecar: bool = True
    # segment tables/parts held in memory ahead of the merge position:
    # deeper prefetch overlaps more object-store reads with device work
    # on true-cold scans, at the cost of host RAM for the in-flight
    # segments
    prefetch_segments: int = 4
    # width of the "sst" decode pool (parquet/sidecar deserialize,
    # window prep); 0 = threads.sst_thread_num.  A [scan]-level
    # override so cold-path tuning lives next to prefetch_segments.
    decode_workers: int = 0
    # tiered scan-cache knobs ([scan.cache])
    cache: ScanCacheConfig = field(default_factory=ScanCacheConfig)
    # aggregate combine/finalize knobs ([scan.combine]): sparse-vs-dense
    # fold mode and the delta-summation parts memo budget
    combine: ScanCombineConfig = field(default_factory=ScanCombineConfig)
    # cold-scan pipelining knobs ([scan.pipeline]); when enabled the
    # pipeline's depth/inflight_bytes supersede prefetch_segments on
    # the cold path (the off path keeps using prefetch_segments)
    pipeline: ScanPipelineConfig = field(
        default_factory=ScanPipelineConfig)
    # device-native decode knobs ([scan.decode]): fuse sidecar decode +
    # filter + bucket-aggregate into one device dispatch for eligible
    # aggregate scans; "host" reproduces the pre-change path exactly
    decode: ScanDecodeConfig = field(default_factory=ScanDecodeConfig)
    # 2-D (time x series) mesh scan knobs ([scan.mesh])
    mesh: ScanMeshConfig = field(default_factory=ScanMeshConfig)


@dataclass
class ThreadsConfig:
    """Worker-pool sizes (ref: the server's threads config feeding
    StorageRuntimes, src/server/src/main.rs:104-109)."""

    sst_thread_num: int = 4
    compact_thread_num: int = 2
    manifest_thread_num: int = 1


@dataclass
class StorageConfig:
    """Top-level engine config (ref: config.rs:157-164)."""

    write: WriteConfig = field(default_factory=WriteConfig)
    manifest: ManifestConfig = field(default_factory=ManifestConfig)
    scheduler: SchedulerConfig = field(default_factory=SchedulerConfig)
    scan: ScanConfig = field(default_factory=ScanConfig)
    threads: ThreadsConfig = field(default_factory=ThreadsConfig)
    retry: RetryConfig = field(default_factory=RetryConfig)
    scrub: ScrubConfig = field(default_factory=ScrubConfig)
    update_mode: UpdateMode = UpdateMode.OVERWRITE


_DURATION_FIELDS = {"schedule_interval", "merge_interval", "ttl",
                    "soft_merge_max_wait", "base_backoff", "max_backoff",
                    "op_deadline", "interval", "grace_period"}
_SIZE_FIELDS = {"memory_limit", "new_sst_max_size"}
# Nested sections, keyed by field name.  This dict is THE mechanism for
# nested coercion: add new nested config dataclasses here.
_NESTED = {
    "write": WriteConfig,
    "manifest": ManifestConfig,
    "scheduler": SchedulerConfig,
    "scan": ScanConfig,
    "cache": ScanCacheConfig,
    "combine": ScanCombineConfig,
    "pipeline": ScanPipelineConfig,
    "decode": ScanDecodeConfig,
    "mesh": ScanMeshConfig,
    "threads": ThreadsConfig,
    "retry": RetryConfig,
    "scrub": ScrubConfig,
}


def _coerce(cls: type, f: dataclasses.Field, value: Any) -> Any:
    where = f"{cls.__name__}.{f.name}"
    if value is None:
        return None
    if f.name in _DURATION_FIELDS:
        if isinstance(value, ReadableDuration):
            return value
        ensure(isinstance(value, str), f'{where} expects a duration string like "10s"')
        return ReadableDuration.parse(value)
    if f.name in _SIZE_FIELDS:
        if isinstance(value, ReadableSize):
            return value
        ensure(isinstance(value, str), f'{where} expects a size string like "2GB"')
        return ReadableSize.parse(value)
    if f.name == "update_mode":
        if isinstance(value, UpdateMode):
            return value
        try:
            return UpdateMode(value)
        except ValueError as e:
            raise Error.context(
                f"{where}: expected one of {[m.value for m in UpdateMode]}", e)
    if f.name == "compression":
        if isinstance(value, CompressionCodec):
            return value
        try:
            return CompressionCodec(str(value).lower())
        except ValueError as e:
            raise Error.context(
                f"{where}: expected one of {[c.value for c in CompressionCodec]}", e)
    if f.name == "column_options":
        ensure(isinstance(value, dict), f"{where} expects a table of column options")
        return {k: from_dict(ColumnOptions, v) for k, v in value.items()}
    if f.name in _NESTED:
        ensure(isinstance(value, dict), f"{where} expects a config table")
        return from_dict(_NESTED[f.name], value)
    return _check_scalar(cls, f, value, where)


def _check_scalar(cls: type, f: dataclasses.Field, value: Any, where: str) -> Any:
    """Validate plain int/bool/str fields against their declared type so
    misconfigurations fail at load, not mid-flight (bool checked before int
    since bool subclasses int)."""
    hints = _type_hints(cls)
    declared = hints.get(f.name)
    if declared is None:
        return value
    origin = typing.get_origin(declared)
    if origin is typing.Union:  # Optional[T]
        args = [a for a in typing.get_args(declared) if a is not type(None)]
        if len(args) != 1:
            return value
        declared = args[0]
    if declared is bool:
        ensure(isinstance(value, bool), f"{where} expects a boolean")
    elif declared is int:
        ensure(isinstance(value, int) and not isinstance(value, bool),
               f"{where} expects an integer")
    elif declared is str:
        ensure(isinstance(value, str), f"{where} expects a string")
    return value


@functools.lru_cache(maxsize=None)
def _type_hints(cls: type) -> dict[str, Any]:
    return typing.get_type_hints(cls)


def from_dict(cls: type, data: dict[str, Any]) -> Any:
    """Build a config dataclass from a parsed TOML/JSON dict.

    Rejects unknown keys, mirroring serde's deny_unknown_fields
    (ref: config.rs:24-26 and every config struct), and validates value
    types at load time so misconfigurations fail here, not mid-flight.
    """
    ensure(isinstance(data, dict), f"{cls.__name__} config must be a table")
    names = {f.name: f for f in dataclasses.fields(cls)}
    unknown = set(data) - set(names)
    if unknown:
        raise Error(f"unknown config keys for {cls.__name__}: {sorted(unknown)}")
    kwargs = {key: _coerce(cls, names[key], value) for key, value in data.items()}
    return cls(**kwargs)
