"""JAX/XLA physical operators — the TPU compute core.

This layer replaces the reference's vendored DataFusion physical operators
(ParquetExec → FilterExec → SortPreservingMergeExec → MergeExec,
ref: src/storage/src/read.rs:429-494) with a TPU-first design:

- Columns live on device as int32 codes / float32 values only — no i64/u64
  on device.  Timestamps are int32 offsets from a per-query epoch; strings
  and u64 sequence numbers are order-preserving dictionary codes built on
  the host (ops/encode.py).  This keeps every array MXU/VPU-friendly and
  avoids x64 mode entirely.
- All ops are static-shape: batches are padded to capacity buckets and
  carry a row-validity count.  No recompilation per batch size.
- The CPU streaming k-way merge (SortPreservingMergeExec + MergeExec's
  row-at-a-time scalar loop, ref: read.rs:262-343) becomes ONE device-wide
  lexicographic sort over concatenated SST batches plus a vectorized
  run-boundary mask and segmented last-select (ops/merge.py).
- Time-bucket downsampling is a segmented reduction over
  (group, bucket) ids (ops/downsample.py).
"""

from horaedb_tpu.ops.encode import (
    ColumnEncoding,
    DeviceBatch,
    decode_to_arrow,
    encode_batch,
    pad_capacity,
)
from horaedb_tpu.ops.merge import (dedup_sorted_last, merge_dedup_last,
                                   sorted_run_starts)
from horaedb_tpu.ops.downsample import time_bucket_aggregate
from horaedb_tpu.ops.filter import (
    And,
    Eq,
    Ge,
    Gt,
    In,
    Le,
    Lt,
    Ne,
    Not,
    Or,
    TimeRangePred,
    eval_predicate,
)
from horaedb_tpu.ops.topk import top_k_groups

__all__ = [
    "And", "ColumnEncoding", "DeviceBatch", "Eq", "Ge", "Gt", "In", "Le",
    "Lt", "Ne", "Not", "Or", "TimeRangePred", "decode_to_arrow",
    "dedup_sorted_last", "encode_batch", "eval_predicate", "merge_dedup_last",
    "pad_capacity",
    "sorted_run_starts", "time_bucket_aggregate", "top_k_groups",
]
