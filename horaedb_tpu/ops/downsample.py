"""Time-bucketed downsampling as segmented reductions.

The reference has no downsample operator yet (the legacy engine pushes
sum/rate into DataFusion aggregates; RFC 20220702 splits them to a query
frontend).  Here it is a first-class device op because it IS the north-star
workload (BASELINE.md configs 1-3, 5): `GROUP BY series, time(bucket)`
over min/max/sum/count/avg/last.

Shape discipline: output is a dense (num_groups, num_buckets) grid —
group ids are dictionary codes, bucket ids are (ts - range_start) //
bucket_ms.  Both counts are static per query, so jit compiles one program
per (capacity, groups, buckets) signature.

Split into partial_aggregate / finalize_aggregate so the multi-chip path
(parallel/scan.py) can combine partial grids across the mesh's time
axis before finalizing — the identity elements (0, +/-inf, INT32_MIN)
combine correctly under collectives, NaNs would not.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from horaedb_tpu.common import deviceprof

_F32_MAX = jnp.float32(jnp.finfo(jnp.float32).max)
_I32_MIN = jnp.int32(-(2**31))


ALL_AGGS = ("count", "sum", "min", "max", "avg", "last")


def partial_aggregate(ts_offset: jax.Array, group_ids: jax.Array,
                      values: jax.Array, n_valid, bucket_ms,
                      num_groups: int, num_buckets: int,
                      which: tuple = ALL_AGGS) -> dict:
    """Raw per-shard aggregate grids, all (num_groups, num_buckets):

      sum (0-init), count (0), min (+F32_MAX), max (-F32_MAX),
      last_ts (I32_MIN), last (0 where empty).

    `which` restricts computation to the requested aggregates (plus
    their dependencies: avg needs sum+count, last needs last_ts; count
    is always produced — finalize and cross-shard combining key on it).
    Combinable across shards: sum/count by +, min by min, max by max,
    (last_ts, last) by argmax-ts with later-shard tie-break.
    """
    want = set(which)
    unknown = want - set(ALL_AGGS)
    if unknown:
        raise ValueError(f"unknown aggregates {sorted(unknown)}; "
                         f"supported: {ALL_AGGS}")
    if "avg" in want:
        want.add("sum")
    capacity = ts_offset.shape[0]
    iota = jnp.arange(capacity, dtype=jnp.int32)
    valid = iota < jnp.asarray(n_valid, dtype=jnp.int32)

    # each stage under a jax.named_scope: the scope rides the HLO
    # metadata of the operations it emits, so a profile can tell the
    # scan programs' fusions apart (docs/observability.md)
    with jax.named_scope("bucket_index"):
        bucket = ts_offset // jnp.asarray(bucket_ms, dtype=jnp.int32)
        in_grid = valid & (bucket >= 0) & (bucket < num_buckets) \
            & (group_ids >= 0) & (group_ids < num_groups)
        num_cells = num_groups * num_buckets
        # out-of-grid rows land in an overflow cell that is sliced away
        seg = jnp.where(in_grid, group_ids * num_buckets + bucket,
                        num_cells)

    grid = lambda a: a.reshape(num_groups, num_buckets)
    with jax.named_scope("scatter_count"):
        ones = in_grid.astype(jnp.float32)
        out = {"count": grid(jax.ops.segment_sum(
            ones, seg, num_segments=num_cells + 1)[:num_cells])}
    if "sum" in want:
        with jax.named_scope("scatter_sum"):
            out["sum"] = grid(jax.ops.segment_sum(
                jnp.where(in_grid, values, 0.0), seg,
                num_segments=num_cells + 1)[:num_cells])
    if "min" in want:
        with jax.named_scope("scatter_min"):
            out["min"] = grid(jax.ops.segment_min(
                jnp.where(in_grid, values, _F32_MAX), seg,
                num_segments=num_cells + 1)[:num_cells])
    if "max" in want:
        with jax.named_scope("scatter_max"):
            out["max"] = grid(jax.ops.segment_max(
                jnp.where(in_grid, values, -_F32_MAX), seg,
                num_segments=num_cells + 1)[:num_cells])
    if "last" in want:
        # "last" = value at the highest timestamp in the cell (later row
        # wins ties, mirroring last-value merge semantics).  Two segmented
        # passes: max ts per cell, then max row index at that ts.
        with jax.named_scope("scatter_last"):
            tmax = jax.ops.segment_max(
                jnp.where(in_grid, ts_offset, _I32_MIN), seg,
                num_segments=num_cells + 1)
            at_max_ts = in_grid & (ts_offset == tmax[seg])
            last_row = jax.ops.segment_max(
                jnp.where(at_max_ts, iota, -1), seg,
                num_segments=num_cells + 1)[:num_cells]
            out["last"] = grid(jnp.where(
                last_row >= 0,
                values[jnp.clip(last_row, 0, capacity - 1)], 0.0))
            out["last_ts"] = grid(tmax[:num_cells])
    return out


def window_local_partials(ts, gid_local, vals, remap, shift, lo,
                          total_buckets, bucket_ms, *, num_groups: int,
                          num_buckets: int, which: tuple = ALL_AGGS) -> dict:
    """One window's partial grids over its LOCAL bucket range — the
    shared inner of the engine's batched (vmap) and meshed (shard_map)
    aggregation programs.

    Args:
      ts: int32 (capacity,) — encoded ts (offsets from the window's
        epoch).
      gid_local: int32 (capacity,) — window-local dense group codes;
        -1 = dropped row (padding or predicate-filtered).
      remap: int32 (num_groups,) — local code -> union-group row.
      shift: scalar int32 — ts + shift = offset from the query range
        start.
      lo: scalar int32 — first bucket this window's grid covers; local
        grid bucket b corresponds to global bucket lo + b.
      total_buckets: traced scalar — global bucket count; rows at or
        beyond it are dropped (windows may overhang the query range).
      num_buckets: static LOCAL grid width.
    """
    with jax.named_scope("decode_group_ids"):
        gid_union = jnp.where(
            gid_local >= 0,
            remap[jnp.clip(gid_local, 0, remap.shape[0] - 1)], -1)
    with jax.named_scope("decode_timestamps"):
        bucket_ms = jnp.asarray(bucket_ms, jnp.int32)
        ts_global = ts + jnp.asarray(shift, jnp.int32)
        bucket_global = ts_global // bucket_ms
        gid_union = jnp.where(
            bucket_global < jnp.asarray(total_buckets, jnp.int32),
            gid_union, -1)
        # exact: (a - lo*b) // b == a//b - lo for integer floor division
        ts_local = ts_global - jnp.asarray(lo, jnp.int32) * bucket_ms
    return partial_aggregate(ts_local, gid_union, vals, ts.shape[0],
                             bucket_ms, num_groups=num_groups,
                             num_buckets=num_buckets, which=which)


def combine_partial_pair(cur: dict, prev: dict) -> dict:
    """Pairwise combine of two partial-grid dicts over the SAME local
    bucket span — the associative op of the mesh scan's segmented time
    -axis reduction (parallel/scan.py mesh_run_partials).  `prev` is
    the EARLIER prefix; ties on last_ts keep `cur` (later window wins,
    mirroring the host fold's `>=` take in storage/combine.py).

    Exactness: count adds are exact integer-valued f32 while a cell's
    combined count stays < 2^24 (the dispatcher bounds time_axis x
    capacity); min/max/last are selection ops; sum is exact only for
    cells with a single contributing window — the dispatcher's overlap
    gate keeps multi-contributor sums off the mesh."""
    out = {"count": cur["count"] + prev["count"]}
    if "sum" in cur:
        out["sum"] = cur["sum"] + prev["sum"]
    if "min" in cur:
        out["min"] = jnp.minimum(cur["min"], prev["min"])
    if "max" in cur:
        out["max"] = jnp.maximum(cur["max"], prev["max"])
    if "last" in cur:
        take_cur = cur["last_ts"] >= prev["last_ts"]
        out["last"] = jnp.where(take_cur, cur["last"], prev["last"])
        out["last_ts"] = jnp.where(take_cur, cur["last_ts"],
                                   prev["last_ts"])
    return out


def finalize_aggregate(partial: dict, which: tuple = ALL_AGGS) -> dict:
    """Turn combined partial grids into user-facing aggregates.
    Empty cells: count 0, sum 0, min +inf, max -inf, avg/last NaN.
    Emits the requested aggregates that `partial` can supply (count is
    always present)."""
    want = set(which) | {"count"}
    count = partial["count"]
    empty = count == 0
    nan = jnp.float32(jnp.nan)
    out = {"count": count}
    if "sum" in partial and "sum" in want:
        out["sum"] = partial["sum"]
    if "sum" in partial and "avg" in want:
        out["avg"] = jnp.where(empty, nan,
                               partial["sum"] / jnp.maximum(count, 1.0))
    if "min" in partial and "min" in want:
        out["min"] = partial["min"]
    if "max" in partial and "max" in want:
        out["max"] = partial["max"]
    if "last" in partial and "last" in want:
        out["last"] = jnp.where(empty, nan, partial["last"])
    return out


def time_bucket_aggregate(ts_offset: jax.Array, group_ids: jax.Array,
                          values: jax.Array, n_valid, bucket_ms,
                          num_groups: int, num_buckets: int,
                          which: tuple = ALL_AGGS) -> dict:
    """See _time_bucket_aggregate_impl; this thin wrapper canonicalizes
    `which` so permutations/duplicates share one compiled program."""
    which = tuple(sorted(set(which)))
    unknown = set(which) - set(ALL_AGGS)
    if unknown:
        raise ValueError(f"unknown aggregates {sorted(unknown)}; "
                         f"supported: {ALL_AGGS}")
    return _time_bucket_aggregate_impl(
        ts_offset, group_ids, values, n_valid, bucket_ms,
        num_groups=num_groups, num_buckets=num_buckets, which=which)


@deviceprof.jit(static_argnames=("num_groups", "num_buckets", "which"))
def _time_bucket_aggregate_impl(ts_offset: jax.Array, group_ids: jax.Array,
                                values: jax.Array, n_valid, bucket_ms,
                                num_groups: int, num_buckets: int,
                                which: tuple = ALL_AGGS) -> dict:
    """Single-shard aggregate: partial + finalize in one compiled program.

    Args:
      ts_offset: int32 (capacity,) — timestamp offsets from the query range
        start (so bucket 0 starts at offset 0).
      group_ids: int32 (capacity,) — dictionary codes of the group key.
      values: float32 (capacity,).
      n_valid: scalar int — real row count.
      bucket_ms: scalar int32 — bucket width in the ts unit.
      num_groups / num_buckets: static grid extents.

    Returns a dict of (num_groups, num_buckets) float32 grids holding
    `count` plus the aggregates requested via `which` (default: sum,
    min, max, avg, last — `last` is the value at max ts per cell).
    """
    return finalize_aggregate(
        partial_aggregate(ts_offset, group_ids, values, n_valid, bucket_ms,
                          num_groups, num_buckets, which=which),
        which=which)
