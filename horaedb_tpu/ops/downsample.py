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


def _wanted(which: tuple) -> set:
    """`which` with its dependencies (avg needs sum); unknown names
    refused."""
    want = set(which)
    unknown = want - set(ALL_AGGS)
    if unknown:
        raise ValueError(f"unknown aggregates {sorted(unknown)}; "
                         f"supported: {ALL_AGGS}")
    if "avg" in want:
        want.add("sum")
    return want


def _cell_index(ts_offset, group_ids, n_valid, bucket_ms,
                num_groups: int, num_buckets: int):
    """(in_grid, cell): whether a row lands in the grid, and the flat
    cell `group * num_buckets + bucket` it lands in (meaningless where
    in_grid is False)."""
    iota = jnp.arange(ts_offset.shape[0], dtype=jnp.int32)
    valid = iota < jnp.asarray(n_valid, dtype=jnp.int32)
    bucket = ts_offset // jnp.asarray(bucket_ms, dtype=jnp.int32)
    in_grid = valid & (bucket >= 0) & (bucket < num_buckets) \
        & (group_ids >= 0) & (group_ids < num_groups)
    return in_grid, group_ids * num_buckets + bucket


def partial_aggregate(ts_offset: jax.Array, group_ids: jax.Array,
                      values: jax.Array, n_valid, bucket_ms,
                      num_groups: int, num_buckets: int,
                      which: tuple = ALL_AGGS) -> dict:
    """Raw per-shard aggregate grids, all (num_groups, num_buckets):

      sum (0-init), count (0), min (+inf: segment_min's fill), max
      (-inf), last_ts (I32_MIN), last (0 where empty).

    `which` restricts computation to the requested aggregates (plus
    their dependencies: avg needs sum+count, last needs last_ts; count
    is always produced — finalize and cross-shard combining key on it).
    Combinable across shards: sum/count by +, min by min, max by max,
    (last_ts, last) by argmax-ts with later-shard tie-break.
    """
    want = _wanted(which)
    capacity = ts_offset.shape[0]
    iota = jnp.arange(capacity, dtype=jnp.int32)
    num_cells = num_groups * num_buckets
    # each stage under a jax.named_scope: the scope rides the HLO
    # metadata of the operations it emits, so a profile can tell the
    # scan programs' fusions apart (docs/observability.md)
    with jax.named_scope("bucket_index"):
        in_grid, cell = _cell_index(ts_offset, group_ids, n_valid,
                                    bucket_ms, num_groups, num_buckets)
        # out-of-grid rows land in an overflow cell that is sliced away
        seg = jnp.where(in_grid, cell, num_cells)

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


# a run reduction scans its rows in blocks of this many (the TPU's
# lane count: a block is one row of a 2-D array, the in-block scan
# shifts along lanes, and a cell's answer is read from ONE gathered
# block)
_RUN_BLOCK = 128

# what a row that is not in the cell contributes to each scanned
# quantity; `last` needs none (`last_ts` decides)
_RUN_IDENTITY = {"count": 0, "sum": 0.0, "min": jnp.inf, "max": -jnp.inf,
                 "last_ts": _I32_MIN, "last": 0.0}


def _shift(x: jax.Array, d: int, fill, axis: int) -> jax.Array:
    """x moved d places toward higher indices along `axis`, `fill`
    entering."""
    pad = [(0, 0)] * x.ndim
    pad[axis] = (d, 0)
    kept = jax.lax.slice_in_dim(x, 0, x.shape[axis] - d, axis=axis)
    return jnp.pad(kept, pad, constant_values=fill)


def _run_fold(left: dict, cur: dict) -> dict:
    """`cur` with the EARLIER rows' `left` folded in: count/sum add,
    min/max select, (last_ts, last) keeps the later row on a tie — what
    the two segment_max passes of partial_aggregate compute."""
    out = {}
    for k in cur:
        if k in ("count", "sum"):
            out[k] = left[k] + cur[k]
        elif k == "min":
            out[k] = jnp.minimum(left[k], cur[k])
        elif k == "max":
            out[k] = jnp.maximum(left[k], cur[k])
    if "last" in cur:
        earlier = left["last_ts"] > cur["last_ts"]
        out["last"] = jnp.where(earlier, left["last"], cur["last"])
        out["last_ts"] = jnp.where(earlier, left["last_ts"],
                                   cur["last_ts"])
    return out


def _run_scan(key: jax.Array, state: dict, axis: int) -> dict:
    """Inclusive SEGMENTED scan along `axis` by log2(n) doubling steps:
    every entry folds in the entries before it that carry its key.
    Keys are non-decreasing along the axis, so equal keys are
    contiguous and `key[i - d] == key[i]` says the whole stretch
    between the two is one run: no start flags to carry.  A run's sum
    is a balanced tree of its rows, never a difference of prefixes."""
    d = 1
    while d < key.shape[axis]:
        same = _shift(key, d, -2, axis) == key
        left = {k: jnp.where(same, _shift(v, d, 0, axis),
                             _RUN_IDENTITY[k])
                for k, v in state.items()}
        state = _run_fold(left, state)
        d *= 2
    return state


def _place_by_search(key: jax.Array, state: dict, num_cells: int) -> dict:
    """Each cell's totals read off the last row of its run, (num_cells,)
    each: a dense compare over the blocks' first keys finds the block
    that row is in, ONE gather brings every scanned quantity of that
    block to the cell, and a compare inside the block picks the row.
    For cells no more than rows: its gather moves a block per CELL."""
    L = key.shape[1]
    cells = jnp.arange(num_cells, dtype=jnp.int32)
    # the last block that starts at or before the cell holds the last
    # row keyed at or before it: the end of the cell's run, if any
    blk = jnp.maximum(jnp.searchsorted(
        key[:, 0], cells, side="right", method="compare_all") - 1, 0)
    names = list(state)
    packed = jnp.concatenate(
        [key] + [jax.lax.bitcast_convert_type(state[k], jnp.int32)
                 for k in names], axis=1)
    rows = packed.at[blk].get(mode="promise_in_bounds")
    pos = jnp.sum(rows[:, :L] <= cells[:, None], axis=1) - 1
    at_end = jnp.arange(L, dtype=jnp.int32)[None, :] == pos[:, None]

    def pick(j, dtype):
        got = jnp.sum(jnp.where(at_end, rows[:, j * L:(j + 1) * L], 0),
                      axis=1)
        return jax.lax.bitcast_convert_type(got, dtype)

    found = (pos >= 0) & (pick(0, jnp.int32) == cells)
    return {k: jnp.where(found, pick(j, state[k].dtype), _RUN_IDENTITY[k])
            for j, k in enumerate(names, start=1)}


def _place_by_scatter(key: jax.Array, state: dict, num_cells: int) -> dict:
    """The same totals, (num_cells,) each, put there from the rows' side:
    the last row of every run writes its cell, every other row is
    dropped.  For cells beyond rows (the point query: 1,024 rows into
    61,440 cells), where a search per cell would cost more than an
    update per ROW per grid; no update collides, and nothing is
    gathered per cell as partial_aggregate's `last` is."""
    flat = key.reshape(-1)
    ends = flat != jnp.concatenate(
        [flat[1:], jnp.full((1,), num_cells + 1, jnp.int32)])
    at = jnp.where(ends & (flat >= 0), flat, num_cells)
    return {k: jnp.full((num_cells,), _RUN_IDENTITY[k], v.dtype)
            .at[at].set(v.reshape(-1), mode="drop")
            for k, v in state.items()}


def run_aggregate(ts_offset: jax.Array, group_ids: jax.Array,
                  values: jax.Array, n_valid, bucket_ms,
                  num_groups: int, num_buckets: int,
                  which: tuple = ALL_AGGS) -> dict:
    """partial_aggregate for rows whose CELL ORDER IS NON-DECREASING:
    over the rows that land in the grid, `group * num_buckets + bucket`
    never falls (rows sorted by (group, ts): the fused device decode's
    served shape, ops/device_decode.plan_segment decides it per slice).
    Same arguments, same grids, same fill in empty cells; dropped rows
    (group -1, padding, outside the bucket range) may lie anywhere and
    hold anything.  count/min/max/last/last_ts are bit-identical to
    partial_aggregate's, sum is float32 in another association (a tree
    over the cell's rows where the scatter adds them in row order).

    Every cell is then one contiguous run of rows, so the rows are
    reduced by runs, in elementwise passes (a TPU scatter or gather
    costs ~9 ns per element moved, 1.1 ms a grid at capacity 131,072;
    a pass over the rows costs ~1 us), and only the run ENDS are
    placed: by a search per cell where the rows outnumber the cells,
    by a scatter from the rows where they do not (chosen from the
    static shapes)."""
    want = _wanted(which)
    capacity = ts_offset.shape[0]
    num_cells = num_groups * num_buckets
    L = _RUN_BLOCK
    nb = -(-capacity // L)
    with jax.named_scope("bucket_index"):
        in_grid, cell = _cell_index(ts_offset, group_ids, n_valid,
                                    bucket_ms, num_groups, num_buckets)

    def blocks(a, fill):
        return jnp.pad(a, (0, nb * L - capacity),
                       constant_values=fill).reshape(nb, L)

    with jax.named_scope("run_keys"):
        # a dropped row rides in the run of the kept row before it
        # (key -1 ahead of the first): it holds the identities, so it
        # adds nothing and splits nothing
        key = blocks(jax.lax.cummax(jnp.where(in_grid, cell, -1)),
                     num_cells)
        kept = blocks(in_grid, False)
        vals = blocks(values, 0.0)
        state = {"count": kept.astype(jnp.int32)}
        if "sum" in want:
            state["sum"] = jnp.where(kept, vals, 0.0)
        if "min" in want:
            state["min"] = jnp.where(kept, vals, jnp.inf)
        if "max" in want:
            state["max"] = jnp.where(kept, vals, -jnp.inf)
        if "last" in want:
            state["last_ts"] = jnp.where(kept, blocks(ts_offset, 0),
                                         _I32_MIN)
            state["last"] = jnp.where(kept, vals, 0.0)

    with jax.named_scope("run_scan"):
        state = _run_scan(key, state, axis=1)
        # across blocks: a block's last entry holds its trailing run,
        # and where the block before ends on the same key the WHOLE
        # block is that run (keys never fall), so the same scan over
        # the blocks' last entries totals every trailing run; the
        # block after takes it into the rows that continue the run
        k_last = key[:, -1]
        tails = _run_scan(k_last, {k: v[:, -1] for k, v in state.items()},
                          axis=0)
        continues = key == _shift(k_last, 1, -2, 0)[:, None]
        state = _run_fold(
            {k: jnp.where(continues, _shift(v, 1, 0, 0)[:, None],
                          _RUN_IDENTITY[k]) for k, v in tails.items()},
            state)

    with jax.named_scope("run_place"):
        place = _place_by_search if num_cells <= capacity \
            else _place_by_scatter
        out = place(key, state, num_cells)
        out["count"] = out["count"].astype(jnp.float32)
    return {k: v.reshape(num_groups, num_buckets) for k, v in out.items()}


def window_local_partials(ts, gid_local, vals, remap, shift, lo,
                          total_buckets, bucket_ms, *, num_groups: int,
                          num_buckets: int, which: tuple = ALL_AGGS,
                          cells_sorted: bool = False) -> dict:
    """One window's partial grids over its LOCAL bucket range — the
    shared inner of the engine's batched (vmap) and meshed (shard_map)
    aggregation programs.

    Args:
      ts: int32 (capacity,) — encoded ts (offsets from the window's
        epoch).
      gid_local: int32 (capacity,) — window-local dense group codes;
        -1 = dropped row (padding or predicate-filtered).
      remap: int32 (num_groups,) — local code -> union-group row; None
        where the local codes ARE the rows (the fused device decode),
        which saves a gather over every row.
      shift: scalar int32 — ts + shift = offset from the query range
        start.
      lo: scalar int32 — first bucket this window's grid covers; local
        grid bucket b corresponds to global bucket lo + b.
      total_buckets: traced scalar — global bucket count; rows at or
        beyond it are dropped (windows may overhang the query range).
      num_buckets: static LOCAL grid width.
      cells_sorted: static — the kept rows' (group, bucket) never
        falls, so the grids come from run_aggregate (no scatter).
    """
    gid_union = gid_local
    if remap is not None:
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
    aggregate = run_aggregate if cells_sorted else partial_aggregate
    return aggregate(ts_local, gid_union, vals, ts.shape[0], bucket_ms,
                     num_groups=num_groups, num_buckets=num_buckets,
                     which=which)


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
