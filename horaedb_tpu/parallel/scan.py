"""shard_map scan programs over the 2-D (time, series) scan mesh
([scan.mesh]; docs/parallel.md).

Data layout: the host stacks one merge window per time slot into
(time, capacity) arrays sharded on the leading axis.  Dedup is
segment-scoped by design (each segment gets its own merge, as in the
reference), so the row work is shard-local; only (groups x buckets)
grids cross chips, in the segmented time-axis combine — never row
streams.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from horaedb_tpu.common import deviceprof
from horaedb_tpu.common.error import Error
from horaedb_tpu.ops import downsample
from horaedb_tpu.ops.topk import pair_add, pair_max_normalized
from horaedb_tpu.parallel.mesh import SERIES_AXIS, TIME_AXIS


def _check_block_is_one(block) -> None:
    """The shard programs index block [0]; a leading axis larger than the
    mesh would silently drop segments.  Fail at trace time instead."""
    if block.shape[0] != 1:
        raise Error(
            f"leading axis {block.shape[0]} exceeds the mesh: stack exactly "
            "one segment batch per device (pad the device axis, or scan in "
            "rounds)")


def shard_time_axis(mesh, arr):
    """Place a (time, ...) host array sharded over the scan mesh's time
    axis, replicated over series.  Series shards re-aggregate every row
    for their own group block — the series axis divides resident grid
    STATE and combine egress, not row work (the output-parallel layout;
    docs/parallel.md)."""
    return deviceprof.device_put(arr, NamedSharding(mesh, P(TIME_AXIS)))


def mesh_run_partials(mesh, *, num_groups: int, num_buckets: int,
                      which: tuple):
    """The 2-D mesh scan program: per-window partial grids sharded
    (time = one merge window per slot, series = group blocks) and a
    SEGMENTED reduction over the time axis — same-segment slots combine
    into per-run grids via a log2(time) ppermute tree, different
    segments never mix (parts stay per-segment, the PartsMemo / replan
    contract).

    fn(ts, gid, vals, remap, shift, lo, seg_ids, total, bucket_ms):
      ts/gid/vals: (time, capacity) sharded on the time axis;
      remap: (time, num_groups) int32 — window-local code -> round row;
      shift/lo: (time,) int32 per-window epoch offset / first bucket;
      seg_ids: (time,) int32 — slots of one segment share an id and
        are CONSECUTIVE (plan-order slot admission); padding slots
        carry unique negative ids so they never combine;
      total: replicated scalar global bucket count; bucket_ms: (1,).

    Output: dict of (time, num_groups, num_buckets) grids sharded
    (time, series); slot t holds the combined grids of its segment's
    slots up to t (inclusive segmented scan), so a run's TAIL slot
    holds the whole run — the host downloads tails only.

    Exactness contract (the mesh-off byte-identity proof, chaos
    -asserted): each window's partials are computed by the SAME
    full-width scatter program as the single-device path and only then
    block-sliced per series shard; the time-axis combine is exact for
    count (integer f32 adds, dispatcher-bounded < 2^24), min/max/last
    (selection ops, later-slot tie-break = the host fold's `>=` take),
    and for sum exactly when no cell has two contributing windows —
    the dispatcher's overlap gate routes anything else off the mesh
    (read.py _flush_mesh_round)."""
    time_n = int(mesh.shape[TIME_AXIS])
    series_n = int(mesh.shape[SERIES_AXIS])
    gb = _series_block(num_groups, series_n)

    def shard_fn(ts, gid, vals, remap, shift, lo, seg_ids, total,
                 bucket_ms):
        _check_block_is_one(ts)
        p = downsample.window_local_partials(
            ts[0], gid[0], vals[0], remap[0], shift[0], lo[0], total,
            bucket_ms[0], num_groups=num_groups,
            num_buckets=num_buckets, which=which)
        p = _series_slice(p, gb)
        state = _segmented_time_combine(p, seg_ids, time_n)
        return {k: v[None] for k, v in state.items()}

    mapped = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(TIME_AXIS, None), P(TIME_AXIS, None),
                  P(TIME_AXIS, None), P(TIME_AXIS, None),
                  P(TIME_AXIS), P(TIME_AXIS), P(TIME_AXIS), P(), P()),
        out_specs=P(TIME_AXIS, SERIES_AXIS),
        check_vma=False,
    )
    return deviceprof.jit(mapped, name="mesh_run_partials")


def _series_block(num_groups: int, series_n: int) -> int:
    if num_groups % series_n:
        raise Error(
            f"mesh group space {num_groups} not divisible by the "
            f"series axis ({series_n}) — pad g to a multiple")
    return num_groups // series_n


def _series_slice(p: dict, gb: int) -> dict:
    """Full-width compute, series-block slice AFTER: the scatter
    program (and therefore every cell's f32 accumulation order) is the
    single-device kernel's; only the RESIDENT state and the collective
    payload shrink to the (gb, width) block."""
    j = jax.lax.axis_index(SERIES_AXIS)
    return {k: jax.lax.dynamic_slice_in_dim(v, j * gb, gb, axis=0)
            for k, v in p.items()}


def _segmented_time_combine(state: dict, seg_ids, time_n: int) -> dict:
    """Inclusive SEGMENTED scan over the time axis via a log2(time)
    ppermute tree: a slot folds in its left neighbour's prefix ONLY
    when it belongs to the same segment (seg id match; ppermute hands
    zeros to slots with no left neighbour — prev_live masks them out).
    Shared by the host-decoded round program above and the fused
    decode round program below — the combine IS the byte-identity
    surface, so both programs must ride the same one."""
    sid = seg_ids  # (1,) block: ppermute needs an array operand
    step = 1
    while step < time_n:
        perm = [(i, i + step) for i in range(time_n - step)]

        def recv(a, _perm=perm):
            return jax.lax.ppermute(a, TIME_AXIS, _perm)

        prev = {k: recv(v) for k, v in state.items()}
        prev_sid = recv(sid)
        prev_live = recv(jnp.ones_like(sid))
        ok = (prev_live[0] > 0) & (prev_sid[0] == sid[0])
        combined = downsample.combine_partial_pair(state, prev)
        state = {k: jnp.where(ok, combined[k], state[k])
                 for k in state}
        step *= 2
    return state


def mesh_decode_partials(mesh, *, num_groups: int, num_buckets: int,
                         which: tuple, key_slots: tuple, num_pks: int,
                         group_pos: int, ts_pos: int, val_slot: int,
                         leaf_prog: tuple, route: str, num_runs: int,
                         cells_sorted: bool):
    """The mesh-placed FUSED decode round: each time slot starts from
    its segment's raw encoded sidecar buffers and runs leaf-filter →
    (k-way merge | sort | presorted) → keep-last dedup → bucket
    aggregate → ppermute segmented combine in ONE shard_map program —
    decode shards along the time axis with the aggregation instead of
    serializing ahead of it on one chip (ROADMAP item 1).

    Static decode geometry (key_slots/leaf_prog/route/cells_sorted/...)
    comes from
    the round's DecodePlan group (ops/device_decode.plan_dispatch);
    the dispatcher only batches plans whose DecodePlan.static_key()
    agree, so one compiled program serves the whole round.

    fn(cols, n_valid, leaf_consts, run_offsets, shift, lo, seg_ids,
       total, bucket_ms):
      cols: tuple of (time, capacity) int32 encoded code columns,
        sharded on the time axis (one segment's buffers per slot);
      n_valid: (time,) int32 real row counts (suffix is padding);
      leaf_consts: tuple of (time, L_i) int32 leaf-constant stacks
        (row t = slot t's constants for leaf i, padded by repetition);
      run_offsets: (time, num_runs + 1) int32 per-slot run bounds
        (all-capacity rows for non-kway routes ride along unused);
      shift/lo/seg_ids: (time,) int32 as in mesh_run_partials;
      total: replicated scalar global bucket count; bucket_ms: (1,).

    Slot-local group codes ARE the round rows (identity remap): the
    dispatcher gives same-segment slots a shared seg id only when
    their dictionaries match, so the combine never mixes code spaces.
    Output: (grids, kept) — grids as in mesh_run_partials (tails hold
    whole runs), kept (time,) int32 post-dedup survivor counts."""
    from horaedb_tpu.ops import device_decode

    time_n = int(mesh.shape[TIME_AXIS])
    series_n = int(mesh.shape[SERIES_AXIS])
    gb = _series_block(num_groups, series_n)

    def shard_fn(cols, n_valid, leaf_consts, run_offsets, shift, lo,
                 seg_ids, total, bucket_ms):
        _check_block_is_one(cols[0])
        p, n_rows = device_decode.decode_partials(
            tuple(c[0] for c in cols), n_valid[0],
            tuple(c[0] for c in leaf_consts), run_offsets[0],
            shift[0], lo[0], total, bucket_ms[0],
            key_slots=key_slots, num_pks=num_pks, group_pos=group_pos,
            ts_pos=ts_pos, val_slot=val_slot, leaf_prog=leaf_prog,
            route=route, num_runs=num_runs, g_pad=num_groups,
            width=num_buckets, which=which, cells_sorted=cells_sorted)
        p = _series_slice(p, gb)
        state = _segmented_time_combine(p, seg_ids, time_n)
        return ({k: v[None] for k, v in state.items()}, n_rows[None])

    mapped = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(TIME_AXIS, None), P(TIME_AXIS),
                  P(TIME_AXIS, None), P(TIME_AXIS, None),
                  P(TIME_AXIS), P(TIME_AXIS), P(TIME_AXIS), P(), P()),
        out_specs=(P(TIME_AXIS, SERIES_AXIS), P(TIME_AXIS)),
        check_vma=False,
    )
    return deviceprof.jit(mapped, name="mesh_decode_partials")


# ---- device-resident top-k score state -------------------------------------
#
# The egress-bounded top-k path (read._aggregate_topk_mesh): the round
# outputs above stay on the mesh; only a per-group score vector and the
# k winners' grid rows ever download.  Rankings by min/max/last are
# SELECTION ops, so accumulating their cells across rounds on device is
# exact — count/sum/avg rankings are additive and take the full-parts
# path instead (reason-counted).  These helpers are plain jitted jnp on
# the sharded round outputs; XLA's sharding propagation keeps the state
# series-partitioned (the round program owns the explicit collectives).
#
# Prefix slots (non-tails of the segmented scan) feed the state too:
# for selection ops a prefix's cells are a subset of its run's, so the
# duplicate combine is a no-op — no tail masking needed on device.

_TS_MIN = jnp.int32(-(2**31))


def mesh_score_init(num_groups: int, padded_buckets: int, by: str):
    """Identity-filled score state.  `padded_buckets` leaves one round
    -width of slack past the query's grid so per-slot dynamic slices
    never clamp (out-of-range buckets are empty cells by construction
    — window_local_partials drops rows past `total`)."""
    shape = (num_groups, padded_buckets)
    fill = {"min": jnp.finfo(jnp.float32).max,
            "max": -jnp.finfo(jnp.float32).max,
            "last": 0.0}[by]
    state = {"by": jnp.full(shape, jnp.float32(fill)),
             "has": jnp.zeros(shape, dtype=bool)}
    if by == "last":
        state["ts"] = jnp.full(shape, _TS_MIN)
    return state


@deviceprof.jit(static_argnames=("by",), donate_argnums=(0,))
def mesh_score_update(state: dict, by_grid, count_grid, last_ts, lo,
                      bucket_ms, *, by: str):
    """Fold one round's (time, groups, width) outputs into the score
    state, slot by slot in time order (the host fold's later-wins tie
    -break for `last`).  `last_ts` is None unless by == "last"; `lo`
    is the per-slot (time,) first-bucket offset."""
    width = by_grid.shape[2]

    def body(t, st):
        has_t = count_grid[t] > 0
        cur_by = jax.lax.dynamic_slice(
            st["by"], (0, lo[t]), (st["by"].shape[0], width))
        cur_has = jax.lax.dynamic_slice(
            st["has"], (0, lo[t]), (st["has"].shape[0], width))
        if by == "min":
            new_by = jnp.minimum(cur_by, by_grid[t])
        elif by == "max":
            new_by = jnp.maximum(cur_by, by_grid[t])
        else:  # last: select by global (range-relative) timestamp
            cur_ts = jax.lax.dynamic_slice(
                st["ts"], (0, lo[t]), (st["ts"].shape[0], width))
            cand_ts = jnp.where(has_t,
                                last_ts[t] + lo[t] * bucket_ms, _TS_MIN)
            take = cand_ts >= cur_ts
            new_by = jnp.where(take, by_grid[t], cur_by)
            st = dict(st)
            st["ts"] = jax.lax.dynamic_update_slice(
                st["ts"], jnp.where(take, cand_ts, cur_ts), (0, lo[t]))
        out = dict(st)
        out["by"] = jax.lax.dynamic_update_slice(st["by"], new_by,
                                                 (0, lo[t]))
        out["has"] = jax.lax.dynamic_update_slice(
            st["has"], cur_has | has_t, (0, lo[t]))
        return out

    return jax.lax.fori_loop(0, by_grid.shape[0], body, state)


@deviceprof.jit(static_argnames=("largest", "num_buckets"))
def mesh_score_finalize(state: dict, *, largest: bool, num_buckets: int):
    """(scores, has_any) per group — the ONLY full-group bytes the
    top-k path downloads.  Score formula mirrors combine_top_k's: the
    best count>0 cell of the ranking grid (NaN cells propagate, as in
    the host's np.max)."""
    by_grid = state["by"][:, :num_buckets]
    has = state["has"][:, :num_buckets]
    if largest:
        scores = jnp.where(has, by_grid, -jnp.inf).max(axis=1)
    else:
        scores = jnp.where(has, by_grid, jnp.inf).min(axis=1)
    return scores, has.any(axis=1)


# ---- additive (count/sum/avg) score state ----------------------------------
#
# Additive rankings cannot reuse the selection state above: a prefix
# slot's cells are NOT a subset of its run's — folding them would
# double-count — and f32 cell adds across rounds drift from the host
# control's f64 part-fold.  So the additive plane (a) folds TAIL slots
# only (the dispatcher passes the tails mask), and (b) keeps each cell
# as an exact (hi, lo) double-float pair (ops/topk.pair_add, the rollup
# plane's compensated discipline): while every add is provably exact
# AND f64-dense, host_f64_fold(same addends, same order) == hi + lo
# bit-exactly, so the ranking the host computes from the downloaded
# pair equals the mesh-off control's.  Any add that is not provably
# exact sets the sticky `lossy` scalar and the query downgrades to the
# full-parts path (reason-counted `additive_topk`) — never silently
# wrong.


def mesh_additive_init(num_groups: int, padded_buckets: int, by: str):
    """Zero-filled additive score state for ranking by `by` (count /
    sum / avg).  Same padded-bucket slack contract as mesh_score_init."""
    shape = (num_groups, padded_buckets)
    # distinct buffers per plane: the update donates the whole state,
    # and donation rejects aliased arguments
    z = lambda: jnp.zeros(shape, dtype=jnp.float32)
    state = {"has": jnp.zeros(shape, dtype=bool),
             "lossy": jnp.zeros((), dtype=bool)}
    if by in ("count", "avg"):
        state["cnt_hi"], state["cnt_lo"] = z(), z()
    if by in ("sum", "avg"):
        state["sum_hi"], state["sum_lo"] = z(), z()
    return state


@deviceprof.jit(static_argnames=("by",), donate_argnums=(0,))
def mesh_additive_update(state: dict, count_grid, sum_grid, tails, lo,
                         *, by: str):
    """Fold one round's (time, groups, width) outputs into the additive
    state — TAIL slots only (`tails` is the (time,) run-tail mask; a
    tail holds its whole run, prefixes would double-count).  Masked
    slots add exact zeros (a canonical-pair no-op) and are excluded
    from the lossy accounting."""
    width = count_grid.shape[2]
    planes = {"count": ("cnt",), "sum": ("sum",),
              "avg": ("cnt", "sum")}[by]
    grids = {"cnt": count_grid, "sum": sum_grid}

    def body(t, st):
        add = tails[t] & (count_grid[t] > 0)
        out = dict(st)
        for name in planes:
            hi = jax.lax.dynamic_slice(
                st[name + "_hi"], (0, lo[t]),
                (st[name + "_hi"].shape[0], width))
            lo_ = jax.lax.dynamic_slice(
                st[name + "_lo"], (0, lo[t]),
                (st[name + "_lo"].shape[0], width))
            h2, l2, exact = pair_add(
                hi, lo_, jnp.where(add, grids[name][t], 0.0))
            out[name + "_hi"] = jax.lax.dynamic_update_slice(
                st[name + "_hi"], h2, (0, lo[t]))
            out[name + "_lo"] = jax.lax.dynamic_update_slice(
                st[name + "_lo"], l2, (0, lo[t]))
            out["lossy"] = out["lossy"] | jnp.any(add & ~exact)
        cur_has = jax.lax.dynamic_slice(
            st["has"], (0, lo[t]), (st["has"].shape[0], width))
        out["has"] = jax.lax.dynamic_update_slice(
            st["has"], cur_has | add, (0, lo[t]))
        return out

    return jax.lax.fori_loop(0, count_grid.shape[0], body, state)


@deviceprof.jit(static_argnames=("by", "largest", "num_buckets"))
def mesh_additive_finalize(state: dict, *, by: str, largest: bool,
                           num_buckets: int):
    """Reduce the additive state to the download payload.

    count/sum: the per-group extreme cell as an exact (hi, lo) pair —
    normalized pairs order lexicographically, so the reduction is two
    masked maxes — O(groups) egress like the selection path.  avg
    needs a division the device cannot do bit-identically to the host,
    so it returns the full (groups, buckets) pair grids for the host's
    f64 sum/count divide — the one honestly O(groups × buckets) score
    egress (documented in docs/parallel.md).  `lossy` rides along."""
    has = state["has"][:, :num_buckets]
    out = {"has_any": has.any(axis=1), "lossy": state["lossy"]}
    if by == "avg":
        for name in ("cnt", "sum"):
            out[name + "_hi"] = state[name + "_hi"][:, :num_buckets]
            out[name + "_lo"] = state[name + "_lo"][:, :num_buckets]
        out["has"] = has
        return out
    name = {"count": "cnt", "sum": "sum"}[by]
    s_hi, s_lo = pair_max_normalized(
        state[name + "_hi"][:, :num_buckets],
        state[name + "_lo"][:, :num_buckets], has, axis=1,
        largest=largest)
    out["score_hi"], out["score_lo"] = s_hi, s_lo
    return out


@deviceprof.jit
def mesh_take_rows(grids: dict, idx):
    """Winner-row gather on device: (time, groups, width) round outputs
    sliced to the k winners' rows BEFORE download — the O(k x buckets
    x aggs) per-chip combine egress."""
    return {k: jnp.take(v, idx, axis=1) for k, v in grids.items()}
