"""Device-native decode: fuse sidecar decode + filter + bucket-aggregate
into ONE device dispatch (ROADMAP item 2).

The cold aggregate scan's measured wall is HOST work: the pipeline's
stall profile shows the device stage starved 137:1 on decode, and the
r6 ladder's 200M cold wall is GIL-bound Python/numpy encode/merge the
pipeline can only *overlap*, never shrink.  The sidecar already stores
columns in a device-shaped layout (int32 dict codes, int32 epoch
offsets, raw float32 — storage/sidecar.py), yet the host still k-way
-merges, windows, uniques and stacks them before the device ever runs.

This module moves that whole chain onto the accelerator.  For an
eligible aggregate plan, an `EncodedSegment`'s encoded buffers upload
RAW (pad + device_put — memcpy-shaped host work) and one jitted
program does the rest.  The device pays per row handed to it (every
scatter and gather runs over the padded capacity), so before the pad
plan_dispatch NARROWS the segment on host to the rows the
conjunction's Eq/In leaves admit (one numpy compare per leaf, in
encoded space, with the constants compile_leaves produced) wherever
that lands it in a smaller capacity bucket: a query for one field of a
ten-field segment uploads a tenth of the rows.  Range leaves take no
part in it, so a plan's capacity depends on which keys a query names
and not on where its window falls (one compiled shape per key
selectivity, not per offset).  For the same reason what a key decides
(plan_segment: the narrowed rows, their layout and route, a
SegmentSlice) is planned apart from what a window decides
(plan_window), and the reader's scan cache keeps the uploaded slice
of a whole segment on the device: a later query with the same key
leaves dispatches from it with nothing read, narrowed or uploaded
(storage/scan_cache.py; scan_decode_resident_total).  The program
itself does:

  leaf filter   — the plan's pushed PK-leaf conjunction evaluated in
                  ENCODED space (constants pre-translated host-side via
                  the same ops.filter helpers the host mask uses): all
                  of it, on whatever rows were uploaded — on a narrowed
                  segment the Eq/In leaves are tautologies, kept so
                  that narrowing adds no compiled program;
  merge-dedup   — lax.sort by (valid, pk codes..., seq, row) and a
                  keep-last-of-PK-run mask: the device twin of the host
                  k-way merge + `_host_dedup_keep`, with dropped rows
                  MASKED (gid = -1), never compacted, so shapes stay
                  static.  The row-index tiebreak reproduces the host
                  merge's stable ordering bit-for-bit, which is what
                  keeps f32 per-cell accumulation order — and therefore
                  the grids' bytes — identical to the host path;
  aggregate     — ops.downsample.window_local_partials over the sorted,
                  masked rows: the partial-grid kernel the host window
                  path vmaps, so the emitted part has the exact
                  conventions storage/combine.py folds.  Where the
                  slice's rows decode with (group, ts) never falling
                  (plan_segment decides it per slice: the served
                  shape, one field grouped by its series) every cell
                  is one run of rows and the kernel reduces by runs
                  with nothing scattered (downsample.run_aggregate;
                  scan_decode_reduce_total{kind} counts both).

The output is one per-segment part `(group_values, bucket_lo, grids)`
— the shape `read._flush_window_batch` produces — so everything
downstream (sparse/dense combine, top-k pushdown, the delta-summation
parts memo) is untouched and the host-decode path remains the control
([scan.decode] mode = "host"; the seeded chaos suite byte-compares the
two, tests/test_device_decode.py): count, min, max and last to the
bit on every slice, sums to the bit where the slice scatters or the
cells' sums are exact in float32 (the suite's integer values), and to
float32 rounding in another association otherwise.

Ineligible plans/segments fall back to host decode with an explicit
per-reason counter (`scan_decode_fallback_total{reason=}`) so a
silently-ineligible plan is visible instead of quietly slow
(docs/observability.md).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from horaedb_tpu.common import deviceprof
from horaedb_tpu.ops import downsample
from horaedb_tpu.ops import filter as filter_ops
from horaedb_tpu.ops import merge as merge_ops
from horaedb_tpu.ops.filter import (
    _const_code_exact,
    _const_code_lower,
    _const_code_upper,
)
from horaedb_tpu.utils import registry, trace_add

# every way a plan or segment can decline the device-decode path, so
# operators can tell "misconfigured dashboard" from "unsupported data"
# (docs/observability.md).
FALLBACK_REASONS = (
    "append_mode",     # BytesMerge needs exact Arrow bytes
    "no_sidecar",      # plan can't serve from sidecars at all
    "predicate",       # predicate not a device-evaluable PK conjunction
    "parquet",         # this segment fell back to a parquet read
    "encoding",        # a column's encoding has no device decode
    "dtype",           # a column's dtype isn't the device layout
    "budget",          # segment exceeds [scan.decode] max_upload_bytes
    "range",           # epoch-to-range shift overflows int32
    "kway_runs",       # multi-run segment declined the k-way merge
                       # (run boundaries unknown / runs not per-run
                       # sorted / too many runs) — the dispatch still
                       # decodes on device but pays the full lax.sort
)

_FALLBACKS = registry.counter(
    "scan_decode_fallback_total",
    "aggregate segments/plans that fell back to host decode, by reason "
    "— a silently-ineligible plan shows up here instead of being "
    "quietly slow")
_FALLBACK_CHILDREN = {r: _FALLBACKS.labels(reason=r)
                      for r in FALLBACK_REASONS}

# compaction-aware sort-free routing (ROADMAP item 2c): per-segment
# routed-vs-sorted evidence for the fused dispatch's O(n log n) device
# sort — the steady-state post-compaction scan should read ~all
# "compacted"
_SORT_SKIPPED = {
    route: registry.counter(
        "scan_decode_sort_skipped_total",
        "fused decode dispatches that skipped the device lax.sort: "
        "compacted = single-run segment, (pk, seq)-sorted by "
        "construction (no host check either); checked = the one-pass "
        "host sortedness check proved the concatenated runs sorted; "
        "kway = multi-run interleaved segment merged on device by the "
        "presorted-run k-way merge (ops/merge.kway_merge_perm) instead "
        "of the full sort"
    ).labels(route=route)
    for route in ("compacted", "checked", "kway")
}
_SORT_RAN = registry.counter(
    "scan_decode_sorted_total",
    "fused decode dispatches that paid the device lax.sort "
    "(multi-run interleaved segments)")


# how often the narrowing engages: rows of every planned dispatch as
# stored in the segment and as handed to the program
_DECODE_ROWS = {
    side: registry.counter(
        "scan_decode_rows_total",
        "rows of every planned fused decode dispatch: stored = the "
        "segment's rows as assembled, uploaded = the rows padded and "
        "uploaded after the host narrowed the segment to what the "
        "plan's Eq/In leaves admit (equal where no such leaf put it "
        "in a smaller capacity bucket; 0 for a dispatch that ran from "
        "a slice resident on the device: nothing crossed)"
    ).labels(side=side)
    for side in ("stored", "uploaded")
}


# how often the run reduction engages: every planned dispatch by the
# reduction its slice takes
_DECODE_REDUCE = {
    kind: registry.counter(
        "scan_decode_reduce_total",
        "planned fused decode dispatches by how their grids are "
        "reduced: runs = the slice's rows arrive with (group, ts) "
        "never falling, so every cell is one contiguous run of rows "
        "and nothing is scattered (ops/downsample.run_aggregate); "
        "scatter = the slice breaks that order (two fields admitted "
        "by one In, a group column behind another varying key) and "
        "pays one scatter update per padded row per grid"
    ).labels(kind=kind)
    for kind in ("runs", "scatter")
}


# the scan cache's resident slices (storage/scan_cache.py, tier hbm):
# per segment of a device-decode plan, whether its narrowed, padded
# upload set was found on the device
_RESIDENT = {
    outcome: registry.counter(
        "scan_decode_resident_total",
        "segments of device-decode plans by what the scan cache held "
        "for them: hit = the narrowed, padded upload set was resident "
        "on the device and the dispatch ran from it (nothing read, "
        "assembled, narrowed or uploaded); miss = probed and not "
        "found, the segment was read and uploaded; bypass = not "
        "probed (mesh rounds group host plans, or the plan opted out "
        "of caching)"
    ).labels(outcome=outcome)
    for outcome in ("hit", "miss", "bypass")
}


# how often the batch engages: every slice dispatched, by whether it
# shared its call
_BATCH_SLICES = {
    mode: registry.counter(
        "scan_decode_batch_slices_total",
        "slices of device-decode plans by how they reached the device: "
        "batched = inside one program call with the plan's other "
        "resident slices (execute_batch; the row-selecting route's "
        "calls, ops/select.py); single = by a call of their "
        "own (execute_plan: a miss, a group of one, a mesh round's "
        "declined plan)"
    ).labels(mode=mode)
    for mode in ("batched", "single")
}
_BATCH_CALLS = registry.counter(
    "scan_decode_batch_total",
    "calls of the batched programs (the fused decode's, two or more "
    "resident slices of one plan a call; the row-selecting route's, "
    "one call with all its fields)")


def note_batched(slices: int, calls: int) -> None:
    """Resident slices that reached the device in batched calls of
    another route's programs (ops/select.py)."""
    _BATCH_SLICES["batched"].inc(slices)
    _BATCH_CALLS.inc(calls)


def note_resident(outcome: str, n: int = 1) -> None:
    if n:
        _RESIDENT[outcome].inc(n)


def note_fallback(reason: str) -> None:
    child = _FALLBACK_CHILDREN.get(reason)
    if child is None:  # unknown reasons still count, labeled verbatim
        child = _FALLBACKS.labels(reason=reason)
        _FALLBACK_CHILDREN[reason] = child
    child.inc()
    trace_add(f"decode_fallback_{reason}", 1)


# ---------------------------------------------------------------------------
# leaf compilation: predicate leaves -> encoded-space ops
# ---------------------------------------------------------------------------

# opcodes are STATIC (they select the compare emitted at trace time);
# constants are traced int32 so varied dashboards share one program
_OP_EQ, _OP_LT, _OP_LE, _OP_GT, _OP_GE, _OP_RANGE, _OP_IN = range(7)
_EDGE_NAMES = {_OP_LT: "lt", _OP_LE: "le", _OP_GT: "gt", _OP_GE: "ge"}

# an In leaf beyond this many resolved codes would trace a (capacity x
# k) compare — fall back to host decode instead of trading HBM for it
_IN_MAX_CODES = 64

# beyond this many presorted runs the k-way merge tree's log2(k) levels
# of binary searches stop beating the full bitonic sort — decline to
# the sort route (counted reason="kway_runs") instead
_KWAY_MAX_RUNS = 64


class _EmptyMatch(Exception):
    """A leaf provably matches nothing (Eq/In constant absent from the
    dictionary): the segment contributes an empty part, no dispatch."""


_I32_LO, _I32_HI = -(2**31), 2**31 - 1


def _exact_i32(c) -> Optional[int]:
    """An equality constant as int32, or None when it cannot match any
    code (out-of-range) — the host mask's numpy compare upcasts and
    yields all-False there; int32-casting unguarded would wrap (old
    numpy) or raise OverflowError (numpy >= 1.24)."""
    c = int(c)
    return c if _I32_LO <= c <= _I32_HI else None


def _thresh_i32(c) -> int:
    """A comparison threshold clamped to int32.  Callers must first
    resolve the out-of-range edges where a clamp would NOT compare
    identically (a raw int32 column may legitimately hold I32_LO or
    I32_HI — see _numeric_edge): after that, clamping is exact."""
    return int(np.clip(int(c), _I32_LO, _I32_HI))


# what an out-of-int32 numeric threshold means for each comparison —
# the host mask compares unclamped via numpy upcast, so a below-range
# `col > c` is a TAUTOLOGY (keep every row, incl. a raw code of
# I32_LO) and an above-range `col >= c` matches NOTHING; a clamp alone
# would wrongly include/exclude codes equal to the int32 extremes.
# Values: "taut" = drop the leaf (no constraint), "empty" = the leaf
# provably matches nothing, None = in range (clamp is exact).
def _numeric_edge(op: int, t: int) -> Optional[str]:
    if t < _I32_LO:
        return {"lt": "empty", "le": "empty",
                "gt": "taut", "ge": "taut"}[_EDGE_NAMES[op]]
    if t > _I32_HI:
        return {"lt": "taut", "le": "taut",
                "gt": "empty", "ge": "empty"}[_EDGE_NAMES[op]]
    return None


def leaf_shape_supported(leaves) -> bool:
    """Plan-level check: every pushed leaf is a type the device program
    can evaluate.  Mirrors parquet_io.conjunct_leaves_ex's leaf list;
    constants translate per segment (they need the encodings)."""
    F = filter_ops
    for leaf in leaves or []:
        if not isinstance(leaf, (F.Eq, F.Lt, F.Le, F.Gt, F.Ge, F.In,
                                 F.TimeRangePred)):
            return False
        if isinstance(leaf, F.In) and len(list(leaf.values)) > _IN_MAX_CODES:
            return False
    return True


def compile_leaves(leaves, encodings) -> tuple[tuple, tuple]:
    """Translate a leaf conjunction into ((column, opcode), ...) static
    program + per-leaf int32 constant arrays, in ENCODED space — the
    exact semantics of ops.filter.eval_predicate's host mask (including
    the dict-code Le/Gt asymmetry), computed with the same helpers.

    Raises _EmptyMatch when a leaf provably matches nothing and
    ValueError when a leaf/encoding combination has no device form
    (caller counts reason="predicate"/"encoding")."""
    F = filter_ops
    prog: list = []
    consts: list = []
    for leaf in leaves or []:
        enc = encodings.get(leaf.column)
        if enc is None:
            raise ValueError(f"leaf column {leaf.column!r} missing")
        if isinstance(leaf, F.Eq):
            c = _const_code_exact(enc, leaf.value)
            c = None if c is None else _exact_i32(c)
            if c is None:
                raise _EmptyMatch
            prog.append((leaf.column, _OP_EQ))
            consts.append(np.asarray([c], dtype=np.int32))
        elif isinstance(leaf, F.In):
            codes = sorted(ci for ci in (
                _exact_i32(c) for c in (_const_code_exact(enc, v)
                                        for v in leaf.values)
                if c is not None) if ci is not None)
            if not codes:
                raise _EmptyMatch
            prog.append((leaf.column, _OP_IN))
            consts.append(np.asarray(codes, dtype=np.int32))
        elif isinstance(leaf, (F.Lt, F.Le, F.Gt, F.Ge)):
            # dict thresholds are searchsorted indices (always in
            # range); numeric/offset map exactly as eval_predicate's
            # host mask, with numeric out-of-int32 edges resolved to
            # tautology / empty-match BEFORE the clamp (a raw int32
            # column may hold the int32 extremes)
            if enc.kind == "dict":
                if isinstance(leaf, F.Lt):
                    op, t = _OP_LT, _const_code_lower(enc, leaf.value)
                elif isinstance(leaf, F.Le):
                    op, t = _OP_LT, _const_code_upper(enc, leaf.value)
                elif isinstance(leaf, F.Gt):
                    op, t = _OP_GE, _const_code_upper(enc, leaf.value)
                else:
                    op, t = _OP_GE, _const_code_lower(enc, leaf.value)
            else:
                if isinstance(leaf, F.Lt):
                    op, t = _OP_LT, _const_code_lower(enc, leaf.value)
                elif isinstance(leaf, F.Le):
                    op, t = _OP_LE, _const_code_upper(enc, leaf.value)
                elif isinstance(leaf, F.Gt):
                    op, t = _OP_GT, _const_code_lower(enc, leaf.value)
                else:
                    op, t = _OP_GE, _const_code_lower(enc, leaf.value)
                if enc.kind == "numeric":
                    edge = _numeric_edge(op, int(t))
                    if edge == "empty":
                        raise _EmptyMatch
                    if edge == "taut":
                        continue  # no constraint: drop the leaf
            prog.append((leaf.column, op))
            consts.append(np.asarray([_thresh_i32(t)], dtype=np.int32))
        elif isinstance(leaf, F.TimeRangePred):
            lo_t = _const_code_lower(enc, leaf.start)
            hi_t = _const_code_lower(enc, leaf.end)
            lo_edge = hi_edge = None
            if enc.kind == "numeric":
                lo_edge = _numeric_edge(_OP_GE, int(lo_t))
                hi_edge = _numeric_edge(_OP_LT, int(hi_t))
            if lo_edge == "empty" or hi_edge == "empty":
                raise _EmptyMatch
            if lo_edge == "taut" and hi_edge == "taut":
                continue
            if lo_edge == "taut":
                prog.append((leaf.column, _OP_LT))
                consts.append(np.asarray([_thresh_i32(hi_t)],
                                         dtype=np.int32))
            elif hi_edge == "taut":
                prog.append((leaf.column, _OP_GE))
                consts.append(np.asarray([_thresh_i32(lo_t)],
                                         dtype=np.int32))
            else:
                prog.append((leaf.column, _OP_RANGE))
                consts.append(np.asarray(
                    [_thresh_i32(lo_t), _thresh_i32(hi_t)],
                    dtype=np.int32))
        else:
            raise ValueError(f"unsupported leaf {type(leaf).__name__}")
    return tuple(prog), tuple(consts)


# ---------------------------------------------------------------------------
# the fused program
# ---------------------------------------------------------------------------


def _leaf_mask(col, op: int, c):
    if op == _OP_EQ:
        return col == c[0]
    if op == _OP_LT:
        return col < c[0]
    if op == _OP_LE:
        return col <= c[0]
    if op == _OP_GT:
        return col > c[0]
    if op == _OP_GE:
        return col >= c[0]
    if op == _OP_RANGE:
        return (col >= c[0]) & (col < c[1])
    # _OP_IN: small resolved-code set, compare-broadcast then any
    return (col[:, None] == c[None, :]).any(axis=1)


def _narrow_to_key_leaves(es, prog: tuple, consts: tuple, pad_capacity):
    """The host half of the leaf filter: the segment cut to the rows
    its Eq/In leaves admit — the compares _leaf_mask emits, on the same
    int32 codes and constants — when that puts it in a smaller
    capacity bucket; the segment itself otherwise (an unselective plan
    pays one numpy compare per such leaf, nothing else); None when no
    row passes.  Leaves are PK-only and masking precedes the dedup on
    the device too, so an equal-PK run passes or fails whole and the
    narrowed dispatch's grids are the un-narrowed one's, byte for
    byte.  Range leaves stay with the device: see the module doc."""
    mask = None
    for (name, op), c in zip(prog, consts):
        if op == _OP_EQ:
            m = es.columns[name] == c[0]
        elif op == _OP_IN:
            m = np.isin(es.columns[name], c)
        else:
            continue
        if mask is None:
            mask = m
        else:
            mask &= m
    if mask is None or not es.n:
        return es
    kept = int(np.count_nonzero(mask))
    if not kept:
        return None
    if pad_capacity(kept) >= pad_capacity(es.n):
        return es
    return es.keep_rows(mask)


def _own_dictionary(enc):
    """`enc` with a dictionary that owns its memory.  A sidecar load
    hands dictionaries out as views of the fetched object's bytes
    (storage/sidecar.py), and a view of a thousand ids keeps the whole
    object alive for as long as anything holds it: a resident slice of
    a 173 MB segment would pin 173 MB of host memory that no account
    sees (ten fields' twelve slices at TSBS scale 1000: 20.8 GB)."""
    d = enc.dictionary
    if d is None or d.base is None:
        return enc
    return dataclasses.replace(enc, dictionary=d.copy())


def _lex_sorted_np(keys: list) -> bool:
    """Host twin of read._is_lex_sorted over unpadded encoded columns:
    one vectorized compare pass decides whether the device program can
    skip its O(n log n) sort entirely — single-SST/post-compaction
    segments (the steady-state cold-scan shape) arrive (pk, seq)-sorted
    already, exactly the check the host k-way merge starts with."""
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


def _cells_sorted(es, route: str, pk_names: list, group_col: str,
                  ts_col: str) -> bool:
    """Whether the rows the program decodes from this (narrowed)
    segment come out with (group code, ts) never falling, so that every
    (group, bucket) cell is one contiguous run of rows and the grids
    can be reduced by runs.  A property of the slice's host columns,
    decided once where the slice is planned.  Rows that arrive in
    order are checked as they lie (one numpy pass, as the route's own
    check); rows the device puts in (pk, seq) order are in (group, ts)
    order when both are PK keys, group ahead of ts, and every other PK
    key ahead of ts holds one value over the slice (a query's Eq on
    the field).  A superset is checked (rows a window's range leaves
    will drop included), so the answer holds for every window."""
    if not es.n:
        return True
    group, ts = es.columns[group_col], es.columns[ts_col]
    if route == "presorted":
        return _lex_sorted_np([group, ts])
    if group_col not in pk_names or ts_col not in pk_names:
        return False
    gi, ti = pk_names.index(group_col), pk_names.index(ts_col)
    return gi < ti and all(
        int(es.columns[nm].min()) == int(es.columns[nm].max())
        for nm in pk_names[:ti] if nm != group_col)


def rows_sorted_kept(cols: tuple, n_valid, leaf_consts: tuple,
                     run_offsets, *, key_slots: tuple, num_pks: int,
                     val_slot: int, leaf_prog: tuple, route: str,
                     num_runs: int):
    """The traced decode→filter→merge→dedup body, shared by the fused
    aggregate (decode_rows_core) and the row-selecting programs
    (ops/select.py).  Returns (valid_s, keys_s, val_s, kept): rows in
    (pk, seq)-sorted order, `valid_s` the rows that are real and pass
    the leaves, `kept` those of them that are the last of their PK run
    (the row a read returns).

    `route` picks how rows reach sorted order:
      presorted — they already are (host-checked / single run);
      kway      — merge the `num_runs` presorted runs bounded by
                  `run_offsets` on device (ops/merge.kway_merge_perm),
                  then stably sink filter-failed rows so the valid
                  prefix is BIT-identical to the sort route's;
      sorted    — the full variadic device sort (the counted fallback).
    """
    cap = cols[0].shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    # stages under jax.named_scope, so a profile's operation metadata
    # tells this program's fusions apart (docs/observability.md)
    with jax.named_scope("filter"):
        valid = iota < jnp.asarray(n_valid, jnp.int32)
        for (slot, op), c in zip(leaf_prog, leaf_consts):
            valid = valid & _leaf_mask(cols[slot], op, c)

    with jax.named_scope("merge_" + route):
        valid_s, keys_s, val_s = _rows_in_order(
            cols, valid, iota, n_valid, run_offsets, key_slots=key_slots,
            num_pks=num_pks, val_slot=val_slot, route=route,
            num_runs=num_runs)
    # keep-last per PK run among surviving rows (_host_dedup_keep):
    # a row survives iff valid and (last row | next row invalid | any
    # pk differs from the next row).  Run boundaries compare the PK
    # keys ONLY — seq orders within a run, it never splits one.
    with jax.named_scope("dedup"):
        differs_next = jnp.zeros(cap - 1, dtype=bool)
        for c in keys_s[:num_pks]:
            differs_next = differs_next | (c[:-1] != c[1:])
        kept = valid_s & jnp.concatenate(
            [differs_next | ~valid_s[1:], jnp.ones(1, dtype=bool)])
    return valid_s, keys_s, val_s, kept


def decode_rows_core(cols: tuple, n_valid, leaf_consts: tuple,
                     run_offsets, *, key_slots: tuple, num_pks: int,
                     group_pos: int, val_slot: int, leaf_prog: tuple,
                     route: str, num_runs: int):
    """rows_sorted_kept as the aggregate takes it, for the single
    -device fused dispatch below and the mesh's per-slot program
    (parallel/scan.mesh_decode_partials).  Returns (keys_s, gid,
    val_s, n_rows): rows in (pk, seq)-sorted order with dropped rows
    masked to gid = -1 — the exact shape window_local_partials expects
    (ts rides in keys_s[ts_pos])."""
    _valid_s, keys_s, val_s, kept = rows_sorted_kept(
        cols, n_valid, leaf_consts, run_offsets, key_slots=key_slots,
        num_pks=num_pks, val_slot=val_slot, leaf_prog=leaf_prog,
        route=route, num_runs=num_runs)
    with jax.named_scope("dedup"):
        gid = jnp.where(kept, keys_s[group_pos], jnp.int32(-1))
        n_rows = jnp.sum(kept.astype(jnp.int32))
    return keys_s, gid, val_s, n_rows


def _rows_in_order(cols: tuple, valid, iota, n_valid, run_offsets, *,
                   key_slots: tuple, num_pks: int, val_slot: int,
                   route: str, num_runs: int):
    """decode_rows_core's route step: (valid, keys, values) with the
    rows in (pk, seq) order."""
    cap = cols[0].shape[0]
    if route == "presorted":
        # rows already arrive (pk, seq)-sorted (host-checked, the
        # single-SST/post-compaction shape): the run-boundary masks
        # below work in place.  Leaf-failed rows cannot split a run —
        # prune leaves are PK-only, so an equal-PK run passes or fails
        # as a whole — and padding rows are trailing.
        valid_s = valid
        keys_s = tuple(cols[i] for i in key_slots)
        val_s = cols[val_slot]
    elif route == "kway":
        # merge the presorted runs by (padding, pk..., seq, row): the
        # padding bit keeps the trailing pad zone (its own run) last
        # without perturbing within-run order, and strict/leq counting
        # inside the merge supplies the row tiebreak.  Filter-failed
        # rows then sink behind the valid prefix via a stable
        # partition, so the prefix — the only thing the grids see —
        # is bit-identical to the sort route's (~valid, keys, row)
        # order.
        pad_bit = (~(iota < jnp.asarray(n_valid, jnp.int32))) \
            .astype(jnp.int32)
        mkeys = (pad_bit,) + tuple(cols[i]
                                   for i in key_slots[:num_pks + 1])
        perm = merge_ops.kway_merge_perm(mkeys, run_offsets,
                                         num_runs=num_runs)
        valid_m = valid[perm]
        vpos = jnp.cumsum(valid_m.astype(jnp.int32))
        n_ok = vpos[-1]
        ipos = jnp.cumsum((~valid_m).astype(jnp.int32))
        pos = jnp.where(valid_m, vpos - 1, n_ok + ipos - 1)
        part = jnp.zeros(cap, dtype=jnp.int32).at[pos].set(perm)
        valid_s = valid[part]
        keys_s = tuple(cols[i][part] for i in key_slots)
        val_s = cols[val_slot][part]
    else:
        # sort by (invalid, pks..., seq, ..., row): invalid rows sink
        # as a block; the row index makes the key total, so equal-
        # (pk, seq) duplicates keep their concatenation order — the
        # host radix merge's stability contract (_plan_merge_perm)
        operands = [(~valid).astype(jnp.int32)] \
            + [cols[i] for i in key_slots] + [iota, cols[val_slot]]
        n_keys = 2 + len(key_slots)
        sorted_ops = merge_ops.lex_sort(tuple(operands), num_keys=n_keys)
        valid_s = sorted_ops[0] == 0
        keys_s = sorted_ops[1:1 + len(key_slots)]
        val_s = sorted_ops[-1]
    return valid_s, keys_s, val_s


def decode_partials(cols: tuple, n_valid, leaf_consts: tuple,
                    run_offsets, shift, lo, total, bucket_ms, *,
                    key_slots: tuple, num_pks: int, group_pos: int,
                    ts_pos: int, val_slot: int, leaf_prog: tuple,
                    route: str, num_runs: int, g_pad: int, width: int,
                    which: tuple, cells_sorted: bool):
    """One slice's traced body, decode to partial grids: the ONE place
    the single-device dispatch below and the mesh's per-slot program
    (parallel/scan.mesh_decode_partials) get their grids, so the two
    stay byte-identical whichever reduction a slice takes.  The slice's
    own group codes are the grid's rows (no remap).  `cells_sorted`
    (static, decided per slice by plan_segment) says the decoded rows'
    (group, bucket) never falls, and the grids are reduced by runs
    (ops/downsample.run_aggregate); a slice without it scatters.
    Returns ({partial grids}, kept_rows)."""
    keys_s, gid, val_s, n_rows = decode_rows_core(
        cols, n_valid, leaf_consts, run_offsets, key_slots=key_slots,
        num_pks=num_pks, group_pos=group_pos, val_slot=val_slot,
        leaf_prog=leaf_prog, route=route, num_runs=num_runs)
    grids = downsample.window_local_partials(
        keys_s[ts_pos], gid, val_s, None, shift, lo, total, bucket_ms,
        num_groups=g_pad, num_buckets=width, which=which,
        cells_sorted=cells_sorted)
    return grids, n_rows


_PROGRAM_STATICS = (
    "key_slots", "num_pks", "group_pos", "ts_pos", "val_slot",
    "leaf_prog", "g_pad", "width", "which", "route",
    "num_runs", "cells_sorted")


@deviceprof.jit(static_argnames=_PROGRAM_STATICS)
def _decode_aggregate_jit(cols: tuple, n_valid, leaf_consts: tuple,
                          shift, lo, total, bucket_ms, run_offsets,
                          **static):
    """THE fused dispatch: encoded columns in, partial grids out —
    decode_partials as one compiled program (`static` is its keyword
    set, every one a static argument).

    `cols` is the tuple of uploaded int32 code columns (pad capacity);
    `key_slots` indexes the sort keys into it — the first `num_pks`
    are the PK code columns, then seq, then any non-PK group/ts column
    (appended AFTER seq so they cannot perturb the dedup order; with
    (pk, seq) effectively unique they only ride along to come back
    sorted).  `group_pos`/`ts_pos` locate the group/ts columns inside
    the sorted key outputs; `val_slot` indexes the f32 value column
    (carried, not a key).  `leaf_prog` is the static (column-slot,
    opcode) program from compile_leaves with `leaf_consts` its traced
    constants.  Row ordering/dedup semantics live in decode_rows_core,
    the reduction in decode_partials (both shared with the mesh round
    program).

    Dropped rows (padding, leaf-filtered, dup-shadowed) are masked to
    gid = -1, never compacted — static shapes, no host round trip.
    Returns ({partial grids}, kept_rows)."""
    return decode_partials(cols, n_valid, leaf_consts, run_offsets,
                           shift, lo, total, bucket_ms, **static)


_LANES = 128  # a tile's width on the device; every capacity's divisor


def stack_slices(cols: tuple, key_consts: tuple, run_offsets: tuple):
    """Several slices' device arrays stacked inside a program, as the
    batched programs take them (one entry a slice, as SegmentSlice
    keeps them on the device): the columns, the key leaves' constants
    and the run bounds."""
    # a column is stacked as [slices, cap / 128, 128]: a slice of the
    # stack is then whole tiles, cut out as it lies; as [slices, cap]
    # eight slices share every tile and each cut reads them all
    stacked = tuple(jnp.stack([x.reshape(-1, _LANES) for x in c])
                    for c in zip(*cols))
    keyed_stacked = tuple(jnp.stack(c) for c in zip(*key_consts))
    return stacked, keyed_stacked, jnp.stack(run_offsets)


def slice_columns(stacked: tuple, i) -> tuple:
    """Slice `i` of stack_slices' columns, each flat again."""
    return tuple(c[i].reshape(-1) for c in stacked)


def slice_consts(leaf_prog: tuple, keyed_stacked: tuple, row, i,
                 at: int) -> tuple:
    """Slice `i`'s leaf constants in leaf order: a key leaf's from the
    slice's own device constants, any other leaf's from `row` (the
    call's host numbers of this slice) from position `at` on."""
    keyed = iter(keyed_stacked)
    consts = []
    for _slot, op in leaf_prog:
        if op in (_OP_EQ, _OP_IN):
            consts.append(next(keyed)[i])
        else:  # compile_leaves: a range is two numbers, an edge one
            width = 2 if op == _OP_RANGE else 1
            consts.append(row[at:at + width])
            at += width
    return tuple(consts)


def window_numbers(leaf_prog: tuple, consts: tuple) -> list:
    """The host side of slice_consts: the constants of the leaves that
    are not key leaves, in leaf order."""
    return [c for (_slot, op), c in zip(leaf_prog, consts)
            if op not in (_OP_EQ, _OP_IN)]


@deviceprof.jit(name="_decode_aggregate_jit",
                static_argnames=_PROGRAM_STATICS)
def _decode_batch_jit(cols: tuple, key_consts: tuple, run_offsets: tuple,
                      nums, **static):
    """The fused dispatch for several resident slices of one plan in
    ONE call: decode_partials, the same traced body, once a slice
    under a loop, so that the body is compiled once whatever the
    number of slices.  Booked under `_decode_aggregate_jit`'s ledger
    name: the route's program, another number of slices a call.

    `cols`, `key_consts` and `run_offsets` hold one entry a slice, as
    SegmentSlice keeps them on the device (stacked here, inside the
    program: the one extra pass over the rows); `nums` is the call's
    one host array, int32 [1 + slices, 3 + window constants]: row 0 is
    (live slices, total buckets, bucket_ms), row 1 + i slice i's
    (rows, shift, first bucket) and the constants of its leaves that
    are not key leaves, in leaf order.  The loop's trip count is the
    traced number of LIVE slices, so the filler that rounds the slices
    up to a power of two (execute_batch) is stacked and never run.
    Returns ({grid: [slices, g_pad, width]}, kept_rows[slices])."""
    stacked, keyed_stacked, offs = stack_slices(cols, key_consts,
                                                run_offsets)
    live, total, bucket_ms = nums[0, 0], nums[0, 1], nums[0, 2]

    def one(i):
        row = nums[1 + i]
        return decode_partials(
            slice_columns(stacked, i), row[0],
            slice_consts(static["leaf_prog"], keyed_stacked, row, i, 3),
            offs[i], row[1], row[2], total, bucket_ms, **static)

    def step(i, acc):
        return jax.tree_util.tree_map(lambda a, o: a.at[i].set(o),
                                      acc, one(i))

    acc = jax.tree_util.tree_map(
        lambda o: jnp.zeros((len(cols),) + o.shape, o.dtype),
        jax.eval_shape(one, jnp.int32(0)))
    return jax.lax.fori_loop(0, live, step, acc)


# ---------------------------------------------------------------------------
# dispatch / finalize wrappers
# ---------------------------------------------------------------------------


@dataclass
class DevicePart:
    """A segment's finished aggregate partial from the device-decode
    path, shaped to coexist with DeviceBatch windows in a segment's
    `windows` list (n_valid/nbytes feed the same pipeline accounting).
    `part` is (group_values, bucket_lo, grids) — exactly what
    `_flush_window_batch` emits — or None when the segment provably
    contributes nothing (an Eq/In constant absent from the
    dictionary).  `resident` is the SegmentSlice the dispatch
    uploaded, where the scan cache may keep it (it held every row of
    its SSTs): the reader's loop admits it and clears the field."""

    part: Optional[tuple]
    n_valid: int   # post-dedup surviving rows (ops-metric parity)
    nbytes: int    # host bytes of the downloaded grids
    resident: Optional["SegmentSlice"] = None


class DecodeDispatch:
    """One segment's in-flight fused dispatch: the jit call has been
    issued (device work runs async); finalize() downloads the grids and
    shapes the part.  Split so the pipeline's decode stage can dispatch
    segment k+1's upload while segment k's kernel still runs."""

    __slots__ = ("outs", "n_rows", "values", "lo", "w_eff", "bucket_ms",
                 "t_dispatch", "upload_bytes", "src_rows", "table",
                 "slice")

    def __init__(self, outs, n_rows, values, lo, w_eff, bucket_ms,
                 t_dispatch, upload_bytes, src_rows, table="",
                 slice=None):
        self.outs = outs
        self.n_rows = n_rows
        self.values = values
        self.lo = lo
        self.w_eff = w_eff
        self.bucket_ms = bucket_ms
        self.t_dispatch = t_dispatch
        self.upload_bytes = upload_bytes
        self.src_rows = src_rows
        self.table = table  # the table scanned: labels the phase spans
        # the slice this dispatch uploaded, if the scan cache may keep it
        self.slice = slice

    def finalize(self) -> DevicePart:
        t0 = time.perf_counter()
        # the sync, then the copy, each charged to its own phase: the
        # wait holds the device's queue and the program's execution
        # (the jit call returned immediately); the full (g_pad, width)
        # grids cross the device boundary in the copy — the d2h charge
        # counts what moved, not what was kept
        host = deviceprof.download(self.outs, fn="_decode_aggregate_jit",
                                   table=self.table)
        part = _finished_part(
            host, self.n_rows, self.values, self.lo, self.w_eff,
            self.bucket_ms, resident=None if self.slice is None
            else self.slice.resident())
        observe_decode_stage(self.t_dispatch
                             + (time.perf_counter() - t0),
                             rows=self.src_rows,
                             nbytes=self.upload_bytes)
        return part


def _finished_part(host: dict, n_rows, values, lo: int, w_eff: int,
                   bucket_ms: int, resident=None) -> DevicePart:
    """One slice's downloaded (g_pad, width) grids as the part the
    host fold takes.  Mirrors _flush_window_batch's emission exactly:
    slice to the real group count and the query-clipped width, then
    re-base window-local last_ts to range_start-relative int64.  The
    slices COPY: a view would pin the full download (a batch's: every
    slice's grids, and a slice's leading rows are contiguous as they
    lie) while nbytes counted only the slice — the PartsMemo
    views-pin-bases defect, not repeated here."""
    g = len(values)
    grids = {k: np.array(v[:g, :w_eff], order="C")
             for k, v in host.items()}
    if "last_ts" in grids:
        lt = grids["last_ts"].astype(np.int64)
        grids["last_ts"] = np.where(
            grids["count"] > 0, lt + lo * bucket_ms, lt)
    return DevicePart(
        part=(values, lo, grids), n_valid=int(n_rows),
        nbytes=sum(int(a.nbytes) for a in grids.values()),
        resident=resident)


class BatchDispatch:
    """Several resident slices' in-flight fused dispatch (execute_batch):
    one program call issued, one download to come.  finalize() gives
    one DevicePart a plan, in the plans' order, each what its own
    DecodeDispatch would have given."""

    __slots__ = ("outs", "n_rows", "plans", "t_dispatch", "table")

    def __init__(self, outs, n_rows, plans, t_dispatch, table=""):
        self.outs = outs
        self.n_rows = n_rows
        self.plans = plans
        self.t_dispatch = t_dispatch
        self.table = table

    def finalize(self) -> list:
        t0 = time.perf_counter()
        host, n_rows = deviceprof.download(
            (self.outs, self.n_rows), fn="_decode_aggregate_jit",
            table=self.table)
        parts = [
            _finished_part({k: v[i] for k, v in host.items()}, n_rows[i],
                           dp.values, dp.lo, dp.w_eff, dp.bucket_ms)
            for i, dp in enumerate(self.plans)]
        # the stage twin, once a batch, with the batch's rows
        observe_decode_stage(self.t_dispatch
                             + (time.perf_counter() - t0),
                             rows=sum(dp.src_rows for dp in self.plans),
                             nbytes=0)
        return parts


# stage attribution twins ride the same labeled families as every other
# plan stage (docs/observability.md)
_STAGE_SECONDS = registry.histogram(
    "scan_stage_seconds", "wall seconds per merge-scan plan stage"
).labels(stage="device_decode")
_STAGE_ROWS = registry.counter(
    "scan_stage_rows_total", "rows entering each plan stage"
).labels(stage="device_decode")
_STAGE_BYTES = registry.counter(
    "scan_stage_bytes_total", "bytes entering each plan stage"
).labels(stage="device_decode")


def observe_decode_stage(seconds: float, rows: int, nbytes: int) -> None:
    _STAGE_SECONDS.observe(seconds)
    trace_add("stage_device_decode_ms", seconds * 1e3)
    if rows:
        _STAGE_ROWS.inc(rows)
        trace_add("stage_device_decode_rows", rows)
    if nbytes:
        _STAGE_BYTES.inc(nbytes)
        trace_add("stage_device_decode_bytes", nbytes)


@dataclass
class SegmentSlice:
    """What a plan's KEY decides about one segment and no window does:
    the segment narrowed to the rows its Eq/In leaves admit, the layout
    of its upload, the route its rows take to sorted order, its group
    dictionary and timestamp encoding — and, once `upload_slice` has
    run, the padded columns and the key leaves' constants as device
    arrays.  The scan cache (storage/scan_cache.py) keeps a slice that
    held every row of its SSTs under (segment, SST ids, columns, key
    leaves' values): a later query with the same key plans its window
    against it (plan_window) and dispatches from the resident arrays,
    with nothing read, assembled, narrowed or uploaded."""

    es: object                # the narrowed host segment; None on a
    #                           slice the scan cache holds (resident())
    src_rows: int             # its rows as stored, before any narrowing
    n: int                    # rows kept = rows uploaded
    cap: int
    admissible: bool          # held every row of every SST of its key
    encodings: dict           # a window's other leaves compile against
    ts_epoch: int
    local_ok: bool            # timestamps are offsets from ts_epoch
    g: int
    g_pad: int
    values: object            # the group dictionary (host array)
    upload_names: list
    key_slots: tuple
    num_pks: int
    group_pos: int
    ts_pos: int
    val_slot: int
    key_prog: tuple           # ((column, opcode), ...): the Eq/In leaves
    key_consts: tuple         # host int32 arrays, one per key leaf
    route: str                # "presorted" | "kway" | "sorted"
    sort_skipped: Optional[str]   # scan_decode_sort_skipped_total's route
    run_offsets: Optional[np.ndarray]
    num_runs: int
    cells_sorted: bool        # decoded rows' (group, ts) never falls:
    #                           the grids reduce by runs, not scatters
    cols_dev: Optional[tuple] = None
    key_consts_dev: tuple = ()
    offs_dev: object = None

    @property
    def nbytes(self) -> int:
        """Device bytes of the padded columns: the HBM admission gate's
        and the scan cache's charge."""
        return self.cap * 4 * len(self.upload_names)

    def resident(self) -> "SegmentSlice":
        """The slice as the scan cache keeps it: the device arrays and
        the layout, not the host columns they were padded from."""
        return dataclasses.replace(self, es=None)


@dataclass
class DecodePlan:
    """One segment's fused dispatch, PLANNED but not yet on the device:
    all gates passed, leaves compiled, routing decided, geometry
    computed — no upload issued.  `seg` is what the plan's key decided
    (read through: `plan.cap`, `plan.es`, `plan.route`, ...), the rest
    what this query's window did.  `execute_plan` runs it standalone on
    the default device; the mesh scheduler instead groups compatible
    plans (same `static_key`) into one sharded per-round program
    (read._run_mesh_decode_round), so decode shards along the time
    axis with the aggregation instead of serializing ahead of it."""

    seg: SegmentSlice
    shift: int
    lo: int
    use_width: int
    w_eff: int
    leaf_prog: tuple          # ((upload slot, opcode), ...), every leaf
    consts: tuple             # host int32 arrays, one per leaf
    which: tuple
    bucket_ms: int
    num_buckets: int

    def __getattr__(self, name: str):
        if name == "seg":  # unset (copy/pickle protocols): no recursion
            raise AttributeError(name)
        return getattr(self.seg, name)

    @property
    def n_valid(self) -> int:
        # windows-list accounting parity (DeviceBatch/DevicePart ride
        # the same lists): source rows, pre-filter/dedup
        return self.seg.src_rows

    def static_key(self) -> tuple:
        """Everything that must match for two plans to share one
        compiled mesh-round program (traced-constant SHAPES included:
        leaf const arrays stack across slots)."""
        return (self.key_slots, self.num_pks, self.group_pos,
                self.ts_pos, self.val_slot, self.leaf_prog,
                tuple(len(c) for c in self.consts), self.route,
                self.num_runs, self.cells_sorted, self.local_ok,
                len(self.upload_names), self.which)

    def statics(self) -> dict:
        """The static arguments of the fused program, single or
        batched (_PROGRAM_STATICS), for this plan."""
        return dict(
            key_slots=self.key_slots, num_pks=self.num_pks,
            group_pos=self.group_pos, ts_pos=self.ts_pos,
            val_slot=self.val_slot, leaf_prog=self.leaf_prog,
            g_pad=self.g_pad, width=self.use_width, which=self.which,
            route=self.route, num_runs=self.num_runs,
            cells_sorted=self.cells_sorted)

    def batch_key(self) -> tuple:
        """Everything that must match for two plans of one query to
        share one call of the batched program (execute_batch): the
        static arguments, the rows' capacity and the grids' shape."""
        return (self.static_key(), self.cap, self.g_pad, self.use_width)


def _is_key_leaf(leaf) -> bool:
    return isinstance(leaf, (filter_ops.Eq, filter_ops.In))


def key_leaves_token(leaves) -> tuple:
    """The leaves' part of a resident slice's cache key, in leaf order:
    an Eq/In leaf in full (its VALUES, not their codes: the probe runs
    before any dictionary is read), any other leaf as its kind and
    column (which decide the upload set and the program; its constants
    are the window's)."""
    return tuple(
        filter_ops.canonical_predicate_key(leaf) if _is_key_leaf(leaf)
        else f"({type(leaf).__name__.lower()} {leaf.column})"
        for leaf in leaves or [])


def plan_segment(es, group_col: str, ts_col: str, value_col: str,
                 pk_names: list, seq_name: str, leaves, max_bytes: int,
                 pad_capacity) -> "SegmentSlice | DevicePart | str":
    """The half of plan_dispatch that a plan's key decides: validate
    one EncodedSegment against the fused program's layout, narrow it to
    what its Eq/In leaves admit (_narrow_to_key_leaves), lay out its
    upload and choose its route, WITHOUT touching the device."""
    encs = es.encodings
    # layout gates, cheapest first; reasons mirror FALLBACK_REASONS
    for name in (group_col, ts_col, value_col, seq_name, *pk_names):
        if name not in es.columns:
            return "encoding"
    ts_enc = encs[ts_col]
    if ts_enc.kind not in ("offset", "numeric"):
        return "encoding"
    g_enc = encs[group_col]
    if g_enc.kind != "dict" or g_enc.dictionary is None \
            or len(g_enc.dictionary) == 0:
        return "encoding"  # codes must BE dense ids over a known space
    if es.columns[value_col].dtype != np.float32:
        return "dtype"
    for name in (ts_col, seq_name, *pk_names):
        if es.columns[name].dtype != np.int32:
            return "dtype"

    try:
        key_prog, key_consts = compile_leaves(
            [leaf for leaf in leaves or [] if _is_key_leaf(leaf)], encs)
    except _EmptyMatch:
        return DevicePart(part=None, n_valid=0, nbytes=0)
    except (ValueError, OverflowError):
        return "predicate"
    # from here on `es` is what uploads: the budget gate, the route
    # and the geometry all follow the narrowed rows
    src_rows = es.n
    admissible = es.whole
    es = _narrow_to_key_leaves(es, key_prog, key_consts, pad_capacity)
    if es is None:
        return DevicePart(part=None, n_valid=0, nbytes=0)
    cap = pad_capacity(es.n)

    # upload slots: pk codes, then seq (the dedup order), then any
    # non-PK group/ts column appended AFTER seq — sort keys past
    # (pk, seq, ..., row) refine an effectively-total order, so they
    # ride along only to come back in sorted row order; the value
    # column and any leaf-only columns complete the upload set
    key_names = list(pk_names)
    key_names.append(seq_name)
    for nm in (group_col, ts_col):
        if nm not in key_names:
            key_names.append(nm)
    slot_of: dict = {}
    upload_names: list = []
    for nm in key_names + [value_col] \
            + [leaf.column for leaf in leaves or []]:
        if nm not in slot_of:
            if nm not in es.columns:
                return "predicate"  # a leaf on a column not read
            slot_of[nm] = len(upload_names)
            upload_names.append(nm)
    # HBM admission over the ACTUAL upload set (non-PK group/ts and
    # leaf-only columns included — undercounting would admit a
    # segment over budget and OOM the device instead of falling back)
    if cap * 4 * len(upload_names) > max_bytes:
        return "budget"

    # compaction-aware sort-free routing: a single-run segment (the
    # post-compaction steady state) is (pk, seq)-sorted by
    # construction — both write paths sort before the SST put and
    # compaction emits merge-sorted — so it routes sort-free without
    # even the one-pass host check; multi-run segments pay the check;
    # interleaved multi-run segments with known per-run boundaries
    # k-way-merge the presorted runs on device (row tiebreak
    # preserved, grids byte-identical); only segments neither route
    # admits pay the device lax.sort, counted reason="kway_runs".
    route = "sorted"
    sort_skipped = None
    run_offsets = None
    num_runs = 0
    key_arrs = [es.columns[nm] for nm in pk_names] \
        + [es.columns[seq_name]]
    if es.source_runs == 1:
        route, sort_skipped = "presorted", "compacted"
    elif _lex_sorted_np(key_arrs):
        route, sort_skipped = "presorted", "checked"
    else:
        rl = getattr(es, "run_lengths", None)
        offs = None
        if rl and 1 < len(rl) <= _KWAY_MAX_RUNS \
                and sum(rl) == es.n:
            offs = np.cumsum(np.asarray((0,) + tuple(rl),
                                        dtype=np.int64))
            if not merge_ops.runs_lex_sorted_np(key_arrs, offs):
                offs = None
        if offs is not None:
            route, sort_skipped = "kway", "kway"
            # runs + the trailing pad zone as its own run, padded to a
            # power of two with empty runs (static merge-tree depth)
            num_runs = 1 << max(1, int(len(rl))).bit_length()
            run_offsets = np.full(num_runs + 1, cap, dtype=np.int32)
            run_offsets[:len(offs)] = offs
            run_offsets[len(rl)] = es.n  # real runs end at n
    g = len(g_enc.dictionary)
    cells_sorted = _cells_sorted(es, route, pk_names, group_col, ts_col)
    # the slice outlives the segment (resident in the scan cache; its
    # group values in every part a dispatch returns), so it owns the
    # dictionaries it keeps
    encodings = {nm: _own_dictionary(encs[nm]) for nm in upload_names}
    return SegmentSlice(
        es=es, src_rows=src_rows, n=es.n, cap=cap, admissible=admissible,
        encodings=encodings,
        ts_epoch=int(ts_enc.epoch), local_ok=ts_enc.kind == "offset",
        g=g, g_pad=max(8, 1 << (g - 1).bit_length()),
        values=encodings[group_col].dictionary, upload_names=upload_names,
        key_slots=tuple(slot_of[nm] for nm in key_names),
        num_pks=len(pk_names),
        # group/ts positions INSIDE the sorted key outputs
        group_pos=key_names.index(group_col),
        ts_pos=key_names.index(ts_col), val_slot=slot_of[value_col],
        key_prog=key_prog, key_consts=key_consts, route=route,
        sort_skipped=sort_skipped, run_offsets=run_offsets,
        num_runs=num_runs, cells_sorted=cells_sorted)


def window_leaves(seg: SegmentSlice,
                  leaves) -> "tuple | DevicePart | str":
    """Every leaf of a window's conjunction against a slice, each in
    its own place (leaf_prog is a static argument of the programs, so
    the order is part of which program runs): the key leaves as the
    slice compiled them, the others compiled now.  Returns (((upload
    slot, opcode), ...), (host int32 constants, ...)), a DevicePart
    where a leaf provably matches nothing, or "predicate"."""
    prog: list = []
    consts: list = []
    keyed = iter(zip(seg.key_prog, seg.key_consts))
    try:
        for leaf in leaves or []:
            if _is_key_leaf(leaf):
                p, c = next(keyed)
                prog.append(p)
                consts.append(c)
            else:
                p, c = compile_leaves([leaf], seg.encodings)
                prog.extend(p)
                consts.extend(c)
    except _EmptyMatch:
        return DevicePart(part=None, n_valid=0, nbytes=0)
    except (ValueError, OverflowError):
        return "predicate"
    return (tuple((seg.upload_names.index(c), op) for c, op in prog),
            tuple(consts))


def plan_window(seg: SegmentSlice, spec, leaves,
                width: int) -> "DecodePlan | DevicePart | str":
    """The half of plan_dispatch that a query's window decides, from
    the slice and the AggregateSpec alone (no host column is touched,
    so it serves a slice the scan cache held as it serves a fresh
    one): the shift to range-relative time, the first bucket, the grid
    width, and the constants of every leaf that is not a key leaf.
    Counts the dispatch (rows, route) once the plan stands."""
    shift = seg.ts_epoch - spec.range_start
    if abs(shift) >= 2**31:
        return "range"
    got = window_leaves(seg, leaves)
    if not isinstance(got, tuple):
        return got
    prog, consts = got
    lo = max(0, shift // spec.bucket_ms) if seg.local_ok else 0
    use_width = width if seg.local_ok else spec.num_buckets
    if seg.sort_skipped is not None:
        _SORT_SKIPPED[seg.sort_skipped].inc()
    else:
        note_fallback("kway_runs")
        _SORT_RAN.inc()
    _DECODE_REDUCE["runs" if seg.cells_sorted else "scatter"].inc()
    _DECODE_ROWS["stored"].inc(seg.src_rows)
    if seg.cols_dev is None:
        _DECODE_ROWS["uploaded"].inc(seg.n)
    return DecodePlan(
        seg=seg, shift=shift, lo=lo, use_width=use_width,
        w_eff=min(use_width, spec.num_buckets - lo),
        leaf_prog=prog, consts=consts, which=spec.which,
        bucket_ms=spec.bucket_ms, num_buckets=spec.num_buckets)


def plan_dispatch(es, spec, pk_names: list, seq_name: str,
                  leaves, max_bytes: int, width: int,
                  pad_capacity) -> "DecodePlan | DevicePart | str":
    """Plan one EncodedSegment's fused dispatch WITHOUT touching the
    device: what its key decides (plan_segment), then what the query's
    window does (plan_window).  Returns a DecodePlan (ready to execute
    or to join a mesh round), a DevicePart (provably-empty segment, no
    dispatch), or a fallback reason string (the caller counts it and
    takes the host path)."""
    seg = plan_segment(es, spec.group_col, spec.ts_col, spec.value_col,
                       pk_names, seq_name, leaves, max_bytes,
                       pad_capacity)
    if not isinstance(seg, SegmentSlice):
        return seg
    return plan_window(seg, spec, leaves, width)


def upload_slice(seg: SegmentSlice) -> int:
    """Pad a slice's columns to its capacity and put them, its key
    leaves' constants and its run bounds on the default device; the
    bytes that crossed."""
    es = seg.es
    upload_bytes = 0
    cols_dev = []
    for nm in seg.upload_names:
        arr = es.columns[nm]
        padded = np.zeros(seg.cap, dtype=arr.dtype)  # calloc: tail free
        padded[:es.n] = arr
        upload_bytes += int(padded.nbytes)
        cols_dev.append(deviceprof.device_put(padded))
    seg.key_consts_dev = tuple(jnp.asarray(c) for c in seg.key_consts)
    seg.offs_dev = jnp.int32(0) if seg.run_offsets is None \
        else jnp.asarray(seg.run_offsets)
    seg.cols_dev = tuple(cols_dev)
    return upload_bytes


def execute_plan(dp: DecodePlan, table: str = "") -> DecodeDispatch:
    """Issue one planned segment's fused dispatch on the default
    device, uploading its slice first unless that is resident — the
    single-device tail of the old prepare path and the per-item
    fallback when a mesh round declines a plan.  `table` labels the
    phase spans of the dispatch's finalize (the caller wraps this call
    in its `scan.dispatch` phase).  One call site issues the program
    for a fresh slice and for a resident one, so both pass it
    arguments of the same kinds and a hit compiles nothing."""
    seg = dp.seg
    t0 = time.perf_counter()
    fresh = seg.cols_dev is None
    upload_bytes = upload_slice(seg) if fresh else 0
    keyed = iter(seg.key_consts_dev)
    consts_dev = tuple(
        next(keyed) if op in (_OP_EQ, _OP_IN) else jnp.asarray(c)
        for (_slot, op), c in zip(dp.leaf_prog, dp.consts))

    _BATCH_SLICES["single"].inc()
    outs, n_rows = _decode_aggregate_jit(
        seg.cols_dev, seg.n, consts_dev,
        np.int32(dp.shift), np.int32(dp.lo),
        np.int32(dp.num_buckets), np.int32(dp.bucket_ms), seg.offs_dev,
        **dp.statics())
    return DecodeDispatch(outs=outs, n_rows=n_rows,
                          values=seg.values, lo=dp.lo, w_eff=dp.w_eff,
                          bucket_ms=dp.bucket_ms,
                          t_dispatch=time.perf_counter() - t0,
                          upload_bytes=upload_bytes,
                          src_rows=seg.src_rows, table=table,
                          slice=seg if fresh and seg.admissible
                          else None)


# the stacked columns of a batched call are a temporary that no cache
# account is charged for: a call stacks at most this many bytes
_BATCH_MAX_STACK_BYTES = 512 << 20

# the batched programs some call has compiled, as the numbers of
# slices each batch_key() has one for, and the lock a program's first
# call takes: clients that reach a new program together (a server's
# first queries) compile it once, not once each
_BATCH_COMPILED: dict = {}
_BATCH_COMPILE_LOCK = threading.Lock()


def _compiled_slots(dp: "DecodePlan", n: int) -> Optional[int]:
    """The least number of slices, `n` or more and within the stack's
    budget, that a compiled batched program of dp's batch_key() takes;
    None where no call has compiled one."""
    return min((slots for slots in _BATCH_COMPILED.get(dp.batch_key(), ())
                if n <= slots
                and slots * dp.seg.nbytes <= _BATCH_MAX_STACK_BYTES),
               default=None)


def execute_batch(plans: list, table: str = "") -> BatchDispatch:
    """Issue ONE call of the batched program for two or more plans of
    equal batch_key() whose slices are resident: the slices' device
    arrays as they lie, and one host array of the window's numbers.
    The number of slices is part of the program's shape, so it is
    rounded up to a power of two as rows are to their capacity (six
    and seven segments share a program); the filler repeats the first
    slice and is never run.  A batch that a compiled program already
    holds takes that one, its further slots filler too, before it
    mints a smaller one of its own: a query that the parts memo served
    some segments of brings fewer slices than its neighbours, and a
    compile (45 s at 1,048,576 rows) costs more than the filler's
    stack ever will."""
    t0 = time.perf_counter()
    first = plans[0]
    slots = _compiled_slots(first, len(plans))
    if slots is not None:
        outs, n_rows = _call_batch(plans, slots)
    else:
        with _BATCH_COMPILE_LOCK:
            # whoever held the lock may have compiled one that fits
            slots = (_compiled_slots(first, len(plans))
                     or 1 << (len(plans) - 1).bit_length())
            outs, n_rows = _call_batch(plans, slots)
            key = first.batch_key()
            _BATCH_COMPILED[key] = tuple(
                {*_BATCH_COMPILED.get(key, ()), slots})
    _BATCH_CALLS.inc()
    _BATCH_SLICES["batched"].inc(len(plans))
    return BatchDispatch(outs=outs, n_rows=n_rows, plans=plans,
                         t_dispatch=time.perf_counter() - t0, table=table)


def _call_batch(plans: list, slots: int):
    """One call of the `slots`-slice batched program over `plans`."""
    first = plans[0]
    rows = [np.concatenate(
        [np.asarray([dp.n, dp.shift, dp.lo], dtype=np.int32),
         *window_numbers(dp.leaf_prog, dp.consts)]) for dp in plans]
    nums = np.zeros((1 + slots, len(rows[0])), dtype=np.int32)
    nums[0, :3] = len(plans), first.num_buckets, first.bucket_ms
    nums[1:1 + len(rows)] = rows
    segs = [dp.seg for dp in plans] + [first.seg] * (slots - len(plans))
    return _decode_batch_jit(
        tuple(seg.cols_dev for seg in segs),
        tuple(seg.key_consts_dev for seg in segs),
        tuple(seg.offs_dev for seg in segs), nums, **first.statics())


def dispatch_resident(plans: list, table: str = "") -> list:
    """Issue every plan of a query whose slice is resident: those that
    may share a program (equal batch_key()) as one batched call a
    group, cut where a call's stacked columns would pass
    _BATCH_MAX_STACK_BYTES, and what is left over (a group of one) by
    execute_plan.  Returns [(positions in `plans`, dispatch)], all in
    flight, for finalize_resident."""
    groups: dict = {}
    for pos, dp in enumerate(plans):
        groups.setdefault(dp.batch_key(), []).append(pos)
    issued = []
    for group in groups.values():
        room = _BATCH_MAX_STACK_BYTES // plans[group[0]].seg.nbytes
        per_call = 1 << (room.bit_length() - 1) if room >= 2 else 1
        for at in range(0, len(group), per_call):
            call = group[at:at + per_call]
            if len(call) > 1:
                issued.append((call, execute_batch(
                    [plans[p] for p in call], table)))
            else:
                issued.append((call, execute_plan(plans[call[0]], table)))
    return issued


def finalize_resident(issued: list) -> list:
    """Download and shape what dispatch_resident issued: one DevicePart
    a plan, in the plans' order."""
    parts: dict = {}
    for positions, dispatch in issued:
        if isinstance(dispatch, BatchDispatch):
            parts.update(zip(positions, dispatch.finalize()))
        else:
            parts[positions[0]] = dispatch.finalize()
    return [parts[pos] for pos in range(len(parts))]
