"""Device merge-dedup: the reference and the device twin of the scan's
host merge — not a mode the scan selects.

The served scan merges on the host (storage/read.py
`_host_merge_window_descs`: a k-way permutation plan over pre-sorted SST
runs, last row kept per PK run); the fused decode program sorts through
`lex_sort` / `kway_merge_perm` here.  `merge_dedup_last` is the plain
statement of the semantics that tests hold the host merge and
`dedup_sorted_last` (its sort-free device twin) to — the reference's
SortPreservingMergeExec + MergeExec `primary_key_eq` loop
(ref: src/storage/src/read.rs:154-156, 262-343) as one compiled program
over the concatenation of all SST batches in a segment:

  1. lexicographic sort by (pk..., seq)      — XLA variadic sort
  2. run-boundary mask (neighbor compare)    — vectorized, replaces the
                                               O(rows × pks) scalar loop
  3. segmented last-select per run           — LastValueOperator semantics
                                               (ref: operator.rs:37-44):
                                               equal PKs keep the row with
                                               the highest sequence

Everything is static-shape: inputs are padded to capacity with a validity
count; padding sorts to the end via an int32 sentinel.  Outputs are padded
too (first `num_runs` rows valid), so downstream ops stay compiled.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from horaedb_tpu.common import deviceprof

_PAD_SENTINEL = jnp.int32(2**31 - 1)


def lex_sort(operands: tuple, num_keys: int,
             is_stable: bool = False) -> tuple:
    """THE `jax.lax.sort` seam: every variadic lexicographic device sort
    in the engine goes through here (tools/lint.py errors on `lax.sort`
    call sites outside this module), so the sort-vs-merge choice lives
    in one place and A/B instrumentation wraps one function."""
    return jax.lax.sort(tuple(operands), num_keys=num_keys,
                        is_stable=is_stable)


def _lex_less(ks: tuple, idx: jax.Array, xs: tuple):
    """Vectorized lexicographic compare of ks[:, idx] against xs[:, j]
    per slot j.  Returns (lt, eq) boolean arrays."""
    lt = jnp.zeros(idx.shape, dtype=bool)
    eq = jnp.ones(idx.shape, dtype=bool)
    for kcol, xcol in zip(ks, xs):
        probe = kcol[idx]
        lt = lt | (eq & (probe < xcol))
        eq = eq & (probe == xcol)
    return lt, eq


@deviceprof.jit(static_argnames=("num_runs",))
def _kway_merge_perm_impl(keys: tuple, offsets: jax.Array, num_runs: int):
    cap = keys[0].shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    # run of each original row; padded runs are empty so searchsorted
    # lands rows on the LAST run starting at-or-before them
    run_of = jnp.clip(
        jnp.searchsorted(offsets, iota, side="right").astype(jnp.int32) - 1,
        0, num_runs - 1)
    # within-run order IS sorted order (the caller's contract), and runs
    # are contiguous ascending — so the identity permutation is the
    # level-0 "sorted within every block" state
    perm = iota
    n_steps = max(1, cap - 1).bit_length() + 1
    level = 1
    while level < num_runs:
        ks = tuple(k[perm] for k in keys)  # keys in block-sorted order
        # elements never leave their block's row range, so the block of
        # slot j is the block of its element's original run
        blk = run_of[perm] // level
        p = blk >> 1
        base = 2 * level * p
        start = offsets[base]
        mid = offsets[base + level]
        end = offsets[base + 2 * level]
        in_a = iota < mid
        # merged rank of slot j's element within its pair block:
        #   A-side: own offset + |{b in B : key(b) <  key(j)}|
        #   B-side: own offset + |{a in A : key(a) <= key(j)}|
        # (runs are contiguous, so every B row index exceeds every A row
        # index — strict/leq encodes the original-row tiebreak exactly)
        lo = jnp.where(in_a, mid, start)
        hi = jnp.where(in_a, end, mid)
        for _ in range(n_steps):
            active = lo < hi
            probe = jnp.clip((lo + hi) // 2, 0, cap - 1)
            p_lt, p_eq = _lex_less(ks, probe, ks)
            go = active & jnp.where(in_a, p_lt, p_lt | p_eq)
            lo = jnp.where(go, probe + 1, lo)
            hi = jnp.where(go | ~active, hi, probe)
        new_slot = jnp.where(in_a,
                             iota + (lo - mid),
                             (iota - mid) + lo)
        perm = jnp.zeros(cap, dtype=jnp.int32).at[new_slot].set(perm)
        level *= 2
    return perm


def kway_merge_perm(keys: tuple, offsets, *, num_runs: int) -> jax.Array:
    """Permutation that stably merges `num_runs` presorted runs — the
    k-way replacement for the full variadic device sort when the store
    already delivers (pk, seq)-sorted per-SST runs.

    Args:
      keys: int32 arrays (capacity,), compare-priority order.  Rows of
        run r (indices [offsets[r], offsets[r+1])) must already be
        sorted lexicographically by `keys`, equal keys in row order.
      offsets: int32 (num_runs + 1,), non-decreasing, offsets[0] == 0,
        offsets[-1] == capacity.  Empty runs allowed — pad the run
        count to a power of two with empty runs to keep it static.
      num_runs: static run count (power of two).

    Returns perm (capacity,) int32 such that gathering rows by `perm`
    yields the stable sort by (keys..., original row index): a
    log2(num_runs)-level pairwise merge tree where each level ranks
    elements by their in-block position plus a lexicographic binary
    search over the partner block — O(n · log n · log k) compares
    instead of the sort's O(n · log² n) full key shuffles.
    """
    return _kway_merge_perm_impl(
        tuple(keys), jnp.asarray(offsets, dtype=jnp.int32),
        num_runs=num_runs)


def runs_lex_sorted_np(key_cols: list, offsets) -> bool:
    """Host-side admission check for `kway_merge_perm`: every run is
    individually lex-sorted by `key_cols` (numpy arrays).  O(n) per key
    column — the per-run twin of the whole-segment sortedness probe."""
    import numpy as np

    for a, b in zip(offsets[:-1], offsets[1:]):
        if b - a <= 1:
            continue
        later = np.zeros(b - a - 1, dtype=bool)
        for col in key_cols:
            seg = np.asarray(col[a:b])
            cur, nxt = seg[:-1], seg[1:]
            if ((cur > nxt) & ~later).any():
                return False
            later = later | (cur < nxt)
    return True


def sorted_run_starts(pk_cols: tuple, valid: jax.Array) -> jax.Array:
    """Boolean mask of primary-key run starts over sorted columns.

    This is the vectorized replacement for `primary_key_eq`
    (ref: read.rs:262-287): rows i and i-1 are in the same run iff all PK
    columns are equal.  Padding rows never start a run.
    """
    neq = jnp.zeros(valid.shape, dtype=bool)
    for col in pk_cols:
        neq = neq | (col != jnp.roll(col, 1))
    first = jnp.zeros_like(neq).at[0].set(True)
    return (first | neq) & valid


@deviceprof.jit(static_argnames=("num_pks", "num_keys"))
def _merge_dedup_impl(cols: tuple, n_valid: jax.Array, num_pks: int, num_keys: int):
    capacity = cols[0].shape[0]
    iota = jnp.arange(capacity, dtype=jnp.int32)
    valid = iota < n_valid

    # Sort ONLY the key columns plus a row-index permutation; value columns
    # are fetched afterwards with a single fused gather.  With V value
    # columns this moves V arrays out of the O(n log n) sort and into an
    # O(n) gather.  Padding must sort last: pad keys become the int32 max
    # sentinel.  num_keys may be num_pks (seq known row-ordered: the
    # stable sort then keeps original order within a run, so last row per
    # run == highest seq without paying for seq as a sort operand).
    keys = tuple(jnp.where(valid, c, _PAD_SENTINEL) for c in cols[:num_keys])
    sorted_all = jax.lax.sort(keys + (iota,), num_keys=num_keys, is_stable=True)
    sorted_keys, perm = sorted_all[:-1], sorted_all[-1]
    sorted_valid = perm < n_valid

    run_starts = sorted_run_starts(sorted_keys[:num_pks], sorted_valid)
    run_ids = jnp.cumsum(run_starts.astype(jnp.int32)) - 1
    num_runs = jnp.sum(run_starts.astype(jnp.int32))

    # Last row of each run == highest seq for that PK (seq is the final
    # sort key).  segment_max over masked row indices finds it.
    masked_iota = jnp.where(sorted_valid, iota, jnp.int32(-1))
    safe_run_ids = jnp.where(sorted_valid, run_ids, capacity - 1)
    last_idx = jax.ops.segment_max(masked_iota, safe_run_ids, num_segments=capacity)
    gather_idx = jnp.clip(last_idx, 0, capacity - 1)

    # compose the two gathers: original row of the winning sorted position
    src_rows = perm[gather_idx]
    out_cols = tuple(c[src_rows] for c in cols)
    out_valid = iota < num_runs
    return out_cols, out_valid, num_runs


@deviceprof.jit(static_argnames=("num_pks", "has_perm"))
def _dedup_presorted_impl(cols: tuple, perm, n_valid: jax.Array,
                          num_pks: int, has_perm: bool):
    capacity = cols[0].shape[0]
    iota = jnp.arange(capacity, dtype=jnp.int32)
    if has_perm:
        # one fused gather applies the host-computed merge permutation;
        # padding rows map to themselves (perm[n:] is identity)
        cols = tuple(c[perm] for c in cols)
    valid = iota < n_valid
    run_starts = sorted_run_starts(cols[:num_pks], valid)
    run_ids = jnp.cumsum(run_starts.astype(jnp.int32)) - 1
    num_runs = jnp.sum(run_starts.astype(jnp.int32))
    # within a run, the LAST row wins (rows arrive in seq-preference
    # order); segment_max over masked row indices finds it
    masked_iota = jnp.where(valid, iota, jnp.int32(-1))
    safe_run_ids = jnp.where(valid, run_ids, capacity - 1)
    last_idx = jax.ops.segment_max(masked_iota, safe_run_ids,
                                   num_segments=capacity)
    gather_idx = jnp.clip(last_idx, 0, capacity - 1)
    out_cols = tuple(c[gather_idx] for c in cols)
    out_valid = iota < num_runs
    return out_cols, out_valid, num_runs


def dedup_sorted_last(pk_cols: tuple, seq: jax.Array, value_cols: tuple,
                      n_valid, perm=None
                      ) -> tuple[tuple, jax.Array, tuple, jax.Array, jax.Array]:
    """Dedup WITHOUT a device sort: the k-way-merge replacement for
    `merge_dedup_last` when the caller already knows the row order.

    The reference merges already-sorted per-SST streams
    (SortPreservingMergeExec, ref: src/storage/src/read.rs:455-480)
    instead of re-sorting; our equivalent exploits that SSTs are written
    PK-sorted (storage.py write path): the host either verifies the
    concatenation is globally sorted (single-SST segments — the
    post-compaction steady state — and time-partitioned writes) or
    computes a merge permutation with an O(n) radix argsort over packed
    int64 keys, while the device only pays one fused gather plus the
    run-mask/segmented-last-select — the O(n log n) variadic
    `lax.sort` drops out of the scan entirely.

    Contract: after applying `perm` (or as given when `perm is None`),
    rows must be sorted by `pk_cols` lexicographically, with rows of
    equal PK ordered so the preferred (highest-seq) row comes LAST.

    Returns the same tuple shape as merge_dedup_last.
    """
    cols = tuple(pk_cols) + (seq,) + tuple(value_cols)
    has_perm = perm is not None
    if not has_perm:
        # jit requires consistent pytree arity; a scalar stands in
        perm = jnp.int32(0)
    out_cols, out_valid, num_runs = _dedup_presorted_impl(
        cols, perm, jnp.asarray(n_valid, dtype=jnp.int32),
        num_pks=len(pk_cols), has_perm=has_perm)
    out_pks = out_cols[: len(pk_cols)]
    out_seq = out_cols[len(pk_cols)]
    out_values = out_cols[len(pk_cols) + 1:]
    return out_pks, out_seq, out_values, out_valid, num_runs


def merge_dedup_last(pk_cols: tuple, seq: jax.Array, value_cols: tuple,
                     n_valid, seq_in_row_order: bool = False
                     ) -> tuple[tuple, tuple, jax.Array, jax.Array]:
    """Sort + dedup, keeping the last-by-sequence row per primary key.

    Args:
      pk_cols: int32 arrays (capacity,) — PK columns in schema order.
      seq: int32 array — per-row sequence rank (order-preserving).
      value_cols: arrays (capacity,) — carried value columns (any dtype).
      n_valid: scalar — number of real rows.
      seq_in_row_order: set True ONLY when seq is non-decreasing with
        row index (e.g. rows are concatenated SSTs sorted by file id and
        seq is the file id).  The stable PK sort then already places the
        highest-seq row last within each run, so seq is carried as a
        value column instead of paying for it as a sort operand.

    Returns (out_pk_cols, out_seq, out_value_cols, out_valid_mask, num_runs);
    outputs are sorted by PK ascending, padded to capacity.  out_seq carries
    each surviving row's original sequence — compaction rewrites depend on
    it for later cross-file dedup.
    """
    cols = tuple(pk_cols) + (seq,) + tuple(value_cols)
    out_cols, out_valid, num_runs = _merge_dedup_impl(
        cols, jnp.asarray(n_valid, dtype=jnp.int32),
        num_pks=len(pk_cols),
        num_keys=len(pk_cols) + (0 if seq_in_row_order else 1))
    out_pks = out_cols[: len(pk_cols)]
    out_seq = out_cols[len(pk_cols)]
    out_values = out_cols[len(pk_cols) + 1:]
    return out_pks, out_seq, out_values, out_valid, num_runs
