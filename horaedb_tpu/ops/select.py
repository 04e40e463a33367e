"""Row selection on the device: the first programs whose output is ROWS.

A value predicate over one field (`usage_user > 90`) answered as whole
rows (that field's matching readings and every other field asked at
the same series and timestamp) is, in this data model (one stored row
a sample a FIELD), a scan of the predicate's field and a join of the
others on (series, timestamp).  Both run here over the slices the
aggregate route keeps resident (ops/device_decode.SegmentSlice: one
field of one segment, narrowed, padded, on the device), with nothing
of its own to keep there:

  select  — the predicate field's slice through the aggregate
            program's own decode, filter and dedup
            (device_decode.rows_sorted_kept: the window's PK leaves,
            then keep-last of every PK run), and ONLY THEN the value
            predicate, on the float32 the slice stores: a newer write
            under the threshold shadows an older one over it.  The
            surviving rows' (series code, timestamp offset, value) are
            compacted, in row order, into a static capacity taken from
            a short ladder (the slice's capacity / 8, / 2, whole: a
            slice is filled to half its capacity or more, so the first
            rung holds a selectivity of up to a quarter, and TSBS's
            tenth never sits on a rung's edge).
            The program also returns how many rows it selected: more
            than the capacity is an overflow, and the caller runs the
            segment group again one rung up (counted; the top rung
            holds every row, so nothing is ever cut).
  join    — another field's slice of the same segment gives its value
            at the selected keys: the slice decodes with (series code,
            timestamp) never falling (SegmentSlice.cells_sorted, which
            plan_segment decides: a slice without it takes the host
            route), so a key is found by a binary search among its
            series' rows, and the LAST row of the key is the one a
            read returns if the dedup kept it.  A key the slice lacks
            comes back with its found flag down (a null in the
            answer): no field is assumed to hold the rows another
            holds.  Before it searches, a slice is held against the
            predicate's own, row for row in decoded order: where the
            two keep the same rows and every kept row is the same
            series at the same timestamp (both dictionaries the same;
            checked on the device, elementwise, in every call), the
            selected rows' values are TAKEN at the selected rows'
            positions, one gather a key over the capacity; where they
            do not, every key is searched for.  A gather costs the
            chip by the element, whatever its table, and a search is
            sixteen of them a key over as many chunks of keys as a
            slice selected: the take is what keeps a query's device
            time from following the rows its data happen to select.
            Series codes are a slice's own dictionary's, so the keys'
            codes go through a remap built on the host from the two
            dictionaries.

A query's resident slices reach the device in ONE call a group of
segments whose windows share their program (_select_rows_joined_jit:
the select, then every field's join at the keys it left, which never
leave the program; the batching of device_decode.execute_batch: the
slices' device arrays as they lie, one small host array of the
window's numbers a stack, a loop over the live slices), and everything
a query selected comes back in ONE download.  The joined fields whose
windows share their statics lie in one stack under one traced join
body, so the program does not grow with the fields asked; how many a
stack holds is part of the program's shape, and a request of fewer
runs a program that exists with its stack filled.  The work inside a
call follows the rows selected, not the capacity: the join searches
chunk by chunk up to the slice's count.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from horaedb_tpu.common import deviceprof
from horaedb_tpu.ops import device_decode
from horaedb_tpu.ops.device_decode import (DevicePart, SegmentSlice,
                                           rows_sorted_kept, slice_columns,
                                           slice_consts, stack_slices,
                                           window_leaves, window_numbers)
from horaedb_tpu.utils import registry

OPS = ("gt", "ge", "lt", "le")

_OVERFLOWS = registry.counter(
    "scan_select_overflow_total",
    "batched select calls that selected more rows in some slice than "
    "the capacity they ran with, and ran again one rung up the ladder "
    "(nothing is ever cut: the top rung is the slice's own capacity)")
_CALLS = {
    kind: registry.counter(
        "scan_select_calls_total",
        "the row-selecting program (one call a group of segments: the "
        "predicate field's select and every other field's join at the "
        "selected keys inside it): select = calls of the program, "
        "join = fields joined inside them (join / select = fields a "
        "call)"
    ).labels(kind=kind)
    for kind in ("select", "join")
}

# the join searches this many keys at a time, for as many chunks as a
# slice selected rows: a capacity's unused tail costs nothing
_JOIN_CHUNK = 4096

# the rung a group of slices last needed, by its programs' key: the
# next query of the same shape starts there, so an overflow is paid
# once (in a server's warm-up) and not once a query
_RUNG: dict = {}

# the programs some call has compiled, and the lock a first call takes
# (clients that reach a new program together compile it once)
_COMPILED: set = set()
_COMPILE_LOCK = threading.Lock()

# the fields a set of joined fields the program has run with, by the
# rest of its shape: what a request of fewer fields may be filled to
_WIDTHS: dict = {}


@dataclass(frozen=True)
class SelectSpec:
    """`value_col` `op` `threshold` over rows grouped by `group_col`
    at `ts_col`; the threshold is compared as float32."""

    group_col: str
    ts_col: str
    value_col: str
    op: str
    threshold: float

    def __post_init__(self):
        if self.op not in OPS:
            raise ValueError(f"unknown op {self.op!r}; one of {OPS}")
        object.__setattr__(self, "threshold",
                           float(np.float32(self.threshold)))


def capacity_ladder(cap: int) -> tuple:
    """The capacities a slice of `cap` rows may be selected into."""
    return tuple(sorted({max(1, cap // 8), max(1, cap // 2), cap}))


@dataclass
class SelectWindow:
    """One resident slice under one query's window: the slice and the
    constants of the window's leaves."""

    seg: SegmentSlice
    leaf_prog: tuple
    consts: tuple

    def batch_key(self) -> tuple:
        """Everything that must match for two windows to share a call
        (the programs' static arguments and the arrays' shapes)."""
        s = self.seg
        return (s.key_slots, s.num_pks, s.group_pos, s.ts_pos, s.val_slot,
                self.leaf_prog, tuple(len(c) for c in self.consts),
                s.route, s.num_runs, len(s.upload_names), s.cap, s.g_pad)

    def statics(self) -> dict:
        s = self.seg
        return dict(key_slots=s.key_slots, num_pks=s.num_pks,
                    group_pos=s.group_pos, ts_pos=s.ts_pos,
                    val_slot=s.val_slot, leaf_prog=self.leaf_prog,
                    route=s.route, num_runs=s.num_runs)


def plan_window(seg: SegmentSlice,
                leaves) -> "SelectWindow | None | str":
    """A slice under a window's leaves: a SelectWindow, None where a
    leaf provably matches nothing, or the reason the slice cannot be
    selected from on the device."""
    if not seg.cells_sorted:
        return "unsorted"
    got = window_leaves(seg, leaves)
    if isinstance(got, DevicePart):
        return None
    if isinstance(got, str):
        return got
    return SelectWindow(seg, *got)


# ---------------------------------------------------------------------------
# the programs
# ---------------------------------------------------------------------------

_DECODE_STATICS = ("key_slots", "num_pks", "group_pos", "ts_pos",
                   "val_slot", "leaf_prog", "route", "num_runs")


def compare(vals, op: str, threshold):
    """`vals` `op` `threshold`, elementwise (traced or numpy)."""
    if op == "gt":
        return vals > threshold
    if op == "ge":
        return vals >= threshold
    if op == "lt":
        return vals < threshold
    return vals <= threshold


def _per_slice(one, live, slots: int):
    """`one(i)` for every live slice i, its results stacked over
    `slots` (the filler's stay zero), under a loop so that the body is
    compiled once whatever the number of slices."""
    def step(i, acc):
        return jax.tree_util.tree_map(lambda a, o: a.at[i].set(o),
                                      acc, one(i))

    acc = jax.tree_util.tree_map(
        lambda o: jnp.zeros((slots,) + o.shape, o.dtype),
        jax.eval_shape(one, jnp.int32(0)))
    return jax.lax.fori_loop(0, live, step, acc)


def _select_rows(cols: tuple, key_consts: tuple, run_offsets: tuple,
                 nums, threshold, *, op: str, capacity: int,
                 group_pos: int, ts_pos: int, **static):
    """The predicate field's slices of one call: per slice the rows
    the window's leaves admit, deduplicated, then tested against the
    threshold and compacted in row order into `capacity`.

    `nums` is the call's host array, int32 [1 + slices, 1 + window
    constants]: nums[0, 0] the live slices, row 1 + i slice i's rows
    and the constants of its leaves that are not key leaves.  Returns
    (codes, ts, values, positions)[slices, capacity], (selected,
    scanned)[slices] and (series code, timestamp, kept)[slices, rows]:
    `positions` are the selected rows' places in their slice's decoded
    order and the last three that order itself (what the join holds
    another field's slice against; neither leaves the program),
    `selected` may pass `capacity` (an overflow: the rows past it are
    not in the arrays), `scanned` counts the rows the predicate was
    put to."""
    stacked, keyed_stacked, offs = stack_slices(cols, key_consts,
                                                run_offsets)

    def one(i):
        row = nums[1 + i]
        _valid, keys_s, val_s, kept = rows_sorted_kept(
            slice_columns(stacked, i), row[0],
            slice_consts(static["leaf_prog"], keyed_stacked, row, i, 1),
            offs[i], **static)
        with jax.named_scope("select"):
            sel = kept & compare(val_s, op, threshold)
            pos = jnp.cumsum(sel.astype(jnp.int32))
            selected = pos[-1]
            # row r of the selected goes to place pos[r] - 1; one
            # beyond the capacity is dropped (and counted above)
            at = jnp.where(sel, pos - 1, capacity)
            rows = jnp.zeros(capacity, jnp.int32).at[at].set(
                jnp.arange(sel.shape[0], dtype=jnp.int32), mode="drop")
            live = jnp.arange(capacity, dtype=jnp.int32) < selected
            codes = jnp.where(live, keys_s[group_pos][rows], -1)
            ts = jnp.where(live, keys_s[ts_pos][rows], 0)
            vals = jnp.where(live, val_s[rows], 0)
        return (codes, ts, vals, jnp.where(live, rows, 0), selected,
                jnp.sum(kept.astype(jnp.int32)),
                (keys_s[group_pos], keys_s[ts_pos], kept))

    return _per_slice(one, nums[0, 0], len(cols))


def _select_join(cols: tuple, key_consts: tuple, run_offsets: tuple,
                 nums, remap, same_series, segments, codes, ts, pos,
                 selected, order, *, group_pos: int, ts_pos: int,
                 route: str, **static):
    """The slices of the joined fields that share these statics, at the
    keys `_select_rows` selected (`codes`, `ts`, `pos`, `selected`,
    `order`: its results, inside the same program): per slice, for
    every selected key, the value of the last row of that (series,
    timestamp) if the dedup kept it.

    The slices lie field after field, each field's live segments in
    the select's order, the filler behind them all: slice j joins the
    keys of the select's slice j % `segments` (the select's live
    slices).  `remap`[slices, g_pad of the keys' slices] turns a key's
    series code into this slice's code for the same series, -1 where
    this slice's dictionary lacks it; `same_series`[slices] says where
    the two dictionaries are one.  `nums` as `_select_rows`.  Returns
    (values, found)[slices, capacity]."""
    stacked, keyed_stacked, offs = stack_slices(cols, key_consts,
                                                run_offsets)
    capacity = codes.shape[1]
    chunk = min(capacity, _JOIN_CHUNK)
    g_pad = remap.shape[1]
    their_code, their_ts, their_kept = order

    def one(j):
        row = nums[1 + j]
        valid_s, keys_s, val_s, kept = rows_sorted_kept(
            slice_columns(stacked, j), row[0],
            slice_consts(static["leaf_prog"], keyed_stacked, row, j, 1),
            offs[j], route=route, **static)
        i = j % segments
        cap = val_s.shape[0]
        live = jnp.arange(capacity, dtype=jnp.int32) < selected[i]

        def taken():
            # this slice is the predicate's row for row: a selected
            # row's value lies at the selected row's position
            return jnp.where(live, val_s[pos[i]], 0), live

        def searched():
            iota = jnp.arange(cap, dtype=jnp.int32)
            # the rows in (code, ts) order: all that are real where
            # they arrive sorted, the leaves' survivors where the
            # device sorted them (the others sink behind those)
            bound = row[0] if route == "presorted" \
                else jnp.sum(valid_s.astype(jnp.int32))
            code_s = jnp.where(iota < bound, keys_s[group_pos],
                               jnp.int32(2**31 - 1))
            ts_s = keys_s[ts_pos]
            # where each series' rows begin: starts[c] .. starts[c + 1]
            starts = jnp.searchsorted(
                code_s, jnp.arange(g_pad + 1, dtype=jnp.int32),
                side="left").astype(jnp.int32)
            steps = 32 - jax.lax.clz(jnp.max(starts[1:] - starts[:-1]))

            def search(j, acc):
                vals_acc, found_acc = acc
                at = j * chunk
                c_key = jax.lax.dynamic_slice(codes[i], (at,), (chunk,))
                t_key = jax.lax.dynamic_slice(ts[i], (at,), (chunk,))
                c = jnp.where(c_key >= 0,
                              remap[j][jnp.clip(c_key, 0, g_pad - 1)], -1)
                known = c >= 0
                c = jnp.clip(c, 0, g_pad - 1)
                first = starts[c]

                # the first row of the series past the key's timestamp
                def halve(_k, lh):
                    lo, hi = lh
                    mid = (lo + hi) // 2
                    go = ts_s[jnp.clip(mid, 0, cap - 1)] <= t_key
                    open_ = lo < hi
                    return (jnp.where(open_ & go, mid + 1, lo),
                            jnp.where(open_ & ~go, mid, hi))

                lo, _hi = jax.lax.fori_loop(0, steps, halve,
                                            (first, starts[c + 1]))
                r = jnp.clip(lo - 1, 0, cap - 1)
                found = known & (lo > first) & (ts_s[r] == t_key) \
                    & kept[r]
                return (jax.lax.dynamic_update_slice(
                            vals_acc, jnp.where(found, val_s[r], 0), (at,)),
                        jax.lax.dynamic_update_slice(found_acc, found,
                                                     (at,)))

            return jax.lax.fori_loop(
                0, (jnp.minimum(selected[i], capacity) + chunk - 1) // chunk,
                search, (jnp.zeros(capacity, val_s.dtype),
                         jnp.zeros(capacity, bool)))

        with jax.named_scope("join"):
            if their_kept.shape[1] != cap:
                return searched()    # other capacities: nothing to hold
            # the same rows kept, and every kept row the same series
            # (one dictionary) at the same timestamp
            same = same_series[j] & jnp.all(
                (kept == their_kept[i])
                & (~kept | ((keys_s[group_pos] == their_code[i])
                            & (keys_s[ts_pos] == their_ts[i]))))
            return jax.lax.cond(same, taken, searched)

    return _per_slice(one, nums[0, 0], len(cols))


@deviceprof.jit(static_argnames=("op", "capacity", "statics"))
def _select_rows_joined_jit(keys: tuple, threshold, joins: tuple, *,
                            op: str, capacity: int, statics: tuple):
    """One call of the row-selecting route: the select over the
    predicate field's slices, then every joined field's join at the
    keys it selected, the keys never leaving the program between the
    two.

    `keys` are `_select_rows`'s four slice arguments; `joins` holds,
    for every set of joined fields whose windows share their statics
    (one traced join body a set, whatever its fields), `_select_join`'s
    six; `statics` the select's and, a set, the join's static
    arguments, as sorted items.  Returns `_select_rows`'s (codes, ts,
    values, selected, scanned) and a set's (values, found)[slices,
    capacity]."""
    key_statics, join_statics = statics
    codes, ts, vals, pos, selected, scanned, order = _select_rows(
        *keys, threshold, op=op, capacity=capacity, **dict(key_statics))
    joined = tuple(
        _select_join(*join, keys[3][0, 0], codes, ts, pos, selected,
                     order, **dict(static))
        for join, static in zip(joins, join_statics))
    return (codes, ts, vals, selected, scanned), joined


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


@dataclass
class SelectedRows:
    """What one segment's slices gave: the selected keys as the host
    reads them and, a field asked, its values and found flags."""

    groups: np.ndarray            # the series' values (the dictionary's)
    timestamps: np.ndarray        # int64
    values: list                  # float32 arrays, one a field asked
    found: list                   # bool arrays, one a field asked
    scanned: int                  # rows the predicate was put to


def _nums(windows: list, slots: int) -> np.ndarray:
    rows = [np.concatenate(
        [np.asarray([w.seg.n], dtype=np.int32),
         *window_numbers(w.leaf_prog, w.consts)]) for w in windows]
    nums = np.zeros((1 + slots, len(rows[0])), dtype=np.int32)
    nums[0, 0] = len(windows)
    nums[1:1 + len(rows)] = rows
    return nums


def _slices_args(windows: list, slots: int) -> tuple:
    segs = [w.seg for w in windows] \
        + [windows[0].seg] * (slots - len(windows))
    return (tuple(s.cols_dev for s in segs),
            tuple(s.key_consts_dev for s in segs),
            tuple(s.offs_dev for s in segs), _nums(windows, slots))


def _remap(keys: list, others: list, slots: int) -> tuple:
    """([slots, g_pad of the keys' slices]: slice i's series codes as
    the other field's slice i names the same series, -1 where it does
    not; [slots]: whether the two slices' dictionaries are one)."""
    out = np.full((slots, keys[0].seg.g_pad), -1, dtype=np.int32)
    same = np.zeros(slots, dtype=bool)
    for i, (k, o) in enumerate(zip(keys, others)):
        mine, theirs = k.seg.values, o.seg.values
        if mine is theirs or np.array_equal(mine, theirs):
            out[i, :len(mine)] = np.arange(len(mine), dtype=np.int32)
            same[i] = True
            continue
        at = np.searchsorted(theirs, mine)
        hit = theirs[np.minimum(at, len(theirs) - 1)] == mine
        out[i, :len(mine)] = np.where(hit, at, -1)
    return out, same


def _first_call(key: tuple, call):
    """`call()`, under the compile lock the first time `key` runs."""
    if key in _COMPILED:
        return call()
    with _COMPILE_LOCK:
        out = call()
        _COMPILED.add(key)
        return out


def _joined_fields(group: list) -> dict:
    """The fields a call over `group` joins (indexes into the fields
    asked), in sets by the batch key their windows share: not a field
    with no row in these segments (nulls), nor the predicate's own
    (its values are the select's)."""
    sets: dict = {}
    for f, window in enumerate(group[0][1:]):
        if window is None:
            continue
        if all(windows[1 + f].seg is windows[0].seg for windows in group):
            continue
        sets.setdefault(window.batch_key(), []).append(f)
    return sets


def _slot_nbytes(windows: list, joins: dict, widths: tuple) -> int:
    """What one segment (`windows`: its predicate's, then one a field
    asked) adds to a call's stacks: the predicate's slice and `widths`
    slices a set of joined fields."""
    return windows[0].seg.nbytes + sum(
        width * windows[1 + fields[0]].seg.nbytes
        for width, fields in zip(widths, joins.values()))


def _run_group(group: list, spec: SelectSpec, joins: dict, phase,
               table: str) -> list:
    """One group of segments whose windows share their program
    (`group`: per segment the predicate field's SelectWindow, then one
    SelectWindow or None a field asked; `joins`: _joined_fields of
    them): ONE call, the select and every set's join inside it, one
    download; again one rung up where a slice overflowed.  Returns one
    SelectedRows a segment."""
    keys = [windows[0] for windows in group]
    first = keys[0]
    n = len(group)
    ladder = capacity_ladder(first.seg.cap)
    slots = 1 << (n - 1).bit_length()
    key = tuple(None if w is None else w.batch_key() for w in group[0])
    rung = min(_RUNG.get(key, 0), len(ladder) - 1)
    threshold = np.float32(spec.threshold)
    want = tuple(map(len, joins.values()))
    statics = (
        tuple(sorted(first.statics().items())),
        tuple(tuple(sorted(group[0][1 + fields[0]].statics().items()))
              for fields in joins.values()))
    while True:
        capacity = ladder[rung]
        shape = (key[0], tuple(joins), slots, capacity, spec.op)
        # fewer fields of a set than a program that exists joins run
        # that program, their stack filled as `slots` fills the
        # segments', where the stacks stay inside the budget
        widths = min(
            (have for have in _WIDTHS.get(shape, ())
             if all(h >= w for h, w in zip(have, want))
             and _slot_nbytes(group[0], joins, have) * slots
             <= device_decode._BATCH_MAX_STACK_BYTES),
            key=sum, default=want)
        with phase("scan.dispatch", sync=True, h2d_bytes=0,
                   slices=n * len(group[0])):
            sets = []
            for width, fields in zip(widths, joins.values()):
                others = [windows[1 + f] for f in fields
                          for windows in group]
                sets.append(
                    _slices_args(others, width * slots)
                    + _remap(keys * len(fields), others, width * slots))
            out = _first_call(
                ("select", shape, widths),
                lambda: _select_rows_joined_jit(
                    _slices_args(keys, slots), threshold, tuple(sets),
                    op=spec.op, capacity=capacity, statics=statics))
            _WIDTHS.setdefault(shape, set()).add(widths)
            _CALLS["select"].inc()
            _CALLS["join"].inc(sum(want))
            device_decode.note_batched(n * (1 + sum(want)), 1)
        (codes, ts, vals, selected, scanned), joined = deviceprof.download(
            out, fn="_select_rows_joined_jit", table=table)
        if int(selected[:n].max(initial=0)) <= capacity:
            break
        _OVERFLOWS.inc()
        rung += 1
    if rung > _RUNG.get(key, 0):
        _RUNG[key] = rung
    # field f's slices are rows at * n .. at * n + n of its set's
    at_of = {f: (s, at) for s, fields in enumerate(joins.values())
             for at, f in enumerate(fields)}
    out = []
    for i, windows in enumerate(group):
        m = int(selected[i])
        seg = windows[0].seg
        values, found = [], []
        for f, window in enumerate(windows[1:]):
            if f in at_of:
                s, at = at_of[f]
                values.append(joined[s][0][at * n + i, :m])
                found.append(joined[s][1][at * n + i, :m])
            elif window is None:
                values.append(np.zeros(m, np.float32))
                found.append(np.zeros(m, bool))
            else:
                values.append(vals[i, :m])
                found.append(np.ones(m, bool))
        out.append(SelectedRows(
            groups=seg.values[codes[i, :m]],
            timestamps=ts[i, :m].astype(np.int64) + seg.ts_epoch,
            values=values, found=found, scanned=int(scanned[i])))
    return out


def select_resident(segments: list, spec: SelectSpec, phase,
                    table: str = "") -> list:
    """Select from every segment of a query whose slices are on the
    device.  `segments`: per segment a list, the predicate field's
    SelectWindow first, then one a field asked (None where that field
    provably has no row there).  Segments whose windows may share
    their program go out together, in ONE call with all their fields,
    cut where the columns a call stacks (the predicate's slice and one
    a joined field, a segment) would pass device_decode's stack
    budget.  Returns one SelectedRows a segment, in order."""
    groups: dict = {}
    for pos, windows in enumerate(segments):
        groups.setdefault(
            tuple(None if w is None else w.batch_key() for w in windows),
            []).append(pos)
    out: dict = {}
    for group in groups.values():
        joins = _joined_fields([segments[p] for p in group])
        room = device_decode._BATCH_MAX_STACK_BYTES // _slot_nbytes(
            segments[group[0]], joins, tuple(map(len, joins.values())))
        per_call = max(1, 1 << (room.bit_length() - 1)) if room else 1
        for at in range(0, len(group), per_call):
            call = group[at:at + per_call]
            out.update(zip(call, _run_group(
                [segments[p] for p in call], spec, joins, phase, table)))
    return [out[pos] for pos in range(len(segments))]
