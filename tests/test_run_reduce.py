"""The run reduction alone (ISSUE 31): `ops.downsample.run_aggregate`
against `partial_aggregate` on rows whose cell order never falls.

count / min / max / last / last_ts must be BIT-identical; sum is
bit-identical on integer-valued inputs whose cell sums stay under 2^24
(every association is exact there) and within 1e-6 relative on uniform
floats (a tree over the cell's rows against the scatter's row order).
Dropped rows (group -1) lie anywhere and carry garbage; rows outside
the bucket range, empty cells, one-row cells, an all-padding slice,
`n_valid = cap`, a capacity that is no multiple of the scan's block,
rows >> cells (the double-groupby's shape) and cells >> rows (the
point query's, whose run ends are placed by a scatter).  No engine
or reader is opened here."""

import dataclasses

import jax
import numpy as np
import pytest

from horaedb_tpu.ops import downsample
from horaedb_tpu.ops.downsample import ALL_AGGS

BUCKET_MS = 1000
# every subset of `which` the engine sends (tests/test_device_decode.py)
WHICH_SETS = (("avg",), ("min", "max"), ("count",), ("sum", "avg"),
              ("last",), ("avg", "max", "last"), ALL_AGGS)


@dataclasses.dataclass
class Shape:
    name: str
    cap: int
    n: int                # real rows; the rest is padding
    groups: int
    buckets: int
    drop: float = 0.2     # share of real rows masked to group -1
    outside: int = 500    # ts reach this far past both ends of the grid
    distinct: bool = False  # one row a cell


SHAPES = [
    Shape("rows_over_cells", 1024, 700, 8, 8),
    Shape("n_valid_is_cap", 1024, 1024, 4, 4),
    Shape("double_groupby_131072", 131072, 72000, 128, 8),
    Shape("point_query_cells_over_rows", 1024, 720, 1024, 60),
    Shape("all_padding", 2048, 0, 8, 8),
    Shape("one_row_cells", 1024, 512, 32, 16, drop=0.0, outside=0,
          distinct=True),
    Shape("mostly_empty_cells", 1024, 40, 32, 32),
    Shape("nothing_dropped", 2048, 1500, 16, 8, drop=0.0, outside=0),
    Shape("everything_dropped", 1024, 600, 8, 8, drop=1.0),
    Shape("capacity_not_a_block_multiple", 1500, 1400, 16, 8),
    Shape("one_cell_holds_every_row", 4096, 4000, 1, 1, outside=0),
]


def rows(shape: Shape, seed: int, integer_values: bool):
    """(ts, gid, values) sorted by (group, ts) over the kept rows, with
    dropped rows interleaved and holding garbage, padded to cap."""
    r = np.random.default_rng(seed)
    n, g, b = shape.n, shape.groups, shape.buckets
    if shape.distinct:
        cell = np.sort(r.choice(g * b, size=n, replace=False))
        gid = (cell // b).astype(np.int32)
        ts = ((cell % b) * BUCKET_MS + r.integers(0, BUCKET_MS, n)
              ).astype(np.int32)
    else:
        gid = r.integers(0, g, n).astype(np.int32)
        ts = r.integers(-shape.outside, b * BUCKET_MS + shape.outside,
                        n).astype(np.int32)
        order = np.lexsort((ts, gid))
        gid, ts = gid[order], ts[order]
    if integer_values:
        # 131,072 rows x 100 < 2^24: any order of adds is exact
        vals = r.integers(-100, 101, n).astype(np.float32)
    else:
        vals = r.uniform(0.0, 100.0, n).astype(np.float32)
    dropped = r.random(n) < shape.drop
    gid = np.where(dropped, -1, gid).astype(np.int32)
    # a dropped row's timestamp and value are whatever the slice held
    ts = np.where(dropped, r.integers(-10**6, 10**6, n), ts).astype(np.int32)
    pad = shape.cap - n
    return (np.pad(ts, (0, pad)), np.pad(gid, (0, pad)),
            np.pad(vals, (0, pad)))


def both(shape: Shape, ts, gid, vals, which):
    out = []
    for fn in (downsample.partial_aggregate, downsample.run_aggregate):
        jitted = jax.jit(fn, static_argnames=("num_groups", "num_buckets",
                                              "which"))
        got = jitted(ts, gid, vals, shape.n, BUCKET_MS,
                     num_groups=shape.groups, num_buckets=shape.buckets,
                     which=which)
        out.append({k: np.asarray(v) for k, v in got.items()})
    return out


def assert_same_grids(want: dict, got: dict, exact_sum: bool):
    assert set(want) == set(got)
    for k in want:
        assert want[k].dtype == got[k].dtype, k
        assert want[k].shape == got[k].shape, k
        if k == "sum" and not exact_sum:
            # empty cells hold 0 on both sides, exactly
            occupied = want["count"] > 0
            assert np.array_equal(want[k][~occupied], got[k][~occupied])
            err = np.abs(want[k][occupied] - got[k][occupied]) \
                / np.maximum(np.abs(want[k][occupied]), 1e-30)
            assert err.size == 0 or err.max() <= 1e-6, err.max()
        else:
            assert want[k].tobytes() == got[k].tobytes(), k


@pytest.mark.parametrize("integer_values", [True, False],
                         ids=["integers", "floats"])
@pytest.mark.parametrize("shape", SHAPES, ids=[s.name for s in SHAPES])
def test_run_aggregate_is_partial_aggregate(shape, integer_values):
    ts, gid, vals = rows(shape, seed=31, integer_values=integer_values)
    want, got = both(shape, ts, gid, vals, ALL_AGGS)
    assert set(got) == {"count", "sum", "min", "max", "last", "last_ts"}
    assert_same_grids(want, got, exact_sum=integer_values)
    if shape.n and shape.drop < 1.0:
        assert want["count"].sum() > 0  # the case is not vacuous


@pytest.mark.parametrize("which", WHICH_SETS,
                         ids=["+".join(w) for w in WHICH_SETS])
@pytest.mark.parametrize("shape", [SHAPES[0], SHAPES[3]],
                         ids=[SHAPES[0].name, SHAPES[3].name])
def test_run_aggregate_honours_which(shape, which):
    ts, gid, vals = rows(shape, seed=32, integer_values=True)
    want, got = both(shape, ts, gid, vals, which)
    assert_same_grids(want, got, exact_sum=True)


def test_unknown_aggregate_is_refused():
    ts, gid, vals = rows(SHAPES[0], seed=1, integer_values=True)
    with pytest.raises(ValueError, match="median"):
        downsample.run_aggregate(ts, gid, vals, 700, BUCKET_MS, 8, 8,
                                 which=("median",))


def test_ties_on_the_last_timestamp_keep_the_later_row():
    """Two rows of one cell at one timestamp: `last` is the later
    row's value, as the scatter's second segment_max picks."""
    ts = np.zeros(1024, np.int32)
    gid = np.full(1024, -1, np.int32)
    vals = np.zeros(1024, np.float32)
    ts[:4] = (5, 7, 7, 1500)
    gid[:4] = (0, 0, 0, 0)
    vals[:4] = (1.0, 2.0, 3.0, 4.0)
    shape = Shape("ties", 1024, 4, 2, 2)
    want, got = both(shape, ts, gid, vals, ("last",))
    assert_same_grids(want, got, exact_sum=True)
    assert got["last"][0, 0] == 3.0 and got["last_ts"][0, 0] == 7
    assert got["last"][0, 1] == 4.0


@pytest.mark.parametrize("remap", ["identity", "none"])
@pytest.mark.parametrize("shape", [SHAPES[0], SHAPES[3]],
                         ids=[SHAPES[0].name, SHAPES[3].name])
def test_window_local_partials_picks_the_reduction(shape, remap):
    """The window kernel's static `cells_sorted` selects the run
    reduction and `remap=None` is the identity remap: the four
    combinations agree (a window shifted, offset and clipped)."""
    ts, gid, vals = rows(shape, seed=33, integer_values=True)
    table = None if remap == "none" \
        else np.arange(shape.groups, dtype=np.int32)
    shift, lo, total = 250, 1, shape.buckets - 1
    got = []
    for cells_sorted in (False, True):
        fn = jax.jit(downsample.window_local_partials, static_argnames=(
            "num_groups", "num_buckets", "which", "cells_sorted"))
        out = fn(ts, gid, vals, table, shift, lo, total, BUCKET_MS,
                 num_groups=shape.groups, num_buckets=shape.buckets,
                 which=ALL_AGGS, cells_sorted=cells_sorted)
        got.append({k: np.asarray(v) for k, v in out.items()})
    assert_same_grids(got[0], got[1], exact_sum=True)
    assert got[0]["count"].sum() > 0
