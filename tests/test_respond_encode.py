"""The downsample response encoder (server/main.py::_downsample_response,
_grids_text) against the plain reference: json.dumps of the nested
lists, which is how the server wrote these bodies before the columnar
encoder.  The answer must be the SAME answer: same keys in the same
order, every number parsing to the same double, NaN as null."""

import json
import math
import struct

import numpy as np
import pytest

from horaedb_tpu.server import main as server_main
from horaedb_tpu.utils import registry

F32_MAX = float(np.finfo(np.float32).max)
F32_DENORMAL = float(np.float32(1e-45))
AGGS = ("count", "sum", "min", "max", "avg", "last", "first")


def _grid_json(grid) -> list:
    """The reference: one Python float and one isnan per cell."""
    out = []
    for row in np.asarray(grid).tolist():
        out.append([None if isinstance(x, float) and math.isnan(x) else x
                    for x in row])
    return out


def _reference(node) -> str:
    def plain(node):
        if isinstance(node, dict):
            return {k: plain(v) for k, v in node.items()}
        return _grid_json(node) if isinstance(node, np.ndarray) else node

    return json.dumps(plain(node))


def _bits(tree):
    """The parsed tree with every number as the 8 bytes of its double
    (so -0.0 != 0.0 and 360 == 360.0), None kept as None."""
    if isinstance(tree, dict):
        return {k: _bits(v) for k, v in tree.items()}
    if isinstance(tree, list):
        return [_bits(v) for v in tree]
    if isinstance(tree, bool) or tree is None or isinstance(tree, str):
        return tree
    return struct.pack("<d", float(tree))


def _key_order(tree):
    """The keys of every object in the order they were written (dict
    equality does not see order)."""
    if not isinstance(tree, dict):
        return None
    return [(k, _key_order(v)) for k, v in tree.items()]


@pytest.fixture(params=["columnar", "by_cell"], autouse=True)
def path(request, monkeypatch):
    """Every case runs down both paths of _grids_text, whatever its
    size: the server picks by the answer's cell count alone."""
    monkeypatch.setattr(server_main, "_COLUMNAR_MIN_CELLS",
                        0 if request.param == "columnar" else 2 ** 62)
    return request.param


def _encode(body: dict) -> bytes:
    resp = server_main._downsample_response(body)
    assert resp.status == 200
    assert resp.content_type == "application/json"
    assert resp.charset == "utf-8"
    return resp.body


def _assert_same_answer(body: dict) -> None:
    got = json.loads(_encode(body))
    want = json.loads(_reference(body))
    assert got == want
    assert _bits(got) == _bits(want)
    assert _key_order(got) == _key_order(want)


def _body(aggs: dict, **extra) -> dict:
    rows = next(iter(aggs.values())).shape[0] if aggs else 0
    cols = next(iter(aggs.values())).shape[1] if aggs else 0
    return {"tsids": [str(2 ** 63 + i) for i in range(rows)],
            "num_buckets": cols, "aggs": dict(aggs), **extra}


def _random_grid(shape, seed=0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return (rng.random(shape) * 100.0).astype(np.float32)


def _row(values) -> np.ndarray:
    return np.asarray([values], dtype=np.float32)


@pytest.mark.parametrize("shape", [(0, 12), (1, 1), (1, 60), (100, 12),
                                   (1000, 12), (3, 0)],
                         ids=lambda s: f"{s[0]}x{s[1]}")
def test_shapes(shape):
    grid = _random_grid(shape)
    if grid.size:
        grid[0, 0] = np.nan
    _assert_same_answer(_body({"avg": grid, "max": _random_grid(shape, 1)}))


VALUES = {
    "all_nan": np.full((4, 12), np.nan, dtype=np.float32),
    "nan_at_the_edges": np.where(
        np.pad(np.ones((3, 10), bool), 1), _random_grid((5, 12)),
        np.float32(np.nan)),
    "infinities": _row([np.inf, -np.inf, 1.0, np.inf]),
    "negative_zero": _row([-0.0, 0.0, -0.0, 1.5]),
    "integral_counts": _row([0, 1, 360, 4320, 2 ** 24 - 1, 2 ** 24]),
    "float32_max": _row([F32_MAX, -F32_MAX]),
    "float32_smallest_denormal": _row([F32_DENORMAL, -F32_DENORMAL]),
    "1e22": _row([1e22, -1e22, 1e15, 1e16, 123456789012345678.0]),
    "1e-7": _row([1e-7, -1e-7, 1e-4, 1e-5, 0.1, 1 / 3]),
    "negative_sums": -_random_grid((6, 12), 2) * 1000.0,
    "widened_float32": _row([50.4, 0.3, 99.99, 12.345678]),
}


@pytest.mark.parametrize("name", sorted(VALUES))
def test_values(name):
    _assert_same_answer(_body({"sum": VALUES[name]}))


def test_how_an_integral_cell_prints(path):
    """Decided once (README.md, "Usage": the downsample body): the
    columnar pass writes `360`, json.dumps `360.0`; one number."""
    text = _encode(_body({"count": _row([360, 2 ** 24])})).decode()
    want = "[[360, 16777216]]" if path == "columnar" \
        else "[[360.0, 16777216.0]]"
    assert f'"count": {want}' in text


def test_the_path_is_picked_by_the_cell_count(monkeypatch):
    monkeypatch.undo()
    below = server_main._COLUMNAR_MIN_CELLS - 1
    small = _encode(_body({"count": np.full((1, below), 360.0)}))
    large = _encode(_body({"count": np.full((1, below + 1), 360.0)}))
    assert b"360.0" in small and b"360.0" not in large
    assert json.loads(small)["aggs"]["count"][0] \
        == json.loads(large)["aggs"]["count"][0][1:]


def test_special_cells_as_json_dumps_writes_them():
    text = _encode(_body({"sum": _row([np.nan, np.inf, -np.inf, -0.0])}))
    assert b'"sum": [[null, Infinity, -Infinity, -0.0]]' in text


def test_widened_float32_keeps_its_digits():
    """`50.4` parses to another double than the float32 cell."""
    text = _encode(_body({"avg": _row([50.4])})).decode()
    assert "50.400001525878906" in text
    assert json.loads(text)["aggs"]["avg"][0][0] == float(np.float32(50.4))


def test_seven_grids_and_a_fn_grid():
    aggs = {a: _random_grid((50, 12), i) for i, a in enumerate(AGGS)}
    aggs["count"] = np.full((50, 12), 360, dtype=np.float32)
    aggs["avg"][7, 3] = np.nan
    # the rate family's grids are float64 with an empty first bucket
    rate = np.random.default_rng(9).random((50, 12)) / 7.0
    rate[:, 0] = np.nan
    aggs["rate"] = rate
    body = _body(aggs)
    _assert_same_answer(body)
    assert list(json.loads(_encode(body))["aggs"]) == [*AGGS, "rate"]


@pytest.mark.parametrize("extra", [
    {}, {"partial": False, "missing_regions": []},
    {"partial": True, "missing_regions": [1, 3]}],
    ids=["single_engine", "whole", "partial"])
def test_partial_marker(extra):
    body = _body({"avg": _random_grid((3, 4))}, **extra)
    _assert_same_answer(body)
    assert list(json.loads(_encode(body))) \
        == ["tsids", "num_buckets", "aggs", *extra]


def test_query_multi_nesting_with_grids_of_different_shapes():
    body = {
        "usage_user": _body({a: _random_grid((4, 6), i)
                             for i, a in enumerate(AGGS)}),
        "usage_system": _body({"sum": _random_grid((9, 6), 7)}),
        "usage_idle": _body({"sum": np.zeros((0, 6), dtype=np.float32)}),
        'quoted "field"': _body({}),
    }
    _assert_same_answer(body)
    assert list(json.loads(_encode(body))) == list(body)


def test_downsample_json_hands_on_its_own_list_like_grids():
    """benchmark/tests/broken_launcher.py wraps this function by name
    and alters `body["aggs"]["avg"][0][0]` the way it would a nested
    list: that must work, reach the wire, and leave the engine's array
    (a memo entry, a read-only download) alone."""
    grid = _random_grid((2, 3))
    grid.setflags(write=False)
    out = {"tsids": [2 ** 63 + 5, 7], "num_buckets": 3,
           "aggs": {"avg": grid, "max": np.zeros((0, 3), np.float32)}}
    body = server_main._downsample_json(out)
    assert list(body) == ["tsids", "num_buckets", "aggs"]
    assert body["tsids"] == [str(2 ** 63 + 5), "7"]
    avg = body["aggs"]["avg"]
    assert avg and avg[0] and avg[0][0] is not None
    assert not body["aggs"]["max"]
    avg[0][0] *= 1.001
    sent = json.loads(_encode(body))["aggs"]["avg"]
    assert sent[0][0] == float(grid[0, 0]) * 1.001
    assert sent[1] == [float(x) for x in grid[1]]
    body["aggs"]["rate"] = grid * 2.0
    assert list(out["aggs"]) == ["avg", "max"]


def test_counters_move_by_one_response():
    names = ("respond_cells_total", "respond_bytes_total",
             "respond_encode_seconds_total")

    def read():
        return [registry.counter(n).value for n in names]

    before = read()
    payload = _encode(_body({a: _random_grid((10, 12), i)
                             for i, a in enumerate(AGGS)}))
    cells, nbytes, seconds = (a - b for a, b in zip(read(), before))
    assert cells == 7 * 10 * 12
    assert nbytes == len(payload)
    assert 0.0 < seconds < 5.0
    exported = registry.render()
    for n in names:
        assert f"\n{n} " in exported
