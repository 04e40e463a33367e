"""The downsample response encoder (server/main.py::_downsample_payload,
_grids_text) against the plain reference: json.dumps of the nested
lists, which is how the server wrote these bodies before the columnar
encoder.  The answer must be the SAME answer: same keys in the same
order, every number parsing to the same double, NaN as null.  And
WHERE it is written (server/main.py::_respond): an answer of
_RESPOND_POOL_MIN_CELLS cells or more on a thread of the `sst` pool, a
smaller one on the event loop's own, the same bytes either way."""

import asyncio
import json
import math
import struct
import threading

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from horaedb_tpu.common import Error
from horaedb_tpu.common.deadline import DeadlineExceeded
from horaedb_tpu.common.runtimes import Runtimes, queue_depths
from horaedb_tpu.metric_engine import MetricEngine
from horaedb_tpu.objstore import MemoryObjectStore
from horaedb_tpu.server import main as server_main
from horaedb_tpu.server.config import ServerConfig
from horaedb_tpu.server.main import ServerState, build_app
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
    return server_main._downsample_payload(body, "loop")


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


# --- where the answer is written: the loop's thread or the pool's ------

T0 = 1_700_000_000_000
HOUR = 3_600_000
FIELDS = ("usage_user", "usage_system")
WINDOW = {"metric": "cpu", "start": T0 + 7, "end": T0 + 3 * HOUR + 7,
          "bucket_ms": 600_000}
REQUESTS = {
    "query": ("/query", dict(WINDOW, field="usage_user")),
    "query_filtered": ("/query", dict(WINDOW, field="usage_system",
                                      filters={"host": "h1"})),
    "query_fn_rate": ("/query", dict(WINDOW, field="usage_user",
                                     fn="rate")),
    "query_fn_increase": ("/query", dict(WINDOW, field="usage_user",
                                         fn="increase")),
    "query_fn_delta": ("/query", dict(WINDOW, field="usage_system",
                                      fn="delta")),
    "query_no_series": ("/query", dict(WINDOW, field="usage_user",
                                       filters={"host": "nobody"})),
    "query_topk": ("/query_topk", dict(WINDOW, field="usage_user", k=2,
                                       by="max")),
    "query_multi": ("/query_multi", dict(WINDOW, fields=list(FIELDS))),
}
# the seven grids of an engine's answer, in the order it writes them
GRIDS = ("count", "sum", "avg", "min", "max", "last", "last_ts")
ENCODED = ("respond_cells_total", "respond_bytes_total",
           "respond_encode_seconds_total",
           "respond_encode_cpu_seconds_total")


def _where_counts() -> dict:
    fam = registry.family("respond_encode_total")
    return {w: fam.labels(where=w).value for w in ("pool", "loop")}


def _moved(before: dict) -> dict:
    return {w: n - before[w] for w, n in _where_counts().items()}


def _set_threshold(monkeypatch, where: str) -> None:
    monkeypatch.setattr(server_main, "_RESPOND_POOL_MIN_CELLS",
                        0 if where == "pool" else 2 ** 62)


@pytest.fixture
def threads(monkeypatch):
    """The names of the threads that ran the encoder, in order."""
    seen = []
    encoder = server_main._downsample_payload

    def recording(body, where):
        seen.append(threading.current_thread().name)
        return encoder(body, where)

    monkeypatch.setattr(server_main, "_downsample_payload", recording)
    return seen


def _on_the_pool(name: str) -> bool:
    return name.startswith("horaedb-sst")


async def _served(fn):
    """`fn(client, engine)` against a served engine that holds four
    hosts' two fields, 200 one-minute samples each."""
    engine = await MetricEngine.open("respond_db", MemoryObjectStore(),
                                     segment_ms=2 * HOUR)
    client = TestClient(TestServer(build_app(
        ServerState(engine, ServerConfig()))))
    await client.start_server()
    try:
        for h in range(4):
            for f, field in enumerate(FIELDS):
                r = await client.post("/write", json={"samples": [
                    {"name": "cpu", "labels": {"host": f"h{h}"},
                     "field": field, "timestamp": T0 + i * 60_000,
                     "value": (i % 37) * 1.1 + h + 100 * f}
                    for i in range(200)]})
                assert r.status == 200
        return await fn(client, engine)
    finally:
        await client.close()
        await engine.close()


async def _post(client, request: str):
    path, body = REQUESTS[request]
    r = await client.post(path, json=body)
    return r.status, r.content_type, r.charset, await r.read()


def _named_bodies() -> dict:
    """Every body shape of the cases above, by name."""
    bodies = {f"shape_{r}x{c}": _body({"avg": _random_grid((r, c)),
                                       "max": _random_grid((r, c), 1)})
              for r, c in [(0, 12), (1, 1), (1, 60), (100, 12),
                           (1000, 12), (3, 0)]}
    bodies.update({f"values_{name}": _body({"sum": grid})
                   for name, grid in VALUES.items()})
    aggs = {a: _random_grid((50, 12), i) for i, a in enumerate(AGGS)}
    aggs["rate"] = np.random.default_rng(9).random((50, 12)) / 7.0
    bodies["seven_grids_and_a_fn_grid"] = _body(aggs)
    bodies["partial"] = _body({"avg": _random_grid((3, 4))}, partial=True,
                              missing_regions=[1, 3])
    bodies["query_multi"] = {
        "usage_user": _body({a: _random_grid((4, 6), i)
                             for i, a in enumerate(AGGS)}),
        "usage_system": _body({"sum": _random_grid((9, 6), 7)}),
        "usage_idle": _body({"sum": np.zeros((0, 6), dtype=np.float32)}),
        'quoted "field"': _body({}),
    }
    return bodies


BODIES = _named_bodies()


@pytest.mark.parametrize("name", sorted(BODIES))
def test_the_pool_thread_writes_the_bytes_the_loop_thread_writes(
        name, threads):
    """The job _respond hands to the pool against the encoder called on
    this thread: one encoder, so the same bytes, for every body shape
    of this file."""
    body = BODIES[name]

    async def go():
        rt = Runtimes(sst_threads=1)
        try:
            return await rt.run(
                "sst", server_main._payload_on_pool,
                lambda where: server_main._downsample_payload(body, where))
        finally:
            rt.close()

    before = _where_counts()
    on_pool = asyncio.run(go())
    assert _moved(before) == {"pool": 1, "loop": 0}
    assert on_pool == server_main._downsample_payload(body, "loop")
    assert _moved(before) == {"pool": 1, "loop": 1}
    assert _on_the_pool(threads[0]) and not _on_the_pool(threads[1])
    assert threads[1] == threading.current_thread().name


@pytest.mark.parametrize("request_name", sorted(REQUESTS))
def test_an_endpoint_answers_the_same_bytes_from_either_thread(
        request_name, monkeypatch, threads):
    """Each downsample endpoint, `fn` and the top-k stage included,
    with the threshold under and over the answer: same status, same
    headers, same bytes, the encoder on the thread the threshold says,
    one count in respond_encode_total{where}."""
    async def go(client, _engine):
        await _post(client, request_name)  # compiles, fills the memo
        answers = {}
        for where in ("loop", "pool"):
            _set_threshold(monkeypatch, where)
            del threads[:]
            before = _where_counts()
            answers[where] = await _post(client, request_name)
            other = "pool" if where == "loop" else "loop"
            assert _moved(before) == {where: 1, other: 0}
            ran_on, = threads
            assert _on_the_pool(ran_on) == (where == "pool")
        return answers

    answers = asyncio.run(_served(go))
    assert answers["pool"] == answers["loop"]
    status, content_type, charset, payload = answers["loop"]
    assert (status, content_type, charset) \
        == (200, "application/json", "utf-8")
    body = json.loads(payload)
    if request_name == "query_multi":
        assert list(body) == list(FIELDS)
        body = body["usage_user"]
    assert list(body)[:3] == ["tsids", "num_buckets", "aggs"]
    fn = REQUESTS[request_name][1].get("fn")
    want = [] if request_name == "query_no_series" \
        else [*GRIDS] if fn is None else [*GRIDS, fn]
    assert list(body["aggs"]) == want
    series = {"query_filtered": 1, "query_no_series": 0,
              "query_topk": 2}.get(request_name, 4)
    assert len(body["tsids"]) == series
    assert all(np.shape(g) == (series, body["num_buckets"])
               for g in body["aggs"].values())


@pytest.mark.parametrize("request_name",
                         ["query", "query_fn_rate", "query_topk",
                          "query_multi"])
def test_the_thread_is_picked_by_the_cell_count(request_name, monkeypatch,
                                                threads):
    """An answer one cell under the threshold stays on the loop's own
    thread, one at it leaves it.  The cells are the grids the engine
    handed over: `fn`'s own grid is made from them afterwards."""
    async def go(client, _engine):
        body = json.loads((await _post(client, request_name))[3])
        bodies = body.values() if request_name == "query_multi" else [body]
        cells = sum(np.size(b["aggs"][g]) for b in bodies for g in GRIDS)
        assert cells > 0
        del threads[:]
        before = _where_counts()
        monkeypatch.setattr(server_main, "_RESPOND_POOL_MIN_CELLS",
                            cells + 1)
        await _post(client, request_name)
        assert _moved(before) == {"pool": 0, "loop": 1}
        monkeypatch.setattr(server_main, "_RESPOND_POOL_MIN_CELLS", cells)
        await _post(client, request_name)
        assert _moved(before) == {"pool": 1, "loop": 1}
        return threading.current_thread().name

    loop_thread = asyncio.run(_served(go))
    under, at = threads
    assert under == loop_thread and _on_the_pool(at)


def test_the_threshold_lies_between_the_two_sides_measured():
    """8,400 cells lost 12 % on the pool and 84,000 gained 54 % (the
    constant's comment): the switch lies where the issue put it."""
    assert 8_400 < 16_384 <= server_main._RESPOND_POOL_MIN_CELLS \
        <= 65_536 < 84_000


@pytest.mark.parametrize("request_name",
                         ["query", "query_topk", "query_multi"])
@pytest.mark.parametrize("raised", [Error, DeadlineExceeded],
                         ids=["error", "deadline"])
def test_an_error_inside_the_job_answers_what_it_answers_on_the_loop(
        request_name, raised, monkeypatch):
    """An Error from the body's construction is /query's 400 with its
    text (the handler's _error_response), a DeadlineExceeded the
    middleware's 504, wherever the body was being built; the encoder
    counted nothing."""
    def failing(_out):
        raise raised("the grids are gone")

    async def go(client, _engine):
        monkeypatch.setattr(server_main, "_downsample_json", failing)
        answers = {}
        before = _where_counts(), [registry.counter(n).value
                                   for n in ENCODED]
        for where in ("loop", "pool"):
            _set_threshold(monkeypatch, where)
            status, _type, _charset, payload = await _post(client,
                                                           request_name)
            answers[where] = (status, payload)
        assert (_where_counts(), [registry.counter(n).value
                                  for n in ENCODED]) == before
        return answers

    answers = asyncio.run(_served(go))
    status, payload = answers["loop"]
    if raised is DeadlineExceeded:
        assert status == 504 and b"deadline exceeded" in payload
    elif request_name == "query":
        assert status == 400
        assert json.loads(payload) == {"error": "the grids are gone"}
    else:
        # outside the handler's try, as before: aiohttp's 500, whose
        # text is a traceback under asyncio's debug mode
        assert status == 500 == answers["pool"][0]
        return
    assert answers["pool"] == answers["loop"]


def test_a_request_cancelled_under_its_job_counts_whole_and_frees_the_pool(
        monkeypatch):
    """The deadline's backstop cancels the handler while its job holds
    a pool thread: the client reads 504, the job runs to its end and
    counts one whole response (cells, bytes and `where` together, none
    of them before the bytes are there), nothing is left queued, and
    the next request is answered from the pool as if nothing had
    happened."""
    started, release = threading.Event(), threading.Event()
    grids_text = server_main._grids_text

    def held(grids):
        started.set()
        assert release.wait(30.0)
        return grids_text(grids)

    def counted():
        return (_where_counts(),
                [registry.counter(n).value for n in ENCODED[:2]])

    async def go(client, _engine):
        loop = asyncio.get_running_loop()
        path, body = REQUESTS["query"]
        sound = await _post(client, "query")
        _set_threshold(monkeypatch, "pool")
        monkeypatch.setattr(server_main, "_grids_text", held)
        before = counted()
        cancelled = asyncio.ensure_future(client.post(
            path, json=body, headers={"X-Deadline-Ms": "400"}))
        assert await loop.run_in_executor(None, started.wait, 30.0)
        r = await cancelled
        assert r.status == 504
        # the job is still on its thread and has counted nothing
        assert counted() == before
        monkeypatch.setattr(server_main, "_grids_text", grids_text)
        release.set()
        answered = await _post(client, "query")
        assert answered == sound
        for _ in range(200):  # the cancelled job's own counts
            if _moved(before[0])["pool"] == 2:
                break
            await asyncio.sleep(0.01)
        (where, (cells, nbytes)), (_, (cells0, bytes0)) = counted(), before
        assert _moved(before[0]) == {"pool": 2, "loop": 0}
        assert nbytes - bytes0 == 2 * len(sound[3])
        grids = json.loads(sound[3])["aggs"]
        assert cells - cells0 == 2 * sum(np.size(g) for g in grids.values())
        assert queue_depths()["sst"] == 0

    try:
        asyncio.run(_served(go))
    finally:
        release.set()
