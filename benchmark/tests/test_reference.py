"""The numpy reference at arbitrary phase against a brute-force loop,
the control's rounding, and the tsid rule against the program's."""

import json

import numpy as np
import pytest

from benchmark.harness.dataset import AGGS, Dataset, round_bf16
from benchmark.harness.seahash import hash64
from benchmark.operations import groupby
from benchmark.tests.helpers import REPO, read_json

CONFIG = dict(read_json(f"{REPO}/benchmark/configs/tsbs-devops-cpu-s100.json"),
              scale=7, span_ms=6 * 3_600_000)


def brute_force(data, start, end, bucket_ms, hosts):
    nb = -(-(end - start) // bucket_ms)
    out = {a: np.full((len(hosts), nb), np.nan) for a in AGGS}
    out["count"][:] = 0
    for hi, h in enumerate(hosts):
        cells = [[] for _ in range(nb)]
        for t in range(data.ticks):
            ts = data.t0 + t * data.interval_ms
            if start <= ts < end:
                cells[(ts - start) // bucket_ms].append(
                    float(data.grid[t, h]))
        for b, vals in enumerate(cells):
            if vals:
                out["count"][hi, b] = len(vals)
                out["sum"][hi, b] = sum(vals)
                out["avg"][hi, b] = sum(vals) / len(vals)
                out["min"][hi, b] = min(vals)
                out["max"][hi, b] = max(vals)
                out["last"][hi, b] = vals[-1]
    return out


@pytest.mark.parametrize("offset,window,bucket", [
    (0, 3_600_000, 300_000),              # on the grid
    (1, 3_600_000, 300_000),              # one millisecond off it
    (1_234_567, 3_600_000, 300_000),      # arbitrary phase
    (9_999, 2 * 3_600_000, 3_600_000),    # just before a tick
    (5 * 3_600_000 + 1_800_001, 3_600_000, 700_000),  # runs off the data,
])                                        # bucket no multiple of the tick
def test_groupby_matches_brute_force(offset, window, bucket):
    data = Dataset(CONFIG, seed=11)
    start = data.t0 + offset
    hosts = [3, 0, 6]
    got = data.groupby(start, start + window, bucket, hosts=hosts)
    want = brute_force(data, start, start + window, bucket, hosts)
    for a in AGGS:
        np.testing.assert_allclose(got[a], want[a], rtol=1e-12,
                                   err_msg=a, equal_nan=True)


def test_values_are_float32_walk_in_range_and_seeded():
    a, b = Dataset(CONFIG, seed=2**31 + 5), Dataset(CONFIG, seed=2**31 + 5)
    c = Dataset(CONFIG, seed=2**31 + 6)
    assert a.grid.dtype == np.float32 and np.array_equal(a.grid, b.grid)
    assert not np.array_equal(a.grid, c.grid)
    assert a.grid.min() >= 0.0 and a.grid.max() <= 100.0
    steps = np.diff(a.grid.astype(np.float64), axis=0)
    assert 0.8 < steps.std() < 1.2      # N(0,1) steps, folded at the walls


def test_round_bf16_keeps_eight_bits():
    x = np.array([1.0, 1.00390625, 1.005, 99.99, 0.0, 3.14159],
                 dtype=np.float32)
    r = round_bf16(x)
    assert (r.view(np.uint32) & 0xFFFF == 0).all()
    assert np.abs(r - x).max() <= np.abs(x).max() * 2.0 ** -8
    assert r[0] == 1.0 and r[4] == 0.0


def test_seahash_copy_is_the_programs():
    from horaedb_tpu.common.seahash import _hash64_py

    rng = np.random.default_rng(0)
    for n in (0, 1, 7, 8, 9, 31, 32, 33, 64, 100):
        buf = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        assert hash64(buf) == _hash64_py(buf)


def test_tsid_rule_is_the_programs_for_a_full_tag_set():
    from horaedb_tpu.metric_engine.types import Label
    from horaedb_tpu.metric_engine.types import tsid_of as program_tsid

    data = Dataset(CONFIG, seed=3)
    for h in (0, 3, 6):
        labels = {t: data.tag_values[t][data.tag_codes[t][h]]
                  for t in data.tags}
        assert labels["hostname"] == f"host_{h}"
        want = program_tsid("cpu", [Label(k, v) for k, v in labels.items()])
        assert data.tsid_of_host[h] == str(int(want))
    assert len(set(data.tsid_of_host)) == data.hosts


def test_rows_have_the_sources_ten_tags_and_ten_fields():
    import pyarrow as pa
    from pyarrow import ipc

    data = Dataset(CONFIG, seed=2**31 + 9)
    spec = {t["name"]: t for t in CONFIG["tags"]}
    assert list(spec) == ["hostname", "region", "datacenter", "rack", "os",
                          "arch", "team", "service", "service_version",
                          "service_environment"]
    assert len(data.fields) == 10 and data.field == "usage_user"
    for h in range(data.hosts):
        region = data.tag_values["region"][data.tag_codes["region"][h]]
        dc = data.tag_values["datacenter"][data.tag_codes["datacenter"][h]]
        assert dc in spec["datacenter"]["choices"][region]
        assert 0 <= int(data.tag_values["rack"][data.tag_codes["rack"][h]]) \
            < 100
    bodies = list(data.bodies(5, 9))
    assert [f for f, _ in bodies] == data.fields
    for k, (_, body) in enumerate(bodies):
        t = ipc.open_stream(body).read_all()
        assert t.column_names == data.tags + ["timestamp", "value"]
        assert t.num_rows == 4 * data.hosts
        assert t.column("hostname").to_pylist()[:data.hosts] == \
            data.host_names
        assert t.column("timestamp").to_pylist()[data.hosts] == \
            data.t0 + 6 * data.interval_ms
        assert np.array_equal(t.column("value").to_numpy(),
                              data.values[k, 5:9].reshape(-1)
                              .astype(np.float64))
        assert t.schema.field("value").type == pa.float64()
    # ten walks, not one repeated
    assert not np.array_equal(data.values[0], data.values[1])


def _answer(data, q, values=None):
    """A server's JSON answer made from the reference itself."""
    order = list(range(data.hosts)) if q["hosts"] is None else q["hosts"]
    ref = data.groupby(q["start"], q["end"], q["bucket_ms"], hosts=order,
                       values=values)
    return json.dumps({
        "tsids": [data.tsid_of_host[h] for h in order],
        "aggs": {a: ref[a].tolist() for a in AGGS}}).encode()


def test_check_passes_the_reference_and_fails_the_control():
    data = Dataset(CONFIG, seed=5)
    traffic = read_json(f"{REPO}/benchmark/traffic/single-groupby-1-1-1.json")
    qs = groupby.make_queries(traffic, data, np.random.default_rng(1), 20)
    sound = groupby.combine(
        [groupby.check(q, _answer(data, q), data) for q in qs])
    assert all(sound[k] <= traffic["limits"][k] for k in traffic["limits"])
    low = round_bf16(data.grid)
    control = groupby.combine(
        [groupby.check(q, _answer(data, q, values=low), data) for q in qs])
    assert control["select_mismatch_cells"] > 0
    assert control["sum_avg_max_rel_err"] > traffic["limits"][
        "sum_avg_max_rel_err"]
    assert control["count_mismatch_cells"] == 0
    broken = groupby.check(qs[0], b"{not json", data)
    assert broken["malformed_responses"] == 1


def test_queries_keep_to_the_span_and_the_seed():
    data = Dataset(CONFIG, seed=5)
    for mix in ("single-groupby-1-1-1", "double-groupby-1"):
        traffic = read_json(f"{REPO}/benchmark/traffic/{mix}.json")
        traffic["window_ms"] = min(traffic["window_ms"], 4 * 3_600_000)
        a = groupby.make_queries(traffic, data,
                                 np.random.default_rng(9), 200)
        b = groupby.make_queries(traffic, data,
                                 np.random.default_rng(9), 200)
        assert [q["body"] for q in a] == [q["body"] for q in b]
        assert all(q["start"] >= data.t0
                   and q["end"] <= data.t0 + data.span_ms for q in a)
        assert len({q["end"] - q["start"] for q in a}) == 1
        # starts fall at the granule, not on a coarse grid
        assert len({q["start"] % 60_000 for q in a}) > 100
        sweep = groupby.sweep_queries(traffic, data)
        assert sweep[-1]["end"] == data.t0 + data.span_ms
        if traffic["hosts"] == 1:
            # every host's window of every 2 h segment is touched
            seg = 7_200_000
            touched = {(q["hosts"][0], k)
                       for q in sweep
                       for k in range((q["start"] - data.t0) // seg,
                                      (q["end"] - 1 - data.t0) // seg + 1)}
            assert touched == {(h, k) for h in range(data.hosts)
                               for k in range(data.span_ms // seg)}
