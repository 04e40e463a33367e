"""The trace reduction on a small trace recorded on a TPU v5e
(benchmark/tests/data/tiny.xplane.pb.gz: three rounds of two jitted
programs, each round after a 4 ms host span `prepare_<i>`; python
tracer off), and its pieces on synthetic planes."""

import os

import pytest

from benchmark.harness import xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "tiny.xplane.pb.gz")


@pytest.fixture(scope="module")
def planes():
    return xplane.load_planes(FIXTURE)


def test_recorded_trace_has_a_device_plane_with_an_ops_line(planes):
    device = [p for p in planes if p["name"] == "/device:TPU:0"]
    assert len(device) == 1
    assert any(line["name"] == xplane.OPS_LINE and line["events"]
               for line in device[0]["lines"])


def test_busy_union_and_kernel_sum(planes):
    out = xplane.reduce_planes(planes)
    assert out["devices"] == 1 and out["op_events"] == 27
    # on one core operations do not overlap: the union is their sum
    assert out["busy_s"] == pytest.approx(out["op_seconds"], rel=1e-6)
    assert 0 < out["busy_s"] < out["window_s"] < 0.1
    names = [n for n, _s in out["device_ops"]]
    assert names[0].startswith("sort.6")       # the sort dominates
    assert sum(s for _n, s in out["device_ops"]) == \
        pytest.approx(out["op_seconds"], rel=1e-6)
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def test_gaps_are_named_after_the_host_span_that_covers_them(planes):
    out = xplane.reduce_planes(planes)
    gaps = out["idle_gaps"]
    assert [s for _n, s in gaps] == sorted((s for _n, s in gaps),
                                           reverse=True)
    # the window opens at the first device operation, after prepare_0:
    # the two longest idle gaps are the other two `prepare_<i>` sleeps
    assert sorted(n for n, _s in gaps[:2]) == ["prepare_1", "prepare_2"]
    assert all(s > 0.003 for _n, s in gaps[:2])
    assert all(s < 0.003 for _n, s in gaps[2:])


def test_union_merges_overlaps_and_touching_intervals():
    assert xplane.union_intervals([(5, 7), (0, 2), (1, 3), (3, 4)]) == [
        (0, 4), (5, 7)]
    assert xplane.union_intervals([]) == []


def test_attribution_prefers_the_most_specific_cover():
    host = [("outer", 0.0, 100.0), ("inner", 10.0, 20.0),
            ("sliver", 12.0, 1.0)]
    assert xplane.attribute_gap(11.0, 29.0, host) == "inner"
    assert xplane.attribute_gap(40.0, 60.0, host) == "outer"
    assert xplane.attribute_gap(200.0, 300.0, host) == "unattributed"
    waiting = [("$selectors.py:451 select", 0.0, 50.0),
               ("$read.py:2480 _fused_run_device_rounds", 0.0, 90.0)]
    assert xplane.attribute_gap(10.0, 40.0, waiting) == \
        "$read.py:2480 _fused_run_device_rounds"


def test_synthetic_two_device_planes_average_busy():
    def plane(name, events):
        return {"name": name, "lines": [{"name": xplane.OPS_LINE,
                                         "events": events}]}
    planes = [
        plane("/device:TPU:0", [("%a = f32[8]{0} fusion()", 0.0, 4e9),
                                ("%b = f32[8]{0} fusion()", 2e9, 4e9)]),
        plane("/device:TPU:1", [("%a = f32[8]{0} fusion()", 0.0, 2e9)]),
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ("$queue.py:171 get", 0.0, 20e9),       # an idle pool thread
            ("$read.py:9 prepare_windows", 2.5e9, 3e9),
            ("late", 7e9, 3e9)]}]},
    ]
    out = xplane.reduce_planes(planes)
    # the window is the device operations' extent, not the host's
    assert out["devices"] == 2 and out["window_s"] == pytest.approx(6.0)
    assert out["busy_s"] == pytest.approx((6.0 + 2.0) / 2)
    assert out["op_seconds"] == pytest.approx(10.0)
    assert out["device_ops"][0] == ["a f32[8]", pytest.approx(6.0)]
    assert out["idle_gaps"] == [
        ["$read.py:9 prepare_windows", pytest.approx(4.0)]]


def test_a_trace_without_device_planes_reads_nothing():
    out = xplane.reduce_planes([{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [("f", 0.0, 1e9)]}]}])
    assert out["devices"] == 0 and "busy_s" not in out


def test_short_name():
    hlo = ("%fusion.16 = s32[8200]{0:T(1024)} fusion(s32[1048576]{0} %p), "
           "kind=kLoop")
    assert xplane.short_name(hlo) == "fusion.16 s32[8200]"
    assert xplane.short_name("plain") == "plain"
