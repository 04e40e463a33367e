"""Each declarative source kind on synthetic observations."""

import pytest

from benchmark.harness import layers, roofline

OBS = {
    "spans": {"total": [10.0, 20.0, 30.0], "downsample": [7.0, 15.0, 22.0],
              "resolve": [1.0, 1.0, 2.0]},
    "counters": {"device.calls": 40.0, "device.compiles": 0.0,
                 "device.transfer.h2d.bytes": 30e6,
                 "stats.m.hits": 1.0, "stats.m.misses": 3.0},
    "queries": 10,
    "trace": {"devices": 1, "window_s": 2.0, "busy_s": 1.5,
              "op_seconds": 1.4, "queries": 20},
    "scan": {"min_seconds_per_query": 0.007},
}


def reader(**source):
    return {"source": source}


@pytest.mark.parametrize("source,want", [
    (dict(kind="span", span="downsample"), 15.0),
    (dict(kind="span_residual", of="total",
          minus=["downsample", "resolve"]), 4.0),
    (dict(kind="counter", counters=["device.calls"], per="query"), 4.0),
    (dict(kind="counter", counters=["device.transfer.h2d.bytes"],
          per="query", scale=1e-6), 3.0),
    (dict(kind="counter", counters=["device.compiles"]), 0.0),
    (dict(kind="ratio", num=["stats.m.hits"],
          den=["stats.m.hits", "stats.m.misses"], scale=100.0), 25.0),
    (dict(kind="ratio", num=["device.compiles"], den=["device.compiles"],
          if_no_events=0.0), 0.0),
    (dict(kind="trace", reduction="busy_pct"), 75.0),
    (dict(kind="trace", reduction="op_ms_per_query"), 70.0),
    (dict(kind="trace", reduction="scan_roofline_pct"), 10.0),
])
def test_source_kinds(source, want):
    assert layers.evaluate(reader(**source), OBS) == pytest.approx(want)


@pytest.mark.parametrize("source", [
    dict(kind="span", span="absent"),
    dict(kind="counter", counters=["absent"]),
    dict(kind="ratio", num=["stats.m.hits"], den=["absent"]),
    dict(kind="ratio", num=["device.compiles"], den=["device.compiles"]),
])
def test_nothing_to_read_returns_nothing(source):
    assert layers.evaluate(reader(**source), OBS) is None
    assert layers.evaluate(reader(kind="trace", reduction="busy_pct"),
                           dict(OBS, trace={"devices": 0})) is None


def test_unknown_kind_is_an_error():
    with pytest.raises(ValueError):
        layers.evaluate(reader(kind="guess"), OBS)


def test_roofline_bytes_and_peaks():
    # 432,000 rows x 12 B + 100 x 12 x 7 grids x 4 B
    assert roofline.scan_min_bytes(432_000, 12, 100, 12, 7) == 5_217_600
    assert roofline.least_seconds(819e9, "TPU v5 lite") == pytest.approx(1.0)
    with pytest.raises(KeyError):
        roofline.peaks("some other chip")


class _Tasks:
    """A server stub answering GET /debug/tasks."""

    def __init__(self, loops):
        self.loops = loops

    def get_json(self, path):
        assert path == "/debug/tasks"
        return {"loops": self.loops}


def _loops(picker_age=0.2, executor_idle=True, **backlog):
    backlog = {"pending_tasks": 0, "pending_triggers": 0,
               "inused_memory": 0, **backlog}
    return [
        {"kind": "watchdog", "idle": False, "last_success_age_s": 9.0},
        {"kind": "compact-picker", "idle": False,
         "last_success_age_s": picker_age, "backlog": backlog},
        {"kind": "compact-executor", "idle": executor_idle,
         "last_success_age_s": None, "backlog": backlog}]


@pytest.mark.parametrize("loops,busy", [
    (_loops(), False),                          # picked since, all parked
    (_loops(picker_age=None), True),            # the picker never ran
    (_loops(picker_age=3.0), True),             # its last pick was before
    (_loops(pending_tasks=1), True),
    (_loops(pending_triggers=1), True),
    (_loops(inused_memory=4096), True),
    (_loops(executor_idle=False), True),        # a rewrite under way
])
def test_compaction_at_rest_is_read_from_the_task_table(loops, busy):
    from benchmark.harness import counters

    assert counters.compaction_busy(_Tasks(loops), asked_s=1.0) is busy
