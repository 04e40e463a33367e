"""The completion rate, the percentile rule and the quarter table on
synthetic completion times."""

import pytest

from benchmark.harness import stats


def _closed_loop(period, t0, t1, stall=None):
    """One client, one request after another, each `period` long; a
    `stall` (from, to) holds up whatever is in flight."""
    out, t = [], t0
    while t < t1:
        done = t + period
        if stall and t < stall[1] and done > stall[0]:
            done += stall[1] - max(stall[0], t)
        out.append((t, done))
        t = done
    return out


def test_rate_is_all_the_work_over_the_whole_window():
    # 2 a second, steadily, the loop running before and after the window
    reqs = _closed_loop(0.5, 90.0, 120.0)
    assert stats.window_rate(reqs, 100.0, 110.0) == pytest.approx(2.0)
    # wherever the edges fall between completions
    assert stats.window_rate(reqs, 100.2, 110.33) == pytest.approx(2.0)
    # whole completions / seconds steps with the edges
    n = sum(1 for _, d in reqs if 100.2 <= d <= 110.33)
    assert n / 10.13 != pytest.approx(2.0, rel=0.005)


@pytest.mark.parametrize("stall", [(104.0, 107.0),      # mid-window
                                   (108.0, 111.0),      # over the end
                                   (98.5, 101.5)])      # over the start
def test_rate_sees_a_stall_wherever_it_falls(stall):
    reqs = _closed_loop(0.5, 90.0, 125.0, stall)
    lost = min(stall[1], 110.0) - max(stall[0], 100.0)
    got = stats.window_rate(reqs, 100.0, 110.0)
    # the held-up request's work is spread evenly over its lengthened
    # time, so a stall over an edge shows to within a fifth of itself
    exact = 2.0 * (10.0 - lost) / 10.0
    assert abs(got - exact) <= 0.2 * (2.0 - exact) + 1e-9
    # the intervals between the first and the last completion inside
    # the window miss a stall that overlaps an edge
    if stall[0] < 100.0 or stall[1] > 110.0:
        done = [d for _, d in reqs if 100.0 <= d <= 110.0]
        assert (len(done) - 1) / (done[-1] - done[0]) == pytest.approx(2.0)


def test_rate_needs_a_window():
    with pytest.raises(ValueError):
        stats.window_rate([(1.0, 2.0)], 5.0, 5.0)


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (100, 5.0),
                                    (95, 4.8), (25, 2.0)])
def test_percentile_interpolates(q, want):
    assert stats.percentile([5.0, 1.0, 4.0, 2.0, 3.0], q) == \
        pytest.approx(want)


@pytest.mark.parametrize("n,want", [(19, None), (20, 50), (100, 90),
                                    (199, 90), (200, 95), (999, 95),
                                    (1000, 99)])
def test_supported_percentile_wants_ten_samples_beyond(n, want):
    assert stats.supported_percentile(n) == want


def test_quarters_split_the_window():
    samples = [(t + 0.5, 0.1 * (1 + t // 10)) for t in range(40)]
    table = stats.quarters(samples, 0.0, 40.0)
    assert [q["completions"] for q in table] == [10, 10, 10, 10]
    assert [round(q["p50_ms"]) for q in table] == [100, 200, 300, 400]
    empty = stats.quarters([], 0.0, 4.0)
    assert all(q["completions"] == 0 and q["p50_ms"] is None for q in empty)


def test_longest_gaps_find_a_stall():
    done = [10.0 + 0.1 * i for i in range(50)] + [19.0 + 0.1 * i
                                                  for i in range(10)]
    gaps = stats.longest_gaps(done, t_start=10.0, top=2)
    assert gaps[0]["gap_ms"] == pytest.approx(4100.0)
    assert gaps[0]["at_s"] == pytest.approx(4.9)
    assert gaps[1]["gap_ms"] == pytest.approx(100.0)
