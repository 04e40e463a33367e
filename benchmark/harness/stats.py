"""The arithmetic behind the end-to-end metrics: percentiles, the
completion rate, the percentile a sample supports, and the
per-quarter stationarity table."""

from __future__ import annotations


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default rule)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def supported_percentile(n: int, ladder=(50, 90, 95, 99)) -> int | None:
    """The highest percentile of the ladder with at least ten samples
    beyond it (choosing-metrics §1): p95 wants 200, p99 wants 1000."""
    best = None
    for q in ladder:
        if n * (100 - q) / 100.0 >= 10:
            best = q
    return best


def window_rate(requests, t_start: float, t_end: float) -> float:
    """Queries' worth of work done inside the window, per second of
    it.  `requests` are the (t_send, t_done) pairs of the good answers,
    those in flight at either edge included; each counts by the share
    of its own time that lies inside the window, so the sum is all the
    work of the window and the divisor all of its time.  A stall shows
    wherever it falls, the edges included: the requests it holds up
    grow longer, and less of each lies inside.  And where the edges
    fall between completions moves nothing: whole completions /
    seconds, printed beside it, steps by clients / completions."""
    if t_end <= t_start:
        raise ValueError("a rate needs a window of some length")
    work = 0.0
    for t_send, t_done in requests:
        inside = min(t_done, t_end) - max(t_send, t_start)
        if inside > 0.0:
            work += inside / (t_done - t_send)
    return work / (t_end - t_start)


def quarters(samples, t_start: float, t_end: float) -> list[dict]:
    """Completions and median latency per quarter of the window;
    `samples` are (t_done, latency_s) pairs inside it."""
    out = []
    width = (t_end - t_start) / 4.0
    for k in range(4):
        lo, hi = t_start + k * width, t_start + (k + 1) * width
        lats = [lat for t, lat in samples
                if lo <= t < hi or (k == 3 and t == hi)]
        out.append({"quarter": k + 1, "completions": len(lats),
                    "p50_ms": (percentile(lats, 50) * 1e3
                               if lats else None)})
    return out


def longest_gaps(done_times, t_start: float, top: int = 3) -> list[dict]:
    """The longest waits between consecutive completions, with their
    offset into the window: a stall of the served path shows here."""
    ts = sorted(done_times)
    gaps = sorted(((b - a, a) for a, b in zip(ts, ts[1:])), reverse=True)
    return [{"gap_ms": g * 1e3, "at_s": a - t_start} for g, a in gaps[:top]]
