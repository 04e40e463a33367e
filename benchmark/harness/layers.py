"""Per-layer metric readers.  Each file benchmark/layer_metrics/<name>.json
declares where its number comes from; `evaluate` takes it from what a
traced run observed:

    obs = {"spans":    {span name: [ms per query]},
           "counters": {flat counter name: delta over the window},
           "queries":  completions between the two counter reads,
           "trace":    the xplane reduction (+ "queries" in its window),
           "scan":     {"min_seconds_per_query": ...}}

Source kinds: `span` (median of one span), `span_residual` (median of
one span minus others, per query), `counter` (sum of counter deltas,
optionally per query, times `scale`), `ratio` (sum of `num` over sum of
`den`, times `scale`; `if_no_events` where `den` did not move), `trace` (a named reduction of the device trace).
A reader that finds nothing to read returns None and the metric is left
out of the line.
"""

from __future__ import annotations

from benchmark.harness.stats import percentile


def _sum(counters: dict, names) -> float | None:
    found = [counters[n] for n in names if n in counters]
    return sum(found) if found else None


def _trace(name: str, obs: dict) -> float | None:
    tr = obs.get("trace") or {}
    if not tr.get("devices") or "busy_s" not in tr:
        return None
    if name == "busy_pct":
        return 100.0 * tr["busy_s"] / tr["window_s"]
    queries = tr.get("queries") or 0
    if not queries:
        return None
    per_query_s = tr["op_seconds"] / tr["devices"] / queries
    if name == "op_ms_per_query":
        return per_query_s * 1e3
    if name == "scan_roofline_pct":
        least = (obs.get("scan") or {}).get("min_seconds_per_query")
        if not least or per_query_s <= 0:
            return None
        return 100.0 * least / per_query_s
    raise ValueError(f"unknown trace reduction {name!r}")


def evaluate(reader: dict, obs: dict) -> float | None:
    src = reader["source"]
    kind = src["kind"]
    spans = obs.get("spans") or {}
    counters = obs.get("counters") or {}
    if kind == "span":
        xs = spans.get(src["span"])
        return percentile(xs, 50) if xs else None
    if kind == "span_residual":
        base = spans.get(src["of"])
        if not base:
            return None
        rest = [spans.get(name) or [0.0] * len(base)
                for name in src["minus"]]
        return percentile(
            [b - sum(r[i] for r in rest) for i, b in enumerate(base)], 50)
    if kind == "counter":
        total = _sum(counters, src["counters"])
        if total is None:
            return None
        if src.get("per") == "query":
            if not obs.get("queries"):
                return None
            total /= obs["queries"]
        return total * src.get("scale", 1.0)
    if kind == "ratio":
        num, den = _sum(counters, src["num"]), _sum(counters, src["den"])
        if num is None or den is None:
            return None
        if not den:
            # the counters are there and did not move: the reader file
            # says what that reads as (a hit share with no probe: 0)
            return src.get("if_no_events")
        return num / den * src.get("scale", 1.0)
    if kind == "trace":
        return _trace(src["reduction"], obs)
    raise ValueError(f"unknown source kind {kind!r}")
