"""The server's own counters, read once before and once after the
window of a traced run and never inside it: GET /debug/device (per-fn
compile and dispatch ledger, transfer bytes), GET /metrics (Prometheus
text) and GET /stats (cache and memo counts), flattened to one
name -> number table so that a per-layer reader can name any of them;
and the span trees of the window's queries from GET /debug/traces."""

from __future__ import annotations

import re

_SAMPLE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{[^}]*\})? (\S+)$")


def _flatten(prefix: str, node, out: dict) -> None:
    if isinstance(node, dict):
        for k, v in node.items():
            _flatten(f"{prefix}.{k}", v, out)
    elif isinstance(node, bool):
        return
    elif isinstance(node, (int, float)):
        out[prefix] = float(node)


def parse_metrics(text: str, out: dict) -> None:
    """Every sample as `metrics.<name>{labels}`, and every family's sum
    over its labels as `metrics.<name>`."""
    for line in text.splitlines():
        m = _SAMPLE.match(line)
        if not m:
            continue
        try:
            value = float(m.group(3))
        except ValueError:
            continue
        name = f"metrics.{m.group(1)}"
        if m.group(2):
            out[name + m.group(2)] = value
        out[name] = out.get(name, 0.0) + value


def read(server) -> dict:
    """One flat snapshot of all three endpoints."""
    out: dict = {}
    dev = server.get_json("/debug/device")
    calls = compiles = 0
    for f in dev["fns"]:
        n = f["compiles"] + f["dispatches"]
        out[f"device.fn.{f['fn']}.calls"] = float(n)
        out[f"device.fn.{f['fn']}.compiles"] = float(f["compiles"])
        calls += n
        compiles += f["compiles"]
    out["device.calls"] = float(calls)
    out["device.compiles"] = float(compiles)
    for direction, t in dev["transfer"].items():
        out[f"device.transfer.{direction}.bytes"] = float(t["bytes"])
        out[f"device.transfer.{direction}.count"] = float(t["count"])
    parse_metrics(server.request("GET", "/metrics").decode(), out)
    _flatten("stats", server.get_json("/stats"), out)
    return out


def compiles(server) -> int:
    """The compile ledger's total (warm-up's stillness test)."""
    return sum(f["compiles"]
               for f in server.get_json("/debug/device")["fns"])


def compactions(server) -> int:
    """Compaction tasks completed so far, over all tables."""
    out: dict = {}
    parse_metrics(server.request("GET", "/metrics").decode(), out)
    return int(out.get("metrics.compaction_completed_total", 0))


def compaction_busy(server, asked_s: float) -> bool:
    """Whether compaction asked for `asked_s` seconds ago may still be
    under way in any table: a picker that has not finished a pick
    since, a queued task, an unanswered trigger, an executor that is
    not parked or holds a rewrite's memory."""
    for loop in server.get_json("/debug/tasks")["loops"]:
        if loop["kind"] == "compact-picker":
            age = loop["last_success_age_s"]
            if age is None or age > asked_s:
                return True
        elif loop["kind"] == "compact-executor" and not loop["idle"]:
            return True
        if (loop["kind"].startswith("compact")
                and any(loop.get("backlog", {}).values())):
            return True
    return False


def compile_keys(server) -> dict:
    """{fn: last compile key}: names the shape that compiled when a
    window's compile count is not 0."""
    return {f["fn"]: f["last_key"]
            for f in server.get_json("/debug/device")["fns"]
            if f["compiles"]}


def delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def query_spans(server, endpoint: str, limit: int = 256) -> dict:
    """{span name: [ms per query]} over the newest traces of `endpoint`
    still in the server's ring (256 by default), `total` being the
    root span: host-clock spans the program records itself."""
    listing = server.get_json(f"/debug/traces?limit={limit}&kind=query")
    spans: dict = {}
    n = 0
    for summary in listing["traces"]:
        if summary["root"] != endpoint or summary["status"] != "ok":
            continue
        tree = server.get_json(f"/debug/traces/{summary['trace_id']}")["tree"]
        per = {"total": tree["duration_ms"]}
        for child in tree["children"]:
            per[child["name"]] = (per.get(child["name"], 0.0)
                                  + child["duration_ms"])
        for name in set(spans) | set(per):
            spans.setdefault(name, [0.0] * n).append(per.get(name, 0.0))
        n += 1
    return spans
