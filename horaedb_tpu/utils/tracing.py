"""Request-scoped tracing: trace IDs, tree-structured spans, a ring of
recent traces, and a slow-query log.

The reference uses field-style tracing events (tracing + EnvFilter,
SURVEY.md section 5) without spans; here spans are first-class and
request-scoped (docs/observability.md):

- every query/write through the HTTP server gets a `trace_id`
  (returned as the `X-Trace-Id` response header);
- `span(name, **fields)` records a real span (span_id/parent_id/
  status/fields) into the ambient trace when one is active — and
  observes its latency histogram either way, so background loops
  (compaction, manifest merge) stay observable without a trace; it
  also enters a `jax.profiler.TraceAnnotation` named `horaedb/<name>`,
  so while a profiler session runs the program's spans lie in the
  xplane on the same clock as the device's operations; a span that
  declares `sync=True` (no `await` inside) may also carry `cpu_ms`,
  its own thread's CPU beside its wall (one such span in four reads
  the clock);
- `phase(name, table, **fields)` is a span of one scan phase (the
  children of `downsample`): its histogram is the labelled family
  `scan_phase_seconds{phase=,table=}`, so the data table's scans are
  told apart from `resolve`'s scans of the index tables, and its CPU
  adds to `scan_phase_cpu_seconds_total{phase=,table=}`;
- `trace_add(name, n)` attributes counted work (object-store GETs and
  bytes, cache tier hits, per-stage wall time) to the active trace;
- the trace context propagates across regions via the `X-Trace-Id`
  request header, and a downstream region exports its recorded spans
  back on the `X-Trace-Export` response header, so a scatter-gathered
  query yields ONE stitched distributed trace on the coordinator;
- completed traces land in a bounded ring (`GET /debug/traces`,
  `/debug/traces/{id}`), and traces over the slow threshold — or ones
  that died on their deadline — hit the slow-query log plus the
  `slow_queries_total` counter.

Context propagates through asyncio tasks natively and into the named
worker pools via `common.runtimes` (which copies the contextvars
context onto the pool thread), so stage attribution recorded inside
parquet decode / merge workers still lands on the right trace.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import logging
import os
import random
import threading
import time
from collections import OrderedDict
from typing import Iterator, Optional

from horaedb_tpu.utils.metrics import registry

logger = logging.getLogger("horaedb_tpu.trace")
slow_logger = logging.getLogger("horaedb_tpu.trace.slow")

TRACE_HEADER = "X-Trace-Id"
EXPORT_HEADER = "X-Trace-Export"

# aiohttp caps a header line at 8190 bytes; exports stay safely under
EXPORT_LIMIT = 7000

_SLOW_QUERIES = registry.counter(
    "slow_queries_total",
    "traced requests over the slow threshold (or deadline-exceeded)")
# ops get their OWN slow counter: an 11-minute compaction is slow, but
# it is not a slow QUERY — alerts on slow_queries_total must not fire
# during routine maintenance
_SLOW_OPS = registry.counter(
    "slow_ops_total",
    "background-op traces over their per-op slow threshold")
_TRACES_RECORDED = registry.counter(
    "traces_recorded_total", "traces completed into the trace ring")

_current_trace: contextvars.ContextVar[Optional["Trace"]] = \
    contextvars.ContextVar("horaedb_trace", default=None)
_current_span_id: contextvars.ContextVar[str] = contextvars.ContextVar(
    "horaedb_span_id", default="")

# ids only need uniqueness, not secrecy; one process-wide PRNG seeded
# from urandom, guarded for thread use
_id_rng = random.Random(int.from_bytes(os.urandom(8), "big"))
_id_lock = threading.Lock()


def new_trace_id() -> str:
    with _id_lock:
        return f"{_id_rng.getrandbits(64):016x}"


# span ids come from a counter that starts at a random point of the
# 32-bit ring: next() on it is atomic under the GIL, so the hot path
# takes no lock, and two processes whose spans are stitched into one
# trace collide as rarely as with random ids
_span_seq = itertools.count(int.from_bytes(os.urandom(4), "big"))


def _new_span_id() -> str:
    return f"{next(_span_seq) & 0xFFFFFFFF:08x}"


def active_trace() -> Optional["Trace"]:
    """The ambient trace, or None outside a traced request."""
    return _current_trace.get()


def current_trace_id() -> str:
    trace = _current_trace.get()
    return trace.trace_id if trace is not None else ""


class Trace:
    """One request's (or background operation's) span buffer +
    counters.  Thread-safe: spans and counts arrive from the event loop
    AND worker-pool threads.  After `finish()` the trace is immutable —
    late adds (a straggler task outliving its request) are dropped, so
    work done after the query ended is attributed to nothing.

    `kind` separates the two trace populations: "query" (HTTP
    query/write requests, the PR-5 surface) and "op" (background
    operations — compaction, flush, WAL commit rounds, rollup passes,
    scrub, health rounds; docs/observability.md, background plane).
    Op traces carry the op name in `op` and may override the recorder's
    slow threshold per-op via `slow_threshold_s`."""

    __slots__ = ("trace_id", "name", "kind", "op", "slow_threshold_s",
                 "root_fields", "root_span_id", "start_ms", "_t0",
                 "spans", "counters", "open_fields", "finished", "_lock")

    def __init__(self, trace_id: str, name: str, kind: str = "query",
                 op: str = "", slow_threshold_s: Optional[float] = None,
                 root_fields: Optional[dict] = None):
        self.trace_id = trace_id
        self.name = name
        self.kind = kind
        self.op = op
        self.slow_threshold_s = slow_threshold_s
        self.root_fields = dict(root_fields or {})
        self.root_span_id = _new_span_id()
        self.start_ms = time.time() * 1e3
        self._t0 = time.perf_counter()
        self.spans: list[dict] = []
        self.counters: dict[str, float] = {}
        # span id -> the fields of a span still open (span_note)
        self.open_fields: dict[str, dict] = {}
        self.finished = False
        self._lock = threading.Lock()

    def record(self, span_dict: dict) -> None:
        with self._lock:
            if not self.finished:
                self.spans.append(span_dict)

    def add(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            if not self.finished:
                self.counters[name] = self.counters.get(name, 0) + value

    # stitching bounds: a trace must stay ring-sized and exportable no
    # matter what its downstream peers send
    _IMPORT_MAX_SPANS = 512
    _IMPORT_MAX_COUNTERS = 256

    def import_remote(self, payload: dict, parent_id: str) -> None:
        """Stitch a downstream region's exported spans under
        `parent_id` (the RPC span that fetched them): remote roots —
        spans whose parent is not in the export — are reparented, and
        the remote's counters fold into ours.  Defensive by contract:
        entries that aren't span-shaped are skipped and both spans and
        counters are bounded — a peer on another version (or anything
        else answering that port) must never be able to blow up or
        bloat the coordinator's trace."""
        spans = payload.get("spans")
        if not isinstance(spans, list):
            spans = []
        spans = [s for s in spans if isinstance(s, dict)]
        ids = {s.get("span_id") for s in spans}
        with self._lock:
            if self.finished:
                return
            budget = self._IMPORT_MAX_SPANS - len(self.spans)
            for s in spans[:max(0, budget)]:
                if s.get("parent_id") not in ids:
                    s = dict(s, parent_id=parent_id)
                self.spans.append(s)
            counters = payload.get("counters")
            for k, v in (counters.items()
                         if isinstance(counters, dict) else ()):
                if not isinstance(v, (int, float)) \
                        or isinstance(v, bool):
                    continue
                if (k not in self.counters
                        and len(self.counters) >= self._IMPORT_MAX_COUNTERS):
                    continue
                self.counters[k] = self.counters.get(k, 0) + v

    def finish(self, status: str = "ok") -> dict:
        with self._lock:
            if self.finished:  # idempotent: first finish wins
                return self.to_dict_locked()
            duration_ms = (time.perf_counter() - self._t0) * 1e3
            self.spans.append({
                "span_id": self.root_span_id, "parent_id": "",
                "name": self.name, "start_ms": round(self.start_ms, 3),
                "duration_ms": round(duration_ms, 3), "status": status,
                "fields": {k: _field(v)
                           for k, v in self.root_fields.items()},
            })
            self.finished = True
            return self.to_dict_locked()

    def to_dict_locked(self) -> dict:
        root = self.spans[-1] if self.finished else None
        return {
            "trace_id": self.trace_id,
            "root": self.name,
            "kind": self.kind,
            "op": self.op,
            "start_ms": round(self.start_ms, 3),
            "duration_ms": (root["duration_ms"] if root else None),
            "status": (root["status"] if root else "active"),
            "counters": dict(self.counters),
            "spans": list(self.spans),
        }


def span_tree(trace_dict: dict) -> dict:
    """Nest a completed trace's flat span list into the JSON tree the
    debug endpoint serves: each node carries its span plus `children`
    sorted by start time.  Orphans (a parent pruned by an export cap)
    attach to the root."""
    spans = sorted(trace_dict.get("spans", []),
                   key=lambda s: s.get("start_ms") or 0)
    nodes = {s["span_id"]: dict(s, children=[]) for s in spans}
    # the trace's own root is the parentless span named after the
    # trace; any other parentless span (stitching leftovers) attaches
    # under it like an orphan
    roots = [nodes[s["span_id"]] for s in spans if not s.get("parent_id")]
    root = next((n for n in roots
                 if n.get("name") == trace_dict.get("root")),
                roots[0] if roots else {"span_id": "", "name":
                                        trace_dict.get("root", ""),
                                        "children": []})
    for s in spans:
        node = nodes[s["span_id"]]
        if node is root:
            continue
        parent = nodes.get(s.get("parent_id") or "")
        (parent["children"] if parent is not None and parent is not node
         else root["children"]).append(node)
    out = {k: v for k, v in trace_dict.items() if k != "spans"}
    out["tree"] = root
    return out


def summarize(trace_dict: dict, top: int = 4) -> str:
    """Compact per-stage summary for the response header / slow log:
    total plus the longest direct children of the root, aggregated by
    span name."""
    spans = trace_dict.get("spans", [])
    roots = {s["span_id"] for s in spans if not s.get("parent_id")}
    by_name: dict[str, float] = {}
    for s in spans:
        if s.get("parent_id") in roots:
            by_name[s["name"]] = (by_name.get(s["name"], 0.0)
                                  + (s.get("duration_ms") or 0.0))
    parts = [f"total={trace_dict.get('duration_ms', 0):.1f}ms"]
    for name, ms in sorted(by_name.items(), key=lambda kv: -kv[1])[:top]:
        parts.append(f"{name}={ms:.1f}ms")
    return ";".join(parts)


def export_payload(trace_dict: dict, limit: int = EXPORT_LIMIT) -> str:
    """Serialize a completed trace for the X-Trace-Export response
    header.  Header lines are size-capped, so over the limit the
    export degrades: span fields are dropped first, then the deepest
    spans (roots survive — the coordinator keeps the region's shape,
    losing only leaf detail), and an oversized counter bag is trimmed
    to its largest entries; `dropped_spans` / `dropped_counters`
    record the cuts.  Guaranteed to terminate and to return a blob
    within `limit` (the floor payload is constant-size)."""
    spans = trace_dict.get("spans", [])
    counters = trace_dict.get("counters", {})
    payload = {"spans": spans, "counters": counters}
    blob = json.dumps(payload, separators=(",", ":"))
    if len(blob) <= limit:
        return blob
    # counters first: a runaway bag (e.g. folded in from many
    # downstream hops) must not eat the whole span budget
    cblob = json.dumps(counters, separators=(",", ":"))
    if len(cblob) > limit // 2:
        kept: dict = {}
        size = 2
        for k, v in sorted(counters.items(),
                           key=lambda kv: -abs(kv[1])):
            entry = len(json.dumps({str(k): v},
                                   separators=(",", ":")))
            if size + entry > limit // 2:
                break
            kept[k] = v
            size += entry
        counters = dict(kept, dropped_counters=len(trace_dict.get(
            "counters", {})) - len(kept))
    slim = [dict(s, fields={}) for s in spans]
    by_id = {s["span_id"]: s for s in slim}

    def depth_of(s: dict) -> int:
        d, seen = 0, set()
        cur = s
        while cur.get("parent_id") in by_id and cur["span_id"] not in seen:
            seen.add(cur["span_id"])
            cur = by_id[cur["parent_id"]]
            d += 1
        return d

    depth = {s["span_id"]: depth_of(s) for s in slim}
    slim.sort(key=lambda s: depth[s["span_id"]])
    while slim:
        payload = {"spans": slim, "counters": counters,
                   "dropped_spans": len(spans) - len(slim)}
        blob = json.dumps(payload, separators=(",", ":"))
        if len(blob) <= limit:
            return blob
        # strictly-shrinking tail cut: empties on the last span rather
        # than spinning on an irreducible payload
        del slim[(len(slim) * 3) // 4:]
    return json.dumps({"spans": [], "counters": {},
                       "dropped_spans": len(spans)},
                      separators=(",", ":"))


def ingest_export(header_value: Optional[str]) -> None:
    """Fold a peer's X-Trace-Export header into the active trace,
    parented under the current span (the RPC span).  Malformed exports
    are dropped — stitching is best-effort observability, never a
    query failure."""
    if not header_value:
        return
    trace = _current_trace.get()
    if trace is None or trace.finished:
        return
    try:
        payload = json.loads(header_value)
        if isinstance(payload, dict):
            trace.import_remote(payload, _current_span_id.get())
    except Exception:  # noqa: BLE001 — observability must not fail RPCs
        logger.warning("dropping malformed trace export (%d bytes)",
                       len(header_value))


class TraceRecorder:
    """Process-wide trace sink: sampling decisions, the bounded ring of
    completed traces, and the slow-query log ([trace] config)."""

    def __init__(self) -> None:
        self.enabled = True
        self.ring_size = 256
        self.slow_threshold_s = 1.0
        self.sample_rate = 1.0
        # op traces get their OWN ring and knobs: a hot background op
        # (a WAL commit round per write group) must never evict query
        # traces, and background ops have very different "slow" scales
        self.op_ring_size = 256
        self.op_slow_threshold_s = 30.0
        self.op_sample_rate = 1.0
        self._ring: "OrderedDict[str, dict]" = OrderedDict()
        self._op_ring: "OrderedDict[str, dict]" = OrderedDict()
        # op traces in flight, {trace_id: (op, start_ms)}: an op that
        # holds the server up is still running when the stall line is
        # written (common/loops.py), so the ring alone would miss it
        self._active_ops: dict = {}
        self._lock = threading.Lock()
        self._rng = random.Random(0xACE)

    def configure(self, enabled: Optional[bool] = None,
                  ring_size: Optional[int] = None,
                  slow_threshold_s: Optional[float] = None,
                  sample_rate: Optional[float] = None,
                  op_ring_size: Optional[int] = None,
                  op_slow_threshold_s: Optional[float] = None,
                  op_sample_rate: Optional[float] = None) -> None:
        if enabled is not None:
            self.enabled = enabled
        if ring_size is not None:
            self.ring_size = max(1, ring_size)
        if slow_threshold_s is not None:
            self.slow_threshold_s = slow_threshold_s
        if sample_rate is not None:
            self.sample_rate = min(1.0, max(0.0, sample_rate))
        if op_ring_size is not None:
            self.op_ring_size = max(1, op_ring_size)
        if op_slow_threshold_s is not None:
            self.op_slow_threshold_s = op_slow_threshold_s
        if op_sample_rate is not None:
            self.op_sample_rate = min(1.0, max(0.0, op_sample_rate))

    def start(self, name: str, trace_id: Optional[str] = None,
              forced: bool = False, kind: str = "query", op: str = "",
              slow_threshold_s: Optional[float] = None,
              root_fields: Optional[dict] = None) -> Optional[Trace]:
        """A new active trace, or None when tracing is off / this
        request lost the sampling draw.  `forced` (an upstream
        coordinator already traced this request) bypasses sampling —
        a stitched trace must not lose limbs to a local coin flip.
        Op traces (kind="op") draw against `op_sample_rate`."""
        if not self.enabled:
            return None
        rate = self.op_sample_rate if kind == "op" else self.sample_rate
        if not forced and rate < 1.0:
            with self._lock:
                if self._rng.random() >= rate:
                    return None
        trace = Trace(trace_id or new_trace_id(), name, kind=kind, op=op,
                      slow_threshold_s=slow_threshold_s,
                      root_fields=root_fields)
        if kind == "op":
            with self._lock:
                self._active_ops[trace.trace_id] = (op or name,
                                                    trace.start_ms)
        return trace

    def finish(self, trace: Trace, status: str = "ok") -> dict:
        """Complete a trace into its ring; fires the slow log on
        threshold breach or a deadline-exceeded outcome.  Ops use
        their per-op threshold when one was set at start, else the
        recorder's op default."""
        d = trace.finish(status)
        if trace.slow_threshold_s is not None:
            thr = trace.slow_threshold_s
        elif trace.kind == "op":
            thr = self.op_slow_threshold_s
        else:
            thr = self.slow_threshold_s
        slow = (status == "timeout"
                or (d["duration_ms"] or 0) >= thr * 1e3)
        d["slow"] = slow
        ring, size = ((self._op_ring, self.op_ring_size)
                      if trace.kind == "op"
                      else (self._ring, self.ring_size))
        with self._lock:
            self._active_ops.pop(trace.trace_id, None)
            ring[trace.trace_id] = d
            ring.move_to_end(trace.trace_id)
            while len(ring) > size:
                ring.popitem(last=False)
        _TRACES_RECORDED.inc()
        if slow:
            (_SLOW_OPS if trace.kind == "op" else _SLOW_QUERIES).inc()
            what = (f"op {trace.op or d['root']}"
                    if trace.kind == "op" else "query")
            slow_logger.warning(
                "[trace] slow %s trace_id=%s root=%s status=%s %s "
                "counters=%s", what, trace.trace_id, d["root"], status,
                summarize(d), json.dumps(d["counters"], sort_keys=True))
        return d

    def get(self, trace_id: str) -> Optional[dict]:
        with self._lock:
            d = self._ring.get(trace_id)
            return d if d is not None else self._op_ring.get(trace_id)

    def list(self, limit: int = 50, kind: str = "query",
             op: Optional[str] = None) -> list[dict]:
        """Newest-first summaries for GET /debug/traces.  `kind` picks
        the population: "query" (default — the PR-5 contract), "op",
        or "all" (both rings merged by start time); `op` filters to
        one op name (implies kind="op")."""
        if op is not None:
            kind = "op"
        with self._lock:
            items = []
            if kind in ("all", "query"):
                items += list(self._ring.values())
            if kind in ("all", "op"):
                items += [d for d in self._op_ring.values()
                          if op is None or d.get("op") == op]
        items.sort(key=lambda d: d.get("start_ms") or 0)
        out = []
        for d in reversed(items[-max(0, limit):] if limit else items):
            out.append({"trace_id": d["trace_id"], "root": d["root"],
                        "kind": d.get("kind", "query"),
                        "op": d.get("op", ""),
                        "start_ms": d["start_ms"],
                        "duration_ms": d["duration_ms"],
                        "status": d["status"], "slow": d.get("slow"),
                        "spans": len(d["spans"])})
        return out

    def ops_overlapping(self, start_ms: float, end_ms: float) -> list:
        """[(op, start_ms, duration_ms or None while in flight)] of the
        background ops whose interval overlaps [start_ms, end_ms)."""
        with self._lock:
            done = [(d.get("op") or d["root"], d["start_ms"],
                     d["duration_ms"]) for d in self._op_ring.values()]
            out = [(op, t0, None) for op, t0 in self._active_ops.values()
                   if t0 < end_ms]
        out += [(op, t0, dur) for op, t0, dur in done
                if t0 < end_ms and t0 + (dur or 0.0) > start_ms]
        return sorted(out, key=lambda o: o[1])

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._op_ring.clear()
            self._active_ops.clear()


recorder = TraceRecorder()


@contextlib.contextmanager
def trace_scope(trace: Optional[Trace]) -> Iterator[Optional[Trace]]:
    """Bind `trace` as the ambient trace (None = explicit no-trace
    scope).  Spans and trace_add() calls inside — including those in
    tasks and pool work spawned inside — attribute to it."""
    tok = _current_trace.set(trace)
    tok_span = _current_span_id.set(
        trace.root_span_id if trace is not None else "")
    # the root lies in the profiler's trace like every span under it
    ann = (_annotation("horaedb/" + trace.name, trace.trace_id)
           if trace is not None else contextlib.nullcontext())
    try:
        with ann:
            yield trace
    finally:
        _current_trace.reset(tok)
        _current_span_id.reset(tok_span)


def trace_add(name: str, value: float = 1.0) -> None:
    """Attribute counted work to the active trace (no-op outside)."""
    trace = _current_trace.get()
    if trace is not None:
        trace.add(name, value)


def span_note(**fields) -> None:
    """Add fields to the innermost span open around the caller (no-op
    outside a trace, or under its root alone): what a callee learned
    that its caller's span should say."""
    trace = _current_trace.get()
    if trace is not None:
        open_fields = trace.open_fields.get(_current_span_id.get())
        if open_fields is not None:
            open_fields.update(fields)


_TraceAnnotation = None
# the share of `sync` spans that read their thread's CPU clock (see
# span): a constant, which the tests set to 1
CPU_SAMPLE = 0.25
_thread_time = time.thread_time
_random = random.random
# span name -> its `span_<name>_seconds` family, so that a span looks
# its histogram up in a plain dict and not under the registry's lock
_SPAN_HISTS: dict = {}


def _annotation(name: str, trace_id: str):
    """A `jax.profiler.TraceAnnotation` (a TraceMe) for one span: with
    no profiler session it costs a constructor and two no-op calls
    (0.6 us measured on the sandbox CPU); while one runs, the span
    lands in the xplane's host plane as `horaedb/<name>` with the
    trace id, on the clock the device's operations are stamped with.
    Spans held across an `await` interleave on the loop's thread: a
    TraceMe is recorded whole at its end (start, end), not pushed on a
    stack, so each keeps its own start and duration.  jax is imported
    at the first span, not with this module."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name, trace_id=trace_id)


class span:
    """Traced operation (a context manager): observes a latency
    histogram (`span_<name>_seconds`; `buckets` overrides the default
    layout — pass metrics.WIDE_BUCKETS for long-running ops so
    compaction/flush don't flatten into +Inf; `hist` replaces the
    family by an already-bound labelled child), records a tree span
    into the active trace when one is bound, and annotates the
    profiler's trace (see _annotation).

    CPU beside wall, under one rule: a span that declares `sync=True`
    (its block holds no `await`, whichever thread runs it) may read
    `time.thread_time()` at both ends and record `cpu_ms`; no other
    span does, since one held across an `await` would be booked the
    CPU of every task that ran meanwhile.  A read is a system call
    (0.45 us in the sandbox, 5.5 us on the chip's host, where all of
    them on every declared span cost a point query 2.5 % of its rate:
    PERF.md §6, PR 37), so one declared span in 1 / CPU_SAMPLE, drawn
    at random, reads the clock, and `cpu`, a counter, takes its CPU
    seconds over CPU_SAMPLE: an estimate of the CPU of all of them.
    Wall minus CPU is time the span stood still: it waited for the
    GIL or sat in a blocking call."""

    __slots__ = ("name", "fields", "_hist", "_buckets", "_trace",
                 "_span_id", "_parent_id", "_tok", "_ann", "_wall_ms",
                 "_t0", "_sync", "_cpu", "_cpu0")

    def __init__(self, name: str, buckets: Optional[tuple] = None,
                 hist=None, sync: bool = False, cpu=None,
                 **fields) -> None:
        self.name = name
        self.fields = fields
        self._hist = hist
        self._buckets = buckets
        self._sync = sync
        self._cpu = cpu
        self._trace = None

    def __enter__(self) -> "span":
        trace = _current_trace.get()
        trace_id = ""
        if trace is not None:
            trace_id = trace.trace_id
            if not trace.finished:
                self._trace = trace
                self._span_id = _new_span_id()
                self._parent_id = (_current_span_id.get()
                                   or trace.root_span_id)
                self._tok = _current_span_id.set(self._span_id)
                trace.open_fields[self._span_id] = self.fields
        self._ann = _annotation("horaedb/" + self.name, trace_id)
        self._ann.__enter__()
        self._wall_ms = time.time() * 1e3
        self._t0 = time.perf_counter()
        # read inside the wall's two readings: cpu_ms <= duration_ms
        self._cpu0 = (_thread_time()
                      if self._sync and _random() < CPU_SAMPLE else None)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        cpu = None if self._cpu0 is None else _thread_time() - self._cpu0
        elapsed = time.perf_counter() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        # failures are observed too — failure-path tail latency matters
        hist = self._hist or _SPAN_HISTS.get(self.name)
        if hist is None:
            name = self.name
            hist_kwargs = ({} if self._buckets is None
                           else {"buckets": self._buckets})
            hist = _SPAN_HISTS[name] = registry.histogram(
                f"span_{name.replace('.', '_')}_seconds",
                f"span {name} duration", **hist_kwargs)
        hist.observe(elapsed)
        if cpu is not None and self._cpu is not None:
            self._cpu.inc(cpu / CPU_SAMPLE)
        trace = self._trace
        if trace is not None:
            _current_span_id.reset(self._tok)
            trace.open_fields.pop(self._span_id, None)
            record = {
                "span_id": self._span_id, "parent_id": self._parent_id,
                "name": self.name, "start_ms": round(self._wall_ms, 3),
                "duration_ms": round(elapsed * 1e3, 3),
                "status": "ok" if exc_type is None else "error",
                "fields": {k: _field(v) for k, v in self.fields.items()},
            }
            if cpu is not None:
                record["cpu_ms"] = round(cpu * 1e3, 3)
            trace.record(record)


# a hop whose two waits together stay under this leaves no span (the
# pool's histograms still count it): nine such hops are 3% of a 60 ms
# scan
HOP_SPAN_FLOOR_MS = 0.2


def record_hop(pool: str, submitted: float, started: float,
               returned: float, resumed: float) -> None:
    """One hop of a traced request through a worker pool, known by four
    time.perf_counter readings (common/runtimes.py): where the hop
    waited, one `pool_hop` child of the current span from submit to
    resumption, with the two waits and the time on the worker as
    fields.  It overlaps the spans the job recorded on the worker, so
    a span that hops between the loop and a pool is closed by its
    children, waits included.  No histogram and no profiler
    annotation: the hop is known only after the fact, and its counters
    are the caller's.  Runs on the loop's thread once per hop: kept
    short."""
    trace = _current_trace.get()
    if trace is None or trace.finished:
        return
    wait_ms = (started - submitted) * 1e3
    resume_ms = (resumed - returned) * 1e3
    if wait_ms + resume_ms < HOP_SPAN_FLOOR_MS:
        return
    trace.record({
        "span_id": _new_span_id(),
        "parent_id": _current_span_id.get() or trace.root_span_id,
        "name": "pool_hop",
        "start_ms": round(trace.start_ms
                          + (submitted - trace._t0) * 1e3, 3),
        "duration_ms": round((resumed - submitted) * 1e3, 3),
        "status": "ok",
        "fields": {"pool": pool, "wait_ms": wait_ms,
                   "run_ms": (returned - started) * 1e3,
                   "resume_ms": resume_ms},
    })


# The phases of one scan (docs/observability.md, scan phases): the
# children of the engine's `downsample` span.  One labelled family, so
# the data table's phases are told apart from those of the index and
# tags tables, which `resolve` scans through the same reader.  A
# reader removes its table's children at close (clear-on-close).
SCAN_PHASES = ("scan.plan", "scan.windows", "scan.group_prep",
               "scan.dispatch", "scan.device_wait", "scan.d2h",
               "scan.combine")
_PHASE_SECONDS = registry.histogram(
    "scan_phase_seconds",
    "wall seconds per scan phase (the children of the downsample "
    "span), by phase and by the table scanned")
_PHASE_CPU = registry.counter(
    "scan_phase_cpu_seconds_total",
    "CPU seconds of the recording thread inside the scan phases that "
    "declare themselves synchronous (dispatch, device_wait, d2h, "
    "combine), by phase and by the table scanned")


# (phase, table) -> the labelled child, looked up without the family's
# lock; dropped with the child at the table's close
_PHASE_CHILDREN: dict = {}
_PHASE_CPU_CHILDREN: dict = {}


def _phase_child(name: str, table: str):
    hist = _PHASE_CHILDREN.get((name, table))
    if hist is None:
        hist = _PHASE_CHILDREN[name, table] = _PHASE_SECONDS.labels(
            phase=name, table=table)
    return hist


def phase(name: str, table: str, sync: bool = False, **fields) -> span:
    """A span of one scan phase on `table`; a `sync` one adds its CPU
    (sampled and scaled: see span) to
    `scan_phase_cpu_seconds_total{phase,table}`, so a phase whose
    every site is `sync` reads its CPU share off the two families."""
    cpu = None
    if sync:
        cpu = _PHASE_CPU_CHILDREN.get((name, table))
        if cpu is None:
            cpu = _PHASE_CPU_CHILDREN[name, table] = _PHASE_CPU.labels(
                phase=name, table=table)
    return span(name, hist=_phase_child(name, table), sync=sync, cpu=cpu,
                table=table, **fields)


def phase_passed(name: str, table: str) -> None:
    """A phase whose seam was passed with nothing to do: an observation
    of 0 and no span, so that the phase's series is on /metrics (and
    reads no time) where a scan reached the seam and never waited."""
    _phase_child(name, table).observe(0.0)


def clear_phases(table: str) -> None:
    """Clear-on-close for a table's phase histograms and CPU counters."""
    for name in SCAN_PHASES:
        _PHASE_CHILDREN.pop((name, table), None)
        _PHASE_SECONDS.remove(phase=name, table=table)
        _PHASE_CPU_CHILDREN.pop((name, table), None)
        _PHASE_CPU.remove(phase=name, table=table)


def _field(v):
    return v if isinstance(v, (str, int, float, bool, type(None))) else str(v)


@contextlib.contextmanager
def op_trace(op: str, slow_s: Optional[float] = None,
             **fields) -> Iterator[Optional[Trace]]:
    """Trace one background operation (compaction execute, memtable
    flush, WAL group-commit round, rollup roll pass, scrub pass,
    health-monitor round) as its own kind="op" trace tree in the
    recorder's op ring — with the same objstore/cache/rows/bytes
    attribution queries get, because every trace_add()/span() inside
    (including pool work, which inherits the contextvars) lands on the
    ambient trace this binds.

    If a trace is ALREADY ambient — a query-triggered flush inside the
    aggregate pushdown's pre-flush, a synchronous roll under a traced
    admin request — the operation records as a span of that trace
    instead of stealing the scope: the work is attributed to whoever
    caused it.

    `slow_s` overrides the recorder's op slow threshold for this op
    (a compaction's "slow" is minutes; a WAL fsync round's is
    seconds)."""
    if _current_trace.get() is not None:
        with span(op, **fields):
            yield None
        return
    trace = recorder.start(op, kind="op", op=op, slow_threshold_s=slow_s,
                           root_fields=fields)
    if trace is None:
        yield None
        return
    status = "ok"
    with trace_scope(trace):
        try:
            yield trace
        except BaseException:
            status = "error"
            raise
        finally:
            recorder.finish(trace, status=status)
