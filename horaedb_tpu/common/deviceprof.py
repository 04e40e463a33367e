"""Device-plane profiler: compile ledger, dispatch profiler, transfer
accounting (docs/observability.md, device plane).

The query-trace, background-loop, and memory planes cover the host;
this registry covers the DEVICE: every `jax.jit` seam in the package
wraps through `deviceprof.jit(...)` (tools/lint.py rejects bare
jax.jit under horaedb_tpu/), which gives each compiled function a
ledger entry answering the three questions XLA keeps to itself:

  did it compile?   per-fn compile count + cumulative compile seconds
                    + the triggering cache key (arg shapes/dtypes and
                    static values), so a recompile names the dimension
                    that churned instead of "it was slow once"
  where did the wall go?   per-dispatch host time (trace/cache-lookup/
                    enqueue) vs the host's wait for the device at the
                    sync seams (block_until_ready, download), which
                    is the `scan.device_wait` phase and nothing else —
                    a cold query's trace states whether it paid
                    compilation, dispatch overhead, or waited for the
                    device.  The wait is NOT a kernel time: it holds
                    the device's queue (other queries' programs ahead
                    of this one) and the execution; kernel times come
                    from a jax.profiler trace
  what moved?       device_transfer_bytes_total{direction=h2d|d2h} and
                    device_transfer_seconds_total, charged at the
                    device_put/download seams (a download syncs first,
                    so its seconds are the copy alone), with per-trace
                    twins, reconciled against the memory ledger's
                    device accounts

Recompile STORMS (N compiles of one fn inside a sliding window — the
shape-churn failure mode of a capacity-padded engine) flag once per
episode, watchdog-style: `device_recompile_storms_total{fn=}` plus a
slow-log line naming the churning key dimension.  The episode clears
when the window drains; the next storm is a new episode.

The profiler also keeps the mesh ROUND timeline: per-round slot fill
ratio, padding-waste rows, and per-shard row imbalance — the batching
quality the [scan.mesh] dispatcher achieved, served with the compile
table, transfer totals, and per-device memory on `GET /debug/device`.

Process-global (like utils.metrics.registry / utils.tracing.recorder /
common.loops.loops / common.memledger.ledger).  All families ride the
clear-on-close discipline: `profiler.clear()` at engine close removes
every labeled child so a closed engine serves no phantom device
series.  Wrappers stay registered — the compiled functions are
module-level and outlive any one engine; only their accounted state
resets.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

from horaedb_tpu.utils.metrics import registry
from horaedb_tpu.utils.tracing import phase, phase_passed, trace_add

logger = logging.getLogger(__name__)
# storms land next to slow queries and watchdog stalls: one stream an
# operator greps for "the system is not keeping up" events
slow_logger = logging.getLogger("horaedb_tpu.trace.slow")

_COMPILES = registry.counter(
    "device_compiles_total",
    "XLA compilations per jitted function (deviceprof.jit seams)")
_COMPILE_SECONDS = registry.counter(
    "device_compile_seconds_total",
    "cumulative trace+lower+compile wall seconds per jitted function")
_STORMS = registry.counter(
    "device_recompile_storms_total",
    "recompile-storm episodes per jitted function (N compiles inside "
    "the [deviceprof] sliding window, flagged once per episode)")
_DISPATCHES = registry.counter(
    "device_dispatches_total",
    "cache-hit dispatches per jitted function (compiling calls count "
    "under device_compiles_total instead)")
_DISPATCH_SECONDS = registry.histogram(
    "device_dispatch_seconds",
    "host-side dispatch wall per cached call (trace-cache lookup + "
    "argument processing + async enqueue), per jitted function")
_TRANSFER_BYTES = registry.counter(
    "device_transfer_bytes_total",
    "bytes moved across the host/device boundary at the device_put "
    "and download seams, by direction (h2d|d2h)")
_TRANSFER_SECONDS = registry.counter(
    "device_transfer_seconds_total",
    "wall seconds spent in instrumented host/device transfers, by "
    "direction (h2d: async puts charge the enqueue wall; d2h: the "
    "copy after the sync, never the wait for the device)")


def _nbytes(x: Any) -> int:
    """Total payload bytes of an array pytree (tuples/lists/dicts of
    array-likes; scalars and static leaves count zero)."""
    if x is None:
        return 0
    nb = getattr(x, "nbytes", None)
    if nb is not None:
        return int(nb)
    if isinstance(x, (tuple, list)):
        return sum(_nbytes(v) for v in x)
    if isinstance(x, dict):
        return sum(_nbytes(v) for v in x.values())
    return 0


def _leaf_key(label: str, x: Any, out: list) -> None:
    """Flatten one call argument into labeled cache-key components.
    Arrays contribute (label.shape, label.dtype); containers recurse
    with indexed labels; everything else is a static VALUE component —
    exactly the dimensions jit's own cache keys on, labeled so a storm
    can name the one that churns."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        out.append((f"{label}.shape", tuple(x.shape)))
        out.append((f"{label}.dtype", str(x.dtype)))
    elif isinstance(x, (tuple, list)):
        for j, v in enumerate(x):
            _leaf_key(f"{label}[{j}]", v, out)
    elif isinstance(x, dict):
        for k in sorted(x):
            _leaf_key(f"{label}.{k}", x[k], out)
    else:
        out.append((label, repr(x)))


def _call_key(args: tuple, kwargs: dict) -> tuple:
    out: list = []
    for i, a in enumerate(args):
        _leaf_key(f"a{i}", a, out)
    for k in sorted(kwargs):
        _leaf_key(k, kwargs[k], out)
    return tuple(out)


class FnRecord:
    """One jitted function's ledger entry.  Scalar fields are written
    under the profiler lock; the wrapper holds the record for the
    process's life (clear() resets state, never identity)."""

    __slots__ = ("name", "compiles", "compile_seconds", "last_compile_s",
                 "last_key", "dispatches", "dispatch_seconds",
                 "storms", "storm_active",
                 "_window", "_churn", "_prev_key")

    def __init__(self, name: str) -> None:
        self.name = name
        self.reset()

    def reset(self) -> None:
        self.compiles = 0
        self.compile_seconds = 0.0
        self.last_compile_s = 0.0
        self.last_key: Optional[tuple] = None
        self.dispatches = 0
        self.dispatch_seconds = 0.0
        self.storms = 0
        self.storm_active = False
        self._window: deque = deque()
        self._churn: dict[str, int] = {}
        self._prev_key: Optional[tuple] = None

    def snapshot(self) -> dict:
        return {
            "fn": self.name,
            "compiles": self.compiles,
            "compile_seconds": round(self.compile_seconds, 6),
            "last_compile_ms": round(self.last_compile_s * 1e3, 3),
            "last_key": (None if self.last_key is None
                         else {k: repr(v) for k, v in self.last_key}),
            "dispatches": self.dispatches,
            "dispatch_seconds": round(self.dispatch_seconds, 6),
            "storms": self.storms,
            "storm_active": self.storm_active,
        }


class ProfiledJit:
    """The callable `deviceprof.jit` returns: jax.jit underneath, the
    ledger on top.  Unknown attributes (lower, eval_shape, trace)
    forward to the jitted function, so AOT call sites keep working."""

    def __init__(self, owner: "DeviceProfiler", fn: Callable, name: str,
                 jit_kwargs: dict) -> None:
        import jax

        self._jitted = jax.jit(fn, **jit_kwargs)  # noqa: the one seam
        self._name = name
        self.__name__ = name
        self.__doc__ = getattr(fn, "__doc__", None)
        self.__wrapped__ = fn
        self._owner = owner
        self._rec = owner._record(name)
        # compile detection reads THIS jitted function's cache size:
        # builders mint many programs under one ledger name (the mesh
        # rounds), and a size shared by name would book their compiles
        # as dispatches.  It survives clear() with the wrapper — jit's
        # own cache is not reset by an engine close either.
        self._seen_cache_size = 0

    def __call__(self, *args, **kwargs):
        if not self._owner.enabled:
            return self._jitted(*args, **kwargs)
        return self._owner._profiled_call(self, args, kwargs)

    def __getattr__(self, item: str):
        return getattr(self._jitted, item)

    def __repr__(self) -> str:
        return f"<deviceprof.jit {self._name}>"


class DeviceProfiler:
    """Process-global device-plane registry ([deviceprof] config)."""

    def __init__(self, clock=time.monotonic) -> None:
        self._clock = clock
        self._lock = threading.Lock()
        self._recs: dict[str, FnRecord] = {}
        self.enabled = True
        # storm = storm_threshold compiles of ONE fn inside
        # storm_window_s (per-episode flag, watchdog-style)
        self.storm_window_s = 60.0
        self.storm_threshold = 5
        self.rounds_kept = 256
        self._rounds: deque = deque(maxlen=self.rounds_kept)
        self.transfer = {"h2d": {"bytes": 0, "seconds": 0.0, "count": 0},
                         "d2h": {"bytes": 0, "seconds": 0.0, "count": 0}}

    def configure(self, enabled: Optional[bool] = None,
                  storm_window_s: Optional[float] = None,
                  storm_threshold: Optional[int] = None,
                  rounds_kept: Optional[int] = None) -> None:
        if enabled is not None:
            self.enabled = enabled
        if storm_window_s is not None:
            self.storm_window_s = max(0.1, storm_window_s)
        if storm_threshold is not None:
            self.storm_threshold = max(2, int(storm_threshold))
        if rounds_kept is not None and rounds_kept != self.rounds_kept:
            self.rounds_kept = max(1, int(rounds_kept))
            with self._lock:
                self._rounds = deque(self._rounds,
                                     maxlen=self.rounds_kept)

    # ---- the jit seam ------------------------------------------------------

    def jit(self, fn: Optional[Callable] = None, *,
            name: Optional[str] = None, **jit_kwargs):
        """jax.jit with a ledger entry.  All three house forms work:

          @deviceprof.jit                       bare decorator
          @deviceprof.jit(static_argnames=...)  parameterized decorator
          deviceprof.jit(mapped, name="...")    direct wrap (the
                                                shard_map builders)
        """
        if fn is None:
            return lambda f: self.jit(f, name=name, **jit_kwargs)
        fn_name = name or getattr(fn, "__name__", None) or repr(fn)
        return ProfiledJit(self, fn, fn_name, jit_kwargs)

    def _record(self, name: str) -> FnRecord:
        with self._lock:
            rec = self._recs.get(name)
            if rec is None:
                rec = FnRecord(name)
                self._recs[name] = rec
            return rec

    def _profiled_call(self, fn: "ProfiledJit", args, kwargs):
        rec = fn._rec
        t0 = time.perf_counter()
        out = fn._jitted(*args, **kwargs)
        wall = time.perf_counter() - t0
        # jit's OWN cache is the ground truth for "did this call
        # compile" — it keys on exactly what triggers a recompile
        size = fn._jitted._cache_size()
        compiled = size > fn._seen_cache_size
        fn._seen_cache_size = size
        if compiled:
            self._note_compile(rec, _call_key(args, kwargs), wall)
        else:
            with self._lock:
                rec.dispatches += 1
                rec.dispatch_seconds += wall
            _DISPATCHES.labels(fn=rec.name).inc()
            _DISPATCH_SECONDS.labels(fn=rec.name).observe(wall)
            trace_add("stage_device_dispatch_ms", wall * 1e3)
        return out

    def _note_compile(self, rec: FnRecord, key: tuple,
                      wall: float) -> None:
        now = self._clock()
        storm_fired = False
        churn_dim = None
        with self._lock:
            rec.compiles += 1
            rec.compile_seconds += wall
            rec.last_compile_s = wall
            # the churn ledger: which key dimension differed from the
            # PREVIOUS compile — a storm names the most frequent one
            if rec._prev_key is not None:
                prev, cur = dict(rec._prev_key), dict(key)
                for k in set(prev) | set(cur):
                    if prev.get(k) != cur.get(k):
                        rec._churn[k] = rec._churn.get(k, 0) + 1
            rec._prev_key = key
            rec.last_key = key
            w = rec._window
            w.append(now)
            while w and w[0] < now - self.storm_window_s:
                w.popleft()
            if len(w) >= self.storm_threshold:
                if not rec.storm_active:
                    rec.storm_active = True  # one episode, one flag
                    rec.storms += 1
                    storm_fired = True
                    churn_dim = (max(rec._churn, key=rec._churn.get)
                                 if rec._churn else
                                 "(keys identical — jit cache lost?)")
            elif rec.storm_active:
                rec.storm_active = False  # episode over; next is new
        _COMPILES.labels(fn=rec.name).inc()
        _COMPILE_SECONDS.labels(fn=rec.name).inc(wall)
        trace_add("stage_device_compile_ms", wall * 1e3)
        if storm_fired:
            _STORMS.labels(fn=rec.name).inc()
            slow_logger.warning(
                "[deviceprof] recompile storm: fn=%s %d compiles "
                "within %.0fs (threshold %d), churning key dimension: "
                "%s — capacity padding should keep shapes stable; a "
                "churning static arg means the dispatcher is minting "
                "program variants per call", rec.name,
                len(rec._window), self.storm_window_s,
                self.storm_threshold, churn_dim)

    # ---- the sync + transfer seams ----------------------------------------

    def block_until_ready(self, x, fn: str = "device", table: str = ""):
        """The sync seam: wall spent here is the host blocked on the
        device (the dispatch already returned; this waits for the
        queue ahead of the computation and for the computation).
        A `scan.device_wait` phase span of `table`, `fn` naming the
        program waited for: the one record of the wait.  Returns `x`
        so call sites stay expressions."""
        import jax

        with phase("scan.device_wait", table, sync=True, fn=fn):
            return jax.block_until_ready(x)

    def download(self, x, fn: str = "device", table: str = ""):
        """The d2h seam, the sync split from the copy: first
        `block_until_ready` (charged to `scan.device_wait`; where
        every leaf is ready already nothing waits: an observation of 0
        and no span), then `np.asarray` of every leaf (the `scan.d2h`
        phase span, and the seconds of
        device_transfer_seconds_total{direction="d2h"}).  Returns the
        same pytree with numpy leaves."""
        import jax
        import numpy as np

        if all(leaf.is_ready() for leaf in jax.tree_util.tree_leaves(x)
               if isinstance(leaf, jax.Array)):
            phase_passed("scan.device_wait", table)
        else:
            self.block_until_ready(x, fn=fn, table=table)
        with phase("scan.d2h", table, sync=True, fn=fn):
            t0 = time.perf_counter()
            out = jax.tree_util.tree_map(np.asarray, x)
            copied = time.perf_counter() - t0
        self.charge_transfer("d2h", _nbytes(out), seconds=copied)
        return out

    def device_put(self, x, *args, **kwargs):
        """jax.device_put with h2d accounting (bytes + enqueue wall)."""
        import jax

        t0 = time.perf_counter()
        out = jax.device_put(x, *args, **kwargs)
        self.charge_transfer("h2d", _nbytes(x),
                             seconds=time.perf_counter() - t0)
        return out

    def charge_transfer(self, direction: str, nbytes: int,
                        seconds: float = 0.0) -> None:
        """Account one host/device transfer.  `direction` is h2d|d2h;
        seams that only know bytes (a download already materialized as
        numpy) pass seconds=0 and the wall rides the enclosing stage."""
        if not self.enabled or nbytes <= 0:
            return
        with self._lock:
            t = self.transfer[direction]
            t["bytes"] += int(nbytes)
            t["seconds"] += seconds
            t["count"] += 1
        _TRANSFER_BYTES.labels(direction=direction).inc(int(nbytes))
        if seconds:
            _TRANSFER_SECONDS.labels(direction=direction).inc(seconds)
        trace_add(f"device_{direction}_bytes", float(nbytes))

    # ---- the mesh round timeline ------------------------------------------

    def record_round(self, kind: str, *, slots: int, capacity: int,
                     rows_per_shard: Optional[list] = None,
                     padding_rows: int = 0, upload_bytes: int = 0,
                     stack_hit: bool = False,
                     seconds: float = 0.0) -> None:
        """One mesh round's batching quality: how full the time axis
        was (`slots`/`capacity`), how many capacity-padding rows rode
        along dead, and how unevenly real rows landed per shard (max /
        mean — 1.0 is perfect balance)."""
        if not self.enabled:
            return
        rec = {
            "kind": kind,
            "slots": int(slots),
            "capacity": int(capacity),
            "fill_ratio": (round(slots / capacity, 4)
                           if capacity else None),
            "padding_rows": int(padding_rows),
            "upload_bytes": int(upload_bytes),
            "stack_hit": bool(stack_hit),
            "seconds": round(seconds, 6),
            "at": round(self._clock(), 3),
        }
        if rows_per_shard:
            rows = [int(r) for r in rows_per_shard]
            mean = sum(rows) / len(rows)
            rec["shard_rows"] = rows
            rec["row_imbalance"] = (round(max(rows) / mean, 4)
                                    if mean > 0 else None)
        with self._lock:
            self._rounds.append(rec)

    # ---- the /debug/device + /stats surface -------------------------------

    def records(self) -> list[FnRecord]:
        with self._lock:
            return list(self._recs.values())

    def snapshot(self) -> dict:
        """Full device-plane state (GET /debug/device): the compile-
        cache table, transfer totals, and the mesh round timeline
        (newest last)."""
        with self._lock:
            rounds = list(self._rounds)
            transfer = {d: dict(t) for d, t in self.transfer.items()}
        for t in transfer.values():
            t["seconds"] = round(t["seconds"], 6)
        fns = sorted((r.snapshot() for r in self.records()),
                     key=lambda d: d["fn"])
        return {
            "enabled": self.enabled,
            "storm": {"window_s": self.storm_window_s,
                      "threshold": self.storm_threshold},
            "fns": fns,
            "transfer": transfer,
            "rounds": rounds,
        }

    @staticmethod
    def backend() -> dict:
        """The device as JAX reports it (GET /debug/device "backend"):
        whoever reads a served number can name the device it ran on."""
        import jax

        devices = jax.devices()
        return {"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices)}

    def summary(self) -> dict:
        """Compact rollup for /stats: totals plus any fn currently in
        a storm episode."""
        recs = self.records()
        with self._lock:
            transfer = {d: t["bytes"] for d, t in self.transfer.items()}
        return {
            "fns": len(recs),
            "compiles": sum(r.compiles for r in recs),
            "compile_seconds": round(
                sum(r.compile_seconds for r in recs), 3),
            "dispatches": sum(r.dispatches for r in recs),
            "storms": sorted(r.name for r in recs if r.storm_active),
            "transfer_bytes": transfer,
        }

    def clear(self) -> None:
        """Clear-on-close: reset every ledger entry and remove every
        labeled child so the families render empty — a closed engine
        serves no phantom device series.  Wrapper registrations (and
        jit's own caches) survive; only accounted state resets."""
        for rec in self.records():
            for fam in (_COMPILES, _COMPILE_SECONDS, _STORMS,
                        _DISPATCHES, _DISPATCH_SECONDS):
                fam.remove(fn=rec.name)
            with self._lock:
                rec.reset()
        with self._lock:
            self._rounds.clear()
            for t in self.transfer.values():
                t["bytes"], t["seconds"], t["count"] = 0, 0.0, 0
        for d in ("h2d", "d2h"):
            _TRANSFER_BYTES.remove(direction=d)
            _TRANSFER_SECONDS.remove(direction=d)


profiler = DeviceProfiler()

# module-level aliases: call sites read `deviceprof.jit(...)` /
# `deviceprof.device_put(...)` like the jax names they replace
jit = profiler.jit
block_until_ready = profiler.block_until_ready
download = profiler.download
device_put = profiler.device_put
charge_transfer = profiler.charge_transfer
record_round = profiler.record_round
