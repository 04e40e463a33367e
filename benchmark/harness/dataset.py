"""The seeded TSBS devops cpu-only data and the plain numpy reference.

Shapes follow TSBS (github.com/timescale/tsbs, devops cpu-only) at the
source's width: every row carries the configuration's ten tags
(`hostname` = `host_0..host_{scale-1}` is the key; the others are
drawn per host from the configuration's choice lists, a datacenter
from its region's) and its ten `usage_*` fields, one point per host
per `interval_ms`, each field a random walk with N(0,1) steps held to
[0, 100].  Rows are time-major (every tick reports all hosts: TSBS's
scrape order).  The server's bulk endpoint takes one field per body,
so a range of ticks is written as one body per field, each with all
the tags.  Values are rounded to float32 and kept as such: the engine
stores float32, so an acknowledged point reads back bit for bit and
min/max/last compare exactly.

The reference answers a group-by query at ANY phase: bucket b of a
query starting at `start` holds the ticks whose timestamp lies in
[start + b*bucket, start + (b+1)*bucket).  It imports nothing of the
program.
"""

from __future__ import annotations

import io

import numpy as np
import pyarrow as pa
from pyarrow import ipc

from benchmark.harness.seahash import tsid_of

AGGS = ("count", "sum", "avg", "min", "max", "last")
EXACT_AGGS = ("min", "max", "last")
ROUNDED_AGGS = ("sum", "avg")


def round_bf16(a: np.ndarray) -> np.ndarray:
    """float32 -> nearest bfloat16 (round to nearest even), as float32:
    the precision step below the configuration's float32."""
    bits = np.ascontiguousarray(a, dtype=np.float32).view(np.uint32)
    rounded = bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16))
                                          & np.uint32(1))
    return (rounded & np.uint32(0xFFFF0000)).view(np.float32)


def _walk(rng, ticks: int, hosts: int) -> np.ndarray:
    """(ticks, hosts) float32.  A reflecting walk is the vectorisable
    twin of TSBS's clamped one: fold the free walk into [0, 100] by
    reflection at both walls (same step law, same range, no per-step
    state)."""
    walk = rng.standard_normal((ticks, hosts),
                               dtype=np.float32).astype(np.float64)
    walk[0] = rng.random(hosts) * 100.0
    np.cumsum(walk, axis=0, out=walk)
    np.mod(walk, 200.0, out=walk)
    np.subtract(200.0, walk, out=walk, where=walk > 100.0)
    return walk.astype(np.float32)


def _draw_tags(specs: list, hosts: int, rng) -> tuple[list, dict, dict]:
    """Per tag, in the configuration's order: its name, its dictionary
    of values and each host's index into it.  A tag is the key
    (`pattern`), a draw from `choices`, a draw from the choices of
    another tag's value (`per`), or a number below `range`."""
    names, values, codes = [], {}, {}
    for spec in specs:
        name = spec["name"]
        names.append(name)
        if "pattern" in spec:
            values[name] = [spec["pattern"].format(i=i)
                            for i in range(hosts)]
            codes[name] = np.arange(hosts)
        elif "range" in spec:
            values[name] = [str(i) for i in range(int(spec["range"]))]
            codes[name] = rng.integers(0, len(values[name]), size=hosts)
        elif "per" in spec:
            parent = spec["per"]
            flat, first = [], {}
            for pv in values[parent]:
                first[pv] = len(flat)
                flat.extend(spec["choices"][pv])
            values[name] = flat
            pick = rng.random(hosts)
            codes[name] = np.array([
                first[pv] + int(u * len(spec["choices"][pv]))
                for pv, u in zip((values[parent][c] for c in codes[parent]),
                                 pick)])
        else:
            values[name] = list(spec["choices"])
            codes[name] = rng.integers(0, len(values[name]), size=hosts)
    return names, values, codes


class Dataset:
    def __init__(self, config: dict, seed: int):
        self.metric = config["metric"]
        self.fields = list(config["fields"])
        # the queries read the first field, as TSBS's
        # GetCPUMetricsSlice(1) does
        self.field = self.fields[0]
        self.hosts = int(config["scale"])
        self.interval_ms = int(config["interval_ms"])
        self.t0 = int(config["start_ms"])
        self.ticks = int(config["span_ms"]) // self.interval_ms
        self.span_ms = self.ticks * self.interval_ms
        self.rows = self.ticks * self.hosts
        rng = np.random.default_rng([int(seed), 0x7513B5])
        # (fields, ticks, hosts) float32; row i of a body's flat layout
        # is tick i // hosts, host i % hosts
        self.values = np.empty((len(self.fields), self.ticks, self.hosts),
                               dtype=np.float32)
        for f in range(len(self.fields)):
            self.values[f] = _walk(rng, self.ticks, self.hosts)
        self.grid = self.values[0]
        self.tags, self.tag_values, self.tag_codes = _draw_tags(
            config["tags"], self.hosts,
            np.random.default_rng([int(seed), 0x7A65]))
        self.key_tag = next(s["name"] for s in config["tags"]
                            if "pattern" in s)
        self.host_names = self.tag_values[self.key_tag]
        self.tsid_of_host = [
            tsid_of(self.metric, {t: self.tag_values[t][self.tag_codes[t][h]]
                                  for t in self.tags})
            for h in range(self.hosts)]
        self.host_of_tsid = {t: i for i, t in enumerate(self.tsid_of_host)}

    # ---- ingest bodies ----------------------------------------------------

    def bodies(self, lo_tick: int, hi_tick: int):
        """Ticks [lo, hi) as one Arrow IPC stream body per field, in the
        configuration's order of fields: (field, body) pairs, each body
        with all the tags, the timestamp and that field's value."""
        n = hi_tick - lo_tick
        columns = {}
        for t in self.tags:
            kind = np.int8 if len(self.tag_values[t]) < 128 else np.int32
            columns[t] = pa.DictionaryArray.from_arrays(
                pa.array(np.tile(self.tag_codes[t].astype(kind), n)),
                pa.array(self.tag_values[t]))
        columns["timestamp"] = pa.array(self.t0 + np.repeat(
            np.arange(lo_tick, hi_tick, dtype=np.int64) * self.interval_ms,
            self.hosts), type=pa.int64())
        for f, field in enumerate(self.fields):
            batch = pa.record_batch({**columns, "value": pa.array(
                self.values[f, lo_tick:hi_tick].reshape(-1)
                .astype(np.float64), type=pa.float64())})
            sink = io.BytesIO()
            with ipc.new_stream(sink, batch.schema) as w:
                w.write_batch(batch)
            yield field, sink.getvalue()

    # ---- reference --------------------------------------------------------

    def tick_range(self, start: int, end: int) -> tuple[int, int]:
        """Ticks whose timestamp lies in [start, end)."""
        lo = max(0, -(-(start - self.t0) // self.interval_ms))
        hi = min(self.ticks, -(-(end - self.t0) // self.interval_ms))
        return lo, max(lo, hi)


    def groupby(self, start: int, end: int, bucket_ms: int,
                hosts=None, values: np.ndarray | None = None) -> dict:
        """Reference grids {agg: (hosts, buckets) f64} for
        [start, end) cut into buckets of `bucket_ms` from `start`.
        `values` substitutes another value grid (the control's)."""
        grid = self.grid if values is None else values
        cols = slice(None) if hosts is None else list(hosts)
        nb = -(-(end - start) // bucket_ms)
        n_hosts = self.hosts if hosts is None else len(cols)
        out = {a: np.full((nb, n_hosts), np.nan) for a in AGGS}
        out["count"][:] = 0.0
        for b in range(nb):
            lo, hi = self.tick_range(start + b * bucket_ms,
                                     min(end, start + (b + 1) * bucket_ms))
            if hi <= lo:
                continue
            cell = grid[lo:hi, cols].astype(np.float64)
            out["count"][b] = hi - lo
            out["sum"][b] = cell.sum(axis=0)
            out["avg"][b] = out["sum"][b] / (hi - lo)
            out["min"][b] = cell.min(axis=0)
            out["max"][b] = cell.max(axis=0)
            out["last"][b] = cell[-1]
        return {a: g.T for a, g in out.items()}
