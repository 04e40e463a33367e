"""Tiered scan cache tests (ISSUE 4): tier-2 encoded-part byte-LRU
semantics, write-through admission visibility, incremental re-merge
correctness under seeded flush/compaction interleavings, per-SST
invalidation, and regression tests for the four satellite bugfixes
(blob-dict offset overflow, union-dictionary bound, sidecar-missing
memo poisoning, all-empty binary payload buffers).

The seeded interleaving test rides `make chaos` with knobs
SCANCACHE_SEED / SCANCACHE_SCHEDULES."""

import asyncio
import json
import os
import random
import struct

import numpy as np
import pyarrow as pa
import pytest

from horaedb_tpu.common import ReadableDuration
from horaedb_tpu.common import runtimes as runtimes_mod
from horaedb_tpu.objstore import MemoryObjectStore, WrappedObjectStore
from horaedb_tpu.ops import encode
from horaedb_tpu.storage import sidecar
from horaedb_tpu.storage.config import StorageConfig, ThreadsConfig, from_dict
from horaedb_tpu.storage.encoded_cache import EncodedSegmentCache
from horaedb_tpu.storage.read import ScanRequest
from horaedb_tpu.storage.storage import CloudObjectStorage, WriteRequest
from horaedb_tpu.storage.types import TimeRange
from horaedb_tpu.wal import IngestStorage, WalConfig

SEED = int(os.environ.get("SCANCACHE_SEED", "1337"), 0)
SCHEDULES = int(os.environ.get("SCANCACHE_SCHEDULES", "8"), 0)

SEGMENT_MS = 3_600_000
SCHEMA = pa.schema([("k", pa.string()), ("ts", pa.int64()),
                    ("v", pa.float64())])


@pytest.fixture(scope="module")
def runtimes():
    rt = runtimes_mod.from_config(ThreadsConfig())
    yield rt
    rt.close()


def run(coro):
    return asyncio.run(coro)


def batch(rows):
    k, t, v = zip(*rows)
    return pa.record_batch(
        [pa.array(list(k)), pa.array(list(t), type=pa.int64()),
         pa.array(list(v), type=pa.float64())], schema=SCHEMA)


def wreq(rows):
    lo = min(r[1] for r in rows)
    hi = max(r[1] for r in rows) + 1
    return WriteRequest(batch(rows), TimeRange.new(lo, hi))


def storage_config(**scan_cache):
    cfg = from_dict(StorageConfig, {
        "scheduler": {"schedule_interval": "1h", "input_sst_min_num": 2},
        "scan": {"cache": scan_cache} if scan_cache else {},
    })
    cfg.manifest.merge_interval = ReadableDuration.parse("1h")
    cfg.scrub.interval = ReadableDuration.parse("1h")
    return cfg


async def open_storage(store, runtimes, **scan_cache):
    return await CloudObjectStorage.open(
        "db", SEGMENT_MS, store, SCHEMA, 2,
        storage_config(**scan_cache), runtimes=runtimes)


async def scan_rows(s, pred=None):
    out = []
    async for b in s.scan(ScanRequest(range=TimeRange.new(0, 10**12),
                                      predicate=pred)):
        out.extend(zip(b.column(0).to_pylist(), b.column(1).to_pylist(),
                       b.column(2).to_pylist()))
    return sorted(out)


class CountingStore(WrappedObjectStore):
    """Counts data-plane reads, split by object kind."""

    def __init__(self, inner=None):
        super().__init__(inner or MemoryObjectStore())
        self.enc_gets = 0
        self.sst_gets = 0

    async def _call(self, op: str, *args):
        if op in ("get", "get_range"):
            path = str(args[0])
            if path.endswith(".enc"):
                self.enc_gets += 1
            elif path.endswith(".sst"):
                self.sst_gets += 1
        return await super()._call(op, *args)


def part(names_arrays):
    """{name: (arr, enc)} of int32 numeric columns for unit tests."""
    return {nm: (np.asarray(a, dtype=np.int32),
                 encode.ColumnEncoding("numeric", pa.int32()))
            for nm, a in names_arrays.items()}


# ---------------------------------------------------------------------------
# tier-2 unit semantics
# ---------------------------------------------------------------------------


@pytest.fixture
def tier2():
    """Builds EncodedSegmentCaches and clears them on exit: their bytes
    ride the process-global scan_cache_bytes{tier="tier2"} gauge, which
    another file's test (test_memledger's close discipline) reads as 0
    when it shares this worker."""
    built = []

    def make(**kw):
        built.append(EncodedSegmentCache(**kw))
        return built[-1]

    yield make
    for c in built:
        c.clear()


def test_byte_lru_eviction_order_and_accounting(tier2):
    one = part({"a": np.zeros(100)})  # 400 bytes
    c = tier2(max_bytes=1000)
    c.put(1, one, 100)
    c.put(2, one, 100)
    assert len(c) == 2 and c.total_bytes == 800
    c.get(1, {"a"})  # 1 becomes MRU; 2 is now LRU
    c.put(3, one, 100)  # 1200 > 1000: evicts 2
    assert c.get(2, {"a"}) is None
    assert c.get(1, {"a"}) is not None
    assert c.get(3, {"a"}) is not None
    assert c.total_bytes == 800 and c.evictions == 1
    # an entry larger than the whole budget is skipped, not thrashed
    c.put(4, part({"a": np.zeros(1000)}), 1000)
    assert c.get(4, {"a"}) is None
    assert c.total_bytes == 800


def test_get_subset_semantics_and_widening(tier2):
    c = tier2(max_bytes=1 << 20)
    c.put(7, part({"a": np.arange(10), "b": np.arange(10)}), 10)
    got = c.get(7, {"a"})
    assert got is not None and set(got[0]) == {"a"} and got[1] == 10
    # a column the entry lacks => miss, not a partial hit
    assert c.get(7, {"a", "c"}) is None
    # inserting a part with the missing column WIDENS the entry
    c.put(7, part({"c": np.arange(10)}), 10)
    got = c.get(7, {"a", "b", "c"})
    assert got is not None and set(got[0]) == {"a", "b", "c"}


def test_invalidate_missing_and_disabled(tier2):
    c = tier2(max_bytes=1 << 20)
    c.put(1, part({"a": np.arange(4)}), 4)
    c.mark_missing(2)
    assert c.is_missing(2)
    assert c.invalidate([1, 2, 99]) == 1
    assert c.get(1, {"a"}) is None and not c.is_missing(2)
    # admission clears a stale negative entry for the same id
    c.mark_missing(3)
    assert c.admit(3, part({"a": np.arange(4)}), 4)
    assert not c.is_missing(3)
    # disabled tier: put/admit are no-ops, negative memo still works
    off = tier2(max_bytes=0)
    off.put(1, part({"a": np.arange(4)}), 4)
    assert not off.admit(2, part({"a": np.arange(4)}), 4)
    assert len(off) == 0 and off.get(1, {"a"}) is None
    off.mark_missing(9)
    assert off.is_missing(9)
    # write_through=False refuses admission but keeps the read path
    ro = tier2(max_bytes=1 << 20, write_through=False)
    assert not ro.admit(1, part({"a": np.arange(4)}), 4)
    ro.put(1, part({"a": np.arange(4)}), 4)
    assert ro.get(1, {"a"}) is not None


# ---------------------------------------------------------------------------
# write-through admission + incremental re-merge through real storage
# ---------------------------------------------------------------------------


def test_write_through_admission_serves_scans_without_store_reads(runtimes):
    async def go():
        store = CountingStore()
        s = await open_storage(store, runtimes)
        try:
            r1 = await s.write(wreq([("a", 10, 1.0), ("b", 20, 2.0)]))
            cache = s.reader.encoded_cache
            assert cache.admissions == 1 and len(cache) == 1
            rows = await scan_rows(s)
            assert rows == [("a", 10, 1.0), ("b", 20, 2.0)]
            # the freshly-written SST was admitted at write time: the
            # scan read NOTHING from the store's data plane
            assert store.enc_gets == 0 and store.sst_gets == 0
            assert cache.hits >= 1

            # incremental re-merge: a second SST lands in the same
            # segment; with admission ON the re-merge still reads
            # nothing
            await s.write(wreq([("b", 20, 9.0), ("c", 30, 3.0)]))
            s.reader.scan_cache.clear()
            rows = await scan_rows(s)
            assert rows == [("a", 10, 1.0), ("b", 20, 9.0),
                            ("c", 30, 3.0)]
            assert store.enc_gets == 0 and store.sst_gets == 0

            # now drop ONE SST's entry: only that sidecar is re-fetched
            cache.invalidate([r1.id])
            s.reader.scan_cache.clear()
            rows = await scan_rows(s)
            assert rows == [("a", 10, 1.0), ("b", 20, 9.0),
                            ("c", 30, 3.0)]
            assert store.enc_gets == 1 and store.sst_gets == 0
        finally:
            await s.close()

    run(go())


def test_tier2_disabled_reproduces_store_reads(runtimes):
    async def go():
        store = CountingStore()
        s = await open_storage(store, runtimes, tier2_max_bytes=0)
        try:
            await s.write(wreq([("a", 10, 1.0)]))
            for i in range(2):
                s.reader.scan_cache.clear()
                assert await scan_rows(s) == [("a", 10, 1.0)]
            # every cold scan re-reads the sidecar: nothing was cached
            assert store.enc_gets == 2
            assert len(s.reader.encoded_cache) == 0
        finally:
            await s.close()

    run(go())


def test_compaction_invalidates_inputs_and_admits_output(runtimes):
    async def go():
        store = CountingStore()
        s = await open_storage(store, runtimes)
        try:
            ids = []
            for i in range(3):
                r = await s.write(wreq([(f"k{i}", 10 + i, float(i)),
                                        ("dup", 50, float(i))]))
                ids.append(r.id)
            sched = s.compact_scheduler
            task = await sched.picker.pick_candidate()
            assert task is not None
            await sched.executor.execute(task)
            cache = s.reader.encoded_cache
            # inputs dropped, compacted output admitted
            for fid in ids:
                assert cache.get(fid, {"k"}) is None
            assert cache.invalidated == 3
            ssts = await s.manifest.all_ssts()
            assert len(ssts) == 1
            out_id = ssts[0].id
            assert cache.get(out_id, {"k", "ts", "v", "__seq__"}) \
                is not None
            # post-compaction scan: served from the admitted entry
            before = store.enc_gets
            s.reader.scan_cache.clear()
            rows = await scan_rows(s)
            assert rows == [("dup", 50, 2.0), ("k0", 10, 0.0),
                            ("k1", 11, 1.0), ("k2", 12, 2.0)]
            assert store.enc_gets == before and store.sst_gets == 0
        finally:
            await s.close()

    run(go())


# ---------------------------------------------------------------------------
# seeded flush-vs-scan / compaction interleavings (make chaos)
# ---------------------------------------------------------------------------


def test_seeded_flush_compaction_scan_interleavings(runtimes, tmp_path):
    """Random op schedules over a WAL-fronted storage: every query's
    rows must equal the last-write-wins model regardless of which tier
    served which segment, across flushes (SST-set changes), compactions
    (SST deletes + admissions), and cache evictions."""

    async def one_schedule(i: int) -> None:
        rng = random.Random(SEED + i)
        store = CountingStore()
        inner = await open_storage(store, runtimes)
        wal_dir = tmp_path / f"wal{i}"
        wc = WalConfig(enabled=True, dir=str(wal_dir), flush_rows=10**6,
                       flush_bytes=1 << 30,
                       flush_age=ReadableDuration.parse("1h"),
                       flush_interval=ReadableDuration.parse("1h"),
                       max_group_wait=ReadableDuration.from_millis(0))
        s = await IngestStorage.open(inner, str(wal_dir), wc)
        model: dict = {}
        seq = 0
        try:
            for _op in range(14):
                op = rng.choice(["write", "write", "write", "flush",
                                 "query", "query", "compact",
                                 "evict1", "evict2"])
                if op == "write":
                    rows = []
                    for _ in range(rng.randint(1, 4)):
                        seg = rng.randint(0, 2)
                        k = f"k{rng.randint(0, 5)}"
                        ts = seg * SEGMENT_MS + rng.randint(0, 999)
                        v = float(seq)
                        seq += 1
                        rows.append((k, ts, v))
                    # one request must stay within one segment
                    seg0 = rows[0][1] // SEGMENT_MS
                    rows = [r for r in rows if r[1] // SEGMENT_MS == seg0]
                    await s.write(wreq(rows))
                    for k, ts, v in rows:
                        model[(k, ts)] = v
                elif op == "flush":
                    await s.flush_all()
                elif op == "compact":
                    await s.flush_all()
                    sched = inner.compact_scheduler
                    task = await sched.picker.pick_candidate()
                    if task is not None:
                        await sched.executor.execute(task)
                elif op == "evict1":
                    inner.reader.scan_cache.clear()
                elif op == "evict2":
                    inner.reader.encoded_cache.clear()
                else:
                    got = await scan_rows(s)
                    want = sorted((k, ts, v) for (k, ts), v
                                  in model.items())
                    assert got == want, f"schedule {i} diverged"
            got = await scan_rows(s)
            want = sorted((k, ts, v) for (k, ts), v in model.items())
            assert got == want, f"schedule {i} final state diverged"
        finally:
            await s.close()

    async def go():
        for i in range(SCHEDULES):
            await one_schedule(i)

    run(go())


# ---------------------------------------------------------------------------
# satellite bugfix regressions
# ---------------------------------------------------------------------------


def test_dict_blob_overflow_refused_by_writer(monkeypatch):
    """A blob dictionary whose payload would wrap int32 offsets must
    not serialize (pre-fix: np.cumsum accumulated in int32 and silently
    wrapped, serving WRONG values on read)."""
    b = batch([("alpha", 10, 1.0), ("beta", 20, 2.0)])
    cols = sidecar.encode_columns(b)
    assert sidecar.serialize(cols, b.num_rows) is not None
    # shrink the bound below the real payload: serialize must refuse
    monkeypatch.setattr(sidecar, "_DICT_BLOB_MAX", 4)
    assert sidecar.serialize(cols, b.num_rows) is None


def _patch_dict_offsets(data: bytes, col: str, new_offs) -> bytes:
    """Rewrite `col`'s blob-dict offsets section inside a serialized
    sidecar (test harness for read-side validation)."""
    (header_len,) = struct.unpack_from("<I", data, 8)
    header = json.loads(data[12:12 + header_len].decode())
    data_start = -(-(12 + header_len) // 16) * 16
    meta = next(m for m in header["columns"] if m["name"] == col)
    off = data_start + header["sections"][meta["dict_section"]]
    raw = np.asarray(new_offs, dtype=np.int32).tobytes()
    return data[:off] + raw + data[off + len(raw):]


def test_corrupt_dict_offsets_read_as_invalid_not_garbage():
    b = batch([("aa", 10, 1.0), ("bb", 20, 2.0)])
    data = sidecar.build(b)
    assert data is not None
    want = {"k", "ts", "v"}
    assert sidecar.deserialize(data, want) is not None
    # wrapped (decreasing / negative) offsets — the pre-fix reader
    # sliced garbage strings out of the blob
    bad = _patch_dict_offsets(data, "k", [0, -3, 1])
    assert sidecar.deserialize(bad, want) is None
    # truncated blob: final offset beyond the stored bytes
    bad = _patch_dict_offsets(data, "k", [0, 2, 2 << 20])
    assert sidecar.deserialize(bad, want) is None


def test_union_dict_bound_falls_back_to_parquet(runtimes, monkeypatch):
    """A cross-SST union dictionary at the merge kernel's pad sentinel
    must fall back to parquet — and must NOT memoize the member SSTs as
    sidecar-missing (the old whole-set memo permanently disabled valid
    sidecars; satellite 3)."""

    async def go():
        store = CountingStore()
        s = await open_storage(store, runtimes)
        try:
            r1 = await s.write(wreq([("a", 10, 1.0), ("b", 11, 2.0)]))
            r2 = await s.write(wreq([("c", 20, 3.0), ("d", 21, 4.0)]))
            expect = [("a", 10, 1.0), ("b", 11, 2.0), ("c", 20, 3.0),
                      ("d", 21, 4.0)]
            # union of the two k-dictionaries (4) exceeds the patched
            # bound -> concat refuses -> parquet serves the scan
            monkeypatch.setattr(sidecar, "_MAX_DICT_CODES", 3)
            s.reader.scan_cache.clear()
            s.reader.encoded_cache.clear()
            assert await scan_rows(s) == expect
            assert store.sst_gets > 0
            cache = s.reader.encoded_cache
            assert not cache.is_missing(r1.id)
            assert not cache.is_missing(r2.id)
            # the failing COMPOSITION is memoized: a repeat cold scan
            # must not re-download the sidecars just to fail again
            assert cache.is_assembly_failed({r1.id, r2.id})
            enc0 = store.enc_gets
            s.reader.scan_cache.clear()
            assert await scan_rows(s) == expect
            assert store.enc_gets == enc0
            # with the real bound restored the same sidecars assemble
            # fine — the failure did not poison them
            monkeypatch.setattr(sidecar, "_MAX_DICT_CODES", 2**31 - 1)
            store.sst_gets = 0
            s.reader.scan_cache.clear()
            s.reader.encoded_cache.clear()
            assert await scan_rows(s) == expect
            assert store.sst_gets == 0 and store.enc_gets >= 2
        finally:
            await s.close()

    run(go())


def test_one_bad_sidecar_memoizes_per_sst_only(runtimes):
    """A segment with one corrupt sidecar falls back to parquet and
    memoizes ONLY the corrupt SST as missing — its healthy sibling's
    sidecar keeps serving other compositions."""

    async def go():
        store = CountingStore()
        s = await open_storage(store, runtimes)
        try:
            r1 = await s.write(wreq([("a", 10, 1.0)]))
            r2 = await s.write(wreq([("b", 20, 2.0)]))
            # corrupt r2's sidecar object in place (ids are immutable,
            # so the reader treats a parse failure as permanent)
            path = sidecar.sidecar_path("db", r2.id)
            await store.put(path, b"HDTPENC1garbage")
            s.reader.scan_cache.clear()
            s.reader.encoded_cache.clear()
            assert await scan_rows(s) == [("a", 10, 1.0), ("b", 20, 2.0)]
            cache = s.reader.encoded_cache
            assert cache.is_missing(r2.id)
            assert not cache.is_missing(r1.id)
        finally:
            await s.close()

    run(go())


def test_payload_buffers_all_empty_binary_falls_back(monkeypatch):
    """buffers()[2] can be None for an all-empty binary array on some
    pyarrow builds; the native fast path must return the
    Python-decoder fallback signal, not crash on .address
    (satellite 4).  from_buffers validates the shape away, so the
    None-data-buffer case is pinned through the _arrow_buffers seam."""
    from horaedb_tpu import native

    arr = pa.array([b"", b""], type=pa.binary())
    # whatever buffer shape this pyarrow materializes must not raise
    native._payload_buffers(arr)
    monkeypatch.setattr(
        native, "_arrow_buffers",
        lambda payloads: [None, payloads.buffers()[1], None])
    holder, ptr, offs, n = native._payload_buffers(arr)
    assert ptr is None and n == 0


# ---------------------------------------------------------------------------
# tier hbm: device-decode slices beside the windows (ISSUE 29)
# ---------------------------------------------------------------------------


class _Slice:
    """What ScanCache needs of a device_decode.SegmentSlice."""

    def __init__(self, nbytes: int):
        self.nbytes = nbytes


def _windows(capacity: int) -> list:
    cols = {"a": np.zeros(capacity, dtype=np.int32)}
    return [encode.DeviceBatch(
        columns=cols,
        encodings={"a": encode.ColumnEncoding("numeric", pa.int32())},
        n_valid=capacity, capacity=capacity)]


def _events(kind: str) -> dict:
    from horaedb_tpu.utils import registry

    fam = registry.family("scan_cache_account_events_total")
    return {e: fam.labels(tier="hbm", kind=kind, event=e).value
            for e in ("evicted", "declined")}


def test_windows_and_slices_never_evict_each_other():
    """One class, two accounts: a budget, an LRU order and counts each.
    Filling either to eviction leaves the other as it was."""
    from horaedb_tpu.storage.scan_cache import (
        ScanCache,
        segment_cache_key,
        windows_nbytes,
    )
    from horaedb_tpu.utils import registry

    windows = _windows(128)
    w_bytes = windows_nbytes(windows)
    tier_evictions = registry.family("scan_cache_evictions_total") \
        .labels(tier="hbm")
    t0 = tier_evictions.value
    e0 = {k: _events(k) for k in ("windows", "slice")}
    cache = ScanCache(max_bytes=w_bytes + 100, slice_max_bytes=2_500)
    kw = segment_cache_key(0, [1], ("a",))
    # the window is not in a slice's key; the key leaves' values are
    k1 = segment_cache_key(0, [1], ("a", "decode", "(eq field 'f1')"))
    k2 = segment_cache_key(0, [1], ("a", "decode", "(eq field 'f2')"))
    assert len({kw, k1, k2}) == 3
    cache.put(kw, windows)
    one, two = _Slice(1_000), _Slice(1_200)
    cache.put_slice(k1, one)
    cache.put_slice(k2, two)
    # 2,200 B of slices beside a windows account with 100 B of room
    assert cache.total_bytes == w_bytes and len(cache) == 1
    assert cache.values() == [windows] and cache.slices() == [one, two]
    assert cache.account_stats() == {
        "windows": {"budget_bytes": w_bytes + 100, "bytes": w_bytes,
                    "entries": 1, "evicted": 0, "declined": 0},
        "slice": {"budget_bytes": 2_500, "bytes": 2_200, "entries": 2,
                  "evicted": 0, "declined": 0}}
    # a key of one account is not found in the other
    assert cache.get(k1) is None and cache.get_slice(kw) is None
    # the slices' own LRU: the older one goes, the windows stay
    assert cache.get_slice(k1) is one
    three = _Slice(1_100)
    k3 = segment_cache_key(3_600_000, [2], ("a", "decode"))
    cache.put_slice(k3, three)
    assert cache.get_slice(k2) is None and cache.slices() == [one, three]
    assert cache.get(kw) is windows
    # a changed SST set is another key: nothing to invalidate
    assert cache.get_slice(segment_cache_key(
        0, [1, 9], ("a", "decode", "(eq field 'f1')"))) is None
    # larger than the slices' whole budget, though the windows' would
    # hold it: declined, nothing evicted for it on either side
    assert 2_501 < w_bytes + 100
    cache.put_slice(k2, _Slice(2_501))
    assert cache.get_slice(k2) is None and cache.slices() == [one, three]
    # the windows' own LRU: another list evicts the first, no slice
    other = _windows(128)
    cache.put(segment_cache_key(3_600_000, [2], ("a",)), other)
    assert cache.values() == [other] and cache.slices() == [one, three]
    stats = cache.account_stats()
    assert (stats["windows"]["evicted"], stats["windows"]["declined"],
            stats["slice"]["evicted"], stats["slice"]["declined"]) \
        == (1, 0, 1, 1)
    assert {k: {e: _events(k)[e] - e0[k][e] for e in e0[k]}
            for k in e0} == {
        "windows": {"evicted": 1, "declined": 0},
        "slice": {"evicted": 1, "declined": 1}}
    assert tier_evictions.value - t0 == 2  # the tier's sum of both
    # the HBM-evicted state drops the slices and keeps the windows
    cache.drop_slices()
    assert cache.slices() == [] and cache.values() == [other]
    assert cache.total_bytes == w_bytes
    cache.close()
    assert cache.total_bytes == 0 and len(cache) == 0
    assert all(a["bytes"] == 0 and a["entries"] == 0
               for a in cache.account_stats().values())


def test_one_budget_given_serves_both_accounts_apart():
    """ScanCache(n): each account gets n, and they still do not
    share it (the CPU backend's reader: no device to ask)."""
    from horaedb_tpu.storage.scan_cache import ScanCache, windows_nbytes

    windows = _windows(64)
    w_bytes = windows_nbytes(windows)
    cache = ScanCache(max_bytes=w_bytes)
    cache.put(("w",), windows)
    cache.put_slice(("s",), _Slice(w_bytes))
    assert cache.values() == [windows] and len(cache.slices()) == 1
    assert [a["budget_bytes"] for a in cache.account_stats().values()] \
        == [w_bytes, w_bytes]
    cache.close()


def test_ledger_account_and_stats_report_a_resident_slice(
        runtimes, monkeypatch):
    """One device-decode aggregate leaves its segment's slice in the
    scan cache's slice account: the `scan_cache_device` ledger account
    reports the padded device columns' bytes (no memo allowance) and
    the windows' `scan_cache` account none of them, /stats counts the
    entry under its account, drop_hbm_state releases it, and close()
    leaves nothing."""
    from horaedb_tpu.common import memledger
    from horaedb_tpu.ops.downsample import ALL_AGGS
    from horaedb_tpu.storage.read import AggregateSpec

    monkeypatch.setenv("HORAEDB_DEVICE_DECODE", "1")
    monkeypatch.setenv("HORAEDB_HOST_AGG", "0")

    def account_bytes(kind="scan_cache_device"):
        kinds = memledger.ledger.snapshot()["accounts"]
        return kinds[kind]["bytes"] if kind in kinds else 0

    async def go():
        s = await open_storage(MemoryObjectStore(), runtimes)
        try:
            await s.write(wreq([(f"k{i % 5}", 1_000 * i, float(i))
                                for i in range(300)]))
            spec = AggregateSpec(group_col="k", ts_col="ts", value_col="v",
                                 range_start=0, bucket_ms=60_000,
                                 num_buckets=60, which=ALL_AGGS)
            req = ScanRequest(range=TimeRange.new(0, SEGMENT_MS))
            await s.scan_aggregate(req, spec)
            stats = s.reader.cache_stats()["scan_cache"]
            # k, ts, seq, v padded to 300 rows' capacity bucket
            nbytes = encode.pad_capacity(300) * 4 * 4
            assert (stats["entries"], stats["decode_slices"]) == (1, 1)
            assert stats["bytes"] == stats["decode_slice_bytes"] == nbytes
            assert stats["accounts"]["slice"]["bytes"] == nbytes
            assert stats["accounts"]["windows"]["bytes"] == 0
            assert s.reader._scan_cache_resident_bytes() == 0
            assert account_bytes() == nbytes
            assert account_bytes("scan_cache") == 0
            s.reader.drop_hbm_state()
            assert s.reader.cache_stats()["scan_cache"]["entries"] == 0
            assert account_bytes() == 0
            s.reader.parts_memo.clear()
            await s.scan_aggregate(req, spec)
            assert account_bytes() == nbytes
        finally:
            await s.close()
        assert account_bytes() == 0 == account_bytes("scan_cache")

    run(go())


def test_stats_cache_section(runtimes):
    async def go():
        s = await open_storage(MemoryObjectStore(), runtimes)
        try:
            await s.write(wreq([("a", 10, 1.0)]))
            await scan_rows(s)
            stats = s.reader.cache_stats()
            assert set(stats) == {"scan_cache", "encoded_cache",
                                  "stack_cache", "pipeline",
                                  "parts_memo", "decode", "mesh"}
            assert stats["decode"]["mode"] == "auto"
            assert stats["pipeline"]["enabled"] is True
            assert stats["encoded_cache"]["entries"] == 1
            assert stats["encoded_cache"]["admissions"] == 1
            assert stats["scan_cache"]["bytes"] >= 0
            assert stats["scan_cache"]["decode_slices"] == 0
            assert stats["scan_cache"]["decode_slice_bytes"] == 0
            # budget, bytes, entries, evicted, declined of each account
            accounts = stats["scan_cache"]["accounts"]
            assert set(accounts) == {"windows", "slice"}
            assert accounts["windows"]["budget_bytes"] \
                == stats["scan_cache"]["max_bytes"] \
                == s.reader.cache_budget_bytes
            assert accounts["slice"] == {
                "budget_bytes": s.reader.slice_budget_bytes, "bytes": 0,
                "entries": 0, "evicted": 0, "declined": 0}
        finally:
            await s.close()

    run(go())
