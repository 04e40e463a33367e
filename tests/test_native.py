"""Native C++ kernel tests: build, parity with numpy fallbacks, and the
snapshot wire format."""

import numpy as np
import pytest

from horaedb_tpu import native


def records(n, seed=0):
    rng = np.random.default_rng(seed)
    out = np.empty(n, dtype=native.RECORD_DTYPE)
    out["id"] = rng.integers(0, 2**63, n, dtype=np.uint64)
    out["start"] = rng.integers(-(2**40), 2**40, n)
    out["end"] = out["start"] + rng.integers(1, 10**6, n)
    out["size"] = rng.integers(0, 2**32, n, dtype=np.uint32)
    out["num_rows"] = rng.integers(0, 2**32, n, dtype=np.uint32)
    return out


def test_native_library_builds():
    assert native.available(), (
        "native library failed to build — g++ toolchain is baked into the "
        "image, so this should never fail here")


def test_native_build_renames_a_finished_library_into_place(tmp_path):
    """xdist workers on a fresh checkout all build on first use: the
    linker must write beside the target and `mv` the finished file over
    it, or a worker loads another's half-written library ("file too
    short") and serves numpy fallbacks for its lifetime.  A stand-in
    compiler records where it was told to write."""
    import os
    import pathlib
    import shutil
    import subprocess

    src = pathlib.Path(native.__file__).resolve().parents[2] / "native"
    shutil.copy(src / "Makefile", tmp_path)
    (tmp_path / "horaedb_native.cpp").write_text("// stand-in\n")
    cxx = tmp_path / "cxx.sh"
    cxx.write_text('#!/bin/sh\nwhile [ "$1" != "-o" ]; do shift; done\n'
                   'echo "$2" > written_to; echo built > "$2"\n')
    cxx.chmod(0o755)
    subprocess.run(["make", "-C", str(tmp_path), f"CXX={cxx}"], check=True,
                   capture_output=True, timeout=60, cwd=tmp_path)
    target = tmp_path / "libhoraedb_native.so"
    assert target.read_text() == "built\n"
    written_to = (tmp_path / "written_to").read_text().strip()
    assert os.path.basename(written_to) != target.name
    assert not os.path.exists(tmp_path / written_to)   # moved, not copied


class TestSnapshotCodec:
    def test_roundtrip(self):
        recs = records(1000)
        buf = native.snapshot_encode(recs)
        assert len(buf) == 14 + 1000 * 32
        back = native.snapshot_decode(buf)
        np.testing.assert_array_equal(back, recs)

    def test_empty(self):
        assert len(native.snapshot_decode(b"")) == 0
        buf = native.snapshot_encode(np.empty(0, dtype=native.RECORD_DTYPE))
        # empty snapshots encode to zero bytes, not a header-only buffer
        # (the reference rejects header-only: encoding.rs requires
        # record_total_length > 0)
        assert buf == b""
        assert len(native.snapshot_decode(buf)) == 0

    def test_header_only_rejected(self):
        import struct

        from horaedb_tpu.common import Error
        header_only = struct.pack("<IBBQ", native.SNAPSHOT_MAGIC,
                                  native.SNAPSHOT_VERSION, 0, 0)
        with pytest.raises(Error, match="empty"):
            native.snapshot_decode(header_only)

    def test_wire_layout_golden(self):
        """The structured dtype's memory IS the wire format."""
        rec = np.zeros(1, dtype=native.RECORD_DTYPE)
        rec["id"] = 0x0102030405060708
        rec["start"] = -1
        rec["size"] = 0xAABBCCDD
        buf = native.snapshot_encode(rec)
        body = buf[14:]
        assert body[:8] == bytes([8, 7, 6, 5, 4, 3, 2, 1])  # LE u64
        assert body[8:16] == b"\xff" * 8                      # -1 as i64
        assert body[24:28] == bytes([0xDD, 0xCC, 0xBB, 0xAA])

    def test_bad_magic(self):
        from horaedb_tpu.common import Error
        with pytest.raises(Error, match="header"):
            native.snapshot_decode(b"\x00" * 46)

    def test_truncated(self):
        from horaedb_tpu.common import Error
        buf = native.snapshot_encode(records(2))
        with pytest.raises(Error, match="mismatch"):
            native.snapshot_decode(buf[:-3])


class TestRunKernels:
    def numpy_starts(self, cols):
        n = len(cols[0])
        starts = np.zeros(n, dtype=bool)
        starts[0] = True
        for c in cols:
            starts[1:] |= c[1:] != c[:-1]
        return starts

    @pytest.mark.parametrize("seed", range(3))
    def test_run_starts_parity(self, seed):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 5000))
        cols = [np.sort(rng.integers(0, 50, n)).astype(np.int64)
                for _ in range(2)]
        got = native.run_starts_i64(cols)
        np.testing.assert_array_equal(got, self.numpy_starts(cols))

    def test_run_last_indices(self):
        starts = np.array([1, 0, 1, 1, 0, 0], dtype=bool)
        out = native.run_last_indices(starts)
        assert out.tolist() == [1, 2, 5]

    def test_single_run(self):
        starts = np.array([1, 0, 0], dtype=bool)
        assert native.run_last_indices(starts).tolist() == [2]

    def test_empty(self):
        assert native.run_starts_i64([np.zeros(0, dtype=np.int64)]).tolist() == []
        assert native.run_last_indices(np.zeros(0, dtype=bool)).tolist() == []


class TestSpecTwinParity:
    """The Python spec classes in encoding.py must produce byte-identical
    output to the native codec — they are the format's cross-check."""

    def test_record_bytes_match_native(self):
        from horaedb_tpu.storage.manifest.encoding import SnapshotRecord
        from horaedb_tpu.storage.types import TimeRange
        rec = SnapshotRecord(id=12345, time_range=TimeRange.new(-77, 999),
                             size=4096, num_rows=8192)
        arr = np.array([(12345, -77, 999, 4096, 8192)],
                       dtype=native.RECORD_DTYPE)
        native_body = native.snapshot_encode(arr)[14:]
        assert rec.to_bytes() == native_body

    def test_header_bytes_match_native(self):
        from horaedb_tpu.storage.manifest.encoding import SnapshotHeader
        arr = np.zeros(3, dtype=native.RECORD_DTYPE)
        native_header = native.snapshot_encode(arr)[:14]
        assert SnapshotHeader(length=3 * 32).to_bytes() == native_header


class TestSeaHashNative:
    """The C++ SeaHash must be byte-identical to the Python spec twin
    (common/seahash._hash64_py) — metric/series ids derive from it."""

    def test_single_matches_spec_twin(self):
        from horaedb_tpu.common.seahash import _hash64_py

        if not native.available():
            import pytest
            pytest.skip("native library unavailable")
        rng = np.random.default_rng(3)
        cases = [b"", b"a", b"to be or not to be", b"x" * 31, b"y" * 32,
                 b"z" * 33] + [
            bytes(rng.integers(0, 256, int(n)).astype(np.uint8))
            for n in rng.integers(0, 300, 64)]
        for buf in cases:
            assert native.seahash64(buf) == _hash64_py(buf), buf

    def test_batch_matches_singles(self):
        from horaedb_tpu.common.seahash import _hash64_py

        if not native.available():
            import pytest
            pytest.skip("native library unavailable")
        keys = [f"cpu{{host=h{i:03d},region=r{i % 5}}}".encode()
                for i in range(512)] + [b""]
        out = native.seahash64_batch(keys)
        assert [int(h) for h in out] == [_hash64_py(k) for k in keys]

    def test_hash64_routes_native_and_masks_consistently(self):
        from horaedb_tpu.common.seahash import _hash64_py, hash64
        from horaedb_tpu.metric_engine.types import (series_key_of,
                                                     tsid_of, tsids_of_keys)
        from horaedb_tpu.metric_engine.types import Label

        if not native.available():  # load so hash64 takes the native route
            import pytest
            pytest.skip("native library unavailable")
        assert native.is_loaded()
        key = series_key_of("cpu", [Label("host", "a"), Label("dc", "b")])
        assert hash64(key) == _hash64_py(key)
        assert int(tsids_of_keys([key])[0]) == tsid_of(
            "cpu", [Label("host", "a"), Label("dc", "b")])


class TestChunkBatchDecode:
    """Native batch chunk decode must be BIT-identical to the Python
    spec twin (metric_engine/chunks.py) across codec modes, chunk
    concatenation order, duplicates, and malformed payloads."""

    def _payloads(self, seed):
        from horaedb_tpu.metric_engine import chunks

        rng = np.random.default_rng(seed)
        payloads = []
        for _ in range(30):
            parts = []
            for _c in range(rng.integers(1, 4)):
                n = int(rng.integers(1, 200))
                base = int(rng.integers(0, 2**40))
                kind = rng.integers(0, 4)
                if kind == 0:  # regular interval, integer gauge
                    ts = base + np.arange(n, dtype=np.int64) * 10_000
                    vals = rng.integers(0, 1000, n).astype(np.float64)
                elif kind == 1:  # jittery interval, float values (XOR)
                    ts = base + np.cumsum(rng.integers(1, 5000, n))
                    vals = rng.random(n) * 1e6
                elif kind == 2:  # 2-decimal gauge (scaled-int)
                    ts = base + np.arange(n, dtype=np.int64) * 500
                    vals = np.round(rng.random(n) * 100, 2)
                else:  # constant series + duplicate timestamps
                    ts = base + rng.integers(0, max(1, n // 2), n) * 1000
                    vals = np.full(n, 42.5)
                parts.append(chunks.encode_chunk(
                    np.asarray(ts, dtype=np.int64), vals))
            payloads.append(b"".join(parts))
        return payloads

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_parity_with_python_decoder(self, seed):
        from horaedb_tpu import native
        from horaedb_tpu.metric_engine import chunks

        if not native.available():
            pytest.skip("native library unavailable")
        payloads = self._payloads(seed)
        got = native.chunk_decode_batch(payloads)
        assert got is not None
        ts, vals, counts = got
        assert counts.sum() == len(ts) == len(vals)
        off = 0
        for i, p in enumerate(payloads):
            want_ts, want_vals = chunks.decode_chunks(p)
            k = int(counts[i])
            assert k == len(want_ts), f"payload {i}"
            np.testing.assert_array_equal(ts[off:off + k], want_ts)
            # bit-identical, not just close: same codec, same math
            np.testing.assert_array_equal(
                vals[off:off + k].view(np.uint64),
                want_vals.view(np.uint64), err_msg=f"payload {i}")
            off += k

    def test_arrow_binary_array_input(self):
        import pyarrow as pa

        from horaedb_tpu import native
        from horaedb_tpu.metric_engine import chunks

        if not native.available():
            pytest.skip("native library unavailable")
        payloads = self._payloads(7)
        arr = pa.array(payloads, type=pa.binary())
        got_arr = native.chunk_decode_batch(arr)
        got_list = native.chunk_decode_batch(payloads)
        assert got_arr is not None and got_list is not None
        for a, b in zip(got_arr, got_list):
            np.testing.assert_array_equal(a, b)
        # sliced array (non-zero offset) must stay correct too
        sl = arr.slice(3, 10)
        got_sl = native.chunk_decode_batch(sl)
        assert got_sl is not None
        off = int(got_list[2][:3].sum())
        k = int(got_list[2][3:13].sum())
        np.testing.assert_array_equal(got_sl[0], got_list[0][off:off + k])

    def test_malformed_payload_returns_none(self):
        from horaedb_tpu import native
        from horaedb_tpu.metric_engine import chunks

        if not native.available():
            pytest.skip("native library unavailable")
        good = chunks.encode_chunk(np.array([1000], dtype=np.int64),
                                   np.array([1.0]))
        assert native.chunk_decode_batch([good]) is not None
        assert native.chunk_decode_batch([b"\xff garbage"]) is None
        assert native.chunk_decode_batch([good[:5]]) is None
        assert native.chunk_decode_batch([good, b"\xc8" + b"\x00" * 5]) \
            is None

    def test_empty_inputs(self):
        from horaedb_tpu import native

        if not native.available():
            pytest.skip("native library unavailable")
        ts, vals, counts = native.chunk_decode_batch([])
        assert len(ts) == 0 and len(counts) == 0
        # empty payload for a row: zero points, not an error
        got = native.chunk_decode_batch([b""])
        assert got is not None and got[2].tolist() == [0]
