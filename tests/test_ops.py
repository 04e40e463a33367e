"""Numerical-equality tests for the JAX compute ops vs numpy references.

These are the kernel-vs-reference tests SURVEY.md section 4 calls for:
every device op must match a straightforward numpy model bit-for-bit
(int paths) or to float32 tolerance (aggregations).
"""

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from horaedb_tpu.ops import (
    And,
    Eq,
    Ge,
    Gt,
    In,
    Le,
    Lt,
    Ne,
    Not,
    Or,
    TimeRangePred,
    decode_to_arrow,
    dedup_sorted_last,
    encode_batch,
    eval_predicate,
    merge_dedup_last,
    pad_capacity,
    sorted_run_starts,
    time_bucket_aggregate,
    top_k_groups,
)


class TestEncodeDecode:
    def test_pad_capacity(self):
        assert pad_capacity(0) == 128
        assert pad_capacity(128) == 128
        assert pad_capacity(129) == 256
        assert pad_capacity(5000) == 8192

    def test_roundtrip_types(self):
        batch = pa.record_batch({
            "host": pa.array(["web-1", "db-0", "web-1", "api-3"]),
            "ts": pa.array([1_700_000_000_000, 1_700_000_060_000,
                            1_700_000_120_000, 1_700_000_000_500],
                           type=pa.int64()),
            "cpu": pa.array([0.5, 0.25, 0.75, 1.0], type=pa.float64()),
            "small": pa.array([1, -2, 3, -4], type=pa.int32()),
        })
        dev = encode_batch(batch)
        assert dev.capacity == 128 and dev.n_valid == 4
        for name in dev.names:
            assert dev.columns[name].dtype in (np.int32, np.float32)
        back = decode_to_arrow(dev)
        assert back.column(0).to_pylist() == batch.column(0).to_pylist()
        assert back.column(1).to_pylist() == batch.column(1).to_pylist()
        assert back.column(2).to_pylist() == pytest.approx(batch.column(2).to_pylist())
        assert back.column(3).to_pylist() == batch.column(3).to_pylist()

    def test_f64_overflow_clamps_and_counts(self):
        """VERDICT item 7: finite f64 values beyond the f32 range clamp
        to ±f32::MAX (with a counter) instead of silently becoming inf;
        true infinities pass through as the caller wrote them."""
        from horaedb_tpu.ops.encode import encode_column
        from horaedb_tpu.utils import registry

        counter = registry.counter("horaedb_encode_overflow_total")
        before = counter.value
        col = pa.array([1e39, -1e39, 1.0, float("inf")], type=pa.float64())
        dev, enc = encode_column(col, "v")
        assert enc.kind == "numeric"
        f32_max = np.finfo(np.float32).max
        assert dev[0] == f32_max and dev[1] == -f32_max
        assert dev[2] == np.float32(1.0)
        assert np.isinf(dev[3])  # caller-supplied inf is not clamped
        assert counter.value == before + 2

    def test_dict_codes_order_preserving(self):
        batch = pa.record_batch({"h": pa.array(["c", "a", "b", "a"])})
        dev = encode_batch(batch)
        codes = np.asarray(dev.columns["h"][:4])
        # sorted uniques: a=0, b=1, c=2
        assert codes.tolist() == [2, 0, 1, 0]

    def test_u64_seq_roundtrip(self):
        seqs = [2**40 + 5, 2**40 + 1, 2**40 + 3]
        batch = pa.record_batch({"__seq__": pa.array(seqs, type=pa.uint64())})
        dev = encode_batch(batch)
        codes = np.asarray(dev.columns["__seq__"][:3])
        # offset-encoded: order preserved
        assert (np.argsort(codes) == np.argsort(seqs)).all()
        assert decode_to_arrow(dev).column(0).to_pylist() == seqs

    def test_wide_span_int64_falls_back_to_rank(self):
        """Sequences from different process starts span >> int32; they
        must rank-encode (dict) and survive merge + decode exactly."""
        seqs = [1_700_000_000_000_000_000, 1_700_000_000_000_000_001,
                1_790_000_000_000_000_000]
        b = pa.record_batch({
            "pk": pa.array([1, 1, 1], type=pa.int32()),
            "__seq__": pa.array(seqs, type=pa.uint64()),
            "v": pa.array([1.0, 2.0, 3.0], type=pa.float64()),
        })
        dev = encode_batch(b)
        assert dev.encodings["__seq__"].kind == "dict"
        out_pks, out_seq, out_vals, _, nr = merge_dedup_last(
            (dev.columns["pk"],), dev.columns["__seq__"],
            (dev.columns["v"],), 3)
        assert int(nr) == 1
        assert float(np.asarray(out_vals[0])[0]) == 3.0  # max-seq row wins
        from horaedb_tpu.ops import DeviceBatch
        out = decode_to_arrow(
            DeviceBatch(columns={"__seq__": out_seq}, encodings=dev.encodings,
                        n_valid=1, capacity=dev.capacity), names=["__seq__"])
        assert out.column(0).to_pylist() == [1_790_000_000_000_000_000]


class TestMergeDedup:
    def np_reference(self, pks, seq, values, n):
        """Sort by (pks..., seq); keep last row of each pk run."""
        rows = list(zip(*[list(c[:n]) for c in pks], list(seq[:n]),
                        *[list(c[:n]) for c in values]))
        rows.sort(key=lambda r: r[: len(pks) + 1])
        out = {}
        for r in rows:
            out[r[: len(pks)]] = r  # later (higher seq) wins
        return sorted(out.values())

    def run_case(self, rng, n, num_pks, capacity=None):
        cap = capacity or pad_capacity(n)
        pks = tuple(
            np.pad(rng.integers(0, 8, n).astype(np.int32), (0, cap - n))
            for _ in range(num_pks)
        )
        seq = np.pad(rng.permutation(n).astype(np.int32), (0, cap - n))
        vals = (np.pad(rng.random(n).astype(np.float32), (0, cap - n)),)
        out_pks, out_seq, out_vals, out_valid, num_runs = merge_dedup_last(
            tuple(jnp.asarray(c) for c in pks), jnp.asarray(seq),
            tuple(jnp.asarray(v) for v in vals), n)
        k = int(num_runs)
        assert bool(np.all(np.asarray(out_valid)[:k]))
        assert not np.any(np.asarray(out_valid)[k:])
        got = list(zip(*[np.asarray(c)[:k].tolist() for c in out_pks],
                       *[np.asarray(v)[:k].tolist() for v in out_vals]))
        expected = [r[: len(pks)] + r[len(pks) + 1:]
                    for r in self.np_reference(pks, seq, vals, n)]
        assert [tuple(g) for g in got] == [tuple(e) for e in expected]

    @pytest.mark.parametrize("seed", range(5))
    def test_random_vs_numpy(self, seed):
        rng = np.random.default_rng(seed)
        self.run_case(rng, n=int(rng.integers(1, 120)), num_pks=2)

    def test_three_pks(self):
        self.run_case(np.random.default_rng(42), n=100, num_pks=3)

    def test_full_capacity_no_padding(self):
        self.run_case(np.random.default_rng(7), n=128, num_pks=1, capacity=128)

    def test_empty(self):
        cap = 128
        z = jnp.zeros(cap, dtype=jnp.int32)
        _, _, _, out_valid, num_runs = merge_dedup_last(
            (z,), z, (jnp.zeros(cap, dtype=jnp.float32),), 0)
        assert int(num_runs) == 0 and not bool(np.any(np.asarray(out_valid)))

    def test_last_by_seq_wins(self):
        """Two files write the same pk; the higher seq's value survives
        (ref: operator.rs LastValueOperator, storage.rs:390-490 scenario)."""
        cap = 128
        pk = np.zeros(cap, dtype=np.int32)
        pk[:4] = [5, 5, 7, 7]
        seq = np.zeros(cap, dtype=np.int32)
        seq[:4] = [1, 2, 2, 1]
        val = np.zeros(cap, dtype=np.float32)
        val[:4] = [10.0, 20.0, 30.0, 40.0]
        out_pks, out_seq, out_vals, _, num_runs = merge_dedup_last(
            (jnp.asarray(pk),), jnp.asarray(seq), (jnp.asarray(val),), 4)
        assert int(num_runs) == 2
        assert np.asarray(out_pks[0])[:2].tolist() == [5, 7]
        assert np.asarray(out_vals[0])[:2].tolist() == [20.0, 30.0]
        # surviving rows carry their original sequence
        assert np.asarray(out_seq)[:2].tolist() == [2, 2]

    def test_run_starts(self):
        col = jnp.asarray(np.array([1, 1, 2, 2, 2, 3, 0, 0], dtype=np.int32))
        valid = jnp.asarray(np.array([1, 1, 1, 1, 1, 1, 0, 0], dtype=bool))
        starts = np.asarray(sorted_run_starts((col,), valid))
        assert starts.tolist() == [True, False, True, False, False, True, False, False]


class TestDedupSorted:
    """dedup_sorted_last + the host merge planner must reproduce the
    device-sort kernel's output exactly on any input."""

    def _plan(self, pks, seq, n):
        from horaedb_tpu.storage.read import _plan_merge_perm

        return _plan_merge_perm([c[:n] for c in pks], seq[:n])

    @pytest.mark.parametrize("seed", range(5))
    def test_random_matches_device_sort(self, seed):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 150))
        cap = pad_capacity(n)
        pks = tuple(
            np.pad(rng.integers(0, 6, n).astype(np.int32), (0, cap - n))
            for _ in range(2))
        seq = np.pad(rng.permutation(n).astype(np.int32), (0, cap - n))
        vals = (np.pad(rng.random(n).astype(np.float32), (0, cap - n)),)

        perm = self._plan(pks, seq, n)
        if perm is not None:
            full = np.arange(cap, dtype=np.int32)
            full[:n] = perm
            perm = jnp.asarray(full)
        got = dedup_sorted_last(
            tuple(jnp.asarray(c) for c in pks), jnp.asarray(seq),
            tuple(jnp.asarray(v) for v in vals), n, perm=perm)
        want = merge_dedup_last(
            tuple(jnp.asarray(c) for c in pks), jnp.asarray(seq),
            tuple(jnp.asarray(v) for v in vals), n)
        k = int(want[4])
        assert int(got[4]) == k
        for g, w in zip(got[0] + (got[1],) + got[2],
                        want[0] + (want[1],) + want[2]):
            np.testing.assert_array_equal(np.asarray(g)[:k],
                                          np.asarray(w)[:k])

    def test_presorted_input_needs_no_perm(self):
        """Single-SST case: rows arrive PK-sorted; the planner proves it
        and the kernel runs gather-free."""
        n, cap = 6, 128
        pk = np.zeros(cap, dtype=np.int32)
        pk[:n] = [1, 1, 2, 3, 3, 3]
        seq = np.zeros(cap, dtype=np.int32)
        seq[:n] = [0, 1, 0, 0, 1, 2]
        val = np.zeros(cap, dtype=np.float32)
        val[:n] = [1, 2, 3, 4, 5, 6]
        assert self._plan((pk,), seq, n) is None
        out_pks, _, out_vals, _, nr = dedup_sorted_last(
            (jnp.asarray(pk),), jnp.asarray(seq), (jnp.asarray(val),), n)
        assert int(nr) == 3
        assert np.asarray(out_pks[0])[:3].tolist() == [1, 2, 3]
        assert np.asarray(out_vals[0])[:3].tolist() == [2.0, 3.0, 6.0]

    def test_planner_merges_presorted_runs(self):
        """Two PK-sorted runs concatenated (two SSTs): the planned
        permutation interleaves them; equal PKs keep run order (stable),
        so the later file's row wins."""
        from horaedb_tpu.storage.read import _plan_merge_perm

        run_a = np.array([1, 3, 5], dtype=np.int32)
        run_b = np.array([2, 3, 4], dtype=np.int32)
        pk = np.concatenate([run_a, run_b])
        perm = _plan_merge_perm([pk], None)
        assert perm is not None
        merged = pk[perm]
        assert merged.tolist() == [1, 2, 3, 3, 4, 5]
        # stable: the 3 from run_a (index 1) precedes run_b's (index 4)
        assert perm.tolist().index(1) < perm.tolist().index(4)

    def test_planner_int64_overflow_falls_back_to_lexsort(self):
        from horaedb_tpu.storage.read import _plan_merge_perm

        rng = np.random.default_rng(0)
        wide = (rng.integers(0, 2**31 - 2, 64)).astype(np.int64)
        cols = [wide, wide[::-1].copy(), rng.integers(0, 2**31 - 2, 64)]
        perm = _plan_merge_perm(cols, None)
        want = np.lexsort(tuple(reversed(cols)))
        np.testing.assert_array_equal(perm, want)


class TestDownsample:
    def np_reference(self, ts, gid, vals, n, bucket_ms, G, B):
        out = {k: np.full((G, B), init, dtype=np.float64)
               for k, init in [("count", 0), ("sum", 0.0),
                               ("min", np.inf), ("max", -np.inf)]}
        last_ts = np.full((G, B), -1, dtype=np.int64)
        last = np.full((G, B), np.nan)
        for i in range(n):
            b = ts[i] // bucket_ms
            g = gid[i]
            if not (0 <= b < B and 0 <= g < G):
                continue
            out["count"][g, b] += 1
            out["sum"][g, b] += vals[i]
            out["min"][g, b] = min(out["min"][g, b], vals[i])
            out["max"][g, b] = max(out["max"][g, b], vals[i])
            if ts[i] >= last_ts[g, b]:
                last_ts[g, b] = ts[i]
                last[g, b] = vals[i]
        return out, last

    @pytest.mark.parametrize("seed", range(3))
    def test_random_vs_numpy(self, seed):
        rng = np.random.default_rng(seed)
        n, G, B, bucket = 500, 7, 11, 60_000
        cap = pad_capacity(n)
        ts = np.pad(rng.integers(0, B * bucket, n).astype(np.int32), (0, cap - n))
        gid = np.pad(rng.integers(0, G, n).astype(np.int32), (0, cap - n))
        vals = np.pad((rng.random(n) * 100).astype(np.float32), (0, cap - n))
        got = time_bucket_aggregate(jnp.asarray(ts), jnp.asarray(gid),
                                    jnp.asarray(vals), n, bucket,
                                    num_groups=G, num_buckets=B)
        exp, exp_last = self.np_reference(ts, gid, vals, n, bucket, G, B)
        np.testing.assert_array_equal(np.asarray(got["count"]), exp["count"])
        np.testing.assert_allclose(np.asarray(got["sum"]), exp["sum"], rtol=1e-5)
        occupied = exp["count"] > 0
        np.testing.assert_allclose(np.asarray(got["min"])[occupied],
                                   exp["min"][occupied], rtol=1e-6)
        np.testing.assert_allclose(np.asarray(got["max"])[occupied],
                                   exp["max"][occupied], rtol=1e-6)
        np.testing.assert_allclose(np.asarray(got["avg"])[occupied],
                                   (exp["sum"] / exp["count"])[occupied], rtol=1e-5)
        np.testing.assert_allclose(np.asarray(got["last"])[occupied],
                                   exp_last[occupied], rtol=1e-6)
        # empty cells
        assert np.all(np.isnan(np.asarray(got["avg"])[~occupied]))
        assert np.all(np.isnan(np.asarray(got["last"])[~occupied]))

    def test_out_of_grid_rows_dropped(self):
        cap = 128
        ts = np.zeros(cap, dtype=np.int32)
        ts[:3] = [0, 100, 500]  # bucket 0, 1, 5 with bucket=100, B=2 -> row 2 dropped
        gid = np.zeros(cap, dtype=np.int32)
        vals = np.ones(cap, dtype=np.float32)
        got = time_bucket_aggregate(jnp.asarray(ts), jnp.asarray(gid),
                                    jnp.asarray(vals), 3, 100,
                                    num_groups=1, num_buckets=2)
        assert np.asarray(got["count"]).tolist() == [[1.0, 1.0]]


class TestFilter:
    def make_batch(self):
        return encode_batch(pa.record_batch({
            "host": pa.array(["a", "b", "c", "b", "d"]),
            "ts": pa.array([100, 200, 300, 400, 500], type=pa.int64()),
            "cpu": pa.array([0.1, 0.2, 0.3, 0.4, 0.5], type=pa.float64()),
        }))

    def mask(self, pred, batch):
        m = np.asarray(eval_predicate(pred, batch))
        return m[:batch.n_valid].tolist()

    def test_eq_dict(self):
        b = self.make_batch()
        assert self.mask(Eq("host", "b"), b) == [False, True, False, True, False]
        assert self.mask(Eq("host", "zzz"), b) == [False] * 5  # absent constant

    def test_ne_and_not(self):
        b = self.make_batch()
        assert self.mask(Ne("host", "b"), b) == [True, False, True, False, True]
        assert self.mask(Not(Eq("host", "b")), b) == [True, False, True, False, True]
        assert self.mask(Ne("host", "zzz"), b) == [True] * 5

    def test_in(self):
        b = self.make_batch()
        assert self.mask(In("host", ["a", "d", "zzz"]), b) == \
            [True, False, False, False, True]

    def test_ordering_on_dict(self):
        b = self.make_batch()
        assert self.mask(Lt("host", "c"), b) == [True, True, False, True, False]
        assert self.mask(Le("host", "b"), b) == [True, True, False, True, False]
        assert self.mask(Gt("host", "b"), b) == [False, False, True, False, True]
        assert self.mask(Ge("host", "c"), b) == [False, False, True, False, True]
        # constants between dictionary entries still order correctly
        assert self.mask(Lt("host", "bb"), b) == [True, True, False, True, False]
        assert self.mask(Gt("host", "bb"), b) == [False, False, True, False, True]

    def test_time_range_on_offset(self):
        b = self.make_batch()
        assert self.mask(TimeRangePred("ts", 200, 400), b) == \
            [False, True, True, False, False]

    def test_numeric_compare(self):
        b = self.make_batch()
        assert self.mask(Gt("cpu", 0.3), b) == [False, False, False, True, True]
        assert self.mask(Le("cpu", 0.2), b) == [True, True, False, False, False]

    def test_and_or(self):
        b = self.make_batch()
        pred = And([TimeRangePred("ts", 100, 500), Or([Eq("host", "a"), Eq("host", "b")])])
        assert self.mask(pred, b) == [True, True, False, True, False]


class TestTopK:
    def test_basic(self):
        scores = jnp.asarray(np.array([1.0, 5.0, 3.0, np.nan, 4.0], dtype=np.float32))
        vals, idxs = top_k_groups(scores, k=3)
        assert np.asarray(idxs).tolist() == [1, 4, 2]
        assert np.asarray(vals).tolist() == [5.0, 4.0, 3.0]

    def test_smallest(self):
        scores = jnp.asarray(np.array([1.0, 5.0, 3.0, np.nan, 4.0], dtype=np.float32))
        vals, idxs = top_k_groups(scores, k=2, largest=False)
        assert np.asarray(idxs).tolist() == [0, 2]
        assert np.asarray(vals).tolist() == [1.0, 3.0]

    def test_k_exceeds_groups(self):
        scores = jnp.asarray(np.array([2.0, 1.0], dtype=np.float32))
        vals, idxs = top_k_groups(scores, k=4)
        assert np.asarray(idxs).tolist() == [0, 1, -1, -1]
        assert np.isnan(np.asarray(vals)[2:]).all()

    def test_all_nan(self):
        scores = jnp.asarray(np.full(4, np.nan, dtype=np.float32))
        vals, idxs = top_k_groups(scores, k=2)
        assert np.asarray(idxs).tolist() == [-1, -1]
        assert np.isnan(np.asarray(vals)).all()


class TestEncodeNulls:
    def test_nulls_rejected(self):
        import pytest as _pytest
        from horaedb_tpu.common import Error
        for arr in (pa.array([1.0, None]), pa.array(["a", None]),
                    pa.array([1, None], type=pa.int64())):
            with _pytest.raises(Error, match="null"):
                encode_batch(pa.record_batch({"c": arr}))


class TestArrowPushdown:
    def test_pk_only_predicates_push(self):
        from horaedb_tpu.ops.filter import to_arrow_expression
        pks = {"host", "ts"}
        assert to_arrow_expression(Eq("host", "a"), pks) is not None
        assert to_arrow_expression(TimeRangePred("ts", 1, 5), pks) is not None
        assert to_arrow_expression(In("host", ["a", "b"]), pks) is not None
        # value-column predicates must NOT push (would break last-value)
        assert to_arrow_expression(Gt("cpu", 0.5), pks) is None
        # partial AND pushes only the PK part
        expr = to_arrow_expression(
            And([Eq("host", "a"), Gt("cpu", 0.5)]), pks)
        assert expr is not None and "cpu" not in str(expr)
        # OR with a value column cannot push at all
        assert to_arrow_expression(
            Or([Eq("host", "a"), Gt("cpu", 0.5)]), pks) is None
        # pure-PK OR and NOT push
        assert to_arrow_expression(
            Or([Eq("host", "a"), Eq("host", "b")]), pks) is not None
        assert to_arrow_expression(Not(Eq("host", "a")), pks) is not None

    def test_pushed_filter_matches_post_merge_filter(self):
        """Row filtering by a PK predicate pre-merge must give the same
        result as filtering post-merge."""
        import pyarrow.parquet as pq, io
        import pyarrow as pa
        from horaedb_tpu.ops.filter import to_arrow_expression
        tbl = pa.table({"host": ["a", "b", "a", "c"],
                        "ts": [1, 2, 3, 4],
                        "cpu": [0.1, 0.2, 0.3, 0.4]})
        sink = io.BytesIO()
        pq.write_table(tbl, sink)
        expr = to_arrow_expression(Eq("host", "a"), {"host", "ts"})
        got = pq.read_table(pa.BufferReader(sink.getvalue()), filters=expr)
        assert got.column("ts").to_pylist() == [1, 3]

    def test_nested_relaxation(self):
        from horaedb_tpu.ops.filter import to_arrow_expression
        pks = {"host", "ts"}
        # nested And under Or: unpushable conjunct relaxes, Or still pushes
        expr = to_arrow_expression(
            Or([And([Eq("host", "a"), Gt("cpu", 0.5)]), Eq("host", "b")]), pks)
        assert expr is not None and "cpu" not in str(expr)
        # nested And under top-level And relaxes too
        expr = to_arrow_expression(
            And([TimeRangePred("ts", 1, 5),
                 And([Eq("host", "a"), Gt("cpu", 0.5)])]), pks)
        assert expr is not None and "host" in str(expr) and "cpu" not in str(expr)
        # but relaxation NEVER happens under Not (would narrow, unsound)
        assert to_arrow_expression(
            Not(And([Eq("host", "a"), Gt("cpu", 0.5)])), pks) is None
        # Or with a fully-unpushable branch stays unpushable
        assert to_arrow_expression(
            Or([Eq("host", "a"), Gt("cpu", 0.5)]), pks) is None


class TestAggregateSubset:
    def base(self):
        rng = np.random.default_rng(0)
        cap = 128
        return (jnp.asarray(rng.integers(0, 500, cap).astype(np.int32)),
                jnp.asarray(rng.integers(0, 3, cap).astype(np.int32)),
                jnp.asarray(rng.random(cap).astype(np.float32)))

    def test_subset_matches_full(self):
        ts, gid, vals = self.base()
        full = time_bucket_aggregate(ts, gid, vals, 100, 100,
                                     num_groups=3, num_buckets=5)
        avg_only = time_bucket_aggregate(ts, gid, vals, 100, 100,
                                         num_groups=3, num_buckets=5,
                                         which=("avg",))
        assert set(avg_only) == {"count", "avg"}
        np.testing.assert_array_equal(np.asarray(full["avg"]),
                                      np.asarray(avg_only["avg"]))
        sum_only = time_bucket_aggregate(ts, gid, vals, 100, 100,
                                         num_groups=3, num_buckets=5,
                                         which=("sum",))
        assert set(sum_only) == {"count", "sum"}

    def test_unknown_aggregate_rejected(self):
        ts, gid, vals = self.base()
        with pytest.raises(ValueError, match="mean"):
            time_bucket_aggregate(ts, gid, vals, 100, 100,
                                  num_groups=3, num_buckets=5,
                                  which=("mean",))

    def test_which_order_canonicalized(self):
        from horaedb_tpu.ops.downsample import _time_bucket_aggregate_impl
        ts, gid, vals = self.base()
        before = _time_bucket_aggregate_impl._cache_size()
        time_bucket_aggregate(ts, gid, vals, 100, 100, num_groups=3,
                              num_buckets=5, which=("count", "avg"))
        mid = _time_bucket_aggregate_impl._cache_size()
        time_bucket_aggregate(ts, gid, vals, 100, 100, num_groups=3,
                              num_buckets=5, which=("avg", "count", "avg"))
        assert _time_bucket_aggregate_impl._cache_size() == mid
