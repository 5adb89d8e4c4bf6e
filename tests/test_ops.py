"""TPU ops tests — run on the virtual CPU mesh; numerical ground truth is
plain numpy (the same data the CPU fallback executor would compute)."""

import numpy as np
import pytest

from horaedb_tpu.common_types import ColumnSchema, DatumKind, RowGroup, Schema
from horaedb_tpu.ops import (
    ScanAggSpec,
    encode_group_codes,
    merge_dedup_permutation,
    pad_to_bucket,
    scan_aggregate,
    shape_bucket,
)
from horaedb_tpu.ops.encoding import (
    build_padded_batch,
    split_i64_sortable,
    split_u64,
    time_buckets,
)


class TestShapeBuckets:
    def test_bucket_rounding(self):
        assert shape_bucket(1) == 4096
        assert shape_bucket(4096) == 4096
        assert shape_bucket(4097) == 8192
        assert shape_bucket(100_000) == 131072

    def test_pad(self):
        a = np.arange(10, dtype=np.int32)
        p = pad_to_bucket(a, 10, fill=-1)
        assert len(p) == 4096 and p[9] == 9 and p[10] == -1


class TestSplit64:
    def test_u64_round_order(self):
        xs = np.array([0, 1, 2**32 - 1, 2**32, 2**63, 2**64 - 1], dtype=np.uint64)
        hi, lo = split_u64(xs)
        pairs = list(zip(hi.tolist(), lo.tolist()))
        assert pairs == sorted(pairs)

    def test_i64_order_preserved(self):
        xs = np.array([-(2**62), -1, 0, 1, 2**62], dtype=np.int64)
        hi, lo = split_i64_sortable(xs)
        pairs = list(zip(hi.tolist(), lo.tolist()))
        assert pairs == sorted(pairs)


class TestGroupEncoding:
    def schema(self):
        return Schema.build(
            [
                ColumnSchema("host", DatumKind.STRING, is_tag=True),
                ColumnSchema("region", DatumKind.STRING, is_tag=True),
                ColumnSchema("v", DatumKind.DOUBLE),
                ColumnSchema("t", DatumKind.TIMESTAMP),
            ],
            timestamp_column="t",
        )

    def rows(self, n=100):
        return RowGroup.from_rows(
            self.schema(),
            [
                {
                    "host": f"h{i % 5}",
                    "region": "east" if i % 2 else "west",
                    "v": float(i),
                    "t": i,
                }
                for i in range(n)
            ],
        )

    def test_single_tag_group(self):
        rows = self.rows()
        enc = encode_group_codes(rows, ["host"])
        assert enc.num_groups == 5
        # code consistency: same host -> same code
        hosts = rows.column("host")
        for c in range(5):
            vals = set(hosts[enc.codes == c])
            assert len(vals) == 1
        assert sorted(enc.key_values[0].tolist()) == [f"h{i}" for i in range(5)]

    def test_composite_tag_group(self):
        enc = encode_group_codes(self.rows(), ["host", "region"])
        assert enc.num_groups == 10
        assert len(enc.key_values) == 2

    def test_empty_group_by(self):
        enc = encode_group_codes(self.rows(), [])
        assert enc.num_groups == 1 and (enc.codes == 0).all()

    def test_time_buckets(self):
        ts = np.array([0, 999, 1000, 5500], dtype=np.int64)
        b, n = time_buckets(ts, 0, 1000)
        assert b.tolist() == [0, 0, 1, 5] and n == 6


def numpy_reference_agg(codes, buckets, mask, values, n_groups, n_buckets):
    """Ground truth with f64 numpy."""
    counts = np.zeros((n_groups, n_buckets), dtype=np.int64)
    sums = np.zeros((len(values), n_groups, n_buckets))
    mins = np.full((len(values), n_groups, n_buckets), np.inf)
    maxs = np.full((len(values), n_groups, n_buckets), -np.inf)
    for i in range(len(codes)):
        if not mask[i]:
            continue
        g, b = codes[i], buckets[i]
        counts[g, b] += 1
        for f in range(len(values)):
            v = values[f][i]
            sums[f, g, b] += v
            mins[f, g, b] = min(mins[f, g, b], v)
            maxs[f, g, b] = max(maxs[f, g, b], v)
    return counts, sums, mins, maxs


class TestScanAggregate:
    def test_matches_numpy_reference(self):
        rng = np.random.default_rng(0)
        n, g, b = 5000, 7, 3
        codes = rng.integers(0, g, n).astype(np.int32)
        buckets = rng.integers(0, b, n).astype(np.int32)
        mask = rng.random(n) > 0.2
        vals = [rng.normal(size=n).astype(np.float32)]

        batch = build_padded_batch(codes, buckets, mask, vals)
        spec = ScanAggSpec(
            n_groups=g, n_buckets=b, n_agg_fields=1, segment_impl="scatter"
        ).padded()
        out = scan_aggregate(batch, spec)

        rc, rs, rmin, rmax = numpy_reference_agg(
            codes, buckets, mask, [v.astype(np.float64) for v in vals], g, b
        )
        assert (out.counts[:g, :b] == rc).all()
        np.testing.assert_allclose(out.sums[:, :g, :b], rs, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(out.mins[:, :g, :b], rmin)
        np.testing.assert_allclose(out.maxs[:, :g, :b], rmax)

    def test_device_numeric_filter(self):
        n = 4096
        codes = np.zeros(n, dtype=np.int32)
        buckets = np.zeros(n, dtype=np.int32)
        mask = np.ones(n, dtype=bool)
        vals = [np.arange(n, dtype=np.float32)]
        batch = build_padded_batch(codes, buckets, mask, vals)
        spec = ScanAggSpec(
            n_groups=1, n_buckets=1, n_agg_fields=1,
            numeric_filters=((0, ">"),), segment_impl="single",
        ).padded()
        out = scan_aggregate(batch, spec, filter_literals=[4000.0])
        assert out.counts[0, 0] == n - 4001
        assert out.mins[0, 0, 0] == 4001.0

    def test_literal_change_no_recompile(self):
        import jax

        n = 4096
        batch = build_padded_batch(
            np.zeros(n, dtype=np.int32),
            np.zeros(n, dtype=np.int32),
            np.ones(n, dtype=bool),
            [np.arange(n, dtype=np.float32)],
        )
        spec = ScanAggSpec(
            n_groups=1, n_buckets=1, n_agg_fields=1, numeric_filters=((0, "<"),),
            segment_impl="single",
        ).padded()
        scan_aggregate(batch, spec, [10.0])
        from horaedb_tpu.ops.scan_agg import _fused_scan_agg

        misses_before = _fused_scan_agg._cache_size()
        out = scan_aggregate(batch, spec, [100.0])
        assert _fused_scan_agg._cache_size() == misses_before
        assert out.counts[0, 0] == 100

    def test_partial_combine_associative(self):
        rng = np.random.default_rng(1)
        n, g, b = 4096, 4, 2
        spec = ScanAggSpec(
            n_groups=g, n_buckets=b, n_agg_fields=1, segment_impl="scatter"
        ).padded()

        def batch():
            return build_padded_batch(
                rng.integers(0, g, n).astype(np.int32),
                rng.integers(0, b, n).astype(np.int32),
                np.ones(n, dtype=bool),
                [rng.normal(size=n).astype(np.float32)],
            )

        b1, b2 = batch(), batch()
        s1, s2 = scan_aggregate(b1, spec), scan_aggregate(b2, spec)
        combined = s1.combine(s2)

        both = build_padded_batch(
            np.concatenate([b1.group_codes[:n], b2.group_codes[:n]]),
            np.concatenate([b1.bucket_ids[:n], b2.bucket_ids[:n]]),
            np.ones(2 * n, dtype=bool),
            [np.concatenate([b1.values[0][:n], b2.values[0][:n]])],
        )
        s_both = scan_aggregate(both, spec)
        assert (combined.counts == s_both.counts).all()
        np.testing.assert_allclose(combined.sums, s_both.sums, rtol=1e-4, atol=1e-4)

    def test_no_agg_fields_count_only(self):
        n = 4096
        batch = build_padded_batch(
            np.zeros(n, dtype=np.int32), np.zeros(n, dtype=np.int32),
            np.ones(n, dtype=bool), [],
        )
        spec = ScanAggSpec(
            n_groups=1, n_buckets=1, n_agg_fields=0, segment_impl="single"
        ).padded()
        out = scan_aggregate(batch, spec)
        assert out.counts[0, 0] == n and out.sums.shape[0] == 0


class TestMergeDedup:
    def test_merges_sorted_runs(self):
        # Two sorted runs with overlapping keys; newest seq must win.
        tsid = np.array([1, 1, 2, 1, 2, 3], dtype=np.uint64)
        ts = np.array([10, 20, 10, 10, 10, 5], dtype=np.int64)
        seq = np.array([1, 1, 1, 2, 2, 2], dtype=np.uint64)
        perm, keep = merge_dedup_permutation(tsid, ts, seq)
        merged_idx = perm[keep]
        out = list(zip(tsid[merged_idx].tolist(), ts[merged_idx].tolist(), seq[merged_idx].tolist()))
        # keys (1,10) and (2,10) dedup to seq=2 versions
        assert out == [(1, 10, 2), (1, 20, 1), (2, 10, 2), (3, 5, 2)]

    def test_no_dedup_keeps_all(self):
        tsid = np.array([1, 1], dtype=np.uint64)
        ts = np.array([10, 10], dtype=np.int64)
        seq = np.array([1, 2], dtype=np.uint64)
        perm, keep = merge_dedup_permutation(tsid, ts, seq, dedup=False)
        assert keep.sum() == 2
        # newest still sorts first
        assert seq[perm[0]] == 2

    def test_matches_numpy_lexsort(self):
        rng = np.random.default_rng(7)
        n = 10_000
        tsid = rng.integers(0, 50, n).astype(np.uint64)
        ts = rng.integers(-1000, 1000, n).astype(np.int64)
        seq = rng.permutation(n).astype(np.uint64)
        perm, keep = merge_dedup_permutation(tsid, ts, seq)

        order = np.lexsort((-(seq.astype(np.int64)), ts, tsid.astype(np.int64)))
        key = np.stack([tsid[order].astype(np.int64), ts[order]])
        first = np.ones(n, dtype=bool)
        first[1:] = (key[:, 1:] != key[:, :-1]).any(axis=0)
        expected = order[first]
        np.testing.assert_array_equal(perm[keep], expected)

    def test_empty(self):
        perm, keep = merge_dedup_permutation(
            np.empty(0, dtype=np.uint64),
            np.empty(0, dtype=np.int64),
            np.empty(0, dtype=np.uint64),
        )
        assert len(perm) == 0 and len(keep) == 0

    def test_extreme_values(self):
        tsid = np.array([0, 2**64 - 1, 2**63], dtype=np.uint64)
        ts = np.array([-(2**62), 2**62, 0], dtype=np.int64)
        seq = np.array([1, 2, 3], dtype=np.uint64)
        perm, keep = merge_dedup_permutation(tsid, ts, seq)
        assert keep.sum() == 3
        assert tsid[perm].tolist() == [0, 2**63, 2**64 - 1]


class TestMergeDedupReady:
    def test_background_compile_gate(self):
        """merge_dedup_ready returns False while compiling, True after;
        only one compile thread per shape bucket."""
        import time

        from horaedb_tpu.ops import merge_dedup as md

        n = 1024
        bucket = __import__("horaedb_tpu.ops.encoding", fromlist=["shape_bucket"]).shape_bucket(n)
        with md._compile_lock:
            md._ready.discard((bucket, True))
        ready = md.merge_dedup_ready(n)
        # either already-compiled jit cache made it instant on a second
        # call, or the background thread lands shortly (CPU compile is ms)
        deadline = time.time() + 30
        while not ready and time.time() < deadline:
            time.sleep(0.01)
            ready = md.merge_dedup_ready(n)
        assert ready

    def test_direct_call_marks_ready(self):
        import numpy as np

        from horaedb_tpu.ops import merge_dedup as md
        from horaedb_tpu.ops.encoding import shape_bucket

        n = 2048
        with md._compile_lock:
            md._ready.discard((shape_bucket(n), True))
        tsid = np.arange(n, dtype=np.uint64)
        ts = np.zeros(n, dtype=np.int64)
        seq = np.arange(n, dtype=np.uint64)
        md.merge_dedup_permutation(tsid, ts, seq)
        assert md.merge_dedup_ready(n)
        # dedup=False is a different kernel: not marked ready by the above
        with md._compile_lock:
            md._ready.discard((shape_bucket(n), False))
            ready_false = (shape_bucket(n), False) in md._ready
        assert not ready_false


class TestCohortKernels:
    """Multi-query fused serving: the vmapped cohort kernels must be
    row-for-row identical to dispatching the packed kernels per query."""

    def _resident(self, n_series=5, rows_per=40, n_fields=2, seed=3):
        rng = np.random.default_rng(seed)
        codes = np.repeat(np.arange(n_series, dtype=np.int32), rows_per)
        ts_rel = np.tile(
            np.arange(rows_per, dtype=np.int32) * 10, n_series
        )
        values = rng.random((n_fields, n_series * rows_per)).astype(
            np.float32
        ) * 100.0
        return codes, ts_rel, values

    def test_cached_agg_cohort_matches_per_query_packed(self):
        import jax
        import jax.numpy as jnp

        from horaedb_tpu.ops.scan_agg import (
            ScanAggSpec,
            cached_scan_agg_cohort,
            cached_scan_agg_packed,
            encode_filter_ops,
            pack_dyn,
            pack_session,
            unpack_packed_state,
        )

        codes, ts_rel, values = self._resident()
        S = 5
        gos = np.append(np.arange(S, dtype=np.int32) % 3, 0)
        spec = ScanAggSpec(
            n_groups=3, n_buckets=4, n_agg_fields=2,
            numeric_filters=((0, ">="),), need_minmax=True,
            segment_impl="scatter",
        ).padded()
        nf = encode_filter_ops(spec.numeric_filters)
        rng = np.random.default_rng(7)
        members = []
        for b in range(4):  # varied allow-lists, literals, time bounds
            allow = np.append(rng.random(S) > 0.3, False)
            lo, hi = 10 * b, 400 - 20 * b
            members.append(
                (
                    pack_session(gos, allow),
                    pack_dyn([float(5 * b)], lo, hi, 0, 100),
                )
            )
        sessions = jnp.asarray(np.stack([m[0] for m in members]))
        dyns = jnp.asarray(np.stack([m[1] for m in members]))
        statics = dict(
            n_groups=spec.n_groups, n_buckets=spec.n_buckets,
            n_agg_fields=spec.n_agg_fields, numeric_filters=nf,
            need_minmax=True, segment_impl="scatter",
        )
        batched = np.asarray(
            jax.device_get(
                cached_scan_agg_cohort(
                    jnp.asarray(codes), jnp.asarray(ts_rel),
                    jnp.asarray(values), sessions, dyns, **statics
                )
            )
        )
        for j, (sess, dyn) in enumerate(members):
            solo = cached_scan_agg_packed(
                jnp.asarray(codes), jnp.asarray(ts_rel),
                jnp.asarray(values), jnp.asarray(sess), jnp.asarray(dyn),
                selective=False, **statics
            )
            a = unpack_packed_state(batched[j], spec)
            b = unpack_packed_state(solo, spec)
            np.testing.assert_array_equal(a.counts, b.counts)
            np.testing.assert_allclose(a.sums, b.sums, rtol=1e-6)
            np.testing.assert_allclose(a.mins, b.mins, rtol=1e-6)
            np.testing.assert_allclose(a.maxs, b.maxs, rtol=1e-6)

    def test_raw_topk_cohort_matches_per_query_packed(self):
        import jax
        import jax.numpy as jnp

        from horaedb_tpu.ops.scan_agg import encode_filter_ops
        from horaedb_tpu.ops.scan_topk import (
            pack_raw_dyn,
            raw_topk_cohort,
            raw_topk_packed,
            topk_key_bounds,
        )

        codes, ts_rel, values = self._resident()
        S = 5
        nf = encode_filter_ops(((0, "<"),))
        rng = np.random.default_rng(11)
        members = []
        for b in range(4):
            allow = np.append(rng.random(S) > 0.25, False).astype(np.int32)
            lo, hi = 5 * b, 390 - 10 * b
            key_lo, key_hi = topk_key_bounds(True, True, lo, hi)
            members.append(
                (allow, pack_raw_dyn([80.0 - b], lo, hi, key_lo, key_hi))
            )
        sessions = jnp.asarray(np.stack([m[0] for m in members]))
        dyns = jnp.asarray(np.stack([m[1] for m in members]))
        statics = dict(
            k=16, descending=True, key_is_ts=True, key_field=0,
            numeric_filters=nf,
        )
        batched = np.asarray(
            jax.device_get(
                raw_topk_cohort(
                    jnp.asarray(codes), jnp.asarray(ts_rel),
                    jnp.asarray(values), sessions, dyns, **statics
                )
            )
        )
        for j, (allow, dyn) in enumerate(members):
            solo = np.asarray(
                jax.device_get(
                    raw_topk_packed(
                        jnp.asarray(codes), jnp.asarray(ts_rel),
                        jnp.asarray(values), jnp.asarray(allow),
                        jnp.asarray(dyn), **statics
                    )
                )
            )
            # slot order is unspecified within ties: compare as sets of
            # selected row ids (the executor re-sorts gathered rows)
            assert set(batched[j][batched[j] >= 0]) == set(solo[solo >= 0])
