"""Distributed aggregation tests on the virtual 8-device CPU mesh."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from horaedb_tpu.ops import ScanAggSpec, scan_aggregate
from horaedb_tpu.ops.encoding import build_padded_batch
from horaedb_tpu.parallel import dist_scan_aggregate


@pytest.fixture(scope="module")
def mesh():
    devs = np.array(jax.devices()[:8])
    assert len(devs) == 8, "conftest must provide 8 virtual devices"
    return Mesh(devs, ("shard",))


class TestDistScanAgg:
    def make_batch(self, n=8192, g=5, b=3, seed=0):
        rng = np.random.default_rng(seed)
        return build_padded_batch(
            rng.integers(0, g, n).astype(np.int32),
            rng.integers(0, b, n).astype(np.int32),
            rng.random(n) > 0.1,
            [rng.normal(size=n).astype(np.float32)],
        )

    def test_matches_single_device(self, mesh):
        batch = self.make_batch()
        spec = ScanAggSpec(
            n_groups=5, n_buckets=3, n_agg_fields=1, segment_impl="scatter"
        ).padded()
        single = scan_aggregate(batch, spec)
        dist = dist_scan_aggregate(mesh, batch, spec)
        np.testing.assert_array_equal(single.counts, dist.counts)
        np.testing.assert_allclose(single.sums, dist.sums, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(single.mins, dist.mins)
        np.testing.assert_allclose(single.maxs, dist.maxs)

    @pytest.mark.parametrize("op", ["<", "<=", ">", ">=", "=", "!="])
    def test_device_filter_in_dist(self, mesh, op):
        # Discretized values so every op differs from every other op's
        # result (a continuous distribution can't tell '>' from '>=').
        rng = np.random.default_rng(5)
        n = 8192
        batch = build_padded_batch(
            rng.integers(0, 5, n).astype(np.int32),
            rng.integers(0, 3, n).astype(np.int32),
            np.ones(n, dtype=bool),
            [rng.integers(-2, 3, n).astype(np.float32)],
        )
        spec = ScanAggSpec(
            n_groups=5, n_buckets=3, n_agg_fields=1, numeric_filters=((0, op),),
            segment_impl="scatter",
        ).padded()
        single = scan_aggregate(batch, spec, [0.0])
        dist = dist_scan_aggregate(mesh, batch, spec, [0.0])
        np.testing.assert_array_equal(single.counts, dist.counts)
        assert single.counts.sum() not in (0, n)  # filter actually selective

    def test_result_replicated_on_all_devices(self, mesh):
        from horaedb_tpu.parallel import make_dist_scan_agg
        import jax.numpy as jnp

        batch = self.make_batch(n=4096)
        spec = ScanAggSpec(
            n_groups=5, n_buckets=3, n_agg_fields=1, segment_impl="scatter"
        ).padded()
        step = make_dist_scan_agg(mesh, spec)
        counts, *_ = step(
            jnp.asarray(batch.group_codes),
            jnp.asarray(batch.bucket_ids),
            jnp.asarray(batch.mask),
            jnp.asarray(batch.values),
            jnp.zeros(0, dtype=jnp.float32),
        )
        assert counts.sharding.is_fully_replicated


class TestServingPathMesh:
    """VERDICT r1 #1: a /sql query must run the shard_map kernel when the
    batch is large enough — same code path the server and dryrun use."""

    def _db(self):
        import horaedb_tpu

        db = horaedb_tpu.connect(None)
        db.execute(
            "CREATE TABLE st (name string TAG, value double, "
            "t timestamp NOT NULL, TIMESTAMP KEY(t)) ENGINE=Analytic"
        )
        return db

    def _write(self, db, n=5000):
        from horaedb_tpu.common_types import RowGroup
        from horaedb_tpu.common_types.schema import compute_tsid

        rng = np.random.default_rng(7)
        names = np.array([f"h{i}" for i in rng.integers(0, 8, n)], dtype=object)
        t = db.catalog.open("st")
        rows = RowGroup(
            t.schema,
            {
                "tsid": compute_tsid([names]),
                "name": names,
                "value": rng.normal(10, 3, n),
                "t": rng.integers(0, 3_600_000, n).astype(np.int64),
            },
        )
        t.write(rows)
        return n

    def test_sql_query_runs_on_mesh_and_matches_host(self, mesh, monkeypatch):
        monkeypatch.setenv("HORAEDB_DIST_MIN_ROWS", "1")
        monkeypatch.setenv("HORAEDB_SCAN_CACHE", "0")
        db = self._db()
        self._write(db)
        sql = (
            "SELECT name, count(value) AS c, avg(value) AS a, "
            "min(value) AS lo, max(value) AS hi FROM st "
            "WHERE value > 4.0 GROUP BY name"
        )
        out = db.execute(sql)
        ex = db.interpreters.executor
        assert ex.last_path == "device-dist"
        assert ex.last_metrics["mesh_devices"] == 8
        dist_rows = {r["name"]: r for r in out.to_pylist()}

        # Host path on the same data must agree.
        orig = ex._device_capable
        ex._device_capable = lambda plan, rows: False
        host = db.execute(sql)
        ex._device_capable = orig
        assert ex.last_path == "host"
        host_rows = {r["name"]: r for r in host.to_pylist()}
        assert set(dist_rows) == set(host_rows)
        for k in host_rows:
            assert dist_rows[k]["c"] == host_rows[k]["c"]
            for f in ("a", "lo", "hi"):
                np.testing.assert_allclose(
                    dist_rows[k][f], host_rows[k][f], rtol=1e-4, atol=1e-5
                )

    def test_small_batch_stays_single_device(self, mesh, monkeypatch):
        monkeypatch.setenv("HORAEDB_SCAN_CACHE", "0")
        # default threshold (256k) far above 5k rows
        db = self._db()
        self._write(db, n=1000)
        db.execute("SELECT name, count(value) AS c FROM st GROUP BY name")
        assert db.interpreters.executor.last_path == "device"

    def test_non_power_of_two_mesh_pads(self):
        from jax.sharding import Mesh as JMesh

        from horaedb_tpu.ops import ScanAggSpec, scan_aggregate
        from horaedb_tpu.ops.encoding import build_padded_batch
        from horaedb_tpu.parallel import dist_scan_aggregate

        devs = np.array(jax.devices()[:6])
        m6 = JMesh(devs, ("shard",))
        rng = np.random.default_rng(3)
        n = 8192  # pow2 padded len, NOT divisible by 6
        batch = build_padded_batch(
            rng.integers(0, 5, n).astype(np.int32),
            rng.integers(0, 3, n).astype(np.int32),
            np.ones(n, dtype=bool),
            [rng.normal(size=n).astype(np.float32)],
        )
        spec = ScanAggSpec(
            n_groups=5, n_buckets=3, n_agg_fields=1, segment_impl="scatter"
        ).padded()
        single = scan_aggregate(batch, spec)
        dist = dist_scan_aggregate(m6, batch, spec)
        np.testing.assert_array_equal(single.counts, dist.counts)
        np.testing.assert_allclose(single.sums, dist.sums, rtol=1e-4, atol=1e-5)
        # Pad rows are zero-valued: a mask leak would corrupt min/max
        # (inject 0.0) before it ever showed in counts/sums.
        np.testing.assert_allclose(single.mins, dist.mins)
        np.testing.assert_allclose(single.maxs, dist.maxs)


class TestDistMergeDedup:
    """Merge-dedup under shard_map: tsid-range chunks mapped to devices,
    zero collectives, output in global key order (dryrun leg 5)."""

    def test_matches_host_oracle(self, mesh):
        from horaedb_tpu.parallel import dist_merge_dedup

        rng = np.random.default_rng(5)
        n = 5000
        tsid = rng.integers(0, 2**63, 80, dtype=np.uint64)[
            rng.integers(0, 80, n)
        ]
        ts = rng.integers(0, 500, n).astype(np.int64)
        seq = rng.integers(1, 7, n).astype(np.uint64)
        sel = dist_merge_dedup(mesh, tsid, ts, seq)
        # survivor set: one row per key, newest sequence wins
        expect: dict = {}
        for i in range(n):
            k = (int(tsid[i]), int(ts[i]))
            # same-seq ties: LAST input row wins (matches the single-chip
            # kernel's reversal + stable-sort contract)
            if k not in expect or int(seq[i]) >= int(seq[expect[k]]):
                expect[k] = i
        got = {(int(tsid[i]), int(ts[i])): i for i in sel}
        assert set(got) == set(expect)
        for k, i in got.items():
            assert int(seq[i]) == int(seq[expect[k]]), k
        merged = [(int(tsid[i]), int(ts[i])) for i in sel]
        assert merged == sorted(merged)

    def test_no_dedup_keeps_all_rows(self, mesh):
        from horaedb_tpu.parallel import dist_merge_dedup

        rng = np.random.default_rng(6)
        n = 1000
        tsid = rng.integers(0, 2**40, n).astype(np.uint64)
        ts = rng.integers(0, 100, n).astype(np.int64)
        seq = np.ones(n, dtype=np.uint64)
        sel = dist_merge_dedup(mesh, tsid, ts, seq, dedup=False)
        assert len(sel) == n
        assert np.array_equal(np.sort(sel), np.arange(n))
