"""Device-resident scan cache tests incl. review regressions."""

import numpy as np
import pytest

import horaedb_tpu


@pytest.fixture()
def db():
    conn = horaedb_tpu.connect(None)
    yield conn
    conn.close()


DDL = (
    "CREATE TABLE t (host string TAG, v double, ts timestamp KEY) "
    "WITH (segment_duration='1h')"
)


def seed(db, n=200, t_base=1_700_000_000_000):
    db.execute(DDL)
    vals = ", ".join(
        f"('h{i % 5}', {float(i)}, {t_base + i * 1000})" for i in range(n)
    )
    db.execute(f"INSERT INTO t (host, v, ts) VALUES {vals}")
    db.flush_all()


def warm(db, sql):
    """Two runs: first records the fingerprint candidate, second builds."""
    db.execute(sql)
    return db.execute(sql)


class TestScanCache:
    def test_builds_on_second_stable_query(self, db):
        seed(db)
        ex = db.interpreters.executor
        sql = "SELECT host, count(*) AS c FROM t GROUP BY host"
        db.execute(sql)
        assert ex.last_path == "device"  # first sighting: no build
        db.execute(sql)
        assert ex.last_path == "device-cached"  # second: builds + serves
        db.execute(sql)
        assert ex.last_path == "device-cached"  # third: pure HBM hit
        assert ex.scan_cache.hits >= 1

    def test_write_invalidates_immediately(self, db):
        seed(db)
        sql = "SELECT count(*) AS c FROM t"
        warm(db, sql)
        db.execute("INSERT INTO t (host, v, ts) VALUES ('hX', 1.0, 1700000000000)")
        out = db.execute(sql).to_pylist()
        assert out == [{"c": 201}]

    def test_alter_invalidates_without_writes(self, db):
        # Review regression: schema version is part of the fingerprint.
        seed(db)
        warm(db, "SELECT count(*) AS c FROM t")
        db.execute("ALTER TABLE t ADD COLUMN v2 double")
        out = db.execute("SELECT count(v2) AS c FROM t").to_pylist()
        assert out == [{"c": 0}]

    def test_empty_range_epoch_timestamps_no_overflow(self, db):
        # Review regression: epoch-ms data + out-of-range query used to
        # overflow np.int32 after the empty-range reset.
        seed(db, t_base=1_700_000_000_000)
        sql = "SELECT count(*) AS c FROM t WHERE ts >= 1900000000000"
        warm(db, "SELECT count(*) AS c FROM t")  # build cache
        out = db.execute(sql).to_pylist()
        assert out == [{"c": 0}]

    def test_huge_bucket_width_falls_back(self, db):
        # Review regression: 30d bucket overflows int32 ms; must fall back.
        seed(db)
        sql = (
            "SELECT time_bucket(ts, '30d') AS b, count(*) AS c FROM t "
            "GROUP BY time_bucket(ts, '30d')"
        )
        db.execute(sql)
        out = db.execute(sql)
        assert db.interpreters.executor.last_path == "device"  # not cached
        assert out.to_pylist()[0]["c"] == 200

    def test_time_sliced_query_on_cached_data(self, db):
        seed(db)
        t0 = 1_700_000_000_000
        warm(db, "SELECT count(*) AS c FROM t")
        sql = f"SELECT count(*) AS c FROM t WHERE ts >= {t0 + 50_000} AND ts < {t0 + 100_000}"
        out = db.execute(sql).to_pylist()
        assert out == [{"c": 50}]
        assert db.interpreters.executor.last_path == "device-cached"

    def test_tag_filter_series_level(self, db):
        seed(db)
        warm(db, "SELECT count(*) AS c FROM t")
        out = db.execute("SELECT count(*) AS c FROM t WHERE host IN ('h1', 'h3')").to_pylist()
        assert out == [{"c": 80}]
        assert db.interpreters.executor.last_path == "device-cached"


class TestByteBudget:
    """VERDICT r4 item 6: the cache is bounded by BYTES (ref:
    mem_cache.rs:64-158), oversized host copies drop, and a single
    giant table never builds."""

    def test_dropped_host_rows_still_serve_device_path(self, db):
        seed(db, n=300)
        ex = db.interpreters.executor
        ex.scan_cache.max_host_rows_bytes = 1  # force the drop policy
        sql = (
            "SELECT host, count(*) AS c, avg(v) AS a FROM t "
            "WHERE host = 'h1' GROUP BY host"
        )
        out = warm(db, sql)
        assert ex.last_path == "device-cached"
        entry = ex.scan_cache._entries["t"]
        assert entry.rows is None, "host rows copy not dropped"
        # steady-state hits keep serving (tag filter via series_rows,
        # selective time gather via ts_rel_host)
        out = db.execute(sql)
        assert ex.last_path == "device-cached"
        row = out.to_pylist()[0]
        assert row["c"] == 60 and abs(row["a"] - np.mean(
            [float(i) for i in range(300) if i % 5 == 1]
        )) < 1e-9

    def test_new_value_column_rereads_after_drop(self, db):
        seed(db, n=300)
        ex = db.interpreters.executor
        ex.scan_cache.max_host_rows_bytes = 1
        warm(db, "SELECT host, count(v) AS c FROM t GROUP BY host")
        entry = ex.scan_cache._entries["t"]
        assert entry.rows is None
        # a NEW value column forces the re-read path; result exact
        out = db.execute("SELECT host, sum(v) AS s FROM t GROUP BY host")
        assert ex.last_path in ("device-cached", "device", "host")
        got = {r["host"]: r["s"] for r in out.to_pylist()}
        for h in range(5):
            assert abs(
                got[f"h{h}"] - sum(float(i) for i in range(300) if i % 5 == h)
            ) < 1e-9

    def test_byte_budget_evicts_lru(self, db):
        ex = db.interpreters.executor
        for name in ("ta", "tb"):
            db.execute(
                f"CREATE TABLE {name} (host string TAG, v double, "
                "ts timestamp KEY) WITH (segment_duration='1h')"
            )
            vals = ", ".join(
                f"('h{i % 3}', {float(i)}, {1_700_000_000_000 + i * 1000})"
                for i in range(200)
            )
            db.execute(f"INSERT INTO {name} (host, v, ts) VALUES {vals}")
        db.flush_all()
        warm(db, "SELECT host, count(*) AS c FROM ta GROUP BY host")
        assert "ta" in ex.scan_cache._entries
        a_bytes = ex.scan_cache._entries["ta"].total_bytes()
        assert a_bytes > 0
        # budget admits only one entry: building tb evicts ta (LRU)
        ex.scan_cache.max_bytes = int(a_bytes * 1.5)
        warm(db, "SELECT host, count(*) AS c FROM tb GROUP BY host")
        assert "tb" in ex.scan_cache._entries
        assert "ta" not in ex.scan_cache._entries, "LRU eviction by bytes"

    def test_giant_single_table_never_builds(self, db):
        seed(db, n=300)
        ex = db.interpreters.executor
        ex.scan_cache.max_bytes = 1024  # smaller than any real entry
        sql = "SELECT host, count(*) AS c FROM t GROUP BY host"
        out = warm(db, sql)
        assert ex.last_path != "device-cached"
        assert "t" not in ex.scan_cache._entries
        assert {r["host"]: r["c"] for r in out.to_pylist()} == {
            f"h{i}": 60 for i in range(5)
        }


class TestSeriesValueStatPruning:
    """Cached-path analog of row-group min/max pruning: series no BASE
    value of which can pass a numeric filter skip the scan; delta rows
    are exempt (fresh values the base stats don't cover)."""

    def _seed(self, db):
        db.execute(DDL)
        # h0: values 0..9 (max 9), h1: values 100..109 (max 109)
        vals = []
        for i in range(10):
            vals.append(f"('h0', {float(i)}, {1_700_000_000_000 + i * 1000})")
            vals.append(
                f"('h1', {float(100 + i)}, {1_700_000_000_000 + i * 1000})"
            )
        db.execute(f"INSERT INTO t (host, v, ts) VALUES {', '.join(vals)}")
        db.flush_all()

    def test_filter_prunes_series_and_answers_exactly(self, db):
        self._seed(db)
        ex = db.interpreters.executor
        sql = "SELECT count(*) AS c, max(v) AS peak FROM t WHERE v > 50"
        out = warm(db, sql)
        assert ex.last_path == "device-cached"
        assert ex.last_metrics.get("series_pruned") == 1, ex.last_metrics
        assert out.to_pylist() == [{"c": 10, "peak": 109.0}]

    def test_delta_rows_escape_base_stat_pruning(self, db):
        self._seed(db)
        ex = db.interpreters.executor
        sql = "SELECT count(*) AS c, max(v) AS peak FROM t WHERE v > 50"
        warm(db, sql)
        assert ex.last_path == "device-cached"
        # h0's base max is 9 (pruned for v > 50) — but a NEW unflushed row
        # of h0 passes the filter and MUST be counted via the delta fold.
        db.execute(
            "INSERT INTO t (host, v, ts) VALUES ('h0', 999.0, 1700000100000)"
        )
        out = db.execute(sql)
        assert ex.last_path == "device-cached", ex.last_path
        assert out.to_pylist() == [{"c": 11, "peak": 999.0}]

    def test_nan_samples_do_not_poison_series_stats(self, db):
        """Review repro: a NaN sample (e.g. a Prometheus stale marker)
        must not prune a series whose real values pass the filter."""
        db.execute(DDL)
        db.execute(
            "INSERT INTO t (host, v, ts) VALUES " + ", ".join(
                [f"('h0', {float(100 + i)}, {1_700_000_000_000 + (i + 1) * 1000})"
                 for i in range(9)]
                + [f"('h1', {float(i)}, {1_700_000_000_000 + i * 1000})"
                   for i in range(10)]
            )
        )
        # inject a NaN row into h0 through the table layer (SQL literals
        # don't spell NaN)
        import numpy as np

        from horaedb_tpu.common_types import RowGroup

        t = db.catalog.open("t")
        t.write(RowGroup.from_rows(t.schema, [
            {"host": "h0", "v": float("nan"), "ts": 1_700_000_000_000}
        ]))
        db.flush_all()
        ex = db.interpreters.executor
        sql = "SELECT count(*) AS c, max(v) AS peak FROM t WHERE v > 50"
        out = warm(db, sql)
        assert ex.last_path == "device-cached"
        assert out.to_pylist() == [{"c": 9, "peak": 108.0}], out.to_pylist()

    def test_equality_filter_uses_interval_rule(self, db):
        self._seed(db)
        ex = db.interpreters.executor
        sql = "SELECT count(*) AS c FROM t WHERE v = 105"
        out = warm(db, sql)
        if ex.last_path == "device-cached":
            assert ex.last_metrics.get("series_pruned") == 1
        assert out.to_pylist() == [{"c": 1}]


class TestShardedCache:
    """The cached serving path itself shards over the mesh (round 2):
    entry arrays live split across devices, the shard_map cached kernel
    combines with collectives — the DEFAULT multi-device serving path."""

    def test_cached_path_runs_on_mesh(self, db, monkeypatch):
        monkeypatch.setenv("HORAEDB_DIST_MIN_ROWS", "1")
        seed(db, n=500)
        ex = db.interpreters.executor
        sql = (
            "SELECT host, count(*) AS c, avg(v) AS a, min(v) AS lo, "
            "max(v) AS hi FROM t GROUP BY host"
        )
        out = warm(db, sql)
        assert ex.last_path == "device-dist"  # the cache's sharded program
        assert ex.last_metrics.get("mesh_devices") == 8
        entry = ex.scan_cache._entries["t"]
        assert entry.mesh is not None
        assert not entry.series_codes_dev.sharding.is_fully_replicated
        cached_rows = {r["host"]: r for r in out.to_pylist()}

        orig_cap, orig_cached = ex._device_capable, ex._try_cached_agg
        ex._device_capable = lambda plan, rows: False
        ex._try_cached_agg = lambda plan, table, m: None
        host = db.execute(sql)
        ex._device_capable, ex._try_cached_agg = orig_cap, orig_cached
        host_rows = {r["host"]: r for r in host.to_pylist()}
        assert set(cached_rows) == set(host_rows)
        for k in host_rows:
            assert cached_rows[k]["c"] == host_rows[k]["c"]
            for f in ("a", "lo", "hi"):
                np.testing.assert_allclose(
                    cached_rows[k][f], host_rows[k][f], rtol=1e-4, atol=1e-5
                )

    def test_sharded_cache_with_filters(self, db, monkeypatch):
        monkeypatch.setenv("HORAEDB_DIST_MIN_ROWS", "1")
        seed(db, n=500)
        ex = db.interpreters.executor
        sql = (
            "SELECT host, count(*) AS c FROM t "
            "WHERE v > 100 AND host = 'h1' GROUP BY host"
        )
        out = warm(db, sql)
        assert ex.last_path == "device-dist"  # the cache's sharded program
        assert ex.last_metrics.get("mesh_devices") == 8
        rows = out.to_pylist()
        # h1 rows: i % 5 == 1 and v=i > 100 -> i in {101..499}: 80 rows
        assert rows == [{"host": "h1", "c": 80}]

    def test_small_table_cache_stays_single_device(self, db):
        # Below the dist threshold the cache builds unsharded even when a
        # mesh exists — collective dispatch would dominate tiny tables.
        seed(db, n=300)
        ex = db.interpreters.executor
        sql = "SELECT host, count(*) AS c FROM t GROUP BY host"
        warm(db, sql)
        assert ex.last_path == "device-cached"
        assert "mesh_devices" not in ex.last_metrics
        assert ex.scan_cache._entries["t"].mesh is None
        # and the unsharded entry is NOT invalidated by the live mesh
        db.execute(sql)
        assert ex.last_path == "device-cached"
        assert ex.scan_cache.hits >= 1


class TestIncrementalCache:
    """Round 2: ingest must NOT evict the HBM base — unflushed rows fold
    in as a delta on top of the cached kernel output."""

    def test_append_ingest_serves_from_cache_plus_delta(self, db):
        db.execute(
            "CREATE TABLE inc (host string TAG, v double, ts timestamp KEY) "
            "WITH (update_mode='append')"
        )
        vals = ", ".join(f"('h{i % 5}', {float(i)}, {1000 + i})" for i in range(200))
        db.execute(f"INSERT INTO inc (host, v, ts) VALUES {vals}")
        db.flush_all()
        ex = db.interpreters.executor
        sql = "SELECT host, count(*) AS c, sum(v) AS s FROM inc GROUP BY host"
        warm(db, sql)
        assert ex.last_metrics["cache"] in ("build", "hit")
        # Ingest MORE rows (existing series, overlapping timestamps — fine
        # in append mode) without flushing.
        db.execute(
            "INSERT INTO inc (host, v, ts) VALUES ('h0', 100.0, 1500), ('h1', 50.0, 900)"
        )
        out = db.execute(sql)
        assert ex.last_path == "device-cached", ex.last_path
        assert ex.last_metrics["cache"] == "hit+delta"
        assert ex.last_metrics["delta_rows"] == 2
        got = {r["host"]: r for r in out.to_pylist()}
        h0 = [float(i) for i in range(200) if i % 5 == 0] + [100.0]
        h1 = [float(i) for i in range(200) if i % 5 == 1] + [50.0]
        assert got["h0"]["c"] == len(h0) and abs(got["h0"]["s"] - sum(h0)) < 1e-6
        assert got["h1"]["c"] == len(h1) and abs(got["h1"]["s"] - sum(h1)) < 1e-6

    def test_overwrite_newer_rows_serve_as_delta(self, db):
        seed(db, n=200)  # overwrite mode, ts up to t_base+199_000
        db.flush_all()
        ex = db.interpreters.executor
        sql = "SELECT host, count(*) AS c, max(v) AS mx FROM t GROUP BY host"
        warm(db, sql)
        # strictly NEWER timestamps on existing series: sound delta
        t_new = 1_700_000_000_000 + 500_000
        db.execute(
            f"INSERT INTO t (host, v, ts) VALUES ('h0', 999.0, {t_new})"
        )
        out = db.execute(sql)
        assert ex.last_metrics.get("cache") == "hit+delta", ex.last_metrics
        got = {r["host"]: r for r in out.to_pylist()}
        assert got["h0"]["c"] == 41 and got["h0"]["mx"] == 999.0

    def test_overwrite_of_base_row_falls_back(self, db):
        seed(db, n=100)
        db.flush_all()
        ex = db.interpreters.executor
        sql = "SELECT count(*) AS c FROM t"
        warm(db, sql)
        # overwrites a BASE timestamp -> delta unsound -> correct fallback
        db.execute(
            "INSERT INTO t (host, v, ts) VALUES ('h0', 5.0, 1700000000000)"
        )
        out = db.execute(sql)
        assert ex.last_metrics.get("cache") != "hit+delta"
        assert out.to_pylist() == [{"c": 100}]  # overwrite: same key count

    def test_new_series_falls_back(self, db):
        seed(db, n=100)
        db.flush_all()
        ex = db.interpreters.executor
        sql = "SELECT count(*) AS c FROM t"
        warm(db, sql)
        db.execute(
            "INSERT INTO t (host, v, ts) VALUES ('brand_new', 5.0, 1800000000000)"
        )
        out = db.execute(sql)
        assert ex.last_metrics.get("cache") != "hit+delta"
        assert out.to_pylist() == [{"c": 101}]

    def test_flush_rebuilds_base(self, db):
        seed(db, n=100)
        db.flush_all()
        ex = db.interpreters.executor
        sql = "SELECT count(*) AS c FROM t"
        warm(db, sql)
        t_new = 1_700_000_000_000 + 900_000
        db.execute(f"INSERT INTO t (host, v, ts) VALUES ('h1', 1.0, {t_new})")
        db.execute(sql)
        assert ex.last_metrics.get("cache") == "hit+delta"
        db.flush_all()  # base fingerprint changes
        db.execute(sql)
        db.execute(sql)  # stability rule: second sighting builds
        out = db.execute(sql)
        assert ex.last_metrics.get("cache") == "hit"
        assert out.to_pylist() == [{"c": 101}]

    def test_delta_respects_filters_and_buckets(self, db):
        db.execute(
            "CREATE TABLE fincr (host string TAG, v double, ts timestamp KEY) "
            "WITH (update_mode='append')"
        )
        vals = ", ".join(f"('a', {float(i)}, {i * 1000})" for i in range(120))
        db.execute(f"INSERT INTO fincr (host, v, ts) VALUES {vals}")
        db.flush_all()
        ex = db.interpreters.executor
        sql = (
            "SELECT time_bucket(ts, '1m') AS b, count(*) AS c FROM fincr "
            "WHERE v > 50 GROUP BY time_bucket(ts, '1m')"
        )
        warm(db, sql)
        # delta rows land in a NEW later bucket; one fails the filter
        db.execute(
            "INSERT INTO fincr (host, v, ts) VALUES ('a', 60.0, 200000), ('a', 10.0, 201000)"
        )
        out = db.execute(sql)
        assert ex.last_metrics.get("cache") == "hit+delta"
        got = {r["b"]: r["c"] for r in out.to_pylist()}
        # base: v>50 -> i in 51..119 at ts=i*1000
        assert got == {0: 9, 60000: 60, 180000: 1}, got  # delta row filtered


class TestBf16Cache:
    def test_bf16_resident_columns_approximate_host(self, db, monkeypatch):
        monkeypatch.setenv("HORAEDB_CACHE_DTYPE", "bf16")
        seed(db, n=400)
        db.flush_all()
        ex = db.interpreters.executor
        sql = (
            "SELECT host, count(*) AS c, sum(v) AS s, avg(v) AS a "
            "FROM t GROUP BY host"
        )
        out = warm(db, sql)
        assert ex.last_path == "device-cached"
        entry = ex.scan_cache._entries["t"]
        import jax.numpy as jnp

        assert entry.value_cols_dev["v"].dtype == jnp.bfloat16
        got = {r["host"]: r for r in out.to_pylist()}

        orig_cap, orig_cached = ex._device_capable, ex._try_cached_agg
        ex._device_capable = lambda plan, rows: False
        ex._try_cached_agg = lambda plan, table, m: None
        host = {r["host"]: r for r in db.execute(sql).to_pylist()}
        ex._device_capable, ex._try_cached_agg = orig_cap, orig_cached

        for h in host:
            assert got[h]["c"] == host[h]["c"]  # counts stay exact
            # bf16 storage: ~3 significant digits on values
            assert abs(got[h]["s"] - host[h]["s"]) / max(abs(host[h]["s"]), 1) < 2e-2
            assert abs(got[h]["a"] - host[h]["a"]) / max(abs(host[h]["a"]), 1) < 2e-2


class TestLayeredDelta:
    """The cached-agg delta path over a layered memtable skips whole
    frozen segments at/below the entry's build point."""

    def test_delta_correct_over_layered_table(self):
        import horaedb_tpu

        conn = horaedb_tpu.connect(None)
        conn.execute(
            "CREATE TABLE ld (host string TAG, v double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic WITH ("
            "memtable_type='layered', mutable_segment_switch_threshold='1b')"
        )
        for i in range(8):
            conn.execute(
                f"INSERT INTO ld (host, v, ts) VALUES ('h{i % 2}', {float(i)}, {1000 + i})"
            )
        q = "SELECT host, count(*) AS c, sum(v) AS s FROM ld GROUP BY host ORDER BY host"
        first = conn.execute(q).to_pylist()
        # every insert above froze a segment; post-build writes land in
        # NEW segments, pre-build ones must be skipped, totals exact
        for i in range(8, 12):
            conn.execute(
                f"INSERT INTO ld (host, v, ts) VALUES ('h{i % 2}', {float(i)}, {1000 + i})"
            )
        second = conn.execute(q).to_pylist()
        assert first == [
            {"host": "h0", "c": 4, "s": 0 + 2 + 4 + 6.0},
            {"host": "h1", "c": 4, "s": 1 + 3 + 5 + 7.0},
        ]
        assert second == [
            {"host": "h0", "c": 6, "s": 0 + 2 + 4 + 6 + 8 + 10.0},
            {"host": "h1", "c": 6, "s": 1 + 3 + 5 + 7 + 9 + 11.0},
        ]


class TestBoundedAggregateScan:
    """VERDICT r4 item 6 (second half): a GROUP BY over more data than
    HORAEDB_AGG_MEMORY_MB completes by aggregating per segment window —
    the whole table is never materialized in one piece (ref:
    instance/read.rs:165-190 streaming reads)."""

    def _seed_windows(self, db, hours=4, per_hour=120):
        db.execute(
            "CREATE TABLE bw (host string TAG, v double, ts timestamp KEY) "
            "WITH (segment_duration='1h')"
        )
        t0 = 1_700_000_000_000
        hour = 3_600_000
        for h in range(hours):
            vals = ", ".join(
                f"('h{i % 3}', {float(h * per_hour + i)}, "
                f"{t0 + h * hour + i * 1000})"
                for i in range(per_hour)
            )
            db.execute(f"INSERT INTO bw (host, v, ts) VALUES {vals}")
            db.flush_all()
        return t0, hours, per_hour

    def test_windowed_partials_match_oracle(self, db, monkeypatch):
        monkeypatch.setenv("HORAEDB_AGG_MEMORY_MB", "0.005")  # tiny cap
        t0, hours, per_hour = self._seed_windows(db)
        n = hours * per_hour

        # Spy: no single engine read may return the full row count.
        from horaedb_tpu.engine.instance import Instance

        read_sizes = []
        orig = Instance.read

        def spy(self, table, predicate=None, projection=None):
            out = orig(self, table, predicate, projection=projection)
            read_sizes.append(len(out))
            return out

        monkeypatch.setattr(Instance, "read", spy)
        out = db.execute(
            "SELECT host, count(v) AS c, sum(v) AS s, min(v) AS lo, "
            "max(v) AS hi, avg(v) AS a FROM bw GROUP BY host"
        )
        ex = db.interpreters.executor
        assert ex.last_metrics.get("path") == "device-partial", ex.last_metrics
        stages = ex.last_metrics.get("partial_stages") or []
        assert stages and stages[0].get("bounded_windows", 0) >= 4, stages
        assert read_sizes and max(read_sizes) < n, read_sizes
        got = {r["host"]: r for r in out.to_pylist()}
        for h in range(3):
            vals = [
                float(hh * 120 + i)
                for hh in range(4)
                for i in range(120)
                if i % 3 == h
            ]
            assert got[f"h{h}"]["c"] == len(vals)
            assert abs(got[f"h{h}"]["s"] - sum(vals)) < 1e-6
            assert got[f"h{h}"]["lo"] == min(vals)
            assert got[f"h{h}"]["hi"] == max(vals)
            assert abs(got[f"h{h}"]["a"] - np.mean(vals)) < 1e-9

    def test_time_bucket_groups_align_across_windows(self, db, monkeypatch):
        monkeypatch.setenv("HORAEDB_AGG_MEMORY_MB", "0.005")
        t0, hours, per_hour = self._seed_windows(db)
        out = db.execute(
            "SELECT time_bucket(ts, '2h') AS b, count(v) AS c FROM bw "
            "GROUP BY b ORDER BY b"
        )
        rows = out.to_pylist()
        # 4 one-hour windows -> 2 two-hour buckets, each combining TWO
        # windows' partials on equal absolute bucket starts
        assert [r["c"] for r in rows] == [240, 240], rows

    def test_cap_disabled_keeps_single_scan(self, db, monkeypatch):
        monkeypatch.setenv("HORAEDB_AGG_MEMORY_MB", "0")
        self._seed_windows(db, hours=2)
        out = db.execute("SELECT host, count(v) AS c FROM bw GROUP BY host")
        ex = db.interpreters.executor
        assert "bounded_windows" not in str(ex.last_metrics)
        assert sum(r["c"] for r in out.to_pylist()) == 240
