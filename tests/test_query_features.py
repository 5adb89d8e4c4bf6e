"""HAVING / DISTINCT / JOIN / UDF registry tests
(ref model: the DataFusion-provided query features, VERDICT r1 #10)."""

import numpy as np
import pytest

import horaedb_tpu


@pytest.fixture()
def db():
    conn = horaedb_tpu.connect(None)
    conn.execute(
        "CREATE TABLE q (host string TAG, region string TAG, v double, "
        "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
    )
    conn.execute(
        "INSERT INTO q (host, region, v, ts) VALUES "
        "('a', 'us', 1.0, 1000), ('a', 'us', 2.0, 2000), "
        "('b', 'us', 3.0, 1000), ('b', 'eu', 4.0, 2000), "
        "('c', 'eu', 5.0, 1000)"
    )
    yield conn
    conn.close()


class TestHaving:
    def test_having_on_aggregate(self, db):
        out = db.execute(
            "SELECT host, count(*) AS c FROM q GROUP BY host HAVING count(*) > 1 "
            "ORDER BY host"
        ).to_pylist()
        assert out == [{"host": "a", "c": 2}, {"host": "b", "c": 2}]

    def test_having_on_alias(self, db):
        out = db.execute(
            "SELECT host, sum(v) AS s FROM q GROUP BY host HAVING s >= 5 ORDER BY host"
        ).to_pylist()
        assert out == [{"host": "b", "s": 7.0}, {"host": "c", "s": 5.0}]

    def test_having_on_group_key(self, db):
        out = db.execute(
            "SELECT host, count(*) AS c FROM q GROUP BY host HAVING host != 'a' "
            "ORDER BY host"
        ).to_pylist()
        assert [r["host"] for r in out] == ["b", "c"]

    def test_having_missing_from_select_errors(self, db):
        with pytest.raises(Exception, match="SELECT list"):
            db.execute("SELECT host, count(*) AS c FROM q GROUP BY host HAVING sum(v) > 1")


class TestDistinct:
    def test_select_distinct(self, db):
        out = db.execute("SELECT DISTINCT region FROM q ORDER BY region").to_pylist()
        assert out == [{"region": "eu"}, {"region": "us"}]

    def test_distinct_multi_column(self, db):
        out = db.execute(
            "SELECT DISTINCT host, region FROM q ORDER BY host, region"
        ).to_pylist()
        assert out == [
            {"host": "a", "region": "us"},
            {"host": "b", "region": "eu"},
            {"host": "b", "region": "us"},
            {"host": "c", "region": "eu"},
        ]

    def test_distinct_with_limit(self, db):
        out = db.execute(
            "SELECT DISTINCT region FROM q ORDER BY region LIMIT 1"
        ).to_pylist()
        assert out == [{"region": "eu"}]


class TestJoin:
    def test_single_key_inner_join(self, db):
        db.execute(
            "CREATE TABLE hosts (host string TAG, owner string TAG, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO hosts (host, owner, ts) VALUES "
            "('a', 'alice', 1), ('b', 'bob', 1)"
        )
        out = db.execute(
            "SELECT host, v, owner FROM q JOIN hosts ON q.host = hosts.host "
            "ORDER BY host, v"
        ).to_pylist()
        assert out == [
            {"host": "a", "v": 1.0, "owner": "alice"},
            {"host": "a", "v": 2.0, "owner": "alice"},
            {"host": "b", "v": 3.0, "owner": "bob"},
            {"host": "b", "v": 4.0, "owner": "bob"},
        ]  # host c has no owner row: inner join drops it

    def test_join_with_where(self, db):
        db.execute(
            "CREATE TABLE own2 (host string TAG, owner string TAG, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute("INSERT INTO own2 (host, owner, ts) VALUES ('a', 'x', 1), ('b', 'y', 1)")
        out = db.execute(
            "SELECT host, v FROM q JOIN own2 ON q.host = own2.host "
            "WHERE owner = 'y' AND v > 3 ORDER BY v"
        ).to_pylist()
        assert out == [{"host": "b", "v": 4.0}]

    def test_multi_key_inner_join(self, db):
        db.execute(
            "CREATE TABLE caps (host string TAG, region string TAG, "
            "cap double, ts timestamp NOT NULL, TIMESTAMP KEY(ts)) "
            "ENGINE=Analytic"
        )
        # (b, us) and (b, eu) differ only in the SECOND key — a
        # single-key join on host would cross-match them.
        db.execute(
            "INSERT INTO caps (host, region, cap, ts) VALUES "
            "('a', 'us', 10.0, 1), ('b', 'us', 20.0, 1), ('b', 'eu', 30.0, 1)"
        )
        out = db.execute(
            "SELECT host, region, v, cap FROM q JOIN caps "
            "ON q.host = caps.host AND q.region = caps.region "
            "ORDER BY host, region, v"
        ).to_pylist()
        assert out == [
            {"host": "a", "region": "us", "v": 1.0, "cap": 10.0},
            {"host": "a", "region": "us", "v": 2.0, "cap": 10.0},
            {"host": "b", "region": "eu", "v": 4.0, "cap": 30.0},
            {"host": "b", "region": "us", "v": 3.0, "cap": 20.0},
        ]  # host c: no caps row; (b,eu) matched only the eu cap

    def test_multi_key_left_join(self, db):
        db.execute(
            "CREATE TABLE caps2 (host string TAG, region string TAG, "
            "cap double, ts timestamp NOT NULL, TIMESTAMP KEY(ts)) "
            "ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO caps2 (host, region, cap, ts) VALUES ('a', 'us', 10.0, 1)"
        )
        out = db.execute(
            "SELECT host, region, cap FROM q LEFT JOIN caps2 "
            "ON q.host = caps2.host AND q.region = caps2.region "
            "WHERE cap IS NULL ORDER BY host, region"
        ).to_pylist()
        assert out == [
            {"host": "b", "region": "eu", "cap": None},
            {"host": "b", "region": "us", "cap": None},
            {"host": "c", "region": "eu", "cap": None},
        ]

    def test_right_outer_join(self, db):
        db.execute(
            "CREATE TABLE own4 (host string TAG, owner string TAG, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO own4 (host, owner, ts) VALUES "
            "('a', 'alice', 1), ('z', 'zoe', 1)"
        )
        # pandas oracle: q RIGHT JOIN own4 on host
        import pandas as pd

        q = pd.DataFrame({
            "host": ["a", "a", "b", "b", "c"],
            "v": [1.0, 2.0, 3.0, 4.0, 5.0],
        })
        own = pd.DataFrame({"host": ["a", "z"], "owner": ["alice", "zoe"]})
        oracle = q.merge(own, on="host", how="right")
        expect = sorted(
            (r.host, None if pd.isna(r.v) else r.v, r.owner)
            for r in oracle.itertuples()
        )
        out = db.execute(
            "SELECT host, v, owner FROM q RIGHT JOIN own4 ON q.host = own4.host"
        ).to_pylist()
        got = sorted((r["host"], r["v"], r["owner"]) for r in out)
        assert got == expect  # 'z' survives with NULL v; b/c dropped

    def test_full_outer_join(self, db):
        db.execute(
            "CREATE TABLE own5 (host string TAG, owner string TAG, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO own5 (host, owner, ts) VALUES "
            "('a', 'alice', 1), ('z', 'zoe', 1)"
        )
        import pandas as pd

        q = pd.DataFrame({
            "host": ["a", "a", "b", "b", "c"],
            "v": [1.0, 2.0, 3.0, 4.0, 5.0],
        })
        own = pd.DataFrame({"host": ["a", "z"], "owner": ["alice", "zoe"]})
        oracle = q.merge(own, on="host", how="outer")
        expect = sorted(
            (
                r.host,
                None if pd.isna(r.v) else r.v,
                None if (isinstance(r.owner, float) and pd.isna(r.owner)) else r.owner,
            )
            for r in oracle.itertuples()
        )
        out = db.execute(
            "SELECT host, v, owner FROM q FULL OUTER JOIN own5 "
            "ON q.host = own5.host"
        ).to_pylist()
        got = sorted((r["host"], r["v"], r["owner"]) for r in out)
        assert got == expect

    def test_three_table_chain(self, db):
        db.execute(
            "CREATE TABLE own6 (host string TAG, owner string TAG, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO own6 (host, owner, ts) VALUES "
            "('a', 'alice', 1), ('b', 'bob', 1)"
        )
        db.execute(
            "CREATE TABLE teams (owner string TAG, team string TAG, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO teams (owner, team, ts) VALUES "
            "('alice', 'core', 1), ('bob', 'infra', 1)"
        )
        out = db.execute(
            "SELECT host, v, owner, team FROM q "
            "JOIN own6 ON q.host = own6.host "
            "JOIN teams ON own6.owner = teams.owner "
            "ORDER BY host, v"
        ).to_pylist()
        assert out == [
            {"host": "a", "v": 1.0, "owner": "alice", "team": "core"},
            {"host": "a", "v": 2.0, "owner": "alice", "team": "core"},
            {"host": "b", "v": 3.0, "owner": "bob", "team": "infra"},
            {"host": "b", "v": 4.0, "owner": "bob", "team": "infra"},
        ]

    def test_chain_with_left_then_inner(self, db):
        db.execute(
            "CREATE TABLE own7 (host string TAG, owner string TAG, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute("INSERT INTO own7 (host, owner, ts) VALUES ('a', 'alice', 1)")
        db.execute(
            "CREATE TABLE teams2 (owner string TAG, team string TAG, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO teams2 (owner, team, ts) VALUES ('alice', 'core', 1)"
        )
        # LEFT keeps b/c rows with NULL owner; the following INNER join on
        # owner then drops them (NULL matches nothing) — SQL semantics.
        out = db.execute(
            "SELECT host, owner, team FROM q "
            "LEFT JOIN own7 ON q.host = own7.host "
            "JOIN teams2 ON own7.owner = teams2.owner "
            "ORDER BY host"
        ).to_pylist()
        assert {(r["host"], r["owner"], r["team"]) for r in out} == {
            ("a", "alice", "core")
        }

    def test_join_aggregate_rejected(self, db):
        db.execute(
            "CREATE TABLE own3 (host string TAG, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        with pytest.raises(Exception, match="JOIN"):
            db.execute(
                "SELECT count(*) AS c FROM q JOIN own3 ON q.host = own3.host"
            )


class TestExists:
    """[NOT] EXISTS — uncorrelated constants and equality-correlated
    semi/anti joins (decorrelated like the scalar subqueries)."""

    def _dim(self, db):
        db.execute(
            "CREATE TABLE act (host string TAG, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO act (host, ts) VALUES ('a', 1), ('c', 1)"
        )

    def test_correlated_exists_semi_join(self, db):
        self._dim(db)
        out = db.execute(
            "SELECT host, v FROM q WHERE EXISTS "
            "(SELECT * FROM act WHERE act.host = q.host) ORDER BY host, v"
        ).to_pylist()
        assert [(r["host"], r["v"]) for r in out] == [
            ("a", 1.0), ("a", 2.0), ("c", 5.0)
        ]

    def test_correlated_not_exists_anti_join(self, db):
        self._dim(db)
        out = db.execute(
            "SELECT host, v FROM q WHERE NOT EXISTS "
            "(SELECT * FROM act WHERE act.host = q.host) ORDER BY host, v"
        ).to_pylist()
        assert [(r["host"], r["v"]) for r in out] == [("b", 3.0), ("b", 4.0)]

    def test_exists_with_residual_inner_filter(self, db):
        self._dim(db)
        db.execute("INSERT INTO act (host, ts) VALUES ('b', 5000)")
        # only act rows with ts >= 5000 count: semi-join keeps just b
        out = db.execute(
            "SELECT DISTINCT host FROM q WHERE EXISTS "
            "(SELECT * FROM act WHERE act.host = q.host AND act.ts >= 5000) "
            "ORDER BY host"
        ).to_pylist()
        assert [r["host"] for r in out] == ["b"]

    def test_uncorrelated_exists_constant(self, db):
        self._dim(db)
        assert len(db.execute(
            "SELECT host FROM q WHERE EXISTS (SELECT * FROM act)"
        ).to_pylist()) == 5
        assert db.execute(
            "SELECT host FROM q WHERE EXISTS "
            "(SELECT * FROM act WHERE ts > 999999)"
        ).to_pylist() == []
        assert len(db.execute(
            "SELECT host FROM q WHERE NOT EXISTS "
            "(SELECT * FROM act WHERE ts > 999999)"
        ).to_pylist()) == 5

    def test_exists_limit_zero_is_false(self, db):
        self._dim(db)
        # LIMIT 0 empties the subquery: EXISTS is false, NOT EXISTS true.
        assert db.execute(
            "SELECT host FROM q WHERE EXISTS (SELECT * FROM act LIMIT 0)"
        ).to_pylist() == []
        assert len(db.execute(
            "SELECT host FROM q WHERE NOT EXISTS (SELECT * FROM act LIMIT 0)"
        ).to_pylist()) == 5

    def test_correlated_exists_over_aggregate_always_true(self, db):
        self._dim(db)
        # An ungrouped aggregate subquery yields exactly ONE row per
        # outer row (NULL max over the empty group included): EXISTS is
        # unconditionally true — even for hosts absent from act.
        out = db.execute(
            "SELECT host, v FROM q WHERE EXISTS "
            "(SELECT max(ts) FROM act WHERE act.host = q.host) ORDER BY v"
        ).to_pylist()
        assert len(out) == 5

    def test_exists_combines_with_other_predicates(self, db):
        self._dim(db)
        out = db.execute(
            "SELECT host, v FROM q WHERE v > 1 AND EXISTS "
            "(SELECT * FROM act WHERE act.host = q.host) ORDER BY v"
        ).to_pylist()
        assert [(r["host"], r["v"]) for r in out] == [("a", 2.0), ("c", 5.0)]


class TestUdfRegistry:
    def test_thetasketch_distinct(self, db):
        out = db.execute(
            "SELECT region, thetasketch_distinct(host) AS d FROM q "
            "GROUP BY region ORDER BY region"
        ).to_pylist()
        assert out == [{"region": "eu", "d": 2}, {"region": "us", "d": 2}]

    def test_registered_scalar(self, db):
        from horaedb_tpu.query.functions import REGISTRY

        def double_fn(args, rows):
            v, m = args[0]
            return v * 2, m

        REGISTRY.register_scalar("double", double_fn)
        try:
            out = db.execute("SELECT host, double(v) AS d FROM q WHERE host = 'c'").to_pylist()
            assert out == [{"host": "c", "d": 10.0}]
        finally:
            REGISTRY._scalars.pop("double", None)

    def test_builtin_scalars_still_work(self, db):
        out = db.execute(
            "SELECT time_bucket(ts, '1s') AS b, count(*) AS c FROM q "
            "GROUP BY time_bucket(ts, '1s') ORDER BY b"
        ).to_pylist()
        assert out == [{"b": 1000, "c": 3}, {"b": 2000, "c": 2}]


class TestReviewRegressions:
    def test_having_without_group_by_rejected(self, db):
        with pytest.raises(Exception, match="HAVING requires GROUP BY"):
            db.execute("SELECT v FROM q HAVING v > 4")

    def test_distinct_respects_nulls(self, db):
        db.execute(
            "CREATE TABLE dn (h string TAG, x double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO dn (h, x, ts) VALUES ('a', 0.0, 1), ('a', NULL, 2), "
            "('a', 0.0, 3), ('a', NULL, 4)"
        )
        out = db.execute("SELECT DISTINCT x FROM dn").to_pylist()
        assert sorted(out, key=lambda r: (r["x"] is None, r["x"])) == [
            {"x": 0.0}, {"x": None},
        ]

    def test_distinct_on_aggregate_output(self, db):
        # two hosts with the same sum collapse under DISTINCT
        out = db.execute(
            "SELECT DISTINCT count(*) AS c FROM q GROUP BY host"
        ).to_pylist()
        assert sorted(r["c"] for r in out) == [1, 2]

    def test_unknown_qualifier_rejected(self, db):
        with pytest.raises(Exception, match="qualifier"):
            db.execute("SELECT nosuch.v FROM q")

    def test_bad_wal_backend_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="wal_backend"):
            horaedb_tpu.connect(str(tmp_path / "x"), wal_backend="objectstore")


class TestSubqueries:
    def test_in_subquery(self, db):
        db.execute(
            "CREATE TABLE big (host string TAG, v double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO big (host, v, ts) VALUES ('a', 100, 1), ('c', 300, 2)"
        )
        out = db.execute(
            "SELECT host, v FROM q WHERE host IN (SELECT host FROM big) ORDER BY v"
        ).to_pylist()
        assert [r["host"] for r in out] == ["a", "a", "c"]
        out = db.execute(
            "SELECT host FROM q WHERE host NOT IN (SELECT host FROM big) "
            "ORDER BY host"
        ).to_pylist()
        assert sorted({r["host"] for r in out}) == ["b"]

    def test_in_subquery_with_inner_filter(self, db):
        db.execute(
            "CREATE TABLE big2 (host string TAG, v double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO big2 (host, v, ts) VALUES ('a', 1, 1), ('b', 9, 2)"
        )
        out = db.execute(
            "SELECT host, count(*) AS c FROM q "
            "WHERE host IN (SELECT host FROM big2 WHERE v > 5) GROUP BY host"
        ).to_pylist()
        assert out == [{"host": "b", "c": 2}]

    def test_scalar_subquery(self, db):
        out = db.execute(
            "SELECT host, v FROM q WHERE v > (SELECT avg(v) FROM q) ORDER BY v"
        ).to_pylist()
        # avg = 3.0 -> rows with v in {4, 5}
        assert [r["v"] for r in out] == [4.0, 5.0]

    def test_scalar_subquery_multi_row_errors(self, db):
        with pytest.raises(Exception, match="scalar subquery"):
            db.execute("SELECT host FROM q WHERE v > (SELECT v FROM q)")

    def test_subquery_multi_column_errors(self, db):
        with pytest.raises(Exception, match="one column"):
            db.execute("SELECT host FROM q WHERE host IN (SELECT host, v FROM q)")

    def test_subquery_in_function_and_select_list(self, db):
        # nested positions: function args, scalar in the select list
        out = db.execute(
            "SELECT host FROM q WHERE abs(v - (SELECT avg(v) FROM q)) < 0.5 "
            "ORDER BY host"
        ).to_pylist()
        assert [r["host"] for r in out] == ["b"]  # v=3 vs avg 3.0
        out = db.execute("SELECT (SELECT max(v) FROM q) AS m FROM q LIMIT 1").to_pylist()
        assert out == [{"m": 5.0}]


class TestLeftJoin:
    def test_left_join_keeps_unmatched(self, db):
        db.execute(
            "CREATE TABLE lo (host string TAG, owner string TAG, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute("INSERT INTO lo (host, owner, ts) VALUES ('a', 'alice', 1)")
        out = db.execute(
            "SELECT host, v, owner FROM q LEFT JOIN lo ON q.host = lo.host "
            "ORDER BY host, v"
        ).to_pylist()
        # a matches, b/c have NULL owner
        assert out[0] == {"host": "a", "v": 1.0, "owner": "alice"}
        assert out[1] == {"host": "a", "v": 2.0, "owner": "alice"}
        assert all(r["owner"] is None for r in out if r["host"] != "a")
        assert len(out) == 5  # every left row survives

    def test_left_outer_join_empty_right(self, db):
        db.execute(
            "CREATE TABLE lo2 (host string TAG, owner string TAG, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        out = db.execute(
            "SELECT host, owner FROM q LEFT OUTER JOIN lo2 ON q.host = lo2.host"
        ).to_pylist()
        assert len(out) == 5 and all(r["owner"] is None for r in out)

    def test_left_join_where_on_right_null(self, db):
        db.execute(
            "CREATE TABLE lo3 (host string TAG, owner string TAG, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute("INSERT INTO lo3 (host, owner, ts) VALUES ('a', 'x', 1)")
        out = db.execute(
            "SELECT DISTINCT host FROM q LEFT JOIN lo3 ON q.host = lo3.host "
            "WHERE owner IS NULL ORDER BY host"
        ).to_pylist()
        assert [r["host"] for r in out] == ["b", "c"]

    def test_left_join_null_compare_and_order(self, db):
        # review regressions: empty-right comparison must not crash on
        # object-dtype columns, and NULL placement under ORDER BY must not
        # leak an arbitrary right-side row's value
        db.execute(
            "CREATE TABLE lo4 (host string TAG, owner string TAG, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        out = db.execute(
            "SELECT host FROM q LEFT JOIN lo4 ON q.host = lo4.host "
            "WHERE owner > 'a'"
        ).to_pylist()
        assert out == []  # all owners NULL -> no row passes
        db.execute(
            "INSERT INTO lo4 (host, owner, ts) VALUES ('b', 'zed', 1)"
        )
        out = db.execute(
            "SELECT DISTINCT host, owner FROM q LEFT JOIN lo4 "
            "ON q.host = lo4.host ORDER BY owner, host"
        ).to_pylist()
        # SQL default NULL placement: LAST under ASC (explicit _null_rank
        # keys — no longer the ''-fill artifact that put NULLs first); and
        # NULL rows surface as None, never an arbitrary right-side value.
        assert out[0]["owner"] == "zed"
        assert all(r["owner"] is None for r in out[1:])
        out_first = db.execute(
            "SELECT DISTINCT host, owner FROM q LEFT JOIN lo4 "
            "ON q.host = lo4.host ORDER BY owner NULLS FIRST, host"
        ).to_pylist()
        assert out_first[-1]["owner"] == "zed"
        assert all(r["owner"] is None for r in out_first[:-1])


class TestLimitPushdown:
    """LIMIT pushdown into the scan for APPEND tables (any n rows are a
    correct answer when no residual filter/sort needs the full set)."""

    def _make(self, tmp_path, n_flushes=5):
        import horaedb_tpu

        conn = horaedb_tpu.connect(str(tmp_path / "db"))
        conn.execute(
            "CREATE TABLE ap (host string TAG, v double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic WITH (update_mode='APPEND')"
        )
        t = conn.catalog.open("ap")
        for k in range(n_flushes):
            vals = ", ".join(
                f"('h{i % 4}', {float(k * 100 + i)}, {10_000 * k + i})"
                for i in range(100)
            )
            conn.execute(f"INSERT INTO ap (host, v, ts) VALUES {vals}")
            conn.instance.flush_table(t.data)
        return conn

    def test_limit_stops_early_and_is_exact(self, tmp_path):
        conn = self._make(tmp_path)
        out = conn.execute("SELECT host, v, ts FROM ap LIMIT 7")
        assert out.num_rows == 7
        m = out.metrics
        assert m["limit_pushdown"] == 7
        # early stop: scanned far fewer than the 500 stored rows
        assert m["rows_scanned"] < 500, m
        # time-only WHERE still pushes down
        out = conn.execute("SELECT v FROM ap WHERE ts >= 0 AND ts < 50000 LIMIT 3")
        assert out.num_rows == 3 and out.metrics["limit_pushdown"] == 3
        conn.close()

    def test_no_pushdown_when_unsafe(self, tmp_path):
        conn = self._make(tmp_path, n_flushes=2)
        # tag filter: scan must NOT stop early (filter runs after scan)
        out = conn.execute("SELECT v FROM ap WHERE host = 'h1' LIMIT 5")
        assert out.num_rows == 5
        assert "limit_pushdown" not in (out.metrics or {})
        # ORDER BY needs the full set
        out = conn.execute("SELECT v FROM ap ORDER BY v DESC LIMIT 5")
        assert "limit_pushdown" not in (out.metrics or {})
        assert [float(v) for v in out.column("v")] == [199.0, 198.0, 197.0, 196.0, 195.0]
        # OVERWRITE tables keep the full merge (dedup correctness)
        conn.execute(
            "CREATE TABLE ow (host string TAG, v double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        conn.execute("INSERT INTO ow (host, v, ts) VALUES ('a', 1.0, 1)")
        out = conn.execute("SELECT v FROM ow LIMIT 1")
        # dedup scans ignore the hint, so the metric must not claim it
        assert out.num_rows == 1 and "limit_pushdown" not in (out.metrics or {})
        conn.close()


class TestCorrelatedSubquery:
    def test_equality_correlated_scalar_executes(self, db):
        db.execute(
            "CREATE TABLE oth (host string TAG, w double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute("INSERT INTO oth (host, w, ts) VALUES ('a', 5.0, 1)")
        # Decorrelated: per-host max(w); hosts without an oth row compare
        # against NULL -> dropped.
        out = db.execute(
            "SELECT host, v FROM q WHERE v < "
            "(SELECT max(w) FROM oth WHERE oth.host = q.host) ORDER BY v"
        ).to_pylist()
        assert out == [{"host": "a", "v": 1.0}, {"host": "a", "v": 2.0}]
        # uncorrelated still works
        out = db.execute(
            "SELECT host FROM q WHERE v < (SELECT max(w) FROM oth) ORDER BY host, v"
        ).to_pylist()
        assert [r["host"] for r in out] == ["a", "a", "b", "b"]  # v < 5.0

    def test_correlated_count_defaults_to_zero(self, db):
        db.execute(
            "CREATE TABLE ev (host string TAG, w double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO ev (host, w, ts) VALUES ('a', 1.0, 1), ('a', 2.0, 2)"
        )
        # COUNT over an empty correlated group is 0, not NULL: hosts with
        # no ev rows satisfy '= 0'.
        out = db.execute(
            "SELECT DISTINCT host FROM q WHERE "
            "(SELECT count(w) FROM ev WHERE ev.host = q.host) = 0 "
            "ORDER BY host"
        ).to_pylist()
        assert [r["host"] for r in out] == ["b", "c"]

    def test_correlated_in_select_item(self, db):
        db.execute(
            "CREATE TABLE sums (host string TAG, w double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO sums (host, w, ts) VALUES "
            "('a', 10.0, 1), ('a', 20.0, 2), ('b', 5.0, 1)"
        )
        out = db.execute(
            "SELECT DISTINCT host, "
            "(SELECT sum(w) FROM sums WHERE sums.host = q.host) AS s "
            "FROM q ORDER BY host"
        ).to_pylist()
        assert out == [
            {"host": "a", "s": 30.0},
            {"host": "b", "s": 5.0},
            {"host": "c", "s": None},
        ]

    def test_correlated_with_residual_filter(self, db):
        db.execute(
            "CREATE TABLE rf (host string TAG, w double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO rf (host, w, ts) VALUES "
            "('a', 100.0, 1), ('a', 1.0, 2), ('b', 100.0, 1)"
        )
        # the uncorrelated conjunct (w < 50) stays inside the subquery
        out = db.execute(
            "SELECT DISTINCT host FROM q WHERE v <= "
            "(SELECT max(w) FROM rf WHERE rf.host = q.host AND w < 50) "
            "ORDER BY host"
        ).to_pylist()
        assert [r["host"] for r in out] == ["a"]  # only a has w<50 rows

    def test_correlation_column_not_otherwise_selected(self, db):
        """The correlation column appears ONLY inside the subquery; scan
        pruning must still fetch it for the lookup."""
        db.execute(
            "CREATE TABLE ev2 (host string TAG, w double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute("INSERT INTO ev2 (host, w, ts) VALUES ('a', 1.0, 1)")
        out = db.execute(
            "SELECT v, (SELECT count(w) FROM ev2 WHERE ev2.host = q.host) AS c "
            "FROM q ORDER BY v"
        ).to_pylist()
        assert [r["c"] for r in out] == [1, 1, 0, 0, 0]

    def test_correlation_on_non_tag_column(self, db):
        """A non-TAG correlation key drives the inner grouped query down
        the host aggregation path (regression: aliased group keys)."""
        db.execute(
            "CREATE TABLE nt (code double, w double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO nt (code, w, ts) VALUES (1.0, 10.0, 1), (1.0, 20.0, 2)"
        )
        out = db.execute(
            "SELECT v, (SELECT sum(w) FROM nt WHERE nt.code = q.v) AS s "
            "FROM q WHERE v = 1.0"
        ).to_pylist()
        assert out == [{"v": 1.0, "s": 30.0}]

    def test_group_key_alias_host_path(self, db):
        # pre-existing host-path bug the decorrelation surfaced:
        # aliased group keys must resolve by expression, not output name
        ex = db.interpreters.executor
        orig = ex._device_capable
        ex._device_capable = lambda plan, rows: False
        try:
            out = db.execute(
                "SELECT host AS h, max(v) AS m FROM q GROUP BY host ORDER BY h"
            ).to_pylist()
        finally:
            ex._device_capable = orig
        assert out == [
            {"h": "a", "m": 2.0},
            {"h": "b", "m": 4.0},
            {"h": "c", "m": 5.0},
        ]

    def test_string_valued_correlated_scalar(self, db):
        db.execute(
            "CREATE TABLE own (host string TAG, owner string TAG, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO own (host, owner, ts) VALUES ('a', 'alice', 1), ('b', 'bob', 1)"
        )
        out = db.execute(
            "SELECT DISTINCT host, "
            "(SELECT owner FROM own WHERE own.host = q.host) AS o "
            "FROM q ORDER BY host"
        ).to_pylist()
        assert out == [
            {"host": "a", "o": "alice"},
            {"host": "b", "o": "bob"},
            {"host": "c", "o": None},
        ]

    def test_correlated_count_is_integer(self, db):
        db.execute(
            "CREATE TABLE ci (host string TAG, w double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute("INSERT INTO ci (host, w, ts) VALUES ('a', 1.0, 1)")
        out = db.execute(
            "SELECT DISTINCT host, "
            "(SELECT count(w) FROM ci WHERE ci.host = q.host) AS c "
            "FROM q ORDER BY host"
        ).to_pylist()
        assert out[0]["c"] == 1 and isinstance(out[0]["c"], int)
        assert out[2]["c"] == 0 and isinstance(out[2]["c"], int)

    def test_null_outer_key_counts_as_zero(self, db):
        """A NULL correlation key matches nothing — COUNT over the empty
        group is 0 (not NULL)."""
        db.execute(
            "CREATE TABLE nk (code double, w double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute("INSERT INTO nk (code, w, ts) VALUES (1.0, 5.0, 1)")
        # outer row with NULL v (field columns are nullable)
        db.execute("INSERT INTO q (host, region, ts) VALUES ('z', 'us', 50)")
        out = db.execute(
            "SELECT host, (SELECT count(w) FROM nk WHERE nk.code = q.v) AS c "
            "FROM q WHERE host = 'z'"
        ).to_pylist()
        assert out == [{"host": "z", "c": 0}]

    def test_null_inner_key_never_matches(self, db):
        """NULL inner correlation keys are not equal to anything — they
        must not surface as the column's fill value (0.0)."""
        db.execute(
            "CREATE TABLE nik (code double, w double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute("INSERT INTO nik (w, ts) VALUES (7.0, 1)")  # code NULL
        db.execute("INSERT INTO q (host, region, v, ts) VALUES ('z', 'us', 0.0, 50)")
        out = db.execute(
            "SELECT host, (SELECT w FROM nik WHERE nik.code = q.v) AS s "
            "FROM q WHERE host = 'z'"
        ).to_pylist()
        assert out == [{"host": "z", "s": None}]
        out = db.execute(
            "SELECT host, (SELECT count(w) FROM nik WHERE nik.code = q.v) AS c "
            "FROM q WHERE host = 'z'"
        ).to_pylist()
        assert out == [{"host": "z", "c": 0}]
        # a real 0.0 key still matches (and the NULL row stays invisible)
        db.execute("INSERT INTO nik (code, w, ts) VALUES (0.0, 5.0, 2)")
        out = db.execute(
            "SELECT host, (SELECT w FROM nik WHERE nik.code = q.v) AS s "
            "FROM q WHERE host = 'z'"
        ).to_pylist()
        assert out == [{"host": "z", "s": 5.0}]

    def test_null_group_key_forms_own_group(self, db):
        """GROUP BY over a nullable column: NULLs form one group reported
        as NULL (not the fill value)."""
        db.execute(
            "CREATE TABLE ng (code double, w double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO ng (code, w, ts) VALUES (0.0, 1.0, 1), (2.0, 3.0, 2)"
        )
        db.execute("INSERT INTO ng (w, ts) VALUES (9.0, 3)")  # code NULL
        rows = db.execute(
            "SELECT code, count(*) AS c, sum(w) AS s FROM ng GROUP BY code"
        ).to_pylist()
        assert len(rows) == 3
        bykey = {r["code"]: r for r in rows}
        assert bykey[None] == {"code": None, "c": 1, "s": 9.0}
        assert bykey[0.0] == {"code": 0.0, "c": 1, "s": 1.0}
        assert bykey[2.0] == {"code": 2.0, "c": 1, "s": 3.0}

    def test_unprobed_duplicate_key_is_fine(self, db):
        """Duplicate correlation keys the outer query never probes must
        not error (SQL errors only on probed keys)."""
        db.execute(
            "CREATE TABLE d2 (host string TAG, w double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        # 'zzz' is duplicated but no outer row has host 'zzz'
        db.execute(
            "INSERT INTO d2 (host, w, ts) VALUES "
            "('a', 9.0, 1), ('zzz', 1.0, 1), ('zzz', 2.0, 2)"
        )
        out = db.execute(
            "SELECT host, v FROM q WHERE v < "
            "(SELECT w FROM d2 WHERE d2.host = q.host) ORDER BY v"
        ).to_pylist()
        assert out == [
            {"host": "a", "v": 1.0},
            {"host": "a", "v": 2.0},
        ]
        # a PROBED duplicate still errors
        db.execute("INSERT INTO q (host, region, v, ts) VALUES ('zzz', 'us', 0.0, 9)")
        with pytest.raises(Exception, match="more than one row"):
            db.execute(
                "SELECT host FROM q WHERE v < "
                "(SELECT w FROM d2 WHERE d2.host = q.host)"
            )

    def test_unsupported_correlation_shape_clear_error(self, db):
        db.execute(
            "CREATE TABLE us (host string TAG, w double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        with pytest.raises(Exception, match="correlated subquery not supported"):
            db.execute(
                "SELECT host FROM q WHERE v < "
                "(SELECT max(w) FROM us WHERE us.w > q.v)"  # non-equality
            )

    def test_nested_correlated_also_clear(self, db):
        db.execute(
            "CREATE TABLE oth2 (host string TAG, w2 double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "CREATE TABLE oth3 (host string TAG, w double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute("INSERT INTO oth3 (host, w, ts) VALUES ('a', 5.0, 1)")
        db.execute("INSERT INTO oth2 (host, w2, ts) VALUES ('a', 5.0, 1)")
        # the correlation is two levels down: still the clear message
        with pytest.raises(Exception, match="correlated subqueries"):
            db.execute(
                "SELECT host FROM q WHERE v < (SELECT max(w) FROM oth3 "
                "WHERE w IN (SELECT w2 FROM oth2 WHERE oth2.host = q.host))"
            )
        # and a legal nested-uncorrelated chain still runs
        out = db.execute(
            "SELECT host FROM q WHERE v < (SELECT max(w) FROM oth3 "
            "WHERE w IN (SELECT w2 FROM oth2)) ORDER BY host, v"
        ).to_pylist()
        assert [r["host"] for r in out] == ["a", "a", "b", "b"]


class TestAdaptivePathRouting:
    def test_router_converges_to_faster_path(self):
        from horaedb_tpu.query.path_router import PathRouter, PROBE_EVERY

        r = PathRouter()
        key = ("t", "shape")
        # "device" until it holds a clean sample, then one host sample
        assert r.choose(key) == "device"
        r.record(key, "device", 2.3, clean=False)  # jit-compile-tainted
        assert r.choose(key) == "device"
        r.record(key, "device", 0.080)  # steady
        assert r.choose(key) == "host"
        r.record(key, "host", 0.002)
        lat = {"host": 0.002, "device": 0.080}

        def serve(calls):
            picks = []
            for _ in range(calls):
                picks.append(r.choose(key))
                r.record(key, picks[-1], lat[picks[-1]])
            return picks

        # the loser's one sample is confirmed after PROBE_EVERY calls of the
        # winner (the host's first sample was one of them) ...
        assert serve(PROBE_EVERY) == ["host"] * (PROBE_EVERY - 1) + ["device"]
        # ... and from its second sample on it (40 x slower) is re-probed once
        # the winner has SERVED PROBE_EVERY x its time: 16 x 0.080 s = 640
        # host serves
        picks = serve(2 * (PROBE_EVERY * 40 + 2))
        assert "device" not in picks[:PROBE_EVERY * 40 - 1]
        assert picks.count("device") == 2  # loser is still re-probed
        assert r.stats(key)["device"] == 0.080  # compile sample dropped

    def test_router_adapts_when_loser_improves(self):
        from horaedb_tpu.query.path_router import PathRouter

        r = PathRouter()
        key = ("t", "s")
        r.record(key, "device", 0.100)
        r.record(key, "device", 0.100)
        r.record(key, "host", 0.010)
        assert r.choose(key) == "host"
        # device improves drastically (e.g. scan cache finished building)
        r.record(key, "device", 0.001)
        assert r.choose(key) == "device"

    def test_router_resists_one_off_hiccups(self):
        from horaedb_tpu.query.path_router import PathRouter

        r = PathRouter()
        key = ("t", "s")
        r.record(key, "device", 0.010)
        r.record(key, "device", 0.010)
        r.record(key, "host", 0.050)
        assert r.choose(key) == "device"
        r.record(key, "device", 1.0)  # single GC pause / dispatch hiccup
        assert r.choose(key) == "device"  # 10% creep, not a flip

    def test_adaptive_routing_serves_host_when_device_slow(self, db, monkeypatch):
        """End-to-end: with adaptive routing forced on and a slow device
        path, repeated queries settle on the host path."""
        monkeypatch.setenv("HORAEDB_ADAPTIVE_PATH", "1")
        ex = db.interpreters.executor
        ex._adaptive = None  # re-resolve from env

        import time as _t
        orig = ex._try_cached_agg

        def slow_cached(plan, table, m):
            _t.sleep(0.05)
            return orig(plan, table, m)

        ex._try_cached_agg = slow_cached
        sql = "SELECT host, avg(v) AS a FROM q GROUP BY host"
        paths = []
        for _ in range(6):
            out = db.execute(sql)
            paths.append(out.metrics["path"])
        assert paths[-1] == "host"
        # results stay identical across paths
        assert sorted(db.execute(sql).to_pylist(), key=str) == sorted(
            out.to_pylist(), key=str
        )
        ex._try_cached_agg = orig

    def test_shape_key_masks_literals(self):
        """Rolling-window refreshes (same query, fresh literals) must share
        one routing key; different shapes must not."""
        import horaedb_tpu
        from horaedb_tpu.query.path_router import plan_shape_key

        conn = horaedb_tpu.connect(None)
        conn.execute(
            "CREATE TABLE sk (host string TAG, v double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        plan = lambda sql: conn.frontend.statement_to_plan(conn.frontend.parse_sql(sql))
        k1 = plan_shape_key(plan("SELECT host, avg(v) AS a FROM sk WHERE ts > 1000 GROUP BY host"))
        k2 = plan_shape_key(plan("SELECT host, avg(v) AS a FROM sk WHERE ts > 99999 GROUP BY host"))
        k3 = plan_shape_key(plan("SELECT host, max(v) AS a FROM sk WHERE ts > 1000 GROUP BY host"))
        assert k1 == k2
        assert k1 != k3
        conn.close()

    def test_router_lru_bound(self):
        from horaedb_tpu.query.path_router import MAX_KEYS, PathRouter

        r = PathRouter()
        for i in range(MAX_KEYS + 50):
            r.record(("t", i), "host", 0.01)
        assert len(r._stats) == MAX_KEYS


class TestWindowFunctions:
    """OVER (PARTITION BY .. ORDER BY ..) on the host path (ref parity:
    DataFusion window functions, query_engine/src/datafusion_impl/mod.rs:54)."""

    @pytest.fixture()
    def wdb(self, db):
        db.execute(
            "CREATE TABLE w (host string TAG, v double, t timestamp KEY) "
            "ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO w (host, v, t) VALUES "
            "('a', 1, 1000), ('a', 3, 2000), ('a', 2, 3000), "
            "('b', 5, 1000), ('b', 5, 2000)"
        )
        return db

    def test_row_number_lag_lead(self, wdb):
        r = wdb.execute(
            "SELECT host, t, row_number() OVER (PARTITION BY host ORDER BY t) rn, "
            "lag(v) OVER (PARTITION BY host ORDER BY t) p, "
            "lead(v) OVER (PARTITION BY host ORDER BY t) nx "
            "FROM w ORDER BY host, t"
        ).to_pylist()
        assert [x["rn"] for x in r] == [1, 2, 3, 1, 2]
        assert [x["p"] for x in r] == [None, 1.0, 3.0, None, 5.0]
        assert [x["nx"] for x in r] == [3.0, 2.0, None, 5.0, None]

    def test_lag_offset_default(self, wdb):
        r = wdb.execute(
            "SELECT lag(v, 2, 0.0) OVER (PARTITION BY host ORDER BY t) p2 "
            "FROM w ORDER BY host, t"
        ).to_pylist()
        assert [x["p2"] for x in r] == [0.0, 0.0, 1.0, 0.0, 0.0]

    def test_rank_ties_and_desc(self, wdb):
        r = wdb.execute(
            "SELECT v, rank() OVER (ORDER BY v DESC) rk, "
            "dense_rank() OVER (ORDER BY v DESC) dr FROM w ORDER BY rk, t"
        ).to_pylist()
        # values desc: 5,5,3,2,1 -> rank 1,1,3,4,5; dense 1,1,2,3,4
        assert [x["rk"] for x in r] == [1, 1, 3, 4, 5]
        assert [x["dr"] for x in r] == [1, 1, 2, 3, 4]

    def test_running_and_partition_aggregates(self, wdb):
        r = wdb.execute(
            "SELECT host, t, sum(v) OVER (PARTITION BY host ORDER BY t) rs, "
            "avg(v) OVER (PARTITION BY host) pa, "
            "min(v) OVER (PARTITION BY host ORDER BY t) rmin, "
            "count() OVER (PARTITION BY host) pc "
            "FROM w ORDER BY host, t"
        ).to_pylist()
        assert [x["rs"] for x in r] == [1.0, 4.0, 6.0, 5.0, 10.0]
        assert [x["pa"] for x in r] == [2.0, 2.0, 2.0, 5.0, 5.0]
        assert [x["rmin"] for x in r] == [1.0, 1.0, 1.0, 5.0, 5.0]
        assert [x["pc"] for x in r] == [3, 3, 3, 2, 2]

    def test_running_peers_share_frame(self, wdb):
        # b's two rows tie on v; ordering by v makes them peers: the
        # running frame (RANGE .. CURRENT ROW) includes both for both.
        r = wdb.execute(
            "SELECT host, count() OVER (PARTITION BY host ORDER BY v) c "
            "FROM w WHERE host = 'b' ORDER BY t"
        ).to_pylist()
        assert [x["c"] for x in r] == [2, 2]

    def test_first_last_value(self, wdb):
        r = wdb.execute(
            "SELECT host, t, first_value(v) OVER (PARTITION BY host ORDER BY t) f, "
            "last_value(v) OVER (PARTITION BY host ORDER BY t) l "
            "FROM w ORDER BY host, t"
        ).to_pylist()
        assert [x["f"] for x in r] == [1.0, 1.0, 1.0, 5.0, 5.0]
        # standard running-frame semantics: last_value == current row
        assert [x["l"] for x in r] == [1.0, 3.0, 2.0, 5.0, 5.0]

    def test_window_in_expression(self, wdb):
        r = wdb.execute(
            "SELECT v - lag(v) OVER (PARTITION BY host ORDER BY t) d "
            "FROM w WHERE host = 'a' ORDER BY t"
        ).to_pylist()
        assert [x["d"] for x in r] == [None, 2.0, -1.0]

    def test_window_limit_sees_all_rows(self, wdb):
        r = wdb.execute(
            "SELECT count() OVER () c FROM w LIMIT 2"
        ).to_pylist()
        assert [x["c"] for x in r] == [5, 5]

    def test_window_errors(self, wdb):
        import pytest as _pytest

        with _pytest.raises(Exception, match="WHERE"):
            wdb.execute("SELECT v FROM w WHERE rank() OVER (ORDER BY v) = 1")
        with _pytest.raises(Exception, match="ORDER BY"):
            wdb.execute("SELECT lag(v) OVER (PARTITION BY host) FROM w")
        with _pytest.raises(Exception, match="mixed"):
            wdb.execute(
                "SELECT host, avg(v), rank() OVER (ORDER BY host) "
                "FROM w GROUP BY host"
            )
        with _pytest.raises(Exception, match="unknown window function"):
            wdb.execute("SELECT ntile(4) OVER (ORDER BY v) FROM w")


class TestUnion:
    @pytest.fixture()
    def udb(self, db):
        db.execute("CREATE TABLE ua (h string TAG, v double, t timestamp KEY) ENGINE=Analytic")
        db.execute("CREATE TABLE ub (h string TAG, v double, t timestamp KEY) ENGINE=Analytic")
        db.execute("INSERT INTO ua (h, v, t) VALUES ('x', 1, 1), ('y', 2, 2)")
        db.execute("INSERT INTO ub (h, v, t) VALUES ('y', 2, 2), ('z', 3, 3)")
        return db

    def test_union_all_and_distinct(self, udb):
        r = udb.execute("SELECT h, v FROM ua UNION ALL SELECT h, v FROM ub").to_pylist()
        assert len(r) == 4
        r = udb.execute("SELECT h, v FROM ua UNION SELECT h, v FROM ub").to_pylist()
        assert len(r) == 3

    def test_union_order_limit(self, udb):
        r = udb.execute(
            "SELECT h, v FROM ua UNION ALL SELECT h, v FROM ub "
            "ORDER BY v DESC LIMIT 2"
        ).to_pylist()
        assert [x["v"] for x in r] == [3.0, 2.0]

    def test_union_aggregate_branches(self, udb):
        r = udb.execute(
            "SELECT h, avg(v) a FROM ua GROUP BY h UNION ALL "
            "SELECT h, avg(v) a FROM ub GROUP BY h ORDER BY h, a"
        ).to_pylist()
        assert [x["h"] for x in r] == ["x", "y", "y", "z"]

    def test_union_column_count_mismatch(self, udb):
        import pytest as _pytest

        with _pytest.raises(Exception, match="column count"):
            udb.execute("SELECT h, v FROM ua UNION ALL SELECT h FROM ub")


class TestCTE:
    def test_cte_chain_and_shadowing(self, db):
        db.execute("CREATE TABLE src (h string TAG, v double, t timestamp KEY) ENGINE=Analytic")
        db.execute("INSERT INTO src (h, v, t) VALUES ('a', 1, 1), ('a', 3, 2), ('b', 10, 1)")
        r = db.execute(
            "WITH m AS (SELECT h, avg(v) a FROM src GROUP BY h), "
            "top AS (SELECT h, a FROM m WHERE a > 1) "
            "SELECT h FROM top ORDER BY h"
        ).to_pylist()
        assert [x["h"] for x in r] == ["a", "b"]
        import pytest as _pytest

        with _pytest.raises(Exception, match="shadows"):
            db.execute("WITH src AS (SELECT h FROM src) SELECT h FROM src")

    def test_cte_time_filter_pushes_into_cte_result(self, db):
        db.execute("CREATE TABLE s2 (h string TAG, v double, t timestamp KEY) ENGINE=Analytic")
        db.execute("INSERT INTO s2 (h, v, t) VALUES ('a', 1, 1000), ('a', 2, 2000), ('a', 3, 3000)")
        r = db.execute(
            "WITH w AS (SELECT h, v, t FROM s2) "
            "SELECT count(v) c FROM w WHERE t >= 2000"
        ).to_pylist()
        assert r == [{"c": 2}]

    def test_cte_without_timestamp_column(self, db):
        db.execute("CREATE TABLE s3 (h string TAG, v double, t timestamp KEY) ENGINE=Analytic")
        db.execute("INSERT INTO s3 (h, v, t) VALUES ('a', 1, 1), ('b', 2, 2)")
        r = db.execute(
            "WITH names AS (SELECT h FROM s3) SELECT h FROM names ORDER BY h"
        ).to_pylist()
        assert [x["h"] for x in r] == ["a", "b"]
        # SELECT * over a ts-less cte must not leak the hidden column
        r2 = db.execute("WITH names AS (SELECT h FROM s3) SELECT * FROM names")
        assert r2.names == ["h"]

    def test_cte_union_body(self, db):
        db.execute("CREATE TABLE s4 (h string TAG, v double, t timestamp KEY) ENGINE=Analytic")
        db.execute("INSERT INTO s4 (h, v, t) VALUES ('a', 1, 1), ('b', 5, 2)")
        r = db.execute(
            "WITH both AS (SELECT h, v FROM s4 WHERE v < 2 "
            "UNION ALL SELECT h, v FROM s4 WHERE v > 2) "
            "SELECT count(v) c FROM both"
        ).to_pylist()
        assert r == [{"c": 2}]


class TestWindowReviewRegressions:
    """Fixes from review: count(*) OVER, count over strings, mixed
    UNION/UNION ALL chains."""

    @pytest.fixture()
    def rdb(self, db):
        db.execute("CREATE TABLE rw (h string TAG, v double, t timestamp KEY) ENGINE=Analytic")
        db.execute("INSERT INTO rw (h, v, t) VALUES ('a', 1, 1), ('a', 2, 2), ('b', 3, 3)")
        return db

    def test_count_star_over(self, rdb):
        r = rdb.execute("SELECT count(*) OVER (PARTITION BY h) c FROM rw ORDER BY t").to_pylist()
        assert [x["c"] for x in r] == [2, 2, 1]

    def test_count_string_column_over(self, rdb):
        r = rdb.execute("SELECT count(h) OVER () c FROM rw").to_pylist()
        assert [x["c"] for x in r] == [3, 3, 3]

    def test_min_string_column_clear_error(self, rdb):
        with pytest.raises(Exception, match="non-numeric"):
            rdb.execute("SELECT min(h) OVER () FROM rw")

    def test_mixed_union_chain_left_assoc(self, rdb):
        # distinct UNION first, then ALL: the ALL branch's duplicates stay
        r = rdb.execute(
            "SELECT h FROM rw UNION SELECT h FROM rw "
            "UNION ALL SELECT h FROM rw"
        ).to_pylist()
        assert len(r) == 2 + 3  # distinct(a,b) + all 3 rows again
        # ALL then distinct: everything dedups at the trailing UNION
        r2 = rdb.execute(
            "SELECT h FROM rw UNION ALL SELECT h FROM rw "
            "UNION SELECT h FROM rw"
        ).to_pylist()
        assert len(r2) == 2


class TestStatisticalAggregates:
    """stddev/variance/median/approx_*/corr/covar families + GROUP BY
    alias resolution and date_trunc bucket keys (ref surface: DataFusion's
    built-in statistical aggregates exposed through the reference's SQL;
    df_operator registry for the UDAF plug point)."""

    def _db(self):
        import numpy as np

        import horaedb_tpu

        db = horaedb_tpu.connect(None)
        db.execute(
            "CREATE TABLE st (host string TAG, v double, w double, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        rng = np.random.default_rng(5)
        vals = rng.normal(10, 3, 120)
        ws = vals * 2 + rng.normal(0, 0.5, 120)
        rows = ", ".join(
            f"('h{i%3}', {vals[i]}, {ws[i]}, {1000*i})" for i in range(120)
        )
        db.execute(f"INSERT INTO st (host, v, w, ts) VALUES {rows}")
        return db, vals, ws

    def test_moment_aggregates_match_numpy(self):
        import numpy as np

        db, vals, ws = self._db()
        for sql, want in [
            ("SELECT stddev(v) AS s FROM st", np.std(vals, ddof=1)),
            ("SELECT stddev_pop(v) AS s FROM st", np.std(vals)),
            ("SELECT variance(v) AS s FROM st", np.var(vals, ddof=1)),
            ("SELECT var_pop(v) AS s FROM st", np.var(vals)),
            ("SELECT median(v) AS s FROM st", np.median(vals)),
            ("SELECT approx_median(v) AS s FROM st", np.median(vals)),
            ("SELECT approx_percentile_cont(v, 0.9) AS s FROM st", np.quantile(vals, 0.9)),
            ("SELECT corr(v, w) AS s FROM st", np.corrcoef(vals, ws)[0, 1]),
            ("SELECT covar(v, w) AS s FROM st", np.cov(vals, ws, ddof=1)[0, 1]),
            ("SELECT covar_pop(v, w) AS s FROM st", np.cov(vals, ws, ddof=0)[0, 1]),
            ("SELECT approx_distinct(host) AS s FROM st", 3),
        ]:
            got = db.execute(sql).to_pylist()[0]["s"]
            assert np.isclose(got, want, rtol=1e-6), (sql, got, want)

    def test_grouped_stddev(self):
        import numpy as np

        db, vals, _ = self._db()
        out = db.execute(
            "SELECT host, stddev(v) AS s FROM st GROUP BY host ORDER BY host"
        ).to_pylist()
        assert len(out) == 3
        for h, row in enumerate(out):
            hv = vals[np.arange(120) % 3 == h]
            assert np.isclose(row["s"], np.std(hv, ddof=1), rtol=1e-6)

    def test_single_value_stddev_is_null(self):
        import horaedb_tpu

        db = horaedb_tpu.connect(None)
        db.execute(
            "CREATE TABLE one (g string TAG, v double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute("INSERT INTO one (g, v, ts) VALUES ('a', 5.0, 1)")
        out = db.execute("SELECT stddev(v) AS s, var_pop(v) AS vp FROM one").to_pylist()
        assert out[0]["s"] is None  # ddof=1 over 1 row
        assert out[0]["vp"] == 0.0

    def test_group_by_alias_resolution(self):
        db, vals, _ = self._db()
        # expression alias
        out = db.execute(
            "SELECT time_bucket(ts, '1m') AS b, count(1) AS c FROM st GROUP BY b ORDER BY b"
        ).to_pylist()
        assert [r["b"] for r in out] == [0, 60000] and sum(r["c"] for r in out) == 120
        # numeric-ms interval
        out2 = db.execute(
            "SELECT time_bucket(ts, 60000) AS b, count(1) AS c FROM st GROUP BY b ORDER BY b"
        ).to_pylist()
        assert out == out2
        # plain column alias
        out3 = db.execute(
            "SELECT host AS h, count(1) AS c FROM st GROUP BY h ORDER BY h"
        ).to_pylist()
        assert [r["h"] for r in out3] == ["h0", "h1", "h2"]

    def test_date_trunc_group_key_and_projection(self):
        import pytest

        db, _, _ = self._db()
        out = db.execute(
            "SELECT date_trunc('minute', ts) AS b, count(1) AS c FROM st GROUP BY b ORDER BY b"
        ).to_pylist()
        assert [r["b"] for r in out] == [0, 60000]
        proj = db.execute(
            "SELECT date_trunc('second', ts) AS s, v FROM st ORDER BY ts LIMIT 2"
        ).to_pylist()
        assert proj[0]["s"] == 0 and proj[1]["s"] == 1000
        with pytest.raises(Exception, match="unsupported date_trunc unit"):
            db.execute("SELECT date_trunc('month', ts) AS b, count(1) AS c FROM st GROUP BY b")

    def test_review_edge_cases(self):
        import pytest

        import horaedb_tpu

        db = horaedb_tpu.connect(None)
        db.execute(
            "CREATE TABLE ec (host string TAG, v double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute("INSERT INTO ec (host, v, ts) VALUES ('a',1.0,1),('a',1.0,2),('b',4.0,3)")
        with pytest.raises(Exception, match="DISTINCT is not supported"):
            db.execute("SELECT median(DISTINCT v) AS m FROM ec")
        # empty row set through date_trunc projection
        assert db.execute(
            "SELECT date_trunc('second', ts) AS s FROM ec WHERE v > 100"
        ).to_pylist() == []
        with pytest.raises(Exception, match="time_bucket interval"):
            db.execute("SELECT time_bucket(ts, 0.5) AS b, count(1) AS c FROM ec GROUP BY b")
        with pytest.raises(Exception, match="requires a numeric column"):
            db.execute("SELECT corr(host, v) AS c FROM ec")


class TestAggregateFilterClause:
    """agg(col) FILTER (WHERE cond) — standard SQL per-aggregate masks
    (DataFusion exposes these through the reference's SQL surface).
    Filtered aggregates always run the host path (_agg_device_shape
    refuses them), so the device kernel shape stays untouched."""

    def _db(self):
        import horaedb_tpu

        db = horaedb_tpu.connect(None)
        db.execute(
            "CREATE TABLE f (host string TAG, v double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        rows = ", ".join(f"('h{i%2}', {float(i)}, {i*1000})" for i in range(20))
        db.execute(f"INSERT INTO f (host, v, ts) VALUES {rows}")
        return db

    def test_filtered_aggregates(self):
        db = self._db()
        out = db.execute(
            "SELECT count(1) AS n, sum(v) FILTER (WHERE host = 'h0') AS s0, "
            "count(*) FILTER (WHERE v >= 10) AS big, "
            "avg(v) FILTER (WHERE v < 10) AS small FROM f"
        ).to_pylist()[0]
        assert out == {"n": 20, "s0": 90.0, "big": 10, "small": 4.5}

    def test_filtered_registry_agg_grouped(self):
        db = self._db()
        g = db.execute(
            "SELECT host, median(v) FILTER (WHERE v < 10) AS m FROM f "
            "GROUP BY host ORDER BY host"
        ).to_pylist()
        assert g == [{"host": "h0", "m": 4.0}, {"host": "h1", "m": 5.0}]

    def test_empty_filter_null_sum_zero_count(self):
        db = self._db()
        e = db.execute(
            "SELECT sum(v) FILTER (WHERE v > 99) AS s, "
            "count(*) FILTER (WHERE v > 99) AS c FROM f"
        ).to_pylist()[0]
        assert e == {"s": None, "c": 0}

    def test_filter_rejected_outside_aggregates(self):
        import pytest

        db = self._db()
        with pytest.raises(Exception, match="only valid on aggregate"):
            db.execute("SELECT abs(v) FILTER (WHERE v > 1) AS x FROM f")
        with pytest.raises(Exception, match="not supported with window"):
            db.execute(
                "SELECT sum(v) FILTER (WHERE v > 1) OVER (ORDER BY ts) AS x FROM f"
            )


class TestExpressionSurface:
    """CASE / CAST / LIKE / OFFSET / NULLS FIRST-LAST / scalar function
    library (ref surface: the reference's SQL goes through DataFusion,
    which provides these; here parser + vectorized host evaluation)."""

    def _db(self):
        import horaedb_tpu

        db = horaedb_tpu.connect(None)
        db.execute(
            "CREATE TABLE ex (host string TAG, v double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute(
            "INSERT INTO ex (host, v, ts) VALUES "
            "('aa',1.0,1),('ab',2.0,2),('bc',3.0,3),('bd',4.0,4)"
        )
        return db

    def test_case_searched_and_simple(self):
        db = self._db()
        out = db.execute(
            "SELECT CASE WHEN v > 2 THEN 'big' ELSE 'small' END AS c, v "
            "FROM ex ORDER BY v"
        ).to_pylist()
        assert [r["c"] for r in out] == ["small", "small", "big", "big"]
        out = db.execute(
            "SELECT CASE host WHEN 'aa' THEN 1 WHEN 'ab' THEN 2 END AS c "
            "FROM ex ORDER BY c NULLS LAST"
        ).to_pylist()
        assert [r["c"] for r in out] == [1, 2, None, None]

    def test_cast(self):
        db = self._db()
        out = db.execute(
            "SELECT cast(v AS bigint) AS i, cast(v AS string) AS s FROM ex "
            "ORDER BY v LIMIT 1"
        ).to_pylist()[0]
        assert out == {"i": 1, "s": "1.0"}

    def test_cast_big_integer_string_exact(self):
        # Integer strings above 2^53 must round-trip exactly (a float64
        # detour would silently lose the low bits); decimal strings still
        # take the float path.
        db = self._db()
        out = db.execute(
            "SELECT cast('9007199254740993' AS bigint) AS big, "
            "cast('2.5' AS bigint) AS dec FROM ex LIMIT 1"
        ).to_pylist()[0]
        assert out["big"] == 9007199254740993
        assert out["dec"] == 2

    def test_concat_never_null(self):
        # Postgres concat(): NULL args concatenate as empty, all-NULL
        # yields '' — never NULL.
        db = self._db()
        db.execute(
            "CREATE TABLE cnul (host string TAG, v double, ts timestamp "
            "NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute("INSERT INTO cnul (host, ts) VALUES ('h', 1)")
        out = db.execute(
            "SELECT concat(CASE WHEN v > 0 THEN 'x' END, "
            "CASE WHEN v > 0 THEN 'y' END) AS c FROM cnul"
        ).to_pylist()[0]
        assert out["c"] == ""

    def test_like_ilike(self):
        db = self._db()
        assert [r["host"] for r in db.execute(
            "SELECT host FROM ex WHERE host LIKE 'a%' ORDER BY host"
        ).to_pylist()] == ["aa", "ab"]
        assert [r["host"] for r in db.execute(
            "SELECT host FROM ex WHERE host NOT LIKE '%b%' ORDER BY host"
        ).to_pylist()] == ["aa"]
        assert [r["host"] for r in db.execute(
            "SELECT host FROM ex WHERE host ILIKE 'A_' ORDER BY host"
        ).to_pylist()] == ["aa", "ab"]
        # regex metacharacters in the pattern are literal
        assert db.execute(
            "SELECT host FROM ex WHERE host LIKE 'a.'"
        ).to_pylist() == []

    def test_offset_with_and_without_limit(self):
        db = self._db()
        assert [r["v"] for r in db.execute(
            "SELECT v FROM ex ORDER BY v LIMIT 2 OFFSET 1"
        ).to_pylist()] == [2.0, 3.0]
        assert [r["v"] for r in db.execute(
            "SELECT v FROM ex ORDER BY v OFFSET 3"
        ).to_pylist()] == [4.0]
        assert [r["v"] for r in db.execute(
            "SELECT v FROM ex UNION ALL SELECT v FROM ex ORDER BY v LIMIT 3 OFFSET 2"
        ).to_pylist()] == [2.0, 2.0, 3.0]

    def test_scalar_functions(self):
        import numpy as np

        db = self._db()
        out = db.execute(
            "SELECT upper(host) AS u, length(host) AS n, concat(host, '-x') AS c, "
            "coalesce(v, 0.0) AS co, round(v + 0.44, 1) AS r, floor(v) AS f, "
            "ceil(v) AS ce, sqrt(v) AS s, power(v, 2) AS p "
            "FROM ex ORDER BY v LIMIT 1"
        ).to_pylist()[0]
        assert out["u"] == "AA" and out["n"] == 2 and out["c"] == "aa-x"
        assert out["co"] == 1.0 and out["r"] == 1.4 and out["f"] == 1.0
        assert out["ce"] == 1.0 and np.isclose(out["s"], 1.0) and out["p"] == 1.0
        neg = db.execute("SELECT sqrt(v - 2.0) AS s FROM ex ORDER BY v LIMIT 1").to_pylist()[0]
        assert neg["s"] is None  # out of domain -> NULL


class TestAggregateExpressions:
    """Arithmetic / CASE / scalar functions over aggregates
    (sum(v)/count(*)): inner aggregate calls lift into hidden __aggN
    result columns (still served by the fused device kernel when core),
    the expression evaluates per group after aggregation on every path
    (device, host, partitioned partial)."""

    def _db(self, partitioned=False):
        import horaedb_tpu

        db = horaedb_tpu.connect(None)
        part = "PARTITION BY KEY(host) PARTITIONS 4 " if partitioned else ""
        db.execute(
            "CREATE TABLE ae (host string TAG, v double, w double, "
            f"ts timestamp NOT NULL, TIMESTAMP KEY(ts)) {part}ENGINE=Analytic"
        )
        rows = ", ".join(
            f"('h{i%2}', {float(i)}, {float(i*2)}, {i*1000})" for i in range(10)
        )
        db.execute(f"INSERT INTO ae (host, v, w, ts) VALUES {rows}")
        return db

    def test_basic_shapes(self):
        db = self._db()
        assert db.execute("SELECT sum(v) / count(*) AS r FROM ae").to_pylist() == [{"r": 4.5}]
        assert db.execute("SELECT max(v) - min(v) AS s FROM ae").to_pylist() == [{"s": 9.0}]
        assert db.execute("SELECT 100 * count(*) AS p FROM ae").to_pylist() == [{"p": 1000}]
        assert db.execute("SELECT round(avg(v), 1) AS a FROM ae").to_pylist() == [{"a": 4.5}]

    def test_grouped_and_case(self):
        db = self._db()
        out = db.execute(
            "SELECT host, sum(v) / count(*) AS r FROM ae GROUP BY host ORDER BY host"
        ).to_pylist()
        assert out == [{"host": "h0", "r": 4.0}, {"host": "h1", "r": 5.0}]
        out = db.execute(
            "SELECT host, CASE WHEN avg(v) > 4.5 THEN 'hi' ELSE 'lo' END AS b "
            "FROM ae GROUP BY host ORDER BY host"
        ).to_pylist()
        assert out == [{"host": "h0", "b": "lo"}, {"host": "h1", "b": "hi"}]

    def test_zero_rows_and_filter(self):
        db = self._db()
        assert db.execute(
            "SELECT sum(v) / count(*) AS r FROM ae WHERE v > 100"
        ).to_pylist() == [{"r": None}]
        assert db.execute(
            "SELECT sum(v) FILTER (WHERE host='h0') / count(*) AS r FROM ae"
        ).to_pylist() == [{"r": 2.0}]

    def test_partitioned_partial_path(self):
        db = self._db(partitioned=True)
        out = db.execute(
            "SELECT host, sum(v) / count(*) AS r FROM ae GROUP BY host ORDER BY host"
        ).to_pylist()
        assert out == [{"host": "h0", "r": 4.0}, {"host": "h1", "r": 5.0}]

    def test_non_group_column_rejected(self):
        import pytest

        db = self._db()
        with pytest.raises(Exception, match="GROUP BY"):
            db.execute("SELECT sum(v) + w AS x FROM ae GROUP BY host")

    def test_hidden_name_collision_and_dedupe(self):
        db = self._db()
        # a user alias may legally be '__agg0' — the hidden name probes
        # around it (FILTER forces the host path, where the collision bit)
        out = db.execute(
            "SELECT host, sum(v) AS __agg0, "
            "sum(w) FILTER (WHERE w > 0) / count(*) AS r "
            "FROM ae GROUP BY host ORDER BY host"
        ).to_pylist()
        assert out[0]["__agg0"] == 20.0 and out[0]["r"] == 8.0
        # an aggregate appearing both standalone and inside an expression
        # is computed once (reuses the select item's result column)
        plan = db.frontend.sql_to_plan("SELECT avg(v) AS a, avg(v)/2 AS h FROM ae")
        assert len(plan.aggs) == 1
        row = db.execute("SELECT avg(v) AS a, avg(v)/2 AS h FROM ae").to_pylist()[0]
        assert row == {"a": 4.5, "h": 2.25}


class TestExplainBreadth:
    def test_explain_union(self):
        import horaedb_tpu

        db = horaedb_tpu.connect(None)
        db.execute(
            "CREATE TABLE eu (host string TAG, v double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        out = db.execute(
            "EXPLAIN SELECT v FROM eu UNION ALL SELECT v FROM eu ORDER BY v LIMIT 5"
        ).to_pylist()
        text = "\n".join(r["plan"] for r in out)
        assert "Union: branches=2" in text and "Branch 1:" in text

    def test_explain_with_and_analyze_union_rejected(self):
        import pytest

        import horaedb_tpu

        db = horaedb_tpu.connect(None)
        db.execute(
            "CREATE TABLE ew (host string TAG, v double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        with pytest.raises(Exception, match="EXPLAIN over WITH"):
            db.execute("EXPLAIN WITH x AS (SELECT v FROM ew) SELECT * FROM x")
        with pytest.raises(Exception, match="ANALYZE over UNION"):
            db.execute("EXPLAIN ANALYZE SELECT v FROM ew UNION SELECT v FROM ew")
