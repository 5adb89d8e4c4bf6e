"""Device-resident raw reads (PR 7).

Covers: the randomized device-vs-host equivalence property for
non-aggregate queries (NULL masks, DESC + tie ordering, LIMIT/OFFSET,
empty allow-list, delta-only tables, the HORAEDB_RAW_MAX_ROWS
boundary), the sharded (shard_map) variant, the HORAEDB_RAW_DEVICE
kill switch, ledger/query_stats coverage, the presorted-ORDER-BY
lexsort skip, and the partial-agg kernel-routing satellite.
"""

import numpy as np
import pytest

import horaedb_tpu
from horaedb_tpu.query.kernel_choice import KERNEL_ROUTER


@pytest.fixture()
def db():
    conn = horaedb_tpu.connect(None)
    yield conn
    conn.close()


@pytest.fixture(autouse=True)
def _deterministic_raw(monkeypatch):
    """Pin routing off: the learned PathRouter would interleave host
    probes between device servings — correct in production, flaky to
    assert on. Eligibility, budget, and kill-switch fallbacks still
    apply; dedicated tests re-enable routing explicitly."""
    monkeypatch.setenv("HORAEDB_ADAPTIVE_PATH", "0")
    KERNEL_ROUTER.reset()
    yield
    KERNEL_ROUTER.reset()


DDL = (
    "CREATE TABLE rd (host string TAG, v double, w double, "
    "ts timestamp NOT NULL, TIMESTAMP KEY(ts))"
)


def _seed(db, n=400, hosts=8, null_w_every=0, ts_step=1000, rng=None):
    db.execute(DDL)
    parts = []
    for i in range(n):
        w = (
            "NULL"
            if null_w_every and i % null_w_every == 0
            else f"{float(3 * i)}"
        )
        v = float(i if rng is None else rng.integers(0, 10 * n))
        parts.append(
            f"('h{i % hosts}', {v}, {w}, {1_700_000_000_000 + i * ts_step})"
        )
    db.execute(f"INSERT INTO rd (host, v, w, ts) VALUES {', '.join(parts)}")


def _warm(db, sql, times=3):
    out = None
    for _ in range(times):
        out = db.execute(sql)
    return out


def _host_ref(db, sql, monkeypatch):
    monkeypatch.setenv("HORAEDB_RAW_DEVICE", "0")
    try:
        return db.execute(sql)
    finally:
        monkeypatch.delenv("HORAEDB_RAW_DEVICE", raising=False)


class TestRawEquivalence:
    """The property: the device raw path must be indistinguishable from
    the host projection path on every eligible query."""

    def test_randomized_topk_and_selection(self, db, monkeypatch):
        rng = np.random.default_rng(42)
        _seed(db, n=500, hosts=10, null_w_every=7)
        filters = ["", "WHERE v < 250", "WHERE v >= 100 AND host IN ('h1', 'h3', 'h5')",
                   "WHERE host = 'h2'", "WHERE v != 123"]
        orders = ["ts DESC", "ts ASC", "v DESC", "v ASC"]
        for trial in range(16):
            where = filters[trial % len(filters)]
            order = orders[trial % len(orders)]
            limit = int(rng.integers(1, 60))
            offset = int(rng.integers(0, 20)) if trial % 3 == 0 else 0
            sql = (
                f"SELECT host, v, w, ts FROM rd {where} ORDER BY {order} "
                f"LIMIT {limit}"
                + (f" OFFSET {offset}" if offset else "")
            )
            got = _warm(db, sql)
            assert got.metrics.get("path") == "raw_device", sql
            assert got.metrics.get("raw_kernel") == "topk", sql
            ref = _host_ref(db, sql, monkeypatch)
            assert ref.metrics.get("path") == "host"
            assert got.to_pylist() == ref.to_pylist(), sql

    def test_selection_multikey_and_no_limit(self, db, monkeypatch):
        _seed(db, n=300, hosts=6, null_w_every=11)
        for sql in (
            "SELECT host, v, w FROM rd WHERE v < 120 ORDER BY host ASC, v DESC",
            "SELECT host, v FROM rd WHERE v >= 250 ORDER BY v ASC, host DESC LIMIT 20 OFFSET 5",
            "SELECT DISTINCT host FROM rd WHERE v < 50 ORDER BY host",
        ):
            got = _warm(db, sql)
            assert got.metrics.get("path") == "raw_device", sql
            assert got.metrics.get("raw_kernel") == "select", sql
            assert got.to_pylist() == _host_ref(db, sql, monkeypatch).to_pylist(), sql

    def test_desc_ties_select_equivalent_keys(self, db, monkeypatch):
        """Duplicate ORDER BY keys: which tied rows cross the LIMIT
        boundary is unspecified SQL, and host read order differs from
        the resident layout — assert on the KEY multiset and the
        predicate instead of exact row identity."""
        db.execute(DDL)
        rows = ", ".join(
            f"('h{i % 4}', {float(i % 5)}, {float(i)}, "
            f"{1_700_000_000_000 + i * 1000})"
            for i in range(200)
        )
        db.execute(f"INSERT INTO rd (host, v, w, ts) VALUES {rows}")
        sql = "SELECT v, w FROM rd WHERE w < 150 ORDER BY v DESC LIMIT 30"
        got = _warm(db, sql)
        assert got.metrics.get("path") == "raw_device"
        ref = _host_ref(db, sql, monkeypatch)
        g, r = got.to_pylist(), ref.to_pylist()
        assert [x["v"] for x in g] == [x["v"] for x in r]
        assert all(x["w"] < 150 for x in g)
        assert len(set((x["v"], x["w"]) for x in g)) == len(g)

    def test_null_in_order_column_falls_back(self, db, monkeypatch):
        """NULLs in the ORDER BY / filter column: resident columns hold
        fill values there — the device path must refuse and the host
        path must serve the 3-valued semantics."""
        db.execute(DDL)
        rows = ", ".join(
            f"('h{i % 3}', {float(i)}, "
            + ("NULL" if i % 2 else f"{float(i)}")
            + f", {1_700_000_000_000 + i * 1000})"
            for i in range(60)
        )
        db.execute(f"INSERT INTO rd (host, v, w, ts) VALUES {rows}")
        sql = "SELECT host, w FROM rd ORDER BY w DESC LIMIT 10"
        got = _warm(db, sql)
        assert got.metrics.get("path") == "host"
        assert got.to_pylist() == _host_ref(db, sql, monkeypatch).to_pylist()

    def test_empty_allow_list(self, db, monkeypatch):
        _seed(db, n=100)
        sql = "SELECT host, v FROM rd WHERE host = 'nope' ORDER BY ts DESC LIMIT 5"
        got = _warm(db, sql)
        assert got.metrics.get("path") == "raw_device"
        assert got.num_rows == 0
        assert got.to_pylist() == _host_ref(db, sql, monkeypatch).to_pylist()

    def test_time_range_and_empty_range(self, db, monkeypatch):
        _seed(db, n=200)
        base = 1_700_000_000_000
        for sql in (
            f"SELECT v, ts FROM rd WHERE ts >= {base + 50_000} AND "
            f"ts < {base + 150_000} ORDER BY ts DESC LIMIT 20",
            f"SELECT v, ts FROM rd WHERE ts >= {base + 10_000_000} "
            "ORDER BY ts DESC LIMIT 20",
        ):
            got = _warm(db, sql)
            assert got.metrics.get("path") == "raw_device", sql
            assert got.to_pylist() == _host_ref(db, sql, monkeypatch).to_pylist(), sql

    def test_delta_rows_including_new_series(self, db, monkeypatch):
        """Writes after the cache build fold in exactly — including a
        series the base has never seen."""
        _seed(db, n=120, ts_step=1000)
        sql = "SELECT host, v, ts FROM rd ORDER BY ts DESC LIMIT 10"
        out = _warm(db, sql)
        assert out.metrics.get("cache") in ("build", "hit")
        newer = 1_700_000_000_000 + 500 * 1000
        db.execute(
            f"INSERT INTO rd (host, v, w, ts) VALUES "
            f"('brand_new', 9001.0, 1.0, {newer}), "
            f"('h1', 9002.0, 2.0, {newer + 1000})"
        )
        got = db.execute(sql)
        assert got.metrics.get("path") == "raw_device"
        assert got.metrics.get("delta_rows") == 2
        assert got.to_pylist() == _host_ref(db, sql, monkeypatch).to_pylist()
        hosts = [r["host"] for r in got.to_pylist()]
        assert hosts[:2] == ["h1", "brand_new"]

    def test_overwrite_shadowing_delta_falls_back(self, db, monkeypatch):
        """An OVERWRITE-mode delta row that could shadow a cached base
        row makes the union unsound — the device path must refuse."""
        _seed(db, n=80)
        sql = "SELECT host, v, ts FROM rd ORDER BY ts DESC LIMIT 5"
        _warm(db, sql)
        # same (series, ts) key as an existing base row -> overwrite
        db.execute(
            "INSERT INTO rd (host, v, w, ts) VALUES "
            f"('h1', 7777.0, 1.0, {1_700_000_000_000 + 1 * 1000})"
        )
        got = db.execute(sql)
        assert got.metrics.get("path") == "host"
        assert got.to_pylist() == _host_ref(db, sql, monkeypatch).to_pylist()

    def test_raw_max_rows_boundary(self, db, monkeypatch):
        """Selection shapes estimate their exact candidate bound; over
        the budget the host serves, at/under it the device does."""
        _seed(db, n=200, hosts=4)
        sql = "SELECT host, v FROM rd ORDER BY host ASC, v ASC"  # multikey: selection
        monkeypatch.setenv("HORAEDB_RAW_MAX_ROWS", "10")  # 200 > 10
        got = _warm(db, sql)
        assert got.metrics.get("path") == "host"
        monkeypatch.setenv("HORAEDB_RAW_MAX_ROWS", "200")  # exactly at bound
        got = _warm(db, sql)
        assert got.metrics.get("path") == "raw_device"
        assert got.metrics.get("raw_kernel") == "select"
        assert got.to_pylist() == _host_ref(db, sql, monkeypatch).to_pylist()

    def test_limit_pushdown_shape_stays_host(self, db):
        """LIMIT with no ORDER BY and no residual stops the host scan at
        LIMIT rows — the device path must not claim it."""
        _seed(db, n=100)
        sql = "SELECT host, v FROM rd LIMIT 5"
        out = _warm(db, sql)
        assert out.metrics.get("path") == "host"
        assert "raw_kernel" not in out.metrics
        assert out.num_rows == 5


class TestRawSharded:
    """The shard_map variant: entries sharded over the (8-device CPU)
    mesh serve raw reads with per-shard kernels + host combine."""

    @pytest.fixture(autouse=True)
    def _small_dist_floor(self, monkeypatch):
        monkeypatch.setenv("HORAEDB_DIST_MIN_ROWS", "1")

    def test_sharded_topk_and_selection(self, db, monkeypatch):
        _seed(db, n=400, hosts=8)
        for sql, kind in (
            ("SELECT host, v, ts FROM rd WHERE v < 333 ORDER BY ts DESC LIMIT 17", "topk"),
            ("SELECT host, v, ts FROM rd WHERE v >= 100 ORDER BY v ASC LIMIT 23 OFFSET 3", "topk"),
            ("SELECT host, v FROM rd WHERE v < 150 ORDER BY host ASC, v DESC", "select"),
        ):
            got = _warm(db, sql)
            assert got.metrics.get("path") == "raw_device", sql
            assert got.metrics.get("raw_kernel") == kind, sql
            assert got.metrics.get("mesh_devices") == 8, sql
            ref = _host_ref(db, sql, monkeypatch)
            assert got.to_pylist() == ref.to_pylist(), sql
        entry = db.interpreters.executor.scan_cache._entries.get("rd")
        assert entry is not None and entry.mesh is not None

    def test_limit_exceeding_shard_length_loses_no_rows(self, db, monkeypatch):
        """Review regression: per-shard k clamps to the shard length, so
        the merged union must be cut at the REQUESTED limit+offset — the
        old cut at the clamped k silently dropped rows whenever
        limit+offset exceeded one shard's row count."""
        _seed(db, n=2000, hosts=8)  # pads to 4096 -> 512 rows/shard
        sql = "SELECT v, ts FROM rd WHERE v < 1900 ORDER BY ts DESC LIMIT 700"
        got = _warm(db, sql)
        assert got.metrics.get("path") == "raw_device"
        assert got.metrics.get("raw_kernel") == "topk"
        assert got.metrics.get("mesh_devices") == 8
        assert got.num_rows == 700
        assert got.to_pylist() == _host_ref(db, sql, monkeypatch).to_pylist()

    def test_sharded_matches_single_device(self, db, monkeypatch):
        """Same query, sharded vs single-device entry: identical rows."""
        _seed(db, n=300, hosts=6)
        sql = "SELECT host, v, ts FROM rd WHERE v < 222 ORDER BY ts DESC LIMIT 11"
        sharded = _warm(db, sql).to_pylist()
        db.interpreters.executor.scan_cache.invalidate("rd")
        monkeypatch.setenv("HORAEDB_DIST_MIN_ROWS", "1000000")
        single = _warm(db, sql)
        assert single.metrics.get("path") == "raw_device"
        assert "mesh_devices" not in single.metrics
        assert sharded == single.to_pylist()


class TestFloatKeyNaN:
    def _seed_with_nan(self, db, n=60, nan_every=4):
        from horaedb_tpu.common_types import RowGroup
        from horaedb_tpu.common_types.schema import compute_tsid

        db.execute(DDL)
        hosts = np.array([f"h{i % 4}" for i in range(n)], dtype=object)
        v = np.arange(n, dtype=np.float64)
        v[::nan_every] = np.nan
        schema = db.catalog.open("rd").schema
        rows = RowGroup(
            schema,
            {
                "tsid": compute_tsid([hosts]),
                "host": hosts,
                "v": v,
                "w": np.ones(n),
                "ts": (1_700_000_000_000 + np.arange(n) * 1000).astype(np.int64),
            },
        )
        db.catalog.open("rd").write(rows)

    def test_nan_sorts_last_both_directions(self, db, monkeypatch):
        """Review regression: the f32->int32 bit transform ranks NaN
        above +inf, but np.lexsort (the host reference) places NaN LAST
        in both directions — the device key must pin NaN to the bottom
        or a DESC top-k returns NaN rows instead of the real maxima."""
        self._seed_with_nan(db)
        for sql in (
            "SELECT v, ts FROM rd ORDER BY v DESC LIMIT 8",
            "SELECT v, ts FROM rd ORDER BY v ASC LIMIT 8",
        ):
            got = _warm(db, sql)
            assert got.metrics.get("path") == "raw_device", sql
            vals = [r["v"] for r in got.to_pylist()]
            assert not any(np.isnan(x) for x in vals), (sql, vals)
            ref = [r["v"] for r in _host_ref(db, sql, monkeypatch).to_pylist()]
            assert vals == ref, sql

    def test_limit_past_real_values_includes_nans_like_host(
        self, db, monkeypatch
    ):
        self._seed_with_nan(db, n=20, nan_every=2)  # 10 real, 10 NaN
        sql = "SELECT v FROM rd ORDER BY v DESC LIMIT 15"
        got = [r["v"] for r in _warm(db, sql).to_pylist()]
        ref = [
            r["v"] for r in _host_ref(db, sql, monkeypatch).to_pylist()
        ]
        assert [np.isnan(x) for x in got] == [np.isnan(x) for x in ref]
        assert [x for x in got if not np.isnan(x)] == [
            x for x in ref if not np.isnan(x)
        ]


class TestRawKillSwitchAndRouting:
    def test_kill_switch_pins_host(self, db, monkeypatch):
        _seed(db, n=100)
        monkeypatch.setenv("HORAEDB_RAW_DEVICE", "0")
        sql = "SELECT host, v FROM rd WHERE v < 50 ORDER BY ts DESC LIMIT 5"
        out = _warm(db, sql)
        assert out.metrics.get("path") == "host"
        assert db.interpreters.executor.last_path == "host"
        assert "raw_kernel" not in out.metrics

    def test_raw_scan_counters_move(self, db):
        from horaedb_tpu.utils.metrics import REGISTRY

        _seed(db, n=100)
        sql = "SELECT host, v FROM rd WHERE v < 50 ORDER BY ts DESC LIMIT 5"
        _warm(db, sql)
        text = REGISTRY.expose()
        assert "horaedb_raw_scan_total" in text

    def test_learned_routing_probes_then_serves(self, db, monkeypatch):
        """With routing enabled the PathRouter serves device until it holds
        a clean sample (no compile, no cache build), samples host once, then
        serves the measured winner."""
        monkeypatch.setenv("HORAEDB_ADAPTIVE_PATH", "1")
        _seed(db, n=150)
        sql = "SELECT host, v FROM rd WHERE v < 60 ORDER BY ts DESC LIMIT 5"
        paths = []
        for _ in range(6):
            out = db.execute(sql)
            paths.append(out.metrics.get("path"))
        assert "host" in paths  # the host probe happened
        from horaedb_tpu.query.path_router import plan_shape_key

        plan = db.frontend.statement_to_plan(db.frontend.parse_sql(sql))
        st = db.interpreters.executor.path_router.stats(plan_shape_key(plan))
        assert "device" in st and "host" in st

    def test_persistent_fallback_converges_to_host(self, db, monkeypatch):
        """Review regression: a shape whose device attempt always
        bounces (NULLs in the ORDER BY column) must charge the DEVICE
        arm — recording it as host left the router in its probe phase,
        re-paying the failed attempt on every query forever."""
        monkeypatch.setenv("HORAEDB_ADAPTIVE_PATH", "1")
        db.execute(DDL)
        rows = ", ".join(
            f"('h{i % 3}', {float(i)}, "
            + ("NULL" if i % 2 else f"{float(i)}")
            + f", {1_700_000_000_000 + i * 1000})"
            for i in range(60)
        )
        db.execute(f"INSERT INTO rd (host, v, w, ts) VALUES {rows}")
        sql = "SELECT host, w FROM rd ORDER BY w DESC LIMIT 5"
        for _ in range(6):
            out = db.execute(sql)
            assert out.metrics.get("path") == "host"
        from horaedb_tpu.query.path_router import plan_shape_key

        plan = db.frontend.statement_to_plan(db.frontend.parse_sql(sql))
        st = db.interpreters.executor.path_router.stats(plan_shape_key(plan))
        # both arms sampled -> the router can judge instead of probing
        # device-first forever (timing RATIOS are host jitter — the
        # convergence property is that both estimates exist)
        assert "device" in st and "host" in st

    def test_ledger_and_query_stats_cover_raw(self, db):
        from horaedb_tpu.proxy import Proxy

        proxy = Proxy(db)
        try:
            _seed(db, n=120)
            sql = "SELECT host, v, ts FROM rd WHERE v < 90 ORDER BY ts DESC LIMIT 7"
            out = None
            for _ in range(3):
                out = proxy.handle_sql(sql)
            assert out.metrics.get("path") == "raw_device"
            stats = proxy.handle_sql(
                "SELECT kernel, raw_rows_returned, route FROM "
                "system.public.query_stats"
            ).to_pylist()
            mine = [r for r in stats if r["route"] == "raw_device"]
            assert mine, stats
            assert mine[-1]["kernel"] == "raw_topk"
            assert mine[-1]["raw_rows_returned"] == 7
        finally:
            proxy.close()

    def test_explain_names_raw_execution(self, db):
        _seed(db, n=50)
        out = db.execute(
            "EXPLAIN SELECT host, v FROM rd WHERE v < 10 "
            "ORDER BY ts DESC LIMIT 5"
        )
        plan_text = "\n".join(out.column("plan"))
        assert "raw device" in plan_text and "top-k" in plan_text


class TestLexsortSkip:
    def test_presorted_helper(self):
        from horaedb_tpu.query.executor import _lex_presorted

        a = np.array([1, 2, 2, 3])
        assert _lex_presorted([a])
        assert not _lex_presorted([a[::-1].copy()])
        # two keys, np.lexsort order: LAST is primary
        primary = np.array([1, 1, 2, 2])
        secondary = np.array([0, 1, 0, 1])
        assert _lex_presorted([secondary, primary])
        assert not _lex_presorted([secondary[::-1].copy(), primary])
        # ties in the primary defer to the secondary
        assert _lex_presorted([np.array([0, 1, 0, 1]), np.array([1, 1, 2, 2])])
        # NaN pairs are conservative: fall through to the real sort
        assert not _lex_presorted([np.array([1.0, np.nan, 2.0])])
        # object keys compare fine; incomparable mixes bail out
        assert _lex_presorted([np.array(["a", "b"], dtype=object)])
        assert not _lex_presorted([np.array(["b", 1], dtype=object)])
        assert _lex_presorted([np.array([5])]) and _lex_presorted([np.array([])])

    def test_single_series_order_by_ts_skips_sort(self, db, monkeypatch):
        """The dashboard shape: one series, ORDER BY ts — storage hands
        over (key, ts)-sorted rows, so the host projection's lexsort is
        the identity and must be skipped."""
        monkeypatch.setenv("HORAEDB_RAW_DEVICE", "0")  # host projection path
        _seed(db, n=120, hosts=3)
        sql = "SELECT v, ts FROM rd WHERE host = 'h1' ORDER BY ts ASC"
        out = db.execute(sql)
        assert out.metrics.get("path") == "host"
        assert out.metrics.get("sort_skipped") is True
        ts = [r["ts"] for r in out.to_pylist()]
        assert ts == sorted(ts)
        # DESC over ascending storage order must NOT skip (and stays right)
        out = db.execute("SELECT v, ts FROM rd WHERE host = 'h1' ORDER BY ts DESC")
        assert out.metrics.get("sort_skipped") is None
        ts = [r["ts"] for r in out.to_pylist()]
        assert ts == sorted(ts, reverse=True)


class TestPartialKernelRouting:
    """Satellite: the partial-agg path now routes its segment impl
    through the shared KernelRouter instead of the static heuristic."""

    def test_bounded_partial_routes_and_matches(self, db, monkeypatch):
        _seed(db, n=400, hosts=20)
        sql = "SELECT host, count(1) AS c, sum(v) AS s FROM rd GROUP BY host"
        expect = db.execute(sql).to_pylist()
        monkeypatch.setenv("HORAEDB_AGG_MEMORY_MB", "0.0001")
        out = db.execute(sql)
        assert out.metrics.get("path") == "device-partial"
        assert sorted(tuple(r.values()) for r in out.to_pylist()) == sorted(
            tuple(r.values()) for r in expect
        )
        partial_keys = [
            k for k in KERNEL_ROUTER._stats
            if isinstance(k, tuple) and k and isinstance(k[0], tuple)
            and k[0] and k[0][0] == "partial"
        ]
        assert partial_keys, "partial path never consulted the KernelRouter"

    def test_partial_runs_the_one_candidate_offered(self, db, monkeypatch):
        from horaedb_tpu.query import kernel_choice

        monkeypatch.setattr(
            kernel_choice, "candidate_kernels", lambda *a, **k: ("mxu",)
        )
        _seed(db, n=300, hosts=10)
        sql = "SELECT host, count(1) AS c FROM rd GROUP BY host"
        expect = db.execute(sql).to_pylist()
        monkeypatch.setenv("HORAEDB_AGG_MEMORY_MB", "0.0001")
        out = db.execute(sql)
        assert out.metrics.get("path") == "device-partial"
        assert sorted(tuple(r.values()) for r in out.to_pylist()) == sorted(
            tuple(r.values()) for r in expect
        )
        (stats,) = [
            KERNEL_ROUTER.stats(k) for k in list(KERNEL_ROUTER._stats)
            if isinstance(k, tuple) and k and isinstance(k[0], tuple)
            and k[0] and k[0][0] == "partial"
        ]
        assert stats["warmed"] == {"mxu"}
